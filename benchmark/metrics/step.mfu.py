"""The whole step's share of the chips' peak: model operations of a step
(``harness/flops.py`` by the family's ``counts/<builder>.py``: from
shapes, forward and backward, recomputation not counted, causal attention
at half) times the steps dispatched before the traced slice, over that
time and the chips' published bf16 peak."""

from benchmark.harness import flops, peaks


def read(ctx):
    lo, hi = ctx["window"][0], ctx["slice"][0] or ctx["window"][1]
    n = ctx["spans"].count("loop.step", lo, hi)
    if not n or hi <= lo:
        return None
    # the count first: a family without a count file fails here, in the
    # rehearsal too, where the CPU's unknown peak is pardoned
    work = flops.step_flops(ctx["config"], ctx["traffic"], ctx["chips"])
    print(f"[step.mfu] {work} model operations a step "
          f"(benchmark/counts/{ctx['config']['builder']}.py), {n} steps in "
          f"{hi - lo:.3f} s", flush=True)
    peak = peaks.peaks(ctx["device"]["kind"]).bf16_flops * ctx["chips"]
    return 100.0 * work * n / (hi - lo) / peak
