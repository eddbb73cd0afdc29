"""Causal latent-attention flash's share of its roofline in this cell (32
heads, q and k of 192 = 128 + 64 rotary, v of 128): operations and bytes
of each layer's attention call the step makes (``counts/<family>.py:
mla_attention_call``: QK^T and dK at the q/k width, PV, dP and dV at the v
width, causal at half; q, k, dq, dk crossing HBM at the q/k width, v, o,
do, dv at the v width) over the device time of the kernels that implement
them (``benchmark/patterns/kernels.flash_mla_roofline/``). An event on a
recomputed path is credited with nothing (the layer keeps the kernel's
outputs; were it to run twice, its time would count and its work would
not)."""

from benchmark.harness import flops, layers, roofline, scopes


def read(ctx):
    if ctx["trace"] is None or not ctx["program"].get("hlo"):
        return None
    counts = flops.counts(ctx["config"])
    if not hasattr(counts, "mla_attention_call"):
        return None
    rows, seq = ctx["traffic"]["rows_per_chip"], ctx["traffic"]["seq"]
    program = scopes.parse_hlo(ctx["program"]["hlo"])

    def work_of(kind, event):
        if layers.pass_of(scopes.path_of(program, event.name)) == "recompute":
            return 0, 0
        return counts.mla_attention_call(ctx["config"], rows, seq, kind)

    try:
        return roofline.share(ctx, "kernels.flash_mla_roofline", work_of)
    except LookupError:
        return None     # a program without these kernels: nothing to read
