"""The ``lfm2`` family in the benchmark: found by files alone (no file of
the harness names it), its operation count against a count by hand at the
rehearsal's size and at the cell's, and the work function of each roofline
it brings (a call that took the least time the chip could take reads 100,
never more; no call is credited with nothing)."""

import re
from pathlib import Path

import pytest

from benchmark.harness import flops, peaks, roofline, runner
from benchmark.harness.manifest import ROOT, Manifest
from benchmark.harness.trace import Event, Trace

M = Manifest()
CONFIG, CELL = "lfm2_24b_a2b", "lfm2_24b_a2b.lm8192"
NEW_METRICS = (
    "shortconv.mixer_ms", "shortconv.gate_ms", "kernels.short_conv_roofline",
    "attn.block_ms", "kernels.flash_gqa64_roofline", "mlp.dense_ms",
    "moe.gated_block_ms", "moe.gated_experts_ms", "moe.gated_dispatch_ms",
    "moe.gated_assignments_held", "moe.gated_load_max_over_mean",
    "kernels.gated_grouped_mm_roofline")


def test_the_family_is_found_by_files_and_the_harness_does_not_name_it():
    config = M.config(CONFIG)
    builder, reference = runner.family(config)
    assert builder.REFERENCE == "lfm2"
    assert reference.__name__ == "benchmark.reference.lfm2"
    assert flops.counts(config).__name__ == "benchmark_counts_lfm2"
    text = Path(reference.__file__).read_text()
    assert not re.search(r"^\s*(import|from)\s+apex_tpu", text, re.M)
    for path in (ROOT / "benchmark" / "harness").glob("*.py"):
        code = "\n".join(line.split("#")[0]
                         for line in path.read_text().splitlines())
        assert not re.search(r"""["'](lfm2|layer_types)["']""", code), path
    mine = [m for m in M.doc["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(NEW_METRICS)
    assert M.doc["per_layer"][-len(mine):] == mine     # appended, in order
    for m in mine:
        assert m["moves"] == "tokens_per_s"
        assert (ROOT / "benchmark" / "metrics" / f"{m['name']}.py").exists()
        if m["name"].endswith("_roofline"):
            assert roofline.pattern_files(M, m["name"])
    # what every cell reads reads this one too
    assert {"step.mfu", "step.live_gib", "device.idle_share",
            "loop.host_ms_per_step", "amp.steps_skipped"} <= {
        m["name"] for m in M.per_layer(CELL)}


def test_forward_flops_by_hand_at_the_rehearsal_size():
    config, traffic = runner._apply_rehearsal(M.config(CONFIG),
                                              M.traffic(CELL))
    counts = flops.counts(config)
    assert counts.pattern(config) == [
        ("conv", "dense"), ("attn", "moe"), ("conv", "moe"), ("conv", "moe"),
        ("conv", "moe")]
    assert counts.layers_of(config, "conv") == [0, 2, 3, 4]
    assert counts.layers_of(config, "moe") == [1, 2, 3, 4]
    rows, S, H, V = 2, 64, 64, 128
    T = rows * S
    conv = 2 * T * H * 3 * H + 2 * T * H * H
    attn = (2 * T * H * (64 + 32 + 32 + 64)           # q, k, v, out: 4 on 2 of 16
            + 2 * 2 * rows * S * S * 64 // 2)
    dense = 3 * 2 * T * H * 96
    moe = 2 * T * H * 16 + 3 * 2 * T * (2 * 4 / 16) * H * 48
    head = 2 * rows * (S - 1) * H * V
    want = int(4 * conv + attn + dense + 4 * moe + head)
    assert counts.forward_flops(config, traffic, rows) == want
    assert flops.step_flops(config, traffic, 1) == 3 * want


def test_the_cell_counts_twenty_teraflop_a_step():
    c, t = M.config(CONFIG), M.traffic(CELL)
    forward = flops.counts(c).forward_flops(c, t, 2)
    assert forward / (2 * 8192) == pytest.approx(0.4058e9, rel=1e-3)
    assert flops.step_flops(c, t, 1) == pytest.approx(1.9946e13, rel=1e-3)
    assert flops.counts(c).attention_shape(c) == {
        "query_heads": 32, "kv_heads": 8, "head_size": 64, "causal": True}


def test_the_calls_by_hand():
    c = M.config(CONFIG)
    counts = flops.counts(c)
    ops, nbytes = counts.short_conv_call(c, 16384, "short_conv_forward")
    assert (ops, nbytes) == (16384 * 2048 * 8, 16384 * 4 * 2048 * 2)
    assert counts.short_conv_call(c, 16384, "short_conv_backward") == (
        3 * ops, 16384 * 7 * 2048 * 2)
    ops, nbytes = counts.grouped_mm_call(c, 1000.0, "experts_forward")
    assert ops == 2 * 1000 * 2048 * 3072 + 2 * 1000 * 1536 * 2048
    assert nbytes == 2 * (1000 * (2048 + 3072) + 8 * 2048 * 3072
                          + 1000 * (1536 + 2048) + 8 * 1536 * 2048)
    assert counts.grouped_mm_call(c, 1000.0, "experts_backward") == (
        2 * ops, 2 * nbytes)
    ops, nbytes = flops.attention_call(c, 2, 8192, "attention_forward")
    assert ops == 2 * 2 * 2 * 8192 * 8192 * 2048 // 2
    assert nbytes == 2 * 8192 * (2 * 2048 + 2 * 512) * 2


def _work(metric):
    c = M.config(CONFIG)
    counts = flops.counts(c)
    if metric == "kernels.short_conv_roofline":
        return lambda kind, ev: counts.short_conv_call(c, 16384, kind)
    if metric == "kernels.gated_grouped_mm_roofline":
        return lambda kind, ev: counts.grouped_mm_call(c, 2048.0, kind)
    return lambda kind, ev: flops.attention_call(c, 2, 8192, kind)


EVENTS = {
    "kernels.short_conv_roofline": ("conv_gate:fwd", "conv_gate:recompute",
                                    "conv_gate:bwd"),
    "kernels.gated_grouped_mm_roofline": ("moe_experts:fwd",
                                          "moe_experts:bwd"),
    "kernels.flash_gqa64_roofline": ("flash_fwd.3", "flash_bwd_dq",
                                     "flash_bwd_dkv.1"),
}


@pytest.mark.parametrize("metric", sorted(EVENTS))
def test_a_call_at_the_least_time_reads_100_and_slower_reads_less(metric):
    """Every event the patterns match is credited with work (never 0), and
    an event that took exactly the least time the chip could take reads
    100: a share can pass 100 only if an event took less than that."""
    peak = peaks.peaks("TPU v5 lite")
    work_of = _work(metric)
    kinds = {}
    for kind, match, _ in roofline.pattern_files(M, metric):
        for name in EVENTS[metric]:
            if re.search(match, name):
                kinds[name] = kind
    assert set(kinds) == set(EVENTS[metric])       # each event has one kind
    at, least, events = 0.0, [], []
    for name, kind in sorted(kinds.items()):
        ops, nbytes = work_of(kind, None)
        assert ops > 0 and nbytes > 0
        t = max(ops / peak.bf16_flops, nbytes / peak.hbm_bytes_per_s)
        least.append(t)
        events.append(Event(name, at, at + t, ""))
        at += 2 * t
    ctx = {"manifest": M, "device": {"kind": "TPU v5 lite"},
           "trace": Trace({0: events}, {}, [], 0.0, 0.0)}
    assert roofline.share(ctx, metric, work_of, log=lambda s: None) == (
        pytest.approx(100.0))
    slow = [Event(e.name, e.start, e.start + 3 * (e.end - e.start), "")
            for e in events]
    assert roofline.share({**ctx, "trace": Trace({0: slow}, {}, [], 0., 0.)},
                          metric, work_of, log=lambda s: None) == (
        pytest.approx(100.0 / 3))


def test_a_recomputed_expert_event_is_credited_with_nothing():
    """The block keeps its rows: ``moe_experts:recompute`` holds the
    weighing alone and no pattern of the gated roofline matches it."""
    for _, match, _ in roofline.pattern_files(
            M, "kernels.gated_grouped_mm_roofline"):
        assert not re.search(match, "moe_experts:recompute")
