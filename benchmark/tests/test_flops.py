"""The operation and byte counts against hand counts for both
configurations' shapes."""

import pytest

from benchmark.harness import flops, hlo, peaks
from benchmark.harness.manifest import Manifest

M = Manifest()


def test_bert_large_step_by_hand():
    c, t = M.config("bert_large"), M.traffic("bert_large.phase2")
    B, S, H, I, V, P = 16, 512, 1024, 4096, 30522, 76
    layer = (4 * 2 * B * S * H * H          # q, k, v, out
             + 2 * 2 * B * S * H * I        # mlp_in, mlp_out
             + 2 * 2 * B * S * S * H)       # QK^T, PV, all heads
    head = 2 * B * P * H * H + 2 * B * P * H * V
    pooled = 2 * B * H * H + 2 * B * H * 2
    assert flops.step_flops(c, t, 1) == 3 * (24 * layer + head + pooled)
    assert flops.step_flops(c, t, 1) == pytest.approx(1.631e13, rel=1e-3)
    # four chips at the same rows per chip: four times the work
    assert flops.step_flops(c, t, 4) == 4 * flops.step_flops(c, t, 1)


def test_gpt2_medium_step_by_hand_causal_at_half():
    c, t = M.config("gpt2_medium"), M.traffic("gpt2_medium.lm1024")
    B, S, H, V = 8, 1024, 1024, 50257
    layer = (4 * 2 * B * S * H * H + 2 * 2 * B * S * H * 4 * H
             + (2 * 2 * B * S * S * H) // 2)
    head = 2 * B * (S - 1) * H * V
    assert flops.step_flops(c, t, 1) == 3 * (24 * layer + head)
    assert flops.step_flops(c, t, 1) == pytest.approx(1.861e13, rel=1e-3)


def test_attention_calls():
    bert, gpt = M.config("bert_large"), M.config("gpt2_medium")
    ops, nbytes = flops.attention_call(bert, 16, 512, "attention_forward")
    assert ops == 4 * 16 * 512 * 512 * 1024
    assert nbytes == 4 * 16 * 512 * 1024 * 2
    ops_b, bytes_b = flops.attention_call(bert, 16, 512, "attention_backward")
    assert (ops_b, bytes_b) == (2 * ops, 2 * nbytes)
    ops_c, bytes_c = flops.attention_call(gpt, 8, 1024, "attention_forward")
    assert ops_c == 4 * 8 * 1024 * 1024 * 1024 // 2
    dq = flops.attention_call(gpt, 8, 1024, "attention_backward_dq")
    dkv = flops.attention_call(gpt, 8, 1024, "attention_backward_dkv")
    assert dq[0] + dkv[0] == 2 * ops_c          # the split backward: 4 matmuls
    assert (dq[1], dkv[1]) == (5 * bytes_c // 4, 6 * bytes_c // 4)


# what the parent's harness/flops.py (commit d333b0f, one file holding both
# families) returned for the two cells' shapes, before the counts moved to
# benchmark/counts/: step.mfu and kernels.flash_roofline read these
PINNED_STEPS = [
    ("bert_large", "bert_large.phase2", 1, 16316141862912),
    ("bert_large", "bert_large.phase2", 4, 65264567451648),
    ("gpt2_medium", "gpt2_medium.lm1024", 1, 18607404957696),
]
PINNED_CALLS = {       # the same for both cells: 16 x 512 and 8 x 1024 / 2
    "attention_forward": (17179869184, 67108864),
    "attention_backward": (34359738368, 134217728),
    "attention_backward_dq": (17179869184, 83886080),
    "attention_backward_dkv": (17179869184, 100663296),
}


@pytest.mark.parametrize("config,cell,chips,operations", PINNED_STEPS)
def test_step_flops_returns_the_parents_integer(config, cell, chips,
                                                operations):
    assert flops.step_flops(M.config(config), M.traffic(cell),
                            chips) == operations


@pytest.mark.parametrize("kind", sorted(PINNED_CALLS))
@pytest.mark.parametrize("config,rows,seq", [("bert_large", 16, 512),
                                             ("gpt2_medium", 8, 1024)])
def test_attention_call_returns_the_parents_integers(config, rows, seq, kind):
    assert flops.attention_call(M.config(config), rows, seq,
                                kind) == PINNED_CALLS[kind]


def _family(monkeypatch, **shape):
    """A family whose count file states this attention shape."""
    from types import SimpleNamespace

    monkeypatch.setattr(flops, "counts", lambda config: SimpleNamespace(
        attention_shape=lambda c: shape or None))


def test_grouped_query_moves_k_and_v_at_their_own_width(monkeypatch):
    B, S, D = 4, 256, 128
    _family(monkeypatch, query_heads=32, kv_heads=2, head_size=D, causal=True)
    q, kv = 32 * D, 2 * D
    pair = 2 * 2 * B * S * S * q // 2
    got = {k: flops.attention_call({}, B, S, k) for k in flops.ATTENTION_CALLS}
    assert got["attention_forward"] == (pair, B * S * (2 * q + 2 * kv) * 2)
    assert got["attention_backward"] == (2 * pair,
                                         B * S * (4 * q + 4 * kv) * 2)
    assert got["attention_backward_dq"] == (pair,
                                            B * S * (3 * q + 2 * kv) * 2)
    assert got["attention_backward_dkv"] == (pair,
                                             B * S * (2 * q + 4 * kv) * 2)
    # grouping saves bytes and no operation
    _family(monkeypatch, query_heads=32, kv_heads=32, head_size=D,
            causal=True)
    full = flops.attention_call({}, B, S, "attention_forward")
    assert full[0] == pair and full[1] == B * S * 4 * q * 2


def test_a_head_size_need_not_be_hidden_over_heads(monkeypatch):
    _family(monkeypatch, query_heads=8, kv_heads=8, head_size=256,
            causal=False)
    ops, nbytes = flops.attention_call({"hidden_size": 1024}, 2, 64,
                                       "attention_forward")
    assert ops == 2 * 2 * 2 * 64 * 64 * 2048
    assert nbytes == 2 * 64 * 4 * 2048 * 2


def test_a_family_without_an_attention_kernel_has_no_attention_call(
        monkeypatch):
    _family(monkeypatch)
    with pytest.raises(LookupError, match="states no attention call"):
        flops.attention_call({"builder": "scan_only"}, 2, 64,
                             "attention_forward")


@pytest.mark.parametrize("call", [
    lambda c: flops.step_flops(c, {"rows_per_chip": 1, "seq": 8}, 1),
    lambda c: flops.attention_call(c, 1, 8, "attention_forward")])
def test_no_count_file_is_an_error_that_names_the_file(call):
    with pytest.raises(FileNotFoundError,
                       match=r"add benchmark/counts/hybrid_ssm\.py"):
        call({"builder": "hybrid_ssm"})


def test_the_harness_names_no_family():
    import re
    from pathlib import Path

    harness = Path(flops.__file__).parent
    for path in harness.glob("*.py"):
        code = "\n".join(line.split("#")[0]
                         for line in path.read_text().splitlines())
        assert not re.search(r"""["'](bert|gpt)["']""", code), path.name
        assert not re.search(r"""builder\W*\]?\s*==""", code), path.name


def test_peaks_unknown_chip_raises():
    assert peaks.peaks("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(peaks.UnknownChip, match="no published peaks"):
        peaks.peaks("cpu")
    assert issubclass(peaks.UnknownChip, KeyError)


def test_collective_stats_counts_async_pairs_once():
    text = (
        "  %ar = f32[367480637]{0} all-reduce(f32[367480637]{0} %x), "
        "replica_groups={{0,1,2,3}}\n"
        "  %s = (f32[10]{0}, f32[]) all-reduce-start(f32[10]{0} %a, f32[] %b)\n"
        "  %d = (f32[10]{0}, f32[]) all-reduce-done(%s)\n"
        "  %g = bf16[4,8]{1,0} all-gather(bf16[1,8]{1,0} %y)\n")
    stats = hlo.collective_stats(text)
    assert stats["all-reduce"] == {"ops": 2,
                                   "bytes": 4 * 367480637 + 40 + 4}
    assert stats["all-gather"] == {"ops": 1, "bytes": 64}
