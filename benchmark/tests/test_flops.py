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


def test_peaks_unknown_chip_raises():
    assert peaks.peaks("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


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
