"""The ``deepseek_v3`` stack (``apex_tpu.models.deepseek_v3``) held to the
benchmark's plain reference (``benchmark/reference/deepseek_v3.py``, which
imports nothing of the program) at the rehearsal's tiny widths on the CPU:
loss and every tensor's gradient over a dense and a sparse layer; the
flash kernels with q/k heads wider than v's against ``mha_reference``,
and their names; interleaved rotary against the family's
permute-then-rotate-half form; a sparse layer cut into shares adds up to
the uncut layer; the configuration file's cut and the counts by hand. The
cell's whole step compiled for a described v5e is
``tests/test_deepseek_v3_compile.py``'s. The trainer's first steps against
the reference are the cell's own comparison (``benchmark/run.py``, on the
chip and in its ``--rehearse`` on the CPU); they are not repeated here,
for the tier-1 run's time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import profiler
from apex_tpu.models.afmoe import SharedExpert, SparseMoE
from apex_tpu.models.deepseek_v3 import (DeepseekV3LMHeadModel,
                                         interleaved_to_half,
                                         keep_fp32_filter)
from apex_tpu.models.lfm2 import rotary
from apex_tpu.ops.flash_attention import flash_attention, mha_reference
from benchmark.builders import deepseek_v3 as builder
from benchmark.harness import flops, masks, runner
from benchmark.harness.manifest import Manifest
from benchmark.reference import deepseek_v3 as reference

CONFIG, CELL = "kanana_2_30b_a3b", "kanana_2_30b_a3b.lm8192"


@pytest.fixture(scope="module")
def tiny():
    """The cell's own configuration and traffic at their rehearsal size."""
    manifest = Manifest()
    config = manifest.config(manifest.cell(CELL)["config"])
    return runner._apply_rehearsal(config, manifest.traffic(CELL))


def _float32(config, **program):
    cfg = builder.model_config(config)
    return cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32, **program})


def _two_layers(config):
    """The dense layer and one sparse layer: every kind of layer."""
    return {**config, "num_hidden_layers": 2}


def _off_the_symmetric_start(weights):
    """Unequal gains and biases of order one, matrices whose scores are
    apart (the expert bias moves the choice, as a trained one would)."""
    keys = jax.random.split(jax.random.PRNGKey(1), len(weights))
    return {n: (w + 0.1 * jax.random.normal(k, w.shape)
                if reference.keeps_float32(n) else 8.0 * w)
            for k, (n, w) in zip(keys, sorted(weights.items()))}


def test_loss_and_gradients_match_the_reference(tiny):
    """Through the flash kernels (interpreted) in float32."""
    config = _two_layers(tiny[0])
    model = DeepseekV3LMHeadModel(_float32(config))
    leaf_map = builder.leaf_map(reference.kinds(config))
    weights = _off_the_symmetric_start(
        reference.init_weights(config, jax.random.PRNGKey(0)))
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0,
                             config["vocab_size"])
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: model.apply(
            {"params": p}, ids, method="loss")[0])(
                leaf_map.to_program(weights))
        lr, gr = jax.value_and_grad(lambda w: reference.loss(
            w, {"ids": ids}, None, config, masks))(weights)
    # float32 on both sides: what is left is summation order (flash's
    # tiles, the grouped matmul's rows)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    got = leaf_map.to_reference(gp)
    assert set(got) == set(gr)
    for name in sorted(gr):
        want = np.asarray(gr[name], np.float64)
        scale = float(np.max(np.abs(want)))
        # every tensor is reached, but the bias, which has no gradient
        assert (scale > 0) == (not name.endswith("expert_bias")), name
        assert float(np.max(np.abs(got[name] - want))) <= 1e-4 * scale, name


# -- the flash kernels with v narrower than q and k -----------------------------

def _pallas_names(fn, *args):
    """Names of the Pallas calls in ``fn``'s jaxpr, inner ones too."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


@pytest.mark.parametrize("shape", [(2, 640, 96, 64), (1, 1024, 192, 128)],
                         ids=["padded", "published"])
def test_flash_at_two_head_sizes_matches_the_reference(shape):
    """Causal, past one tile (640 -> 768 in 384-blocks with q and k padded
    from 96 to 128 lanes, 1,024 in 512-blocks; interpreted): the output at
    v's head size, and dq, dk at q's and k's, dv at v's, against the
    composed reference; the calls are ``flash_mla_*``."""
    H, S, D, Dv = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(kk, (1, H, S, D)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (1, H, S, Dv))
    g = jax.random.normal(ks[3], (1, H, S, Dv))

    def loss(attend):
        return lambda q, k, v: jnp.sum(
            attend(q, k, v, None, True, D ** -0.5) * g)

    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, None, True, D ** -0.5)
        want = mha_reference(q, k, v, None, True, D ** -0.5)
        got_g = jax.grad(loss(flash_attention), (0, 1, 2))(q, k, v)
        want_g = jax.grad(loss(mha_reference), (0, 1, 2))(q, k, v)
    assert out.shape == (1, H, S, Dv)
    # float32 tiles against one float32 softmax: summation order alone
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5
    for name, a, b in zip(("dq", "dk", "dv"), got_g, want_g):
        assert a.shape == b.shape, name
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5 * float(
            jnp.max(jnp.abs(b))), name
    names = _pallas_names(jax.grad(loss(flash_attention), (0, 1, 2)),
                          q, k, v)
    assert sorted(names) == ["flash_mla_bwd_dkv", "flash_mla_bwd_dq",
                             "flash_mla_fwd"]


@pytest.mark.parametrize("S", [512, 1024], ids=["one_tile", "tiles"])
def test_flash_at_one_head_size_keeps_its_names(S):
    """Where q, k and v share a head size the calls are named as they
    were (``flash_fwd``, ``flash_bwd`` or ``flash_bwd_dq`` +
    ``flash_bwd_dkv``): the other cells' patterns read them still."""
    q = jnp.ones((1, 2, S, 64), jnp.bfloat16)
    f = jax.grad(lambda q: jnp.sum(flash_attention(
        q, q, q, None, True, 0.125).astype(jnp.float32)))
    names = sorted(_pallas_names(f, q))
    assert names == (["flash_bwd", "flash_fwd"] if S == 512 else
                     ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"])
    with pytest.raises(ValueError, match="head size"):
        flash_attention(q, q[..., :32], q, None, True, 0.125)


def test_interleaved_rotary_is_the_permuted_rotate_half():
    """The reference turns the pairs ``(t_2i, t_2i+1)`` where they lie; the
    program moves them to ``(i, i + d/2)`` and turns the halves, as the
    family does: the same vector up to that permutation, and the same dot
    product of every query with every key."""
    ks = jax.random.split(jax.random.PRNGKey(5))
    q = jax.random.normal(ks[0], (1, 12, 3, 8))
    k = jax.random.normal(ks[1], (1, 12, 1, 8))
    theta = 1e6
    ref_q = reference.rotary_interleaved(q[0], theta)
    ref_k = reference.rotary_interleaved(k[0], theta)
    got_q = rotary(interleaved_to_half(q), theta)[0]
    got_k = rotary(interleaved_to_half(k), theta)[0]
    np.testing.assert_allclose(got_q, interleaved_to_half(ref_q), atol=1e-6)
    dots = jnp.einsum("ihd,jhd->hij", ref_q, jnp.broadcast_to(ref_k,
                                                               ref_q.shape))
    got = jnp.einsum("ihd,jhd->hij", got_q, jnp.broadcast_to(got_k,
                                                             got_q.shape))
    np.testing.assert_allclose(got, dots, atol=1e-5)
    # position 0 is not turned; a pair keeps its length
    np.testing.assert_allclose(ref_q[0], q[0, 0], atol=1e-7)
    np.testing.assert_allclose(
        jnp.hypot(ref_q[..., 0::2], ref_q[..., 1::2]),
        jnp.hypot(q[0, ..., 0::2], q[0, ..., 1::2]), atol=1e-5)


def test_the_sparse_layer_shares_add_up_to_the_uncut_layer(tiny):
    """Four ranks of 2 experts each (offsets 0, 2, 4, 6 of 8: the cut of
    ``kanana_2_30b_a3b``, 0, 8, .., 120 of 128, at this size): their
    routed parts, with the shared experts counted ONCE, give the
    reference's uncut layer; each assignment is computed on one rank."""
    config = {**tiny[0], "n_routed_experts": 8, "num_experts_per_tok": 2,
              "deployment": {"n_routed_experts_published": 8,
                             "expert_offset": 0}}
    weights = _off_the_symmetric_start(
        reference.init_weights(_two_layers(config), jax.random.PRNGKey(3)))
    lw = {n: weights[f"layers/moe/{n}"][0] for n in reference.KINDS["moe"]}
    x = jax.random.normal(jax.random.PRNGKey(4),
                          (2, 24, config["hidden_size"]))
    cfg = _float32(config, fused_kernels=False)
    shared = {"gate_up": {"kernel": lw["shared_gate_up"]},
              "down": {"kernel": lw["shared_down"]}}
    with jax.default_matmul_precision("highest"):
        whole = jax.vmap(lambda row: reference.experts(
            row, lw, config, lambda a, b: jnp.matmul(a, b)))(x)
        total, pairs, ranks = 0.0, 0.0, range(0, 8, 2)
        for first in ranks:
            mine = {"expert_bias": lw["expert_bias"], "shared": shared,
                    "experts": {"router": lw["router"],
                                "w_gate_up": lw["w_gate_up"][first:first + 2],
                                "w_down": lw["w_down"][first:first + 2]}}
            layer = SparseMoE(cfg.__class__(**{**cfg.__dict__,
                                               "experts_held": 2,
                                               "expert_offset": first}))
            y, counters = layer.apply({"params": mine}, x)
            total = total + y
            pairs += float(counters[profiler.MOE_ASSIGNMENTS_HELD])
        shared_part = SharedExpert(cfg).apply({"params": shared}, x)
    total = total - (len(ranks) - 1) * shared_part
    assert pairs == x.shape[0] * x.shape[1] * 2          # each pair once
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-5 * float(
        jnp.max(jnp.abs(whole)))
    # the two shared experts are one SwiGLU of their summed width
    assert lw["shared_down"].shape[0] == 2 * config["moe_intermediate_size"]


def test_the_filter_keeps_what_the_reference_keeps(tiny):
    config = tiny[0]
    leaf_map = builder.leaf_map(reference.kinds(config))
    weights = reference.init_weights(config, jax.random.PRNGKey(0))
    kept = leaf_map.to_reference(jax.tree_util.tree_map_with_path(
        lambda path, x: float(keep_fp32_filter("/".join(
            str(p.key) for p in path))), leaf_map.to_program(weights)))
    for name, flags in kept.items():
        assert np.all(flags == float(reference.keeps_float32(name))), name
    # the final norm; a layer's two norms and the latent's; the router and
    # the expert bias (the dense and the sparse layers' pre-MLP norms are
    # stacked apart)
    assert sum(reference.keeps_float32(n) for n in weights) == 7


# -- the configuration, its cell and its counts ---------------------------------

def test_the_configuration_file_states_its_cut():
    M = Manifest()
    entry, c = M._entry("configs", CONFIG), M.config(CONFIG)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    # every width is the published one
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_attention_heads"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["qk_head_dim"], c["v_head_dim"], c["num_experts_per_tok"],
            c["n_shared_experts"], c["q_lora_rank"]) == (
        2048, 6144, 768, 32, 512, 128, 64, 192, 128, 6, 2, None)
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"]) == (5, 1, 8, 16032)
    d = c["deployment"]
    assert (d["chips_sharing_a_layer"], d["expert_parallel"],
            d["vocab_parallel"], d["n_routed_experts_published"],
            d["vocab_size_published"], d["num_hidden_layers_published"]) == (
        16, 16, 8, 128, 128256, 48)
    assert d["vocab_size_published"] // d["vocab_parallel"] == c["vocab_size"]
    for key in ("attention", "scale", "rotary pairing", "router", "experts",
                "expert bias", "optimizer", "weights", "remat"):
        assert key in c["assumed"], key
    assert len(c["departures"]) == 1 and "noaux_tc" in c["departures"][0]
    rehearsal = c["rehearsal"]
    assert rehearsal["qk_head_dim"] == 24 != rehearsal["v_head_dim"]
    cell = M.cell(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "1/16" in cell["why"] and "768 where 12,288" in cell["why"]
    t = M.traffic(CELL)
    assert (t["seq"], t["rows_per_chip"], t["feed"], t["corpus_rows"],
            t["prefetch"]) == (8192, 2, "loader", 512, 2)
    mine = [m["name"] for m in M.doc["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["kernels.flash_mla_roofline", "attn.mla_ms",
                    "attn.mla_latent_ms"]
    first = [m["name"] for m in M.doc["per_layer"]].index(mine[0])
    assert [m["name"] for m in M.doc["per_layer"][first:first + 3]] == mine


def test_the_counts_by_hand():
    """915.8 MFLOP a token forward (latent attention three quarters of it,
    the causal core at q/k 192 and v 128 45.8%), 45.0 TFLOP a step; one
    forward call 1.37 TFLOP; no call counted at one head size."""
    M = Manifest()
    c, t = M.config(CONFIG), M.traffic(CELL)
    counts = flops.counts(c)
    assert counts.pattern(c) == [("attn", "dense")] + [("attn", "moe")] * 4
    S, rows, H, V, n = 8192, 2, 2048, 16032, 32
    T = rows * S
    proj = 2 * T * (H * n * 192 + H * 576 + 512 * n * 256 + n * 128 * H)
    core = 2 * rows * (S * S // 2) * n * (192 + 128)
    dense = 3 * 2 * T * H * 6144
    moe = (2 * T * H * 128 + 3 * 2 * T * H * 1536
           + 3 * 2 * T * (6 * 8 / 128) * H * 768)
    want = int(5 * (proj + core) + dense + 4 * moe
               + 2 * rows * (S - 1) * H * V)
    assert counts.forward_flops(c, t, rows) == want
    assert want / T / 1e6 == pytest.approx(915.8, abs=0.05)
    assert 5 * core / want == pytest.approx(0.458, abs=0.001)
    assert flops.step_flops(c, t, 1) == 3 * want == pytest.approx(4.50e13,
                                                                 rel=0.001)
    ops, nbytes = counts.mla_attention_call(c, rows, S, "attention_forward")
    assert ops == core == pytest.approx(1.374e12, rel=0.001)
    assert nbytes == T * n * (2 * 192 + 2 * 128) * 2
    for kind, pairs, at_qk, at_v in (("attention_backward_dq", 1, 3, 2),
                                     ("attention_backward_dkv", 1, 3, 3),
                                     ("attention_backward", 2, 4, 4)):
        assert counts.mla_attention_call(c, rows, S, kind) == (
            pairs * ops, T * n * (at_qk * 192 + at_v * 128) * 2)
    assert counts.attention_shape(c) is None
    with pytest.raises(LookupError, match="attention_shape is None"):
        flops.attention_call(c, rows, S, "attention_forward")
