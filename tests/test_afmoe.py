"""The ``afmoe`` stack (``apex_tpu.models.afmoe``) held to the benchmark's
plain reference (``benchmark/reference/afmoe.py``, which imports nothing of
the program) at the rehearsal's tiny widths on the CPU: loss and every
tensor's gradient over window and global layers, dense and sparse, fused
and composed; a sparse layer cut into shares adds up to the uncut layer;
three optimizer steps through amp O2 + FusedAdam + ``build_train_step``
against ``reference/train.py: run``, and the float8 control and a layer
with its gate or its window left out failing the same limits; counters,
the parameter count, the configuration file's cut, the scopes, the counts
by hand, and the cell's rehearsal."""

import functools
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import profiler
from apex_tpu.models.afmoe import (GLOBAL, WINDOW, AfmoeConfig,
                                   AfmoeLMHeadModel, SharedExpert, SparseMoE,
                                   keep_fp32_filter)
from benchmark import control
from benchmark.builders import afmoe as builder
from benchmark.harness import check, flops, masks, runner
from benchmark.harness.manifest import ROOT, Manifest
from benchmark.reference import afmoe as reference, train

CONFIG, CELL = "trinity_mini", "trinity_mini.lm8192"


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
    """A window layer with the dense MLP and a global layer with experts:
    every kind of attention and of feed-forward."""
    return {**config, "num_hidden_layers": 2, "num_dense_layers": 1,
            "layer_types": [WINDOW, GLOBAL]}


def _off_the_symmetric_start(weights):
    """Unequal gains and biases of order one, matrices whose scores are
    apart (the expert bias moves the choice, as a trained one would)."""
    keys = jax.random.split(jax.random.PRNGKey(1), len(weights))
    return {n: (w + 0.1 * jax.random.normal(k, w.shape)
                if reference.keeps_float32(n) else 8.0 * w)
            for k, (n, w) in zip(keys, sorted(weights.items()))}


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "composed"])
def test_loss_and_gradients_match_the_reference(tiny, fused):
    config = _two_layers(tiny[0])
    model = AfmoeLMHeadModel(_float32(config, fused_kernels=fused))
    leaf_map = builder.KindLeafMap(reference.kinds(config))
    weights = _off_the_symmetric_start(
        reference.init_weights(config, jax.random.PRNGKey(0)))
    # longer than the window (24), so that the band cuts
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


def test_the_sparse_layer_shares_add_up_to_the_uncut_layer(tiny):
    """Four ranks of 2 experts each (offsets 0, 2, 4, 6 of 8: the cut of
    ``trinity_mini``, 0, 8, .., 120 of 128, at this size): their routed
    parts, with the shared expert counted ONCE, give the reference's uncut
    layer; each assignment is computed on one rank."""
    config = {**tiny[0], "num_experts": 8, "num_experts_per_tok": 2,
              "deployment": {"num_experts_published": 8, "expert_offset": 0}}
    weights = _off_the_symmetric_start(
        reference.init_weights(_two_layers(config),
                               jax.random.PRNGKey(3)))
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


def test_the_filter_keeps_what_the_reference_keeps(tiny):
    config = tiny[0]
    leaf_map = builder.KindLeafMap(reference.kinds(config))
    weights = reference.init_weights(config, jax.random.PRNGKey(0))
    kept = leaf_map.to_reference(jax.tree_util.tree_map_with_path(
        lambda path, x: float(keep_fp32_filter("/".join(
            str(p.key) for p in path))), leaf_map.to_program(weights)))
    for name, flags in kept.items():
        assert np.all(flags == float(reference.keeps_float32(name))), name
    # the final norm; a layer's four norms, q's and k's gains; the router
    # and the expert bias
    assert sum(reference.keeps_float32(n) for n in weights) == 11


def test_counters_logits_and_the_parameter_count(tiny):
    config = _two_layers(tiny[0])
    cfg = _float32({**config, "num_experts": 16}, fused_kernels=False)
    model = AfmoeLMHeadModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)["params"]
    logits, counters = model.apply({"params": params}, ids)
    assert logits.shape == (2, 24, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    # all 16 experts held: every assignment of a sparse layer is here
    sparse = cfg.num_hidden_layers - cfg.num_dense_layers
    assert float(counters[profiler.MOE_ASSIGNMENTS_HELD]) == (
        sparse * 2 * 24 * cfg.num_experts_per_tok)
    assert float(counters[profiler.MOE_TOKENS_DROPPED]) == 0.0
    assert float(counters[profiler.MOE_LOAD_MAX_OVER_MEAN]) >= 1.0
    # the embedding's muP scale and the untied head
    assert params["lm_head"].shape == (cfg.hidden_size, cfg.vocab_size)
    assert "gate_proj" in params["model"]["layers_0"]["self_attn"]
    with pytest.raises(ValueError, match="multiple"):
        AfmoeConfig.tiny(num_attention_heads=3)
    with pytest.raises(ValueError, match="layer_types"):
        AfmoeConfig.tiny(layer_types=("conv",))
    # the cell's own size, from shapes: the configuration file's count
    c = Manifest().config(CONFIG)
    full = AfmoeLMHeadModel(builder.model_config(c))
    shapes = jax.eval_shape(lambda k: full.init(k, ids)["params"],
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == \
        c["n_params"] == 504_147_712


def test_the_configuration_file_states_its_cut():
    M = Manifest()
    entry, c = M._entry("configs", CONFIG), M.config(CONFIG)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    # every width is the published one
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["head_dim"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["num_experts_per_tok"], c["sliding_window"]) == (
        2048, 6144, 1024, 128, 32, 4, 8, 2048)
    # published layer 0 (dense, window) and layers 4-7 (window x 3, global)
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"]) == (5, 1, 8, 25024)
    assert c["layer_types"] == [WINDOW] * 4 + [GLOBAL]
    d = c["deployment"]
    assert (d["chips_sharing_a_layer"], d["expert_parallel"],
            d["vocab_parallel"], d["num_experts_published"],
            d["vocab_size_published"], d["num_hidden_layers_published"]) == (
        16, 16, 8, 128, 200192, 32)
    assert d["vocab_size_published"] // d["vocab_parallel"] == c["vocab_size"]
    for key in ("window layers", "global layers", "output gate", "norms",
                "muP", "router", "expert bias", "optimizer", "weights",
                "remat"):
        assert key in c["assumed"], key
    assert len(c["departures"]) == 1 and "load_balance_coeff" in \
        c["departures"][0]
    cell = M.cell(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "1/16" in cell["why"] and "1,024 where 16,384" in cell["why"]
    t = M.traffic(CELL)
    assert (t["seq"], t["rows_per_chip"], t["feed"], t["corpus_rows"],
            t["prefetch"]) == (8192, 2, "loader", 512, 2)
    mine = [m["name"] for m in M.doc["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["kernels.flash_window_roofline", "attn.window_ms",
                    "attn.global_ms", "kernels.flash_global_roofline"]
    first = [m["name"] for m in M.doc["per_layer"]].index(mine[0])
    assert M.doc["per_layer"][first:first + 4] == [
        m for m in M.doc["per_layer"] if m.get("workloads") == [CELL]]


def test_the_counts_by_hand():
    """A window layer's attention at the band's visible pairs (``sum_i
    min(i + 1, W)`` a head: 1,792 keys a query on average at S = 8,192, W
    = 2,048), the global layer's causal at half; a step near 35 TFLOP."""
    c, t = Manifest().config(CONFIG), Manifest().traffic(CELL)
    counts = flops.counts(c)
    assert counts.pattern(c) == [("window", "dense")] + [
        ("window", "moe")] * 3 + [("global", "moe")]
    S, W, rows, H, V = 8192, 2048, 2, 2048, 25024
    pairs = W * (W + 1) // 2 + (S - W) * W
    assert counts.window_pairs(S, W) == pairs == 14_681_088
    assert counts.window_pairs(100, 500) == 100 * 101 // 2    # causal
    assert pairs / S == pytest.approx(1792.1, abs=0.1)
    T, qw, kvw = rows * S, 32 * 128, 4 * 128
    proj = 2 * T * H * (3 * qw + 2 * kvw)
    window = proj + 2 * 2 * rows * pairs * qw
    glob = proj + 2 * 2 * rows * S * S * qw // 2
    dense = 3 * 2 * T * H * 6144
    moe = (2 * T * H * 128 + 3 * 2 * T * H * 1024
           + 3 * 2 * T * (8 * 8 / 128) * H * 1024)
    want = int(4 * window + glob + dense + 4 * moe
               + 2 * rows * (S - 1) * H * V)
    assert counts.forward_flops(c, t, rows) == want
    assert flops.step_flops(c, t, 1) == 3 * want
    assert flops.step_flops(c, t, 1) == pytest.approx(3.50e13, rel=0.01)
    ops, nbytes = counts.window_attention_call(c, rows, S,
                                               "attention_forward")
    assert ops == 2 * 2 * rows * pairs * qw
    assert nbytes == rows * S * (2 * qw + 2 * kvw) * 2
    for kind, n, at_q, at_kv in (("attention_backward_dq", 1, 3, 2),
                                 ("attention_backward_dkv", 1, 2, 4),
                                 ("attention_backward", 2, 4, 4)):
        assert counts.window_attention_call(c, rows, S, kind) == (
            n * ops, rows * S * (at_q * qw + at_kv * kvw) * 2)
    # the forward call's 70 live tiles of 512 x 512 hold its pairs
    assert 70 * 512 * 512 >= pairs > 42 * 512 * 512
    assert counts.attention_shape(c) == {
        "query_heads": 32, "kv_heads": 4, "head_size": 128, "causal": True}
    # the global layer's causal call (kernels.flash_global_roofline): at half
    assert flops.attention_call(c, rows, S, "attention_forward") == (
        2 * 2 * rows * S * S * qw // 2, nbytes)


def test_layer_scopes_are_in_the_step(tiny):
    """Every layer scope this family emits is on some op of the lowered
    train step, under ``train_fwd_bwd``; the gate sits in both kinds of
    attention, rotary in the window layers alone; the docstring table
    lists each new scope."""
    config, traffic = tiny
    built = builder.build(config, traffic, reference, seed=0,
                          key=runner.weights_key(0))
    batch = {"ids": jnp.zeros((1, traffic["rows_per_chip"], traffic["seq"]),
                              jnp.int32)}
    text = built.step.lower(built.state, batch).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    mine = (profiler.WINDOW_ATTENTION, profiler.GLOBAL_ATTENTION,
            profiler.ATTN_GATE, profiler.ATTN_QK_NORM, profiler.ATTN_ROPE,
            profiler.MLP_DENSE, profiler.MOE_ROUTER, profiler.MOE_DISPATCH,
            profiler.MOE_EXPERTS, profiler.MOE_SHARED, profiler.MOE_COMBINE,
            profiler.LM_HEAD, profiler.LM_LOSS)

    def under(scope, path):
        return re.search(r"(^|[/(])" + scope + r"([/)]|$)", path)

    for scope in mine:
        assert any(under(scope, p) for p in paths), scope
    gated = [p for p in paths if under(profiler.ATTN_GATE, p)]
    for kind in (profiler.WINDOW_ATTENTION, profiler.GLOBAL_ATTENTION):
        assert any(under(kind, p) for p in gated), kind
    assert all(under(profiler.WINDOW_ATTENTION, p) for p in paths
               if under(profiler.ATTN_ROPE, p))
    assert not any(under(profiler.GLOBAL_ATTENTION, p)
                   and under(profiler.ATTN_ROPE, p) for p in paths)
    for name in mine[:3]:
        assert name in profiler.LAYER_SCOPES and name not in profiler.SCOPES
        assert re.search(r"^" + name + r"\s", profiler.__doc__, re.M), name
    for name in ("flash_window_fwd", "flash_window_bwd_dq",
                 "flash_window_bwd_dkv"):
        assert name in profiler.KERNEL_NAMES


# -- three steps of the trainer against the reference ---------------------------------

SEEDS = (4000000011, 4000000012, 4000000013)


@pytest.fixture(scope="module")
def first_steps(tiny):
    """``seed -> (program, reference)``: three steps of the program (amp O2
    + FusedAdam + build_train_step + TrainLoop, as the cell builds them,
    compiled once) and of the plain reference, on the same seeded weights
    and batches; beside it the reference's runner and the limits."""
    config, traffic = tiny
    program = control._Program(config, traffic, builder, reference, 1)

    def batches(seed):
        return control.first_batches(config, traffic, seed, 1,
                                     runner.FIRST_STEPS)

    @functools.cache
    def plain(seed, **options):
        return train.run(reference, config, config["optimizer"],
                         runner.weights_key(seed), batches(seed), masks,
                         **options)

    def both(seed):
        return program.first_steps(seed, batches(seed)), plain(seed)

    return both, plain, traffic["limits"]


@pytest.mark.parametrize("seed", SEEDS)
def test_three_steps_match_the_reference(first_steps, seed):
    both, _, limits = first_steps
    verdict = check.compare(*both(seed), limits)
    assert verdict["correct"], verdict["numbers"]
    assert {"grad_median_leaf", "change_median_leaf"} <= {
        n for n, v in limits.items() if v is not None}


def test_a_run_computed_in_float8_fails_the_same_limits(first_steps):
    """The rehearsal's limits sit between the bf16 program's reading and
    the reading of the reference with every matmul rounded through
    float8_e4m3: that run is NOT correct."""
    _, plain, limits = first_steps
    verdict = check.compare(plain(SEEDS[0], precision="fp8"),
                            plain(SEEDS[0]), limits)
    assert not verdict["correct"], verdict["numbers"]


@pytest.mark.parametrize("left_out", ["gate", "window"])
def test_a_layer_without_its_gate_or_window_fails_the_same_limits(
        tiny, first_steps, monkeypatch, left_out):
    """The reference with one term of the layer left out - the output gate
    (``u = o``), or the window (a window layer attends causally over the
    whole row, 64 positions against a window of 24) - put in the program's
    place: NOT correct by the same limits."""
    config, traffic = tiny
    _, plain, limits = first_steps
    if left_out == "gate":
        monkeypatch.setattr(reference, "gated_output", lambda ctx, g: ctx)
    else:
        monkeypatch.setattr(reference, "visible",
                            lambda i, j, window: i >= j)
    batches = control.first_batches(config, traffic, SEEDS[0], 1,
                                    runner.FIRST_STEPS)
    faulty = train.run(reference, config, config["optimizer"],
                       runner.weights_key(SEEDS[0]), batches, masks)
    verdict = check.compare(faulty, plain(SEEDS[0]), limits)
    assert not verdict["correct"], verdict["numbers"]


def test_the_new_cell_rehearses_to_correct():
    """``benchmark/run.py --rehearse`` of the cell, traced, in a child
    process: correct, the counters read, no compilation in the window."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", "4000000021", "--seconds", "1", "--trace", "1",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    shape = json.loads(done.stdout.split(
        "REHEARSAL on the CPU, not a result: ")[1].splitlines()[0])
    assert shape["correct"] is True
    assert {"step.live_gib", "amp.steps_skipped",
            "loop.host_ms_per_step"} <= set(shape["metrics"])
    assert 'compared compilations_in_window: {"value": 0' in done.stderr
    assert "(benchmark/counts/afmoe.py)" in done.stdout
