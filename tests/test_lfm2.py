"""The ``lfm2`` stack (``apex_tpu.models.lfm2``) held to the benchmark's
plain reference (``benchmark/reference/lfm2.py``, which imports nothing
of the program) at the rehearsal's tiny widths on the CPU: each kind of
layer and the whole stack in loss and gradients, three optimizer steps
through amp O2 + FusedAdam + ``build_train_step`` against
``reference/train.py: run``; the short convolution and rotary + q/k norm
alone against their explicit forms; and the family's place in the
benchmark (mirrored from ``benchmark/tests/test_lfm2_family.py``, which
tier 1 does not run)."""

import functools
import importlib.util
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
from apex_tpu.models import lfm2
from apex_tpu.models.lfm2 import Lfm2Config, Lfm2LMHeadModel, keep_fp32_filter
from apex_tpu.ops.short_conv import (gated_short_conv,
                                     gated_short_conv_reference)
from benchmark import control
from benchmark.builders import lfm2 as builder
from benchmark.harness import check, masks, runner
from benchmark.harness.manifest import ROOT, Manifest
from benchmark.reference import lfm2 as reference, train

CONFIG, CELL = "lfm2_24b_a2b", "lfm2_24b_a2b.lm8192"
FIVE = ("conv", "full_attention", "conv", "conv", "conv")


@pytest.fixture(scope="module")
def tiny():
    """The cell's own configuration and traffic at their rehearsal size."""
    manifest = Manifest()
    config = manifest.config(manifest.cell(CELL)["config"])
    return runner._apply_rehearsal(config, manifest.traffic(CELL))


def _sides(config, layer_types, dense, seq=40, rows=2, seed=0, **program):
    """(program loss fn of its params, reference loss fn of its weights,
    the leaf map, the seeded weights) for a stack of ``layer_types``."""
    config = {**config, "layer_types": list(layer_types),
              "num_hidden_layers": len(layer_types),
              "num_dense_layers": dense}
    cfg = builder.model_config(config)
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32, **program})
    model = Lfm2LMHeadModel(cfg)
    leaf_map = builder.KindLeafMap(reference.kinds(config))
    weights = reference.init_weights(config, jax.random.PRNGKey(seed))
    # off the symmetric start: unequal gains and taps of order one, an
    # expert bias that changes the choice (seeded weights leave gains at
    # 1 and the bias at 0)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(weights))
    weights = {n: (w + 0.1 * jax.random.normal(k, w.shape)
                   if reference.keeps_float32(n) else 8.0 * w)
               for k, (n, w) in zip(keys, sorted(weights.items()))}
    ids = jax.random.randint(jax.random.PRNGKey(seed + 2), (rows, seq), 0,
                             config["vocab_size"])

    def program_loss(params):
        return model.apply({"params": params}, ids, method="loss")[0]

    def reference_loss(w):
        return reference.loss(w, {"ids": ids}, 0, config, masks)

    return program_loss, reference_loss, leaf_map, weights


STACKS = {"conv+dense": (("conv",), 1), "attention+experts":
          (("full_attention",), 0), "conv+experts": (("conv",), 0),
          "the five layers": (FIVE, 1)}


@pytest.mark.parametrize("stack,fused", [
    (stack, True) for stack in sorted(STACKS)] + [("the five layers", False)],
    ids=lambda v: v if isinstance(v, str) else ("fused" if v else "composed"))
def test_loss_and_gradients_match_the_reference(tiny, stack, fused):
    program_loss, reference_loss, leaf_map, weights = _sides(
        tiny[0], *STACKS[stack], fused_kernels=fused)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program_loss)(
            leaf_map.to_program(weights))
        lr, gr = jax.value_and_grad(reference_loss)(weights)
    # float32 on both sides: what is left is summation order (flash's
    # tiles, the grouped matmul's rows, the kernel's blocks)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    got = leaf_map.to_reference(gp)
    assert set(got) == set(gr)
    for name in sorted(gr):
        want = np.asarray(gr[name], np.float64)
        scale = float(np.max(np.abs(want)))
        if name.endswith("expert_bias"):       # a buffer: no gradient
            assert scale == 0.0 and not np.any(got[name])
            continue
        assert scale > 0, name                  # every tensor is reached
        assert float(np.max(np.abs(got[name] - want))) < 1e-4 * scale, name


def test_the_filter_keeps_what_the_reference_keeps(tiny):
    config = tiny[0]
    leaf_map = builder.KindLeafMap(reference.kinds(config))
    weights = reference.init_weights(config, jax.random.PRNGKey(0))
    kept = leaf_map.to_reference(jax.tree_util.tree_map_with_path(
        lambda path, x: float(keep_fp32_filter("/".join(
            str(p.key) for p in path))), leaf_map.to_program(weights)))
    for name, flags in kept.items():
        assert np.all(flags == float(reference.keeps_float32(name))), name
    # the final norm, a norm per kind of part, q's and k's gains, the
    # router, the expert bias, the taps
    assert sum(reference.keeps_float32(n) for n in weights) == 1 + 4 + 2 + 3


def test_counters_logits_and_the_parameter_count(tiny):
    config = tiny[0]
    cfg = builder.model_config({**config, "num_experts": 16})
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32})
    model = Lfm2LMHeadModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)["params"]
    logits, counters = model.apply({"params": params}, ids)
    assert logits.shape == (2, 24, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    # all 16 experts held: every assignment is computed here
    assert float(counters[profiler.MOE_ASSIGNMENTS_HELD]) == (
        4 * 2 * 24 * cfg.num_experts_per_tok)
    assert float(counters[profiler.MOE_TOKENS_DROPPED]) == 0.0
    assert float(counters[profiler.MOE_LOAD_MAX_OVER_MEAN]) >= 1.0
    loss, _ = model.apply({"params": params}, ids, method="loss")
    lse = jax.nn.logsumexp(logits[:, :-1], -1)
    picked = jnp.take_along_axis(logits[:, :-1], ids[:, 1:, None], -1)[..., 0]
    assert float(loss) == pytest.approx(float(jnp.mean(lse - picked)),
                                        rel=1e-6)
    with pytest.raises(ValueError, match="only"):
        Lfm2Config(layer_types=("conv", "mamba"))
    # the cell's own size, from shapes: the configuration file's arithmetic
    full = Lfm2LMHeadModel(builder.model_config(Manifest().config(CONFIG)))
    shapes = jax.eval_shape(lambda k: full.init(k, ids)["params"],
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(shapes)) == 469_285_248


def test_the_configuration_file_states_its_cut():
    M = Manifest()
    entry, c = M._entry("configs", CONFIG), M.config(CONFIG)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    catalog = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs") else []
    for row in catalog:
        if row["source_url"] == entry["source"]:
            for key, value in row["config"].items():
                if key not in c["reduced"]:
                    assert c[key] == value, key
    # every width is the published one
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["num_experts_per_tok"],
            c["conv_L_cache"]) == (2048, 11776, 1536, 32, 8, 4, 3)
    assert tuple(c["layer_types"]) == FIVE
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"]) == (5, 1, 8, 8192)
    d = c["deployment"]
    assert (d["chips_sharing_a_layer"], d["num_experts_published"],
            d["vocab_size_published"], d["num_hidden_layers_published"],
            d["num_dense_layers_published"]) == (8, 64, 65536, 40, 2)
    assert len(c["departures"]) == 1 and "bias" in c["departures"][0]
    assert "tie_word_embeddings" in c["assumed"]
    cell = M.cell(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "1/8" in cell["why"] and "1 of 5" in cell["why"]
    t = M.traffic(CELL)
    assert (t["seq"], t["rows_per_chip"], t["feed"], t["corpus_rows"],
            t["prefetch"]) == (8192, 2, "loader", 512, 2)


def test_layer_scopes_are_in_the_step(tiny):
    """Every layer scope this family emits is on some op of the lowered
    train step, under ``train_fwd_bwd``; the docstring table lists each."""
    config, traffic = tiny
    built = builder.build(config, traffic, reference, seed=0,
                          key=runner.weights_key(0))
    batch = {"ids": jnp.zeros((1, traffic["rows_per_chip"], traffic["seq"]),
                              jnp.int32)}
    text = built.step.lower(built.state, batch).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    mine = (profiler.CONV_IN_PROJ, profiler.CONV_GATE, profiler.CONV_OUT_PROJ,
            profiler.ATTN_QK_NORM, profiler.ATTN_ROPE, profiler.MLP_DENSE,
            profiler.GQA_ATTENTION, profiler.MOE_ROUTER,
            profiler.MOE_DISPATCH, profiler.MOE_EXPERTS, profiler.MOE_COMBINE,
            profiler.LM_HEAD, profiler.LM_LOSS)
    for scope in mine:
        under = [p for p in paths
                 if re.search(r"(^|[/(])" + scope + r"([/)]|$)", p)]
        assert under, scope
    for name in mine[:6]:
        assert name in profiler.LAYER_SCOPES and name not in profiler.SCOPES
        assert re.search(r"^" + name + r"\s", profiler.__doc__, re.M), name
    # the flax paths the benchmark's readers go by
    for part in ("conv", "self_attn", "dense_ffn", "expert_ffn"):
        assert any(re.search(r"(^|[/(])" + part + r"([/)]|$)", p)
                   for p in paths), part


# -- the short convolution alone ------------------------------------------------------

@pytest.mark.parametrize("rows,tokens,width,taps", [
    (2, 40, 32, 3),       # one block
    (1, 2, 32, 3),        # a sequence shorter than the taps
    (2, 600, 64, 3),      # three blocks backward, two forward
    (1, 33, 128, 4),      # another number of taps
])
def test_short_conv_matches_the_three_shift_form(rows, tokens, width, taps):
    keys = jax.random.split(jax.random.PRNGKey(tokens), 5)
    b, c, x, g = (jax.random.normal(k, (rows, tokens, width))
                  for k in keys[:4])
    w = jax.random.normal(keys[4], (taps, width))

    def scalar(fn):
        return lambda *args: jnp.sum(fn(*args) * g)

    y, want = gated_short_conv(b, c, x, w), gated_short_conv_reference(
        b, c, x, w)
    assert y.shape == want.shape == x.shape
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5
    # causal, and zeros before the start: token 0 sees its own tap alone
    np.testing.assert_allclose(y[:, 0], c[:, 0] * b[:, 0] * x[:, 0] * w[-1],
                               rtol=1e-5, atol=1e-6)
    got = jax.grad(scalar(gated_short_conv), argnums=(0, 1, 2, 3))(b, c, x, w)
    ref = jax.grad(scalar(gated_short_conv_reference),
                   argnums=(0, 1, 2, 3))(b, c, x, w)
    for a, r in zip(got, ref):
        assert float(jnp.max(jnp.abs(a - r))) < 1e-5 * float(
            jnp.max(jnp.abs(r)))


def test_short_conv_in_bfloat16_rounds_once():
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    b, c, x = (jax.random.normal(k, (2, 48, 32)).astype(jnp.bfloat16)
               for k in keys[:3])
    w = jax.random.normal(keys[3], (3, 32))
    y = gated_short_conv(b, c, x, w)
    assert y.dtype == jnp.bfloat16
    exact = gated_short_conv_reference(*(t.astype(jnp.float32)
                                         for t in (b, c, x)), w)
    # half a bfloat16 ulp of the float32 result (and the float32 sum's
    # own order): one rounding, at the end
    gap = jnp.abs(y.astype(jnp.float32) - exact)
    assert bool(jnp.all(gap <= 2.0 ** -8 * jnp.abs(exact) + 1e-30))


# -- rotary positions and the q/k norm alone ------------------------------------------

def test_rotary_and_qk_norm_match_the_explicit_form():
    hd, l, theta, eps = 16, 24, 1e6, 1e-5
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    q = jax.random.normal(k1, (2, l, 4, hd))
    gain = 1.0 + 0.1 * jax.random.normal(k2, (hd,))
    got = lfm2.rotary(lfm2.head_rms_norm(q, gain, eps), theta)
    cos, sin = reference.rope_tables(l, hd, theta)
    normed = reference.rms_norm(q, gain, eps)
    want = (normed * cos[None, :, None, :]
            + reference.rotate_half(normed) * sin[None, :, None, :])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    # position 0 is the identity; a turn keeps a pair's length
    np.testing.assert_allclose(lfm2.rotary(q, theta)[:, 0], q[:, 0],
                               rtol=0, atol=0)
    turned = lfm2.rotary(q, theta)
    np.testing.assert_allclose(
        turned[..., :hd // 2] ** 2 + turned[..., hd // 2:] ** 2,
        q[..., :hd // 2] ** 2 + q[..., hd // 2:] ** 2, rtol=1e-4, atol=1e-5)
    # scores depend on the distance alone: shifting both positions alike
    k = jax.random.normal(k2, (2, l, 4, hd))
    rq, rk = lfm2.rotary(q, theta), lfm2.rotary(k, theta)
    near = jnp.einsum("bhd,bhd->bh", rq[:, 5], rk[:, 3])
    pad = jnp.zeros((2, 7, 4, hd))
    rq2 = lfm2.rotary(jnp.concatenate([pad, q], 1), theta)
    rk2 = lfm2.rotary(jnp.concatenate([pad, k], 1), theta)
    far = jnp.einsum("bhd,bhd->bh", rq2[:, 12], rk2[:, 10])
    np.testing.assert_allclose(near, far, rtol=1e-4, atol=1e-4)


# -- three steps of the trainer against the reference ---------------------------------

SEEDS = (3400000011, 3400000012, 3400000013, 3400000014)


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
    # the limits hold something: a gradient number and a change number (the
    # median tensors': at this size a router's near-ties swing the worst)
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


def test_the_new_cell_rehearses_to_correct():
    """``benchmark/run.py --rehearse`` of the cell, traced, in a child
    process: correct, the counters read, no compilation in the window."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", "3400000021", "--seconds", "1", "--trace", "1",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    shape = json.loads(done.stdout.split(
        "REHEARSAL on the CPU, not a result: ")[1].splitlines()[0])
    assert shape["correct"] is True
    assert {"moe.gated_assignments_held", "moe.gated_load_max_over_mean",
            "step.live_gib", "amp.steps_skipped"} <= set(shape["metrics"])
    assert 'compared compilations_in_window: {"value": 0' in done.stderr
    assert "(benchmark/counts/lfm2.py)" in done.stdout


# -- the family's place in the benchmark (benchmark/tests/test_lfm2_family.py) --------

def _mirrored(name):
    path = ROOT / "benchmark" / "tests" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"mirrored_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_family = _mirrored("test_lfm2_family")


def test_the_family_is_found_by_files_and_the_harness_does_not_name_it():
    """The mirrored test with ONE assertion restated: there the family's
    per-layer entries are the LAST of ``BENCHMARK.json`` (true when PR 34
    wrote it; a later family appends after them); here they are one run of
    entries in the order PR 34 gave them. The file under ``benchmark/`` is
    the benchmark's and is not this repository's tests' to edit."""
    import re as _re
    from pathlib import Path

    from benchmark.harness import flops, roofline

    M = Manifest()
    config = M.config(CONFIG)
    family_builder, family_reference = runner.family(config)
    assert family_builder.REFERENCE == "lfm2"
    assert family_reference.__name__ == "benchmark.reference.lfm2"
    assert flops.counts(config).__name__ == "benchmark_counts_lfm2"
    text = Path(family_reference.__file__).read_text()
    assert not _re.search(r"^\s*(import|from)\s+apex_tpu", text, _re.M)
    for path in (ROOT / "benchmark" / "harness").glob("*.py"):
        code = "\n".join(line.split("#")[0]
                         for line in path.read_text().splitlines())
        assert not _re.search(r"""["'](lfm2|layer_types)["']""", code), path
    names = [m["name"] for m in M.doc["per_layer"]]
    mine = [m for m in M.doc["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(_family.NEW_METRICS)
    first = names.index(mine[0]["name"])
    assert M.doc["per_layer"][first:first + len(mine)] == mine  # one run
    for m in mine:
        assert m["moves"] == "tokens_per_s"
        assert (ROOT / "benchmark" / "metrics" / f"{m['name']}.py").exists()
        if m["name"].endswith("_roofline"):
            assert roofline.pattern_files(M, m["name"])
    assert {"step.mfu", "step.live_gib", "device.idle_share",
            "loop.host_ms_per_step", "amp.steps_skipped"} <= {
        m["name"] for m in M.per_layer(CELL)}


test_forward_flops_by_hand_at_the_rehearsal_size = \
    _family.test_forward_flops_by_hand_at_the_rehearsal_size
test_the_cell_counts_twenty_teraflop_a_step = \
    _family.test_the_cell_counts_twenty_teraflop_a_step
test_the_calls_by_hand = _family.test_the_calls_by_hand
test_a_call_at_the_least_time_reads_100_and_slower_reads_less = \
    _family.test_a_call_at_the_least_time_reads_100_and_slower_reads_less
test_a_recomputed_expert_event_is_credited_with_nothing = \
    _family.test_a_recomputed_expert_event_is_credited_with_nothing
