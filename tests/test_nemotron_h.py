"""The ``nemotron_h`` stack (``apex_tpu.models.nemotron_h``) held to the
benchmark's plain reference (``benchmark/reference/nemotron_h.py``, which
imports nothing of the program) at tiny widths on the CPU: each kind of
block and the whole stack in loss and gradients, and three optimizer
steps through amp O2 + FusedAdam + ``build_train_step`` against
``reference/train.py: run``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import profiler
from apex_tpu.models.nemotron_h import (NemotronHConfig,
                                        NemotronHLMHeadModel,
                                        keep_fp32_filter)
from benchmark import control
from benchmark.builders import nemotron_h as builder
from benchmark.harness import check, masks, runner
from benchmark.harness.manifest import Manifest
from benchmark.reference import nemotron_h as reference, train

CELL = "nemotron_twotower_30b_a3b.lm8192"


@pytest.fixture(scope="module")
def tiny():
    """The cell's own configuration and traffic at their rehearsal size."""
    manifest = Manifest()
    config = manifest.config(manifest.cell(CELL)["config"])
    return runner._apply_rehearsal(config, manifest.traffic(CELL))


def _sides(config, pattern, seq=40, rows=2, seed=0, **program):
    """(program loss fn of its params, reference loss fn of its weights,
    the leaf map, the seeded weights, ids) for a stack of ``pattern``."""
    config = {**config, "hybrid_override_pattern": pattern,
              "num_hidden_layers": len(pattern)}
    cfg = builder.model_config(config)
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32, **program})
    model = NemotronHLMHeadModel(cfg)
    leaf_map = builder.KindLeafMap(pattern)
    weights = reference.init_weights(config, jax.random.PRNGKey(seed))
    # off the symmetric start: unequal gains, a conv bias, a skip that
    # is not 1 (seeded weights leave them at 1, 0 and 1)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(weights))
    weights = {n: (w + 0.1 * jax.random.normal(k, w.shape)
                   if reference.keeps_float32(n) or n.endswith("conv_b")
                   else 8.0 * w)
               for k, (n, w) in zip(keys, sorted(weights.items()))}
    ids = jax.random.randint(jax.random.PRNGKey(seed + 2), (rows, seq), 0,
                             config["vocab_size"])

    def program_loss(params):
        return model.apply({"params": params}, ids, method="loss")[0]

    def reference_loss(w):
        return reference.loss(w, {"ids": ids}, 0, config, masks)

    return program_loss, reference_loss, leaf_map, weights


# seq 40 is no multiple of the rehearsal's chunk of 16
@pytest.mark.parametrize("fused", [False, True], ids=["composed", "fused"])
@pytest.mark.parametrize("pattern", ["M", "E", "*", "MEMEM*E"])
def test_loss_and_gradients_match_the_reference(tiny, pattern, fused):
    program_loss, reference_loss, leaf_map, weights = _sides(
        tiny[0], pattern, fused_kernels=fused)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program_loss)(
            leaf_map.to_program(weights))
        lr, gr = jax.value_and_grad(reference_loss)(weights)
    # float32 on both sides: what is left is summation order (the scan's
    # chunks, flash's tiles, the grouped matmul's rows)
    assert abs(float(lp) - float(lr)) < 2e-5 * abs(float(lr))
    got = leaf_map.to_reference(gp)
    assert set(got) == set(gr)
    for name in sorted(gr):
        want = np.asarray(gr[name], np.float64)
        scale = float(np.max(np.abs(want)))
        assert scale > 0, name                  # every tensor is reached
        assert float(np.max(np.abs(got[name] - want))) < 5e-4 * scale, name


def test_the_filter_keeps_what_the_reference_keeps(tiny):
    config = tiny[0]
    pattern = reference.pattern(config)
    leaf_map = builder.KindLeafMap(pattern)
    weights = reference.init_weights(config, jax.random.PRNGKey(0))
    kept = leaf_map.to_reference(jax.tree_util.tree_map_with_path(
        lambda path, x: float(keep_fp32_filter("/".join(
            str(p.key) for p in path))), leaf_map.to_program(weights)))
    for name, flags in kept.items():
        assert np.all(flags == float(reference.keeps_float32(name))), name
    # norm_f, a block norm per kind, the router, dt_bias, A_log, D, gate_norm
    assert sum(reference.keeps_float32(n) for n in weights) == 1 + 3 + 1 + 4


def test_counters_and_logits(tiny):
    config = tiny[0]
    cfg = builder.model_config({**config, "n_routed_experts": 16})
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32})
    model = NemotronHLMHeadModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)["params"]
    logits, counters = model.apply({"params": params}, ids)
    assert logits.shape == (2, 24, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    experts = cfg.pattern.count("E")
    # all 16 experts held: every assignment is computed here
    assert float(counters[profiler.MOE_ASSIGNMENTS_HELD]) == (
        experts * 2 * 24 * cfg.num_experts_per_tok)
    assert float(counters[profiler.MOE_TOKENS_DROPPED]) == 0.0
    assert float(counters[profiler.MOE_LOAD_MAX_OVER_MEAN]) >= 1.0
    loss, _ = model.apply({"params": params}, ids, method="loss")
    lse = jax.nn.logsumexp(logits[:, :-1], -1)
    picked = jnp.take_along_axis(logits[:, :-1], ids[:, 1:, None], -1)[..., 0]
    assert float(loss) == pytest.approx(float(jnp.mean(lse - picked)),
                                        rel=1e-6)
    with pytest.raises(ValueError, match="letters M, E"):
        NemotronHConfig(pattern="MXE")


def test_layer_scopes_are_in_the_step(tiny):
    """Every layer scope of the vocabulary is on some op of the lowered
    train step, under ``train_fwd_bwd``."""
    import re

    config, traffic = tiny
    built = builder.build(config, traffic, reference, seed=0,
                          key=runner.weights_key(0))
    batch = {"ids": jnp.zeros((1, traffic["rows_per_chip"], traffic["seq"]),
                              jnp.int32)}
    text = built.step.lower(built.state, batch).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    # the layer scopes of this family (the others are ``models/lfm2.py``'s)
    mine = tuple(s for s in profiler.LAYER_SCOPES
                 if s.startswith(("ssm_", "moe_", "gqa_")))
    assert len(mine) == 10
    for scope in mine + (profiler.LM_HEAD, profiler.LM_LOSS):
        under = [p for p in paths
                 if re.search(r"(^|[/(])" + scope + r"([/)]|$)", p)]
        assert under, scope
    for name in profiler.LAYER_SCOPES + profiler.STEP_COUNTERS:
        assert re.fullmatch(r"[a-z][a-z0-9_]*", name), name
        assert name not in profiler.SCOPES      # no phase of their own
    for name in profiler.LAYER_SCOPES:         # the docstring lists each
        assert re.search(r"^" + name + r"\s", profiler.__doc__, re.M), name


# PR 30's rehearsal seeds: three of the four caught a build that kept the
# expert rows under recomputation without the routing that ordered them
SEEDS = (3000000011, 3000000012, 3000000013, 3000000014)


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
    # the limits hold something: a gradient number and a change number
    assert {"grad_median_leaf", "change_worst_leaf"} <= {
        n for n, v in limits.items() if v is not None}


def test_a_run_computed_in_float8_fails_the_same_limits(first_steps):
    """The rehearsal's limits sit between the bf16 program's reading and
    the reading of the reference with every matmul rounded through
    float8_e4m3: that run is NOT correct."""
    _, plain, limits = first_steps
    verdict = check.compare(plain(SEEDS[0], precision="fp8"),
                            plain(SEEDS[0]), limits)
    assert not verdict["correct"], verdict["numbers"]
