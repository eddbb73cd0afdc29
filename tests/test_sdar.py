"""The ``sdar`` stack and its block-diffusion objective
(``apex_tpu.models.sdar``) held to the benchmark's plain reference
(``benchmark/reference/sdar.py``, which imports nothing of the program) at
the rehearsal's tiny widths on the CPU: loss and every tensor's gradient,
fused and composed; the two sides' noise masks bit for bit; three optimizer
steps through amp O2 + FusedAdam + ``build_train_step`` against
``reference/train.py: run``, and the float8 control failing the same
limits; counters, shapes, the parameter count, the configuration file's
cut, the scopes, and the cell's rehearsal."""

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
from apex_tpu.models import sdar
from apex_tpu.models.sdar import SdarConfig, SdarLMHeadModel, keep_fp32_filter
from benchmark import control
from benchmark.builders import sdar as builder
from benchmark.harness import check, masks, runner
from benchmark.harness.manifest import ROOT, Manifest
from benchmark.reference import sdar as reference, train

CONFIG, CELL = "sdar_30b_a3b_chat", "sdar_30b_a3b_chat.bd8192"


@pytest.fixture(scope="module")
def tiny():
    """The cell's own configuration and traffic at their rehearsal size."""
    manifest = Manifest()
    config = manifest.config(manifest.cell(CELL)["config"])
    return runner._apply_rehearsal(config, manifest.traffic(CELL))


def _float32(config, **program):
    cfg = builder.model_config(config)
    return cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32, **program})


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "composed"])
def test_loss_and_gradients_match_the_reference(tiny, fused):
    """Two layers: a whole one (both copies' queries) and the LAST (the
    noised copy's queries alone), against a reference that computes both
    whole."""
    layers = 2
    config = {**tiny[0], "num_hidden_layers": layers}
    model = SdarLMHeadModel(_float32(config, fused_kernels=fused))
    leaf_map = builder.leaf_map(layers)
    weights = reference.init_weights(config, jax.random.PRNGKey(0))
    # off the symmetric start: unequal gains of order one, a router whose
    # scores are apart
    keys = jax.random.split(jax.random.PRNGKey(1), len(weights))
    weights = {n: (w + 0.1 * jax.random.normal(k, w.shape)
                   if reference.keeps_float32(n) else 8.0 * w)
               for k, (n, w) in zip(keys, sorted(weights.items()))}
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0,
                             config["vocab_size"])
    seed = jnp.int32(36000 + layers)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: model.apply(
            {"params": p}, ids, seed, method="loss")[0])(
                leaf_map.to_program(weights))
        lr, gr = jax.value_and_grad(lambda w: reference.loss(
            w, {"ids": ids}, seed, config, masks))(weights)
    # float32 on both sides: what is left is summation order (flash's
    # tiles, the grouped matmul's rows)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    got = leaf_map.to_reference(gp)
    assert set(got) == set(gr)
    for name in sorted(gr):
        want = np.asarray(gr[name], np.float64)
        scale = float(np.max(np.abs(want)))
        assert scale > 0, name                  # every tensor is reached
        assert float(np.max(np.abs(got[name] - want))) < 1e-4 * scale, name


@pytest.mark.parametrize("seed", [0, 7, 3600000001 % (2 ** 31 - 1),
                                  2 ** 31 - 2])
def test_both_sides_draw_the_same_noise(seed):
    rows, L, g, floor = 3, 64, 4, 1e-3
    ids = jnp.zeros((rows, L), jnp.int32)
    masked, p = jax.jit(functools.partial(
        sdar.diffusion_noise, block_length=g, floor=floor))(ids,
                                                            jnp.int32(seed))
    want_masked, want_p = jax.jit(functools.partial(
        reference.noise, rows, L, g=g, floor=floor))(jnp.int32(seed))
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(want_masked))
    # compiled alike the two sides' p are equal too; the last bit is the
    # compiler's (a fused multiply-add or not), hence not held to the bit
    np.testing.assert_allclose(np.asarray(p), np.asarray(want_p), rtol=3e-7)
    # one t a block, p inside [floor, 1), rows apart, about half masked
    p = np.asarray(p).reshape(rows, L // g, g)
    assert np.all(p == p[..., :1]) and floor <= p.min() and p.max() < 1.0
    assert not np.array_equal(p[0], p[1])
    assert 0.2 < np.asarray(masked).mean() < 0.8


def test_the_filter_keeps_what_the_reference_keeps(tiny):
    config = tiny[0]
    leaf_map = builder.leaf_map(config["num_hidden_layers"])
    weights = reference.init_weights(config, jax.random.PRNGKey(0))
    kept = leaf_map.to_reference(jax.tree_util.tree_map_with_path(
        lambda path, x: float(keep_fp32_filter("/".join(
            str(p.key) for p in path))), leaf_map.to_program(weights)))
    for name, flags in kept.items():
        assert np.all(flags == float(reference.keeps_float32(name))), name
    # the final norm, a layer's two, q's and k's gains, the router
    assert sum(reference.keeps_float32(n) for n in weights) == 6


def test_counters_logits_and_the_parameter_count(tiny):
    config = tiny[0]
    cfg = _float32({**config, "num_experts": 16}, fused_kernels=False)
    model = SdarLMHeadModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids, 3)["params"]
    logits, counters = model.apply({"params": params}, ids, 3)
    # the noised copy's positions alone reach the head
    assert logits.shape == (2, 24, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    # all 16 experts held: every assignment is computed here; every layer
    # but the last routes both copies, the last the noised copy alone
    n = cfg.num_hidden_layers
    assert float(counters[profiler.MOE_ASSIGNMENTS_HELD]) == (
        (2 * (n - 1) + 1) * 2 * 24 * cfg.num_experts_per_tok)
    assert float(counters[profiler.MOE_TOKENS_DROPPED]) == 0.0
    assert float(counters[profiler.MOE_LOAD_MAX_OVER_MEAN]) >= 1.0
    masked, p = sdar.diffusion_noise(ids, 3, cfg.block_length,
                                     cfg.noise.floor)
    assert float(counters[profiler.DIFFUSION_MASKED_TOKENS]) == float(
        jnp.sum(masked)) > 0
    assert profiler.DIFFUSION_MASKED_TOKENS in profiler.DIFFUSION_COUNTERS
    # the loss is the masked positions' cross-entropy over p, no shift
    loss, _ = model.apply({"params": params}, ids, 3, method="loss")
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, ids[..., None], -1)[..., 0]
    want = jnp.sum(jnp.where(masked, (lse - picked) / p, 0.0)) / ids.size
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    # a masked position reads MASK whatever its id; an id equal to MASK
    # that the draw left alone is an ordinary token (same loss either way
    # only if what is masked goes by the draw)
    assert cfg.mask_id == config["mask_token_id"] == cfg.vocab_size - 1
    with pytest.raises(ValueError, match="multiple"):
        SdarConfig.tiny(num_attention_heads=3)
    with pytest.raises(ValueError, match="block_length"):
        model.apply({"params": params}, ids[:, :23], 3)
    # the cell's own size, from shapes: the configuration file's arithmetic
    full = SdarLMHeadModel(builder.model_config(Manifest().config(CONFIG)))
    shapes = jax.eval_shape(lambda k: full.init(k, ids, 0)["params"],
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(shapes)) == 456_346_624


def test_the_configuration_file_states_its_cut():
    M = Manifest()
    entry, c = M._entry("configs", CONFIG), M.config(CONFIG)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    catalog = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs") else []
    for row in catalog:
        if row["source_url"] == entry["source"]:
            for key, value in row["config"].items():
                if key not in c["reduced"]:
                    assert c[key] == value, key
    # every width is the published one
    assert (c["hidden_size"], c["moe_intermediate_size"], c["head_dim"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["num_experts_per_tok"]) == (2048, 768, 128, 32, 4, 8)
    assert (c["num_hidden_layers"], c["num_experts"],
            c["vocab_size"]) == (4, 16, 18992)
    d = c["deployment"]
    assert (d["chips_sharing_a_layer"], d["expert_parallel"],
            d["vocab_parallel"], d["num_experts_published"],
            d["vocab_size_published"],
            d["num_hidden_layers_published"]) == (8, 8, 8, 128, 151936, 48)
    assert (c["block_length"], c["mask_token_id"]) == (4, 18991)
    assert c["noise"] == {"schedule": "linear", "floor": 0.001,
                          "t_drawn_per": "block", "loss_weight": "1/p"}
    for key in ("objective", "block_length", "noise schedule",
                "noise formula", "loss", "mask_token_id", "optimizer",
                "weights", "remat"):
        assert key in c["assumed"], key
    assert c["departures"] == [] and "none known" in c["departures_note"]
    cell = M.cell(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "1/8" in cell["why"] and "4 layers" in cell["why"]
    t = M.traffic(CELL)
    assert (t["seq"], t["rows_per_chip"], t["feed"], t["corpus_rows"],
            t["prefetch"]) == (8192, 1, "loader", 256, 2)


def test_layer_scopes_are_in_the_step(tiny):
    """Every layer scope this family emits is on some op of the lowered
    train step, under ``train_fwd_bwd``; the docstring table lists each."""
    config, traffic = tiny
    built = builder.build(config, traffic, reference, seed=0,
                          key=runner.weights_key(0))
    batch = {"ids": jnp.zeros((1, traffic["rows_per_chip"], traffic["seq"]),
                              jnp.int32),
             "seed": jnp.ones((1, 1), jnp.int32)}
    text = built.step.lower(built.state, batch).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    mine = (profiler.DIFFUSION_NOISE, profiler.BLOCKDIFF_ATTENTION,
            profiler.DIFFUSION_LOSS, profiler.ATTN_QK_NORM,
            profiler.ATTN_ROPE, profiler.MOE_ROUTER, profiler.MOE_DISPATCH,
            profiler.MOE_EXPERTS, profiler.MOE_COMBINE, profiler.LM_HEAD,
            profiler.LM_LOSS)

    def under(scope, path):
        return re.search(r"(^|[/(])" + scope + r"([/)]|$)", path)

    for scope in mine:
        assert any(under(scope, p) for p in paths), scope
    # the loss's scope sits inside ``lm_loss``, which the phase table knows
    assert all(under(profiler.LM_LOSS, p) for p in paths
               if under(profiler.DIFFUSION_LOSS, p))
    for name in mine[:3]:
        assert name in profiler.LAYER_SCOPES and name not in profiler.SCOPES
        assert re.search(r"^" + name + r"\s", profiler.__doc__, re.M), name
    # the flax paths the benchmark's readers go by
    for part in ("self_attn", "expert_ffn"):
        assert any(under(part, p) for p in paths), part


# -- three steps of the trainer against the reference ---------------------------------

SEEDS = (3600000011, 3600000012, 3600000013)


@pytest.fixture(scope="module")
def first_steps(tiny):
    """``seed -> (program, reference)``: three steps of the program (amp O2
    + FusedAdam + build_train_step + TrainLoop, as the cell builds them,
    compiled once) and of the plain reference, on the same seeded weights,
    batches and noise; beside it the reference's runner and the limits."""
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


def test_the_new_cell_rehearses_to_correct():
    """``benchmark/run.py --rehearse`` of the cell, traced, in a child
    process: correct, the counters read, no compilation in the window."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", "3600000021", "--seconds", "1", "--trace", "1",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    shape = json.loads(done.stdout.split(
        "REHEARSAL on the CPU, not a result: ")[1].splitlines()[0])
    assert shape["correct"] is True
    assert {"diffusion.masked_tokens", "moe.softmax_assignments_held",
            "moe.softmax_load_max_over_mean", "step.live_gib",
            "amp.steps_skipped"} <= set(shape["metrics"])
    assert 'compared compilations_in_window: {"value": 0' in done.stderr
    assert "(benchmark/counts/sdar.py)" in done.stdout
