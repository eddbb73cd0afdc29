"""apex_tpu.train, sharded: the 3D-parallel single-dispatch step.

The GSPMD ``build_train_step(mesh=...)`` promotion (ISSUE 20): scanned
accumulation + amp overflow skip + ZeRO flat-shard optimizer update +
tensor-parallel activations, compiled into ONE donated dispatch on the
serving mesh. The certification ladder mirrors PR 4's fused-vs-loop
contract: a (1, 1) mesh is BIT-identical to the meshless step across
the amp x optimizer x accum matrix; real mesh shapes hold the
drift-bounded tier (the test_train_step.py SPMD concession) with the
compile count pinned at one; and the per-mesh collective contract is
certified from AOT-lowered HLO, never from wall-clock.
"""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import flax.linen as nn

import apex_tpu.amp as amp
from apex_tpu.contrib.optimizers import (
    DistributedFusedAdam,
    DistributedFusedLAMB,
)
from apex_tpu.models.gpt import GPTConfig, GPTLMHeadModel, lm_loss
from apex_tpu.optimizers import FusedAdam, FusedLAMB
from apex_tpu.serving.mesh import (
    build_mesh,
    train_expected_collectives,
)
from apex_tpu.train import (
    NonFiniteLossError,
    WatchdogConfig,
    build_train_step,
)
from apex_tpu.utils.checkpoint import (
    load_train_state,
    save_train_state,
    state_mesh_shape,
)
from apex_tpu.utils.faults import FaultPlan, FaultSpec
from apex_tpu.utils.hlo_audit import collective_stats


# ---------------------------------------------------------------------------
# fixtures: a tiny GPT (the TP-decomposed tree) and a small dense net
# ---------------------------------------------------------------------------


ACCUM, B, S = 2, 4, 16
LR, STEPS = 1e-3, 3     # the GPT trajectories of the sharded certs


@pytest.fixture(scope="module")
def gpt_setup():
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (ACCUM, B, S), 0, cfg.vocab_size))
    params = jax.device_get(
        model.init(jax.random.PRNGKey(0), jnp.asarray(tokens[0]))["params"])

    def loss_fn(p, mb):
        return lm_loss(model.apply({"params": p}, mb), mb)

    return cfg, loss_fn, params, tokens


def _gpt_run(gpt_setup, optimizer, mesh_shape, steps=STEPS,
             amp_handle=None):
    cfg, loss_fn, params, tokens = gpt_setup
    kw = dict(amp=amp_handle, accum_steps=ACCUM)
    if mesh_shape is not None:
        kw.update(mesh=build_mesh(mesh_shape), num_heads=cfg.num_heads)
    ts = build_train_step(loss_fn, optimizer, **kw)
    state = ts.init(jax.tree.map(jnp.asarray, params))
    losses = []
    for _ in range(steps):
        state, metrics = ts.step(state, jnp.asarray(tokens))
        losses.append(float(jax.device_get(metrics["loss"])))
    return ts, state, losses


class _Net(nn.Module):
    """Dense net WITH a norm layer so the O2 arm exercises the mixed
    fp32/bf16 tree (the test_train_step.py Net, shrunk)."""

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(32, param_dtype=jnp.float32)(x)
        x = nn.LayerNorm(param_dtype=jnp.float32)(x)
        return nn.Dense(4, param_dtype=jnp.float32)(nn.relu(x))


@pytest.fixture(scope="module")
def net_setup():
    model = _Net()
    rng = np.random.RandomState(0)
    xs = jnp.asarray(rng.randn(4, 8, 16).astype("f4"))
    ys = jnp.asarray(rng.randint(0, 4, (4, 8)))
    params = jax.device_get(
        model.init(jax.random.PRNGKey(1), xs[0])["params"])

    def loss_fn(p, mb):
        x, y = mb
        logits = model.apply({"params": p}, x).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, y[:, None], 1))

    return loss_fn, params, (xs, ys)


def _trees_bit_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _trees_certified(a, b, atol=1e-5):
    """The sharded drift-bounded tier (test_train_step.py
    ``_assert_certified_equal`` rationale: XLA:CPU rounds fp32 SPMD
    arithmetic differently per partitioning; a composition bug is off
    by 1e-1..65536x, not 1e-3)."""
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-3, atol=atol)


# ---------------------------------------------------------------------------
# (1, 1) bit-identity matrix: amp x optimizer x accum
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("accum", [1, 4])
@pytest.mark.parametrize("opt_cls", [FusedAdam, DistributedFusedAdam])
@pytest.mark.parametrize("opt_level", ["O1", "O2"])
def test_mesh11_bit_identity_matrix(net_setup, opt_level, opt_cls, accum):
    """A (1, 1) mesh must be a spelling of the meshless step, not a
    different program: params, optimizer state, and scaler state stay
    BIT-identical through the full amp composition, and each side
    compiles exactly once."""
    loss_fn, params, (xs, ys) = net_setup
    xs, ys = xs[:accum], ys[:accum]

    def make(mesh_shape):
        opt = (opt_cls(lr=1e-2, flat_mode="global")
               if opt_cls is DistributedFusedAdam else opt_cls(lr=1e-2))
        p, opt, handle = amp.initialize(
            jax.tree.map(jnp.asarray, params), opt,
            opt_level=opt_level, verbosity=0)
        kw = dict(amp=handle, accum_steps=accum)
        if mesh_shape is not None:
            kw["mesh"] = build_mesh(mesh_shape)
        ts = build_train_step(loss_fn, opt, **kw)
        return ts, ts.init(p)

    ts0, s0 = make(None)
    ts1, s1 = make((1, 1))
    for _ in range(3):
        s0, m0 = ts0.step(s0, (xs, ys))
        s1, m1 = ts1.step(s1, (xs, ys))
    _trees_bit_equal(s0.params, s1.params)
    _trees_bit_equal(s0.opt_state, s1.opt_state)
    _trees_bit_equal(s0.scaler_state, s1.scaler_state)
    assert float(jax.device_get(m0["loss"])) == \
        float(jax.device_get(m1["loss"]))
    assert ts1._jitted._cache_size() == 1


# ---------------------------------------------------------------------------
# sharded certs: real mesh shapes vs the meshless step
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gpt_meshless_ref(gpt_setup):
    """Meshless 3-step trajectories, one per optimizer family."""
    out = {}
    for name, opt in [("adam", FusedAdam(lr=LR)),
                      ("zero", DistributedFusedAdam(lr=LR,
                                                    flat_mode="global"))]:
        _, state, losses = _gpt_run(gpt_setup, opt, None)
        out[name] = (jax.device_get(state.params), losses)
    return out


@pytest.mark.parametrize("mesh_shape", [(2, 1), (1, 2), (2, 2)])
@pytest.mark.parametrize("opt_name", ["adam", "zero"])
def test_sharded_cert_and_collective_contract(gpt_setup, gpt_meshless_ref,
                                              opt_name, mesh_shape):
    """Every real mesh shape: drift-bounded agreement with the meshless
    trajectory, ONE compile for 3 dispatched steps, and the AOT audit
    pins the per-mesh collective contract (ZeRO round trip for the
    flat optimizer, >= 2*num_layers all-reduces on the TP leg, no
    all-to-all of real data) plus a positive donation-alias count."""
    cfg, _, _, tokens = gpt_setup
    opt = (FusedAdam(lr=LR) if opt_name == "adam"
           else DistributedFusedAdam(lr=LR, flat_mode="global"))
    ts, state, losses = _gpt_run(gpt_setup, opt, mesh_shape)
    ref_params, ref_losses = gpt_meshless_ref[opt_name]
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
    # the floor is relative to what Adam can move a leaf at all,
    # ``lr * steps``: the update is the gradient over its own running
    # magnitude, so in a leaf whose true gradient is zero (the key
    # bias: softmax ignores a shift of every score) round-off is the
    # whole signal and its sign is the partitioning's. Those two leaves
    # sit at ~1e-5 and differ by 1.4e-5 at mesh (2, 2); every other
    # bias has moved 3e-3, so a wrong composition misses a twentieth
    # of that reach by far
    _trees_certified(state.params, ref_params, atol=0.05 * LR * STEPS)
    assert ts._jitted._cache_size() == 1
    audit = ts.audit_collectives(state, jnp.asarray(tokens))
    assert audit["alias"]["pairs"] >= audit["sharded_leaves"] > 0
    table = {k: v["ops"] for k, v in audit["collectives"].items()
             if k not in ("total", "degenerate")}
    assert table["all-to-all"] == 0 and table["collective-permute"] == 0
    if mesh_shape[1] > 1:
        # the TP leg: one all-reduce per block matmul pair, forward and
        # backward — the >= 2*num_layers floor of the contract
        assert table["all-reduce"] >= 2 * cfg.num_layers
    if mesh_shape[0] > 1 and opt_name == "zero":
        # the ZeRO leg, either HLO spelling
        assert (table["reduce-scatter"] >= 1
                or table["all-reduce"] >= 1)
        assert table["all-gather"] >= 1
    assert audit["contract"] == train_expected_collectives(
        mesh_shape, num_layers=cfg.num_layers, zero=(opt_name == "zero"))


def test_mesh11_audit_is_collective_free(gpt_setup):
    """The (1, 1) contract is exact: zero collective ops in the whole
    compiled global step."""
    ts, state, _ = _gpt_run(gpt_setup, FusedAdam(lr=1e-3), (1, 1),
                            steps=1)
    cfg, _, _, tokens = gpt_setup
    audit = ts.audit_collectives(state, jnp.asarray(tokens))
    assert audit["contract"] == {"exact_total_ops": 0}
    assert audit["collectives"]["total"]["ops"] == 0


def test_audit_requires_gspmd_path(net_setup):
    loss_fn, params, _ = net_setup
    ts = build_train_step(loss_fn, FusedAdam(lr=1e-2), accum_steps=1)
    state = ts.init(jax.tree.map(jnp.asarray, params))
    with pytest.raises(ValueError, match="mesh"):
        ts.audit_collectives(state, None)


# ---------------------------------------------------------------------------
# satellite 1: mesh-geometry validation with named-knob errors
# ---------------------------------------------------------------------------


def test_geometry_model_axis_must_divide_heads(net_setup, gpt_setup):
    cfg, loss_fn, _, _ = gpt_setup
    with pytest.raises(ValueError, match="num_heads"):
        build_train_step(loss_fn, FusedAdam(lr=1e-3), accum_steps=ACCUM,
                         mesh=build_mesh((1, 8)), num_heads=cfg.num_heads)


def test_geometry_axis_names_must_match_serving_mesh(net_setup):
    loss_fn, _, _ = net_setup
    bad = jax.make_mesh((2, 1), ("dp", "tp"))
    with pytest.raises(ValueError, match="batch.*model|model.*batch"):
        build_train_step(loss_fn, FusedAdam(lr=1e-2), accum_steps=1,
                         mesh=bad)


def test_geometry_batch_axis_must_divide_batch_dim(net_setup):
    """B=8 microbatches cannot shard over an 8-way batch axis when a
    leaf's batch dim is smaller — the error names the offending leaf
    dim and the knob."""
    loss_fn, params, (xs, ys) = net_setup
    ts = build_train_step(loss_fn, FusedAdam(lr=1e-2), accum_steps=4,
                          mesh=build_mesh((8, 1)))
    state = ts.init(jax.tree.map(jnp.asarray, params))
    bad = (xs[:, :6], ys[:, :6])  # batch dim 6, batch axis 8
    with pytest.raises(ValueError, match="batch"):
        ts.step(state, bad)


def test_geometry_zero_group_size_must_match_batch_axis(net_setup):
    loss_fn, _, _ = net_setup
    opt = DistributedFusedAdam(lr=1e-2, flat_mode="global", group_size=3)
    with pytest.raises(ValueError, match="group_size"):
        build_train_step(loss_fn, opt, accum_steps=1,
                         mesh=build_mesh((2, 1)))


# ---------------------------------------------------------------------------
# satellite 2: flat-buffer padding counted once and exposed
# ---------------------------------------------------------------------------


def test_flat_pad_stats_surface():
    opt = DistributedFusedAdam(lr=1e-2, flat_mode="global")
    with pytest.raises(ValueError, match="stats"):
        opt.stats()
    params = {"w": jnp.ones((5, 7)), "b": jnp.ones((3,))}
    opt.init(params)
    st = opt.stats()
    assert st["flat_total_elems"] == 5 * 7 + 3
    assert st["flat_padded_elems"] == \
        st["flat_total_elems"] + st["flat_pad_elems"]
    assert st["flat_padded_elems"] % 128 == 0
    assert st["flat_world"] == 1
    assert st["flat_shard_elems"] * st["flat_world"] == \
        st["flat_padded_elems"]
    assert st["opt_state_bytes_per_shard"] == st["flat_shard_elems"] * 12
    # counted once: the meta is cached per (world, tree) key
    assert opt.stats() == st


def test_flat_pad_stats_sharded(gpt_setup, net_setup):
    loss_fn, params, _ = net_setup
    opt = DistributedFusedAdam(lr=1e-2, flat_mode="global")
    ts = build_train_step(loss_fn, opt, accum_steps=1,
                          mesh=build_mesh((2, 1)))
    ts.init(jax.tree.map(jnp.asarray, params))
    st = ts._core.optimizer.stats()
    assert st["flat_world"] == 2
    assert st["flat_shard_elems"] * 2 == st["flat_padded_elems"]
    # the ZeRO memory story: a rank of the 2-way batch axis holds less
    # master / m / v than the same tree on a batch axis of one
    one = DistributedFusedAdam(lr=1e-2, flat_mode="global")
    one.init(jax.tree.map(jnp.asarray, params))
    assert one.stats()["flat_world"] == 1
    assert (st["opt_state_bytes_per_shard"]
            < one.stats()["opt_state_bytes_per_shard"])


# ---------------------------------------------------------------------------
# checkpoint/resume under sharding
# ---------------------------------------------------------------------------


def test_sharded_checkpoint_resume_bit_identical(gpt_setup, tmp_path):
    """Save at step 2 on a (2, 1) mesh, resume onto an EQUAL mesh:
    steps 3-4 of the resumed run are bit-identical to the
    uninterrupted one, and the resumed step re-dispatches the compiled
    program (no retrace). A (1, 2) template is REFUSED by the mesh
    fingerprint; a meshless template still loads (the payload is
    host-replicated, topology-free)."""
    cfg, loss_fn, params, tokens = gpt_setup

    def make(shape):
        kw = dict(accum_steps=ACCUM)
        if shape is not None:
            kw.update(mesh=build_mesh(shape), num_heads=cfg.num_heads)
        ts = build_train_step(loss_fn, FusedAdam(lr=1e-3), **kw)
        return ts, ts.init(jax.tree.map(jnp.asarray, params))

    ts, state = make((2, 1))
    assert state_mesh_shape(state) == [["batch", 2], ["model", 1]]
    for _ in range(2):
        state, _ = ts.step(state, jnp.asarray(tokens))
    save_train_state(str(tmp_path), state)
    ref = state
    for _ in range(2):
        ref, _ = ts.step(ref, jnp.asarray(tokens))

    ts2, tmpl = make((2, 1))
    resumed, step = load_train_state(str(tmp_path), tmpl)
    assert step == 2
    for _ in range(2):
        resumed, _ = ts2.step(resumed, jnp.asarray(tokens))
    _trees_bit_equal(ref.params, resumed.params)
    _trees_bit_equal(ref.opt_state, resumed.opt_state)
    assert ts2._jitted._cache_size() == 1

    ts3, tmpl3 = make((1, 2))
    with pytest.raises(ValueError, match="mesh"):
        load_train_state(str(tmp_path), tmpl3)

    _, tmpl4 = make(None)
    st4, step4 = load_train_state(str(tmp_path), tmpl4)
    assert step4 == 2 and state_mesh_shape(st4) is None


# ---------------------------------------------------------------------------
# watchdog rescale under sharding
# ---------------------------------------------------------------------------


def test_watchdog_rescale_survives_sharding(net_setup):
    """The watchdog's host-side loss-scale halving must re-commit the
    replacement scalar onto the mesh — an uncommitted leaf would make
    the next dispatch retrace (and a donated retrace recompiles the
    whole global step)."""
    from apex_tpu.amp.scaler import LossScaler

    loss_fn, params, (xs, ys) = net_setup
    ts = build_train_step(loss_fn, FusedAdam(lr=1e-2),
                          amp=LossScaler(), accum_steps=1,
                          mesh=build_mesh((2, 1)))
    loop = ts.loop(
        ts.init(jax.tree.map(jnp.asarray, params)),
        faults=FaultPlan([FaultSpec(site="train_step", kind="nan",
                                    every=1)]),
        watchdog=WatchdogConfig(skip_steps=1, rescale_steps=2,
                                min_scale=1.0))
    scale0 = float(jax.device_get(loop.state.scaler_state.loss_scale))
    batches = [(xs[:1], ys[:1])] * 8
    with pytest.raises(NonFiniteLossError):
        loop.run(batches)
    s = loop.stats()
    assert s["watchdog_rescales"] == 2
    scale1 = float(jax.device_get(loop.state.scaler_state.loss_scale))
    assert scale1 == scale0 / 4
    # the rebuilt scalar landed back on the mesh, and the program
    # never retraced through the rescues
    sharding = loop.state.scaler_state.loss_scale.sharding
    assert getattr(sharding, "mesh", None) is not None
    assert ts._jitted._cache_size() == 1


# ---------------------------------------------------------------------------
# ZeRO LAMB on the global path
# ---------------------------------------------------------------------------


def test_lamb_global_smoke(net_setup):
    """DistributedFusedLAMB's flat_mode="global" world-of-1 must track
    the per-leaf FusedLAMB trajectory (same math, flat storage)."""
    loss_fn, params, (xs, ys) = net_setup
    runs = {}
    for name, opt in [("ref", FusedLAMB(lr=1e-2)),
                      ("flat", DistributedFusedLAMB(lr=1e-2,
                                                    flat_mode="global"))]:
        ts = build_train_step(loss_fn, opt, accum_steps=2)
        state = ts.init(jax.tree.map(jnp.asarray, params))
        for _ in range(3):
            state, _ = ts.step(state, (xs[:2], ys[:2]))
        runs[name] = jax.device_get(state.params)
    _trees_certified(runs["flat"], runs["ref"])


# ---------------------------------------------------------------------------
# hlo_audit: degenerate-collective classification (unit)
# ---------------------------------------------------------------------------


_SYNTH_HLO = """
  %broadcast.1 = f32[1,32,32]{2,1,0} broadcast(f32[] %constant.9), dimensions={}
  %all-to-all.1 = (f32[1,32,32]{2,1,0}, f32[1,32,32]{2,1,0}) all-to-all(f32[1,32,32]{2,1,0} %broadcast.1, f32[1,32,32]{2,1,0} %broadcast.1), channel_id=7
  %all-reduce.1 = f32[64,64]{1,0} all-reduce(f32[64,64]{1,0} %add.5), channel_id=8
"""


def test_collective_stats_degenerate_classification():
    """An all-to-all whose every operand is a scalar broadcast (the
    CSE-merged constant artifact) is excluded only under
    ``exclude_degenerate=True`` — and a real-data collective never
    is."""
    raw = collective_stats(_SYNTH_HLO)
    assert raw["all-to-all"]["ops"] == 1
    assert raw["all-reduce"]["ops"] == 1
    assert "degenerate" not in raw
    strict = collective_stats(_SYNTH_HLO, exclude_degenerate=True)
    assert strict["all-to-all"]["ops"] == 0
    assert strict["degenerate"]["ops"] == 1
    assert strict["all-reduce"]["ops"] == 1
    assert strict["total"]["ops"] == 1


def test_train_expected_collectives_table():
    assert train_expected_collectives((1, 1)) == {"exact_total_ops": 0}
    tp = train_expected_collectives((1, 2), num_layers=2)
    assert tp["min_ops"]["all-reduce"] == 4
    assert "all-to-all" in tp["forbidden"]
    z = train_expected_collectives((2, 2), num_layers=2, zero=True)
    assert z["min_ops"]["reduce-scatter"] == 1
    assert z["alt_min_ops"]["all-gather"] >= 1
    assert "all-to-all" in z["forbidden"]
