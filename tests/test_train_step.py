"""apex_tpu.train: the fused single-dispatch train step.

The certification contract (ISSUE 5, the greedy analog of the serving
cross-K certification): the fused scanned-accumulation step must be
BIT-IDENTICAL to the hand-wired per-microbatch dispatch loop it
replaces — across amp opt levels, DDP flat-buffer modes, optimizers,
and through an overflow-skip step mid-run — and the compiled program
must POSITIVELY show donated buffers aliasing (XLA drops donation with
only a warning, so absence-of-error proves nothing).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import flax.linen as nn

import apex_tpu.amp as amp
from apex_tpu.optimizers import FusedAdam, FusedLAMB
from apex_tpu.optimizers._base import FusedOptimizer
from apex_tpu.parallel import DistributedDataParallel
from apex_tpu.train import (
    TrainLoop,
    build_reference_loop,
    build_train_step,
)
from apex_tpu.utils.hlo_audit import input_output_alias_stats


class Net(nn.Module):
    """Small net WITH a norm-named layer so O2's keep_batchnorm_fp32
    path exercises a mixed fp32/bf16 param tree."""

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(32, param_dtype=jnp.float32)(x)
        x = nn.LayerNorm(param_dtype=jnp.float32)(x)
        x = nn.relu(x)
        return nn.Dense(4, param_dtype=jnp.float32)(x)


def _data(accum, batch, feat=16, seed=0):
    rng = np.random.RandomState(seed)
    xs = jnp.asarray(rng.randn(accum, batch, feat).astype("f4"))
    ys = jnp.asarray(rng.randint(0, 4, (accum, batch)))
    return xs, ys


def _loss_fn(model):
    def loss_fn(p, mb):
        x, y = mb
        logits = model.apply({"params": p}, x).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, y[:, None], 1))

    return loss_fn


def _setup(opt_level, optimizer, seed=0):
    model = Net()
    xs, ys = _data(4, 8, seed=seed)
    params = model.init(jax.random.PRNGKey(1), xs[0])["params"]
    params, opt, handle = amp.initialize(
        params, optimizer, opt_level=opt_level, verbosity=0)
    return model, params, opt, handle, (xs, ys)


def _trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


# ---------------------------------------------------------------------------
# fused vs hand-wired reference: single device
# ---------------------------------------------------------------------------


def test_fused_matches_reference_single_device():
    model, p0, opt, handle, batch = _setup("O1", FusedAdam(lr=1e-2))
    loss_fn = _loss_fn(model)
    ts = build_train_step(loss_fn, opt, amp=handle, accum_steps=4)
    ref = build_reference_loop(loss_fn, opt, amp=handle, accum_steps=4)
    sA, sB = ts.init(_copy(p0)), ref.init(_copy(p0))
    for _ in range(6):
        sA, mA = ts.step(sA, batch)
        sB, mB = ref.step(sB, batch)
    assert _trees_equal(sA.params, sB.params)
    assert _trees_equal(sA.opt_state, sB.opt_state)
    assert _trees_equal(sA.scaler_state, sB.scaler_state)
    # metrics contract: device scalars with the documented keys
    for key in ("loss", "loss_scale", "skipped", "steps_skipped", "step"):
        assert key in mA, key
        assert np.asarray(mA[key]).ndim == 0
    assert int(np.asarray(mA["step"])) == 6
    assert float(np.asarray(mA["loss"])) == pytest.approx(
        float(np.asarray(mB["loss"])))


def test_accum_steps_one_matches_reference():
    model, p0, opt, handle, (xs, ys) = _setup("O1", FusedAdam(lr=1e-2))
    loss_fn = _loss_fn(model)
    batch = (xs[:1], ys[:1])
    ts = build_train_step(loss_fn, opt, amp=handle, accum_steps=1)
    ref = build_reference_loop(loss_fn, opt, amp=handle, accum_steps=1)
    sA, sB = ts.init(_copy(p0)), ref.init(_copy(p0))
    for _ in range(4):
        sA, _ = ts.step(sA, batch)
        sB, _ = ref.step(sB, batch)
    assert _trees_equal(sA.params, sB.params)


# ---------------------------------------------------------------------------
# cross-composition: amp {O1,O2} x DDP delay_allreduce x {Adam, LAMB}
# with an overflow-skip step mid-run (the L1 cross-product, composed
# through the builder and bit-compared against the hand-wired loop)
# ---------------------------------------------------------------------------


def _assert_certified_equal(treeA, treeB, opt_level):
    """The certification tier each composition can honestly hold.

    O1 trees (uniform f32 graph) and every bf16 leaf: BIT identity.
    The fp32 values of an O2 (mixed-precision) composition under
    shard_map — kept-fp32 norm leaves, fp32 moments, fp32 masters:
    drift-bounded agreement only. Bisected root cause: XLA:CPU's
    fusion/FMA contraction compiles fp32 arithmetic of a MIXED-
    precision SPMD graph with different last-bit rounding in a scan
    body than in a standalone program (the divergence appears in the
    per-microbatch gradient itself, pre-reduction; no barrier/unroll
    placement removes it, while two standalone programs agree). The
    same compositions are fully bit-identical single-device (test
    below), so the concession is an SPMD-compilation artifact, not an
    accumulation-semantics one. The tolerance is ulp-drift-scale: a
    real composition bug (wrong averaging, doubled allreduce, missed
    unscale) is off by 1e-1 .. 65536x, not 1e-3."""
    for a, b in zip(jax.tree.leaves(treeA), jax.tree.leaves(treeB)):
        a, b = np.asarray(a), np.asarray(b)
        if opt_level == "O1" or a.dtype != np.float32:
            assert np.array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-6)


@pytest.mark.parametrize("opt_level", ["O1", "O2"])
@pytest.mark.parametrize("delay", [False, True])
@pytest.mark.parametrize("opt_cls", [FusedAdam, FusedLAMB])
def test_cross_composition_ddp(opt_level, delay, opt_cls):
    model, p0, opt, handle, (xs, ys) = _setup(
        opt_level, opt_cls(lr=1e-2), seed=3)
    loss_fn = _loss_fn(model)
    mesh = jax.make_mesh((8,), ("data",))
    ddp = DistributedDataParallel(axis_name="data",
                                  delay_allreduce=delay,
                                  message_size=64)
    kw = dict(amp=handle, ddp=ddp, accum_steps=4, mesh=mesh)
    ts = build_train_step(loss_fn, opt, **kw)
    ref = build_reference_loop(loss_fn, opt, **kw)
    sA, sB = ts.init(_copy(p0)), ref.init(_copy(p0))
    # poison ONE microbatch's input (one device's shard) at step 2: the
    # overflow must skip the whole global step on EVERY device, back
    # the scale off once, and leave params/moments untouched — in both
    # programs
    xs_bad = xs.at[2, 5, :].set(jnp.inf)
    for t in range(5):
        batch = (xs_bad if t == 2 else xs, ys)
        sA, mA = ts.step(sA, batch)
        sB, mB = ref.step(sB, batch)
    _assert_certified_equal(sA.params, sB.params, opt_level)
    _assert_certified_equal(sA.opt_state, sB.opt_state, opt_level)
    assert _trees_equal(sA.scaler_state, sB.scaler_state)
    assert int(np.asarray(sA.scaler_state.steps_skipped)) == 1
    assert float(np.asarray(sA.scaler_state.loss_scale)) == 2.0 ** 15
    assert int(np.asarray(mA["step"])) == 5


@pytest.mark.parametrize("opt_cls", [FusedAdam, FusedLAMB])
def test_o2_ddp_bit_identity_uniform_cast_net(opt_cls):
    """O2 + DDP, norm-free net: every PARAM leaf casts to bf16 and the
    fused-vs-hand-wired params stay BIT-identical through master
    weights + the overflow skip; the fp32 optimizer state rides the
    drift-bounded tier (see _assert_certified_equal)."""

    class DenseNet(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(32, param_dtype=jnp.float32)(x)
            x = nn.relu(x)
            return nn.Dense(4, param_dtype=jnp.float32)(x)

    model = DenseNet()
    xs, ys = _data(4, 8, seed=5)
    p0 = model.init(jax.random.PRNGKey(1), xs[0])["params"]
    p0, opt, handle = amp.initialize(
        p0, opt_cls(lr=1e-2), opt_level="O2", verbosity=0)
    loss_fn = _loss_fn(model)
    mesh = jax.make_mesh((8,), ("data",))
    ddp = DistributedDataParallel(axis_name="data", delay_allreduce=True)
    kw = dict(amp=handle, ddp=ddp, accum_steps=4, mesh=mesh)
    ts = build_train_step(loss_fn, opt, **kw)
    ref = build_reference_loop(loss_fn, opt, **kw)
    sA, sB = ts.init(_copy(p0)), ref.init(_copy(p0))
    xs_bad = xs.at[1, 3, :].set(jnp.nan)
    for t in range(5):
        batch = (xs_bad if t == 2 else xs, ys)
        sA, _ = ts.step(sA, batch)
        sB, _ = ref.step(sB, batch)
    assert _trees_equal(sA.params, sB.params)       # bf16: bitwise
    _assert_certified_equal(sA.opt_state, sB.opt_state, "O2")
    assert _trees_equal(sA.scaler_state, sB.scaler_state)
    assert int(np.asarray(sA.scaler_state.steps_skipped)) == 1


def test_o2_single_device_keep_norm_fp32_bit_identity():
    """O2 with the fp32-kept norm leaves IS bit-identical single-device
    (the ulp concession in _assert_certified_equal is strictly an
    SPMD-compilation artifact, not an accumulation-semantics one)."""
    model, p0, opt, handle, batch = _setup("O2", FusedAdam(lr=1e-2))
    loss_fn = _loss_fn(model)
    ts = build_train_step(loss_fn, opt, amp=handle, accum_steps=4)
    ref = build_reference_loop(loss_fn, opt, amp=handle, accum_steps=4)
    sA, sB = ts.init(_copy(p0)), ref.init(_copy(p0))
    for _ in range(5):
        sA, _ = ts.step(sA, batch)
        sB, _ = ref.step(sB, batch)
    assert _trees_equal(sA.params, sB.params)
    assert _trees_equal(sA.opt_state, sB.opt_state)


def test_overflow_step_leaves_state_untouched():
    model, p0, opt, handle, (xs, ys) = _setup("O1", FusedAdam(lr=1e-2))
    loss_fn = _loss_fn(model)
    ts = build_train_step(loss_fn, opt, amp=handle, accum_steps=4)
    state = ts.init(_copy(p0))
    state, _ = ts.step(state, (xs, ys))
    params_before = _copy(state.params)
    moments_before = _copy(state.opt_state.exp_avg)
    state, m = ts.step(state, (xs.at[0, 0, 0].set(jnp.nan), ys))
    assert bool(np.asarray(m["skipped"]))
    assert _trees_equal(state.params, params_before)
    assert _trees_equal(state.opt_state.exp_avg, moments_before)
    assert int(np.asarray(m["steps_skipped"])) == 1
    # but the step counter in metrics still advanced (a skipped step is
    # a consumed batch, matching the reference's epoch accounting)
    assert int(np.asarray(m["step"])) == 2


# ---------------------------------------------------------------------------
# donation: the compiled program must SHOW the aliasing
# ---------------------------------------------------------------------------


def test_donation_aliases_params_and_moments():
    model, p0, opt, handle, batch = _setup("O2", FusedAdam(lr=1e-2))
    ts = build_train_step(_loss_fn(model), opt, amp=handle,
                          accum_steps=4)
    state = ts.init(_copy(p0))
    stats = ts.alias_stats(state, batch)
    n_params = len(jax.tree.leaves(state.params))
    n_state = len(jax.tree.leaves(state))
    # every param leaf AND at least the moment/master/scaler buffers
    # must alias; a dropped donation (layout mismatch) shows up here as
    # a hard count, not an XLA warning
    assert stats["pairs"] >= n_params + 1
    assert stats["pairs"] <= n_state
    assert set(stats["kinds"]) <= {"may-alias", "must-alias"}
    # and the audit is a positive signal: the undonated build aliases 0
    ts_nodonate = build_train_step(_loss_fn(model), opt, amp=handle,
                                   accum_steps=4, donate=False)
    assert ts_nodonate.alias_stats(ts_nodonate.init(_copy(p0)),
                                   batch)["pairs"] == 0


def test_donated_state_is_consumed():
    model, p0, opt, handle, batch = _setup("O1", FusedAdam(lr=1e-2))
    ts = build_train_step(_loss_fn(model), opt, amp=handle,
                          accum_steps=4)
    state = ts.init(_copy(p0))
    old_leaf = jax.tree.leaves(state.params)[0]
    new_state, _ = ts.step(state, batch)
    with pytest.raises(RuntimeError):
        np.asarray(old_leaf)  # buffer was donated into new_state
    assert np.all(np.isfinite(np.asarray(jax.tree.leaves(
        new_state.params)[0])))


def test_input_output_alias_stats_parses_header():
    text = ("HloModule jit_step, is_scheduled=true, input_output_alias="
            "{ {0}: (0, {}, may-alias), {1}: (2, {}, must-alias) }, "
            "entry_computation_layout={(f32[4]{0})->(f32[4]{0})}")
    stats = input_output_alias_stats(text)
    assert stats["pairs"] == 2
    assert stats["params"] == [0, 2]
    assert stats["kinds"] == {"may-alias": 1, "must-alias": 1}
    assert input_output_alias_stats("HloModule bare")["pairs"] == 0


# ---------------------------------------------------------------------------
# deferred metrics loop
# ---------------------------------------------------------------------------


def test_train_loop_defers_metrics_by_one_step():
    model, p0, opt, handle, batch = _setup("O1", FusedAdam(lr=1e-2))
    ts = build_train_step(_loss_fn(model), opt, amp=handle,
                          accum_steps=4)
    # ground truth: the same stream, fetched eagerly
    eager_losses = []
    s = ts.init(_copy(p0))
    for _ in range(5):
        s, m = ts.step(s, batch)
        eager_losses.append(float(np.asarray(m["loss"])))

    loop = TrainLoop(ts, ts.init(_copy(p0)))
    got = []
    assert loop.step(batch) is None       # nothing pending on call 1
    for _ in range(4):
        m = loop.step(batch)
        assert isinstance(m["loss"], float)   # host scalars, not arrays
        assert isinstance(m["step"], int)
        got.append(m["loss"])
    final = loop.drain()
    got.append(final["loss"])
    assert loop.drain() is None
    assert got == eager_losses
    assert final["step"] == 5
    assert int(np.asarray(loop.state.step)) == 5


def test_train_loop_run_collects_all_metrics():
    model, p0, opt, handle, batch = _setup("O1", FusedAdam(lr=1e-2))
    ts = build_train_step(_loss_fn(model), opt, amp=handle,
                          accum_steps=4)
    loop = ts.loop(ts.init(_copy(p0)))
    out = loop.run([batch] * 4)
    assert [m["step"] for m in out] == [1, 2, 3, 4]


# ---------------------------------------------------------------------------
# builder knobs
# ---------------------------------------------------------------------------


def test_lr_schedule_and_grad_norm():
    model, p0, opt, handle, batch = _setup("O1", FusedAdam(lr=1e-2))
    loss_fn = _loss_fn(model)
    # lr schedule pinned to 0: params must not move, but moments do
    ts = build_train_step(loss_fn, opt, amp=handle, accum_steps=4,
                          lr_schedule=lambda step: 0.0,
                          with_grad_norm=True)
    state = ts.init(_copy(p0))
    new_state, m = ts.step(state, batch)
    assert _trees_equal(new_state.params, p0)
    # ...but the step still ran: moments moved off zero
    assert not _trees_equal(
        new_state.opt_state.exp_avg,
        jax.tree.map(jnp.zeros_like, new_state.opt_state.exp_avg))
    assert float(np.asarray(m["grad_norm"])) > 0


def test_batch_shape_validation():
    model, p0, opt, handle, (xs, ys) = _setup("O1", FusedAdam(lr=1e-2))
    ts = build_train_step(_loss_fn(model), opt, amp=handle,
                          accum_steps=8)
    state = ts.init(_copy(p0))
    with pytest.raises(ValueError, match="accum_steps=8"):
        ts.step(state, (xs, ys))  # xs has leading dim 4, not 8


def test_has_aux_surfaces_in_metrics():
    model, p0, opt, handle, batch = _setup("O1", FusedAdam(lr=1e-2))

    def loss_fn(p, mb):
        x, y = mb
        logits = model.apply({"params": p}, x).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(lp, y[:, None], 1))
        return loss, jnp.argmax(logits, -1)

    ts = build_train_step(loss_fn, opt, amp=handle, accum_steps=4,
                          has_aux=True)
    _, m = ts.step(ts.init(_copy(p0)), batch)
    assert np.asarray(m["aux"]).shape == (4, 8)  # stacked per microbatch


def test_has_aux_gathers_all_devices_under_ddp():
    """aux is device-varying; under DDP the builder must all_gather it
    to an explicit leading device axis, not let an undefined single
    shard survive the replicated out_spec."""
    model, p0, opt, handle, (xs, ys) = _setup("O1", FusedAdam(lr=1e-2))

    def loss_fn(p, mb):
        x, y = mb
        logits = model.apply({"params": p}, x).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(lp, y[:, None], 1))
        return loss, jnp.argmax(logits, -1)

    mesh = jax.make_mesh((8,), ("data",))
    ddp = DistributedDataParallel(axis_name="data")
    ts = build_train_step(loss_fn, opt, amp=handle, ddp=ddp,
                          accum_steps=4, mesh=mesh, has_aux=True)
    _, m = ts.step(ts.init(_copy(p0)), (xs, ys))
    aux = np.asarray(m["aux"])
    assert aux.shape == (8, 4, 1)  # [world, accum, local batch]
    # every device's shard present: the 8 local predictions reassemble
    # the global batch of 8
    assert sorted(aux.reshape(8, 4)[:, 0].tolist()) == sorted(
        np.asarray(jnp.argmax(
            model.apply({"params": p0}, xs[0]).astype(jnp.float32),
            -1)).tolist())


def test_scaler_none_is_unity_static():
    model, p0, opt, handle, batch = _setup("O0", FusedAdam(lr=1e-2))
    ts = build_train_step(_loss_fn(model), opt, amp=None, accum_steps=4)
    state, m = ts.step(ts.init(_copy(p0)), batch)
    assert float(np.asarray(m["loss_scale"])) == 1.0
    assert not bool(np.asarray(m["skipped"]))


# ---------------------------------------------------------------------------
# donation-friendly optimizer apply surface
# ---------------------------------------------------------------------------


def test_apply_gradients_uniform_across_optimizers():
    p = {"w": jnp.ones((4,), jnp.float32)}
    g = {"w": jnp.full((4,), 0.1, jnp.float32)}
    for opt in (FusedAdam(lr=1e-2), FusedLAMB(lr=1e-2)):
        st = opt.init(p)
        out = opt.apply_gradients(g, st, p)
        assert len(out) == 2  # always (params, state), never a 3-tuple
        # grad_scale folds in natively (LAMB) or via pre-unscale (Adam)
        out2 = opt.apply_gradients(
            jax.tree.map(lambda x: x * 8.0, g), opt.init(p), p,
            grad_scale=8.0)
        assert len(out2) == 2
        np.testing.assert_allclose(np.asarray(out[0]["w"]),
                                   np.asarray(out2[0]["w"]), rtol=1e-6)


def test_apply_gradients_rejects_alias_breaking_update():
    class BadOpt(FusedOptimizer):
        def init(self, params):
            return {}

        def step(self, grads, state, params, skip_if=None, lr=None):
            # dtype drift: a donated f32 buffer can't alias f16 output
            return jax.tree.map(lambda p: p.astype(jnp.float16), params), {}

    p = {"w": jnp.ones((4,), jnp.float32)}
    with pytest.raises(ValueError, match="donated buffer"):
        BadOpt().apply_gradients(p, {}, p)


def test_allreduce_accumulated_divides_then_syncs_once():
    from apex_tpu.utils.collectives import compat_shard_map

    mesh = jax.make_mesh((8,), ("data",))
    ddp = DistributedDataParallel(axis_name="data")
    stacked = jnp.stack([jnp.full((4,), float(i + 1)) for i in range(8)])

    def f(acc):
        return ddp.allreduce_accumulated(
            jax.tree.map(lambda x: x[0], acc), 2)

    out = jax.jit(compat_shard_map(
        f, mesh, in_specs=P("data"), out_specs=P()))(stacked)
    # mean over devices of (per-device sum / accum=2): mean(1..8)/2
    np.testing.assert_allclose(np.asarray(out),
                               np.full((4,), 4.5 / 2.0), rtol=1e-6)


@pytest.mark.parametrize("delay", [False, True])
@pytest.mark.parametrize("check_vma", [False, True])
def test_allreduce_grads_sums_exactly_once(check_vma, delay):
    """Hand-assembled per-device values AND autodiff grads of replicated
    params each come out as the mean over the 8 devices — summed exactly
    once — under the repo's wrapper (``check_vma=False``: no vma tracked,
    autodiff grads are device-local) and under ``jax.shard_map``'s
    default (``check_vma=True``: autodiff already psummed them). A
    gradient that passed through unsummed would read device 0's value
    over 8; one summed twice would read 8x the mean."""
    from apex_tpu.utils.collectives import compat_shard_map

    mesh = jax.make_mesh((8,), ("data",))
    ddp = DistributedDataParallel(axis_name="data", delay_allreduce=delay)
    w = {"a": jnp.arange(4.0) + 1.0, "b": jnp.ones((2, 3))}
    x = jnp.arange(8.0) + 1.0  # device i holds x = i + 1

    def f(w, x):
        local_x = x[0]
        hand = jax.tree.map(lambda p: p * local_x, w)
        auto = jax.grad(lambda p: sum(
            jnp.sum(v * v) for v in jax.tree.leaves(p)) * local_x)(w)
        # both in ONE call: a mixed tree must not promote a summed leaf
        return ddp.allreduce_grads({"hand": hand, "auto": auto})

    if check_vma:
        sm = jax.shard_map(f, mesh=mesh, in_specs=(P(), P("data")),
                           out_specs=P())
    else:
        sm = compat_shard_map(f, mesh, in_specs=(P(), P("data")),
                              out_specs=P())
    out = jax.jit(sm)(w, x)
    mean_x = 4.5
    for k, v in w.items():
        np.testing.assert_allclose(np.asarray(out["hand"][k]),
                                   np.asarray(v) * mean_x, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out["auto"][k]),
                                   2.0 * np.asarray(v) * mean_x, rtol=1e-6)
