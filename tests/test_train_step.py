"""apex_tpu.train: the fused single-dispatch train step.

The certification contract (ISSUE 5, the greedy analog of the serving
cross-K certification): the fused scanned-accumulation step must be
BIT-IDENTICAL to the hand-wired per-microbatch dispatch loop it
replaces — across amp opt levels, DDP flat-buffer modes, optimizers,
and through an overflow-skip step mid-run — and the compiled program
must POSITIVELY show donated buffers aliasing (XLA drops donation with
only a warning, so absence-of-error proves nothing).
"""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

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


# ---------------------------------------------------------------------------
# bench section smoke (CI satellite: no more blank bench rounds)
# ---------------------------------------------------------------------------


def _load_bench():
    import importlib.util

    path = Path(__file__).resolve().parents[1] / "bench.py"
    spec = importlib.util.spec_from_file_location("_bench_train_smoke",
                                                 path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_train_step_section_smoke():
    """The bench train-step sweep (fast shape) must run end-to-end,
    certify fused-vs-loop bit identity, and report a positive donation
    audit."""
    rec = _load_bench().bench_train_step(fast=True)
    assert rec["unit"] == "steps/sec"
    assert rec["final_params_bit_identical"] is True
    assert rec["donated_alias_pairs"] >= 1
    assert rec["accum_steps_swept"] == [1, 4]
    for arm in rec["sweep"].values():
        assert arm["bit_identical"] is True
        assert arm["fused_steps_per_sec"] > 0
        assert arm["loop_steps_per_sec"] > 0
    assert rec["value"] > 0 and rec["vs_baseline"] > 0


def test_bench_smoke_mode_every_section_rc0():
    """``bench.py --smoke`` (the tier-1 guard against blank bench
    rounds: rc=1, nothing parsed) must exit 0 with one valid JSON
    record per section."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, str(repo / "bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=str(repo))
    assert out.returncode == 0, out.stderr[-2000:]
    records = [json.loads(line) for line in
               out.stdout.strip().splitlines()]
    metrics = {r["metric"] for r in records if "metric" in r}
    assert metrics == {
        "fused_layer_norm_fwdbwd_speedup_vs_xla",
        "fused_lamb_step_speedup_vs_per_leaf_eager",
        "ddp_syncbn_allreduce_bytes_over_grad_bytes_8dev",
        "serving_tiny_smoke_decode_steps_per_sec",
        "serving_tiny_smoke_multistep_decode_tokens_per_sec",
        "serving_tiny_speculative_decode_tokens_per_sec",
        "serving_tiny_overload_goodput_tokens_per_sec",
        "serving_tiny_multitenant_victim_goodput_tok_per_sec",
        "serving_tiny_kv_memory_int8_decode_tokens_per_sec",
        "serving_tiny_weight_quant_int8_decode_tokens_per_sec",
        "serving_tiny_fleet_kill_goodput_tok_per_sec",
        "serving_tiny_integrity_sdc_detection_latency_ticks",
        "serving_tiny_mesh_decode_tokens_per_sec",
        "serving_tiny_process_kill_goodput_tok_per_sec",
        "serving_tiny_disagg_ttft_p99_ticks",
        "serving_tiny_shared_prefix_fleet_hit_rate",
        "train_step_tiny_smoke_fused_steps_per_sec",
        "train_tiny_sharded_steps_per_sec",
        "obs_pipeline_smoke_requests_summarized",
    }
    for r in records:
        if "metric" in r:
            assert "value" in r and "vs_baseline" in r, r["metric"]
    # the speculative arm must actually speculate in smoke shape: a
    # zero acceptance count would mean the drafter is silently off and
    # the record a quiet perf lie
    spec = [r for r in records
            if r.get("metric") == "serving_tiny_speculative_decode_tokens_per_sec"][0]
    assert spec["acceptance_rate"] > 0, spec
    assert spec["arms"]["speculative"]["num_accepted_tokens"] > 0, spec
    assert spec["outputs_bit_identical"] is True, spec
    # the overload arm's latency percentiles and goodput must be
    # present and FINITE (the r01/r05 dead-section lesson extended to
    # the tail-latency arm: a NaN percentile is a quiet perf lie), with
    # zero engine stalls and the queue bound respected
    ov = [r for r in records
          if r.get("metric") == "serving_tiny_overload_goodput_tokens_per_sec"][0]
    for key in ("p50_ttft_s", "p99_ttft_s", "p50_itl_s", "p99_itl_s",
                "goodput_tokens_per_sec", "decode_tokens_per_sec",
                "slo_attainment"):
        assert key in ov and math.isfinite(ov[key]), (key, ov)
    assert ov["num_stalls"] == 0, ov
    assert ov["queue_depth_peak"] <= ov["max_waiting"] + ov["max_batch"]
    assert ov["status_counts"].get("finished", 0) > 0, ov
    # the multitenant arm must have actually confined the flood (the
    # in-section asserts do the heavy lifting; here we pin the record
    # shape so a silently-skipped phase cannot pass)
    mt = [r for r in records
          if r.get("metric")
          == "serving_tiny_multitenant_victim_goodput_tok_per_sec"][0]
    assert mt["flood_only_shed"] is True, mt
    assert mt["allocator_integrity_ok"] is True, mt
    assert mt["chaos_aborts"] > 0 and mt["chaos_retries"] > 0, mt
    for t in ("acme", "bolt"):
        assert mt["per_tenant"][t]["door_sheds"] == 0, mt
        assert mt["per_tenant"][t]["throttled"] == 0, mt
        assert mt["per_tenant"][t]["goodput_tokens"] > 0, mt
    assert math.isfinite(mt["vs_baseline"]), mt
    # the kv-memory arm (docs/serving.md memory tiers) must show
    # quantization buying REAL concurrency under an equal byte budget
    # and the spill tier actually re-admitting on the re-serve pass —
    # a silently-skipped phase or a zero hit rate is a quiet capacity
    # lie
    km = [r for r in records
          if r.get("metric")
          == "serving_tiny_kv_memory_int8_decode_tokens_per_sec"][0]
    assert km["residents_ratio"] >= 1.5, km
    assert km["int8"]["peak_residents"] > km["fp"]["peak_residents"], km
    assert km["int8"]["num_blocks"] > km["fp"]["num_blocks"], km
    assert km["spill"]["hit_rate"] > 0, km
    assert km["spill"]["blocks_spilled"] > 0, km
    assert km["spill"]["reserve_token_identical"] is True, km
    assert math.isfinite(km["value"]) and km["value"] > 0, km
    # the weight-quant arm (docs/serving.md "Quantized weight
    # storage") must prove the capacity headline (>= 1.8x model bytes
    # per chip at an equal HBM budget) AND the greedy token-identity
    # cert — a non-asserting arm would be a quiet numerics lie
    wq = [r for r in records
          if r.get("metric")
          == "serving_tiny_weight_quant_int8_decode_tokens_per_sec"][0]
    assert wq["bytes_ratio"] >= 1.8, wq
    assert wq["vs_baseline"] == wq["bytes_ratio"], wq
    assert wq["int8_residents"] > wq["fp_residents"], wq
    assert wq["int8_param_bytes"] < wq["fp_param_bytes"], wq
    assert wq["greedy_token_identical"] is True, wq
    assert wq["int8"]["decode_tokens"] > 0, wq
    assert math.isfinite(wq["value"]) and wq["value"] > 0, wq
    # the fleet arm (docs/fleet.md) must prove the crash-tolerance
    # headline: a 1-replica fleet bit-identical to the bare engine, a
    # replica killed mid-burst with ZERO lost accepted requests,
    # failover + drain-and-migrate both actually fired, and the
    # victims' p99 TTFT inside its bound vs the no-kill baseline — a
    # silently-skipped kill would be a quiet robustness lie
    flr = [r for r in records
           if r.get("metric")
           == "serving_tiny_fleet_kill_goodput_tok_per_sec"][0]
    assert flr["identity_ok"] is True, flr
    assert flr["zero_lost"] is True, flr
    assert flr["num_lost_requests"] == 0, flr
    assert flr["num_failovers"] >= 1, flr
    assert flr["num_migrations"] >= 1, flr
    assert flr["num_accepted"] > 0, flr
    assert (flr["victim_p99_ttft_ticks"]
            <= flr["victim_p99_bound_ticks"]), flr
    assert flr["status_counts"].get("finished", 0) > 0, flr
    assert flr["allocator_integrity_ok"] is True, flr
    assert math.isfinite(flr["vs_baseline"]) and flr["value"] > 0, flr
    # the data-integrity arm (docs/robustness.md "Data integrity")
    # must prove the whole detection story: integrity-off bit-identity
    # held, spill rot was detected AND served token-identically by
    # recompute, the fleet-wide artifact chaos lost nothing while
    # catching every fired corruption, and the SDC-faulted replica was
    # caught by the cross-check with a real (finite, nonnegative)
    # detection latency — a silently-skipped phase would be a quiet
    # integrity lie
    it = [r for r in records
          if r.get("metric")
          == "serving_tiny_integrity_sdc_detection_latency_ticks"][0]
    assert it["identity_ok"] is True, it
    assert it["spill_corrupt_discards"] > 0, it
    assert it["spill_served_token_identical"] is True, it
    assert it["chaos_detections"] > 0, it
    assert it["chaos_zero_lost"] is True, it
    assert it["sdc_suspects"] >= 1, it
    assert it["sdc_checks"] >= 1, it
    assert it["sdc_zero_lost"] is True and it["sdc_exactly_once"] is True
    assert math.isfinite(it["value"]) and it["value"] >= 0, it
    assert it["sdc_suspect_tick"] >= it["sdc_first_corrupt_tick"], it
    assert math.isfinite(it["vs_baseline"]) and it["vs_baseline"] > 0
    # the mesh arm (docs/serving.md "Mesh sharding") must prove the
    # pod-scale promotion story: (1,1) bit-identical to the pre-mesh
    # engine, greedy outputs token-identical across mesh shapes,
    # compile counts pinned at one per program under BOTH meshes, and
    # the collective contract (zero at (1,1), all-reduce traffic in
    # every program at (1,2)) — a silently-single-device arm would be
    # a quiet scale-up lie
    ms = [r for r in records
          if r.get("metric") == "serving_tiny_mesh_decode_tokens_per_sec"][0]
    assert ms["mesh11_bit_identical"] is True, ms
    assert ms["cross_mesh_token_identical"] is True, ms
    for arm_name in ("mesh_1x1", "mesh_1x2"):
        arm = ms["arms"][arm_name]
        assert arm["prefill_compilations"] == 1, ms
        assert arm["decode_compilations"] == 1, ms
    assert all(v == 0 for v in
               ms["arms"]["mesh_1x1"]["collective_ops"].values()), ms
    # reduction_ops, not the raw all-reduce count: XLA may spell one
    # all-reduce as a reduce-scatter + all-gather pair (the hlo_audit
    # round-5 lesson) and both spellings satisfy the contract
    assert all(v >= 1 for v in
               ms["arms"]["mesh_1x2"]["reduction_ops"].values()), ms
    assert math.isfinite(ms["value"]) and ms["value"] > 0, ms
    assert math.isfinite(ms["vs_baseline"]) and ms["vs_baseline"] > 0, ms
    # the process-replica arm (docs/fleet.md "Process replicas") must
    # prove the out-of-process story end to end: a 1-process-replica
    # fleet bit-identical to in-process, a child SIGKILLED for real
    # mid-burst with zero lost accepted requests and a fresh child pid
    # in the victim slot, the victims' p99 TTFT inside its bound, and
    # the autoscaler ramp growing, shrinking back, and never flapping
    # — a silently-in-process arm would be a quiet isolation lie
    pr = [r for r in records
          if r.get("metric")
          == "serving_tiny_process_kill_goodput_tok_per_sec"][0]
    assert pr["identity_ok"] is True, pr
    assert pr["zero_lost"] is True, pr
    assert pr["num_lost_requests"] == 0, pr
    assert pr["num_failovers"] >= 1, pr
    assert pr["num_respawns"] >= 1, pr
    assert pr["child_pid_fresh"] is True, pr
    assert pr["num_accepted"] > 0, pr
    assert (pr["victim_p99_ttft_ticks"]
            <= pr["victim_p99_bound_ticks"]), pr
    assert pr["autoscale_peak_replicas"] > 1, pr
    assert pr["autoscale_num_spawned"] == pr["autoscale_num_retired"], pr
    assert pr["autoscale_flap_free"] is True, pr
    assert pr["status_counts"].get("finished", 0) > 0, pr
    assert math.isfinite(pr["vs_baseline"]) and pr["value"] > 0, pr
    # the disaggregation arm (docs/fleet.md "Disaggregated roles")
    # must prove the two-stage story: the specialist fleet beat the
    # colocated one on TTFT p99 at equal device count, the handoff
    # actually moved requests/bytes, decode specialists never
    # prefilled a fresh prompt, and the prefill-specialist kill lost
    # nothing — a silently-colocated arm would be a quiet latency lie
    dg = [r for r in records
          if r.get("metric") == "serving_tiny_disagg_ttft_p99_ticks"][0]
    assert dg["vs_baseline"] < 1.0, dg
    assert dg["value"] < dg["colocated_ttft_p99_ticks"], dg
    assert dg["num_handoffs"] >= 1, dg
    assert dg["num_handoff_requests"] >= 1, dg
    assert dg["num_handoff_bytes"] > 0, dg
    assert dg["num_affinity_probes_skipped"] >= 1, dg
    assert (dg["decode_specialist_prefill_chunks"]
            <= dg["decode_specialist_imports"]), dg
    assert dg["zero_lost"] is True, dg
    assert dg["kill_num_failovers"] >= 1, dg
    assert dg["kill_num_lost_requests"] == 0, dg
    assert dg["status_counts"].get("finished", 0) > 0, dg
    assert dg["allocator_integrity_ok"] is True, dg
    assert math.isfinite(dg["vs_baseline"]) and dg["value"] > 0, dg
    # the shared-prefix-tier arm (docs/fleet.md "Shared prefix tier")
    # must prove the fleet-global cache story: the shared arm beat
    # the per-replica arm's fleet-wide hit rate AND steady-state TTFT
    # p99 at equal total spill bytes, dedupe/publish/hit all moved,
    # outputs stayed token-identical across arms, and the mid-trace
    # replica kill lost nothing — a tier that never dedupes or never
    # serves a fleet-wide hit would be a quiet capacity lie
    sp = [r for r in records
          if r.get("metric")
          == "serving_tiny_shared_prefix_fleet_hit_rate"][0]
    assert sp["vs_baseline"] < 1.0, sp
    assert sp["value"] > sp["per_replica_hit_rate"], sp
    assert (sp["shared_steady_ttft_p99_ticks"]
            < sp["per_replica_steady_ttft_p99_ticks"]), sp
    assert sp["num_shared_publishes"] >= 1, sp
    assert sp["num_shared_dedupe"] >= 1, sp
    assert sp["shared_tier_hits"] >= 1, sp
    assert sp["tokens_identical_across_arms"] is True, sp
    assert sp["zero_lost"] is True, sp
    assert sp["kill_num_failovers"] >= 1, sp
    assert sp["kill_num_lost_requests"] == 0, sp
    assert sp["status_counts"].get("finished", 0) > 0, sp
    assert sp["allocator_integrity_ok"] is True, sp
    assert math.isfinite(sp["vs_baseline"]) and sp["value"] > 0, sp
    # the sharded-train arm (docs/training.md "Sharded training") must
    # prove the 3D-parallel promotion story: mesh-arm losses certified
    # against meshless, compile counts pinned at ONE per arm (the spec-
    # canonicalization retrace gate), the collective contract audited
    # from AOT HLO (zero all-to-all; donation aliases cover every
    # sharded leaf), and the ZeRO shard bytes actually falling at
    # flat_world=2 — a silently-replicated arm would be a quiet
    # memory-scaling lie
    tsh = [r for r in records
           if r.get("metric") == "train_tiny_sharded_steps_per_sec"][0]
    assert tsh["loss_certified"] is True, tsh
    assert tsh["arms"]["meshless"]["steps_per_sec"] > 0, tsh
    for arm_name in ("mesh_1x2", "mesh_2x2"):
        arm = tsh["arms"][arm_name]
        assert arm["steps_per_sec"] > 0, tsh
        assert arm["compiles"] == 1, tsh
        assert arm["collective_ops"].get("all-to-all", 0) == 0, tsh
        assert arm["collective_ops"].get("collective-permute", 0) == 0, tsh
        assert arm["alias_pairs"] >= arm["sharded_leaves"] > 0, tsh
    assert tsh["arms"]["mesh_2x2"]["flat_world"] == 2, tsh
    assert (tsh["arms"]["mesh_2x2"]["opt_state_bytes_per_shard"]
            < tsh["arms"]["mesh_1x2"]["opt_state_bytes_per_shard"]), tsh
    assert tsh["opt_state_bytes_ratio"] > 1.0, tsh
    assert math.isfinite(tsh["value"]) and tsh["value"] > 0, tsh
    assert math.isfinite(tsh["vs_baseline"]) and tsh["vs_baseline"] > 0
    # the observability pipeline arm (docs/observability.md) certifies
    # dump -> trace_summary end to end AND re-checks zero perturbation
    ob = [r for r in records
          if r.get("metric") == "obs_pipeline_smoke_requests_summarized"][0]
    assert ob["bit_identical_with_observer"] is True, ob
    assert ob["trace_events"] > 0 and ob["recorder_events"] > 0, ob
    assert ob["ttft_observed"] == ob["value"], ob
    assert ob["summary_lines"] > 0, ob
    # every section also leaves a wall-time/exit-status record, so a
    # section that dies is a visible "failed" entry in the artifact,
    # never just an absence
    sections = {r["section"]: r for r in records if "section" in r}
    assert set(sections) == {
        "bench_layer_norm", "bench_fused_lamb", "bench_ddp_scaling",
        "bench_serving", "bench_serving_multistep",
        "bench_serving_speculative", "bench_serving_overload",
        "bench_serving_multitenant", "bench_serving_kv_memory",
        "bench_weight_quant",
        "bench_serving_fleet", "bench_serving_integrity",
        "bench_serving_mesh", "bench_serving_process",
        "bench_serving_disagg", "bench_serving_shared_prefix",
        "bench_train_step", "bench_train_sharded",
        "bench_obs_pipeline",
    }
    for rec in sections.values():
        assert rec["status"] == "ok", rec
        assert rec["wall_time_s"] > 0
