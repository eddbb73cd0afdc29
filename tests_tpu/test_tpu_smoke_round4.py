"""On-hardware smoke for this session's additions: the hysteresis
scaler inside a compiled train step and the fused l2norm_scale op.
Same contract as the other smoke files: real compiled path,
auto-skipped off-TPU by conftest."""

import jax
import jax.numpy as jnp
import numpy as np


def test_hysteresis_scaler_step_on_chip():
    """A jitted O2-style step with LossScaler(hysteresis=2): the first
    overflow holds the scale (step skipped), the second backs off —
    all as in-graph selects, no host callbacks."""
    from apex_tpu.amp import LossScaler
    from apex_tpu.optimizers import FusedAdam

    params = {"w": jnp.ones((256, 256), jnp.bfloat16)}
    opt = FusedAdam(lr=1e-3).with_master_weights(True)
    scaler = LossScaler(hysteresis=2)
    ost = opt.init(params)
    sst = scaler.init()
    x = jnp.asarray(np.random.RandomState(0).randn(16, 256), jnp.bfloat16)

    @jax.jit
    def step(params, ost, sst, poison):
        def loss_fn(p):
            h = jnp.tanh(x @ p["w"])
            return jnp.mean(h.astype(jnp.float32) ** 2) * poison

        (loss, found), grads = scaler.value_and_grad(loss_fn, sst)(params)
        p2, ost2 = opt.step(grads, ost, params, skip_if=found)
        return p2, ost2, scaler.update(sst, found), loss

    params, ost, sst, _ = step(params, ost, sst, 1.0)
    w_before = params["w"]
    params, ost, sst, _ = step(params, ost, sst, jnp.inf)
    assert float(sst.loss_scale) == 2.0 ** 16      # held (tolerance 2->1)
    assert int(sst.steps_skipped) == 1
    assert bool(jnp.all(params["w"] == w_before))  # step skipped
    params, ost, sst, _ = step(params, ost, sst, jnp.inf)
    assert float(sst.loss_scale) == 2.0 ** 15      # depleted: backed off
    params, ost, sst, _ = step(params, ost, sst, 1.0)
    assert not bool(jnp.all(params["w"] == w_before))  # training resumed


def test_l2norm_scale_compiles_on_chip():
    """multi_tensor_l2norm_scale at aligned AND unaligned shapes."""
    from apex_tpu.multi_tensor_apply import multi_tensor_applier
    from apex_tpu.ops import multi_tensor as mt

    rng = np.random.RandomState(1)
    xs = [jnp.asarray(rng.randn(512, 128).astype("f4")),
          jnp.asarray(rng.randn(1000, 7).astype("f4")),           # unaligned
          jnp.asarray(rng.randn(33), jnp.bfloat16)]               # mixed dtype

    @jax.jit
    def f(xs):
        return multi_tensor_applier(
            mt.multi_tensor_l2norm_scale, None,
            [xs, [jnp.zeros_like(x) for x in xs]], 0.25, per_tensor=True)

    outs, gnorm, per, flag = f(xs)
    ref = np.sqrt(sum(float(np.sum((np.asarray(x) * 0.25) ** 2))
                      for x in xs))
    np.testing.assert_allclose(float(gnorm), ref, rtol=1e-5)
    assert not bool(flag)
    for x, o in zip(xs, outs):
        np.testing.assert_allclose(np.asarray(o), np.asarray(x) * 0.25,
                                   rtol=1e-6)
