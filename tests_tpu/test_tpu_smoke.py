"""On-TPU smoke tests: every Pallas kernel fwd+bwd at aligned AND
unaligned shapes, compiled by Mosaic and executed on the chip, plus one
tiny end-to-end O2 + FusedLAMB train step.

These are the exact failure classes that round 1's CPU-only suite missed:
Mosaic lowering gaps (scatter), tiled-layout blowups, and runtime buffer
semantics on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# (rows, hidden): aligned to (8,128) tiles, and deliberately unaligned.
LN_SHAPES = [(64, 256), (64, 100), (57, 768), (3, 257)]
# (batch, heads, q, k) for the softmax family.
SM_SHAPES = [(2, 4, 128, 128), (2, 4, 100, 100), (1, 2, 37, 64)]


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


@pytest.mark.parametrize("shape", LN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layer_norm_fwd_bwd_compiles_and_matches(shape, dtype):
    from apex_tpu.ops.layer_norm import (
        fused_layer_norm_affine, layer_norm_reference)

    n, h = shape
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, h), dtype)
    w = jnp.ones((h,), jnp.float32) + 0.1
    b = jnp.full((h,), 0.05, jnp.float32)

    y = jax.jit(fused_layer_norm_affine)(x, w, b)
    y_ref = layer_norm_reference(x, w, b)
    assert _max_err(y, y_ref) < (0.03 if dtype == jnp.bfloat16 else 1e-4)

    def f(x, w, b):
        return jnp.sum(fused_layer_norm_affine(x, w, b) * 1.7)

    def fr(x, w, b):
        return jnp.sum(layer_norm_reference(x, w, b) * 1.7)

    g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(x, w, b)
    gr = jax.jit(jax.grad(fr, argnums=(0, 1, 2)))(x, w, b)
    tol = 0.06 if dtype == jnp.bfloat16 else 1e-3
    for a, r in zip(g, gr):
        assert _max_err(a, r) < tol


@pytest.mark.parametrize("shape", LN_SHAPES[:2])
def test_rms_norm_fwd_bwd_compiles_and_matches(shape):
    from apex_tpu.ops.layer_norm import fused_rms_norm_affine, rms_norm_reference

    n, h = shape
    x = jax.random.normal(jax.random.PRNGKey(1), (n, h), jnp.bfloat16)
    w = jnp.ones((h,), jnp.float32) + 0.1

    y = jax.jit(fused_rms_norm_affine)(x, w)
    assert _max_err(y, rms_norm_reference(x, w)) < 0.03

    g = jax.jit(jax.grad(lambda x, w: jnp.sum(fused_rms_norm_affine(x, w)),
                         argnums=(0, 1)))(x, w)
    gr = jax.jit(jax.grad(lambda x, w: jnp.sum(rms_norm_reference(x, w)),
                          argnums=(0, 1)))(x, w)
    for a, r in zip(g, gr):
        assert _max_err(a, r) < 0.06


@pytest.mark.parametrize("shape", SM_SHAPES)
def test_scaled_masked_softmax_fwd_bwd(shape):
    from apex_tpu.ops.softmax import scaled_masked_softmax, softmax_reference

    b, h, q, k = shape
    x = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.bfloat16)
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (b, 1, q, k)) < 0.2)

    y = jax.jit(lambda x, m: scaled_masked_softmax(x, m, 0.5))(x, mask)
    y_ref = softmax_reference(x, mask, 0.5)
    assert _max_err(y, y_ref) < 0.02

    g = jax.jit(jax.grad(
        lambda x: jnp.sum(scaled_masked_softmax(x, mask, 0.5) * 1.3)))(x)
    gr = jax.jit(jax.grad(
        lambda x: jnp.sum(softmax_reference(x, mask, 0.5) * 1.3)))(x)
    assert _max_err(g, gr) < 0.03


@pytest.mark.parametrize("shape", SM_SHAPES[:2])
def test_upper_triang_softmax_fwd_bwd(shape):
    from apex_tpu.ops.softmax import (
        scaled_upper_triang_masked_softmax, softmax_reference)

    x = jax.random.normal(jax.random.PRNGKey(4), shape, jnp.bfloat16)
    y = jax.jit(lambda x: scaled_upper_triang_masked_softmax(x, 0.7))(x)
    y_ref = softmax_reference(x, None, 0.7, causal=True)
    assert _max_err(y, y_ref) < 0.02

    g = jax.jit(jax.grad(
        lambda x: jnp.sum(scaled_upper_triang_masked_softmax(x, 0.7))))(x)
    gr = jax.jit(jax.grad(
        lambda x: jnp.sum(softmax_reference(x, None, 0.7, causal=True))))(x)
    assert _max_err(g, gr) < 0.03


def test_tiny_bert_o2_fused_lamb_train_step():
    """End-to-end: tiny BERT, amp O2, FusedLAMB, fused kernels, real chip."""
    import apex_tpu.amp as amp
    from apex_tpu.models import BertConfig, BertForPreTraining, pretraining_loss
    from apex_tpu.optimizers import FusedLAMB

    cfg = BertConfig.tiny(dtype=jnp.bfloat16, fused_kernels=True,
                          hidden_dropout=0.0, attention_dropout=0.0)
    model = BertForPreTraining(cfg)
    rng = np.random.RandomState(0)
    B, S = 2, 16
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
    types = jnp.zeros((B, S), jnp.int32)
    attn = jnp.ones((B, S), jnp.int32)
    mlm_labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
    nsp_labels = jnp.asarray(rng.randint(0, 2, (B,)))

    params = model.init(jax.random.PRNGKey(0), ids, types, attn)["params"]
    opt = FusedLAMB(lr=1e-3, weight_decay=0.01)
    params, opt, handle = amp.initialize(params, opt, opt_level="O2",
                                         verbosity=0)
    ost, sst = opt.init(params), handle.init_state()

    @jax.jit
    def step(params, ost, sst):
        def loss_fn(p):
            mlm, nsp = model.apply({"params": p}, ids, types, attn)
            return pretraining_loss(mlm, nsp, mlm_labels, nsp_labels)

        (loss, found), grads = handle.value_and_grad(loss_fn, sst)(params)
        p2, ost2 = opt.step(grads, ost, params, skip_if=found)
        return p2, ost2, handle.scalers[0].update(sst, found), loss

    losses = []
    for _ in range(5):
        params, ost, sst, loss = step(params, ost, sst)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
    assert float(sst.loss_scale) == 65536.0  # no spurious overflow backoff


def test_multi_tensor_ops_on_chip():
    """scale / l2norm / adam execute compiled (not interpreted) on TPU."""
    from apex_tpu.ops.multi_tensor import (
        ADAM_MODE_ADAMW, multi_tensor_adam, multi_tensor_l2norm,
        multi_tensor_scale)

    ts = [jax.random.normal(jax.random.PRNGKey(i), s)
          for i, s in enumerate([(17,), (8, 128), (3, 5, 7)])]
    outs, flag = jax.jit(
        lambda ts: multi_tensor_scale(0, None, [ts, ts], 0.25))(ts)
    assert not bool(flag)
    for o, t in zip(outs, ts):
        np.testing.assert_allclose(np.asarray(o), np.asarray(t) * 0.25,
                                   rtol=1e-6)

    gn, per = jax.jit(
        lambda ts: multi_tensor_l2norm(0, None, [ts], per_tensor=True))(ts)
    ref = np.sqrt(sum(float(jnp.sum(t.astype(jnp.float32) ** 2)) for t in ts))
    assert abs(float(gn) - ref) < 1e-2

    g = [jnp.full_like(t, 0.1) for t in ts]
    m = [jnp.zeros_like(t) for t in ts]
    v = [jnp.zeros_like(t) for t in ts]
    (p2, m2, v2) = jax.jit(lambda g, p, m, v: multi_tensor_adam(
        0, None, [g, p, m, v], 1e-2, 0.9, 0.999, 1e-8, 1,
        ADAM_MODE_ADAMW, True, 0.0))(g, ts, m, v)
    for a, b in zip(p2, ts):
        assert _max_err(a, b) > 1e-5  # params moved


@pytest.mark.parametrize("shape,causal,use_mask", [
    ((2, 4, 128, 64), False, True),
    ((1, 2, 512, 64), False, True),
    ((1, 2, 640, 64), True, False),      # multi-block online softmax
    ((1, 1, 100, 64), False, True),      # unaligned
])
def test_flash_attention_fwd_bwd_on_chip(shape, causal, use_mask):
    from apex_tpu.ops.flash_attention import flash_attention, mha_reference

    B, H, S, D = shape
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.bfloat16)
    km = ((jax.random.uniform(jax.random.PRNGKey(9), (B, S)) < 0.3)
          if use_mask else None)
    scale = 1.0 / np.sqrt(D)

    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, km, causal, scale))(
        q, k, v)
    ref = mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), km, causal, scale)
    assert _max_err(out, ref) < 0.02

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, km, causal, scale)
                       .astype(jnp.float32))

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for a in g:
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
