"""Plain building blocks shared by the references: float32 ``jax.numpy``
with no kernels, no cache, no batching tricks. Nothing here imports the
program (``apex_tpu``) or takes anything the program has made.

The one thing the references share with the program by *definition* is
the stream of dropout masks, because a training step with dropout can be
compared only under the same masks. That definition is written down in
``benchmark/harness/masks.py`` and in each configuration's ``assumed``.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FILL = -30000.0          # finite fill of masked scores (exp() of it is 0)
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# -- precision: float32 at `highest`, or the control's fp8 ------------------

def _q8(x):
    """Per-tensor scaled round trip through float8_e4m3 (the control's
    precision: the nearest below bfloat16)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


@jax.custom_vjp
def _mm8(a, b):
    return jnp.matmul(_q8(a), _q8(b), precision=HIGHEST)


def _mm8_fwd(a, b):
    return _mm8(a, b), (a, b)


def _mm8_bwd(res, g):
    a, b = res
    g8, a8, b8 = _q8(g), _q8(a), _q8(b)
    da = jnp.matmul(g8, jnp.swapaxes(b8, -1, -2), precision=HIGHEST)
    db = jnp.matmul(jnp.swapaxes(a8, -1, -2), g8, precision=HIGHEST)
    # un-broadcast a weight shared over leading batch dims
    while db.ndim > b.ndim:
        db = db.sum(0)
    return da, db


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def matmul(a, b, precision: str):
    """``a @ b`` in the stated precision: ``"fp32"`` is float32 at
    ``highest``; ``"fp8"`` rounds both operands (and, in the backward
    pass, the cotangent) through float8_e4m3."""
    if precision == "fp32":
        return jnp.matmul(a, b, precision=HIGHEST)
    if precision == "fp8":
        return _mm8(a, b)
    raise ValueError(f"unknown precision {precision!r}")


# -- layers -----------------------------------------------------------------

def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def dropout(x, keep, rate):
    """``keep`` is a boolean mask of ``x``'s shape (or None: rate 0)."""
    if keep is None:
        return x
    return jnp.where(keep, x * (1.0 / (1.0 - rate)), 0.0)


def attention(q, k, v, num_heads, *, key_mask=None, causal=False,
              keep=None, rate=0.0, precision="fp32"):
    """Multi-head attention over flat ``(B, S, H)`` projections with the
    whole ``(B, heads, S, S)`` score tensor materialised. ``key_mask``
    ``(B, S)`` is True where a key is padding; ``keep`` is the dropout
    mask of the probabilities."""
    B, S, H = q.shape
    d = H // num_heads

    def heads(t):
        return t.reshape(B, S, num_heads, d).transpose(0, 2, 1, 3)

    qh, kh, vh = heads(q), heads(k), heads(v)
    s = matmul(qh, jnp.swapaxes(kh, -1, -2), precision) * (d ** -0.5)
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :], FILL, s)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        s = jnp.where((row >= col)[None, None], s, FILL)
    p = jax.nn.softmax(s, axis=-1)
    p = dropout(p, keep, rate)
    ctx = matmul(p, vh, precision)
    return ctx.transpose(0, 2, 1, 3).reshape(B, S, H)


def cross_entropy(logits, labels):
    """Per-position ``logsumexp - picked`` in float32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - picked


# -- dropout seeds ------------------------------------------------------------

def site_key(root_key, path, count: int = 1):
    """The key flax's ``make_rng`` hands the module at ``path`` on its
    ``count``-th draw from a stream rooted at ``root_key``: the root
    folded with the first four bytes of the SHA-1 of the path's names
    and the draw count (flax.core.scope.LazyRng, written out)."""
    m = hashlib.sha1()
    for x in tuple(path) + (count,):
        if isinstance(x, str):
            m.update(x.encode("utf-8"))
        else:
            m.update(x.to_bytes((x.bit_length() + 7) // 8, "big"))
    folded = int.from_bytes(m.digest()[:4], "big")
    return jax.random.fold_in(root_key, jnp.uint32(folded))


def site_seed(root_key, path):
    """int32 seed of the dropout site at ``path``."""
    return jax.random.randint(site_key(root_key, path), (), 0, 2 ** 31 - 1,
                              dtype=jnp.int32)


# -- weights ------------------------------------------------------------------

def round_bf16(x):
    """``x`` rounded to bfloat16's precision, held in float32. Not
    ``x.astype(bfloat16).astype(float32)``: XLA may drop that round trip
    (``xla_allow_excess_precision``), and on the chip it does."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def normal_bf16(key, shape, std=0.02):
    """N(0, std) rounded to bfloat16 and held in float32: the values a
    bf16 model copy and its fp32 masters both start from, exactly."""
    return round_bf16(std * jax.random.normal(key, shape, jnp.float32))


def named_keys(key, names):
    return {n: jax.random.fold_in(key, i) for i, n in enumerate(names)}


# -- optimizers (fp32, per leaf) ------------------------------------------------

def tree_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree.leaves(tree)))


def lamb_step(p, g, m, v, step, *, lr, b1=0.9, b2=0.999, eps=1e-6,
              wd=0.01, max_grad_norm=1.0, leaf_axes=None):
    """One step of LAMB as NVIDIA apex's FusedLAMB states it: gradients
    clipped by their global norm to ``max_grad_norm``, Adam moments with
    bias correction, decoupled weight decay added to the direction, one
    trust ratio ``|p| / |u|`` per tensor (1 where either norm is 0).
    ``leaf_axes(name)`` gives the axes over which one tensor's norm runs
    (stacked layers keep their leading axis)."""
    gn = tree_norm(g)
    clip = jnp.where(gn > max_grad_norm, max_grad_norm / gn, 1.0)
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    new_p, new_m, new_v = {}, {}, {}
    for name in p:
        gi = g[name] * clip
        mi = b1 * m[name] + (1.0 - b1) * gi
        vi = b2 * v[name] + (1.0 - b2) * gi * gi
        u = (mi / bc1) / (jnp.sqrt(vi / bc2) + eps) + wd * p[name]
        axes = leaf_axes(name, p[name])
        pn = jnp.sqrt(jnp.sum(jnp.square(p[name]), axis=axes, keepdims=True))
        un = jnp.sqrt(jnp.sum(jnp.square(u), axis=axes, keepdims=True))
        ratio = jnp.where((pn > 0) & (un > 0), pn / jnp.where(un > 0, un, 1.0),
                          1.0)
        new_p[name] = p[name] - lr * ratio * u
        new_m[name], new_v[name] = mi, vi
    return new_p, new_m, new_v


def adamw_step(p, g, m, v, step, *, lr, b1=0.9, b2=0.999, eps=1e-8,
               wd=0.01, leaf_axes=None):
    """One step of AdamW (decoupled weight decay on every leaf)."""
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    new_p, new_m, new_v = {}, {}, {}
    for name in p:
        mi = b1 * m[name] + (1.0 - b1) * g[name]
        vi = b2 * v[name] + (1.0 - b2) * g[name] * g[name]
        u = (mi / bc1) / (jnp.sqrt(vi / bc2) + eps) + wd * p[name]
        new_p[name] = p[name] - lr * u
        new_m[name], new_v[name] = mi, vi
    return new_p, new_m, new_v


OPTIMIZERS = {"lamb": lamb_step, "adamw": adamw_step}


def leaf_norms(tree, leaf_axes):
    """{name: norm per tensor}: a stacked leaf gives one norm per layer."""
    return {n: jnp.sqrt(jnp.sum(jnp.square(x), axis=leaf_axes(n, x)))
            for n, x in tree.items()}
