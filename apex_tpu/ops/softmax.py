"""Pallas TPU kernels: fused scale + mask + softmax (fwd + bwd).

Rebuild of the reference's ``csrc/megatron/scaled_masked_softmax*.cu`` and
``scaled_upper_triang_masked_softmax*.cu`` (SURVEY.md §2.2): attention-
score softmax with the scale multiply and (padding or causal) mask folded
into one pass — the op behind ``FusedScaleMaskSoftmax``
(``apex/transformer/functional``).

TPU design: rows are flattened to (N, Sk) and tiled into VMEM row blocks;
max/sum are VPU lane reductions; the causal mask is generated in-kernel
from ``broadcasted_iota`` (no mask tensor traffic, like the reference's
upper-triang variant); the key dim is padded to the 128-lane width with
``-inf``-equivalent so padded lanes contribute zero probability. Backward
uses the saved softmax output: dx = scale * y * (g - sum(g*y)).

Unlike the CUDA kernels (hard seq-len limits 16..16384, pow-2 shapes —
their ``is_kernel_available`` gate), any shape works here; the module
keeps the gate trivially true.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._common import (
    LANE,
    interpret_mode as _interpret,
    out_struct,
    round_up as _round_up,
)

_NEG = -30000.0  # large-negative fill, safe in bf16/fp32 (reference: -10000)
# wrapper-padding fill: far below _NEG so padded lanes contribute exactly
# zero even in a fully-user-masked row (whose live lanes all sit at _NEG
# and must degrade to a uniform distribution over the TRUE keys only)
_PAD_NEG = -1e30


def _block_rows(n):
    if n >= 256:
        return 256
    return _round_up(max(n, 1), 8)


def _fwd_kernel(x_ref, *rest, scale, causal, sq, true_k, padded, mask_mode):
    if mask_mode is not None:
        m_ref, y_ref = rest
    else:
        m_ref, (y_ref,) = None, rest
    x = x_ref[:].astype(jnp.float32) * scale
    # mask applied AFTER the scale multiply — the reference kernel's
    # order, valid for any scale incl. <= 0
    if mask_mode == "add":
        x = x + m_ref[:].astype(jnp.float32)
    elif mask_mode == "fill":
        # boolean-mask semantics: REPLACE with the finite fill (so a
        # fully-masked row degrades to uniform, like the reference)
        x = jnp.where(m_ref[:] > 0, _NEG, x)
    rows = x.shape[0]
    if causal:
        # global row index = block_start + local row; key col must be <= the
        # query position (row % sq when rows are (b*h*sq))
        row0 = pl.program_id(0) * rows
        local = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        q_pos = (row0 + local) % sq
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(col <= q_pos, x, _NEG)
    if padded:
        # LAST, so no finite mask/causal fill re-raises a padded lane
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(col < true_k, x, _PAD_NEG)
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=1, keepdims=True)
    y_ref[:] = (e / s).astype(y_ref.dtype)


def _bwd_kernel(g_ref, y_ref, dx_ref, *, scale):
    g = g_ref[:].astype(jnp.float32)
    y = y_ref[:].astype(jnp.float32)
    dot = jnp.sum(g * y, axis=1, keepdims=True)
    dx_ref[:] = (scale * y * (g - dot)).astype(dx_ref.dtype)


def _pallas_softmax_fwd(x2, m2=None, *, scale, causal, sq, true_k,
                        mask_mode=None):
    n, kpad = x2.shape
    br = _block_rows(n)
    in_specs = [pl.BlockSpec((br, kpad), lambda i: (i, 0))]
    args = [x2]
    if m2 is not None:
        in_specs.append(pl.BlockSpec((br, kpad), lambda i: (i, 0)))
        args.append(m2)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, sq=sq,
                          true_k=true_k, padded=(true_k != kpad),
                          mask_mode=mask_mode if m2 is not None else None),
        grid=(n // br,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, kpad), lambda i: (i, 0)),
        out_shape=out_struct((n, kpad), x2.dtype, x2),
        name="softmax_fwd",
        interpret=_interpret(),
    )(*args)


def _pallas_softmax_bwd(g2, y2, *, scale):
    n, kpad = g2.shape
    br = _block_rows(n)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, kpad), lambda i: (i, 0)),
            pl.BlockSpec((br, kpad), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, kpad), lambda i: (i, 0)),
        out_shape=out_struct((n, kpad), g2.dtype, g2, y2),
        name="softmax_bwd",
        interpret=_interpret(),
    )(g2, y2)


def _fwd4_kernel(x_ref, *rest, scale, causal, true_k, padded, mask_mode):
    """4D variant: block (1, 1, br, kpad) of (B, H, Sq, Sk); the mask
    block keeps its broadcast dims (size-1 B/H/Sq), so a (B, 1, 1, Sk)
    attention mask is read as-is instead of being materialized at
    (B, H, Sq, Sk)."""
    if mask_mode is not None:
        m_ref, y_ref = rest
    else:
        m_ref, (y_ref,) = None, rest
    x = x_ref[0, 0].astype(jnp.float32) * scale
    if mask_mode == "add":
        x = x + m_ref[0, 0].astype(jnp.float32)   # (1|br, kpad) broadcasts
    elif mask_mode == "fill":
        x = jnp.where(m_ref[0, 0] > 0, _NEG, x)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    if causal:
        row0 = pl.program_id(2) * x.shape[0]
        local = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        x = jnp.where(col <= row0 + local, x, _NEG)
    if padded:
        x = jnp.where(col < true_k, x, _PAD_NEG)
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=1, keepdims=True)
    y_ref[0, 0] = (e / s).astype(y_ref.dtype)


def _mask_4d_compatible(mshape, xshape):
    return (len(mshape) == 4 and len(xshape) == 4
            and mshape[0] in (1, xshape[0]) and mshape[1] in (1, xshape[1])
            and mshape[2] in (1, xshape[2]) and mshape[3] == xshape[3])


def _pallas_softmax_fwd4(x, m, *, scale, causal, mask_mode):
    B, H, Sq, K = x.shape
    kpad = _round_up(K, LANE)
    br = _block_rows(Sq)
    sqp = _round_up(Sq, br)
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, sqp - Sq), (0, kpad - K)))
    ms = m.shape[2]
    mp = jnp.pad(m.astype(jnp.float32),
                 ((0, 0), (0, 0), (0, (sqp - Sq) if ms > 1 else 0),
                  (0, kpad - K)))
    mb, mh, msq = mp.shape[0], mp.shape[1], mp.shape[2]
    mbr = br if msq > 1 else 1

    def m_idx(b, h, j):
        return (b if mb > 1 else 0, h if mh > 1 else 0,
                j if msq > 1 else 0, 0)

    yp = pl.pallas_call(
        functools.partial(_fwd4_kernel, scale=scale, causal=causal,
                          true_k=K, padded=(K != kpad),
                          mask_mode=mask_mode),
        grid=(B, H, sqp // br),
        in_specs=[
            pl.BlockSpec((1, 1, br, kpad), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, mbr, kpad), m_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, br, kpad),
                               lambda b, h, j: (b, h, j, 0)),
        out_shape=out_struct((B, H, sqp, kpad), x.dtype, x, m),
        name="softmax_fwd",
        interpret=_interpret(),
    )(xp, mp)
    return yp[:, :, :Sq, :K]


def _prep(x):
    k = x.shape[-1]
    lead = x.shape[:-1]
    n = 1
    for d in lead:
        n *= d
    x2 = x.reshape(n, k)
    kpad = _round_up(k, LANE)
    npad = _round_up(n, _block_rows(n))
    if kpad != k or npad != n:
        x2 = jnp.pad(x2, ((0, npad - n), (0, kpad - k)))
    return x2, lead, n, k


def _softmax_impl(x, m, scale, causal, sq, mask_mode):
    from apex_tpu.ops._common import use_jnp_fallback

    if use_jnp_fallback(x, m):
        ref_mask = None if m is None else (
            m > 0 if mask_mode == "fill" else m)
        return softmax_reference(x, ref_mask, scale, causal)
    if m is not None and _mask_4d_compatible(m.shape, x.shape):
        return _pallas_softmax_fwd4(x, m, scale=scale, causal=causal,
                                    mask_mode=mask_mode)
    x2, lead, n, k = _prep(x)
    m2 = None
    if m is not None:
        m2, _, _, _ = _prep(jnp.broadcast_to(m, x.shape)
                            .astype(jnp.float32))
    y2 = _pallas_softmax_fwd(x2, m2, scale=scale, causal=causal, sq=sq,
                             true_k=k, mask_mode=mask_mode)
    return y2[:n, :k].reshape(*lead, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_softmax(x, m, scale, causal, mask_mode=None):
    """softmax over the last dim of masked ``scale * x``. ``m`` is an
    optional fp32 mask tile applied in-kernel after the scale multiply —
    added when ``mask_mode == "add"``, or a 0/1 fill indicator replacing
    masked lanes with the finite ``_NEG`` when ``mask_mode == "fill"``
    (boolean-mask reference semantics: fully-masked rows degrade to
    uniform). Constant wrt autodiff, so the softmax backward is
    unchanged."""
    sq = x.shape[-2] if causal else 0
    return _softmax_impl(x, m, scale, causal, sq, mask_mode)


def _fs_fwd(x, m, scale, causal, mask_mode):
    sq = x.shape[-2] if causal else 0
    y = _softmax_impl(x, m, scale, causal, sq, mask_mode)
    return y, (y, m)


def _mask_cotangent(y, g, m, mask_mode):
    """d loss / d additive-mask. The mask enters as ``scale*x + m``, so
    its cotangent is the softmax backward WITHOUT the scale factor,
    summed back over the mask's broadcast axes. "fill" masks are 0/1
    indicators (boolean origin) — no meaningful cotangent."""
    from apex_tpu.ops._common import match_vma

    if m is None or mask_mode != "add":
        return None
    yf = y.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    dot = jnp.sum(gf * yf, axis=-1, keepdims=True)
    dm = yf * (gf - dot)
    mshape = (1,) * (dm.ndim - m.ndim) + tuple(m.shape)
    axes = tuple(i for i in range(dm.ndim)
                 if mshape[i] == 1 and dm.shape[i] != 1)
    if axes:
        dm = jnp.sum(dm, axis=axes, keepdims=True)
    return match_vma(dm.reshape(m.shape).astype(m.dtype), m)


def _fs_bwd(scale, causal, mask_mode, res, g):
    from apex_tpu.ops._common import match_vma, use_jnp_fallback

    y, m = res
    dm = _mask_cotangent(y, g, m, mask_mode)
    if use_jnp_fallback(y, g):
        yf = y.astype(jnp.float32)
        gf = g.astype(jnp.float32)
        dot = jnp.sum(gf * yf, axis=-1, keepdims=True)
        return (match_vma((scale * yf * (gf - dot)).astype(g.dtype), y),
                dm)
    y2, lead, n, k = _prep(y)
    g2, _, _, _ = _prep(g)
    dx2 = _pallas_softmax_bwd(g2, y2, scale=scale)
    return (match_vma(dx2[:n, :k].reshape(*lead, k), y), dm)


_fused_softmax.defvjp(_fs_fwd, _fs_bwd)


def scaled_softmax(x, scale: float = 1.0):
    """softmax(scale * x) (reference: ``scaled_softmax_cuda``)."""
    return _fused_softmax(x, None, float(scale), False, None)


def scaled_masked_softmax(x, mask, scale: float = 1.0,
                          causal: bool = False):
    """softmax(scale * x + mask) for a padding mask (reference:
    ``scaled_masked_softmax_cuda``). ``mask`` is boolean (True = masked,
    the reference convention) or additive float; broadcastable to x.
    Any ``scale`` (including <= 0) is supported — like the reference,
    the mask is applied after the scale multiply.

    Two kernel routes, chosen for traffic:
    - boolean mask with a scale where the large-negative fill divides
      exactly (the overwhelmingly common attention case): pre-fold
      ``fill/scale`` into x host-side — the ``where`` fuses into the
      kernel's input producer, zero extra HBM reads, and the in-kernel
      multiply restores the exact fill;
    - anything else (float masks, scale <= 0, fills that would clamp):
      pass the mask into the kernel as an additive fp32 tile applied
      after the scale — reference-order semantics at the cost of one
      extra tensor read."""
    scale = float(scale)
    if mask is None:
        return _fused_softmax(x, None, scale, causal, None)
    if (mask.dtype == jnp.bool_ and scale > 0.0
            and _NEG / scale >= float(jnp.finfo(x.dtype).min)):
        x = jnp.where(mask, jnp.asarray(_NEG / scale, x.dtype), x)
        return _fused_softmax(x, None, scale, causal, None)
    if mask.dtype == jnp.bool_:
        return _fused_softmax(x, mask.astype(jnp.float32), scale, causal,
                              "fill")
    return _fused_softmax(x, mask.astype(jnp.float32), scale, causal, "add")


def scaled_upper_triang_masked_softmax(x, scale: float = 1.0):
    """Causal softmax(scale * x) over (..., sq, sk) with sq == sk
    (reference: ``scaled_upper_triang_masked_softmax_cuda``); the causal
    mask is generated in-kernel."""
    if x.shape[-1] != x.shape[-2]:
        raise ValueError("causal softmax requires square (sq, sk) trailing dims")
    return _fused_softmax(x, None, float(scale), True, None)


def softmax_reference(x, mask=None, scale=1.0, causal=False):
    """Pure-jnp reference for tests."""
    xf = x.astype(jnp.float32) * scale
    if mask is not None:
        if mask.dtype == jnp.bool_:
            xf = jnp.where(mask, _NEG, xf)
        else:
            xf = xf + mask
    if causal:
        q = xf.shape[-2]
        kk = xf.shape[-1]
        tri = jnp.tril(jnp.ones((q, kk), bool))
        xf = jnp.where(tri, xf, _NEG)
    return jax.nn.softmax(xf, axis=-1).astype(x.dtype)
