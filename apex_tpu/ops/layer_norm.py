"""Pallas TPU kernels: fused LayerNorm / RMSNorm forward + backward.

Rebuild of the reference's ``csrc/layer_norm_cuda_kernel.cu`` (SURVEY.md
§2.2 — an explicit north-star item): LayerNorm and RMSNorm fwd/bwd with
affine and mixed-dtype variants (low-precision activations, fp32 weights —
the ``MixedFused*`` / ``*AffineMixedDtypes`` surface).

TPU design notes:
- One grid dimension over row blocks; each kernel instance normalizes a
  ``(block_rows, H)`` tile resident in VMEM. Row statistics are plain VPU
  reductions along the lane dimension — the Welford/warp-shuffle machinery
  of the CUDA kernel exists to cope with rows spread across threads, which
  has no analog here.
- The backward kernel *recomputes* (mean, rstd) from the x tile instead of
  saving them: on TPU the recompute is two cheap VPU reductions over data
  already in VMEM, cheaper than an extra HBM round-trip — the
  rematerialization idiom (and the semantics of the reference's
  ``memory_efficient=True`` mode, which it reaches by reconstructing
  inputs).
- Backward computes dx in one pass and ACCUMULATES dgamma/dbeta in-kernel
  across the sequential row-block grid into one VMEM-resident (8, H)
  output block (constant index map) — where the CUDA
  ``cuComputeGradGammaBeta`` needs a second kernel pass over a partials
  buffer, the TPU grid's sequential execution makes the reduction free.
- All in-kernel arithmetic is fp32 regardless of I/O dtype (matching the
  CUDA kernels' float accumulators).
- H is padded to the 128-lane width by the wrapper when needed; padded
  columns are masked in-kernel and statistics divide by the true H.

On non-TPU backends the same kernels run under ``interpret=True`` so the
test suite exercises identical code paths on the 8-device CPU sim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._common import (
    LANE,
    interpret_mode as _interpret,
    out_struct,
    round_up as _round_up,
)


def _block_rows(n_rows: int, hpad: int) -> int:
    """Row-block size, tuned per hidden size (the role of the reference's
    contrib ``fast_layer_norm`` per-hidden-size kernels): keep the fp32
    working tile near ~2 MB so VMEM holds the in/out/scratch set at any
    H — 256 rows up to H=2048, shrinking for wider rows (H=8192 -> 64
    rows) instead of blowing the ~16 MB budget."""
    budget_rows = max(2 * 1024 * 1024 // (hpad * 4), 8)
    cap = min(256, _round_up(budget_rows, 8))
    if n_rows >= cap:
        return cap
    return _round_up(max(n_rows, 1), 8)


def _stats(x, true_h, rms):
    """fp32 (mean, rstd) of the valid columns of a padded fp32 tile."""
    h = jnp.float32(true_h)
    if rms:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
    else:
        mean = jnp.sum(x, axis=1, keepdims=True) / h
    centered = x - mean
    return mean, centered


def _mask_tile(x, true_h):
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(col < true_h, x, 0.0)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, *, eps, true_h, rms, padded):
    x = x_ref[:].astype(jnp.float32)
    if padded:
        x = _mask_tile(x, true_h)
    h = jnp.float32(true_h)
    mean, centered = _stats(x, true_h, rms)
    if padded:
        centered = _mask_tile(centered, true_h)
    var = jnp.sum(centered * centered, axis=1, keepdims=True) / h
    rstd = jax.lax.rsqrt(var + eps)
    y = centered * rstd * w_ref[:].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)


def _fwd_kernel_b(x_ref, w_ref, b_ref, y_ref, **kw):
    _fwd_kernel(x_ref, w_ref, b_ref, y_ref, **kw)


def _fwd_kernel_nb(x_ref, w_ref, y_ref, **kw):
    _fwd_kernel(x_ref, w_ref, None, y_ref, **kw)


def _bwd_kernel(g_ref, x_ref, w_ref, dx_ref, dw_ref, db_ref, dw_s, db_s,
                *, eps, true_h, rms, padded):
    i = pl.program_id(0)
    g = g_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    if padded:
        g = _mask_tile(g, true_h)
        x = _mask_tile(x, true_h)
    h = jnp.float32(true_h)

    mean, centered = _stats(x, true_h, rms)
    if padded:
        centered = _mask_tile(centered, true_h)
    var = jnp.sum(centered * centered, axis=1, keepdims=True) / h
    rstd = jax.lax.rsqrt(var + eps)
    xhat = centered * rstd
    wg = g * w

    # dgamma/dbeta accumulate IN-KERNEL across the sequential row-block
    # grid in VMEM scratch, flushed to the (8, H) outputs at the last
    # step — no (grid*8, H) partial buffer in HBM, no host-side
    # reduction over it (round-3 design summed grid*8 rows outside).
    # Scratch (not a revisited output block) keeps the accumulator out
    # of Mosaic's output-DMA pipeline: accumulating directly into a
    # constant-index output block measured 0.66x (inter-step
    # read-after-write stalls), scratch restores full overlap. Partials
    # stay 8 sublanes tall (the fp32 min tile): each block's (br, H)
    # product folds to (br/8, 8, H) -> sum over axis 0, and the caller
    # sums the final 8 rows.
    br = x.shape[0]
    dw_p = jnp.sum((g * xhat).reshape(br // 8, 8, x.shape[1]), axis=0)
    db_p = jnp.sum(g.reshape(br // 8, 8, x.shape[1]), axis=0)

    @pl.when(i == 0)
    def _init():
        dw_s[:] = dw_p
        db_s[:] = db_p

    @pl.when(i > 0)
    def _acc():
        dw_s[:] = dw_s[:] + dw_p
        db_s[:] = db_s[:] + db_p

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        dw_ref[:] = dw_s[:]
        db_ref[:] = db_s[:]

    # dx (standard fused layernorm backward)
    c1 = jnp.sum(wg * xhat, axis=1, keepdims=True) / h
    if rms:
        dx = (wg - xhat * c1) * rstd
    else:
        c2 = jnp.sum(wg, axis=1, keepdims=True) / h
        dx = (wg - xhat * c1 - c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _pallas_forward(x2, weight, bias, *, eps, true_h, rms):
    n, hpad = x2.shape
    br = _block_rows(n, hpad)
    kernel = functools.partial(
        _fwd_kernel_nb if bias is None else _fwd_kernel_b,
        eps=eps, true_h=true_h, rms=rms, padded=(true_h != hpad),
    )
    in_specs = [
        pl.BlockSpec((br, hpad), lambda i: (i, 0)),
        pl.BlockSpec((hpad,), lambda i: (0,)),
    ]
    args = [x2, weight]
    if bias is not None:
        in_specs.append(pl.BlockSpec((hpad,), lambda i: (0,)))
        args.append(bias)
    return pl.pallas_call(
        kernel,
        grid=(n // br,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, hpad), lambda i: (i, 0)),
        out_shape=out_struct((n, hpad), x2.dtype, *args),
        name="layer_norm_fwd",
        interpret=_interpret(),
    )(*args)


def _pallas_backward(g2, x2, weight, *, eps, true_h, rms):
    n, hpad = x2.shape
    br = _block_rows(n, hpad)
    grid = n // br
    kernel = functools.partial(
        _bwd_kernel, eps=eps, true_h=true_h, rms=rms, padded=(true_h != hpad),
    )
    dx, dw_part, db_part = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, hpad), lambda i: (i, 0)),
            pl.BlockSpec((br, hpad), lambda i: (i, 0)),
            pl.BlockSpec((hpad,), lambda i: (0,)),
        ],
        out_specs=(
            pl.BlockSpec((br, hpad), lambda i: (i, 0)),
            # constant index maps: the (8, H) accumulators stay VMEM-
            # resident across the whole sequential grid (see _bwd_kernel)
            pl.BlockSpec((8, hpad), lambda i: (0, 0)),
            pl.BlockSpec((8, hpad), lambda i: (0, 0)),
        ),
        out_shape=(
            out_struct((n, hpad), g2.dtype, g2, x2, weight),
            out_struct((8, hpad), jnp.float32, g2, x2, weight),
            out_struct((8, hpad), jnp.float32, g2, x2, weight),
        ),
        scratch_shapes=[
            pltpu.VMEM((8, hpad), jnp.float32),
            pltpu.VMEM((8, hpad), jnp.float32),
        ],
        name="layer_norm_bwd",
        interpret=_interpret(),
    )(g2, x2, weight)
    return dx, dw_part.sum(axis=0), db_part.sum(axis=0)


# ---------------------------------------------------------------------------
# public functional API (custom_vjp)
# ---------------------------------------------------------------------------

def _prep(x, weight, bias):
    """Flatten leading dims; pad H to the lane width and N to the row-block
    size (padded rows are zeros: their stats are finite and their outputs
    are sliced away; in backward their zero grads contribute nothing)."""
    h = x.shape[-1]
    lead = x.shape[:-1]
    n = 1
    for d in lead:
        n *= d
    x2 = x.reshape(n, h)
    hpad = _round_up(h, LANE)
    npad = _round_up(n, _block_rows(n, hpad))
    if hpad != h or npad != n:
        x2 = jnp.pad(x2, ((0, npad - n), (0, hpad - h)))
        weight = jnp.pad(weight, (0, hpad - h))
        if bias is not None:
            bias = jnp.pad(bias, (0, hpad - h))
    return x2, weight, bias, lead, n, h, hpad


# Widest hidden size the Pallas training path wins at (v5e, marginal
# timing 2026-07-31): at H=1024 the kernels match XLA fusion at roofline
# and win ~3 ms/step at the BERT-large headline (in-kernel dgamma
# accumulation); at H in {4096, 8192} the lane-dim reductions over wide
# rows lose to XLA's fusion by ~1.4x — wide rows dispatch to the jnp
# formula (XLA autodiff) instead.
_PALLAS_MAX_H = 2048


def _fwd_impl(x, weight, bias, eps, rms):
    from apex_tpu.ops._common import use_jnp_fallback

    if use_jnp_fallback(x, weight, bias) or x.shape[-1] > _PALLAS_MAX_H:
        if rms:
            return rms_norm_reference(x, weight, eps)
        return layer_norm_reference(x, weight, bias, eps)
    x2, w2, b2, lead, n, h, hpad = _prep(x, weight, bias)
    y2 = _pallas_forward(x2, w2, b2, eps=eps, true_h=h, rms=rms)
    return y2[:n, :h].reshape(*lead, h)


def _bwd_jnp(g, x, weight, eps, rms):
    """Same math as _bwd_kernel, in plain jnp (interpreter fallback)."""
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    w = weight.astype(jnp.float32)
    if rms:
        mean = 0.0
    else:
        mean = xf.mean(-1, keepdims=True)
    centered = xf - mean
    var = (centered * centered).mean(-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = centered * rstd
    wg = gf * w
    c1 = (wg * xhat).mean(-1, keepdims=True)
    if rms:
        dx = (wg - xhat * c1) * rstd
    else:
        c2 = wg.mean(-1, keepdims=True)
        dx = (wg - xhat * c1 - c2) * rstd
    reduce_axes = tuple(range(x.ndim - 1))
    dw = jnp.sum(gf * xhat, axis=reduce_axes)
    db = jnp.sum(gf, axis=reduce_axes)
    return dx.astype(x.dtype), dw, db


def _bwd_impl(g, x, weight, eps, rms):
    from apex_tpu.ops._common import use_jnp_fallback

    if use_jnp_fallback(g, x, weight) or x.shape[-1] > _PALLAS_MAX_H:
        return _bwd_jnp(g, x, weight, eps, rms)
    x2, w2, _, lead, n, h, hpad = _prep(x, weight, None)
    g2 = g.reshape(n, h)
    npad = x2.shape[0]
    if hpad != h or npad != n:
        g2 = jnp.pad(g2, ((0, npad - n), (0, hpad - h)))
    dx2, dw, db = _pallas_backward(g2, x2, w2, eps=eps, true_h=h, rms=rms)
    return dx2[:n, :h].reshape(*lead, h), dw[:h], db[:h]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_layer_norm_affine(x, weight, bias, eps: float = 1e-5,
                            memory_efficient: bool = True):
    """LayerNorm with affine transform, Pallas-fused fwd+bwd.

    Reference surface: ``FusedLayerNormAffineFunction`` /
    ``FusedLayerNormAffineMixedDtypesFunction``
    (``apex/normalization/fused_layer_norm.py``). Mixed-dtype by
    construction: any floating x with fp32 (or matching) weight/bias;
    output dtype follows x. ``memory_efficient`` is accepted for parity —
    the TPU backward always recomputes statistics (see module docstring).

    Mode-dependent kernel selection (docs/kernels.md measured table):
    this primal body runs only when the call is NOT being differentiated
    (inference/serving), where letting XLA fuse the jnp formula into its
    neighbors beats the standalone Pallas kernel by ~9 ms at BERT-large
    shapes (a separate kernel is an HBM fusion barrier). Under autodiff,
    custom_vjp dispatches to ``_ln_affine_fwd`` instead — the Pallas
    fwd+bwd pair, the measured-best training combination.

    Numerical parity note: the two bodies agree to float rounding but are
    NOT bitwise identical (jnp two-pass moments vs the kernel's Welford
    accumulation in a different summation order), so the same call can
    yield bitwise-different outputs depending on differentiation context.
    Train-vs-eval logit-matching tests must compare with a dtype-scaled
    tolerance, not exact equality.
    """
    return layer_norm_reference(x, weight, bias, eps)


# Training-path forward selection (round 5). Measured on v5e at the
# (8192, 1024) transformer-layer shape (LN between GEMMs, fwd+bwd,
# marginal timing): XLA-fused jnp fwd + Pallas bwd = 5.19 ms/call vs
# 7.01 stock-XLA and 7.23 all-Pallas — the standalone Pallas fwd kernel
# is an HBM fusion barrier between the LN and the GEMM that consumes
# it, while the Pallas BWD pair (one-pass dx + in-kernel dgamma/dbeta
# accumulation, recomputed stats) beats XLA's save-xhat autodiff. The
# "pallas" setting keeps the all-Pallas fwd for A/B runs.
def _ln_fwd_mode() -> str:
    # read per TRACE (not per import) so APEX_TPU_LN_FWD set mid-process
    # affects subsequent jit traces; already-compiled programs keep the
    # mode they were traced with (the jit cache does not key on env)
    import os

    return os.environ.get("APEX_TPU_LN_FWD", "xla")


def _ln_affine_fwd(x, weight, bias, eps, memory_efficient):
    if _ln_fwd_mode() == "pallas":
        return _fwd_impl(x, weight, bias, eps, rms=False), (x, weight)
    return layer_norm_reference(x, weight, bias, eps), (x, weight)


def _ln_affine_bwd(eps, memory_efficient, res, g):
    from apex_tpu.ops._common import match_vma

    x, weight = res
    dx, dw, db = _bwd_impl(g, x, weight, eps, rms=False)
    return (
        match_vma(dx, x),
        match_vma(dw.astype(weight.dtype), weight),
        match_vma(db.astype(weight.dtype), weight),
    )


fused_layer_norm_affine.defvjp(_ln_affine_fwd, _ln_affine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_rms_norm_affine(x, weight, eps: float = 1e-5,
                          memory_efficient: bool = True):
    """RMSNorm with affine transform, Pallas-fused fwd+bwd.

    Reference surface: ``FusedRMSNormAffineFunction`` /
    ``FusedRMSNormAffineMixedDtypesFunction``. Same mode-dependent
    kernel selection as :func:`fused_layer_norm_affine`: jnp (XLA-fused)
    when not differentiating, Pallas fwd+bwd under autodiff — and the
    same parity caveat: the two bodies agree to rounding, not bitwise."""
    return rms_norm_reference(x, weight, eps)


def _rms_affine_fwd(x, weight, eps, memory_efficient):
    if _ln_fwd_mode() == "pallas":
        return _fwd_impl(x, weight, None, eps, rms=True), (x, weight)
    return rms_norm_reference(x, weight, eps), (x, weight)


def _rms_affine_bwd(eps, memory_efficient, res, g):
    from apex_tpu.ops._common import match_vma

    x, weight = res
    dx, dw, _ = _bwd_impl(g, x, weight, eps, rms=True)
    return match_vma(dx, x), match_vma(dw.astype(weight.dtype), weight)


fused_rms_norm_affine.defvjp(_rms_affine_fwd, _rms_affine_bwd)


def fused_layer_norm(x, normalized_shape=None, eps: float = 1e-5):
    """Elementwise-affine-free LayerNorm (reference: ``fused_layer_norm``)."""
    h = x.shape[-1]
    w = jnp.ones((h,), jnp.float32)
    b = jnp.zeros((h,), jnp.float32)
    return fused_layer_norm_affine(x, w, b, eps)


def fused_rms_norm(x, normalized_shape=None, eps: float = 1e-5):
    """Affine-free RMSNorm (reference: ``fused_rms_norm``)."""
    h = x.shape[-1]
    w = jnp.ones((h,), jnp.float32)
    return fused_rms_norm_affine(x, w, eps)


# Pure-jnp references (used by tests and as a documented fallback).

def layer_norm_reference(x, weight, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_reference(x, weight, eps=1e-5):
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps) * weight.astype(jnp.float32)
    return y.astype(x.dtype)
