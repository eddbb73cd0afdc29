"""Fused Pallas read kernel for paged attention (docs/serving.md).

The serving decode/prefill read chain —
:func:`apex_tpu.ops.flash_attention.paged_prefill_attention` — is a
gather (block table -> pool rows), a position mask, an fp32 softmax,
and a weighted sum. The composed XLA form materializes the gathered
``[B, ctx_max, H, D]`` K and V (two full copies of every resident
token's cache, per layer, per dispatch) before attending. This module
fuses the whole chain into ONE ``pallas_call``: the kernel walks the
block table with the scalar-prefetch pattern (the table rides in SMEM
and the ``BlockSpec`` index map picks which pool block each grid step
streams into VMEM), so gathered K/V tiles live only in VMEM and HBM
traffic drops to one pass over the pool rows the table actually names
plus the ``[B, C, H, D]`` output.

READ SIDE ONLY, by design: the first round's lesson
is that Pallas TPU has no scatter lowering — the K/V *writes*
(:func:`apex_tpu.serving.kv_cache.write_kv`) stay in XLA, whose
``scatter mode="drop"`` is exactly right for them, and the kernel
reads what XLA wrote.

Numerical contract (certified in tests/test_kv_memory.py, interpret
mode): the kernel performs the SAME primitive sequence as the XLA
chain — fp32 einsum scores, the shared finite ``FILL`` mask,
``jax.nn.softmax`` over the full context row (NOT an online-softmax
recurrence: scores for one batch lane accumulate in a VMEM scratch
across the table walk and normalize once), one fp32 einsum weighted
sum — so the fp path is BIT-IDENTICAL to the XLA fallback, decode
(C == 1) included. Two structural choices are load-bearing for that:
the grid is ``(B, num_table_entries)`` with ALL heads per kernel step,
and both contractions are head-batched einsums — per-head 2-D
matmuls (or a per-head grid axis) lower the C == 1 GEMV with a
different XLA:CPU reduction order and drift by 1 ulp. Quantized pools
(int8/fp8 + per-row scales) dequantize inside the kernel, tile by
tile, and certify against the XLA dequantizing chain to tight
tolerance.

Selection: ``paged_prefill_attention(..., use_pallas=True)`` or the
``APEX_PAGED_ATTENTION_PALLAS=1`` env flag (read at trace time); the
static shape gate (:func:`pallas_paged_read_supported`) keeps the XLA
chain as the universal fallback — interpret mode (every non-TPU
backend) always qualifies, native TPU additionally needs lane/sublane-
tileable blocks and a VMEM-feasible score scratch.

SINGLE-DEVICE ONLY: ``pallas_call`` has no SPMD partitioning rule, so
the kernel cannot run over a GSPMD-sharded pool (docs/serving.md
"Mesh sharding" — the engine rejects the env flag when its mesh's
``model`` axis is > 1, where the XLA chain partitions collective-free
instead; a future shard_map-wrapped variant could lift this).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._common import interpret_mode as _interpret

# the shared finite masked fill (ops/flash_attention.FILL) — redeclared
# here to avoid a circular import; the equality is pinned by a test
FILL = -30000.0

_ENV_FLAG = "APEX_PAGED_ATTENTION_PALLAS"

# native-TPU VMEM budget for the kernel's scratch (score buffer +
# gathered V); shapes past it fall back to the XLA chain
_VMEM_SCRATCH_BUDGET = 8 * 1024 * 1024


def pallas_paged_read_wanted(use_pallas=None) -> bool:
    """Whether the caller asked for the fused kernel: an explicit
    ``use_pallas`` wins; ``None`` consults the env flag (read at trace
    time — set it before the engine compiles its programs)."""
    if use_pallas is not None:
        return bool(use_pallas)
    return os.environ.get(_ENV_FLAG, "").strip().lower() in (
        "1", "true", "on", "yes")


def pallas_paged_read_supported(k_pages, num_table_entries=None,
                                chunk=None) -> bool:
    """Static shape gate for the native kernel: pool rows must be
    Mosaic-tileable ((bs, H*D) tiles — lane dim a 128 multiple,
    sublane a multiple of 8) and the full-softmax scratch must fit
    VMEM. Interpret mode (every non-TPU backend) has no tiling
    constraints and always qualifies — which is also what lets the
    CPU equivalence tests drive every shape the engine uses."""
    if _interpret():
        return True
    _, bs, H, D = k_pages.shape
    if (H * D) % 128 != 0 or bs % 8 != 0:
        return False
    if num_table_entries is not None and chunk is not None:
        ctx = num_table_entries * bs
        scratch = 4 * (H * chunk * ctx + ctx * H * D)
        if scratch > _VMEM_SCRATCH_BUDGET:
            return False
    return True


def _read_kernel(tbl_ref, ctx_ref, qpos_ref, q_ref, k_ref, v_ref, *rest,
                 scale, bs, C, H, D, M, decode, quant):
    """One (batch b, table step i) grid step: stream pool block
    ``tbl[b, i]``'s full rows (all heads) into VMEM, score them
    against the lane's whole query chunk into the score scratch, park
    the (dequantized) V rows in the value scratch; the LAST table step
    normalizes the full context row and emits the output —
    full-softmax semantics, accumulated across the walk, so the math
    (and on the fp path the bits) equals the composed XLA chain."""
    if quant:
        ks_ref, vs_ref, o_ref, s_buf, v_buf = rest
    else:
        ks_ref, vs_ref = None, None
        o_ref, s_buf, v_buf = rest
    b = pl.program_id(0)
    i = pl.program_id(1)

    q = q_ref[0].reshape(C, H, D).astype(jnp.float32)
    k = k_ref[0].reshape(bs, H, D).astype(jnp.float32)
    v = v_ref[0].reshape(bs, H, D).astype(jnp.float32)
    if quant:
        k = k * ks_ref[0][:, :, None]             # (bs, H) scale rows
        v = v * vs_ref[0][:, :, None]
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32) * scale

    # the block's absolute key positions; same mask algebra as the XLA
    # chain (decode: the collapsed single comparison; prefill/verify:
    # causal-by-absolute-position AND the context-length bound)
    kpos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    ctx = ctx_ref[b]
    if decode:
        visible = jnp.broadcast_to(kpos < ctx, (C, bs))
    else:
        qpos = qpos_ref[b, :][:, None]            # (C, 1)
        visible = (kpos <= qpos) & (kpos < ctx)
    s = jnp.where(visible[None], s, FILL)         # (H, C, bs)
    s_buf[:, :, pl.ds(i * bs, bs)] = s
    v_buf[pl.ds(i * bs, bs), :] = v.reshape(bs, H * D)

    @pl.when(i == M - 1)
    def _finish():
        p = jax.nn.softmax(s_buf[:], axis=-1)     # (H, C, M*bs)
        out = jnp.einsum("hqk,khd->qhd", p,
                         v_buf[:].reshape(M * bs, H, D),
                         preferred_element_type=jnp.float32)
        o_ref[0] = out.reshape(C, H * D).astype(o_ref.dtype)


def paged_read_attention(q, k_pages, v_pages, block_tables, q_positions,
                         context_lens, scale: float = 1.0,
                         k_scales=None, v_scales=None):
    """The fused read chain: same signature semantics as
    :func:`apex_tpu.ops.flash_attention.paged_prefill_attention`
    (``q_positions=None`` = the decode fast path). Callers normally
    reach this THROUGH ``paged_prefill_attention(use_pallas=...)``,
    which owns the flag/gate/fallback arbitration."""
    B, C, H, D = q.shape
    N, bs = k_pages.shape[0], k_pages.shape[1]
    M = block_tables.shape[1]
    quant = k_scales is not None
    decode = q_positions is None

    # the pool's trailing (H, D) collapses to H*D so one block's rows
    # are a contiguous tile (metadata reshape, no copy); the table
    # clips exactly like the XLA chain (device convention:
    # out-of-bounds id for unmapped entries — their rows are read but
    # masked by context_lens)
    tbl = jnp.minimum(block_tables, N - 1).astype(jnp.int32)
    ctx = jnp.asarray(context_lens, jnp.int32)
    qpos = (jnp.zeros((B, C), jnp.int32) if decode
            else jnp.asarray(q_positions, jnp.int32))

    kernel = functools.partial(
        _read_kernel, scale=scale, bs=bs, C=C, H=H, D=D, M=M,
        decode=decode, quant=quant)
    # index maps see the scalar-prefetch refs after the grid indices:
    # the table ref IS the gather — grid step (b, i) streams pool
    # block tbl[b, i]'s rows
    in_specs = [
        pl.BlockSpec((1, C, H * D), lambda b, i, t, c, p: (b, 0, 0)),
        pl.BlockSpec((1, bs, H * D),
                     lambda b, i, t, c, p: (t[b, i], 0, 0)),
        pl.BlockSpec((1, bs, H * D),
                     lambda b, i, t, c, p: (t[b, i], 0, 0)),
    ]
    inputs = [q.reshape(B, C, H * D), k_pages.reshape(N, bs, H * D),
              v_pages.reshape(N, bs, H * D)]
    if quant:
        in_specs += [
            pl.BlockSpec((1, bs, H),
                         lambda b, i, t, c, p: (t[b, i], 0, 0)),
            pl.BlockSpec((1, bs, H),
                         lambda b, i, t, c, p: (t[b, i], 0, 0)),
        ]
        inputs += [k_scales.astype(jnp.float32),
                   v_scales.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, C, H * D),
                               lambda b, i, t, c, p: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, C, M * bs), jnp.float32),
            pltpu.VMEM((M * bs, H * D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, H * D), q.dtype),
        interpret=_interpret(),
    )(tbl, ctx, qpos, *inputs)
    return out.reshape(B, C, H, D)
