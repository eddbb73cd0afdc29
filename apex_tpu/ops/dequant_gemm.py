"""Fused Pallas dequant-GEMM for quantized weight storage.

Weight quantization (:func:`apex_tpu.models.gpt.quantize_gpt_params`)
stores the six GPT qkv/proj/mlp kernels as int8/fp8 with a
per-OUTPUT-channel fp32 scale. The read chain is: dequantize
(``w_q.astype(f32) * scale[None, :]``), then matmul. The composed XLA
form (:func:`dequant_matmul_reference`) materializes the full
dequantized ``(K, N)`` fp32 kernel in HBM on every dispatch —
surrendering the very HBM-traffic win quantization bought on the
weight-bound decode path. This module fuses the chain into ONE
``pallas_call``: the grid walks the output-channel (N) axis in lane
tiles, each step streams one int8/fp8 kernel tile plus its scale
sliver into VMEM, dequantizes in-register, and contracts the full K
axis against the activations — the fp32 weights never exist outside
VMEM, so HBM reads stay at the quantized byte width.

READ SIDE ONLY, by design: the first round's lesson
is that Pallas TPU has no scatter lowering — quantization itself (the
*write* of the quantized tree, a one-time construction-cost in
``quantize_gpt_params``) stays in XLA, and the kernel reads what XLA
wrote. Same division of labor as ``paged_attention_pallas.py``.

Numerical contract (certified in tests/test_weight_quant.py, interpret
mode): the kernel performs the SAME primitive sequence as the XLA
chain — elementwise dequant in fp32, then one fp32
``jnp.dot(..., preferred_element_type=f32)`` over the full K axis —
and the grid tiles ONLY the output-channel axis, never K. Output
column ``j`` is a K-reduction over ``x`` and ``w[:, j]`` alone, so
tiling N leaves every column's reduction order untouched and the
kernel is BIT-IDENTICAL to :func:`dequant_matmul_reference` at matrix
shapes (a K-split with a partial-sum accumulator would not be — that
is why there isn't one; K lives entirely in VMEM per step). At a
one-row (decode) shape XLA:CPU lowers the reference's matrix-vector
product with another reduction order, and the two agree to a few
float32 ulp of the accumulation; the contract is agreement with the
reference, not bit-identity to one lowering.

Selection: ``dequant_matmul(..., use_pallas=True)`` or the
``APEX_DEQUANT_GEMM_PALLAS=1`` env flag (read at trace time); the
static shape gate (:func:`dequant_gemm_supported`) keeps the XLA
chain as the universal fallback — interpret mode (every non-TPU
backend) always qualifies, native TPU additionally needs
lane/sublane-tileable operands and a VMEM-feasible working set.

SINGLE-DEVICE ONLY: ``pallas_call`` has no SPMD partitioning rule, so
the kernel cannot run over GSPMD-sharded kernels (docs/serving.md
"Mesh sharding" — the engine rejects the env flag when its mesh's
``model`` axis is > 1, where the XLA chain partitions collective-free
instead, scales riding their kernel's shard).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._common import interpret_mode as _interpret

_ENV_FLAG = "APEX_DEQUANT_GEMM_PALLAS"

# native-TPU VMEM budget for one grid step's working set (activations +
# kernel tile + output tile, fp32); shapes past it fall back to XLA
_VMEM_BUDGET = 8 * 1024 * 1024

_LANE_TILE = 128


def dequant_gemm_wanted(use_pallas=None) -> bool:
    """Whether the caller asked for the fused kernel: an explicit
    ``use_pallas`` wins; ``None`` consults the env flag (read at trace
    time — set it before the engine compiles its programs)."""
    if use_pallas is not None:
        return bool(use_pallas)
    return os.environ.get(_ENV_FLAG, "").strip().lower() in (
        "1", "true", "on", "yes")


def dequant_gemm_supported(m: int, k: int, n: int) -> bool:
    """Static shape gate for the native kernel: operands must be
    Mosaic-tileable (K and N lane/sublane-aligned for the int8 tile
    shape, M a sublane multiple) and one grid step's fp32 working set
    must fit VMEM. Interpret mode (every non-TPU backend) has no
    tiling constraints and always qualifies — which is what lets the
    CPU bit-identity certification drive every shape the model uses."""
    if _interpret():
        return True
    if m % 8 != 0 or k % _LANE_TILE != 0 or n % _LANE_TILE != 0:
        return False
    tn = _LANE_TILE
    if 4 * (m * k + k * tn + m * tn) > _VMEM_BUDGET:
        return False
    return True


def dequant_matmul_reference(x, w_q, scale):
    """The composed XLA dequant-then-matmul chain — the universal
    fallback and the certification reference: dequantize the whole
    kernel to fp32, one fp32 dot. ``x: (..., K)``, ``w_q: (K, N)``
    int8/fp8, ``scale: (N,)`` fp32 -> ``(..., N)`` fp32."""
    w = w_q.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]
    return jnp.dot(x.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32)


def _dequant_gemm_kernel(x_ref, w_ref, s_ref, o_ref):
    """One output-channel tile: dequantize this tile's columns in
    VMEM, contract the FULL K axis. Same two primitives, same order,
    same fp32 types as the reference — see the module docstring for
    why N-only tiling makes this bit-identical."""
    w = w_ref[...].astype(jnp.float32) * s_ref[0][None, :]
    o_ref[...] = jnp.dot(x_ref[...].astype(jnp.float32), w,
                         preferred_element_type=jnp.float32)


def _pallas_dequant_gemm(x2d, w_q, scale):
    M, K = x2d.shape
    N = w_q.shape[1]
    TN = _LANE_TILE if N % _LANE_TILE == 0 else N
    out = pl.pallas_call(
        _dequant_gemm_kernel,
        grid=(N // TN,),
        in_specs=[
            pl.BlockSpec((M, K), lambda j: (0, 0)),
            pl.BlockSpec((K, TN), lambda j: (0, j)),
            pl.BlockSpec((1, TN), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((M, TN), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=_interpret(),
    )(x2d, w_q, scale.astype(jnp.float32).reshape(1, N))
    return out


def dequant_matmul(x, w_q, scale, use_pallas=None):
    """Quantized-weight matmul: ``(..., K) @ dequant((K, N)) ->
    (..., N)`` fp32. Owns the flag/gate/fallback arbitration — the
    fused kernel runs only when wanted (explicit ``use_pallas`` or the
    ``APEX_DEQUANT_GEMM_PALLAS`` env flag) AND the static gate admits
    the shape; everything else takes :func:`dequant_matmul_reference`.
    ``QuantDense`` (models/gpt.py) is the production caller."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w_q.shape[1]
    x2d = x.reshape(-1, K)
    if (dequant_gemm_wanted(use_pallas)
            and dequant_gemm_supported(x2d.shape[0], K, N)):
        out = _pallas_dequant_gemm(x2d, w_q, scale)
    else:
        out = dequant_matmul_reference(x2d, w_q, scale)
    return out.reshape(*lead, N)
