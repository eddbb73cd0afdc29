"""Pallas TPU fused dropout: hardware-PRNG mask, regenerated in backward.

Why this exists (measured on v5e, BERT-large B=16 S=512): the composed
``nn.Dropout`` path draws its masks from JAX's threefry, which is pure
ALU work on the VPU — the ~49 hidden-dropout sites of a BERT-large step
cost ~42 ms/step, dwarfing the attention-dropout kernel (~3.5 ms). The
reference never pays this because cuDNN/Philox dropout is fused into its
kernels (``apex/contrib/csrc/multihead_attn/`` dropout epilogues). Here:

- forward: one elementwise Pallas pass; the keep-mask comes from the TPU
  hardware PRNG (``pltpu.prng_seed``/``prng_random_bits``) seeded by
  (user seed, tile id) — no mask tensor is ever written to HBM;
- backward: the cotangent pass re-seeds identically and replays the
  exact mask — dropout becomes pure bandwidth (read + write) with zero
  mask storage and zero threefry FLOPs.

Interpret mode (CPU sim) has no TPU PRNG: the same kernel takes
precomputed uint32 bits generated host-side from the seed (deterministic
across fwd/bwd). Under shard_map-on-CPU vma contexts a pure-jnp replica
of the kernel runs on the SAME bits/threshold/layout — bit-identical, so
a forward/backward pair may take different routes without mask skew.
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
    keep_threshold as _keep_threshold,
    match_vma,
    round_up as _round_up,
    use_jnp_fallback,
)

_BLOCK_R = 512  # (512, 512) f32 tile = 1 MB VMEM; bandwidth-bound anyway
_BLOCK_C = 512


def _kernel(x_ref, *rest, rate, native_prng):
    if native_prng:
        seed_ref, o_ref = rest
        pltpu.prng_seed(seed_ref[0], pl.program_id(0))
        bits = pltpu.bitcast(
            pltpu.prng_random_bits(x_ref.shape[1:]), jnp.uint32)
    else:
        bits_ref, o_ref = rest
        bits = bits_ref[0]
    keep = bits < _keep_threshold(rate)
    x = x_ref[0]
    o_ref[0] = jnp.where(keep, x * (1.0 / (1.0 - rate)),
                         jnp.zeros_like(x)).astype(o_ref.dtype)


def _call(x2, drop_in, rate):
    R, C = x2.shape[1:]
    native = drop_in.ndim == 1
    extra_spec = (pl.BlockSpec(memory_space=pltpu.SMEM) if native
                  else pl.BlockSpec((1, R, C), lambda i: (i, 0, 0)))
    return pl.pallas_call(
        functools.partial(_kernel, rate=rate, native_prng=native),
        grid=(x2.shape[0],),
        in_specs=[pl.BlockSpec((1, R, C), lambda i: (i, 0, 0)), extra_spec],
        out_specs=pl.BlockSpec((1, R, C), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        name="dropout_apply",
        interpret=_interpret(),
    )(x2, drop_in)


def _shape2(n):
    """Factor a flat length into (tiles, rows, cols) tile geometry."""
    c = min(_round_up(n, LANE), _BLOCK_C)
    rows_total = _round_up(n, c) // c
    r = min(_round_up(rows_total, 8), _BLOCK_R)
    tiles = _round_up(rows_total, r) // r
    return tiles, r, c


def _drop_in(seed, tiles, r, c):
    seed = jnp.asarray(seed, jnp.int32).reshape(())
    if _interpret():
        return jax.random.bits(jax.random.PRNGKey(seed), (tiles, r, c),
                               jnp.uint32)
    return seed.reshape((1,))


def _apply(x, rate, seed, force_jnp=False):
    n = x.size
    tiles, r, c = _shape2(n)
    x2 = jnp.pad(x.reshape(-1), (0, tiles * r * c - n)).reshape(tiles, r, c)
    if force_jnp:
        # pure-jnp replica of the interpret kernel — SAME bits tensor,
        # SAME threshold, SAME padded layout — for shard_map-vma contexts
        # the Pallas HLO interpreter mishandles. Bit-identical to the
        # kernel path, so a forward/backward pair may mix routes freely.
        bits = jax.random.bits(
            jax.random.PRNGKey(jnp.asarray(seed, jnp.int32)),
            (tiles, r, c), jnp.uint32)
        y2 = jnp.where(bits < _keep_threshold(rate),
                       x2 * (1.0 / (1.0 - rate)), jnp.zeros_like(x2))
    else:
        y2 = _call(x2, _drop_in(seed, tiles, r, c), rate)
    return y2.reshape(-1)[:n].reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def fused_dropout(x, rate: float, seed=None):
    """``dropout(x, rate)`` with the keep-mask generated in-kernel and
    replayed (never stored) in the backward pass.

    Args:
      x: any-shape floating tensor.
      rate: static drop probability in [0, 1).
      seed: int32 scalar (may be traced); required when rate > 0. Vary
        per call site and step.
    """
    if rate == 0.0:
        return x
    if seed is None:
        raise ValueError("fused_dropout with rate > 0 requires a seed")
    return _apply(x, rate, seed, force_jnp=use_jnp_fallback(x))


def _fd_fwd(x, rate, seed):
    return fused_dropout(x, rate, seed), seed


def _fd_bwd(rate, seed, g):
    if rate == 0.0:
        return g, None
    # replay: dropout is self-adjoint up to the same mask/scale
    return match_vma(fused_dropout(g, rate, seed), g), None


fused_dropout.defvjp(_fd_fwd, _fd_bwd)
