"""Shared helpers for the Pallas kernel wrappers."""

from __future__ import annotations

import jax

LANE = 128  # TPU vector lane width (minor tile dim)


def round_up(x: int, m: int) -> int:
    """Round x up to a multiple of m (tile/lane alignment)."""
    return (x + m - 1) // m * m


def interpret_mode() -> bool:
    """Pallas kernels run compiled on TPU, interpreted elsewhere (the
    CPU-sim test path exercises identical kernel code)."""
    return jax.default_backend() != "tpu"


def keep_threshold(dropout_rate):
    """uint32 threshold shared by every fused-dropout kernel: a lane is
    kept iff its random bits are < this. keep_prob maps onto the full
    uint32 range so the kept fraction is exact to 2^-32 (the reference
    Philox kernels use the same compare-against-scaled-keep-prob
    construction)."""
    import jax.numpy as jnp

    keep = 1.0 - dropout_rate
    return jnp.uint32(min(int(keep * 4294967296.0), 4294967295))


def mix_seed(seed, n):
    """Decorrelated int32 PRNG seed from (seed, n): golden-ratio
    multiplicative hash in uint32 wraparound arithmetic, masked to
    non-negative int32. Shared by every consumer that derives per-rank /
    per-block dropout seeds (ring block pairs, Ulysses context ranks) so
    the derivation can't drift between them; sequential `seed + n` would
    give adjacent consumers correlated hardware-PRNG streams, and the
    uint32 round-trip avoids int32 overflow near 2^31."""
    import jax.numpy as jnp

    mixed = (jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
             ^ (jnp.asarray(n).astype(jnp.uint32) * jnp.uint32(0x9E3779B9)))
    return (mixed & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)


def _vma_of(x):
    """The varying-axes set of a value (empty outside ``shard_map`` and
    under ``check_vma=False``)."""
    return frozenset(jax.typeof(x).vma)


def use_jnp_fallback(*arrays) -> bool:
    """True when the Pallas interpreter cannot be used: non-TPU backend AND
    inputs varying over shard_map axes (this JAX version's HLO interpreter
    mishandles vma inside its internal loops). The jnp fallbacks compute
    the identical formulas; real TPU always takes the compiled kernels."""
    if jax.default_backend() == "tpu":
        return False
    return any(_vma_of(a) for a in arrays if a is not None)


def match_vma(cotangent, primal_example):
    """Align a custom_vjp cotangent's varying-axes set to its primal's.

    Inside ``shard_map``, autodiff inserts boundary psums for primitives
    automatically, but a custom_vjp bwd rule is on its own: if the
    incoming gradient varies over more mesh axes than the primal input
    (e.g. params replicated across ``data`` receiving data-sharded
    batch gradients), the bwd rule must psum over the extra axes itself.
    """
    want = _vma_of(primal_example)
    have = _vma_of(cotangent)
    extra = have - want
    if extra:
        cotangent = jax.lax.psum(cotangent, tuple(sorted(extra)))
    return cotangent


def out_struct(shape, dtype, *like):
    """``ShapeDtypeStruct`` whose varying-axes set is the union of the
    inputs'. Inside ``shard_map`` with vma checking, pallas_call outputs
    must declare how they vary across mesh axes; outside, the empty set is
    accepted and ignored."""
    vma = frozenset()
    for r in like:
        vma |= _vma_of(r)
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
