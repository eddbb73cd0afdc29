"""The dropout-mask stream that the configurations state (``assumed`` in
each configuration file), generated by the benchmark itself.

A training step with dropout can be held against a reference only under
the same masks, so the stream is part of the configuration, like the
seed of the weights:

* a mask is drawn in tiles; tile ``t`` of a site is the chip's hardware
  PRNG seeded with ``(site seed, t)`` and asked for one block of bits;
  an element is kept where its bits, read as uint32, lie under
  ``min(int((1 - rate) * 2**32), 2**32 - 1)``;
* an elementwise site flattens its tensor and cuts it into tiles of
  ``r x c`` (at most 512 x 512, see :func:`flat_geometry`);
* an attention site has one tile per ``(batch row, head, query block,
  key block)``, numbered in that order, of ``bq x bk`` with
  ``bq = min(512, S rounded up to 128)``.

Off the chip there is no hardware PRNG. The stand-in stream there, used
only by the CPU tests and the rehearsal, is ``jax.random.bits`` of the
site's seed over the same logical shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANE = 128
TILE = 512


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def threshold(rate: float):
    return jnp.uint32(min(int((1.0 - rate) * 4294967296.0), 4294967295))


def on_chip() -> bool:
    return jax.default_backend() == "tpu"


def flat_geometry(n: int):
    """(tiles, rows, cols) of an elementwise site over ``n`` elements."""
    c = min(_round_up(n, LANE), TILE)
    rows_total = _round_up(n, c) // c
    r = min(_round_up(rows_total, 8), TILE)
    tiles = _round_up(rows_total, r) // r
    return tiles, r, c


def score_block(seq: int) -> int:
    """Rows (= columns) of one attention score tile."""
    if seq > TILE and seq % TILE:
        raise ValueError(f"sequence {seq}: beyond {TILE} the stream is "
                         f"stated for multiples of {TILE} only")
    return min(TILE, _round_up(seq, LANE))


def _hw_tiles(seed, n_tiles: int, r: int, c: int, rate: float):
    """(n_tiles, r, c) float32 0/1 keep mask from the hardware PRNG."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(seed_ref, o_ref):
        pltpu.prng_seed(seed_ref[0], pl.program_id(0))
        bits = pltpu.bitcast(pltpu.prng_random_bits((r, c)), jnp.uint32)
        # the threshold is made inside the kernel: a captured array
        # constant is refused by pallas_call
        o_ref[0] = (bits < threshold(rate)).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((1, r, c), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, r, c), jnp.float32),
    )(jnp.asarray(seed, jnp.int32).reshape((1,)))


def elementwise_keep(seed, shape, rate: float):
    """Boolean keep mask of an elementwise dropout site over a tensor of
    ``shape``."""
    n = 1
    for s in shape:
        n *= s
    tiles, r, c = flat_geometry(n)
    if on_chip():
        keep = _hw_tiles(seed, tiles, r, c, rate) > 0.5
    else:
        bits = jax.random.bits(
            jax.random.PRNGKey(jnp.asarray(seed, jnp.int32)),
            (tiles, r, c), jnp.uint32)
        keep = bits < threshold(rate)
    return keep.reshape(-1)[:n].reshape(shape)


def attention_keep(seed, batch: int, heads: int, seq: int, rate: float):
    """Boolean ``(batch, heads, seq, seq)`` keep mask of an attention
    site's probabilities."""
    b = score_block(seq)
    sp = _round_up(seq, b)
    nb = sp // b
    if on_chip():
        keep = _hw_tiles(seed, batch * heads * nb * nb, b, b, rate) > 0.5
        keep = keep.reshape(batch, heads, nb, nb, b, b).transpose(
            0, 1, 2, 4, 3, 5).reshape(batch, heads, sp, sp)
    else:
        bits = jax.random.bits(
            jax.random.PRNGKey(jnp.asarray(seed, jnp.int32)),
            (batch, heads, sp, sp), jnp.uint32)
        keep = bits < threshold(rate)
    return keep[:, :, :seq, :seq]
