"""Pallas TPU flash attention: tiled online-softmax fwd + recompute bwd.

Rebuild of the reference's fused multi-head attention tier
(``apex/contrib/csrc/fmha/`` — the MLPerf-BERT seqlen<=512 kernels — and
``apex/contrib/csrc/multihead_attn/``, SURVEY.md §2.2): attention without
ever materializing the (B, H, Sq, Sk) score tensor in HBM.

TPU design notes:
- Forward: grid ``(B, H, nq, nk)`` with the key-block dimension innermost.
  Each (b, h, iq) row-block keeps fp32 running statistics (row max ``m``,
  normalizer ``l``) and an fp32 ``(bq, D)`` accumulator in VMEM scratch,
  which persists across the sequentially-executed ``ik`` steps — the
  online-softmax recurrence. Score tiles live only in VMEM; HBM traffic is
  O(S*D) instead of O(S^2). A step takes its query rows in sub-blocks of
  ``_FWD_ROWS`` (128), each its own q k^T, softmax and p v, so that the
  scheduler can run one sub-block's softmax beside another's matmuls; the
  dq kernel's step likewise, in sub-blocks of ``_DQ_ROWS`` (256).
- The padding mask is a per-key boolean (True = masked), folded in with
  the same finite ``-30000`` fill the reference kernels use (finite so
  fully-masked rows degrade to a uniform distribution instead of NaN,
  matching ``scaled_masked_softmax`` semantics). Under ``causal=True``
  past one tile, a row whose every causally visible key is user-masked
  degrades to uniform over the keys of its LIVE tiles (below), not over
  all Sk keys as the composed reference does; ``mha_reference`` is the
  specification for every row with at least one visible key.
- Attention past one tile skips the tiles its mask kills. A mask that is
  a static function of (query index, key index) - ``causal`` (``row >=
  col``) or a ``score_mask`` description (:class:`BlockDiffusionMask`,
  :class:`SlidingWindowMask`) - makes each score tile (iq, ik) of the
  multi-tile kernels (fwd, dq, dkv) dead (every pair masked), full (none)
  or partly masked; :func:`tile_classes` counts them for any kind of mask
  (causal S=1024 at 512-blocks: dead 1, partial 2, full 1; 2048: (6, 4,
  6); 640 -> 768 at 384-blocks: (1, 2, 1); block diffusion over two
  copies of L=8192 in blocks of 4: 736 dead, 48 partial, 240 full of
  1,024; a window of 2,048 over S=8192: 186 dead, 28 partial, 42 full of
  256). A dead tile
  contributed exactly 0 (``p = exp(FILL - m) = 0`` in fp32), so outputs,
  lse and gradients are bit-identical to the unskipped kernels'. Full
  tiles run the partly masked tiles' body: a maskless second body
  measured slower on the v5e (see the comment at ``_causal_dead``).
  Under ``causal`` the grid stays ``(B, H, nq, nk)`` (and with it
  ``_tile_id`` and the dropout stream): a tile's class comes from
  ``program_id`` and the static block sizes in closed form (its live
  tiles are one run a row); on a dead tile the body does not run
  (``pl.when``) and the index maps of the operands that vary along the
  inner grid axis re-name the nearest live block (k, v, key mask and the
  interpret-mode dropout bits in fwd/dq; q, do, lse, delta and the bits
  in dkv), so the pipeline sees an unchanged block index and issues no
  DMA; ``_init``/``_finish`` stay tied to the first and last inner step,
  dead or not. A ``score_mask``'s live tiles are several runs a row and
  under a third of the grid, so its kernels' grid is ``(B, H, live
  tiles)``: the list of live tiles in walking order (query block, key
  block, first / last of its row - of its column in dkv) is made from
  the description when the call is traced and read from SMEM at the grid
  step (scalar prefetch) by every index map and by the body, which runs
  ``_init`` / ``_finish`` where the list says; no grid step is spent on
  a dead tile (:func:`grid_steps`). Its element mask comes from iotas
  and the description's own arithmetic. No mask or score tensor larger
  than a tile exists anywhere.
- "No key mask" is static: with ``key_mask=None`` and no key padding the
  multi-tile kernels are built without the two key-mask selects.
- Forward also emits the per-row logsumexp; backward recomputes score
  tiles from (q, k, lse) instead of saving probabilities — the flash
  rematerialization. Two kernels: dq (grid over q blocks, accumulating
  over k blocks) and dk/dv (grid over k blocks, accumulating over q
  blocks); ``delta = rowsum(dout * out)`` is a cheap O(S*D) jnp reduction.
- All matmuls carry ``preferred_element_type=fp32`` so bf16 tiles hit the
  MXU with fp32 accumulation.
- Head dim and sequence lengths are padded to the 128-lane tile in the
  wrapper; padded keys are masked, padded query rows are sliced away (and
  receive zero cotangents in backward).
- v may be narrower than q and k (latent attention: scores over heads of
  192, values of 128): v, o, do and dv are blocked at v's own head size
  and q, k, dq and dk at theirs, with no padding of v to the wider one;
  such a call's kernels are ``flash_mla_fwd``, ``flash_mla_bwd_dq`` and
  ``flash_mla_bwd_dkv`` (``flash_mla_bwd`` at one tile).

On non-TPU backends the kernels run under ``interpret=True`` (same code
path, CPU-sim testable); a pure-jnp reference is used under shard_map vma
on CPU (see ops/_common.py) and for parity tests.

Attention dropout is FUSED (the reference fmha kernels generate their
Philox dropout in-kernel; this is the MLPerf-BERT *training* config):
- On real TPU the keep-mask is generated in-kernel from the hardware PRNG
  (``pltpu.prng_seed`` keyed by ``(seed, b, h, iq, ik)`` +
  ``prng_random_bits``), so no (B, H, Sq, Sk) mask ever touches HBM. The
  backward pass re-seeds identically per tile and replays the exact mask
  during recompute.
- The dropout multiplies the *unnormalized* probability tile only where it
  feeds the ``p @ v`` accumulation; the online-softmax statistics (m, l,
  lse) stay pre-dropout, so the math equals composed
  ``dropout(softmax(s)) @ v`` by linearity of the final ``acc / l``.
- ``delta = rowsum(dO * O)`` already equals ``rowsum(P_dropped * dP)``
  when O carries dropout, so the backward needs no extra correction — the
  keep-mask is simply replayed onto ``dp`` (and onto ``p`` for dv).
- Interpret mode (CPU sim) has no TPU PRNG; there the same kernels take a
  precomputed uint32 bits tensor generated host-side from the seed — the
  identical thresholding math, deterministic across fwd/bwd.
``flash_dropout_keep_mask`` reproduces the kernel's exact mask on either
backend so tests can compose a bit-matched reference.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import profiler
from apex_tpu.ops._common import (
    LANE,
    interpret_mode as _interpret,
    keep_threshold as _keep_threshold,
    match_vma,
    out_struct,
    round_up as _round_up,
    use_jnp_fallback,
)

FILL = -30000.0  # finite masked fill, matches ops/softmax.py



def _dot(a, b, dims, prec):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=prec)


def _prec(dtype):
    """fp32 inputs get true-fp32 MXU passes; low-precision inputs use the
    native single-pass MXU path with fp32 accumulation."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _tile_id(b, h, iq, ik, H, nq, nk):
    """Injective int32 id of score tile (iq, ik) of head (b, h) — the
    PRNG seed coordinate shared by fwd/dq/dkv regardless of their own
    grid iteration order (Mosaic's prng_seed takes at most 2 values, so
    the coordinates are flattened into one)."""
    return ((b * H + h) * nq + iq) * nk + ik


def _keep_mask(drop_ref, tile_id, bq, bk, dropout_rate, native_prng,
               interp_idx=(0, 0)):
    """(bq, bk) boolean keep-mask for one score tile.

    native_prng: seed the TPU hardware PRNG with (user seed, tile id) —
    any kernel regenerates the identical mask for the same tile.
    Otherwise drop_ref is a precomputed uint32 block (interpret mode)
    and ``interp_idx`` selects the (bq, bk) slice (head-pair kernels
    carry two heads per block)."""
    if native_prng:
        pltpu.prng_seed(drop_ref[0], tile_id)
        bits = pltpu.bitcast(pltpu.prng_random_bits((bq, bk)), jnp.uint32)
    else:
        bits = drop_ref[interp_idx]
    return bits < _keep_threshold(dropout_rate)


# ---------------------------------------------------------------------------
# causal tile classes (multi-tile kernels)
# ---------------------------------------------------------------------------
#
# Under ``causal=True`` the mask is ``row >= col`` on absolute indices, so a
# (bq, bk) score tile (iq, ik) is dead (every element masked), full (none
# masked) or diagonal. The kernels act on ``dead`` alone: a full tile runs
# the diagonal tile's body (its mask select is the identity). A second,
# maskless body for full tiles was built and measured on the v5e (PR 28):
# the mask ops are hidden under the tile step's real bound, and the second
# body cost 0.3% of GPT-2-medium's step and 12.7 MB of program memory.
# The predicates take Python ints (the static count below, the tests) or
# traced ``program_id``s (kernel bodies, index maps).

def _causal_dead(iq, ik, bq, bk):
    """Tile (iq, ik) lies wholly above the diagonal: its smallest column
    is beyond its largest row."""
    return ik * bk > iq * bq + bq - 1


def _causal_full(iq, ik, bq, bk):
    """Tile (iq, ik) lies wholly on or below the diagonal: its largest
    column is not beyond its smallest row."""
    return ik * bk + bk - 1 <= iq * bq


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask:
    """Static description of the block-diffusion training mask (Arriola et
    al., *Block Diffusion*, ICLR 2025): a row of ``seq_len`` tokens is fed
    as a NOISED copy (positions ``0 .. seq_len - 1``) followed by the CLEAN
    copy (``seq_len .. 2 seq_len - 1``), in blocks of ``block`` consecutive
    tokens, ``b(i) = (i mod seq_len) // block``. The query at ``r`` sees the
    key at ``c`` iff

    * both are noised and ``b(r) == b(c)`` (a block sees itself, both ways),
    * ``r`` is noised, ``c`` clean and ``b(c) < b(r)`` (strictly earlier
      blocks of the clean copy), or
    * both are clean and ``b(c) <= b(r)`` (block-causal);

    nothing sees forward and no clean query sees a noised key. Keys are
    always the two copies (``Sk = 2 seq_len``). ``clean_queries=False``
    describes a call whose queries are the noised copy alone (``Sq =
    seq_len``): the last layer of a stack, whose clean rows feed nothing.

    Hashable, so it rides through ``jax.custom_vjp`` as a static argument.
    ``tag`` names the kernels of such a call (``flash_<tag>_fwd`` ...), so
    that a trace tells them from the causal ones."""

    seq_len: int
    block: int
    clean_queries: bool = True
    tag = "blockdiff"

    def __post_init__(self):
        if self.block < 1 or self.seq_len < 1 or self.seq_len % self.block:
            raise ValueError(
                f"BlockDiffusionMask: seq_len ({self.seq_len}) must be a "
                f"positive multiple of block ({self.block})")

    @property
    def q_len(self) -> int:
        return (2 if self.clean_queries else 1) * self.seq_len

    @property
    def k_len(self) -> int:
        return 2 * self.seq_len

    def visible(self, row, col):
        """Boolean ``row sees col`` on absolute indices (int arrays that
        broadcast against each other; numpy or traced). Two compares at
        the broadcast shape, joined by the key's copy (and / or: Mosaic
        has no select between booleans): a noised query's block id is
        compared for equality with a noised key's (and is -1, equal to
        none, for a clean query); a clean key's block id must lie below
        the query's, plus one for a clean query."""
        L, g = self.seq_len, self.block
        row_noised, col_noised = row < L, col < L
        rb = _div(jnp.where(row_noised, row, row - L), g)
        cb = _div(jnp.where(col_noised, col, col - L), g)
        same = jnp.where(row_noised, rb, -1)
        below = jnp.where(row_noised, rb, rb + 1)
        return (col_noised & (cb == same)) | (~col_noised & (cb < below))

    def _parts(self, lo, hi):
        """The index range ``lo .. hi`` as (noised positions, clean
        positions), each ``(first, last)`` within its copy or None."""
        L = self.seq_len
        noised = (lo, min(hi, L - 1)) if lo < L else None
        clean = (max(lo, L) - L, hi - L) if hi >= L else None
        return noised, clean

    def tile_class(self, r0, r1, c0, c1) -> str:
        """``"dead"``, ``"full"`` or ``"partial"``: whether no, every or
        some pair of the rows ``r0 .. r1`` and columns ``c0 .. c1``
        (inclusive, inside the call's lengths) is visible. Python ints."""
        g = self.block
        (rn, rc), (cn, cc) = self._parts(r0, r1), self._parts(c0, c1)
        any_, all_ = False, not (rc and cn)
        if rn and cn:      # a block sees itself
            any_ |= rn[0] // g <= cn[1] // g and cn[0] // g <= rn[1] // g
            all_ &= rn[0] // g == rn[1] // g == cn[0] // g == cn[1] // g
        if rn and cc:      # strictly earlier clean blocks
            any_ |= cc[0] // g < rn[1] // g
            all_ &= cc[1] // g < rn[0] // g
        if rc and cc:      # block-causal
            any_ |= cc[0] // g <= rc[1] // g
            all_ &= cc[1] // g <= rc[0] // g
        return "dead" if not any_ else ("full" if all_ else "partial")

    def check(self, Sq, Sk):
        if (Sq, Sk) != (self.q_len, self.k_len):
            raise ValueError(
                f"{self}: the call has {Sq} queries and {Sk} keys, the "
                f"description {self.q_len} and {self.k_len}")


@dataclasses.dataclass(frozen=True)
class SlidingWindowMask:
    """Static description of the sliding-window causal mask over one
    sequence of ``seq_len`` tokens (queries and keys alike): the query at
    ``r`` sees the key at ``c`` iff ``0 <= r - c < window``, itself and
    the ``window - 1`` keys before it (transformers' sliding-window causal
    mask). A window of ``seq_len`` or more is the causal mask.

    The visible pairs lie in a band of the score matrix, so a tile's class
    is a closed form in its least and greatest ``r - c`` (``r0 - c1`` and
    ``r1 - c0``): dead where that range misses ``[0, window)``, full where
    it lies inside. At ``seq_len`` 8,192, ``window`` 2,048 and tiles of 512
    a head has 70 live tiles of 256.

    Hashable, so it rides through ``jax.custom_vjp`` as a static argument.
    ``tag`` names the kernels of such a call (``flash_window_fwd`` ...)."""

    seq_len: int
    window: int
    tag = "window"

    def __post_init__(self):
        if self.seq_len < 1 or self.window < 1:
            raise ValueError(
                f"SlidingWindowMask: seq_len ({self.seq_len}) and window "
                f"({self.window}) must be positive")

    @property
    def q_len(self) -> int:
        return self.seq_len

    @property
    def k_len(self) -> int:
        return self.seq_len

    def visible(self, row, col):
        """Boolean ``row sees col`` on absolute indices (int arrays that
        broadcast against each other; numpy or traced): two compares of
        ``row - col``, joined by and."""
        d = row - col
        return (d >= 0) & (d < self.window)

    def tile_class(self, r0, r1, c0, c1) -> str:
        """``"dead"``, ``"full"`` or ``"partial"``: whether no, every or
        some pair of the rows ``r0 .. r1`` and columns ``c0 .. c1``
        (inclusive) is visible. Python ints."""
        lo, hi = r0 - c1, r1 - c0
        if hi < 0 or lo >= self.window:
            return "dead"
        return "full" if lo >= 0 and hi < self.window else "partial"

    check = BlockDiffusionMask.check


def _div(x, g):
    """``x // g`` for non-negative ints: a shift where ``g`` is a power of
    two (cheaper on the TPU's vector unit than a divide, which also
    compiles)."""
    if g & (g - 1) == 0:
        return x >> (g.bit_length() - 1)
    return x // g


def _tile_class_table(Sq, Sk, bq, bk, causal=False, score_mask=None):
    """``(nq, nk)`` numpy array of ``"dead"`` / ``"partial"`` / ``"full"``:
    the class of every score tile of one head under ``causal`` or a
    ``score_mask`` description (one of the two), at block sizes (bq, bk).
    Rows and columns the wrapper pads beyond (Sq, Sk) are not counted: a
    tile of padding alone is dead."""
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    table = np.empty((nq, nk), dtype="<U7")
    for iq in range(nq):
        for ik in range(nk):
            if score_mask is not None:
                table[iq, ik] = score_mask.tile_class(
                    iq * bq, min(iq * bq + bq, Sq) - 1,
                    ik * bk, min(ik * bk + bk, Sk) - 1)
            elif _causal_dead(iq, ik, bq, bk):
                table[iq, ik] = "dead"
            else:
                table[iq, ik] = ("full" if _causal_full(iq, ik, bq, bk)
                                 else "partial")
    return table


def tile_classes(Sq, Sk, bq, bk, causal=False, score_mask=None):
    """``(dead, partial, full)`` tile counts of one head's score matrix at
    block sizes (bq, bk) under ``causal=True`` or a ``score_mask``
    description: the multi-tile kernels run the partial and the full ones;
    a dead one costs no compute and no DMA under ``causal`` and is no grid
    step at all under a description (:func:`grid_steps`). Static in the
    shapes."""
    if causal == (score_mask is not None):
        raise ValueError("tile_classes counts under causal=True or under "
                         "a score_mask, one of the two")
    table = _tile_class_table(Sq, Sk, bq, bk, causal, score_mask)
    dead, full = int(np.sum(table == "dead")), int(np.sum(table == "full"))
    return dead, table.size - dead - full, full


def grid_steps(Sq, Sk, bq, bk, causal=False, score_mask=None):
    """Grid steps a head that each multi-tile kernel (fwd, dq, dkv)
    launches at block sizes (bq, bk): every tile of the score matrix under
    ``causal`` or no mask (a dead causal tile is still a step, which runs
    no body and fetches nothing), the live tiles alone under a
    ``score_mask`` description, whose kernels walk a list of them
    (:func:`tile_classes`' partial + full). Static in the shapes."""
    if score_mask is None:
        return -(-Sq // bq) * -(-Sk // bk)
    if causal:
        raise ValueError("grid_steps counts under causal=True or under a "
                         "score_mask, not both")
    return len(_mask_tables(score_mask, bq, bk)[0].iq)


class _TileList(NamedTuple):
    """One walking order of a description's live tiles: flat int32 arrays
    with an entry a live tile, which a ``score_mask`` call's kernel and
    index maps read from SMEM at the grid step ``t`` (scalar prefetch)."""

    iq: np.ndarray      # the step's query block
    ik: np.ndarray      # the step's key block
    first: np.ndarray   # 1 where the step opens its row (k-major: column)
    last: np.ndarray    # 1 where the step closes it


@functools.lru_cache(maxsize=None)
def _mask_tables(score_mask, bq, bk):
    """``(q_major, k_major)``: the live tiles of a ``score_mask`` call as
    the two :class:`_TileList` its kernels walk, made from the same
    classification :func:`tile_classes` counts. q-major (fwd, dq): row by
    row of query blocks, key blocks ascending within a row; k-major (dkv):
    column by column of key blocks, query blocks ascending. A row's (a
    column's) tiles are one run of steps, so its output block is written
    back once, and they come in the order a walk over every tile would
    meet them, so the accumulations see the same tiles in the same order.
    A row or a column with no live tile would never be written: such a
    description raises."""
    live = _tile_class_table(score_mask.q_len, score_mask.k_len, bq, bk,
                             score_mask=score_mask) != "dead"
    if not (live.any(axis=1).all() and live.any(axis=0).all()):
        raise ValueError(
            f"{score_mask}: a block of {bq} queries or of {bk} keys has no "
            f"live tile; its output would never be written")

    def walk(live):
        outer, inner = np.nonzero(live)         # row-major: outer ascending
        first = np.append(True, outer[1:] != outer[:-1])
        return outer, inner, first, np.append(first[1:], True)

    def frozen(*arrays):
        arrays = [np.ascontiguousarray(a, np.int32) for a in arrays]
        for a in arrays:                        # the cache hands them out
            a.flags.writeable = False
        return _TileList(*arrays)

    iq, ik, first, last = walk(live)
    q_major = frozen(iq, ik, first, last)
    ik, iq, first, last = walk(live.T)
    return q_major, frozen(iq, ik, first, last)


def _live_k(causal, iq, ik, bq, bk):
    """Key-block index the q-major kernels (fwd, dq) fetch at step
    (iq, ik): a dead step re-names the row's last live block, so the
    pipeline sees an unchanged block index and issues no DMA."""
    if not causal:
        return ik
    return jnp.where(_causal_dead(iq, ik, bq, bk),
                     (iq * bq + bq - 1) // bk, ik)


def _live_q(causal, iq, ik, bq, bk, nq):
    """Query-block index the k-major kernel (dkv) fetches at step
    (ik, iq): a dead step re-names the column's first live block (kept
    inside the grid where Sq < Sk leaves a key block no live tile)."""
    if not causal:
        return iq
    return jnp.where(_causal_dead(iq, ik, bq, bk),
                     jnp.minimum((ik * bk) // bq, nq - 1), iq)


def _on_live_tile(causal, iq, ik, bq, bk, body):
    """Run ``body()`` for tile (iq, ik) unless the causal mask kills it (a
    ``score_mask`` call's grid holds no dead tile)."""
    if not causal:
        body()
    else:
        pl.when(jnp.logical_not(_causal_dead(iq, ik, bq, bk)))(body)


def _rows(shape, iq, bq, r0):
    """Absolute query index along axis 0 of ``shape``: the rows from
    ``r0`` on of query block ``iq`` (no add of a zero ``r0``: a kernel
    that takes its block whole keeps its program)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) + (
        iq * bq + r0 if r0 else iq * bq)


def _visible_tile(score_mask, iq, ik, bq, bk, r0=0, rows=None):
    """``(rows, bk)`` boolean element mask of tile (iq, ik) under a
    ``score_mask`` description - its rows from ``r0`` on, all ``bq`` of
    them by default - from a column of row indices and a row of column
    indices (the description's arithmetic runs at those shapes)."""
    row = _rows((rows or bq, 1), iq, bq, r0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) + ik * bk
    return score_mask.visible(row, col)


def _walk(tiles, k_major=False):
    """Where a multi-tile kernel's grid step stands: ``(iq, ik, inner
    steps, first, last)`` - the score tile's block indices, the length of
    the inner grid axis, and two thunks that say whether the step opens
    and closes the accumulation of its row of query blocks (``k_major``,
    the dkv kernel: of its column of key blocks).

    Without ``tiles`` the grid is ``(B, H, outer, inner)`` over every tile
    and the inner index says; a ``score_mask`` call's grid is ``(B, H,
    live tiles)`` and its prefetched :class:`_TileList` says (it has no
    inner axis: None). Thunks, so that each compare is emitted where the
    kernel asks."""
    if tiles is None:
        outer, inner = pl.program_id(2), pl.program_id(3)
        n = pl.num_programs(3)
        iq, ik = (inner, outer) if k_major else (outer, inner)
        return iq, ik, n, lambda: inner == 0, lambda: inner == n - 1
    t = pl.program_id(2)
    return (tiles.iq[t], tiles.ik[t], None,
            lambda: tiles.first[t] != 0, lambda: tiles.last[t] != 0)


def _listed(kernel):
    """``kernel`` for a ``score_mask`` call, whose scalar-prefetched tile
    list comes before the operands."""
    def with_tiles(iq_ref, ik_ref, first_ref, last_ref, *refs):
        return kernel(*refs,
                      tiles=_TileList(iq_ref, ik_ref, first_ref, last_ref))
    return with_tiles


def _grid(spec, tiles):
    """``pl.pallas_call``'s grid arguments: as they are, or as a grid with
    the ``score_mask`` call's tile list prefetched into SMEM."""
    if not tiles:
        return spec
    return {"grid_spec": pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tiles), **spec)}


def _score_tile(q, k, mask_ref, iq, ik, *, scale, causal, bq, bk, has_mask,
                score_mask=None, r0=0):
    """fp32 (rows, bk) masked scores of tile (iq, ik), and the key-mask row
    (None when the call has neither a user mask nor key padding). ``q``
    holds the rows from ``r0`` on of query block ``iq``: all ``bq`` of
    them, or one of the forward's or dq's row sub-blocks.

    mask codes: 0 = live, 1 = user-masked (finite FILL — a fully-masked
    row degrades to uniform over the TRUE keys), 2 = wrapper padding
    (excluded from the distribution entirely, else an unaligned Sk
    inflates the denominator by Skp/Sk)."""
    rows = q.shape[0]
    s = _dot(q, k, ((1,), (1,)), _prec(q.dtype)) * scale
    mrow = None
    if has_mask:
        mrow = mask_ref[0, 0][None, :]             # (1, bk) -> broadcast
        s = jnp.where(mrow != 0, FILL, s)
    if causal:
        row = _rows((rows, bk), iq, bq, r0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1) + ik * bk
        s = jnp.where(row >= col, s, FILL)
    if score_mask is not None:
        s = jnp.where(_visible_tile(score_mask, iq, ik, bq, bk, r0, rows), s,
                      FILL)
    return s, mrow


def _zero_padded_keys(p, mrow):
    """Padded keys (code 2) get p exactly 0."""
    return p if mrow is None else jnp.where(mrow >= 2, 0.0, p)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, *rest, scale, causal, bq, bk,
                has_mask=True, dropout_rate=0.0, native_prng=True,
                score_mask=None, tiles=None, rows):
    """Multi-tile forward: one grid step is one live (bq, bk) score tile,
    folded into its row block's running statistics. The step's query rows
    go in sub-blocks of ``rows`` (``_FWD_ROWS``), which share only the
    tile's k, v and keep mask: a row's softmax needs its whole score row,
    so over one block of 512 rows the vector units wait for the whole
    q k^T and the MXU for the whole softmax, where four chains of 128 rows
    let the scheduler run one's softmax beside another's matmuls. The
    statistics are per row, so every row's arithmetic is the unsplit
    step's, bit for bit."""
    if dropout_rate > 0.0:
        drop_ref, o_ref, lse_ref, acc_s, m_s, l_s = rest
    else:
        drop_ref, (o_ref, lse_ref, acc_s, m_s, l_s) = None, rest
    b, hh = pl.program_id(0), pl.program_id(1)
    iq, ik, nk, first, last = _walk(tiles)

    @pl.when(first())
    def _init():
        m_s[:] = jnp.full_like(m_s, -1e30)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def _tile():
        prec = _prec(q_ref.dtype)
        keep = None
        for r0 in range(0, bq, rows):
            r = slice(r0, r0 + rows)
            q = q_ref[0, 0, r]                     # (rows, D)
            k = k_ref[0, 0]                        # (bk, D)
            s, mrow = _score_tile(q, k, mask_ref, iq, ik, scale=scale,
                                  causal=causal, bq=bq, bk=bk,
                                  has_mask=has_mask, score_mask=score_mask,
                                  r0=r0)

            m_prev = m_s[r, :1]                    # (rows, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = _zero_padded_keys(jnp.exp(s - m_new), mrow)  # (rows, bk)
            alpha = jnp.exp(m_prev - m_new)        # (rows, 1)
            l_new = alpha * l_s[r, :1] + jnp.sum(p, axis=1, keepdims=True)

            v = v_ref[0, 0]                        # (bk, D)
            # dropout multiplies only the p @ v path; m/l/lse stay
            # pre-dropout so the final acc/l equals composed
            # dropout(softmax) @ v by linearity. The tile's mask is drawn
            # whole, once (the stream of the unsplit tile), and sliced.
            if dropout_rate > 0.0:
                if keep is None:
                    tid = _tile_id(b, hh, iq, ik, pl.num_programs(1),
                                   pl.num_programs(2), nk)
                    keep = _keep_mask(drop_ref, tid, bq, bk, dropout_rate,
                                      native_prng)
                p_av = jnp.where(keep[r], p, 0.0) * (
                    1.0 / (1.0 - dropout_rate))
            else:
                p_av = p
            pv = _dot(p_av.astype(v.dtype), v, ((1,), (0,)), prec)
            acc_s[r] = acc_s[r] * alpha + pv
            m_s[r] = jnp.broadcast_to(m_new, (rows, m_s.shape[1]))
            l_s[r] = jnp.broadcast_to(l_new, (rows, l_s.shape[1]))

    _on_live_tile(causal, iq, ik, bq, bk, _tile)

    @pl.when(last())
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_s[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = (m_s[:, :1] + jnp.log(safe_l))[:, 0]


def _fwd_single_kernel(q_ref, k_ref, v_ref, mask_ref, *rest, scale, causal,
                       bq, bk, dropout_rate=0.0, native_prng=True,
                       score_mask=None):
    """Single-tile forward (nq == nk == 1): the whole attention row fits
    one tile, so the softmax is direct — no VMEM running-statistics
    scratch, no alpha rescale of the accumulator, no @pl.when phases."""
    if dropout_rate > 0.0:
        drop_ref, o_ref, lse_ref = rest
    else:
        drop_ref, (o_ref, lse_ref) = None, rest
    b, hh = pl.program_id(0), pl.program_id(1)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    prec = _prec(q.dtype)
    s = _dot(q, k, ((1,), (1,)), prec) * scale
    mrow = mask_ref[0, 0][None, :]
    s = jnp.where(mrow != 0, FILL, s)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(row >= col, s, FILL)
    if score_mask is not None:
        s = jnp.where(_visible_tile(score_mask, 0, 0, bq, bk), s, FILL)

    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mrow >= 2, 0.0, p)
    l = jnp.sum(p, axis=1, keepdims=True)
    if dropout_rate > 0.0:
        tid = _tile_id(b, hh, 0, 0, pl.num_programs(1), 1, 1)
        keep = _keep_mask(drop_ref, tid, bq, bk, dropout_rate, native_prng)
        p_av = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
    else:
        p_av = p
    v = v_ref[0, 0]
    pv = _dot(p_av.astype(v.dtype), v, ((1,), (0,)), prec)
    safe_l = jnp.where(l > 0, l, 1.0)
    o_ref[0, 0] = (pv / safe_l).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = (m + jnp.log(safe_l))[:, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                   *rest, scale, causal, bq, bk, has_mask=True,
                   dropout_rate=0.0, native_prng=True, score_mask=None,
                   tiles=None, rows):
    """Multi-tile dq: one grid step is one live (bq, bk) score tile, whose
    ``ds k`` is added into its query rows' dq. The step's query rows go
    in sub-blocks of ``rows`` (``_DQ_ROWS``), each its own q k^T,
    softmax, do v^T, ds and ds k, sharing only the tile's k, v and keep
    mask: a row of dq reads nothing of another row, so every row's
    arithmetic is the unsplit step's, bit for bit."""
    if dropout_rate > 0.0:
        drop_ref, dq_ref, dq_s = rest
    else:
        drop_ref, (dq_ref, dq_s) = None, rest
    b, hh = pl.program_id(0), pl.program_id(1)
    iq, ik, nk, first, last = _walk(tiles)

    @pl.when(first())
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    def _tile():
        prec = _prec(q_ref.dtype)
        keep = None
        for r0 in range(0, bq, rows):
            r = slice(r0, r0 + rows)
            q = q_ref[0, 0, r]                     # (rows, D)
            k = k_ref[0, 0]                        # (bk, D)
            s, mrow = _score_tile(q, k, mask_ref, iq, ik, scale=scale,
                                  causal=causal, bq=bq, bk=bk,
                                  has_mask=has_mask, score_mask=score_mask,
                                  r0=r0)

            lse = lse_ref[0, 0, 0, r][:, None]     # (rows, 1)
            p = _zero_padded_keys(jnp.exp(s - lse), mrow)  # (rows, bk)
            do = do_ref[0, 0, r]                   # (rows, D)
            v = v_ref[0, 0]                        # (bk, D)
            dp = _dot(do, v, ((1,), (1,)), prec)
            if dropout_rate > 0.0:
                # replay the forward's exact keep-mask onto dp (dP =
                # mask/keep * dO·V); delta already carries the dropout
                # through O. Drawn whole, once, and sliced by rows.
                if keep is None:
                    tid = _tile_id(b, hh, iq, ik, pl.num_programs(1),
                                   pl.num_programs(2), nk)
                    keep = _keep_mask(drop_ref, tid, bq, bk, dropout_rate,
                                      native_prng)
                dp = jnp.where(keep[r], dp, 0.0) * (
                    1.0 / (1.0 - dropout_rate))
            delta = delta_ref[0, 0, 0, r][:, None]  # (rows, 1)
            ds = p * (dp - delta) * scale          # (rows, bk)
            dq_s[r] = dq_s[r] + _dot(ds.astype(k.dtype), k, ((1,), (0,)),
                                     prec)

    _on_live_tile(causal, iq, ik, bq, bk, _tile)

    @pl.when(last())
    def _finish():
        dq_ref[0, 0] = dq_s[:].astype(dq_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                      delta_ref, *rest, scale, causal, bq, bk,
                      dropout_rate=0.0, native_prng=True, score_mask=None):
    """Single-tile backward (nq == nk == 1 — the reference fmha's
    seqlen<=512 specialization): one (b, h) grid step recomputes s and p
    ONCE and emits dq, dk, AND dv — 5 matmuls instead of the 7 the
    split dq/dkv kernels pay (each recomputes s, and dp is computed
    twice), plus one kernel launch instead of two."""
    if dropout_rate > 0.0:
        drop_ref, dq_ref, dk_ref, dv_ref = rest
    else:
        drop_ref, (dq_ref, dk_ref, dv_ref) = None, rest
    b, hh = pl.program_id(0), pl.program_id(1)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    prec = _prec(q.dtype)
    s = _dot(q, k, ((1,), (1,)), prec) * scale
    mrow = mask_ref[0, 0][None, :]
    s = jnp.where(mrow != 0, FILL, s)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(row >= col, s, FILL)
    if score_mask is not None:
        s = jnp.where(_visible_tile(score_mask, 0, 0, bq, bk), s, FILL)

    lse = lse_ref[0, 0, 0][:, None]
    p = jnp.exp(s - lse)
    p = jnp.where(mrow >= 2, 0.0, p)
    do = do_ref[0, 0]
    v = v_ref[0, 0]
    dp = _dot(do, v, ((1,), (1,)), prec)
    if dropout_rate > 0.0:
        tid = _tile_id(b, hh, 0, 0, pl.num_programs(1), 1, 1)
        keep = _keep_mask(drop_ref, tid, bq, bk, dropout_rate, native_prng)
        inv_keep = 1.0 / (1.0 - dropout_rate)
        p_av = jnp.where(keep, p, 0.0) * inv_keep
        dp = jnp.where(keep, dp, 0.0) * inv_keep
    else:
        p_av = p
    dv_ref[0, 0] = _dot(p_av.astype(do.dtype), do, ((0,), (0,)),
                        prec).astype(dv_ref.dtype)
    delta = delta_ref[0, 0, 0][:, None]
    ds = p * (dp - delta) * scale
    dq_ref[0, 0] = _dot(ds.astype(k.dtype), k, ((1,), (0,)),
                        prec).astype(dq_ref.dtype)
    dk_ref[0, 0] = _dot(ds.astype(q.dtype), q, ((0,), (0,)),
                        prec).astype(dk_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                    *rest, scale, causal, bq, bk, has_mask=True,
                    dropout_rate=0.0, native_prng=True, score_mask=None,
                    tiles=None):
    if dropout_rate > 0.0:
        drop_ref, dk_ref, dv_ref, dk_s, dv_s = rest
    else:
        drop_ref, (dk_ref, dv_ref, dk_s, dv_s) = None, rest
    b, hh = pl.program_id(0), pl.program_id(1)
    iq, ik, nq, first, last = _walk(tiles, k_major=True)

    @pl.when(first())
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def _tile():
        q = q_ref[0, 0]                            # (bq, D)
        k = k_ref[0, 0]                            # (bk, D)
        prec = _prec(q.dtype)
        s, mrow = _score_tile(q, k, mask_ref, iq, ik, scale=scale,
                              causal=causal, bq=bq, bk=bk, has_mask=has_mask,
                              score_mask=score_mask)

        lse = lse_ref[0, 0, 0][:, None]
        p = _zero_padded_keys(jnp.exp(s - lse), mrow)     # (bq, bk)
        do = do_ref[0, 0]                          # (bq, D)
        v = v_ref[0, 0]
        dp = _dot(do, v, ((1,), (1,)), prec)
        if dropout_rate > 0.0:
            # seed with (iq, ik) — the same tile coordinates the forward
            # used — even though this kernel's grid iterates (ik, iq)
            tid = _tile_id(b, hh, iq, ik, pl.num_programs(1), nq,
                           pl.num_programs(2))
            keep = _keep_mask(drop_ref, tid, bq, bk, dropout_rate,
                              native_prng)
            inv_keep = 1.0 / (1.0 - dropout_rate)
            p_av = jnp.where(keep, p, 0.0) * inv_keep
            dp = jnp.where(keep, dp, 0.0) * inv_keep
        else:
            p_av = p
        # dv += dropout(p)^T @ do
        dv_s[:] = dv_s[:] + _dot(p_av.astype(do.dtype), do, ((0,), (0,)),
                                 prec)
        delta = delta_ref[0, 0, 0][:, None]
        ds = p * (dp - delta) * scale              # (bq, bk)
        # dk += ds^T @ q
        dk_s[:] = dk_s[:] + _dot(ds.astype(q.dtype), q, ((0,), (0,)), prec)

    _on_live_tile(causal, iq, ik, bq, bk, _tile)

    @pl.when(last())
    def _finish():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers (operate on padded (B, H, S, D) tensors)
# ---------------------------------------------------------------------------

def _spec4(bs, D, index_map):
    """BlockSpec for a (B, H, S, D) tensor blocked along S."""
    return pl.BlockSpec((1, 1, bs, D), index_map)


def _drop_arg(drop_in, bq, bk, index_map):
    """(inputs, in_specs) extension for the dropout source: the (1,) SMEM
    seed for the native-PRNG path, or the blocked uint32 bits tensor for
    interpret mode."""
    if drop_in is None:
        return [], []
    if drop_in.ndim == 1:  # native path: scalar seed
        return [drop_in], [pl.BlockSpec(memory_space=pltpu.SMEM)]
    return [drop_in], [pl.BlockSpec((1, 1, bq, bk), index_map)]


def _kv_head(q, k):
    """Query head -> the key/value head of its group. Grouped-query
    attention hands k and v over at their own head count ``Hkv`` (``H %
    Hkv == 0``, query heads ``g * H // Hkv .. (g + 1) * H // Hkv - 1`` read
    group ``g``): the kernels reach a group through this index map, so no
    repeated copy of k and v exists. With ``Hkv == H`` it is the identity
    and the index maps are what they were."""
    H, Hkv = q.shape[1], k.shape[1]
    if H % Hkv:
        raise ValueError(f"{H} query heads are not a multiple of {Hkv} "
                         f"key/value heads")
    group = H // Hkv
    return (lambda h: h) if group == 1 else (lambda h: h // group)


def _sum_groups(dk, k):
    """Per-query-head ``dk`` / ``dv`` ``(B, H, Sk, D)`` summed over each
    group to ``k``'s ``(B, Hkv, Sk, D)``, outside the kernel."""
    B, Hkv, Sk, D = k.shape
    if dk.shape[1] == Hkv:
        return dk
    return dk.reshape(B, Hkv, -1, Sk, D).sum(axis=2)


class _Maps(NamedTuple):
    """Index maps of a multi-tile call's operands, by how each is blocked."""

    q: object         # (B, H, Sq, D) by query block: q, o, do, dq
    row: object       # (B, H, 1, Sq) by query block: lse, delta
    kv: object        # (B, Hkv, Sk, D) by key block, through the group
    dkv: object       # (B, H, Sk, D) by key block: dk, dv a query head
    key_mask: object  # (B, 1, Sk)
    bits: object      # (B, H, Sq, Sk): the interpret-mode dropout bits


def _index_maps(causal, bq, bk, nq, kv_head, k_major=False, listed=False):
    """:class:`_Maps` of one multi-tile call. After ``b`` and ``h`` an
    index map gets the grid step: ``(iq, ik)`` on the q-major grid over
    every tile (fwd, dq), where a dead causal step keeps the row's last
    live key block (``_live_k``); ``(ik, iq)`` on the k-major one (dkv),
    where it keeps the column's first live query block (``_live_q``); or,
    ``listed``, a ``score_mask`` call's ``t`` and then its prefetched
    :class:`_TileList`, which names both blocks in either order."""
    if listed:
        def q_block(t, iq_ref, ik_ref, first_ref, last_ref):
            return iq_ref[t]

        def k_block(t, iq_ref, ik_ref, first_ref, last_ref):
            return ik_ref[t]
    elif k_major:
        def q_block(ik, iq):
            return _live_q(causal, iq, ik, bq, bk, nq)

        def k_block(ik, iq):
            return ik
    else:
        def q_block(iq, ik):
            return iq

        def k_block(iq, ik):
            return _live_k(causal, iq, ik, bq, bk)

    return _Maps(
        q=lambda b, h, *step: (b, h, q_block(*step), 0),
        row=lambda b, h, *step: (b, h, 0, q_block(*step)),
        kv=lambda b, h, *step: (b, kv_head(h), k_block(*step), 0),
        dkv=lambda b, h, *step: (b, h, k_block(*step), 0),
        key_mask=lambda b, h, *step: (b, 0, k_block(*step)),
        bits=lambda b, h, *step: (b, h, q_block(*step), k_block(*step)))


def _kernel_name(kind, score_mask, q, v):
    """``flash_<kind>``, or ``flash_<tag>_<kind>`` for a ``score_mask``
    call, with ``mla_`` before ``<kind>`` where v's blocks are not as
    wide as q's and k's (latent attention; the widths after the wrapper's
    padding to 64 lanes): a trace tells the calls apart by name."""
    if q.shape[3] != v.shape[3]:
        kind = f"mla_{kind}"
    return f"flash_{kind}" if score_mask is None else (
        f"flash_{score_mask.tag}_{kind}")


# Query rows of one sub-block of a multi-tile kernel's tile step, on the
# v5e at head sizes 64 and 128 (calls alone, ``PERF.md`` section 6 and
# ``docs/kernels.md``): the forward's (``_fwd_kernel``) 128 beat the whole
# 512-row block by 9-16% a call and 256, 64 and 32 rows; dq's
# (``_bwd_dq_kernel``) 256 beat it by 0.1-3.5% at every shape the cells
# send, where 128 lost 1.4-2.0% in the window and causal calls at 8,192
# and 64 lost 14-27%. The dk / dv kernel takes its step whole: sub-blocks
# of its key rows cost 19-33% a call at 256 and 52-86% at 128.
_FWD_ROWS = 128
_DQ_ROWS = 256


def _sub_block(block, rows):
    """Rows of one sub-block of a tile step over ``block`` rows: ``rows``
    where it divides the block, else the whole block (not split)."""
    return rows if block % rows == 0 else block


def _flash_fwd_call(q, k, v, mask, *, scale, causal, bq, bk, has_mask=True,
                    dropout_rate=0.0, drop_in=None, score_mask=None):
    """``has_mask=False`` (static: no user key mask, no key padding, so
    ``mask`` is all zeros) builds the multi-tile kernel without its two
    key-mask selects; the single-tile kernel ignores it. ``score_mask``
    (static; the lengths it was checked against are the unpadded ones)
    adds its element mask to the kernels and, past one tile, makes the
    grid the list of its live tiles (``_mask_tables``). q and k are at
    head size ``D``; v, and with it the output, at its own ``Dv``."""
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    native = drop_in is not None and drop_in.ndim == 1
    kv_head = _kv_head(q, k)

    if Sq == bq and Sk == bk:
        extra, extra_specs = _drop_arg(drop_in, bq, bk,
                                       lambda b, h: (b, h, 0, 0))
        return pl.pallas_call(
            functools.partial(_fwd_single_kernel, scale=scale,
                              causal=causal, bq=bq, bk=bk,
                              dropout_rate=dropout_rate,
                              native_prng=native, score_mask=score_mask),
            grid=(B, H),
            in_specs=[
                _spec4(bq, D, lambda b, h: (b, h, 0, 0)),
                _spec4(bk, D, lambda b, h: (b, kv_head(h), 0, 0)),
                _spec4(bk, Dv, lambda b, h: (b, kv_head(h), 0, 0)),
                pl.BlockSpec((1, 1, bk), lambda b, h: (b, 0, 0)),
            ] + extra_specs,
            out_specs=(
                _spec4(bq, Dv, lambda b, h: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, 1, bq), lambda b, h: (b, h, 0, 0)),
            ),
            out_shape=(
                out_struct((B, H, Sq, Dv), q.dtype, q, k, v),
                out_struct((B, H, 1, Sq), jnp.float32, q, k, v),
            ),
            name=_kernel_name("fwd", score_mask, q, v),
            interpret=_interpret(),
        )(q, k, v, mask, *extra)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq,
                               bk=bk, has_mask=has_mask,
                               dropout_rate=dropout_rate, native_prng=native,
                               score_mask=score_mask,
                               rows=_sub_block(bq, _FWD_ROWS))
    tiles, steps = (), (Sq // bq, Sk // bk)
    listed = score_mask is not None
    if listed:
        tiles, _ = _mask_tables(score_mask, bq, bk)
        kernel, steps = _listed(kernel), (len(tiles.iq),)
    maps = _index_maps(causal, bq, bk, Sq // bq, kv_head, listed=listed)
    extra, extra_specs = _drop_arg(drop_in, bq, bk, maps.bits)
    out, lse = pl.pallas_call(
        kernel,
        **_grid(dict(
            grid=(B, H, *steps),
            in_specs=[
                _spec4(bq, D, maps.q),
                _spec4(bk, D, maps.kv),
                _spec4(bk, Dv, maps.kv),
                pl.BlockSpec((1, 1, bk), maps.key_mask),
            ] + extra_specs,
            out_specs=(
                _spec4(bq, Dv, maps.q),
                pl.BlockSpec((1, 1, 1, bq), maps.row),
            ),
            scratch_shapes=[
                pltpu.VMEM((bq, Dv), jnp.float32),
                pltpu.VMEM((bq, LANE), jnp.float32),
                pltpu.VMEM((bq, LANE), jnp.float32),
            ]), tiles),
        out_shape=(
            out_struct((B, H, Sq, Dv), q.dtype, q, k, v),
            out_struct((B, H, 1, Sq), jnp.float32, q, k, v),
        ),
        name=_kernel_name("fwd", score_mask, q, v),
        interpret=_interpret(),
    )(*tiles, q, k, v, mask, *extra)
    return out, lse


def _flash_bwd_call(q, k, v, mask, do, lse, delta, *, scale, causal, bq, bk,
                    has_mask=True, dropout_rate=0.0, drop_in=None,
                    score_mask=None):
    """dq, dk at q's and k's head size ``D``; dv (and the ``do`` read) at
    v's ``Dv``."""
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    native = drop_in is not None and drop_in.ndim == 1
    kv_head = _kv_head(q, k)
    # grouped keys/values: the kernels write dk, dv per QUERY head (in
    # float32, to be summed over each group by the caller); multi-head
    # attention writes them in k's and v's dtype as before
    grouped = k.shape[1] != H
    dk_dtype, dv_dtype = ((jnp.float32, jnp.float32) if grouped
                          else (k.dtype, v.dtype))

    if Sq == bq and Sk == bk:
        # whole attention row in one tile: fused dq+dk+dv kernel
        extra, extra_specs = _drop_arg(drop_in, bq, bk,
                                       lambda b, h: (b, h, 0, 0))
        return pl.pallas_call(
            functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                              bq=bq, bk=bk, dropout_rate=dropout_rate,
                              native_prng=native, score_mask=score_mask),
            grid=(B, H),
            in_specs=[
                _spec4(bq, D, lambda b, h: (b, h, 0, 0)),
                _spec4(bk, D, lambda b, h: (b, kv_head(h), 0, 0)),
                _spec4(bk, Dv, lambda b, h: (b, kv_head(h), 0, 0)),
                pl.BlockSpec((1, 1, bk), lambda b, h: (b, 0, 0)),
                _spec4(bq, Dv, lambda b, h: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, 1, bq), lambda b, h: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, 1, bq), lambda b, h: (b, h, 0, 0)),
            ] + extra_specs,
            out_specs=(
                _spec4(bq, D, lambda b, h: (b, h, 0, 0)),
                _spec4(bk, D, lambda b, h: (b, h, 0, 0)),
                _spec4(bk, Dv, lambda b, h: (b, h, 0, 0)),
            ),
            out_shape=(
                out_struct((B, H, Sq, D), q.dtype, q, k, v, do),
                out_struct((B, H, Sk, D), dk_dtype, q, k, v, do),
                out_struct((B, H, Sk, Dv), dv_dtype, q, k, v, do),
            ),
            name=_kernel_name("bwd", score_mask, q, v),
            interpret=_interpret(),
        )(q, k, v, mask, do, lse, delta, *extra)

    nq, nk = Sq // bq, Sk // bk
    kern = dict(scale=scale, causal=causal, bq=bq, bk=bk, has_mask=has_mask,
                dropout_rate=dropout_rate, native_prng=native,
                score_mask=score_mask)
    dq_kernel = functools.partial(_bwd_dq_kernel, **kern,
                                  rows=_sub_block(bq, _DQ_ROWS))
    dkv_kernel = functools.partial(_bwd_dkv_kernel, **kern)
    dq_tiles, dkv_tiles = (), ()
    dq_steps, dkv_steps = (nq, nk), (nk, nq)
    listed = score_mask is not None
    if listed:
        dq_tiles, dkv_tiles = _mask_tables(score_mask, bq, bk)
        dq_kernel, dkv_kernel = _listed(dq_kernel), _listed(dkv_kernel)
        dq_steps = dkv_steps = (len(dq_tiles.iq),)

    def operands(maps):
        """(inputs past the tile list, their specs): q, k, v, key mask,
        do, lse, delta and the dropout source."""
        extra, extra_specs = _drop_arg(drop_in, bq, bk, maps.bits)
        return (q, k, v, mask, do, lse, delta, *extra), [
            _spec4(bq, D, maps.q),
            _spec4(bk, D, maps.kv),
            _spec4(bk, Dv, maps.kv),
            pl.BlockSpec((1, 1, bk), maps.key_mask),
            _spec4(bq, Dv, maps.q),
            pl.BlockSpec((1, 1, 1, bq), maps.row),
            pl.BlockSpec((1, 1, 1, bq), maps.row),
        ] + extra_specs

    maps = _index_maps(causal, bq, bk, nq, kv_head, listed=listed)
    inputs, in_specs = operands(maps)
    dq = pl.pallas_call(
        dq_kernel,
        **_grid(dict(
            grid=(B, H, *dq_steps),
            in_specs=in_specs,
            out_specs=_spec4(bq, D, maps.q),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)]), dq_tiles),
        out_shape=out_struct((B, H, Sq, D), q.dtype, q, k, v, do),
        name=_kernel_name("bwd_dq", score_mask, q, v),
        interpret=_interpret(),
    )(*dq_tiles, *inputs)

    maps = _index_maps(causal, bq, bk, nq, kv_head, k_major=True,
                       listed=listed)
    inputs, in_specs = operands(maps)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        **_grid(dict(
            grid=(B, H, *dkv_steps),
            in_specs=in_specs,
            out_specs=(_spec4(bk, D, maps.dkv), _spec4(bk, Dv, maps.dkv)),
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, Dv), jnp.float32),
            ]), dkv_tiles),
        out_shape=(
            out_struct((B, H, Sk, D), dk_dtype, q, k, v, do),
            out_struct((B, H, Sk, Dv), dv_dtype, q, k, v, do),
        ),
        name=_kernel_name("bwd_dkv", score_mask, q, v),
        interpret=_interpret(),
    )(*dkv_tiles, *inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API (custom_vjp over padded wrappers)
# ---------------------------------------------------------------------------

def _pad_inputs(q, k, v, key_mask, bq, bk):
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    # Pad D to a 64 multiple, NOT the 128 lane width: Mosaic handles a
    # 64-lane minor block (verified identical outputs on-chip), while
    # padding 64->128 physically doubles q/k/v/o (+ their gradients')
    # HBM traffic AND pays a pad-copy of every operand per call — the
    # D=64-per-head flagship shape was paying both on every layer.
    Dp, Dvp = _round_up(D, 64), _round_up(Dv, 64)
    Sqp = _round_up(Sq, bq)
    Skp = _round_up(Sk, bk)
    if key_mask is None:
        mask = jnp.zeros((B, 1, Sk), jnp.int32)
    else:
        mask = key_mask.astype(jnp.int32)[:, None, :]
    if (Dp, Dvp, Sqp, Skp) != (D, Dv, Sq, Sk):
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sqp - Sq), (0, Dp - D)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Skp - Sk), (0, Dp - D)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Skp - Sk), (0, Dvp - Dv)))
        # padding code 2: excluded from the softmax denominator in-kernel
        # (code 1 = user-masked keys still count toward a fully-masked
        # row's uniform fallback, matching the composed reference)
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, Skp - Sk)), constant_values=2)
    return q, k, v, mask


def _has_mask(key_mask, Sk, bk):
    """Static: does ``_pad_inputs``' mask hold anything but zeros? Not
    when the caller gave no key mask and Sk needs no padding."""
    return key_mask is not None or Sk % bk != 0


# Relative per-FLOP cost of a block size (v5e measurement: 512-blocks
# beat 128-blocks by 2.1x; intermediate sizes interpolated). Used to
# trade padding waste against block efficiency.
_BLOCK_COST = {512: 1.0, 384: 1.08, 256: 1.25, 128: 2.1}


def _block_dim(S):
    """Pick the block size minimizing (padded_len/S) * per-FLOP cost.

    Neither extreme is right alone: always padding to 512-blocks wastes
    2.5x FLOPs at S=640, while insisting the block divide round_up(S,128)
    forces 128-blocks at S=896 (no larger divisor) — ~60% slower than
    padding 896→1024 with 512-blocks. The cost model arbitrates."""
    best, best_cost = LANE, None
    for b, c in _BLOCK_COST.items():
        cost = (_round_up(S, b) / max(S, 1)) * c
        if best_cost is None or cost < best_cost:
            best, best_cost = b, cost
    return best


def _block_sizes(Sq, Sk):
    """Measured on v5e: large blocks win — at S=512, (512, 512) runs the
    whole attention row per grid step (the shape the reference fmha
    specializes for) and beats (128, 128) by 2.1x; VMEM stays bounded
    (score tile 512*512*4B = 1 MB). Longer sequences tile with the
    online-softmax recurrence across key blocks."""
    return (_block_dim(Sq), _block_dim(Sk))


def _drop_input(dropout_rate, seed, B, H, Sqp, Skp):
    """Dropout source array for the kernels: the (1,) int32 seed on real
    TPU (in-kernel PRNG), or the full precomputed uint32 bits tensor in
    interpret mode (no TPU PRNG emulation on CPU). Deterministic in the
    seed, so the backward regenerates the identical bits."""
    if dropout_rate == 0.0:
        return None
    if seed is None:
        raise ValueError(
            "flash_attention with dropout_rate > 0 requires dropout_seed "
            "(an int32 scalar; fold in the training step / layer index)")
    seed = jnp.asarray(seed, jnp.int32).reshape(())
    if _interpret():
        return jax.random.bits(jax.random.PRNGKey(seed),
                               (B, H, Sqp, Skp), jnp.uint32)
    return seed.reshape((1,))


def flash_dropout_keep_mask(B, H, Sq, Sk, dropout_rate, seed):
    """The exact (B, H, Sq, Sk) boolean keep-mask the flash kernels apply
    for this shape/rate/seed — bit-identical to the in-kernel generation
    on either backend, so tests can run composed attention with the same
    mask and assert numerical parity with the fused path."""
    bq, bk = _block_sizes(Sq, Sk)
    Sqp, Skp = _round_up(Sq, bq), _round_up(Sk, bk)
    if _interpret():
        bits = jax.random.bits(
            jax.random.PRNGKey(jnp.asarray(seed, jnp.int32)),
            (B, H, Sqp, Skp), jnp.uint32)
        return (bits < _keep_threshold(dropout_rate))[:, :, :Sq, :Sk]

    def mask_kernel(seed_ref, o_ref):
        tid = _tile_id(pl.program_id(0), pl.program_id(1),
                       pl.program_id(2), pl.program_id(3),
                       pl.num_programs(1), pl.num_programs(2),
                       pl.num_programs(3))
        keep = _keep_mask(seed_ref, tid, bq, bk, dropout_rate, True)
        o_ref[0, 0] = keep.astype(o_ref.dtype)

    keep = pl.pallas_call(
        mask_kernel,
        grid=(B, H, Sqp // bq, Skp // bk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((1, 1, bq, bk),
                               lambda b, h, iq, ik: (b, h, iq, ik)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sqp, Skp), jnp.float32),
        name="dropout_mask",
        interpret=_interpret(),
    )(jnp.asarray(seed, jnp.int32).reshape((1,)))
    return (keep > 0.5)[:, :, :Sq, :Sk]


def _repeat_groups(k, H):
    """k or v at ``Hkv`` heads repeated to the ``H`` query heads."""
    return k if k.shape[1] == H else jnp.repeat(k, H // k.shape[1], axis=1)


def _scores(q, k, key_mask, causal, scale, score_mask=None):
    """(B, H, Sq, Sk) fp32 masked scores — shared by every composed path."""
    k = _repeat_groups(k, q.shape[1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :], FILL, s)
    if causal:
        Sq, Sk = s.shape[-2:]
        row = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where((row >= col)[None, None], s, FILL)
    if score_mask is not None:
        Sq, Sk = s.shape[-2:]
        score_mask.check(Sq, Sk)
        s = jnp.where(score_mask.visible(np.arange(Sq)[:, None],
                                         np.arange(Sk)[None, :]), s, FILL)
    return s


def mha_reference(q, k, v, key_mask=None, causal=False, scale=1.0,
                  dropout_rate=0.0, dropout_seed=None, score_mask=None):
    """Composed-ops reference: materializes (B, H, Sq, Sk) scores (under a
    ``score_mask`` description, its dense mask too).

    With dropout the mask comes from ``jax.random`` (same distribution as
    the kernel's hardware PRNG, different bits — use
    ``flash_dropout_keep_mask`` + ``mha_with_mask_reference`` for
    bit-matched parity tests)."""
    p = jax.nn.softmax(_scores(q, k, key_mask, causal, scale, score_mask),
                       axis=-1)
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError(
                "mha_reference with dropout_rate > 0 requires dropout_seed")
        keep = jax.random.bernoulli(
            jax.random.PRNGKey(jnp.asarray(dropout_seed, jnp.int32)),
            1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      _repeat_groups(v, q.shape[1]).astype(jnp.float32)
                      ).astype(q.dtype)


def mha_with_mask_reference(q, k, v, keep, key_mask=None, causal=False,
                            scale=1.0, dropout_rate=0.0):
    """Composed attention with an EXPLICIT keep-mask — pair with
    ``flash_dropout_keep_mask`` to reproduce the fused path exactly."""
    p = jax.nn.softmax(_scores(q, k, key_mask, causal, scale), axis=-1)
    p = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 8))
def flash_attention(q, k, v, key_mask=None, causal: bool = False,
                    scale: float = 1.0, dropout_rate: float = 0.0,
                    dropout_seed=None, score_mask=None):
    """Multi-head attention without materializing the score matrix.

    Args:
      q, k, v: ``(B, H, S, D)`` (any floating dtype; fp32 accumulation).
        v may have a head size of its own, ``(B, H, S, Dv)``, and the
        output has it: latent attention scores at ``D = 192`` and mixes
        values of ``Dv = 128`` (the kernels are then named
        ``flash_mla_*``). Grouped-query attention: k and v may carry fewer heads
        ``(B, Hkv, S, D)``, ``H % Hkv == 0``; query head ``h`` reads group
        ``h // (H // Hkv)`` through the kernels' index maps (no repeated
        copy of k, v). The backward kernels write dk, dv per query head
        in float32 and the sum over each group is taken outside them.
      key_mask: optional ``(B, Sk)`` boolean, True = key position masked
        (the reference's padding-mask convention).
      causal: apply the upper-triangular causal mask in-kernel.
      score_mask: a static description of a mask that is a function of
        (query index, key index), beyond ``causal``
        (:class:`BlockDiffusionMask`, :class:`SlidingWindowMask`;
        hashable, not traced). Past one
        tile the kernels walk the list of its live tiles (no grid step on
        a dead one) and mask them from iotas; no mask tensor exists. The
        call's lengths must be the description's. Not together with
        ``causal``, ``key_mask`` or dropout (nothing needs the
        combinations; they raise).
      scale: softmax temperature (typically ``1/sqrt(D)``).
      dropout_rate: attention-probability dropout, fused in-kernel (the
        reference fmha's Philox dropout; static Python float).
      dropout_seed: int32 scalar (may be traced) seeding the in-kernel
        PRNG; required when ``dropout_rate > 0``. Vary it per step (and
        per TP rank for head-sharded attention) for fresh masks.

    Replaces the reference's ``fmha``/``fast_multihead_attn`` fused
    attention. Differentiable via the flash recompute backward, which
    replays the identical dropout mask from the seed.
    """
    out, _ = _flash_fwd(q, k, v, key_mask, causal, scale, dropout_rate,
                        dropout_seed, score_mask)
    return out


def _check_score_mask(score_mask, Sq, Sk, key_mask, causal, dropout_rate):
    """A ``score_mask`` call stands alone: the description holds the whole
    mask, and its tile lists are made for the call's own lengths."""
    if score_mask is None:
        return
    score_mask.check(Sq, Sk)
    if causal:
        raise ValueError(
            "flash_attention: score_mask describes the whole mask (what it "
            "lets a query see is already behind it); causal=True on top "
            "would need the product of two tile classifications that no "
            "model asks for")
    if key_mask is not None:
        raise ValueError(
            "flash_attention: score_mask with a key_mask is not built: a "
            "fully user-masked live tile would need the causal path's rule "
            "for rows with no visible key, and the block-diffusion rows "
            "are unpadded")
    if dropout_rate > 0.0:
        raise ValueError(
            "flash_attention: score_mask with attention dropout is not "
            "built (the block-diffusion objective has none)")


def _flash_fwd(q, k, v, key_mask, causal, scale, dropout_rate=0.0,
               dropout_seed=None, score_mask=None):
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError(
            "flash_attention with dropout_rate > 0 requires dropout_seed "
            "(an int32 scalar; fold in the training step / layer index)")
    _check_score_mask(score_mask, q.shape[2], k.shape[2], key_mask, causal,
                      dropout_rate)
    if k.shape[3] != q.shape[3]:
        raise ValueError(f"flash_attention: k's head size {k.shape[3]} is "
                         f"not q's {q.shape[3]}")
    if use_jnp_fallback(q, k, v, key_mask):
        out = mha_reference(q, k, v, key_mask, causal, scale,
                            dropout_rate, dropout_seed, score_mask)
        return out, None
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk = _block_sizes(Sq, Sk)
    qp, kp, vp, mask = _pad_inputs(q, k, v, key_mask, bq, bk)
    drop_in = _drop_input(dropout_rate, dropout_seed, B, H,
                          qp.shape[2], kp.shape[2])
    out, lse = _flash_fwd_call(qp, kp, vp, mask, scale=scale, causal=causal,
                               bq=bq, bk=bk,
                               has_mask=_has_mask(key_mask, Sk, bk),
                               dropout_rate=dropout_rate, drop_in=drop_in,
                               score_mask=score_mask)
    return out[:, :, :Sq, :v.shape[3]], lse


def _name_residuals(out, lse):
    """Tag the forward kernel's outputs so that a rematerialised caller
    can keep them by name (``apex_tpu/transformer/remat.py``) and not run
    the kernel twice; the identity anywhere else. The tagged ``out`` must
    also be the primal output: an untagged twin would be recomputed."""
    out = checkpoint_name(out, profiler.FLASH_OUT)
    if lse is not None:  # the jnp fallback has none
        lse = checkpoint_name(lse, profiler.FLASH_LSE)
    return out, lse


def _flash_vjp_fwd(q, k, v, key_mask, causal, scale, dropout_rate,
                   dropout_seed, score_mask):
    out, lse = _name_residuals(*_flash_fwd(
        q, k, v, key_mask, causal, scale, dropout_rate, dropout_seed,
        score_mask))
    return out, (q, k, v, key_mask, out, lse, dropout_seed)


def _kernel_bwd(causal, scale, q, k, v, key_mask, out, lse_padded, g,
                g_lse=None, dropout_rate=0.0, dropout_seed=None,
                score_mask=None):
    """Shared recompute backward for both vjps. ``lse_padded`` is the
    kernel's padded-width lse; ``g_lse`` (optional, (B, H, 1, Sq)) is the
    lse cotangent, folded into delta (d lse/d s = p, so
    ds = p * (dP - (rowsum(dO*O) - dlse)))."""
    B, H, Sq, D = q.shape
    Sk, Dv = k.shape[2], v.shape[3]
    bq, bk = _block_sizes(Sq, Sk)
    qp, kp, vp, mask = _pad_inputs(q, k, v, key_mask, bq, bk)
    Sqp = qp.shape[2]
    Dvp = vp.shape[3]
    drop_in = _drop_input(dropout_rate, dropout_seed, B, H,
                          Sqp, kp.shape[2])
    gp = g
    outp = out
    if (Sqp, Dvp) != (Sq, Dv):
        gp = jnp.pad(g, ((0, 0), (0, 0), (0, Sqp - Sq), (0, Dvp - Dv)))
        outp = jnp.pad(out, ((0, 0), (0, 0), (0, Sqp - Sq), (0, Dvp - Dv)))
    # lse was computed on padded shapes in fwd, so it already covers any
    # padded query rows. delta is carried (B, H, 1, Sq) to match lse's
    # Mosaic-friendly layout (size-1 block dims must equal array dims).
    delta = jnp.sum(gp.astype(jnp.float32) * outp.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    if g_lse is not None:
        glp = g_lse
        if Sqp != Sq:
            glp = jnp.pad(g_lse, ((0, 0), (0, 0), (0, 0), (0, Sqp - Sq)))
        delta = delta - glp.astype(jnp.float32)
    dq, dk, dv = _flash_bwd_call(qp, kp, vp, mask, gp, lse_padded, delta,
                                 scale=scale, causal=causal, bq=bq, bk=bk,
                                 has_mask=_has_mask(key_mask, Sk, bk),
                                 dropout_rate=dropout_rate, drop_in=drop_in,
                                 score_mask=score_mask)
    dk, dv = _sum_groups(dk, kp), _sum_groups(dv, vp)
    return (match_vma(dq[:, :, :Sq, :D].astype(q.dtype), q),
            match_vma(dk[:, :, :Sk, :D].astype(k.dtype), k),
            match_vma(dv[:, :, :Sk, :Dv].astype(v.dtype), v),
            None)


def _flash_vjp_bwd(causal, scale, dropout_rate, score_mask, res, g):
    q, k, v, key_mask, out, lse, dropout_seed = res
    if lse is None:  # jnp fallback path: differentiate the reference
        def f(q, k, v):
            return mha_reference(q, k, v, key_mask, causal, scale,
                                 dropout_rate, dropout_seed, score_mask)

        _, vjp = jax.vjp(f, q, k, v)
        dq, dk, dv = vjp(g)
        return (match_vma(dq, q), match_vma(dk, k), match_vma(dv, v),
                None, None)
    dq, dk, dv, dmask = _kernel_bwd(causal, scale, q, k, v, key_mask, out,
                                    lse, g, dropout_rate=dropout_rate,
                                    dropout_seed=dropout_seed,
                                    score_mask=score_mask)
    return dq, dk, dv, dmask, None


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# (out, lse) variant for blockwise consumers (ring attention)
# ---------------------------------------------------------------------------

def _with_lse_reference(q, k, v, key_mask, causal, scale,
                        dropout_rate=0.0, dropout_seed=None):
    """Composed (out, lse): the differentiable fallback path. With
    dropout it reproduces the kernel semantics exactly — the keep-mask
    comes from :func:`flash_dropout_keep_mask` (bit-identical bits to
    the in-kernel generation for this backend), applied to the
    NORMALIZED probabilities while lse stays pre-dropout."""
    s = _scores(q, k, key_mask, causal, scale)
    lse = jax.nn.logsumexp(s, axis=-1)[:, :, None, :]
    p = jnp.exp(s - lse.transpose(0, 1, 3, 2))
    if dropout_rate > 0.0:
        B, H, Sq, _ = q.shape
        keep = flash_dropout_keep_mask(B, H, Sq, k.shape[2], dropout_rate,
                                       dropout_seed)
        p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
    out = jnp.einsum("bhqk,bhkd->bhqd", p,
                     v.astype(jnp.float32)).astype(q.dtype)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention_with_lse(q, k, v, key_mask=None, causal: bool = False,
                             scale: float = 1.0, dropout_rate: float = 0.0,
                             dropout_seed=None):
    """Flash attention returning ``(out, lse)`` with lse trimmed to the
    true Sq — the building block for blockwise/ring consumers that merge
    per-block results via log-sum-exp. Differentiable INCLUDING the lse
    output: its cotangent folds into the recompute backward's delta
    (``delta = rowsum(dO*O) - dlse``; d lse/d s = p).

    Dropout composes with the lse merge: the kernels apply the keep-mask
    only where the probability tile feeds ``p @ v`` while every
    statistic (m, l, lse) stays PRE-dropout, so a blockwise consumer
    that rescales partial outputs by ``exp(lse_i - lse_total)`` gets
    exactly ``sum_j drop(p_hat_j) v_j`` — composed dropout(softmax) @ v
    over the merged distribution, nothing double-counted. Blockwise
    callers must pass a DISTINCT seed per (global q-block, global
    kv-block) pair (see ring_attention's hashed tile seeds) so tiles
    draw independent streams and backward replays the same mask."""
    if use_jnp_fallback(q, k, v, key_mask):
        return _with_lse_reference(q, k, v, key_mask, causal, scale,
                                   dropout_rate, dropout_seed)
    out, lse = _flash_fwd(q, k, v, key_mask, causal, scale, dropout_rate,
                          dropout_seed)
    return out, lse[..., :q.shape[2]]


def _fwl_fwd(q, k, v, key_mask, causal, scale, dropout_rate, dropout_seed):
    if use_jnp_fallback(q, k, v, key_mask):
        out, lse_t = _with_lse_reference(q, k, v, key_mask, causal, scale,
                                         dropout_rate, dropout_seed)
        return (out, lse_t), (q, k, v, key_mask, out, None, dropout_seed)
    out, lse = _name_residuals(*_flash_fwd(
        q, k, v, key_mask, causal, scale, dropout_rate, dropout_seed))
    return ((out, lse[..., :q.shape[2]]),
            (q, k, v, key_mask, out, lse, dropout_seed))


def _fwl_bwd(causal, scale, dropout_rate, res, cotangents):
    q, k, v, key_mask, out, lse_padded, dropout_seed = res
    g, g_lse = cotangents
    if lse_padded is None:  # fallback path: autodiff the composed form
        def f(q, k, v):
            return _with_lse_reference(q, k, v, key_mask, causal, scale,
                                       dropout_rate, dropout_seed)

        _, vjp = jax.vjp(f, q, k, v)
        dq, dk, dv = vjp((g, g_lse))
        return (match_vma(dq, q), match_vma(dk, k), match_vma(dv, v),
                None, None)
    dq, dk, dv, dmask = _kernel_bwd(causal, scale, q, k, v, key_mask, out,
                                    lse_padded, g, g_lse,
                                    dropout_rate=dropout_rate,
                                    dropout_seed=dropout_seed)
    return dq, dk, dv, dmask, None


flash_attention_with_lse.defvjp(_fwl_fwd, _fwl_bwd)


# ---------------------------------------------------------------------------
# (B, S, NH*D)-layout entry: attention without head split/merge transposes
# ---------------------------------------------------------------------------
#
# The transposed (B, NH, S, D) convention costs the model 4 layout copies
# per layer forward (q, k, v head-split + context merge) and their 4
# mirrors in backward — ~8 x 17 MB of pure HBM traffic per BERT-large
# layer. Here the kernel reads heads directly out of the flat activation
# via the BlockSpec index map and writes the context back the same way,
# so the model keeps everything (B, S, H) end to end.
#
# Mosaic requires lane-dim blocks to be multiples of 128, so a D=64 head
# cannot be block-sliced alone out of a 1024-lane activation; instead
# each grid step owns a HEAD PAIR — a (1, S, 2*D=128) block holding
# heads 2h and 2h+1 side by side — and the kernel computes the two
# heads' attention from in-register lane slices of the pair. (This also
# halves the grid, amortizing per-step overheads.) Constraints for the
# kernel path: 2*D % 128 == 0, even NH, and the single-tile sequence
# regime (S <= 512 — the flagship shape); anything else falls back to
# the transposed entry transparently.


def _bsh_hpb(NH, D):
    """Heads per block for the bsh kernels: the widest of {4, 2, 1}
    whose lane block (hpb*D) is a 128 multiple and divides NH. 0 means
    the layout can't be block-sliced (fallback to the transposed entry).
    hpb=2 at D=64 is the Mosaic-minimum 128-lane block; hpb=4 was A/B'd
    at the headline as an alternative (fewer grid steps, more VMEM per
    step)."""
    import os

    forced = os.environ.get("APEX_BSH_HPB")
    cand = (4, 2, 1)
    if forced:
        try:
            cand = (int(forced),)
        except ValueError:
            cand = ()
        if not any(h > 0 and NH % h == 0 and (h * D) % 128 == 0
                   for h in cand):
            # an unusable forced value must NOT silently divert to the
            # transposed entry — the A/B the env var exists for would
            # record the wrong code path; warn and use the default sweep
            import warnings

            warnings.warn(
                f"APEX_BSH_HPB={forced!r} is not a valid head grouping "
                f"for NH={NH}, D={D}; using the default (4, 2, 1) sweep "
                f"instead", stacklevel=3)
            cand = (4, 2, 1)
    for h in cand:
        if h > 0 and NH % h == 0 and (h * D) % 128 == 0:
            return h
    return 0  # no valid grouping: caller falls back to transposed entry


def _fwd_single_kernel_bsh(q_ref, k_ref, v_ref, mask_ref, *rest, scale,
                           causal, bq, bk, NH, D, hpb,
                           dropout_rate=0.0, native_prng=True):
    """Head-group single-tile forward on (B, S, NH*D)-layout refs: the
    (1, bq, hpb*D) blocks hold heads hp*hpb .. hp*hpb+hpb-1; same math
    as _fwd_single_kernel per head."""
    if dropout_rate > 0.0:
        drop_ref, o_ref, lse_ref = rest
    else:
        drop_ref, (o_ref, lse_ref) = None, rest
    b, hp = pl.program_id(0), pl.program_id(1)
    mrow = mask_ref[0, 0][None, :]
    q2, k2, v2 = q_ref[0], k_ref[0], v_ref[0]       # (bq, hpb*D)
    prec = _prec(q2.dtype)
    outs = []
    for j in range(hpb):
        q = q2[:, j * D:(j + 1) * D]
        k = k2[:, j * D:(j + 1) * D]
        s = _dot(q, k, ((1,), (1,)), prec) * scale
        s = jnp.where(mrow != 0, FILL, s)
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(row >= col, s, FILL)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        p = jnp.where(mrow >= 2, 0.0, p)
        l = jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            # per-HEAD tile id (hpb*hp + j): identical mask stream to
            # the transposed entry at the same (b, h) coordinates
            tid = _tile_id(b, hpb * hp + j, 0, 0, NH, 1, 1)
            keep = _keep_mask(drop_ref, tid, bq, bk, dropout_rate,
                              native_prng, interp_idx=(0, j))
            p_av = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
        else:
            p_av = p
        v = v2[:, j * D:(j + 1) * D]
        pv = _dot(p_av.astype(v.dtype), v, ((1,), (0,)), prec)
        safe_l = jnp.where(l > 0, l, 1.0)
        outs.append((pv / safe_l).astype(o_ref.dtype))
        lse_ref[0, j, 0] = (m + jnp.log(safe_l))[:, 0]
    o_ref[0] = outs[0] if hpb == 1 else jnp.concatenate(outs, axis=1)


def _bwd_fused_kernel_bsh(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                          delta_ref, *rest, scale, causal, bq, bk, NH, D,
                          hpb, dropout_rate=0.0, native_prng=True):
    """Head-group single-tile fused backward on (B, S, NH*D)-layout
    refs: recomputes s and p once per head and emits dq, dk, dv for the
    group (same 5-matmul-per-head economy as _bwd_fused_kernel)."""
    if dropout_rate > 0.0:
        drop_ref, dq_ref, dk_ref, dv_ref = rest
    else:
        drop_ref, (dq_ref, dk_ref, dv_ref) = None, rest
    b, hp = pl.program_id(0), pl.program_id(1)
    mrow = mask_ref[0, 0][None, :]
    q2, k2, v2, do2 = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    prec = _prec(q2.dtype)
    dqs, dks, dvs = [], [], []
    for j in range(hpb):
        q = q2[:, j * D:(j + 1) * D]
        k = k2[:, j * D:(j + 1) * D]
        s = _dot(q, k, ((1,), (1,)), prec) * scale
        s = jnp.where(mrow != 0, FILL, s)
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(row >= col, s, FILL)
        lse = lse_ref[0, j, 0][:, None]
        p = jnp.exp(s - lse)
        p = jnp.where(mrow >= 2, 0.0, p)
        do = do2[:, j * D:(j + 1) * D]
        v = v2[:, j * D:(j + 1) * D]
        dp = _dot(do, v, ((1,), (1,)), prec)
        if dropout_rate > 0.0:
            tid = _tile_id(b, hpb * hp + j, 0, 0, NH, 1, 1)
            keep = _keep_mask(drop_ref, tid, bq, bk, dropout_rate,
                              native_prng, interp_idx=(0, j))
            inv_keep = 1.0 / (1.0 - dropout_rate)
            p_av = jnp.where(keep, p, 0.0) * inv_keep
            dp = jnp.where(keep, dp, 0.0) * inv_keep
        else:
            p_av = p
        dvs.append(_dot(p_av.astype(do.dtype), do, ((0,), (0,)),
                        prec).astype(dv_ref.dtype))
        delta = delta_ref[0, j, 0][:, None]
        ds = p * (dp - delta) * scale
        dqs.append(_dot(ds.astype(k.dtype), k, ((1,), (0,)),
                        prec).astype(dq_ref.dtype))
        dks.append(_dot(ds.astype(q.dtype), q, ((0,), (0,)),
                        prec).astype(dk_ref.dtype))
    if hpb == 1:
        dq_ref[0], dk_ref[0], dv_ref[0] = dqs[0], dks[0], dvs[0]
    else:
        dq_ref[0] = jnp.concatenate(dqs, axis=1)
        dk_ref[0] = jnp.concatenate(dks, axis=1)
        dv_ref[0] = jnp.concatenate(dvs, axis=1)


def _bsh_spec(bs, D2):
    """BlockSpec slicing head group hp of a (B, S_padded, NH*D) tensor
    (lane block hpb*D, a 128 multiple)."""
    return pl.BlockSpec((1, bs, D2), lambda b, hp: (b, 0, hp))


def _bsh_drop_arg(drop_in, bq, bk, hpb):
    """Dropout input for the group kernels: scalar seed (native) or the
    (B, NH, Sqp, Skp) bits tensor blocked (1, hpb, bq, bk) per group."""
    if drop_in is None:
        return [], []
    if drop_in.ndim == 1:
        return [drop_in], [pl.BlockSpec(memory_space=pltpu.SMEM)]
    return [drop_in], [pl.BlockSpec((1, hpb, bq, bk),
                                    lambda b, hp: (b, hp, 0, 0))]


def _flash_fwd_call_bsh(q, k, v, mask, *, scale, causal, bq, bk, NH, D,
                        hpb, dropout_rate=0.0, drop_in=None):
    B, Sp, _ = q.shape
    native = drop_in is not None and drop_in.ndim == 1
    extra, extra_specs = _bsh_drop_arg(drop_in, bq, bk, hpb)
    return pl.pallas_call(
        functools.partial(_fwd_single_kernel_bsh, scale=scale,
                          causal=causal, bq=bq, bk=bk, NH=NH, D=D,
                          hpb=hpb, dropout_rate=dropout_rate,
                          native_prng=native),
        grid=(B, NH // hpb),
        in_specs=[
            _bsh_spec(bq, hpb * D),
            _bsh_spec(bk, hpb * D),
            _bsh_spec(bk, hpb * D),
            pl.BlockSpec((1, 1, bk), lambda b, hp: (b, 0, 0)),
        ] + extra_specs,
        out_specs=(
            _bsh_spec(bq, hpb * D),
            pl.BlockSpec((1, hpb, 1, bq), lambda b, hp: (b, hp, 0, 0)),
        ),
        out_shape=(
            out_struct((B, Sp, NH * D), q.dtype, q, k, v),
            out_struct((B, NH, 1, Sp), jnp.float32, q, k, v),
        ),
        name="flash_fwd",
        interpret=_interpret(),
    )(q, k, v, mask, *extra)


def _flash_bwd_call_bsh(q, k, v, mask, do, lse, delta, *, scale, causal,
                        bq, bk, NH, D, hpb, dropout_rate=0.0,
                        drop_in=None):
    B, Sp, _ = q.shape
    native = drop_in is not None and drop_in.ndim == 1
    extra, extra_specs = _bsh_drop_arg(drop_in, bq, bk, hpb)
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel_bsh, scale=scale,
                          causal=causal, bq=bq, bk=bk, NH=NH, D=D,
                          hpb=hpb, dropout_rate=dropout_rate,
                          native_prng=native),
        grid=(B, NH // hpb),
        in_specs=[
            _bsh_spec(bq, hpb * D),
            _bsh_spec(bk, hpb * D),
            _bsh_spec(bk, hpb * D),
            pl.BlockSpec((1, 1, bk), lambda b, hp: (b, 0, 0)),
            _bsh_spec(bq, hpb * D),
            pl.BlockSpec((1, hpb, 1, bq), lambda b, hp: (b, hp, 0, 0)),
            pl.BlockSpec((1, hpb, 1, bq), lambda b, hp: (b, hp, 0, 0)),
        ] + extra_specs,
        out_specs=(
            _bsh_spec(bq, hpb * D),
            _bsh_spec(bk, hpb * D),
            _bsh_spec(bk, hpb * D),
        ),
        out_shape=(
            out_struct((B, Sp, NH * D), q.dtype, q, k, v, do),
            out_struct((B, Sp, NH * D), k.dtype, q, k, v, do),
            out_struct((B, Sp, NH * D), v.dtype, q, k, v, do),
        ),
        name="flash_bwd",
        interpret=_interpret(),
    )(q, k, v, mask, do, lse, delta, *extra)


def _bsh_kernel_ok(S, H, num_heads):
    """Static gate for the bsh kernel path: a head group must tile the
    128-lane block exactly, and the single-tile regime must hold."""
    if H % num_heads:
        return False
    if _bsh_hpb(num_heads, H // num_heads) == 0:
        return False
    bq = _block_dim(S)
    return _round_up(S, bq) == bq  # single tile after padding


def _bsh_transpose_fallback(q, k, v, key_mask, num_heads, causal, scale,
                            dropout_rate, dropout_seed):
    B, S, H = q.shape
    D = H // num_heads

    def split(t):
        return t.reshape(B, S, num_heads, D).transpose(0, 2, 1, 3)

    out = flash_attention(split(q), split(k), split(v), key_mask, causal,
                          scale, dropout_rate, dropout_seed)
    return out.transpose(0, 2, 1, 3).reshape(B, S, H)


def _bsh_pad(q, k, v, key_mask, bq):
    """Row-pad (B, S, H) activations to the block size; padded keys get
    mask code 2 (excluded from the softmax denominator)."""
    B, S, H = q.shape
    Sp = _round_up(S, bq)
    if key_mask is None:
        mask = jnp.zeros((B, 1, S), jnp.int32)
    else:
        mask = key_mask.astype(jnp.int32)[:, None, :]
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, Sp - S)),
                       constant_values=2)
    return q, k, v, mask


def flash_attention_bsh(q, k, v, key_mask=None, num_heads=None,
                        causal: bool = False, scale: float = 1.0,
                        dropout_rate: float = 0.0, dropout_seed=None):
    """Flash attention on flat (B, S, NH*D) activations — no head
    split/merge transposes anywhere. Heads are interleaved in the lane
    dim (head h owns columns [h*D, (h+1)*D)); the kernel slices them via
    its BlockSpec index maps, and gradients come back in the same flat
    layout. Semantics (masking, causal, fused dropout, seeds) are
    identical to :func:`flash_attention` on the transposed layout.

    Falls back to transpose + :func:`flash_attention` when the kernel
    constraints don't hold (D not a multiple of 64, or S beyond the
    single-tile regime), and to the composed reference under shard_map
    on CPU — callers use one entry everywhere.
    """
    if num_heads is None:
        raise ValueError("flash_attention_bsh requires num_heads")
    B, S, H = q.shape
    if use_jnp_fallback(q, k, v, key_mask) or not _bsh_kernel_ok(
            S, H, num_heads):
        return _bsh_transpose_fallback(q, k, v, key_mask, num_heads,
                                       causal, scale, dropout_rate,
                                       dropout_seed)
    return _flash_bsh_core(q, k, v, key_mask, num_heads, causal, scale,
                           dropout_rate, dropout_seed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_bsh_core(q, k, v, key_mask, num_heads, causal, scale,
                    dropout_rate, dropout_seed=None):
    out, _ = _bsh_fwd_impl(q, k, v, key_mask, num_heads, causal, scale,
                           dropout_rate, dropout_seed)
    return out


def _bsh_fwd_impl(q, k, v, key_mask, num_heads, causal, scale,
                  dropout_rate, dropout_seed):
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError(
            "flash_attention_bsh with dropout_rate > 0 requires "
            "dropout_seed")
    B, S, H = q.shape
    D = H // num_heads
    bq = bk = _block_dim(S)
    qp, kp, vp, mask = _bsh_pad(q, k, v, key_mask, bq)
    drop_in = _drop_input(dropout_rate, dropout_seed, B, num_heads,
                          qp.shape[1], kp.shape[1])
    out, lse = _flash_fwd_call_bsh(qp, kp, vp, mask, scale=scale,
                                   causal=causal, bq=bq, bk=bk,
                                   NH=num_heads, D=D,
                                   hpb=_bsh_hpb(num_heads, D),
                                   dropout_rate=dropout_rate,
                                   drop_in=drop_in)
    return out[:, :S], lse


def _bsh_vjp_fwd(q, k, v, key_mask, num_heads, causal, scale,
                 dropout_rate, dropout_seed=None):
    out, lse = _name_residuals(*_bsh_fwd_impl(
        q, k, v, key_mask, num_heads, causal, scale, dropout_rate,
        dropout_seed))
    return out, (q, k, v, key_mask, out, lse, dropout_seed)


def _bsh_vjp_bwd(num_heads, causal, scale, dropout_rate, res, g):
    q, k, v, key_mask, out, lse, dropout_seed = res
    B, S, H = q.shape
    D = H // num_heads
    bq = bk = _block_dim(S)
    qp, kp, vp, mask = _bsh_pad(q, k, v, key_mask, bq)
    Sp = qp.shape[1]
    drop_in = _drop_input(dropout_rate, dropout_seed, B, num_heads, Sp, Sp)
    gp, outp = g, out
    if Sp != S:
        gp = jnp.pad(g, ((0, 0), (0, Sp - S), (0, 0)))
        outp = jnp.pad(out, ((0, 0), (0, Sp - S), (0, 0)))
    # per-head delta = rowsum_D(dO * O): (B, Sp, NH) -> (B, NH, 1, Sp)
    delta = (gp.astype(jnp.float32) * outp.astype(jnp.float32)).reshape(
        B, Sp, num_heads, D).sum(-1).transpose(0, 2, 1)[:, :, None, :]
    dq, dk, dv = _flash_bwd_call_bsh(qp, kp, vp, mask, gp, lse, delta,
                                     scale=scale, causal=causal, bq=bq,
                                     bk=bk, NH=num_heads, D=D,
                                     hpb=_bsh_hpb(num_heads, D),
                                     dropout_rate=dropout_rate,
                                     drop_in=drop_in)
    return (match_vma(dq[:, :S].astype(q.dtype), q),
            match_vma(dk[:, :S].astype(k.dtype), k),
            match_vma(dv[:, :S].astype(v.dtype), v),
            None, None)


_flash_bsh_core.defvjp(_bsh_vjp_fwd, _bsh_vjp_bwd)


# ---------------------------------------------------------------------------
# paged decode attention (single-query attention against a block table)
# ---------------------------------------------------------------------------
#
# The serving decode step: each sequence contributes ONE query token that
# attends over its entire cached context, which lives scattered across
# the paged KV pool (apex_tpu.serving.kv_cache) rather than in a
# contiguous (B, S, H, D) tensor. The score tensor is (B, H, 1, ctx) —
# there is no S_q dimension to tile, no online-softmax recurrence to
# carry, and no backward pass (inference only), so the flash machinery
# above buys nothing here; what matters is the GATHER (block table ->
# pool rows) and the fp32 masked softmax, which XLA fuses into a
# bandwidth-bound gather + GEMV chain on both CPU and TPU. Masking
# follows this file's conventions: fp32 accumulation via
# preferred_element_type, the finite FILL for dead positions (a fully
# empty context — an inactive batch slot — degrades to a uniform read of
# zero-initialized pool rows instead of NaN).


def paged_decode_attention(q, k_pages, v_pages, block_tables, context_lens,
                           scale: float = 1.0, k_scales=None,
                           v_scales=None, use_pallas=None):
    """Single-query attention against the paged KV pool.

    Args:
      q: ``[B, H, D]`` — one query token per sequence (the token being
        decoded, whose K/V must already be written into the pool).
      k_pages, v_pages: ``[num_blocks, block_size, H, D]`` — ONE layer's
        block pool (callers index the stacked ``[L, ...]`` cache).
      block_tables: ``[B, max_blocks_per_seq]`` int32 block ids in
        sequence order; entries past a sequence's allocation may be any
        value (out-of-bounds ids are clipped into the pool and the
        positions masked by ``context_lens``).
      context_lens: ``[B]`` int32 — valid tokens per sequence INCLUDING
        the current one.
      scale: softmax temperature (typically ``1/sqrt(D)``).
      k_scales, v_scales: ``[num_blocks, block_size, H]`` fp32 per-row
        dequantization scales of a quantized pool (None = the pool is
        full precision). Dequantization happens inside the read.
      use_pallas: route the read chain through the fused Pallas kernel
        (:mod:`apex_tpu.ops.paged_attention_pallas`); None consults the
        ``APEX_PAGED_ATTENTION_PALLAS`` env flag.

    Returns ``[B, H, D]`` in ``q.dtype``.
    """
    # decode IS the single-query case of the chunked-prefill kernel: a
    # one-token "chunk" at position context_len - 1 (its causal mask
    # kpos <= ctx-1 is exactly the decode mask kpos < ctx, including
    # the empty-context lane, where both degrade to the uniform FILL
    # read). One gather/mask/softmax chain to maintain, not two.
    # q_positions=None selects the collapsed single-comparison mask —
    # this call sits inside the engine's K-step decode scan, so the
    # per-query mask broadcast it skips would otherwise run K times
    # per dispatch.
    return paged_prefill_attention(
        q[:, None], k_pages, v_pages, block_tables,
        None, context_lens, scale, k_scales=k_scales,
        v_scales=v_scales, use_pallas=use_pallas)[:, 0]


def paged_prefill_attention(q, k_pages, v_pages, block_tables, q_positions,
                            context_lens, scale: float = 1.0,
                            k_scales=None, v_scales=None,
                            use_pallas=None):
    """Chunked-prefill attention: a fixed-size chunk of queries against
    the paged KV pool.

    The serving engine prefills a prompt in fixed ``[1, chunk]`` pieces
    (docs/serving.md): each chunk's K/V are scattered into the pool
    first, then its queries attend over EVERYTHING the sequence has
    cached so far — the shared-prefix blocks matched at admission, the
    earlier chunks, and the chunk itself — under a causal-by-absolute-
    position mask. Like :func:`paged_decode_attention` there is no
    backward pass and the work is gather-dominated, so this is the same
    fp32 masked-softmax chain, just with a query axis: scores are
    ``[B, H, C, ctx_max]`` where ``C`` is the (small, fixed) chunk and
    ``ctx_max`` the table's span. Dead key positions take the finite
    FILL; a query past its sequence's length (chunk padding) still sees
    at least key position 0, so padding lanes stay finite and are
    simply ignored by the caller.

    Args:
      q: ``[B, C, H, D]`` — the chunk's query tokens.
      k_pages, v_pages: ``[num_blocks, block_size, H, D]`` — ONE layer's
        block pool (callers index the stacked ``[L, ...]`` cache); must
        already contain this chunk's K/V.
      block_tables: ``[B, max_blocks_per_seq]`` int32 block ids in
        sequence order (out-of-bounds ids are clipped into the pool and
        the positions masked by ``context_lens``).
      q_positions: ``[B, C]`` int32 absolute position of each query
        token (the chunk's offset into the sequence) — or ``None``, the
        decode fast path: every query is THE LAST cached position
        (``context_lens - 1``), so the causal and length masks collapse
        into the single comparison ``kpos < context_lens`` and the
        per-query ``[B, C, ctx_max]`` mask broadcast is skipped
        entirely (the mask VALUES are bit-identical; only the work to
        build them goes away). The engine's multi-step decode scan runs
        this mask once per inner iteration, which is what makes the
        skip worth having.
      context_lens: ``[B]`` int32 — valid tokens in the cache INCLUDING
        this chunk's.
      scale: softmax temperature (typically ``1/sqrt(D)``).
      k_scales, v_scales: ``[num_blocks, block_size, H]`` fp32 per-row
        dequantization scales of a quantized pool (None = full
        precision; the fp path is untouched when absent, bit for bit).
        The scales gather through the SAME clipped table as the
        payload and dequantize inside the read — quantized K/V never
        materializes at full precision outside this chain.
      use_pallas: run the gather→mask→softmax→weighted-sum chain as
        ONE fused ``pallas_call``
        (:mod:`apex_tpu.ops.paged_attention_pallas`) instead of the
        composed XLA chain — READ side only (writes stay in XLA:
        Pallas TPU has no scatter lowering, the first round's lesson).
        None consults the ``APEX_PAGED_ATTENTION_PALLAS`` env flag;
        either way the kernel is taken only when its static shape
        gate holds (interpret mode always qualifies), so the XLA
        path below remains the universal fallback.

    Returns ``[B, C, H, D]`` in ``q.dtype``.

    Mesh sharding (docs/serving.md): under the engine's GSPMD mesh
    the pool, the scales, and the queries all arrive sharded on the
    HEAD axis (``H`` over ``"model"``), and the whole chain here is
    head-elementwise — gather and mask index only block/position
    axes, the softmax reduces over keys, both einsums contract ``d``
    or ``k`` per head — so GSPMD partitions it with ZERO collectives;
    the all-reduce lives in the model's row-parallel output
    projection, not in attention. (The fused Pallas route is
    single-device: the engine rejects the env flag on a sharded
    model axis.)
    """
    B, C, H, D = q.shape
    N = k_pages.shape[0]
    from apex_tpu.ops.paged_attention_pallas import (
        pallas_paged_read_wanted, pallas_paged_read_supported,
        paged_read_attention)

    if (pallas_paged_read_wanted(use_pallas)
            and pallas_paged_read_supported(k_pages,
                                            block_tables.shape[1], C)
            and not use_jnp_fallback(q, k_pages, v_pages)):
        return paged_read_attention(
            q, k_pages, v_pages, block_tables, q_positions,
            context_lens, scale, k_scales=k_scales, v_scales=v_scales)
    tbl = jnp.minimum(block_tables, N - 1)
    k = k_pages[tbl].reshape(B, -1, H, D)        # [B, ctx_max, H, D]
    v = v_pages[tbl].reshape(B, -1, H, D)
    if k_scales is not None:
        k = k.astype(jnp.float32) \
            * k_scales[tbl].reshape(B, -1, H)[..., None]
        v = v.astype(jnp.float32) \
            * v_scales[tbl].reshape(B, -1, H)[..., None]
    ctx_max = k.shape[1]

    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    kpos = jax.lax.broadcasted_iota(jnp.int32, (B, ctx_max), 1)
    if q_positions is None:
        # decode: kpos <= ctx-1 AND kpos < ctx are the same predicate;
        # [B, 1, ctx_max] broadcasts over both H and the C=1 query axis
        visible = (kpos < context_lens[:, None])[:, None, :]
    else:
        visible = ((kpos[:, None, :] <= q_positions[:, :, None])
                   & (kpos[:, None, :] < context_lens[:, None, None]))
    s = jnp.where(visible[:, None], s, FILL)     # [B, H, C, ctx_max]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
