"""Paged KV-cache: fixed-shape block pools + host-side block accounting.

The serving-side analog of vLLM's PagedAttention cache (PAPERS.md) on
XLA's terms: device memory is a fixed pool of ``num_blocks`` blocks per
layer, laid out ``[num_layers, num_blocks, block_size, num_heads,
head_dim]``, and a sequence owns a *block table* — the ordered list of
block ids holding its tokens. Every jitted program sees only fixed
shapes (the pool, a ``[B, max_blocks_per_seq]`` int32 table, and
``[B]`` lengths), so admission, eviction, and sequence growth never
trigger recompilation: the continuous-batching engine swaps table
*values*, not shapes.

Division of labor (the load-bearing design point):

- **Device side** (jit-stable, pure): :func:`paged_write` scatters new
  K/V into blocks, :func:`gather_kv` reads a sequence back out,
  :func:`copy_block` duplicates one block (the copy-on-write step), and
  :func:`gather_blocks` applies a defrag permutation. All take the
  pool + int32 indices; invalid slots are routed to an out-of-bounds
  block id and dropped by the scatter (``mode="drop"``), so inactive
  batch slots cost nothing and write nowhere.
- **Host side** (Python, between steps): :class:`BlockAllocator` owns
  the block ids — a free list, a per-block **reference count** (blocks
  are shared between sequences under prefix caching), and a
  **prefix index** mapping a hash-chain of full-block token contents to
  the block id that already holds those tokens. ``free`` releases a
  reference; a registered block whose refcount hits zero is *retained*
  in an LRU set and only actually evicted when the free list runs dry
  (:meth:`BlockAllocator.alloc` evicts least-recently-used cached
  blocks on demand). The scheduler consults the allocator; the device
  never sees it.

Prefix caching hashes full blocks only: ``hash_block_tokens`` chains
each block's hash through its predecessor's, so a block id is matched
only when the *entire* token prefix up to and including that block is
identical — the RadixAttention sharing rule (PAPERS.md) collapsed onto
a flat dict.

Storage dtype rides the existing amp policy: :func:`default_kv_dtype`
returns the active ``amp.initialize`` handle's compute dtype (bf16 for
O1-O3, fp32 for O0) unless overridden — the cache is activation-class
state, so it follows the activation precision, not the master-weight
precision.

**Quantized block storage** (``KVCache.create(quantization="int8")``,
docs/serving.md memory tiers): the K/V payload pools store int8 (or
fp8 where the backend supports it) with fp32 scales carried alongside
the pool, organized per block — ``k_scale``/``v_scale`` are ``[L, N,
bs, H]``, one scale per written (token, head) row, scattered/copied/
permuted with exactly the block ops that move the payload (so CoW,
defrag, and spill move a block's scales with its bytes). The quantize
path reuses :func:`apex_tpu.ops.multi_tensor.stochastic_round` keyed
by the token's ABSOLUTE cache position, so a given K/V row always
rounds the same way regardless of lane placement, ``decode_steps``,
or preemption/resume — quantized runs keep the engine's determinism
contract. Dequantization happens inside the attention read
(:func:`apex_tpu.ops.flash_attention.paged_prefill_attention`). With
``quantization=None`` the scale fields are ``None`` and every code
path is the pre-quantization one, bit for bit.

**Host-RAM spill tier** (:class:`HostSpillStore`, docs/serving.md):
instead of discarding an LRU-evicted or ladder-flushed prefix block,
the allocator (when a store is attached) copies its contents to a
bounded host-side LRU keyed by the block's SHA-256 chain hash; a later
prefix match re-admits it by device upload instead of recompute. The
store holds only blocks NOT currently device-indexed (re-admission
pops; re-registration discards) — the invariant
:meth:`BlockAllocator.check_integrity` enforces.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from apex_tpu.utils.integrity import payload_checksum

# the tenant every un-labelled caller is accounted to — single-tenant
# traffic runs entirely under this id and behaves exactly like the
# pre-tenancy allocator (the accounting is bookkeeping, never policy:
# allocation ORDER is tenant-blind, so default-tenant behavior is
# bit-identical)
DEFAULT_TENANT = "default"


def default_kv_dtype(dtype=None):
    """Resolve the KV-storage dtype through the amp policy: an explicit
    ``dtype`` wins; otherwise the last ``amp.initialize`` handle's
    compute dtype (bf16 under O1-O3); fp32 when amp was never set up."""
    if dtype is not None:
        return jnp.dtype(dtype)
    from apex_tpu.amp import _amp_state

    handle = _amp_state._amp_state.handle
    if handle is not None:
        return jnp.dtype(handle.properties.compute_dtype)
    return jnp.dtype(jnp.float32)


# the storage modes KVCache.create accepts (docs/serving.md memory
# tiers): None = full-precision (the amp-policy dtype), "int8" =
# symmetric int8 with per-row fp32 scales, "fp8" = float8_e4m3 with
# per-row fp32 scales (backends without an fp8 dtype raise at create)
KV_QUANT_MODES = (None, "int8", "fp8")

# base key of the quantizer's stochastic rounding, folded with each
# token's ABSOLUTE cache position — a module constant (not the engine
# seed) so the same K/V values at the same position always round
# identically across engines, restores, and re-prefills (the resume-
# determinism contract extended to the quantized path)
_KV_QUANT_SEED = 0x51CA17


def fp8_kv_dtype():
    """The fp8 storage dtype, or None when this jax has no fp8."""
    return getattr(jnp, "float8_e4m3fn", None)


def _quant_storage_dtype(quantization):
    if quantization == "int8":
        return jnp.dtype(jnp.int8)
    if quantization == "fp8":
        dt = fp8_kv_dtype()
        if dt is None:
            raise NotImplementedError(
                "kv quantization 'fp8' requires a jax with "
                "jnp.float8_e4m3fn; use 'int8' on this backend")
        return jnp.dtype(dt)
    raise ValueError(
        f"unknown kv quantization {quantization!r} "
        f"(expected one of {KV_QUANT_MODES})")


def _quant_value_max(quantization) -> float:
    """The quantizer's design max: scales are ``amax / qmax`` so the
    largest row magnitude maps onto the representable extreme."""
    if quantization == "int8":
        return 127.0
    return float(jnp.finfo(fp8_kv_dtype()).max)


def kv_block_bytes(num_layers: int, block_size: int, num_heads: int,
                   head_dim: int, dtype=None, quantization=None) -> int:
    """Device bytes one block costs across every layer — K + V payload
    plus (when quantized) the per-row fp32 scales. The number behind
    a byte-budget pool sizing and the tenant ledger's
    reduced-footprint charge for quantized blocks."""
    if quantization is None:
        item = default_kv_dtype(dtype).itemsize
        return 2 * num_layers * block_size * num_heads * head_dim * item
    item = _quant_storage_dtype(quantization).itemsize
    payload = 2 * num_layers * block_size * num_heads * head_dim * item
    scales = 2 * num_layers * block_size * num_heads * 4
    return payload + scales


class KVCache(NamedTuple):
    """The device-side block pools (a pytree of two payload arrays,
    plus two scale arrays when quantized).

    ``k`` / ``v``: ``[num_layers, num_blocks, block_size, num_heads,
    head_dim]``. The pool is allocated once at engine start and updated
    functionally (scatter in, new pytree out); the layout keeps the
    ``(num_heads * head_dim)`` product in the trailing dims so a block
    row is lane-tileable on TPU.

    ``k_scale`` / ``v_scale`` (quantized storage only, else ``None``):
    ``[num_layers, num_blocks, block_size, num_heads]`` fp32 — one
    dequantization scale per written (token, head) row, organized per
    block so every op that moves a block (scatter, CoW copy, defrag
    permutation, host spill) moves its scales by the same indices.
    """

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @property
    def quantization(self) -> Optional[str]:
        """The storage mode this pool was created with (from dtype)."""
        if self.k_scale is None:
            return None
        return "int8" if self.k.dtype == jnp.int8 else "fp8"

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def num_heads(self) -> int:
        return self.k.shape[3]

    @property
    def head_dim(self) -> int:
        return self.k.shape[4]

    def partition_specs(self, model_axis: str = "model",
                        batch_axis: Optional[str] = None) -> "KVCache":
        """The pool's mesh layout (docs/serving.md, "Mesh sharding"):
        a :class:`~jax.sharding.PartitionSpec` per pool, sharding the
        HEAD axis over ``model_axis`` — heads are the one axis the
        paged ops never index by data (scatter/gather/CoW/defrag all
        address layer/block/slot), so a head split needs zero
        collectives for pool maintenance, and the per-row scale pools
        split on the same axis so a block's scales stay colocated with
        its bytes. With ``batch_axis`` set (the data-parallel lane
        split), the BLOCK axis shards over it too: the allocator keeps
        a lane's blocks inside its shard's contiguous id range, so the
        sharded programs index only shard-local blocks and the split
        stays collective-free (docs/serving.md, "The batch axis").
        Returned as a KVCache-of-specs so callers ``tree.map`` it
        against the pool (``None`` scale fields line up with ``None``
        specs)."""
        payload = PartitionSpec(None, batch_axis, None, model_axis, None)
        scale = (None if self.k_scale is None
                 else PartitionSpec(None, batch_axis, None, model_axis))
        return KVCache(k=payload, v=payload, k_scale=scale, v_scale=scale)

    @classmethod
    def create(cls, num_layers: int, num_blocks: int, block_size: int,
               num_heads: int, head_dim: int, dtype=None,
               quantization: Optional[str] = None) -> "KVCache":
        shape = (num_layers, num_blocks, block_size, num_heads, head_dim)
        if quantization is None:
            dt = default_kv_dtype(dtype)
            return cls(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt))
        dt = _quant_storage_dtype(quantization)
        sshape = shape[:-1]
        return cls(k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt),
                   k_scale=jnp.zeros(sshape, jnp.float32),
                   v_scale=jnp.zeros(sshape, jnp.float32))


class CacheOutOfBlocks(RuntimeError):
    """The allocator cannot serve an allocation even after evicting
    every refcount-0 cached block (admission should have been
    throttled, or the pool is simply undersized for the request)."""


def hash_block_tokens(prev_hash: Optional[str],
                      tokens: Sequence[int]) -> str:
    """Chain hash for one FULL block of token ids. ``prev_hash`` is the
    previous block's chain hash (``None`` for the first block), so equal
    hashes imply the whole prefix up to and including this block is
    equal — the property prefix matching relies on. SHA-256, not
    Python's builtin ``hash``: the index serves KV blocks on hash
    equality ALONE, so a collision would silently attend one request
    against another request's cache (wrong tokens + cross-request
    prompt leakage) — a non-cryptographic, PYTHONHASHSEED-dependent
    hash is not acceptable there (vLLM hit exactly this)."""
    h = hashlib.sha256()
    if prev_hash is not None:
        h.update(prev_hash.encode("ascii"))
    h.update(np.asarray(tokens, np.int64).tobytes())
    return h.hexdigest()


class BlockAllocator:
    """Host-side block-id accounting: free list + reference counts +
    the prefix-cache index.

    Lives entirely outside jit: the scheduler calls ``alloc`` / ``free``
    / ``match_prefix`` between steps and writes the resulting ids into
    host block tables, which are shipped to the device as plain int32
    inputs.

    Lifecycle of a block id:

    - **free** — on the free list; ``alloc`` hands it out with
      refcount 1.
    - **active** — refcount >= 1. ``acquire`` adds a reference (prefix
      sharing), ``free`` drops one; dropping below zero raises (the
      double-free guard).
    - **cached** — refcount 0 but registered in the prefix index: the
      block's contents are retained and matchable. ``alloc`` evicts
      cached blocks least-recently-used when the free list is empty;
      ``match_prefix`` revives them.
    """

    def __init__(self, num_blocks: int, block_weight: float = 1.0,
                 num_shards: int = 1):
        self.num_blocks = int(num_blocks)
        # the data-parallel block-shard count (the mesh's ``batch``
        # axis size): shard ``s`` owns the contiguous id range
        # ``[s * blocks_per_shard, (s + 1) * blocks_per_shard)``, and
        # shard-scoped alloc/evict/match keep every sequence's blocks
        # inside its lane's shard — the host-side invariant that makes
        # the device-side batch split collective-free. ``num_shards=1``
        # (the default and every pre-batch-axis engine) makes every
        # shard argument a no-op: behavior is bit-identical.
        self.num_shards = int(num_shards)
        if self.num_shards < 1:
            raise ValueError(
                f"num_shards must be >= 1, got {num_shards}")
        if self.num_blocks % self.num_shards:
            raise ValueError(
                f"num_shards ({self.num_shards}) must divide num_blocks "
                f"({self.num_blocks}): the pool splits into equal "
                "contiguous shard ranges")
        self.blocks_per_shard = self.num_blocks // self.num_shards
        # the per-block charge unit of the tenant ledger: quantized
        # pools pass their reduced byte footprint relative to the
        # full-precision block (e.g. ~0.28 for int8-vs-fp32), so a
        # tenant's fractional resident charge — and therefore its
        # max_resident_blocks quota — is denominated in FULL-PRECISION
        # block equivalents and quantization genuinely buys headroom.
        # 1.0 (the default, and every unquantized engine) keeps the
        # ledger bit-identical to the pre-quantization allocator.
        if not block_weight > 0:
            raise ValueError(
                f"block_weight must be > 0, got {block_weight}")
        self.block_weight = float(block_weight)
        # the host-RAM spill tier (attach_spill): evicted/flushed
        # prefix blocks copy to this store instead of vanishing
        self.spill_store: Optional["HostSpillStore"] = None
        self._spill_fetch = None
        # pop() from the end serves ascending ids first — keeps early
        # allocations compact, which makes defrag cheap in the common case
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._ref: Dict[int, int] = {}            # block id -> refcount (>0)
        self._hash_to_block: Dict[str, int] = {}  # prefix index
        self._block_to_hash: Dict[int, str] = {}
        # refcount-0 registered blocks, insertion order = LRU order
        self._evictable: "OrderedDict[int, None]" = OrderedDict()
        self.num_evictions = 0
        # -- per-tenant accounting (docs/robustness.md, isolation) -----
        # Every reference is attributed to a tenant: _tenant_refs[b]
        # splits _ref[b] by holder, so a block shared across tenants
        # charges each FRACTIONALLY by refcount (tenant_charge). Cached
        # (refcount-0, prefix-indexed) blocks are attributed to the
        # tenant that REGISTERED them (_cached_owner), so rung-2
        # flushes and LRU evictions charge the tenant whose traffic
        # parked the block. Pure bookkeeping: allocation/eviction ORDER
        # never consults a tenant, so single-tenant behavior is
        # bit-identical to the pre-tenancy allocator.
        self._tenant_refs: Dict[int, Dict[str, int]] = {}
        self._cached_owner: Dict[int, str] = {}
        self._evicted_by_tenant: Dict[str, int] = {}
        self._flushed_by_tenant: Dict[str, int] = {}
        # incrementally-maintained fractional charge per tenant (the
        # O(1) read behind tenant_charge — the engine consults it per
        # admission candidate and per lane-growth check, so a scan
        # over every active block would sit on the scheduler's hot
        # path). _charge_block applies/removes one block's current
        # shares around each mutation; check_integrity re-derives the
        # exact sums and REBASES, bounding float drift.
        self._tenant_charge_acc: Dict[str, float] = {}

    # -- accounting --------------------------------------------------------

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_cached(self) -> int:
        """Refcount-0 blocks retained for prefix reuse (evictable)."""
        return len(self._evictable)

    @property
    def num_used(self) -> int:
        """Blocks currently referenced by live sequences."""
        return self.num_blocks - len(self._free) - len(self._evictable)

    def shard_of(self, block_id: int) -> int:
        """The data-parallel shard owning a block id (shard ranges are
        contiguous: ``id // blocks_per_shard``). Always 0 unsharded."""
        return int(block_id) // self.blocks_per_shard

    def free_in_shard(self, shard: int) -> int:
        """Free blocks inside one shard's id range."""
        return sum(1 for b in self._free
                   if b // self.blocks_per_shard == shard)

    def cached_in_shard(self, shard: int) -> int:
        """Evictable (refcount-0, prefix-indexed) blocks inside one
        shard's id range."""
        return sum(1 for b in self._evictable
                   if b // self.blocks_per_shard == shard)

    @property
    def utilization(self) -> float:
        """Fraction of pool blocks currently owned by live sequences."""
        return self.num_used / max(self.num_blocks, 1)

    def refcount(self, block_id: int) -> int:
        return self._ref.get(int(block_id), 0)

    def tenant_refcount(self, block_id: int, tenant: str) -> int:
        """How many of a block's references ``tenant`` holds."""
        return self._tenant_refs.get(int(block_id), {}).get(tenant, 0)

    def _charge_block(self, b: int, sign: int) -> None:
        """Apply (+1) or remove (-1) block ``b``'s CURRENT per-tenant
        fractional shares to the running charge accumulator — called
        around every mutation of the block's holder set."""
        total = self._ref.get(b, 0)
        if not total:
            return
        w = self.block_weight
        for t, n in self._tenant_refs[b].items():
            self._tenant_charge_acc[t] = \
                self._tenant_charge_acc.get(t, 0.0) + sign * w * n / total

    def tenant_charge(self, tenant: str) -> float:
        """The tenant's fractional resident-block charge: each active
        block contributes ``block_weight * tenant_refs / total_refs``
        — a private block charges ``block_weight`` (1.0 unquantized;
        the reduced byte footprint for quantized pools), a block
        shared evenly across two tenants charges each half that. This
        is the number the engine's ``max_resident_blocks`` quota is
        enforced against (sharing a prefix makes a tenant CHEAPER,
        never more expensive — and so does quantization). O(1):
        maintained incrementally by the mutation paths."""
        return max(0.0, self._tenant_charge_acc.get(tenant, 0.0))

    def tenant_stats(self) -> Dict[str, Dict[str, object]]:
        """Per-tenant accounting picture: fractional resident charge,
        cached (evictable) blocks attributed by registering tenant, and
        the eviction/flush attribution counters."""
        tenants = set(self._evicted_by_tenant) | set(self._flushed_by_tenant)
        for refs in self._tenant_refs.values():
            tenants.update(refs)
        cached_by: Dict[str, int] = {}
        for b in self._evictable:
            owner = self._cached_owner.get(b)
            if owner is not None:
                tenants.add(owner)
                cached_by[owner] = cached_by.get(owner, 0) + 1
        return {t: {
            "resident_block_charge": round(self.tenant_charge(t), 6),
            "cached_blocks": cached_by.get(t, 0),
            "evicted_blocks": self._evicted_by_tenant.get(t, 0),
            "flushed_blocks": self._flushed_by_tenant.get(t, 0),
        } for t in sorted(tenants)}

    # -- the host-RAM spill tier (docs/serving.md memory tiers) ------------

    def attach_spill(self, store: "HostSpillStore", fetch) -> None:
        """Wire the host spill tier in: every block
        :meth:`_evict_one` drops (LRU pressure or a ladder flush) is
        first copied to ``store`` under its chain hash, using
        ``fetch(block_id) -> payload dict | None`` to read the device
        contents (the engine owns the pool, so it owns the fetch — a
        fetch returning None, e.g. on a transient device error, simply
        skips the spill: the tier is an optimization, never a
        correctness dependency). :meth:`register_prefix` discards the
        stored copy for a hash the moment a device block is indexed
        under it, keeping the store's contents disjoint from the
        device index (the :meth:`check_integrity` invariant)."""
        self.spill_store = store
        self._spill_fetch = fetch

    # -- alloc / free / share ----------------------------------------------

    def _evict_one(self, flushed: bool = False,
                   shard: Optional[int] = None) -> int:
        """Drop the least-recently-used cached block (unregister it),
        charging the eviction to the tenant that registered the block
        (``flushed`` routes the charge to the flush counter — the
        degradation ladder's rung-2 accounting). With a spill tier
        attached, the block's contents are copied to the host store
        first — the eviction stops being a future recompute and
        becomes a future upload. ``shard`` restricts the LRU walk to
        one shard's id range (the batch-axis pools evict only where
        the allocation must land); raises ``KeyError`` when that shard
        holds no cached block — callers gate on
        :meth:`cached_in_shard`."""
        if shard is None:
            b, _ = self._evictable.popitem(last=False)
        else:
            b = next(x for x in self._evictable
                     if x // self.blocks_per_shard == shard)
            del self._evictable[b]
        h = self._block_to_hash.pop(b)
        del self._hash_to_block[h]
        owner = self._cached_owner.pop(b, None)
        if self.spill_store is not None and self._spill_fetch is not None:
            payload = self._spill_fetch(b)
            if payload is not None:
                self.spill_store.put(h, payload,
                                     tenant=owner or DEFAULT_TENANT)
        if owner is not None:
            counter = (self._flushed_by_tenant if flushed
                       else self._evicted_by_tenant)
            counter[owner] = counter.get(owner, 0) + 1
        self.num_evictions += 1
        return b

    def alloc(self, n: int, tenant: str = DEFAULT_TENANT,
              shard: Optional[int] = None) -> List[int]:
        """Hand out ``n`` blocks at refcount 1 (charged to ``tenant``),
        evicting LRU cached blocks when the free list alone cannot
        serve the request. ``shard`` restricts the allocation to one
        shard's contiguous id range (the batch-axis engine allocates a
        lane's blocks only on the lane's shard); a shard-scoped
        request that cannot be served from THAT shard raises
        ``CacheOutOfBlocks`` even when other shards hold free blocks —
        cross-shard placement would break the collective-free device
        split. ``shard=None`` (and every single-shard allocator) is
        the pre-batch-axis path, bit for bit."""
        if shard is None or self.num_shards == 1:
            if n > len(self._free) + len(self._evictable):
                raise CacheOutOfBlocks(
                    f"requested {n} blocks, {len(self._free)} free + "
                    f"{len(self._evictable)} evictable of "
                    f"{self.num_blocks}")
            out = []
            for _ in range(n):
                b = self._free.pop() if self._free else self._evict_one()
                self._ref[b] = 1
                self._tenant_refs[b] = {tenant: 1}
                self._charge_block(b, +1)
                out.append(b)
            return out
        shard = int(shard)
        if not 0 <= shard < self.num_shards:
            raise ValueError(
                f"shard {shard} out of range [0, {self.num_shards})")
        free_s = self.free_in_shard(shard)
        if n > free_s + self.cached_in_shard(shard):
            raise CacheOutOfBlocks(
                f"requested {n} blocks on shard {shard}, {free_s} free "
                f"+ {self.cached_in_shard(shard)} evictable of "
                f"{self.blocks_per_shard} shard blocks")
        out = []
        for _ in range(n):
            b = None
            # same LIFO discipline as the unsharded pop(): the most
            # recently freed block of the shard serves first
            for i in range(len(self._free) - 1, -1, -1):
                if self._free[i] // self.blocks_per_shard == shard:
                    b = self._free.pop(i)
                    break
            if b is None:
                b = self._evict_one(shard=shard)
            self._ref[b] = 1
            self._tenant_refs[b] = {tenant: 1}
            self._charge_block(b, +1)
            out.append(b)
        return out

    def free(self, ids: Sequence[int], tenant: str = DEFAULT_TENANT) -> None:
        """Release one of ``tenant``'s references per id. A registered
        block whose count hits zero is retained as cached (evictable);
        an unregistered one returns to the free list. Raises
        ``ValueError`` on an unknown block id, a double free (releasing
        a block that holds no reference), or a tenant releasing a
        reference it never took, instead of silently corrupting the
        free list or the tenant ledger."""
        for b in ids:
            b = int(b)
            if not (0 <= b < self.num_blocks):
                raise ValueError(f"block id {b} out of range")
            if self._ref.get(b, 0) <= 0:
                raise ValueError(f"double free of block {b}")
            holders = self._tenant_refs[b]
            if holders.get(tenant, 0) <= 0:
                raise ValueError(
                    f"tenant {tenant!r} holds no reference on block {b} "
                    f"(holders: {holders})")
            self._charge_block(b, -1)
            holders[tenant] -= 1
            if holders[tenant] == 0:
                del holders[tenant]
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                del self._tenant_refs[b]
                if b in self._block_to_hash:
                    self._evictable[b] = None      # most-recently-used end
                else:
                    self._free.append(b)
            else:
                self._charge_block(b, +1)

    def acquire(self, ids: Sequence[int],
                tenant: str = DEFAULT_TENANT) -> None:
        """Add one reference per id for ``tenant`` (prefix sharing).
        Revives cached (refcount-0) blocks; raises for blocks that are
        neither active nor cached — a free block holds no meaningful
        contents."""
        for b in ids:
            b = int(b)
            if self._ref.get(b, 0) > 0:
                self._charge_block(b, -1)
                self._ref[b] += 1
                holders = self._tenant_refs[b]
                holders[tenant] = holders.get(tenant, 0) + 1
                self._charge_block(b, +1)
            elif b in self._evictable:
                del self._evictable[b]
                self._ref[b] = 1
                self._tenant_refs[b] = {tenant: 1}
                self._charge_block(b, +1)
            else:
                raise ValueError(
                    f"cannot acquire block {b}: neither active nor cached")

    # -- the prefix index --------------------------------------------------

    def register_prefix(self, block_hash: str, block_id: int,
                        tenant: str = DEFAULT_TENANT) -> bool:
        """Index a FULL block's contents under its chain hash. First
        registration wins — a concurrent identical prefill keeps the
        already-indexed block and leaves the duplicate unregistered (it
        returns to the free list when released). The winning
        registration records ``tenant`` as the block's cached-state
        owner: if the block is ever evicted or flushed while cached,
        THAT tenant is charged. Returns whether this block is now the
        indexed one."""
        block_id = int(block_id)
        if block_hash in self._hash_to_block:
            return self._hash_to_block[block_hash] == block_id
        if block_id in self._block_to_hash:   # already indexed elsewhere
            return False
        self._hash_to_block[block_hash] = block_id
        self._block_to_hash[block_id] = block_hash
        self._cached_owner[block_id] = tenant
        if self.spill_store is not None:
            # a device block now serves this hash: the host copy is
            # redundant (and would violate the disjointness invariant
            # check_integrity enforces) — a fresh recompute registering
            # the same content supersedes the spilled copy
            self.spill_store.discard(block_hash)
        return True

    def indexed_block(self, block_hash: str) -> Optional[int]:
        """The device block currently serving a chain hash, or None —
        the read-only point lookup behind the fleet router's affinity
        probe and the migration transport's device-vs-spill split."""
        return self._hash_to_block.get(block_hash)

    def lookup_prefix(self, hashes: Sequence[str],
                      shard: Optional[int] = None) -> List[int]:
        """Longest indexed prefix of the hash chain, WITHOUT taking
        references — for capacity checks before committing to an
        admission (no rollback, no LRU perturbation). ``shard`` stops
        the walk at the first block OUTSIDE that shard's id range: a
        batch-axis lane can only share blocks resident on its own
        shard (a cross-shard match would put a foreign block id in a
        table the sharded program cannot reach)."""
        out: List[int] = []
        for h in hashes:
            b = self._hash_to_block.get(h)
            if b is None:
                break
            if (shard is not None
                    and b // self.blocks_per_shard != shard):
                break
            out.append(b)
        return out

    def match_prefix(self, hashes: Sequence[str],
                     tenant: str = DEFAULT_TENANT,
                     shard: Optional[int] = None) -> List[int]:
        """Longest indexed prefix of the hash chain: returns the block
        ids (in sequence order) and acquires a reference on each for
        ``tenant`` — callers own the returned blocks and must ``free``
        them under the same tenant. ``shard`` applies the
        :meth:`lookup_prefix` shard restriction."""
        out = self.lookup_prefix(hashes, shard=shard)
        self.acquire(out, tenant=tenant)
        return out

    def trim_to(self, blocks: Sequence[int], keep: int,
                tenant: str = DEFAULT_TENANT) -> List[int]:
        """Release the tail of a sequence's block list past its first
        ``keep`` entries and return the kept prefix as a new list — the
        **speculative-reservation rollback**: the engine reserves
        blocks for a verify span's worst case (every draft written),
        and when rejection leaves the sequence short of the span, the
        blocks holding only unaccepted positions go back to the pool
        here instead of idling on the slot until the request finishes.

        Safety contract, enforced: a trimmed block must be PRIVATE
        (refcount exactly 1) and UNREGISTERED — a shared or
        prefix-indexed block holds context some sequence (or the cache
        index) still reaches, and trimming it would be a use-after-free
        of live K/V. Violations raise ``ValueError`` before anything is
        released. The tail is freed deepest-first, matching the other
        release paths."""
        blocks = [int(b) for b in blocks]
        keep = int(keep)
        if not 0 <= keep <= len(blocks):
            raise ValueError(
                f"keep must be in [0, {len(blocks)}], got {keep}")
        tail = blocks[keep:]
        for b in tail:
            if self._ref.get(b, 0) != 1:
                raise ValueError(
                    f"cannot trim block {b}: refcount "
                    f"{self._ref.get(b, 0)} != 1 (shared or not owned)")
            if b in self._block_to_hash:
                raise ValueError(
                    f"cannot trim block {b}: registered in the prefix "
                    "index (it is matchable cached context)")
        self.free(list(reversed(tail)), tenant=tenant)
        return blocks[:keep]

    def flush_evictable(self) -> int:
        """Evict EVERY cached (refcount-0, prefix-indexed) block back
        to the free list — the degradation ladder's aggressive-eviction
        rung (docs/robustness.md): under sustained pool pressure the
        engine trades future prefix hits for immediately-allocatable
        headroom. Each drop counts as an eviction (the blocks really do
        leave the index) and charges the registering tenant's flush
        counter. Returns how many blocks were flushed."""
        n = len(self._evictable)
        while self._evictable:
            self._free.append(self._evict_one(flushed=True))
        return n

    def reset(self) -> None:
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._ref.clear()
        self._hash_to_block.clear()
        self._block_to_hash.clear()
        self._evictable.clear()
        self._tenant_refs.clear()
        self._cached_owner.clear()
        self._tenant_charge_acc.clear()
        # the eviction/flush attribution counters deliberately survive:
        # reset is the crash-recovery path, and observability should
        # not lose history to it (matching num_evictions)

    # -- robustness: audit + integrity (docs/robustness.md) ----------------

    def snapshot_state(self) -> Dict[str, object]:
        """JSON-serializable picture of the allocator: refcounts, the
        prefix index, the evictable LRU order, and the free list. This
        is the AUDIT section of an engine snapshot — block ids and the
        KV contents behind them do not survive a process, so restore
        rebuilds allocator state from re-prefills rather than loading
        this (tests verify the rebuild reproduces the same hash chains
        and refcount structure)."""
        return {
            "refcounts": {str(b): int(c) for b, c in self._ref.items()},
            "prefix_index": dict(self._hash_to_block),
            "evictable": [int(b) for b in self._evictable],
            "free": [int(b) for b in self._free],
            "num_evictions": int(self.num_evictions),
            "tenant_refs": {str(b): dict(refs)
                            for b, refs in self._tenant_refs.items()},
            "cached_owners": {str(b): t
                              for b, t in self._cached_owner.items()},
            "evicted_by_tenant": dict(self._evicted_by_tenant),
            "flushed_by_tenant": dict(self._flushed_by_tenant),
        }

    def check_integrity(self, expected_refcounts: Optional[Dict[int, int]]
                        = None,
                        expected_tenant_refs: Optional[
                            Dict[int, Dict[str, int]]] = None) -> None:
        """Raise ``ValueError`` on any violated allocator invariant:
        every block in exactly one of {free, active, cached}; the
        hash↔block maps a bijection; cached blocks registered at
        refcount 0; the per-tenant reference split summing exactly to
        each block's refcount; and, when the caller supplies the
        refcounts its own bookkeeping implies (one per sequence
        referencing the block — optionally split by tenant), an EXACT
        match against the internal counts."""
        free, active = set(self._free), set(self._ref)
        cached = set(self._evictable)
        if len(free) != len(self._free):
            raise ValueError("free list contains duplicates")
        for name, ids in (("free", free), ("active", active),
                          ("cached", cached)):
            bad = [b for b in ids if not 0 <= b < self.num_blocks]
            if bad:
                raise ValueError(f"{name} ids out of range: {bad}")
        overlaps = (free & active) | (free & cached) | (active & cached)
        if overlaps:
            raise ValueError(f"blocks in multiple states: {sorted(overlaps)}")
        if len(free) + len(active) + len(cached) != self.num_blocks:
            raise ValueError(
                f"state partition covers {len(free) + len(active) + len(cached)}"
                f" of {self.num_blocks} blocks")
        if any(c <= 0 for c in self._ref.values()):
            raise ValueError("active block with non-positive refcount")
        inv = {b: h for h, b in self._hash_to_block.items()}
        if inv != self._block_to_hash:
            raise ValueError("prefix index hash<->block maps disagree")
        unregistered = cached - set(self._block_to_hash)
        if unregistered:
            raise ValueError(
                f"cached blocks missing from the index: {sorted(unregistered)}")
        registered_free = free & set(self._block_to_hash)
        if registered_free:
            raise ValueError(
                f"free blocks still indexed: {sorted(registered_free)}")
        if set(self._tenant_refs) != active:
            raise ValueError(
                f"tenant-ref map keys {sorted(self._tenant_refs)} != "
                f"active blocks {sorted(active)}")
        for b, refs in self._tenant_refs.items():
            if any(c <= 0 for c in refs.values()):
                raise ValueError(
                    f"block {b}: non-positive tenant refcount {refs}")
            if sum(refs.values()) != self._ref[b]:
                raise ValueError(
                    f"block {b}: tenant refs {refs} sum to "
                    f"{sum(refs.values())}, refcount is {self._ref[b]}")
        stray_owner = set(self._cached_owner) - set(self._block_to_hash)
        if stray_owner:
            raise ValueError(
                f"cached-owner entries for unregistered blocks: "
                f"{sorted(stray_owner)}")
        # the host spill tier must stay disjoint from the device index
        # (a hash served by a resident block has no business holding a
        # host copy — re-admission pops, re-registration discards) and
        # within its configured byte bound
        if self.spill_store is not None:
            overlap = (set(self.spill_store.hashes())
                       & set(self._hash_to_block))
            if overlap:
                raise ValueError(
                    f"{len(overlap)} hash(es) both device-indexed and "
                    f"spilled (e.g. {sorted(overlap)[:2]})")
            if self.spill_store.total_bytes > self.spill_store.max_bytes:
                raise ValueError(
                    f"spill store holds {self.spill_store.total_bytes} "
                    f"bytes, over its {self.spill_store.max_bytes} bound")
        # the incremental charge accumulator must track the exact
        # per-block sums (within float tolerance); verified then
        # REBASED to the exact values so drift never accumulates
        # across integrity checkpoints
        exact: Dict[str, float] = {}
        for b, refs in self._tenant_refs.items():
            for t, n in refs.items():
                exact[t] = exact.get(t, 0.0) \
                    + self.block_weight * n / self._ref[b]
        for t in set(exact) | set(self._tenant_charge_acc):
            if abs(exact.get(t, 0.0)
                   - self._tenant_charge_acc.get(t, 0.0)) > 1e-6:
                raise ValueError(
                    f"tenant {t!r}: incremental charge "
                    f"{self._tenant_charge_acc.get(t, 0.0)} diverged "
                    f"from exact {exact.get(t, 0.0)}")
        self._tenant_charge_acc = exact
        if expected_tenant_refs is not None:
            expect = {int(b): {t: int(c) for t, c in refs.items() if c > 0}
                      for b, refs in expected_tenant_refs.items()}
            expect = {b: refs for b, refs in expect.items() if refs}
            if expect != self._tenant_refs:
                raise ValueError(
                    f"tenant refs diverge from caller bookkeeping: "
                    f"expected {expect}, allocator holds "
                    f"{self._tenant_refs}")
        if expected_refcounts is not None:
            expected = {int(b): int(c) for b, c in expected_refcounts.items()
                        if int(c) > 0}
            if expected != self._ref:
                raise ValueError(
                    f"refcounts diverge from caller bookkeeping: "
                    f"expected {expected}, allocator holds {self._ref}")


def blocks_needed(num_tokens: int, block_size: int) -> int:
    return -(-int(num_tokens) // int(block_size))


def seq_block_hashes(tokens: Sequence[int],
                     block_size: int) -> List[str]:
    """The chain-hash walk over a token sequence's FULL blocks — the
    one shared builder behind the engine's prefix matching and the
    fleet router's affinity probe / migration transport (two copies
    drifting apart would silently break cross-replica hash
    comparability)."""
    hashes: List[str] = []
    prev = None
    for j in range(len(tokens) // block_size):
        prev = hash_block_tokens(
            prev, tokens[j * block_size: (j + 1) * block_size])
        hashes.append(prev)
    return hashes


class HostSpillStore:
    """The host-RAM spill tier of the prefix cache (docs/serving.md
    memory tiers): a bounded LRU of evicted prefix blocks, keyed by
    the SHA-256 chain hash the device index uses — hashes are globally
    comparable, so a spilled block is re-admittable by ANY engine with
    the same model/config (the fleet-migration enabler ROADMAP item 2
    names).

    Each entry is one block's full device contents as host numpy
    arrays: ``{"k": [L, bs, H, D], "v": [L, bs, H, D]}`` in the pool's
    storage dtype, plus ``"k_scale"``/``"v_scale"`` (``[L, bs, H]``
    fp32) for quantized pools — a spilled quantized block re-admits
    bit-identically, scales included. ``max_bytes`` bounds the payload
    total; inserts evict least-recently-used entries past it (and an
    entry larger than the whole bound is dropped on arrival, counted
    as an eviction).

    The store is an OPTIMIZATION tier, never identity: entries are
    audit-only in ``snapshot()`` (restore never reads them), a miss
    just means recompute, and a hit is token-identical to recompute
    (the re-admit equivalence cert in tests/test_kv_memory.py).

    **Integrity** (docs/robustness.md, "Data integrity"): with
    ``verify=True`` every entry stores a SHA-256 content checksum
    taken at :meth:`put`, re-checked at every read (:meth:`pop` /
    :meth:`export_entry`) and by the background :meth:`scrub` — a
    mismatch (host-RAM rot, a corrupted copy) discards the entry,
    counts it (``corrupt_discards``), reports it through
    ``on_corrupt(site, block_hash)``, and reads as a plain miss: the
    tier's whole contract is that a miss means recompute, so detection
    degrades to correctness, never to an error. ``corrupt_hook(site,
    payload) -> payload`` is the chaos seam (the engine wires its
    :class:`~apex_tpu.utils.faults.FaultPlan`'s ``"spill_put"`` /
    ``"spill_get"`` corrupt sites through it); with ``verify=False``
    no checksum is taken and reads trust their bytes — byte-identical
    to the pre-integrity store."""

    def __init__(self, max_bytes: int, verify: bool = True,
                 corrupt_hook=None, on_corrupt=None):
        if max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1, got {max_bytes}")
        self.max_bytes = int(max_bytes)
        self.verify = bool(verify)
        self._corrupt_hook = corrupt_hook
        self._on_corrupt = on_corrupt
        # hash -> {"payload": dict of np arrays, "tenant": str,
        # "bytes": int, "checksum": str|None}; insertion order = LRU
        # order (puts re-insert)
        self._entries: "OrderedDict[str, Dict[str, object]]" = \
            OrderedDict()
        self.total_bytes = 0
        self.puts = 0          # lifetime blocks spilled in
        self.evictions = 0     # entries dropped by the byte bound
        self.refused = 0           # oversize entries never admitted
        self.corrupt_discards = 0  # entries dropped on checksum mismatch
        self._scrub_cursor = 0     # round-robin position of scrub()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, block_hash: str) -> bool:
        return block_hash in self._entries

    def hashes(self):
        return self._entries.keys()

    def entry_tenants(self) -> Dict[str, str]:
        """Chain hash -> owning tenant for every resident entry — the
        fleet router's shared-tier publish sweep reads this to carry
        attribution across the transport (JSON-friendly: part of the
        narrow replica surface)."""
        return {h: str(rec["tenant"])
                for h, rec in self._entries.items()}

    def _drop(self, block_hash: str) -> None:
        rec = self._entries.pop(block_hash)
        self.total_bytes -= rec["bytes"]

    def put(self, block_hash: str, payload: Dict[str, np.ndarray],
            tenant: str = DEFAULT_TENANT) -> bool:
        """Insert (or refresh) a block's contents at the MRU end,
        evicting LRU entries past the byte bound. Returns whether the
        entry is resident after the call."""
        nbytes = sum(int(a.nbytes) for a in payload.values()
                     if isinstance(a, np.ndarray))
        if block_hash in self._entries:
            self._drop(block_hash)
        self.puts += 1
        if nbytes > self.max_bytes:
            self.evictions += 1
            self.refused += 1
            return False
        # checksum the TRUE bytes first, then let the chaos hook rot
        # them — exactly the order real corruption happens in (the
        # checksum is taken at the source; the flip happens in RAM)
        checksum = payload_checksum(payload) if self.verify else None
        if self._corrupt_hook is not None:
            payload = self._corrupt_hook("spill_put", payload)
        self._entries[block_hash] = {
            "payload": payload, "tenant": tenant, "bytes": nbytes,
            "checksum": checksum}
        self.total_bytes += nbytes
        while self.total_bytes > self.max_bytes:
            # every removal funnels through _drop so subclasses that
            # keep per-entry side tables (SharedPrefixStore's refcounts
            # and ownership shares) stay consistent under eviction
            self._drop(next(iter(self._entries)))
            self.evictions += 1
        return block_hash in self._entries

    def _read_ok(self, block_hash: str, payload, checksum) -> bool:
        """The shared read-side verification: recompute the payload's
        checksum against the one taken at put. A mismatch counts as a
        corrupt discard and reports through ``on_corrupt`` — the
        caller turns it into a miss (recompute serves the request)."""
        if not self.verify or checksum is None:
            return True
        if payload_checksum(payload) == checksum:
            return True
        self.corrupt_discards += 1
        if self._on_corrupt is not None:
            self._on_corrupt("spill_get", block_hash)
        return False

    def pop(self, block_hash: str) -> Optional[Dict[str, np.ndarray]]:
        """Remove and return a block's payload (None on miss OR on a
        checksum mismatch — a corrupt entry is discarded, counted, and
        served by recompute) — the re-admission read. Popping (rather
        than peeking) keeps the store disjoint from the device index:
        the caller is about to upload and register a device block
        under this hash."""
        rec = self._entries.get(block_hash)
        if rec is None:
            return None
        self._drop(block_hash)
        payload = rec["payload"]
        if self._corrupt_hook is not None:
            payload = self._corrupt_hook("spill_get", payload)
        if not self._read_ok(block_hash, payload, rec.get("checksum")):
            return None
        return payload

    def discard(self, block_hash: str) -> None:
        if block_hash in self._entries:
            self._drop(block_hash)

    # -- cross-replica transport (docs/fleet.md) ---------------------------

    def export_entry(self, block_hash: str
                     ) -> Optional[Dict[str, np.ndarray]]:
        """A deep-copied payload for cross-replica transport (None on
        miss). A PEEK, not a pop: the entry stays resident here (the
        exporting replica keeps serving it) and its LRU recency is
        untouched — chain hashes are globally comparable, so the copy
        is re-admittable by any engine with the same model/config
        (:meth:`import_entry` on the receiving store)."""
        rec = self._entries.get(block_hash)
        if rec is None:
            return None
        payload = {k: np.array(v, copy=True)
                   for k, v in rec["payload"].items()}
        if self._corrupt_hook is not None:
            payload = self._corrupt_hook("spill_get", payload)
        if not self._read_ok(block_hash, payload, rec.get("checksum")):
            # rot detected on the read: the resident entry is no
            # longer trustworthy either — discard it (a future local
            # hit would re-detect anyway; dropping now keeps the
            # byte accounting honest)
            self._drop(block_hash)
            return None
        return payload

    def import_entry(self, block_hash: str,
                     payload: Dict[str, np.ndarray],
                     tenant: str = DEFAULT_TENANT) -> bool:
        """Insert a payload exported by another replica's store (or
        read from its device pool): validated for the K/V keys, then
        standard :meth:`put` semantics — MRU insert, byte-bound LRU
        eviction. Returns whether the entry is resident after the
        call. The importing engine's next prefix match re-admits it by
        device upload, token-identical to recompute (the migration
        transport's correctness rests on the same re-admit cert as
        local spill hits)."""
        missing = [k for k in ("k", "v") if k not in payload]
        if missing:
            raise ValueError(
                f"imported payload for {block_hash!r} is missing "
                f"{missing} (expected the block's K/V arrays)")
        return self.put(block_hash, payload, tenant=tenant)

    def scrub(self, n: int) -> Tuple[int, int]:
        """Re-verify up to ``n`` resident entries against their put-time
        checksums, round-robin from where the last scrub stopped — the
        background integrity pass (docs/robustness.md): rot in a COLD
        entry is found while recompute is still cheap, not at the
        admission that needed it. Corrupt entries are discarded and
        counted exactly like a read-side detection. Returns
        ``(entries_verified, corruptions_found)``; (0, 0) with
        verification off or an empty store."""
        if not self.verify or n < 1 or not self._entries:
            return (0, 0)
        hashes = list(self._entries.keys())
        start = self._scrub_cursor % len(hashes)
        scanned = min(int(n), len(hashes))
        verified = corrupt = 0
        for j in range(scanned):
            h = hashes[(start + j) % len(hashes)]
            rec = self._entries.get(h)
            if rec is None or rec.get("checksum") is None:
                continue
            verified += 1
            if payload_checksum(rec["payload"]) != rec["checksum"]:
                self._drop(h)
                self.corrupt_discards += 1
                corrupt += 1
                if self._on_corrupt is not None:
                    self._on_corrupt("scrub", h)
        self._scrub_cursor = start + scanned
        return (verified, corrupt)

    def stats(self) -> Dict[str, int]:
        return {
            "blocks": len(self._entries),
            "bytes": int(self.total_bytes),
            "puts": int(self.puts),
            "evictions": int(self.evictions),
            # the uniform refusal/corruption surface (docs/robustness.md
            # "Data integrity"): oversize entries never admitted, and
            # entries dropped on a checksum mismatch
            "refused": int(self.refused),
            "corrupt_discards": int(self.corrupt_discards),
        }


class SharedPrefixStore(HostSpillStore):
    """The FLEET-level shared prefix tier (docs/fleet.md, "Shared
    prefix tier"): one byte-budgeted, content-addressed store the
    router owns, fed by replica evictions and finished-prefill
    handoffs, probed at placement so a prefix prefilled on any replica
    is warm fleet-wide. Same checksummed-entry discipline as the
    per-replica :class:`HostSpillStore` it extends — put-time SHA-256
    checksums re-verified at every read and by the round-robin
    :meth:`scrub`, corrupt entries discarded-and-recomputed, LRU past
    ``max_bytes`` — plus the two things a SHARED tier needs:

    **Refcounted dedupe.** Entries are content-addressed by chain
    hash, so the same prefix published from two replicas stores ONCE:
    a re-publish of a resident hash adds a reference (and an ownership
    share) instead of bytes, counted in ``dedupe_hits``. Eviction and
    corruption discards drop the entry with all its references — the
    tier is a cache, and a reference is attribution, not a pin.

    **Fractional ownership attribution.** Each entry carries per-tenant
    publisher shares; :meth:`tenant_bytes` charges an entry's bytes to
    its owning tenants proportionally (the fractional block ledger
    discipline, applied to the shared tier), which is what the fleet's
    ``stats()["tenants"]`` ``shared_tier_bytes`` rows read.
    :meth:`check_integrity` audits the refcount/share/byte invariants
    the same way the allocator audits its ledger."""

    def __init__(self, max_bytes: int, verify: bool = True,
                 corrupt_hook=None, on_corrupt=None):
        super().__init__(max_bytes, verify=verify,
                         corrupt_hook=corrupt_hook,
                         on_corrupt=on_corrupt)
        # per-resident-hash publisher refcount, and the per-tenant
        # share split of that refcount (sums to it; audited)
        self._refs: Dict[str, int] = {}
        self._owners: Dict[str, Dict[str, int]] = {}
        self.dedupe_hits = 0   # publishes deduped against a resident entry

    def _drop(self, block_hash: str) -> None:
        super()._drop(block_hash)
        self._refs.pop(block_hash, None)
        self._owners.pop(block_hash, None)

    def publish(self, block_hash: str,
                payload: Optional[Dict[str, np.ndarray]] = None,
                tenant: str = DEFAULT_TENANT) -> bool:
        """Content-addressed insert with refcounted dedupe. A resident
        hash gains a reference and an ownership share — no bytes
        stored, no payload needed (``payload=None`` is the publisher
        saying "I hold these bytes too"), and the entry refreshes to
        MRU (a re-publish is evidence of fleet-wide heat). A new hash
        needs its payload and follows :meth:`HostSpillStore.put`
        semantics (checksum at the source, byte-bound LRU eviction).
        Returns whether the entry is resident after the call."""
        if block_hash in self._entries:
            self.dedupe_hits += 1
            self._refs[block_hash] += 1
            shares = self._owners[block_hash]
            shares[tenant] = shares.get(tenant, 0) + 1
            self._entries.move_to_end(block_hash)
            return True
        if payload is None:
            return False
        if self.put(block_hash, payload, tenant=tenant):
            self._refs[block_hash] = 1
            self._owners[block_hash] = {tenant: 1}
            return True
        return False

    def fetch(self, block_hash: str
              ) -> Optional[Dict[str, np.ndarray]]:
        """A deep-copied payload for seeding a replica's local spill
        tier (None on miss or checksum mismatch — a corrupt entry is
        discarded with its references and served by recompute). A PEEK
        like :meth:`export_entry` — the tier keeps serving the other
        replicas — but a fetch IS a hit, so the entry refreshes to MRU
        (export_entry's transport reads deliberately do not)."""
        payload = self.export_entry(block_hash)
        if payload is not None:
            self._entries.move_to_end(block_hash)
        return payload

    def probe(self, hashes: Sequence[str], start: int = 0) -> int:
        """Length of the contiguous resident run of ``hashes``
        beginning at ``start`` — the placement-time coverage probe
        (read-only; same leading-run discipline as the engine's
        prefix match)."""
        n = int(start)
        while n < len(hashes) and hashes[n] in self._entries:
            n += 1
        return n - int(start)

    def tenant_bytes(self) -> Dict[str, float]:
        """Per-tenant fractional byte charge: each entry's bytes split
        across its owning tenants by publisher share (an entry two
        tenants each published once charges half to each)."""
        out: Dict[str, float] = {}
        for h, rec in self._entries.items():
            refs = self._refs.get(h, 1)
            for t, n in (self._owners.get(h) or {}).items():
                out[t] = out.get(t, 0.0) + rec["bytes"] * n / refs
        return {t: round(v, 6) for t, v in out.items()}

    def check_integrity(self) -> None:
        """Audit the refcount/ownership/byte invariants (raises
        ``ValueError`` — a violated shared ledger has no safe
        degradation): every resident entry has a positive refcount
        whose per-tenant shares sum to it exactly, no side-table row
        outlives its entry, and the byte accumulator equals the sum of
        resident entry sizes within the budget."""
        total = sum(int(rec["bytes"]) for rec in self._entries.values())
        if total != self.total_bytes:
            raise ValueError(
                f"shared tier byte accumulator {self.total_bytes} != "
                f"sum of resident entries {total}")
        if self.total_bytes > self.max_bytes:
            raise ValueError(
                f"shared tier holds {self.total_bytes} bytes over its "
                f"budget {self.max_bytes}")
        for name, table in (("refcount", self._refs),
                            ("ownership", self._owners)):
            if set(table) != set(self._entries):
                stray = set(table) ^ set(self._entries)
                raise ValueError(
                    f"shared tier {name} table out of sync with the "
                    f"resident entries (mismatched hashes: "
                    f"{sorted(stray)[:3]})")
        for h, refs in self._refs.items():
            if refs < 1:
                raise ValueError(
                    f"shared entry {h!r} has refcount {refs} < 1")
            shares = self._owners[h]
            if any(n < 1 for n in shares.values()):
                raise ValueError(
                    f"shared entry {h!r} has a non-positive ownership "
                    f"share: {shares}")
            if sum(shares.values()) != refs:
                raise ValueError(
                    f"shared entry {h!r} ownership shares {shares} do "
                    f"not sum to its refcount {refs}")

    def stats(self) -> Dict[str, int]:
        out = super().stats()
        out["dedupe_hits"] = int(self.dedupe_hits)
        return out


class DeviceMirror:
    """A dirty-tracked host→device buffer: the device copy of host state
    that changes RARELY relative to how often it is consumed.

    The serving engine ships a ``[max_batch, max_blocks_per_seq]`` block
    table and a handful of per-lane sampling arrays into every decode
    dispatch. Their contents change only when the SLOT COMPOSITION
    changes (admission, finish, preemption, block growth) — not on the
    steady-state tick — yet the pre-mirror engine rebuilt and re-uploaded
    them from scratch every ``step()``. A mirror caches the built device
    value and rebuilds only after :meth:`invalidate`:

        mirror.get(build_fn)   # cached device value, or build_fn() once
        mirror.invalidate()    # host state changed; next get() rebuilds

    Pure host-side bookkeeping (no jax calls of its own): ``build_fn``
    owns the upload, the mirror owns only the decision to skip it. The
    scheduler invalidates at its mutation points; forgetting one is a
    correctness bug (a stale table scatters K/V into freed blocks), so
    mutation sites funnel through the engine's ``_invalidate_*``
    helpers rather than touching mirrors directly.
    """

    __slots__ = ("_value",)

    def __init__(self):
        self._value = None

    @property
    def dirty(self) -> bool:
        return self._value is None

    def invalidate(self) -> None:
        self._value = None

    def get(self, build):
        if self._value is None:
            self._value = build()
        return self._value


def device_block_table(host_tables: np.ndarray, num_blocks: int) -> jax.Array:
    """Host tables use -1 for unallocated entries; the device convention
    is ``num_blocks`` (one past the pool) so scatters drop and gathers
    clip into already-masked positions."""
    t = np.asarray(host_tables, np.int32)
    return jnp.asarray(np.where(t >= 0, t, num_blocks), jnp.int32)


def _page_offsets(block_tables: jax.Array, positions: jax.Array,
                  valid: jax.Array, N: int, bs: int):
    """(page, off) scatter coordinates for per-token block writes;
    invalid positions route to the out-of-bounds page ``N`` so the
    caller's ``mode="drop"`` scatter discards them."""
    page = jnp.take_along_axis(block_tables, positions // bs, axis=1)
    page = jnp.where(valid, page, N)
    return page, positions % bs


def paged_write(pages: jax.Array, layer: int, block_tables: jax.Array,
                positions: jax.Array, values: jax.Array,
                valid: jax.Array) -> jax.Array:
    """Scatter per-token K or V into one layer's blocks.

    Args:
      pages: the full pool ``[L, N, bs, H, D]``.
      layer: static layer index.
      block_tables: ``[B, max_blocks_per_seq]`` int32 (device
        convention: out-of-bounds id for unallocated entries).
      positions: ``[B, S]`` absolute token positions within each
        sequence.
      values: ``[B, S, H, D]`` the tokens' K or V heads.
      valid: ``[B, S]`` bool; False routes the write out of bounds,
        where ``mode="drop"`` discards it (padding tokens, inactive
        decode slots, already-cached prefix positions).
    """
    N, bs = pages.shape[1], pages.shape[2]
    page, off = _page_offsets(block_tables, positions, valid, N, bs)
    return pages.at[layer, page, off].set(
        values.astype(pages.dtype), mode="drop")


def quantize_kv_rows(values: jax.Array, positions: jax.Array,
                     quantization: str, stream: int = 0):
    """Quantize ``[B, S, H, D]`` K/V rows to the storage dtype.

    Per (token, head) row: ``scale = max|row| / qmax`` (qmax = 127 for
    int8, the fp8 finite max for fp8), payload = the scaled row rounded
    into storage. int8 rounding is STOCHASTIC via
    :func:`apex_tpu.ops.multi_tensor.stochastic_round`, keyed by
    ``(stream, absolute position)`` (``positions``, ``[B, S]``) — a
    pure function of (value, stream, position), so re-prefilling the
    same token after preemption/restore reproduces the identical
    quantized bytes and the engine's resume-determinism contract
    survives quantization. ``stream`` decorrelates consumers sharing
    positions: :func:`write_kv` tags each (layer, K-vs-V) pair with
    its own stream, so a token's K and V rows — and its rows across
    layers — draw INDEPENDENT rounding noise (correlated noise would
    compound in one direction through the network instead of
    averaging out; determinism only needs the stream to be a static
    property of the call site, which (layer, k/v) is). fp8 rounds by
    the cast (round-to-nearest; its mantissa keeps relative error, so
    stochastic bits buy nothing).

    Returns ``(payload [B, S, H, D] storage-dtype, scales [B, S, H]
    fp32)``; an all-zero row stores payload 0 with scale 0 (dequant
    reproduces the zeros exactly).
    """
    from apex_tpu.ops.multi_tensor import stochastic_round

    dt = _quant_storage_dtype(quantization)
    qmax = _quant_value_max(quantization)
    v32 = values.astype(jnp.float32)
    amax = jnp.max(jnp.abs(v32), axis=-1)              # [B, S, H]
    scale = amax / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    x = v32 / safe[..., None]
    if quantization == "fp8":
        return x.astype(dt), scale
    B, S = positions.shape
    base = jax.random.fold_in(jax.random.PRNGKey(_KV_QUANT_SEED),
                              int(stream))
    keys = jax.vmap(lambda p: jax.random.fold_in(base, p))(
        positions.reshape(-1))
    q = jax.vmap(lambda row, key: stochastic_round(row, dt, key))(
        x.reshape((B * S,) + x.shape[2:]), keys)
    return q.reshape(x.shape), scale


def write_kv(cache: KVCache, layer: int, block_tables: jax.Array,
             positions: jax.Array, k_values: jax.Array,
             v_values: jax.Array, valid: jax.Array) -> KVCache:
    """Scatter one layer's K AND V rows into the pool, quantizing on
    the way in when the pool stores quantized blocks (payload + scales
    land through the same ``(page, off)`` coordinates, so a block's
    scales always travel with its bytes). The full-precision path is
    exactly two :func:`paged_write` calls — bit-identical to the
    pre-quantization write."""
    mode = cache.quantization
    if mode is None:
        return cache._replace(
            k=paged_write(cache.k, layer, block_tables, positions,
                          k_values, valid),
            v=paged_write(cache.v, layer, block_tables, positions,
                          v_values, valid))
    N, bs = cache.k.shape[1], cache.k.shape[2]
    page, off = _page_offsets(block_tables, positions, valid, N, bs)
    # distinct rounding streams per (layer, K-vs-V): same positions,
    # independent noise (see quantize_kv_rows)
    qk, sk = quantize_kv_rows(k_values, positions, mode,
                              stream=2 * layer)
    qv, sv = quantize_kv_rows(v_values, positions, mode,
                              stream=2 * layer + 1)
    return KVCache(
        k=cache.k.at[layer, page, off].set(qk, mode="drop"),
        v=cache.v.at[layer, page, off].set(qv, mode="drop"),
        k_scale=cache.k_scale.at[layer, page, off].set(sk, mode="drop"),
        v_scale=cache.v_scale.at[layer, page, off].set(sv, mode="drop"))


def gather_kv(pages: jax.Array, layer: int,
              block_tables: jax.Array) -> jax.Array:
    """Read every sequence's cached tokens back out of one layer's pool:
    ``[B, max_blocks_per_seq * bs, H, D]`` in position order. Entries
    past a sequence's length hold stale pool contents and MUST be
    masked by the consumer (the decode attention masks on length)."""
    N = pages.shape[1]
    tbl = jnp.minimum(block_tables, N - 1)  # clip OOB ids into the pool
    out = pages[layer][tbl]                 # [B, M, bs, H, D]
    B, M, bs, H, D = out.shape
    return out.reshape(B, M * bs, H, D)


def copy_block(cache: KVCache, src, dst) -> KVCache:
    """Duplicate one block's contents across every layer (``new[dst] =
    old[src]``) — the device half of copy-on-write: when a sequence
    would append into a block shared with another sequence, the
    scheduler allocates a private block, copies the shared contents
    here, and rewrites its table entry. ``src``/``dst`` may be traced
    int32 scalars so a single jitted program serves every copy."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = KVCache(
        k=cache.k.at[:, dst].set(cache.k[:, src]),
        v=cache.v.at[:, dst].set(cache.v[:, src]),
    )
    if cache.k_scale is not None:
        # quantized pools: the copy must carry the source block's
        # scales, or the CoW'd block would dequantize the right bytes
        # with the wrong (stale/zero) scales — silently wrong K/V
        out = out._replace(
            k_scale=cache.k_scale.at[:, dst].set(cache.k_scale[:, src]),
            v_scale=cache.v_scale.at[:, dst].set(cache.v_scale[:, src]))
    return out


def gather_blocks(cache: KVCache, perm: jax.Array) -> KVCache:
    """Apply a block permutation to the pool (``new[i] = old[perm[i]]``)
    — the device half of :func:`defragment`. Scale pools (quantized
    storage) permute with their payload."""
    out = KVCache(k=cache.k[:, perm], v=cache.v[:, perm])
    if cache.k_scale is not None:
        out = out._replace(k_scale=cache.k_scale[:, perm],
                           v_scale=cache.v_scale[:, perm])
    return out


def defragment(cache: KVCache, allocator: BlockAllocator,
               host_tables: np.ndarray):
    """Compact live blocks to the low pool indices.

    Long-running continuous batching interleaves allocations from many
    sequences, so frees leave the pool checkerboarded; compaction
    restores a contiguous free region (and, on hardware with block-
    granular paging tricks, locality). Returns ``(new_cache,
    new_host_tables)`` and rewrites the allocator's free list,
    refcounts, and prefix index in the compacted id space. Refcount-0
    cached blocks are dropped (they appear in no table, so compaction
    cannot preserve them) — an acceptable trade for a maintenance op.
    The device shuffle is one gather over the pool — call it rarely,
    from a maintenance point, never inside the per-step loop.
    """
    tables = np.array(host_tables, np.int32, copy=True)
    live = np.unique(tables[tables >= 0])
    live_set = {int(x) for x in live}
    missing = [b for b in allocator._ref if b not in live_set]
    if missing:
        raise ValueError(
            f"defragment: blocks {sorted(missing)} hold references but "
            "appear in no table — allocator and tables are inconsistent")
    mapping = {int(old): new for new, old in enumerate(live)}
    perm = np.arange(cache.num_blocks, dtype=np.int32)
    perm[: len(live)] = live
    # the remaining slots get the displaced (dead) blocks, keeping perm
    # a true permutation so no block id aliases another
    dead = np.setdiff1d(np.arange(cache.num_blocks, dtype=np.int32), live,
                        assume_unique=False)
    perm[len(live):] = dead
    for idx, old in np.ndenumerate(tables):
        if old >= 0:
            tables[idx] = mapping[int(old)]
    # rebuild allocator state in the compacted id space: cached blocks
    # are evicted, live blocks keep their refcounts and index entries
    for b in allocator._evictable:       # dropped, charged as evictions
        owner = allocator._cached_owner.pop(b, None)
        if owner is not None:
            allocator._evicted_by_tenant[owner] = \
                allocator._evicted_by_tenant.get(owner, 0) + 1
    allocator.num_evictions += len(allocator._evictable)
    allocator._evictable.clear()
    allocator._ref = {mapping[b]: c for b, c in allocator._ref.items()}
    allocator._tenant_refs = {mapping[b]: refs for b, refs in
                              allocator._tenant_refs.items()}
    allocator._hash_to_block = {
        h: mapping[b] for h, b in allocator._hash_to_block.items()
        if b in mapping}
    allocator._block_to_hash = {
        b: h for h, b in allocator._hash_to_block.items()}
    allocator._cached_owner = {
        mapping[b]: t for b, t in allocator._cached_owner.items()
        if b in mapping}
    allocator._free = list(range(cache.num_blocks - 1, len(live) - 1, -1))
    return gather_blocks(cache, jnp.asarray(perm)), tables
