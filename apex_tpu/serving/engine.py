"""Continuous-batching inference engine: chunked prefill + multi-step
fused decode over the paged KV-cache, with a fixed-shape scheduler,
prefix caching, and optimistic admission backed by preemption.

The Orca/vLLM serving loop (PAPERS.md) restated for XLA, where a shape
change means a recompile and a recompile means a multi-second stall
mid-traffic. The engine therefore holds a **fixed-program contract**:

- ``prefill``: one request at a time at the fixed shape
  ``[1, prefill_chunk]``, iterated over the prompt — each chunk's K/V
  are scattered into the sequence's cache blocks, then the chunk's
  queries attend against EVERYTHING cached so far (matched prefix
  blocks, earlier chunks, the chunk itself) through the block table
  (Sarathi-style chunked prefill: a long prompt no longer head-of-line
  blocks the decode slots, and prompts up to ``max_seq_len`` are
  admissible regardless of the chunk size). The FIRST generated token
  is sampled from the last real position's logits of the final chunk.
- ``decode``: ALL slots at once, ``decode_steps`` (K) iterations fused
  into ONE dispatch via ``jax.lax.scan`` — each inner step writes the
  previous token's K/V through the block table, attends, samples one
  token per lane (per-lane PRNG keys, see below), advances per-lane
  context lengths on-device, and feeds the token back as the next
  query. A per-lane active mask freezes lanes that hit EOS or their
  ``max_new_tokens`` budget mid-scan: frozen lanes stop writing
  (``write_start`` pushes their scatter out of the valid range) and
  emit a ``-1`` sentinel. The program returns ``[max_batch, K]`` tokens
  (``-1`` sentinels past each lane's emitted prefix), and the host
  fetch is DEFERRED: the next tick's admission and prefill work is
  dispatched before the host blocks on the in-flight decode, so
  scheduler overhead overlaps device compute. ``K == 1`` runs the same
  single-token computation and scheduling cadence as the pre-multistep
  engine (greedy outputs are unchanged; sampled draws come from the
  rekeyed per-request scheme below, which intentionally replaced the
  old step-counter keys at every K). Non-decoding lanes (empty, or
  still prefilling) ride along masked (their table rows point out of
  bounds, so their writes drop and their outputs are ignored).
- ``cow copy`` (rare): one block duplicated when a sequence would
  append into a block it shares with another sequence — compiled
  lazily, only if copy-on-write ever triggers.

**Speculative decoding** (``spec_tokens > 0``, docs/serving.md) swaps
the decode program — same slot in the contract, still exactly one
compilation — for draft-and-verify: a host-side drafter
(:mod:`~apex_tpu.serving.drafter`, prompt-lookup by default) proposes
up to ``spec_tokens`` continuation tokens per lane each decode phase,
and ONE ``[max_batch, spec_tokens + 1]`` target forward scores every
candidate position through the multi-query paged-prefill path, accepts
a per-lane prefix on-device (the Leviathan et al. rejection rule,
:func:`~apex_tpu.serving.sampling.spec_verify_tokens`), and emits
``1..spec_tokens + 1`` tokens per dispatch under the same ``-1``
sentinel/stop-mask conventions — the deferred-drain contract below is
untouched, the host just advances each lane by its own emitted count.
Blocks are reserved for the worst case (every proposal written) and
the drain returns what rejection stranded
(:meth:`~apex_tpu.serving.kv_cache.BlockAllocator.trim_to`). Greedy
output is bit-identical to non-speculative greedy; a crashing drafter
is quarantined and the engine degrades to non-speculative decoding.

Everything that varies between steps — which slots are live, block
tables, chunk offsets, context lengths, sampling knobs — varies as
*array values*, so XLA compiles one program per shape for the lifetime
of the engine (``stats()["prefill_compilations"] == 1`` and likewise
for decode; the acceptance tests pin this). The block table and the
per-lane sampling/EOS/key arrays are **dirty-tracked device-resident
mirrors** (:class:`~apex_tpu.serving.kv_cache.DeviceMirror`):
re-uploaded when the slot composition or a table row changes, reused
untouched on the steady-state tick.

Sampling determinism is **schedule-invariant**: every request owns a
PRNG key (the engine seed folded with the request's arrival index),
and its ``j``-th generated token is drawn with
``fold_in(request_key, j)`` — on-device, the scan folds the running
per-lane generated-count into the lane's key each iteration. Outputs
are therefore bit-for-bit identical for any ``decode_steps``, any lane
placement, and any preemption/resume schedule (tested).

Scheduling (host-side, between jitted dispatches), per ``step()``:

1. **Admission** fills free decode slots from the FIFO waiting queue
   on *current* need, not worst case: the prompt's uncached tail blocks
   plus one must fit in the pool (free + evictable). With prefix
   caching enabled, the longest block-aligned cached prefix is matched
   by content hash and shared (refcounted) instead of recomputed.
2. **One prefill chunk** runs for the oldest admitted request still
   mid-prompt — at most one chunk per step ahead of the decode
   dispatch, so decode slots keep streaming tokens while a long prompt
   loads (stall-free batching).
3. **Drain** the PREVIOUS tick's decode dispatch (the deferred sync):
   fetch its ``[B, K]`` tokens + counts, append K/V bookkeeping,
   register newly-full blocks, finish/evict satisfied requests, then
   top up admissions into any lanes that just freed.
4. **Decode** dispatches the next fused K-step scan for every started
   slot. When a K-step block reservation fails, the YOUNGEST slot is
   preempted: its references are released and the request re-queued at
   the front carrying its already-generated tokens — on re-admission
   it re-prefills ``prompt + generated[:-1]`` (cheap under prefix
   caching: its own blocks are usually still cached) and continues, so
   emitted tokens are never resampled and per-request output is
   deterministic. Preemption granularity is K tokens: a preempted lane
   loses at most the current dispatch's unconsumed reservation, never
   an emitted token.

Finished requests *release references* instead of freeing: with prefix
caching on, their full blocks stay indexed and evictable (LRU) until
the pool actually needs the space.

**Robustness** (docs/robustness.md): every jitted dispatch runs under a
fault-gated, bounded-backoff retry (``max_dispatch_retries``); a
request whose dispatch keeps failing is *quarantined* — failed with
terminal status instead of killing the engine. Requests carry optional
wall-clock deadlines (``Request.deadline_s``) and expire gracefully
with status ``"timeout"`` and the tokens they emitted.
``snapshot()``/``restore()`` round-trip the complete host-side picture
through JSON: a restored engine re-prefills its live requests (cheap
under prefix caching) and — because sampling is schedule-invariant —
continues bit-identically to the uninterrupted run. ``run()`` raises a
diagnostic :class:`EngineStalledError` instead of spinning if a full
step ever makes no progress while work remains.

**Overload protection** (docs/robustness.md): faults are one failure
mode; too much *legitimate* traffic is the other. The waiting queue is
bounded (``max_waiting``; ``add_request`` raises
:class:`QueueFullError`, ``try_add`` returns ``False`` — explicit
backpressure instead of unbounded memory growth), requests carry an
integer ``priority`` class (0 = most urgent; admission and preemption
order by ``(priority, age)``, and uniform-priority traffic schedules
bit-identically to the pre-priority FIFO), an **admit-time feasibility
gate** sheds requests whose deadline cannot cover even a
contention-free service estimate (status ``"rejected"``, fed by cheap
EWMAs of observed per-dispatch wall time) before they burn pool blocks
they would time out of, and a **degradation ladder** steps the engine
down deterministically under sustained pressure (free-block /
queue-depth watermarks with hysteresis): suspend speculative decoding,
flush the prefix cache aggressively, pause admission of the lowest
priority class — and back up when pressure clears, every transition
counted in ``stats()`` and serialized through snapshot/restore.

**Multi-tenant isolation** (docs/robustness.md): overload protection
treats traffic as one cooperating client; real traffic is mutually
untrusting tenants. Every request carries a ``tenant`` id: admission
WITHIN a priority class is weighted deficit-round-robin across tenants
(strict priority between classes is kept — the documented contract),
per-tenant quotas (:class:`TenantQuota`: waiting entries, fractional
resident-block charge, token rate) shed over-quota submissions with
terminal status ``"throttled"`` before they burn pool blocks, and the
allocator attributes every block reference — shared prefix blocks
fractionally by refcount — so flushes and evictions charge the tenant
that parked them. Two client-lifecycle primitives ride the tenant
ledger: :meth:`InferenceEngine.abort` (cancellation with full
resource reclamation, status ``"cancelled"``) and
:meth:`InferenceEngine.pop_stream_events` (streaming ``(uid, token,
is_last)`` delivery; a disconnect callback maps onto ``abort``).
Tenancy is pure scheduling: sampling stays arrival-keyed, so outputs
are invariant to tenant assignment, and uniform-tenant traffic is
bit-identical to the pre-tenancy engine.

**Observability** (docs/observability.md): pass an
:class:`~apex_tpu.observability.Observability` via ``obs=`` and the
engine narrates itself — per-request span timelines (Perfetto
exportable), a flight-recorder ring of tick/ladder/quarantine/retry
events whose tail rides :class:`EngineStalledError` and the crash-dump
file, and latency histograms (TTFT, inter-token, dispatch service,
queue wait) with Prometheus exposition, merged by ``stats(deep=True)``.
The contract is ZERO perturbation: observers consume events through the
engine's injectable ``_clock`` and never feed a decision, so outputs
with observability attached are bit-identical to without (tested across
greedy/sampled x speculative/not x preemption x snapshot/restore).
Observer state is excluded from the snapshot fingerprint; recorder and
trace tails ride ``snapshot()`` only as an audit section ``restore()``
never reloads.

**Memory tiers** (docs/serving.md): KV memory bounds concurrent
users, so the cache is tiered. ``kv_quantization`` stores int8/fp8
block payloads with per-row scales (quantize inside the jitted write,
dequantize inside the attention read; position-keyed stochastic
rounding keeps every determinism contract, and a quantized block
charges the tenant ledger its reduced byte footprint).
``spill_max_bytes`` adds a bounded host-RAM spill tier: LRU-evicted
and ladder-flushed prefix blocks copy to a host store keyed by their
chain hash and re-admit by device upload instead of recompute —
token-identical, audit-only in snapshots. The read chain itself can
run as one fused Pallas kernel (``APEX_PAGED_ATTENTION_PALLAS=1``,
read side only, fp path bit-identical to the XLA chain).

**Mesh sharding** (docs/serving.md): ``mesh_shape`` promotes the
engine from single-device to mesh-native over a logical
``("batch", "model")`` GSPMD mesh (:mod:`apex_tpu.serving.mesh`) —
the KV pools (payloads AND quantized scales) and the model's
qkv/proj/mlp weights shard their head axis over ``model`` via
:class:`~jax.sharding.NamedSharding` annotations, and the same three
jitted programs compile once under the mesh with the collectives
jit-inserted (``audit_collectives`` pins the program-shape contract:
zero collectives at a 1-sized model axis, all-reduce traffic once
heads split). Everything host-side — admission, DRR, quotas, the
ladder, drafters, snapshot/spill/integrity — is mesh-agnostic (block
ids and chain hashes are layout-independent), so prefix caching, the
spill tier, and fleet migration work unchanged at any shape. Mesh
``(1, 1)``, the default, is certified bit-identical to the pre-mesh
engine; ``mesh_shape`` is part of the restore-fingerprint identity
set.
"""

from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.utils.faults import (
    TRANSIENT_ERRORS,
    DispatchFailedError,
    SimulatedCrash,
    guarded_call,
    perturb_json,
    perturb_payload,
    perturb_tokens,
)
from apex_tpu.utils.integrity import (
    IntegrityError,
    payload_checksum,
    seal_record,
    verify_payload,
    verify_record,
)

from apex_tpu.serving.kv_cache import (
    DEFAULT_TENANT,
    KV_QUANT_MODES,
    BlockAllocator,
    CacheOutOfBlocks,
    DeviceMirror,
    HostSpillStore,
    KVCache,
    blocks_needed,
    copy_block,
    device_block_table,
    hash_block_tokens,
    kv_block_bytes,
    seq_block_hashes,
)
from apex_tpu.models.gpt import (
    WEIGHT_QUANT_MODES,
    gpt_param_bytes,
    quantize_gpt_model,
)
from apex_tpu.serving import mesh as mesh_lib
from apex_tpu.serving.drafter import NgramDrafter
from apex_tpu.serving.sampling import (
    SamplingParams,
    sample_tokens,
    sample_tokens_per_lane,
    spec_verify_tokens,
)

# new-observation weight of the per-dispatch wall-time EWMAs feeding
# the admit-time feasibility gate
_EWMA_ALPHA = 0.25
# degradation-ladder rungs (cumulative): 1 = speculation suspended,
# 2 = + prefix cache flushed every tick, 3 = + lowest-class admission
# paused
_LADDER_TOP = 3
# while the dynamic speculation cap (spec_adapt) sits at 0, every Nth
# decode phase runs a 1-token probe so a recovered drafter can earn
# its cap back (a capped-out engine otherwise never observes
# acceptance again and stays degraded forever)
_SPEC_PROBE_EVERY = 16
# the FaultPlan sites where "corrupt" specs perturb a serialized host
# artifact (docs/robustness.md, "Data integrity"): the spill tier's
# write/read paths, the periodic checkpoint, and migration records on
# the way out / in. Corruption-only — see the construction check.
_INTEGRITY_SITES = ("spill_put", "spill_get", "checkpoint",
                    "export", "import")


@dataclasses.dataclass(frozen=True)
class TenantQuota:
    """Per-tenant resource bounds (``EngineConfig.tenant_quotas``), all
    optional — ``None`` leaves that axis unbounded. Enforcement points
    (docs/robustness.md, isolation):

    - ``max_waiting``: entries the tenant may hold in the waiting queue
      at once; the door sheds past it with terminal status
      ``"throttled"`` (:class:`TenantThrottledError`; ``try_add``
      returns False).
    - ``max_resident_blocks``: the tenant's fractional resident-block
      charge ceiling (:meth:`~apex_tpu.serving.kv_cache.BlockAllocator.
      tenant_charge` — shared prefix blocks charge fractionally by
      refcount). A request whose worst-case private footprint exceeds
      it is shed ``"throttled"`` at the door (it could never run);
      admission skips an over-charge tenant's queue (other tenants
      flow past); decode-time growth past the cap preempts the
      tenant's OWN lowest-class/youngest other lane, never a
      different tenant's.
    - ``tokens_per_s``: token-rate budget, enforced at the door
      against an exponentially-decayed per-tenant rate estimator
      (``tenant_rate_tau_s``); over-rate submissions shed
      ``"throttled"`` before touching the queue or the pool.
    """

    max_waiting: Optional[int] = None
    max_resident_blocks: Optional[int] = None
    tokens_per_s: Optional[float] = None

    def validate(self, tenant: str) -> None:
        if self.max_waiting is not None and self.max_waiting < 1:
            raise ValueError(
                f"tenant {tenant!r}: max_waiting must be >= 1 (or None), "
                f"got {self.max_waiting}")
        if (self.max_resident_blocks is not None
                and self.max_resident_blocks < 1):
            raise ValueError(
                f"tenant {tenant!r}: max_resident_blocks must be >= 1 "
                f"(or None), got {self.max_resident_blocks}")
        if self.tokens_per_s is not None and self.tokens_per_s <= 0:
            raise ValueError(
                f"tenant {tenant!r}: tokens_per_s must be > 0 (or "
                f"None), got {self.tokens_per_s}")


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request. ``prompt`` is a token-id sequence;
    generation runs until EOS (if ``eos_token_id`` is set) or
    ``max_new_tokens``, whichever comes first — or until the request
    leaves the engine early: past its ``deadline_s`` TTL (status
    ``"timeout"``) or quarantined after repeated dispatch failures
    (status ``"failed"``). Early exits are graceful: tokens already
    emitted are returned."""

    uid: str
    prompt: Sequence[int]
    max_new_tokens: int = 16
    sampling: SamplingParams = SamplingParams()
    eos_token_id: Optional[int] = None
    # Wall-clock TTL in seconds from add_request, measured against the
    # engine's clock (injectable for tests). None = no deadline.
    deadline_s: Optional[float] = None
    # Priority class, 0 = most urgent. Admission considers classes in
    # ascending value (FIFO within a class) and preemption/quarantine
    # yield the lowest class first (then youngest). A pure SCHEDULING
    # knob: sampling is arrival-keyed, so per-request outputs are
    # identical under any priority assignment (tested), and
    # uniform-priority traffic is bit-identical to the pre-priority
    # FIFO scheduler.
    priority: int = 0
    # The submitting tenant: admission WITHIN a priority class is
    # weighted deficit-round-robin across tenants (strict priority
    # between classes is unchanged), and per-tenant quotas
    # (EngineConfig.tenant_quotas) are enforced against this id. A
    # pure SCHEDULING/ADMISSION label like priority: sampling is
    # arrival-keyed, so per-request outputs are identical under any
    # tenant assignment (tested), and uniform-tenant traffic is
    # bit-identical to the pre-tenancy engine.
    tenant: str = DEFAULT_TENANT
    # Terminal lifecycle status — "finished" | "timeout" | "failed" |
    # "rejected" | "throttled" | "cancelled" — written by the engine
    # via object.__setattr__ when the request leaves it (the one
    # engine-owned field of the frozen request); None while
    # waiting/active. Excluded from equality/hash.
    status: Optional[str] = dataclasses.field(default=None, compare=False)


@dataclasses.dataclass(frozen=True)
class RequestResult:
    """One entry of ``run(return_status=True)``: the generated tokens
    plus the request's terminal status (the result contract in
    docs/serving.md). ``tokens`` may be shorter than ``max_new_tokens``
    for ``"timeout"``/``"failed"``/``"rejected"``/``"throttled"``/
    ``"cancelled"`` exits — everything emitted before the cut is
    preserved."""

    tokens: List[int]
    status: str


class QueueFullError(RuntimeError):
    """``add_request`` refused: the waiting queue already holds
    ``EngineConfig.max_waiting`` entries. The explicit backpressure
    signal — callers shed, retry later, or route to another replica
    instead of growing an unbounded queue that will only manufacture
    timeouts. ``try_add`` is the non-raising variant."""


class TenantThrottledError(RuntimeError):
    """``add_request`` refused by the submitting TENANT's quota
    (:class:`TenantQuota`): its waiting-entry cap, its resident-block
    ceiling (a request that could never fit it), or its token-rate
    budget. Unlike the engine-wide :class:`QueueFullError` door shed,
    a throttled request DOES get a terminal verdict — status
    ``"throttled"``, zero tokens, drained by ``run()`` — because the
    shed is the tenant's own doing, not global load, and the tenant's
    ledger must show it. ``try_add`` returns False for this too."""


class EngineStalledError(RuntimeError):
    """``has_work`` is true but a full ``step()`` made no progress —
    no admission, prefill chunk, decode dispatch, drain, expiry,
    preemption, or quarantine. The scheduler would spin forever;
    ``engine_stats`` carries ``stats()`` at the stall for diagnosis;
    ``recorder_tail`` the flight recorder's last events when an
    :class:`~apex_tpu.observability.Observability` was attached (None
    otherwise) — the stall ships its own post-mortem."""

    def __init__(self, message: str, stats: Dict[str, object],
                 recorder_tail=None):
        super().__init__(f"{message} (stats: {stats})")
        self.engine_stats = stats
        self.recorder_tail = recorder_tail


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 8            # decode slots
    block_size: int = 16
    num_blocks: int = 256         # pool size (per layer)
    max_prefill_len: int = 64     # default prefill chunk (see below)
    max_seq_len: int = 256        # prompt + generation cap per sequence
    # THE prefill shape: prompts are prefilled in [1, prefill_chunk]
    # pieces, so prompts up to max_seq_len are admissible regardless of
    # the chunk. None inherits max_prefill_len (the pre-chunking shape,
    # keeping existing configs' compiled footprint identical).
    prefill_chunk: Optional[int] = None
    # Multi-step fused decode: each decode dispatch runs this many
    # scanned iterations on-device, amortizing one scheduler tick (host
    # table/array work + dispatch + fetch) over K generated tokens.
    # Outputs are bit-identical for any K (per-request, per-token PRNG
    # keys); K trades per-token latency (tokens surface K at a time)
    # for throughput, and makes K tokens the preemption granularity.
    # 1 keeps the pre-multistep single-token cadence (sampled draws
    # use the rekeyed per-request scheme at every K, including 1).
    decode_steps: int = 1
    # Share identical block-aligned prompt prefixes through the
    # allocator's content-hash index; finished requests' blocks stay
    # cached (LRU-evictable) instead of freed. Off by default: caching
    # retains pool blocks after a request finishes, which changes
    # utilization accounting workloads may assert on.
    enable_prefix_caching: bool = False
    kv_dtype: Optional[object] = None   # None = follow the amp policy
    # Quantized block storage (docs/serving.md memory tiers): "int8"
    # (symmetric int8, stochastic-rounded) or "fp8" (float8_e4m3,
    # where the backend has it) K/V payloads with per-row fp32 scales
    # carried block-wise; dequantization happens inside the attention
    # read. None (default) keeps full-precision storage — bit-identical
    # to the pre-quantization engine. Quantized outputs are tolerance-
    # certified against the fp path, not bit-equal to it; the
    # quantized path is itself fully deterministic (position-keyed
    # rounding), so preemption/resume/snapshot bit-identity holds
    # WITHIN a storage mode. A quantized block charges the tenant
    # ledger its reduced byte footprint (the allocator's block_weight).
    kv_quantization: Optional[str] = None
    # Quantized WEIGHT storage (docs/serving.md memory tiers): "int8"
    # or "fp8" re-expresses the GPT qkv/proj/mlp kernels as int8/fp8
    # with per-output-channel fp32 scales at engine construction
    # (models/gpt.quantize_gpt_model — deterministic round-to-nearest,
    # weights are static) and routes those matmuls through the
    # dequant-GEMM read path (apex_tpu.ops.dequant_gemm; the fused
    # Pallas kernel opts in via APEX_DEQUANT_GEMM_PALLAS, single-
    # device meshes only). Quantized logits are tolerance-certified
    # against the fp path, greedy decode token-identical at the
    # certified tolerance; within a mode the engine stays fully
    # deterministic. IDENTITY, not operational: like kv_quantization,
    # the mode joins the restore fingerprint and the process-replica
    # params-checksum handshake — snapshots restore across EQUAL
    # storage modes only, and a replica booted with a mismatched mode
    # is refused. Composes with kv_quantization (weights) x (KV pool)
    # and with the model-axis mesh (scale leaves shard with their
    # kernels — gpt_param_pspec).
    weight_quantization: Optional[str] = None
    # Host-RAM spill tier for the prefix cache (docs/serving.md):
    # LRU-evicted and ladder-flushed prefix blocks are copied to a
    # bounded host store (this many payload bytes) keyed by their
    # chain hash, and a later prefix match re-admits them by device
    # upload instead of recompute. Requires enable_prefix_caching
    # (the tier is keyed by the prefix index's hashes). None = off.
    # Operational, not identity: spill state is audit-only in
    # snapshots and the knob stays out of the restore fingerprint —
    # a re-admitted block is certified token-identical to recompute.
    spill_max_bytes: Optional[int] = None
    # -- pod-scale serving (docs/serving.md, "Mesh sharding") ----------
    # The logical ("batch", "model") GSPMD device mesh the engine's
    # programs compile under (apex_tpu.serving.mesh): the KV pools and
    # the model's qkv/proj/mlp weights shard their HEAD axis over
    # "model" via NamedSharding annotations and jax.jit inserts the
    # collectives — the host-side machinery (admission, DRR, quotas,
    # ladder, drafters, snapshot/spill/integrity) is mesh-agnostic.
    # (1, 1) — the default — is certified bit-identical to the
    # pre-mesh engine (outputs, statuses, full stats()), and the
    # model-axis size must divide the model's num_heads (checked at
    # engine construction, where the model is known). IDENTITY, not
    # operational: mesh_shape stays in the restore fingerprint —
    # sharded snapshots restore across EQUAL meshes only (the records
    # themselves are host-side and layout-free).
    mesh_shape: Tuple[int, int] = (1, 1)
    # Donate the cache pool to the jitted steps so XLA updates it in
    # place instead of materializing a second pool + copy per step
    # (double peak HBM and a full-pool write otherwise). Default off:
    # the CPU backend ignores donation with a warning, and the donated
    # engine has not yet been run on the chip.
    donate_cache: bool = False
    # Robustness knobs (docs/robustness.md): a failed prefill/decode
    # dispatch is retried up to max_dispatch_retries times with
    # exponential backoff (retry_backoff_s * 2**attempt seconds between
    # attempts; 0 = immediate, the test default) before the offending
    # request is quarantined with terminal status "failed".
    max_dispatch_retries: int = 2
    retry_backoff_s: float = 0.0
    # Speculative decoding (docs/serving.md): > 0 swaps the K-step
    # decode scan for draft-and-verify — a host-side drafter proposes
    # up to spec_tokens continuation tokens per lane, and ONE target
    # forward over [max_batch, spec_tokens + 1] scores every candidate
    # position, accepts a prefix on-device (rejection rule in
    # sampling.spec_verify_tokens), and emits 1..spec_tokens + 1 tokens
    # per dispatch. Greedy output is bit-identical to non-speculative
    # greedy; sampled output is exactly distribution-preserving (its
    # realized draws depend on span boundaries — docs/serving.md).
    # decode_steps is ignored while speculation is on: the verify
    # forward IS the dispatch, there is no scan to fuse.
    spec_tokens: int = 0
    # -- overload protection (docs/robustness.md) ----------------------
    # Bound on the waiting queue: add_request past it raises
    # QueueFullError (try_add returns False) — explicit backpressure
    # instead of unbounded memory growth. None = unbounded (the
    # pre-overload behavior). Preemption/recovery requeues of already-
    # resident requests bypass the bound (at most max_batch extra).
    max_waiting: Optional[int] = None
    # Degradation-ladder watermarks: pressure is queue depth >=
    # queue_high_watermark OR allocatable fraction ((num_free +
    # num_cached) / num_blocks — evictable counts as headroom, or a
    # warm prefix cache would read as overload and sawtooth the
    # ladder) <= free_block_low_watermark. After degrade_patience
    # CONSECUTIVE
    # pressure ticks the engine steps one rung down; after the same
    # number of consecutive clear ticks, one rung up (the hysteresis).
    # Rungs, cumulative: 1 = suspend speculative decoding, 2 = flush
    # the prefix cache every tick, 3 = pause admission of priority
    # classes >= degrade_admit_priority (unless the engine is otherwise
    # idle — an idle engine serves whatever it has). Both watermarks
    # None = ladder off (default).
    queue_high_watermark: Optional[int] = None
    free_block_low_watermark: Optional[float] = None
    degrade_patience: int = 2
    degrade_admit_priority: int = 1
    # -- multi-tenant isolation (docs/robustness.md) -------------------
    # DRR weight per tenant id (>= 1; unlisted tenants weigh 1): each
    # visit of the admission walk credits a tenant weight * drr_quantum
    # deficit "tokens" (a request costs its committed budget,
    # len(prompt) + max_new_tokens, charged ONCE — preemption requeues
    # and restores re-admit free), so a weight-3 tenant admits ~3x the
    # token volume of a weight-1 tenant under contention. None = every
    # tenant weighs 1. Pure scheduling: sampling is arrival-keyed, so
    # outputs are invariant to weights, and single-tenant traffic is
    # bit-identical to the pre-tenancy engine at ANY weight.
    tenant_weights: Optional[Mapping[str, int]] = None
    # Per-tenant resource bounds (TenantQuota); unlisted tenants are
    # unbounded. None = no quotas (the pre-tenancy behavior).
    tenant_quotas: Optional[Mapping[str, "TenantQuota"]] = None
    # The DRR credit per walk visit, in committed-budget tokens.
    # Smaller = finer-grained interleaving across tenants; larger =
    # longer per-tenant admission bursts. Irrelevant with one tenant.
    drr_quantum: int = 64
    # Time constant (seconds) of the per-tenant token-rate estimator
    # feeding TenantQuota.tokens_per_s: the observed rate decays as
    # exp(-dt / tau), and each delivered token adds 1/tau — a larger
    # tau forgives longer bursts around the same average rate.
    tenant_rate_tau_s: float = 1.0
    # -- dynamic speculation (docs/serving.md) -------------------------
    # Adapt the per-plan draft cap to the observed acceptance rate: an
    # EWMA of per-dispatch acceptance shrinks the cap by one (toward 0
    # = speculation off, riding the ladder's rung-1 empty-plan
    # machinery) whenever it sits below spec_accept_low, and restores
    # it by one (toward spec_tokens) above spec_accept_high — the
    # [low, high] dead band is the hysteresis. While the cap is 0, a
    # 1-token probe runs every 16th decode phase so recovery is
    # possible. Requires spec_tokens > 0. When acceptance stays at or
    # above spec_accept_high, the cap never moves and the engine is
    # bit-identical to static speculation (tested).
    spec_adapt: bool = False
    spec_accept_low: float = 0.5
    spec_accept_high: float = 0.8
    # -- fleet serving (docs/fleet.md) ---------------------------------
    # Periodic lightweight checkpointing: every N scheduler ticks the
    # engine refreshes ``last_checkpoint`` with :meth:`checkpoint` — a
    # snapshot-format host picture taken WITHOUT draining the in-flight
    # decode dispatch (no host sync, unlike snapshot()), so a fleet
    # router holds a bounded-staleness failover picture at near-zero
    # steady-state cost. Tokens emitted after the checkpoint are
    # re-derived bit-identically on restore (resume determinism: the
    # records carry prompt + generated-so-far + the arrival PRNG
    # identity). None = off (the default; snapshot() is unchanged).
    # Operational, not identity: excluded from the restore fingerprint
    # like the retry/overload knobs.
    snapshot_interval_ticks: Optional[int] = None
    # -- data integrity (docs/robustness.md, "Data integrity") ---------
    # Verify the SHA-256 content checksums every serialized host
    # artifact carries — spilled KV blocks at re-admission, migration
    # records at import, snapshots/checkpoints at restore, transported
    # KV payloads at spill-tier seeding — at the point of consumption.
    # A mismatch routes through the artifact's existing degradation
    # path (a corrupt spill entry is a miss served by recompute, a
    # corrupt migration import is refused with IntegrityError, a
    # corrupt snapshot refuses to restore); checksum-less LEGACY
    # artifacts always load (detection covers sealed artifacts only).
    # On clean artifacts verification changes nothing — outputs and
    # schedule counters are bit-identical with it on or off (tested) —
    # and False skips both the checksumming and the checks, the
    # byte-identical pre-integrity path. Operational, not identity:
    # excluded from the restore fingerprint.
    verify_artifacts: bool = True
    # Budgeted background scrubbing: every N scheduler ticks the engine
    # re-verifies scrub_spill_blocks spill-tier entries against their
    # put-time checksums (round-robin, corrupt entries discarded and
    # counted) and runs one full allocator/ledger check_integrity
    # audit — rot is found while recompute is still cheap, and a
    # silently-corrupted ledger fails loudly instead of mis-charging
    # forever. None = off (the default). Scrub state is operational:
    # counters ride stats(), the spill cursor rides the audit-only
    # spill snapshot section, and both knobs stay out of the restore
    # fingerprint.
    scrub_interval_ticks: Optional[int] = None
    scrub_spill_blocks: int = 4
    seed: int = 0

    def __post_init__(self):
        # construction-time validation: a bad geometry knob used to
        # surface as a shape error deep inside the first dispatch —
        # fail here, with the knob's name, instead
        for name in ("max_batch", "block_size", "num_blocks",
                     "max_seq_len", "max_prefill_len"):
            v = getattr(self, name)
            if v < 1:
                raise ValueError(f"{name} must be >= 1, got {v}")
        chunk = (self.prefill_chunk if self.prefill_chunk is not None
                 else self.max_prefill_len)
        if chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {chunk}")
        if chunk > self.max_seq_len:
            raise ValueError(
                f"prefill_chunk ({chunk}) exceeds max_seq_len "
                f"({self.max_seq_len})")
        if self.decode_steps < 1:
            raise ValueError(
                f"decode_steps must be >= 1, got {self.decode_steps}")
        if self.kv_quantization not in KV_QUANT_MODES:
            raise ValueError(
                f"kv_quantization must be one of {KV_QUANT_MODES}, "
                f"got {self.kv_quantization!r}")
        if self.weight_quantization not in WEIGHT_QUANT_MODES:
            raise ValueError(
                f"weight_quantization must be one of "
                f"{WEIGHT_QUANT_MODES}, got {self.weight_quantization!r}")
        # normalize (a caller's list restores as the identical
        # fingerprint value) and validate the mesh geometry against the
        # backend, including the batch axis's lane/pool divisibility
        # (a non-dividing split has no equal shard layout); the
        # num_heads divisibility half runs at engine construction,
        # where the model is known
        object.__setattr__(self, "mesh_shape",
                           mesh_lib.validate_mesh_shape(
                               self.mesh_shape,
                               max_batch=self.max_batch,
                               num_blocks=self.num_blocks))
        if self.spill_max_bytes is not None:
            if self.spill_max_bytes < 1:
                raise ValueError(
                    f"spill_max_bytes must be >= 1 (or None for no "
                    f"spill tier), got {self.spill_max_bytes}")
            if not self.enable_prefix_caching:
                raise ValueError(
                    "spill_max_bytes requires enable_prefix_caching: "
                    "the spill tier is keyed by the prefix index's "
                    "hash chains, and nothing registers without it")
        if self.spec_tokens < 0:
            raise ValueError(
                f"spec_tokens must be >= 0, got {self.spec_tokens}")
        if self.max_dispatch_retries < 0:
            raise ValueError(
                f"max_dispatch_retries must be >= 0, got "
                f"{self.max_dispatch_retries}")
        if self.max_waiting is not None and self.max_waiting < 1:
            raise ValueError(
                f"max_waiting must be >= 1 (or None for unbounded), "
                f"got {self.max_waiting}")
        if (self.queue_high_watermark is not None
                and self.queue_high_watermark < 1):
            raise ValueError(
                f"queue_high_watermark must be >= 1, got "
                f"{self.queue_high_watermark}")
        if (self.queue_high_watermark is not None
                and self.max_waiting is not None
                and self.queue_high_watermark
                > self.max_waiting + self.max_batch):
            # client adds cap the queue at max_waiting and requeues
            # overshoot by at most max_batch: a higher watermark is
            # unreachable and the ladder's queue signal silently inert
            raise ValueError(
                f"queue_high_watermark ({self.queue_high_watermark}) is "
                f"unreachable: the queue never exceeds max_waiting + "
                f"max_batch ({self.max_waiting} + {self.max_batch})")
        if (self.free_block_low_watermark is not None
                and not 0.0 < self.free_block_low_watermark <= 1.0):
            raise ValueError(
                f"free_block_low_watermark must be in (0, 1], got "
                f"{self.free_block_low_watermark}")
        if self.degrade_patience < 1:
            raise ValueError(
                f"degrade_patience must be >= 1, got "
                f"{self.degrade_patience}")
        if self.degrade_admit_priority < 1:
            raise ValueError(
                f"degrade_admit_priority must be >= 1 (0 would pause "
                f"every class), got {self.degrade_admit_priority}")
        if self.tenant_weights is not None:
            for t, w in self.tenant_weights.items():
                if int(w) < 1:
                    raise ValueError(
                        f"tenant_weights[{t!r}] must be >= 1, got {w}")
        if self.tenant_quotas is not None:
            for t, q in self.tenant_quotas.items():
                if not isinstance(q, TenantQuota):
                    raise ValueError(
                        f"tenant_quotas[{t!r}] must be a TenantQuota, "
                        f"got {type(q).__name__}")
                q.validate(t)
        if self.drr_quantum < 1:
            raise ValueError(
                f"drr_quantum must be >= 1, got {self.drr_quantum}")
        if self.tenant_rate_tau_s <= 0:
            raise ValueError(
                f"tenant_rate_tau_s must be > 0, got "
                f"{self.tenant_rate_tau_s}")
        if (self.snapshot_interval_ticks is not None
                and self.snapshot_interval_ticks < 1):
            raise ValueError(
                f"snapshot_interval_ticks must be >= 1 (or None for no "
                f"periodic checkpointing), got "
                f"{self.snapshot_interval_ticks}")
        if (self.scrub_interval_ticks is not None
                and self.scrub_interval_ticks < 1):
            raise ValueError(
                f"scrub_interval_ticks must be >= 1 (or None for no "
                f"background scrubbing), got {self.scrub_interval_ticks}")
        if self.scrub_spill_blocks < 1:
            raise ValueError(
                f"scrub_spill_blocks must be >= 1, got "
                f"{self.scrub_spill_blocks}")
        if self.spec_adapt and self.spec_tokens < 1:
            raise ValueError(
                "spec_adapt requires spec_tokens >= 1 (there is no "
                "draft cap to adapt at spec_tokens == 0)")
        if not 0.0 <= self.spec_accept_low <= self.spec_accept_high <= 1.0:
            raise ValueError(
                f"spec acceptance thresholds must satisfy 0 <= low <= "
                f"high <= 1, got low={self.spec_accept_low} "
                f"high={self.spec_accept_high}")


@dataclasses.dataclass
class _QueueEntry:
    """A waiting (or preempted-and-requeued) request. ``generated``
    carries tokens already emitted before a preemption so they are
    never resampled — re-admission re-prefills ``prompt +
    generated[:-1]`` and resumes decoding from ``generated[-1]``.
    ``arrival`` is the request's add_request order: it seeds the
    request's PRNG key, so it must survive preemption unchanged (the
    resumed request continues the SAME key sequence at the next token
    index). ``hashes`` memoizes the prefill sequence's block hash chain
    (the sequence is frozen per entry), so a head blocked on pool
    pressure is not re-hashed on every scheduler tick. ``enq_t`` /
    ``enq_tick`` stamp when the entry (re-)entered the queue — the
    queue-wait observability in ``stats()`` (a preempted requeue
    restarts the wait)."""

    request: Request
    arrival: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    hashes: Optional[List[str]] = None
    enq_t: float = 0.0
    enq_tick: int = 0
    # whether the entry's DRR cost (the committed token budget) was
    # already charged against its tenant's deficit: admission charges
    # exactly once, so preemption/crash-recovery requeues and restored
    # residents re-admit FREE and ahead of uncharged work (the old
    # front-of-the-class requeue discipline, tenant-aware)
    drr_charged: bool = False


class _ClassQueue:
    """One priority class of the waiting queue: per-tenant FIFO
    :class:`deque`\\ s plus the class's DRR walk state. ``ring`` lists
    the tenants with non-empty deques in first-enqueue order;
    ``cursor`` is the walk's current ring position, ``credited``
    whether the cursor tenant has received its quantum for the current
    visit, ``deficits`` the per-tenant leftover credit. A tenant whose
    deque drains leaves the ring and forfeits its deficit (standard
    DRR — credit never accumulates while you have nothing queued)."""

    __slots__ = ("queues", "ring", "cursor", "credited", "deficits")

    def __init__(self):
        self.queues: Dict[str, deque] = {}
        self.ring: List[str] = []
        self.cursor: int = 0
        self.credited: bool = False
        self.deficits: Dict[str, float] = {}

    def remove_tenant(self, tenant: str) -> None:
        i = self.ring.index(tenant)
        self.ring.pop(i)
        del self.queues[tenant]
        self.deficits.pop(tenant, None)
        if not self.ring:
            self.cursor, self.credited = 0, False
            return
        if i < self.cursor:
            self.cursor -= 1
        elif i == self.cursor:
            # the cursor now points at the NEXT tenant — a fresh visit
            self.credited = False
            if self.cursor >= len(self.ring):
                self.cursor = 0


class _WaitingQueue:
    """The waiting queue: strict priority BETWEEN classes (scanned in
    ascending class value, 0 = most urgent — the documented PR 8
    contract), weighted deficit-round-robin across TENANTS within each
    class (:class:`_ClassQueue`). ``append`` enqueues at the tail of
    the request's (class, tenant) FIFO, ``appendleft`` (preemption /
    crash-recovery requeues) at its head. Entries whose DRR cost was
    already charged (``drr_charged`` — requeues, restored residents)
    are served OUT OF BAND ahead of the walk, leaving the walk state
    untouched: with a single tenant this collapses to exactly the old
    per-class FIFO + front-requeue discipline, bit-for-bit. Iteration
    order (also the snapshot serialization order) is class by class,
    ring order within, FIFO within a tenant."""

    def __init__(self, weights: Optional[Mapping[str, int]] = None,
                 quantum: int = 64):
        self._classes: Dict[int, _ClassQueue] = {}
        self._weights = dict(weights or {})
        self._quantum = max(1, int(quantum))
        self._tenant_depth: Dict[str, int] = {}

    @staticmethod
    def _cost(entry: _QueueEntry) -> int:
        """The DRR cost of admitting an entry: its committed token
        budget (what it may make the engine serve). Charged once per
        request lifetime (``drr_charged``)."""
        if entry.drr_charged:
            return 0
        return len(entry.request.prompt) + entry.request.max_new_tokens

    def _weight(self, tenant: str) -> int:
        return max(1, int(self._weights.get(tenant, 1)))

    def tenant_depth(self, tenant: str) -> int:
        """Waiting entries currently held by ``tenant`` (all classes) —
        the O(1) backing of TenantQuota.max_waiting's door check."""
        return self._tenant_depth.get(tenant, 0)

    def _classes_ascending(self, below: Optional[int]):
        for p in sorted(self._classes):
            if below is not None and p >= below:
                return
            yield self._classes[p]

    def _note_removed(self, cq: _ClassQueue, tenant: str) -> None:
        self._tenant_depth[tenant] -= 1
        if not self._tenant_depth[tenant]:
            del self._tenant_depth[tenant]
        if not cq.queues[tenant]:
            cq.remove_tenant(tenant)

    def append(self, entry: _QueueEntry) -> None:
        self._enqueue(entry, left=False)

    def appendleft(self, entry: _QueueEntry) -> None:
        self._enqueue(entry, left=True)

    def _enqueue(self, entry: _QueueEntry, left: bool) -> None:
        cq = self._classes.setdefault(entry.request.priority,
                                      _ClassQueue())
        t = entry.request.tenant
        q = cq.queues.get(t)
        if q is None:
            q = cq.queues[t] = deque()
            cq.ring.append(t)           # new tenants join at the tail
            cq.deficits.setdefault(t, 0.0)
        (q.appendleft if left else q.append)(entry)
        self._tenant_depth[t] = self._tenant_depth.get(t, 0) + 1

    def _walk(self, cq: _ClassQueue, skip, mutate: bool):
        """The next entry the class would admit — ``mutate=False``
        peeks, ``mutate=True`` pops it and commits the walk. ``skip``
        tenants are passed over without credit (the engine's per-tick
        quota hold). Returns None when nothing in the class is
        servable."""
        skip = skip or ()
        n = len(cq.ring)
        # phase 1: already-charged heads (preemption requeues, restored
        # residents) serve out of band, ring order from the cursor,
        # without touching the walk state — the old front-of-the-class
        # discipline, tenant-aware
        for k in range(n):
            t = cq.ring[(cq.cursor + k) % n]
            if t in skip:
                continue
            q = cq.queues[t]
            if q and q[0].drr_charged:
                if not mutate:
                    return q[0]
                e = q.popleft()
                self._note_removed(cq, t)
                return e
        # phase 2: the weighted DRR walk
        candidates = [t for t in cq.ring if t not in skip]
        if not candidates:
            return None
        deficits = cq.deficits if mutate else dict(cq.deficits)
        cursor, credited = cq.cursor, cq.credited
        # termination bound (bug guard only): a tenant needs at most
        # ceil(max_cost / quantum) quantum credits, and each credit
        # costs TWO loop iterations (the credit itself, then the
        # cursor advance after the affordability re-check fails), per
        # ring member per cycle — hence the factor 2
        max_cost = max(self._cost(cq.queues[t][0]) for t in candidates)
        limit = 2 * len(cq.ring) * (max_cost // self._quantum + 2) + 16
        for _ in range(limit):
            t = cq.ring[cursor]
            if t in skip:
                cursor = (cursor + 1) % len(cq.ring)
                credited = False
                continue
            head = cq.queues[t][0]
            cost = self._cost(head)
            if deficits[t] >= cost:
                if not mutate:
                    return head
                e = cq.queues[t].popleft()
                deficits[t] -= cost
                e.drr_charged = True
                # the cursor STAYS on the serving tenant: DRR serves
                # while the deficit lasts, then moves on
                cq.cursor, cq.credited = cursor, credited
                self._note_removed(cq, t)
                return e
            if not credited:
                deficits[t] += self._quantum * self._weight(t)
                credited = True
                continue
            cursor = (cursor + 1) % len(cq.ring)
            credited = False
        raise RuntimeError(
            "DRR walk failed to terminate — invariant bug "
            f"(ring={cq.ring}, deficits={deficits})")

    def head(self, below: Optional[int] = None,
             skip=None) -> Optional[_QueueEntry]:
        """The next admissible entry, or None. ``below`` restricts to
        classes < it (the ladder's admission pause); ``skip`` tenants
        are passed over (quota holds) — a class whose every tenant is
        skipped falls through to the next class, so one tenant's quota
        never gates another tenant's lower class."""
        for cq in self._classes_ascending(below):
            e = self._walk(cq, skip, mutate=False)
            if e is not None:
                return e
        return None

    def popleft(self, below: Optional[int] = None,
                skip=None) -> _QueueEntry:
        """Pop exactly the entry :meth:`head` (same arguments)
        returns."""
        for p in sorted(self._classes):
            if below is not None and p >= below:
                break
            cq = self._classes[p]
            e = self._walk(cq, skip, mutate=True)
            if e is not None:
                if not cq.ring:
                    # drop drained classes: priority is an arbitrary
                    # client int, and dead entries would grow the scan
                    # with every distinct value ever submitted
                    del self._classes[p]
                return e
        raise IndexError("pop from an empty waiting queue")

    def has_priority_below(self, limit: int) -> bool:
        return any(True for _ in self._classes_ascending(limit))

    def expel(self, pred) -> List[_QueueEntry]:
        """Remove (and return, in iteration order) every entry matching
        ``pred``, preserving the order of the survivors and the DRR
        walk state of every surviving tenant — the deadline-expiry and
        abort sweep."""
        removed: List[_QueueEntry] = []
        for p in sorted(self._classes):
            cq = self._classes[p]
            for t in list(cq.ring):
                q = cq.queues[t]
                kept: deque = deque()
                while q:
                    e = q.popleft()
                    if pred(e):
                        removed.append(e)
                        self._tenant_depth[t] -= 1
                        if not self._tenant_depth[t]:
                            del self._tenant_depth[t]
                    else:
                        kept.append(e)
                cq.queues[t] = kept
                if not kept:
                    cq.remove_tenant(t)
            if not cq.ring:
                del self._classes[p]
        return removed

    def snapshot_state(self) -> Dict[str, object]:
        """The JSON-able DRR walk state per class: ring order, the
        cursor tenant, its credited flag, and the deficits. Restoring
        them (:meth:`restore_state`) resumes the identical admission
        walk mid-cycle (docs/robustness.md)."""
        out = {}
        for p, cq in self._classes.items():
            out[str(p)] = {
                "ring": list(cq.ring),
                "cursor_tenant": (cq.ring[cq.cursor] if cq.ring
                                  else None),
                "credited": bool(cq.credited),
                "deficits": {t: float(d) for t, d in cq.deficits.items()},
            }
        return out

    def restore_state(self, state: Mapping[str, object]) -> None:
        """Re-apply :meth:`snapshot_state` after the queue's entries
        were re-appended. Tenants present now but absent from the
        serialized ring (previously-resident requests re-queued by
        restore) append at the ring tail; serialized tenants no longer
        present drop out. The cursor re-anchors on its tenant."""
        for key, rec in (state or {}).items():
            cq = self._classes.get(int(key))
            if cq is None:
                continue
            serialized = [t for t in rec.get("ring", ()) if t in cq.queues]
            cq.ring = serialized + [t for t in cq.ring
                                    if t not in serialized]
            for t, d in (rec.get("deficits") or {}).items():
                if t in cq.queues:
                    cq.deficits[t] = float(d)
            cur = rec.get("cursor_tenant")
            if cur in cq.ring:
                cq.cursor = cq.ring.index(cur)
                cq.credited = bool(rec.get("credited", False))
            else:
                cq.cursor, cq.credited = 0, False

    def __iter__(self):
        for p in sorted(self._classes):
            cq = self._classes[p]
            for t in cq.ring:
                yield from cq.queues[t]

    def __len__(self) -> int:
        return sum(self._tenant_depth.values())



@dataclasses.dataclass
class _Slot:
    """Host-side state of one batch lane (prefilling or decoding)."""

    entry: _QueueEntry
    admit_seq: int                # monotonic admission order (preemption
                                  # evicts the largest = youngest)
    tokens: List[int]             # tokens whose K/V belong in the cache;
                                  # grows by one per decoded token
    prefill_len: int              # tokens to cache before decoding starts
    prefill_pos: int              # prompt tokens already cached
    context_len: int              # tokens currently valid in the cache
    blocks: List[int]             # owned/shared block ids, sequence order
    block_hashes: List[str]       # chain hashes per full block (lazy tail)
    num_registered: int           # full blocks already in the prefix index
    generated: List[int]
    last_token: int
    started: bool                 # first token known -> decoding

    @property
    def request(self) -> Request:
        return self.entry.request


class InferenceEngine:
    """Drives a :class:`~apex_tpu.models.gpt.GPTLMHeadModel` (or any
    model exposing the same ``kv_cache=`` apply contract) through
    continuous-batching generation.

    Usage::

        engine = InferenceEngine(model, params, EngineConfig(...))
        engine.add_request(Request("a", prompt, max_new_tokens=32))
        outputs = engine.run()          # {"a": [tok, tok, ...]}

    ``add_request`` may be called at any time, including between
    ``step()`` calls while other requests are mid-generation — that is
    the continuous-batching point.
    """

    def __init__(self, model, params, config: EngineConfig, *,
                 drafter=None, faults=None, clock=None, obs=None,
                 mesh=None):
        cfg = model.cfg
        self.model = model
        self.params = params
        self.config = config
        # quantized weight storage: re-express the params as int8/fp8
        # + per-output-channel scales and rebuild the model to read
        # them through the dequant-GEMM path. Runs FIRST so everything
        # downstream (sharding, program compilation, checksums) sees
        # only the quantized representation — the fp tree never
        # reaches the device when the knob is set.
        self._weight_quant_bytes = None
        if config.weight_quantization is not None:
            fp_bytes = gpt_param_bytes(params)
            self.model, self.params = quantize_gpt_model(
                model, params, config.weight_quantization)
            model, params = self.model, self.params
            self._weight_quant_bytes = (fp_bytes,
                                        gpt_param_bytes(self.params))
        # optional chaos harness (apex_tpu.utils.faults.FaultPlan): every
        # jitted dispatch fires the plan at its site ("prefill"/"decode",
        # plus "draft" around the speculative proposer) before
        # launching, so chaos tests are seeded and reproducible
        self.faults = faults
        if faults is not None:
            # the engine's outputs are integer tokens, so there is no
            # float output the "nan" kind could meaningfully corrupt —
            # reject rather than record a fire that changed nothing
            bad = [s.site for s in getattr(faults, "specs", ())
                   if s.kind == "nan"
                   and s.site in ("prefill", "decode", "draft")]
            if bad:
                raise ValueError(
                    f"nan faults are not supported at serving sites "
                    f"{sorted(set(bad))}; use transient/crash (the "
                    f"train loop's watchdog owns nan handling)")
            # the integrity sites are corruption-only (a transient/
            # crash there would raise from inside host bookkeeping
            # with no defined recovery), and "corrupt" at a dispatch
            # site is meaningful only at "decode" (the SDC model: a
            # wrong token emitted from the drain) — prefill/draft
            # corruption has no defined consumer
            bad = [s.site for s in getattr(faults, "specs", ())
                   if (s.site in _INTEGRITY_SITES
                       and s.kind != "corrupt")
                   or (s.kind == "corrupt"
                       and s.site in ("prefill", "draft"))]
            if bad:
                raise ValueError(
                    f"unsupported fault kind/site combination at "
                    f"{sorted(set(bad))}: integrity sites "
                    f"{_INTEGRITY_SITES} take only 'corrupt' specs, "
                    f"and 'corrupt' dispatch faults are supported at "
                    f"'decode' only (docs/robustness.md)")
        # deadline clock, injectable so TTL tests are deterministic
        self._clock = time.monotonic if clock is None else clock
        # observability (docs/observability.md): tracer + flight
        # recorder + metrics, all OUTPUT-only — no engine decision ever
        # reads observer state (the zero-perturbation contract), and
        # every observer timestamp comes from the engine's own clock so
        # traces are deterministic under fake clocks. None = off, at
        # zero cost on the hot paths.
        self._obs = obs
        if obs is not None:
            obs.bind_engine(self._clock)
            # both storage quantization modes surface as one labeled
            # gauge family the moment the engine exists (the modes are
            # identity, not runtime state — set once, never moved)
            from apex_tpu.observability import QUANT_MODE_CODES
            obs.gauge("kv_quant_mode",
                      QUANT_MODE_CODES[config.kv_quantization])
            obs.gauge("weight_quant_mode",
                      QUANT_MODE_CODES[config.weight_quantization])
            if self._weight_quant_bytes is not None:
                fp_b, q_b = self._weight_quant_bytes
                obs.record("dequant_gemm",
                           mode=config.weight_quantization,
                           fp_bytes=fp_b, quant_bytes=q_b)
        # (dispatch t0, dispatch seq) of the in-flight decode, tracked
        # only while an observer wants the dispatch->drain trace span
        self._pending_obs = None
        self._chunk = (config.prefill_chunk if config.prefill_chunk
                       is not None else config.max_prefill_len)
        # speculative decoding: the drafter defaults to prompt-lookup;
        # a custom one rides the same propose() contract (drafter.py)
        if config.spec_tokens > 0:
            self.drafter = NgramDrafter() if drafter is None else drafter
        elif drafter is not None:
            raise ValueError(
                "a drafter requires spec_tokens >= 1 (speculative "
                "decoding is off at spec_tokens == 0)")
        else:
            self.drafter = None
        # flipped off forever if the drafter is quarantined: the verify
        # program with zero proposals is a plain single-token step, so
        # the engine degrades to non-speculative decoding, not death
        self._drafter_ok = config.spec_tokens > 0
        # the coming dispatch's proposals: {lane: [token, ...]},
        # rebuilt every decode phase (step 4), consumed by the dispatch
        self._draft_plan: Dict[int, List[int]] = {}
        if config.max_seq_len > cfg.max_position_embeddings:
            raise ValueError(
                f"max_seq_len ({config.max_seq_len}) exceeds the model's "
                f"max_position_embeddings ({cfg.max_position_embeddings})")
        self.max_blocks_per_seq = blocks_needed(config.max_seq_len,
                                                config.block_size)
        head_dim = cfg.hidden_size // cfg.num_heads
        self.cache = KVCache.create(
            cfg.num_layers, config.num_blocks, config.block_size,
            cfg.num_heads, head_dim, dtype=config.kv_dtype,
            quantization=config.kv_quantization)
        # -- the GSPMD mesh (docs/serving.md, "Mesh sharding") ----------
        # The config's shape was geometry-validated at construction;
        # the model-dependent half (heads must split evenly) runs here.
        # ``mesh=`` lets a fleet router build ONE mesh and thread it
        # through every replica (equal NamedShardings across replicas
        # by construction); it must agree with the config.
        mesh_lib.validate_mesh_shape(config.mesh_shape,
                                     num_heads=cfg.num_heads)
        if mesh is not None:
            if (tuple(mesh.axis_names) != mesh_lib.MESH_AXES
                    or tuple(mesh.devices.shape)
                    != tuple(config.mesh_shape)):
                raise ValueError(
                    f"mesh= (axes {tuple(mesh.axis_names)}, shape "
                    f"{tuple(mesh.devices.shape)}) does not match "
                    f"mesh_shape {tuple(config.mesh_shape)} over axes "
                    f"{mesh_lib.MESH_AXES}")
            self.mesh = mesh
        else:
            self.mesh = mesh_lib.build_mesh(config.mesh_shape)
        if config.mesh_shape[1] > 1:
            from apex_tpu.ops.paged_attention_pallas import (
                pallas_paged_read_wanted)
            if pallas_paged_read_wanted():
                # the fused Pallas read kernel is a single-device
                # program (no SPMD partitioning rule); under a sharded
                # pool it would fail at trace time with a far worse
                # error than this one
                raise ValueError(
                    "APEX_PAGED_ATTENTION_PALLAS is incompatible with "
                    f"a sharded model axis (mesh_shape "
                    f"{tuple(config.mesh_shape)}): the fused paged-read "
                    "kernel is single-device — unset the flag or run "
                    "mesh (1, 1)")
            from apex_tpu.ops.dequant_gemm import dequant_gemm_wanted
            if dequant_gemm_wanted():
                # same single-device story as the paged-read kernel:
                # pallas_call has no SPMD partitioning rule, and the
                # XLA dequant chain partitions collective-free with
                # the scales riding their kernel's shard
                raise ValueError(
                    "APEX_DEQUANT_GEMM_PALLAS is incompatible with a "
                    f"sharded model axis (mesh_shape "
                    f"{tuple(config.mesh_shape)}): the fused "
                    "dequant-GEMM kernel is single-device — unset the "
                    "flag or run mesh (1, 1)")
        # weights and KV pools commit to their mesh layout (head axis
        # over "model"; see gpt.gpt_param_pspec / KVCache.
        # partition_specs), and every jitted program pins its returned
        # cache to the same layout — without the out_shardings pin,
        # GSPMD may hand back a different pool layout and the next
        # dispatch's changed input sharding would recompile, breaking
        # the one-program compile-count contract
        self.params = mesh_lib.shard_params(self.mesh, self.params)
        self.cache = mesh_lib.shard_cache(self.mesh, self.cache)
        self._program_out = mesh_lib.program_out_shardings(self.mesh,
                                                           self.cache)
        # the tenant ledger's per-block charge unit: a quantized block
        # charges its reduced byte footprint relative to the full-
        # precision block this config would otherwise store, so
        # max_resident_blocks quotas are denominated in full-precision
        # block equivalents (1.0 — and the pre-quantization ledger,
        # bit for bit — when quantization is off)
        if config.kv_quantization is not None:
            self._block_weight = (
                kv_block_bytes(cfg.num_layers, config.block_size,
                               cfg.num_heads, head_dim,
                               quantization=config.kv_quantization)
                / kv_block_bytes(cfg.num_layers, config.block_size,
                                 cfg.num_heads, head_dim,
                                 dtype=config.kv_dtype))
        else:
            self._block_weight = 1.0
        # -- the batch axis (docs/serving.md, "The batch axis") --------
        # B > 1 splits the max_batch decode lanes and the block pool
        # into B contiguous shards (lane i -> shard i // lanes_per_
        # shard; block b -> shard b // blocks_per_shard). The allocator
        # enforces shard residency host-side; the sharded programs
        # localize tables by subtracting the shard base. B == 1 keeps
        # every code path byte-identical to the pre-batch-axis engine.
        self._batch_shards = config.mesh_shape[0]
        self._lanes_per_shard = config.max_batch // self._batch_shards
        self._blocks_per_shard = config.num_blocks // self._batch_shards
        self.allocator = BlockAllocator(config.num_blocks,
                                        block_weight=self._block_weight,
                                        num_shards=self._batch_shards)
        # the host-RAM spill tier (docs/serving.md memory tiers):
        # evicted/flushed prefix blocks copy to this bounded host
        # store; _admit re-admits matches by device upload
        self.spill: Optional[HostSpillStore] = None
        self._spill_hits = 0
        self._spill_misses = 0
        # -- data integrity (docs/robustness.md) -----------------------
        self._num_corruptions_detected = 0
        self._num_import_refusals = 0
        self._num_scrubs = 0
        self._num_scrub_blocks_verified = 0
        # the corrupt seed captured at the decode dispatch, applied to
        # the drained tokens (the SDC fault model rides the deferred
        # sync: dispatch fires the plan, drain perturbs the fetch)
        self._pending_corrupt: Optional[int] = None
        if config.spill_max_bytes is not None:
            self.spill = HostSpillStore(
                config.spill_max_bytes,
                verify=config.verify_artifacts,
                # the chaos seam exists only when a plan does — the
                # no-faults engine runs the store's bare read/write
                corrupt_hook=(self._corrupt_payload_hook
                              if faults is not None else None),
                on_corrupt=self._note_corruption)
            self.allocator.attach_spill(self.spill, self._spill_payload)
            # the upload program: one jitted scatter of a host block
            # into the pool (its own jit slot — the prefill/decode
            # compile-count contract is untouched)
            self._upload = jax.jit(
                (self._upload_sharded_impl if self._batch_shards > 1
                 else self._upload_impl),
                donate_argnums=(0,) if config.donate_cache else (),
                **self._cache_out_kw())
        self.slots: List[Optional[_Slot]] = [None] * config.max_batch
        self.waiting = _WaitingQueue(weights=config.tenant_weights,
                                     quantum=config.drr_quantum)
        # every uid currently waiting or resident — the O(1) backing of
        # add_request's duplicate guard (maintained at enqueue/restore,
        # cleared by _set_status at every terminal transition)
        self._live_uids: set = set()
        self.finished: Dict[str, List[int]] = {}
        # terminal status per finished uid ("finished"|"timeout"|"failed");
        # drained alongside `finished` by run()
        self.statuses: Dict[str, str] = {}
        self._deadline: Dict[str, float] = {}   # uid -> absolute deadline
        self._key = jax.random.PRNGKey(config.seed)
        self._arrival_count = 0
        self._admit_count = 0
        self._num_prefills = 0
        self._num_prefill_chunks = 0
        self._num_decode_dispatches = 0
        self._num_tokens_decoded = 0
        self._num_preemptions = 0
        self._num_cow_copies = 0
        self._prefix_hit_blocks = 0
        self._prefix_lookup_blocks = 0
        self._prompt_blocks_allocated = 0
        self._num_timeouts = 0
        self._num_dispatch_retries = 0
        self._num_quarantines = 0
        self._num_draft_tokens = 0
        self._num_accepted_tokens = 0
        self._num_draft_retries = 0
        self._num_drafter_quarantines = 0
        self._num_spec_blocks_rolled_back = 0
        self._num_snapshots = 0
        self._num_restores = 0
        # -- fleet serving (docs/fleet.md) -----------------------------
        # the bounded-staleness failover picture: refreshed every
        # snapshot_interval_ticks by checkpoint(), read by the fleet
        # router when this replica dies
        self.last_checkpoint: Optional[Dict[str, object]] = None
        self._num_checkpoints = 0
        self._num_migrated_in = 0
        self._num_migrated_out = 0
        # the arrival PRNG identity of each uid this engine exported,
        # retained CLEAN on this side of the wire: when a record rots
        # in transit and the target refuses it, the router re-injects
        # the request fresh — and only this index lets the recompute
        # re-draw the same sampled tokens (sampling is arrival-keyed;
        # the corrupted record's own "arrival" field is untrustworthy)
        self._exported_arrivals: Dict[str, int] = {}
        # -- overload protection (docs/robustness.md) ------------------
        self._num_ticks = 0
        self._queue_depth_peak = 0
        self._queue_wait_count = 0
        self._queue_wait_ticks_sum = 0
        self._queue_wait_ticks_max = 0
        self._queue_wait_s_sum = 0.0
        self._queue_wait_s_max = 0.0
        self._num_rejected_queue_full = 0
        self._num_rejected_infeasible = 0
        # cheap service-time estimators feeding the admit-time
        # feasibility gate: EWMAs of observed per-dispatch wall time
        # (None until the first observation — the gate stays open)
        self._ewma_prefill_s: Optional[float] = None
        self._ewma_decode_s: Optional[float] = None
        # the degradation ladder: current rung (0 = normal), the
        # pressure/clear streaks driving its hysteresis, and the
        # transition counters
        self._degradation_level = 0
        self._pressure_streak = 0
        self._clear_streak = 0
        self._num_degrade_steps_down = 0
        self._num_degrade_steps_up = 0
        self._num_degrade_flushed_blocks = 0
        # -- multi-tenant isolation (docs/robustness.md) ---------------
        self._num_throttled = 0
        self._num_cancelled = 0
        # the tenant ledger: every tenant ever submitted to this
        # engine, its delivered-token count, its exponentially-decayed
        # token-rate estimator (value + last-update time), its
        # terminal-status tallies, and its quota-preemption count
        self._tenant_seen: set = {DEFAULT_TENANT}
        self._tenant_tokens: Dict[str, int] = {}
        self._tenant_rate: Dict[str, float] = {}
        self._tenant_rate_t: Dict[str, float] = {}
        self._tenant_status: Dict[str, Dict[str, int]] = {}
        self._tenant_preemptions: Dict[str, int] = {}
        # streaming delivery (docs/serving.md): (uid, token, is_last)
        # events appended as tokens become host-visible, drained by
        # pop_stream_events(); every terminal transition appends a
        # (uid, -1, True) sentinel
        self._stream: deque = deque()
        # dynamic speculation (spec_adapt): the adaptive per-plan draft
        # cap, the acceptance-rate EWMA driving it, and the probe
        # countdown that lets a capped-out engine re-measure
        self._spec_cap = config.spec_tokens
        self._spec_accept_ewma: Optional[float] = None
        self._spec_probe_countdown = _SPEC_PROBE_EVERY
        self._num_spec_cap_shrinks = 0
        self._num_spec_cap_restores = 0
        self._fetch_failures = 0   # consecutive failed deferred drains
        # the in-flight decode dispatch: (device [B, K] tokens, device
        # [B] counts, the lane indices it covers). Fetched — the only
        # host sync of the decode path — at the NEXT tick, after that
        # tick's admission/prefill work is already dispatched.
        self._pending = None
        # dirty-tracked device mirrors of slot-composition state: the
        # decode block table, and the per-lane sampling/EOS/key arrays.
        # Steady-state decode ticks reuse them without a rebuild.
        self._dev_tables = DeviceMirror()
        self._dev_lanes = DeviceMirror()
        self._table_rebuilds = 0
        # the fixed program set; anything else jitted here would break
        # the compile-count contract the tests pin. Arg 1 is the cache
        # pool in every signature (donated when the runtime allows).
        # With speculation on, THE decode program is the verify program
        # — same slot in the contract, still exactly one compilation
        # (zero-proposal lanes run through it as single-token steps, so
        # no second "fallback" program ever exists).
        donate = (1,) if config.donate_cache else ()
        # B > 1 swaps in the batch-axis sharded wrappers (same program
        # slots, same arg signatures, one compilation each — the
        # compile-count contract is shape-based and unchanged); B == 1
        # keeps the exact pre-batch-axis callables, so the (1, 1)
        # bit-identity certification never sees the wrapper.
        sharded = self._batch_shards > 1
        prefill_fn = (self._prefill_sharded_impl if sharded
                      else self._prefill_impl)
        if config.spec_tokens > 0:
            decode_fn = (self._spec_decode_sharded_impl if sharded
                         else self._spec_decode_impl)
        else:
            decode_fn = (self._decode_sharded_impl if sharded
                         else self._decode_impl)
        self._prefill = jax.jit(prefill_fn, donate_argnums=donate,
                                **self._pair_out_kw())
        self._decode = jax.jit(decode_fn, donate_argnums=donate,
                               **self._pair_out_kw())
        self._cow = jax.jit(
            self._cow_sharded_impl if sharded else copy_block,
            donate_argnums=(0,) if config.donate_cache else (),
            **self._cache_out_kw())

    def _pair_out_kw(self) -> Dict[str, object]:
        """``jax.jit`` kwargs pinning a ``(cache, tokens)`` program's
        output layout to the mesh (empty when the mesh layer is
        neutered — the pre-mesh jit, byte for byte)."""
        if self._program_out is None:
            return {}
        return {"out_shardings": self._program_out}

    def _cache_out_kw(self) -> Dict[str, object]:
        """Same, for the cache-only programs (CoW copy, spill upload)."""
        if self._program_out is None:
            return {}
        return {"out_shardings": self._program_out[0]}

    # -- the jitted programs ----------------------------------------------

    def _prefill_impl(self, params, cache, ids, positions, seq_len,
                      write_start, sample_idx, table, key, temp, top_k,
                      top_p):
        logits, cache = self.model.apply(
            params, ids, deterministic=True, kv_cache=cache,
            block_tables=table, cache_positions=positions,
            seq_lens=seq_len, write_start=write_start)
        last = jnp.take_along_axis(
            logits, sample_idx[:, None, None], axis=1)[:, 0]   # [1, V]
        # ``key`` is the REQUEST's key; the first generated token is
        # token index 0 of its per-token key chain (decode continues at
        # index 1), so schedule changes never perturb the draw
        tok = sample_tokens(last, jax.random.fold_in(key, 0),
                            temp, top_k, top_p)
        return cache, tok

    def _decode_impl(self, params, cache, tokens, tables, context_lens,
                     budgets, gen_counts, eos_ids, lane_keys, temp,
                     top_k, top_p):
        """K = ``decode_steps`` fused decode iterations in ONE dispatch.

        Each scan step writes the carried token's K/V at the lane's
        context position, attends through the (loop-invariant) block
        table, samples the next token with the lane's per-token key,
        and feeds it back. Lanes freeze — stop writing, emit ``-1`` —
        once their remaining ``budgets`` hit zero or they sample their
        EOS id (``eos_ids``; ``-1`` = none); a frozen lane's query
        still rides the batch but its ``write_start`` sits one past its
        context position, so the scatter drops. Returns the updated
        cache and ``[B, K]`` emitted tokens — ``-1`` where nothing was
        emitted, so each lane's count is the length of its non-sentinel
        prefix (token ids are always ``>= 0``; the host derives counts
        from the one fetched array instead of a second device output).
        """
        def body(carry, _):
            cache, tok, ctx, budget, gcount = carry
            act = budget > 0
            write_start = jnp.where(act, ctx, ctx + 1)
            logits, cache = self.model.apply(
                params, tok[:, None], deterministic=True, kv_cache=cache,
                block_tables=tables, cache_positions=ctx[:, None],
                seq_lens=ctx + 1, write_start=write_start)
            keys = jax.vmap(jax.random.fold_in)(lane_keys, gcount)
            new = sample_tokens_per_lane(logits[:, 0], keys, temp, top_k,
                                         top_p)
            emitted = act.astype(jnp.int32)
            out = jnp.where(act, new, jnp.int32(-1))
            budget = budget - emitted
            stop = (budget <= 0) | ((eos_ids >= 0) & (new == eos_ids))
            cont = act & ~stop
            # zeroing the budget on EOS folds both stop conditions into
            # the single ``budget > 0`` activity test next iteration
            carry = (cache, jnp.where(cont, new, tok), ctx + emitted,
                     jnp.where(cont, budget, jnp.int32(0)),
                     gcount + emitted)
            return carry, out

        (cache, _, _, _, _), toks = jax.lax.scan(
            body, (cache, tokens, context_lens, budgets, gen_counts),
            None, length=self.config.decode_steps)
        return cache, toks.T

    def _spec_decode_impl(self, params, cache, tokens, drafts, draft_lens,
                          tables, context_lens, budgets, gen_counts,
                          eos_ids, lane_keys, temp, top_k, top_p):
        """Draft-and-verify decode: ONE target forward scores a whole
        drafted span per lane (``spec_tokens > 0`` replaces the K-step
        scan with this program).

        Each lane's query chunk is its carried token followed by its
        ``draft_lens`` proposals, at absolute positions ``ctx .. ctx +
        d`` — the multi-query paged-prefill path, so position ``p``'s
        logits are exactly the target distribution given the drafts
        before it, and the chunk's K/V (the carried token's AND every
        draft's) scatter into the lane's reserved span in the same
        dispatch. The accept rule
        (:func:`~apex_tpu.serving.sampling.spec_verify_tokens`) keeps a
        prefix of the drafts and samples the correction/bonus token
        with the lane's schedule-invariant per-token keys; the same
        stop-mask conventions as the scan then apply — inactive lanes
        emit nothing (and ``write_start`` drops their writes), an
        accepted/emitted EOS truncates the lane's remaining span, and
        the program returns ``[max_batch, spec_tokens + 1]`` tokens
        with ``-1`` sentinels past each lane's emitted prefix, so the
        deferred-drain contract is byte-for-byte the scan's.

        Rejected drafts need no device-side rollback: their K/V sits at
        positions past the lane's new context length, which every
        attention mask already excludes, and the next dispatch's writes
        land over them before the context ever reaches those positions.
        (The HOST-side reservation rollback — returning span blocks the
        rejection stranded — happens at drain time via
        ``BlockAllocator.trim_to``.)
        """
        # lane count from the INPUT (not config.max_batch): under the
        # batch-axis vmap each shard verifies its own lane group; the
        # unsharded program passes all max_batch lanes, so the traced
        # value is unchanged there
        B = tokens.shape[0]
        P = self.config.spec_tokens + 1
        act = budgets > 0
        q_ids = jnp.concatenate([tokens[:, None], drafts], axis=1)
        pos = (context_lens[:, None]
               + jax.lax.broadcasted_iota(jnp.int32, (B, P), 1))
        # the lane's span: carried token + its proposals; padded query
        # slots past it are masked (no write, ignored logits)
        seq_lens = context_lens + 1 + draft_lens
        write_start = jnp.where(act, context_lens, context_lens + P + 1)
        logits, cache = self.model.apply(
            params, q_ids, deterministic=True, kv_cache=cache,
            block_tables=tables, cache_positions=pos, seq_lens=seq_lens,
            write_start=write_start)
        token_idx = (gen_counts[:, None]
                     + jax.lax.broadcasted_iota(jnp.int32, (B, P), 1))
        emitted, n_emit = spec_verify_tokens(
            logits, drafts, draft_lens, lane_keys, token_idx, temp,
            top_k, top_p)
        # stop masks, mirroring the scan: emit only the accepted-prefix
        # + correction window, cut everything after the first EOS, and
        # mask inactive lanes entirely. All three are prefix masks, so
        # the host's count-by-sentinel-prefix drain stays valid.
        ii = jax.lax.broadcasted_iota(jnp.int32, (B, P), 1)
        within = ii < n_emit[:, None]
        is_eos = (within & (eos_ids[:, None] >= 0)
                  & (emitted == eos_ids[:, None]))
        after_eos = (jnp.cumsum(is_eos.astype(jnp.int32), axis=1)
                     - is_eos.astype(jnp.int32)) > 0
        keep = within & ~after_eos & act[:, None]
        return cache, jnp.where(keep, emitted, jnp.int32(-1))

    # -- the batch-axis sharded programs (docs/serving.md) ----------------
    #
    # At mesh_shape = (B, M) with B > 1 the jitted programs wrap the
    # (1, M) bodies above in a per-shard vmap: the pool's block axis
    # reshapes [L, N, ...] -> [B, L, N/B, ...] exactly on the shard
    # boundaries the NamedSharding put there (a local reshape — GSPMD
    # inserts nothing), lane arrays reshape [max_batch] -> [B, N/B
    # lanes], and the GLOBAL block-table ids localize per shard. The
    # allocator's shard-residency invariant means the owning shard's
    # entries land in [0, blocks_per_shard) and every foreign entry
    # clamps to the out-of-bounds sentinel, where the scatter drops
    # and the gather reads already-masked garbage — so non-owners need
    # no masking and the whole split lowers collective-free (the
    # audit_collectives batch contract). The clamp is explicit because
    # jnp indexing WRAPS negative traced indices Python-style; a raw
    # base subtraction would alias a foreign block into a valid local
    # id.

    def _cache_split(self, cache):
        B = self._batch_shards

        def split(x):
            y = x.reshape((x.shape[0], B, x.shape[1] // B) + x.shape[2:])
            return jnp.moveaxis(y, 1, 0)

        return jax.tree.map(split, cache)

    def _cache_merge(self, scache):
        B = self._batch_shards

        def merge(x):
            y = jnp.moveaxis(x, 0, 1)
            return y.reshape((y.shape[0], B * y.shape[2]) + y.shape[3:])

        return jax.tree.map(merge, scache)

    def _localize_tables(self, tables):
        """``[B, lanes, M]``-shaped global-id tables -> per-shard local
        ids: in-range entries subtract the shard base, everything else
        (foreign shards' blocks, the host's ``num_blocks`` sentinel)
        becomes the local out-of-bounds id ``blocks_per_shard``."""
        Nl = self._blocks_per_shard
        bases = (jnp.arange(self._batch_shards, dtype=jnp.int32)
                 * Nl)[:, None, None]
        local = tables - bases
        return jnp.where((local >= 0) & (local < Nl), local,
                         jnp.int32(Nl))

    def _prefill_sharded_impl(self, params, cache, ids, positions,
                              seq_len, write_start, sample_idx, table,
                              key, temp, top_k, top_p):
        """B > 1 prefill: every shard traces the same ``[1, C]`` chunk
        (inputs broadcast across the vmap), but only the shard owning
        the slot's blocks sees in-range localized table entries — its
        scatter writes the chunk and its attention reads real K/V;
        every other shard's writes drop and its sampled token is
        deterministic garbage the host discards. Returns ``[B]``
        tokens (batch-sharded); the host keeps index ``lane_shard``."""
        B = self._batch_shards
        scache = self._cache_split(cache)
        tbl = self._localize_tables(
            jnp.broadcast_to(table, (B,) + table.shape))

        def one(c, tb):
            return self._prefill_impl(params, c, ids, positions,
                                      seq_len, write_start, sample_idx,
                                      tb, key, temp, top_k, top_p)

        scache, tok = jax.vmap(one)(scache, tbl)
        return self._cache_merge(scache), tok.reshape(B)

    def _decode_sharded_impl(self, params, cache, tokens, tables,
                             context_lens, budgets, gen_counts, eos_ids,
                             lane_keys, temp, top_k, top_p):
        """B > 1 decode: each shard scans its own lane group against
        its own pool range. Tokens return ``[max_batch, K]`` in the
        global lane order (lane = shard * lanes_per_shard + local), so
        the host drain is byte-identical to the unsharded program's."""
        B, Lp = self._batch_shards, self._lanes_per_shard
        scache = self._cache_split(cache)
        tbl = self._localize_tables(tables.reshape(B, Lp, -1))

        def one(c, tb, tok, cx, bud, gc, eo, ky, tp, tk, tpp):
            return self._decode_impl(params, c, tok, tb, cx, bud, gc,
                                     eo, ky, tp, tk, tpp)

        scache, toks = jax.vmap(one)(
            scache, tbl, tokens.reshape(B, Lp),
            context_lens.reshape(B, Lp), budgets.reshape(B, Lp),
            gen_counts.reshape(B, Lp), eos_ids.reshape(B, Lp),
            lane_keys.reshape((B, Lp) + lane_keys.shape[1:]),
            temp.reshape(B, Lp), top_k.reshape(B, Lp),
            top_p.reshape(B, Lp))
        return (self._cache_merge(scache),
                toks.reshape((self.config.max_batch,) + toks.shape[2:]))

    def _spec_decode_sharded_impl(self, params, cache, tokens, drafts,
                                  draft_lens, tables, context_lens,
                                  budgets, gen_counts, eos_ids,
                                  lane_keys, temp, top_k, top_p):
        """B > 1 draft-and-verify: the verify program vmapped over the
        shard axis, same conventions as the sharded scan decode."""
        B, Lp = self._batch_shards, self._lanes_per_shard
        scache = self._cache_split(cache)
        tbl = self._localize_tables(tables.reshape(B, Lp, -1))

        def one(c, tok, dr, dl, tb, cx, bud, gc, eo, ky, tp, tk, tpp):
            return self._spec_decode_impl(params, c, tok, dr, dl, tb,
                                          cx, bud, gc, eo, ky, tp, tk,
                                          tpp)

        scache, toks = jax.vmap(one)(
            scache, tokens.reshape(B, Lp),
            drafts.reshape((B, Lp) + drafts.shape[1:]),
            draft_lens.reshape(B, Lp), tbl,
            context_lens.reshape(B, Lp), budgets.reshape(B, Lp),
            gen_counts.reshape(B, Lp), eos_ids.reshape(B, Lp),
            lane_keys.reshape((B, Lp) + lane_keys.shape[1:]),
            temp.reshape(B, Lp), top_k.reshape(B, Lp),
            top_p.reshape(B, Lp))
        return (self._cache_merge(scache),
                toks.reshape((self.config.max_batch,) + toks.shape[2:]))

    def _cow_sharded_impl(self, cache, src, dst):
        """B > 1 copy-on-write: the owning shard (src and dst share a
        shard — the allocator allocates the private copy on the slot's
        shard) copies localized ids; every other shard targets the
        out-of-bounds id, where the explicit ``mode="drop"`` discards
        the write."""
        B, Nl = self._batch_shards, self._blocks_per_shard
        scache = self._cache_split(cache)
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        shard_ids = jnp.arange(B, dtype=jnp.int32)
        own = shard_ids == src // Nl
        src_l = jnp.where(own, src % Nl, jnp.int32(Nl))
        dst_l = jnp.where(own, dst % Nl, jnp.int32(Nl))

        def one(c, s, d):
            # copy_block's shape, with explicit drop modes: the
            # non-owning shards' OOB src clamps (reads garbage) and
            # OOB dst drops (writes nothing)
            s = jnp.minimum(s, jnp.int32(Nl - 1))
            out = KVCache(
                k=c.k.at[:, d].set(c.k[:, s], mode="drop"),
                v=c.v.at[:, d].set(c.v[:, s], mode="drop"))
            if c.k_scale is not None:
                out = out._replace(
                    k_scale=c.k_scale.at[:, d].set(c.k_scale[:, s],
                                                   mode="drop"),
                    v_scale=c.v_scale.at[:, d].set(c.v_scale[:, s],
                                                   mode="drop"))
            return out

        return self._cache_merge(jax.vmap(one)(scache, src_l, dst_l))

    def _upload_sharded_impl(self, cache, ids, k_blk, v_blk, *scales):
        """B > 1 spill upload: the ``[max_blocks_per_seq]`` global ids
        localize per shard (foreign/padding entries clamp out of
        bounds and drop), payloads broadcast — each shard scatters
        only the rows it owns."""
        B, Nl = self._batch_shards, self._blocks_per_shard
        scache = self._cache_split(cache)
        bases = (jnp.arange(B, dtype=jnp.int32) * Nl)[:, None]
        local = jnp.asarray(ids, jnp.int32)[None, :] - bases
        ids_l = jnp.where((local >= 0) & (local < Nl), local,
                          jnp.int32(Nl))

        def one(c, i):
            return self._upload_impl(c, i, k_blk, v_blk, *scales)

        return self._cache_merge(jax.vmap(one)(scache, ids_l))

    # -- host-side scheduling ---------------------------------------------

    def add_request(self, request: Request) -> int:
        """Validate, door-check, and enqueue one request. Returns the
        ARRIVAL INDEX assigned to it — the request's PRNG identity
        (sampled draws key on it), which is what makes a completed
        request replayable bit-for-bit on any equal-config engine: the
        fleet router's SDC cross-check (docs/fleet.md) records it per
        accepted request."""
        n = len(request.prompt)
        if n == 0:
            raise ValueError(f"request {request.uid!r}: empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError(
                f"request {request.uid!r}: max_new_tokens must be >= 1 "
                f"(got {request.max_new_tokens}); prefill always samples "
                "the first token")
        if n + request.max_new_tokens > self.config.max_seq_len:
            raise ValueError(
                f"request {request.uid!r}: prompt + max_new_tokens "
                f"({n} + {request.max_new_tokens}) exceeds max_seq_len "
                f"({self.config.max_seq_len})")
        if request.deadline_s is not None and request.deadline_s <= 0:
            raise ValueError(
                f"request {request.uid!r}: deadline_s must be positive "
                f"(got {request.deadline_s})")
        if request.priority < 0:
            raise ValueError(
                f"request {request.uid!r}: priority must be >= 0 "
                f"(got {request.priority}); 0 is the most urgent class")
        if not isinstance(request.tenant, str) or not request.tenant:
            raise ValueError(
                f"request {request.uid!r}: tenant must be a non-empty "
                f"string (got {request.tenant!r})")
        request.sampling.validate()
        # a uid that is still waiting or resident would collide in the
        # uid-keyed deadline map and the engine-owned status field —
        # and a terminal-but-undrained uid would have its result in
        # finished/statuses silently CLOBBERED by the new lifecycle's
        # exit. Reject both loudly; a DRAINED uid starts a fresh
        # lifecycle as before.
        uid = request.uid
        if uid in self._live_uids:
            raise ValueError(
                f"request uid {uid!r} is already waiting or resident in "
                "this engine; drain it (run()) or pick a distinct uid")
        if uid in self.statuses:
            raise ValueError(
                f"request uid {uid!r} has a terminal result "
                f"({self.statuses[uid]!r}) awaiting drain; run() before "
                "reusing the uid, or pick a distinct one")
        # the engine owns the terminal-status field from here on (a
        # re-submitted request object starts a fresh lifecycle) —
        # cleared BEFORE the queue-full shed, so a door-shed request
        # reads status None, not a stale verdict from its previous
        # lifecycle (the documented "no status" contract)
        object.__setattr__(request, "status", None)
        self._tenant_seen.add(request.tenant)
        # tenant quotas first (the shed is the TENANT's own doing and
        # is charged to its ledger with a real terminal verdict —
        # docs/robustness.md, isolation), then the engine-wide bound
        reason = self._door_throttle_reason(request)
        if reason is not None:
            if self._obs is not None:
                self._obs.note_shed(uid, "throttled", queued=False)
            self.finished[uid] = []
            self._set_status(request, "throttled")
            self._num_throttled += 1
            raise TenantThrottledError(
                f"request {uid!r} throttled: tenant "
                f"{request.tenant!r} {reason}")
        # backpressure: the bounded queue is the overload contract —
        # callers get an explicit shed signal, not unbounded growth
        if (self.config.max_waiting is not None
                and len(self.waiting) >= self.config.max_waiting):
            self._num_rejected_queue_full += 1
            if self._obs is not None:
                # a door shed: the request never entered the engine
                # and gets NO terminal status, but the trace must
                # still show the refusal
                self._obs.note_shed(uid, "queue_full", queued=False)
            raise QueueFullError(
                f"request {uid!r} rejected: waiting queue is at "
                f"max_waiting ({self.config.max_waiting})")
        self._live_uids.add(uid)
        if request.deadline_s is not None:
            self._deadline[request.uid] = self._clock() + request.deadline_s
        enq_t = self._clock()
        arrival = self._arrival_count
        self.waiting.append(_QueueEntry(request=request,
                                        arrival=arrival,
                                        enq_t=enq_t,
                                        enq_tick=self._num_ticks))
        if self._obs is not None:
            # reuse the engine-read timestamp: observation adds no
            # clock call of its own here
            self._obs.note_enqueue(uid, tenant=request.tenant,
                                   priority=request.priority,
                                   prompt_len=n, t=enq_t)
        self._arrival_count += 1
        self._queue_depth_peak = max(self._queue_depth_peak,
                                     len(self.waiting))
        return arrival

    def try_add(self, request: Request) -> bool:
        """Non-raising backpressure variant of :meth:`add_request`:
        returns False when the bounded queue or the tenant's quota
        sheds the request (and counts it; a quota shed additionally
        leaves terminal status ``"throttled"``), True when enqueued.
        Validation errors — bad geometry, duplicate uid — still raise:
        those are caller bugs, not load."""
        try:
            self.add_request(request)
        except (QueueFullError, TenantThrottledError):
            return False
        return True

    # -- the tenant ledger (docs/robustness.md, isolation) -----------------

    def _tenant_quota(self, tenant: str) -> Optional[TenantQuota]:
        quotas = self.config.tenant_quotas
        return None if quotas is None else quotas.get(tenant)

    def _tenant_rate_now(self, tenant: str) -> float:
        """The tenant's token-rate estimate decayed to now (read-only:
        delivery updates happen in :meth:`_note_tenant_tokens`)."""
        r = self._tenant_rate.get(tenant, 0.0)
        if r == 0.0:
            return 0.0
        dt = max(0.0, self._clock() - self._tenant_rate_t[tenant])
        return r * math.exp(-dt / self.config.tenant_rate_tau_s)

    def _note_tenant_tokens(self, tenant: str, n: int) -> None:
        """Account ``n`` delivered tokens to the tenant: the running
        total, and the exponentially-decayed rate estimator the
        ``tokens_per_s`` quota reads (each token adds ``1/tau``, so a
        constant rate R settles the estimator at R)."""
        self._tenant_tokens[tenant] = \
            self._tenant_tokens.get(tenant, 0) + n
        now = self._clock()
        tau = self.config.tenant_rate_tau_s
        r = self._tenant_rate.get(tenant, 0.0)
        if r:
            dt = max(0.0, now - self._tenant_rate_t[tenant])
            r *= math.exp(-dt / tau)
        self._tenant_rate[tenant] = r + n / tau
        self._tenant_rate_t[tenant] = now

    def _door_throttle_reason(self, request: Request) -> Optional[str]:
        """The tenant-quota door check: the reason this submission is
        over quota, or None. Checked BEFORE the request touches the
        queue, the deadline map, or the pool — an over-quota request
        burns nothing."""
        q = self._tenant_quota(request.tenant)
        if q is None:
            return None
        if q.max_resident_blocks is not None:
            # worst-case charge in block_weight units (quantized
            # blocks charge their reduced footprint, so quantization
            # admits requests a full-precision pool would refuse)
            worst = self._block_weight * blocks_needed(
                len(request.prompt) + request.max_new_tokens,
                self.config.block_size)
            if worst > q.max_resident_blocks + 1e-9:
                return (f"needs up to {worst:g} block-units but is "
                        f"capped at max_resident_blocks="
                        f"{q.max_resident_blocks} (it could never run)")
        if (q.max_waiting is not None
                and self.waiting.tenant_depth(request.tenant)
                >= q.max_waiting):
            return (f"already holds {q.max_waiting} waiting entries "
                    f"(max_waiting)")
        if q.tokens_per_s is not None:
            rate = self._tenant_rate_now(request.tenant)
            if rate > q.tokens_per_s:
                return (f"is over its token-rate budget "
                        f"({rate:.1f} > {q.tokens_per_s} tokens/s)")
        return None

    def _tenant_has_resident(self, tenant: str) -> bool:
        return any(s is not None and s.request.tenant == tenant
                   for s in self.slots)

    # -- client lifecycle: cancellation + streaming (docs/serving.md) ------

    def abort(self, uid: str) -> bool:
        """Cancel a WAITING or RESIDENT request: every resource it
        holds is reclaimed now — queue entry removed (DRR walk state
        of the surviving tenants untouched), or its lane freed with
        blocks released via the usual deepest-first discipline — and
        it reaches terminal status ``"cancelled"`` carrying the tokens
        it already emitted. A disconnect callback maps straight onto
        this. Returns False for a uid the engine does not currently
        own (unknown, already terminal, or already drained).

        Safe against the in-flight decode dispatch: the pending drain
        matches lanes by the uid they held AT DISPATCH and discards
        results for an aborted (or re-filled) lane; any K/V the
        dispatch wrote into the freed blocks sits past every live
        sequence's position masks until the blocks' next owner
        overwrites it — the same argument that makes speculative
        rollback and trimmed reservations safe.
        ``check_allocator_integrity`` certifies the reclamation after
        chaos runs mixing aborts with faults and preemptions."""
        if uid not in self._live_uids:
            return False
        removed = self.waiting.expel(lambda e: e.request.uid == uid)
        if removed:
            entry = removed[0]
            self.finished[uid] = list(entry.generated)
            self._set_status(entry.request, "cancelled")
            self._num_cancelled += 1
            return True
        for i, slot in enumerate(self.slots):
            if slot is not None and slot.request.uid == uid:
                self._finish(i, status="cancelled")
                self._num_cancelled += 1
                return True
        return False    # unreachable while _live_uids is consistent

    def pop_stream_events(self) -> List[Tuple[str, int, bool]]:
        """Drain the streaming buffer: ``(uid, token, is_last)`` events
        in emission order, appended as tokens become host-visible (the
        prefill's first token at its fetch, decode tokens at the
        deferred drain) — callers consume tokens as they stream
        instead of waiting on terminal ``run()`` results. Every
        terminal transition — finish, timeout, failure, rejection,
        throttle, cancellation — appends a ``(uid, -1, True)``
        sentinel (the device's -1 "no token" convention), so a
        consumer learns each request's end exactly once; queue-full
        door sheds never entered the engine and emit nothing. The
        buffer grows until popped — a streaming caller should drain it
        every few ticks."""
        out = list(self._stream)
        self._stream.clear()
        return out

    def _request_key(self, entry: _QueueEntry):
        """The request's own PRNG key: engine seed x arrival order.
        Token ``j`` of the request is drawn with ``fold_in(key, j)`` —
        never from a step counter — so draws are invariant to lane
        placement, batch composition, ``decode_steps``, and
        preemption/resume (the re-queued entry keeps its arrival)."""
        return jax.random.fold_in(self._key, entry.arrival)

    def _lane_shard(self, lane: int) -> int:
        """The batch-axis shard owning a lane (contiguous lane groups:
        ``lane // lanes_per_shard``). Always 0 at ``B == 1``."""
        return lane // self._lanes_per_shard

    def _admit_lane_order(self):
        """The free-lane scan order of ``_admit``: plain index order
        unsharded (bit-identical to the pre-batch-axis engine); at
        ``B > 1``, round-robin ACROSS shards (lane 0 of every shard,
        then lane 1, ...) so admissions spread residents — and pool
        pressure — evenly over the data-parallel shards instead of
        filling shard 0 first."""
        if self._batch_shards == 1:
            return range(self.config.max_batch)
        return (s * self._lanes_per_shard + l
                for l in range(self._lanes_per_shard)
                for s in range(self._batch_shards))

    def _invalidate_lanes(self) -> None:
        """Slot composition changed (admit/start/finish/preempt): both
        the decode table and the per-lane arrays must rebuild."""
        self._dev_lanes.invalidate()
        self._dev_tables.invalidate()

    def _invalidate_tables(self) -> None:
        """A lane's block list changed (growth/CoW): same lanes, new
        table rows."""
        self._dev_tables.invalidate()

    def _host_tables(self, decode_only: bool = False) -> np.ndarray:
        """[max_batch, max_blocks_per_seq] host tables (-1 = unmapped).
        ``decode_only`` leaves still-prefilling lanes unmapped so the
        decode step's stray write at position 0 drops out of bounds
        instead of corrupting their first block."""
        t = np.full((self.config.max_batch, self.max_blocks_per_seq), -1,
                    np.int32)
        for i, slot in enumerate(self.slots):
            if slot is None or (decode_only and not slot.started):
                continue
            t[i, : len(slot.blocks)] = slot.blocks
        return t

    def _sampling_arrays(self, per_slot):
        temp = np.zeros(len(per_slot), np.float32)
        top_k = np.zeros(len(per_slot), np.int32)
        top_p = np.ones(len(per_slot), np.float32)
        for i, sp in enumerate(per_slot):
            if sp is not None:
                temp[i], top_k[i], top_p[i] = (sp.temperature, sp.top_k,
                                               sp.top_p)
        return (jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p))

    def _build_decode_tables(self):
        self._table_rebuilds += 1
        return device_block_table(self._host_tables(decode_only=True),
                                  self.config.num_blocks)

    def _build_lane_meta(self):
        """The slot-composition-keyed decode inputs: sampling knobs,
        EOS ids (-1 = none), and per-request PRNG keys, one row per
        lane (zeros/-1 for lanes that are empty or still prefilling —
        their draws are masked to the sentinel on-device)."""
        B = self.config.max_batch
        temp, top_k, top_p = self._sampling_arrays(
            [s.request.sampling if s is not None and s.started else None
             for s in self.slots])
        eos = np.full(B, -1, np.int32)
        arrivals = np.zeros(B, np.int32)
        for i, s in enumerate(self.slots):
            if s is None or not s.started:
                continue
            if s.request.eos_token_id is not None:
                eos[i] = s.request.eos_token_id
            arrivals[i] = s.entry.arrival
        keys = jax.vmap(lambda a: jax.random.fold_in(self._key, a))(
            jnp.asarray(arrivals))
        return temp, top_k, top_p, jnp.asarray(eos), keys

    def _set_status(self, request: Request, status: str,
                    lane: Optional[int] = None) -> None:
        """Record a terminal status: in the drain-able ``statuses`` map,
        on the request object itself, out of the deadline watch and
        the live-uid set, into the tenant's status tally, and onto the
        stream as the ``(uid, -1, True)`` terminal sentinel (every
        terminal transition funnels through here — the uid is
        re-usable from this point, and stream consumers learn
        terminality exactly once). ``lane`` is the slot the request
        exited from (None for queue-side exits) — trace-only context:
        the terminal event closes the lane's residency span."""
        self.statuses[request.uid] = status
        object.__setattr__(request, "status", status)
        self._deadline.pop(request.uid, None)
        self._live_uids.discard(request.uid)
        tally = self._tenant_status.setdefault(request.tenant, {})
        tally[status] = tally.get(status, 0) + 1
        self._stream.append((request.uid, -1, True))
        if self._obs is not None:
            self._obs.note_terminal(request.uid, status, lane=lane)
        self._prune_tenant_if_idle(request.tenant)

    def _tenant_is_listed(self, tenant: str) -> bool:
        """Tenants named in the config (weights or quotas) plus the
        default tenant keep permanent ledger rows."""
        return (tenant == DEFAULT_TENANT
                or tenant in (self.config.tenant_weights or {})
                or tenant in (self.config.tenant_quotas or {}))

    def _prune_tenant_if_idle(self, tenant: str) -> None:
        """Drop an UNLISTED tenant's ledger state once it has no
        waiting or resident footprint: ``tenant`` is a free-form
        client string, and a hostile (or buggy) client minting a fresh
        id per request would otherwise grow five per-tenant maps — and
        every snapshot and ``stats()`` call — without bound, in the
        engine whose whole point is surviving hostile tenants (the
        same hygiene the waiting queue applies to dead priority
        classes). The cost: an ephemeral tenant's token/status tallies
        and rate estimator reset once it drains — list a tenant in
        ``tenant_weights``/``tenant_quotas`` to make its row (and its
        rate budget) permanent. Allocator-side attribution (cached
        blocks, evictions) is untouched and still surfaces its row in
        ``stats()["tenants"]`` while any footprint remains."""
        if self._tenant_is_listed(tenant):
            return
        if (self.waiting.tenant_depth(tenant)
                or self._tenant_has_resident(tenant)):
            return
        self._tenant_seen.discard(tenant)
        self._tenant_tokens.pop(tenant, None)
        self._tenant_rate.pop(tenant, None)
        self._tenant_rate_t.pop(tenant, None)
        self._tenant_status.pop(tenant, None)
        self._tenant_preemptions.pop(tenant, None)

    def _yield_key(self, idx: int):
        """Victim-selection order for preemption and decode quarantine-
        by-elimination: the LOWEST priority class first (largest class
        value), then the youngest (largest ``admit_seq``) — ``max()``
        over this key picks the victim, so a victim's class is always
        >= every survivor's. Uniform-priority traffic reduces exactly
        to the pre-priority youngest-first rule."""
        slot = self.slots[idx]
        return (slot.request.priority, slot.admit_seq, idx)

    @staticmethod
    def _resume_tokens(slot: "_Slot") -> List[int]:
        """The tokens a slot's request carries out of residency — into
        ``finished``, a requeue entry, or a snapshot record. A started
        slot owns its live ``generated`` list; one still mid-prefill
        never resampled, so its history is the queue entry's."""
        return (list(slot.generated) if slot.started
                else list(slot.entry.generated))

    def _finish(self, idx: int, status: str = "finished") -> None:
        """Release the slot: refs drop, and with prefix caching on the
        registered blocks stay cached (evictable) rather than freed.
        Released DEEPEST-first: eviction pops the oldest insertion, and
        evicting a chain's head block orphans every descendant (the
        lookup misses at hash 0), so the tail must age out before the
        head for partial chains to stay matchable. ``status`` is the
        terminal outcome ("finished", or "timeout" for a deadline
        expiry mid-generation — the tokens emitted so far are kept)."""
        slot = self.slots[idx]
        self.allocator.free(list(reversed(slot.blocks)),
                            tenant=slot.request.tenant)
        self.finished[slot.request.uid] = self._resume_tokens(slot)
        # clear the lane BEFORE the terminal transition: _set_status's
        # idle-tenant pruning must not see the finishing slot as a
        # live resident
        self.slots[idx] = None
        self._set_status(slot.request, status, lane=idx)
        self._invalidate_lanes()

    def _quarantine_slot(self, idx: int) -> None:
        """Terminal-fail one lane's request after its dispatches
        exhausted every retry: same release path as a normal finish,
        status ``"failed"``, tokens already emitted kept. The engine —
        and every other lane — keeps serving. With a recorder attached
        the quarantine freezes the current event tail as an incident —
        the poisoned dispatch's post-mortem outlives the ring."""
        uid = self.slots[idx].request.uid
        self._finish(idx, status="failed")
        self._num_quarantines += 1
        if self._obs is not None:
            self._obs.record("quarantine", uid=uid, lane=idx)
            self._obs.incident("quarantine", uid=uid)

    def _expire_deadlines(self, include_started: bool) -> int:
        """Finish every request past its deadline with status
        ``"timeout"`` — gracefully: tokens already emitted ride into
        ``finished``. Waiting entries and mid-prefill (unstarted)
        slots expire any time — an in-flight decode only covers
        STARTED lanes; started slots only when no decode dispatch is
        in flight over them (``include_started`` — callers pass True
        only after the drain), because finishing a lane the pending
        fetch still covers would corrupt the drain bookkeeping."""
        if not self._deadline:
            return 0
        now = self._clock()
        # O(#deadlines) pre-check: only rebuild the queue's deques when
        # something actually expired (the common tick touches nothing)
        due = {uid for uid, dl in self._deadline.items() if now >= dl}
        if not due:
            return 0
        expired = 0
        if self.waiting:
            for entry in self.waiting.expel(
                    lambda e: e.request.uid in due):
                self.finished[entry.request.uid] = list(entry.generated)
                self._set_status(entry.request, "timeout")
                self._num_timeouts += 1
                expired += 1
        for i, slot in enumerate(self.slots):
            if slot is None or (slot.started and not include_started):
                continue
            if slot.request.uid in due:
                self._finish(i, status="timeout")
                self._num_timeouts += 1
                expired += 1
        return expired

    def _reset_device_state(self) -> None:
        """The in-process analog of a crash restore: requeue every
        resident request (preemption-style, carrying its emitted
        tokens, oldest at the head), wipe the allocator — refcounts,
        prefix index, LRU set — and zero the pool. Everything
        device-resident re-derives from host state through re-prefill,
        bit-identically (the resume-determinism contract). Used when a
        failed decode drain may have poisoned the pool; also the
        reason fetch-failure recovery needs no rollback copy."""
        live = sorted(((s.admit_seq, i)
                       for i, s in enumerate(self.slots)
                       if s is not None), reverse=True)
        if self._obs is not None:
            self._obs.record("device_reset", residents=len(live),
                             fetch_failures=self._fetch_failures)
            self._obs.incident("device_reset")
        for _, i in live:    # youngest first, so the oldest lands at head
            slot = self.slots[i]
            requeue_t = self._clock()
            self.waiting.appendleft(_QueueEntry(
                request=slot.request, arrival=slot.entry.arrival,
                generated=self._resume_tokens(slot),
                enq_t=requeue_t, enq_tick=self._num_ticks,
                drr_charged=True))
            self.slots[i] = None
            if self._obs is not None:
                self._obs.note_preempt(slot.request.uid, i,
                                       reason="device_reset", t=requeue_t)
                self._obs.note_enqueue(slot.request.uid,
                                       tenant=slot.request.tenant,
                                       priority=slot.request.priority,
                                       requeue=True, t=requeue_t)
        # requeues are the one path that pushes the queue past
        # max_waiting (by at most max_batch) — the exact overshoot the
        # peak metric exists to expose, sampled here before admission
        # can re-absorb it
        self._queue_depth_peak = max(self._queue_depth_peak,
                                     len(self.waiting))
        self.allocator.reset()
        self.cache = jax.tree.map(jnp.zeros_like, self.cache)
        self._draft_plan = {}   # its lanes no longer exist
        self._invalidate_lanes()

    def _guarded_dispatch(self, site: str, fn, *args):
        """One jitted dispatch (including its fetch, when the caller
        folds it into ``fn``) under the shared retry policy
        (:func:`apex_tpu.utils.faults.guarded_call`): transient
        failures — injected, or the runtime's real dispatch errors —
        retry ``max_dispatch_retries`` times with exponential backoff;
        exhaustion raises :class:`DispatchFailedError` for the caller
        to quarantine the offending request. Retry is sound because
        ``donate_cache`` defaults off: a failed attempt's inputs are
        intact (with donation the pool may be consumed; recover via
        snapshot/restore instead)."""

        def count(attempt):
            self._num_dispatch_retries += 1
            if self._obs is not None:
                self._obs.record("fault_retry", site=site,
                                 attempt=attempt)

        out, _ = guarded_call(
            fn, *args, plan=self.faults, site=site,
            retries=self.config.max_dispatch_retries,
            backoff_s=self.config.retry_backoff_s, on_retry=count)
        return out

    @staticmethod
    def _ewma_update(prev: Optional[float], dt: float) -> float:
        """The feasibility gate's service-time estimator: first
        observation seeds it, later ones blend at ``_EWMA_ALPHA``."""
        dt = max(0.0, float(dt))
        return dt if prev is None else (1.0 - _EWMA_ALPHA) * prev \
            + _EWMA_ALPHA * dt

    def _record_token(self, idx: int, token: int,
                      t_vis: Optional[float] = None) -> None:
        """Append a sampled token to a slot, finishing on EOS/max-len.
        The single funnel for FRESH tokens (resumed histories bypass
        it), so it also feeds the stream-event buffer and the tenant's
        delivered-token ledger exactly once per token. ``t_vis`` is
        the host-visibility timestamp the caller already read (prefill
        fetch end / drain fetch end) — the observer reuses it instead
        of reading the clock again."""
        slot = self.slots[idx]
        slot.generated.append(token)
        slot.last_token = token
        req = slot.request
        self._stream.append((req.uid, int(token), False))
        if self._obs is not None:
            self._obs.note_token(req.uid, t=t_vis)
        self._note_tenant_tokens(req.tenant, 1)
        if ((req.eos_token_id is not None and token == req.eos_token_id)
                or len(slot.generated) >= req.max_new_tokens):
            self._finish(idx)

    # -- prefix caching ----------------------------------------------------

    def _seq_hashes(self, tokens: Sequence[int]) -> List[str]:
        return seq_block_hashes(tokens, self.config.block_size)

    def _register_full_blocks(self, slot: _Slot) -> None:
        """Index every newly-FULL block of the slot (prompt blocks as
        chunks land, generated blocks as decode crosses boundaries)."""
        if not self.config.enable_prefix_caching:
            return
        bs = self.config.block_size
        n_full = slot.context_len // bs
        while slot.num_registered < n_full:
            j = slot.num_registered
            if j >= len(slot.block_hashes):
                prev = slot.block_hashes[j - 1] if j else None
                slot.block_hashes.append(hash_block_tokens(
                    prev, slot.tokens[j * bs: (j + 1) * bs]))
            self.allocator.register_prefix(slot.block_hashes[j],
                                           slot.blocks[j],
                                           tenant=slot.request.tenant)
            slot.num_registered += 1

    # -- data integrity (docs/robustness.md, "Data integrity") -------------

    def _corrupt_payload_hook(self, site: str, payload):
        """The spill store's chaos seam: fire the fault plan at the
        store's read/write site and, on a ``"corrupt"`` hit, hand back
        a seeded-deterministically perturbed copy — the bit flip the
        checksums exist to catch. Identity (and zero extra RNG draws)
        when no corrupt spec matches."""
        self.faults.fire(site)
        seed = self.faults.corrupt_seed(site)
        if seed is None:
            return payload
        return perturb_payload(payload, seed)

    def _maybe_corrupt_record(self, site: str, rec: Dict) -> Dict:
        """Fire the fault plan at a record-artifact site (checkpoint /
        export / import) and perturb the record on a corrupt hit —
        AFTER sealing, so the stale checksum is exactly what detection
        sees. No-op without a plan."""
        if self.faults is None:
            return rec
        self.faults.fire(site)
        seed = self.faults.corrupt_seed(site)
        if seed is None:
            return rec
        return perturb_json(rec, seed)

    def _note_corruption(self, site: str, detail: str) -> None:
        """Count one detected corruption and surface it to the flight
        recorder — EVERY detection path funnels through here, so
        ``num_corruptions_detected`` is the one number the chaos certs
        (and an operator) compare against injected faults."""
        self._num_corruptions_detected += 1
        if self._obs is not None:
            self._obs.record("corruption_detected", site=site,
                             detail=str(detail))

    def _maybe_scrub(self) -> None:
        """The budgeted background integrity pass
        (``scrub_interval_ticks``): re-verify ``scrub_spill_blocks``
        spill entries round-robin and audit the allocator/ledger
        invariants exactly. A corrupt spill entry is discarded (a
        future admission recomputes — the tier's normal miss path); a
        violated allocator invariant RAISES, because a corrupt ledger
        has no safe degradation — the process (or the fleet's failover)
        owns that recovery."""
        interval = self.config.scrub_interval_ticks
        if interval is None or self._num_ticks % interval:
            return
        self._num_scrubs += 1
        verified = corrupt = 0
        if self.spill is not None:
            verified, corrupt = self.spill.scrub(
                self.config.scrub_spill_blocks)
            self._num_scrub_blocks_verified += verified
        self.check_allocator_integrity()
        if self._obs is not None:
            self._obs.record("scrub", verified=int(verified),
                             corrupt=int(corrupt))

    # -- the host-RAM spill tier (docs/serving.md memory tiers) ------------

    def _spill_payload(self, block_id: int, record: bool = True):
        """The allocator's spill fetch: one block's device contents as
        host numpy arrays (scales included for quantized pools), or
        None when the device read fails — the spill is an
        optimization, so a transient fetch error (e.g. a poisoned
        in-flight dispatch surfacing at this sync) just skips it; the
        eviction proceeds as a plain discard and the next prefix miss
        recomputes. Never called from ``_reset_device_state``'s
        allocator reset (reset clears without evicting), so a known-
        poisoned pool is never captured into the host tier.
        ``record=False`` suppresses the recorder's ``spill`` event —
        :meth:`export_prefix_payloads` reads blocks for migration
        transport, which is not an eviction."""
        try:
            payload = {"k": np.asarray(self.cache.k[:, block_id]),
                       "v": np.asarray(self.cache.v[:, block_id])}
            if self.cache.k_scale is not None:
                payload["k_scale"] = np.asarray(
                    self.cache.k_scale[:, block_id])
                payload["v_scale"] = np.asarray(
                    self.cache.v_scale[:, block_id])
        except SimulatedCrash:
            raise
        except Exception:
            return None
        if record and self._obs is not None:
            self._obs.record(
                "spill", block=int(block_id),
                bytes=int(sum(a.nbytes for a in payload.values())))
        return payload

    def _upload_args(self, up_blocks, payloads):
        """Fixed-shape inputs for the ONE upload dispatch an admission
        pays regardless of how many blocks it re-admits: ids padded to
        ``[max_blocks_per_seq]`` with the out-of-bounds id (the
        scatter's ``mode="drop"`` discards padding rows), payloads
        zero-padded to match — one compiled program, one full-pool
        functional update per admission instead of one per block."""
        M = self.max_blocks_per_seq
        ids = np.full(M, self.config.num_blocks, np.int32)
        ids[:len(up_blocks)] = up_blocks

        def stack(key):
            proto = payloads[0][key]
            buf = np.zeros((M,) + proto.shape, proto.dtype)
            for i, p in enumerate(payloads):
                buf[i] = p[key]
            return jnp.asarray(buf)

        args = [jnp.asarray(ids), stack("k"), stack("v")]
        if self.cache.k_scale is not None:
            args += [stack("k_scale"), stack("v_scale")]
        return args

    def _upload_impl(self, cache, ids, k_blk, v_blk, *scales):
        """An admission's spilled blocks re-admitted in ONE scatter:
        ``ids`` is ``[max_blocks_per_seq]`` int32 (out-of-bounds
        padding dropped), payloads ``[M, L, bs, H, D]`` (+ scales for
        quantized pools) — the device half of a spill hit. The
        uploaded bytes are exactly the bytes each block held when it
        was spilled, so a re-admitted prefix attends bit-identically
        to the never-evicted one (and, on the fp path, to recompute)."""
        ids = jnp.asarray(ids, jnp.int32)
        out = KVCache(
            k=cache.k.at[:, ids].set(jnp.moveaxis(k_blk, 0, 1),
                                     mode="drop"),
            v=cache.v.at[:, ids].set(jnp.moveaxis(v_blk, 0, 1),
                                     mode="drop"))
        if scales:
            ks, vs = scales
            out = out._replace(
                k_scale=cache.k_scale.at[:, ids].set(
                    jnp.moveaxis(ks, 0, 1), mode="drop"),
                v_scale=cache.v_scale.at[:, ids].set(
                    jnp.moveaxis(vs, 0, 1), mode="drop"))
        return out

    # -- admission (optimistic: current need, not worst case) --------------

    def _admission_priority_limit(self) -> Optional[int]:
        """The ladder's admission pause (rung 3): classes >=
        ``degrade_admit_priority`` are held in the queue while the
        engine sheds load — UNLESS nothing more urgent exists anywhere
        (no resident lane, no admissible higher-class entry): an
        otherwise-idle engine serves whatever it has (work
        conservation; without it, a queue holding only paused classes
        would deadlock against the stall guard)."""
        if self._degradation_level < 3:
            return None
        limit = self.config.degrade_admit_priority
        if (any(s is not None for s in self.slots)
                or self.waiting.has_priority_below(limit)):
            return limit
        return None

    def _estimate_service_s(self, prompt_tail: int, remaining: int,
                            skips_prefill: bool = False) -> Optional[float]:
        """Contention-free service-time estimate for the feasibility
        gate: uncached-prompt chunks at the prefill EWMA plus
        ``ceil(remaining / K)`` decode dispatches at the decode EWMA —
        a LOWER bound on serving the request's FULL ``max_new_tokens``
        budget (it assumes an idle engine), so the gate only sheds
        requests whose committed demand could not meet the deadline
        even alone. The budget is the demand the gate prices: a
        request counting on an early EOS to beat its deadline should
        ask for fewer tokens (the engine cannot know where EOS falls).
        None (gate open) until at least one dispatch was observed.
        A zero tail still costs one chunk — a fresh fully-cached prompt
        runs one write-suppressed pass for its logits — EXCEPT when the
        caller knows the entry skips prefill entirely (a resumed entry
        whose whole history is cached goes straight to decode)."""
        pf, dc = self._ewma_prefill_s, self._ewma_decode_s
        if pf is None and dc is None:
            return None
        if skips_prefill and prompt_tail <= 0:
            chunks = 0
        else:
            chunks = max(1, -(-max(prompt_tail, 0) // self._chunk))
        # speculating, a dispatch GUARANTEES only one token (every
        # proposal may be rejected) — the conservative per-dispatch
        # floor, like the scan's K
        per = 1 if self.config.spec_tokens > 0 else self.config.decode_steps
        dispatches = -(-max(remaining, 0) // per)
        return chunks * (pf or 0.0) + dispatches * (dc or 0.0)

    def _shed_if_infeasible(self, entry: _QueueEntry,
                            uncached_tail: int,
                            below: Optional[int],
                            skip=None) -> bool:
        """The admit-time feasibility gate: a deadline that cannot
        cover even the contention-free service estimate is shed NOW,
        with status ``"rejected"`` — before the request burns pool
        blocks and prefill compute it is guaranteed to time out of.
        Tokens a preempted entry already carries are preserved."""
        req = entry.request
        dl = self._deadline.get(req.uid)
        if dl is None:
            return False
        remaining = req.max_new_tokens - len(entry.generated)
        if not entry.generated:
            # fresh entry: the FINAL prefill chunk emits the first
            # generated token (_record_token in the prefill tick), so
            # decode owes one fewer — a resumed entry's re-prefill
            # emits nothing new (its tokens ride the queue entry)
            remaining -= 1
        est = self._estimate_service_s(
            uncached_tail, remaining,
            # a resumed entry whose whole history is cached skips
            # prefill entirely (_admit starts it decoding directly)
            skips_prefill=bool(entry.generated) and uncached_tail <= 0)
        if est is None or self._clock() + est <= dl:
            return False
        if self._obs is not None:
            self._obs.note_shed(req.uid, "rejected", queued=True)
        self.waiting.popleft(below=below, skip=skip)  # exactly this entry
        self.finished[req.uid] = list(entry.generated)
        self._set_status(req, "rejected")
        self._num_rejected_infeasible += 1
        return True

    def _note_admitted_wait(self, entry: _QueueEntry):
        wait_ticks = self._num_ticks - entry.enq_tick
        now = self._clock()
        wait_s = max(0.0, now - entry.enq_t)
        self._queue_wait_count += 1
        self._queue_wait_ticks_sum += wait_ticks
        self._queue_wait_ticks_max = max(self._queue_wait_ticks_max,
                                         wait_ticks)
        self._queue_wait_s_sum += wait_s
        self._queue_wait_s_max = max(self._queue_wait_s_max, wait_s)
        return wait_s, now

    def _admit(self) -> int:
        """Move waiting requests into free lanes while the pool can
        cover their CURRENT need — the uncached prompt-tail blocks plus
        one (vs. the old worst-case reservation of the full generation
        budget, which collapsed pool utilization under long
        ``max_new_tokens``; over-commit is safe now that decode-time
        exhaustion preempts instead of aborting). Prefix caching makes
        the need smaller still: the longest cached block-aligned prefix
        is shared by reference, and only the tail is prefilled.

        Candidates are considered class by class, weighted-DRR across
        tenants within a class (:class:`_WaitingQueue`); an
        infeasible-deadline head is shed by the gate and the next
        candidate considered; a head whose TENANT is over its
        resident-block quota is held back (the tenant joins this
        pass's ``skip`` set — other tenants flow past it, so one
        tenant's quota never blocks another's admission), while a head
        that merely does not FIT the pool blocks everything behind it
        (head-of-line blocking — no starvation WITHIN a (class,
        tenant) lane; across classes the strict priority order is the
        design: sustained higher-class load starves lower classes,
        bounded only by their deadlines)."""
        bs = self.config.block_size
        admitted = 0
        below = self._admission_priority_limit()
        skip: set = set()
        for idx in self._admit_lane_order():
            if self.slots[idx] is not None:
                continue
            # at B > 1 every allocation/match of this lane is scoped to
            # its shard's pool range (the shard-residency invariant the
            # sharded programs rely on); None = the whole pool,
            # bit-identical to the pre-batch-axis engine
            shard = (self._lane_shard(idx) if self._batch_shards > 1
                     else None)
            while True:
                entry = self.waiting.head(below=below, skip=skip)
                if entry is None:
                    return admitted
                seq = list(entry.request.prompt)
                if entry.generated:
                    seq += entry.generated[:-1]   # resume: re-cache history
                L = len(seq)
                matched: List[int] = []
                hashes: List[str] = []
                if self.config.enable_prefix_caching:
                    if entry.hashes is None:
                        entry.hashes = self._seq_hashes(seq)
                    hashes = entry.hashes
                    matched = self.allocator.lookup_prefix(hashes,
                                                           shard=shard)
                # the spill tier extends the device match: the run of
                # chain hashes CONTINUING the device prefix that the
                # host store still holds re-admits by upload instead
                # of recompute (chain order matters — a spilled block
                # past a gap is unreachable, exactly like the device
                # index)
                spill_run: List[str] = []
                if self.spill is not None:
                    j = len(matched)
                    while j < len(hashes) and hashes[j] in self.spill:
                        spill_run.append(hashes[j])
                        j += 1
                n_up = len(spill_run)
                m_tok = (len(matched) + n_up) * bs
                if self._shed_if_infeasible(entry, L - m_tok, below, skip):
                    continue    # gate shed the head; try the next one
                tail = blocks_needed(L, bs) - len(matched) - n_up
                # current need = blocks through the FIRST decode write
                # (position L): blocks_needed(L + 1). That is tail + 1
                # only when the prompt exactly fills its blocks — an
                # exact-fit request whose whole generation lives in the
                # last partial block needs no headroom at all.
                # Upload blocks are fresh allocations, so they count.
                need = blocks_needed(L + 1, bs) - len(matched)
                # per-tenant block quota: would this admission push the
                # tenant's fractional resident charge over its cap?
                # (new private blocks charge 1 each; acquiring a
                # matched block adds a 1/(refs + 1) share)
                tenant = entry.request.tenant
                q = self._tenant_quota(tenant)
                if q is not None and q.max_resident_blocks is not None:
                    # charges are in block_weight units (quantized
                    # blocks charge their reduced footprint)
                    extra = self._block_weight * (need + sum(
                        1.0 / (self.allocator.refcount(b) + 1)
                        for b in matched))
                    if (self.allocator.tenant_charge(tenant) + extra
                            > q.max_resident_blocks + 1e-9):
                        if not self._tenant_has_resident(tenant):
                            # nothing of this tenant's will ever free a
                            # block — shed instead of wedging its lane
                            # (unreachable for door-validated requests,
                            # kept as the no-deadlock backstop)
                            if self._obs is not None:
                                self._obs.note_shed(entry.request.uid,
                                                    "throttled",
                                                    queued=True)
                            self.waiting.popleft(below=below, skip=skip)
                            self.finished[entry.request.uid] = \
                                list(entry.generated)
                            self._set_status(entry.request, "throttled")
                            self._num_throttled += 1
                            continue
                        # hold the TENANT, not the queue: its own lanes
                        # must drain first; other tenants flow past
                        skip.add(tenant)
                        continue
                # matched blocks that are currently cached (refcount 0)
                # stop being evictable once we take them, so they don't
                # count toward the capacity the tail can draw from
                reviving = sum(1 for b in matched
                               if self.allocator.refcount(b) == 0)
                if shard is None:
                    capacity = (self.allocator.num_free
                                + self.allocator.num_cached)
                else:
                    capacity = (self.allocator.free_in_shard(shard)
                                + self.allocator.cached_in_shard(shard))
                if need > capacity - reviving:
                    if shard is not None:
                        # this SHARD cannot fit the head; another
                        # shard's free lane may — head-of-line blocking
                        # is per shard at B > 1
                        break
                    # head-of-line blocking: don't let a small request
                    # starve the head
                    return admitted
                self.allocator.acquire(matched, tenant=tenant)
                self.waiting.popleft(below=below, skip=skip)
                wait_s, admit_t = self._note_admitted_wait(entry)
                if self._obs is not None:
                    self._obs.note_admit(entry.request.uid, idx, wait_s,
                                         cached_blocks=len(matched),
                                         t=admit_t)
                # spill hits re-admit by upload: fresh device blocks,
                # the host payloads scattered in by ONE fixed-shape
                # dispatch, the chain hashes registered — the slot
                # owns them exactly like matched blocks, and the
                # positions they cover never re-prefill. Payloads are
                # popped BEFORE the alloc: alloc may itself evict
                # cached blocks INTO the spill store, and the store's
                # byte-bound LRU could then drop exactly the entries
                # this admission probed (the probe does not refresh
                # recency) — popping first makes that race impossible.
                up_blocks: List[int] = []
                if spill_run:
                    # pop one entry at a time, stopping at the first
                    # miss — which includes a CHECKSUM MISMATCH (the
                    # store discards the rotten entry, counts it, and
                    # returns None): entries past a miss are
                    # unreachable exactly like the device index, and
                    # the positions the lost entries would have
                    # covered fall back to recompute (spill is an
                    # optimization, never a correctness dependency)
                    payloads = []
                    ok_run: List[str] = []
                    for h in spill_run:
                        p = self.spill.pop(h)
                        if p is None:
                            break
                        ok_run.append(h)
                        payloads.append(p)
                    if len(ok_run) < n_up:
                        # re-plan: the blocks the lost entries would
                        # have uploaded are recomputed instead. Total
                        # fresh allocations are unchanged (need priced
                        # uploads and tail alike), so the capacity and
                        # quota checks above still hold exactly.
                        tail += n_up - len(ok_run)
                        spill_run, n_up = ok_run, len(ok_run)
                        m_tok = (len(matched) + n_up) * bs
                if spill_run:
                    up_blocks = self.allocator.alloc(n_up, tenant=tenant,
                                                     shard=shard)
                    self.cache = self._upload(
                        self.cache,
                        *self._upload_args(up_blocks, payloads))
                    for h, nb in zip(spill_run, up_blocks):
                        self.allocator.register_prefix(h, nb,
                                                       tenant=tenant)
                    self._spill_hits += n_up
                    if self._obs is not None:
                        self._obs.record("spill_upload",
                                         uid=entry.request.uid,
                                         blocks=n_up)
                if self.spill is not None:
                    # per-BLOCK misses, the same unit as the hits (one
                    # per re-admitted block), so spill_hit_rate is the
                    # fraction of spill-eligible blocks the tier
                    # served; counted only at a committed admission
                    # (not per blocked-head re-peek, which would
                    # inflate the denominator)
                    self._spill_misses += (len(hashes) - len(matched)
                                           - n_up)
                blocks = matched + up_blocks \
                    + (self.allocator.alloc(tail, tenant=tenant,
                                            shard=shard)
                       if tail else [])
                self._prefix_lookup_blocks += len(hashes)
                self._prefix_hit_blocks += len(matched)
                self._prompt_blocks_allocated += tail
                self._admit_count += 1
                slot = _Slot(entry=entry, admit_seq=self._admit_count,
                             tokens=seq, prefill_len=L, prefill_pos=m_tok,
                             context_len=m_tok, blocks=blocks,
                             block_hashes=list(hashes),
                             num_registered=len(matched) + n_up,
                             generated=[], last_token=0, started=False)
                if entry.generated and m_tok == L:
                    # resumed and fully cached: nothing to recompute
                    slot.generated = list(entry.generated)
                    slot.last_token = slot.generated[-1]
                    slot.started = True
                self.slots[idx] = slot
                self._invalidate_lanes()
                admitted += 1
                break
        return admitted

    # -- chunked prefill ---------------------------------------------------

    def _prefill_tick(self) -> bool:
        """Run ONE ``[1, prefill_chunk]`` piece for the oldest admitted
        request still mid-prompt — at most one chunk per step, ahead of
        the decode dispatch, so long prompts load without stalling the
        streaming slots. A fully-prefix-cached prompt still runs one
        final pass with writes suppressed (``write_start == L``): the
        last position's logits are recomputed from the shared blocks
        without allocating or touching a single one."""
        cand = [(s.admit_seq, i) for i, s in enumerate(self.slots)
                if s is not None and not s.started]
        if not cand:
            return False
        idx = min(cand)[1]
        slot = self.slots[idx]
        L, C = slot.prefill_len, self._chunk
        if slot.prefill_pos < L:
            start = slot.prefill_pos
        else:                       # fully cached: logits-only pass
            start = max(0, L - C)
        end = min(start + C, L)
        ids = np.zeros((1, C), np.int32)
        ids[0, : end - start] = slot.tokens[start:end]
        positions = (start + np.arange(C, dtype=np.int32))[None]
        table = np.full((1, self.max_blocks_per_seq), -1, np.int32)
        table[0, : len(slot.blocks)] = slot.blocks
        temp, top_k, top_p = self._sampling_arrays([slot.request.sampling])

        # the EWMA times the attempt BODY only (set by the successful
        # attempt): retry backoff sleeps are failure handling, not
        # service time, and folding them in would inflate the
        # feasibility gate's contention-free lower bound into
        # over-shedding after one transient fault
        attempt_s = [0.0, 0.0]   # [dt, t0] of the successful attempt

        def attempt():
            # dispatch AND fetch inside the retry unit — EVERY chunk,
            # deliberately paying one host sync per chunk: prefill's
            # only device output is one token, and async dispatch
            # surfaces real runtime failures at the fetch — `self.cache`
            # is untouched until the whole attempt succeeds, so a retry
            # reruns the identical program (no rollback needed; under
            # donate_cache a failed attempt consumed the pool and the
            # retry's deleted-buffer error propagates as non-transient).
            # A launch-only guard on intermediate chunks would defer an
            # async failure into a LATER dispatch that shares the (now
            # poisoned) cache — decode over other lanes, or the next
            # chunk — quarantining innocent requests or cascading into
            # the drain-failure reset; the per-chunk sync is the price
            # of exact fault isolation, amortized over C tokens of
            # forward compute
            t0 = self._clock()
            cache, tok = self._prefill(
                self.params, self.cache, jnp.asarray(ids),
                jnp.asarray(positions),
                jnp.asarray([end], jnp.int32),
                jnp.asarray([slot.prefill_pos], jnp.int32),   # write_start
                jnp.asarray([(L - 1) - start], jnp.int32),    # sample_idx
                device_block_table(table, self.config.num_blocks),
                self._request_key(slot.entry), temp, top_k, top_p)
            # the owning shard's sampled token (index 0 == the whole
            # program's single token at B == 1; at B > 1 the sharded
            # prefill returns one candidate per shard and only the
            # lane's shard attended over real K/V)
            tok0 = int(tok[self._lane_shard(idx)
                           if self._batch_shards > 1 else 0])
            # the fetch is part of service time
            attempt_s[0] = self._clock() - t0
            attempt_s[1] = t0
            return cache, tok0

        try:
            self.cache, tok0 = self._guarded_dispatch("prefill", attempt)
        except DispatchFailedError:
            # the failing program saw exactly one request: quarantine it
            # (terminal "failed", blocks released) and keep serving
            self._quarantine_slot(idx)
            return True
        self._ewma_prefill_s = self._ewma_update(self._ewma_prefill_s,
                                                 attempt_s[0])
        self._num_prefill_chunks += 1
        if self._obs is not None:
            self._obs.note_prefill_chunk(slot.request.uid, idx, start,
                                         end, attempt_s[1], attempt_s[0])
        slot.prefill_pos = end
        slot.context_len = max(slot.context_len, end)
        self._register_full_blocks(slot)
        if end == L:
            self._num_prefills += 1
            slot.started = True
            self._invalidate_lanes()
            if slot.entry.generated:
                # resumed after preemption: the history's tokens are
                # already emitted — never resample them
                slot.generated = list(slot.entry.generated)
                slot.last_token = slot.generated[-1]
            else:
                self._record_token(idx, tok0,
                                   t_vis=attempt_s[1] + attempt_s[0])
        return True

    # -- speculative drafting (docs/serving.md) ----------------------------

    def _build_draft_plan(self, active: List[int]) -> None:
        """Ask the drafter for up to ``spec_tokens`` proposals per
        decoding lane — the host half of draft-and-verify, run once per
        decode phase BEFORE the span reservation (the reservation is
        sized by each lane's proposal count).

        Per lane the proposal budget is ``min(spec_tokens, remaining -
        1)``: capping one under the lane's remaining ``max_new_tokens``
        means the verify program can never emit past the budget (it
        emits at most ``proposals + 1`` tokens), which also keeps every
        span write inside ``max_seq_len`` (``add_request`` bounds
        ``prompt + max_new_tokens``). Proposals are sanitized — the
        drafter is third-party code — by truncating at the first token
        outside the vocabulary.

        The drafter runs under the shared retry policy
        (:func:`~apex_tpu.utils.faults.guarded_call`, site ``"draft"``).
        A drafter that exhausts its retries — or raises anything
        non-transient — is **quarantined**: ``_drafter_ok`` flips off
        for the engine's lifetime and every future plan is empty, so
        the verify program degrades to plain single-token decoding
        (bit-identically — a zero-proposal verify IS one decode step)
        instead of the crash killing the engine."""
        self._draft_plan = {}
        if not self._drafter_ok:
            return
        if self._degradation_level >= 1:
            # ladder rung 1: speculation suspended — the same
            # empty-plan degrade path quarantine uses (a zero-proposal
            # verify IS a single decode step, greedy-bit-identically),
            # but REVERSIBLE: plans resume when pressure clears
            return
        S = self.config.spec_tokens
        if self.config.spec_adapt:
            S = min(S, self._spec_cap)
            if S == 0:
                # capped out: every _SPEC_PROBE_EVERY-th plan runs a
                # 1-token probe so acceptance is re-measured and the
                # cap can climb back (otherwise no observations ever
                # arrive and the degrade is permanent)
                self._spec_probe_countdown -= 1
                if self._spec_probe_countdown > 0:
                    return
                self._spec_probe_countdown = _SPEC_PROBE_EVERY
                S = 1
        vocab = self.model.cfg.vocab_size
        plan: Dict[int, List[int]] = {}

        def count(attempt):
            self._num_draft_retries += 1

        for i in active:
            slot = self.slots[i]
            cap = min(S, slot.request.max_new_tokens
                      - len(slot.generated) - 1)
            if cap < 1:
                continue
            history = list(slot.request.prompt) + slot.generated
            try:
                props, _ = guarded_call(
                    self.drafter.propose, history, cap,
                    plan=self.faults, site="draft",
                    retries=self.config.max_dispatch_retries,
                    backoff_s=self.config.retry_backoff_s,
                    on_retry=count)
            except SimulatedCrash:
                raise
            except Exception:
                # retries exhausted (DispatchFailedError) or a drafter
                # bug: degrade to non-speculative decoding, permanently
                self._drafter_ok = False
                self._num_drafter_quarantines += 1
                if self._obs is not None:
                    self._obs.record("drafter_quarantine")
                    self._obs.incident("drafter_quarantine")
                return
            clean: List[int] = []
            for t in list(props)[:cap]:
                t = int(t)
                if not 0 <= t < vocab:
                    break
                clean.append(t)
            if clean:
                plan[i] = clean
        self._draft_plan = plan
        # num_draft_tokens is counted at DISPATCH, not here: proposals
        # a preemption or failed dispatch drops before verification
        # must not dilute the acceptance rate

    # -- decode-time block growth, CoW, preemption -------------------------

    def _preempt_for(self, requester: int) -> bool:
        """Free the lowest-class, youngest lane (:meth:`_yield_key`) to
        un-wedge an allocation for ``requester``; its request re-queues
        at the front of its class carrying its generated tokens. The
        victim's class is >= every survivor's, so preemption never
        inverts priority, and within the class youngest-first
        guarantees the oldest request always progresses, so the system
        drains. Returns False when the requester is the only lane
        (nothing to free — the pool is simply too small for it). At
        ``B > 1`` victims come only from the REQUESTER'S shard: a
        foreign shard's lane frees blocks the requester's shard-scoped
        allocation can never draw from."""
        cand = [i for i, s in enumerate(self.slots) if s is not None
                and (self._batch_shards == 1
                     or self._lane_shard(i)
                     == self._lane_shard(requester))]
        if len(cand) <= 1:
            return False
        idx = max(cand, key=self._yield_key)
        return self._preempt_slot(idx)

    def _preempt_tenant_lane(self, tenant: str, requester: int) -> bool:
        """Quota-driven preemption: a lane growing past its TENANT's
        ``max_resident_blocks`` evicts the tenant's OWN lowest-class,
        youngest other lane — the tenant pays for its growth out of its
        own residency, never another tenant's. Only lanes whose release
        can actually LOWER the tenant's fractional charge are
        candidates: a lane holds such charge iff it owns a block
        privately (refcount 1 — freeing returns a whole unit) or a
        block some OTHER tenant co-holds (freeing shrinks this
        tenant's fraction). A sibling whose every block is fully
        shared within the tenant contributes nothing reclaimable —
        freeing it just re-concentrates the same charge — so evicting
        it would churn lanes without relieving the quota. False when
        no reducing candidate exists (growth proceeds: residency is
        then bounded by lane count x the door-validated worst case)."""
        alloc = self.allocator

        def reduces(slot: "_Slot") -> bool:
            return any(alloc.refcount(b) == 1
                       or alloc.tenant_refcount(b, tenant)
                       < alloc.refcount(b)
                       for b in slot.blocks)

        cand = [i for i, s in enumerate(self.slots)
                if s is not None and i != requester
                and s.request.tenant == tenant and reduces(s)]
        if not cand:
            return False
        idx = max(cand, key=self._yield_key)
        tally = self._tenant_preemptions
        tally[tenant] = tally.get(tenant, 0) + 1
        return self._preempt_slot(idx, reason="quota")

    def _preempt_slot(self, idx: int,
                      reason: str = "pool_pressure") -> bool:
        slot = self.slots[idx]
        gen = self._resume_tokens(slot)
        # deepest-first, same as _finish: keep evictable chains matchable
        self.allocator.free(list(reversed(slot.blocks)),
                            tenant=slot.request.tenant)
        requeue_t = self._clock()
        self.waiting.appendleft(_QueueEntry(request=slot.request,
                                            arrival=slot.entry.arrival,
                                            generated=gen,
                                            enq_t=requeue_t,
                                            enq_tick=self._num_ticks,
                                            drr_charged=True))
        # sample the peak at the requeue itself — admission may
        # re-absorb the entry before step()'s end-of-tick sample
        self._queue_depth_peak = max(self._queue_depth_peak,
                                     len(self.waiting))
        self.slots[idx] = None
        self._invalidate_lanes()
        self._num_preemptions += 1
        if self._obs is not None:
            self._obs.note_preempt(slot.request.uid, idx, reason=reason,
                                   t=requeue_t)
            self._obs.note_enqueue(slot.request.uid,
                                   tenant=slot.request.tenant,
                                   priority=slot.request.priority,
                                   requeue=True, t=requeue_t)
        return True

    def _ensure_decode_blocks(self) -> None:
        """Each started slot is about to write K/V at positions
        ``context_len .. context_len + span - 1`` (``span`` = the
        coming dispatch's write bound: ``decode_steps`` capped by the
        lane's remaining budget — or, speculating, the carried token
        plus the lane's proposal count, every candidate K/V landing in
        the same dispatch whether or not it is accepted) — make sure
        PRIVATE blocks
        cover the whole span: allocate the missing tail (preempting the
        youngest lane if the pool is dry), and copy-on-write any
        covering block shared with another sequence (a full-block
        prefix match never shares a partial tail, so CoW is a guard for
        exotic sharing patterns, not the steady state). Reserving the
        span UP FRONT keeps the scan free of host intervention: a
        mid-scan allocation failure is impossible, so preemption
        granularity is K tokens, decided before the dispatch."""
        bs = self.config.block_size
        K = self.config.decode_steps
        order = sorted((s.admit_seq, i) for i, s in enumerate(self.slots)
                       if s is not None and s.started)
        for _, i in order:
            while self.slots[i] is not None:
                slot = self.slots[i]
                if self.config.spec_tokens > 0:
                    # verify-span writes: the carried token + every
                    # proposal (rejected ones too — the drain trims
                    # blocks the rejection strands back to the pool)
                    span = 1 + len(self._draft_plan.get(i, ()))
                else:
                    span = min(K, slot.request.max_new_tokens
                               - len(slot.generated))
                need = blocks_needed(slot.context_len + span, bs)
                if len(slot.blocks) < need:
                    grow = need - len(slot.blocks)
                    tenant = slot.request.tenant
                    q = self._tenant_quota(tenant)
                    if (q is not None
                            and q.max_resident_blocks is not None
                            and self.allocator.tenant_charge(tenant)
                            + grow * self._block_weight
                            > q.max_resident_blocks + 1e-9
                            and self._preempt_tenant_lane(tenant, i)):
                        # over quota: the tenant paid with its own
                        # youngest lane — re-check (the freed charge
                        # usually covers the growth). When no other
                        # lane of the tenant exists, growth proceeds:
                        # a single lane's private worst case fits the
                        # quota by the door bound.
                        continue
                    try:
                        slot.blocks.extend(
                            self.allocator.alloc(
                                grow, tenant=tenant,
                                shard=(self._lane_shard(i)
                                       if self._batch_shards > 1
                                       else None)))
                        self._invalidate_tables()
                    except CacheOutOfBlocks:
                        if not self._preempt_for(i):
                            if self._obs is not None:
                                self._obs.record(
                                    "alloc_pressure",
                                    uid=slot.request.uid,
                                    free=self.allocator.num_free)
                            raise CacheOutOfBlocks(
                                f"request {slot.request.uid!r} cannot grow "
                                f"past {slot.context_len} cached tokens: "
                                f"{self.allocator.num_free} blocks free of "
                                f"{self.allocator.num_blocks} and no other "
                                "lane left to preempt")
                    continue   # re-check: the slot itself may be gone
                first = slot.context_len // bs
                last = (slot.context_len + span - 1) // bs
                j = next((j for j in range(first, last + 1)
                          if self.allocator.refcount(slot.blocks[j]) > 1),
                         None)
                if j is None:
                    break
                try:
                    # CoW rides outside the tenant quota check: it nets
                    # +1 - (shared fraction) charge, bounded by the
                    # same door-validated worst case. The private copy
                    # lands on the slot's shard (src and dst must share
                    # one for the sharded copy program).
                    nb = self.allocator.alloc(
                        1, tenant=slot.request.tenant,
                        shard=(self._lane_shard(i)
                               if self._batch_shards > 1 else None))[0]
                except CacheOutOfBlocks:
                    if not self._preempt_for(i):
                        if self._obs is not None:
                            self._obs.record(
                                "alloc_pressure", uid=slot.request.uid,
                                free=self.allocator.num_free)
                        raise CacheOutOfBlocks(
                            f"request {slot.request.uid!r}: cannot "
                            "copy-on-write a shared block, pool "
                            "exhausted and no lane left to preempt")
                    continue
                b = slot.blocks[j]
                self.cache = self._cow(self.cache,
                                       jnp.int32(b), jnp.int32(nb))
                self.allocator.free([b], tenant=slot.request.tenant)
                slot.blocks[j] = nb
                self._invalidate_tables()
                # the copy diverges from the indexed contents the
                # moment we append; registration state stays with
                # the ORIGINAL block
                if slot.num_registered > j:
                    slot.num_registered = j
                self._num_cow_copies += 1
                # loop again: the span may cross FURTHER shared blocks

    # -- the fused decode dispatch + deferred drain ------------------------

    def _dispatch_decode(self, active: List[int]) -> None:
        """Launch the K-step fused decode for ``active`` lanes and
        leave the result in flight (``self._pending``). Only the small
        per-tick arrays (tokens, context lens, budgets, counts) upload
        here; the block table and lane meta come from their mirrors.

        When the dispatch exhausts its retries, the batch is poisoned
        but nothing says which lane: isolation is by elimination — the
        lowest-class youngest lane is quarantined (same yield order as
        preemption, :meth:`_yield_key`) and the
        dispatch is rebuilt over the survivors, until it launches or no
        decoding lane remains. A persistent site-wide fault therefore
        fails requests one at a time instead of killing the engine."""
        B = self.config.max_batch
        spec = self.config.spec_tokens > 0
        while active:
            tokens = np.zeros(B, np.int32)
            ctx = np.zeros(B, np.int32)
            budgets = np.zeros(B, np.int32)
            gcounts = np.zeros(B, np.int32)
            for i in active:
                slot = self.slots[i]
                tokens[i] = slot.last_token
                ctx[i] = slot.context_len
                budgets[i] = (slot.request.max_new_tokens
                              - len(slot.generated))
                gcounts[i] = len(slot.generated)
            tables = self._dev_tables.get(self._build_decode_tables)
            temp, top_k, top_p, eos, keys = self._dev_lanes.get(
                self._build_lane_meta)
            if spec:
                # this tick's draft plan, as fixed-shape arrays: the
                # verify program's ONE compiled shape regardless of
                # how many proposals each lane actually carries
                drafts = np.zeros((B, self.config.spec_tokens), np.int32)
                dlens = np.zeros(B, np.int32)
                for i in active:
                    p = self._draft_plan.get(i, ())
                    drafts[i, : len(p)] = p
                    dlens[i] = len(p)
                args = (self.params, self.cache, jnp.asarray(tokens),
                        jnp.asarray(drafts), jnp.asarray(dlens), tables,
                        jnp.asarray(ctx), jnp.asarray(budgets),
                        jnp.asarray(gcounts), eos, keys, temp, top_k,
                        top_p)
            else:
                args = (self.params, self.cache, jnp.asarray(tokens),
                        tables, jnp.asarray(ctx), jnp.asarray(budgets),
                        jnp.asarray(gcounts), eos, keys, temp, top_k,
                        top_p)
            try:
                self.cache, toks = self._guarded_dispatch(
                    "decode", self._decode, *args)
            except DispatchFailedError:
                idx = max(active, key=self._yield_key)
                self._quarantine_slot(idx)
                active = [i for i, s in enumerate(self.slots)
                          if s is not None and s.started]
                continue
            self._num_decode_dispatches += 1
            # the SDC fault model (docs/robustness.md): a "corrupt"
            # spec at the decode site marks THIS dispatch's output for
            # a seeded wrong-token perturbation at the drain — the
            # silent wrong-compute no checksum can catch (the fleet's
            # determinism cross-check exists for exactly this)
            self._pending_corrupt = (
                self.faults.corrupt_seed("decode")
                if self.faults is not None else None)
            if spec:
                # count drafted tokens HERE, for the lanes this
                # dispatch actually verifies — plan-time counting would
                # inflate the acceptance-rate denominator with
                # proposals that preemption or a failed dispatch
                # dropped before any verification could accept them
                self._num_draft_tokens += int(dlens.sum())
            # the uid each covered lane held at dispatch: the drain
            # discards results for lanes whose request was aborted (or
            # whose lane was re-filled) while the dispatch was in
            # flight — matching on uid, not lane index
            self._pending = (toks, list(active),
                             {i: self.slots[i].request.uid
                              for i in active})
            if self._obs is not None:
                self._pending_obs = (self._clock(),
                                     self._num_decode_dispatches)
            return

    def _drain_decode(self) -> bool:
        """The deferred host sync: fetch the in-flight dispatch's
        ``[B, K]`` tokens (the ONLY decode-path block on the device)
        and replay them through the per-token bookkeeping —
        cache-token append, block registration, EOS/budget finish. The
        device's stop mask mirrors ``_record_token`` exactly, so a lane
        that froze mid-scan finishes here on the same token.

        Dispatch is asynchronous, so a REAL runtime failure surfaces
        here, at the fetch, not at the launch `_guarded_dispatch`
        guards — and a failed program poisons every output it produced,
        including the new pool. Recovery is the in-process analog of a
        crash restore (:meth:`_reset_device_state`): every resident
        request re-queues carrying its emitted tokens, the allocator
        and prefix index reset, the pool zeroes, and re-prefill
        re-derives everything — bit-identical continuation by the same
        resume determinism ``restore()`` leans on, and valid even under
        ``donate_cache`` (nothing from the failed dispatch is reused).
        Consecutive drain failures count against
        ``max_dispatch_retries``; exhaustion quarantines the youngest
        covered lane before the reset."""
        if self._pending is None:
            return False
        toks, active, uids = self._pending
        self._pending = None
        pending_obs, self._pending_obs = self._pending_obs, None
        corrupt_seed, self._pending_corrupt = self._pending_corrupt, None
        # the decode EWMA times THIS fetch block only — the remaining
        # in-flight device time at drain. The full launch->drain span
        # would fold caller inter-tick pauses and host scheduling into
        # the feasibility gate's "contention-free lower bound" and
        # over-shed (the same reasoning that keeps retry backoff out of
        # the prefill EWMA); under-measuring merely sheds less — the
        # safe direction for a lower bound.
        t_fetch = self._clock()
        try:
            toks = np.asarray(toks)
        except SimulatedCrash:
            raise
        except TRANSIENT_ERRORS:
            self._fetch_failures += 1
            if self._fetch_failures > self.config.max_dispatch_retries:
                # exhausted — same attempt arithmetic as guarded_call
                # (N retries = N+1 attempts, no sleep after the last),
                # so serving/training retry counters stay comparable
                live = [i for i in active
                        if self.slots[i] is not None
                        and self.slots[i].started
                        # a lane aborted (and possibly re-filled)
                        # mid-flight was no part of the failed
                        # dispatch: never quarantine its new owner
                        and self.slots[i].request.uid == uids[i]]
                if live:
                    idx = max(live, key=self._yield_key)
                    self._quarantine_slot(idx)
                self._fetch_failures = 0
            else:
                self._num_dispatch_retries += 1
                if self._obs is not None:
                    self._obs.record("fault_retry", site="decode_drain",
                                     attempt=self._fetch_failures)
                if self.config.retry_backoff_s > 0.0:
                    time.sleep(self.config.retry_backoff_s
                               * (2 ** (self._fetch_failures - 1)))
            self._reset_device_state()
            return True
        self._fetch_failures = 0
        t_end = self._clock()
        self._ewma_decode_s = self._ewma_update(
            self._ewma_decode_s, t_end - t_fetch)
        # each lane's emitted tokens are its non-sentinel prefix (lanes
        # freeze permanently mid-scan, and real token ids are >= 0)
        counts = (toks >= 0).sum(axis=1)
        if corrupt_seed is not None:
            # the injected SDC: one emitted token flips to a different
            # in-vocabulary id. Deliberately applied BEFORE any host
            # bookkeeping — the wrong token feeds the KV append, the
            # stream, and the next dispatch's context exactly like a
            # real flaky-chip sample would, and NOTHING in this engine
            # can tell (detection is the fleet cross-check's job).
            toks = perturb_tokens(toks, counts,
                                  self.model.cfg.vocab_size,
                                  corrupt_seed)
        if self._obs is not None and pending_obs is not None:
            # trace the dispatch BEFORE replaying its tokens, so each
            # request's timeline reads decode -> drain -> terminal in
            # emission order; aborted/re-filled lanes (uid mismatch)
            # are excluded exactly as the replay below excludes them
            self._obs.note_decode_drained(
                pending_obs[1], pending_obs[0], t_end, t_end - t_fetch,
                [(uids[i], i, int(counts[i])) for i in active
                 if self.slots[i] is not None
                 and self.slots[i].request.uid == uids[i]])
        spec = self.config.spec_tokens > 0
        bs = self.config.block_size
        drafted_this = accepted_this = 0
        for i in active:
            slot = self.slots[i]
            if slot is None or slot.request.uid != uids[i]:
                # the lane's request was aborted (and the lane possibly
                # re-filled by admission) while this dispatch was in
                # flight: its results are DISCARDED — the blocks were
                # already reclaimed, and any K/V the dispatch wrote to
                # them sits past every live sequence's masks until
                # overwritten (docs/serving.md, cancellation)
                continue
            n = int(counts[i])
            for j in range(n):
                slot.tokens.append(slot.last_token)   # its K/V landed
                slot.context_len += 1
                self._register_full_blocks(slot)
                self._record_token(i, int(toks[i, j]), t_vis=t_end)
                if self.slots[i] is None:
                    break
            self._num_tokens_decoded += n
            if not spec:
                continue
            # speculative bookkeeping: an emitted token that matches
            # the lane's proposal at its index IS an accepted draft
            # (the correction is drawn with the draft masked out and a
            # greedy rejection means argmax != draft, so a match can
            # only be an acceptance; the bonus sits past the plan)
            prop = self._draft_plan.get(i, ())
            drafted_this += len(prop)
            for j in range(min(n, len(prop))):
                if int(toks[i, j]) != prop[j]:
                    break
                self._num_accepted_tokens += 1
                accepted_this += 1
            # reservation rollback: the span was reserved for EVERY
            # proposal's write, but rejection advanced the context by
            # less — blocks holding only unaccepted K/V go back to the
            # pool now instead of idling on the slot (the K/V itself
            # needs no rollback: it sits past the context length every
            # attention mask already excludes)
            slot = self.slots[i]
            if slot is not None:
                keep = blocks_needed(slot.context_len, bs)
                if len(slot.blocks) > keep:
                    trimmed = len(slot.blocks) - keep
                    slot.blocks = self.allocator.trim_to(
                        slot.blocks, keep, tenant=slot.request.tenant)
                    self._num_spec_blocks_rolled_back += trimmed
                    # deliberately NO table invalidation: the trimmed
                    # entries sit past blocks_needed(context_len), so
                    # every gather of them is position-masked, and any
                    # future span reaching that region must first
                    # allocate (need > len(blocks)) — which invalidates
                    # and rebuilds. Skipping it here keeps the device
                    # mirror warm in the low-acceptance regime, where
                    # trim would otherwise force a rebuild every tick.
                    # (Eager reclaim itself is load-bearing: held
                    # reservations would let a low-acceptance engine
                    # squat on spec_tokens-worth of blocks per lane,
                    # changing admission/preemption under tight pools.)
        if spec and self.config.spec_adapt and drafted_this:
            # dynamic speculation (docs/serving.md): the acceptance
            # EWMA walks the per-plan draft cap one step per
            # observation — below spec_accept_low shrink toward 0
            # (riding the rung-1 empty-plan machinery), above
            # spec_accept_high restore toward spec_tokens; the dead
            # band between them is the hysteresis. While acceptance
            # stays >= high the cap never moves, so the engine is
            # bit-identical to static speculation.
            self._spec_accept_ewma = self._ewma_update(
                self._spec_accept_ewma, accepted_this / drafted_this)
            if (self._spec_accept_ewma < self.config.spec_accept_low
                    and self._spec_cap > 0):
                self._spec_cap -= 1
                self._num_spec_cap_shrinks += 1
                if self._obs is not None:
                    self._obs.record("spec_cap", cap=self._spec_cap,
                                     direction="shrink",
                                     ewma=self._spec_accept_ewma)
            elif (self._spec_accept_ewma > self.config.spec_accept_high
                    and self._spec_cap < self.config.spec_tokens):
                self._spec_cap += 1
                self._num_spec_cap_restores += 1
                if self._obs is not None:
                    self._obs.record("spec_cap", cap=self._spec_cap,
                                     direction="restore",
                                     ewma=self._spec_accept_ewma)
        return True

    # -- the degradation ladder (docs/robustness.md) -----------------------

    def _ladder_enabled(self) -> bool:
        return (self.config.queue_high_watermark is not None
                or self.config.free_block_low_watermark is not None)

    def _under_pressure(self) -> bool:
        """The watermark signal: queue depth at/over the high mark, or
        the ALLOCATABLE fraction — free plus evictable (cached)
        blocks, the headroom ``alloc()`` can actually draw on — at/
        under the low mark. Counting evictable as headroom matters: a
        warm prefix cache under light traffic parks most of the pool
        at refcount 0, and a bare free-list signal would read that
        healthy state as overload and drive a perpetual
        degrade/flush/re-warm sawtooth. The flip side is that rung 2's
        flush does not relieve THIS signal (free + cached is invariant
        under it) — correct, since block pressure the flush can't fix
        is active-sequence pressure, which only draining relieves;
        the flush's value is making that headroom 1-hop allocatable."""
        cfg = self.config
        if (cfg.queue_high_watermark is not None
                and len(self.waiting) >= cfg.queue_high_watermark):
            return True
        if cfg.free_block_low_watermark is not None:
            allocatable = (self.allocator.num_free
                           + self.allocator.num_cached)
            if (allocatable / max(self.allocator.num_blocks, 1)
                    <= cfg.free_block_low_watermark):
                return True
        return False

    def _update_ladder(self) -> bool:
        """One hysteresis tick of the degradation ladder: after
        ``degrade_patience`` CONSECUTIVE pressure ticks, step one rung
        down; after as many consecutive clear ticks, one rung up —
        deterministic, single-rung transitions, so a given (traffic,
        clock) schedule always walks the same ladder path. While at
        rung >= 2 every tick flushes the prefix cache's evictable
        blocks back to the free list (trading future hits for
        allocatable headroom). Rung 1 (speculation suspended) is
        enforced in :meth:`_build_draft_plan`; rung 3 (lowest-class
        admission pause) in :meth:`_admission_priority_limit`. Returns
        whether a transition happened (it counts as step progress)."""
        if not self._ladder_enabled():
            return False
        transition = False
        if self._under_pressure():
            self._pressure_streak += 1
            self._clear_streak = 0
            if (self._degradation_level < _LADDER_TOP
                    and self._pressure_streak
                    >= self.config.degrade_patience):
                self._degradation_level += 1
                self._pressure_streak = 0
                self._num_degrade_steps_down += 1
                transition = True
                if self._obs is not None:
                    self._obs.record("ladder", direction="down",
                                     level=self._degradation_level)
        else:
            self._clear_streak += 1
            self._pressure_streak = 0
            if (self._degradation_level > 0
                    and self._clear_streak >= self.config.degrade_patience):
                self._degradation_level -= 1
                self._clear_streak = 0
                self._num_degrade_steps_up += 1
                transition = True
                if self._obs is not None:
                    self._obs.record("ladder", direction="up",
                                     level=self._degradation_level)
        if self._degradation_level >= 2:
            self._num_degrade_flushed_blocks += \
                self.allocator.flush_evictable()
        return transition

    def step(self) -> bool:
        """One scheduler tick: update the degradation ladder, expire
        deadlines, admit, run at most one prefill chunk, drain the
        previous tick's in-flight decode, then dispatch one fused
        K-step decode for every started slot (if any). The drain comes
        AFTER admission/prefill on purpose — tick t+1's host scheduling
        work overlaps tick t's device decode (the deferred sync) — with
        an admission top-up behind it so lanes freed by the drain (or a
        timeout) don't idle a tick.

        Returns True when the tick made progress — admitted, chunked,
        drained, expired, shed, dispatched, preempted, quarantined, or
        stepped the ladder. ``run()`` turns a no-progress tick with
        work remaining into :class:`EngineStalledError` instead of
        spinning.
        """
        self._num_ticks += 1
        pre_shed = self._num_rejected_infeasible
        stepped = self._update_ladder()
        # waiting entries and mid-prefill slots are expirable up front
        # (so an expired slot never gets one last wasted chunk);
        # started slots only when no decode dispatch is in flight over
        # them — otherwise the post-drain sweep picks them up
        expired = self._expire_deadlines(
            include_started=self._pending is None)
        admitted = self._admit()
        chunked = self._prefill_tick()
        synced = self._drain_decode()
        # the in-flight dispatch (if any) is drained now, so resident
        # slots are safe to expire too
        expired += self._expire_deadlines(include_started=True)
        if synced or expired:
            admitted += self._admit()
        self._queue_depth_peak = max(self._queue_depth_peak,
                                     len(self.waiting))
        shed = self._num_rejected_infeasible - pre_shed
        made = bool(admitted or chunked or synced or expired or stepped
                    or shed)
        if all(s is None for s in self.slots):
            if self.waiting and not made:
                # zero live sequences and nothing in flight means
                # nothing will ever free a block — the queue head can
                # never be admitted (the pool is undersized for it).
                # Raise, don't spin. (The ladder cannot park us here:
                # its admission pause yields to work conservation the
                # moment nothing more urgent exists.)
                entry = self.waiting.head()
                need = blocks_needed(len(entry.request.prompt) + 1,
                                     self.config.block_size)
                if self._obs is not None:
                    self._obs.record("alloc_pressure",
                                     uid=entry.request.uid, need=need)
                raise CacheOutOfBlocks(
                    f"request {entry.request.uid!r} needs {need} blocks "
                    f"to admit but only {self.allocator.num_blocks} exist "
                    "in the pool")
            self._maybe_scrub()
            self._maybe_checkpoint()
            self._record_tick(admitted, chunked, synced, expired, shed,
                              made)
            return made
        pre_preempt = self._num_preemptions
        pre_quarantine = self._num_quarantines
        active = [i for i, s in enumerate(self.slots)
                  if s is not None and s.started]
        if active and self.config.spec_tokens > 0:
            # proposals first: the span reservation below is sized by
            # each lane's proposal count
            self._build_draft_plan(active)
        if active:
            self._ensure_decode_blocks()
            # preemption may have cleared lanes — re-collect
            active = [i for i, s in enumerate(self.slots)
                      if s is not None and s.started]
        if active:
            self._dispatch_decode(active)
        progressed = bool(made or self._pending is not None
                          or self._num_preemptions > pre_preempt
                          or self._num_quarantines > pre_quarantine)
        self._maybe_scrub()
        self._maybe_checkpoint()
        self._record_tick(admitted, chunked, synced, expired, shed,
                          progressed)
        return progressed

    def _maybe_checkpoint(self) -> None:
        """The ``snapshot_interval_ticks`` cadence: refresh
        ``last_checkpoint`` at the end of every N-th tick. Lightweight
        by construction (:meth:`checkpoint` never drains), so the
        steady-state tick pays only the host-side record build."""
        interval = self.config.snapshot_interval_ticks
        if interval is not None and self._num_ticks % interval == 0:
            self.checkpoint()

    def _record_tick(self, admitted: int, chunked: bool, synced: bool,
                     expired: int, shed: int, progress: bool) -> None:
        """One flight-recorder ``tick`` summary per ``step()`` — the
        rolling narration of what the scheduler decided, O(1) per tick
        and only when a recorder is attached."""
        obs = self._obs
        if obs is None or obs.recorder is None:
            return
        obs.record(
            "tick", tick=self._num_ticks, admitted=int(admitted),
            chunked=bool(chunked), drained=bool(synced),
            expired=int(expired), shed=int(shed),
            progress=bool(progress),
            active=sum(s is not None for s in self.slots),
            waiting=len(self.waiting),
            blocks_free=self.allocator.num_free,
            level=self._degradation_level)

    @property
    def has_work(self) -> bool:
        """True while anything is queued, resident in a lane, or IN
        FLIGHT (an undrained decode dispatch). This is ``run()``'s loop
        condition, public so external step-at-a-time drivers (the tick
        loop of tests/_traffic.py) drain completely without
        duplicating it — a hand-rolled ``waiting or slots`` check would
        silently drop the last dispatch's tokens."""
        return (bool(self.waiting) or self._pending is not None
                or any(s is not None for s in self.slots))

    def run(self, return_status: bool = False):
        """Drain: step until every queued, active, and in-flight
        request reaches a terminal state. Returns ``{uid:
        generated_token_ids}`` — or, with ``return_status=True``,
        ``{uid: RequestResult(tokens, status)}`` where ``status`` is
        ``"finished"`` | ``"timeout"`` | ``"failed"`` | ``"rejected"``
        | ``"throttled"`` | ``"cancelled"`` (the result
        contract in docs/serving.md; the same status is written onto
        each ``Request.status``). If a full step makes no progress
        while work remains, raises :class:`EngineStalledError` with
        ``stats()`` attached instead of spinning forever (plus the
        flight recorder's tail when an observer is attached — and any
        exception escaping the drive loop writes the observer's crash
        dump to its ``crash_dump_path`` before propagating, so a run
        that dies ships its own post-mortem)."""
        try:
            while self.has_work:
                if not self.step():
                    tail = None
                    if self._obs is not None:
                        self._obs.record("stall")
                        if self._obs.recorder is not None:
                            tail = self._obs.recorder.tail()
                    raise EngineStalledError(
                        "engine has work but a full step made no "
                        "progress", self.stats(), recorder_tail=tail)
        except Exception as e:
            if self._obs is not None:
                self._obs.crash_dump(e)
            raise
        out, self.finished = self.finished, {}
        statuses, self.statuses = self.statuses, {}
        # run() IS the non-streaming consumption path: the terminal
        # result dict it returns supersedes any unconsumed stream
        # events, so drop them — otherwise every run()-based caller
        # (which never calls pop_stream_events) leaks one buffered
        # event per token for the engine's lifetime. Streaming callers
        # drain via pop_stream_events BEFORE the terminal run().
        self._stream.clear()
        if return_status:
            return {uid: RequestResult(tokens=toks,
                                       status=statuses.get(uid, "finished"))
                    for uid, toks in out.items()}
        return out

    # -- the fleet surface (docs/fleet.md) ---------------------------------

    def pop_results(self) -> Dict[str, "RequestResult"]:
        """Drain every terminal result accumulated so far WITHOUT
        stepping the engine — the fleet router's per-tick result
        collection (``run()`` is the drive-to-completion variant; this
        is the incremental one). Each drained uid becomes reusable,
        exactly as after ``run()``. Stream events are left alone:
        streaming callers drain them via :meth:`pop_stream_events`."""
        out, self.finished = self.finished, {}
        statuses, self.statuses = self.statuses, {}
        return {uid: RequestResult(tokens=toks,
                                   status=statuses.get(uid, "finished"))
                for uid, toks in out.items()}

    def load(self) -> Dict[str, float]:
        """The cheap health/load surface a fleet router polls per
        routing decision — a strict (float-valued) subset of
        ``stats()``, built without the full dict: queue depth, active
        lanes, the feasibility-gate service EWMAs, and allocatable
        headroom (free + evictable blocks, the same measure the
        degradation ladder reads)."""
        return {
            "queue_depth": float(len(self.waiting)),
            "active_slots": float(
                sum(s is not None for s in self.slots)),
            "ewma_prefill_dispatch_s": float(self._ewma_prefill_s or 0.0),
            "ewma_decode_dispatch_s": float(self._ewma_decode_s or 0.0),
            "blocks_allocatable": float(self.allocator.num_free
                                        + self.allocator.num_cached),
        }

    # the replica-surface discriminator a router reads to know whether
    # this replica lives in its own OS process (ProcessReplica reports
    # "process"); a class attribute so even a dead slot still answers
    mode = "in_process"

    @property
    def block_weight(self) -> float:
        """The per-block resident-cost weight (1.0 unquantized; the
        packed fraction under KV quantization) — part of the narrow
        replica surface so the router's door throttle can price tenant
        block charges without reaching into engine internals (which a
        process replica could not serve)."""
        return float(self._block_weight)

    @property
    def queue_depth(self) -> int:
        """``len(waiting)`` as a surface method — the router's
        ``stats()`` aggregate reads this, not the queue object."""
        return len(self.waiting)

    @property
    def active_slot_count(self) -> int:
        """Occupied decode lanes — same narrow-surface rationale as
        :attr:`queue_depth`."""
        return sum(s is not None for s in self.slots)

    def tenant_charge(self, tenant: str) -> int:
        """The tenant's resident-block charge (allocator attribution),
        surfaced for the router's per-tenant door throttle."""
        return self.allocator.tenant_charge(tenant)

    def tenant_depth(self, tenant: str) -> int:
        """The tenant's waiting-queue depth, surfaced for the router's
        per-tenant door throttle."""
        return self.waiting.tenant_depth(tenant)

    def probe_prefix(self, hashes: Sequence[str]) -> int:
        """How many leading blocks of a hash chain this engine could
        serve WITHOUT recompute: the device prefix index's longest
        match, extended by the contiguous run of spilled hashes the
        host tier holds (the same lookup :meth:`_admit` performs, read
        only — no references taken, no LRU perturbation). The fleet
        router's prefix-affinity signal: SHA-256 chain hashes are
        globally comparable, so any replica can score any prompt."""
        if not self.config.enable_prefix_caching:
            return 0
        n = len(self.allocator.lookup_prefix(hashes))
        if self.spill is not None:
            while n < len(hashes) and hashes[n] in self.spill:
                n += 1
        return n

    def spilled_hashes(self) -> Dict[str, str]:
        """Chain hash -> owning tenant for every entry resident in the
        local host spill tier — the fleet router's shared-tier publish
        sweep reads this to learn what this replica evicted (and whose
        it was), then pulls the payloads it wants through
        :meth:`export_prefix_payloads`. Read-only, host-side,
        JSON-friendly: part of the narrow replica surface. Empty with
        no spill tier configured."""
        if self.spill is None:
            return {}
        return self.spill.entry_tenants()

    def decoding_uids(self) -> List[str]:
        """Uids of resident slots whose prefill has COMPLETED (first
        token known, decode phase entered), in admission order. The
        disaggregated fleet's handoff signal (docs/fleet.md,
        "Disaggregated roles"): a prefill-specialist replica's router
        migrates exactly these to a decode specialist each tick —
        waiting entries and mid-prefill lanes stay put. Read-only,
        host-side, no sync."""
        started = [(s.admit_seq, s.request.uid) for s in self.slots
                   if s is not None and s.started]
        return [uid for _, uid in sorted(started)]

    def export_requests(self, uids: Optional[Sequence[str]] = None
                        ) -> List[Dict]:
        """Drain-and-migrate EXPORT: remove the given waiting/resident
        requests (all of them when ``uids`` is None) from this engine
        and return them as snapshot-format entry records —
        :meth:`import_requests` on another replica resumes them. The
        in-flight decode is drained first (one host sync — migration
        is a deliberate synchronous operation), so the records carry
        every emitted token; each resident's blocks release through
        the usual deepest-first discipline (cached and re-matchable
        under prefix caching) and its deadline serializes as remaining
        budget. Requests already terminal (awaiting ``pop_results``)
        are NOT exported — their verdicts stay here. Because the
        records preserve the arrival PRNG identity, a migrated request
        resumed on a replica with the same seed continues its token
        stream bit-identically (docs/fleet.md, migration protocol)."""
        self._drain_decode()
        want = None if uids is None else {str(u) for u in uids}
        now = self._clock()
        records: List[Dict] = []
        live = sorted((s.admit_seq, i) for i, s in enumerate(self.slots)
                      if s is not None)
        for _, i in live:
            slot = self.slots[i]
            if want is not None and slot.request.uid not in want:
                continue
            records.append(self._entry_record(
                _QueueEntry(request=slot.request,
                            arrival=slot.entry.arrival,
                            generated=self._resume_tokens(slot),
                            drr_charged=True), now))
            self.allocator.free(list(reversed(slot.blocks)),
                                tenant=slot.request.tenant)
            self.slots[i] = None
            self._invalidate_lanes()
            self._release_exported(slot.request)
        for entry in self.waiting.expel(
                lambda e: want is None or e.request.uid in want):
            records.append(self._entry_record(entry, now))
            self._release_exported(entry.request)
        # stash each record's arrival identity BEFORE the chaos site
        # can touch the caller's copy (see _exported_arrivals)
        for rec in records:
            self._exported_arrivals[str(rec["uid"])] = \
                int(rec["arrival"])
        # each record is sealed for the wire (import_requests verifies
        # it), THEN run through the "export" chaos site — one fire per
        # record, so a seeded plan can rot exactly the record it means
        # to (docs/robustness.md, "Data integrity")
        records = [self._maybe_corrupt_record("export", seal_record(rec))
                   for rec in records]
        self._num_migrated_out += len(records)
        return records

    def drop_stream_events(self, uid: str) -> int:
        """Discard this engine's UNDRAINED stream events for ``uid`` —
        the refused-import recompute's companion: the re-injected
        request re-derives (and re-emits) every token past the
        router's delivered watermark, so stale copies the router never
        drained would otherwise arrive twice — once stale, once
        re-derived — and shift every later position in the delivered
        ledger. Returns how many events were dropped."""
        uid = str(uid)
        before = len(self._stream)
        self._stream = deque(ev for ev in self._stream
                             if ev[0] != uid)
        return before - len(self._stream)

    def exported_arrival(self, uid: str) -> Optional[int]:
        """The arrival PRNG index this engine last exported for
        ``uid`` — the clean, source-side copy the router's
        refused-import recompute reads so a re-injected request keeps
        its sampled-token identity (``None`` when the uid never left
        through :meth:`export_requests`)."""
        v = self._exported_arrivals.get(str(uid))
        return None if v is None else int(v)

    def _release_exported(self, request: Request) -> None:
        """Forget an exported request WITHOUT a terminal transition:
        it is still alive, just owned by another replica now — no
        status, no stream sentinel (unlike every other exit path),
        and fleet-wide uid uniqueness stays the router's job."""
        self._live_uids.discard(request.uid)
        self._deadline.pop(request.uid, None)
        self._prune_tenant_if_idle(request.tenant)

    def import_requests(self, records: Sequence[Dict]) -> int:
        """Drain-and-migrate IMPORT: enqueue entry records exported by
        another replica (or read from its checkpoint) into this
        engine's waiting queue. Records keep their arrival PRNG
        identity (``_arrival_count`` advances past every imported
        index so future local arrivals never collide) and their
        ``drr_charged`` standing — a migrated RESIDENT re-admits ahead
        of the DRR walk exactly like a preemption requeue, a migrated
        waiting entry rejoins the walk uncharged. A record without an
        ``arrival`` (a router re-injecting a post-checkpoint accept it
        only knows as a Request) gets a fresh local index. Deadlines
        re-anchor their remaining budget on this clock. Deliberately
        NO door-quota check: quota enforcement happened at the
        original door, and failover/migration of already-accepted work
        must never manufacture a shed (docs/fleet.md, zero-lost
        contract). Raises ``ValueError`` — before touching anything —
        if any uid is already live or awaiting drain here, and
        :class:`~apex_tpu.utils.integrity.IntegrityError` — likewise
        before touching anything — if a SEALED record fails its
        checksum (``verify_artifacts``): a corrupt migration import is
        REFUSED, so the router's copy (and the source replica) stay
        the request's truth instead of corrupt state re-entering the
        fleet. Checksum-less LEGACY records import as before (the
        fleet seals every hop — export, failover placement — so only
        hand-built records arrive unsealed)."""
        now = self._clock()
        if self.faults is not None:
            # target-side chaos: one "import" fire per received record
            # (in-transit rot arriving at this replica)
            records = [self._maybe_corrupt_record("import", rec)
                       for rec in records]
        for rec in records:
            if self.config.verify_artifacts:
                try:
                    verify_record(rec, "import")
                except IntegrityError as e:
                    self._num_import_refusals += 1
                    self._note_corruption("import", e.detail)
                    raise
            uid = rec["uid"]
            if uid in self._live_uids:
                raise ValueError(
                    f"cannot import uid {uid!r}: already waiting or "
                    "resident in this engine")
            if uid in self.statuses:
                raise ValueError(
                    f"cannot import uid {uid!r}: a terminal result "
                    "awaits drain here")
        for rec in records:
            deadline = rec.get("deadline_remaining_s")
            req = Request(
                uid=rec["uid"], prompt=list(rec["prompt"]),
                max_new_tokens=int(rec["max_new_tokens"]),
                sampling=SamplingParams(
                    temperature=rec["sampling"]["temperature"],
                    top_k=rec["sampling"]["top_k"],
                    top_p=rec["sampling"]["top_p"]),
                eos_token_id=rec.get("eos_token_id"),
                deadline_s=deadline,
                priority=int(rec.get("priority", 0)),
                tenant=str(rec.get("tenant", DEFAULT_TENANT)))
            if deadline is not None:
                # an already-blown deadline stays blown (<= now)
                self._deadline[req.uid] = now + float(deadline)
            arrival = rec.get("arrival")
            if arrival is None:
                arrival = self._arrival_count
            arrival = int(arrival)
            self._arrival_count = max(self._arrival_count, arrival + 1)
            # the uid lives HERE now: any stale source-side export
            # stamp of ours is superseded by this admission
            self._exported_arrivals.pop(req.uid, None)
            self._live_uids.add(req.uid)
            self._tenant_seen.add(req.tenant)
            self.waiting.append(_QueueEntry(
                request=req, arrival=arrival,
                generated=[int(t) for t in rec.get("generated", ())],
                enq_t=now, enq_tick=self._num_ticks,
                drr_charged=bool(rec.get("drr_charged", False))))
            if self._obs is not None:
                # anchor the migrated request's timeline exactly as
                # restore() anchors restored records (requeue, not
                # enqueue: its submit time belongs to the source)
                self._obs.note_enqueue(req.uid, tenant=req.tenant,
                                       priority=req.priority,
                                       prompt_len=len(req.prompt),
                                       requeue=True, t=now)
        self._num_migrated_in += len(records)
        self._queue_depth_peak = max(self._queue_depth_peak,
                                     len(self.waiting))
        return len(records)

    def export_prefix_payloads(self, hashes: Sequence[str]
                               ) -> Dict[str, Dict]:
        """The leading run of a hash chain as host payloads — the
        cross-replica KV transport (docs/fleet.md): device-indexed
        blocks read out through the spill fetch path, spilled ones
        through :meth:`~apex_tpu.serving.kv_cache.HostSpillStore.
        export_entry`. Stops at the first hash served by neither (a
        payload past a gap is unreachable, like the prefix match) or
        at the first failed device read (transport is an optimization,
        never a dependency — the importer just recomputes)."""
        out: Dict[str, Dict] = {}
        if not self.config.enable_prefix_caching:
            return out
        for h in hashes:
            b = self.allocator.indexed_block(h)
            if b is not None:
                payload = self._spill_payload(b, record=False)
            elif self.spill is not None:
                payload = self.spill.export_entry(h)
            else:
                payload = None
            if payload is None:
                break
            if self.config.verify_artifacts:
                # a detached content checksum rides the payload dict
                # (string-valued, skipped by the array checksum and by
                # the upload path) — the importer verifies the bytes
                # end to end across the transport
                payload = dict(payload)
                payload["checksum"] = payload_checksum(payload)
            out[h] = payload
        return out

    def import_prefix_payloads(self, payloads: Mapping[str, Dict]) -> int:
        """Seed this engine's spill tier with payloads another replica
        exported: the next admission matching those chain hashes
        re-admits them by device upload instead of recompute —
        token-identical, by the spill-tier equivalence cert. Hashes a
        device block already serves are skipped (the disjointness
        invariant); returns how many entries the tier accepted (0 with
        no spill tier configured — the transport is optional)."""
        if self.spill is None:
            return 0
        n = 0
        for h, payload in payloads.items():
            if self.allocator.indexed_block(h) is not None:
                continue
            payload = dict(payload)
            checksum = payload.pop("checksum", None)
            if self.config.verify_artifacts and checksum is not None:
                try:
                    verify_payload(payload, checksum, "import_payload")
                except IntegrityError as e:
                    # a corrupt transported block is SKIPPED, not
                    # refused: each payload is an independent cache
                    # seed, and a skip just means the importer
                    # recomputes that block (the tier's normal miss)
                    self._note_corruption("import_payload", e.detail)
                    continue
            if self.spill.import_entry(h, payload):
                n += 1
        return n

    # -- crash-consistent snapshot / restore (docs/robustness.md) ---------

    def _config_fingerprint(self) -> Dict[str, object]:
        """The engine config as JSON-able values; a snapshot only
        restores into an engine built with the identical config (the
        compiled-program shapes, pool geometry, and PRNG seed all hang
        off it — any drift breaks the bit-identity contract). The
        retry knobs are operational, not identity: an operator
        recovering from an incident may legitimately restore into an
        engine with a bigger retry budget or no backoff, and outputs
        are unaffected, so they stay out of the fingerprint. The
        overload knobs (queue bound, ladder watermarks, admission-pause
        class) are operational in the same sense — restoring into a
        replica with a bigger queue or different watermarks is exactly
        the incident-recovery move — so they stay out too."""
        d = dataclasses.asdict(self.config)
        d["kv_dtype"] = (None if self.config.kv_dtype is None
                         else str(jnp.dtype(self.config.kv_dtype)))
        # as a LIST, not a tuple: the fingerprint must compare equal
        # before and after riding the JSON wire (which has no tuples),
        # and mesh_shape IS identity — a sharded snapshot restores
        # across equal meshes only
        d["mesh_shape"] = [int(v) for v in self.config.mesh_shape]
        for knob in ("max_dispatch_retries", "retry_backoff_s",
                     # the spill tier is operational capacity tuning:
                     # a re-admitted block is certified token-identical
                     # to recompute, so restoring into a replica with a
                     # different (or no) spill bound changes nothing
                     # the fingerprint protects. kv_quantization AND
                     # weight_quantization STAY in the fingerprint:
                     # quantized outputs are not the fp outputs —
                     # storage mode IS identity.
                     "spill_max_bytes",
                     "max_waiting", "queue_high_watermark",
                     "free_block_low_watermark", "degrade_patience",
                     "degrade_admit_priority",
                     # the tenancy knobs are operational in the same
                     # sense: restoring into a replica with different
                     # weights or quotas is the incident-recovery move,
                     # and outputs are arrival-keyed (tenant-invariant)
                     "tenant_weights", "tenant_quotas", "drr_quantum",
                     "tenant_rate_tau_s",
                     # spec_adapt changes SCHEDULE (span boundaries),
                     # not identity; its cap state rides the overload
                     # section with the same config-guard as the ladder
                     "spec_adapt", "spec_accept_low",
                     "spec_accept_high",
                     # periodic checkpointing is pure observation of
                     # host state (checkpoint() never drains or
                     # mutates scheduling) — restoring into a replica
                     # with a different cadence changes nothing
                     "snapshot_interval_ticks",
                     # the integrity knobs are operational in the same
                     # sense: verification and scrubbing are pure
                     # detection on clean artifacts (certified
                     # bit-identical on or off), and restoring a
                     # verify-off snapshot into a verify-on engine is
                     # exactly the hardening-after-an-incident move
                     "verify_artifacts", "scrub_interval_ticks",
                     "scrub_spill_blocks"):
            d.pop(knob, None)
        return d

    def _entry_record(self, entry: _QueueEntry, now: float) -> Dict:
        req = entry.request
        rec = {
            "uid": req.uid,
            "prompt": [int(t) for t in req.prompt],
            "max_new_tokens": int(req.max_new_tokens),
            "eos_token_id": (None if req.eos_token_id is None
                             else int(req.eos_token_id)),
            "sampling": {"temperature": float(req.sampling.temperature),
                         "top_k": int(req.sampling.top_k),
                         "top_p": float(req.sampling.top_p)},
            "arrival": int(entry.arrival),
            "priority": int(req.priority),
            "tenant": str(req.tenant),
            "drr_charged": bool(entry.drr_charged),
            "generated": [int(t) for t in entry.generated],
        }
        dl = self._deadline.get(req.uid)
        if dl is not None:
            # deadlines serialize as REMAINING budget: the restoring
            # process re-anchors them on its own clock
            rec["deadline_remaining_s"] = float(dl - now)
        return rec

    def snapshot(self) -> Dict[str, object]:
        """Crash-consistent, JSON-serializable picture of the engine.

        Drains the in-flight decode first (one host sync), so no
        emitted token is ever lost to a snapshot boundary. Live slots
        serialize as preempted-style resumable entries — prompt,
        emitted tokens, arrival index (the PRNG identity) — in
        admission order, ahead of the waiting queue; ``finished``,
        terminal statuses, remaining deadline budgets, and the config
        fingerprint ride along. The block tables and allocator state
        (refcounts, prefix index, LRU order) are included as an AUDIT
        section: :meth:`restore` deliberately does not reload them,
        because KV block contents do not survive a process — the
        restored engine re-prefills through the prefix cache and
        rebuilds them (bit-identically, by resume determinism)."""
        self._drain_decode()
        self._num_snapshots += 1
        snap = self._build_snapshot()
        if self._obs is not None:
            self._obs.record("snapshot", requests=len(snap["requests"]))
        return snap

    def checkpoint(self) -> Dict[str, object]:
        """The LIGHTWEIGHT snapshot variant (docs/fleet.md): the same
        restore()-loadable picture as :meth:`snapshot`, built WITHOUT
        draining the in-flight decode dispatch — no host sync, so a
        periodic caller (``snapshot_interval_ticks``, or a fleet
        router's health loop) never stalls the pipeline. The price is
        bounded staleness: tokens riding the undrained dispatch (at
        most ``decode_steps``/``spec_tokens + 1`` per lane) are absent
        from the records and are RE-DERIVED bit-identically on restore
        (resume determinism — the records carry prompt + emitted
        history + the arrival PRNG identity). The result is stored on
        ``last_checkpoint`` — the failover picture a fleet router
        reads when this replica dies — and also returned."""
        self._num_checkpoints += 1
        snap = self._build_snapshot(lightweight=True)
        # the chaos seam (docs/robustness.md): a "corrupt" spec at the
        # "checkpoint" site rots the just-sealed record — the fleet's
        # failover verification must then refuse it and fall back to
        # fresh re-injection
        snap = self._maybe_corrupt_record("checkpoint", snap)
        self.last_checkpoint = snap
        if self._obs is not None:
            self._obs.record("snapshot", requests=len(snap["requests"]),
                             lightweight=True)
        return snap

    def _build_snapshot(self, lightweight: bool = False
                        ) -> Dict[str, object]:
        """The shared snapshot/checkpoint body: pure host-state READS
        (plus the counter the caller already bumped) — nothing here
        drains, allocates, or touches scheduling state, which is what
        makes :meth:`checkpoint` safe on every tick and callable even
        from a replica whose last dispatch just raised."""
        now = self._clock()
        live = sorted((s.admit_seq, i) for i, s in enumerate(self.slots)
                      if s is not None)
        requests = []
        for _, i in live:
            slot = self.slots[i]
            requests.append(self._entry_record(
                _QueueEntry(request=slot.request, arrival=slot.entry.arrival,
                            generated=self._resume_tokens(slot),
                            # a resident's DRR cost was paid at its
                            # admission: restore re-admits it free,
                            # leaving the serialized walk untouched
                            drr_charged=True), now))
        for entry in self.waiting:
            requests.append(self._entry_record(entry, now))
        snap = {
            "version": 1,
            "config": self._config_fingerprint(),
            "arrival_count": int(self._arrival_count),
            "requests": requests,
            "finished": {uid: [int(t) for t in toks]
                         for uid, toks in self.finished.items()},
            "statuses": dict(self.statuses),
            "counters": self.stats(),
            # behavioral, not audit: a quarantined drafter must STAY
            # quarantined across restore — resumed speculation would
            # draw accept/resample uniforms the uninterrupted
            # (empty-plan) run never drew, breaking sampled-lane
            # restore bit-identity
            "drafter_ok": bool(self._drafter_ok),
            # behavioral too: a restored engine continues the SAME
            # ladder walk (its rung gates speculation and admission),
            # streaks included so hysteresis resumes mid-count — and
            # the feasibility-gate EWMAs ride along, or the restored
            # gate would reopen blind and admit doomed tight-deadline
            # requests at exactly the moment load is highest (restore
            # re-queues every previously resident request)
            "overload": {
                "degradation_level": int(self._degradation_level),
                "pressure_streak": int(self._pressure_streak),
                "clear_streak": int(self._clear_streak),
                "ewma_prefill_s": self._ewma_prefill_s,
                "ewma_decode_s": self._ewma_decode_s,
                # the dynamic-speculation refinement rides here too: a
                # restored engine resumes the same cap walk (sampled
                # lanes' realized draws depend on span boundaries, so
                # silently resetting the cap would break restore
                # bit-identity under spec_adapt)
                "spec_cap": int(self._spec_cap),
                "spec_accept_ewma": self._spec_accept_ewma,
                "spec_probe_countdown": int(self._spec_probe_countdown),
            },
            # the tenant ledger: DRR walk state per class (ring order
            # is implied by the requests' serialization order), the
            # token-rate estimators (ages re-anchor on the restoring
            # clock, like deadlines), and the observability tallies
            "tenancy": {
                "classes": self.waiting.snapshot_state(),
                "rates": {t: {"rate": float(r),
                              "age_s": float(now - self._tenant_rate_t[t])}
                          for t, r in self._tenant_rate.items()},
                "tokens": {t: int(n)
                           for t, n in self._tenant_tokens.items()},
                "status_counts": {t: dict(c) for t, c in
                                  self._tenant_status.items()},
                "preemptions": dict(self._tenant_preemptions),
                "seen": sorted(self._tenant_seen),
            },
            "block_tables": {
                self.slots[i].request.uid: [int(b) for b in
                                            self.slots[i].blocks]
                for _, i in live},
            "allocator": self.allocator.snapshot_state(),
        }
        if self.spill is not None:
            # AUDIT-ONLY, like the allocator section: spilled K/V
            # bytes do not ride a JSON snapshot and restore() never
            # reads this — a restored engine starts with an empty
            # spill tier and re-warms it (hits are an optimization,
            # never identity; the fingerprint excludes the knob). The
            # scrub cursor rides here under the same policy: the
            # restored store is empty, so the walk restarts.
            snap["spill"] = dict(self.spill.stats(), audit_only=True,
                                 hits=int(self._spill_hits),
                                 misses=int(self._spill_misses),
                                 scrub_cursor=int(
                                     self.spill._scrub_cursor))
        if self._obs is not None:
            # AUDIT-ONLY, like the block tables: the flight-recorder
            # tail and trace depth ride along for post-mortems, and
            # restore() deliberately never reads this section —
            # observer state must not influence a restored engine
            # (the zero-perturbation contract), and it is excluded
            # from the config fingerprint for the same reason
            audit = {"audit_only": True}
            if self._obs.recorder is not None:
                audit["recorder_tail"] = self._obs.recorder.tail()
                audit["recorder_dropped"] = self._obs.recorder.dropped
            if self._obs.tracer is not None:
                audit["trace_events"] = len(self._obs.tracer)
            snap["observability"] = audit
        if lightweight:
            snap["lightweight"] = True
        # sealed LAST (docs/robustness.md, "Data integrity"): the
        # embedded checksum covers every field above, survives the
        # JSON wire format bit-for-bit, and is verified by restore()
        # and by the fleet router before a failover trusts the record
        return seal_record(snap)

    def restore(self, snap: Dict[str, object]) -> None:
        """Load a :meth:`snapshot` into a FRESHLY constructed engine
        (same model, params, and config — the fingerprint is checked,
        the params are the caller's contract). Every unfinished request
        re-enters the waiting queue in snapshot order carrying its
        emitted tokens and original arrival index, so re-admission
        re-prefills ``prompt + generated[:-1]`` (cheap when its blocks
        are still/again cached) and the schedule-invariant sampler
        continues the exact token stream: a restored ``run()`` is
        bit-identical to the uninterrupted one (tested, including
        across processes)."""
        # integrity FIRST (docs/robustness.md): a sealed snapshot must
        # verify before ANY field of it is believed — including the
        # version number, which is itself a corruptible numeric leaf
        # (acting on it first would mis-report a detected corruption
        # as "unknown version" and dodge the detection counter). A
        # corrupt snapshot refuses to restore (the operator recovers
        # from an older artifact, a fleet router falls back to fresh
        # re-injection); checksum-less legacy snapshots load as
        # before — detection covers sealed artifacts only.
        if self.config.verify_artifacts:
            try:
                verify_record(snap, "restore")
            except IntegrityError as e:
                self._note_corruption("restore", e.detail)
                raise
        if snap.get("version") != 1:
            raise ValueError(f"unknown snapshot version {snap.get('version')!r}")
        mine, theirs = self._config_fingerprint(), dict(snap["config"])
        # compare by .get() so a knob ADDED since the snapshot was
        # taken (absent key) equals its None default — an older
        # snapshot restores into an engine that leaves the new knob
        # off, which is exactly the config it ran under
        diff = {k: (theirs.get(k), mine.get(k))
                for k in set(mine) | set(theirs)
                if mine.get(k) != theirs.get(k)}
        if diff:
            raise ValueError(
                f"snapshot config mismatch (snapshot vs engine): {diff}")
        if self.has_work or self._arrival_count or self.finished:
            raise RuntimeError(
                "restore() requires a fresh engine: this one has queued, "
                "resident, in-flight, or finished requests")
        now = self._clock()
        for rec in snap["requests"]:
            deadline = rec.get("deadline_remaining_s")
            req = Request(
                uid=rec["uid"], prompt=list(rec["prompt"]),
                max_new_tokens=int(rec["max_new_tokens"]),
                sampling=SamplingParams(
                    temperature=rec["sampling"]["temperature"],
                    top_k=rec["sampling"]["top_k"],
                    top_p=rec["sampling"]["top_p"]),
                eos_token_id=rec.get("eos_token_id"),
                deadline_s=deadline,
                priority=int(rec.get("priority", 0)),
                tenant=str(rec.get("tenant", DEFAULT_TENANT)))
            if deadline is not None:
                # an already-blown deadline stays blown (<= now)
                self._deadline[req.uid] = now + deadline
            self._live_uids.add(req.uid)
            self._tenant_seen.add(req.tenant)
            self.waiting.append(_QueueEntry(
                request=req, arrival=int(rec["arrival"]),
                generated=[int(t) for t in rec["generated"]],
                enq_t=now, enq_tick=self._num_ticks,
                drr_charged=bool(rec.get("drr_charged", False))))
            if self._obs is not None:
                # anchor the restored request's timeline (requeue, not
                # enqueue: no fresh-request counter, no TTFT state —
                # its true submit time belongs to the dead process)
                self._obs.note_enqueue(req.uid, tenant=req.tenant,
                                       priority=req.priority,
                                       prompt_len=len(req.prompt),
                                       requeue=True, t=now)
        self._arrival_count = int(snap["arrival_count"])
        self.finished.update({uid: [int(t) for t in toks]
                              for uid, toks in snap["finished"].items()})
        self.statuses.update(snap["statuses"])
        # drafter-quarantine state is behavioral (see snapshot): a
        # pre-quarantine snapshot restores with speculation live, a
        # post-quarantine one stays degraded — either way the restored
        # token stream matches the uninterrupted run. The drafter
        # OBJECT itself is the caller's contract, like params: restore
        # with an equivalent (pure-function-of-history) drafter.
        self._drafter_ok = (bool(snap["drafter_ok"])
                            and self.config.spec_tokens > 0)
        # the ladder resumes where the snapshot left it — rungs gate
        # speculation and admission, so a restore mid-degradation must
        # not silently jump back to full service (the restoring
        # engine's own watermarks walk it up when pressure clears).
        # UNLESS this engine's ladder is disabled (no watermarks — the
        # overload knobs are legitimately restorable-across, like the
        # retry knobs): _update_ladder could then never step the rung
        # back up, leaving speculation/admission degraded FOREVER —
        # same config-mismatch guard as drafter_ok above
        overload = snap.get("overload", {})
        if self._ladder_enabled():
            self._degradation_level = int(
                overload.get("degradation_level", 0))
            self._pressure_streak = int(overload.get("pressure_streak", 0))
            self._clear_streak = int(overload.get("clear_streak", 0))
        # the gate's estimators restore UNCONDITIONALLY (they exist
        # independent of the ladder): a blind re-opened gate would
        # admit doomed deadlines right when the requeued backlog is
        # largest. Absent keys (older snapshots) leave the gate open.
        for attr, key in (("_ewma_prefill_s", "ewma_prefill_s"),
                          ("_ewma_decode_s", "ewma_decode_s")):
            v = overload.get(key)
            if v is not None:
                setattr(self, attr, float(v))
        # the dynamic-speculation cap resumes its walk ONLY when this
        # engine adapts too (same guard shape as the ladder rung: a
        # non-adapting engine could never restore the cap, leaving
        # speculation degraded forever)
        if self.config.spec_adapt:
            self._spec_cap = int(overload.get("spec_cap",
                                              self.config.spec_tokens))
            ewma = overload.get("spec_accept_ewma")
            if ewma is not None:
                self._spec_accept_ewma = float(ewma)
            self._spec_probe_countdown = int(
                overload.get("spec_probe_countdown", _SPEC_PROBE_EVERY))
        # the tenant ledger: DRR walk state re-anchors after the
        # re-appends above (serialized ring order wins; restored
        # residents' tenants join at ring tails), rate estimators
        # re-anchor their ages on this clock, tallies carry over
        tenancy = snap.get("tenancy", {})
        self.waiting.restore_state(tenancy.get("classes", {}))
        for t, rec in (tenancy.get("rates") or {}).items():
            self._tenant_rate[t] = float(rec["rate"])
            self._tenant_rate_t[t] = now - max(0.0, float(rec["age_s"]))
        for t, n in (tenancy.get("tokens") or {}).items():
            self._tenant_tokens[t] = int(n)
        for t, counts in (tenancy.get("status_counts") or {}).items():
            self._tenant_status[t] = {s: int(c)
                                      for s, c in counts.items()}
        for t, n in (tenancy.get("preemptions") or {}).items():
            self._tenant_preemptions[t] = int(n)
        self._tenant_seen.update(tenancy.get("seen", ()))
        # the snapshot's "observability" audit section (if any) is
        # deliberately NOT read: observer state never shapes behavior
        self._num_restores += 1
        if self._obs is not None:
            self._obs.record("restore", requests=len(snap["requests"]))

    # -- mesh program-shape audit (docs/serving.md, "Mesh sharding") -------

    def program_collective_stats(self, program: str) -> Dict[str, Dict]:
        """Collective ops/bytes of one compiled engine program
        (:func:`apex_tpu.utils.hlo_audit.collective_stats`), lowered
        from ABSTRACT arguments at the program's real call shapes and
        the engine's committed shardings — no dispatch runs, and the
        explicit AOT lowering leaves the jit call caches (the pinned
        ``*_compilations`` counters) untouched. ``program``:
        ``"prefill"``, ``"decode"``, or ``"verify"`` (the last two are
        the same jit slot — ``"verify"`` just insists speculation is
        on, so a contract test cannot silently audit the wrong
        program)."""
        B = self.config.max_batch
        M = self.max_blocks_per_seq

        def i32(shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        def f32(shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32)

        def abstract(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=getattr(x, "sharding",
                                                         None))

        aparams = jax.tree.map(abstract, self.params)
        acache = jax.tree.map(abstract, self.cache)
        if program == "prefill":
            C = self._chunk
            fn, args = self._prefill, (
                aparams, acache, i32((1, C)), i32((1, C)), i32((1,)),
                i32((1,)), i32((1,)), i32((1, M)),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
                f32((1,)), i32((1,)), f32((1,)))
        elif program in ("decode", "verify"):
            if program == "verify" and self.config.spec_tokens < 1:
                raise ValueError(
                    "program 'verify' requires spec_tokens >= 1 (the "
                    "decode slot holds the plain scan otherwise)")
            keys = jax.ShapeDtypeStruct((B, 2), jnp.uint32)
            if self.config.spec_tokens > 0:
                S = self.config.spec_tokens
                args = (aparams, acache, i32((B,)), i32((B, S)),
                        i32((B,)), i32((B, M)), i32((B,)), i32((B,)),
                        i32((B,)), i32((B,)), keys, f32((B,)),
                        i32((B,)), f32((B,)))
            else:
                args = (aparams, acache, i32((B,)), i32((B, M)),
                        i32((B,)), i32((B,)), i32((B,)), i32((B,)),
                        keys, f32((B,)), i32((B,)), f32((B,)))
            fn = self._decode
        else:
            raise ValueError(
                f"unknown program {program!r} (expected 'prefill', "
                "'decode', or 'verify')")
        from apex_tpu.utils.hlo_audit import collective_stats

        return collective_stats(fn.lower(*args).compile().as_text())

    def audit_collectives(self) -> Dict[str, Dict[str, Dict]]:
        """Check every compiled program against the mesh's collective
        contract (:func:`apex_tpu.serving.mesh.expected_collectives`):
        zero collectives while the model axis is 1 (the bit-identity
        precondition), reduction traffic — and nothing exotic — once
        the heads split. Raises ``AssertionError`` on violation;
        returns ``{program: collective_stats}`` for reporting."""
        from apex_tpu.utils.hlo_audit import assert_collective_contract

        contract = mesh_lib.expected_collectives(self.config.mesh_shape)
        out = {}
        programs = ["prefill",
                    "verify" if self.config.spec_tokens > 0 else "decode"]
        for prog in programs:
            stats = self.program_collective_stats(prog)
            assert_collective_contract(
                stats,
                label=f"{prog}@mesh{tuple(self.config.mesh_shape)}",
                **contract)
            out[prog] = stats
        return out

    def check_allocator_integrity(self) -> None:
        """Cross-check the allocator against the engine's own
        bookkeeping: internal invariants plus an EXACT refcount match —
        each block's count must equal the number of resident slots
        referencing it (chaos tests call this after restore + LRU
        churn). The per-tenant reference split is cross-checked too:
        each block's tenant refs must equal the residents referencing
        it, split by their tenants — the certification that aborts,
        quota sheds, and preemptions reclaimed exactly what they
        owned. With a sharded ``batch`` axis (``mesh_shape[0] > 1``)
        every resident's blocks must additionally live on its LANE's
        shard — the invariant the sharded programs' subtraction
        localization silently depends on (a foreign block would read
        masked garbage, not raise)."""
        expected: Dict[int, int] = {}
        expected_tenants: Dict[int, Dict[str, int]] = {}
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            t = slot.request.tenant
            for b in slot.blocks:
                expected[b] = expected.get(b, 0) + 1
                per = expected_tenants.setdefault(b, {})
                per[t] = per.get(t, 0) + 1
                if (self._batch_shards > 1
                        and self.allocator.shard_of(b)
                        != self._lane_shard(i)):
                    raise AssertionError(
                        f"slot {i} (shard {self._lane_shard(i)}) holds "
                        f"block {b} on shard "
                        f"{self.allocator.shard_of(b)}: batch-axis "
                        "shard residency violated")
        self.allocator.check_integrity(
            expected_refcounts=expected,
            expected_tenant_refs=expected_tenants)

    def stats(self, deep: bool = False) -> Dict[str, object]:
        """The observability counters. Honest typing note: despite its
        long life as ``Dict[str, float]``, the dict has carried the
        NESTED per-tenant ledger (``"tenants"``) since PR 9 — the
        value type is ``object``; flatten nested sections with
        :func:`apex_tpu.observability.flatten_stats` when a scalar
        map is needed. ``deep=True`` additionally merges the attached
        observer's section (metric values, recorder/trace depths)
        under ``"observability"`` — absent entirely when no observer
        is attached or at the default ``deep=False``."""
        alloc = self.allocator
        lookups = self._prefix_lookup_blocks
        out = {
            "prefill_compilations": self._prefill._cache_size(),
            "decode_compilations": self._decode._cache_size(),
            # the GSPMD mesh the programs compiled under (docs/
            # serving.md "Mesh sharding"): static per config, so equal
            # configs keep full-stats identity certs byte-comparable
            "mesh_devices": (self.config.mesh_shape[0]
                             * self.config.mesh_shape[1]),
            "mesh_model_axis": self.config.mesh_shape[1],
            "mesh_batch_axis": self.config.mesh_shape[0],
            # the storage quantization modes (docs/serving.md memory
            # tiers): static per config like the mesh keys — equal
            # configs keep full-stats identity certs byte-comparable —
            # closing the asymmetry where the modes rode the restore
            # fingerprint but no observable surface
            "kv_quantization": self.config.kv_quantization,
            "weight_quantization": self.config.weight_quantization,
            "num_prefills": self._num_prefills,
            "num_prefill_chunks": self._num_prefill_chunks,
            "num_decode_dispatches": self._num_decode_dispatches,
            # tokens actually emitted by decode dispatches (drained
            # ones; an in-flight dispatch counts after its sync). The
            # dispatches:tokens ratio is the multi-step amortization.
            "num_tokens_decoded": self._num_tokens_decoded,
            # back-compat alias: pre-multistep dashboards/tests read
            # num_decode_steps, which meant DISPATCHES (at K=1 the two
            # were indistinguishable)
            "num_decode_steps": self._num_decode_dispatches,
            "decode_table_rebuilds": self._table_rebuilds,
            "num_preemptions": self._num_preemptions,
            "num_cow_copies": self._num_cow_copies,
            "num_cache_evictions": alloc.num_evictions,
            "active_slots": sum(s is not None for s in self.slots),
            "waiting": len(self.waiting),
            "cache_utilization": alloc.utilization,
            "blocks_free": alloc.num_free,
            "blocks_cached": alloc.num_cached,
            "blocks_active": alloc.num_used,
            "prefix_lookup_blocks": lookups,
            "prefix_hit_blocks": self._prefix_hit_blocks,
            "prefix_cache_hit_rate": (self._prefix_hit_blocks / lookups
                                      if lookups else 0.0),
            "prompt_blocks_allocated": self._prompt_blocks_allocated,
            # the host-RAM spill tier (docs/serving.md memory tiers):
            # current residency, lifetime traffic, and the re-admit
            # hit rate — all zero with the tier off
            # `is not None`, not truthiness: the store defines __len__
            # and an empty (fully re-admitted) store is falsy
            "spill_blocks": (len(self.spill) if self.spill is not None
                             else 0),
            "spill_bytes": (self.spill.total_bytes
                            if self.spill is not None else 0),
            "num_blocks_spilled": (self.spill.puts
                                   if self.spill is not None else 0),
            "num_spill_evictions": (self.spill.evictions
                                    if self.spill is not None else 0),
            "spill_hits": self._spill_hits,
            "spill_misses": self._spill_misses,
            "spill_hit_rate": (
                self._spill_hits
                / (self._spill_hits + self._spill_misses)
                if self._spill_hits + self._spill_misses else 0.0),
            # the uniform spill refusal/corruption surface + the data-
            # integrity counters (docs/robustness.md "Data integrity"):
            # oversize puts the store refused, entries discarded on a
            # checksum mismatch, total detections across every
            # verification point, refused migration imports, and the
            # background scrub's cadence/coverage
            "num_spill_refused": (self.spill.refused
                                  if self.spill is not None else 0),
            "num_spill_corrupt_discards": (
                self.spill.corrupt_discards
                if self.spill is not None else 0),
            "num_corruptions_detected": self._num_corruptions_detected,
            "num_import_refusals": self._num_import_refusals,
            "num_scrubs": self._num_scrubs,
            "num_scrub_blocks_verified": self._num_scrub_blocks_verified,
            # robustness counters (docs/robustness.md): every failure
            # path feeds one, so chaos runs are assertable from stats()
            "num_timeouts": self._num_timeouts,
            "num_dispatch_retries": self._num_dispatch_retries,
            "num_quarantines": self._num_quarantines,
            "num_snapshots": self._num_snapshots,
            "num_restores": self._num_restores,
            # fleet serving (docs/fleet.md): the periodic lightweight
            # checkpoint cadence and the drain-and-migrate traffic
            # through this replica
            "num_checkpoints": self._num_checkpoints,
            "num_migrated_in": self._num_migrated_in,
            "num_migrated_out": self._num_migrated_out,
            # overload observability (docs/robustness.md): queue depth
            # and wait, shed counters, and the degradation ladder —
            # overload must be visible HERE before the first timeout
            # ever fires
            "num_ticks": self._num_ticks,
            "queue_depth": len(self.waiting),
            "queue_depth_peak": self._queue_depth_peak,
            "queue_wait_mean_ticks": (
                self._queue_wait_ticks_sum / self._queue_wait_count
                if self._queue_wait_count else 0.0),
            "queue_wait_max_ticks": self._queue_wait_ticks_max,
            "queue_wait_mean_s": (
                self._queue_wait_s_sum / self._queue_wait_count
                if self._queue_wait_count else 0.0),
            "queue_wait_max_s": self._queue_wait_s_max,
            "num_rejected_queue_full": self._num_rejected_queue_full,
            "num_rejected_infeasible": self._num_rejected_infeasible,
            "ewma_prefill_dispatch_s": float(self._ewma_prefill_s or 0.0),
            "ewma_decode_dispatch_s": float(self._ewma_decode_s or 0.0),
            "degradation_level": self._degradation_level,
            "num_degrade_steps_down": self._num_degrade_steps_down,
            "num_degrade_steps_up": self._num_degrade_steps_up,
            "num_degrade_flushed_blocks": self._num_degrade_flushed_blocks,
            "admission_paused": int(
                self._admission_priority_limit() is not None),
            # speculative decoding (docs/serving.md): proposed vs
            # accepted draft tokens — the acceptance rate is THE
            # speculation health metric (tokens per target forward =
            # 1 + rate * spec_tokens, roughly); speculation_active
            # drops to 0 when a crashing drafter was quarantined
            "num_draft_tokens": self._num_draft_tokens,
            "num_accepted_tokens": self._num_accepted_tokens,
            "draft_acceptance_rate": (
                self._num_accepted_tokens / self._num_draft_tokens
                if self._num_draft_tokens else 0.0),
            "num_draft_retries": self._num_draft_retries,
            "num_drafter_quarantines": self._num_drafter_quarantines,
            "num_spec_blocks_rolled_back":
                self._num_spec_blocks_rolled_back,
            # 0 while quarantined (permanent) OR suspended by the
            # degradation ladder (reversible)
            "speculation_active": int(self._drafter_ok
                                      and self._degradation_level < 1),
            # dynamic speculation (spec_adapt): the adaptive per-plan
            # cap, the acceptance EWMA driving it, and its transitions
            "spec_cap": self._spec_cap,
            "spec_accept_ewma": float(self._spec_accept_ewma or 0.0),
            "num_spec_cap_shrinks": self._num_spec_cap_shrinks,
            "num_spec_cap_restores": self._num_spec_cap_restores,
            # multi-tenant isolation (docs/robustness.md): the global
            # shed/cancel counters, the streaming backlog, and the
            # per-tenant ledger
            "num_throttled": self._num_throttled,
            "num_cancelled": self._num_cancelled,
            "stream_backlog": len(self._stream),
            "tenants": self._tenant_section(),
        }
        if deep and self._obs is not None:
            out["observability"] = self._obs.deep_stats()
        return out

    def _tenant_section(self) -> Dict[str, Dict[str, object]]:
        """``stats()["tenants"]``: one row per tenant ever seen —
        delivered tokens, the decayed rate estimate, current queue and
        residency footprint (fractional block charge), the
        eviction/flush attribution, quota preemptions, and terminal
        statuses. The numbers an operator needs to tell WHICH tenant
        is eating the replica."""
        alloc_ts = self.allocator.tenant_stats()
        out: Dict[str, Dict[str, object]] = {}
        for t in sorted(self._tenant_seen | set(alloc_ts)):
            a = alloc_ts.get(t, {})
            out[t] = {
                "tokens": self._tenant_tokens.get(t, 0),
                "rate_tokens_per_s": round(self._tenant_rate_now(t), 6),
                "waiting": self.waiting.tenant_depth(t),
                "resident_slots": sum(
                    1 for s in self.slots
                    if s is not None and s.request.tenant == t),
                "resident_block_charge":
                    a.get("resident_block_charge", 0.0),
                "cached_blocks": a.get("cached_blocks", 0),
                "evicted_blocks": a.get("evicted_blocks", 0),
                "flushed_blocks": a.get("flushed_blocks", 0),
                "quota_preemptions": self._tenant_preemptions.get(t, 0),
                "statuses": dict(self._tenant_status.get(t, {})),
            }
        return out
