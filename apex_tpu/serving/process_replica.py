"""Out-of-process replicas: the parent-side handle and the
serialization layer (docs/fleet.md, "Process replicas").

:class:`ProcessReplica` runs one :class:`~apex_tpu.serving.engine.
InferenceEngine` in a CHILD OS PROCESS (``python -m apex_tpu.serving.
replica_worker``) and exposes the exact in-process replica surface —
``add_request`` / ``step`` / ``load`` / ``probe_prefix`` /
``export_requests`` / ``import_requests`` / ``pop_results`` /
``pop_stream_events`` / ``abort`` / ``checkpoint`` /
``export_prefix_payloads`` / ``import_prefix_payloads`` / ``stats`` —
as RPCs over the :mod:`~apex_tpu.serving.wire` frame protocol on the
child's stdio, so :class:`~apex_tpu.serving.fleet.FleetRouter` drives
process replicas and in-process engines through ONE code path and a
1-process-replica fleet certifies bit-identical to the in-process
fleet (tests/test_process_replica.py).

The failure contract mirrors the in-process one deliberately:

- engine-level refusals come back as the REAL exception types
  (``QueueFullError``, ``TenantThrottledError``, ``ValueError``,
  ``IntegrityError`` with its site/detail) so the router's door
  logic, import-refusal handling, and zero-lost accounting apply
  unchanged;
- a torn or rotted RESPONSE frame (``IntegrityError`` from the wire)
  is retried by resending the SAME request id up to ``rpc_retries``
  times — the worker's at-most-once dedupe answers a duplicate id
  from its response cache WITHOUT re-executing, so a retried
  ``add_request`` can never double-enqueue;
- an unresponsive child (:class:`~apex_tpu.serving.wire.
  WireTimeoutError`), a closed pipe, or exhausted retries mark the
  handle DEAD and raise :class:`ReplicaUnavailableError` — which
  escapes the router's ``step()`` exactly like an in-process engine
  exception and drives the existing ``_fail_replica`` checkpoint
  failover. The parent caches every checkpoint the child piggybacks
  on its ``step()`` responses in :attr:`ProcessReplica.
  last_checkpoint`, so failover-from-checkpoint reads host-side
  state even when the child died mid-SIGKILL.

Terminal statuses: the in-process engine writes terminal status onto
the caller's own :class:`Request` object; a child can only mutate its
deserialized copy, so the handle mirrors the status onto the original
object when the verdict drains through ``pop_results`` (and
immediately for a door ``throttled``). Requests that migrate away via
``export_requests`` stop being mirrored — identical to the in-process
fleet, where an imported request is a fresh object too.

Everything here and in the worker speaks JSON-able records; numpy
payloads ride :func:`wire.encode_arrays`. The frame/RPC layer itself
is stdlib-only — jax/numpy appear only inside the engine-facing
serialization helpers.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from apex_tpu.serving import wire
from apex_tpu.serving.engine import (
    DEFAULT_TENANT,
    EngineConfig,
    QueueFullError,
    Request,
    RequestResult,
    TenantQuota,
    TenantThrottledError,
)
from apex_tpu.serving.sampling import SamplingParams
from apex_tpu.utils.faults import (
    FaultPlan,
    plan_record,
    split_plan,
    validate_wire_specs,
    wire_chaos,
)
from apex_tpu.utils.integrity import IntegrityError

# a child boots jax + compiles nothing until first step, but the
# import alone is tens of seconds on a cold cache — the handshake gets
# its own generous budget, separate from the per-RPC timeout
DEFAULT_BOOT_TIMEOUT_S = 300.0
DEFAULT_RPC_TIMEOUT_S = 300.0
DEFAULT_RPC_RETRIES = 2


class ReplicaUnavailableError(RuntimeError):
    """The child replica process is dead or unresponsive (closed pipe,
    RPC timeout, or frame retries exhausted). Escapes the router's
    ``step()`` like any in-process engine failure and drives the
    checkpoint-failover path."""


class RemoteEngineError(RuntimeError):
    """A child-side exception with no richer local mapping (the mapped
    types — queue/tenant sheds, ``ValueError``, ``IntegrityError`` —
    re-raise as themselves)."""


# -- serialization: configs, requests, models, clocks -----------------------


def engine_config_record(config: EngineConfig) -> Dict:
    """An :class:`EngineConfig` as a JSON-able record — every field,
    operational knobs included (the child must run the SAME engine,
    not just a fingerprint-equal one). ``kv_dtype`` flattens to its
    canonical dtype string, ``tenant_quotas`` to plain dicts."""
    import dataclasses

    import jax.numpy as jnp

    rec = {}
    for f in dataclasses.fields(EngineConfig):
        v = getattr(config, f.name)
        if f.name == "kv_dtype":
            v = None if v is None else str(jnp.dtype(v))
        elif f.name == "mesh_shape":
            v = None if v is None else [int(x) for x in v]
        elif f.name == "tenant_quotas" and v is not None:
            v = {t: {"max_waiting": q.max_waiting,
                     "max_resident_blocks": q.max_resident_blocks,
                     "tokens_per_s": q.tokens_per_s}
                 for t, q in v.items()}
        elif f.name == "tenant_weights" and v is not None:
            v = {t: float(w) for t, w in v.items()}
        rec[f.name] = v
    return rec


def engine_config_from_record(rec: Dict) -> EngineConfig:
    """Invert :func:`engine_config_record`. ``EngineConfig.
    __post_init__`` re-validates everything, so a rotted record fails
    loudly at construction. A dtype STRING stays a string — jax
    accepts it everywhere a dtype goes, and the config fingerprint
    canonicalizes through ``jnp.dtype`` anyway."""
    kw = dict(rec)
    if kw.get("mesh_shape") is not None:
        kw["mesh_shape"] = tuple(int(x) for x in kw["mesh_shape"])
    if kw.get("tenant_quotas") is not None:
        kw["tenant_quotas"] = {
            t: TenantQuota(max_waiting=q.get("max_waiting"),
                           max_resident_blocks=q.get("max_resident_blocks"),
                           tokens_per_s=q.get("tokens_per_s"))
            for t, q in kw["tenant_quotas"].items()}
    return EngineConfig(**kw)


def request_record(req: Request) -> Dict:
    """A :class:`Request` as the JSON-able shape ``add_request`` ships
    to the child (original ``deadline_s`` budget — the child's door
    anchors it, exactly as the in-process door would)."""
    return {
        "uid": req.uid,
        "prompt": [int(t) for t in req.prompt],
        "max_new_tokens": int(req.max_new_tokens),
        "eos_token_id": (None if req.eos_token_id is None
                         else int(req.eos_token_id)),
        "sampling": {"temperature": float(req.sampling.temperature),
                     "top_k": int(req.sampling.top_k),
                     "top_p": float(req.sampling.top_p)},
        "deadline_s": (None if req.deadline_s is None
                       else float(req.deadline_s)),
        "priority": int(req.priority),
        "tenant": str(req.tenant),
    }


def request_from_record(rec: Dict) -> Request:
    s = rec.get("sampling") or {}
    return Request(
        uid=rec["uid"], prompt=list(rec["prompt"]),
        max_new_tokens=int(rec["max_new_tokens"]),
        sampling=SamplingParams(
            temperature=float(s.get("temperature", 0.0)),
            top_k=int(s.get("top_k", 0)),
            top_p=float(s.get("top_p", 1.0))),
        eos_token_id=rec.get("eos_token_id"),
        deadline_s=rec.get("deadline_s"),
        priority=int(rec.get("priority", 0)),
        tenant=str(rec.get("tenant", DEFAULT_TENANT)))


def gpt_model_spec(cfg, init_seed: int = 0, init_len: int = 8) -> Dict:
    """A GPT model + its seeded init as a JSON-able spec: the child
    rebuilds the SAME weights from the same PRNG key, and the parent's
    ``params_checksum`` handshake proves it did (a spec drifting from
    the parent's params is refused at boot, not discovered as an SDC
    mystery later)."""
    import dataclasses

    import jax.numpy as jnp

    d = dataclasses.asdict(cfg)
    d["dtype"] = str(jnp.dtype(d["dtype"]))
    return {"family": "gpt", "config": d,
            "init_seed": int(init_seed), "init_len": int(init_len)}


def build_model_from_spec(spec: Dict):
    """``(model, params)`` from a :func:`gpt_model_spec` record — the
    child's half of the weight handshake (also usable parent-side to
    build the router's own copy from the same spec)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTConfig, GPTLMHeadModel

    family = spec.get("family")
    if family != "gpt":
        raise ValueError(f"unknown model family {family!r} in model "
                         "spec (supported: 'gpt')")
    d = dict(spec["config"])
    d["dtype"] = jnp.dtype(d.get("dtype", "float32"))
    model = GPTLMHeadModel(GPTConfig(**d))
    params = model.init(
        jax.random.PRNGKey(int(spec.get("init_seed", 0))),
        jnp.zeros((1, int(spec.get("init_len", 8))), jnp.int32))
    return model, params


def params_checksum(params, weight_quantization: Optional[str] = None) -> str:
    """SHA-256 over every weight leaf (path-keyed, order-independent)
    via the house :func:`~apex_tpu.utils.integrity.payload_checksum` —
    the boot-time proof that parent and child hold bit-identical
    weights.

    ``weight_quantization`` makes the checksum cover the QUANTIZED
    representation the engine actually serves: the fp tree is
    re-expressed via :func:`~apex_tpu.models.gpt.quantize_gpt_params`
    (deterministic round-to-nearest, so equal fp weights always hash
    equal) and the mode itself is folded in as an extra leaf — a
    replica booted with a mismatched mode computes a different
    checksum from the same spec and is refused at hello, instead of
    serving different-numerics logits behind an "equal weights"
    handshake."""
    import jax
    import numpy as np

    from apex_tpu.utils.integrity import payload_checksum

    if weight_quantization is not None:
        from apex_tpu.models.gpt import quantize_gpt_params

        params = quantize_gpt_params(params, weight_quantization)
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    payload = {jax.tree_util.keystr(path): np.asarray(leaf)
               for path, leaf in leaves}
    if weight_quantization is not None:
        payload["__weight_quantization__"] = np.frombuffer(
            weight_quantization.encode("utf-8"), np.uint8)
    return payload_checksum(payload)


def clock_from_spec(spec: Optional[Dict]):
    """A child-side clock from its JSON spec: ``None`` /
    ``{"kind": "monotonic"}`` → the engine's default wall clock;
    ``{"kind": "constant", "t": v}`` → the frozen clock the identity
    certs run both sides on (a parent lambda cannot cross a process
    boundary — the spec is the serializable subset that can)."""
    if spec is None:
        return None
    kind = spec.get("kind", "monotonic")
    if kind == "monotonic":
        return None
    if kind == "constant":
        t = float(spec["t"])
        return lambda: t
    raise ValueError(f"unknown clock spec kind {kind!r} "
                     "(supported: 'monotonic', 'constant')")


def _map_error(err: Dict) -> Exception:
    """A child-side exception record back into the REAL local type
    where the router's logic depends on it; everything unmapped
    becomes :class:`RemoteEngineError` (still carrying the child-side
    type name)."""
    etype = err.get("type")
    msg = str(err.get("message", ""))
    if etype == "QueueFullError":
        return QueueFullError(msg)
    if etype == "TenantThrottledError":
        return TenantThrottledError(msg)
    if etype == "ValueError":
        return ValueError(msg)
    if etype == "IntegrityError":
        return IntegrityError(str(err.get("site", "wire")),
                              str(err.get("detail", msg)))
    return RemoteEngineError(f"{etype}: {msg}")


class ProcessReplica:
    """One engine in a child OS process, behind the in-process replica
    surface. See the module docstring for the failure contract; see
    :mod:`~apex_tpu.serving.replica_worker` for the other end.

    ``faults`` takes the replica's WHOLE chaos plan: ``"wire"``-site
    rules stay on this (parent) side as the frame chaos hook
    (:func:`~apex_tpu.utils.faults.wire_chaos`), the rest ships to the
    child engine — one plan still describes one replica. ``on_retry``
    / ``on_timeout`` are the router's counter hooks (``stats()``'s
    ``num_rpc_retries`` / ``num_rpc_timeouts``).
    """

    mode = "process"

    def __init__(self, engine_config: EngineConfig, model_spec: Dict, *,
                 faults: Optional[FaultPlan] = None,
                 clock_spec: Optional[Dict] = None,
                 rpc_timeout_s: float = DEFAULT_RPC_TIMEOUT_S,
                 rpc_retries: int = DEFAULT_RPC_RETRIES,
                 boot_timeout_s: float = DEFAULT_BOOT_TIMEOUT_S,
                 expect_params_checksum: Optional[str] = None,
                 on_retry: Optional[Callable[[], None]] = None,
                 on_timeout: Optional[Callable[[], None]] = None):
        wire_plan, child_plan = split_plan(faults, "wire")
        if wire_plan is not None:
            validate_wire_specs(wire_plan.specs)
        self._chaos = None if wire_plan is None else wire_chaos(wire_plan)
        self.wire_faults = wire_plan  # audit surface for tests
        self._timeout_s = float(rpc_timeout_s)
        self._retries = int(rpc_retries)
        self._on_retry = on_retry
        self._on_timeout = on_timeout
        self._seq = 0
        self._dead = False
        self._live: Dict[str, Request] = {}
        self.last_checkpoint: Optional[Dict] = None
        self._proc = subprocess.Popen(
            [sys.executable, "-m", "apex_tpu.serving.replica_worker"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        self.pid = self._proc.pid
        try:
            wire.write_frame(self._proc.stdin.fileno(), {
                "type": "init",
                "config": engine_config_record(engine_config),
                "model_spec": model_spec,
                "params_checksum": expect_params_checksum,
                "faults": (None if child_plan is None
                           else plan_record(child_plan)),
                "clock": clock_spec,
            })
            # the hello frame is read WITHOUT the chaos hook: boot is
            # not an RPC, and a plan aimed at call 0 should hit the
            # first real call on both chaos and chaos-free runs
            hello = wire.read_frame(self._proc.stdout.fileno(),
                                    timeout_s=float(boot_timeout_s))
        except Exception:
            self._abandon()
            raise
        if not hello.get("ok"):
            err = _map_error(hello.get("error") or {})
            self._abandon()
            raise err
        self.child_pid = int(hello.get("pid", self.pid))

    # -- the RPC core ------------------------------------------------------

    def _unavailable(self, why: str) -> ReplicaUnavailableError:
        self._abandon()
        return ReplicaUnavailableError(
            f"replica child pid {self.pid} unavailable: {why}")

    def _call(self, method: str, *args):
        if self._dead:
            raise ReplicaUnavailableError(
                f"replica child pid {self.pid} is already dead")
        self._seq += 1
        rid = self._seq
        frame = {"type": "call", "id": rid, "method": method,
                 "args": list(args)}
        attempts = 0
        while True:
            try:
                wire.write_frame(self._proc.stdin.fileno(), frame)
                resp = wire.read_frame(self._proc.stdout.fileno(),
                                       timeout_s=self._timeout_s,
                                       chaos=self._chaos)
            except IntegrityError as e:
                # a torn/rotted frame MAY be transient — resend the
                # same id; the worker's dedupe makes the retry safe
                attempts += 1
                if attempts > self._retries:
                    raise self._unavailable(
                        f"{method} failed {attempts} frame attempts; "
                        f"last: {e}")
                if self._on_retry is not None:
                    self._on_retry()
                continue
            except wire.WireTimeoutError as e:
                if self._on_timeout is not None:
                    self._on_timeout()
                raise self._unavailable(f"{method} timed out: {e}")
            except (wire.WireClosedError, BrokenPipeError, OSError) as e:
                raise self._unavailable(
                    f"pipe closed during {method}: "
                    f"{type(e).__name__}: {e}")
            if resp.get("id") != rid:
                # the child reported a torn REQUEST (id None) — resend
                attempts += 1
                if attempts > self._retries:
                    raise self._unavailable(
                        f"{method} failed {attempts} frame attempts; "
                        f"child saw a torn request")
                if self._on_retry is not None:
                    self._on_retry()
                continue
            if "checkpoint" in resp:
                self.last_checkpoint = resp["checkpoint"]
            if resp.get("ok"):
                return resp.get("result")
            raise _map_error(resp.get("error") or {})

    # -- the replica surface ----------------------------------------------

    def add_request(self, request: Request) -> int:
        try:
            arrival = self._call("add_request", request_record(request))
        except TenantThrottledError:
            # mirror the in-process door: a quota shed leaves terminal
            # status "throttled" on the caller's object (the result
            # record itself drains from the child via pop_results)
            object.__setattr__(request, "status", "throttled")
            raise
        except QueueFullError:
            object.__setattr__(request, "status", None)
            raise
        object.__setattr__(request, "status", None)
        self._live[request.uid] = request
        return int(arrival)

    def step(self) -> bool:
        return bool(self._call("step"))

    @property
    def has_work(self) -> bool:
        return bool(self._call("has_work"))

    def load(self) -> Dict[str, float]:
        return {k: float(v) for k, v in self._call("load").items()}

    def probe_prefix(self, hashes: Sequence[str]) -> int:
        return int(self._call("probe_prefix", list(hashes)))

    def spilled_hashes(self) -> Dict[str, str]:
        return {str(h): str(t)
                for h, t in self._call("spilled_hashes").items()}

    def decoding_uids(self) -> List[str]:
        return [str(u) for u in self._call("decoding_uids")]

    def exported_arrival(self, uid: str) -> Optional[int]:
        v = self._call("exported_arrival", str(uid))
        return None if v is None else int(v)

    def drop_stream_events(self, uid: str) -> int:
        return int(self._call("drop_stream_events", str(uid)))

    def export_requests(self, uids: Optional[Sequence[str]] = None
                        ) -> List[Dict]:
        records = self._call(
            "export_requests", None if uids is None else list(uids))
        for rec in records:
            # migrated away: the destination owns a fresh object now,
            # exactly as in the in-process fleet
            self._live.pop(rec.get("uid"), None)
        return records

    def import_requests(self, records: Sequence[Dict]) -> int:
        return int(self._call("import_requests", list(records)))

    def pop_results(self) -> Dict[str, RequestResult]:
        out = {}
        for uid, rec in self._call("pop_results").items():
            res = RequestResult(tokens=[int(t) for t in rec["tokens"]],
                                status=rec["status"])
            req = self._live.pop(uid, None)
            if req is not None:
                object.__setattr__(req, "status", res.status)
            out[uid] = res
        return out

    def pop_stream_events(self) -> List[Tuple[str, int, bool]]:
        return [(u, int(t), bool(last))
                for u, t, last in self._call("pop_stream_events")]

    def abort(self, uid: str) -> bool:
        return bool(self._call("abort", uid))

    def checkpoint(self) -> Dict:
        snap = self._call("checkpoint")
        self.last_checkpoint = snap
        return snap

    def export_prefix_payloads(self, hashes: Sequence[str]) -> Dict:
        return wire.decode_arrays(
            self._call("export_prefix_payloads", list(hashes)))

    def import_prefix_payloads(self, payloads: Dict) -> int:
        return int(self._call("import_prefix_payloads",
                              wire.encode_arrays(payloads)))

    def stats(self) -> Dict:
        return self._call("stats")

    # -- the narrow router accessors ---------------------------------------

    @property
    def block_weight(self) -> float:
        return float(self._call("block_weight"))

    @property
    def queue_depth(self) -> int:
        return int(self._call("queue_depth"))

    @property
    def active_slot_count(self) -> int:
        return int(self._call("active_slot_count"))

    def tenant_charge(self, tenant: str):
        return self._call("tenant_charge", tenant)

    def tenant_depth(self, tenant: str) -> int:
        return int(self._call("tenant_depth", tenant))

    # -- lifecycle ---------------------------------------------------------

    @property
    def alive(self) -> bool:
        """True while the handle is usable AND the child has not been
        reaped (a SIGKILLed child flips this on the next poll)."""
        return not self._dead and self._proc.poll() is None

    def _abandon(self) -> None:
        """Mark dead and reap, keeping whatever ``last_checkpoint``
        was already cached — the failover picture survives the
        corpse."""
        self._dead = True
        try:
            if self._proc.poll() is None:
                self._proc.kill()
            self._proc.wait(timeout=10)
        except Exception:
            pass
        for pipe in (self._proc.stdin, self._proc.stdout):
            try:
                if pipe is not None:
                    pipe.close()
            except Exception:
                pass

    def kill(self) -> None:
        """SIGKILL the child — the REAL chaos hook behind the router's
        ``kill_replica`` in process mode (and the disposal path for a
        corpse). Idempotent."""
        if not self._dead and self._proc.poll() is None:
            try:
                os.kill(self.pid, signal.SIGKILL)
            except OSError:
                pass
        self._abandon()

    def close(self) -> None:
        """Graceful shutdown: ask the worker to exit, then reap. Falls
        back to :meth:`kill` when the child is already unreachable."""
        if self._dead:
            return
        try:
            self._seq += 1
            wire.write_frame(self._proc.stdin.fileno(),
                             {"type": "shutdown", "id": self._seq})
            wire.read_frame(self._proc.stdout.fileno(), timeout_s=10.0)
        except Exception:
            pass
        self._abandon()
