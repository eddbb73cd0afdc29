"""Deterministic fault injection for chaos-testing the dispatch paths.

A production engine's failure story is only as good as its tests, and
failure tests are only as good as their reproducibility: "the decode
dispatch died once under load" is not a regression test. This module
makes faults *data* — a :class:`FaultPlan` is a seeded, declarative
schedule of failures keyed by **call site** (a string like ``"decode"``
or ``"train_step"``) and **call index** at that site, so a chaos run is
exactly as replayable as the bit-deterministic serving/training runs it
attacks (docs/robustness.md).

Four fault kinds, mirroring the ways a dispatch (or its data) dies:

- ``"transient"`` — raise :class:`TransientDispatchError` *instead of*
  running the dispatch: the runtime hiccuped before launch, a retry
  would succeed. Consumers retry with bounded backoff
  (the engine's ``max_dispatch_retries``, :class:`TrainLoop`'s
  ``max_retries``) and escalate when retries exhaust.
- ``"nan"`` — let the dispatch run, then corrupt the float leaves of
  its output (or hand the flag back to the caller, who knows which
  output is the loss): the silent failure mode — a poisoned batch, a
  numerically-dead layer — that no exception ever surfaces. Consumers
  watch for it (the train loop's non-finite-loss watchdog).
- ``"crash"`` — raise :class:`SimulatedCrash`: process death at a
  chosen step. Nothing catches this (that is the point); tests catch it
  at top level and prove recovery from the last snapshot/checkpoint is
  bit-identical to the uninterrupted run.
- ``"corrupt"`` — silent data corruption (docs/robustness.md, "Data
  integrity"): the call proceeds, and the caller perturbs the artifact
  it owns with a SEEDED deterministic byte/value flip
  (:func:`perturb_payload` / :func:`perturb_json` /
  :func:`perturb_tokens`, keyed by :meth:`FaultPlan.corrupt_seed`).
  Fired at the integrity sites — ``"spill_put"`` / ``"spill_get"``
  (the host spill tier's write/read paths), ``"checkpoint"`` (the
  periodic failover picture), ``"export"`` / ``"import"`` (migration
  records, one fire per record) — where checksum verification must
  catch it, and at ``"decode"``, where it models a flaky chip emitting
  a wrong token (no checksum can catch compute corruption; the fleet's
  determinism cross-check does). The ``"wire"`` site (docs/fleet.md,
  "Process replicas") is the cross-process frame path: ``corrupt``
  there rots one numeric leaf of a received frame and ``transient``
  truncates it (:func:`wire_chaos`), so the parent's
  verify-and-resend loop is exercised without a real flaky pipe —
  only those two kinds are legal at the site
  (:func:`validate_wire_specs`, checked at replica construction the
  way the engine checks its integrity sites).

The plan fires BEFORE the wrapped call for ``transient``/``crash``
(the dispatch never launches, so no donated buffer is consumed and the
caller's retry sees intact state) and AFTER it for ``nan``/``corrupt``.

Determinism: exact-index triggers (``at=``, ``every=``) depend only on
the per-site call count; probabilistic triggers (``prob=``) draw from
one ``random.Random(seed)`` in call order, which is deterministic
whenever the instrumented program's call order is — true for the
serving engine and the train loop by construction.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

_FAULT_KINDS = ("transient", "nan", "crash", "corrupt")
# the cross-process frame path only has two failure modes worth
# modeling — a rotted frame (corrupt) and a torn one (transient, which
# the hook realizes as truncation); "crash" there is just child death
# (SIGKILL the child instead) and "nan" has no float artifact to hit
WIRE_SITE = "wire"
WIRE_FAULT_KINDS = ("transient", "corrupt")


class TransientDispatchError(RuntimeError):
    """An injected (or real) dispatch failure a retry may cure."""


class SimulatedCrash(RuntimeError):
    """Injected process death. Never caught by the engine or the train
    loop — it unwinds the whole driver, exactly like a SIGKILL would,
    and recovery must come from a snapshot/checkpoint."""


class DispatchFailedError(RuntimeError):
    """A dispatch site kept failing after every allotted retry.

    Raised by retrying consumers (not by the plan itself) once backoff
    is exhausted; carries the site and attempt count so the caller can
    quarantine whatever work unit kept poisoning the dispatch."""

    def __init__(self, site: str, attempts: int, last: Exception):
        super().__init__(
            f"dispatch site {site!r} failed {attempts} consecutive "
            f"attempt(s); last error: {type(last).__name__}: {last}")
        self.site = site
        self.attempts = attempts
        self.last = last


def _transient_error_types() -> Tuple[type, ...]:
    """The exception types a retry is allowed to eat: the injected kind
    plus the runtime's real dispatch-failure type (jaxlib's
    XlaRuntimeError when present)."""
    types: List[type] = [TransientDispatchError]
    try:  # jaxlib >= 0.4: the one runtime-error type PJRT raises
        from jaxlib.xla_extension import XlaRuntimeError  # type: ignore

        types.append(XlaRuntimeError)
    except Exception:  # pragma: no cover - vintage-dependent
        pass
    return tuple(types)


TRANSIENT_ERRORS: Tuple[type, ...] = _transient_error_types()


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One declarative fault rule.

    Fires at ``site`` on call indices listed in ``at`` (0-based), on
    every ``every``-th call (indices ``every-1, 2*every-1, ...``), or
    with probability ``prob`` per call (seeded draw); ``max_fires``
    bounds the total (None = unbounded). A spec with none of the three
    triggers never fires."""

    site: str
    kind: str
    at: Tuple[int, ...] = ()
    every: Optional[int] = None
    prob: float = 0.0
    max_fires: Optional[int] = None

    def __post_init__(self):
        if self.kind not in _FAULT_KINDS:
            raise ValueError(
                f"kind must be one of {_FAULT_KINDS}, got {self.kind!r}")
        if self.every is not None and self.every < 1:
            raise ValueError(f"every must be >= 1, got {self.every}")
        if not 0.0 <= self.prob <= 1.0:
            raise ValueError(f"prob must be in [0, 1], got {self.prob}")
        # tuples survive dataclass frozen-ness; normalize lists for
        # callers who wrote at=[3]
        object.__setattr__(self, "at", tuple(int(i) for i in self.at))


class FaultPlan:
    """A seeded schedule of :class:`FaultSpec` rules.

    Consumers call :meth:`fire` once per guarded call site invocation,
    BEFORE the dispatch: ``transient``/``crash`` rules raise there,
    ``nan`` rules make it return True and the caller corrupts the
    output it knows to be floating-point (or uses :meth:`wrap`, which
    NaN-fills every inexact array leaf). ``fired`` keeps the full audit
    log; ``counts`` aggregates it for assertions.
    """

    def __init__(self, specs: Sequence[FaultSpec], seed: int = 0):
        import random

        self.specs = tuple(specs)
        self.seed = int(seed)
        self._rng = random.Random(self.seed)
        self._calls: Dict[str, int] = {}
        self._spec_fires = [0] * len(self.specs)
        self.fired: List[Tuple[str, str, int]] = []  # (site, kind, index)
        # per site: the call index of the MOST RECENT fire() that hit a
        # "corrupt" spec, reset to None on every call — the one-call
        # window in which corrupt_seed() hands the caller its
        # perturbation key
        self._last_corrupt: Dict[str, Optional[int]] = {}

    def calls(self, site: str) -> int:
        """How many times ``site`` has been guarded so far."""
        return self._calls.get(site, 0)

    def counts(self) -> Dict[str, Dict[str, int]]:
        """``{site: {kind: fire_count}}`` over the whole run."""
        out: Dict[str, Dict[str, int]] = {}
        for site, kind, _ in self.fired:
            out.setdefault(site, {}).setdefault(kind, 0)
            out[site][kind] += 1
        return out

    def fire(self, site: str) -> bool:
        """Advance the site's call counter and apply matching rules.

        Raises for ``transient``/``crash`` hits; returns True when a
        ``nan`` rule hit (the caller owns the corruption). A
        ``corrupt`` hit does NOT raise the flag — it arms
        :meth:`corrupt_seed` for this one call, and the caller applies
        the seeded perturbation to the artifact it owns. Specs are
        scanned in declaration order and a raising hit stops the scan,
        so a later probabilistic spec's RNG draw is skipped on that
        call — keep at most one probabilistic spec per site when you
        need draw-for-draw reproducibility across plan edits."""
        i = self._calls.get(site, 0)
        self._calls[site] = i + 1
        self._last_corrupt[site] = None
        nan_hit = False
        for s_idx, spec in enumerate(self.specs):
            if spec.site != site:
                continue
            if (spec.max_fires is not None
                    and self._spec_fires[s_idx] >= spec.max_fires):
                continue
            hit = i in spec.at
            if not hit and spec.every is not None:
                hit = (i + 1) % spec.every == 0
            if not hit and spec.prob > 0.0:
                hit = self._rng.random() < spec.prob
            if not hit:
                continue
            self._spec_fires[s_idx] += 1
            self.fired.append((site, spec.kind, i))
            if spec.kind == "crash":
                raise SimulatedCrash(
                    f"injected crash at site {site!r} call {i}")
            if spec.kind == "transient":
                raise TransientDispatchError(
                    f"injected transient failure at site {site!r} call {i}")
            if spec.kind == "corrupt":
                # corrupt is its own silent channel, NOT a nan hit:
                # the caller consults corrupt_seed() and applies the
                # seeded perturbation it owns — returning True here
                # would make an unvalidated consumer (the train loop's
                # nan watchdog, wrap()'s NaN-fill) treat corruption as
                # a nan fault
                self._last_corrupt[site] = i
                continue
            nan_hit = True
        return nan_hit

    def corrupt_seed(self, site: str) -> Optional[int]:
        """The deterministic perturbation seed for the MOST RECENT
        :meth:`fire` at ``site`` — ``None`` unless that call hit a
        ``"corrupt"`` spec. Derived from (plan seed, site, call index),
        so a given chaos plan corrupts the same artifact the same way
        on every run (:func:`corruption_seed`)."""
        i = self._last_corrupt.get(site)
        if i is None:
            return None
        return corruption_seed(self.seed, site, i)

    def wrap(self, site: str, fn, corrupt=None):
        """``fn`` guarded by this plan at ``site``. ``corrupt`` maps the
        output on a ``nan`` hit; the default NaN-fills every inexact
        (float/complex) array leaf of the output pytree, leaving integer
        outputs (e.g. sampled token ids) untouched."""
        if corrupt is None:
            corrupt = nan_corrupt

        def guarded(*args, **kwargs):
            nan_hit = self.fire(site)
            out = fn(*args, **kwargs)
            return corrupt(out) if nan_hit else out

        return guarded


def guarded_call(fn, *args, plan: Optional[FaultPlan] = None,
                 site: str = "dispatch", retries: int = 0,
                 backoff_s: float = 0.0, on_retry=None):
    """THE retry policy both dispatch consumers share (the serving
    engine's ``_guarded_dispatch``, :class:`TrainLoop`'s step): fire
    the plan at ``site``, run ``fn(*args)``, retry transient failures
    up to ``retries`` times sleeping ``backoff_s * 2**attempt`` between
    tries (``on_retry(attempt)`` is the caller's counter hook), and
    raise :class:`DispatchFailedError` on exhaustion.
    :class:`SimulatedCrash` is never caught — it is process death.

    Returns ``(result, nan_hit)`` — ``nan_hit`` is the plan's silent-
    corruption flag, for callers that know which output is the loss.
    Retry soundness is the caller's contract: ``fn``'s inputs must be
    intact after a failed attempt (true when the failure precedes
    buffer consumption — injected faults and launch-time errors; a
    consumed donated buffer raises non-transient on the retry and
    propagates)."""
    last = None
    for attempt in range(retries + 1):
        if attempt:
            if on_retry is not None:
                on_retry(attempt)
            if backoff_s > 0.0:
                time.sleep(backoff_s * (2 ** (attempt - 1)))
        try:
            nan_hit = plan.fire(site) if plan is not None else False
            return fn(*args), nan_hit
        except SimulatedCrash:
            raise
        except TRANSIENT_ERRORS as e:
            last = e
    raise DispatchFailedError(site, retries + 1, last)


def corruption_seed(plan_seed: int, site: str, index: int) -> int:
    """The perturbation key of one ``"corrupt"`` fire: a pure function
    of (plan seed, site, per-site call index), so corruption is as
    replayable as the schedule it attacks."""
    import hashlib

    digest = hashlib.sha256(
        f"{int(plan_seed)}:{site}:{int(index)}".encode("ascii")).digest()
    return int.from_bytes(digest[:4], "big")


def perturb_payload(payload, seed: int):
    """Deterministically flip ONE byte of one array in a numpy payload
    dict (the spill/transport corruption model: a bit flip in host RAM
    after the checksum was taken). Returns a NEW dict — only the
    touched array is copied; non-array values pass through."""
    import numpy as np

    keys = sorted(k for k, v in payload.items()
                  if isinstance(v, np.ndarray) and v.nbytes > 0)
    out = dict(payload)
    if not keys:
        return out
    rng = np.random.RandomState(seed & 0xFFFFFFFF)
    k = keys[rng.randint(len(keys))]
    a = np.array(payload[k], copy=True)
    flat = a.view(np.uint8).reshape(-1)
    flat[rng.randint(flat.size)] ^= np.uint8(1 + rng.randint(255))
    out[k] = a
    return out


def perturb_json(obj, seed: int):
    """Deterministically perturb ONE numeric leaf of a JSON-able tree
    (the record/checkpoint corruption model). Deep-copies via the JSON
    round trip the artifact would ride anyway; bool leaves are left
    alone (they encode as ``true``/``false``, not numbers). A tree
    with no numeric leaf comes back unchanged."""
    import json
    import random

    out = json.loads(json.dumps(obj))
    leaves = []

    def walk(node, container, key):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], node, k)
        elif isinstance(node, list):
            for i, v in enumerate(node):
                walk(v, node, i)
        elif isinstance(node, (int, float)) and not isinstance(node, bool):
            leaves.append((container, key))

    walk(out, None, None)
    if leaves:
        rng = random.Random(seed)
        container, key = leaves[rng.randrange(len(leaves))]
        delta = 1 + rng.randrange(997)
        container[key] = container[key] + delta
    return out


def perturb_tokens(tokens, counts, vocab_size: int, seed: int):
    """Deterministically corrupt ONE emitted token of a drained decode
    batch — the silent-data-corruption model: a flaky chip computed a
    wrong (but in-vocabulary) token id. ``tokens`` is the fetched
    ``[B, K]`` int array, ``counts`` the per-lane valid-token counts;
    the perturbed copy is returned (unchanged when no lane emitted
    anything). The replacement differs from the original by
    construction and stays in ``[0, vocab_size)`` — nothing downstream
    can tell it from a legitimately-sampled token, which is the
    point."""
    import numpy as np

    tokens = np.array(tokens, copy=True)
    lanes = [i for i in range(tokens.shape[0]) if counts[i] > 0]
    if not lanes or vocab_size < 2:
        return tokens
    rng = np.random.RandomState(seed & 0xFFFFFFFF)
    lane = lanes[rng.randint(len(lanes))]
    pos = rng.randint(int(counts[lane]))
    old = int(tokens[lane, pos])
    tokens[lane, pos] = (old + 1 + rng.randint(vocab_size - 1)) \
        % vocab_size
    return tokens


def validate_wire_specs(specs: Sequence[FaultSpec]) -> None:
    """Construction-time validation of ``"wire"``-site rules: only
    :data:`WIRE_FAULT_KINDS` are legal there (the same discipline the
    engine applies to its integrity sites) — a plan wiring ``crash``
    or ``nan`` at the frame path is a test bug, surfaced at replica
    construction instead of silently never firing."""
    for spec in specs:
        if spec.site == WIRE_SITE and spec.kind not in WIRE_FAULT_KINDS:
            raise ValueError(
                f"fault kind {spec.kind!r} is not valid at site "
                f"{WIRE_SITE!r}; legal kinds: {WIRE_FAULT_KINDS} "
                "(SIGKILL the child to model a crash)")


def wire_chaos(plan: FaultPlan):
    """The parent-side frame chaos hook: a ``bytes -> bytes`` callable
    for ``wire.read_frame(chaos=...)``, firing ``plan`` at the
    ``"wire"`` site once per received frame. A ``transient`` hit
    truncates the body to half (a torn frame — the reader's JSON parse
    fails with an ``IntegrityError``); a ``corrupt`` hit perturbs one
    numeric leaf via :func:`perturb_json` and re-encodes (the embedded
    checksum goes stale — ``verify_record`` refuses). Either way the
    full frame already left the pipe, so the simulated damage never
    desyncs the stream — the parent's resend of the SAME request id
    exercises the real retry/dedupe path."""
    validate_wire_specs(plan.specs)

    def hook(body: bytes) -> bytes:
        import json

        try:
            plan.fire(WIRE_SITE)
        except TransientDispatchError:
            return body[: len(body) // 2]
        seed = plan.corrupt_seed(WIRE_SITE)
        if seed is not None:
            rec = perturb_json(json.loads(body.decode("utf-8")), seed)
            return json.dumps(rec, separators=(",", ":")).encode("utf-8")
        return body

    return hook


def spec_record(spec: FaultSpec) -> Dict:
    """One :class:`FaultSpec` as a JSON-able record — the shape a
    fault plan rides to a child replica process in (docs/fleet.md,
    "Process replicas")."""
    return {
        "site": spec.site,
        "kind": spec.kind,
        "at": list(spec.at),
        "every": spec.every,
        "prob": spec.prob,
        "max_fires": spec.max_fires,
    }


def plan_record(plan: FaultPlan) -> Dict:
    """A FRESH plan's declarative content (seed + specs) as a
    JSON-able record. Runtime state (call counters, the audit log) is
    deliberately not carried: the receiver reconstructs an unfired
    plan, which is the only thing it makes sense to ship."""
    return {"seed": plan.seed,
            "specs": [spec_record(s) for s in plan.specs]}


def plan_from_record(rec: Dict) -> FaultPlan:
    """Invert :func:`plan_record` — ``FaultSpec.__post_init__``
    re-validates every rule, so a rotted record fails loudly here."""
    specs = [FaultSpec(site=s["site"], kind=s["kind"],
                       at=tuple(s.get("at") or ()),
                       every=s.get("every"),
                       prob=float(s.get("prob") or 0.0),
                       max_fires=s.get("max_fires"))
             for s in rec.get("specs", ())]
    return FaultPlan(specs, seed=int(rec.get("seed", 0)))


def split_plan(plan: Optional[FaultPlan], site: str
               ) -> Tuple[Optional[FaultPlan], Optional[FaultPlan]]:
    """Partition a plan into ``(at_site, elsewhere)`` sub-plans (same
    seed, None where empty): the router keeps the ``"wire"`` rules on
    its side of the pipe and ships the rest to the child, so one chaos
    plan still describes the whole replica."""
    if plan is None:
        return None, None
    here = [s for s in plan.specs if s.site == site]
    there = [s for s in plan.specs if s.site != site]
    return (FaultPlan(here, seed=plan.seed) if here else None,
            FaultPlan(there, seed=plan.seed) if there else None)


def nan_corrupt(tree):
    """NaN-fill every inexact array leaf of ``tree`` (the default
    ``nan`` corruption): the shape/dtype-preserving analog of a batch
    whose activations went non-finite."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def leaf(x):
        if hasattr(x, "dtype") and jnp.issubdtype(
                np.dtype(x.dtype), np.inexact):
            return jnp.full(jnp.shape(x), jnp.nan, x.dtype)
        return x

    return jax.tree.map(leaf, tree)
