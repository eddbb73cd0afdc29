"""Overload-protection certification (tier-1, CPU): the ISSUE 8 layer.

Priority admission, bounded-queue backpressure (``QueueFullError`` /
``try_add``), the admit-time feasibility gate (deadline-aware shedding
with status ``"rejected"``), priority-aware preemption, and the
degradation ladder (speculation suspension -> prefix-cache flush ->
lowest-class admission pause) — each held to the determinism bar the
scheduler has carried since PR 2/3: priorities and ladder transitions
are pure SCHEDULE changes, and sampling is schedule-invariant, so
per-request outputs never depend on them (uniform-priority traffic is
bit-identical to the pre-priority FIFO engine)."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTConfig, GPTLMHeadModel
from apex_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    QueueFullError,
    Request,
    SamplingParams,
)

# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return model, params


ENGINE_KW = dict(max_batch=2, block_size=4, num_blocks=32,
                 max_prefill_len=8, max_seq_len=32, seed=7)


def _mk(tiny_gpt, clock=None, **overrides):
    model, params = tiny_gpt
    kw = dict(ENGINE_KW)
    kw.update(overrides)
    return InferenceEngine(model, params, EngineConfig(**kw),
                           clock=clock)


def _req(uid, seed=0, n=5, new=4, **kw):
    prompt = list(np.random.RandomState(seed).randint(1, 100, n))
    return Request(uid, prompt, max_new_tokens=new, **kw)


# ---------------------------------------------------------------------------
# satellite: duplicate-uid rejection
# ---------------------------------------------------------------------------


def test_add_request_rejects_duplicate_uid(tiny_gpt):
    engine = _mk(tiny_gpt)
    engine.add_request(_req("a"))
    # duplicate while WAITING: the uid-keyed deadline map and the
    # engine-owned status field would silently collide
    with pytest.raises(ValueError, match="already waiting or resident"):
        engine.add_request(_req("a", seed=1))
    engine.step()   # "a" becomes resident
    assert any(s is not None and s.request.uid == "a"
               for s in engine.slots)
    with pytest.raises(ValueError, match="already waiting or resident"):
        engine.add_request(_req("a", seed=2))
    out = engine.run()
    assert len(out["a"]) == 4
    # a FINISHED (drained) uid starts a fresh lifecycle, as before
    engine.add_request(_req("a", seed=3))
    assert len(engine.run()["a"]) == 4
    # terminal but NOT yet drained: a fresh lifecycle would clobber
    # the result sitting in finished/statuses — also rejected
    engine.add_request(_req("a", seed=4))
    while engine.has_work:
        engine.step()
    assert "a" in engine.finished
    with pytest.raises(ValueError, match="awaiting drain"):
        engine.add_request(_req("a", seed=5))
    assert len(engine.run()["a"]) == 4     # the result survived


# ---------------------------------------------------------------------------
# bounded queue + backpressure
# ---------------------------------------------------------------------------


def test_queue_bound_raises_and_try_add_sheds(tiny_gpt):
    engine = _mk(tiny_gpt, max_waiting=2)
    engine.add_request(_req("r0", seed=0))
    engine.add_request(_req("r1", seed=1))
    with pytest.raises(QueueFullError, match="max_waiting"):
        engine.add_request(_req("r2", seed=2))
    assert engine.try_add(_req("r3", seed=3)) is False
    # the shed request was never touched: no status, no deadline entry
    assert engine.stats()["num_rejected_queue_full"] == 2
    assert engine.stats()["queue_depth"] == 2
    out = engine.run()
    assert set(out) == {"r0", "r1"}
    # the queue drained — the backpressure signal clears with it
    assert engine.try_add(_req("r2", seed=2)) is True
    assert engine.run()["r2"]
    # a drained request OBJECT re-submitted into a full queue is shed
    # with status None — never a stale verdict from its old lifecycle
    done = _req("old", seed=7)
    engine.add_request(done)
    engine.run()
    assert done.status == "finished"
    engine.add_request(_req("f0", seed=8))
    engine.add_request(_req("f1", seed=9))
    assert engine.try_add(done) is False
    assert done.status is None


def test_try_add_still_raises_on_caller_bugs(tiny_gpt):
    engine = _mk(tiny_gpt, max_waiting=4)
    engine.add_request(_req("a"))
    with pytest.raises(ValueError, match="already waiting"):
        engine.try_add(_req("a", seed=1))   # a bug, not load
    with pytest.raises(ValueError, match="priority"):
        engine.try_add(_req("b", priority=-1))


def test_queue_bound_config_validation():
    for bad in (dict(max_waiting=0), dict(queue_high_watermark=0),
                dict(free_block_low_watermark=0.0),
                dict(free_block_low_watermark=1.5),
                dict(degrade_patience=0),
                dict(degrade_admit_priority=0),
                # unreachable watermark: the queue never exceeds
                # max_waiting + max_batch, so the ladder's queue
                # signal would be silently inert
                dict(max_batch=2, max_waiting=4,
                     queue_high_watermark=20)):
        with pytest.raises(ValueError):
            EngineConfig(**bad)
    # reachable (inside the requeue overshoot) validates fine
    EngineConfig(max_batch=2, max_waiting=4, queue_high_watermark=6)


# ---------------------------------------------------------------------------
# priority admission + priority-aware preemption
# ---------------------------------------------------------------------------


def test_priority_classes_admit_in_priority_then_arrival_order(tiny_gpt):
    engine = _mk(tiny_gpt, max_batch=1)
    engine.add_request(_req("low", seed=0, priority=2))
    engine.add_request(_req("hi", seed=1, priority=0))
    engine.add_request(_req("mid", seed=2, priority=1))
    out = engine.run()
    # finish order == admission order (max_batch=1): most urgent class
    # first, FIFO within a class
    assert list(out) == ["hi", "mid", "low"]
    # uniform priorities: plain arrival FIFO, the pre-priority behavior
    engine2 = _mk(tiny_gpt, max_batch=1)
    for uid, seed in (("low", 0), ("hi", 1), ("mid", 2)):
        engine2.add_request(_req(uid, seed=seed))
    assert list(engine2.run()) == ["low", "hi", "mid"]


def test_outputs_are_invariant_to_priority_assignment(tiny_gpt):
    """Priorities reorder SCHEDULING only: sampling is arrival-keyed,
    so each request's tokens are identical under any priority mix —
    the PR 2/3 determinism certs extended to mixed-priority
    schedules."""
    def serve(priorities):
        engine = _mk(tiny_gpt, max_batch=2, num_blocks=16)
        for i, prio in enumerate(priorities):
            engine.add_request(Request(
                f"r{i}", list(np.random.RandomState(i).randint(1, 100, 5)),
                max_new_tokens=6, priority=prio,
                sampling=(SamplingParams() if i % 2 == 0 else
                          SamplingParams(temperature=0.8, top_k=12))))
        return engine.run()

    uniform = serve([0, 0, 0, 0])
    mixed = serve([2, 0, 1, 0])
    inverted = serve([0, 1, 2, 3])
    assert uniform == mixed == inverted


def test_preemption_evicts_lowest_class_even_when_older(tiny_gpt):
    """The victim rule is (lowest class, then youngest): a LOW-priority
    lane yields even though it is the OLDER resident — where the old
    youngest-first rule would have evicted the high-priority one — and
    the preempted request still finishes with exactly its reference
    tokens (resume determinism is priority-blind)."""
    reqs = [_req("low", seed=3, n=5, new=8, priority=1),
            _req("hi", seed=4, n=5, new=8, priority=0)]

    def serve(num_blocks):
        engine = _mk(tiny_gpt, num_blocks=num_blocks, max_seq_len=16)
        for r in reqs:     # add_request starts a fresh lifecycle
            engine.add_request(r)
        preempted_uid = None
        while engine.has_work:
            before = engine.stats()["num_preemptions"]
            engine.step()
            if (preempted_uid is None
                    and engine.stats()["num_preemptions"] > before):
                resident = {s.request.uid for s in engine.slots
                            if s is not None}
                preempted_uid = ({"low", "hi"} - resident).pop()
        out, engine.finished = dict(engine.finished), {}
        return out, preempted_uid, engine.stats()["num_preemptions"]

    roomy, _, n_roomy = serve(num_blocks=32)
    tight, victim, n_tight = serve(num_blocks=4)
    assert n_roomy == 0 and n_tight >= 1
    # "low" was admitted FIRST (older) yet yields: class beats age
    assert victim == "low"
    assert tight == roomy


# ---------------------------------------------------------------------------
# the admit-time feasibility gate
# ---------------------------------------------------------------------------


def test_feasibility_gate_sheds_infeasible_deadlines(tiny_gpt):
    now = [0.0]
    engine = _mk(tiny_gpt, clock=lambda: now[0])
    # seed the estimators as if dispatches were observed at 1s each:
    # an 8-token prompt (one chunk, which emits the first token) + 5
    # decode ticks estimates 6s
    engine._ewma_prefill_s = 1.0
    engine._ewma_decode_s = 1.0
    engine.add_request(_req("doomed", seed=0, n=8, new=6, deadline_s=3.0))
    engine.add_request(_req("fine", seed=1, n=8, new=6, deadline_s=20.0))
    out = engine.run(return_status=True)
    assert out["doomed"].status == "rejected"
    assert out["doomed"].tokens == []
    assert out["fine"].status == "finished"
    assert len(out["fine"].tokens) == 6
    s = engine.stats()
    assert s["num_rejected_infeasible"] == 1
    assert s["num_timeouts"] == 0         # shed BEFORE burning the TTL
    # the request object carries the verdict too
    assert engine.allocator.num_used == 0


def test_feasibility_gate_prices_prefills_first_token(tiny_gpt):
    """The final prefill chunk emits the first generated token, so a
    fresh request owes decode only max_new_tokens - 1 — the gate must
    not charge a phantom decode dispatch (max_new_tokens=1 is served
    by the prefill pass alone)."""
    now = [0.0]
    engine = _mk(tiny_gpt, clock=lambda: now[0])
    engine._ewma_prefill_s = 1.0
    engine._ewma_decode_s = 1.0
    # est = 1 chunk + 0 decode dispatches = 1.0 <= 1.5 (the old
    # full-budget pricing said 2.0 and shed it)
    engine.add_request(_req("one", seed=0, n=8, new=1, deadline_s=1.5))
    out = engine.run(return_status=True)
    assert out["one"].status == "finished"
    assert len(out["one"].tokens) == 1
    assert engine.stats()["num_rejected_infeasible"] == 0


def test_feasibility_gate_stays_open_without_observations(tiny_gpt):
    # no dispatch observed yet => no estimate => no shedding: the gate
    # never guesses (a fresh engine under a fake clock serves a
    # tight-deadline request instead of rejecting it blind)
    now = [0.0]
    engine = _mk(tiny_gpt, clock=lambda: now[0])
    engine.add_request(_req("tight", seed=0, n=8, new=4, deadline_s=0.5))
    out = engine.run(return_status=True)
    assert out["tight"].status == "finished"
    assert engine.stats()["num_rejected_infeasible"] == 0


def test_feasibility_gate_models_decode_amortization(tiny_gpt):
    """The estimator counts decode DISPATCHES (ceil(remaining / K)),
    not tokens: the same deadline that is infeasible at K=1 admits at
    K=4 — the gate understands the multi-step engine it guards."""
    def verdict(k):
        now = [0.0]
        engine = _mk(tiny_gpt, clock=lambda: now[0], decode_steps=k)
        engine._ewma_prefill_s = 1.0
        engine._ewma_decode_s = 1.0
        # the prefill chunk emits token 1, so decode owes 5:
        # est(K=1) = 1 + 5 = 6 > 3.5; est(K=4) = 1 + 2 = 3 <= 3.5
        engine.add_request(_req("r", seed=0, n=8, new=6, deadline_s=3.5))
        return engine.run(return_status=True)["r"].status

    assert verdict(1) == "rejected"
    assert verdict(4) == "finished"


def test_feasibility_gate_charges_no_chunk_for_cached_resume(tiny_gpt):
    """A resumed entry whose whole history is prefix-cached skips
    prefill entirely (_admit starts it decoding directly) — the gate
    must not charge it a phantom chunk, or it sheds a request that was
    guaranteed to finish in time. A FRESH fully-cached prompt still
    costs one chunk (the write-suppressed logits pass)."""
    engine = _mk(tiny_gpt)
    engine._ewma_prefill_s = 1.0
    engine._ewma_decode_s = 0.1
    assert engine._estimate_service_s(0, 3) == pytest.approx(1.3)
    assert engine._estimate_service_s(0, 3, skips_prefill=True) \
        == pytest.approx(0.3)
    # a real uncached tail always charges its chunks
    assert engine._estimate_service_s(5, 3, skips_prefill=True) \
        == pytest.approx(1.3)


def test_duplicate_uid_guard_survives_snapshot_restore(tiny_gpt):
    """The O(1) live-uid set behind the duplicate guard must be
    repopulated by restore(): a restored queue's uids are waiting."""
    engine = _mk(tiny_gpt, max_batch=1)
    engine.add_request(_req("a", new=6))
    engine.add_request(_req("b", seed=1, new=6))
    engine.step()
    restored = _mk(tiny_gpt, max_batch=1)
    restored.restore(engine.snapshot())
    for uid in ("a", "b"):
        with pytest.raises(ValueError,
                           match="already waiting or resident"):
            restored.add_request(_req(uid, seed=5))
    out = restored.run()
    assert set(out) == {"a", "b"}
    # drained => uids live again
    restored.add_request(_req("a", seed=6))
    restored.run()


def test_ewma_estimators_populate_from_real_dispatches(tiny_gpt):
    engine = _mk(tiny_gpt)
    engine.add_request(_req("a"))
    engine.run()
    s = engine.stats()
    assert s["ewma_prefill_dispatch_s"] > 0.0
    assert s["ewma_decode_dispatch_s"] > 0.0


def test_prefill_ewma_excludes_retry_backoff(tiny_gpt, monkeypatch):
    """Backoff sleeps between retry attempts are failure handling, not
    service time: one transient fault must not inflate the feasibility
    gate's contention-free estimate into over-shedding. The fake clock
    advances ONLY inside the backoff sleeper, so any nonzero EWMA here
    is backoff contamination."""
    from apex_tpu.utils import faults as faults_mod
    from apex_tpu.utils.faults import FaultPlan, FaultSpec

    now = [0.0]
    monkeypatch.setattr(faults_mod.time, "sleep",
                        lambda s: now.__setitem__(0, now[0] + s))
    model, params = tiny_gpt
    engine = InferenceEngine(
        model, params,
        EngineConfig(retry_backoff_s=0.5, **ENGINE_KW),
        clock=lambda: now[0],
        faults=FaultPlan([FaultSpec(site="prefill", kind="transient",
                                    at=(0,))]))
    engine.add_request(_req("a"))
    res = engine.run(return_status=True)
    assert res["a"].status == "finished"
    s = engine.stats()
    assert s["num_dispatch_retries"] == 1      # the fault really fired
    assert s["ewma_prefill_dispatch_s"] == 0.0


# ---------------------------------------------------------------------------
# the degradation ladder
# ---------------------------------------------------------------------------


def test_ladder_steps_down_under_pressure_and_recovers(tiny_gpt):
    engine = _mk(tiny_gpt, max_batch=1, queue_high_watermark=3,
                 degrade_patience=1)
    for i in range(5):
        engine.add_request(_req(f"r{i}", seed=i, new=2))
    peak = 0
    while engine.has_work:
        engine.step()
        peak = max(peak, engine.stats()["degradation_level"])
    assert peak >= 1
    s = engine.stats()
    assert s["num_degrade_steps_down"] >= 1
    # idle ticks are clear ticks: the ladder walks back to 0
    for _ in range(4):
        engine.step()
    s = engine.stats()
    assert s["degradation_level"] == 0
    assert s["num_degrade_steps_up"] == s["num_degrade_steps_down"]


def test_ladder_rung2_flushes_prefix_cache(tiny_gpt):
    engine = _mk(tiny_gpt, enable_prefix_caching=True,
                 queue_high_watermark=100, degrade_patience=50)
    engine.add_request(_req("a", seed=0, n=8))
    engine.run()
    assert engine.stats()["blocks_cached"] > 0
    engine._degradation_level = 2     # hold the rung (patience=50)
    engine.step()
    s = engine.stats()
    assert s["blocks_cached"] == 0
    assert s["num_degrade_flushed_blocks"] > 0
    assert s["num_cache_evictions"] > 0


def test_ladder_rung1_suspends_speculation_reversibly(tiny_gpt):
    """Rung 1 reuses the quarantine degrade path (empty draft plan ->
    the verify program runs as a single-token step, bit-identically for
    greedy) but is REVERSIBLE — and it never flips ``_drafter_ok``."""
    from apex_tpu.serving import Drafter

    model, params = tiny_gpt

    class _EchoDrafter(Drafter):
        # always proposes (repeat the last token) and is a pure
        # function of the history — guarantees draft traffic exists
        # for the suspension to visibly stop
        def propose(self, history, max_tokens):
            return [int(history[-1])] * max_tokens

    prompt = list(np.random.RandomState(5).randint(1, 100, 8))
    cfg = EngineConfig(max_batch=2, block_size=4, num_blocks=64,
                       max_prefill_len=8, max_seq_len=64, seed=7,
                       spec_tokens=4, queue_high_watermark=100,
                       degrade_patience=50)

    def mk():
        return InferenceEngine(model, params, cfg,
                               drafter=_EchoDrafter())

    ref_engine = mk()
    ref_engine.add_request(Request("r", prompt, max_new_tokens=8))
    ref = ref_engine.run()
    assert ref_engine.stats()["num_draft_tokens"] > 0

    engine = mk()
    engine._degradation_level = 1
    assert engine.stats()["speculation_active"] == 0
    engine.add_request(Request("r", prompt, max_new_tokens=8))
    out = engine.run()
    assert out == ref                      # greedy bit-identity
    assert engine.stats()["num_draft_tokens"] == 0   # really suspended
    assert engine._drafter_ok              # NOT quarantined
    engine._degradation_level = 0          # pressure cleared
    assert engine.stats()["speculation_active"] == 1
    engine.add_request(Request("r2", prompt, max_new_tokens=8))
    engine.run()
    assert engine.stats()["num_draft_tokens"] > 0    # speculating again


def test_ladder_rung3_pauses_lowest_class_but_work_conserves(tiny_gpt):
    engine = _mk(tiny_gpt, queue_high_watermark=100, degrade_patience=50)
    engine._degradation_level = 3
    engine.add_request(_req("lo", seed=0, priority=1))
    engine.add_request(_req("hi", seed=1, priority=0))
    engine.step()
    resident = {s.request.uid for s in engine.slots if s is not None}
    # both lanes are free, but the paused class stays queued
    assert resident == {"hi"}
    assert engine.stats()["admission_paused"] == 1
    assert engine.stats()["queue_depth"] == 1
    # once nothing more urgent exists, the idle engine serves what it
    # has (work conservation — no deadlock against the stall guard)
    out = engine.run()
    assert set(out) == {"hi", "lo"}


def test_warm_prefix_cache_is_not_pressure(tiny_gpt):
    """The free-block watermark measures ALLOCATABLE headroom (free +
    evictable): a warm prefix cache under light traffic parks most of
    the pool at refcount 0, and a bare free-list signal would read
    that healthy state as overload and sawtooth the ladder
    (degrade -> flush -> re-warm -> degrade) forever."""
    engine = _mk(tiny_gpt, num_blocks=16, enable_prefix_caching=True,
                 free_block_low_watermark=0.3, degrade_patience=1)
    # two sequential distinct prompts: while either is RESIDENT the
    # allocatable fraction stays above the watermark (no real
    # pressure), but their retained cache blocks leave the bare free
    # list below it afterwards
    for i in range(2):
        engine.add_request(_req(f"warm{i}", seed=i, n=24, new=2))
        engine.run()
    s = engine.stats()
    assert s["blocks_cached"] > 0
    # the cache holds most of the pool, the free list is below the
    # watermark — but every cached block is allocatable headroom
    assert (engine.allocator.num_free
            / engine.allocator.num_blocks) <= 0.3
    for _ in range(4):
        engine.step()
    s = engine.stats()
    assert s["degradation_level"] == 0
    assert s["num_degrade_steps_down"] == 0
    assert s["blocks_cached"] > 0              # cache NOT flushed


def test_gate_ewmas_ride_snapshot_restore(tiny_gpt):
    """The feasibility-gate estimators serialize with the ladder
    state: a restored gate must not reopen blind (admitting doomed
    tight-deadline requests) right when the requeued backlog is at its
    largest. Absent keys (older snapshots) leave the gate open."""
    engine = _mk(tiny_gpt)
    engine._ewma_prefill_s = 0.75
    engine._ewma_decode_s = 0.25
    snap = json.loads(json.dumps(engine.snapshot()))
    restored = _mk(tiny_gpt)
    restored.restore(snap)
    s = restored.stats()
    assert s["ewma_prefill_dispatch_s"] == pytest.approx(0.75)
    assert s["ewma_decode_dispatch_s"] == pytest.approx(0.25)
    # a pre-overload snapshot without the keys: gate stays open. A
    # genuinely older snapshot predates the embedded checksum too —
    # drop the seal, or the (correct) integrity check reads this
    # hand-edited record as corruption
    del snap["overload"]["ewma_prefill_s"]
    del snap["overload"]["ewma_decode_s"]
    del snap["checksum"]
    older = _mk(tiny_gpt)
    older.restore(snap)
    assert older._ewma_prefill_s is None
    assert older._ewma_decode_s is None


def test_restore_into_ladder_disabled_config_clears_rung(tiny_gpt):
    """The overload knobs are restorable-across (out of the config
    fingerprint, like the retry knobs) — but an engine with NO
    watermarks can never walk the ladder back up, so restoring a
    mid-degradation snapshot into it must clear the rung instead of
    suspending speculation / pausing admission forever."""
    engine = _mk(tiny_gpt, max_batch=1, queue_high_watermark=2,
                 degrade_patience=1)
    for i in range(4):
        engine.add_request(_req(f"r{i}", seed=i, new=3, priority=i % 2))
    while engine.has_work and engine.stats()["degradation_level"] < 1:
        engine.step()
    snap = engine.snapshot()
    assert snap["overload"]["degradation_level"] >= 1

    plain = _mk(tiny_gpt, max_batch=1)     # ladder off (the default)
    plain.restore(snap)
    s = plain.stats()
    assert s["degradation_level"] == 0
    assert s["admission_paused"] == 0
    plain.run()                            # and it drains cleanly


def test_ladder_state_serializes_through_snapshot_restore(tiny_gpt):
    engine = _mk(tiny_gpt, max_batch=1, queue_high_watermark=2,
                 degrade_patience=1)
    for i in range(4):
        engine.add_request(_req(f"r{i}", seed=i, new=3,
                                priority=i % 2))
    while engine.has_work and engine.stats()["degradation_level"] < 1:
        engine.step()
    assert engine.stats()["degradation_level"] >= 1
    snap = engine.snapshot()
    assert snap["overload"]["degradation_level"] >= 1
    # priorities round-trip on every serialized request
    by_uid = {r["uid"]: r["priority"] for r in snap["requests"]}
    for uid, prio in by_uid.items():
        assert prio == int(uid[1:]) % 2, uid

    restored = _mk(tiny_gpt, max_batch=1, queue_high_watermark=2,
                   degrade_patience=1)
    restored.restore(snap)
    s = restored.stats()
    assert s["degradation_level"] == snap["overload"]["degradation_level"]
    restored.run()   # and it still drains cleanly


def test_decode_ewma_excludes_caller_pauses(tiny_gpt):
    """The decode EWMA times the drain's device fetch only: a driver
    that pauses between step() calls (or an operator pausing before
    snapshot) must not inflate the feasibility gate's contention-free
    estimate with idle time. The fake clock advances only BETWEEN
    ticks, so any nonzero EWMA here is pause contamination."""
    now = [0.0]
    engine = _mk(tiny_gpt, clock=lambda: now[0])
    engine.add_request(_req("a", new=5))
    while engine.has_work:
        engine.step()
        now[0] += 0.4                      # caller-side pause per tick
    engine.run()
    s = engine.stats()
    assert s["num_decode_dispatches"] > 0
    assert s["ewma_decode_dispatch_s"] == 0.0


def test_queue_depth_peak_counts_preemption_requeues(tiny_gpt):
    """The peak metric exists to expose the requeue overshoot past
    max_waiting — it must sample AT the requeue, before admission can
    re-absorb the entry (with an otherwise-empty queue, preemption is
    the only thing that ever makes depth nonzero here)."""
    engine = _mk(tiny_gpt, num_blocks=4, max_seq_len=16)
    engine.add_request(_req("a", seed=3, n=5, new=8))
    engine.add_request(_req("b", seed=4, n=5, new=8))
    engine.run()
    s = engine.stats()
    assert s["num_preemptions"] >= 1
    # both fit the 2-lane engine up front, so the client-side peak is
    # 2 — anything above proves the requeue was sampled; at minimum
    # the preempted entry must register depth >= 1 post-admission
    assert s["queue_depth_peak"] >= 1


def test_waiting_queue_drops_drained_priority_classes(tiny_gpt):
    """Dead per-class deques must not accumulate: priority is an
    arbitrary client int, and a long-lived engine fed distinct values
    would otherwise scan (and hold) every class ever seen."""
    engine = _mk(tiny_gpt, max_batch=1)
    for i in range(4):
        engine.add_request(_req(f"r{i}", seed=i, new=2, priority=10 * i))
    engine.run()
    assert engine.waiting._classes == {}
    # expel (deadline sweep) drops drained classes too
    now = [0.0]
    engine2 = _mk(tiny_gpt, clock=lambda: now[0])
    engine2.add_request(_req("d", seed=0, priority=7, deadline_s=0.5))
    now[0] = 1.0
    engine2.step()
    assert engine2.waiting._classes == {}
    assert engine2.stats()["num_timeouts"] == 1


# ---------------------------------------------------------------------------
# queue observability
# ---------------------------------------------------------------------------


def test_stats_report_queue_depth_and_wait(tiny_gpt):
    now = [0.0]
    engine = _mk(tiny_gpt, max_batch=1, clock=lambda: now[0])
    for i in range(3):
        engine.add_request(_req(f"r{i}", seed=i, new=2))
    s = engine.stats()
    assert s["queue_depth"] == 3 and s["queue_depth_peak"] == 3
    while engine.has_work:
        now[0] += 1.0
        engine.step()
    s = engine.stats()
    assert s["queue_depth"] == 0
    assert s["queue_depth_peak"] == 3
    assert s["num_ticks"] >= 3
    # r1/r2 waited in the queue while r0 (admitted at wait 0) served
    assert s["queue_wait_max_ticks"] >= 1
    assert s["queue_wait_max_s"] >= s["queue_wait_mean_s"] > 0.0
    assert s["queue_wait_max_ticks"] >= s["queue_wait_mean_ticks"]
    for key in ("num_rejected_queue_full", "num_rejected_infeasible",
                "num_degrade_steps_down", "num_degrade_steps_up",
                "num_degrade_flushed_blocks", "admission_paused",
                "degradation_level"):
        assert key in s, key


# ---------------------------------------------------------------------------
# the overload scenario: a seeded Poisson trace with a 4x burst, the
# whole protection stack on
# ---------------------------------------------------------------------------


def test_overload_burst_scenario_bounded_queue_no_stalls(tiny_gpt):
    """Mixed prompt / output lengths, priorities and deadlines arrive
    at ``Poisson(0.6)`` a tick with a 4x burst in the middle third,
    against a bounded queue, the feasibility gate and the degradation
    ladder. One tick of the injected clock is one ``step()``, so the
    deadlines, the TTFT and the inter-token gaps are all in ticks.
    The engine never stalls with work queued, the queue's high-water
    mark stays inside ``max_waiting + max_batch`` (client adds are
    bounded by ``max_waiting``; requeues of preempted residents can
    overshoot by at most a batch), the burst really overloads it (the
    door sheds, the ladder steps down), and every admitted request
    ends in a terminal status - most of them ``finished``, with finite
    latency tails."""
    from _traffic import TickClock, drive, poisson_burst_trace
    from apex_tpu.observability import percentile

    cfg = dict(max_batch=4, block_size=8, num_blocks=64,
               max_prefill_len=16, max_seq_len=48, max_waiting=8,
               queue_high_watermark=5, free_block_low_watermark=0.125,
               degrade_patience=2)
    clock = TickClock()
    engine = _mk(tiny_gpt, clock=clock, **cfg)
    rng = np.random.RandomState(3)
    deadlines = (None, None, 2.0, 40.0, 120.0)     # ticks

    def make_request(tick, k):
        return Request(
            uid=f"o{k}",
            prompt=list(rng.randint(0, 128, int(rng.choice((6, 10, 14))))),
            max_new_tokens=int(rng.choice((3, 5, 8))),
            priority=int(rng.choice((0, 1, 2), p=(0.3, 0.5, 0.2))),
            deadline_s=deadlines[int(rng.randint(len(deadlines)))],
            sampling=(SamplingParams() if k % 2 == 0 else
                      SamplingParams(temperature=1.0, top_k=40)))

    phase = 8
    trace = poisson_burst_trace(
        rng, ticks=3 * phase, base_rate=0.6, make_request=make_request,
        burst_start=phase, burst_end=2 * phase, burst_factor=4)

    seen = drive(engine, trace, clock=clock)
    results = engine.run(return_status=True)
    stats = engine.stats()

    assert seen.stalls == 0
    assert stats["queue_depth_peak"] <= cfg["max_waiting"] + cfg["max_batch"]
    assert len(seen.shed) > 0, "the burst never filled the queue"
    assert len(seen.shed) == stats["num_rejected_queue_full"]
    # and the ladder walked down under it and back up after it
    assert stats["num_degrade_steps_down"] > 0
    assert stats["num_degrade_steps_up"] == stats["num_degrade_steps_down"]
    # every admitted request is accounted for, exactly once
    assert sorted(results) == sorted(seen.accepted)
    assert len(results) + len(seen.shed) == len(trace)
    statuses = [r.status for r in results.values()]
    assert set(statuses) <= {"finished", "timeout", "rejected"}
    assert statuses.count("finished") > len(statuses) // 2
    assert (statuses.count("timeout") + statuses.count("rejected")
            == stats["num_timeouts"] + stats["num_rejected_infeasible"])
    # the tails exist and are ordered; a request that produced a token
    # was submitted first
    ttft, itl = list(seen.ttft.values()), seen.itl
    assert len(ttft) >= statuses.count("finished") and itl
    for xs in (ttft, itl):
        p50, p99 = percentile(xs, 50), percentile(xs, 99)
        assert math.isfinite(p50) and math.isfinite(p99)
        assert 0 <= p50 <= p99
    engine.check_allocator_integrity()
