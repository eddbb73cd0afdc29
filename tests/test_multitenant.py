"""Multi-tenant isolation certification (tier-1, CPU): the ISSUE 10
layer (docs/robustness.md, isolation; docs/serving.md, tenancy).

Weighted DRR admission within priority classes (uniform-tenant traffic
bit-identical to the pre-tenancy engine; outputs invariant to tenant
assignment — sampling is arrival-keyed), per-tenant quotas enforced at
the door / admission / block growth with terminal status
``"throttled"``, per-tenant allocator accounting (fractional charge,
eviction/flush attribution), ``abort(uid)`` cancellation with
certified reclamation, streaming delivery, snapshot/restore of the
tenant ledger + mid-DRR-cycle admission walk, and a property-style
fuzz of the admission queue against a naive reference model."""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    QueueFullError,
    Request,
    SamplingParams,
    TenantQuota,
    TenantThrottledError,
)
from apex_tpu.serving.engine import _QueueEntry, _WaitingQueue
from apex_tpu.models import GPTConfig, GPTLMHeadModel


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


def _entry(uid, tenant="default", priority=0, n=5, new=5, charged=False,
           seed=None):
    prompt = list(np.random.RandomState(
        seed if seed is not None else abs(hash(uid)) % 1000).randint(
            1, 100, n))
    return _QueueEntry(request=Request(uid, prompt, max_new_tokens=new,
                                       tenant=tenant, priority=priority),
                       drr_charged=charged)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def test_tenancy_config_validation():
    good = dict(max_batch=2, block_size=4, num_blocks=16,
                max_prefill_len=8, max_seq_len=16)
    with pytest.raises(ValueError, match="tenant_weights"):
        EngineConfig(**good, tenant_weights={"a": 0})
    with pytest.raises(ValueError, match="drr_quantum"):
        EngineConfig(**good, drr_quantum=0)
    with pytest.raises(ValueError, match="tenant_rate_tau_s"):
        EngineConfig(**good, tenant_rate_tau_s=0.0)
    with pytest.raises(ValueError, match="max_waiting"):
        EngineConfig(**good, tenant_quotas={"a": TenantQuota(max_waiting=0)})
    with pytest.raises(ValueError, match="max_resident_blocks"):
        EngineConfig(**good, tenant_quotas={
            "a": TenantQuota(max_resident_blocks=0)})
    with pytest.raises(ValueError, match="tokens_per_s"):
        EngineConfig(**good, tenant_quotas={
            "a": TenantQuota(tokens_per_s=0.0)})
    with pytest.raises(ValueError, match="TenantQuota"):
        EngineConfig(**good, tenant_quotas={"a": {"max_waiting": 1}})
    with pytest.raises(ValueError, match="spec_adapt"):
        EngineConfig(**good, spec_adapt=True)
    with pytest.raises(ValueError, match="low"):
        EngineConfig(**good, spec_tokens=4, spec_adapt=True,
                     spec_accept_low=0.9, spec_accept_high=0.5)


def test_add_request_rejects_bad_tenant(tiny_gpt):
    engine = _mk(tiny_gpt)
    with pytest.raises(ValueError, match="tenant"):
        engine.add_request(_req("a", tenant=""))


# ---------------------------------------------------------------------------
# uniform-tenant bit-identity + tenant-assignment invariance
# ---------------------------------------------------------------------------


def _mixed_reqs(tag="r", tenants=None, n_req=6):
    """Staggered greedy+sampled requests; small pool forces
    preemptions so the certification covers the interesting paths."""
    out = []
    for i in range(n_req):
        kw = {}
        if tenants is not None:
            kw["tenant"] = tenants[i % len(tenants)]
        out.append(_req(
            f"{tag}{i}", seed=i, n=4 + i % 3, new=3 + (i % 3) * 2,
            priority=i % 2,
            sampling=(SamplingParams(temperature=1.0, top_k=13)
                      if i % 3 == 0 else SamplingParams()),
            **kw))
    return out


def test_single_tenant_traffic_bit_identical_to_default(tiny_gpt):
    """All requests under ONE tenant id — at any weight — must produce
    the identical schedule AND outputs as the untagged engine (the
    PR 8 behavior): DRR over a single tenant degenerates to the
    per-class FIFO."""
    runs = []
    for weights, tenant in ((None, None), ({"solo": 5}, "solo")):
        engine = _mk(tiny_gpt, num_blocks=12,
                     tenant_weights=weights, drr_quantum=3)
        reqs = _mixed_reqs(tenants=[tenant] if tenant else None)
        for r in reqs[:4]:
            engine.add_request(r)
        engine.step(); engine.step()
        for r in reqs[4:]:
            engine.add_request(r)
        out = engine.run()
        stats = engine.stats()
        runs.append((out, stats["num_preemptions"],
                     stats["num_decode_dispatches"],
                     stats["num_prefill_chunks"]))
    assert runs[0][0] == runs[1][0]          # outputs bit-identical
    assert runs[0][1:] == runs[1][1:]        # and the SCHEDULE matches


def test_outputs_invariant_to_tenant_assignment(tiny_gpt):
    """Scattering the same requests across tenants (with weights —
    admission ORDER genuinely changes) must not change any request's
    tokens: sampling is arrival-keyed."""
    base = None
    for tenants in (None, ("a", "b", "c")):
        engine = _mk(tiny_gpt, num_blocks=12,
                     tenant_weights={"a": 3} if tenants else None,
                     drr_quantum=4)
        for r in _mixed_reqs(tenants=tenants):
            engine.add_request(r)
        out = engine.run()
        if base is None:
            base = out
        else:
            assert out == base
    engine.check_allocator_integrity()


# ---------------------------------------------------------------------------
# the DRR walk (queue level)
# ---------------------------------------------------------------------------


def test_drr_weighted_fairness_pop_order():
    q = _WaitingQueue(weights={"a": 2, "b": 1}, quantum=10)
    for i in range(6):
        q.append(_entry(f"a{i}", tenant="a", n=5, new=5))   # cost 10
        q.append(_entry(f"b{i}", tenant="b", n=5, new=5))
    order = [q.popleft().request.uid for _ in range(9)]
    # weight 2:1 in committed tokens -> a serves two for b's one
    assert order == ["a0", "a1", "b0", "a2", "a3", "b1", "a4", "a5",
                     "b2"]


def test_drr_strict_priority_between_classes():
    q = _WaitingQueue(weights={"a": 1, "b": 8}, quantum=10)
    q.append(_entry("b-low", tenant="b", priority=1))
    q.append(_entry("a-hi", tenant="a", priority=0))
    # class 0 drains first no matter the weights: strict priority
    # between classes is the documented contract
    assert q.popleft().request.uid == "a-hi"
    assert q.popleft().request.uid == "b-low"


def test_drr_charged_entries_serve_first_and_free():
    q = _WaitingQueue(weights={"a": 1, "b": 1}, quantum=10)
    for i in range(2):
        q.append(_entry(f"a{i}", tenant="a", n=5, new=5))
        q.append(_entry(f"b{i}", tenant="b", n=5, new=5))
    assert q.popleft().request.uid == "a0"
    # a preemption requeue (charged) for b jumps the whole walk
    q.appendleft(_entry("b-resume", tenant="b", charged=True))
    assert q.popleft().request.uid == "b-resume"
    # ...and consumed no deficit and moved no cursor: the walk resumes
    # exactly where it was (1:1 weights alternate, so b0 then a1 —
    # identical to the order WITHOUT the charged insert)
    assert q.popleft().request.uid == "b0"
    assert q.popleft().request.uid == "a1"


def test_drr_head_matches_popleft_with_skip():
    q = _WaitingQueue(weights={"a": 4}, quantum=10)
    for t in ("a", "b", "c"):
        for i in range(2):
            q.append(_entry(f"{t}{i}", tenant=t))
    for skip in (None, {"a"}, {"a", "b"}, {"a", "b", "c"}):
        h = q.head(skip=skip)
        if h is None:
            with pytest.raises(IndexError):
                q.popleft(skip=skip)
            continue
        assert q.popleft(skip=skip) is h
        assert h.request.tenant not in (skip or ())


# ---------------------------------------------------------------------------
# satellite: property-style fuzz vs a naive reference model
# ---------------------------------------------------------------------------


class _RefModel:
    """The naive reference: per-(class, tenant) FIFO lists plus the
    declarative properties the real queue must satisfy — no deques, no
    incremental counters, everything recomputed from scratch."""

    def __init__(self):
        self.lanes = {}        # (priority, tenant) -> [uid, ...]

    def add(self, entry, left=False):
        lane = self.lanes.setdefault(
            (entry.request.priority, entry.request.tenant), [])
        lane.insert(0, entry.request.uid) if left else \
            lane.append(entry.request.uid)

    def remove(self, uid):
        for lane in self.lanes.values():
            if uid in lane:
                lane.remove(uid)

    def size(self):
        return sum(len(v) for v in self.lanes.values())

    def min_class(self):
        live = [p for (p, _), lane in self.lanes.items() if lane]
        return min(live) if live else None

    def lane_head(self, priority, tenant):
        lane = self.lanes.get((priority, tenant), [])
        return lane[0] if lane else None

    def tenant_depth(self, tenant):
        return sum(len(lane) for (p, t), lane in self.lanes.items()
                   if t == tenant)


def test_queue_fuzz_against_reference_model():
    rng = np.random.RandomState(1234)
    q = _WaitingQueue(weights={"t0": 3, "t1": 1}, quantum=7)
    ref = _RefModel()
    uid_counter = [0]

    def fresh_entry(left=False):
        t = f"t{rng.randint(3)}"
        e = _entry(f"u{uid_counter[0]}", tenant=t,
                   priority=int(rng.randint(3)),
                   n=int(rng.randint(1, 8)), new=int(rng.randint(1, 8)),
                   charged=bool(left and rng.randint(2)))
        uid_counter[0] += 1
        return e

    for _ in range(400):
        op = rng.randint(5)
        if op == 0 or len(q) == 0:                       # append
            e = fresh_entry()
            q.append(e)
            ref.add(e)
        elif op == 1:                                    # requeue
            e = fresh_entry(left=True)
            q.appendleft(e)
            ref.add(e, left=True)
        elif op == 2:                                    # pop
            h = q.head()
            e = q.popleft()
            assert e is h                     # head == popleft, always
            r = e.request
            # strict priority: always the most urgent nonempty class
            assert r.priority == ref.min_class()
            # FIFO within the (class, tenant) lane
            assert ref.lane_head(r.priority, r.tenant) == r.uid
            assert e.drr_charged        # charged exactly at service
            ref.remove(r.uid)
        elif op == 3:                                    # expel
            victim = f"u{rng.randint(max(uid_counter[0], 1))}"
            removed = q.expel(lambda e: e.request.uid == victim)
            assert len(removed) in (0, 1)
            for e in removed:
                ref.remove(e.request.uid)
        else:                                            # audit tick
            pass
        # global invariants, every step
        assert len(q) == ref.size()
        assert {e.request.uid for e in q} == {
            u for lane in ref.lanes.values() for u in lane}
        for t in ("t0", "t1", "t2"):
            assert q.tenant_depth(t) == ref.tenant_depth(t)
    # drain completely: every entry must come out exactly once
    remaining = ref.size()
    seen = set()
    while len(q):
        seen.add(q.popleft().request.uid)
    assert len(seen) == remaining
    assert seen == {u for lane in ref.lanes.values() for u in lane}


def test_drr_serves_costs_far_above_the_quantum():
    """A committed budget many quanta deep must be served, not trip
    the walk's termination guard (each credit costs two loop
    iterations — the bound must cover that)."""
    q = _WaitingQueue(quantum=64)
    q.append(_entry("huge", n=600, new=128))
    assert q.head().request.uid == "huge"
    assert q.popleft().request.uid == "huge"
    q = _WaitingQueue(weights={"a": 1, "b": 2}, quantum=16)
    for i in range(3):
        q.append(_entry(f"a{i}", tenant="a", n=400, new=100))
        q.append(_entry(f"b{i}", tenant="b", n=400, new=100))
    served = [q.popleft().request.uid for _ in range(6)]
    assert set(served) == {f"{t}{i}" for t in "ab" for i in range(3)}


def test_drr_long_run_share_tracks_weights():
    """Backlogged tenants with weights 3:1 must be served committed
    token volume in ~3:1 (the fairness property, not just the exact
    small-case order)."""
    q = _WaitingQueue(weights={"a": 3, "b": 1}, quantum=8)
    for i in range(120):
        q.append(_entry(f"a{i}", tenant="a", n=4, new=4))    # cost 8
        q.append(_entry(f"b{i}", tenant="b", n=4, new=4))
    served = {"a": 0, "b": 0}
    for _ in range(120):
        served[q.popleft().request.tenant] += 1
    ratio = served["a"] / max(served["b"], 1)
    assert 2.5 <= ratio <= 3.5, served


def test_engine_lifecycle_fuzz_live_uid_consistency(tiny_gpt):
    """Random interleavings of add / try_add / abort / step / expire
    across tenants and priorities: the live-uid set must always equal
    waiting + resident uids, the queue bound must hold for client
    adds, and every accepted request must end terminal."""
    t = [0.0]
    engine = _mk(tiny_gpt, num_blocks=16, max_waiting=6,
                 clock=lambda: t[0],
                 tenant_weights={"x": 2},
                 tenant_quotas={"z": TenantQuota(max_waiting=2)})
    rng = np.random.RandomState(99)
    accepted, k = set(), 0
    for _ in range(90):
        op = rng.randint(6)
        if op <= 1:
            uid = f"f{k}"; k += 1
            ok = engine.try_add(_req(
                uid, seed=k, n=int(rng.randint(2, 7)),
                new=int(rng.randint(1, 5)),
                tenant=f"{'xyz'[rng.randint(3)]}",
                priority=int(rng.randint(2)),
                deadline_s=(None if rng.randint(3) else 5.0)))
            if ok:
                accepted.add(uid)
        elif op == 2 and accepted:
            uid = sorted(accepted)[rng.randint(len(accepted))]
            engine.abort(uid)
        elif op == 3:
            t[0] += float(rng.rand())
            engine.step()
        else:
            engine.step()
        waiting_uids = {e.request.uid for e in engine.waiting}
        resident_uids = {s.request.uid for s in engine.slots
                         if s is not None}
        assert engine._live_uids == waiting_uids | resident_uids
        assert len(engine.waiting) <= 6 + 2     # bound + requeue slack
    res = engine.run(return_status=True)
    # every accepted request reached a terminal verdict exactly once
    assert accepted <= set(res)
    assert all(r.status in ("finished", "timeout", "failed",
                            "cancelled", "rejected", "throttled")
               for r in res.values())
    engine.check_allocator_integrity()


# ---------------------------------------------------------------------------
# quotas
# ---------------------------------------------------------------------------


def test_throttle_per_tenant_max_waiting(tiny_gpt):
    engine = _mk(tiny_gpt, tenant_quotas={"f": TenantQuota(max_waiting=2)})
    engine.add_request(_req("f0", tenant="f"))
    engine.add_request(_req("f1", seed=1, tenant="f"))
    with pytest.raises(TenantThrottledError, match="max_waiting"):
        engine.add_request(_req("f2", seed=2, tenant="f"))
    # OTHER tenants are untouched by f's quota
    engine.add_request(_req("g0", seed=3, tenant="g"))
    assert engine.try_add(_req("f3", seed=4, tenant="f")) is False
    res = engine.run(return_status=True)
    assert res["f2"].status == "throttled"
    assert res["f3"].status == "throttled"
    assert res["f2"].tokens == []
    assert {res[u].status for u in ("f0", "f1", "g0")} == {"finished"}
    assert engine.stats()["num_throttled"] == 2


def test_throttle_token_rate_budget(tiny_gpt):
    t = [0.0]
    engine = _mk(tiny_gpt, clock=lambda: t[0], tenant_rate_tau_s=2.0,
                 tenant_quotas={"f": TenantQuota(tokens_per_s=3.0)})
    engine.add_request(_req("f0", tenant="f", new=8))
    out = engine.run()
    assert len(out["f0"]) == 8
    # 8 tokens at t=0 -> estimator 8/tau = 4.0 > 3.0: over budget
    with pytest.raises(TenantThrottledError, match="token-rate"):
        engine.add_request(_req("f1", seed=1, tenant="f"))
    # an unquota'd tenant at the same instant is fine
    engine.add_request(_req("g0", seed=2, tenant="g"))
    # after decay the budget recovers: rate 4 * exp(-4/2) ~ 0.54
    t[0] += 4.0
    engine.add_request(_req("f2", seed=3, tenant="f"))
    res = engine.run(return_status=True)
    assert res["f2"].status == "finished"
    rate = engine.stats()["tenants"]["f"]["rate_tokens_per_s"]
    assert rate > 0.0


def test_throttle_impossible_footprint_at_door(tiny_gpt):
    # worst case blocks_needed(6 + 20, 4) = 7 > cap 3: can never run
    engine = _mk(tiny_gpt,
                 tenant_quotas={"f": TenantQuota(max_resident_blocks=3)})
    with pytest.raises(TenantThrottledError, match="never run"):
        engine.add_request(_req("f0", tenant="f", n=6, new=20))
    # within the ceiling is accepted and runs
    engine.add_request(_req("f1", seed=1, tenant="f", n=6, new=4))
    assert engine.run(return_status=True)["f1"].status == "finished"


def test_block_quota_holds_tenant_not_class(tiny_gpt):
    """A tenant at its block ceiling is SKIPPED by admission while its
    lanes drain — another tenant in the same class flows past it."""
    engine = _mk(tiny_gpt, max_batch=2, num_blocks=32,
                 tenant_quotas={"f": TenantQuota(max_resident_blocks=3)})
    # f0 occupies ~3 blocks (prompt 6 + up to 4 new -> ceil(10/4)=3)
    engine.add_request(_req("f0", tenant="f", n=6, new=4))
    engine.add_request(_req("f1", seed=1, tenant="f", n=6, new=4))
    engine.add_request(_req("v0", seed=2, tenant="v", n=6, new=4))
    engine.step()
    # one lane holds f0; f1 must NOT take the second lane (quota),
    # v0 must: the hold is per-tenant, not head-of-line
    resident = {s.request.uid for s in engine.slots if s is not None}
    assert resident == {"f0", "v0"}
    res = engine.run(return_status=True)
    assert {r.status for r in res.values()} == {"finished"}
    charge = engine.stats()["tenants"]["f"]["resident_block_charge"]
    assert charge == 0.0    # drained


def test_block_quota_growth_preempts_own_lane(tiny_gpt):
    """Decode-time growth past the tenant's ceiling preempts the
    tenant's OWN youngest lane — the victim tenant's lane survives."""
    engine = _mk(tiny_gpt, max_batch=3, num_blocks=32, decode_steps=4,
                 tenant_quotas={"f": TenantQuota(max_resident_blocks=4)})
    engine.add_request(_req("f0", tenant="f", n=7, new=9))   # grows
    engine.add_request(_req("f1", seed=1, tenant="f", n=7, new=9))
    engine.add_request(_req("v0", seed=2, tenant="v", n=7, new=9))
    seen_preempt = False
    while engine.has_work:
        engine.step()
        resident = {s.request.uid for s in engine.slots if s is not None}
        if engine.stats()["tenants"]["f"]["quota_preemptions"] > 0:
            seen_preempt = True
            assert "v0" in resident or "v0" in engine.finished
    assert seen_preempt
    res = engine.run(return_status=True)
    assert {r.status for r in res.values()} == {"finished"}
    # outputs unaffected by the quota-preemption schedule
    base = _mk(tiny_gpt, max_batch=3, num_blocks=32, decode_steps=4)
    for r in (_req("f0", n=7, new=9), _req("f1", seed=1, n=7, new=9),
              _req("v0", seed=2, n=7, new=9)):
        base.add_request(r)
    assert {u: r.tokens for u, r in res.items()} == base.run()


# ---------------------------------------------------------------------------
# abort
# ---------------------------------------------------------------------------


def test_abort_waiting_and_unknown(tiny_gpt):
    engine = _mk(tiny_gpt)
    engine.add_request(_req("a"))
    engine.add_request(_req("b", seed=1))
    assert engine.abort("b") is True
    assert engine.abort("b") is False        # already terminal
    assert engine.abort("nope") is False     # unknown
    res = engine.run(return_status=True)
    assert res["b"].status == "cancelled"
    assert res["b"].tokens == []
    assert res["a"].status == "finished"
    # the uid is reusable after drain, like any terminal exit
    engine.add_request(_req("b", seed=2))
    assert engine.run(return_status=True)["b"].status == "finished"


def test_abort_resident_reclaims_blocks(tiny_gpt):
    engine = _mk(tiny_gpt, max_batch=2)
    engine.add_request(_req("a", new=10))
    engine.add_request(_req("b", seed=1, new=10))
    engine.step()            # both admitted, prefilling
    engine.step()
    resident = {s.request.uid for s in engine.slots if s is not None}
    assert "a" in resident
    free_before = engine.allocator.num_free
    assert engine.abort("a") is True
    assert engine.allocator.num_free > free_before
    engine.check_allocator_integrity()
    res = engine.run(return_status=True)
    assert res["a"].status == "cancelled"
    assert res["b"].status == "finished"
    assert len(res["b"].tokens) == 10


def test_abort_mid_flight_discards_lane_results(tiny_gpt):
    """Abort a STARTED lane while its decode dispatch is in flight:
    the deferred drain must discard that lane's tokens (matching by
    uid), the request keeps only what it had, and a new request
    admitted into the freed lane is unharmed."""
    engine = _mk(tiny_gpt, max_batch=2, decode_steps=4)
    engine.add_request(_req("a", new=12))
    engine.add_request(_req("b", seed=1, new=12))
    while engine._pending is None or len(engine._pending[1]) < 2:
        engine.step()
    # the dispatch is in flight over both lanes: abort one now
    covered = set(engine._pending[2].values())
    assert covered == {"a", "b"}
    pre_tokens = dict(engine.finished)
    assert engine.abort("a") is True
    a_tokens_at_abort = engine.finished["a"]
    engine.add_request(_req("c", seed=2, new=4))
    res = engine.run(return_status=True)
    assert res["a"].status == "cancelled"
    assert res["a"].tokens == a_tokens_at_abort   # nothing post-abort
    assert len(res["a"].tokens) < 12
    assert res["b"].status == "finished"
    assert len(res["b"].tokens) == 12
    assert res["c"].status == "finished"
    engine.check_allocator_integrity()
    # determinism: the surviving lanes' outputs match an abort-free run
    base = _mk(tiny_gpt, max_batch=2, decode_steps=4)
    base.add_request(_req("b", seed=1, new=12))
    assert base.run()["b"] == res["b"].tokens


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------


def test_streaming_matches_run_and_sentinels_once(tiny_gpt):
    engine = _mk(tiny_gpt,
                 tenant_quotas={"f": TenantQuota(max_waiting=1)})
    reqs = [_req("s0", new=5), _req("s1", seed=1, new=3,
                                    sampling=SamplingParams(
                                        temperature=1.0, top_k=17))]
    for r in reqs:
        engine.add_request(r)
    engine.add_request(_req("f0", seed=2, tenant="f"))
    with pytest.raises(TenantThrottledError):
        engine.add_request(_req("f1", seed=3, tenant="f"))
    events = []
    while engine.has_work:
        engine.step()
        events += engine.pop_stream_events()
    events += engine.pop_stream_events()
    assert engine.stats()["stream_backlog"] == 0
    res = engine.run(return_status=True)
    # per-uid token streams reassemble the run() results exactly
    for uid, r in res.items():
        toks = [t for u, t, last in events if u == uid and not last]
        assert toks == r.tokens, uid
    # exactly one terminal sentinel per request, -1 payload, ordered
    # after every token of its uid
    for uid in res:
        lasts = [i for i, (u, t, last) in enumerate(events)
                 if u == uid and last]
        assert len(lasts) == 1, uid
        assert events[lasts[0]][1] == -1
        tok_idx = [i for i, (u, t, last) in enumerate(events)
                   if u == uid and not last]
        assert all(i < lasts[0] for i in tok_idx)
    # throttled-at-door still announces termination on the stream
    assert res["f1"].status == "throttled"


def test_streaming_does_not_replay_resumed_history(tiny_gpt):
    """Preempted requests resume carrying their tokens — the stream
    must emit each token ONCE even across preempt/resume."""
    engine = _mk(tiny_gpt, max_batch=2, num_blocks=6, decode_steps=2)
    for i in range(4):
        engine.add_request(_req(f"p{i}", seed=i, n=6, new=8))
    events = []
    while engine.has_work:
        engine.step()
        events += engine.pop_stream_events()
    events += engine.pop_stream_events()
    assert engine.stats()["num_preemptions"] > 0   # the point
    res = engine.run(return_status=True)
    for uid, r in res.items():
        toks = [t for u, t, last in events if u == uid and not last]
        assert toks == r.tokens, uid


# ---------------------------------------------------------------------------
# snapshot / restore
# ---------------------------------------------------------------------------


def _record_admissions(engine):
    order = []
    orig = engine._note_admitted_wait

    def wrapped(entry):
        order.append(entry.request.uid)
        return orig(entry)   # pass the (wait_s, admit_t) pair through

    engine._note_admitted_wait = wrapped
    return order


def test_snapshot_mid_drr_cycle_restores_admission_walk(tiny_gpt):
    """THE acceptance bar: snapshot while the DRR walk is mid-cycle;
    the restored engine must admit the remaining waiting entries in
    the identical order (and produce identical outputs)."""
    kw = dict(max_batch=2, num_blocks=32, drr_quantum=5,
              tenant_weights={"x": 2, "y": 1, "z": 1})
    reqs = [_req(f"{t}{j}", seed=7 * i + j, n=4 + j, new=3,
                 tenant=t,
                 sampling=(SamplingParams(temperature=1.0, top_k=11)
                           if j % 2 else SamplingParams()))
            for i, t in enumerate(("x", "y", "z"))
            for j in range(3)]
    a = _mk(tiny_gpt, **kw)
    a_order = _record_admissions(a)
    for r in reqs:
        a.add_request(r)
    while a._admit_count < 3:
        a.step()
    n_at_snap = len(a_order)
    resident_at_snap = {s.request.uid for s in a.slots if s is not None}
    snap = a.snapshot()
    out_a = a.run()                      # the uninterrupted run

    b = _mk(tiny_gpt, **kw)
    b_order = _record_admissions(b)
    b.restore(snap)
    out_b = b.run()
    # identical outputs (sampled lanes included)...
    assert out_b == out_a
    # ...and the identical admission walk: modulo the residents that
    # restore re-admits (charged, out of band), the restored engine
    # admits the same uids in the same order
    b_fresh = [u for u in b_order if u not in resident_at_snap]
    assert b_fresh == a_order[n_at_snap:]


def test_snapshot_roundtrip_tenant_ledger(tiny_gpt):
    t = [0.0]
    kw = dict(tenant_weights={"a": 2},
              tenant_quotas={"f": TenantQuota(max_waiting=1)})
    a = _mk(tiny_gpt, clock=lambda: t[0], **kw)
    a.add_request(_req("a0", tenant="a", new=3))
    a.add_request(_req("f0", seed=1, tenant="f", new=3))
    with pytest.raises(TenantThrottledError):
        a.add_request(_req("f1", seed=2, tenant="f"))
    a.abort("f0")
    a.add_request(_req("a1", seed=3, tenant="a", new=3))
    for _ in range(3):
        a.step()
    snap = a.snapshot()

    b = _mk(tiny_gpt, clock=lambda: t[0], **kw)
    b.restore(snap)
    out = b.run(return_status=True)
    assert out["a0"].status == "finished"
    sa, sb = snap["tenancy"], b.snapshot()["tenancy"]
    ts = b.stats()["tenants"]
    assert ts["f"]["statuses"] == {"throttled": 1, "cancelled": 1}
    # delivered-token ledger carried over and kept counting
    assert ts["a"]["tokens"] >= sa["tokens"].get("a", 0)
    assert b.stats()["num_restores"] == 1
    b.check_allocator_integrity()


def test_stats_tenant_section_shape(tiny_gpt):
    # "acme" is LISTED (a weight entry), so its ledger row is
    # permanent; unlisted tenants prune once idle (next test)
    engine = _mk(tiny_gpt, tenant_weights={"acme": 2})
    engine.add_request(_req("a0", tenant="acme"))
    engine.run()
    ts = engine.stats()["tenants"]
    assert set(ts) >= {"acme", "default"}
    row = ts["acme"]
    for key in ("tokens", "rate_tokens_per_s", "waiting",
                "resident_slots", "resident_block_charge",
                "cached_blocks", "evicted_blocks", "flushed_blocks",
                "quota_preemptions", "statuses"):
        assert key in row, key
    assert row["tokens"] == 4
    assert row["statuses"] == {"finished": 1}


def test_unlisted_idle_tenants_are_pruned(tiny_gpt):
    """tenant is a free-form client string: an adversary minting a
    fresh id per request must not grow the ledger without bound.
    Unlisted tenants drop from the ledger once they have no waiting or
    resident footprint; listed ones (weights/quotas) are permanent."""
    engine = _mk(tiny_gpt, tenant_weights={"keep": 1})
    for i in range(6):
        engine.add_request(_req(f"e{i}", seed=i, tenant=f"ephemeral-{i}"))
    engine.add_request(_req("k", seed=9, tenant="keep"))
    engine.run()
    ts = engine.stats()["tenants"]
    assert "keep" in ts and ts["keep"]["tokens"] == 4
    assert not any(t.startswith("ephemeral-") for t in ts), set(ts)
    # while live, the row IS there (observability before the drain)
    engine.add_request(_req("e9", seed=10, tenant="ephemeral-9"))
    assert "ephemeral-9" in engine.stats()["tenants"]
    engine.run()
    assert "ephemeral-9" not in engine.stats()["tenants"]


def test_match_prefix_is_tenant_scoped():
    from apex_tpu.serving import BlockAllocator, hash_block_tokens
    a = BlockAllocator(8)
    b = a.alloc(1, tenant="acme")[0]
    h = hash_block_tokens(None, [1, 2, 3, 4])
    a.register_prefix(h, b, tenant="acme")
    a.free([b], tenant="acme")           # retained, cached
    got = a.match_prefix([h], tenant="bolt")
    assert got == [b]
    a.free(got, tenant="bolt")           # the same tenant releases it
    a.check_integrity()


def test_prefix_flush_charges_registering_tenant(tiny_gpt):
    """Rung-2 flushes / LRU evictions are attributed to the tenant
    that parked the blocks in the prefix cache."""
    engine = _mk(tiny_gpt, enable_prefix_caching=True, num_blocks=16)
    engine.add_request(_req("a0", tenant="hog", n=8, new=2))
    engine.run()
    assert engine.stats()["tenants"]["hog"]["cached_blocks"] > 0
    flushed = engine.allocator.flush_evictable()
    assert flushed > 0
    assert (engine.stats()["tenants"]["hog"]["flushed_blocks"]
            == flushed)


# ---------------------------------------------------------------------------
# the isolation scenario: one flooding tenant against two victims
# ---------------------------------------------------------------------------


def test_flooding_tenant_alone_is_shed_scenario(tiny_gpt):
    """Two well-behaved tenants with deadlines share a prefix-cached
    pool with an adversary that floods identical prompts at six times
    their rate, under weighted DRR admission and quotas on the flood
    (waiting cap, resident-block ceiling). Ticks of the injected clock
    throughout. (1) The victims' exact seeded trace runs solo for
    their baseline TTFT p99. (2) The same trace runs against the
    flood: the flood is the ONLY tenant shed at the door or throttled,
    both victims finish tokens, and each victim's TTFT p99 stays
    within ``3 x solo + 12`` ticks. (3) The mix runs again under
    injected prefill / decode faults, an abort of every fifth accepted
    request and low ladder watermarks: aborts, retries, quota sheds
    and ladder steps all fire, every accepted request ends terminal,
    and the allocator's per-tenant accounting is exact."""
    from _traffic import TickClock, drive, poisson_burst_trace
    from apex_tpu.utils.faults import FaultPlan, FaultSpec

    ekw = dict(max_batch=4, block_size=8, num_blocks=64,
               max_prefill_len=16, max_seq_len=48, max_waiting=24,
               enable_prefix_caching=True,
               tenant_weights={"acme": 4, "bolt": 4, "flood": 1},
               tenant_quotas={"flood": TenantQuota(
                   max_waiting=4, max_resident_blocks=5)},
               drr_quantum=16)
    ticks = 24
    victims = ("acme", "bolt")

    def victim_trace():
        # their OWN rng: the solo and the mixed run see the same bytes
        rng = np.random.RandomState(1790)

        def make(tick, k):
            tenant = victims[k % 2]
            return Request(
                uid=f"{tenant}-{k}",
                prompt=list(rng.randint(0, 128, int(rng.choice((6, 10))))),
                max_new_tokens=int(rng.choice((3, 5))),
                tenant=tenant, deadline_s=60.0,
                sampling=(SamplingParams() if k % 2 == 0 else
                          SamplingParams(temperature=1.0, top_k=40)))

        return poisson_burst_trace(rng, ticks, 0.25, make)

    def flood_trace():
        rng = np.random.RandomState(1791)
        shared = list(rng.randint(0, 128, 10))
        return poisson_burst_trace(
            rng, ticks, 1.5,
            lambda tick, k: Request(uid=f"flood-{k}", prompt=list(shared),
                                    max_new_tokens=5, tenant="flood"))

    def serve(trace, abort_every=None, faults=None, **overrides):
        model, params = tiny_gpt
        clock = TickClock()
        engine = InferenceEngine(
            model, params, EngineConfig(**{**ekw, **overrides}),
            clock=clock, faults=faults)
        aborted = set()

        def abort_some(tick, seen):
            for uid in seen.accepted[abort_every - 1::abort_every]:
                if uid not in aborted and engine.abort(uid):
                    aborted.add(uid)

        seen = drive(engine, trace, clock=clock,
                     before_step=abort_some if abort_every else None)
        assert seen.stalls == 0
        return engine, seen, engine.run(return_status=True)

    def p99(seen, tenant):
        return seen.ttft_p99([u for u in seen.accepted
                              if u.startswith(tenant)])

    def tenants_of(requests):
        return {r.tenant for r in requests}

    # (1) the victims solo, (2) the same victims against the flood
    _, solo, _ = serve(victim_trace())
    mixed = sorted(victim_trace() + flood_trace(), key=lambda x: x[0])
    engine, mix, res = serve(mixed)
    assert tenants_of(mix.shed) <= {"flood"}
    refused = {u for u, r in res.items()
               if r.status in ("throttled", "rejected")}
    assert all(u.startswith("flood") for u in refused), refused
    assert mix.shed or engine.stats()["num_throttled"] > 0, \
        "the flood was never shed: the quotas were not exercised"
    for t in victims:
        assert p99(mix, t) <= 3.0 * p99(solo, t) + 12.0
        assert sum(len(r.tokens) for u, r in res.items()
                   if u.startswith(t) and r.status == "finished") > 0
        assert engine.stats()["tenants"][t]["statuses"].get(
            "throttled", 0) == 0

    # (3) chaos over the same mix
    faults = FaultPlan([
        FaultSpec(site="prefill", kind="transient", every=11),
        FaultSpec(site="decode", kind="transient", every=13),
    ], seed=1792)
    # the flood's queue share is capped at 4, so 4 is the pressure
    # mark the ladder can reach
    engine, chaos, res = serve(
        mixed, abort_every=5, faults=faults,
        max_waiting=8, queue_high_watermark=4,
        free_block_low_watermark=0.25, degrade_patience=1,
        max_dispatch_retries=3)
    engine.check_allocator_integrity()
    stats = engine.stats()
    assert stats["num_cancelled"] > 0, "no abort fired"
    assert stats["num_dispatch_retries"] > 0, "no fault fired"
    assert stats["num_throttled"] > 0 or chaos.shed, "no quota shed"
    assert stats["num_degrade_steps_down"] > 0, "the ladder never stepped"
    # every accepted request ended terminal; what else has a verdict
    # is the quota's refusals at the door
    assert set(chaos.accepted) <= set(res)
    assert {res[u].status for u in chaos.accepted} <= {
        "finished", "cancelled", "timeout", "rejected", "failed"}
    assert {res[u].status for u in set(res) - set(chaos.accepted)} \
        <= {"throttled"}
