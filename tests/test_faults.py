"""Chaos certification (tier-1, CPU): the robustness layer of ISSUE 6.

Every failure path — transient dispatch errors, poison-request
quarantine, request deadlines, simulated process death, non-finite-loss
escalation — is driven by a seeded deterministic
:class:`~apex_tpu.utils.faults.FaultPlan`, and the recovery paths are
held to the bit-identity bar PRs 2-4 set: a snapshot/restored engine's
outputs and a checkpoint/resumed train run's final params must equal
the fault-free run exactly. All failure-path counters are asserted
nonzero where their path fires."""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flax.linen as nn

from apex_tpu.models import GPTConfig, GPTLMHeadModel
from apex_tpu.optimizers import FusedAdam
from apex_tpu.serving import (
    EngineConfig,
    EngineStalledError,
    InferenceEngine,
    Request,
    RequestResult,
    SamplingParams,
)
from apex_tpu.train import (
    NonFiniteLossError,
    TrainLoop,
    WatchdogConfig,
    build_train_step,
)
from apex_tpu.utils.checkpoint import load_train_state
from apex_tpu.utils.faults import (
    DispatchFailedError,
    FaultPlan,
    FaultSpec,
    SimulatedCrash,
    TransientDispatchError,
    nan_corrupt,
)

# ---------------------------------------------------------------------------
# fixtures: one tiny GPT + a standard two-request workload
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return model, params


ENGINE_KW = dict(max_batch=2, block_size=4, num_blocks=32,
                 max_prefill_len=8, max_seq_len=32,
                 enable_prefix_caching=True, seed=7)


def _mk_engine(tiny_gpt, faults=None, clock=None, **overrides):
    model, params = tiny_gpt
    kw = dict(ENGINE_KW)
    kw.update(overrides)
    return InferenceEngine(model, params, EngineConfig(**kw),
                           faults=faults, clock=clock)


def _requests():
    # one greedy, one sampled: the sampled lane certifies the
    # schedule-invariant PRNG chain survives recovery too
    return [Request("greedy", [1, 2, 3, 4, 5], max_new_tokens=6),
            Request("sampled", [9, 8, 7], max_new_tokens=6,
                    sampling=SamplingParams(temperature=0.8, top_k=12))]


@pytest.fixture(scope="module")
def reference_outputs(tiny_gpt):
    """The fault-free run every recovery path must reproduce exactly."""
    engine = _mk_engine(tiny_gpt)
    for r in _requests():
        engine.add_request(r)
    return engine.run()


# ---------------------------------------------------------------------------
# the FaultPlan harness itself
# ---------------------------------------------------------------------------


def test_fault_plan_is_deterministic_and_counts():
    def drive(plan):
        log = []
        for i in range(20):
            try:
                nan = plan.fire("site")
                log.append("nan" if nan else "ok")
            except TransientDispatchError:
                log.append("transient")
        return log

    specs = [FaultSpec(site="site", kind="transient", at=(2,)),
             FaultSpec(site="site", kind="transient", prob=0.3),
             FaultSpec(site="site", kind="nan", every=7, max_fires=1)]
    a, b = FaultPlan(specs, seed=11), FaultPlan(specs, seed=11)
    la, lb = drive(a), drive(b)
    assert la == lb                       # seeded => replayable
    assert la[2] == "transient"           # exact-index trigger
    assert la.count("nan") == 1           # max_fires bound
    assert drive(FaultPlan(specs, seed=12)) != la  # the seed matters
    counts = a.counts()["site"]
    assert counts["transient"] >= 1 and counts["nan"] == 1
    assert a.calls("site") == 20 and a.calls("other") == 0


def test_fault_plan_wrap_nan_corrupts_float_leaves_only():
    plan = FaultPlan([FaultSpec(site="f", kind="nan", at=(0,))])
    fn = plan.wrap("f", lambda: {"x": jnp.ones(3), "i": jnp.arange(2)})
    out = fn()
    assert np.all(np.isnan(np.asarray(out["x"])))
    np.testing.assert_array_equal(np.asarray(out["i"]), [0, 1])
    clean = fn()   # index 1: no fault
    assert not np.any(np.isnan(np.asarray(clean["x"])))


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultSpec(site="s", kind="meteor")
    with pytest.raises(ValueError, match="prob"):
        FaultSpec(site="s", kind="nan", prob=1.5)
    with pytest.raises(ValueError, match="every"):
        FaultSpec(site="s", kind="nan", every=0)
    assert nan_corrupt(jnp.int32(3)) == 3  # integers pass through


def test_engine_rejects_nan_specs_at_serving_sites(tiny_gpt):
    # serving outputs are integer tokens: a nan spec there would record
    # a fire that corrupted nothing, so construction must refuse it
    plan = FaultPlan([FaultSpec(site="decode", kind="nan", at=(0,))])
    with pytest.raises(ValueError, match="nan faults"):
        _mk_engine(tiny_gpt, faults=plan)
    # nan at the TRAIN site riding along in a shared plan is fine
    shared = FaultPlan([FaultSpec(site="train_step", kind="nan", at=(0,))])
    _mk_engine(tiny_gpt, faults=shared)


# ---------------------------------------------------------------------------
# serving: retry, quarantine, deadlines, stall guard
# ---------------------------------------------------------------------------


def test_transient_dispatch_failures_are_retried_bit_identically(
        tiny_gpt, reference_outputs):
    plan = FaultPlan([FaultSpec(site="prefill", kind="transient", at=(0,)),
                      FaultSpec(site="decode", kind="transient",
                                at=(1, 4))])
    engine = _mk_engine(tiny_gpt, faults=plan)
    for r in _requests():
        engine.add_request(r)
    out = engine.run(return_status=True)
    assert {u: r.tokens for u, r in out.items()} == reference_outputs
    assert all(r.status == "finished" for r in out.values())
    assert engine.stats()["num_dispatch_retries"] >= 3
    assert engine.stats()["num_quarantines"] == 0


def test_poisoned_prefill_is_quarantined_and_engine_survives(
        tiny_gpt, reference_outputs):
    # the FIRST request's prefill fails beyond every retry
    # (max_dispatch_retries=2 => 3 attempts); the second must sail
    # through untouched
    plan = FaultPlan([FaultSpec(site="prefill", kind="transient",
                                at=(0, 1, 2))])
    engine = _mk_engine(tiny_gpt, faults=plan)
    reqs = _requests()
    for r in reqs:
        engine.add_request(r)
    out = engine.run(return_status=True)
    assert out["greedy"].status == "failed"
    assert out["greedy"].tokens == []
    assert reqs[0].status == "failed"        # surfaced on the object too
    assert out["sampled"].status == "finished"
    assert out["sampled"].tokens == reference_outputs["sampled"]
    assert engine.stats()["num_quarantines"] == 1


def test_persistent_decode_failure_drains_lanes_without_killing_engine(
        tiny_gpt, reference_outputs):
    # two clean decode dispatches, then the site fails permanently: the
    # engine quarantines lanes youngest-first by elimination and keeps
    # running to a clean empty state instead of raising
    plan = FaultPlan([FaultSpec(site="decode", kind="transient",
                                at=tuple(range(2, 200)))])
    engine = _mk_engine(tiny_gpt, faults=plan)
    for r in _requests():
        engine.add_request(r)
    out = engine.run(return_status=True)
    assert {r.status for r in out.values()} == {"failed"}
    for uid, res in out.items():
        # tokens emitted before the failures are preserved exactly
        n = len(res.tokens)
        assert res.tokens == reference_outputs[uid][:n]
    assert engine.stats()["num_quarantines"] == 2
    assert not engine.has_work


def test_request_deadline_times_out_gracefully(tiny_gpt,
                                               reference_outputs):
    now = [0.0]
    engine = _mk_engine(tiny_gpt, clock=lambda: now[0])
    engine.add_request(Request("greedy", [1, 2, 3, 4, 5], max_new_tokens=6,
                               deadline_s=10.0))
    engine.add_request(_requests()[1])   # no deadline
    # a few ticks of progress, then the clock blows the deadline
    for _ in range(3):
        engine.step()
    now[0] = 11.0
    out = engine.run(return_status=True)
    assert out["greedy"].status == "timeout"
    n = len(out["greedy"].tokens)
    assert n < 6    # cut short...
    assert out["greedy"].tokens == reference_outputs["greedy"][:n]  # ...cleanly
    assert out["sampled"].status == "finished"
    assert out["sampled"].tokens == reference_outputs["sampled"]
    assert engine.stats()["num_timeouts"] == 1


def test_waiting_request_expires_without_ever_running(tiny_gpt):
    now = [0.0]
    engine = _mk_engine(tiny_gpt, clock=lambda: now[0])
    engine.add_request(Request("late", [1, 2, 3], max_new_tokens=4,
                               deadline_s=5.0))
    now[0] = 6.0
    out = engine.run(return_status=True)
    assert out["late"] == RequestResult(tokens=[], status="timeout")


def test_deadline_validation(tiny_gpt):
    engine = _mk_engine(tiny_gpt)
    with pytest.raises(ValueError, match="deadline_s"):
        engine.add_request(Request("bad", [1], deadline_s=0.0))


def test_midprefill_slot_expires_while_decode_in_flight(tiny_gpt):
    # an in-flight decode only covers STARTED lanes, so a mid-prefill
    # slot past its deadline must expire up front — before burning one
    # more prefill chunk — even while a dispatch is pending
    now = [0.0]
    engine = _mk_engine(tiny_gpt, clock=lambda: now[0], prefill_chunk=2)
    engine.add_request(Request("fast", [1, 2], max_new_tokens=8))
    engine.step()   # fast prefills + its decode dispatch goes in flight
    engine.add_request(Request("slowpoke", [1, 2, 3, 4, 5, 6],
                               max_new_tokens=4, deadline_s=5.0))
    engine.step()   # slowpoke admitted, chunk 1 of 3, decode in flight
    assert engine._pending is not None
    now[0] = 6.0
    chunks = engine.stats()["num_prefill_chunks"]
    engine.step()
    assert engine.statuses["slowpoke"] == "timeout"
    assert engine.stats()["num_prefill_chunks"] == chunks  # no last chunk
    out = engine.run(return_status=True)
    assert out["slowpoke"] == RequestResult(tokens=[], status="timeout")
    assert out["fast"].status == "finished"


def test_stalled_run_raises_diagnostic_not_spin(tiny_gpt):
    engine = _mk_engine(tiny_gpt)
    engine.add_request(Request("r", [1, 2, 3], max_new_tokens=2))
    engine.step = lambda: False   # a scheduler bug: work, no progress
    with pytest.raises(EngineStalledError) as ei:
        engine.run()
    assert ei.value.engine_stats["waiting"] == 1
    assert "no progress" in str(ei.value)


class _PoisonedFetch:
    """A device-array stand-in whose host fetch fails ``failures``
    times: dispatch is asynchronous, so REAL runtime errors surface at
    ``np.asarray(...)`` in the deferred drain, not at the launch the
    fault plan guards — this double injects exactly that."""

    def __init__(self, toks, failures):
        self._toks = toks
        self._failures = failures

    def __array__(self, dtype=None, copy=None):
        if self._failures:
            self._failures -= 1
            raise TransientDispatchError("injected fetch-time failure")
        return np.asarray(self._toks)


def test_fetch_time_failure_rolls_back_and_redispatches_bit_identically(
        tiny_gpt, reference_outputs):
    engine = _mk_engine(tiny_gpt)
    for r in _requests():
        engine.add_request(r)
    while engine._pending is None:
        engine.step()
    toks, active, uids = engine._pending
    engine._pending = (_PoisonedFetch(toks, 1), active, uids)
    out = engine.run(return_status=True)
    # the in-process reset requeues residents with their emitted
    # tokens and re-prefills: same tokens, nothing lost, nobody failed
    assert {u: r.tokens for u, r in out.items()} == reference_outputs
    assert {r.status for r in out.values()} == {"finished"}
    assert engine.stats()["num_dispatch_retries"] >= 1
    assert engine.stats()["num_quarantines"] == 0


def test_persistent_fetch_failure_quarantines_and_engine_survives(
        tiny_gpt, reference_outputs):
    engine = _mk_engine(tiny_gpt)
    for r in _requests():
        engine.add_request(r)
    for _ in range(3):    # let both lanes emit something first
        engine.step()
    real_decode = engine._decode

    def poisoned(*args):
        cache, toks = real_decode(*args)
        return cache, _PoisonedFetch(toks, 10 ** 9)

    engine._decode = poisoned
    out = engine.run(return_status=True)
    engine._decode = real_decode   # stats() reads the jit's cache size
    assert {r.status for r in out.values()} == {"failed"}
    for uid, res in out.items():
        n = len(res.tokens)
        assert res.tokens == reference_outputs[uid][:n]
    assert engine.stats()["num_quarantines"] == 2
    assert not engine.has_work


# ---------------------------------------------------------------------------
# serving: crash-consistent snapshot / restore
# ---------------------------------------------------------------------------


def test_chaos_certification_snapshot_restore_bit_identical(
        tiny_gpt, reference_outputs):
    """The acceptance gate: transient faults + one simulated crash;
    the engine snapshots every tick, dies, restores in a fresh engine,
    and the COMBINED outputs equal the fault-free run bit-for-bit."""
    plan = FaultPlan([FaultSpec(site="decode", kind="transient", at=(1,)),
                      FaultSpec(site="decode", kind="crash", at=(4,))])
    engine = _mk_engine(tiny_gpt, faults=plan)
    for r in _requests():
        engine.add_request(r)
    snap = None
    with pytest.raises(SimulatedCrash):
        while engine.has_work:
            engine.step()
            snap = engine.snapshot()
    assert snap is not None
    assert engine.stats()["num_dispatch_retries"] >= 1
    assert engine.stats()["num_snapshots"] >= 1
    # ... the process is gone; only `snap` survives (JSON round-trip
    # proves nothing device-resident leaked into it)
    snap = json.loads(json.dumps(snap))
    restored = _mk_engine(tiny_gpt)
    restored.restore(snap)
    assert restored.stats()["num_restores"] == 1
    out = restored.run()
    assert out == reference_outputs
    restored.check_allocator_integrity()


def test_snapshot_drains_inflight_and_carries_statuses(tiny_gpt):
    now = [0.0]
    engine = _mk_engine(tiny_gpt, clock=lambda: now[0])
    engine.add_request(Request("t", [1, 2], max_new_tokens=3,
                               deadline_s=1.0))
    engine.add_request(Request("ok", [3, 4], max_new_tokens=3))
    now[0] = 2.0
    for _ in range(3):
        engine.step()
    snap = engine.snapshot()
    assert engine._pending is None          # the drain happened
    assert snap["statuses"]["t"] == "timeout"
    assert snap["finished"]["t"] == []
    restored = _mk_engine(tiny_gpt, clock=lambda: now[0])
    restored.restore(snap)
    out = restored.run(return_status=True)
    assert out["t"].status == "timeout"
    assert out["ok"].status == "finished"


def test_restore_rejects_config_mismatch_and_used_engines(tiny_gpt):
    engine = _mk_engine(tiny_gpt)
    engine.add_request(Request("a", [1, 2, 3], max_new_tokens=2))
    engine.step()
    snap = engine.snapshot()
    other = _mk_engine(tiny_gpt, seed=8)
    with pytest.raises(ValueError, match="config mismatch"):
        other.restore(snap)
    used = _mk_engine(tiny_gpt)
    used.add_request(Request("b", [4, 5], max_new_tokens=2))
    with pytest.raises(RuntimeError, match="fresh engine"):
        used.restore(snap)
    fresh = _mk_engine(tiny_gpt)
    fresh.restore(snap)
    out = fresh.run()
    # the retry knobs are operational, not identity: restoring into an
    # engine with a bigger retry budget (the incident-recovery move the
    # snapshot exists for) must work, and outputs are unaffected
    relaxed = _mk_engine(tiny_gpt, max_dispatch_retries=7,
                         retry_backoff_s=0.25)
    relaxed.restore(snap)
    assert relaxed.run() == out


def test_allocator_prefix_index_integrity_after_restore_and_lru(tiny_gpt):
    """Refcounts and hash chains after a restore + LRU churn must be
    EXACTLY what the engine's own bookkeeping implies — and the
    restored engine must keep producing reference outputs while the
    pool evicts under pressure."""
    shared = list(range(1, 13))   # three full shared blocks
    # pool of 10: the fourth request's growth must evict LRU cached
    # chains left behind by the finished ones
    reqs = [Request(f"r{i}", shared + [50 + i], max_new_tokens=8)
            for i in range(4)]
    ref_engine = _mk_engine(tiny_gpt, num_blocks=10)
    for r in reqs:
        ref_engine.add_request(r)
    ref = ref_engine.run()

    engine = _mk_engine(tiny_gpt, num_blocks=10)
    for r in reqs[:2]:
        engine.add_request(r)
    for _ in range(4):
        engine.step()
    snap = engine.snapshot()
    # audit section is present and JSON-able
    assert set(snap["allocator"]) >= {"refcounts", "prefix_index",
                                      "evictable", "free"}
    restored = _mk_engine(tiny_gpt, num_blocks=10)
    restored.restore(json.loads(json.dumps(snap)))
    out = dict(restored.run())
    for r in reqs[2:]:            # post-restore traffic: LRU churn
        restored.add_request(r)
    out.update(restored.run())
    assert out == ref
    st = restored.stats()
    assert st["num_cache_evictions"] > 0     # LRU actually exercised
    restored.check_allocator_integrity()     # exact refcount rebuild
    # the re-prefilled prefix index recovered the shared chain: the
    # last request's prompt found cached blocks again
    assert st["prefix_hit_blocks"] > 0


def test_snapshot_counters_in_stats(tiny_gpt):
    engine = _mk_engine(tiny_gpt)
    engine.add_request(Request("a", [1, 2, 3], max_new_tokens=2))
    engine.step()
    engine.snapshot()
    st = engine.stats()
    for key in ("num_timeouts", "num_dispatch_retries", "num_quarantines",
                "num_snapshots", "num_restores"):
        assert key in st
    assert st["num_snapshots"] == 1


def test_snapshot_restores_in_fresh_process(tiny_gpt, reference_outputs,
                                            tmp_path):
    """A snapshot taken mid-stream restores in a BRAND NEW process and
    finishes bit-identically: nothing device-resident or
    interpreter-resident is load-bearing."""
    engine = _mk_engine(tiny_gpt)
    for r in _requests():
        engine.add_request(r)
    for _ in range(4):
        engine.step()
    snap = engine.snapshot()
    assert any(rec["generated"] for rec in snap["requests"]), \
        "snapshot should be mid-stream (tokens already emitted)"
    snap_file = tmp_path / "snap.json"
    snap_file.write_text(json.dumps(snap))

    script = tmp_path / "restore_and_run.py"
    script.write_text(
        "import json, sys\n"
        "import jax, jax.numpy as jnp\n"
        "from apex_tpu.models import GPTConfig, GPTLMHeadModel\n"
        "from apex_tpu.serving import EngineConfig, InferenceEngine\n"
        "cfg = GPTConfig.tiny(dropout=0.0, remat=False)\n"
        "model = GPTLMHeadModel(cfg)\n"
        "params = model.init(jax.random.PRNGKey(0),\n"
        "                    jnp.zeros((1, 8), jnp.int32))\n"
        f"engine = InferenceEngine(model, params, EngineConfig(**{ENGINE_KW!r}))\n"
        f"engine.restore(json.load(open({str(snap_file)!r})))\n"
        "out = engine.run(return_status=True)\n"
        "print(json.dumps({u: {'tokens': r.tokens, 'status': r.status}\n"
        "                  for u, r in out.items()}))\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = str(repo)   # the script lives in tmp_path
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, timeout=540,
                          env=env, cwd=str(repo))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    combined = {u: list(t) for u, t in snap["finished"].items()}
    combined.update({u: r["tokens"] for u, r in out.items()})
    assert combined == reference_outputs
    assert all(r["status"] == "finished" for r in out.values())


# ---------------------------------------------------------------------------
# serving: overload chaos (ISSUE 8 — burst + faults + deadlines)
# ---------------------------------------------------------------------------


def test_overload_burst_with_faults_never_stalls_and_bounds_queue(
        tiny_gpt):
    """A 4x burst wave through a bounded queue WITH transient faults
    and a deadline/priority mix: the engine must never stall, never let
    client adds push the queue past ``max_waiting`` (requeues of
    residents may add at most ``max_batch``), and land every accepted
    request on a terminal status."""
    plan = FaultPlan([FaultSpec(site="prefill", kind="transient", at=(1,)),
                      FaultSpec(site="decode", kind="transient",
                                at=(2, 5))])
    now = [0.0]
    engine = _mk_engine(tiny_gpt, faults=plan, clock=lambda: now[0],
                        max_waiting=4, queue_high_watermark=3,
                        degrade_patience=1)
    rng = np.random.RandomState(3)
    offered = accepted = uid = 0
    # three waves: pre / 4x burst / post
    for count in (2, 8, 2):
        for _ in range(count):
            r = Request(f"o{uid}",
                        list(rng.randint(1, 100, 3 + uid % 4)),
                        max_new_tokens=3 + uid % 3,
                        priority=uid % 2,
                        deadline_s=(1.0 if uid % 3 == 0 else None))
            offered += 1
            accepted += int(engine.try_add(r))
            uid += 1
        for _ in range(2):
            had = engine.has_work
            progressed = engine.step()
            assert progressed or not had      # the stall contract
            now[0] += 0.4
    out = engine.run(return_status=True)
    s = engine.stats()
    assert accepted < offered                 # the burst really shed
    assert s["num_rejected_queue_full"] == offered - accepted
    assert s["queue_depth_peak"] <= 4 + engine.config.max_batch
    assert len(out) == accepted               # every accepted: terminal
    assert {r.status for r in out.values()} <= {
        "finished", "timeout", "failed", "rejected"}
    assert sum(r.status == "finished" for r in out.values()) > 0
    assert s["num_dispatch_retries"] >= 1     # the faults really fired
    assert s["num_degrade_steps_down"] >= 1   # the ladder really moved
    assert not engine.has_work


def test_restore_mid_degradation_is_bit_identical(tiny_gpt):
    """Snapshot taken WHILE the degradation ladder is engaged, restored
    into a fresh engine: the ladder state rides the snapshot and the
    combined outputs equal the uninterrupted run bit-for-bit (ladder
    transitions are schedule changes; sampling is schedule-invariant,
    sampled lanes included)."""
    kw = dict(max_batch=1, queue_high_watermark=2, degrade_patience=1)

    def reqs():
        return [Request(f"r{i}", [10 + i, 20 + i, 30 + i],
                        max_new_tokens=4, priority=i % 2,
                        sampling=(SamplingParams(temperature=0.8,
                                                 top_k=12)
                                  if i == 2 else SamplingParams()))
                for i in range(4)]

    ref_engine = _mk_engine(tiny_gpt, **kw)
    for r in reqs():
        ref_engine.add_request(r)
    ref = ref_engine.run()

    engine = _mk_engine(tiny_gpt, **kw)
    for r in reqs():
        engine.add_request(r)
    while engine.stats()["degradation_level"] < 1:
        engine.step()
    snap = json.loads(json.dumps(engine.snapshot()))
    assert snap["overload"]["degradation_level"] >= 1
    restored = _mk_engine(tiny_gpt, **kw)
    restored.restore(snap)
    assert (restored.stats()["degradation_level"]
            == snap["overload"]["degradation_level"])
    combined = {u: list(t) for u, t in snap["finished"].items()}
    combined.update(restored.run())
    assert combined == ref
    restored.check_allocator_integrity()


def test_multitenant_chaos_aborts_quotas_faults_ladder(tiny_gpt):
    """The ISSUE 10 chaos gate: aborts fired mid-flight, per-tenant
    quota sheds, transient prefill/decode faults, and degradation-
    ladder steps over interleaved tenants — the engine must never
    stall, land every accepted request on a terminal status, fire
    every chaos path at least once, and leave the allocator's
    per-tenant refcount split EXACT."""
    from apex_tpu.serving import TenantQuota

    plan = FaultPlan([FaultSpec(site="prefill", kind="transient",
                                at=(1, 6)),
                      FaultSpec(site="decode", kind="transient",
                                at=(2, 7))])
    now = [0.0]
    engine = _mk_engine(
        tiny_gpt, faults=plan, clock=lambda: now[0],
        max_waiting=5, queue_high_watermark=4, degrade_patience=1,
        enable_prefix_caching=True,
        tenant_weights={"good": 3, "flood": 1},
        tenant_quotas={"flood": TenantQuota(max_waiting=2,
                                            max_resident_blocks=4)})
    rng = np.random.RandomState(17)
    uid = 0
    accepted = []
    for wave in range(6):
        for _ in range(4):
            tenant = "flood" if uid % 2 else "good"
            r = Request(f"{tenant}-{uid}",
                        list(rng.randint(1, 100, 3 + uid % 4)),
                        max_new_tokens=3 + uid % 3, tenant=tenant,
                        priority=uid % 2,
                        deadline_s=(2.0 if uid % 5 == 0 else None))
            if engine.try_add(r):
                accepted.append(r.uid)
            uid += 1
        for _ in range(2):
            had = engine.has_work
            progressed = engine.step()
            assert progressed or not had       # the stall contract
            now[0] += 0.3
        if wave % 2 and accepted:
            engine.abort(accepted[rng.randint(len(accepted))])
    out = engine.run(return_status=True)
    s = engine.stats()
    engine.check_allocator_integrity()         # the certification
    assert {r.status for r in out.values()} <= {
        "finished", "timeout", "failed", "rejected", "throttled",
        "cancelled"}
    assert s["num_cancelled"] >= 1             # aborts fired
    assert s["num_throttled"] >= 1             # quota sheds fired
    assert s["num_dispatch_retries"] >= 1      # faults fired
    assert s["num_degrade_steps_down"] >= 1    # the ladder moved
    assert sum(r.status == "finished" for r in out.values()) > 0
    # only the flood tenant was ever throttled
    throttled = {u for u, r in out.items() if r.status == "throttled"}
    assert throttled and all(u.startswith("flood") for u in throttled)
    assert not engine.has_work


# ---------------------------------------------------------------------------
# crash-safe checkpoints (ISSUE 10 satellite): a torn save is invisible
# ---------------------------------------------------------------------------


def test_torn_checkpoint_save_is_skipped_on_resume(tmp_path,
                                                   monkeypatch):
    """Kill the process between the payload write and the terminal
    marker write: ``latest_step``/``load_checkpoint`` must resume from
    the PREVIOUS complete step, never the torn one."""
    from apex_tpu.utils import checkpoint as ck

    ck.save_checkpoint(str(tmp_path), 1, params={"w": np.ones(3)})
    ck.save_checkpoint(str(tmp_path), 2, params={"w": np.full(3, 2.0)})
    assert ck.latest_step(str(tmp_path)) == 2

    def crash(*a, **k):
        raise SimulatedCrash("killed between payload and marker")

    monkeypatch.setattr(ck, "_write_marker", crash)
    with pytest.raises(SimulatedCrash):
        ck.save_checkpoint(str(tmp_path), 3,
                           params={"w": np.full(3, 3.0)})
    monkeypatch.undo()
    # the torn step-3 payload exists on disk but is invisible
    assert (tmp_path / "step_000000003").exists()
    assert ck.latest_step(str(tmp_path)) == 2
    restored = ck.load_checkpoint(str(tmp_path))
    assert restored["_step"] == 2
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.full(3, 2.0))
    # explicitly naming the torn step raises rather than loading it
    with pytest.raises(FileNotFoundError, match="torn"):
        ck.load_checkpoint(str(tmp_path), step=3)
    # a clean re-save of the same step re-commits it
    ck.save_checkpoint(str(tmp_path), 3, params={"w": np.full(3, 9.0)})
    assert ck.latest_step(str(tmp_path)) == 3
    # overwrite path: the marker drops BEFORE the payload is replaced,
    # so a crash mid-overwrite reads as incomplete too
    monkeypatch.setattr(ck, "_write_marker", crash)
    with pytest.raises(SimulatedCrash):
        ck.save_checkpoint(str(tmp_path), 3,
                           params={"w": np.zeros(3)})
    monkeypatch.undo()
    assert ck.latest_step(str(tmp_path)) == 2


def test_legacy_markerless_checkpoints_stay_loadable(tmp_path):
    """A directory written entirely by the pre-marker code (no
    .complete files anywhere) keeps the old semantics: its steps are
    visible and loadable — upgrading must never orphan an existing
    run's checkpoints."""
    from apex_tpu.utils import checkpoint as ck

    ck.save_checkpoint(str(tmp_path), 4, params={"w": np.ones(2)})
    ck.save_checkpoint(str(tmp_path), 5, params={"w": np.full(2, 5.0)})
    # simulate a legacy directory by stripping the markers AND the
    # marker-era sentinel
    for f in tmp_path.glob("*.complete"):
        f.unlink()
    (tmp_path / ck._ERA_SENTINEL).unlink()
    assert ck.latest_step(str(tmp_path)) == 5
    assert ck.load_checkpoint(str(tmp_path))["_step"] == 5
    assert ck.load_checkpoint(str(tmp_path), step=4)["_step"] == 4
    # the first NEW save flips the directory to marker-governed:
    # the legacy steps (marker-less) now read as unproven
    ck.save_checkpoint(str(tmp_path), 6, params={"w": np.zeros(2)})
    assert ck.latest_step(str(tmp_path)) == 6


# ---------------------------------------------------------------------------
# training: retry, watchdog escalation, checkpoint/resume
# ---------------------------------------------------------------------------


class _Net(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.Dense(16, param_dtype=jnp.float32)(x)
        return nn.Dense(4, param_dtype=jnp.float32)(nn.relu(x))


@pytest.fixture(scope="module")
def train_setup():
    model = _Net()
    params = jax.device_get(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8)))["params"])

    def loss_fn(p, mb):
        x, y = mb
        logits = model.apply({"params": p}, x).astype(jnp.float32)
        onehot = jax.nn.one_hot(y, 4)
        return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1))

    rng = np.random.RandomState(0)
    batches = [(jnp.asarray(rng.randn(1, 4, 8).astype("f4")),
                jnp.asarray(rng.randint(0, 4, (1, 4))))
               for _ in range(8)]
    return params, loss_fn, batches


def _fresh_loop(train_setup, amp=None, **kwargs):
    params, loss_fn, _ = train_setup
    step = build_train_step(loss_fn, FusedAdam(lr=1e-2), amp=amp,
                            accum_steps=1)
    # params are COPIED per loop: the donating step consumes its
    # state's buffers, and the module fixture must stay reusable
    return step, step.loop(step.init(jax.tree.map(jnp.asarray, params)),
                           **kwargs)


@pytest.fixture(scope="module")
def train_reference(train_setup):
    _, loop = _fresh_loop(train_setup)
    metrics = loop.run(train_setup[2])
    return jax.device_get(loop.state.params), metrics


def _assert_params_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_train_transient_retry_matches_reference(train_setup,
                                                 train_reference):
    plan = FaultPlan([FaultSpec(site="train_step", kind="transient",
                                at=(1, 5))])
    _, loop = _fresh_loop(train_setup, faults=plan)
    metrics = loop.run(train_setup[2])
    _assert_params_equal(train_reference[0],
                         jax.device_get(loop.state.params))
    assert metrics == train_reference[1]
    assert loop.stats()["dispatch_retries"] == 2


def test_train_retry_exhaustion_raises_and_finally_drains(train_setup):
    plan = FaultPlan([FaultSpec(site="train_step", kind="transient",
                                at=tuple(range(2, 40)))])
    _, loop = _fresh_loop(train_setup, faults=plan, max_retries=1)
    with pytest.raises(DispatchFailedError, match="train_step"):
        loop.run(train_setup[2])
    # steps 0 and 1 completed; the finally-drain preserved BOTH their
    # metrics even though run() unwound mid-iteration
    assert len(loop.last_run_metrics) == 2
    assert [m["step"] for m in loop.last_run_metrics] == [1, 2]
    assert loop.stats()["dispatch_retries"] == 1


def test_watchdog_ladder_skip_rescale_halt(train_setup):
    from apex_tpu.amp.scaler import LossScaler

    plan = FaultPlan([FaultSpec(site="train_step", kind="nan", every=1)])
    # a dynamic scaler (init 2**16) so the rescale rung's halving is
    # observable — with amp=None the static unity scale is already at
    # the floor
    _, loop = _fresh_loop(
        train_setup, amp=LossScaler(), faults=plan,
        watchdog=WatchdogConfig(skip_steps=1, rescale_steps=2,
                                min_scale=1.0))
    scale0 = float(jax.device_get(loop.state.scaler_state.loss_scale))
    with pytest.raises(NonFiniteLossError) as ei:
        loop.run(train_setup[2])
    s = loop.stats()
    assert (s["watchdog_skips"], s["watchdog_rescales"],
            s["watchdog_halts"]) == (1, 2, 1)
    assert s["watchdog_nonfinite"] >= 4
    # the rescale rung really halved the scale, twice
    scale1 = float(jax.device_get(loop.state.scaler_state.loss_scale))
    assert scale1 == scale0 / 4
    assert math.isnan(float(ei.value.metrics["loss"]))
    assert ei.value.loop_stats["watchdog_rescales"] == 2
    # the halting run still surfaced every fetched step's metrics
    assert loop.last_run_metrics


def test_watchdog_halts_when_threshold_crossed_on_final_step(train_setup):
    # the halt rung first crossed by the LAST step's metrics is seen by
    # the completed-run drain, which must still raise — a wedged run
    # must never return as success just because it ran out of batches
    plan = FaultPlan([FaultSpec(site="train_step", kind="nan", every=1)])
    _, loop = _fresh_loop(
        train_setup, faults=plan,
        watchdog=WatchdogConfig(skip_steps=3, rescale_steps=3))
    with pytest.raises(NonFiniteLossError):
        loop.run(train_setup[2][:7])
    s = loop.stats()
    assert s["watchdog_halts"] == 1
    assert len(loop.last_run_metrics) == 6   # m1..m6; m7 is the halt


def test_watchdog_recovers_when_loss_turns_finite(train_setup):
    # non-finite for 2 steps, then clean: the ladder resets instead of
    # climbing to a halt
    plan = FaultPlan([FaultSpec(site="train_step", kind="nan", at=(1, 2))])
    _, loop = _fresh_loop(
        train_setup, faults=plan,
        watchdog=WatchdogConfig(skip_steps=2, rescale_steps=1))
    loop.run(train_setup[2])
    s = loop.stats()
    assert s["watchdog_skips"] == 2
    assert s["watchdog_rescales"] == 0 and s["watchdog_halts"] == 0


def test_chaos_certification_checkpoint_resume_bit_identical(
        train_setup, train_reference, tmp_path):
    """The training acceptance gate: transient faults + a crash; resume
    from the periodic checkpoint reproduces the uninterrupted final
    params bit-for-bit."""
    plan = FaultPlan([FaultSpec(site="train_step", kind="transient",
                                at=(2,)),
                      FaultSpec(site="train_step", kind="crash", at=(7,))])
    step, loop = _fresh_loop(train_setup, faults=plan,
                             checkpoint_dir=str(tmp_path),
                             checkpoint_every=2)
    with pytest.raises(SimulatedCrash):
        loop.run(train_setup[2])
    s = loop.stats()
    assert s["dispatch_retries"] >= 1
    assert s["checkpoints_saved"] >= 1
    assert s["last_checkpoint_step"] is not None

    step2, loop2 = _fresh_loop(train_setup)
    state, k = load_train_state(str(tmp_path), loop2.state)
    assert k == s["last_checkpoint_step"]
    resumed = TrainLoop(step2, state)
    resumed.run(train_setup[2][k:])
    _assert_params_equal(train_reference[0],
                         jax.device_get(resumed.state.params))
