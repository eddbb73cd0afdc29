"""Fleet-serving certification (tier-1, CPU): the ISSUE 13 layer
(docs/fleet.md).

The router's determinism bar: a 1-replica fleet is bit-identical to
the bare engine (outputs, statuses, schedule counters; greedy +
sampled, speculation on/off); migration mid-decode resumes
bit-identically; failover from the periodic lightweight checkpoint
(``snapshot_interval_ticks``) loses zero accepted requests and
re-derives post-checkpoint tokens exactly. Plus: the lightweight
checkpoint restore cert (the PR 6 cert extended), the spill-store
export/import transport (re-admit token-identical to recompute),
affinity/load routing, fleet-wide quotas, the router-level poison
quarantine, the recorder/trace_summary surface, and a fuzz
interleaving of add/abort/kill/migrate asserting every accepted uid
reaches exactly one terminal status fleet-wide."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTConfig, GPTLMHeadModel
from apex_tpu.observability import Observability
from apex_tpu.serving import (
    EngineConfig,
    FleetConfig,
    FleetFailedError,
    FleetRouter,
    HostSpillStore,
    InferenceEngine,
    Request,
    SamplingParams,
    TenantQuota,
    TenantThrottledError,
)
from apex_tpu.utils.faults import FaultPlan, FaultSpec


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return model, params


ENGINE_KW = dict(max_batch=2, block_size=4, num_blocks=32,
                 max_prefill_len=8, max_seq_len=32, seed=7,
                 enable_prefix_caching=True)


def _engine(tiny_gpt, clock=None, **overrides):
    model, params = tiny_gpt
    kw = dict(ENGINE_KW)
    kw.update(overrides)
    return InferenceEngine(model, params, EngineConfig(**kw),
                           clock=clock)


def _fleet(tiny_gpt, n=2, fleet_kw=None, clock=None, faults=None,
           obs=None, **overrides):
    model, params = tiny_gpt
    kw = dict(ENGINE_KW)
    kw.update(overrides)
    return FleetRouter(model, params, EngineConfig(**kw),
                       FleetConfig(num_replicas=n, **(fleet_kw or {})),
                       clock=clock, faults=faults, obs=obs)


def _reqs(n=5, sampled=True, prompt_len=6, new=5, seed=3, uid="r"):
    rng = np.random.RandomState(seed)
    out = []
    for k in range(n):
        prompt = list(rng.randint(1, 50, prompt_len))
        samp = (SamplingParams(temperature=1.0, top_k=10)
                if sampled and k % 2 == 0 else SamplingParams())
        out.append(Request(f"{uid}{k}", prompt, max_new_tokens=new,
                           sampling=samp))
    return out


def _resdict(res):
    return {u: (tuple(r.tokens), r.status) for u, r in res.items()}


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def test_fleet_config_validation():
    with pytest.raises(ValueError, match="num_replicas"):
        FleetConfig(num_replicas=0)
    with pytest.raises(ValueError, match="affinity_weight"):
        FleetConfig(affinity_weight=-1.0)
    with pytest.raises(ValueError, match="health_patience"):
        FleetConfig(health_patience=0)
    with pytest.raises(ValueError, match="max_request_failovers"):
        FleetConfig(max_request_failovers=0)
    with pytest.raises(ValueError, match="tenant_rate_tau_s"):
        FleetConfig(tenant_rate_tau_s=0.0)
    with pytest.raises(ValueError, match="TenantQuota"):
        FleetConfig(tenant_quotas={"a": 3})
    with pytest.raises(ValueError, match="tokens_per_s"):
        FleetConfig(tenant_quotas={"a": TenantQuota(tokens_per_s=-1)})


def test_engine_config_snapshot_interval_validation():
    with pytest.raises(ValueError, match="snapshot_interval_ticks"):
        EngineConfig(**ENGINE_KW, snapshot_interval_ticks=0)


def test_per_replica_lists_must_match(tiny_gpt):
    model, params = tiny_gpt
    with pytest.raises(ValueError, match="faults"):
        FleetRouter(model, params, EngineConfig(**ENGINE_KW),
                    FleetConfig(num_replicas=2), faults=[None])


# ---------------------------------------------------------------------------
# the 1-replica identity cert
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [0, 3])
def test_single_replica_fleet_bit_identical(tiny_gpt, spec):
    """1-replica fleet == bare engine bit-for-bit: outputs, terminal
    statuses, AND the full stats dict (schedule counters included) —
    greedy + sampled lanes, speculation on and off, under a constant
    clock so every time-derived stat compares exactly."""
    kw = dict(spec_tokens=spec, snapshot_interval_ticks=2)
    bare = _engine(tiny_gpt, clock=lambda: 0.0, **kw)
    for r in _reqs():
        bare.add_request(r)
    bare_res = bare.run(return_status=True)
    bare_stats = bare.stats()

    fleet = _fleet(tiny_gpt, n=1, clock=lambda: 0.0, **kw)
    for r in _reqs():
        fleet.add_request(r)
    fleet_res = fleet.run(return_status=True)
    assert _resdict(fleet_res) == _resdict(bare_res)
    assert fleet.replicas[0].engine.stats() == bare_stats
    assert fleet.stats()["num_lost_requests"] == 0


# ---------------------------------------------------------------------------
# the lightweight checkpoint (satellite: snapshot_interval_ticks)
# ---------------------------------------------------------------------------


def test_checkpoint_restore_rederives_inflight_tokens(tiny_gpt):
    """The PR 6 restore cert extended to checkpoint(): a LIGHTWEIGHT
    checkpoint taken WITHOUT draining the in-flight decode restores
    into a run bit-identical to the uninterrupted one — the tokens the
    undrained dispatch held are re-derived deterministically."""
    ref = _engine(tiny_gpt)
    for r in _reqs(n=3, new=8):
        ref.add_request(r)
    expect = ref.run(return_status=True)

    eng = _engine(tiny_gpt)
    for r in _reqs(n=3, new=8):
        eng.add_request(r)
    for _ in range(3):
        eng.step()
    assert eng._pending is not None, "no in-flight dispatch to strand"
    snap = eng.checkpoint()
    assert snap["lightweight"] is True
    # the checkpoint did NOT drain: the dispatch is still in flight
    assert eng._pending is not None
    assert eng.stats()["num_checkpoints"] == 1
    assert eng.stats()["num_snapshots"] == 0

    fresh = _engine(tiny_gpt)
    fresh.restore(snap)
    resumed = fresh.run(return_status=True)
    # pre-checkpoint terminal results (if any) rode the snapshot's
    # finished section; combined, the two runs equal the reference
    combined = dict(expect)
    assert {u: (r.tokens, r.status) for u, r in resumed.items()} == \
        {u: (combined[u].tokens, combined[u].status) for u in resumed}
    assert set(resumed) | set(snap["finished"]) == set(expect)


def test_snapshot_interval_auto_checkpoints(tiny_gpt):
    eng = _engine(tiny_gpt, snapshot_interval_ticks=2)
    assert eng.last_checkpoint is None
    for r in _reqs(n=2):
        eng.add_request(r)
    eng.run()
    stats = eng.stats()
    assert stats["num_checkpoints"] >= 2
    assert eng.last_checkpoint is not None
    # the final checkpoint is restorable (an empty engine picture by
    # then — but the format round-trips)
    fresh = _engine(tiny_gpt, snapshot_interval_ticks=2)
    fresh.restore(eng.last_checkpoint)


def test_interval_knob_out_of_restore_fingerprint(tiny_gpt):
    eng = _engine(tiny_gpt, snapshot_interval_ticks=2)
    for r in _reqs(n=1):
        eng.add_request(r)
    snap = eng.snapshot()
    fresh = _engine(tiny_gpt)   # no interval — still restorable
    fresh.restore(snap)
    assert fresh.run() is not None


# ---------------------------------------------------------------------------
# export / import (the migration records)
# ---------------------------------------------------------------------------


def test_export_import_resumes_bit_identical(tiny_gpt):
    """Engine-level drain-and-migrate: export a mid-decode request
    from A, import into B (same config/seed) — B's continuation is
    bit-identical to the never-migrated run, greedy AND sampled."""
    ref = _engine(tiny_gpt)
    for r in _reqs(n=2, new=8):
        ref.add_request(r)
    expect = ref.run()

    a = _engine(tiny_gpt)
    for r in _reqs(n=2, new=8):
        a.add_request(r)
    for _ in range(4):
        a.step()
    records = a.export_requests(["r0"])
    assert [r["uid"] for r in records] == ["r0"]
    assert a.stats()["num_migrated_out"] == 1
    a.check_allocator_integrity()

    b = _engine(tiny_gpt)
    b.import_requests(records)
    assert b.stats()["num_migrated_in"] == 1
    out_b = b.run()
    out_a = a.run()
    assert out_b["r0"] == expect["r0"]
    assert out_a["r1"] == expect["r1"]


def test_export_all_releases_everything(tiny_gpt):
    eng = _engine(tiny_gpt)
    for r in _reqs(n=4):
        eng.add_request(r)
    for _ in range(2):
        eng.step()
    records = eng.export_requests()
    assert len(records) == 4
    assert not eng.has_work
    eng.check_allocator_integrity()
    assert eng._live_uids == set()
    # exported requests got NO terminal status (they are alive
    # elsewhere): nothing to drain
    assert eng.run() == {}


def test_import_rejects_duplicate_uid(tiny_gpt):
    eng = _engine(tiny_gpt)
    req = _reqs(n=1)[0]
    eng.add_request(req)
    with pytest.raises(ValueError, match="already waiting"):
        eng.import_requests([{
            "uid": req.uid, "prompt": [1, 2], "max_new_tokens": 2,
            "sampling": {"temperature": 0.0, "top_k": 0, "top_p": 1.0},
        }])


def test_import_preserves_deadline_budget(tiny_gpt):
    t = [0.0]
    a = _engine(tiny_gpt, clock=lambda: t[0])
    a.add_request(Request("d0", [1, 2, 3, 4], max_new_tokens=4,
                          deadline_s=10.0))
    t[0] = 4.0
    rec = a.export_requests(["d0"])[0]
    assert rec["deadline_remaining_s"] == pytest.approx(6.0)
    t2 = [100.0]
    b = _engine(tiny_gpt, clock=lambda: t2[0])
    b.import_requests([rec])
    assert b._deadline["d0"] == pytest.approx(106.0)


# ---------------------------------------------------------------------------
# spill-store transport (satellite: export_entry / import_entry)
# ---------------------------------------------------------------------------


def test_spill_export_import_readmits_token_identical(tiny_gpt):
    """The cross-replica KV transport: blocks spilled on A, exported,
    imported into B's store — B serves the prompt token-identical to
    a plain recompute engine, with a nonzero spill hit rate."""
    spill_kw = dict(spill_max_bytes=1 << 20)
    prompt = list(np.random.RandomState(11).randint(1, 50, 12))

    def serve(eng, uid):
        eng.add_request(Request(uid, list(prompt), max_new_tokens=4))
        return eng.run()[uid]

    a = _engine(tiny_gpt, **spill_kw)
    expect = serve(a, "warm")
    # flush the device prefix cache: every registered block spills
    a.allocator.flush_evictable()
    assert len(a.spill) > 0
    hashes = a._seq_hashes(prompt)
    payloads = {h: a.spill.export_entry(h) for h in hashes
                if h in a.spill}
    assert payloads
    # export is a PEEK: A's store still holds (and can re-admit) them
    assert len(a.spill) == len(payloads)

    b = _engine(tiny_gpt, **spill_kw)
    assert b.import_prefix_payloads(payloads) == len(payloads)
    got = serve(b, "migrated")
    assert got == expect
    assert b.stats()["spill_hits"] > 0
    b.check_allocator_integrity()

    plain = _engine(tiny_gpt)
    assert serve(plain, "recompute") == expect


def test_spill_import_entry_validates_payload():
    store = HostSpillStore(1 << 16)
    with pytest.raises(ValueError, match="missing"):
        store.import_entry("h", {"k": np.zeros(4)})
    payload = {"k": np.zeros(4, np.float32), "v": np.ones(4, np.float32)}
    assert store.import_entry("h", payload) is True
    out = store.export_entry("h")
    np.testing.assert_array_equal(out["v"], payload["v"])
    out["v"][0] = 7.0   # deep copy: the store's entry is untouched
    np.testing.assert_array_equal(store.export_entry("h")["v"],
                                  payload["v"])
    assert store.export_entry("missing") is None


# ---------------------------------------------------------------------------
# fleet routing
# ---------------------------------------------------------------------------


def test_affinity_routing_prefers_warm_replica(tiny_gpt):
    fleet = _fleet(tiny_gpt, n=2)
    prompt = list(np.random.RandomState(5).randint(1, 50, 8))
    fleet.add_request(Request("warm", list(prompt), max_new_tokens=2))
    fleet.run()
    # replica 0 (ties break low) now caches the prompt's blocks; a
    # same-prefix request must land there, a distinct one elsewhere
    fleet.add_request(Request("hit", list(prompt), max_new_tokens=2))
    assert fleet.owners()["hit"] == 0
    other = list(np.random.RandomState(6).randint(50, 99, 8))
    fleet.add_request(Request("cold", other, max_new_tokens=2))
    assert fleet.owners()["cold"] == 1
    fleet.run()
    assert fleet.stats()["num_affinity_hits"] >= 1


def test_fleet_uid_uniqueness_and_abort(tiny_gpt):
    fleet = _fleet(tiny_gpt, n=2)
    req = _reqs(n=1)[0]
    fleet.add_request(req)
    with pytest.raises(ValueError, match="already live"):
        fleet.add_request(Request(req.uid, [1, 2], max_new_tokens=2))
    assert fleet.abort(req.uid) is True
    assert fleet.abort("ghost") is False
    res = fleet.run(return_status=True)
    assert res[req.uid].status == "cancelled"
    assert fleet.stats()["num_lost_requests"] == 0


def test_fleet_door_quota_aggregates_across_replicas(tiny_gpt):
    fleet = _fleet(tiny_gpt, n=2, fleet_kw=dict(
        tenant_quotas={"t": TenantQuota(max_waiting=2)}))
    reqs = _reqs(n=3, uid="q", sampled=False)
    for r in reqs[:2]:
        fleet.add_request(Request(r.uid, list(r.prompt),
                                  max_new_tokens=2, tenant="t"))
    # per-replica depth is 1 each — only the FLEET aggregate trips
    with pytest.raises(TenantThrottledError, match="fleet"):
        fleet.add_request(Request("q2", list(reqs[2].prompt),
                                  max_new_tokens=2, tenant="t"))
    assert fleet.try_add(Request("q3", [1, 2, 3],
                                 max_new_tokens=2, tenant="t")) is False
    res = fleet.run(return_status=True)
    assert res["q2"].status == "throttled"
    stats = fleet.stats()
    assert stats["num_throttled"] == 2
    assert stats["tenants"]["t"]["statuses"]["router_throttled"] == 2


# ---------------------------------------------------------------------------
# failover
# ---------------------------------------------------------------------------


def test_crash_fault_failover_zero_loss(tiny_gpt):
    """An injected FaultPlan crash escapes the replica's step() — the
    router declares it dead and re-homes everything; every accepted
    uid reaches exactly one terminal status."""
    faults = [FaultPlan([FaultSpec(site="decode", kind="crash", at=(2,))],
                        seed=1),
              None]
    fleet = _fleet(tiny_gpt, n=2, faults=faults,
                   snapshot_interval_ticks=2)
    for r in _reqs(n=4, new=6):
        fleet.add_request(r)
    res = fleet.run(return_status=True)
    stats = fleet.stats()
    assert set(res) == {f"r{k}" for k in range(4)}
    assert stats["num_failovers"] == 1
    assert stats["num_replicas_down"] == 1
    assert stats["replicas_alive"] == 1
    assert stats["num_lost_requests"] == 0
    assert all(r.status in ("finished", "failed") for r in res.values())
    assert sum(r.status == "finished" for r in res.values()) >= 3


def test_kill_replica_rederives_from_checkpoint(tiny_gpt):
    """Hard kill (engine discarded unread): recovery from the last
    periodic checkpoint alone, and the re-homed requests' token
    streams equal the no-kill fleet run bit-for-bit (arrival identity
    rides the checkpoint records; equal seeds across the fleet)."""
    def build():
        fleet = _fleet(tiny_gpt, n=2, snapshot_interval_ticks=2)
        for r in _reqs(n=4, new=6):
            fleet.add_request(r)
        return fleet

    ref = build()
    expect = ref.run(return_status=True)

    fleet = build()
    for _ in range(3):
        fleet.step()
    killed = fleet.owners()["r0"]
    fleet.kill_replica(killed)
    assert fleet.replicas[killed].engine is None
    res = fleet.run(return_status=True)
    assert _resdict(res) == _resdict(expect)
    assert fleet.stats()["num_lost_requests"] == 0
    assert fleet.stats()["num_failovers"] == 1


def test_failover_without_checkpoint_reinjects_fresh(tiny_gpt):
    """No snapshot_interval_ticks and a hard kill: last_checkpoint is
    None, so everything re-injects fresh from the router's Request
    copies — still zero loss (fresh arrivals, so sampled draws may
    differ; nothing was delivered, so nothing diverges)."""
    fleet = _fleet(tiny_gpt, n=2)
    for r in _reqs(n=4, sampled=False):
        fleet.add_request(r)
    for _ in range(2):
        fleet.step()
    fleet.kill_replica(0)
    res = fleet.run(return_status=True)
    stats = fleet.stats()
    assert set(res) == {f"r{k}" for k in range(4)}
    assert stats["num_lost_requests"] == 0
    assert stats["num_reinjected_requests"] >= 1


def test_stalled_replica_fails_over_after_patience(tiny_gpt):
    fleet = _fleet(tiny_gpt, n=2, fleet_kw=dict(health_patience=2),
                   snapshot_interval_ticks=1)
    for r in _reqs(n=2, sampled=False):
        fleet.add_request(r)
    fleet.step()
    # wedge replica 0: has work, but step() reports no progress
    victim = fleet.replicas[0].engine
    if not victim.has_work:
        pytest.skip("routing sent nothing to replica 0")
    victim.step = lambda: False
    res = fleet.run(return_status=True)
    stats = fleet.stats()
    assert stats["num_replicas_down"] == 1
    assert fleet.replicas[0].alive is False
    assert "stall" in fleet.replicas[0].error
    assert set(res) == {"r0", "r1"}
    assert stats["num_lost_requests"] == 0


def test_poison_request_router_quarantine(tiny_gpt):
    """A request that keeps killing replicas terminal-fails at the
    router (max_request_failovers) instead of cascading forever: every
    replica — respawns included, which reuse the slot's fault plan —
    crashes EVERY decode dispatch, so only the quarantine can end the
    run. The fleet survives and the verdict is exactly-once."""
    model, params = tiny_gpt
    plans = [FaultPlan([FaultSpec(site="decode", kind="crash",
                                  every=1)], seed=s) for s in (2, 3)]
    fleet = FleetRouter(
        model, params, EngineConfig(**ENGINE_KW),
        FleetConfig(num_replicas=2, respawn=True,
                    max_request_failovers=2),
        faults=plans)
    fleet.add_request(_reqs(n=1, sampled=False)[0])
    res = fleet.run(return_status=True)
    stats = fleet.stats()
    assert res["r0"].status == "failed"
    assert stats["num_router_failed"] == 1
    assert stats["num_replicas_down"] == 3   # max_request_failovers + 1
    assert stats["num_respawns"] == 3
    assert stats["num_lost_requests"] == 0
    assert stats["replicas_alive"] == 2      # the fleet itself survived


def test_all_replicas_dead_raises_fleet_failed(tiny_gpt):
    faults = [FaultPlan([FaultSpec(site="decode", kind="crash",
                                   at=(0,))], seed=3)]
    fleet = _fleet(tiny_gpt, n=1, faults=faults,
                   fleet_kw=dict(max_request_failovers=5))
    fleet.add_request(_reqs(n=1, sampled=False)[0])
    with pytest.raises(FleetFailedError):
        fleet.run()


# ---------------------------------------------------------------------------
# migration (fleet-level)
# ---------------------------------------------------------------------------


def test_migration_mid_decode_bit_identical(tiny_gpt):
    """drain-and-migrate mid-decode: the migrated fleet run equals the
    unmigrated fleet run bit-for-bit (greedy + sampled lanes)."""
    def build():
        fleet = _fleet(tiny_gpt, n=2)
        for r in _reqs(n=3, new=8):
            fleet.add_request(r)
        return fleet

    ref = build()
    expect = ref.run(return_status=True)

    fleet = build()
    for _ in range(3):
        fleet.step()
    src = fleet.owners().get("r0")
    if src is None:
        pytest.skip("r0 already finished before migration")
    moved = fleet.migrate(["r0"], src)
    assert moved == 1
    assert fleet.owners()["r0"] != src
    res = fleet.run(return_status=True)
    assert _resdict(res) == _resdict(expect)
    stats = fleet.stats()
    assert stats["num_migrations"] == 1
    assert stats["num_migrated_requests"] == 1
    assert stats["num_lost_requests"] == 0


def test_drain_replica_retires_cleanly(tiny_gpt):
    fleet = _fleet(tiny_gpt, n=2)
    for r in _reqs(n=4, sampled=False):
        fleet.add_request(r)
    fleet.step()
    moved = fleet.drain_replica(0, retire=True)
    assert fleet.replicas[0].alive is False
    assert fleet.replicas[0].error == "retired"
    res = fleet.run(return_status=True)
    assert set(res) == {f"r{k}" for k in range(4)}
    stats = fleet.stats()
    assert stats["num_failovers"] == 0      # clean: no failover path
    assert stats["num_migrated_requests"] == moved
    assert stats["num_lost_requests"] == 0


def test_retire_delivers_results_finished_by_the_export_drain(tiny_gpt):
    """Regression: export_requests drains the in-flight decode, which
    can FINISH a lane (budget hit inside the synced dispatch) — a
    retire must collect that verdict before leaving the per-tick
    drain loop, or the result would be stranded on the corpse."""
    fleet = _fleet(tiny_gpt, n=2)
    fleet.add_request(Request("tiny", [1, 2, 3, 4, 5],
                              max_new_tokens=2))
    src = fleet.owners()["tiny"]
    eng = fleet.replicas[src].engine
    # step the ENGINE directly so the finishing drain happens inside
    # drain_replica's export, not a router tick
    while eng._pending is None and eng.has_work:
        eng.step()
    assert eng._pending is not None
    moved = fleet.drain_replica(src, retire=True)
    assert moved == 0          # the export's drain finished it first
    res = fleet.run(return_status=True)
    assert res["tiny"].status == "finished"
    assert len(res["tiny"].tokens) == 2
    assert fleet.stats()["num_lost_requests"] == 0


def test_migration_ships_spill_payloads(tiny_gpt):
    """With spill tiers on both ends, migration seeds the target's
    store with the prompt's KV payloads — the target re-admits by
    upload (spill_hits > 0) instead of recomputing."""
    fleet = _fleet(tiny_gpt, n=2, spill_max_bytes=1 << 20)
    prompt = list(np.random.RandomState(9).randint(1, 50, 12))
    fleet.add_request(Request("m0", list(prompt), max_new_tokens=6))
    src = fleet.owners()["m0"]
    # let it prefill + decode a little so blocks are registered
    for _ in range(4):
        fleet.step()
    if fleet.owners().get("m0") is None:
        pytest.skip("request finished before migration")
    dst = 1 - src
    fleet.migrate(["m0"], src, dst=dst)
    fleet.run()
    assert fleet.replicas[dst].engine.stats()["spill_hits"] > 0


# ---------------------------------------------------------------------------
# observability surface
# ---------------------------------------------------------------------------


def test_router_recorder_events_and_trace_summary(tiny_gpt, tmp_path):
    obs = Observability(trace=False, metrics=False)
    fleet = _fleet(tiny_gpt, n=2, snapshot_interval_ticks=2, obs=obs)
    for r in _reqs(n=4, new=8, sampled=False):
        fleet.add_request(r)
    for _ in range(2):
        fleet.step()
    # everything onto replica 1 (a migrate event), then kill it (a
    # replica_down + failover re-homing onto replica 0)
    moved = fleet.migrate(None, 0, dst=1)
    assert moved > 0, "nothing lived on replica 0 to migrate"
    fleet.kill_replica(1)
    fleet.run()
    assert fleet.stats()["num_lost_requests"] == 0
    kinds = {e["kind"] for e in obs.recorder.tail()}
    assert {"migrate", "replica_down", "failover"} <= kinds

    import json
    dump_path = tmp_path / "fleet_dump.json"
    dump_path.write_text(json.dumps(obs.dump(), default=str))
    spec = importlib.util.spec_from_file_location(
        "_trace_summary",
        Path(__file__).resolve().parents[1] / "tools" /
        "trace_summary.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report = mod.summarize_file(str(dump_path))
    assert "-- fleet:" in report
    assert "replicas down" in report


def test_fleet_stats_surface(tiny_gpt):
    fleet = _fleet(tiny_gpt, n=2)
    stats = fleet.stats()
    for key in ("num_replicas", "replicas_alive", "num_failovers",
                "num_migrations", "num_lost_requests", "replicas",
                "tenants", "num_affinity_hits", "queue_depth"):
        assert key in stats
    assert stats["replicas"]["0"]["alive"] is True
    # the engine-side load surface the router polls
    ld = fleet.replicas[0].engine.load()
    assert set(ld) == {"queue_depth", "active_slots",
                       "ewma_prefill_dispatch_s",
                       "ewma_decode_dispatch_s", "blocks_allocatable"}


# ---------------------------------------------------------------------------
# review-hardening regressions
# ---------------------------------------------------------------------------


def test_cold_replica_backlog_weighs_neutral_not_zero(tiny_gpt):
    """Regression: a replica with no service EWMAs (cold/respawned)
    must weigh its backlog at the neutral 1.0 — a relative weight of
    0 made its queue invisible to placement and funneled every
    arrival at it."""
    fleet = _fleet(tiny_gpt, n=2)
    warm, cold = fleet.replicas[0].engine, fleet.replicas[1].engine
    warm._ewma_prefill_s = warm._ewma_decode_s = 0.01
    # warm replica: small backlog; cold replica: triple it
    warm.add_request(Request("w0", [1, 2, 3], max_new_tokens=2))
    for k in range(3):
        cold.add_request(Request(f"c{k}", [4 + k, 5, 6],
                                 max_new_tokens=2))
    # the cold replica's larger backlog must lose the placement
    ranked = fleet._ranked([7, 8, 9, 10])
    assert ranked[0][0] == 0


def test_retire_last_alive_replica_refuses(tiny_gpt):
    fleet = _fleet(tiny_gpt, n=1)
    fleet.add_request(_reqs(n=1, sampled=False)[0])
    with pytest.raises(ValueError, match="last alive replica"):
        fleet.drain_replica(0, retire=True)
    # nothing was harmed: the request still serves
    assert fleet.run(return_status=True)["r0"].status == "finished"
    # an IDLE last replica may retire
    fleet2 = _fleet(tiny_gpt, n=1)
    assert fleet2.drain_replica(0, retire=True) == 0
    assert fleet2.replicas[0].alive is False


def test_failover_preserves_streamed_tokens_of_uncheckpointed(tiny_gpt):
    """Regression: a SAMPLED request accepted after the last
    checkpoint (here: no checkpoint at all) that already streamed
    tokens must carry them through the fresh re-injection — the new
    arrival identity redraws only future tokens, so the delivered
    stream stays a prefix of the terminal result."""
    fleet = _fleet(tiny_gpt, n=2)   # no snapshot_interval_ticks
    fleet.add_request(Request(
        "s0", [3, 1, 4, 1, 5], max_new_tokens=8,
        sampling=SamplingParams(temperature=1.0, top_k=10)))
    streamed = []
    for _ in range(4):
        fleet.step()
        streamed += [tok for uid, tok, last
                     in fleet.pop_stream_events() if tok >= 0]
    assert streamed, "nothing streamed before the kill"
    fleet.kill_replica(fleet.owners()["s0"])
    res = fleet.run(return_status=True)
    assert res["s0"].tokens[:len(streamed)] == streamed
    assert fleet.stats()["num_reinjected_requests"] == 1


def test_stream_tokens_exactly_once_under_kill(tiny_gpt):
    """Regression: tokens a failover re-derivation replays (emitted
    after the checkpoint, streamed before the crash) are suppressed
    by the delivery watermark — per uid, the streamed token sequence
    equals the terminal result exactly, no duplicates."""
    fleet = _fleet(tiny_gpt, n=2, snapshot_interval_ticks=2)
    for r in _reqs(n=4, new=8):
        fleet.add_request(r)
    streamed = {}
    killed = False
    tick = 0
    while fleet.has_work:
        fleet.step()
        tick += 1
        # kill AFTER a checkpoint boundary with later ticks streamed,
        # so the checkpoint is genuinely stale
        if tick == 3 and not killed:
            fleet.kill_replica(fleet.owners()[
                next(iter(fleet.owners()))])
            killed = True
        for uid, tok, last in fleet.pop_stream_events():
            if tok >= 0:
                streamed.setdefault(uid, []).append(tok)
    assert killed
    res = fleet.run(return_status=True)
    for uid, toks in streamed.items():
        assert toks == res[uid].tokens, (
            f"{uid}: streamed {toks} != result {res[uid].tokens}")
    assert fleet.stats()["num_lost_requests"] == 0


def test_fleet_door_resident_charge_sums_across_replicas(tiny_gpt):
    """Regression: the fleet-wide max_resident_blocks quota must
    compare the tenant's resident charge SUMMED across replicas plus
    the request's worst case — not only the per-request footprint."""
    fleet = _fleet(tiny_gpt, n=2, fleet_kw=dict(
        tenant_quotas={"t": TenantQuota(max_resident_blocks=4)}))
    # 8-token prompt + 4 new = 3 blocks worst case: passes the
    # per-request check (3 <= 4)
    fleet.add_request(Request("a", list(range(1, 9)),
                              max_new_tokens=4, tenant="t"))
    fleet.step()     # admitted: the tenant now HOLDS blocks
    with pytest.raises(TenantThrottledError, match="resident"):
        fleet.add_request(Request("b", list(range(1, 9)),
                                  max_new_tokens=4, tenant="t"))
    res = fleet.run(return_status=True)
    assert res["a"].status == "finished"
    assert res["b"].status == "throttled"
    # charge drains with the residency: the same request is admissible
    # once "a" finished (its cached blocks hold no references)
    fleet.add_request(Request("c", list(range(1, 9)),
                              max_new_tokens=4, tenant="t"))
    assert fleet.run(return_status=True)["c"].status == "finished"


def test_failover_adopts_only_owned_checkpoint_results(tiny_gpt):
    """Regression: a stale checkpoint listing finished uids from
    already-delivered lifetimes must not resurrect them (or disown a
    reused uid now live elsewhere) — adoption is restricted to uids
    the dead replica still owns."""
    fleet = _fleet(tiny_gpt, n=2, snapshot_interval_ticks=1)
    fleet.add_request(Request("x", [1, 2, 3, 4], max_new_tokens=2))
    first = fleet.run(return_status=True)
    assert first["x"].status == "finished"
    # the dead replica's checkpoint still lists batch-1 "x" as
    # finished (it was undrained at checkpoint time); batch 2 reuses
    # the uid on the OTHER replica
    owner1 = 0
    fleet.add_request(Request("y", [9, 9, 9, 9, 9, 9, 9, 9],
                              max_new_tokens=4))
    # force the reused uid onto the survivor by loading replica 0
    fleet.add_request(Request("x", [5, 6, 7, 8], max_new_tokens=3))
    kill = owner1 if fleet.owners()["x"] != owner1 else 1
    assert fleet.owners()["x"] != kill
    fleet.kill_replica(kill)
    res = fleet.run(return_status=True)
    # the reused uid's result is the NEW lifetime's, not batch 1's
    assert len(res["x"].tokens) == 3
    assert fleet.stats()["num_lost_requests"] == 0


def test_soft_death_drains_stream_before_checkpoint(tiny_gpt):
    """Regression: an in-process replica death (exception escape)
    must collect the intact engine's buffered stream events before
    the failover checkpoint, or the delivery watermark anchors past
    tokens the consumer never received (a silent stream gap)."""
    faults = [FaultPlan([FaultSpec(site="decode", kind="crash",
                                   at=(3,))], seed=4), None]
    fleet = _fleet(tiny_gpt, n=2, faults=faults)
    fleet.add_request(Request(
        "g0", [2, 7, 1, 8], max_new_tokens=8,
        sampling=SamplingParams(temperature=1.0, top_k=10)))
    streamed = []
    while fleet.has_work:
        fleet.step()
        streamed += [tok for uid, tok, last
                     in fleet.pop_stream_events() if tok >= 0]
    res = fleet.run(return_status=True)
    assert fleet.stats()["num_replicas_down"] == 1
    # gapless and exactly-once: the streamed sequence IS the result
    assert streamed == res["g0"].tokens


def test_import_requests_anchors_observer_timeline(tiny_gpt):
    model, params = tiny_gpt
    obs = Observability(recorder_capacity=0, metrics=False)
    eng = InferenceEngine(model, params, EngineConfig(**ENGINE_KW),
                          obs=obs)
    eng.import_requests([{
        "uid": "mig", "prompt": [1, 2, 3], "max_new_tokens": 2,
        "sampling": {"temperature": 0.0, "top_k": 0, "top_p": 1.0},
        "generated": [], "arrival": 5,
    }])
    evs = obs.tracer.request_timeline("mig")
    assert any(e["type"] == "requeue" for e in evs)
    eng.run()


# ---------------------------------------------------------------------------
# the kill scenario: a replica lost mid-burst, and what its victims pay
# ---------------------------------------------------------------------------


def test_kill_mid_burst_victims_ttft_bounded_scenario(tiny_gpt):
    """Three replicas serve a seeded Poisson trace with a 3x burst
    and shared-prefix groups. Run once undisturbed for the baseline
    TTFT p99 in router ticks; then again with transient faults on every
    replica, replica 1 hard-killed mid-burst (recovery from its last
    periodic checkpoint alone) and replica 2 drained and migrated
    later. Nothing accepted is lost, the failover and the migration
    both fire, the survivors' allocators stay exact, and the requests
    the dead replica owned at the kill - which pay the failover's
    re-prefill - see their first token within ``4 x baseline p99 + 16``
    ticks."""
    from _traffic import TickClock, drive, poisson_burst_trace

    model, params = tiny_gpt
    ekw = dict(max_batch=4, block_size=8, num_blocks=64,
               max_prefill_len=16, max_seq_len=48,
               enable_prefix_caching=True, snapshot_interval_ticks=2,
               max_waiting=32, seed=11)
    ticks, kill_tick, drain_tick = 16, 6, 10
    heads = np.random.RandomState(1813)
    prefixes = [list(heads.randint(0, 128, 8)) for _ in range(3)]
    rng = np.random.RandomState(1814)

    def make(tick, k):
        # a group's requests open with the same block-aligned head, so
        # affinity routing has something to win
        tail = int(rng.choice((8, 14))) - 4
        prompt = (prefixes[k % 3] + list(rng.randint(0, 128, tail)))[:14]
        samp = (SamplingParams() if k % 2 else
                SamplingParams(temperature=1.0, top_k=40))
        new = int(rng.choice((4, 6)))
        return lambda: Request(uid=f"q{k}", prompt=list(prompt),
                               max_new_tokens=new, sampling=samp)

    trace = poisson_burst_trace(
        rng, ticks, 0.5, make, burst_start=ticks // 3,
        burst_end=2 * ticks // 3, burst_factor=3)

    clock = TickClock()     # placement by the trace, not the machine

    def fleet_of(faults=None, **engine_kw):
        return FleetRouter(model, params, EngineConfig(**ekw, **engine_kw),
                           FleetConfig(num_replicas=3), faults=faults,
                           clock=clock)

    def serve(fleet, before_step=None):
        seen = drive(fleet, trace, clock=clock, before_step=before_step)
        res = fleet.run(return_status=True)
        assert sorted(res) == sorted(seen.accepted)     # exactly once
        assert fleet.stats()["num_lost_requests"] == 0
        return seen, res

    base, _ = serve(fleet_of())

    fleet = fleet_of(
        faults=[FaultPlan([FaultSpec(site=site, kind="transient",
                                     every=every)], seed=seed)
                for site, every, seed in (("prefill", 9, 1815),
                                          ("decode", 11, 1816),
                                          ("decode", 13, 1817))],
        max_dispatch_retries=3)
    victims = []

    def kill_then_drain(tick, seen):
        if tick == kill_tick:
            victims.extend(u for u, o in fleet.owners().items() if o == 1)
            fleet.kill_replica(1)
        if tick == drain_tick and fleet.replicas[2].alive:
            fleet.drain_replica(2)

    seen, res = serve(fleet, kill_then_drain)
    stats = fleet.stats()
    assert stats["num_failovers"] >= 1, "the kill never fired"
    assert stats["num_migrations"] >= 1, "the drain never migrated"
    assert sum(r.status == "finished" for r in res.values()) > 0
    for _, rep in fleet._alive():
        rep.engine.check_allocator_integrity()
    assert victims, "the killed replica owned nothing"
    assert seen.ttft_p99(victims) <= 4.0 * base.ttft_p99() + 16.0


# ---------------------------------------------------------------------------
# the fuzz interleaving (satellite)
# ---------------------------------------------------------------------------


def test_fuzz_add_abort_kill_migrate_exactly_once(tiny_gpt):
    """Seeded fuzz over add/abort/kill/migrate/step: every accepted
    uid reaches EXACTLY ONE terminal status fleet-wide, the zero-lost
    gauge stays 0 throughout, and the surviving allocators stay
    exact."""
    rng = np.random.RandomState(1234)
    model, params = tiny_gpt
    fleet = FleetRouter(
        model, params,
        EngineConfig(**ENGINE_KW, snapshot_interval_ticks=2),
        FleetConfig(num_replicas=3, respawn=True))
    shared = list(rng.randint(1, 50, 8))
    accepted, uid = [], 0
    kills = 0
    for op_i in range(60):
        op = rng.rand()
        if op < 0.45:
            prompt = (list(shared) if rng.rand() < 0.5
                      else list(rng.randint(1, 50, rng.randint(3, 10))))
            samp = (SamplingParams(temperature=1.0, top_k=10)
                    if rng.rand() < 0.5 else SamplingParams())
            req = Request(f"f{uid}", prompt,
                          max_new_tokens=int(rng.randint(1, 6)),
                          sampling=samp)
            uid += 1
            if fleet.try_add(req):
                accepted.append(req.uid)
        elif op < 0.55 and accepted:
            fleet.abort(accepted[int(rng.randint(len(accepted)))])
        elif op < 0.62 and kills < 3:
            alive = [i for i, rep in enumerate(fleet.replicas)
                     if rep.alive]
            if len(alive) > 1:
                fleet.kill_replica(alive[int(rng.randint(len(alive)))])
                kills += 1
        elif op < 0.72:
            owners = fleet.owners()
            if owners:
                u = list(owners)[int(rng.randint(len(owners)))]
                fleet.migrate([u], owners[u])
        else:
            fleet.step()
        assert fleet.stats()["num_lost_requests"] == 0
    res = fleet.run(return_status=True)
    assert kills > 0, "fuzz never killed a replica"
    # exactly-once: every accepted uid has one terminal verdict
    assert set(res) >= set(accepted)
    terminal = {"finished", "timeout", "failed", "rejected",
                "throttled", "cancelled"}
    assert all(r.status in terminal for r in res.values())
    stats = fleet.stats()
    assert stats["num_lost_requests"] == 0
    for rep in fleet.replicas:
        if rep.alive and rep.engine is not None:
            rep.engine.check_allocator_integrity()


def test_fuzz_with_corruption_faults_zero_undetected(tiny_gpt):
    """The 60-op fuzz under seeded CORRUPTION plans covering every
    checksum point (spill writes/reads, checkpoints, migration records
    both directions), with independent test-side oracles wrapped
    around every consumption path: the zero-lost gauge reads 0 after
    every op, and ZERO corrupted artifacts are consumed undetected —
    every spill payload an engine admits hashes to the clean bytes its
    put recorded, and every migration record an import ACCEPTS matches
    the record the caller sent (a corruption either refused/discarded
    — caught — or never consumed)."""
    from apex_tpu.utils.integrity import payload_checksum

    rng = np.random.RandomState(4321)
    model, params = tiny_gpt
    plans = [FaultPlan([
        FaultSpec(site="spill_put", kind="corrupt", every=3),
        FaultSpec(site="spill_get", kind="corrupt", every=4),
        FaultSpec(site="checkpoint", kind="corrupt", every=2),
        FaultSpec(site="export", kind="corrupt", every=2),
        FaultSpec(site="import", kind="corrupt", every=3),
    ], seed=100 + i) for i in range(3)]
    ekw = dict(ENGINE_KW, num_blocks=12, spill_max_bytes=1 << 20,
               snapshot_interval_ticks=2, scrub_interval_ticks=3)
    fleet = FleetRouter(
        model, params, EngineConfig(**ekw),
        FleetConfig(num_replicas=3, respawn=True),
        faults=plans)
    truth: dict = {}    # chain hash -> clean payload checksum

    def wrap_store(store):
        orig_put, orig_pop = store.put, store.pop

        def put(h, payload, tenant="default"):
            truth[h] = payload_checksum(payload)  # the TRUE bytes
            return orig_put(h, payload, tenant=tenant)

        def pop(h):
            out = orig_pop(h)
            if out is not None:
                assert payload_checksum(out) == truth[h], (
                    f"UNDETECTED corrupt spill admission for {h}")
            return out

        store.put, store.pop = put, pop

    def wrap_import(eng):
        orig = eng.import_requests

        def import_requests(records):
            want = {r["uid"]: ([int(t) for t in r["prompt"]],
                               [int(t) for t in r.get("generated", ())])
                    for r in records}
            n = orig(records)
            for entry in eng.waiting:
                got = want.get(entry.request.uid)
                if got is not None:
                    assert ([int(t) for t in entry.request.prompt],
                            [int(t) for t in entry.generated]) == got, (
                        "UNDETECTED corrupt import accepted")
            return n

        eng.import_requests = import_requests

    def instrument(rep):
        if rep.engine is None:
            return
        if rep.engine.spill is not None:
            wrap_store(rep.engine.spill)
        wrap_import(rep.engine)

    for rep in fleet.replicas:
        instrument(rep)
    shared = list(rng.randint(1, 50, 8))
    accepted, uid, kills = [], 0, 0
    for op_i in range(60):
        op = rng.rand()
        if op < 0.45:
            prompt = (list(shared) if rng.rand() < 0.5
                      else list(rng.randint(1, 50, rng.randint(3, 10))))
            samp = (SamplingParams(temperature=1.0, top_k=10)
                    if rng.rand() < 0.5 else SamplingParams())
            req = Request(f"z{uid}", prompt,
                          max_new_tokens=int(rng.randint(1, 6)),
                          sampling=samp)
            uid += 1
            if fleet.try_add(req):
                accepted.append(req.uid)
        elif op < 0.55 and accepted:
            fleet.abort(accepted[int(rng.randint(len(accepted)))])
        elif op < 0.62 and kills < 3:
            alive = [i for i, rep in enumerate(fleet.replicas)
                     if rep.alive]
            if len(alive) > 1:
                victim = alive[int(rng.randint(len(alive)))]
                fleet.kill_replica(victim)
                instrument(fleet.replicas[victim])   # the respawn
                kills += 1
        elif op < 0.72:
            owners = fleet.owners()
            if owners:
                u = list(owners)[int(rng.randint(len(owners)))]
                fleet.migrate([u], owners[u])
        else:
            fleet.step()
        assert fleet.stats()["num_lost_requests"] == 0
    res = fleet.run(return_status=True)
    assert kills > 0
    assert set(res) >= set(accepted)
    stats = fleet.stats()
    assert stats["num_lost_requests"] == 0
    # the chaos genuinely fired AND was genuinely caught somewhere:
    # refused imports, corrupt checkpoints, or spill discards
    detections = (
        stats["num_refused_imports"] + stats["num_corrupt_checkpoints"]
        + sum(rep.engine.stats()["num_corruptions_detected"]
              for rep in fleet.replicas
              if rep.alive and rep.engine is not None))
    assert detections > 0, "corruption plan never detected anything"
    for rep in fleet.replicas:
        if rep.alive and rep.engine is not None:
            rep.engine.check_allocator_integrity()
