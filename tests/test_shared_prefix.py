"""Fleet-global shared prefix tier certification (tier-1, CPU): the
ISSUE 18 layer (docs/fleet.md, "Shared prefix tier").

The :class:`SharedPrefixStore` unit contracts — content-addressed
refcounted dedupe (one copy, publisher shares audited by
``check_integrity``), byte-budget LRU eviction with the side tables
kept consistent, corrupt-entry discard on fetch and on the
round-robin scrub, fractional per-tenant attribution — and the
router-level certs: a shared-tier hit is token-identical to recompute
(fp + int8, greedy + sampled, speculation on/off), a corrupt shared
entry is discarded and served by recompute token-identically, the
tier off is bit-identical run-to-run under a constant clock with
every shared counter reading zero, process replicas publish/probe/
fetch over the framed RPC wire (torn frames retried, nothing lost),
drain-and-migrate and the SDC cross-check compose with the tier, and
the placement hot path's one-chain-hash-walk-per-decision bound stays
pinned (``num_hash_walks``)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTConfig, GPTLMHeadModel
from apex_tpu.observability import Observability
from apex_tpu.serving import (
    EngineConfig,
    FleetConfig,
    FleetRouter,
    Request,
    SamplingParams,
    SharedPrefixStore,
)
from apex_tpu.serving.process_replica import gpt_model_spec
from apex_tpu.utils.faults import FaultPlan, FaultSpec


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


BLK = 4096   # comfortably above one tiny-model block payload

# the proven shared-tier physics (the equal-bytes scenario below): a
# pool small enough that finished prompts EVICT into the local spill
# tier (num_blocks=8 = one full 32-token sequence), a local tier big
# enough to hold a whole seeded 7-block run (8 blocks — a run larger
# than its landing tier evicts its own head before _admit sees it),
# and 28-token prompts so one prompt is 7 chain blocks
SMALL_KW = dict(max_batch=2, block_size=4, num_blocks=8,
                max_prefill_len=8, max_seq_len=32, seed=11,
                enable_prefix_caching=True, max_waiting=64,
                snapshot_interval_ticks=2, spill_max_bytes=8 * BLK)
SHARED_FLEET_KW = dict(affinity_weight=0.0,       # affinity-BLIND
                       shared_prefix_bytes=60 * BLK)


def _fleet(tiny_gpt, n=2, fleet_kw=None, clock=None, faults=None,
           obs=None, process=False, **overrides):
    cfg, model, params = tiny_gpt
    kw = dict(SMALL_KW)
    kw.update(overrides)
    fkw = dict(fleet_kw or {})
    extra = {}
    if process:
        fkw.setdefault("replica_mode", "process")
        fkw.setdefault("rpc_timeout_s", 60.0)
        extra["model_spec"] = gpt_model_spec(cfg)
    return FleetRouter(model, params, EngineConfig(**kw),
                       FleetConfig(num_replicas=n, **fkw),
                       clock=clock, faults=faults, obs=obs, **extra)


def _warm_trace(n=12, npref=3, sampled=False, new=4, seed=17,
                uid="w", tenant=None):
    """``n`` requests cycling over ``npref`` distinct 28-token
    prompts (7 chain blocks each). npref is ODD on purpose: paired
    placement on two replicas alternates, and an even prefix count
    would partition the prefixes perfectly by replica parity — every
    request a LOCAL hit, nothing for the shared tier to prove."""
    assert npref % 2 == 1
    rng = np.random.RandomState(seed)
    prefixes = [list(rng.randint(1, 50, 28)) for _ in range(npref)]
    out = []
    for k in range(n):
        samp = (SamplingParams(temperature=1.0, top_k=10)
                if sampled else SamplingParams())
        out.append(Request(f"{uid}{k}", list(prefixes[k % npref]),
                           max_new_tokens=new, sampling=samp,
                           **({"tenant": tenant(k)} if tenant else {})))
    return out


def _drive_pairs(fleet, reqs):
    """Submit in pairs and DRAIN between pairs — the load pattern the
    seed-at-placement tier is built for: evictions from finished pairs
    publish before the next placement probes."""
    for k in range(0, len(reqs), 2):
        for r in reqs[k:k + 2]:
            fleet.add_request(r)
        while fleet.has_work:
            fleet.step()
    return fleet.run(return_status=True)


def _resdict(res):
    return {u: (tuple(r.tokens), r.status) for u, r in res.items()}


def _payload(seed, nbytes=1024):
    rng = np.random.RandomState(seed)
    half = nbytes // 2
    return {"k": rng.randint(0, 127, half).astype(np.int8),
            "v": rng.randint(0, 127, half).astype(np.int8)}


# ---------------------------------------------------------------------------
# SharedPrefixStore units: dedupe, LRU budget, attribution, audit
# ---------------------------------------------------------------------------


def test_store_dedupe_is_refcounted_and_audited():
    store = SharedPrefixStore(1 << 20)
    assert store.publish("h0", _payload(0), tenant="a") is True
    bytes_one = store.total_bytes
    # the same hash from two more publishers: references, not bytes
    assert store.publish("h0", None, tenant="b") is True
    assert store.publish("h0", _payload(0), tenant="a") is True
    assert len(store) == 1
    assert store.total_bytes == bytes_one
    assert store.dedupe_hits == 2
    assert store._refs["h0"] == 3
    assert store._owners["h0"] == {"a": 2, "b": 1}
    store.check_integrity()
    # a payload-less publish of a NON-resident hash cannot store
    assert store.publish("h1", None, tenant="a") is False
    assert "h1" not in store
    st = store.stats()
    assert st["blocks"] == 1 and st["dedupe_hits"] == 2


def test_store_byte_budget_lru_keeps_side_tables_consistent():
    store = SharedPrefixStore(3 * 1024)
    for k in range(4):
        assert store.publish(f"h{k}", _payload(k)) is True
    # h0 fell off the LRU end; its refcount/ownership rows went with it
    assert "h0" not in store and store.evictions == 1
    assert len(store) == 3 and store.total_bytes == 3 * 1024
    assert set(store._refs) == set(store._owners) == {"h1", "h2", "h3"}
    store.check_integrity()
    # probe: contiguous resident run only, honoring start
    assert store.probe(["h1", "h2", "h3"]) == 3
    assert store.probe(["h0", "h1"]) == 0
    assert store.probe(["h1", "hX", "h2"]) == 1
    assert store.probe(["h0", "h1", "h2"], start=1) == 2
    # a dedupe publish refreshes recency: h1 survives the next insert
    assert store.publish("h1", None) is True
    assert store.publish("h4", _payload(4)) is True
    assert "h1" in store and "h2" not in store
    store.check_integrity()
    # an entry over the whole budget is refused, never resident
    assert SharedPrefixStore(100).publish("big", _payload(9)) is False
    small = SharedPrefixStore(100)
    small.publish("big", _payload(9))
    assert small.refused == 1 and len(small) == 0


def test_store_tenant_bytes_split_by_publisher_share():
    store = SharedPrefixStore(1 << 20)
    store.publish("h", _payload(3), tenant="a")
    store.publish("h", None, tenant="b")
    store.publish("h", None, tenant="a")
    tb = store.tenant_bytes()
    assert tb["a"] == pytest.approx(1024 * 2 / 3, abs=1e-3)
    assert tb["b"] == pytest.approx(1024 * 1 / 3, abs=1e-3)
    assert sum(tb.values()) == pytest.approx(store.total_bytes,
                                             abs=1e-3)


def test_store_check_integrity_catches_ledger_violations():
    store = SharedPrefixStore(1 << 20)
    store.publish("h", _payload(1))
    store._refs["h"] = 0
    with pytest.raises(ValueError, match="refcount"):
        store.check_integrity()
    store._refs["h"] = 1
    store._owners["stray"] = {"a": 1}
    with pytest.raises(ValueError, match="out of sync"):
        store.check_integrity()
    del store._owners["stray"]
    store._owners["h"] = {"a": 2}
    with pytest.raises(ValueError, match="sum to its refcount"):
        store.check_integrity()


# ---------------------------------------------------------------------------
# SharedPrefixStore units: corruption discard (fetch + scrub)
# ---------------------------------------------------------------------------


def test_store_fetch_discards_corrupt_entry_with_references():
    hits = []
    store = SharedPrefixStore(1 << 20,
                              on_corrupt=lambda s, h: hits.append((s, h)))
    store.publish("h", _payload(5), tenant="a")
    store.publish("h", None, tenant="b")
    # host-RAM rot: flip a stored byte AFTER the put-time checksum
    store._entries["h"]["payload"]["k"].view(np.uint8)[0] ^= 0xFF
    assert store.fetch("h") is None
    assert store.corrupt_discards == 1
    assert hits == [("spill_get", "h")]
    # discarded WITH its references — a reference is attribution,
    # not a pin — and the ledger still audits clean
    assert "h" not in store and "h" not in store._refs
    store.check_integrity()
    # a fresh publish of the same hash stores clean bytes again
    assert store.publish("h", _payload(5), tenant="a") is True
    assert store.fetch("h") is not None


def test_store_scrub_round_robin_finds_cold_rot():
    hits = []
    store = SharedPrefixStore(1 << 20,
                              on_corrupt=lambda s, h: hits.append((s, h)))
    for k in range(3):
        store.publish(f"h{k}", _payload(k))
    store._entries["h1"]["payload"]["v"].view(np.uint8)[0] ^= 0xFF
    # two budgeted passes cover all three entries round-robin
    v0, c0 = store.scrub(2)
    v1, c1 = store.scrub(2)
    assert v0 + v1 >= 3 and c0 + c1 == 1
    assert "h1" not in store and len(store) == 2
    assert ("scrub", "h1") in hits
    store.check_integrity()


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def test_shared_tier_config_validation(tiny_gpt):
    cfg, model, params = tiny_gpt
    with pytest.raises(ValueError, match="shared_prefix_bytes"):
        FleetConfig(shared_prefix_bytes=0)
    with pytest.raises(ValueError, match="shared_scrub_blocks"):
        FleetConfig(shared_scrub_blocks=-1)
    with pytest.raises(ValueError, match="max_bytes"):
        SharedPrefixStore(0)
    kw = dict(SMALL_KW, enable_prefix_caching=False)
    kw.pop("spill_max_bytes")
    with pytest.raises(ValueError, match="enable_prefix_caching"):
        FleetRouter(model, params, EngineConfig(**kw),
                    FleetConfig(num_replicas=1,
                                shared_prefix_bytes=1 << 20))


# ---------------------------------------------------------------------------
# the hit cert: shared-tier hit token-identical to recompute
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sampled,spec_tokens,quant", [
    (False, 0, None),
    (True, 0, None),
    (False, 3, None),
    (True, 3, None),
    (False, 0, "int8"),
])
def test_shared_hit_token_identical_to_recompute(tiny_gpt, sampled,
                                                 spec_tokens, quant):
    """The tier's whole contract: with the shared tier ON (and
    genuinely hitting — publishes, dedupe and seeded hits all
    nonzero), every request's tokens and status are IDENTICAL to the
    tier-off fleet that recomputes everything. fp + int8, greedy +
    sampled, speculation on/off."""
    overrides = dict(spec_tokens=spec_tokens)
    if quant is not None:
        overrides["kv_quantization"] = quant
    outs = {}
    for arm, fkw in (("off", dict(affinity_weight=0.0)),
                     ("on", dict(SHARED_FLEET_KW))):
        fleet = _fleet(tiny_gpt, n=2, fleet_kw=fkw, **overrides)
        res = _drive_pairs(fleet, _warm_trace(n=12, sampled=sampled))
        outs[arm] = _resdict(res)
        st = fleet.stats()
        assert st["num_lost_requests"] == 0
        if arm == "on":
            assert st["num_shared_publishes"] >= 1, st
            assert st["num_shared_dedupe"] >= 1, st
            assert st["shared_tier_hits"] >= 1, st
            assert st["num_shared_corrupt_discards"] == 0, st
            fleet._shared.check_integrity()
        else:
            for k in ("shared_tier_blocks", "shared_tier_bytes",
                      "shared_tier_hits", "num_shared_publishes",
                      "num_shared_dedupe", "num_shared_evictions",
                      "num_shared_refused",
                      "num_shared_corrupt_discards",
                      "num_shared_scrub_blocks_verified"):
                assert st[k] == 0, (k, st[k])
    assert outs["on"] == outs["off"]
    assert all(s == "finished" for _, s in outs["on"].values())


def test_tier_off_constant_clock_stats_bit_identical(tiny_gpt):
    """The tier-off regression bar: two identical tier-off fleets
    under a constant clock produce the same outputs AND the same FULL
    stats() — the shared-tier code paths are provably dormant."""
    runs = []
    for _ in range(2):
        fleet = _fleet(tiny_gpt, n=2, clock=lambda: 0.0)
        res = _drive_pairs(fleet, _warm_trace(n=8, sampled=True))
        runs.append((_resdict(res),
                     json.loads(json.dumps(fleet.stats(),
                                           sort_keys=True,
                                           default=str))))
    assert runs[0] == runs[1]


# ---------------------------------------------------------------------------
# the capacity scenario: one shared tier against per-replica tiers at
# equal total spill bytes
# ---------------------------------------------------------------------------


def test_shared_tier_beats_per_replica_tiers_at_equal_bytes_scenario(
        tiny_gpt):
    """What the fleet-global tier is for. Seven rotating 28-token
    prefixes (odd, and placement is affinity-blind, so BOTH replicas
    see EVERY prefix) finish as 8-block sequences: a deduped working
    set of 56 blocks. The shared arm gives each replica an 8-block
    local tier and the fleet one 60-block shared tier that holds the
    set once; the per-replica arm splits the same 76 blocks into two
    38-block local tiers, each too small for the 56 it needs privately,
    so its LRU cycles. On one greedy trace the shared arm's fleet-wide
    hit rate (prefix hits + spill re-admissions over looked-up blocks)
    and its steady-state TTFT p99 in ticks (second half of the trace:
    the cold misses are the same in both arms) beat the per-replica
    arm's; publishes, dedupe and shared hits all moved; the tokens are
    the same in both arms; and a replica killed mid-trace with the
    tier on loses nothing."""
    from _traffic import TickClock
    from apex_tpu.observability import percentile

    n_reqs = 28
    reqs = _warm_trace(n=n_reqs, npref=7, seed=1713, uid="q")

    def serve(spill_blocks, reqs=reqs, kill_before_pair=None, **fleet_kw):
        # the router weighs a replica's backlog by its measured service
        # time: under the tick clock every dispatch measures 0 and the
        # placement is the trace's alone, not the machine's
        clock = TickClock()
        fleet = _fleet(tiny_gpt, n=2, spill_max_bytes=spill_blocks * BLK,
                       clock=clock,
                       fleet_kw=dict(affinity_weight=0.0, **fleet_kw))
        submit, first, tick = {}, {}, 0
        for k in range(0, len(reqs), 2):    # pairs, drained (_drive_pairs)
            if k // 2 == kill_before_pair:
                fleet.kill_replica(0)
            for r in reqs[k:k + 2]:
                fleet.add_request(Request(
                    r.uid, list(r.prompt), sampling=r.sampling,
                    max_new_tokens=r.max_new_tokens))
                submit[r.uid] = tick
            while fleet.has_work:
                clock.now = float(tick)
                fleet.step()
                for uid, tok, _last in fleet.pop_stream_events():
                    if tok >= 0:
                        first.setdefault(uid, tick)
                tick += 1
        res = fleet.run(return_status=True)
        assert sorted(res) == sorted(r.uid for r in reqs)
        assert fleet.stats()["num_lost_requests"] == 0
        engines = [rep.engine.stats() for _, rep in fleet._alive()]
        hit_rate = (sum(s["prefix_hit_blocks"] + s["spill_hits"]
                        for s in engines)
                    / sum(s["prefix_lookup_blocks"] for s in engines))
        steady = [first[r.uid] - submit[r.uid]
                  for r in reqs[len(reqs) // 2:]]
        return fleet, _resdict(res), hit_rate, percentile(steady, 99)

    _, per_tokens, per_rate, per_p99 = serve(38)
    fleet, sh_tokens, sh_rate, sh_p99 = serve(
        8, shared_prefix_bytes=60 * BLK)
    assert sh_rate > per_rate, (sh_rate, per_rate)
    assert sh_p99 < per_p99, (sh_p99, per_p99)
    st = fleet.stats()
    assert st["num_shared_publishes"] >= 1
    assert st["num_shared_dedupe"] >= 1, (
        "both replicas' evictions of one prefix should collide in the "
        "shared tier")
    assert st["shared_tier_hits"] >= 1
    assert sh_tokens == per_tokens
    assert all(status == "finished" for _, status in sh_tokens.values())

    # the kill needs the tier warm, not the whole trace: two visits of
    # every prefix, replica 0 killed before the fifth pair
    chaos, _, _, _ = serve(8, reqs=reqs[:14], kill_before_pair=4,
                           respawn=True, shared_prefix_bytes=60 * BLK)
    assert chaos.stats()["num_failovers"] >= 1, "the kill never fired"
    for _, rep in chaos._alive():
        rep.engine.check_allocator_integrity()


# ---------------------------------------------------------------------------
# corrupt shared entries: discarded, recomputed, token-identical
# ---------------------------------------------------------------------------


def test_corrupt_shared_entry_discarded_and_recomputed(tiny_gpt):
    """Rot every resident shared entry mid-trace: later requests must
    fetch nothing poisoned — corrupt entries are discarded (counted,
    surfaced as shared_* corruption_detected events) and the requests
    finish token-identical to the tier-off recompute arm."""
    trace = lambda: _warm_trace(n=16, npref=3)
    base = _fleet(tiny_gpt, n=2, fleet_kw=dict(affinity_weight=0.0))
    expect = _resdict(_drive_pairs(base, trace()))

    obs = Observability(trace=False, metrics=False)
    fleet = _fleet(tiny_gpt, n=2, fleet_kw=dict(SHARED_FLEET_KW),
                   obs=obs)
    reqs = trace()
    got = dict(_resdict(_drive_pairs(fleet, reqs[:8])))
    store = fleet._shared
    assert len(store) > 0
    for h in list(store.hashes()):
        store._entries[h]["payload"]["k"].view(np.uint8)[0] ^= 0xFF
    got.update(_resdict(_drive_pairs(fleet, reqs[8:])))

    assert got == expect
    st = fleet.stats()
    assert st["num_shared_corrupt_discards"] >= 1, st
    assert st["num_lost_requests"] == 0
    sites = {e.get("site") for e in obs.recorder.tail()
             if e["kind"] == "corruption_detected"}
    assert any(str(s).startswith("shared_") for s in sites), sites
    store.check_integrity()


def test_shared_scrubber_coverage_counts(tiny_gpt):
    """The router-walked scrub: with ``shared_scrub_blocks`` > 0 the
    verified-entry counter grows tick over tick; with 0 the scrub is
    disabled and the counter stays flat."""
    for n, expect_scrub in ((8, True), (0, False)):
        fleet = _fleet(tiny_gpt, n=2, fleet_kw=dict(
            SHARED_FLEET_KW, shared_scrub_blocks=n))
        _drive_pairs(fleet, _warm_trace(n=8))
        st = fleet.stats()
        assert st["num_shared_publishes"] >= 1, st
        assert (st["num_shared_scrub_blocks_verified"] > 0) \
            is expect_scrub, st


# ---------------------------------------------------------------------------
# recorder + tenant attribution surfaces
# ---------------------------------------------------------------------------


def test_shared_events_recorded_and_tenant_rows_sum(tiny_gpt):
    obs = Observability(trace=False, metrics=False)
    fleet = _fleet(tiny_gpt, n=2, fleet_kw=dict(SHARED_FLEET_KW),
                   obs=obs)
    tenant = lambda k: "acme" if k % 2 == 0 else "bravo"
    res = _drive_pairs(fleet, _warm_trace(n=12, tenant=tenant))
    assert all(r.status == "finished" for r in res.values())
    kinds = {e["kind"] for e in obs.recorder.tail()}
    assert {"shared_publish", "shared_hit"} <= kinds, kinds
    st = fleet.stats()
    rows = st["tenants"]
    # the fractional ledger, shared-tier leg: per-tenant charges sum
    # to the __shared__ row, which is the tier's resident total
    assert rows["__shared__"]["shared_tier_bytes"] == pytest.approx(
        st["shared_tier_bytes"], abs=1e-3)
    charged = sum(r["shared_tier_bytes"] for t, r in rows.items()
                  if t != "__shared__")
    assert charged == pytest.approx(
        rows["__shared__"]["shared_tier_bytes"], abs=1e-3)
    assert any(rows.get(t, {}).get("shared_tier_bytes", 0) > 0
               for t in ("acme", "bravo")), rows


# ---------------------------------------------------------------------------
# process mode: publish/probe/fetch over the framed RPC wire
# ---------------------------------------------------------------------------


def test_process_mode_shared_tier_over_the_wire(tiny_gpt):
    """The shared tier rides the existing framed-RPC spill surface:
    a 2-process-replica fleet publishes, dedupes and seeds hits over
    the wire, token-identical to the in-process shared fleet — with a
    TORN response frame injected mid-trace (retried by the parent,
    zero lost, at-most-once preserved)."""
    inproc = _fleet(tiny_gpt, n=2, fleet_kw=dict(SHARED_FLEET_KW))
    expect = _resdict(_drive_pairs(inproc, _warm_trace(n=8)))
    ist = inproc.stats()
    assert ist["shared_tier_hits"] >= 1, ist

    faults = [FaultPlan([FaultSpec(site="wire", kind="transient",
                                   at=(7,))], seed=3), None]
    fleet = _fleet(tiny_gpt, n=2, process=True,
                   fleet_kw=dict(SHARED_FLEET_KW, rpc_retries=2),
                   faults=faults)
    try:
        got = _resdict(_drive_pairs(fleet, _warm_trace(n=8)))
        st = fleet.stats()
    finally:
        fleet.close()
    assert got == expect
    assert st["num_shared_publishes"] >= 1, st
    assert st["num_shared_dedupe"] >= 1, st
    assert st["shared_tier_hits"] >= 1, st
    assert st["num_rpc_retries"] >= 1, st
    assert st["num_lost_requests"] == 0


# ---------------------------------------------------------------------------
# composition: drain-and-migrate + SDC replay with the tier on
# ---------------------------------------------------------------------------


def test_drain_retire_and_sdc_compose_with_shared_tier(tiny_gpt):
    """The tier must not confuse the other fleet machinery: with SDC
    replay on, seeded shared hits replay clean (checks run, zero
    suspects — a hit really is recompute-identical); draining and
    retiring a replica mid-trace loses nothing, clears its published
    ledger, and the survivor keeps serving shared hits."""
    fleet = _fleet(tiny_gpt, n=2, fleet_kw=dict(
        SHARED_FLEET_KW, sdc_check_interval_ticks=2))
    reqs = _warm_trace(n=12)
    res = dict(_drive_pairs(fleet, reqs[:6]))
    # mid-trace clean shutdown of replica 0, work in flight
    for r in reqs[6:8]:
        fleet.add_request(r)
    fleet.step()
    fleet.drain_replica(0, dst=1, retire=True)
    assert fleet._published[0] == set()
    res.update(fleet.run(return_status=True))
    # chill the survivor's LOCAL tiers: flush its device blocks, let
    # the next tick publish them into the shared tier, then drop its
    # local spill copies — the shared tier is now the only warm copy,
    # so the final wave can only land warm through shared-tier seeding
    # (structural, not churn-dependent: hits below are guaranteed)
    survivor = fleet.replicas[1].engine
    survivor.allocator.flush_evictable()
    fleet.step()
    for h in list(survivor.spill.hashes()):
        survivor.spill._drop(h)
    for r in reqs[8:]:
        fleet.add_request(r)
    res.update(fleet.run(return_status=True))
    assert sorted(res) == sorted(r.uid for r in reqs)
    assert all(r.status == "finished" for r in res.values())
    st = fleet.stats()
    assert st["num_lost_requests"] == 0
    assert st["num_retired"] == 1 or st["replicas_alive"] == 1, st
    assert st["shared_tier_hits"] >= 1, st
    assert st["num_sdc_checks"] > 0, st
    assert st["num_sdc_suspects"] == 0, st
    fleet._shared.check_integrity()
    for _, rep in fleet._alive():
        rep.engine.check_allocator_integrity()


# ---------------------------------------------------------------------------
# the placement hot path: ONE chain-hash walk per decision
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tier_on", [False, True])
def test_one_hash_walk_per_placement_decision(tiny_gpt, tier_on):
    """Regression pin for the hoist: ``add_request`` walks the
    prompt's chain hashes exactly once and hands them to ``_ranked``
    AND the shared-tier seeding — never a second walk, tier on or
    off, and a plain run adds none after placement."""
    fkw = dict(SHARED_FLEET_KW) if tier_on \
        else dict(affinity_weight=0.0)
    fleet = _fleet(tiny_gpt, n=2, fleet_kw=fkw)
    reqs = _warm_trace(n=6)
    for k, r in enumerate(reqs):
        fleet.add_request(r)
        assert fleet.stats()["num_hash_walks"] == k + 1
    fleet.run()
    assert fleet.stats()["num_hash_walks"] == len(reqs)
