"""End-to-end data-integrity certification (tier-1, CPU): the ISSUE 14
layer (docs/robustness.md, "Data integrity").

The detection bar: under seeded ``"corrupt"`` fault plans covering
every checksum point — spill writes/reads, checkpoints, migration
records in and out, transported KV payloads — zero corrupted artifacts
are consumed undetected: corrupt spill entries are discarded and the
request is served by recompute TOKEN-IDENTICALLY, corrupt checkpoints
fail over via fresh re-injection with zero lost accepted requests,
corrupt migration imports are refused with the source keeping the
request. The perturbation bar: integrity machinery fully disabled
(``verify_artifacts=False``, no scrub, no cross-check) is bit-identical
to the pre-integrity engine and fleet — outputs, statuses, and the
full stats dict — and enabling checksums alone changes no served
token. Plus: the ``"corrupt"`` fault kind and its seeded perturbation
helpers, the checksum/seal primitives (JSON-wire stable), budgeted
background scrubbing, the fleet SDC determinism cross-check (a
compute-corrupted replica is detected and retired), and the
recorder / trace_summary surface."""

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTConfig, GPTLMHeadModel
from apex_tpu.observability import RECORDER_EVENT_KINDS, Observability
from apex_tpu.serving import (
    EngineConfig,
    FleetConfig,
    FleetRouter,
    HostSpillStore,
    InferenceEngine,
    Request,
    SamplingParams,
)
from apex_tpu.utils.faults import (
    FaultPlan,
    FaultSpec,
    corruption_seed,
    perturb_json,
    perturb_payload,
    perturb_tokens,
)
from apex_tpu.utils.integrity import (
    IntegrityError,
    is_sealed,
    payload_checksum,
    record_checksum,
    seal_record,
    verify_payload,
    verify_record,
)


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
# a pool tight enough that the six distinct prompts below churn it:
# evictions spill, re-serves hit the spill tier
SPILL_KW = dict(ENGINE_KW, num_blocks=10, spill_max_bytes=1 << 20)

_PROMPT_RNG = np.random.RandomState(5)
PROMPTS = [list(_PROMPT_RNG.randint(1, 40, 8)) for _ in range(6)]


def _engine(tiny_gpt, faults=None, **overrides):
    model, params = tiny_gpt
    kw = dict(ENGINE_KW)
    kw.update(overrides)
    return InferenceEngine(model, params, EngineConfig(**kw),
                           faults=faults, clock=lambda: 0.0)


def _serve_waves(eng, waves=3, new=3):
    """Serve every PROMPT ``waves`` times through a churning pool —
    the spill-tier round trip — returning {uid: tokens}."""
    outs = {}
    for wave in range(waves):
        for k, p in enumerate(PROMPTS):
            eng.add_request(Request(f"w{wave}r{k}", list(p),
                                    max_new_tokens=new))
            outs.update(eng.run())
    return outs


def _fleet(tiny_gpt, n=2, faults=None, fleet_kw=None, **overrides):
    model, params = tiny_gpt
    kw = dict(ENGINE_KW)
    kw.update(overrides)
    return FleetRouter(model, params, EngineConfig(**kw),
                       FleetConfig(num_replicas=n, **(fleet_kw or {})),
                       faults=faults, clock=lambda: 0.0)


# ---------------------------------------------------------------------------
# the checksum/seal primitives
# ---------------------------------------------------------------------------


def test_payload_checksum_content_keyed():
    a = {"k": np.arange(8, dtype=np.float32),
         "v": np.ones(4, np.int8)}
    b = {"v": np.ones(4, np.int8),
         "k": np.arange(8, dtype=np.float32)}
    assert payload_checksum(a) == payload_checksum(b)  # key-order free
    c = {"k": np.arange(8, dtype=np.float32),
         "v": np.zeros(4, np.int8)}
    assert payload_checksum(a) != payload_checksum(c)
    # non-array metadata (the detached transport checksum) is skipped
    d = dict(a, checksum="abc")
    assert payload_checksum(d) == payload_checksum(a)


def test_payload_checksum_covers_dtype_and_shape():
    a = {"k": np.zeros(8, np.float32)}
    assert payload_checksum(a) != payload_checksum(
        {"k": np.zeros(8, np.int32)})
    assert payload_checksum(a) != payload_checksum(
        {"k": np.zeros((2, 4), np.float32)})


def test_record_checksum_stable_across_json_wire():
    # int dict keys are the trap: the wire stringifies them, which
    # reorders sort_keys — the checksum must normalize first
    rec = {"uid": "a", "classes": {10: [1, 2], 9: [3]},
           "pi": 0.1 + 0.2, "t": (1, 2)}
    wired = json.loads(json.dumps(rec))
    assert record_checksum(rec) == record_checksum(wired)


def test_seal_and_verify_record():
    rec = seal_record({"uid": "x", "prompt": [1, 2, 3]})
    assert is_sealed(rec)
    assert verify_record(rec, "test") is True
    assert verify_record({"uid": "x"}, "test") is False  # legacy
    rec["prompt"][0] = 99
    with pytest.raises(IntegrityError, match="test"):
        verify_record(rec, "test")


def test_verify_payload_detached():
    p = {"k": np.arange(4, dtype=np.float32)}
    cs = payload_checksum(p)
    assert verify_payload(p, cs, "t") is True
    assert verify_payload(p, None, "t") is False   # unchecksummed
    p["k"][0] = 7.0
    with pytest.raises(IntegrityError):
        verify_payload(p, cs, "t")


# ---------------------------------------------------------------------------
# the "corrupt" fault kind + perturbation helpers
# ---------------------------------------------------------------------------


def test_corrupt_fault_kind_and_seed():
    plan = FaultPlan([FaultSpec(site="spill_put", kind="corrupt",
                                at=[1])], seed=3)
    assert plan.fire("spill_put") is False
    assert plan.corrupt_seed("spill_put") is None   # index 0: no hit
    # a corrupt hit is its own silent channel — NOT a nan hit (an
    # unvalidated consumer like the train loop's watchdog must not
    # NaN-fill on it)
    assert plan.fire("spill_put") is False
    seed = plan.corrupt_seed("spill_put")
    assert seed == corruption_seed(3, "spill_put", 1)
    # the window is one call wide
    plan.fire("spill_put")
    assert plan.corrupt_seed("spill_put") is None
    # replayable: an identical plan derives the identical seed
    plan2 = FaultPlan([FaultSpec(site="spill_put", kind="corrupt",
                                 at=[1])], seed=3)
    plan2.fire("spill_put")
    plan2.fire("spill_put")
    assert plan2.corrupt_seed("spill_put") == seed
    assert ("spill_put", "corrupt", 1) in plan.fired


def test_perturb_payload_changes_one_array_deterministically():
    p = {"k": np.arange(16, dtype=np.float32),
         "v": np.arange(16, dtype=np.float32)}
    a = perturb_payload(p, 42)
    b = perturb_payload(p, 42)
    assert payload_checksum(a) == payload_checksum(b)   # deterministic
    assert payload_checksum(a) != payload_checksum(p)   # changed
    changed = [k for k in ("k", "v")
               if not np.array_equal(a[k], p[k])]
    assert len(changed) == 1
    # the original is untouched
    assert np.array_equal(p["k"], np.arange(16, dtype=np.float32))


def test_perturb_json_numeric_leaf_only():
    rec = {"uid": "keepme", "prompt": [1, 2, 3], "nested": {"x": 5}}
    a = perturb_json(rec, 7)
    assert a == perturb_json(rec, 7)            # deterministic
    assert a != rec                             # changed
    assert a["uid"] == "keepme"                 # strings intact
    assert rec["prompt"] == [1, 2, 3]           # original intact


def test_perturb_tokens_in_vocab_and_counted():
    toks = np.array([[3, 5, -1], [-1, -1, -1]], np.int32)
    counts = np.array([2, 0])
    out = perturb_tokens(toks, counts, vocab_size=50, seed=9)
    assert np.array_equal(out, perturb_tokens(toks, counts, 50, 9))
    diff = (out != toks)
    assert diff.sum() == 1
    lane, pos = np.argwhere(diff)[0]
    assert lane == 0 and pos < 2                # only valid positions
    assert 0 <= out[lane, pos] < 50
    # nothing to corrupt -> unchanged
    empty = np.full((2, 3), -1, np.int32)
    assert np.array_equal(
        perturb_tokens(empty, np.zeros(2, int), 50, 9), empty)


def test_engine_rejects_bad_fault_site_kind_combos(tiny_gpt):
    with pytest.raises(ValueError, match="integrity sites"):
        _engine(tiny_gpt, faults=FaultPlan(
            [FaultSpec(site="spill_put", kind="transient", every=1)]))
    with pytest.raises(ValueError, match="'decode' only"):
        _engine(tiny_gpt, faults=FaultPlan(
            [FaultSpec(site="prefill", kind="corrupt", every=1)]))
    # corrupt at decode is the supported SDC model
    _engine(tiny_gpt, faults=FaultPlan(
        [FaultSpec(site="decode", kind="corrupt", every=100)]))


def test_integrity_config_validation():
    with pytest.raises(ValueError, match="scrub_interval_ticks"):
        EngineConfig(scrub_interval_ticks=0)
    with pytest.raises(ValueError, match="scrub_spill_blocks"):
        EngineConfig(scrub_spill_blocks=0)
    with pytest.raises(ValueError, match="sdc_check_interval_ticks"):
        FleetConfig(sdc_check_interval_ticks=0)


# ---------------------------------------------------------------------------
# the spill store's checksum discipline
# ---------------------------------------------------------------------------


def _payload(seed=0, n=32):
    rng = np.random.RandomState(seed)
    return {"k": rng.randn(n).astype(np.float32),
            "v": rng.randn(n).astype(np.float32)}


def test_store_clean_roundtrip_and_refused_counter():
    store = HostSpillStore(max_bytes=300)
    assert store.put("h1", _payload(1))
    got = store.pop("h1")
    assert np.array_equal(got["k"], _payload(1)["k"])
    # oversize: refused AND surfaced uniformly in stats
    assert not store.put("big", _payload(2, n=200))
    st = store.stats()
    assert st["refused"] == 1 and st["corrupt_discards"] == 0
    assert st["evictions"] == 1     # back-compat: refusals still count


def test_store_detects_put_side_rot():
    fired = []
    hook_on = {"on": True}

    def rot(site, payload):
        if site == "spill_put" and hook_on["on"]:
            return perturb_payload(payload, 5)
        return payload

    store = HostSpillStore(1 << 20, corrupt_hook=rot,
                           on_corrupt=lambda s, d: fired.append(s))
    store.put("h1", _payload(1))
    assert store.pop("h1") is None          # detected -> miss
    assert store.corrupt_discards == 1
    assert fired == ["spill_get"]           # detection is read-side
    assert "h1" not in store
    # clean entries still serve
    hook_on["on"] = False
    store.put("h2", _payload(2))
    assert store.pop("h2") is not None


def test_store_detects_read_side_rot_on_export():
    def rot(site, payload):
        return (perturb_payload(payload, 6)
                if site == "spill_get" else payload)

    store = HostSpillStore(1 << 20, corrupt_hook=rot)
    store.put("h1", _payload(1))
    assert store.export_entry("h1") is None
    assert store.corrupt_discards == 1
    assert "h1" not in store                # rot -> resident dropped


def test_store_verify_off_trusts_bytes():
    def rot(site, payload):
        return (perturb_payload(payload, 7)
                if site == "spill_put" else payload)

    store = HostSpillStore(1 << 20, verify=False, corrupt_hook=rot)
    store.put("h1", _payload(1))
    assert store.pop("h1") is not None      # the pre-integrity path
    assert store.corrupt_discards == 0


def test_store_scrub_finds_resident_rot():
    def rot(site, payload):
        return (perturb_payload(payload, 8)
                if site == "spill_put" else payload)

    store = HostSpillStore(1 << 20, corrupt_hook=rot,
                           on_corrupt=lambda s, d: sites.append(s))
    sites = []
    store.put("h1", _payload(1))
    verified, corrupt = store.scrub(4)
    assert (verified, corrupt) == (1, 1)
    assert sites == ["scrub"]
    assert len(store) == 0
    assert store.scrub(4) == (0, 0)         # empty store: nothing


def test_store_scrub_walks_round_robin():
    store = HostSpillStore(1 << 20)
    for i in range(5):
        store.put(f"h{i}", _payload(i))
    assert store.scrub(2) == (2, 0)
    assert store.scrub(2) == (2, 0)
    assert store._scrub_cursor == 4         # advanced, not reset


# ---------------------------------------------------------------------------
# engine end-to-end: corrupt artifacts are served by recompute,
# token-identically; integrity off/on is bit-identical on clean runs
# ---------------------------------------------------------------------------


def test_verify_on_off_bit_identical_clean(tiny_gpt):
    a = _engine(tiny_gpt, verify_artifacts=True, **{})
    b = _engine(tiny_gpt, verify_artifacts=False, **{})
    for eng in (a, b):
        for k, p in enumerate(PROMPTS):
            eng.add_request(Request(
                f"r{k}", list(p), max_new_tokens=4,
                sampling=(SamplingParams(temperature=1.0, top_k=10)
                          if k % 2 else SamplingParams())))
    ra = a.run(return_status=True)
    rb = b.run(return_status=True)
    assert {u: (r.tokens, r.status) for u, r in ra.items()} \
        == {u: (r.tokens, r.status) for u, r in rb.items()}
    assert a.stats() == b.stats()


@pytest.mark.parametrize("site", ["spill_put", "spill_get"])
def test_spill_corruption_served_by_recompute_identically(
        tiny_gpt, site):
    model, params = tiny_gpt
    clean_eng = InferenceEngine(model, params, EngineConfig(**SPILL_KW),
                                clock=lambda: 0.0)
    clean = _serve_waves(clean_eng)
    cs = clean_eng.stats()
    assert cs["num_blocks_spilled"] > 0 and cs["spill_hits"] > 0
    plan = FaultPlan([FaultSpec(site=site, kind="corrupt", every=2)],
                     seed=9)
    eng = InferenceEngine(model, params, EngineConfig(**SPILL_KW),
                          faults=plan, clock=lambda: 0.0)
    assert _serve_waves(eng) == clean       # recompute serves, exactly
    st = eng.stats()
    assert st["num_spill_corrupt_discards"] > 0
    assert st["num_corruptions_detected"] \
        == st["num_spill_corrupt_discards"]


def test_scrub_cadence_and_detection(tiny_gpt):
    model, params = tiny_gpt
    plan = FaultPlan([FaultSpec(site="spill_put", kind="corrupt",
                                every=1)], seed=11)
    eng = InferenceEngine(
        model, params,
        EngineConfig(**SPILL_KW, scrub_interval_ticks=1,
                     scrub_spill_blocks=8),
        faults=plan, clock=lambda: 0.0)
    _serve_waves(eng, waves=1)
    st = eng.stats()
    assert st["num_scrubs"] > 0
    assert st["num_scrub_blocks_verified"] > 0
    # EVERY spill was rotten; the scrub (or a read) caught each one
    assert st["num_spill_corrupt_discards"] > 0
    assert st["spill_hits"] == 0


def test_scrub_on_token_identical(tiny_gpt):
    model, params = tiny_gpt
    a = InferenceEngine(model, params, EngineConfig(**SPILL_KW),
                        clock=lambda: 0.0)
    b = InferenceEngine(model, params,
                        EngineConfig(**SPILL_KW, scrub_interval_ticks=2),
                        clock=lambda: 0.0)
    assert _serve_waves(a) == _serve_waves(b)


# ---------------------------------------------------------------------------
# snapshot / checkpoint sealing
# ---------------------------------------------------------------------------


def test_snapshot_sealed_and_wire_restorable(tiny_gpt):
    eng = _engine(tiny_gpt)
    eng.add_request(Request("s0", PROMPTS[0], max_new_tokens=4))
    snap = json.loads(json.dumps(eng.snapshot()))
    assert is_sealed(snap)
    fresh = _engine(tiny_gpt)
    fresh.restore(snap)
    assert fresh.run() == eng.run()


def test_corrupt_snapshot_refuses_restore(tiny_gpt):
    eng = _engine(tiny_gpt)
    eng.add_request(Request("s0", PROMPTS[0], max_new_tokens=4))
    snap = eng.snapshot()
    bad = perturb_json(snap, 13)
    fresh = _engine(tiny_gpt)
    with pytest.raises(IntegrityError, match="restore"):
        fresh.restore(bad)
    assert fresh.stats()["num_corruptions_detected"] == 1
    eng.run()


def test_corrupt_version_field_still_counts_as_corruption(tiny_gpt):
    """Integrity verifies before ANY field is believed — a corruption
    landing on the version leaf must raise IntegrityError (and count),
    not masquerade as an 'unknown snapshot version' ValueError that
    dodges the detection counter."""
    eng = _engine(tiny_gpt)
    eng.add_request(Request("s0", PROMPTS[0], max_new_tokens=2))
    snap = eng.snapshot()
    snap = json.loads(json.dumps(snap))
    snap["version"] = 44
    fresh = _engine(tiny_gpt)
    with pytest.raises(IntegrityError):
        fresh.restore(snap)
    assert fresh.stats()["num_corruptions_detected"] == 1
    eng.run()


def test_legacy_unsealed_snapshot_restores(tiny_gpt):
    eng = _engine(tiny_gpt)
    eng.add_request(Request("s0", PROMPTS[0], max_new_tokens=4))
    snap = eng.snapshot()
    del snap["checksum"]                    # the pre-integrity format
    fresh = _engine(tiny_gpt)
    fresh.restore(snap)
    assert fresh.run() == eng.run()


def test_verify_off_restores_corrupt_snapshot(tiny_gpt):
    # the escape hatch is explicit: verification off trusts the bytes
    eng = _engine(tiny_gpt)
    eng.add_request(Request("s0", PROMPTS[0], max_new_tokens=2))
    snap = eng.snapshot()
    snap["arrival_count"] = snap["arrival_count"] + 0  # keep loadable
    snap["counters"] = dict(snap["counters"], num_ticks=999)  # "rot"
    fresh = _engine(tiny_gpt, verify_artifacts=False)
    fresh.restore(snap)
    eng.run()
    fresh.run()


# ---------------------------------------------------------------------------
# migration records: sealed out, verified in, refused on rot
# ---------------------------------------------------------------------------


def test_clean_export_records_are_sealed_and_import(tiny_gpt):
    src = _engine(tiny_gpt)
    dst = _engine(tiny_gpt)
    src.add_request(Request("m0", PROMPTS[0], max_new_tokens=4))
    recs = src.export_requests()
    assert all(is_sealed(r) for r in recs)
    dst.import_requests(recs)
    assert dst.run()["m0"]


def test_corrupt_export_refused_at_import(tiny_gpt):
    plan = FaultPlan([FaultSpec(site="export", kind="corrupt",
                                at=[0])], seed=3)
    src = _engine(tiny_gpt, faults=plan)
    dst = _engine(tiny_gpt)
    src.add_request(Request("m0", PROMPTS[0], max_new_tokens=4))
    recs = src.export_requests()
    with pytest.raises(IntegrityError, match="import"):
        dst.import_requests(recs)
    st = dst.stats()
    assert st["num_import_refusals"] == 1
    assert st["num_corruptions_detected"] == 1
    assert not dst.has_work                 # refused BEFORE any mutation


def test_import_site_corruption_refused(tiny_gpt):
    # rot on the TARGET side of the wire: the import fire
    src = _engine(tiny_gpt)
    plan = FaultPlan([FaultSpec(site="import", kind="corrupt",
                                at=[0])], seed=4)
    dst = _engine(tiny_gpt, faults=plan)
    src.add_request(Request("m0", PROMPTS[0], max_new_tokens=4))
    with pytest.raises(IntegrityError):
        dst.import_requests(src.export_requests())
    assert not dst.has_work


def test_fleet_migrate_refusal_source_keeps_request(tiny_gpt):
    plans = [FaultPlan([FaultSpec(site="export", kind="corrupt",
                                  every=1)], seed=4), None]
    fl = _fleet(tiny_gpt, n=2, faults=plans)
    fl.add_request(Request("g0", PROMPTS[0], max_new_tokens=4))
    owner = fl.owners()["g0"]
    fl.step()
    moved = fl.migrate(["g0"], owner, dst=1 - owner)
    st = fl.stats()
    assert moved == 0
    assert st["num_refused_imports"] == 1
    assert fl.owners()["g0"] == owner       # the source kept it
    res = fl.run(return_status=True)
    assert res["g0"].status == "finished"
    assert fl.stats()["num_lost_requests"] == 0


def test_corrupt_payload_transport_skipped(tiny_gpt):
    model, params = tiny_gpt
    src = InferenceEngine(model, params, EngineConfig(**SPILL_KW),
                          clock=lambda: 0.0)
    dst = InferenceEngine(model, params, EngineConfig(**SPILL_KW),
                          clock=lambda: 0.0)
    src.add_request(Request("p0", PROMPTS[0], max_new_tokens=3))
    src.run()
    hashes = src._seq_hashes(PROMPTS[0])
    payloads = src.export_prefix_payloads(hashes)
    assert payloads and all("checksum" in p for p in payloads.values())
    # clean transport imports
    assert dst.import_prefix_payloads(payloads) == len(payloads)
    # rotted transport: each corrupt entry skipped + counted
    dst2 = InferenceEngine(model, params, EngineConfig(**SPILL_KW),
                           clock=lambda: 0.0)
    rotted = {h: perturb_payload(p, 21) for h, p in payloads.items()}
    assert dst2.import_prefix_payloads(rotted) == 0
    assert dst2.stats()["num_corruptions_detected"] == len(payloads)


# ---------------------------------------------------------------------------
# fleet: corrupt checkpoints fail over via fresh re-injection
# ---------------------------------------------------------------------------


def test_corrupt_checkpoint_falls_back_to_fresh_reinject(tiny_gpt):
    plans = [FaultPlan([FaultSpec(site="checkpoint", kind="corrupt",
                                  every=1)], seed=5), None]
    fl = _fleet(tiny_gpt, n=2, faults=plans,
                snapshot_interval_ticks=1)
    for k in range(4):
        fl.add_request(Request(f"c{k}", [1 + k] + PROMPTS[0][1:],
                               max_new_tokens=4))
    for _ in range(3):
        fl.step()
    fl.kill_replica(0)
    res = fl.run(return_status=True)
    st = fl.stats()
    assert st["num_corrupt_checkpoints"] >= 1
    assert st["num_lost_requests"] == 0
    assert set(res) == {f"c{k}" for k in range(4)}
    assert all(r.status == "finished" for r in res.values())


def test_failover_placement_refusal_retries_clean_copy(tiny_gpt):
    """A refused FAILOVER placement (in-transit rot at the survivor's
    import site) retries once from the router's clean Request copy
    before giving up: one corruption event must not convert a
    recoverable request into a client-visible failure."""
    plans = [None, FaultPlan([FaultSpec(site="import", kind="corrupt",
                                        at=[0])], seed=8)]
    fl = _fleet(tiny_gpt, n=2, faults=plans)
    fl.add_request(Request("p0", PROMPTS[0], max_new_tokens=4))
    if fl.owners()["p0"] != 0:  # pin the request onto replica 0
        fl.migrate(["p0"], 1, dst=0)
    fl.step()
    fl.kill_replica(0)          # no checkpoint -> fresh re-inject
    res = fl.run(return_status=True)
    st = fl.stats()
    assert st["num_refused_imports"] == 1       # the first hop refused
    assert res["p0"].status == "finished"       # the retry served it
    assert st["num_lost_requests"] == 0


# ---------------------------------------------------------------------------
# the SDC determinism cross-check
# ---------------------------------------------------------------------------


def _sdc_fleet(tiny_gpt, faults=None, n=2, interval=2):
    return _fleet(tiny_gpt, n=n, faults=faults,
                  fleet_kw=dict(sdc_check_interval_ticks=interval))


def _mixed_requests(k=6, new=4):
    return [Request(f"q{i}", [1 + i] + PROMPTS[0][1:],
                    max_new_tokens=new,
                    sampling=(SamplingParams(temperature=1.0, top_k=10)
                              if i % 2 else SamplingParams()))
            for i in range(k)]


def test_sdc_clean_no_suspects_outputs_unchanged(tiny_gpt):
    off = _fleet(tiny_gpt, n=2)
    on = _sdc_fleet(tiny_gpt)
    for fl in (off, on):
        for r in _mixed_requests():
            fl.add_request(Request(r.uid, list(r.prompt),
                                   max_new_tokens=r.max_new_tokens,
                                   sampling=r.sampling))
    ro = off.run(return_status=True)
    rn = on.run(return_status=True)
    assert {u: (r.tokens, r.status) for u, r in ro.items()} \
        == {u: (r.tokens, r.status) for u, r in rn.items()}
    st = on.stats()
    assert st["num_sdc_checks"] > 0
    assert st["num_sdc_suspects"] == 0
    assert st["num_lost_requests"] == 0
    # replays ran under the INTERNAL tenant and never charged a real
    # one: the real tenant's fleet-wide ledger (delivered tokens,
    # statuses) is identical to the sdc-off run; any residual
    # "__sdc__" row is allocator-side cached-block attribution only
    # (honest pool accounting), with its token/status history pruned
    off_t = off.stats()["tenants"]["default"]
    on_t = st["tenants"]["default"]
    assert on_t["tokens"] == off_t["tokens"]
    assert on_t["statuses"] == off_t["statuses"]
    sdc_row = st["tenants"].get("__sdc__")
    if sdc_row is not None:
        assert sdc_row["tokens"] == 0 and sdc_row["statuses"] == {}


def test_sdc_catches_and_retires_corrupt_replica(tiny_gpt):
    plans = [FaultPlan([FaultSpec(site="decode", kind="corrupt",
                                  every=3)], seed=6), None, None]
    fl = _sdc_fleet(tiny_gpt, faults=plans, n=3)
    reqs = _mixed_requests()
    for r in reqs:
        fl.add_request(r)
    res = fl.run(return_status=True)
    st = fl.stats()
    assert st["num_sdc_suspects"] >= 1
    assert not fl.replicas[0].alive
    assert fl.replicas[0].error == "sdc divergence"
    assert st["num_lost_requests"] == 0
    # exactly-once terminals for every accepted uid, replays excluded
    assert set(res) == {r.uid for r in reqs}
    for rep in fl.replicas:
        if rep.alive and rep.engine is not None:
            rep.engine.check_allocator_integrity()


@pytest.mark.parametrize("corrupt_idx", [0, 1, 2])
def test_sdc_arbitration_retires_the_corrupt_replica_only(
        tiny_gpt, corrupt_idx):
    """The majority arbitration: whichever replica carries the corrupt
    chip — the owner of the replayed request OR its first verifier —
    the confirmation replay on an independent third replica sides with
    the healthy majority, the corrupt replica retires, and no healthy
    replica is ever the suspect."""
    plans = [None, None, None]
    plans[corrupt_idx] = FaultPlan(
        [FaultSpec(site="decode", kind="corrupt", every=2)], seed=6)
    fl = _sdc_fleet(tiny_gpt, faults=plans, n=3, interval=1)
    for k in range(9):
        fl.add_request(Request(f"q{k}", [1 + k] + PROMPTS[0][1:],
                               max_new_tokens=4))
    res = fl.run(return_status=True)
    st = fl.stats()
    assert st["num_lost_requests"] == 0
    assert set(res) == {f"q{k}" for k in range(9)}
    assert not fl.replicas[corrupt_idx].alive, "corrupt replica lived"
    assert all(fl.replicas[i].alive for i in range(3)
               if i != corrupt_idx), "a healthy replica was retired"
    assert st["num_sdc_suspects"] >= 1


def test_sdc_rehoming_with_history_drops_eligibility(tiny_gpt):
    """A request re-homed CARRYING generated history mixes two
    replicas' compute in one stream — a later divergence could blame
    the healthy final owner for the previous owner's corruption, so it
    leaves the cross-check pool; a re-homed request with NO history
    (still waiting) stays attributable and stays eligible."""
    fl = _sdc_fleet(tiny_gpt, n=2, interval=1000)   # never launches
    fl.add_request(Request("h0", PROMPTS[0], max_new_tokens=6))
    fl.add_request(Request("h1", PROMPTS[1], max_new_tokens=6))
    assert "h0" in fl._sdc_arrivals and "h1" in fl._sdc_arrivals
    # step until h0's owner has emitted something for it
    owner = fl.owners()["h0"]
    for _ in range(30):
        fl.step()
        if any(s is not None and s.request.uid == "h0" and s.generated
               for s in fl.replicas[owner].engine.slots):
            break
    fl.migrate(None, owner)     # drain everything off the owner
    assert "h0" not in fl._sdc_arrivals     # history rode the record
    res = fl.run(return_status=True)
    assert {u: r.status for u, r in res.items()} \
        == {"h0": "finished", "h1": "finished"}


def test_sdc_replays_never_reach_the_client(tiny_gpt):
    fl = _sdc_fleet(tiny_gpt, interval=1)
    for r in _mixed_requests(4):
        fl.add_request(r)
    seen = []
    while fl.has_work:
        fl.step()
        seen += fl.pop_stream_events()
    res = fl.run(return_status=True)
    assert all(not u.startswith("__sdc__") for u, _, _ in seen)
    assert all(not u.startswith("__sdc__") for u in res)
    assert fl.stats()["num_sdc_checks"] > 0


def test_sdc_sampled_with_speculation_ineligible(tiny_gpt):
    # sampled streams are not replica-invariant under speculation
    # (span boundaries are schedule-dependent): only the greedy
    # requests may enter the replay pool
    fl = _sdc_fleet(tiny_gpt, interval=1)
    fl.engine_config = dataclasses_replace_spec(fl.engine_config)
    sampled = Request("s0", PROMPTS[0], max_new_tokens=3,
                      sampling=SamplingParams(temperature=1.0, top_k=5))
    fl._maybe_capture_sdc("s0", _fake_result([1, 2, 3]))
    assert len(fl._sdc_queue) == 0          # unknown uid: not captured
    # a live greedy request IS captured
    fl.add_request(Request("g0", PROMPTS[1], max_new_tokens=3))
    fl._maybe_capture_sdc("g0", _fake_result([1, 2, 3]))
    assert len(fl._sdc_queue) == 1
    # the sampled one is rejected once speculation is on
    fl.add_request(sampled)
    fl._maybe_capture_sdc("s0", _fake_result([1, 2, 3]))
    assert len(fl._sdc_queue) == 1
    fl.run()


def dataclasses_replace_spec(cfg):
    import dataclasses as _dc

    return _dc.replace(cfg, spec_tokens=2)


def _fake_result(tokens):
    from apex_tpu.serving import RequestResult

    return RequestResult(tokens=list(tokens), status="finished")


# ---------------------------------------------------------------------------
# observability surface
# ---------------------------------------------------------------------------


def test_recorder_kinds_exist():
    for kind in ("corruption_detected", "scrub", "sdc_suspect"):
        assert kind in RECORDER_EVENT_KINDS


def test_corruption_events_reach_the_recorder(tiny_gpt):
    model, params = tiny_gpt
    obs = Observability(metrics=False)
    plan = FaultPlan([FaultSpec(site="spill_put", kind="corrupt",
                                every=1)], seed=12)
    eng = InferenceEngine(
        model, params,
        EngineConfig(**SPILL_KW, scrub_interval_ticks=1),
        faults=plan, clock=lambda: 0.0, obs=obs)
    _serve_waves(eng, waves=1)
    kinds = {e["kind"] for e in obs.recorder.tail()}
    assert "scrub" in kinds
    assert "corruption_detected" in kinds


def _load_tool(name):
    path = Path(__file__).resolve().parents[1] / "tools" / name
    spec = importlib.util.spec_from_file_location(
        f"_{name.removesuffix('.py')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_summary_integrity_line():
    ts = _load_tool("trace_summary.py")
    dump = {"recorder": {"events": [
        {"kind": "scrub", "t": 0.0, "verified": 4, "corrupt": 1},
        {"kind": "corruption_detected", "t": 0.1, "site": "spill_get"},
        {"kind": "corruption_detected", "t": 0.2, "site": "import"},
        {"kind": "sdc_suspect", "t": 0.3, "replica": 1},
    ]}}
    out = ts.summarize(dump)
    line = [ln for ln in out.splitlines() if "integrity" in ln]
    assert len(line) == 1
    assert "1 scrubs verifying 4 blocks" in line[0]
    assert "2 corruptions caught (import=1, spill_get=1)" in line[0]
    assert "1 SDC suspects retired (replica 1)" in line[0]
    # absent entirely on a clean run
    assert "integrity" not in ts.summarize({"recorder": {"events": []}})
