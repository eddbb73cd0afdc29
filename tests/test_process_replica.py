"""Out-of-process replica certification (tier-1, CPU): the ISSUE 16
layer (docs/fleet.md, "Process replicas").

The wire protocol's failure classes (round trip, clean close,
truncation, rot, bad JSON, oversize refusal at both ends, timeout —
every damaged frame an ``IntegrityError``, never a silent mis-parse);
the seeded ``"wire"`` fault site (truncating/rotting chaos hook,
construction-time kind validation, plan serialization and the
wire/child split); the serialization layer (EngineConfig, Request,
clock specs, the numpy array codec); the :class:`ProcessReplica`
surface against a REAL child process — status mirroring, engine-error
mapping, the retry + at-most-once dedupe loop under injected frame
damage, the params-checksum boot handshake; the 1-process-replica
fleet bit-identity cert (outputs, statuses, full stats; greedy +
sampled, speculation on/off); and the SIGKILL chaos cert — a real
``kill -9`` of a child mid-burst with zero lost accepted requests,
exactly-once terminals, and respawn into a fresh OS process."""

import json
import os
import signal
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTConfig, GPTLMHeadModel
from apex_tpu.serving import (
    EngineConfig,
    FleetConfig,
    FleetRouter,
    ProcessReplica,
    ReplicaUnavailableError,
    Request,
    SamplingParams,
    TenantQuota,
)
from apex_tpu.serving import wire
from apex_tpu.serving.process_replica import (
    build_model_from_spec,
    clock_from_spec,
    engine_config_from_record,
    engine_config_record,
    gpt_model_spec,
    params_checksum,
    request_from_record,
    request_record,
)
from apex_tpu.utils.faults import (
    FaultPlan,
    FaultSpec,
    plan_from_record,
    plan_record,
    split_plan,
    validate_wire_specs,
    wire_chaos,
)
from apex_tpu.utils.integrity import IntegrityError

ENGINE_KW = dict(max_batch=2, block_size=4, num_blocks=32,
                 max_prefill_len=8, max_seq_len=32, seed=7,
                 enable_prefix_caching=True)


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


@pytest.fixture()
def pipe_pair():
    r, w = os.pipe()
    yield r, w
    for fd in (r, w):
        try:
            os.close(fd)
        except OSError:
            pass


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


# ---------------------------------------------------------------------------
# the frame protocol
# ---------------------------------------------------------------------------


def test_frame_round_trip(pipe_pair):
    r, w = pipe_pair
    rec = {"type": "call", "id": 3, "method": "step",
           "args": [[1, 2], {"k": 0.5, "s": "x"}], "flag": True}
    wire.write_frame(w, dict(rec))
    got = wire.read_frame(r)
    got.pop("checksum")
    assert got == rec


def test_frame_clean_eof_is_wire_closed(pipe_pair):
    r, w = pipe_pair
    os.close(w)
    with pytest.raises(wire.WireClosedError):
        wire.read_frame(r)


def test_frame_truncated_header_and_body(pipe_pair):
    r, w = pipe_pair
    frame = wire.encode_frame({"type": "x"})
    # a few header bytes, then EOF: torn, not clean-closed
    os.write(w, frame[:3])
    os.close(w)
    with pytest.raises(IntegrityError, match="truncated header"):
        wire.read_frame(r)
    r2, w2 = os.pipe()
    try:
        os.write(w2, frame[:-4])     # full header, partial body
        os.close(w2)
        with pytest.raises(IntegrityError, match="truncated body"):
            wire.read_frame(r2)
    finally:
        os.close(r2)


def test_frame_rotted_byte_raises_integrity(pipe_pair):
    r, w = pipe_pair
    frame = bytearray(wire.encode_frame({"type": "resp", "value": 7}))
    # flip one byte inside a JSON number: still valid JSON, but the
    # embedded checksum no longer matches
    idx = frame.index(b'"value":7') + len(b'"value":')
    frame[idx] = ord("9")
    os.write(w, bytes(frame))
    with pytest.raises(IntegrityError):
        wire.read_frame(r)


def test_frame_garbage_body_raises_integrity(pipe_pair):
    r, w = pipe_pair
    body = b"\xff\xfenot json"
    os.write(w, wire._HEADER.pack(len(body)) + body)
    with pytest.raises(IntegrityError, match="torn frame"):
        wire.read_frame(r)
    # a valid-JSON non-object body is refused too
    body = json.dumps([1, 2, 3]).encode()
    os.write(w, wire._HEADER.pack(len(body)) + body)
    with pytest.raises(IntegrityError, match="record object"):
        wire.read_frame(r)


def test_frame_oversize_refused_both_ends(pipe_pair):
    r, w = pipe_pair
    with pytest.raises(IntegrityError, match="oversize"):
        wire.encode_frame({"blob": "x" * 256}, max_bytes=64)
    # a corrupt length prefix is refused before any body allocation
    os.write(w, wire._HEADER.pack(wire.MAX_FRAME_BYTES + 1))
    with pytest.raises(IntegrityError, match="oversize frame refused"):
        wire.read_frame(r)


def test_frame_timeout(pipe_pair):
    r, w = pipe_pair
    with pytest.raises(wire.WireTimeoutError):
        wire.read_frame(r, timeout_s=0.05)
    # ... including stalling mid-frame
    frame = wire.encode_frame({"type": "x"})
    os.write(w, frame[: wire.HEADER_BYTES + 2])
    with pytest.raises(wire.WireTimeoutError):
        wire.read_frame(r, timeout_s=0.05)


def test_frame_write_survives_pipe_buffer(pipe_pair):
    # a frame larger than the pipe buffer must still round-trip (the
    # writer loops over partial os.write results)
    r, w = pipe_pair
    rec = {"type": "bulk", "blob": "a" * (1 << 20)}
    err = []

    def reader():
        try:
            got = wire.read_frame(r, timeout_s=30.0)
            assert got["blob"] == rec["blob"]
        except Exception as e:  # pragma: no cover - surfaced below
            err.append(e)

    t = threading.Thread(target=reader)
    t.start()
    wire.write_frame(w, rec)
    t.join(timeout=30.0)
    assert not err and not t.is_alive()


def test_arrays_codec_round_trip():
    payload = {
        "k": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
        "nested": {"v": np.array([1, -2, 3], dtype=np.int64),
                   "scalar": 7, "s": "txt"},
        "list": [np.zeros((2, 2), dtype=np.float16), None, True],
    }
    enc = wire.encode_arrays(payload)
    json.dumps(enc)    # must be JSON-able as-is
    dec = wire.decode_arrays(enc)
    np.testing.assert_array_equal(dec["k"], payload["k"])
    assert dec["k"].dtype == np.float32
    np.testing.assert_array_equal(dec["nested"]["v"],
                                  payload["nested"]["v"])
    assert dec["list"][0].dtype == np.float16
    assert dec["nested"]["scalar"] == 7 and dec["list"][1:] == [None, True]
    # the input tree was not mutated
    assert isinstance(payload["k"], np.ndarray)


# ---------------------------------------------------------------------------
# the "wire" fault site
# ---------------------------------------------------------------------------


def test_wire_chaos_transient_truncates(pipe_pair):
    r, w = pipe_pair
    plan = FaultPlan([FaultSpec(site="wire", kind="transient", at=(1,))])
    hook = wire_chaos(plan)
    wire.write_frame(w, {"type": "a", "n": 1})
    wire.write_frame(w, {"type": "b", "n": 2})
    assert wire.read_frame(r, chaos=hook)["type"] == "a"   # call 0: clean
    with pytest.raises(IntegrityError):                    # call 1: torn
        wire.read_frame(r, chaos=hook)
    assert plan.counts() == {"wire": {"transient": 1}}


def test_wire_chaos_corrupt_rots_checksum(pipe_pair):
    r, w = pipe_pair
    plan = FaultPlan([FaultSpec(site="wire", kind="corrupt", at=(0,))],
                     seed=11)
    hook = wire_chaos(plan)
    wire.write_frame(w, {"type": "resp", "id": 5, "result": 42})
    with pytest.raises(IntegrityError):
        wire.read_frame(r, chaos=hook)
    # deterministic: the same plan rots the same frame the same way
    plan2 = FaultPlan([FaultSpec(site="wire", kind="corrupt", at=(0,))],
                      seed=11)
    body = wire.encode_frame(
        {"type": "resp", "id": 5, "result": 42})[wire.HEADER_BYTES:]
    assert wire_chaos(plan2)(body) == wire_chaos(FaultPlan(
        [FaultSpec(site="wire", kind="corrupt", at=(0,))], seed=11))(body)


def test_validate_wire_specs():
    validate_wire_specs([FaultSpec(site="wire", kind="corrupt", at=(0,)),
                         FaultSpec(site="wire", kind="transient", at=(1,)),
                         FaultSpec(site="decode", kind="crash", at=(0,))])
    for kind in ("crash", "nan"):
        with pytest.raises(ValueError, match="not valid at site"):
            validate_wire_specs([FaultSpec(site="wire", kind=kind,
                                           at=(0,))])


def test_plan_record_round_trip_and_split():
    plan = FaultPlan([
        FaultSpec(site="wire", kind="corrupt", at=(2,), max_fires=1),
        FaultSpec(site="decode", kind="transient", every=3),
        FaultSpec(site="wire", kind="transient", prob=0.5),
    ], seed=9)
    clone = plan_from_record(json.loads(json.dumps(plan_record(plan))))
    assert clone.seed == plan.seed and clone.specs == plan.specs
    here, there = split_plan(plan, "wire")
    assert [s.site for s in here.specs] == ["wire", "wire"]
    assert [s.site for s in there.specs] == ["decode"]
    assert here.seed == there.seed == 9
    assert split_plan(None, "wire") == (None, None)
    only_wire, none = split_plan(FaultPlan(
        [FaultSpec(site="wire", kind="corrupt", at=(0,))]), "wire")
    assert none is None and len(only_wire.specs) == 1


# ---------------------------------------------------------------------------
# serialization: configs, requests, clocks
# ---------------------------------------------------------------------------


def test_engine_config_record_round_trip():
    cfg = EngineConfig(**ENGINE_KW, kv_dtype="float32",
                       mesh_shape=(1, 1),
                       tenant_quotas={"a": TenantQuota(max_waiting=3)},
                       tenant_weights={"a": 2.0})
    rec = json.loads(json.dumps(engine_config_record(cfg)))
    clone = engine_config_from_record(rec)
    assert clone.max_batch == cfg.max_batch
    assert clone.mesh_shape == (1, 1)
    assert str(jnp.dtype(clone.kv_dtype)) == "float32"
    assert clone.tenant_quotas["a"].max_waiting == 3
    assert clone.tenant_weights == {"a": 2.0}
    # the identity that matters: the restore fingerprints match
    rec2 = engine_config_record(clone)
    assert rec2 == engine_config_record(engine_config_from_record(rec2))


def test_request_record_round_trip():
    req = Request("u1", [3, 1, 4], max_new_tokens=6,
                  sampling=SamplingParams(temperature=0.7, top_k=5,
                                          top_p=0.9),
                  eos_token_id=2, deadline_s=1.5, priority=1,
                  tenant="acme")
    clone = request_from_record(json.loads(json.dumps(
        request_record(req))))
    assert (clone.uid, clone.prompt, clone.max_new_tokens) == \
        ("u1", [3, 1, 4], 6)
    assert (clone.sampling.temperature, clone.sampling.top_k,
            clone.sampling.top_p) == (0.7, 5, 0.9)
    assert (clone.eos_token_id, clone.deadline_s, clone.priority,
            clone.tenant) == (2, 1.5, 1, "acme")


def test_clock_from_spec():
    assert clock_from_spec(None) is None
    assert clock_from_spec({"kind": "monotonic"}) is None
    frozen = clock_from_spec({"kind": "constant", "t": 2.5})
    assert frozen() == 2.5 and frozen() == 2.5
    with pytest.raises(ValueError, match="clock spec"):
        clock_from_spec({"kind": "wall"})


def test_model_spec_rebuilds_identical_weights(tiny_gpt):
    cfg, _, params = tiny_gpt
    spec = json.loads(json.dumps(gpt_model_spec(cfg)))
    _, rebuilt = build_model_from_spec(spec)
    assert params_checksum(rebuilt) == params_checksum(params)
    with pytest.raises(ValueError, match="model family"):
        build_model_from_spec({"family": "bert", "config": {}})


# ---------------------------------------------------------------------------
# process-mode construction validation (no child is ever spawned)
# ---------------------------------------------------------------------------


def test_fleet_process_mode_validation(tiny_gpt):
    cfg, model, params = tiny_gpt
    ecfg = EngineConfig(**ENGINE_KW)
    with pytest.raises(ValueError, match="replica_mode"):
        FleetConfig(replica_mode="thread")
    with pytest.raises(ValueError, match="rpc_timeout_s"):
        FleetConfig(rpc_timeout_s=0.0)
    with pytest.raises(ValueError, match="rpc_retries"):
        FleetConfig(rpc_retries=-1)
    with pytest.raises(ValueError, match="model_spec"):
        FleetRouter(model, params, ecfg,
                    FleetConfig(num_replicas=1, replica_mode="process"))
    with pytest.raises(ValueError, match="child_clock"):
        FleetRouter(model, params, ecfg,
                    FleetConfig(num_replicas=1, replica_mode="process"),
                    model_spec=gpt_model_spec(cfg), clock=lambda: 0.0)
    with pytest.raises(ValueError, match="child_clock"):
        FleetRouter(model, params, ecfg, FleetConfig(num_replicas=1),
                    child_clock={"kind": "constant", "t": 0.0})
    with pytest.raises(ValueError, match="wire"):
        FleetRouter(model, params, ecfg, FleetConfig(num_replicas=1),
                    faults=[FaultPlan([FaultSpec(site="wire",
                                                 kind="corrupt",
                                                 at=(0,))])])


# ---------------------------------------------------------------------------
# the ProcessReplica surface (one real child)
# ---------------------------------------------------------------------------


def test_process_replica_surface_and_error_mapping(tiny_gpt):
    cfg, _, params = tiny_gpt
    spec = gpt_model_spec(cfg)
    rep = ProcessReplica(EngineConfig(**ENGINE_KW), spec,
                         expect_params_checksum=params_checksum(params),
                         clock_spec={"kind": "constant", "t": 0.0})
    try:
        assert rep.mode == "process" and rep.alive
        assert rep.child_pid > 0
        assert not rep.has_work
        assert rep.queue_depth == 0 and rep.active_slot_count == 0
        req = Request("p0", [5, 6, 7], max_new_tokens=3,
                      sampling=SamplingParams())
        assert rep.add_request(req) == 0
        assert req.status is None            # door passed, mirrored
        assert rep.queue_depth == 1 and rep.has_work
        # an engine-level refusal maps back to the REAL local type
        with pytest.raises(ValueError, match="max_seq_len"):
            rep.add_request(Request("bad", [1] * 40, max_new_tokens=2,
                                    sampling=SamplingParams()))
        # per-tenant accessors mirror the in-process narrow surface
        assert rep.tenant_depth("nosuch") == 0
        load = rep.load()
        assert set(load) >= {"queue_depth", "active_slots",
                             "blocks_allocatable"}
        assert rep.block_weight > 0
        assert rep.probe_prefix([]) == 0
        n = 0
        while rep.has_work and n < 60:
            rep.step()
            n += 1
        res = rep.pop_results()
        assert res["p0"].status == "finished"
        assert len(res["p0"].tokens) == 3
        assert req.status == "finished"      # terminal status mirrored
        assert rep.abort("p0") is False      # already terminal
        snap = rep.checkpoint()
        assert rep.last_checkpoint is snap and "checksum" in snap
        stats = rep.stats()
        json.dumps(stats)                    # JSON-normalized by wire
        assert stats["num_ticks"] > 0
        # an unknown RPC method is a loud ValueError, not a hang
        with pytest.raises(ValueError, match="unknown RPC method"):
            rep._call("frobnicate")
    finally:
        rep.close()
    assert not rep.alive
    with pytest.raises(ReplicaUnavailableError):
        rep.step()
    rep.kill()          # idempotent on a closed handle


def test_process_replica_retry_and_at_most_once(tiny_gpt):
    """Injected frame damage on RPC responses: the parent resends the
    SAME id, the worker answers duplicates from its response cache
    without re-executing — so a retried add_request never
    double-enqueues (the at-most-once cert)."""
    cfg, _, params = tiny_gpt
    retries = []
    # response frames: call 0 rotted (stale checksum), call 2 torn
    plan = FaultPlan([FaultSpec(site="wire", kind="corrupt", at=(0,)),
                      FaultSpec(site="wire", kind="transient", at=(2,))],
                     seed=5)
    rep = ProcessReplica(EngineConfig(**ENGINE_KW), gpt_model_spec(cfg),
                         expect_params_checksum=params_checksum(params),
                         clock_spec={"kind": "constant", "t": 0.0},
                         faults=plan, rpc_retries=2,
                         on_retry=lambda: retries.append(1))
    try:
        req = Request("q0", [9, 8, 7], max_new_tokens=3,
                      sampling=SamplingParams())
        assert rep.add_request(req) == 0     # call 0 rotted -> retried
        assert len(retries) == 1
        assert rep.queue_depth == 1          # call 2 torn -> retried;
        assert len(retries) == 2             # and NOT double-enqueued
        out = {}
        n = 0
        while rep.has_work and n < 60:
            rep.step()
            out.update(rep.pop_results())
            n += 1
        out.update(rep.pop_results())
        assert out["q0"].status == "finished"
        # split_plan kept the wire rules parent-side; its audit log
        # shows exactly the two injected hits
        assert rep.wire_faults.counts()["wire"] == {"corrupt": 1,
                                                    "transient": 1}
    finally:
        rep.close()


def test_child_refuses_params_checksum_mismatch(tiny_gpt):
    """The boot handshake: a model spec that does not reproduce the
    parent's weights is refused at hello, never served."""
    cfg, _, _ = tiny_gpt
    with pytest.raises(IntegrityError, match="checksum"):
        ProcessReplica(EngineConfig(**ENGINE_KW), gpt_model_spec(cfg),
                       expect_params_checksum="0" * 64)


# ---------------------------------------------------------------------------
# the 1-process-replica fleet bit-identity cert
# ---------------------------------------------------------------------------


def _normalized_stats(fleet):
    st = fleet.stats()
    for row in st["replicas"].values():
        # the per-replica "mode" is the ONE documented difference
        # between the arms (docs/fleet.md, "Process replicas")
        row.pop("mode")
    return json.loads(json.dumps(st, sort_keys=True, default=str))


@pytest.mark.parametrize("spec_tokens", [0, 3])
def test_single_process_replica_fleet_bit_identical(tiny_gpt,
                                                    spec_tokens):
    cfg, model, params = tiny_gpt
    ecfg = EngineConfig(**ENGINE_KW, spec_tokens=spec_tokens)
    outs = {}
    for mode in ("in_process", "process"):
        kw = {}
        if mode == "process":
            kw = dict(model_spec=gpt_model_spec(cfg),
                      child_clock={"kind": "constant", "t": 0.0})
        fleet = FleetRouter(model, params, ecfg,
                            FleetConfig(num_replicas=1,
                                        replica_mode=mode),
                            clock=lambda: 0.0, **kw)
        try:
            for req in _reqs(n=5, sampled=True):
                fleet.add_request(req)
            res = fleet.run(return_status=True)
            outs[mode] = (
                {u: (tuple(r.tokens), r.status) for u, r in res.items()},
                _normalized_stats(fleet))
        finally:
            fleet.close()
    assert outs["process"][0] == outs["in_process"][0]
    assert outs["process"][1] == outs["in_process"][1]


# ---------------------------------------------------------------------------
# the SIGKILL chaos cert: kill -9 a real child mid-burst
# ---------------------------------------------------------------------------


def test_fleet_survives_real_sigkill(tiny_gpt):
    """A child is SIGKILLed for real three ticks into a burst: nothing
    accepted is lost, the slot respawns into a fresh OS process, and
    the requests the dead child owned see their first token within
    ``4 x p99 + 16`` router ticks of the same burst served undisturbed
    (in process: the tick schedule is the mode's, the boot cost of the
    respawn is wall time)."""
    from _traffic import drive

    cfg, model, params = tiny_gpt
    ecfg = EngineConfig(**ENGINE_KW, snapshot_interval_ticks=2)

    def burst():
        return [(0, r) for r in _reqs(n=6, sampled=True, uid="k")]

    base = drive(FleetRouter(model, params, ecfg,
                             FleetConfig(num_replicas=2)), burst())
    fleet = FleetRouter(
        model, params, ecfg,
        FleetConfig(num_replicas=2, replica_mode="process",
                    respawn=True, rpc_timeout_s=60.0),
        model_spec=gpt_model_spec(cfg))
    try:
        victim = fleet.replicas[0].engine
        pid0 = victim.child_pid
        owned = []

        def kill_at_tick_3(tick, seen):
            if tick == 3:
                owned.extend(u for u, o in fleet.owners().items()
                             if o == 0)
                os.kill(pid0, signal.SIGKILL)        # a REAL kill -9

        seen = drive(fleet, burst(), before_step=kill_at_tick_3)
        res = fleet.run(return_status=True)
        # zero lost accepted requests, exactly-once terminals
        assert sorted(res) == sorted(seen.accepted) and len(res) == 6
        assert all(r.status == "finished" for r in res.values())
        st = fleet.stats()
        assert st["num_lost_requests"] == 0
        assert st["num_replicas_down"] == 1
        assert st["num_failovers"] == 1
        assert st["num_respawns"] == 1
        # the slot respawned into a FRESH OS process
        fresh = fleet.replicas[0].engine
        assert fresh is not victim and fresh is not None
        assert fresh.child_pid != pid0 and fresh.alive
        # the corpse really is gone (waitpid would have reaped it;
        # poll() on the handle did)
        assert not victim.alive
        assert owned, "the killed child owned nothing"
        assert seen.ttft_p99(owned) <= 4.0 * base.ttft_p99() + 16.0
    finally:
        fleet.close()
    # close() disposed every child: none of the handles poll alive
    assert all(rep.engine is None or not rep.engine.alive
               for rep in fleet.replicas)


def test_router_kill_replica_is_a_real_sigkill(tiny_gpt):
    """kill_replica in process mode delivers an actual SIGKILL (the
    chaos hook stops simulating) and recovery still runs from the
    parent-cached checkpoint alone."""
    cfg, model, params = tiny_gpt
    ecfg = EngineConfig(**ENGINE_KW, snapshot_interval_ticks=2)
    fleet = FleetRouter(
        model, params, ecfg,
        FleetConfig(num_replicas=2, replica_mode="process",
                    rpc_timeout_s=60.0),
        model_spec=gpt_model_spec(cfg))
    try:
        reqs = _reqs(n=4, sampled=False, uid="s")
        for req in reqs:
            fleet.add_request(req)
        for _ in range(2):
            fleet.step()
        victim = fleet.replicas[0].engine
        pid0 = victim.child_pid
        fleet.kill_replica(0)
        # the child process is DEAD (SIGKILL delivered, corpse reaped)
        assert not victim.alive
        with pytest.raises(OSError):
            os.kill(pid0, 0)        # no such process (reaped by wait)
        assert fleet.replicas[0].engine is None
        res = fleet.run(return_status=True)
        assert sorted(res) == sorted(r.uid for r in reqs)
        assert fleet.stats()["num_lost_requests"] == 0
    finally:
        fleet.close()
