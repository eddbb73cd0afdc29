"""Disaggregated prefill/decode serving + the data-parallel batch
axis (tier-1, CPU, 8 virtual devices): the ISSUE 17 layer
(docs/fleet.md "Disaggregated roles", docs/serving.md "Mesh
sharding").

The certification matrix: a disaggregated fleet (prefill specialists
handing chain-hashed KV through the checksummed transport to decode
specialists) is token-identical to the colocated fleet, greedy +
sampled x speculation on/off; the two-stage router skips affinity
probes of prefill specialists during decode placement (counted);
handoffs survive the 'corrupt' fault kind at the transport sites
(refused -> recompute, token-identical, zero corrupt state admitted);
a dead prefill replica's in-flight work lands on survivors with zero
lost accepted requests; the autoscaler reads the PER-ROLE watermark
signal (a prefill backlog spawns a prefill specialist even while the
fleet-wide mean sits below the watermark). Plus the batch mesh axis:
``(2, 1)``/``(2, 2)`` token-identical to ``(1, 1)`` on fixed seeds,
``(1, 1)`` bit-identical run to run (full constant-clock stats),
compile counts pinned, the collective contract audited per shape, and
shard-residency allocator integrity after churn."""

import importlib.util
import json
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
    FleetRouter,
    InferenceEngine,
    Request,
    SamplingParams,
    validate_mesh_shape,
)
from apex_tpu.utils.faults import FaultPlan, FaultSpec

CONST_CLOCK = lambda: 0.0  # noqa: E731 — constant-clock stats compare


@pytest.fixture(scope="module")
def tiny_gpt():
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return model, params


# the disaggregated-fleet engine geometry: prefix caching + the spill
# tier on (the handoff transport), max_seq_len long enough that no
# request is truncated mid-experiment
DISAGG_KW = dict(max_batch=4, block_size=4, num_blocks=32,
                 max_prefill_len=8, max_seq_len=48, decode_steps=2,
                 seed=7, enable_prefix_caching=True,
                 spill_max_bytes=1 << 20)

# the batch-axis engine geometry (mesh tests): max_batch/num_blocks
# divisible by every batch-axis size under test
MESH_KW = dict(max_batch=4, block_size=4, num_blocks=32,
               max_prefill_len=8, max_seq_len=32, decode_steps=2,
               seed=7)


def _fleet(tiny_gpt, n=2, fleet_kw=None, clock=CONST_CLOCK,
           faults=None, obs=None, **overrides):
    model, params = tiny_gpt
    kw = dict(DISAGG_KW)
    kw.update(overrides)
    return FleetRouter(model, params, EngineConfig(**kw),
                       FleetConfig(num_replicas=n, **(fleet_kw or {})),
                       clock=clock, faults=faults, obs=obs)


def _reqs(n=6, sampled=True, new=6, seed=3, uid="r"):
    """Seeded mixed workload: varied prompt lengths, greedy AND
    sampled lanes (per-request keys make the draws placement- and
    mesh-invariant)."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        prompt = list(rng.randint(1, 50, 7 + i))
        samp = (SamplingParams(temperature=0.7, top_k=8, top_p=0.9)
                if sampled and i % 2 else SamplingParams())
        out.append(Request(f"{uid}{i}", prompt,
                           max_new_tokens=new + (i % 3), sampling=samp))
    return out


def _resdict(res):
    return {u: (tuple(r.tokens), r.status) for u, r in res.items()}


def _run(fleet, reqs):
    for r in reqs:
        fleet.add_request(r)
    return fleet.run(return_status=True)


# ---------------------------------------------------------------------------
# role-config validation
# ---------------------------------------------------------------------------


def test_replica_roles_validation():
    with pytest.raises(ValueError, match="replica_roles"):
        FleetConfig(num_replicas=2, replica_roles=("prefill",))
    with pytest.raises(ValueError, match="replica_roles"):
        FleetConfig(num_replicas=2,
                    replica_roles=("prefill", "verifier"))
    # a disaggregated fleet needs BOTH specialist kinds
    with pytest.raises(ValueError, match="replica_roles"):
        FleetConfig(num_replicas=2,
                    replica_roles=("prefill", "prefill"))
    with pytest.raises(ValueError, match="replica_roles"):
        FleetConfig(num_replicas=2, replica_roles=("decode", "decode"))
    # a list normalizes to a tuple
    cfg = FleetConfig(num_replicas=2,
                      replica_roles=["prefill", "decode"])
    assert cfg.replica_roles == ("prefill", "decode")


def test_roles_require_prefix_caching(tiny_gpt):
    """The handoff rides the prefix-payload transport: roles without
    ``enable_prefix_caching`` have no handoff path and are refused at
    construction, not discovered as a silent colocated fallback."""
    model, params = tiny_gpt
    kw = dict(DISAGG_KW)
    kw.update(enable_prefix_caching=False, spill_max_bytes=None)
    with pytest.raises(ValueError, match="enable_prefix_caching"):
        FleetRouter(model, params, EngineConfig(**kw),
                    FleetConfig(num_replicas=2,
                                replica_roles=("prefill", "decode")))


# ---------------------------------------------------------------------------
# the disaggregation identity cert
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [0, 2])
def test_disagg_token_identical_to_colocated(tiny_gpt, spec):
    """THE handoff cert: a {1 prefill + 1 decode} disaggregated fleet
    reproduces the colocated 1-replica fleet token for token, status
    for status — greedy + sampled lanes, speculation on and off. The
    single router door preserves arrival order, so per-request PRNG
    identity holds; the handoff itself is the certified migrate path
    (drain -> checksummed export -> prefix-seeded import)."""
    co = _run(_fleet(tiny_gpt, n=1, spec_tokens=spec), _reqs())
    dis_fleet = _fleet(
        tiny_gpt, n=2, spec_tokens=spec,
        fleet_kw=dict(replica_roles=("prefill", "decode")))
    dis = _run(dis_fleet, _reqs())
    assert _resdict(dis) == _resdict(co)
    st = dis_fleet.stats()
    assert st["num_lost_requests"] == 0
    # work actually moved: the decode specialist did not sit idle
    assert st["num_handoffs"] > 0
    assert st["num_handoff_requests"] > 0
    assert st["num_handoff_bytes"] > 0
    assert st["replicas"]["0"]["role"] == "prefill"
    assert st["replicas"]["1"]["role"] == "decode"


def test_two_stage_router_skips_prefill_probes(tiny_gpt):
    """Satellite: during decode-stage placement the router never
    affinity-probes a prefill specialist — the skips are counted. A
    colocated fleet (no roles) probes everyone and counts zero."""
    dis = _fleet(tiny_gpt, n=2,
                 fleet_kw=dict(replica_roles=("prefill", "decode")))
    _run(dis, _reqs())
    assert dis.stats()["num_affinity_probes_skipped"] > 0

    co = _fleet(tiny_gpt, n=2)
    _run(co, _reqs())
    cs = co.stats()
    assert cs["num_affinity_probes_skipped"] == 0
    # the colocated fleet keeps the pre-role surface quiet: no
    # handoffs, every replica the single "mixed" role
    assert cs["num_handoffs"] == 0
    assert cs["num_handoff_bytes"] == 0
    assert all(r["role"] == "mixed" for r in cs["replicas"].values())


def test_handoff_survives_corrupt_transport(tiny_gpt):
    """Handoff under the 'corrupt' fault kind at the transport site:
    a rotted export is REFUSED at the decode specialist's import
    verify, the request re-enters fresh at the source (recompute), and
    the fleet output stays token-identical to the colocated run —
    corrupt state never re-enters, correctness never depends on the
    transport staying clean."""
    co = _run(_fleet(tiny_gpt, n=1), _reqs())
    faults = [FaultPlan([FaultSpec(site="export", kind="corrupt",
                                   every=2)]),
              None]
    dis_fleet = _fleet(
        tiny_gpt, n=2, faults=faults,
        fleet_kw=dict(replica_roles=("prefill", "decode")))
    dis = _run(dis_fleet, _reqs())
    assert _resdict(dis) == _resdict(co)
    st = dis_fleet.stats()
    assert st["num_refused_imports"] > 0, \
        "the corrupt fault never fired at the handoff transport"
    assert st["num_lost_requests"] == 0


def test_role_aware_failover_zero_lost(tiny_gpt):
    """Kill the only prefill specialist mid-trace: its in-handoff and
    still-prefilling requests land on survivors (zero-lost outranks
    specialization — the survivor pool falls back to every alive
    replica when a role group empties) and every accepted request
    reaches a terminal status."""
    fleet = _fleet(
        tiny_gpt, n=3,
        fleet_kw=dict(replica_roles=("prefill", "decode", "decode")))
    reqs = _reqs()
    for r in reqs:
        fleet.add_request(r)
    fleet.step()
    fleet.kill_replica(0)
    res = fleet.run(return_status=True)
    st = fleet.stats()
    assert st["num_failovers"] >= 1
    assert st["num_lost_requests"] == 0
    assert set(res) == {r.uid for r in reqs}
    assert all(v.status in ("finished", "aborted") for v in res.values())
    for _, rep in fleet._alive():
        rep.engine.check_allocator_integrity()


# ---------------------------------------------------------------------------
# the interference scenario: specialists against a colocated fleet
# ---------------------------------------------------------------------------


def test_specialists_beat_colocated_ttft_p99_scenario(tiny_gpt):
    """What disaggregation is for, at EQUAL replica count: on a seeded
    Poisson mix of long-decode requests and short latency-sensitive
    ones, with two lanes a replica, a colocated replica's lanes are
    pinned through whole decodes and a newcomer's prefill waits them
    out; a prefill specialist recycles its lanes at the handoff. So
    the {1 prefill + 1 decode} fleet's TTFT p99, in router ticks, is
    under the 2-replica colocated fleet's on the same trace; the
    handoff moved requests and bytes; and the decode specialist
    prefilled only what it imported (sub-block tail resumes: at most
    one chunk an import, never a fresh prompt)."""
    from _traffic import TickClock, drive, poisson_burst_trace

    model, params = tiny_gpt
    ecfg = EngineConfig(max_batch=2, block_size=8, num_blocks=96,
                        max_prefill_len=8, max_seq_len=64,
                        enable_prefix_caching=True, spill_max_bytes=1 << 20,
                        snapshot_interval_ticks=2, max_waiting=64, seed=11)
    ticks = 14
    rng = np.random.RandomState(1713)

    def make(tick, k):
        heavy = k % 3 != 2
        # single-chunk prompts: the contended resource is the lane a
        # long decode pins, not prefill bandwidth
        prompt = list(rng.randint(
            0, 128, int(rng.randint(6, 9) if heavy else rng.randint(4, 7))))
        new = int(16 + rng.randint(0, 4) if heavy else rng.randint(2, 5))
        samp = (SamplingParams() if k % 2 else
                SamplingParams(temperature=1.0, top_k=40))
        return lambda: Request(uid=f"q{k}", prompt=list(prompt),
                               max_new_tokens=new, sampling=samp)

    trace = poisson_burst_trace(
        rng, ticks, 1.0, make, burst_start=ticks // 3,
        burst_end=2 * ticks // 3, burst_factor=2)

    def serve(**fleet_kw):
        clock = TickClock()     # placement by the trace, not the machine
        fleet = FleetRouter(model, params, ecfg,
                            FleetConfig(num_replicas=2, **fleet_kw),
                            clock=clock)
        seen = drive(fleet, trace, clock=clock)
        res = fleet.run(return_status=True)
        assert sorted(res) == sorted(seen.accepted) and len(res) == len(trace)
        assert fleet.stats()["num_lost_requests"] == 0
        return fleet, seen.ttft_p99()

    _, p99_colocated = serve()
    fleet, p99_disagg = serve(replica_roles=("prefill", "decode"))
    assert p99_disagg < p99_colocated, (p99_disagg, p99_colocated)
    st = fleet.stats()
    assert st["num_handoffs"] >= 1
    assert st["num_handoff_requests"] >= 1
    assert st["num_handoff_bytes"] > 0
    decode = st["replicas"]["1"]
    assert decode["role"] == "decode"
    chunks = fleet.replicas[1].engine.stats()["num_prefill_chunks"]
    assert chunks <= decode["num_migrated_in"], (
        f"the decode specialist ran {chunks} prefill chunks for "
        f"{decode['num_migrated_in']} imports: fresh prompts leaked "
        f"onto the decode pool")


# ---------------------------------------------------------------------------
# the per-role autoscaler signal (satellite)
# ---------------------------------------------------------------------------


def test_autoscaler_reads_per_role_watermark(tiny_gpt):
    """A prefill backlog behind an idle decode specialist: the
    fleet-wide mean queue depth sits BELOW the high watermark (the
    pre-role signal would never fire) while the prefill-role mean sits
    above it — the autoscaler must spawn, and spawn a PREFILL
    specialist."""
    obs = Observability(trace=False, metrics=False)
    fleet = _fleet(
        tiny_gpt, n=2, obs=obs,
        fleet_kw=dict(replica_roles=("prefill", "decode"),
                      autoscale_high_watermark=4.0,
                      autoscale_patience=1,
                      autoscale_max_replicas=3))
    for r in _reqs(n=12, sampled=False):
        fleet.add_request(r)
    # every request queues at the one prefill specialist: prefill-role
    # mean ~ 12 > 4.0 while the fleet-wide mean ~ 6 ... still above;
    # step once so the drained depth (what the signal reads) settles
    fleet.step()
    st = fleet.stats()
    assert st["num_spawned"] >= 1, "the per-role signal never fired"
    spawns = [e for e in obs.recorder.tail()
              if e["kind"] == "replica_spawn"]
    assert spawns and all(e["role"] == "prefill" for e in spawns)
    roles = [r["role"] for r in st["replicas"].values()]
    assert roles.count("decode") == 1, \
        "the idle decode role must not have scaled"
    fleet.run()
    assert fleet.stats()["num_lost_requests"] == 0


def test_colocated_autoscaler_unchanged(tiny_gpt):
    """No roles -> the single 'mixed' group IS the pre-role signal:
    the scalar streak attributes keep their exact meaning and a quiet
    fleet never scales."""
    fleet = _fleet(tiny_gpt, n=1,
                   fleet_kw=dict(autoscale_high_watermark=100.0,
                                 autoscale_patience=2,
                                 autoscale_max_replicas=2))
    _run(fleet, _reqs(n=3))
    assert fleet.stats()["num_spawned"] == 0
    assert fleet._autoscale_hi_streak == 0


# ---------------------------------------------------------------------------
# the observability surface
# ---------------------------------------------------------------------------


def test_handoff_recorder_events_and_trace_summary(tiny_gpt, tmp_path):
    obs = Observability(trace=False, metrics=False)
    fleet = _fleet(
        tiny_gpt, n=2, obs=obs,
        fleet_kw=dict(replica_roles=("prefill", "decode")))
    _run(fleet, _reqs())
    evs = [e for e in obs.recorder.tail()
           if e["kind"] == "prefill_handoff"]
    assert evs, "no prefill_handoff events recorded"
    for e in evs:
        assert e["src"] == 0
        assert e["requests"] > 0
        assert e["bytes"] > 0
        assert "prefill_queue" in e and "decode_queue" in e

    dump_path = tmp_path / "disagg_dump.json"
    dump_path.write_text(json.dumps(obs.dump(), default=str))
    spec = importlib.util.spec_from_file_location(
        "_trace_summary",
        Path(__file__).resolve().parents[1] / "tools" /
        "trace_summary.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report = mod.summarize_file(str(dump_path))
    assert "-- disaggregation:" in report
    assert "prefill->decode" in report


# ---------------------------------------------------------------------------
# the batch mesh axis (tentpole (a) certification matrix)
# ---------------------------------------------------------------------------


def _mesh_engine(tiny_gpt, mesh_shape, **overrides):
    model, params = tiny_gpt
    kw = dict(MESH_KW)
    kw.update(overrides)
    return InferenceEngine(model, params,
                           EngineConfig(mesh_shape=mesh_shape, **kw),
                           clock=CONST_CLOCK)


def _mesh_serve(tiny_gpt, mesh_shape, reqs, **overrides):
    eng = _mesh_engine(tiny_gpt, mesh_shape, **overrides)
    for r in reqs:
        eng.add_request(r)
    return eng, eng.run(return_status=True)


def test_batch_axis_divisibility_validation():
    with pytest.raises(ValueError, match="max_batch"):
        validate_mesh_shape((3, 1), max_batch=4)
    with pytest.raises(ValueError, match="num_blocks"):
        validate_mesh_shape((2, 1), max_batch=4, num_blocks=31)
    kw = dict(MESH_KW)
    kw["max_batch"] = 6
    with pytest.raises(ValueError, match="max_batch"):
        EngineConfig(mesh_shape=(4, 1), **kw)
    kw = dict(MESH_KW)
    kw["num_blocks"] = 30
    with pytest.raises(ValueError, match="num_blocks"):
        EngineConfig(mesh_shape=(4, 1), **kw)


@pytest.mark.parametrize("spec", [0, 2])
def test_batch_mesh11_bit_identity(tiny_gpt, spec):
    """The batch axis at size 1 is the unsharded engine, byte for
    byte: two (1, 1) runs under the constant clock agree on outputs,
    statuses, and the FULL stats() dict — the baseline every
    cross-mesh comparison below leans on."""
    a_eng, a = _mesh_serve(tiny_gpt, (1, 1), _reqs(n=5),
                           spec_tokens=spec)
    b_eng, b = _mesh_serve(tiny_gpt, (1, 1), _reqs(n=5),
                           spec_tokens=spec)
    assert _resdict(a) == _resdict(b)
    assert a_eng.stats() == b_eng.stats()
    assert a_eng.stats()["mesh_batch_axis"] == 1


@pytest.mark.parametrize("mesh", [(2, 1), (2, 2)],
                         ids=["b2m1", "b2m2"])
@pytest.mark.parametrize("spec", [0, 2])
def test_batch_axis_cross_mesh_token_identity(tiny_gpt, mesh, spec):
    """THE batch-axis cert: splitting decode lanes, block tables, and
    the KV pool's lane/block dimension over the ``batch`` axis — alone
    at (2, 1), combined with the Megatron head split at (2, 2) —
    reproduces the (1, 1) token streams exactly on fixed seeds,
    greedy + sampled, speculation on and off; compile counts stay
    pinned at one per program; the collective contract holds (the
    batch axis lowers ZERO new collectives); and after the run every
    resident's blocks live on its lane's shard."""
    reqs = _reqs(n=5)
    _, base = _mesh_serve(tiny_gpt, (1, 1), reqs, spec_tokens=spec)
    eng, out = _mesh_serve(tiny_gpt, mesh, reqs, spec_tokens=spec)
    assert _resdict(out) == _resdict(base)
    st = eng.stats()
    assert st["mesh_batch_axis"] == mesh[0]
    assert st["mesh_model_axis"] == mesh[1]
    assert st["prefill_compilations"] == 1
    assert st["decode_compilations"] == 1
    eng.audit_collectives()
    eng.check_allocator_integrity()


def test_batch_axis_multiplies_concurrency(tiny_gpt):
    """What the axis is FOR: at (2, 1) with max_batch=4 each shard
    owns 2 lanes and half the pool — the engine still admits and
    finishes a workload deeper than one shard's lane count, and the
    shard-residency invariant holds through the churn."""
    reqs = _reqs(n=8, sampled=False, new=4)
    eng, out = _mesh_serve(tiny_gpt, (2, 1), reqs)
    assert len(out) == 8
    assert all(v.status == "finished" for v in out.values())
    eng.check_allocator_integrity()


def test_disagg_fleet_on_batch_sharded_engines(tiny_gpt):
    """The two tentpoles composed: a disaggregated fleet whose every
    replica runs a (2, 1) batch-sharded engine is token-identical to
    the colocated (1, 1) single-replica fleet."""
    model, params = tiny_gpt
    kw = dict(DISAGG_KW)

    def fleet_for(mesh, n, roles):
        return FleetRouter(
            model, params, EngineConfig(mesh_shape=mesh, **kw),
            FleetConfig(num_replicas=n, replica_roles=roles),
            clock=CONST_CLOCK)

    co = _run(fleet_for((1, 1), 1, None), _reqs())
    dis_fleet = fleet_for((2, 1), 2, ("prefill", "decode"))
    dis = _run(dis_fleet, _reqs())
    assert _resdict(dis) == _resdict(co)
    st = dis_fleet.stats()
    assert st["num_handoffs"] > 0
    assert st["num_lost_requests"] == 0
    for rep in dis_fleet.replicas:
        if rep.alive and rep.engine is not None:
            rep.engine.check_allocator_integrity()
