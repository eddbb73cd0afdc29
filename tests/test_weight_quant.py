"""Quantized weight storage certification (tier-1, CPU): the ISSUE 19
layer (docs/serving.md "Quantized weight storage").

The quantization transform (per-output-channel scales, deterministic
bytes, the byte shrink, idempotency); the fused Pallas dequant-GEMM
certified against the XLA dequantize-then-dot reference in
interpret mode (bit-identical at the tiled and single-tile matrix
shapes, a few float32 ulp at the decode row); quantized logits at tight tolerance to fp; engine greedy
decode token-identical across ``weight_quantization`` on/off with
speculation on/off; the restore-fingerprint refusal across mismatched
modes; the process-replica params-checksum handshake covering the
quantized representation; scale sharding on the ``model`` axis (the
(1, 1) bit-identity + cross-mesh token-identity matrix, pinned compile
counts, the hlo_audit collective contract); the env-flag gate at a
sharded model axis; the labeled quantization-mode gauges; and the
``dequant_gemm`` recorder event surfaced by ``tools/trace_summary.py``.
"""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.models import GPTConfig, GPTLMHeadModel
from apex_tpu.models.gpt import (
    WEIGHT_QUANT_MODES,
    fp8_weight_dtype,
    gpt_param_bytes,
    gpt_param_pspec,
    quantize_dense_kernel,
    quantize_gpt_params,
    quantize_gpt_model,
)
from apex_tpu.observability import QUANT_MODE_CODES, Observability
from apex_tpu.ops import dequant_gemm as dg
from apex_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    ProcessReplica,
    Request,
    SamplingParams,
)
from apex_tpu.serving import mesh as mesh_lib
from apex_tpu.serving.process_replica import (
    gpt_model_spec,
    params_checksum,
)
from apex_tpu.utils.integrity import IntegrityError

CONST_CLOCK = lambda: 0.0  # noqa: E731 — constant-clock stats compare

QUANT_MODES = ["int8"] + (["fp8"] if fp8_weight_dtype() is not None
                          else [])


@pytest.fixture(scope="module")
def tiny():
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


def _config(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("max_prefill_len", 8)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("seed", 7)
    return EngineConfig(**kw)


def _requests(cfg, n=5, sampled=False, seed=3):
    rr = np.random.RandomState(seed)
    out = []
    for i in range(n):
        sp = (SamplingParams(temperature=0.7, top_k=8, top_p=0.9)
              if sampled and i % 2 else SamplingParams())
        out.append(Request(
            uid=f"r{i}", prompt=list(rr.randint(0, cfg.vocab_size, 6 + i)),
            max_new_tokens=6, sampling=sp))
    return out


def _serve(model, params, ecfg, requests, **kw):
    eng = InferenceEngine(model, params, ecfg, clock=CONST_CLOCK, **kw)
    for r in requests:
        eng.add_request(r)
    return eng, eng.run()


# ---------------------------------------------------------------------------
# the quantization transform
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", QUANT_MODES)
def test_quantize_dense_kernel_shape_dtype_determinism(mode):
    rr = np.random.RandomState(0)
    w = jnp.asarray(rr.randn(16, 12), jnp.float32)
    q1, s1 = quantize_dense_kernel(w, mode)
    q2, s2 = quantize_dense_kernel(w, mode)
    assert q1.shape == (16, 12) and s1.shape == (12,)
    assert s1.dtype == jnp.float32
    assert q1.dtype != jnp.float32
    # deterministic bytes — what lets the checksum handshake cover
    # the quantized representation
    assert np.array_equal(np.asarray(q1), np.asarray(q2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    # round trip lands near the fp kernel: int8 has 2^7 symmetric
    # steps per column; fp8 e4m3's 3-bit mantissa is coarser
    back = np.asarray(q1, np.float32) * np.asarray(s1)[None, :]
    amax = float(np.abs(np.asarray(w)).max())
    bound = amax / (64.0 if mode == "int8" else 8.0)
    assert np.abs(back - np.asarray(w)).max() <= bound


def test_quantize_gpt_params_tree_and_bytes(tiny):
    _, _, params = tiny
    q = quantize_gpt_params(params, "int8")
    blocks = q["params"]["transformer"]["h_0"]
    for module in ("attn_q", "attn_k", "attn_v", "attn_out",
                   "mlp_in", "mlp_out"):
        rec = blocks[module]
        assert rec["kernel"].dtype == jnp.int8
        assert rec["scale"].dtype == jnp.float32
        assert rec["scale"].shape == (rec["kernel"].shape[1],)
        assert rec["bias"].dtype == jnp.float32
    # embeddings / norms pass through untouched
    assert q["params"]["transformer"]["wte"].dtype == jnp.float32
    # the memory win the whole PR exists for: >= 1.8x fewer bytes
    assert gpt_param_bytes(params) / gpt_param_bytes(q) >= 1.8


def test_quantize_gpt_model_idempotent_and_remode_refused(tiny):
    _, model, params = tiny
    qmodel, qparams = quantize_gpt_model(model, params, "int8")
    assert qmodel.cfg.weight_quantization == "int8"
    # same mode on already-quantized storage: identity (re-quantizing
    # int8 bytes would corrupt them)
    m2, p2 = quantize_gpt_model(qmodel, qparams, "int8")
    assert m2 is qmodel and p2 is qparams
    with pytest.raises(ValueError, match="re-quantize"):
        quantize_gpt_model(qmodel, qparams, "fp8")
    with pytest.raises(ValueError, match="weight_quantization"):
        quantize_gpt_model(model, params, "int4")
    # mode=None is the identity
    assert quantize_gpt_model(model, params, None) == (model, params)


def test_scale_leaves_shard_like_their_module(tiny):
    """The PR 11 colocate-scales-with-bytes rule applied to weights:
    a quantized kernel's per-output-channel scales take the SAME
    model-axis placement as the output dim of their kernel —
    column-parallel scales shard, row-parallel scales replicate."""
    _, _, params = tiny
    q = quantize_gpt_params(params, "int8")
    specs = {}
    def visit(path, leaf):
        names = [str(getattr(p, "key", p)) for p in path]
        if names[-1] == "scale":
            specs[tuple(names[-2:])] = gpt_param_pspec(path)
        return leaf
    jax.tree_util.tree_map_with_path(visit, q)
    assert specs[("attn_q", "scale")] == P("model")
    assert specs[("mlp_in", "scale")] == P("model")
    assert specs[("attn_out", "scale")] == P()
    assert specs[("mlp_out", "scale")] == P()


# ---------------------------------------------------------------------------
# the fused Pallas dequant-GEMM: agreement with the XLA reference
# ---------------------------------------------------------------------------

# the one-row tolerance, in units of float32 eps times the column's
# accumulation magnitude sum_k |x_k * w_k| (what one rounding of a
# partial sum can cost): reference and kernel sit 0.9-1.4 units apart
# on the installed XLA, a weight rounded to int8 or fp8 sits ~6,000 /
# ~25,000 units away
_ONE_ROW_ULPS = 4.0


@pytest.mark.parametrize("mode", QUANT_MODES)
@pytest.mark.parametrize("m,k,n", [
    (1, 64, 256),      # decode row, tiled N (2 x 128 lanes)
    (8, 128, 128),     # aligned everything, single tile
    (4, 48, 96),       # unaligned single-tile fallback shape
])
def test_pallas_dequant_gemm_matches_reference(mode, m, k, n):
    """THE kernel cert: the fused kernel agrees with the XLA
    dequantize-then-dot reference (``dequant_matmul_reference``;
    interpret mode on CPU). N-only tiling leaves every output column's
    K-reduction untouched, so the matrix shapes are held BIT for bit.
    The decode (single-row) shape is held to ``_ONE_ROW_ULPS``: XLA:CPU
    lowers a matrix-vector product with another reduction order than a
    matrix-matrix one, so the last bits differ there and neither side
    is the more exact - the contract is agreement with the reference,
    not bit-identity to one CPU lowering. The tolerance is one a
    quantisation error cannot hide under: the unquantised product must
    miss it by two orders of magnitude."""
    rr = np.random.RandomState(7)
    x = jnp.asarray(rr.randn(m, k), jnp.float32)
    w = jnp.asarray(rr.randn(k, n), jnp.float32)
    w_q, scale = quantize_dense_kernel(w, mode)
    ref = np.asarray(dg.dequant_matmul_reference(x, w_q, scale))
    fused = np.asarray(dg.dequant_matmul(x, w_q, scale, use_pallas=True))
    if m > 1:
        assert np.array_equal(ref, fused)
        return
    w_deq = np.asarray(w_q, np.float32) * np.asarray(scale)[None, :]
    tol = (_ONE_ROW_ULPS * np.finfo(np.float32).eps
           * (np.abs(np.asarray(x)) @ np.abs(w_deq)))
    assert np.all(np.abs(fused - ref) <= tol)
    unquantised = np.asarray(x) @ np.asarray(w)
    assert np.median(np.abs(unquantised - ref) / tol) > 100.0


def test_dequant_matmul_default_is_reference(monkeypatch):
    """Flag off -> the universal XLA fallback, byte-for-byte."""
    monkeypatch.delenv(dg._ENV_FLAG, raising=False)
    assert not dg.dequant_gemm_wanted()
    monkeypatch.setenv(dg._ENV_FLAG, "1")
    assert dg.dequant_gemm_wanted()
    assert not dg.dequant_gemm_wanted(use_pallas=False)
    rr = np.random.RandomState(1)
    x = jnp.asarray(rr.randn(2, 3, 32), jnp.float32)   # leading dims fold
    w_q, scale = quantize_dense_kernel(
        jnp.asarray(rr.randn(32, 64), jnp.float32), "int8")
    out = dg.dequant_matmul(x, w_q, scale, use_pallas=False)
    ref = dg.dequant_matmul_reference(
        x.reshape(-1, 32), w_q, scale).reshape(2, 3, 64)
    assert out.shape == (2, 3, 64)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# quantized logits + engine decode identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", QUANT_MODES)
def test_quantized_logits_close_to_fp(tiny, mode):
    cfg, model, params = tiny
    qmodel, qparams = quantize_gpt_model(model, params, mode)
    tokens = jnp.asarray(
        np.random.RandomState(2).randint(0, cfg.vocab_size, (2, 12)))
    fp = model.apply(params, tokens, deterministic=True)
    q = qmodel.apply(qparams, tokens, deterministic=True)
    assert q.shape == fp.shape
    np.testing.assert_allclose(np.asarray(q), np.asarray(fp),
                               rtol=0.15, atol=0.15)


@pytest.mark.parametrize("spec", [0, 2])
@pytest.mark.parametrize("mode", QUANT_MODES)
def test_engine_greedy_token_identity_across_modes(tiny, mode, spec):
    """Greedy decode is argmax over logits whose quantization error is
    far below the argmax margins on the test seeds: the quantized
    engine must emit the EXACT fp token streams, speculation on or
    off — and the sampled lanes must run to completion under the same
    per-lane keyed draws."""
    cfg, model, params = tiny
    reqs = _requests(cfg, sampled=True)
    _, fp_out = _serve(model, params, _config(spec_tokens=spec), reqs)
    qeng, q_out = _serve(model, params,
                         _config(spec_tokens=spec,
                                 weight_quantization=mode), reqs)
    greedy = [r.uid for r in reqs
              if r.sampling.temperature == 0.0]
    assert greedy, "matrix needs greedy lanes"
    for uid in greedy:
        assert q_out[uid] == fp_out[uid], uid
    assert set(q_out) == set(fp_out)          # sampled lanes finished
    st = qeng.stats()
    assert st["weight_quantization"] == mode
    assert st["kv_quantization"] is None


def test_engine_rejects_unknown_mode():
    with pytest.raises(ValueError, match="weight_quantization"):
        _config(weight_quantization="int4")


def test_fingerprint_refuses_mismatched_mode(tiny):
    """IDENTITY: quantized storage is a different numerical program,
    so a snapshot taken under one mode must not restore into an
    engine running another."""
    _, model, params = tiny
    fp_eng = InferenceEngine(model, params, _config())
    snap = fp_eng.snapshot()
    q_eng = InferenceEngine(model, params,
                            _config(weight_quantization="int8"))
    with pytest.raises(ValueError, match="config mismatch"):
        q_eng.restore(snap)
    # matched mode round-trips
    q2 = InferenceEngine(model, params,
                         _config(weight_quantization="int8"))
    q2.restore(q_eng.snapshot())


# ---------------------------------------------------------------------------
# mesh matrix: scale sharding under the model axis
# ---------------------------------------------------------------------------

def test_quant_mesh11_bit_identity(tiny, monkeypatch):
    """The (1, 1) mesh engine with quantized weights reproduces the
    meshless quantized engine bit for bit (a 1-partition SPMD program
    is the unpartitioned program — scales included)."""
    cfg, model, params = tiny
    reqs = _requests(cfg)
    ecfg = _config(weight_quantization="int8")
    mesh_eng, mesh_out = _serve(model, params, ecfg, reqs)
    monkeypatch.setattr(mesh_lib, "shard_params",
                        lambda mesh, params, pspec_fn=None: params)
    monkeypatch.setattr(mesh_lib, "shard_cache", lambda mesh, cache: cache)
    monkeypatch.setattr(mesh_lib, "program_out_shardings",
                        lambda mesh, cache: None)
    plain_eng, plain_out = _serve(model, params, ecfg, reqs)
    assert mesh_out == plain_out
    assert mesh_eng.stats() == plain_eng.stats()


def test_quant_cross_mesh_token_identity_and_contract(tiny):
    """(1, 1) / (2, 1) / (1, 2) with int8 weights: identical token
    streams, compile counts pinned at one per program, and the
    collective contract holding with the sharded scale leaves in the
    weights (zero collectives at a 1-sized model axis; audited
    all-reduce-only traffic once heads split)."""
    cfg, model, params = tiny
    reqs = _requests(cfg, n=4)
    baseline = None
    for shape in ((1, 1), (2, 1), (1, 2)):
        eng, out = _serve(model, params,
                          _config(mesh_shape=shape,
                                  weight_quantization="int8"), reqs)
        if baseline is None:
            baseline = out
        else:
            assert out == baseline, f"mesh {shape} diverged"
        s = eng.stats()
        assert s["prefill_compilations"] == 1, s
        assert s["decode_compilations"] == 1, s
        audited = eng.audit_collectives()   # raises on violation
        if shape[1] == 1:
            assert all(v["total"]["ops"] == 0 for v in audited.values())


def test_dequant_flag_rejected_on_sharded_model_axis(tiny, monkeypatch):
    _, model, params = tiny
    monkeypatch.setenv(dg._ENV_FLAG, "1")
    with pytest.raises(ValueError, match="APEX_DEQUANT_GEMM_PALLAS"):
        InferenceEngine(model, params,
                        _config(mesh_shape=(1, 2),
                                weight_quantization="int8"))
    # a 1-sized model axis is single-device: the flag stays legal
    InferenceEngine(model, params,
                    _config(weight_quantization="int8"))


# ---------------------------------------------------------------------------
# process-replica handshake: the checksum covers the quantized bytes
# ---------------------------------------------------------------------------

def test_params_checksum_covers_quantized_representation(tiny):
    _, _, params = tiny
    base = params_checksum(params)
    q = params_checksum(params, weight_quantization="int8")
    assert base != q
    # deterministic across calls (round-to-nearest, no stochasticity)
    assert q == params_checksum(params, weight_quantization="int8")
    if fp8_weight_dtype() is not None:
        assert q != params_checksum(params, weight_quantization="fp8")


def test_process_replica_weight_quant_handshake(tiny):
    """A child booted with a MATCHING weight_quantization mode passes
    the hello handshake and serves; a parent expectation computed
    under a different mode is refused at hello — the mismatched-mode
    boot can never serve different-numerics logits behind an
    "equal weights" handshake."""
    cfg, _, params = tiny
    ecfg = _config(max_batch=2, weight_quantization="int8")
    good = params_checksum(params, weight_quantization="int8")
    rep = ProcessReplica(ecfg, gpt_model_spec(cfg),
                         expect_params_checksum=good)
    try:
        rep.add_request(Request(uid="q0", prompt=[1, 2, 3],
                                max_new_tokens=3))
        out, n = {}, 0
        while rep.has_work and n < 60:
            rep.step()
            out.update(rep.pop_results())
            n += 1
        out.update(rep.pop_results())
        assert out["q0"].status == "finished"
    finally:
        rep.close()
    # fp expectation vs int8 child: refused at hello
    with pytest.raises(IntegrityError, match="checksum"):
        ProcessReplica(ecfg, gpt_model_spec(cfg),
                       expect_params_checksum=params_checksum(params))


# ---------------------------------------------------------------------------
# observability: labeled mode gauges + the recorder event
# ---------------------------------------------------------------------------

def test_quant_mode_gauges_and_recorder_event(tiny):
    cfg, model, params = tiny
    obs = Observability(clock=CONST_CLOCK)
    eng, _ = _serve(model, params,
                    _config(weight_quantization="int8",
                            kv_quantization="int8"),
                    _requests(cfg, n=2), obs=obs)
    expo = obs.metrics.exposition()
    assert 'serving_quantization_mode{kind="kv"} 1' in expo
    assert 'serving_quantization_mode{kind="weight"} 1' in expo
    # one family header for the two labeled members
    assert expo.count("# TYPE serving_quantization_mode gauge") == 1
    assert QUANT_MODE_CODES[None] == 0.0
    evs = [e for e in obs.recorder.dump()["events"]
           if e["kind"] == "dequant_gemm"]
    assert len(evs) == 1
    e = evs[0]
    assert e["mode"] == "int8"
    assert e["fp_bytes"] > e["quant_bytes"] > 0
    assert e["fp_bytes"] / e["quant_bytes"] >= 1.8


def _load_trace_summary():
    path = (Path(__file__).resolve().parents[1] / "tools"
            / "trace_summary.py")
    spec = importlib.util.spec_from_file_location("_trace_summary_wq",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_summary_reports_weight_quant_line(tiny):
    ts = _load_trace_summary()
    cfg, model, params = tiny
    obs = Observability(clock=CONST_CLOCK)
    _serve(model, params, _config(weight_quantization="int8"),
           _requests(cfg, n=2), obs=obs)
    report = ts.summarize(obs.dump())
    assert "weight quantization: mode=int8" in report
    assert "x smaller" in report


def test_off_mode_gauges_zero_and_no_event(tiny):
    cfg, model, params = tiny
    obs = Observability(clock=CONST_CLOCK)
    _serve(model, params, _config(), _requests(cfg, n=2), obs=obs)
    expo = obs.metrics.exposition()
    assert 'serving_quantization_mode{kind="kv"} 0' in expo
    assert 'serving_quantization_mode{kind="weight"} 0' in expo
    assert not [e for e in obs.recorder.dump()["events"]
                if e["kind"] == "dequant_gemm"]
