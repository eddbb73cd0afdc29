"""Memory scale-up tests (tier-1, CPU): quantized KV block storage
(int8/fp8 + per-row scales), the host-RAM spill tier for the prefix
cache, and the fused Pallas paged-read kernel — docs/serving.md
"memory tiers".

The certification layers:
- fp path untouched: quantization off + Pallas off is the PR 10
  engine, bit for bit (the existing serving/speculative/fault suites
  enforce that; here we pin the structural facts they rely on).
- quantized path: tolerance-certified against the fp path at the
  logits level, and DETERMINISTIC in itself — cross-K, preemption/
  resume, and snapshot/restore bit-identity all hold within a storage
  mode (position-keyed stochastic rounding).
- spill tier: a re-admitted block is token-identical to recompute,
  store contents stay disjoint from the device index, and the byte
  bound holds (check_integrity cross-checks both).
- Pallas read kernel: bit-identical to the XLA chain on the fp path
  (decode C == 1 included), tolerance-certified on the quantized path,
  in interpret mode.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTConfig, GPTLMHeadModel
from apex_tpu.observability import Observability
from apex_tpu.ops.flash_attention import (
    FILL as _ATTN_FILL,
    paged_prefill_attention,
)
from apex_tpu.ops.multi_tensor import stochastic_round
from apex_tpu.ops.paged_attention_pallas import (
    FILL as _PALLAS_FILL,
    pallas_paged_read_wanted,
)
from apex_tpu.serving import (
    EngineConfig,
    HostSpillStore,
    InferenceEngine,
    KVCache,
    Request,
    SamplingParams,
    TenantQuota,
    TenantThrottledError,
)
from apex_tpu.serving.kv_cache import (
    BlockAllocator,
    copy_block,
    defragment,
    device_block_table,
    fp8_kv_dtype,
    kv_block_bytes,
    quantize_kv_rows,
    write_kv,
)

QUANT_MODES = ["int8"] + (["fp8"] if fp8_kv_dtype() is not None else [])


def _tiny_model():
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


@pytest.fixture(scope="module")
def tiny():
    return _tiny_model()


def _requests(cfg, n=3, plen=12, new=6, sampled=False, seed=7,
              prefix=None, uid="r"):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        prompt = list(prefix or []) + list(
            rng.randint(0, cfg.vocab_size, plen))
        out.append(Request(
            uid=f"{uid}{i}", prompt=prompt, max_new_tokens=new,
            sampling=(SamplingParams(temperature=1.0, top_k=40)
                      if sampled else SamplingParams())))
    return out


def _serve(tiny, ecfg, reqs):
    cfg, model, params = tiny
    eng = InferenceEngine(model, params, ecfg)
    for r in reqs:
        eng.add_request(dataclasses.replace(r))
    return eng, eng.run()


BASE = dict(max_batch=4, block_size=8, num_blocks=64,
            max_prefill_len=16, max_seq_len=48)


# ---------------------------------------------------------------------------
# quantization primitives
# ---------------------------------------------------------------------------

def test_stochastic_round_integer_targets_unbiased_and_clamped():
    x = jnp.asarray([0.3, -0.7, 126.9, -250.0, 300.0, 0.0])
    acc = np.zeros(len(x))
    n = 400
    for i in range(n):
        r = stochastic_round(x, jnp.int8, jax.random.PRNGKey(i))
        assert r.dtype == jnp.int8
        acc += np.asarray(r, np.float64)
    mean = acc / n
    # unbiased within the clamp range; clamped symmetric at +/-127
    assert abs(mean[0] - 0.3) < 0.1 and abs(mean[1] + 0.7) < 0.1
    assert 126.0 <= mean[2] <= 127.0
    assert mean[3] == -127.0 and mean[4] == 127.0 and mean[5] == 0.0
    # non-finite rounds to 0 for integer targets
    r = stochastic_round(jnp.asarray([jnp.inf, jnp.nan]), jnp.int8,
                         jax.random.PRNGKey(0))
    assert np.asarray(r).tolist() == [0, 0]


@pytest.mark.parametrize("mode", QUANT_MODES)
def test_quantize_kv_rows_roundtrip_bounded_and_deterministic(mode):
    vals = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 3, 8)) * 3.0
    pos = jnp.tile(jnp.arange(6)[None], (2, 1))
    q1, s1 = quantize_kv_rows(vals, pos, mode)
    q2, s2 = quantize_kv_rows(vals, pos, mode)
    # deterministic: position-keyed rounding, no ambient randomness
    assert jnp.array_equal(q1, q2) and jnp.array_equal(s1, s2)
    deq = q1.astype(jnp.float32) * s1[..., None]
    err = jnp.abs(deq - vals.astype(jnp.float32))
    if mode == "int8":
        # absolute quantum: one int8 step = the row's scale
        assert bool(jnp.all(err <= s1[..., None] + 1e-7))
    else:
        # fp8 e4m3 keeps RELATIVE precision (3 mantissa bits, <= 2^-3
        # rounding error) down to the subnormal floor (one scale unit)
        bound = (jnp.abs(vals.astype(jnp.float32)) * 0.125
                 + s1[..., None] + 1e-7)
        assert bool(jnp.all(err <= bound))
    # an all-zero row stores scale 0 and dequantizes to exact zeros
    zq, zs = quantize_kv_rows(jnp.zeros((1, 2, 2, 4)),
                              jnp.zeros((1, 2), jnp.int32), mode)
    assert float(jnp.max(jnp.abs(zq.astype(jnp.float32)))) == 0.0
    assert float(jnp.max(jnp.abs(zs))) == 0.0


def test_quantize_same_position_same_rounding_different_elsewhere():
    """The rounding stream is a function of the ABSOLUTE position: the
    same row at the same position always rounds identically (the
    resume-determinism premise); a different position may not."""
    vals = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 2, 16))
    q_a, _ = quantize_kv_rows(vals, jnp.asarray([[5]], jnp.int32), "int8")
    q_b, _ = quantize_kv_rows(vals, jnp.asarray([[5]], jnp.int32), "int8")
    q_c, _ = quantize_kv_rows(vals, jnp.asarray([[6]], jnp.int32), "int8")
    assert jnp.array_equal(q_a, q_b)
    assert not jnp.array_equal(q_a, q_c)   # fresh stream per position
    # distinct streams (write_kv tags each (layer, K/V) pair) draw
    # independent noise at the SAME position — correlated rounding
    # would compound one-directionally through the layers
    q_d, _ = quantize_kv_rows(vals, jnp.asarray([[5]], jnp.int32),
                              "int8", stream=1)
    assert not jnp.array_equal(q_a, q_d)


def test_write_kv_fp_path_is_plain_paged_write():
    """Quantization off: write_kv must produce the exact bytes the two
    paged_write calls produced (the fp bit-identity premise)."""
    from apex_tpu.serving.kv_cache import paged_write

    cache = KVCache.create(2, 8, 4, 2, 8, dtype=jnp.float32)
    assert cache.quantization is None and cache.k_scale is None
    tbl = device_block_table(np.array([[0, 1, -1]], np.int32), 8)
    pos = jnp.arange(6)[None]
    k = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 2, 8))
    valid = jnp.ones((1, 6), bool)
    got = write_kv(cache, 1, tbl, pos, k, v, valid)
    want_k = paged_write(cache.k, 1, tbl, pos, k, valid)
    want_v = paged_write(cache.v, 1, tbl, pos, v, valid)
    assert jnp.array_equal(got.k, want_k)
    assert jnp.array_equal(got.v, want_v)
    assert got.k_scale is None and got.v_scale is None


@pytest.mark.parametrize("mode", QUANT_MODES)
def test_copy_block_and_defragment_move_scales(mode):
    """The CoW copy and the defrag permutation must carry a block's
    scales with its payload — a quantized block whose scales stay
    behind dequantizes the right bytes with the wrong scales."""
    cache = KVCache.create(2, 6, 4, 2, 8, quantization=mode)
    tbl = device_block_table(np.array([[4, 2, -1]], np.int32), 6)
    pos = jnp.arange(8)[None]
    k = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 8)) * 2.0
    v = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 8)) * 2.0
    cache = write_kv(cache, 0, tbl, pos, k, v, jnp.ones((1, 8), bool))

    def deq(c, b):
        return (c.k[0, b].astype(jnp.float32)
                * c.k_scale[0, b][..., None])

    src_vals = deq(cache, 4)
    copied = copy_block(cache, 4, 1)
    assert jnp.array_equal(deq(copied, 1), src_vals)
    assert jnp.array_equal(copied.k_scale[:, 1], cache.k_scale[:, 4])

    # defragment: blocks {4, 2} compact to {0, 1}; dequantized contents
    # must survive the permutation (scales moved with payload)
    alloc = BlockAllocator(6)
    ids = alloc.alloc(5)        # 0..4
    alloc.free([i for i in ids if i not in (4, 2)])
    tables = np.array([[4, 2, -1]], np.int32)
    new_cache, new_tables = defragment(cache, alloc, tables)
    b_new = int(new_tables[0, 0])
    assert jnp.array_equal(deq(new_cache, b_new), src_vals)


def test_kv_block_bytes_quantized_footprint():
    fp = kv_block_bytes(2, 8, 4, 16, dtype=jnp.float32)
    q8 = kv_block_bytes(2, 8, 4, 16, quantization="int8")
    # int8 payload is 1/4 the fp32 bytes; scales add 4B per (tok, head)
    assert q8 < fp / 2
    assert q8 == fp // 4 + 2 * 2 * 8 * 4 * 4


# ---------------------------------------------------------------------------
# the fused Pallas read kernel (interpret mode)
# ---------------------------------------------------------------------------

def _paged_setup(mode, seed=0):
    cache = KVCache.create(1, 8, 4, 2, 8, quantization=mode)
    tbl = jnp.asarray(np.array([[0, 1, 6, 8], [3, 2, 8, 8]], np.int32))
    tbl = jnp.where(tbl >= 0, tbl, 8)
    pos = jnp.tile(jnp.arange(10)[None], (2, 1))
    k = jax.random.normal(jax.random.PRNGKey(seed), (2, 10, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 10, 2, 8))
    valid = pos < jnp.asarray([[10], [7]])
    cache = write_kv(cache, 0, tbl, pos, k, v, valid)
    scales = ((None, None) if cache.k_scale is None
              else (cache.k_scale[0], cache.v_scale[0]))
    return cache, tbl, scales


def test_pallas_fill_matches_flash_attention_fill():
    assert _PALLAS_FILL == _ATTN_FILL


@pytest.mark.parametrize("mode", [None] + QUANT_MODES)
@pytest.mark.parametrize("chunk", [1, 3, 5])
def test_pallas_read_chain_equivalence_matrix(mode, chunk):
    """The Pallas-vs-XLA equivalence matrix (interpret mode): decode
    (C == 1, q_positions None), prefill-chunk, and verify-style reads,
    fp and quantized. fp is BIT-identical; quantized is certified to
    tight tolerance (and is observed bitwise on this backend)."""
    cache, tbl, (ks, vs) = _paged_setup(mode)
    ctx = jnp.asarray([10, 7], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(2), (2, chunk, 2, 8))
    qpos = (None if chunk == 1 else
            jnp.tile(jnp.arange(10 - chunk, 10)[None], (2, 1)))

    def call(use_pallas):
        return paged_prefill_attention(
            q, cache.k[0], cache.v[0], tbl, qpos, ctx, 0.35,
            k_scales=ks, v_scales=vs, use_pallas=use_pallas)

    a, b = call(False), call(True)
    if mode is None:
        assert jnp.array_equal(a, b), (
            f"fp Pallas read must be bit-identical (C={chunk})")
    else:
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0, atol=1e-6)

    # jitted (the engine's calling convention) — same contract
    fj = jax.jit(lambda q: paged_prefill_attention(
        q, cache.k[0], cache.v[0], tbl, qpos, ctx, 0.35,
        k_scales=ks, v_scales=vs, use_pallas=True))
    if mode is None:
        assert jnp.array_equal(a, fj(q))


def test_pallas_flag_env_and_kwarg(monkeypatch):
    monkeypatch.delenv("APEX_PAGED_ATTENTION_PALLAS", raising=False)
    assert pallas_paged_read_wanted(None) is False
    assert pallas_paged_read_wanted(True) is True
    monkeypatch.setenv("APEX_PAGED_ATTENTION_PALLAS", "1")
    assert pallas_paged_read_wanted(None) is True
    assert pallas_paged_read_wanted(False) is False
    monkeypatch.setenv("APEX_PAGED_ATTENTION_PALLAS", "0")
    assert pallas_paged_read_wanted(None) is False


@pytest.mark.parametrize("sampled", [False, True])
def test_pallas_engine_end_to_end_bit_identical(tiny, monkeypatch,
                                                sampled):
    """The whole engine (prefill + decode + prefix caching) with the
    fused read kernel produces the identical token streams — the env
    flag is read at trace time, so it must be set before the engine
    compiles its programs."""
    cfg, _, _ = tiny
    reqs = _requests(cfg, n=3, sampled=sampled)
    ecfg = EngineConfig(**BASE, enable_prefix_caching=True)
    monkeypatch.delenv("APEX_PAGED_ATTENTION_PALLAS", raising=False)
    _, base_out = _serve(tiny, ecfg, reqs)
    monkeypatch.setenv("APEX_PAGED_ATTENTION_PALLAS", "1")
    _, pallas_out = _serve(tiny, ecfg, reqs)
    assert pallas_out == base_out


# ---------------------------------------------------------------------------
# quantized engine: tolerance + determinism matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", QUANT_MODES)
def test_quantized_prefill_logits_tolerance(tiny, mode):
    """End-to-end forward tolerance: the same prompt prefilled through
    a quantized cache must produce last-position logits close to the
    fp-cache forward — the quantization error budget surfaced at the
    only place the engine consumes the cache."""
    cfg, model, params = tiny

    def logits_with(quantization):
        cache = KVCache.create(
            cfg.num_layers, 16, 8, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads, dtype=jnp.float32,
            quantization=quantization)
        ids = jnp.asarray(
            np.random.RandomState(3).randint(0, cfg.vocab_size, (1, 16)))
        tbl = device_block_table(np.array([[0, 1, -1]], np.int32), 16)
        out, _ = model.apply(
            params, ids, deterministic=True, kv_cache=cache,
            block_tables=tbl,
            cache_positions=jnp.arange(16)[None],
            seq_lens=jnp.asarray([16], jnp.int32),
            write_start=jnp.asarray([0], jnp.int32))
        return out[0, -1]

    fp = logits_with(None)
    quant = logits_with(mode)
    # loose enough for int8 end-to-end through every layer, tight
    # enough that a scale/payload mismatch (wrong block, stale scale)
    # fails by orders of magnitude
    np.testing.assert_allclose(np.asarray(quant), np.asarray(fp),
                               rtol=0.15, atol=0.15)


@pytest.mark.parametrize("sampled", [False, True])
def test_quantized_outputs_identical_across_decode_steps(tiny, sampled):
    cfg, _, _ = tiny
    reqs = _requests(cfg, sampled=sampled)
    outs = [_serve(tiny, EngineConfig(**BASE, kv_quantization="int8",
                                      decode_steps=k), reqs)[1]
            for k in (1, 4)]
    assert outs[0] == outs[1]


def test_quantized_preemption_resume_deterministic(tiny):
    """Tight pool forces preemption + cached resume; the re-prefill
    re-quantizes the history bit-identically (position-keyed
    rounding), so outputs equal the roomy-pool run's."""
    cfg, _, _ = tiny
    reqs = _requests(cfg, n=4, plen=12, new=8, sampled=True)
    roomy = EngineConfig(**BASE, kv_quantization="int8",
                         enable_prefix_caching=True)
    tight = dataclasses.replace(roomy, num_blocks=7, max_batch=3)
    eng_r, out_r = _serve(tiny, roomy, reqs)
    eng_t, out_t = _serve(tiny, tight, reqs)
    assert eng_t.stats()["num_preemptions"] > 0
    assert out_t == out_r


@pytest.mark.parametrize("spec", [0, 4])
def test_quantized_snapshot_restore_bit_identical(tiny, spec):
    cfg, model, params = tiny
    ecfg = EngineConfig(**BASE, kv_quantization="int8",
                        spec_tokens=spec)
    reqs = _requests(cfg, n=3, plen=10, new=8, sampled=True)
    _, uninterrupted = _serve(tiny, ecfg, reqs)

    eng = InferenceEngine(model, params, ecfg)
    for r in reqs:
        eng.add_request(dataclasses.replace(r))
    for _ in range(3):
        eng.step()
    snap = eng.snapshot()
    fresh = InferenceEngine(model, params, ecfg)
    fresh.restore(snap)
    out = dict(snap["finished"])
    out.update(fresh.run())
    assert out == uninterrupted


def test_quantized_greedy_speculative_matches_plain(tiny):
    """Greedy spec-vs-not bit-identity is structural (argmax equality)
    and survives quantization: the verify forward reads the same
    quantized cache the scan would."""
    cfg, _, _ = tiny
    reqs = _requests(cfg, n=3, plen=12, new=8, sampled=False)
    _, plain = _serve(tiny, EngineConfig(**BASE, kv_quantization="int8"),
                      reqs)
    _, spec = _serve(tiny, EngineConfig(**BASE, kv_quantization="int8",
                                        spec_tokens=4), reqs)
    assert spec == plain


def test_quantized_block_charges_reduced_footprint(tiny):
    """The tenant ledger denominates in full-precision block units: a
    request the fp ledger throttles at the door fits under int8 (its
    worst case charges block_weight < 1 per block)."""
    cfg, model, params = tiny
    quotas = {"t": TenantQuota(max_resident_blocks=2)}
    req = Request(uid="q0", prompt=list(range(1, 17)), max_new_tokens=8,
                  tenant="t")   # 24 tokens = 3 blocks worst case
    fp_eng = InferenceEngine(model, params, EngineConfig(
        **BASE, tenant_quotas=quotas))
    with pytest.raises(TenantThrottledError):
        fp_eng.add_request(dataclasses.replace(req))
    q_eng = InferenceEngine(model, params, EngineConfig(
        **BASE, kv_quantization="int8", tenant_quotas=quotas))
    assert q_eng._block_weight < 0.7
    q_eng.add_request(dataclasses.replace(req))
    out = q_eng.run()
    assert len(out["q0"]) == 8
    q_eng.check_allocator_integrity()


def test_kv_quantization_config_validation(tiny):
    with pytest.raises(ValueError, match="kv_quantization"):
        EngineConfig(**BASE, kv_quantization="int4")
    # fp engine keeps a scale-less pool and zeroed spill stats
    cfg, model, params = tiny
    eng = InferenceEngine(model, params, EngineConfig(**BASE))
    assert eng.cache.k_scale is None
    st = eng.stats()
    assert st["spill_blocks"] == 0 and st["spill_hit_rate"] == 0.0


def test_int8_pool_holds_more_residents_at_one_byte_budget(tiny):
    """What quantized blocks are for: at ONE device byte budget (ten
    float32 blocks' worth) the int8-with-scales pool holds more blocks,
    and the same seeded bursty trace - 32-token requests, four float
    blocks each - reaches at least 1.5x the peak concurrent residents
    on it. Both pools decode every token of the trace."""
    from _traffic import drive, poisson_burst_trace

    cfg, model, params = tiny
    bs, hd = 8, cfg.hidden_size // cfg.num_heads
    fp_block = kv_block_bytes(cfg.num_layers, bs, cfg.num_heads, hd,
                              dtype=jnp.float32)
    q_block = kv_block_bytes(cfg.num_layers, bs, cfg.num_heads, hd,
                             quantization="int8")
    budget = 10 * fp_block
    ticks = 6

    def peak_residents(quant, num_blocks):
        eng = InferenceEngine(model, params, EngineConfig(
            max_batch=8, block_size=bs, num_blocks=num_blocks,
            max_prefill_len=16, max_seq_len=32, decode_steps=4,
            kv_dtype=jnp.float32, kv_quantization=quant))
        prompts = np.random.RandomState(1)
        trace = poisson_burst_trace(
            np.random.RandomState(2), ticks, 1.5,
            lambda tick, k: Request(
                uid=f"m{k}", max_new_tokens=16,
                prompt=list(prompts.randint(0, cfg.vocab_size, 16))),
            burst_start=ticks // 3, burst_end=2 * ticks // 3,
            burst_factor=2)
        peak = [0]

        def watch(tick, seen):
            peak[0] = max(peak[0], int(eng.stats()["active_slots"]))

        seen = drive(eng, trace, after_step=watch)
        assert len(seen.accepted) == len(trace) and seen.stalls == 0
        return peak[0], int(eng.stats()["num_tokens_decoded"])

    fp_peak, fp_tokens = peak_residents(None, budget // fp_block)
    q_peak, q_tokens = peak_residents("int8", budget // q_block)
    assert budget // q_block > budget // fp_block
    assert q_peak >= 1.5 * fp_peak > 0, (q_peak, fp_peak)
    assert q_tokens == fp_tokens > 0


# ---------------------------------------------------------------------------
# the host-RAM spill tier
# ---------------------------------------------------------------------------

def _spill_cfg(**kw):
    base = dict(max_batch=2, block_size=8, num_blocks=8,
                max_prefill_len=16, max_seq_len=32,
                enable_prefix_caching=True, spill_max_bytes=10_000_000)
    base.update(kw)
    return EngineConfig(**base)


def _distinct_prompts(cfg, n=4, plen=16, seed=3):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(0, cfg.vocab_size, plen)) for _ in range(n)]


def _serve_prompts(eng, prompts, tag, new=4):
    for i, p in enumerate(prompts):
        eng.add_request(Request(uid=f"{tag}{i}", prompt=p,
                                max_new_tokens=new))
    return eng.run()


@pytest.mark.parametrize("quant", [None, "int8"])
def test_spill_readmit_token_identical_vs_recompute(tiny, quant):
    """The core spill cert: flush the prefix cache into the host tier,
    re-serve the same prompts, and the upload-re-admitted run must be
    TOKEN-IDENTICAL to the recompute run of a spill-less engine."""
    cfg, model, params = tiny
    prompts = _distinct_prompts(cfg)

    def serve_twice(spill_bytes):
        kw = dict(kv_quantization=quant)
        if spill_bytes is None:
            base = dict(max_batch=2, block_size=8, num_blocks=8,
                        max_prefill_len=16, max_seq_len=32,
                        enable_prefix_caching=True, **kw)
            eng = InferenceEngine(model, params, EngineConfig(**base))
        else:
            eng = InferenceEngine(model, params,
                                  _spill_cfg(spill_max_bytes=spill_bytes,
                                             **kw))
        o1 = _serve_prompts(eng, prompts, "a")
        eng.allocator.flush_evictable()   # rung-2's call: all -> spill
        o2 = _serve_prompts(eng, prompts, "b")
        return eng, o1, o2

    spill_eng, s1, s2 = serve_twice(10_000_000)
    none_eng, n1, n2 = serve_twice(None)
    assert (s1, s2) == (n1, n2)
    st = spill_eng.stats()
    assert st["num_blocks_spilled"] > 0
    assert st["spill_hits"] > 0 and st["spill_hit_rate"] > 0
    assert none_eng.stats()["spill_hits"] == 0
    spill_eng.check_allocator_integrity()


def test_spill_store_lru_byte_bound():
    store = HostSpillStore(max_bytes=1000)
    blk = {"k": np.zeros((1, 8, 2, 4), np.int8),
           "v": np.zeros((1, 8, 2, 4), np.int8)}     # 128 B
    for i in range(10):
        store.put(f"h{i}", dict(blk))
    assert store.total_bytes <= 1000
    assert len(store) == 7 and store.evictions == 3
    assert "h0" not in store and "h9" in store       # LRU dropped first
    # an entry bigger than the whole bound is refused, counted
    big = {"k": np.zeros((4, 64, 8, 8), np.float32), "v": None}
    assert store.put("huge", big) is False
    assert "huge" not in store
    # pop removes; discard tolerates absence
    assert store.pop("h9") is not None and store.pop("h9") is None
    store.discard("h9")
    with pytest.raises(ValueError):
        HostSpillStore(max_bytes=0)


def test_spill_integrity_cross_check(tiny):
    """check_integrity must reject a hash both device-indexed and
    spilled, and a store over its byte bound — the new tier rides
    engine.check_allocator_integrity()."""
    cfg, model, params = tiny
    eng = InferenceEngine(model, params, _spill_cfg())
    prompts = _distinct_prompts(cfg, n=2)
    _serve_prompts(eng, prompts, "a")
    eng.allocator.flush_evictable()
    _serve_prompts(eng, prompts, "b")
    eng.check_allocator_integrity()     # healthy churn passes
    # violate disjointness: copy a device-indexed hash into the store
    live_hash = next(iter(eng.allocator._hash_to_block))
    eng.spill.put(live_hash, {"k": np.zeros(4, np.int8),
                              "v": np.zeros(4, np.int8)})
    with pytest.raises(ValueError, match="device-indexed and spilled"):
        eng.check_allocator_integrity()
    eng.spill.discard(live_hash)
    eng.check_allocator_integrity()
    # violate the byte bound behind the store's back
    eng.spill.max_bytes = -1
    eng.spill.total_bytes = 5
    with pytest.raises(ValueError, match="over its"):
        eng.check_allocator_integrity()


def test_spill_snapshot_audit_only_and_cross_restore(tiny):
    """Spill state is audit-only: the snapshot carries a 'spill'
    section restore() never reads, the fingerprint excludes the knob,
    and a snapshot from a spill engine restores bit-identically into
    an engine WITHOUT the tier (and vice versa)."""
    cfg, model, params = tiny
    spill_cfg = _spill_cfg()
    plain_cfg = dataclasses.replace(spill_cfg, spill_max_bytes=None)
    reqs = _requests(cfg, n=3, plen=10, new=6, sampled=True, seed=5)

    def interrupted(build_cfg, restore_cfg):
        eng = InferenceEngine(model, params, build_cfg)
        for r in reqs:
            eng.add_request(dataclasses.replace(r))
        for _ in range(3):
            eng.step()
        snap = eng.snapshot()
        if build_cfg.spill_max_bytes is not None:
            assert snap["spill"]["audit_only"] is True
        fresh = InferenceEngine(model, params, restore_cfg)
        fresh.restore(snap)
        out = dict(snap["finished"])
        out.update(fresh.run())
        return out

    _, uninterrupted = _serve(tiny, plain_cfg, reqs)
    assert interrupted(spill_cfg, plain_cfg) == uninterrupted
    assert interrupted(plain_cfg, spill_cfg) == uninterrupted


def test_spill_recorder_events_and_trace_summary(tiny, tmp_path):
    """The flight recorder narrates the tier (spill + spill_upload are
    vocabulary now) and tools/trace_summary.py reports them."""
    import importlib.util
    import json as _json
    import pathlib

    cfg, model, params = tiny
    obs = Observability()
    eng = InferenceEngine(model, params, _spill_cfg(), obs=obs)
    prompts = _distinct_prompts(cfg, n=2)
    _serve_prompts(eng, prompts, "a")
    eng.allocator.flush_evictable()
    _serve_prompts(eng, prompts, "b")
    kinds = {e["kind"] for e in obs.recorder.tail()}
    assert "spill" in kinds and "spill_upload" in kinds

    dump_path = tmp_path / "dump.json"
    with open(dump_path, "w") as f:
        _json.dump(obs.dump(), f)
    spec = importlib.util.spec_from_file_location(
        "_ts", pathlib.Path(__file__).resolve().parents[1]
        / "tools" / "trace_summary.py")
    ts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ts)
    report = ts.summarize_file(str(dump_path))
    assert "spill tier" in report


def test_spill_config_validation():
    with pytest.raises(ValueError, match="enable_prefix_caching"):
        EngineConfig(**BASE, spill_max_bytes=1000)
    with pytest.raises(ValueError, match="spill_max_bytes"):
        EngineConfig(**BASE, enable_prefix_caching=True,
                     spill_max_bytes=0)
