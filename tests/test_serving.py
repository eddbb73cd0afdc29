"""apex_tpu.serving tests (tier-1, CPU): paged KV-cache correctness,
decode parity vs the full-sequence forward, continuous batching with
staggered arrivals/EOS under the two-program compilation contract, and
sampling determinism. (The old tp=2 shard_map decode smoke folded into
the mesh matrix — tests/test_mesh_serving.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTConfig, GPTLMHeadModel
from apex_tpu.serving import (
    BlockAllocator,
    CacheOutOfBlocks,
    EngineConfig,
    InferenceEngine,
    KVCache,
    Request,
    SamplingParams,
    blocks_needed,
    defragment,
    device_block_table,
    gather_kv,
    paged_write,
    sample_tokens,
)


def _tiny_model(**kw):
    kw.setdefault("dropout", 0.0)
    kw.setdefault("remat", False)
    cfg = GPTConfig.tiny(**kw)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


def _ids(B, S, vocab=128, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, vocab, (B, S)))


# ---------------------------------------------------------------------------
# block allocator + paged write/read primitives
# ---------------------------------------------------------------------------

def test_block_allocator_alloc_free_defrag_accounting():
    a = BlockAllocator(8)
    assert a.num_free == 8 and a.num_used == 0
    first = a.alloc(3)
    assert sorted(first) == [0, 1, 2]      # low ids served first
    assert a.num_used == 3
    assert a.utilization == pytest.approx(3 / 8)
    a.free([first[1]])
    assert a.num_free == 6
    with pytest.raises(ValueError, match="double free"):
        a.free([first[0], first[0]])
    with pytest.raises(CacheOutOfBlocks):
        a.alloc(100)
    assert blocks_needed(17, 8) == 3 and blocks_needed(16, 8) == 2


def test_paged_write_and_gather_roundtrip():
    """Tokens written through a (deliberately scrambled) block table must
    come back in position order; invalid positions must write nothing."""
    L, N, bs, H, D = 2, 6, 4, 2, 3
    cache = KVCache.create(L, N, bs, H, D, dtype=jnp.float32)
    B, S = 2, 10   # spans 3 blocks per sequence
    rng = np.random.RandomState(0)
    vals = jnp.asarray(rng.randn(B, S, H, D).astype("f4"))
    tables = np.array([[5, 0, 3, -1], [2, 4, 1, -1]], np.int32)
    dtbl = device_block_table(tables, N)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    seq_lens = jnp.asarray([10, 7], jnp.int32)   # row 1: tail is padding
    valid = pos < seq_lens[:, None]
    k = paged_write(cache.k, 1, dtbl, pos, vals, valid)

    out = gather_kv(k, 1, dtbl)                  # [B, 4*bs, H, D]
    np.testing.assert_array_equal(np.asarray(out[0, :10]),
                                  np.asarray(vals[0]))
    np.testing.assert_array_equal(np.asarray(out[1, :7]),
                                  np.asarray(vals[1, :7]))
    # the padding positions of row 1 were dropped, not written
    np.testing.assert_array_equal(np.asarray(out[1, 7:10]),
                                  np.zeros((3, H, D), np.float32))
    # layer 0 untouched
    assert float(jnp.max(jnp.abs(k[0]))) == 0.0


def test_defragment_compacts_and_preserves_contents():
    L, N, bs, H, D = 1, 16, 4, 2, 2
    cache = KVCache.create(L, N, bs, H, D, dtype=jnp.float32)
    alloc = BlockAllocator(N)
    rng = np.random.RandomState(1)
    tables = np.full((2, 4), -1, np.int32)
    # interleave allocations from two sequences, then free a third to
    # checkerboard the pool
    other = alloc.alloc(2)
    tables[0, :2] = alloc.alloc(2)
    tables[1, :3] = alloc.alloc(3)
    alloc.free(other)
    vals = [jnp.asarray(rng.randn(1, 8, H, D).astype("f4")),
            jnp.asarray(rng.randn(1, 12, H, D).astype("f4"))]
    for b, (n_tok, v) in enumerate([(8, vals[0]), (12, vals[1])]):
        pos = jnp.arange(n_tok, dtype=jnp.int32)[None]
        k = paged_write(cache.k, 0, device_block_table(tables[b:b + 1], N),
                        pos, v, jnp.ones((1, n_tok), bool))
        cache = cache._replace(k=k)

    before = [np.asarray(gather_kv(cache.k, 0,
                                   device_block_table(tables[b:b + 1], N)))
              for b in range(2)]
    cache2, tables2 = defragment(cache, alloc, tables)
    # live blocks now occupy the low indices, free list is the tail
    assert set(tables2[tables2 >= 0].ravel()) == set(range(5))
    assert alloc.num_free == N - 5
    for b in range(2):
        after = np.asarray(gather_kv(
            cache2.k, 0, device_block_table(tables2[b:b + 1], N)))
        np.testing.assert_array_equal(after, before[b])
    # and the pool still allocates from the compacted tail
    assert sorted(alloc.alloc(2)) == [5, 6]


def test_kv_dtype_follows_amp_policy():
    from apex_tpu.amp import _amp_state
    from apex_tpu.serving import default_kv_dtype

    saved = _amp_state._amp_state.handle
    try:
        _amp_state._amp_state.handle = None
        assert default_kv_dtype() == jnp.dtype(jnp.float32)
        assert default_kv_dtype(jnp.bfloat16) == jnp.dtype(jnp.bfloat16)

        import apex_tpu.amp as amp
        from apex_tpu.optimizers import FusedAdam

        params = {"w": jnp.ones((4, 4), jnp.float32)}
        _, _, handle = amp.initialize(params, FusedAdam(), opt_level="O2",
                                      verbosity=0)
        assert default_kv_dtype() == jnp.dtype(jnp.bfloat16)
        # explicit dtype overrides the policy
        assert default_kv_dtype(jnp.float32) == jnp.dtype(jnp.float32)
        cache = KVCache.create(1, 2, 4, 2, 2)
        assert cache.k.dtype == jnp.bfloat16
    finally:
        _amp_state._amp_state.handle = saved


# ---------------------------------------------------------------------------
# decode parity vs the full-sequence forward (acceptance criterion)
# ---------------------------------------------------------------------------

def test_decode_with_paged_cache_matches_full_forward():
    """Prefill + one-token-at-a-time decode through the paged cache must
    reproduce the full-sequence forward's logits to <= 1e-5 (fp32,
    2-layer GPT) — including ragged prompts (per-row padding)."""
    cfg, model, params = _tiny_model()
    B, S, pre = 2, 24, 16
    ids = _ids(B, S)
    ref = model.apply(params, ids)

    N, bs = 32, 8
    cache = KVCache.create(cfg.num_layers, N, bs, cfg.num_heads,
                           cfg.hidden_size // cfg.num_heads,
                           dtype=jnp.float32)
    alloc = BlockAllocator(N)
    tables = np.full((B, 8), -1, np.int32)
    for b in range(B):
        tables[b, :blocks_needed(S, bs)] = alloc.alloc(blocks_needed(S, bs))
    dtbl = device_block_table(tables, N)

    pos = jnp.broadcast_to(jnp.arange(pre, dtype=jnp.int32)[None], (B, pre))
    logits, cache = model.apply(
        params, ids[:, :pre], kv_cache=cache, block_tables=dtbl,
        cache_positions=pos, seq_lens=jnp.full((B,), pre, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref[:, :pre]),
                               atol=1e-5, rtol=0)

    for t in range(pre, S):
        step, cache = model.apply(
            params, ids[:, t:t + 1], kv_cache=cache, block_tables=dtbl,
            cache_positions=jnp.full((B, 1), t, jnp.int32),
            seq_lens=jnp.full((B,), t + 1, jnp.int32))
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   np.asarray(ref[:, t]),
                                   atol=1e-5, rtol=0)


def test_ragged_prefill_masks_padding():
    """A right-padded prefill batch must produce, at each row's true
    positions, the logits of that row's unpadded forward."""
    cfg, model, params = _tiny_model()
    lens = [5, 11]
    P = 16
    ids = _ids(2, P, seed=3)
    N, bs = 16, 4
    cache = KVCache.create(cfg.num_layers, N, bs, cfg.num_heads,
                           cfg.hidden_size // cfg.num_heads,
                           dtype=jnp.float32)
    alloc = BlockAllocator(N)
    tables = np.full((2, 4), -1, np.int32)
    for b, n in enumerate(lens):
        tables[b, :blocks_needed(n, bs)] = alloc.alloc(blocks_needed(n, bs))
    pos = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None], (2, P))
    logits, _ = model.apply(
        params, ids, kv_cache=cache,
        block_tables=device_block_table(tables, N),
        cache_positions=pos, seq_lens=jnp.asarray(lens, jnp.int32))
    for b, n in enumerate(lens):
        solo = model.apply(params, ids[b:b + 1, :n])
        np.testing.assert_allclose(np.asarray(logits[b, :n]),
                                   np.asarray(solo[0]), atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# continuous batching engine (acceptance criterion: 8 staggered requests,
# exactly two jit compilations)
# ---------------------------------------------------------------------------

def _build_engine(seed=0, **cfg_kw):
    cfg, model, params = _tiny_model()
    ecfg = EngineConfig(max_batch=4, block_size=8, num_blocks=64,
                        max_prefill_len=16, max_seq_len=64, seed=seed,
                        **cfg_kw)
    return InferenceEngine(model, params, ecfg)


def _staggered_workload(engine):
    """8 requests: 4 up front, 2 scheduler ticks, 4 late arrivals —
    different prompt lengths, generation budgets, and samplers."""
    rng = np.random.RandomState(7)
    reqs = []
    for i in range(8):
        samp = (SamplingParams() if i % 2 == 0 else
                SamplingParams(temperature=0.7, top_k=10, top_p=0.9))
        reqs.append(Request(uid=f"r{i}",
                            prompt=list(rng.randint(0, 128, 3 + i)),
                            max_new_tokens=2 + (i % 4) * 3,
                            sampling=samp))
    for r in reqs[:4]:
        engine.add_request(r)
    engine.step()
    engine.step()
    for r in reqs[4:]:
        engine.add_request(r)
    out = engine.run()
    return reqs, out


def test_continuous_batching_staggered_two_compilations():
    engine = _build_engine()
    reqs, out = _staggered_workload(engine)
    assert set(out) == {r.uid for r in reqs}
    for r in reqs:
        assert len(out[r.uid]) == r.max_new_tokens
        assert all(0 <= t < 128 for t in out[r.uid])
    stats = engine.stats()
    # THE two-program contract: one prefill shape, one decode shape
    assert stats["prefill_compilations"] == 1
    assert stats["decode_compilations"] == 1
    assert stats["num_prefills"] == 8
    # every slot and every block was handed back
    assert stats["active_slots"] == 0
    assert engine.allocator.num_used == 0


def test_engine_is_deterministic_under_a_fixed_seed():
    _, out1 = _staggered_workload(_build_engine(seed=123))
    _, out2 = _staggered_workload(_build_engine(seed=123))
    assert out1 == out2
    # and the sampled half actually depends on the seed
    _, out3 = _staggered_workload(_build_engine(seed=456))
    sampled = [f"r{i}" for i in range(8) if i % 2 == 1]
    assert any(out1[u] != out3[u] for u in sampled)


def test_engine_eos_evicts_early():
    """A request whose eos_token_id equals the token greedy decoding
    actually produces must stop at that token, well before its
    max_new_tokens budget."""
    prompt = list(np.random.RandomState(3).randint(0, 128, 6))
    pilot = _build_engine()
    pilot.add_request(Request(uid="p", prompt=prompt, max_new_tokens=8))
    first = pilot.run()["p"][0]

    engine = _build_engine()
    engine.add_request(Request(uid="q", prompt=prompt, max_new_tokens=8,
                               eos_token_id=int(first)))
    out = engine.run()["q"]
    assert out == [first]
    assert engine.allocator.num_used == 0


def test_engine_admission_control_and_validation():
    engine = _build_engine()
    # prompts longer than the prefill chunk are admissible now (chunked
    # prefill) — only the total budget is capped
    engine.add_request(Request(uid="long-ok", prompt=list(range(17)),
                               max_new_tokens=2))
    with pytest.raises(ValueError, match="max_seq_len"):
        engine.add_request(Request(uid="huge", prompt=[1] * 60))
    with pytest.raises(ValueError, match="max_seq_len"):
        engine.add_request(Request(uid="deep", prompt=[1] * 8,
                                   max_new_tokens=100))
    with pytest.raises(ValueError, match="empty prompt"):
        engine.add_request(Request(uid="empty", prompt=[]))
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.add_request(Request(uid="zero", prompt=[1],
                                   max_new_tokens=0))
    with pytest.raises(ValueError, match="top_p"):
        engine.add_request(Request(uid="bad", prompt=[1],
                                   sampling=SamplingParams(top_p=0.0)))
    out = engine.run()
    assert set(out) == {"long-ok"}


def test_engine_optimistic_admission_overcommits_and_preempts():
    """Two long-budget requests whose WORST cases together exceed the
    pool are now admitted together on current need (prompt blocks + 1);
    the resulting decode-time exhaustion preempts the youngest lane and
    both still finish with full-length, correct output."""
    cfg, model, params = _tiny_model()
    # pool of 5 blocks; worst case is 8+24=32 tokens -> 4 blocks each,
    # but current need at admission is just 1 prompt block (+1 headroom)
    engine = InferenceEngine(model, params, EngineConfig(
        max_batch=2, block_size=8, num_blocks=5, max_prefill_len=8,
        max_seq_len=32))
    for uid in ("a", "b"):
        engine.add_request(Request(uid=uid, prompt=[1, 2, 3, 4, 5, 6, 7, 8],
                                   max_new_tokens=24))
    engine.step()
    # the old worst-case reservation would have left "b" queued
    assert engine.stats()["active_slots"] == 2
    assert engine.stats()["waiting"] == 0
    out = engine.run()
    assert sorted(out) == ["a", "b"]
    assert all(len(v) == 24 for v in out.values())
    stats = engine.stats()
    assert stats["num_preemptions"] >= 1
    assert stats["prefill_compilations"] == 1
    assert stats["decode_compilations"] == 1
    assert engine.allocator.num_used == 0


def test_exact_fit_request_is_admitted_without_headroom():
    """A request whose whole generation lives inside its prompt's last
    partial block needs NO headroom block: a pool exactly the size of
    blocks_needed(prompt) must serve it (the naive 'prompt blocks + 1'
    admission rule would wrongly raise CacheOutOfBlocks here)."""
    cfg, model, params = _tiny_model()
    engine = InferenceEngine(model, params, EngineConfig(
        max_batch=1, block_size=8, num_blocks=4, max_prefill_len=8,
        max_seq_len=32))
    # 25 + 7 = 32 tokens -> exactly 4 blocks, generation never leaves
    # block 3 (positions 25..31)
    engine.add_request(Request(uid="fit", prompt=[1] * 25,
                               max_new_tokens=7))
    out = engine.run()
    assert len(out["fit"]) == 7
    assert engine.allocator.num_used == 0


def test_preemption_preserves_greedy_outputs():
    """Preemption-under-pressure determinism: the same greedy workload
    served from a pool tight enough to force preemption must emit
    byte-identical tokens to a pool that never preempts (emitted tokens
    are carried across preemption, and the cached re-prefill rebuilds
    the exact same context)."""
    cfg, model, params = _tiny_model()
    rng = np.random.RandomState(11)
    reqs = [Request(uid=f"r{i}", prompt=list(rng.randint(0, 128, 6 + i)),
                    max_new_tokens=20) for i in range(3)]

    def serve(num_blocks):
        engine = InferenceEngine(model, params, EngineConfig(
            max_batch=3, block_size=8, num_blocks=num_blocks,
            max_prefill_len=8, max_seq_len=32))
        for r in reqs:
            engine.add_request(r)
        return engine.run(), engine.stats()

    roomy, roomy_stats = serve(num_blocks=16)
    tight, tight_stats = serve(num_blocks=6)
    assert roomy_stats["num_preemptions"] == 0
    assert tight_stats["num_preemptions"] >= 1
    assert tight == roomy
    assert tight_stats["prefill_compilations"] == 1
    assert tight_stats["decode_compilations"] == 1


def test_block_allocator_refcounts_prefix_index_and_lru_eviction():
    """The prefix-cache contract on the allocator: registered full
    blocks are matchable by hash chain, sharing is refcounted, freed
    registered blocks are retained (cached) until allocation pressure
    evicts them least-recently-used."""
    from apex_tpu.serving import hash_block_tokens

    a = BlockAllocator(4)
    h1 = hash_block_tokens(None, [7] * 8)
    h2 = hash_block_tokens(h1, [9] * 8)
    b = a.alloc(2)
    assert a.register_prefix(h1, b[0]) and a.register_prefix(h2, b[1])
    # a second holder matches the chain and shares by reference
    assert a.match_prefix([h1, h2]) == b
    assert a.refcount(b[0]) == 2 and a.refcount(b[1]) == 2
    # a chain that diverges after the first block matches one block only
    h2x = hash_block_tokens(h1, [1] * 8)
    assert a.match_prefix([h1, h2x]) == [b[0]]
    a.free([b[0]])
    a.free(b)
    a.free(b)   # all references released -> cached, NOT freed
    assert a.num_free == 2 and a.num_cached == 2 and a.num_used == 0
    # matching revives a cached block
    got = a.match_prefix([h1])
    assert got == [b[0]] and a.num_cached == 1 and a.refcount(b[0]) == 1
    a.free(got)  # LRU order is now [b1, b0]: b0 was just revived
    # allocation beyond the free list evicts least-recently-used first
    c = a.alloc(3)
    assert a.num_evictions == 1 and a.num_cached == 1
    assert a.match_prefix([h2]) == []             # h2's block was evicted
    got = a.match_prefix([h1, h2])                # h1's (recent) survived
    assert got == [b[0]]
    a.free(got)
    a.free(c)
    assert a.num_free + a.num_cached == 4 and a.num_used == 0


def test_block_allocator_free_raises_on_double_free_and_unknown_id():
    a = BlockAllocator(4)
    b = a.alloc(1)
    a.free(b)
    with pytest.raises(ValueError, match="double free"):
        a.free(b)
    with pytest.raises(ValueError, match="out of range"):
        a.free([17])
    with pytest.raises(ValueError, match="double free"):
        a.free([2])   # never allocated
    # the failed frees must not have corrupted the free list
    assert sorted(a.alloc(4)) == [0, 1, 2, 3]


def _prefix_engine(model, params, **kw):
    base = dict(max_batch=4, block_size=8, num_blocks=64,
                max_prefill_len=16, max_seq_len=64)
    base.update(kw)
    return InferenceEngine(model, params, EngineConfig(**base))


def test_chunked_prefill_admits_long_prompts_and_matches_monolithic():
    """A prompt longer than the prefill chunk must be admissible and
    emit byte-identical greedy tokens to a monolithic (one-chunk)
    prefill of the same prompt — the chunk loop attends each chunk
    against the previously-written cache blocks, so chunking is purely
    an execution-schedule choice."""
    cfg, model, params = _tiny_model()
    prompt = list(np.random.RandomState(5).randint(0, 128, 40))

    mono = _prefix_engine(model, params, max_prefill_len=48)
    mono.add_request(Request(uid="m", prompt=prompt, max_new_tokens=6))
    ref = mono.run()["m"]
    assert mono.stats()["num_prefill_chunks"] == 1

    chunked = _prefix_engine(model, params, max_prefill_len=48,
                             prefill_chunk=16)
    chunked.add_request(Request(uid="c", prompt=prompt, max_new_tokens=6))
    out = chunked.run()["c"]
    assert out == ref
    stats = chunked.stats()
    assert stats["num_prefill_chunks"] == 3   # ceil(40 / 16)
    assert stats["prefill_compilations"] == 1
    assert stats["decode_compilations"] == 1


def test_prefix_cached_second_serving_allocates_zero_prompt_blocks():
    """THE acceptance scenario: an identical (block-aligned) prompt
    served twice with prefix caching emits identical tokens both times,
    and the second admission matches every prompt block from the cache
    — zero new prompt blocks, and the first-token logits are recomputed
    from shared blocks without a single cache write."""
    cfg, model, params = _tiny_model()
    prompt = list(np.random.RandomState(9).randint(0, 128, 32))  # 4 blocks

    plain = _prefix_engine(model, params)
    plain.add_request(Request(uid="p", prompt=prompt, max_new_tokens=6))
    ref = plain.run()["p"]

    engine = _prefix_engine(model, params, enable_prefix_caching=True)
    engine.add_request(Request(uid="one", prompt=prompt, max_new_tokens=6))
    first = engine.run()["one"]
    assert first == ref
    s1 = engine.stats()
    assert s1["blocks_cached"] > 0          # finished blocks retained
    assert engine.allocator.num_used == 0

    engine.add_request(Request(uid="two", prompt=prompt, max_new_tokens=6))
    second = engine.run()["two"]
    assert second == ref
    s2 = engine.stats()
    # every prompt block came from the cache: nothing newly allocated
    assert s2["prefix_hit_blocks"] - s1["prefix_hit_blocks"] == 4
    assert (s2["prompt_blocks_allocated"]
            == s1["prompt_blocks_allocated"])
    # one logits-only pass replaces the whole prefill
    assert s2["num_prefill_chunks"] - s1["num_prefill_chunks"] == 1
    # the fixed-program contract survives caching, chunking, both runs
    assert s2["prefill_compilations"] == 1
    assert s2["decode_compilations"] == 1
    assert 0.0 < s2["prefix_cache_hit_rate"] <= 1.0


def test_prefix_cache_shares_blocks_between_live_requests():
    """Two concurrent requests with a shared block-aligned prefix:
    the second must reference the first's prompt blocks (refcount 2)
    rather than re-prefilling them, once the first has registered them."""
    cfg, model, params = _tiny_model()
    rng = np.random.RandomState(13)
    shared = list(rng.randint(0, 128, 16))          # 2 full blocks
    a = Request(uid="a", prompt=shared + [3], max_new_tokens=12)
    b = Request(uid="b", prompt=shared + [5], max_new_tokens=12)

    engine = _prefix_engine(model, params, enable_prefix_caching=True)
    engine.add_request(a)
    engine.step()                 # a prefilled; its full blocks registered
    engine.add_request(b)
    engine.step()                 # b admitted: matches the 2 shared blocks
    slot_a = next(s for s in engine.slots if s and s.request.uid == "a")
    slot_b = next(s for s in engine.slots if s and s.request.uid == "b")
    assert slot_b.blocks[:2] == slot_a.blocks[:2]
    assert all(engine.allocator.refcount(x) == 2
               for x in slot_a.blocks[:2])
    out = engine.run()
    # sharing must not contaminate either generation: each must equal
    # its solo (uncached) serving
    for req in (a, b):
        solo = _prefix_engine(model, params)
        solo.add_request(req)
        assert solo.run()[req.uid] == out[req.uid]
    assert engine.allocator.num_used == 0


def test_copy_on_write_unshares_a_shared_partial_tail():
    """If a slot's partial tail block is shared (refcount > 1), the
    decode append must copy it to a private block first — and the copy
    must preserve contents exactly (greedy continuation unchanged)."""
    cfg, model, params = _tiny_model()
    prompt = list(np.random.RandomState(17).randint(0, 128, 12))

    ref_engine = _prefix_engine(model, params, enable_prefix_caching=True)
    ref_engine.add_request(Request(uid="r", prompt=prompt,
                                   max_new_tokens=8))
    ref = ref_engine.run()["r"]

    engine = _prefix_engine(model, params, enable_prefix_caching=True)
    engine.add_request(Request(uid="x", prompt=prompt, max_new_tokens=8))
    engine.step()     # prefill (12 tokens -> blocks [full, partial])
    slot = next(s for s in engine.slots if s is not None)
    tail = slot.blocks[1]
    engine.allocator.acquire([tail])      # simulate a second holder
    out = engine.run()["x"]
    assert engine.stats()["num_cow_copies"] >= 1
    assert out == ref                     # copy preserved the contents
    # the shared original still belongs to the simulated holder
    assert engine.allocator.refcount(tail) == 1
    engine.allocator.free([tail])
    assert engine.allocator.num_used == 0


def test_lru_eviction_keeps_engine_serving_under_cache_pressure():
    """With prefix caching on, finished requests' blocks pile up as
    cached; a stream of distinct prompts must keep serving by evicting
    LRU cached blocks instead of running out of pool."""
    cfg, model, params = _tiny_model()
    engine = _prefix_engine(model, params, num_blocks=16,
                            enable_prefix_caching=True)
    rng = np.random.RandomState(23)
    for i in range(8):
        engine.add_request(Request(uid=f"s{i}",
                                   prompt=list(rng.randint(0, 128, 16)),
                                   max_new_tokens=8))
    out = engine.run()
    assert len(out) == 8 and all(len(v) == 8 for v in out.values())
    stats = engine.stats()
    assert stats["num_cache_evictions"] > 0
    assert stats["prefill_compilations"] == 1
    assert stats["decode_compilations"] == 1


def test_stats_reports_block_accounting_and_scheduler_counters():
    cfg, model, params = _tiny_model()
    engine = _prefix_engine(model, params, enable_prefix_caching=True)
    prompt = list(np.random.RandomState(29).randint(0, 128, 16))
    engine.add_request(Request(uid="a", prompt=prompt, max_new_tokens=4))
    engine.step()   # a prefills and registers its full blocks
    engine.add_request(Request(uid="b", prompt=prompt, max_new_tokens=4))
    engine.run()
    stats = engine.stats()
    for key in ("blocks_free", "blocks_cached", "blocks_active",
                "prefix_cache_hit_rate", "prefix_hit_blocks",
                "prefix_lookup_blocks", "num_preemptions",
                "num_cow_copies", "num_cache_evictions",
                "num_prefill_chunks", "prompt_blocks_allocated"):
        assert key in stats, key
    assert (stats["blocks_free"] + stats["blocks_cached"]
            + stats["blocks_active"]) == engine.config.num_blocks
    assert stats["blocks_active"] == 0          # everything finished
    assert stats["prefix_hit_blocks"] >= 2      # b reused a's blocks
    assert 0.0 <= stats["prefix_cache_hit_rate"] <= 1.0


def _multistep_engine(model, params, k, seed=11, **kw):
    base = dict(max_batch=4, block_size=8, num_blocks=64,
                max_prefill_len=16, max_seq_len=64, seed=seed,
                decode_steps=k)
    base.update(kw)
    return InferenceEngine(model, params, EngineConfig(**base))


def _multistep_workload(engine):
    """6 staggered requests, mixed greedy/sampled, generation budgets
    deliberately NOT multiples of 4 or 8 so lanes finish mid-scan."""
    rng = np.random.RandomState(37)
    reqs = []
    for i in range(6):
        samp = (SamplingParams() if i % 2 == 0 else
                SamplingParams(temperature=0.9, top_k=12, top_p=0.85))
        reqs.append(Request(uid=f"m{i}",
                            prompt=list(rng.randint(0, 128, 4 + 2 * i)),
                            max_new_tokens=3 + (i % 3) * 5,
                            sampling=samp))
    for r in reqs[:3]:
        engine.add_request(r)
    engine.step()
    engine.step()
    for r in reqs[3:]:
        engine.add_request(r)
    return reqs, engine.run()


def test_multistep_decode_outputs_identical_across_k():
    """THE multi-step acceptance scenario: greedy AND seeded-sampled
    outputs are bit-identical for decode_steps in {1, 4, 8} (per-
    request/per-token PRNG keys make generation schedule-invariant),
    the compile contract stays one prefill + one decode program, and
    K > 1 actually amortizes dispatches over tokens."""
    cfg, model, params = _tiny_model()
    outs, stats = {}, {}
    for k in (1, 4, 8):
        engine = _multistep_engine(model, params, k)
        _, outs[k] = _multistep_workload(engine)
        s = engine.stats()
        assert s["prefill_compilations"] == 1
        assert s["decode_compilations"] == 1
        assert engine.allocator.num_used == 0
        stats[k] = s
    assert outs[1] == outs[4] == outs[8]
    # same tokens, fewer dispatches: the amortization is observable
    assert (stats[1]["num_tokens_decoded"] == stats[4]["num_tokens_decoded"]
            == stats[8]["num_tokens_decoded"])
    assert stats[4]["num_decode_dispatches"] < stats[1]["num_decode_dispatches"]
    assert stats[8]["num_decode_dispatches"] <= stats[4]["num_decode_dispatches"]
    # and the sampled half still actually depends on the engine seed
    _, alt = _multistep_workload(_multistep_engine(model, params, 8,
                                                   seed=999))
    sampled = [f"m{i}" for i in range(6) if i % 2 == 1]
    assert any(alt[u] != outs[8][u] for u in sampled)


def test_multistep_eos_and_budget_freeze_lanes_mid_scan():
    """A lane that samples EOS (or exhausts max_new_tokens) mid-scan
    must freeze on-device — later scan iterations emit the sentinel and
    write nothing — and the host must finish it on exactly the same
    token a K=1 engine would."""
    cfg, model, params = _tiny_model()
    prompt = list(np.random.RandomState(31).randint(0, 128, 6))
    pilot = _multistep_engine(model, params, 1)
    pilot.add_request(Request(uid="p", prompt=prompt, max_new_tokens=6))
    ref = pilot.run()["p"]

    # eos on (the first occurrence of) the 4th greedy token: fires on
    # scan iteration 2 or 3 of the single K=8 dispatch
    eos = int(ref[3])
    expected = ref[: ref.index(eos) + 1]
    engine = _multistep_engine(model, params, 8)
    engine.add_request(Request(uid="e", prompt=prompt, max_new_tokens=6,
                               eos_token_id=eos))
    engine.add_request(Request(uid="b", prompt=prompt, max_new_tokens=6))
    out = engine.run()
    assert out["e"] == expected
    assert out["b"] == ref
    stats = engine.stats()
    # both lanes' whole generation fits inside single K=8 dispatches
    # (budget 5 < 8 after the prefill-sampled first token)
    total_decode = (len(expected) - 1) + (len(ref) - 1)
    assert stats["num_tokens_decoded"] == total_decode
    assert stats["num_decode_dispatches"] <= 2
    assert stats["decode_compilations"] == 1
    assert engine.allocator.num_used == 0


def test_multistep_preemption_resume_is_deterministic():
    """Preemption-under-pressure at K=4, with a SAMPLED lane in the
    mix: a pool tight enough to force preemption (granularity is now K
    tokens of block headroom) must emit byte-identical tokens to a
    roomy pool — and to a roomy K=1 engine — because emitted tokens are
    carried across preemption and per-token keys make the resumed
    sampling continue the same draw sequence."""
    cfg, model, params = _tiny_model()
    rng = np.random.RandomState(19)
    reqs = [Request(uid=f"r{i}", prompt=list(rng.randint(0, 128, 6 + i)),
                    max_new_tokens=20,
                    sampling=(SamplingParams(temperature=0.8, top_k=12)
                              if i == 1 else SamplingParams()))
            for i in range(3)]

    def serve(num_blocks, k):
        engine = InferenceEngine(model, params, EngineConfig(
            max_batch=3, block_size=8, num_blocks=num_blocks,
            max_prefill_len=8, max_seq_len=32, decode_steps=k, seed=5))
        for r in reqs:
            engine.add_request(r)
        return engine.run(), engine.stats()

    roomy, roomy_stats = serve(num_blocks=16, k=4)
    tight, tight_stats = serve(num_blocks=6, k=4)
    single, single_stats = serve(num_blocks=16, k=1)
    assert roomy_stats["num_preemptions"] == 0
    assert tight_stats["num_preemptions"] >= 1
    assert tight == roomy == single
    for s in (roomy_stats, tight_stats, single_stats):
        assert s["prefill_compilations"] == 1
        assert s["decode_compilations"] == 1


def test_stats_split_decode_dispatches_from_tokens_with_alias():
    """stats() reports num_decode_dispatches and num_tokens_decoded
    separately; the legacy num_decode_steps key survives as an alias
    for dispatches (its pre-multistep meaning)."""
    cfg, model, params = _tiny_model()
    engine = _multistep_engine(model, params, 4)
    for uid in ("a", "b"):
        engine.add_request(Request(uid=uid, prompt=[3, 1, 4, 1, 5],
                                   max_new_tokens=9))
    out = engine.run()
    stats = engine.stats()
    # every generated token past the prefill-sampled first one came
    # from a decode dispatch
    decode_tokens = sum(len(v) - 1 for v in out.values())
    assert stats["num_tokens_decoded"] == decode_tokens
    assert stats["num_decode_steps"] == stats["num_decode_dispatches"]
    assert stats["num_decode_dispatches"] < stats["num_tokens_decoded"]
    # the dirty-tracked table uploaded at most once per dispatch
    assert stats["decode_table_rebuilds"] <= stats["num_decode_dispatches"]


def test_engine_raises_when_pool_can_never_serve_the_queue():
    """A request whose prompt needs more blocks than the whole pool must
    raise CacheOutOfBlocks instead of spinning the scheduler forever."""
    cfg, model, params = _tiny_model()
    engine = InferenceEngine(model, params, EngineConfig(
        max_batch=2, block_size=8, num_blocks=2, max_prefill_len=16,
        max_seq_len=32))
    engine.add_request(Request(uid="big", prompt=[1] * 16,
                               max_new_tokens=2))
    with pytest.raises(CacheOutOfBlocks):
        engine.run()


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampling_greedy_topk_topp_determinism():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(4, 64).astype("f4") * 2.0)
    key = jax.random.PRNGKey(42)
    ones = jnp.ones((4,), jnp.float32)
    zeros_i = jnp.zeros((4,), jnp.int32)

    # temperature <= 0: exact argmax
    toks = sample_tokens(logits, key, jnp.zeros((4,)), zeros_i, ones)
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, -1)))
    # top_k = 1 is greedy regardless of temperature
    toks = sample_tokens(logits, key, ones * 5.0,
                         jnp.ones((4,), jnp.int32), ones)
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, -1)))
    # a vanishing nucleus keeps only the argmax token
    toks = sample_tokens(logits, key, ones, zeros_i, ones * 1e-6)
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, -1)))
    # fixed key -> identical draws; different key -> (some) different
    a = sample_tokens(logits, key, ones, zeros_i, ones)
    b = sample_tokens(logits, key, ones, zeros_i, ones)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    draws = np.stack([
        np.asarray(sample_tokens(logits, jax.random.PRNGKey(s), ones * 2.0,
                                 zeros_i, ones))
        for s in range(16)])
    assert len(np.unique(draws)) > 1

    # top-k draws stay inside the k most likely tokens
    k = 5
    topk_sets = np.asarray(jnp.argsort(-logits, axis=-1)[:, :k])
    for s in range(16):
        toks = np.asarray(sample_tokens(
            logits, jax.random.PRNGKey(s), ones * 3.0,
            jnp.full((4,), k, jnp.int32), ones))
        for row in range(4):
            assert toks[row] in topk_sets[row]


def test_sampling_top_k_at_least_vocab_equals_disabled():
    """The documented alias: top_k >= V keeps every rank, so it must
    draw exactly what top_k = 0 (disabled) draws under the same key —
    and validate() must accept it (it cannot clamp: the vocabulary size
    is a model property the params object never sees)."""
    rng = np.random.RandomState(2)
    V = 32
    logits = jnp.asarray(rng.randn(4, V).astype("f4") * 2.0)
    ones = jnp.ones((4,), jnp.float32)
    SamplingParams(temperature=1.0, top_k=10 ** 6).validate()
    for s in range(8):
        key = jax.random.PRNGKey(s)
        ref = np.asarray(sample_tokens(logits, key, ones,
                                       jnp.zeros((4,), jnp.int32), ones))
        for k in (V, V + 1, 10 ** 6):
            got = np.asarray(sample_tokens(
                logits, key, ones, jnp.full((4,), k, jnp.int32), ones))
            np.testing.assert_array_equal(got, ref)


def test_sample_tokens_per_lane_draws_are_lane_invariant():
    """The property the multi-step decode keys rely on: a row's draw
    depends only on ITS key and logits — permuting the batch permutes
    the draws, it never changes them (the shared-key sampler folds the
    row index into the noise, so this deliberately does NOT hold for
    sample_tokens)."""
    from apex_tpu.serving import sample_tokens_per_lane

    rng = np.random.RandomState(4)
    logits = jnp.asarray(rng.randn(3, 64).astype("f4") * 2.0)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in (100, 101, 102)])
    ones = jnp.ones((3,), jnp.float32)
    zeros_i = jnp.zeros((3,), jnp.int32)
    out = np.asarray(sample_tokens_per_lane(logits, keys, ones * 1.5,
                                            zeros_i, ones))
    perm = np.array([2, 0, 1])
    out_p = np.asarray(sample_tokens_per_lane(
        logits[perm], keys[perm], ones * 1.5, zeros_i, ones))
    np.testing.assert_array_equal(out_p, out[perm])
    # greedy rows ignore the key entirely
    greedy = np.asarray(sample_tokens_per_lane(
        logits, keys, jnp.zeros((3,)), zeros_i, ones))
    np.testing.assert_array_equal(greedy,
                                  np.asarray(jnp.argmax(logits, -1)))


def test_device_mirror_rebuilds_only_after_invalidate():
    from apex_tpu.serving import DeviceMirror

    calls = []

    def build():
        calls.append(1)
        return len(calls)

    m = DeviceMirror()
    assert m.dirty
    assert m.get(build) == 1 and m.get(build) == 1 and len(calls) == 1
    assert not m.dirty
    m.invalidate()
    assert m.dirty
    assert m.get(build) == 2 and len(calls) == 2


def test_sampling_top_p_renormalizes_over_top_k_survivors():
    """The documented composition: top-p mass is measured over the
    RENORMALIZED top-k distribution. Logits (3.0, 1.9, rest 1.0):
    within top-2 token 0 holds e^3/(e^3+e^1.9) ~ 0.75 of the mass, so
    top_p=0.7 must always return token 0 — while over the full
    vocabulary token 0 holds only ~0.10, under which token 1 would
    (wrongly) stay sampleable ~25% of draws."""
    logits = np.full((1, 64), 1.0, np.float32)
    logits[0, 0], logits[0, 1] = 3.0, 1.9
    logits = jnp.asarray(logits)
    ones = jnp.ones((1,), jnp.float32)
    for s in range(32):
        tok = int(sample_tokens(logits, jax.random.PRNGKey(s),
                                ones, jnp.full((1,), 2, jnp.int32),
                                ones * 0.7)[0])
        assert tok == 0


