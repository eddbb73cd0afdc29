"""Speculative decoding tests (tier-1, CPU): the draft-and-verify
decode path (docs/serving.md) — n-gram/small-GPT drafters, the
rejection-sampling accept rule, greedy bit-identity vs the
non-speculative engine across decode_steps/lane placements/preemption/
snapshot-restore, mid-span EOS, drafter quarantine, block-reservation
rollback, the sampling greedy fast path, and EngineConfig validation."""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTConfig, GPTLMHeadModel
from apex_tpu.serving import (
    BlockAllocator,
    Drafter,
    EngineConfig,
    GPTDrafter,
    InferenceEngine,
    NgramDrafter,
    Request,
    SamplingParams,
    sample_tokens,
    sample_tokens_per_lane,
    spec_verify_tokens,
)
from apex_tpu.utils.faults import FaultPlan, FaultSpec


def _tiny_model(**kw):
    kw.setdefault("dropout", 0.0)
    kw.setdefault("remat", False)
    cfg = GPTConfig.tiny(**kw)
    model = GPTLMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


def _engine(model, params, seed=11, **kw):
    base = dict(max_batch=4, block_size=8, num_blocks=64,
                max_prefill_len=16, max_seq_len=64, seed=seed)
    base.update(kw)
    return InferenceEngine(model, params, EngineConfig(**base))


def _greedy_reqs(tag, n=5, seed=37, max_new=None):
    """Staggered all-greedy requests (greedy is the bit-identity
    certification regime; budgets deliberately not span multiples)."""
    rng = np.random.RandomState(seed)
    return [Request(uid=f"{tag}{i}", prompt=list(rng.randint(0, 128, 4 + 2 * i)),
                    max_new_tokens=(max_new or (3 + (i % 3) * 7)))
            for i in range(n)]


def _serve(engine, reqs, stagger=True):
    for r in reqs[:3]:
        engine.add_request(r)
    if stagger:
        engine.step()
        engine.step()
    for r in reqs[3:]:
        engine.add_request(r)
    return engine.run()


class _NullDrafter(Drafter):
    def propose(self, history, max_tokens):
        return []


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------

def test_ngram_drafter_prompt_lookup():
    d = NgramDrafter(max_ngram=3, min_ngram=1)
    # suffix [7, 8] occurred earlier; propose its continuation
    assert d.propose([7, 8, 9, 1, 7, 8], 3) == [9, 1, 7]
    # longest suffix match wins over a shorter, more recent one
    assert d.propose([1, 2, 3, 9, 3, 1, 2, 3], 2) == [9, 3]
    # the LATEST earlier occurrence of the n-gram is used
    assert d.propose([5, 4, 5, 6, 5], 1) == [6]
    # a continuation that runs into the present extends periodically
    assert d.propose([1, 2, 1, 2], 8) == [1, 2, 1, 2, 1, 2, 1, 2]
    # no earlier occurrence -> no proposal; short history -> none
    assert d.propose([1, 2, 3, 4], 4) == []
    assert d.propose([3], 4) == []
    assert d.propose([1, 2, 1], 0) == []
    with pytest.raises(ValueError, match="min_ngram"):
        NgramDrafter(max_ngram=0)
    with pytest.raises(ValueError, match="min_ngram"):
        NgramDrafter(max_ngram=2, min_ngram=3)


def test_gpt_drafter_is_deterministic_and_validates():
    cfg, model, params = _tiny_model()
    d = GPTDrafter(model, params, window=8)
    hist = [3, 1, 4, 1, 5]
    a = d.propose(hist, 4)
    assert len(a) == 4 and all(0 <= t < cfg.vocab_size for t in a)
    # pure function of the history (the resume-determinism contract)
    assert d.propose(list(hist), 4) == a
    # proposals chain: the first k of a longer proposal are the
    # proposal for k tokens
    assert d.propose(hist, 2) == a[:2]
    with pytest.raises(ValueError, match="window"):
        GPTDrafter(model, params, window=0)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        GPTDrafter(model, params, window=10 ** 6)


# ---------------------------------------------------------------------------
# the accept rule
# ---------------------------------------------------------------------------

def test_spec_verify_tokens_greedy_accept_rule():
    """Hand-built logits: greedy lanes accept exactly the prefix of
    drafts that equal each position's argmax, and the final token is
    the first-rejection argmax (or the bonus argmax past the span)."""
    B, S, V = 3, 3, 16
    P = S + 1
    lg = np.full((B, P, V), -10.0, np.float32)
    argmax = np.array([[4, 5, 6, 7],
                       [3, 2, 1, 0],
                       [9, 9, 9, 9]])
    for b in range(B):
        for p in range(P):
            lg[b, p, argmax[b, p]] = 10.0
    drafts = jnp.asarray([[4, 5, 6],     # all accepted -> bonus 7
                          [3, 9, 1],     # reject at pos 1 -> correct 2
                          [0, 0, 0]], jnp.int32)   # reject at 0 -> 9
    dlens = jnp.asarray([3, 3, 2], jnp.int32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(B))
    tidx = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[None], (B, P))
    zeros = jnp.zeros(B, jnp.float32)
    emitted, n_emit = spec_verify_tokens(
        jnp.asarray(lg), drafts, dlens, keys, tidx,
        zeros, jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32))
    emitted, n_emit = np.asarray(emitted), np.asarray(n_emit)
    assert list(n_emit) == [4, 2, 1]
    assert list(emitted[0]) == [4, 5, 6, 7]
    assert list(emitted[1][:2]) == [3, 2]
    assert list(emitted[2][:1]) == [9]


def test_spec_verify_tokens_sampled_is_distribution_preserving():
    """The rejection rule must reproduce the target distribution
    exactly: over many keys, the first emitted token's histogram under
    drafting matches direct sampling from the same (filtered) target
    distribution — the Leviathan et al. guarantee."""
    V = 8
    logits = jnp.asarray(np.linspace(0.0, 2.0, V, dtype=np.float32))[None]
    target = np.asarray(jax.nn.softmax(logits[0]))
    n = 4000
    draft = jnp.full((n, 1), 5, jnp.int32)   # a fixed, mediocre guess
    dlens = jnp.ones(n, jnp.int32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
    lg = jnp.broadcast_to(logits[:, None, :], (n, 2, V))
    tidx = jnp.broadcast_to(jnp.arange(2, dtype=jnp.int32)[None], (n, 2))
    emitted, _ = spec_verify_tokens(
        lg, draft, dlens, keys, tidx,
        jnp.ones(n, jnp.float32), jnp.zeros(n, jnp.int32),
        jnp.ones(n, jnp.float32))
    first = np.asarray(emitted[:, 0])
    hist = np.bincount(first, minlength=V) / n
    # generous tolerance: 4000 draws, max std ~0.008
    np.testing.assert_allclose(hist, target, atol=0.035)


# ---------------------------------------------------------------------------
# engine: greedy bit-identity certification matrix
# ---------------------------------------------------------------------------

def test_speculative_greedy_bit_identical_across_k_and_spec():
    """THE speculative acceptance scenario: greedy output is
    bit-identical between non-speculative engines at decode_steps in
    {1, 4, 8} and speculative engines at spec_tokens in {2, 4, 8},
    over a staggered multi-lane workload; compile counts stay pinned
    at one prefill + one decode program; and the drafter actually
    accepts tokens (fewer dispatches than K=1 for the same stream)."""
    cfg, model, params = _tiny_model()
    outs, stats = {}, {}
    for arm, kw in {"k1": dict(decode_steps=1),
                    "k4": dict(decode_steps=4),
                    "k8": dict(decode_steps=8),
                    "s2": dict(spec_tokens=2),
                    "s4": dict(spec_tokens=4),
                    "s8": dict(spec_tokens=8)}.items():
        engine = _engine(model, params, **kw)
        outs[arm] = _serve(engine, _greedy_reqs("m"))
        s = engine.stats()
        assert s["prefill_compilations"] == 1
        assert s["decode_compilations"] == 1
        assert engine.allocator.num_used == 0
        stats[arm] = s
    first = outs["k1"]
    assert all(o == first for o in outs.values())
    for arm in ("s2", "s4", "s8"):
        assert stats[arm]["num_draft_tokens"] > 0
        assert stats[arm]["num_accepted_tokens"] > 0
        assert 0.0 < stats[arm]["draft_acceptance_rate"] <= 1.0
        assert (stats[arm]["num_accepted_tokens"]
                <= stats[arm]["num_draft_tokens"])
        # >1 token per target forward on average is the whole point
        assert (stats[arm]["num_decode_dispatches"]
                < stats["k1"]["num_decode_dispatches"])
        assert (stats[arm]["num_tokens_decoded"]
                == stats["k1"]["num_tokens_decoded"])


def test_speculative_sampled_null_drafter_bit_identical():
    """A speculative engine whose drafter proposes NOTHING runs the
    verify program as plain single-token decoding — and because the
    bonus token is keyed exactly like the non-speculative token at the
    same index, even SAMPLED lanes are bit-identical to spec-off."""
    cfg, model, params = _tiny_model()
    rng = np.random.RandomState(7)
    reqs = [Request(uid=f"s{i}", prompt=list(rng.randint(0, 128, 5 + i)),
                    max_new_tokens=9,
                    sampling=(SamplingParams(temperature=0.9, top_k=12,
                                             top_p=0.85)
                              if i % 2 else SamplingParams()))
            for i in range(4)]
    base = _engine(model, params)
    out_base = _serve(base, reqs, stagger=False)
    spec = InferenceEngine(model, params, EngineConfig(
        max_batch=4, block_size=8, num_blocks=64, max_prefill_len=16,
        max_seq_len=64, seed=11, spec_tokens=3), drafter=_NullDrafter())
    out_spec = _serve(spec, reqs, stagger=False)
    assert out_spec == out_base
    s = spec.stats()
    assert s["num_draft_tokens"] == 0
    assert s["decode_compilations"] == 1


def test_speculative_sampled_lanes_accept_and_greedy_stay_identical():
    """With a real drafter and sampled lanes in the mix: greedy lanes
    remain bit-identical to the non-speculative engine (the structural
    argmax identity holds regardless of proposals), sampled lanes keep
    their budgets/lengths, and the run is deterministic (re-serving
    reproduces it bit-for-bit)."""
    cfg, model, params = _tiny_model()
    rng = np.random.RandomState(3)
    reqs = [Request(uid=f"x{i}", prompt=list(rng.randint(0, 128, 6)),
                    max_new_tokens=12,
                    sampling=(SamplingParams(temperature=1.0, top_k=20)
                              if i % 2 else SamplingParams()))
            for i in range(4)]
    out_base = _serve(_engine(model, params), reqs, stagger=False)
    out_a = _serve(_engine(model, params, spec_tokens=4), reqs,
                   stagger=False)
    out_b = _serve(_engine(model, params, spec_tokens=4), reqs,
                   stagger=False)
    assert out_a == out_b                      # deterministic
    for i in (0, 2):                           # greedy lanes: identical
        assert out_a[f"x{i}"] == out_base[f"x{i}"]
    for i in (1, 3):                           # sampled lanes: full runs
        assert len(out_a[f"x{i}"]) == len(out_base[f"x{i}"]) == 12


def test_speculative_mid_span_eos_truncates_like_k1():
    """EOS accepted (or corrected) mid-verify-span must cut the lane's
    remaining emission on-device and finish it on exactly the token a
    non-speculative K=1 engine finishes on."""
    cfg, model, params = _tiny_model()
    prompt = list(np.random.RandomState(31).randint(0, 128, 6))
    pilot = _engine(model, params)
    pilot.add_request(Request(uid="p", prompt=prompt, max_new_tokens=8))
    ref = pilot.run()["p"]
    eos = int(ref[3])
    expected = ref[: ref.index(eos) + 1]
    engine = _engine(model, params, spec_tokens=8)
    engine.add_request(Request(uid="e", prompt=prompt, max_new_tokens=8,
                               eos_token_id=eos))
    engine.add_request(Request(uid="b", prompt=prompt, max_new_tokens=8))
    out = engine.run()
    assert out["e"] == expected
    assert out["b"] == ref
    assert engine.allocator.num_used == 0
    assert engine.stats()["decode_compilations"] == 1


def test_speculative_preemption_resume_is_deterministic():
    """Preemption at speculative-span granularity: a pool tight enough
    to preempt mid-stream must emit byte-identical greedy tokens to a
    roomy speculative pool AND to a roomy non-speculative engine —
    emitted tokens are carried across preemption and re-prefill
    re-derives the lane, drafts and all."""
    cfg, model, params = _tiny_model()
    rng = np.random.RandomState(19)
    reqs = [Request(uid=f"r{i}", prompt=list(rng.randint(0, 128, 6 + i)),
                    max_new_tokens=20)
            for i in range(3)]

    def serve(num_blocks, **kw):
        engine = InferenceEngine(model, params, EngineConfig(
            max_batch=3, block_size=8, num_blocks=num_blocks,
            max_prefill_len=8, max_seq_len=32, seed=5, **kw))
        for r in reqs:
            engine.add_request(r)
        return engine.run(), engine.stats()

    roomy, roomy_stats = serve(num_blocks=16, spec_tokens=4)
    tight, tight_stats = serve(num_blocks=6, spec_tokens=4)
    plain, plain_stats = serve(num_blocks=16)
    assert roomy_stats["num_preemptions"] == 0
    assert tight_stats["num_preemptions"] >= 1
    assert tight == roomy == plain
    for s in (roomy_stats, tight_stats, plain_stats):
        assert s["prefill_compilations"] == 1
        assert s["decode_compilations"] == 1


def test_speculative_snapshot_restore_bit_identical():
    """A snapshot taken mid-stream of a speculative engine restores
    into a fresh speculative engine and completes bit-identically to
    the uninterrupted run (the PR 6 crash-consistency contract holds
    with drafting on; the config fingerprint covers spec_tokens)."""
    cfg, model, params = _tiny_model()
    reqs = _greedy_reqs("c", n=4, seed=9, max_new=14)
    ref_engine = _engine(model, params, spec_tokens=4)
    uninterrupted = _serve(ref_engine, reqs, stagger=False)

    eng = _engine(model, params, spec_tokens=4)
    for r in reqs:
        eng.add_request(r)
    for _ in range(4):
        eng.step()
    snap = eng.snapshot()
    fresh = _engine(model, params, spec_tokens=4)
    fresh.restore(snap)
    merged = dict(snap["finished"])
    merged.update(fresh.run())
    assert merged == uninterrupted
    # a non-speculative engine must refuse the speculative snapshot
    with pytest.raises(ValueError, match="spec_tokens"):
        _engine(model, params).restore(snap)


def test_speculative_with_prefix_caching_reuses_blocks():
    """Drafting composes with prefix caching: the second serving of an
    identical prompt matches its cached blocks (zero prompt-block
    allocations) and still emits the same greedy tokens; span-
    reservation rollback never trims a prefix-registered block."""
    cfg, model, params = _tiny_model()
    prompt = list(np.random.RandomState(4).randint(0, 128, 16))
    engine = _engine(model, params, spec_tokens=4,
                     enable_prefix_caching=True)
    engine.add_request(Request(uid="a", prompt=prompt, max_new_tokens=10))
    first = engine.run()["a"]
    allocated = engine.stats()["prompt_blocks_allocated"]
    engine.add_request(Request(uid="b", prompt=prompt, max_new_tokens=10))
    second = engine.run()["b"]
    assert second == first
    assert engine.stats()["prompt_blocks_allocated"] == allocated
    assert engine.stats()["prefix_hit_blocks"] >= 2


# ---------------------------------------------------------------------------
# drafter quarantine (degrade, don't die)
# ---------------------------------------------------------------------------

def test_crashing_drafter_degrades_to_nonspeculative():
    """A drafter whose propose keeps failing transiently exhausts the
    shared retry policy and is QUARANTINED: speculation flips off for
    the engine's lifetime and the verify program keeps emitting
    bit-identical tokens as plain decode — the engine never dies."""
    cfg, model, params = _tiny_model()
    reqs = _greedy_reqs("q", n=4, seed=2, max_new=10)
    out_base = _serve(_engine(model, params), reqs, stagger=False)
    plan = FaultPlan(specs=[FaultSpec(site="draft", kind="transient",
                                      every=1)], seed=0)
    engine = InferenceEngine(
        model, params,
        EngineConfig(max_batch=4, block_size=8, num_blocks=64,
                     max_prefill_len=16, max_seq_len=64, seed=11,
                     spec_tokens=4, max_dispatch_retries=1),
        faults=plan)
    out = _serve(engine, reqs, stagger=False)
    assert out == out_base
    s = engine.stats()
    assert s["num_drafter_quarantines"] == 1
    assert s["num_draft_retries"] >= 1
    assert s["speculation_active"] == 0
    assert s["num_draft_tokens"] == 0
    assert s["num_quarantines"] == 0          # no REQUEST was failed


def test_buggy_drafter_quarantined_without_retry_eating_the_bug():
    """A drafter that raises a non-transient exception (a plain bug) is
    quarantined immediately — the engine degrades instead of dying, and
    outputs stay bit-identical to non-speculative decode."""
    cfg, model, params = _tiny_model()

    class Buggy(Drafter):
        def propose(self, history, max_tokens):
            raise ZeroDivisionError("drafter bug")

    reqs = _greedy_reqs("z", n=3, seed=6, max_new=8)
    out_base = _serve(_engine(model, params), reqs, stagger=False)
    engine = InferenceEngine(model, params, EngineConfig(
        max_batch=4, block_size=8, num_blocks=64, max_prefill_len=16,
        max_seq_len=64, seed=11, spec_tokens=4), drafter=Buggy())
    out = _serve(engine, reqs, stagger=False)
    assert out == out_base
    assert engine.stats()["num_drafter_quarantines"] == 1
    assert engine.stats()["speculation_active"] == 0


def test_drafter_quarantine_survives_snapshot_restore():
    """Quarantine is part of the engine's behavioral state: a snapshot
    taken after the drafter was quarantined restores DEGRADED, even
    into an engine handed a healthy drafter. Resumed speculation would
    draw accept/resample uniforms the uninterrupted (empty-plan) run
    never drew, so a sampled lane would diverge from the
    crash-consistency contract — the restored run must stay
    bit-identical to the uninterrupted degraded one."""
    cfg, model, params = _tiny_model()

    class Buggy(Drafter):
        def propose(self, history, max_tokens):
            raise ZeroDivisionError("drafter bug")

    rng = np.random.RandomState(5)
    pat = list(rng.randint(0, 128, 3))
    reqs = [
        # a repetitive sampled lane: exactly where a healthy n-gram
        # drafter WOULD propose (and shift the key chain) post-restore
        Request(uid="s0", prompt=(pat * 6)[:14], max_new_tokens=12,
                sampling=SamplingParams(temperature=0.8, top_k=32)),
        Request(uid="g0", prompt=(pat * 5)[:12], max_new_tokens=10),
        Request(uid="g1", prompt=list(rng.randint(0, 128, 8)),
                max_new_tokens=8),
    ]

    def fresh_reqs():
        return [dc.replace(r) for r in reqs]

    ecfg = dict(spec_tokens=4)
    ref = InferenceEngine(model, params, EngineConfig(
        max_batch=4, block_size=8, num_blocks=64, max_prefill_len=16,
        max_seq_len=64, seed=11, **ecfg), drafter=Buggy())
    for r in fresh_reqs():
        ref.add_request(r)
    uninterrupted = ref.run()
    assert ref.stats()["speculation_active"] == 0

    eng = InferenceEngine(model, params, EngineConfig(
        max_batch=4, block_size=8, num_blocks=64, max_prefill_len=16,
        max_seq_len=64, seed=11, **ecfg), drafter=Buggy())
    for r in fresh_reqs():
        eng.add_request(r)
    for _ in range(3):
        eng.step()
    assert eng.stats()["speculation_active"] == 0   # quarantine fired
    snap = eng.snapshot()

    restored = InferenceEngine(model, params, EngineConfig(
        max_batch=4, block_size=8, num_blocks=64, max_prefill_len=16,
        max_seq_len=64, seed=11, **ecfg), drafter=NgramDrafter())
    restored.restore(snap)
    assert restored.stats()["speculation_active"] == 0
    merged = dict(snap["finished"])
    merged.update(restored.run())
    assert merged == uninterrupted
    assert restored.stats()["num_draft_tokens"] == 0


def test_out_of_vocab_proposals_are_truncated():
    """Proposals are sanitized at the first out-of-vocabulary token:
    the lane verifies the clean prefix, output stays bit-identical."""
    cfg, model, params = _tiny_model()

    class Wild(Drafter):
        def __init__(self):
            self.inner = NgramDrafter()

        def propose(self, history, max_tokens):
            good = self.inner.propose(history, max_tokens)
            return good[:1] + [10 ** 9] + good[1:]

    reqs = _greedy_reqs("w", n=3, seed=8, max_new=10)
    out_base = _serve(_engine(model, params), reqs, stagger=False)
    engine = InferenceEngine(model, params, EngineConfig(
        max_batch=4, block_size=8, num_blocks=64, max_prefill_len=16,
        max_seq_len=64, seed=11, spec_tokens=4), drafter=Wild())
    out = _serve(engine, reqs, stagger=False)
    assert out == out_base
    assert engine.stats()["speculation_active"] == 1


# ---------------------------------------------------------------------------
# block-reservation rollback
# ---------------------------------------------------------------------------

def test_trim_to_releases_private_tail_and_guards_shared():
    a = BlockAllocator(8)
    blocks = a.alloc(5)
    kept = a.trim_to(blocks, 2)
    assert kept == blocks[:2]
    assert a.num_free == 6
    # shared tail: refcount != 1 must refuse before freeing anything
    a.acquire([kept[1]])
    with pytest.raises(ValueError, match="refcount"):
        a.trim_to(kept, 0)
    assert a.num_free == 6                    # nothing was released
    # prefix-registered tail must refuse too (it is matchable context)
    b = a.alloc(1)
    a.register_prefix("h0", b[0])
    with pytest.raises(ValueError, match="prefix"):
        a.trim_to(b, 0)
    with pytest.raises(ValueError, match="keep"):
        a.trim_to(kept, 3)


def test_speculative_rollback_returns_stranded_blocks():
    """A rejection that leaves a lane short of its reserved span must
    return the stranded blocks to the pool at drain time (observable
    via the rollback counter), and the allocator must balance to zero
    when the workload finishes."""
    cfg, model, params = _tiny_model()
    # block_size=2 makes every span cross block boundaries, so any
    # rejection strands at least one block
    engine = _engine(model, params, spec_tokens=6, block_size=2,
                     num_blocks=128, max_seq_len=48)
    for r in _greedy_reqs("t", n=4, seed=12, max_new=12):
        engine.add_request(r)
    engine.run()
    s = engine.stats()
    assert s["num_draft_tokens"] > 0
    assert engine.allocator.num_used == 0
    if s["num_accepted_tokens"] < s["num_draft_tokens"]:
        assert s["num_spec_blocks_rolled_back"] > 0


# ---------------------------------------------------------------------------
# sampling greedy fast path (satellite)
# ---------------------------------------------------------------------------

def test_greedy_fast_path_bit_identity():
    """temperature == 0 everywhere short-circuits the sort/filter/
    softmax chain to argmax — and must be bit-identical to the mixed-
    batch path's greedy rows (which still run the full chain's
    where-select)."""
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(6, 64).astype(np.float32))
    argmax = np.argmax(np.asarray(logits), axis=-1)
    zeros = jnp.zeros(6, jnp.float32)
    k0 = jnp.zeros(6, jnp.int32)
    p1 = jnp.ones(6, jnp.float32)
    key = jax.random.PRNGKey(0)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(6))

    fast = sample_tokens(logits, key, zeros, k0, p1)
    np.testing.assert_array_equal(np.asarray(fast), argmax)
    fast_l = sample_tokens_per_lane(logits, keys, zeros, k0, p1)
    np.testing.assert_array_equal(np.asarray(fast_l), argmax)

    # mixed batch: row 3 samples, every greedy row must STILL be argmax
    mixed_t = zeros.at[3].set(0.9)
    mixed = np.asarray(sample_tokens(logits, key, mixed_t, k0, p1))
    mixed_l = np.asarray(sample_tokens_per_lane(logits, keys, mixed_t,
                                                k0, p1))
    for row in (0, 1, 2, 4, 5):
        assert mixed[row] == argmax[row]
        assert mixed_l[row] == argmax[row]


# ---------------------------------------------------------------------------
# EngineConfig validation (satellite)
# ---------------------------------------------------------------------------

def test_engine_config_validation_rejects_bad_geometry():
    good = dict(max_batch=2, block_size=8, num_blocks=16,
                max_prefill_len=16, max_seq_len=32)
    EngineConfig(**good)                      # sanity: valid
    with pytest.raises(ValueError, match="block_size"):
        EngineConfig(**{**good, "block_size": 0})
    with pytest.raises(ValueError, match="num_blocks"):
        EngineConfig(**{**good, "num_blocks": -1})
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        EngineConfig(**{**good, "prefill_chunk": 64})
    with pytest.raises(ValueError, match="prefill_chunk"):
        EngineConfig(**{**good, "prefill_chunk": 0})
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        # prefill_chunk=None inherits max_prefill_len, which must obey
        # the same bound
        EngineConfig(**{**good, "max_prefill_len": 64})
    with pytest.raises(ValueError, match="decode_steps"):
        EngineConfig(**{**good, "decode_steps": 0})
    with pytest.raises(ValueError, match="spec_tokens"):
        EngineConfig(**{**good, "spec_tokens": -1})
    with pytest.raises(ValueError, match="max_dispatch_retries"):
        EngineConfig(**{**good, "max_dispatch_retries": -1})


def test_engine_rejects_drafter_without_spec_tokens():
    cfg, model, params = _tiny_model()
    with pytest.raises(ValueError, match="spec_tokens"):
        InferenceEngine(model, params, EngineConfig(
            max_batch=2, block_size=8, num_blocks=16, max_prefill_len=16,
            max_seq_len=32), drafter=NgramDrafter())


# ---------------------------------------------------------------------------
# dynamic speculation (spec_adapt — docs/serving.md)
# ---------------------------------------------------------------------------


class _WrongDrafter(Drafter):
    """Adversarial drafter: proposes a constant (almost always
    rejected) token — the low-acceptance regime spec_adapt exists
    for."""

    def __init__(self, token):
        self._t = int(token)

    def propose(self, history, max_tokens):
        return [self._t] * max_tokens


def _adapt_engine(model, params, cfg, **kw):
    base = dict(max_batch=4, block_size=8, num_blocks=64,
                max_prefill_len=16, max_seq_len=64, seed=11,
                spec_tokens=4)
    base.update(kw)
    return InferenceEngine(model, params, EngineConfig(**base),
                           drafter=_WrongDrafter(cfg.vocab_size - 1))


def _self_repeating_reqs(model, params, tag, n=4, max_new=10):
    """Traffic whose acceptance stays high whatever the installed XLA
    rounds: constant prompts ``[t] * k`` for tokens ``t`` that the
    model's own greedy decode keeps repeating (a non-speculative pilot
    picks them), so the prompt-lookup draft IS what the model emits.
    A prompt that merely looks structured does not do that: the
    random-init model leaves ``[5, 6, 7, 8] * k`` for its own
    attractors, acceptance reads 0.64 and the cap steps down as
    designed."""
    pilot = _engine(model, params)
    candidates = (2, 17, 19, 37, 45, 46, 47, 49)
    for t in candidates:
        pilot.add_request(Request(uid=str(t), prompt=[t] * 12,
                                  max_new_tokens=max_new))
    out = pilot.run()
    tokens = [t for t in candidates if set(out[str(t)]) == {t}][:n]
    assert len(tokens) == n, out
    return [Request(uid=f"{tag}{i}", prompt=[t] * (8 + 4 * (i % 2)),
                    max_new_tokens=max_new)
            for i, t in enumerate(tokens)]


def test_spec_adapt_high_acceptance_bit_identical_to_static():
    cfg, model, params = _tiny_model()
    reqs = _self_repeating_reqs(model, params, "h")
    outs, stats = {}, {}
    for arm, kw in {"static": dict(spec_tokens=4),
                    "adapt": dict(spec_tokens=4, spec_adapt=True)}.items():
        engine = _engine(model, params, **kw)
        outs[arm] = _serve(engine, reqs)
        stats[arm] = engine.stats()
    assert outs["adapt"] == outs["static"]
    # acceptance stayed above the high threshold: the cap never moved,
    # and the SCHEDULE matched too (same dispatch count)
    assert stats["adapt"]["spec_cap"] == 4
    assert stats["adapt"]["num_spec_cap_shrinks"] == 0
    assert stats["adapt"]["draft_acceptance_rate"] > 0.8
    assert (stats["adapt"]["num_decode_dispatches"]
            == stats["static"]["num_decode_dispatches"])


def test_spec_adapt_caps_out_under_rejecting_drafter():
    cfg, model, params = _tiny_model()
    adapt = _adapt_engine(model, params, cfg, spec_adapt=True)
    rng = np.random.RandomState(3)
    reqs = [Request(uid=f"c{i}", prompt=list(rng.randint(0, 128, 8)),
                    max_new_tokens=30) for i in range(2)]
    for r in reqs:
        adapt.add_request(r)
    out = adapt.run()
    s = adapt.stats()
    # the cap walked all the way down (4 shrink steps), so the engine
    # stopped paying for spans it always rejects...
    assert s["spec_cap"] == 0
    assert s["num_spec_cap_shrinks"] == 4
    assert s["speculation_active"] == 1     # not quarantined: adaptive
    # ...while greedy output stays bit-identical to the non-speculative
    # engine (the rejection rule never let a wrong draft through)
    base = _engine(model, params)
    for r in reqs:
        base.add_request(Request(uid=r.uid, prompt=r.prompt,
                                 max_new_tokens=r.max_new_tokens))
    assert out == base.run()
    # a static engine with the same drafter keeps drafting full spans:
    # the adaptive engine drafted strictly less
    static = _adapt_engine(model, params, cfg)
    for r in reqs:
        static.add_request(Request(uid=r.uid, prompt=r.prompt,
                                   max_new_tokens=r.max_new_tokens))
    static.run()
    assert s["num_draft_tokens"] < static.stats()["num_draft_tokens"]


def test_spec_adapt_cap_rides_snapshot_overload_section():
    cfg, model, params = _tiny_model()
    a = _adapt_engine(model, params, cfg, spec_adapt=True)
    a.add_request(Request(uid="s", prompt=[3, 9, 4, 1, 7],
                          max_new_tokens=24))
    for _ in range(10):
        a.step()
    snap = a.snapshot()
    assert snap["overload"]["spec_cap"] < 4   # mid-walk
    # an adapting engine resumes the walk exactly...
    b = _adapt_engine(model, params, cfg, spec_adapt=True)
    b.restore(snap)
    assert b.stats()["spec_cap"] == snap["overload"]["spec_cap"]
    out_b = b.run()
    # ...and a NON-adapting engine ignores it (it could never restore
    # the cap — same guard shape as the ladder rung)
    c = _adapt_engine(model, params, cfg)
    c.restore(snap)
    assert c.stats()["spec_cap"] == 4
    out_c = c.run()
    # greedy continuation identical either way (and to uninterrupted)
    out_a = a.run()
    assert out_b == out_a == out_c
