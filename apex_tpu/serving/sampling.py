"""Token sampling for the decode loop: greedy / temperature / top-k /
top-p, fully vectorized and jit-stable.

Every knob is a *traced* per-slot array (``[B]``), never a static
argument: the continuous-batching engine serves requests with different
sampling settings from the same compiled decode program, so a request's
temperature must be data, not a trace constant. The whole sampler is
branch-free — greedy is the ``temperature <= 0`` lane of a ``where``,
top-k and top-p are masks over the descending-sorted logits — and runs
inside the engine's two jitted programs (a separately-jitted sampler
would be a third compilation, breaking the two-program contract
documented in docs/serving.md).

Three entry points share one filtering chain:

- :func:`sample_tokens` — one PRNG key for the whole batch. A row's
  draw still depends on its ROW INDEX (the key's Gumbel noise is laid
  out per row), so it is only reproducible while batch composition is
  fixed — fine for standalone use and the prefill path (``B == 1``).
- :func:`sample_tokens_per_lane` — one PRNG key PER ROW. A row's draw
  depends only on its own key and logits, never on which lane it
  occupies or what else shares the batch. The engine keys each lane by
  ``fold_in(request_key, token_index)``, which is what makes generation
  bit-for-bit identical across ``decode_steps`` settings, lane
  placements, and preemption/resume schedules (docs/serving.md).
- :func:`spec_verify_tokens` — the speculative-decoding accept rule
  (Leviathan et al.): given target logits for every candidate position
  of a drafted span, decide per lane how many draft tokens the target
  distribution accepts and sample the first-rejection correction (or
  the all-accepted bonus) token. Greedy lanes use the exact argmax
  equality test, so greedy speculative output is bit-identical to
  non-speculative greedy whenever the verify and decode programs
  agree on argmaxes (certified per backend — see the function
  docstring); sampled lanes use the
  rejection rule for a deterministic drafter (accept ``d`` with
  probability ``p(d)`` under the filtered target distribution, resample
  the rejection from ``p`` with ``d`` removed), which preserves the
  output distribution exactly.

Both batch entry points short-circuit to a plain ``argmax`` via
``jax.lax.cond`` when NO row samples (``temperature <= 0``
everywhere): the predicate is traced, so one compiled program serves
both regimes, but an all-greedy batch skips the sort/filter/softmax
chain at run time — a micro-win paid on every decode iteration and
every speculative verify step.

The sampler is deliberately MESH-OBLIVIOUS (docs/serving.md, "Mesh
sharding"): by the time logits reach it they are replicated — the
model's row-parallel projections all-reduced the last sharded
contraction — and every op here (argmax, the descending sort, the
rank/mass masks, the categorical draws) reduces over the UNSHARDED
vocabulary axis with per-lane keys, so the engine's sharded programs
sample bit-identically to the single-device ones at any mesh shape
and sampling adds zero collectives of its own.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration.

    ``temperature <= 0`` selects greedy decoding (argmax); ``top_k <= 0``
    disables the top-k filter; ``top_p >= 1`` disables nucleus
    filtering. Filters compose: top-k first, then top-p over what
    survives, matching the common serving convention.

    ``top_k`` values at or above the vocabulary size are equivalent to
    ``top_k = 0`` (disabled): the filter keeps the ``top_k``
    best-ranked tokens, and every token ranks inside ``top_k >= V``.
    ``validate()`` cannot clamp this — the vocabulary size is a model
    property the params object never sees — so the equivalence is the
    contract instead (regression-tested in tests/test_serving.py).
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0

    def validate(self) -> "SamplingParams":
        if self.top_p <= 0.0 or self.top_p > 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        return self


def _filtered_sorted_logits(logits, temperature, top_k, top_p):
    """The shared filtering chain: temperature-scale, sort descending,
    mask by top-k rank and top-p mass. Returns ``(filtered, order,
    greedy)`` where ``filtered`` are the sorted scaled logits with
    killed positions at ``-inf``, ``order`` maps sorted rank back to
    vocabulary id, and ``greedy`` is the plain argmax per row."""
    lg = logits.astype(jnp.float32)
    V = lg.shape[-1]
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)

    safe_t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = lg / safe_t

    # descending sort once; both filters are rank/mass masks over it
    order = jnp.argsort(-scaled, axis=-1)               # [B, V]
    sorted_lg = jnp.take_along_axis(scaled, order, axis=-1)
    rank = jax.lax.broadcasted_iota(jnp.int32, sorted_lg.shape, 1)
    # top_k >= V keeps every rank — the documented "disabled" alias
    k_eff = jnp.where(top_k > 0, top_k, V)[:, None]
    keep_k = rank < k_eff
    # nucleus mass is measured over the RENORMALIZED top-k survivors
    # (the HF warper-chain composition the docstring promises), not the
    # full-vocabulary distribution — otherwise combining the two knobs
    # keeps systematically more tail tokens than configured
    probs = jax.nn.softmax(jnp.where(keep_k, sorted_lg, -jnp.inf), axis=-1)
    # exclusive cumulative mass: a token stays while the mass BEFORE it
    # is under top_p, so the first token always survives
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    keep = keep_k & (cum_before < top_p[:, None])
    return jnp.where(keep, sorted_lg, -jnp.inf), order, greedy


def sample_tokens(logits, key, temperature, top_k, top_p):
    """Draw one token per row from a single shared key.

    Args:
      logits: ``[B, V]`` (any float dtype; filtering runs in fp32).
      key: a single PRNG key; rows draw independent categorical samples.
      temperature: ``[B]`` fp32; ``<= 0`` means greedy for that row.
      top_k: ``[B]`` int32; ``<= 0`` (or ``>= V``) disables.
      top_p: ``[B]`` fp32 nucleus mass; ``>= 1`` disables.

    Returns ``[B]`` int32 token ids.
    """
    greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)

    def _sampled(_):
        filtered, order, _ = _filtered_sorted_logits(
            logits, temperature, top_k, top_p)
        pos = jax.random.categorical(key, filtered, axis=-1)
        sampled = jnp.take_along_axis(order, pos[:, None], axis=-1)[:, 0]
        return jnp.where(temperature > 0.0, sampled, greedy).astype(jnp.int32)

    # all-greedy batches skip the whole sort/filter chain at run time;
    # greedy rows of mixed batches still take the argmax lane of the
    # where, so the fast path is bit-identical by construction (tested)
    return jax.lax.cond(jnp.any(temperature > 0.0), _sampled,
                        lambda _: greedy, None)


def sample_tokens_per_lane(logits, keys, temperature, top_k, top_p):
    """Draw one token per row, each row from ITS OWN key.

    Same filtering semantics as :func:`sample_tokens`; the difference is
    reproducibility scope. Row ``i`` draws ``categorical(keys[i],
    filtered[i])`` — no row-index dependence, no cross-row coupling —
    so a sequence keyed by per-request/per-token keys samples the same
    token no matter which batch lane it rides in, how many other lanes
    are live, or how many scan steps the dispatch fuses. This is the
    decode-side sampler of the multi-step fused decode program
    (docs/serving.md).

    Args:
      logits: ``[B, V]``.
      keys: ``[B]`` PRNG keys (a ``[B, 2]`` uint32 array for the
        threefry impl), one per row.
      temperature / top_k / top_p: as in :func:`sample_tokens`.

    Returns ``[B]`` int32 token ids.
    """
    greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)

    def _sampled(_):
        filtered, order, _ = _filtered_sorted_logits(
            logits, temperature, top_k, top_p)
        pos = jax.vmap(jax.random.categorical)(keys, filtered)
        sampled = jnp.take_along_axis(order, pos[:, None], axis=-1)[:, 0]
        return jnp.where(temperature > 0.0, sampled, greedy).astype(jnp.int32)

    return jax.lax.cond(jnp.any(temperature > 0.0), _sampled,
                        lambda _: greedy, None)


def spec_verify_tokens(logits, drafts, draft_lens, lane_keys, token_idx,
                       temperature, top_k, top_p):
    """The speculative-decoding accept/correct rule, vectorized over
    lanes and candidate positions.

    The target model scored a drafted span in ONE forward: position
    ``p`` of ``logits`` holds the target distribution for the lane's
    token index ``token_idx[:, p]`` given the carried token plus drafts
    ``0..p-1`` (the engine's verify dispatch arranges exactly this).
    Draft ``p`` (``p < S``) claims the token position ``p`` scores:

    - **greedy lanes** (``temperature <= 0``): accept iff the draft
      equals the position's argmax; the correction and bonus tokens are
      the argmax too. Since accepted drafts ARE the argmaxes, the
      emitted sequence is the non-speculative greedy sequence by
      induction — GIVEN that the verify forward and the scan's decode
      body agree on every position's argmax. That agreement is a
      numerical property of two differently-shaped compiled programs
      (the PR 4 scan-vs-standalone drift is the cautionary tale), so
      it is certified empirically per backend: the cross-K/spec
      bit-identity tests of tests/test_speculative.py on the CPU; no
      run on the chip holds it yet.
    - **sampled lanes**: accept draft ``d`` with probability ``p(d)``
      under the FILTERED target distribution (the same
      temperature/top-k/top-p chain non-speculative sampling draws
      from); a rejection resamples from ``p`` with ``d`` masked out —
      ``max(p - q, 0)`` renormalized, for a deterministic
      (point-mass) drafter ``q``. With all drafts accepted the bonus
      token is a FULL sample keyed exactly like the non-speculative
      token at that index, so a lane the drafter left empty emits a
      bit-identical token to the non-speculative engine even when
      sampling.

    Per-token randomness is keyed off ``fold_in(lane_key, token_idx)``
    (the engine's schedule-invariant chain): the accept uniform for a
    token index folds ``1`` on top, the rejection resample folds ``2``,
    and the full/bonus sample uses the base key unchanged — three
    independent streams, all invariant to lane placement,
    ``decode_steps``, and preemption/resume.

    Args:
      logits: ``[B, P, V]`` target logits, ``P = S + 1`` candidate
        positions (the carried token plus ``S`` draft slots).
      drafts: ``[B, S]`` int32 proposed tokens (padding arbitrary).
      draft_lens: ``[B]`` int32 valid proposals per lane (``<= S``).
      lane_keys: ``[B]`` per-request PRNG keys (``[B, 2]`` uint32).
      token_idx: ``[B, P]`` int32 generation index each position
        scores (``gen_count + p``).
      temperature / top_k / top_p: ``[B]`` as elsewhere.

    Returns ``(emitted, n_emit)``: ``emitted`` is ``[B, P]`` int32
    whose first ``n_emit[b]`` entries are lane ``b``'s tokens —
    ``n_acc`` accepted drafts then the correction/bonus token
    (``n_emit = n_acc + 1``); entries past ``n_emit`` are meaningless.
    EOS/budget truncation is the caller's job (the engine's stop-mask
    machinery owns it).
    """
    B, P, V = logits.shape
    S = P - 1
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)          # [B, P]
    # pad drafts to [B, P]: position S scores only the bonus token, its
    # "draft" is never consulted (n_acc <= draft_lens <= S)
    drafts_pad = jnp.concatenate(
        [drafts.astype(jnp.int32), jnp.zeros((B, 1), jnp.int32)], axis=1)

    def _greedy_only(_):
        return drafts_pad[:, :S] == greedy[:, :S], greedy, greedy

    def _with_sampled(_):
        flat = lg.reshape(B * P, V)
        t = jnp.repeat(temperature, P)
        k = jnp.repeat(top_k, P)
        p_ = jnp.repeat(top_p, P)
        filtered, order, _ = _filtered_sorted_logits(flat, t, k, p_)
        probs = jax.nn.softmax(filtered, axis=-1)       # killed ranks -> 0
        d_flat = drafts_pad.reshape(B * P)
        hit = order == d_flat[:, None]                  # rank of the draft
        p_draft = jnp.sum(jnp.where(hit, probs, 0.0), axis=-1)
        keys = jax.vmap(jax.random.fold_in)(
            jnp.repeat(lane_keys, P, axis=0), token_idx.reshape(-1))
        u = jax.vmap(
            lambda kk: jax.random.uniform(jax.random.fold_in(kk, 1)))(keys)
        accept_s = (u < p_draft).reshape(B, P)[:, :S]
        # rejection residual: the filtered distribution with the draft
        # token removed (max(p - q, 0) renormalized for point-mass q)
        resid = jnp.where(hit, -jnp.inf, filtered)
        pos_r = jax.vmap(lambda kk, l: jax.random.categorical(
            jax.random.fold_in(kk, 2), l))(keys, resid)
        corr_s = jnp.take_along_axis(
            order, pos_r[:, None], axis=-1)[:, 0].reshape(B, P)
        pos_f = jax.vmap(jax.random.categorical)(keys, filtered)
        full_s = jnp.take_along_axis(
            order, pos_f[:, None], axis=-1)[:, 0].reshape(B, P)
        sampled = (temperature > 0.0)[:, None]
        accept = jnp.where(sampled, accept_s,
                           drafts_pad[:, :S] == greedy[:, :S])
        corr = jnp.where(sampled, corr_s, greedy).astype(jnp.int32)
        full = jnp.where(sampled, full_s, greedy).astype(jnp.int32)
        return accept, corr, full

    accept, corr, full = jax.lax.cond(
        jnp.any(temperature > 0.0), _with_sampled, _greedy_only, None)
    valid = (jax.lax.broadcasted_iota(jnp.int32, (B, S), 1)
             < draft_lens[:, None])
    chain = jnp.cumprod((accept & valid).astype(jnp.int32), axis=1)
    n_acc = jnp.sum(chain, axis=1)                      # [B]
    # all valid drafts accepted -> bonus (full sample at position
    # n_acc); otherwise the rejection correction at position n_acc
    bonus = n_acc == draft_lens
    at = n_acc[:, None]
    final = jnp.where(
        bonus,
        jnp.take_along_axis(full, at, axis=1)[:, 0],
        jnp.take_along_axis(corr, at, axis=1)[:, 0])
    ii = jax.lax.broadcasted_iota(jnp.int32, (B, P), 1)
    emitted = jnp.where(ii < at, drafts_pad, final[:, None])
    return emitted.astype(jnp.int32), n_acc + 1
