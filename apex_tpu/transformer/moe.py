"""Mixture-of-experts layer with expert parallelism.

The reference (apex) predates MoE and has no expert subsystem; this
module extends the Megatron-style transformer tier
(``apex/transformer/`` (U), SURVEY.md §2.3) with the one parallelism
axis the reference lacks, designed TPU-first:

- **Static-capacity routing** (Switch/GShard style): every expert
  processes exactly ``capacity`` token slots per step, so all shapes are
  static and XLA can tile every matmul onto the MXU. Overflow tokens are
  dropped (their combine weight is zero, the residual stream carries
  them through), underflow slots are zero-padded — the standard TPU
  trade against dynamic gather/scatter, which Mosaic cannot lower and
  XLA cannot tile.
- **Dispatch/combine as one-hot einsums**: token→slot routing is a
  (T, E, C) 0/1 tensor contracted on the MXU, not a scatter.
- **Expert parallelism over the ``expert`` mesh axis**
  (:data:`apex_tpu.transformer.parallel_state.EXPERT_AXIS`):
  ``jax.lax.all_to_all`` exchanges token slots so each rank computes only
  its local experts; with ``ep == 1`` no collective is emitted and the
  layer runs unchanged on a single device.
- **fp32 router**: gate logits/softmax/losses in float32 regardless of
  activation dtype (bf16 routing is known to destabilize training).

Beside it, :class:`DroplessMoE` is the **dropless expert-parallel share**:
a rank is told which experts it holds (``experts_held``,
``expert_offset``), routes over all of them, sorts its token-expert
assignments by expert and computes its own experts' rows by one grouped
matmul each way (:func:`grouped_matmul`: on the TPU a Pallas kernel
over 512-row tiles that runs only the tiles its groups fill, so the
matmul work follows the assignments that land here). No capacity,
no ``(T, E, C)`` tensor, no token dropped under any routing.

:class:`MoEMLP`'s losses follow the Switch Transformer recipe: ``aux_loss`` is the
load-balance term ``E * mean(fraction_dispatched * mean_gate_prob)``
(minimized at uniform routing, where it equals 1), ``z_loss`` is
``mean(logsumexp(logits)^2)`` to keep router logits from drifting.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu import profiler
from apex_tpu.transformer import parallel_state
from apex_tpu.utils.collectives import axis_is_bound, mark_varying


class RouterOutput(NamedTuple):
    """Routing decision for one batch of tokens.

    dispatch: (T, E, C) 0/1 — token t goes to slot c of expert e.
    combine:  (T, E, C) fp32 — dispatch scaled by the gate probability.
    aux_loss: scalar load-balance loss (Switch Transformer eq. 4-6).
    z_loss:   scalar router z-loss.
    """

    dispatch: jax.Array
    combine: jax.Array
    aux_loss: jax.Array
    z_loss: jax.Array


def route_top_k(logits, k: int, capacity: int) -> RouterOutput:
    """Top-k static-capacity routing (GShard order: the k-th choices of
    all tokens queue behind every token's (k-1)-th choice, so a token's
    primary expert is only dropped if the expert is full of primaries).

    logits: (T, E) fp32 router scores. Returns :class:`RouterOutput`.
    """
    T, E = logits.shape
    if k > E:
        raise ValueError(f"top-k ({k}) exceeds number of experts ({E}): "
                         "later rounds would re-dispatch expert 0")
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)

    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    remaining = probs
    used = jnp.zeros((T, E), jnp.float32)  # experts already chosen per token
    fill = jnp.zeros((E,), jnp.float32)    # slots already taken per expert
    frac_dispatched = jnp.zeros((E,), jnp.float32)

    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)            # (T,)
        mask = jax.nn.one_hot(choice, E, dtype=jnp.float32)
        gate = jnp.sum(probs * mask, axis=-1)              # (T,)
        # arrival order within the expert, offset by earlier rounds' fill
        order = jnp.cumsum(mask, axis=0) * mask            # 1-based
        position = order + fill[None, :] * mask - 1.0
        keep = (position < capacity) & (mask > 0)
        position = jnp.where(keep, position, 0).astype(jnp.int32)
        keepf = keep.astype(jnp.float32)                   # (T, E)
        slot = jax.nn.one_hot(position, capacity, dtype=jnp.float32)
        contrib = mask[:, :, None] * keepf[:, :, None] * slot
        dispatch = dispatch + contrib
        combine = combine + contrib * gate[:, None, None]
        frac_dispatched = frac_dispatched + jnp.sum(mask, axis=0) / T
        fill = fill + jnp.sum(mask * keepf, axis=0)
        used = used + mask
        remaining = jnp.where(used > 0, -jnp.inf, remaining)

    # Switch load-balance loss over the PRIMARY assignment distribution
    mean_prob = jnp.mean(probs, axis=0)                    # (E,)
    aux_loss = E * jnp.sum((frac_dispatched / k) * mean_prob)
    z = jax.nn.logsumexp(logits, axis=-1)
    return RouterOutput(dispatch, combine, aux_loss, jnp.mean(z * z))


class MoEMLP(nn.Module):
    """Mixture-of-experts MLP block (drop-in for a dense transformer MLP).

    ``num_experts`` is the GLOBAL expert count; with expert parallelism
    each rank holds ``num_experts // ep`` experts, initialized from a
    rank-folded key (experts are decorrelated across ranks by design —
    unlike TP shards, expert weights are independent parameters, not
    slices of a master matrix). Token slots travel between ranks via
    ``all_to_all`` over :data:`parallel_state.EXPERT_AXIS`.

    Composes with tensor parallelism (Megatron TPxEP): when the mesh has
    ``tp > 1``, each expert's FFN is additionally column/row-split over
    the ``tensor`` axis (master-weight init: the full per-expert matrix
    from the shared key, tp rank slices its shard) and the row-parallel
    partials are psum'd. Input tokens must then be REPLICATED over the
    tensor axis (the usual Megatron placement: MoE sits where activations
    are tp-replicated; compose with SP gather/scatter outside if used).

    Expert-parallel gradient flow: expert params are varying over the
    ``expert`` (and, with tp>1, ``tensor``) axes; their cotangents stay
    per-rank (no sync needed beyond ``data``-axis DP, see
    :func:`parallel_state.get_expert_data_parallel_group`).
    """

    hidden_size: int
    ffn_hidden_size: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    activation: Callable = nn.gelu
    router_jitter: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    params_dtype: jnp.dtype = jnp.float32
    # tp>1 only: False skips materializing the full per-expert matrix at
    # init (same escape hatch as tensor_parallel.layers for weights too
    # large per rank). Variance-correct either way here: the init scales
    # by the FULL fan-in explicitly, not shard shape.
    master_weight_init: bool = True

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        """x: (..., hidden) -> (y, aux_loss, z_loss). Flattens leading
        dims to a token axis internally."""
        ep = parallel_state.get_expert_model_parallel_world_size()
        # Abstract tracing outside shard_map (eval_shape for spec trees):
        # the expert axis is unbound, so skip collectives/rank folding —
        # every op in the skipped set is shape-preserving, so derived
        # shapes stay correct.
        bound = ep == 1 or axis_is_bound(parallel_state.EXPERT_AXIS)
        E, H, F = self.num_experts, self.hidden_size, self.ffn_hidden_size
        if E % ep != 0:
            raise ValueError(
                f"num_experts ({E}) not divisible by expert parallel size "
                f"({ep})")
        if self.top_k > E:
            raise ValueError(
                f"top_k ({self.top_k}) exceeds num_experts ({E})")
        e_local = E // ep

        lead = x.shape[:-1]
        tokens = x.reshape(-1, H)
        T = tokens.shape[0]
        capacity = max(1, int(-(-self.top_k * T * self.capacity_factor
                                // E)))  # ceil, static

        # --- router (fp32, replicated over the expert axis) ---
        wr = self.param("router", nn.initializers.normal(stddev=0.02),
                        (H, E), self.params_dtype)
        logits = tokens.astype(jnp.float32) @ wr.astype(jnp.float32)
        if self.router_jitter and not deterministic:
            key = self.make_rng("dropout")
            logits = logits * jax.random.uniform(
                key, logits.shape, jnp.float32,
                1.0 - self.router_jitter, 1.0 + self.router_jitter)
        routing = route_top_k(logits, self.top_k, capacity)

        # --- expert weights: e_local experts per rank (rank-folded key),
        # each expert's FFN optionally tensor-parallel: w1 column-split /
        # w2 row-split over the ``tensor`` axis (Megatron TPxEP grouped
        # GEMM), using the same master-weight init scheme as
        # tensor_parallel.layers — the full per-expert matrix is drawn
        # from the (ep-folded) key and the tp rank slices its shard, so
        # fan-in scaling sees the full matrix and the assembled weight is
        # independent of tp.
        tp = parallel_state.get_tensor_model_parallel_world_size()
        tp_bound = tp == 1 or axis_is_bound(parallel_state.TENSOR_AXIS)
        if F % tp != 0:
            raise ValueError(
                f"ffn_hidden_size ({F}) not divisible by tensor parallel "
                f"size ({tp})")
        f_local = F // tp

        def expert_init(slice_axis):
            # the same master-weight scheme as tensor_parallel.layers.
            # _master_init, inlined because the full fan-in (full[1]) is
            # known here even on the per-shard fallback path, which makes
            # master_weight_init=False variance-correct (unlike generic
            # fan-scaled initializers over a shard shape)
            def init(key, s, d):
                if ep > 1 and bound:
                    key = jax.random.fold_in(
                        key, parallel_state.get_expert_model_parallel_rank())
                full = list(s)
                full[slice_axis] = full[slice_axis] * tp
                scale = 1.0 / jnp.sqrt(full[1])  # FULL per-expert fan-in
                if tp == 1:
                    return jax.random.normal(key, tuple(full), d) * scale
                if not self.master_weight_init:
                    if tp_bound:
                        key = jax.random.fold_in(
                            key,
                            parallel_state.get_tensor_model_parallel_rank())
                    return jax.random.normal(key, s, d) * scale
                w = jax.random.normal(key, tuple(full), d) * scale
                starts = [0] * len(full)
                if tp_bound:
                    starts[slice_axis] = (
                        parallel_state.get_tensor_model_parallel_rank()
                        * s[slice_axis])
                return jax.lax.dynamic_slice(w, starts, s)
            return init

        w1 = self.param("w1", expert_init(2), (e_local, H, f_local),
                        self.params_dtype)
        b1 = self.param("b1", nn.initializers.zeros, (e_local, f_local),
                        self.params_dtype)
        w2 = self.param("w2", expert_init(1), (e_local, f_local, H),
                        self.params_dtype)
        b2 = self.param("b2", nn.initializers.zeros, (e_local, H),
                        self.params_dtype)
        if ep > 1 and bound:
            w1, b1, w2, b2 = mark_varying(
                (w1, b1, w2, b2), parallel_state.EXPERT_AXIS)
        if tp > 1 and tp_bound:
            w1, b1, w2 = mark_varying((w1, b1, w2),
                                      parallel_state.TENSOR_AXIS)

        def a2a(t):
            """all_to_all over the expert axis (identity when tracing
            outside shard_map — shape-preserving, so eval_shape-derived
            spec trees stay correct)."""
            if not bound:
                return t
            return jax.lax.all_to_all(t, parallel_state.EXPERT_AXIS,
                                      split_axis=0, concat_axis=0,
                                      tiled=False)

        # --- dispatch: (T, E, C) x (T, H) -> (E, C, H) on the MXU ---
        slots = jnp.einsum("tec,th->ech",
                           routing.dispatch.astype(self.dtype),
                           tokens.astype(self.dtype))
        if ep > 1:
            # (E, C, H) -> (ep, e_local, C, H); all_to_all swaps the ep
            # shard dim for the token-source dim: each rank ends up with
            # ITS experts' slots from ALL ep ranks.
            slots = a2a(slots.reshape(ep, e_local, capacity, H))
            # (ep_src, e_local, C, H) -> (e_local, ep_src*C, H): each local
            # expert batches its slots from every source rank
            slots = slots.transpose(1, 0, 2, 3).reshape(
                e_local, ep * capacity, H)

        # --- expert computation (batched over local experts; with tp>1
        # each rank computes its f_local slice and the row-parallel
        # partials are psum'd over the tensor axis, bias added once) ---
        h = jnp.einsum("ech,ehf->ecf", slots, w1.astype(self.dtype))
        h = self.activation(h + b1[:, None, :].astype(self.dtype))
        out = jnp.einsum("ecf,efh->ech", h, w2.astype(self.dtype))
        if tp > 1 and tp_bound:
            out = jax.lax.psum(out, parallel_state.TENSOR_AXIS)
        out = out + b2[:, None, :].astype(self.dtype)

        if ep > 1:
            # (e_local, ep_src*C, H) -> (ep_src, e_local, C, H), send each
            # source rank's slots home; after the exchange dim0 indexes the
            # expert's OWNER rank, so the flat view is global expert order.
            out = a2a(out.reshape(e_local, ep, capacity, H)
                      .transpose(1, 0, 2, 3))
            out = out.reshape(E, capacity, H)

        # --- combine: weighted un-dispatch back to token order ---
        y = jnp.einsum("ech,tec->th", out.astype(jnp.float32),
                       routing.combine)
        return (y.astype(self.dtype).reshape(*lead, H),
                routing.aux_loss, routing.z_loss)


# ---------------------------------------------------------------------------
# dropless routing over a share of the experts
# ---------------------------------------------------------------------------

def squared_relu(x):
    return jnp.square(jax.nn.relu(x))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_slots(tokens, perm, inv_perm, k):
    """``tokens[perm // k]``: row ``p`` of the sorted buffer is the token of
    assignment slot ``perm[p]`` (slot ``t * k + j`` is token ``t``'s
    ``j``-th choice). ``perm`` is a permutation of the ``T * k`` slots, so
    the transpose is a gather by ``inv_perm`` and a sum over a token's
    ``k`` slots, not a scatter-add."""
    return tokens[perm // k]


def _rows_of_slots_fwd(tokens, perm, inv_perm, k):
    return tokens[perm // k], (perm, inv_perm)


def _rows_of_slots_bwd(k, res, g):
    _, inv_perm = res
    by_slot = g[inv_perm].reshape(-1, k, g.shape[-1])
    return (jnp.sum(by_slot.astype(jnp.float32), axis=1).astype(g.dtype),
            None, None)


_rows_of_slots.defvjp(_rows_of_slots_fwd, _rows_of_slots_bwd)


@jax.custom_vjp
def _permute_rows(x, perm, inv_perm):
    """``x[perm]`` for a permutation; transposed by a gather too."""
    return x[perm]


_permute_rows.defvjp(
    lambda x, perm, inv_perm: (x[perm], (perm, inv_perm)),
    lambda res, g: (g[res[1]], None, None))


@jax.custom_vjp
def _permute_scalars(x, perm, inv_perm):
    """``x[perm]`` for a permutation of scalars, as a sort by ``inv_perm``
    (and transposed by a sort by ``perm``): on a v5e a sort of the 98,304
    slots takes a tenth of the time a gather of as many scalars does."""
    return jax.lax.sort((inv_perm, x), num_keys=1)[1]


_permute_scalars.defvjp(
    lambda x, perm, inv_perm: (_permute_scalars(x, perm, inv_perm), perm),
    lambda perm, g: (jax.lax.sort((perm, g), num_keys=1)[1], None, None))


# rows, contraction and output tile of the TPU grouped-matmul kernel
_GMM_TILING = (512, 1024, 1024)


def grouped_matmul(rows, weights, group_sizes):
    """``rows[group g] @ weights[g]`` for rows sorted by group: ``rows``
    (R, K), ``weights`` (G, K, N), ``group_sizes`` (G,) int32 whose sum may
    be less than R; rows past the last group come out zero.

    Lowered for a TPU this is the Pallas grouped matmul that ships with
    JAX (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and
    for the rows' gradient, ``tgmm`` for the weights'): its grid runs over
    the row tiles the groups fill and no others, so the work follows the
    assignments. The rows no held group fills go in as one more group
    with no weights of its own, which is the kernel's own case of
    sharded experts. Anywhere else it is ``jax.lax.ragged_dot``. (On the
    TPU ``ragged_dot`` is a native grouped kernel too, but XLA names its
    calls ``ragged-dot-none`` with no scope, so a trace cannot file them
    under ``moe_experts``; it also ran at 1.5 ms a call here against the
    0.2 ms its work would take.)"""
    def on_tpu(rows, weights, group_sizes):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        n = rows.shape[0]
        pad = -n % _GMM_TILING[0]          # whole row tiles (none at T * k)
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        rest = n + pad - jnp.sum(group_sizes)
        sizes = jnp.concatenate([group_sizes, rest[None]])
        return gmm(rows, weights, sizes, rows.dtype, _GMM_TILING)[:n]

    return jax.lax.platform_dependent(
        rows, weights, group_sizes, tpu=on_tpu,
        default=jax.lax.ragged_dot)


def zero_step_counters():
    """The step counters of :data:`apex_tpu.profiler.STEP_COUNTERS` before
    any expert layer has reported."""
    return {name: jnp.float32(0.0) for name in profiler.STEP_COUNTERS}


def add_step_counters(total, counters):
    """``total`` with one expert layer's ``counters`` folded in: the
    assignments held and the tokens dropped add up, the fullest expert's
    load over the mean is the worst layer's."""
    total = dict(total)
    for name in (profiler.MOE_ASSIGNMENTS_HELD, profiler.MOE_TOKENS_DROPPED):
        total[name] = total[name] + counters[name]
    name = profiler.MOE_LOAD_MAX_OVER_MEAN
    total[name] = jnp.maximum(total[name], counters[name])
    return total


# the router's score of a token over ALL the experts, float32 in and out
_SCORE_FUNCTIONS = {"sigmoid": jax.nn.sigmoid,
                    "softmax": functools.partial(jax.nn.softmax, axis=-1)}


class DroplessMoE(nn.Module):
    """One rank's share of a dropless mixture-of-experts layer.

    The router scores every token over all ``num_experts`` experts in
    float32 - ``score_function`` ``"sigmoid"`` (each expert alone) or
    ``"softmax"`` (over all the experts), which the model sets from its
    family - picks the ``top_k`` largest of ``score + selection_bias`` and
    weighs them by their scores, normalised over the chosen ``top_k``
    (``norm_topk_prob``: ``s_i / (sum + norm_topk_eps)``) and times
    ``routed_scaling_factor``. This rank holds experts
    ``expert_offset .. expert_offset + experts_held - 1`` and returns the
    part of the sum that THEY give, ``sum_{i chosen, held} w_i expert_i(h)``,
    with no bias anywhere. An expert has one of two forms, which the model
    sets from its family (``gated``):

    * plain: ``W_down_i act(W_up_i h)``, parameters ``w_up (held, H, F)``
      and ``w_down (held, F, H)``;
    * gated: ``W_down_i (act(W_gate_i h) * W_up_i h)``, parameters
      ``w_gate_up (held, H, 2F)`` - the gate's columns, then the up
      projection's, so that both are ONE grouped matmul - and ``w_down``.

    What absent experts would add is another rank's part; the exchange
    that sums the parts goes around this layer.

    Every assignment that lands on a held expert is computed: the sorted
    buffer has one row for each of the ``T * top_k`` slots (the most that
    can land here), and the grouped matmuls run over the rows the held
    groups fill. A row is weighed before its down projection (``W_down_i
    (w_i a_i)``, the weight applied in float32 at the hidden width, with
    the gate where there is one), so the combine is a gather and a sum and
    the projection's output is no residual. What the backward pass needs
    beside the input carries a ``checkpoint_name``
    (:data:`apex_tpu.profiler.MOE_RESIDUALS`: the routing and the first
    grouped matmul's output, ``(slots, F)`` or ``(slots, 2F)``), for a
    rematerialised block to keep
    (:func:`apex_tpu.transformer.remat.remat_routing_block`).
    Returns ``(y, counters)``; the counters are
    ``assignments_held`` (token-expert pairs computed here),
    ``load_max_over_mean`` (the fullest held expert over the mean) and
    ``tokens_dropped`` (held assignments left out of the buffer: 0).
    """

    hidden_size: int
    ffn_hidden_size: int
    num_experts: int
    top_k: int
    experts_held: int
    expert_offset: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    activation: Callable = squared_relu
    dtype: jnp.dtype = jnp.bfloat16
    params_dtype: jnp.dtype = jnp.float32
    gated: bool = False
    norm_topk_eps: float = 1e-20
    score_function: str = "sigmoid"

    @nn.compact
    def __call__(self, x, selection_bias=None):
        E, H, F = self.num_experts, self.hidden_size, self.ffn_hidden_size
        k, held, first = self.top_k, self.experts_held, self.expert_offset
        if not 0 <= first <= first + held <= E:
            raise ValueError(f"experts {first}..{first + held - 1} are not "
                             f"among the {E} experts")
        if k > E:
            raise ValueError(f"top_k ({k}) exceeds num_experts ({E})")
        if self.score_function not in _SCORE_FUNCTIONS:
            raise ValueError(f"score_function {self.score_function!r}: one "
                             f"of {sorted(_SCORE_FUNCTIONS)}")
        init = nn.initializers.normal(stddev=0.02)
        router = self.param("router", init, (H, E), self.params_dtype)
        if self.gated:
            w_in = self.param("w_gate_up", init, (held, H, 2 * F),
                              self.params_dtype)
        else:
            w_in = self.param("w_up", init, (held, H, F), self.params_dtype)
        w_down = self.param("w_down", init, (held, F, H), self.params_dtype)

        lead = x.shape[:-1]
        tokens = x.reshape(-1, H).astype(self.dtype)
        T = tokens.shape[0]
        slots = T * k

        # the ``checkpoint_name``s below are ``profiler.MOE_RESIDUALS``
        with jax.named_scope(profiler.MOE_ROUTER):
            scores = _SCORE_FUNCTIONS[self.score_function](jnp.dot(
                tokens.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            chosen_by = scores if selection_bias is None else (
                scores + selection_bias.astype(jnp.float32))
            _, chosen = jax.lax.top_k(chosen_by, k)               # (T, k)
            chosen = checkpoint_name(chosen, profiler.MOE_CHOSEN)
            weight = jnp.take_along_axis(scores, chosen, axis=-1)
            if self.norm_topk_prob:
                weight = weight / (jnp.sum(weight, -1, keepdims=True)
                                   + self.norm_topk_eps)
            weight = weight * self.routed_scaling_factor

        with jax.named_scope(profiler.MOE_DISPATCH):
            local = chosen - first
            here = (local >= 0) & (local < held)
            # held assignments first, grouped by expert; the rest behind
            key = jnp.where(here, local, held).reshape(slots)
            perm = checkpoint_name(
                jnp.argsort(key, stable=True).astype(jnp.int32),
                profiler.MOE_PERM)
            inv_perm = checkpoint_name(
                jnp.argsort(perm).astype(jnp.int32), profiler.MOE_INV_PERM)
            # a slot's weight in the sorted order; zero off the held experts
            w_row = checkpoint_name(_permute_scalars(
                jnp.where(here, weight, 0.0).reshape(slots), perm, inv_perm),
                profiler.MOE_WEIGHTS)
            group_sizes = checkpoint_name(jnp.sum(
                key[:, None] == jnp.arange(held, dtype=key.dtype), axis=0,
                dtype=jnp.int32), profiler.MOE_GROUP_SIZES)
            n_here = jnp.sum(group_sizes)
            rows = _rows_of_slots(tokens, perm, inv_perm, k)

        with jax.named_scope(profiler.MOE_EXPERTS):
            h = checkpoint_name(
                grouped_matmul(rows, w_in.astype(self.dtype), group_sizes),
                profiler.MOE_HIDDEN)
            # a row is weighed BEFORE its down projection, in float32 at
            # the hidden width, and rounded once where the grouped
            # matmul's operand was rounded already: sum_i w_i W_i a_i =
            # sum_i W_i (w_i a_i), so the projection's output is no
            # residual of the combine
            if self.gated:
                a = (self.activation(h[:, :F].astype(jnp.float32))
                     * h[:, F:].astype(jnp.float32))
            else:
                a = self.activation(h.astype(jnp.float32))
            a = (a * w_row[:, None]).astype(self.dtype)
            out = grouped_matmul(a, w_down.astype(self.dtype), group_sizes)

        with jax.named_scope(profiler.MOE_COMBINE):
            # rows no held group fills are zero (`grouped_matmul`), in
            # the backward pass too, and their weight is zero besides
            by_slot = _permute_rows(out, inv_perm, perm).reshape(T, k, H)
            y = jnp.sum(by_slot.astype(jnp.float32), axis=1)

        loads = group_sizes.astype(jnp.float32)
        counters = {
            profiler.MOE_ASSIGNMENTS_HELD: n_here.astype(jnp.float32),
            profiler.MOE_LOAD_MAX_OVER_MEAN:
                jnp.max(loads) / jnp.maximum(jnp.mean(loads), 1.0),
            profiler.MOE_TOKENS_DROPPED:
                (jnp.sum(here) - n_here).astype(jnp.float32),
        }
        return y.astype(self.dtype).reshape(*lead, H), counters
