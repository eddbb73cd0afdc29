"""``sdar_moe``-style decoder trained by the block-diffusion objective:
grouped-query attention with per-head RMSNorm of q and k and rotary
positions, then softmax-routed gated sparse experts, every layer alike; the
training path.

**The objective** (Arriola et al., *Block Diffusion*, ICLR 2025, which the
family's report adopts). A row ``x0`` of ``L`` ids is cut into blocks of
``block_length`` consecutive positions. Each block draws ``t ~ U(0, 1)``
and masks its positions independently with probability ``p = (1 - floor)
t + floor`` (:func:`diffusion_noise`: ``xt = where(masked, MASK, x0)``).
The model reads the TWO copies ``[xt ; x0]``, ``2 L`` positions that count
their rotary positions ``0 .. L - 1`` each, under the mask of
:class:`apex_tpu.ops.flash_attention.BlockDiffusionMask` (a noised block
sees itself both ways and the clean copy of strictly earlier blocks; the
clean copy is block-causal and sees nothing noised). The logits at noised
position ``i`` predict ``x0[i]`` (no shift) and ``loss = (1 / (rows x L))
sum over masked i of CE_i / p_i``, logits and loss in float32. What is
masked goes by the draw, never by comparing ids with ``mask_token_id``.

**The layer.** ``x + attention(input_layernorm(x))`` then ``x +
experts(post_attention_layernorm(x))``, the residual stream in the compute
dtype; one RMSNorm after the last layer; an untied head.

- :class:`BlockDiffAttention`: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` key/value heads of ``head_dim``; q and k normed
  over each head (one gain of ``head_dim`` each) and turned by their
  position (:func:`apex_tpu.models.lfm2.head_rms_norm`, ``rotary``: the
  same functions, each copy on its own so that both count from 0); ONE
  :func:`~apex_tpu.ops.flash_attention.flash_attention` call over the two
  copies under the mask description, so a noised query's softmax runs over
  its noised block and its clean prefix at once; past one tile the
  kernels' grid is the list of the mask's live tiles (288 of 1,024 a head
  at ``L`` = 8,192 in tiles of 512; 152 of 512 in the last layer), so no
  grid step is spent on a dead one; keys and values stay at their own
  head count into the kernel; no mask or score tensor exists.
- :class:`ExpertFFN`: the dropless share of
  :class:`apex_tpu.transformer.moe.DroplessMoE`, gated, scored by
  ``softmax`` over all ``num_experts`` in float32 and normalised over the
  chosen ``num_experts_per_tok`` (``norm_topk_prob``); this rank holds
  ``experts_held`` experts from ``expert_offset`` and adds their part
  only. No shared expert, no selection bias, no auxiliary loss.

In the LAST layer the clean copy's queries feed nothing (the head reads
the noised positions; only the clean keys and values are read), so that
layer projects q for the noised copy alone, attends ``L`` queries over the
``2 L`` keys (``BlockDiffusionMask(clean_queries=False)``) and runs its
output projection, residual and experts over ``L`` positions: the layer
SKIPS the clean copy's query-side work, which changes no loss and no
gradient (those rows' cotangent is zero).

Under amp O2 pass :func:`keep_fp32_filter` to ``amp.initialize``: every
RMSNorm gain (q's and k's among them) and the router stay float32.
Recomputation (``remat``, the class default): a layer keeps its matmul
outputs, flash attention's ``o`` + ``lse`` and its expert layer's routing
and ordered rows (:func:`~apex_tpu.transformer.remat.remat_routing_block`
under ``"selective"``) and recomputes the elementwise ops; the head and
the loss run one row at a time. Beside the loss the model returns the step
counters of :data:`apex_tpu.profiler.STEP_COUNTERS` and
:data:`~apex_tpu.profiler.DIFFUSION_COUNTERS` (use
``build_train_step(..., has_aux=True)``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu import profiler
from apex_tpu.amp.frontend import _default_norm_filter
from apex_tpu.models.lfm2 import head_rms_norm, rotary
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops.flash_attention import (BlockDiffusionMask,
                                          flash_attention, mha_reference)
from apex_tpu.transformer.moe import (DroplessMoE, add_step_counters,
                                      zero_step_counters)
from apex_tpu.transformer.remat import remat_routing_block

_INIT = nn.initializers.normal(stddev=0.02)


def keep_fp32_filter(path: str) -> bool:
    """amp O2's ``keep_fp32_filter`` for this family: the RMSNorm gains
    (both norms of a layer, the final one, q's and k's) and the router."""
    return path.rsplit("/", 1)[-1] == "router" or _default_norm_filter(path)


@dataclasses.dataclass(frozen=True)
class LinearNoise:
    """The linear schedule: a block at time ``t`` masks a position with
    probability ``p = (1 - floor) t + floor`` and weighs its loss ``1 /
    p``; ``t`` is drawn once a block."""

    floor: float = 1e-3


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1000000.0
    # experts
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    experts_held: Optional[int] = None       # None: all of them
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
    # the objective
    block_length: int = 4
    mask_token_id: Optional[int] = None      # None: the last id held
    noise: LinearNoise = LinearNoise()
    dtype: jnp.dtype = jnp.float32
    remat: bool = True
    fused_kernels: bool = True

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads is no multiple of "
                             "num_key_value_heads")
        if not 0 <= self.mask_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_id} is not among "
                             f"the {self.vocab_size} ids held")

    @property
    def mask_id(self) -> int:
        return (self.vocab_size - 1 if self.mask_token_id is None
                else self.mask_token_id)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=8, num_experts=8, num_experts_per_tok=2,
                    moe_intermediate_size=24)
        base.update(kw)
        return SdarConfig(**base)


def diffusion_noise(ids, seed, block_length, floor):
    """The step's draw for rows ``ids`` ``(B, L)``: ``(masked bool (B, L),
    p float32 (B, L))``. Row ``r`` draws from ``fold_in(PRNGKey(seed),
    r)`` split in two: ``t = uniform((L // block_length,))`` once a block,
    ``u = uniform((L,))`` once a position, float32; ``p = (1 - floor) t +
    floor`` repeated over the block; ``masked = u < p``. Threefry: the
    same bits on the CPU and on the chip. (``p``'s last bit goes by whether
    the compiler fuses the multiply-add; ``u`` is a multiple of ``2 **
    -23``, so a mask bit can tell two such ``p`` apart about once in
    ``10 ** 8`` positions.)"""
    B, L = ids.shape
    if L % block_length:
        raise ValueError(f"a row of {L} is no multiple of block_length "
                         f"({block_length})")
    root = jax.random.PRNGKey(seed, impl="threefry2x32")

    def row(r):
        k_t, k_u = jax.random.split(jax.random.fold_in(root, r))
        t = jax.random.uniform(k_t, (L // block_length,), jnp.float32)
        u = jax.random.uniform(k_u, (L,), jnp.float32)
        p = jnp.repeat((1.0 - floor) * t + floor, block_length)
        return u < p, p

    return jax.vmap(row)(jnp.arange(B))


def _dense(cfg, features, name):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=jnp.float32, kernel_init=_INIT, name=name)


def _norm(cfg, name):
    """RMSNorm whose gain lives at ``<name>/scale`` on both paths."""
    if cfg.fused_kernels:
        return FusedRMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps, name=name)
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
                      param_dtype=jnp.float32, name=name)


def _each_copy(fn, t, L):
    """``fn`` on each copy of ``t`` ``(B, copies x L, ...)`` as a row of
    its own: both copies count their positions from 0."""
    b = t.shape[0]
    return fn(t.reshape((-1, L) + t.shape[2:])).reshape((b, -1) + t.shape[2:])


class BlockDiffAttention(nn.Module):
    """Attention over the two copies ``h`` ``(B, 2 L, H)``. ``last``: the
    stack's last layer, whose queries are the noised copy alone; it
    returns ``(B, L, H)``."""

    cfg: SdarConfig
    last: bool = False

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        nq, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        b, two_l, _ = h.shape
        L = two_l // 2
        with jax.named_scope(profiler.BLOCKDIFF_ATTENTION):
            hq = h[:, :L] if self.last else h
            q = _dense(cfg, nq * d, "q_proj")(hq).reshape(b, -1, nq, d)
            k = _dense(cfg, nkv * d, "k_proj")(h).reshape(b, two_l, nkv, d)
            v = _dense(cfg, nkv * d, "v_proj")(h).reshape(b, two_l, nkv, d)
            q_gain = self.param("q_norm", nn.initializers.ones, (d,),
                                jnp.float32)
            k_gain = self.param("k_norm", nn.initializers.ones, (d,),
                                jnp.float32)
            with jax.named_scope(profiler.ATTN_QK_NORM):
                q = head_rms_norm(q, q_gain, cfg.rms_norm_eps)
                k = head_rms_norm(k, k_gain, cfg.rms_norm_eps)
            with jax.named_scope(profiler.ATTN_ROPE):
                turn = lambda t: rotary(t, cfg.rope_theta)  # noqa: E731
                q = _each_copy(turn, q, L).astype(cfg.dtype)
                k = _each_copy(turn, k, L).astype(cfg.dtype)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            mask = BlockDiffusionMask(L, cfg.block_length,
                                      clean_queries=not self.last)
            attend = flash_attention if cfg.fused_kernels else mha_reference
            ctx = attend(q, k, v, None, False, d ** -0.5,
                         score_mask=mask).astype(cfg.dtype)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, -1, nq * d)
            return _dense(cfg, cfg.hidden_size, "o_proj")(ctx)


class ExpertFFN(nn.Module):
    cfg: SdarConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        held = (cfg.num_experts if cfg.experts_held is None
                else cfg.experts_held)
        # the normed tokens: kept by a rematerialised layer, so that the
        # row gather reads what the forward pass read
        x = checkpoint_name(x, profiler.MOE_INPUT)
        return DroplessMoE(
            hidden_size=cfg.hidden_size,
            ffn_hidden_size=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
            experts_held=held, expert_offset=cfg.expert_offset,
            norm_topk_prob=cfg.norm_topk_prob, activation=jax.nn.silu,
            gated=True, score_function="softmax", dtype=cfg.dtype,
            name="experts")(x)


class SdarLayer(nn.Module):
    """Attention and then the experts, each with its norm and residual, on
    the two copies ``(B, 2 L, H)``; returns ``(x, counters)``. The last
    layer of a stack returns the noised copy alone, ``(B, L, H)``."""

    cfg: SdarConfig
    last: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        y = BlockDiffAttention(cfg, self.last, name="self_attn")(
            _norm(cfg, "input_layernorm")(x))
        if self.last:
            x = x[:, :x.shape[1] // 2]
        x = x + y.astype(x.dtype)
        y, counters = ExpertFFN(cfg, name="expert_ffn")(
            _norm(cfg, "post_attention_layernorm")(x))
        return x + y.astype(x.dtype), counters


class SdarModel(nn.Module):
    """Embedding of the two copies, the layers, the final RMSNorm of the
    noised copy. ``ids2`` is ``[xt ; x0]`` ``(B, 2 L)``; returns ``(hidden
    (B, L, H), counters)``."""

    cfg: SdarConfig

    @nn.compact
    def __call__(self, ids2):
        cfg = self.cfg
        table = self.param("embed_tokens", _INIT,
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = table[ids2].astype(cfg.dtype)
        layer_cls = (remat_routing_block(SdarLayer, "selective")
                     if cfg.remat else SdarLayer)
        total = zero_step_counters()
        for i in range(cfg.num_hidden_layers):
            x, counters = layer_cls(cfg, i == cfg.num_hidden_layers - 1,
                                    name=f"layers_{i}")(x)
            total = add_step_counters(total, counters)
        return _norm(cfg, "norm")(x), total


def blockdiff_lm_loss(hidden, head, labels, masked, p):
    """``(1 / (B L)) sum over masked positions of CE / p``, one row at a
    time: the head's matmul (``lm_head``) and the float32 logsumexp
    (``lm_loss`` / ``diffusion_loss``) of a row are recomputed in the
    backward pass, so one row's ``(L, V)`` logits are live at a time. The
    logits at position ``i`` predict ``labels[i]`` (no shift)."""
    B, L, _ = hidden.shape
    weight = jnp.where(masked, 1.0 / p, 0.0).astype(jnp.float32)

    def row(args):
        h, ids, w = args
        with jax.named_scope(profiler.LM_HEAD):
            logits = jnp.dot(h, head.astype(h.dtype),
                             preferred_element_type=jnp.float32)
        with jax.named_scope(profiler.LM_LOSS), \
                jax.named_scope(profiler.DIFFUSION_LOSS):
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, ids[:, None], axis=-1)
            return jnp.sum((lse - picked[:, 0]) * w)

    return jnp.sum(jax.lax.map(jax.checkpoint(row), (hidden, labels, weight))
                   ) / (B * L)


class SdarLMHeadModel(nn.Module):
    """The stack with its untied head. ``apply(params, ids, seed)`` gives
    ``(logits float32 (B, L, V) at the noised positions, counters)``;
    ``apply(params, ids, seed, method="loss")`` gives ``(loss, counters)``
    without ever holding the batch's logits. ``ids`` are the clean rows
    ``(B, L)``; ``seed`` (an int32 scalar, may be traced) draws the step's
    noise on the device."""

    cfg: SdarConfig

    def setup(self):
        self.model = SdarModel(self.cfg)
        self.lm_head = self.param(
            "lm_head", _INIT, (self.cfg.hidden_size, self.cfg.vocab_size),
            jnp.float32)

    def _noised(self, input_ids, seed):
        cfg = self.cfg
        with jax.named_scope(profiler.DIFFUSION_NOISE):
            masked, p = diffusion_noise(input_ids, seed, cfg.block_length,
                                        cfg.noise.floor)
            xt = jnp.where(masked, cfg.mask_id, input_ids)
            ids2 = jnp.concatenate([xt, input_ids], axis=1)
        x, counters = self.model(ids2)
        counters = dict(counters)
        counters[profiler.DIFFUSION_MASKED_TOKENS] = jnp.sum(
            masked, dtype=jnp.float32)
        return x, masked, p, counters

    def __call__(self, input_ids, seed):
        x, _, _, counters = self._noised(input_ids, seed)
        with jax.named_scope(profiler.LM_HEAD):
            logits = jnp.dot(x, self.lm_head.astype(x.dtype),
                             preferred_element_type=jnp.float32)
        return logits, counters

    def loss(self, input_ids, seed):
        x, masked, p, counters = self._noised(input_ids, seed)
        return blockdiff_lm_loss(x, self.lm_head, input_ids, masked,
                                 p), counters
