"""``lfm2_moe``-style hybrid decoder: gated short convolutions and
grouped-query attention as mixers, a dense gated MLP in the leading
layers and gated sparse experts after, for the training path.

A layer is a mixer and then a feed-forward, each with its own RMSNorm
and residual (``x + mixer(operator_norm(x))``, then ``x +
ffn(ffn_norm(x))``); the residual stream stays in the compute dtype. The
mixer of layer ``i`` is ``layer_types[i]`` (HF ``layer_types``); its
feed-forward is dense for ``i < num_dense_layers``, the experts after.
One more RMSNorm follows the last layer (the family calls it
``embedding_norm``), then the head, which is the embedding (tied).

- **Gated short convolution** (:class:`ShortConvMixer`, ``conv``):
  ``in_proj -> [B | C | x]``, ``y = C * conv(B * x)`` depthwise and causal
  over ``conv_L_cache`` taps with no bias and no activation
  (:func:`apex_tpu.ops.short_conv.gated_short_conv`: one kernel pass
  forward, one backward), ``out_proj``.
- **Attention** (:class:`AttentionMixer`, ``full_attention``):
  ``num_attention_heads`` query heads on ``num_key_value_heads``
  key/value heads; RMSNorm over each head of q and of k (one gain of
  ``head_dim`` each), then rotary positions over the whole head in the
  half-split pairing (element ``i`` with ``i + head_dim / 2``), angles in
  float32; causal :func:`apex_tpu.ops.flash_attention.flash_attention`,
  which reads a key/value group by index and makes no repeated copy.
- **Dense feed-forward** (:class:`DenseMLP`): ``W2 (silu(W1 h) * W3 h)``,
  ``W1`` and ``W3`` side by side in one matrix (``gate_up``).
- **Experts** (:class:`ExpertFFN`): the dropless share of
  :class:`apex_tpu.transformer.moe.DroplessMoE` in its gated form (this
  rank holds ``experts_held`` experts from ``expert_offset`` and adds
  their part only): sigmoid scores over all ``num_experts``, the
  ``num_experts_per_tok`` largest of score + ``expert_bias`` (a float32
  buffer with no gradient and no update rule here: it stays where it is
  initialised, at zero), weights normalised over the chosen; no shared
  expert, no auxiliary loss.

Under amp O2 pass :func:`keep_fp32_filter` to ``amp.initialize``: every
RMSNorm gain (the q and k gains among them), the router, the expert bias
and the convolution's taps stay float32.

Recomputation (``remat``, the class default): a layer keeps its matmul
outputs and flash attention's ``o`` + ``lse``
(``transformer/remat.py``: ``"selective"``) and recomputes the
elementwise ops; an expert layer keeps its routing and the rows it
ordered besides (:func:`~apex_tpu.transformer.remat.remat_routing_block`).
The loss (:meth:`Lfm2LMHeadModel.loss`) runs the head and the
cross-entropy one sequence at a time
(:func:`apex_tpu.models.nemotron_h.blocked_lm_loss`). Beside the loss the
model returns the step counters of :data:`apex_tpu.profiler.STEP_COUNTERS`
(use ``build_train_step(..., has_aux=True)``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu import profiler
from apex_tpu.amp.frontend import _default_norm_filter
from apex_tpu.models.nemotron_h import blocked_lm_loss
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops.flash_attention import flash_attention, mha_reference
from apex_tpu.ops.short_conv import (gated_short_conv,
                                     gated_short_conv_reference)
from apex_tpu.transformer.moe import (DroplessMoE, add_step_counters,
                                      zero_step_counters)
from apex_tpu.transformer.remat import remat_block, remat_routing_block

_INIT = nn.initializers.normal(stddev=0.02)
_FP32_LEAVES = ("router", "expert_bias", "conv_kernel")
CONV, ATTENTION = "conv", "full_attention"


def keep_fp32_filter(path: str) -> bool:
    """amp O2's ``keep_fp32_filter`` for this family: the RMSNorm gains
    (both norms of a layer, the final one, q's and k's), the router, the
    expert bias and the convolution's taps."""
    return (path.rsplit("/", 1)[-1] in _FP32_LEAVES
            or _default_norm_filter(path))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (CONV, CONV, ATTENTION, CONV, CONV, CONV)
    num_dense_layers: int = 2
    intermediate_size: int = 11776
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1000000.0
    # short convolution
    conv_L_cache: int = 3
    # experts
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    experts_held: Optional[int] = None       # None: all of them
    expert_offset: int = 0
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    remat: bool = True
    fused_kernels: bool = True

    def __post_init__(self):
        bad = set(self.layer_types) - {CONV, ATTENTION}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types {self.layer_types!r}: "
                             f"{CONV!r} and {ATTENTION!r} only")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is no multiple of "
                             "num_attention_heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=32,
                    layer_types=(CONV, ATTENTION, CONV), num_dense_layers=1,
                    intermediate_size=48, num_attention_heads=4,
                    num_key_value_heads=2, num_experts=8,
                    num_experts_per_tok=2, moe_intermediate_size=24)
        base.update(kw)
        return Lfm2Config(**base)


def _dense(cfg, features, name):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=jnp.float32, kernel_init=_INIT, name=name)


def _norm(cfg, name):
    """RMSNorm whose gain lives at ``<name>/scale`` on both paths."""
    if cfg.fused_kernels:
        return FusedRMSNorm(cfg.hidden_size, eps=cfg.norm_eps, name=name)
    return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                      param_dtype=jnp.float32, name=name)


class _InProj(nn.Module):
    """``in_proj: H -> [B | C | x]``: one matrix, its three column blocks
    applied one by one, so that no pass over the tokens splits or joins
    them at three times the width."""

    cfg: Lfm2Config

    @nn.compact
    def __call__(self, x):
        H, dtype = self.cfg.hidden_size, self.cfg.dtype
        kernel = self.param("kernel", _INIT, (H, 3 * H), jnp.float32)
        kernel = kernel.astype(dtype)
        return tuple(jnp.dot(x.astype(dtype), kernel[:, i * H:(i + 1) * H])
                     for i in range(3))


class ShortConvMixer(nn.Module):
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        with jax.named_scope(profiler.CONV_IN_PROJ):
            b, c, xs = _InProj(cfg, name="in_proj")(x)
        taps = self.param("conv_kernel", _INIT,
                          (cfg.conv_L_cache, cfg.hidden_size), jnp.float32)
        conv = (gated_short_conv if cfg.fused_kernels
                else gated_short_conv_reference)
        y = conv(b, c, xs, taps)
        with jax.named_scope(profiler.CONV_OUT_PROJ):
            return _dense(cfg, cfg.hidden_size, "out_proj")(y)


def head_rms_norm(t, gain, eps):
    """RMSNorm over the last axis (one head) in float32; float32 out."""
    t = t.astype(jnp.float32)
    return (t * jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True)
                              + eps) * gain.astype(jnp.float32))


def rotary(t, theta):
    """Rotary positions on ``t`` ``(batch, tokens, heads, head_dim)``,
    float32 in and out: position ``p`` turns the pair (element ``i``,
    element ``i + head_dim / 2``) by ``p * theta ** (-2 i / head_dim)``."""
    d = t.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    lo, hi = t[..., :d // 2], t[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


class AttentionMixer(nn.Module):
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        nq, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        b, l, _ = x.shape
        with jax.named_scope(profiler.GQA_ATTENTION):
            q = _dense(cfg, nq * d, "q")(x).reshape(b, l, nq, d)
            k = _dense(cfg, nkv * d, "k")(x).reshape(b, l, nkv, d)
            v = _dense(cfg, nkv * d, "v")(x).reshape(b, l, nkv, d)
            q_gain = self.param("q_norm", nn.initializers.ones, (d,),
                                jnp.float32)
            k_gain = self.param("k_norm", nn.initializers.ones, (d,),
                                jnp.float32)
            with jax.named_scope(profiler.ATTN_QK_NORM):
                q = head_rms_norm(q, q_gain, cfg.norm_eps)
                k = head_rms_norm(k, k_gain, cfg.norm_eps)
            with jax.named_scope(profiler.ATTN_ROPE):
                q = rotary(q, cfg.rope_theta).astype(cfg.dtype)
                k = rotary(k, cfg.rope_theta).astype(cfg.dtype)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            attend = flash_attention if cfg.fused_kernels else mha_reference
            ctx = attend(q, k, v, None, True, d ** -0.5).astype(cfg.dtype)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, l, nq * d)
            return _dense(cfg, cfg.hidden_size, "out")(ctx)


class DenseMLP(nn.Module):
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        F = cfg.intermediate_size
        with jax.named_scope(profiler.MLP_DENSE):
            gu = _dense(cfg, 2 * F, "gate_up")(x)
            a = (jax.nn.silu(gu[..., :F].astype(jnp.float32))
                 * gu[..., F:].astype(jnp.float32)).astype(cfg.dtype)
            return _dense(cfg, cfg.hidden_size, "down")(a), None


class ExpertFFN(nn.Module):
    cfg: Lfm2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        held = (cfg.num_experts if cfg.experts_held is None
                else cfg.experts_held)
        bias = None
        if cfg.use_expert_bias:
            bias = jax.lax.stop_gradient(self.param(
                "expert_bias", nn.initializers.zeros, (cfg.num_experts,),
                jnp.float32))
        # the normed tokens: kept by a rematerialised layer, so that the
        # row gather reads what the forward pass read
        x = checkpoint_name(x, profiler.MOE_INPUT)
        return DroplessMoE(
            hidden_size=cfg.hidden_size,
            ffn_hidden_size=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
            experts_held=held, expert_offset=cfg.expert_offset,
            routed_scaling_factor=cfg.routed_scaling_factor,
            norm_topk_prob=cfg.norm_topk_prob, activation=jax.nn.silu,
            gated=True, norm_topk_eps=1e-6, dtype=cfg.dtype,
            name="experts")(x, bias)


_MIXERS = {CONV: ("conv", ShortConvMixer),
           ATTENTION: ("self_attn", AttentionMixer)}


class Lfm2Layer(nn.Module):
    """A mixer and then a feed-forward, each with its norm and residual;
    returns ``(x, counters or None)``. The submodules' names say their
    kind: ``conv`` or ``self_attn``, ``dense_ffn`` or ``expert_ffn``."""

    cfg: Lfm2Config
    mixer: str
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        name, mixer_cls = _MIXERS[self.mixer]
        y = mixer_cls(cfg, name=name)(_norm(cfg, "operator_norm")(x))
        x = x + y.astype(x.dtype)
        ffn = (DenseMLP(cfg, name="dense_ffn") if self.dense
               else ExpertFFN(cfg, name="expert_ffn"))
        y, counters = ffn(_norm(cfg, "ffn_norm")(x))
        return x + y.astype(x.dtype), counters


class Lfm2Model(nn.Module):
    """Embedding, the layers, the final RMSNorm. Returns ``(hidden,
    counters, embedding table)``; the counters sum the expert layers'
    ``moe_assignments_held`` and ``moe_tokens_dropped`` and keep the
    largest ``moe_load_max_over_mean``."""

    cfg: Lfm2Config

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        table = self.param("embedding", _INIT,
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = table[input_ids].astype(cfg.dtype)
        dense_cls = expert_cls = Lfm2Layer
        if cfg.remat:
            dense_cls = remat_block(Lfm2Layer, (), "selective")
            expert_cls = remat_routing_block(Lfm2Layer, "selective")
        total = zero_step_counters()
        for i, mixer in enumerate(cfg.layer_types):
            dense = i < cfg.num_dense_layers
            layer_cls = dense_cls if dense else expert_cls
            x, counters = layer_cls(cfg, mixer, dense,
                                    name=f"layers_{i}")(x)
            if counters is not None:
                total = add_step_counters(total, counters)
        return _norm(cfg, "embedding_norm")(x), total, table


class Lfm2LMHeadModel(nn.Module):
    """The stack with its tied head. ``apply(params, ids)`` gives
    ``(logits float32, counters)``; ``apply(params, ids, method="loss")``
    gives ``(loss, counters)`` without ever holding the batch's
    logits."""

    cfg: Lfm2Config

    def setup(self):
        self.backbone = Lfm2Model(self.cfg)

    def __call__(self, input_ids):
        x, counters, table = self.backbone(input_ids)
        with jax.named_scope(profiler.LM_HEAD):
            logits = jnp.dot(x, table.T.astype(x.dtype),
                             preferred_element_type=jnp.float32)
        return logits, counters

    def loss(self, input_ids):
        x, counters, table = self.backbone(input_ids)
        return blocked_lm_loss(x, table.T, input_ids), counters
