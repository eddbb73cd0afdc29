"""``afmoe``-style decoder (Arcee Trinity): window and global attention
layers mixed, a dense gated MLP in the leading layers and sigmoid-routed
gated sparse experts with a shared expert after; the training path.

**The layer equations.** Hidden size ``H``, head size ``d``, ``nq`` query
heads on ``nkv`` key/value heads (query head ``j`` reads group ``j //
(nq / nkv)``):

- ``x0 = E[ids] * sqrt(H)`` (the muP input scale, ``mup_enabled``).
- For each layer ``l`` of kind ``layer_types[l]``, ``sliding_attention``
  (a WINDOW layer) or ``full_attention`` (a GLOBAL one):

  - ``a = RMSNorm_in(x)``; ``q = a W_q``, ``k = a W_k``, ``v = a W_v``,
    ``g = a W_g`` (``W_g``: ``H x nq d``);
  - RMSNorm over each head of q and of k (one gain of ``d`` each, float32
    arithmetic: :func:`apex_tpu.models.lfm2.head_rms_norm`);
  - a window layer turns q and k by their position (rotary over the whole
    head, half-split pairing, ``rope_theta``, float32 angles:
    :func:`apex_tpu.models.lfm2.rotary`) and lets the query at ``i`` see
    the key at ``j`` iff ``0 <= i - j < sliding_window``
    (:class:`apex_tpu.ops.flash_attention.SlidingWindowMask`: past one
    tile the kernels' grid is the list of the band's live tiles); a global
    layer has no position term (NoPE) and a causal mask;
  - ``o = softmax(q k^T / sqrt(d) + mask) v``; ``u = o * sigmoid(g)`` (the
    output gate, float32 arithmetic);
  - ``x <- x + RMSNorm_post_attn(u W_o)``;
  - ``b = RMSNorm_pre_mlp(x)``; ``F(b)`` is the dense SwiGLU ``W_down
    (silu(W_gate b) * W_up b)`` of width ``intermediate_size`` for ``l <
    num_dense_layers``, else ``shared(b) + sum over the chosen experts held
    here of w_i E_i(b)``: ``s = sigmoid(b W_r)`` over all ``num_experts``
    in float32, the ``num_experts_per_tok`` largest of ``s + beta`` (the
    expert bias: a float32 buffer with no gradient, held at zero here),
    ``w_i = route_scale * s_i / (sum of the chosen s + 1e-20)``
    (``route_norm``); ``E_i`` and ``shared`` are SwiGLU of width
    ``moe_intermediate_size`` (times ``num_shared_experts`` for the
    shared one);
  - ``x <- x + RMSNorm_post_mlp(F)``.
- ``logits = RMSNorm_final(x_L) W_head`` (untied). Every norm has eps
  ``rms_norm_eps`` and a float32 gain.

The output gate, NoPE on the global layers, the four norms a layer and the
muP scale are the family's public modelling code (transformers
``models/afmoe``); ``config.json`` has no key for them.

Under amp O2 pass :func:`keep_fp32_filter` to ``amp.initialize``: every
RMSNorm gain (q's and k's among them), the router and the expert bias stay
float32. Every layer is recomputed in the backward pass: it keeps its
matmul outputs and flash attention's ``o`` + ``lse`` (``"selective"``), a
sparse layer its routing and ordered rows besides
(:func:`~apex_tpu.transformer.remat.remat_routing_block`) and its routed
output, which the post-feed-forward norm's backward pass reads; the
elementwise ops are recomputed. The loss (:meth:`AfmoeLMHeadModel.loss`)
runs the head and the cross-entropy a sequence at a time
(:func:`apex_tpu.models.nemotron_h.blocked_lm_loss`). Beside the loss the
model returns the step counters of :data:`apex_tpu.profiler.STEP_COUNTERS`
(use ``build_train_step(..., has_aux=True)``).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu import profiler
from apex_tpu.amp.frontend import _default_norm_filter
from apex_tpu.models.lfm2 import DenseMLP, head_rms_norm, rotary
from apex_tpu.models.nemotron_h import blocked_lm_loss
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops.flash_attention import (SlidingWindowMask,
                                          flash_attention, mha_reference)
from apex_tpu.transformer.moe import (DroplessMoE, add_step_counters,
                                      zero_step_counters)
from apex_tpu.transformer.remat import remat_block, remat_routing_block

_INIT = nn.initializers.normal(stddev=0.02)
_FP32_LEAVES = ("router", "expert_bias")
WINDOW, GLOBAL = "sliding_attention", "full_attention"


def keep_fp32_filter(path: str) -> bool:
    """amp O2's ``keep_fp32_filter`` for this family: the RMSNorm gains
    (four a layer, the final one, q's and k's), the router and the expert
    bias."""
    return (path.rsplit("/", 1)[-1] in _FP32_LEAVES
            or _default_norm_filter(path))


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, GLOBAL) * 8
    num_dense_layers: int = 2
    intermediate_size: int = 6144
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    # experts
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    route_scale: float = 2.826
    route_norm: bool = True
    experts_held: int = 128
    expert_offset: int = 0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    dtype: jnp.dtype = jnp.float32
    fused_kernels: bool = True

    def __post_init__(self):
        bad = set(self.layer_types) - {WINDOW, GLOBAL}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types {self.layer_types!r}: "
                             f"{WINDOW!r} and {GLOBAL!r} only")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads is no multiple of "
                             "num_key_value_heads")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=32,
                    layer_types=(WINDOW, WINDOW, GLOBAL), num_dense_layers=1,
                    intermediate_size=48, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=8, sliding_window=12,
                    num_experts=8, num_experts_per_tok=2,
                    moe_intermediate_size=24, experts_held=8)
        base.update(kw)
        return AfmoeConfig(**base)


def _dense(cfg, features, name):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=jnp.float32, kernel_init=_INIT, name=name)


def _norm(cfg, name):
    """RMSNorm whose gain lives at ``<name>/scale`` on both paths."""
    if cfg.fused_kernels:
        return FusedRMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps, name=name)
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
                      param_dtype=jnp.float32, name=name)


class GatedAttention(nn.Module):
    """A window layer's attention (``window``) or a global layer's."""

    cfg: AfmoeConfig
    window: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        nq, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        b, l, _ = x.shape
        scope = (profiler.WINDOW_ATTENTION if self.window
                 else profiler.GLOBAL_ATTENTION)
        with jax.named_scope(scope):
            q = _dense(cfg, nq * d, "q_proj")(x).reshape(b, l, nq, d)
            k = _dense(cfg, nkv * d, "k_proj")(x).reshape(b, l, nkv, d)
            v = _dense(cfg, nkv * d, "v_proj")(x).reshape(b, l, nkv, d)
            g = _dense(cfg, nq * d, "gate_proj")(x)
            q_gain = self.param("q_norm", nn.initializers.ones, (d,),
                                jnp.float32)
            k_gain = self.param("k_norm", nn.initializers.ones, (d,),
                                jnp.float32)
            with jax.named_scope(profiler.ATTN_QK_NORM):
                q = head_rms_norm(q, q_gain, cfg.rms_norm_eps)
                k = head_rms_norm(k, k_gain, cfg.rms_norm_eps)
            if self.window:
                with jax.named_scope(profiler.ATTN_ROPE):
                    q = rotary(q, cfg.rope_theta)
                    k = rotary(k, cfg.rope_theta)
            q, k = q.astype(cfg.dtype), k.astype(cfg.dtype)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            attend = flash_attention if cfg.fused_kernels else mha_reference
            if self.window:
                ctx = attend(q, k, v, None, False, d ** -0.5,
                             score_mask=SlidingWindowMask(
                                 l, cfg.sliding_window))
            else:
                ctx = attend(q, k, v, None, True, d ** -0.5)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, l, nq * d)
            with jax.named_scope(profiler.ATTN_GATE):
                u = (ctx.astype(jnp.float32)
                     * jax.nn.sigmoid(g.astype(jnp.float32))).astype(cfg.dtype)
            return _dense(cfg, cfg.hidden_size, "o_proj")(u)


class SharedExpert(nn.Module):
    """SwiGLU of width ``moe_intermediate_size * num_shared_experts``,
    ``[gate | up]`` as one matrix (:class:`apex_tpu.models.lfm2.DenseMLP`'s
    form at the expert width)."""

    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        width = cfg.moe_intermediate_size * cfg.num_shared_experts
        gu = _dense(cfg, 2 * width, "gate_up")(x)
        a = (jax.nn.silu(gu[..., :width].astype(jnp.float32))
             * gu[..., width:].astype(jnp.float32)).astype(cfg.dtype)
        return _dense(cfg, cfg.hidden_size, "down")(a)


class SparseMoE(nn.Module):
    """The shared expert on every token plus this rank's share of the
    routed experts (:class:`~apex_tpu.transformer.moe.DroplessMoE`, gated,
    sigmoid-scored, selection by ``s + expert_bias``)."""

    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        bias = jax.lax.stop_gradient(self.param(
            "expert_bias", nn.initializers.zeros, (cfg.num_experts,),
            jnp.float32))
        # the normed tokens: kept by a rematerialised layer, so that the
        # row gather reads what the forward pass read
        x = checkpoint_name(x, profiler.MOE_INPUT)
        routed, counters = DroplessMoE(
            hidden_size=cfg.hidden_size,
            ffn_hidden_size=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
            experts_held=cfg.experts_held, expert_offset=cfg.expert_offset,
            routed_scaling_factor=cfg.route_scale,
            norm_topk_prob=cfg.route_norm, activation=jax.nn.silu,
            gated=True, score_function="sigmoid", dtype=cfg.dtype,
            name="experts")(x, bias)
        with jax.named_scope(profiler.MOE_SHARED):
            shared = SharedExpert(cfg, name="shared")(x)
        # the routed part is the combine's sum over a token's slots; the
        # post-feed-forward norm's backward pass reads it, so it is kept
        # (else the down projection's grouped matmul would run again)
        return checkpoint_name(routed, profiler.MOE_OUTPUT) + shared, counters


class AfmoeLayer(nn.Module):
    """Attention and then the feed-forward, each between its two norms
    (sandwich) and with its residual; returns ``(x, counters or None)``.
    The submodules' names say their kind: ``self_attn``, and ``mlp``
    (dense) or ``moe``."""

    cfg: AfmoeConfig
    window: bool
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        y = GatedAttention(cfg, self.window, name="self_attn")(
            _norm(cfg, "input_layernorm")(x))
        x = x + _norm(cfg, "post_attention_layernorm")(y).astype(x.dtype)
        ffn = (DenseMLP(cfg, name="mlp") if self.dense
               else SparseMoE(cfg, name="moe"))
        y, counters = ffn(_norm(cfg, "pre_mlp_layernorm")(x))
        return (x + _norm(cfg, "post_mlp_layernorm")(y).astype(x.dtype),
                counters)


class AfmoeModel(nn.Module):
    """Embedding (times ``sqrt(H)`` under ``mup_enabled``), the layers,
    the final RMSNorm. Returns ``(hidden, counters)``; the counters sum
    the sparse layers' ``moe_assignments_held`` and ``moe_tokens_dropped``
    and keep the largest ``moe_load_max_over_mean``."""

    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        table = self.param("embed_tokens", _INIT,
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = table[input_ids].astype(jnp.float32)
        if cfg.mup_enabled:
            x = x * cfg.hidden_size ** 0.5
        x = x.astype(cfg.dtype)
        dense_cls = remat_block(AfmoeLayer, (), "selective")
        sparse_cls = remat_routing_block(AfmoeLayer, "selective")
        total = zero_step_counters()
        for i, kind in enumerate(cfg.layer_types):
            dense = i < cfg.num_dense_layers
            layer_cls = dense_cls if dense else sparse_cls
            x, counters = layer_cls(cfg, kind == WINDOW, dense,
                                    name=f"layers_{i}")(x)
            if counters is not None:
                total = add_step_counters(total, counters)
        return _norm(cfg, "norm")(x), total


class AfmoeLMHeadModel(nn.Module):
    """The stack with its untied head. ``apply(params, ids)`` gives
    ``(logits float32, counters)``; ``apply(params, ids, method="loss")``
    gives ``(loss, counters)`` without ever holding the batch's
    logits."""

    cfg: AfmoeConfig

    def setup(self):
        self.model = AfmoeModel(self.cfg)
        self.lm_head = self.param(
            "lm_head", _INIT, (self.cfg.hidden_size, self.cfg.vocab_size),
            jnp.float32)

    def __call__(self, input_ids):
        x, counters = self.model(input_ids)
        with jax.named_scope(profiler.LM_HEAD):
            logits = jnp.dot(x, self.lm_head.astype(x.dtype),
                             preferred_element_type=jnp.float32)
        return logits, counters

    def loss(self, input_ids):
        x, counters = self.model(input_ids)
        return blocked_lm_loss(x, self.lm_head, input_ids), counters
