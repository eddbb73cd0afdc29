"""``deepseek_v3``-style decoder (DeepSeek-V3, Kanana-2): multi-head latent
attention, a dense SwiGLU in the leading layers and sigmoid-routed gated
sparse experts with shared experts after; the training path.

**The layer equations.** Hidden size ``H``, ``n`` heads, a query/key head
of ``d_n + d_r`` (``qk_nope_head_dim`` without position, then
``qk_rope_head_dim`` with rotary position), a value head of ``d_v``, a kv
latent of rank ``r`` (``kv_lora_rank``); there is no query latent
(``q_lora_rank`` null):

- ``x0 = E[ids]``.
- For each layer ``l``:

  - ``a = RMSNorm_in(x)``;
  - ``q = a W_q`` over ``n`` heads of ``d_n + d_r``, split into ``q_n``
    and ``q_r``;
  - ``[c, k_r] = a W_kva`` (``kv_a_proj_with_mqa``, ``H -> r + d_r``);
    ``c = RMSNorm_kv(c)`` (``kv_a_layernorm``, a gain of ``r``);
    ``[k_n, v] = c W_kvb`` over ``n`` heads of ``d_n + d_v``;
  - rotary positions over ``q_r`` and ``k_r`` in the interleaved pairing
    (``rope_interleave``: element ``2i`` with ``2i + 1``), ``rope_theta``,
    float32 angles; the family permutes each pair's elements to the
    half-split order first (``[x0, x2, .., x1, x3, ..]``) and turns the
    halves, which changes no dot product of q with k, and so does this
    code (:func:`interleaved_to_half`, :func:`apex_tpu.models.lfm2.rotary`);
  - ``k_r`` is ONE head, seen by all ``n``: ``q = [q_n, q_r]``, ``k =
    [k_n, k_r]`` (``k_r`` broadcast over the heads, materialised once per
    head: the flash kernels take one k a head);
  - ``o = softmax(q k^T / sqrt(d_n + d_r) + causal) v`` at ``d_v`` a head
    (:func:`apex_tpu.ops.flash_attention.flash_attention` with v narrower
    than q and k: the ``flash_mla_*`` kernels); ``x <- x + o W_o``;
  - ``b = RMSNorm_post_attn(x)``; ``F(b)`` is the dense SwiGLU of width
    ``intermediate_size`` for ``l < first_k_dense_replace``
    (:class:`apex_tpu.models.lfm2.DenseMLP`), else the shared experts plus
    the held routed experts' part (:class:`apex_tpu.models.afmoe.SparseMoE`:
    ``s = sigmoid(b W_r)`` over all ``num_experts`` in float32, the
    ``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` (a
    float32 buffer with no gradient, held at zero here), ``w_i =
    route_scale s_i / (sum of the chosen s + 1e-20)``; SwiGLU experts of
    ``moe_intermediate_size``; the ``n_shared_experts`` shared experts as
    ONE SwiGLU of their summed width, on every token);
  - ``x <- x + F``.
- ``logits = RMSNorm_final(x_L) W_head`` (untied). Every norm but the
  latent's has eps ``rms_norm_eps`` over ``H``; every gain is float32.

There are no biases. ``n_group`` and ``topk_group`` 1 limit no group.

Under amp O2 pass :func:`keep_fp32_filter` to ``amp.initialize``: every
RMSNorm gain (the latent's among them), the router and the expert bias
stay float32. Every layer is recomputed in the backward pass: it keeps its
matmul outputs (``q``, ``[c, k_r]``, ``[k_n, v]``, the output projection's
and the dense MLP's) and flash attention's ``o`` + ``lse`` (``"selective"``),
a sparse layer its routing and ordered rows besides
(:func:`~apex_tpu.transformer.remat.remat_routing_block`); the latent's
norm, the rotary positions, the assembly of q and k and the other
elementwise ops are recomputed. The loss (:meth:`DeepseekV3LMHeadModel.loss`)
runs the head and the cross-entropy a sequence at a time
(:func:`apex_tpu.models.nemotron_h.blocked_lm_loss`). Beside the loss the
model returns the step counters of :data:`apex_tpu.profiler.STEP_COUNTERS`
(use ``build_train_step(..., has_aux=True)``).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import profiler
# afmoe's filter keeps what this family keeps: every RMSNorm gain, the
# router and the expert bias
from apex_tpu.models.afmoe import SparseMoE, keep_fp32_filter  # noqa: F401
from apex_tpu.models.lfm2 import DenseMLP, rotary
from apex_tpu.models.nemotron_h import blocked_lm_loss
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops.flash_attention import flash_attention, mha_reference
from apex_tpu.transformer.moe import add_step_counters, zero_step_counters
from apex_tpu.transformer.remat import remat_block, remat_routing_block

_INIT = nn.initializers.normal(stddev=0.02)
_LATENT_EPS = 1e-6       # kv_a_layernorm: the family's RMSNorm default


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """The family's keys under the names the shared expert layer reads:
    ``num_experts`` (``n_routed_experts``), ``num_shared_experts``
    (``n_shared_experts``), ``route_scale`` (``routed_scaling_factor``),
    ``route_norm`` (``norm_topk_prob``)."""

    vocab_size: int = 128256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    intermediate_size: int = 6144
    # latent attention
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1000000.0
    # experts
    num_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 768
    num_shared_experts: int = 2
    route_scale: float = 2.448
    route_norm: bool = True
    experts_held: int = 128
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    fused_kernels: bool = True


def _dense(cfg, features, name):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=jnp.float32, kernel_init=_INIT, name=name)


def _norm(cfg, name, width=None, eps=None):
    """RMSNorm whose gain lives at ``<name>/scale`` on both paths."""
    width = width or cfg.hidden_size
    eps = cfg.rms_norm_eps if eps is None else eps
    if cfg.fused_kernels:
        return FusedRMSNorm(width, eps=eps, name=name)
    return nn.RMSNorm(epsilon=eps, dtype=cfg.dtype, param_dtype=jnp.float32,
                      name=name)


def interleaved_to_half(t):
    """The last axis's pairs ``(t_2i, t_2i+1)`` moved to ``(i, i + d/2)``:
    ``[t0, t2, .., t1, t3, ..]``, the family's step before it turns the
    halves (transformers ``apply_rotary_pos_emb_interleave``)."""
    d = t.shape[-1]
    return t.reshape(*t.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(
        t.shape)


class LatentAttention(nn.Module):
    """Multi-head latent attention, expanded: the latent is projected up
    to every head's keys and values before the flash call."""

    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        n, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        r = cfg.kv_lora_rank
        b, l, _ = x.shape
        with jax.named_scope(profiler.MLA_ATTENTION):
            with jax.named_scope(profiler.MLA_Q):
                q = _dense(cfg, n * (dn + dr), "q_proj")(x).reshape(
                    b, l, n, dn + dr)
            with jax.named_scope(profiler.MLA_KV_DOWN):
                ckr = _dense(cfg, r + dr, "kv_a_proj_with_mqa")(x)
                c = _norm(cfg, "kv_a_layernorm", r, _LATENT_EPS)(
                    ckr[..., :r])
            with jax.named_scope(profiler.MLA_KV_UP):
                kv = _dense(cfg, n * (dn + dv), "kv_b_proj")(c).reshape(
                    b, l, n, dn + dv)
                v = kv[..., dn:].transpose(0, 2, 1, 3)
            with jax.named_scope(profiler.MLA_ROPE):
                q_r = rotary(interleaved_to_half(q[..., dn:]).astype(
                    jnp.float32), cfg.rope_theta)
                k_r = rotary(interleaved_to_half(ckr[..., None, r:]).astype(
                    jnp.float32), cfg.rope_theta)
                q = jnp.concatenate([q[..., :dn], q_r.astype(cfg.dtype)], -1)
                k = jnp.concatenate(
                    [kv[..., :dn],
                     jnp.broadcast_to(k_r.astype(cfg.dtype), (b, l, n, dr))],
                    -1)
                q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
            with jax.named_scope(profiler.MLA_CORE):
                attend = (flash_attention if cfg.fused_kernels
                          else mha_reference)
                ctx = attend(q, k, v, None, True, (dn + dr) ** -0.5)
            with jax.named_scope(profiler.MLA_OUT):
                ctx = ctx.transpose(0, 2, 1, 3).reshape(b, l, n * dv)
                return _dense(cfg, cfg.hidden_size, "o_proj")(ctx)


class DeepseekV3Layer(nn.Module):
    """Latent attention and then the feed-forward, each after its norm and
    with its residual; returns ``(x, counters or None)``. The feed-forward
    is ``mlp``, dense or sparse by ``dense``."""

    cfg: DeepseekV3Config
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        y = LatentAttention(cfg, name="self_attn")(
            _norm(cfg, "input_layernorm")(x))
        x = x + y.astype(x.dtype)
        ffn = (DenseMLP(cfg, name="mlp") if self.dense
               else SparseMoE(cfg, name="mlp"))
        y, counters = ffn(_norm(cfg, "post_attention_layernorm")(x))
        return x + y.astype(x.dtype), counters


class DeepseekV3Model(nn.Module):
    """Embedding, the layers, the final RMSNorm. Returns ``(hidden,
    counters)``; the counters sum the sparse layers' ``moe_assignments_held``
    and ``moe_tokens_dropped`` and keep the largest
    ``moe_load_max_over_mean``."""

    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        table = self.param("embed_tokens", _INIT,
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = table[input_ids].astype(cfg.dtype)
        dense_cls = remat_block(DeepseekV3Layer, (), "selective")
        sparse_cls = remat_routing_block(DeepseekV3Layer, "selective")
        total = zero_step_counters()
        for i in range(cfg.num_hidden_layers):
            dense = i < cfg.first_k_dense_replace
            layer_cls = dense_cls if dense else sparse_cls
            x, counters = layer_cls(cfg, dense, name=f"layers_{i}")(x)
            if counters is not None:
                total = add_step_counters(total, counters)
        return _norm(cfg, "norm")(x), total


class DeepseekV3LMHeadModel(nn.Module):
    """The stack with its untied head. ``apply(params, ids)`` gives
    ``(logits float32, counters)``; ``apply(params, ids, method="loss")``
    gives ``(loss, counters)`` without ever holding the batch's
    logits."""

    cfg: DeepseekV3Config

    def setup(self):
        self.model = DeepseekV3Model(self.cfg)
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
