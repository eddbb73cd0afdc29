"""``nemotron_h``-style hybrid decoder: Mamba-2, sparse-expert and
grouped-query-attention blocks in one stack, for the training path.

A stack is a string over three letters (HF ``hybrid_override_pattern``):
``M`` a Mamba-2 mixer, ``E`` a mixture of experts with one shared expert,
``*`` causal grouped-query attention. Every block is
``x + mixer(RMSNorm(x))`` with ONE mixer; the residual stream stays in
the compute dtype. There is no position term of any kind: order comes
from the Mamba blocks and the causal mask. The ends are an untied
embedding and head around a final RMSNorm.

- **Mamba-2** (:class:`MambaMixer`): ``in_proj -> [z | x | B | C | dt]``,
  a depthwise causal convolution of width ``conv_kernel`` with bias over
  ``[x | B | C]`` then SiLU, ``dt = clip(softplus(dt + dt_bias))``,
  ``A = -exp(A_log)``, the chunked scan of
  :func:`apex_tpu.ops.ssd_scan.ssd_scan`, ``RMSNorm`` over ``n_groups``
  groups of ``y * SiLU(z)`` with a gain, ``out_proj``.
- **Experts** (:class:`ExpertMixer`): the dropless share of
  :class:`apex_tpu.transformer.moe.DroplessMoE` (this rank holds
  ``experts_held`` experts from ``expert_offset`` and adds their part
  only) plus a shared expert for every token, both
  ``W_down relu(W_up h)^2`` with no gate and no bias.
- **Attention** (:class:`AttentionMixer`): ``num_attention_heads`` query
  heads on ``num_key_value_heads`` key/value heads through
  :func:`apex_tpu.ops.flash_attention.flash_attention`, which reads a
  group by index and makes no repeated copy of k and v.

Under amp O2 pass :func:`keep_fp32_filter` to ``amp.initialize``: the
router, ``A_log``, ``D``, ``dt_bias`` and every RMSNorm gain stay float32.

With ``remat`` (the default) every block is rematerialised, and what it
keeps follows its letter (:mod:`apex_tpu.transformer.remat`). ``M`` and
``*`` blocks keep their dense matmul outputs (``in_proj``; q, k, v) and
flash attention's output and log-sum-exp (``"selective"``): the backward
pass does the RMSNorm, the conv and SiLU, ``softplus``, the gated norm, the
head transposes and the chunked scan again (its einsums carry batch
dimensions and name nothing), and no projection and no flash call. An ``E``
block keeps its normed input, its routing and its hidden rows and nothing
else: the shared expert's up projection is done again, because keeping its
output cost the gradients' accuracy (``PERF.md`` section 6, PR 33).

The loss (:meth:`NemotronHLMHeadModel.loss`) runs the head and the
cross-entropy one sequence at a time under ``jax.checkpoint``: one row's
float32 logits are live, never the batch's. Beside the loss the model
returns the step counters of :data:`apex_tpu.profiler.STEP_COUNTERS`
(use ``build_train_step(..., has_aux=True)``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu import profiler
from apex_tpu.amp.frontend import _default_norm_filter
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops.flash_attention import flash_attention, mha_reference
from apex_tpu.ops.ssd_scan import ssd_scan
from apex_tpu.transformer.moe import (DroplessMoE, add_step_counters,
                                      squared_relu, zero_step_counters)
from apex_tpu.transformer.remat import remat_block, remat_routing_block

_INIT = nn.initializers.normal(stddev=0.02)
_FP32_LEAVES = ("router", "A_log", "D", "dt_bias")


def keep_fp32_filter(path: str) -> bool:
    """amp O2's ``keep_fp32_filter`` for this family: the RMSNorm gains
    (block, final and the Mamba mixer's gated norm), the router, and the
    state-space scalars ``A_log``, ``D``, ``dt_bias``."""
    return (path.rsplit("/", 1)[-1] in _FP32_LEAVES
            or _default_norm_filter(path))


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = "MEMEM*EME"
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_limit: Tuple[float, Optional[float]] = (0.0, None)
    # experts
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    experts_held: Optional[int] = None       # None: all of them
    expert_offset: int = 0
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    remat: bool = True
    fused_kernels: bool = True

    def __post_init__(self):
        bad = set(self.pattern) - set("ME*")
        if bad or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: letters M, E, * only")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=32, pattern="ME*M",
                    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                    mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
                    n_groups=2, chunk_size=16, n_routed_experts=8,
                    num_experts_per_tok=2, moe_intermediate_size=24,
                    moe_shared_expert_intermediate_size=40)
        base.update(kw)
        return NemotronHConfig(**base)


def _dense(cfg, features, name):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=jnp.float32, kernel_init=_INIT, name=name)


def _block_norm(cfg, name):
    """RMSNorm whose gain lives at ``<name>/scale`` on both paths."""
    if cfg.fused_kernels:
        return FusedRMSNorm(cfg.hidden_size, eps=cfg.norm_eps, name=name)
    return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                      param_dtype=jnp.float32, name=name)


def _dt_bias_init(key, shape, dtype=jnp.float32, lo=1e-3, hi=0.1,
                  floor=1e-4):
    """Inverse softplus of a log-uniform step size in ``[lo, hi]``."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    dt = jnp.maximum(dt, floor)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


class MambaMixer(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        Hm, P, N, G = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                       cfg.ssm_state_size, cfg.n_groups)
        inner, K = cfg.mamba_inner, cfg.conv_kernel
        conv_dim = inner + 2 * G * N
        b, l, _ = x.shape

        with jax.named_scope(profiler.SSM_IN_PROJ):
            zxbcdt = _dense(cfg, 2 * inner + 2 * G * N + Hm, "in_proj")(x)
            z = zxbcdt[..., :inner]
            xbc = zxbcdt[..., inner:inner + conv_dim]
            dt = zxbcdt[..., inner + conv_dim:]

        with jax.named_scope(profiler.SSM_CONV):
            w = self.param("conv_kernel", _INIT, (K, conv_dim), jnp.float32)
            cb = self.param("conv_bias", nn.initializers.zeros, (conv_dim,),
                            jnp.float32)
            padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
            acc = cb.astype(jnp.float32)
            for j in range(K):           # tap j reads token t - (K - 1) + j
                acc = acc + (padded[:, j:j + l].astype(jnp.float32)
                             * w[j].astype(jnp.float32))
            xbc = jax.nn.silu(acc).astype(cfg.dtype)
            xs = xbc[..., :inner].reshape(b, l, Hm, P)
            B = xbc[..., inner:inner + G * N].reshape(b, l, G, N)
            C = xbc[..., inner + G * N:].reshape(b, l, G, N)

        dt_bias = self.param("dt_bias", _dt_bias_init, (Hm,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (Hm,), jnp.float32)
        d_skip = self.param("D", nn.initializers.ones, (Hm,), jnp.float32)
        lo, hi = cfg.time_step_limit
        dt = jnp.clip(jax.nn.softplus(dt.astype(jnp.float32)
                                      + dt_bias.astype(jnp.float32)), lo, hi)
        y = ssd_scan(xs, dt, -jnp.exp(a_log.astype(jnp.float32)), B, C,
                     d_skip, chunk=cfg.chunk_size)

        with jax.named_scope(profiler.SSM_OUT):
            gain = self.param("norm_scale", nn.initializers.ones, (inner,),
                              jnp.float32)
            g = (y.reshape(b, l, inner).astype(jnp.float32)
                 * jax.nn.silu(z.astype(jnp.float32)))
            g = g.reshape(b, l, G, inner // G)
            g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                                  + cfg.norm_eps)
            g = (g.reshape(b, l, inner) * gain.astype(jnp.float32)
                 ).astype(cfg.dtype)
            return _dense(cfg, cfg.hidden_size, "out_proj")(g)


class AttentionMixer(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        nq, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        b, l, _ = x.shape
        with jax.named_scope(profiler.GQA_ATTENTION):
            def heads(t, n):
                return t.reshape(b, l, n, d).transpose(0, 2, 1, 3)

            q = heads(_dense(cfg, nq * d, "q")(x), nq)
            k = heads(_dense(cfg, nkv * d, "k")(x), nkv)
            v = heads(_dense(cfg, nkv * d, "v")(x), nkv)
            attend = flash_attention if cfg.fused_kernels else mha_reference
            ctx = attend(q, k, v, None, True, d ** -0.5).astype(cfg.dtype)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, l, nq * d)
            return _dense(cfg, cfg.hidden_size, "out")(ctx)


class ExpertMixer(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        held = (cfg.n_routed_experts if cfg.experts_held is None
                else cfg.experts_held)
        # the normed tokens: kept by a rematerialised block, so that the row
        # gather and the shared expert's up projection (done again in the
        # backward pass: keeping ITS output cost the gradients' accuracy,
        # PERF.md section 6, PR 33) read what the forward pass read
        x = checkpoint_name(x, profiler.MOE_INPUT)
        routed, counters = DroplessMoE(
            hidden_size=cfg.hidden_size,
            ffn_hidden_size=cfg.moe_intermediate_size,
            num_experts=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
            experts_held=held, expert_offset=cfg.expert_offset,
            routed_scaling_factor=cfg.routed_scaling_factor,
            norm_topk_prob=cfg.norm_topk_prob, activation=squared_relu,
            dtype=cfg.dtype, name="experts")(x)
        with jax.named_scope(profiler.MOE_SHARED):
            up = _dense(cfg, cfg.moe_shared_expert_intermediate_size,
                        "shared_up")(x)
            shared = _dense(cfg, cfg.hidden_size, "shared_down")(
                squared_relu(up))
        return routed + shared, counters


_MIXERS = {"M": MambaMixer, "*": AttentionMixer, "E": ExpertMixer}


class NemotronHBlock(nn.Module):
    """``x + mixer(RMSNorm(x))``; returns ``(x, counters or None)``."""

    cfg: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        y = _block_norm(self.cfg, "norm")(x)
        y = _MIXERS[self.kind](self.cfg, name="mixer")(y)
        counters = None
        if self.kind == "E":
            y, counters = y
        return x + y.astype(x.dtype), counters


class NemotronHModel(nn.Module):
    """Embedding, the blocks of ``cfg.pattern``, final RMSNorm. Returns
    ``(hidden, counters)``; the counters sum the expert layers'
    ``moe_assignments_held`` and ``moe_tokens_dropped`` and keep the
    largest ``moe_load_max_over_mean``."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        table = self.param("embedding", _INIT,
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = table[input_ids].astype(cfg.dtype)
        # what a block keeps follows its letter (the module's docstring):
        # an expert block its routing and the rows it ordered, a Mamba or
        # an attention block its matmul outputs and flash's residuals
        plain_cls = expert_cls = NemotronHBlock
        if cfg.remat:
            plain_cls = remat_block(NemotronHBlock, (), "selective")
            expert_cls = remat_routing_block(NemotronHBlock)
        total = zero_step_counters()
        for i, kind in enumerate(cfg.pattern):
            block_cls = expert_cls if kind == "E" else plain_cls
            x, counters = block_cls(cfg, kind, name=f"layers_{i}")(x)
            if counters is not None:
                total = add_step_counters(total, counters)
        return _block_norm(cfg, "norm_f")(x), total


def blocked_lm_loss(hidden, head, labels):
    """Mean next-token cross-entropy over every position but the last,
    one sequence at a time: the head's matmul (``lm_head``) and the
    float32 logsumexp (``lm_loss``) of a row are recomputed in the
    backward pass, so one row's ``(S, V)`` logits are live at a time."""
    B, S, _ = hidden.shape

    def row(args):
        h, ids = args
        with jax.named_scope(profiler.LM_HEAD):
            logits = jnp.dot(h[:-1], head.astype(h.dtype),
                             preferred_element_type=jnp.float32)
        with jax.named_scope(profiler.LM_LOSS):
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, ids[1:, None], axis=-1)
            return jnp.sum(lse - picked[:, 0])

    return jnp.sum(jax.lax.map(jax.checkpoint(row), (hidden, labels))
                   ) / (B * (S - 1))


class NemotronHLMHeadModel(nn.Module):
    """The stack with its untied head. ``apply(params, ids)`` gives
    ``(logits float32, counters)``; ``apply(params, ids,
    method="loss")`` gives ``(loss, counters)`` without ever holding the
    batch's logits."""

    cfg: NemotronHConfig

    def setup(self):
        self.backbone = NemotronHModel(self.cfg)
        self.lm_head = self.param(
            "lm_head", _INIT, (self.cfg.hidden_size, self.cfg.vocab_size),
            jnp.float32)

    def __call__(self, input_ids):
        x, counters = self.backbone(input_ids)
        with jax.named_scope(profiler.LM_HEAD):
            logits = jnp.dot(x, self.lm_head.astype(x.dtype),
                             preferred_element_type=jnp.float32)
        return logits, counters

    def loss(self, input_ids):
        x, counters = self.backbone(input_ids)
        return blocked_lm_loss(x, self.lm_head, input_ids), counters
