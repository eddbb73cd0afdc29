"""GPT-style causal language model — the decoder-family workload.

Like :mod:`apex_tpu.models.bert`, the reference ships no models (apex is
a library); this is the causal counterpart assembled from the same
framework pieces: pre-LN blocks with Pallas FusedLayerNorm, causal flash
attention (or ring / Ulysses context parallelism for long sequences),
and the fused-logsumexp LM loss (no (B, S, V) log-prob tensor). For
Megatron tensor/sequence parallelism see the BERT flagship, which wires
the tensor_parallel layers; this model focuses on the context-parallel
(long-sequence) axis.

Attention backend selection (``attention_backend``):
- ``"flash"`` (default): single-device Pallas flash attention, causal.
- ``"ring"``: :func:`apex_tpu.ops.ring_attention` over the
  ``context_axis`` mesh axis — activations arrive sequence-sharded
  (B, S_local); O(S/cp) keys per device.
- ``"ulysses"``: :func:`apex_tpu.ops.ulysses_attention` — all-to-all
  head re-sharding; needs ``num_heads % cp == 0``.
Both parallel backends require running inside ``shard_map`` with the
context axis in scope (see ``examples/train_long_context.py`` for the
mesh setup pattern).

Serving: ``apply(..., kv_cache=...)`` (plus ``block_tables`` /
``cache_positions`` / ``seq_lens``) switches to the paged-KV-cache
inference path — prefill writes the prompt's K/V into cache blocks and
runs the ordinary causal attention; a one-token call decodes against
the block table. The engine's multi-step decode traces this one-token
call once as the body of a ``jax.lax.scan`` (K fused iterations per
dispatch), so everything here must be — and is — shape-stable under
traced ``cache_positions``/``seq_lens`` that advance inside the loop.
The same multi-token path doubles as the speculative-decoding
**verify-mode forward**: a ``[B, spec_tokens + 1]`` call whose per-lane
``cache_positions`` start at each lane's own context offset scores a
whole drafted span in one dispatch — the chunk writes the carried
token's and every draft's K/V through the block table and attends
causally by absolute position, so position ``p``'s logits are exactly
the target distribution given drafts ``0..p-1``. Lanes whose proposal
count falls short of the chunk ride with PADDED trailing queries:
their writes are suppressed by ``seq_lens``/``write_start`` and their
logits ignored, but their (clamped) position lookups must stay
in-range — see :class:`GPTModel`. See :mod:`apex_tpu.serving` and
docs/serving.md.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional

import flax.linen as nn

from apex_tpu.models._dropout import (
    TPDropout as _TPDropout,
    dropout_seed as _dropout_seed,
)
import jax
import jax.numpy as jnp

from apex_tpu import profiler
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.transformer.remat import remat_block

_INIT = nn.initializers.normal(stddev=0.02)

# serving-mesh layout (docs/serving.md "Mesh sharding"): the modules
# whose output dim splits over the mesh's "model" axis (qkv columns =
# heads; mlp_in columns = the 4h expansion) and those whose INPUT dim
# splits to match (the Megatron row-parallel halves, whose partial
# products GSPMD all-reduces). Everything else — embeddings,
# layernorms — replicates.
_COL_PARALLEL = ("attn_q", "attn_k", "attn_v", "mlp_in")
_ROW_PARALLEL = ("attn_out", "mlp_out")

# the dense modules weight quantization applies to: exactly the six
# qkv/proj/mlp matmuls the mesh layout shards. Embeddings, layernorms,
# and the weight-tied LM head stay full precision — they are a small
# fraction of the bytes and the tied ``wte`` is read by two ops with
# different contraction axes (no single per-channel scale axis).
_QUANT_DENSE = _COL_PARALLEL + _ROW_PARALLEL

# weight storage modes (mirrors serving.kv_cache.KV_QUANT_MODES):
# ``None`` = full precision, ``"int8"`` = symmetric round-to-nearest
# int8, ``"fp8"`` = float8_e4m3 where the backend has the dtype.
# Weights are STATIC, so rounding is deterministic round-to-nearest —
# no position-keyed stochastic rounding like the KV pools need.
WEIGHT_QUANT_MODES = (None, "int8", "fp8")


def fp8_weight_dtype():
    """The fp8 weight storage dtype, or None when this jax has no
    fp8 (same probe as ``serving.kv_cache.fp8_kv_dtype``)."""
    return getattr(jnp, "float8_e4m3fn", None)


def _weight_quant_dtype(mode):
    if mode == "int8":
        return jnp.dtype(jnp.int8)
    if mode == "fp8":
        dt = fp8_weight_dtype()
        if dt is None:
            raise NotImplementedError(
                "weight quantization 'fp8' requires a jax with "
                "jnp.float8_e4m3fn; use 'int8' on this backend")
        return jnp.dtype(dt)
    raise ValueError(
        f"unknown weight quantization {mode!r} "
        f"(expected one of {WEIGHT_QUANT_MODES})")


def _weight_quant_max(mode) -> float:
    """The quantizer's design max: per-output-channel scales are
    ``amax / qmax`` so each column's largest magnitude maps onto the
    representable extreme."""
    if mode == "int8":
        return 127.0
    return float(jnp.finfo(fp8_weight_dtype()).max)


def quantize_dense_kernel(kernel, mode):
    """``(q_kernel, scale)`` for one ``(in, out)`` dense kernel:
    symmetric per-OUTPUT-channel quantization, deterministic
    round-to-nearest (weights are static — same values always quantize
    to the same bytes, which is what lets the process-replica params
    handshake cover the quantized representation)."""
    w = jnp.asarray(kernel, jnp.float32)
    qmax = _weight_quant_max(mode)
    amax = jnp.max(jnp.abs(w), axis=0)                     # (out,)
    scale = jnp.where(amax > 0.0, amax / qmax, 1.0).astype(jnp.float32)
    q = w / scale[None, :]
    if mode == "int8":
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    return q.astype(_weight_quant_dtype(mode)), scale


def quantize_gpt_params(params, mode):
    """The fp GPT param tree re-expressed in quantized storage: every
    ``_QUANT_DENSE`` module's ``kernel`` becomes an int8/fp8 array with
    a per-output-channel fp32 ``scale`` leaf alongside (biases and all
    other leaves pass through untouched). The result is what a model
    built with ``GPTConfig(weight_quantization=mode)`` applies —
    dequantization happens only on the read side, inside the fused
    dequant-GEMM (:mod:`apex_tpu.ops.dequant_gemm`)."""
    _weight_quant_dtype(mode)     # validate mode / fp8 availability

    def walk(node):
        if not isinstance(node, Mapping):
            return node
        out = {}
        for key, child in node.items():
            if (key in _QUANT_DENSE and isinstance(child, Mapping)
                    and "kernel" in child):
                rec = {k: v for k, v in child.items() if k != "kernel"}
                q, scale = quantize_dense_kernel(child["kernel"], mode)
                rec["kernel"] = q
                rec["scale"] = scale
                out[key] = rec
            else:
                out[key] = walk(child)
        return out

    return walk(params)


def quantize_gpt_model(model, params, mode):
    """``(quantized_model, quantized_params)`` for a GPT LM and its fp
    params: the model is rebuilt with ``weight_quantization=mode`` (so
    its dense modules read quantized storage) and the params are
    re-expressed via :func:`quantize_gpt_params`. ``mode=None`` is the
    identity. The serving engine calls this at construction when
    ``EngineConfig.weight_quantization`` is set."""
    if mode not in WEIGHT_QUANT_MODES:
        raise ValueError(
            f"weight_quantization must be one of {WEIGHT_QUANT_MODES}, "
            f"got {mode!r}")
    if mode is None:
        return model, params
    cfg = getattr(model, "cfg", None)
    if not dataclasses.is_dataclass(cfg) or not any(
            f.name == "weight_quantization"
            for f in dataclasses.fields(cfg)):
        raise ValueError(
            "weight_quantization requires a GPT-family model whose "
            f"config carries the knob; got {type(model).__name__}")
    if cfg.weight_quantization is not None:
        # already quantized storage: idempotent for the same mode
        # (the params are already the quantized tree — re-quantizing
        # int8 bytes would corrupt them), a hard error across modes
        if cfg.weight_quantization == mode:
            return model, params
        raise ValueError(
            f"model already carries weight_quantization="
            f"{cfg.weight_quantization!r}; cannot re-quantize to "
            f"{mode!r}")
    qcfg = dataclasses.replace(cfg, weight_quantization=mode)
    return type(model)(qcfg), quantize_gpt_params(params, mode)


def gpt_param_bytes(params) -> int:
    """Total device bytes of a param tree — the number
    tests/test_weight_quant.py and the ``dequant_gemm`` recorder event
    compare between the fp and quantized representations."""
    return int(sum(x.size * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree.leaves(params)))


def gpt_num_layers(params) -> int:
    """Transformer-block count of a GPT param tree, read off the tree
    itself (the ``h_{i}`` block subtrees) — lets the sharded-train
    collective contract (``serving.mesh.train_expected_collectives``)
    scale its ``2 * num_layers`` tensor-parallel all-reduce floor
    without threading a :class:`GPTConfig` through the train step.
    Returns 0 for a non-GPT tree (callers fall back to the layer-count-
    unknown floor)."""
    blocks = set()

    def walk(tree):
        if not isinstance(tree, dict):
            return
        for k, v in tree.items():
            if (isinstance(k, str) and k.startswith("h_")
                    and k[2:].isdigit()):
                blocks.add(k)
            walk(v) if isinstance(v, dict) else None

    walk(params)
    return len(blocks)


def gpt_param_pspec(path, model_axis: str = "model"):
    """:class:`~jax.sharding.PartitionSpec` for one GPT param leaf,
    keyed by its pytree path (``jax.tree_util.tree_map_with_path``
    keys) — the model-owned half of the serving mesh layout
    (:mod:`apex_tpu.serving.mesh` binds it to a concrete mesh):

    - ``attn_q``/``attn_k``/``attn_v``/``mlp_in`` kernels
      column-shard (``P(None, model)``) with their biases along
      (``P(model)``) — qkv columns are head-major, so the head split
      of the KV pools lines up with the projection split;
    - ``attn_out``/``mlp_out`` kernels row-shard (``P(model, None)``),
      biases replicated (they add after the all-reduce);
    - quantized-weight ``scale`` leaves (per-OUTPUT-channel fp32, one
      per kernel column — ``weight_quantization``) shard exactly like
      the bias of their module: ``P(model)`` under column-parallel
      (the output dim is the sharded one), replicated under
      row-parallel (the output dim is unsharded there) — the KV-pool
      colocate-scales-with-bytes rule applied to weights: a kernel
      shard and the scales that dequantize it always land on the same
      device, so the fused dequant-GEMM never reaches across the mesh
      for a scale;
    - ``wte``/``wpe``/layernorms replicate.
    """
    from jax.sharding import PartitionSpec as P

    names = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
    module = names[-2] if len(names) >= 2 else ""
    leaf = names[-1] if names else ""
    if module in _COL_PARALLEL:
        if leaf == "kernel":
            return P(None, model_axis)
        # bias AND the quantized kernel's per-output-channel "scale":
        # both are (out,) vectors along the column-sharded output dim
        return P(model_axis)
    if module in _ROW_PARALLEL:
        if leaf == "kernel":
            return P(model_axis, None)
        # bias and "scale" lie along the UNSHARDED output dim here
        # (they apply after the all-reduce) — replicate
        return P()
    return P()


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position_embeddings: int = 1024
    dropout: float = 0.1
    layernorm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    remat: bool = True                 # activation checkpointing per block
    # what a checkpointed dense block keeps (apex_tpu/transformer/
    # remat.py): "selective" keeps the matmul and flash-attention outputs
    # and recomputes only the elementwise ops; "full" keeps the block's
    # input alone and recomputes the whole forward pass - 18% more step
    # time for 3.78 GiB less live memory at 24 x 1024, S=1024, B=8 on a
    # v5e (PERF.md section 6, PR 31): the way back for a job that fitted
    # only under full recomputation. An expert block is always "full"
    remat_policy: str = "selective"    # "selective" | "full"
    fused_kernels: bool = True
    attention_backend: str = "flash"   # flash | ring | ulysses
    context_axis: str = "context"
    # Mixture-of-experts (0 = dense MLP). Experts shard over the
    # ``expert`` mesh axis when parallel_state is initialized with
    # expert_model_parallel_size_ > 1; see apex_tpu.transformer.moe.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_layer_freq: int = 1   # every Nth block is MoE (1 = all)
    moe_aux_loss_coeff: float = 0.01
    moe_z_loss_coeff: float = 1e-3
    # Quantized weight storage (None | "int8" | "fp8"): routes the six
    # _QUANT_DENSE matmuls through QuantDense, whose params are the
    # int8/fp8 kernel + per-output-channel fp32 scale that
    # quantize_gpt_params produces. Normally set via
    # quantize_gpt_model / EngineConfig.weight_quantization rather
    # than by hand — the params MUST be the quantized tree.
    weight_quantization: Optional[str] = None

    @staticmethod
    def gpt2_small(**kw):
        return GPTConfig(**kw)

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 128)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("max_position_embeddings", 128)
        return GPTConfig(**kw)


class QuantDense(nn.Module):
    """Dense layer over quantized weight storage: an int8/fp8
    ``kernel`` (in, out) plus a per-output-channel fp32 ``scale``
    (out,) — the leaves :func:`quantize_gpt_params` produces — and an
    fp32 ``bias``. The forward is the fused dequant-GEMM
    (:func:`apex_tpu.ops.dequant_gemm.dequant_matmul`): dequantization
    happens on the read side only, inside the matmul, so the weights
    never materialize at full precision in HBM.

    Param shapes/dtypes must match the quantized tree exactly (flax
    validates shapes against these init_fns even in apply mode); the
    zeros/ones inits only matter for standalone ``init()`` of a
    quantized-config model, e.g. in eval_shape.
    """

    features: int
    mode: str
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from apex_tpu.ops.dequant_gemm import dequant_matmul

        qdt = _weight_quant_dtype(self.mode)
        kernel = self.param(
            "kernel", nn.initializers.zeros_init(),
            (x.shape[-1], self.features), qdt)
        scale = self.param(
            "scale", nn.initializers.ones_init(),
            (self.features,), jnp.float32)
        bias = self.param(
            "bias", nn.initializers.zeros_init(),
            (self.features,), jnp.float32)
        y = dequant_matmul(x, kernel, scale)
        return (y + bias).astype(self.dtype)


def _dense(cfg, features, name):
    mode = getattr(cfg, "weight_quantization", None)
    if mode is not None and name in _QUANT_DENSE:
        return QuantDense(features, mode=mode, dtype=cfg.dtype,
                          name=name)
    return nn.Dense(features, dtype=cfg.dtype, param_dtype=jnp.float32,
                    kernel_init=_INIT, name=name)


def _norm(cfg, name):
    if cfg.fused_kernels:
        return FusedLayerNorm(cfg.hidden_size, eps=cfg.layernorm_eps,
                              name=name)
    return nn.LayerNorm(epsilon=cfg.layernorm_eps, dtype=cfg.dtype,
                        param_dtype=jnp.float32, name=name)


def _ctx_fold_axes(cfg):
    """Mesh axes to fold into hidden-dropout seeds: the context axis when
    activations are sequence-sharded (ring/Ulysses), else nothing."""
    if cfg.attention_backend in ("ring", "ulysses"):
        return (cfg.context_axis,)
    return ()


def _causal_attend(cfg, q, k, v, scale, dropout_rate=0.0, seed=None):
    """(B, nh, S, hd) causal attention via the selected backend.
    ``dropout_rate``/``seed``: fused in-kernel attention-probability
    dropout, supported by EVERY backend — flash, composed, Ulysses
    (full-sequence flash after head re-sharding), and ring (per-block
    fused dropout keyed on global block-pair ids; the lse merge keeps
    statistics pre-dropout so nothing double-counts — see
    ops/ring_attention.py). All backends train at the true config."""
    if cfg.attention_backend == "ring":
        from apex_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, None, True, scale,
                              axis_name=cfg.context_axis,
                              dropout_rate=dropout_rate,
                              dropout_seed=seed)
    if cfg.attention_backend == "ulysses":
        from apex_tpu.ops.ulysses_attention import ulysses_attention

        return ulysses_attention(q, k, v, None, True, scale,
                                 axis_name=cfg.context_axis,
                                 dropout_rate=dropout_rate,
                                 dropout_seed=seed)
    if cfg.fused_kernels:
        from apex_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, None, True, scale,
                               dropout_rate, seed)
    # composed fallback: the shared parity reference
    from apex_tpu.ops.flash_attention import mha_reference

    return mha_reference(q, k, v, None, True, scale, dropout_rate, seed)



def _cached_attention(cfg, q, k, v, kv_cache, layer, block_tables,
                      cache_positions, seq_lens, write_start=None):
    """Serving attention against the paged KV-cache (flat (B, S, H)
    projections in, flat context out, plus the updated cache).

    Both serving modes write the freshly-projected K/V into the cache
    blocks first, then attend:
    - prefill chunk (S > 1): the chunk's queries attend against the
      FULL cached context through the block table — the shared-prefix
      blocks matched at admission, earlier chunks, and the chunk itself
      — via :func:`apex_tpu.ops.flash_attention.paged_prefill_attention`
      (causal by absolute position, padding key-masked by ``seq_lens``).
      Speculative verification is this same mode at ``[B, spec + 1]``:
      each lane's chunk holds its carried token plus its drafted span
      at per-lane absolute positions, so one forward scores every
      candidate position against the drafts before it;
    - decode (S == 1): single-query attention against the block table
      via :func:`apex_tpu.ops.flash_attention.paged_decode_attention`.
    ``write_start`` (``[B]`` int32, optional) suppresses cache writes
    below that absolute position: positions already in the cache — a
    matched shared prefix, or a fully-cached prompt recomputing only
    its last-position logits — must not be re-scattered (a shared block
    belongs to other sequences too). The multi-step decode scan also
    leans on it to FREEZE a lane mid-scan (EOS / budget exhausted):
    setting a lane's ``write_start`` one past its ``cache_positions``
    drops its scatter while the lane's query harmlessly rides the
    batch. The mode is static (S is a trace constant), so an engine
    compiles exactly one program per shape — see docs/serving.md.
    """
    from apex_tpu.serving.kv_cache import write_kv

    B, S, h = q.shape
    nh = cfg.num_heads
    hd = h // nh
    scale = 1.0 / (hd ** 0.5)
    qh = q.reshape(B, S, nh, hd)
    kh = k.reshape(B, S, nh, hd)
    vh = v.reshape(B, S, nh, hd)

    valid = cache_positions < seq_lens[:, None]
    if write_start is not None:
        valid = valid & (cache_positions >= write_start[:, None])
    # write_kv quantizes on the way in when the pool stores quantized
    # blocks (per-row scales scattered through the same coordinates,
    # docs/serving.md memory tiers); a full-precision pool takes
    # exactly the pre-quantization paged_write path, bit for bit
    kv_cache = write_kv(kv_cache, layer, block_tables, cache_positions,
                        kh, vh, valid)
    k_scales = (None if kv_cache.k_scale is None
                else kv_cache.k_scale[layer])
    v_scales = (None if kv_cache.v_scale is None
                else kv_cache.v_scale[layer])

    if S == 1:
        from apex_tpu.ops.flash_attention import paged_decode_attention

        ctx = paged_decode_attention(qh[:, 0], kv_cache.k[layer],
                                     kv_cache.v[layer], block_tables,
                                     seq_lens, scale,
                                     k_scales=k_scales,
                                     v_scales=v_scales)
        return ctx.reshape(B, 1, h), kv_cache

    from apex_tpu.ops.flash_attention import paged_prefill_attention

    ctx = paged_prefill_attention(qh, kv_cache.k[layer],
                                  kv_cache.v[layer], block_tables,
                                  cache_positions, seq_lens, scale,
                                  k_scales=k_scales, v_scales=v_scales)
    return ctx.reshape(B, S, h), kv_cache


class GPTBlock(nn.Module):
    cfg: GPTConfig
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, deterministic: bool = True, kv_cache=None,
                 layer: int = 0, block_tables=None, cache_positions=None,
                 seq_lens=None, write_start=None):
        cfg = self.cfg
        h, nh = cfg.hidden_size, cfg.num_heads
        hd = h // nh
        B, S = x.shape[0], x.shape[1]

        # pre-LN attention: three flat (B, S, H) projections shared by
        # every backend (one param layout — checkpoints stay portable
        # between flash / ring / Ulysses / composed / serving configs)
        y = _norm(cfg, "ln_1")(x)
        q = _dense(cfg, h, "attn_q")(y)
        k = _dense(cfg, h, "attn_k")(y)
        v = _dense(cfg, h, "attn_v")(y)

        # attention-probability dropout never applies on the serving
        # path (inference); the block tail below is shared with training
        attn_drop = (0.0 if deterministic or kv_cache is not None
                     else cfg.dropout)
        # Ulysses ranks share local head indices for different global
        # heads (rank folded into the seed inside ulysses_attention);
        # ring ranks share the base seed and decorrelate via the global
        # block-pair hash inside ring_attention
        seed = (_dropout_seed(self, False) if attn_drop > 0.0 else None)
        if kv_cache is not None:
            ctx, kv_cache = _cached_attention(
                cfg, q, k, v, kv_cache, layer, block_tables,
                cache_positions, seq_lens, write_start)
            ctx = ctx.astype(cfg.dtype)
        elif cfg.attention_backend == "flash" and cfg.fused_kernels:
            from apex_tpu.ops.flash_attention import flash_attention_bsh

            # transpose-free (B, S, H) kernels in the single-tile
            # regime; falls back to the transposed entry beyond it
            ctx = flash_attention_bsh(q, k, v, None, nh, True,
                                      1.0 / (hd ** 0.5), attn_drop,
                                      seed).astype(cfg.dtype)
        else:
            def heads(t):
                return t.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)

            ctx = _causal_attend(cfg, heads(q), heads(k), heads(v),
                                 1.0 / (hd ** 0.5), attn_drop, seed)
            ctx = ctx.astype(cfg.dtype).transpose(0, 2, 1, 3).reshape(
                B, S, h)
        attn = _dense(cfg, h, "attn_out")(ctx)
        ctx_axes = _ctx_fold_axes(cfg)
        attn = _TPDropout(cfg.dropout, fused=cfg.fused_kernels,
                          fold_axes=ctx_axes)(
            attn, deterministic=deterministic)
        x = x + attn

        # pre-LN MLP (dense or mixture-of-experts)
        y = _norm(cfg, "ln_2")(x)
        if self.use_moe:
            from apex_tpu.transformer.moe import MoEMLP

            y, aux, z = MoEMLP(
                hidden_size=h, ffn_hidden_size=4 * h,
                num_experts=cfg.num_experts, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                dtype=cfg.dtype, name="moe_mlp",
            )(y, deterministic=deterministic)
            self.sow("losses", "moe_aux_loss", cfg.moe_aux_loss_coeff * aux)
            self.sow("losses", "moe_z_loss", cfg.moe_z_loss_coeff * z)
        else:
            y = nn.gelu(_dense(cfg, 4 * h, "mlp_in")(y))
            y = _dense(cfg, h, "mlp_out")(y)
        y = _TPDropout(cfg.dropout, fused=cfg.fused_kernels,
                       fold_axes=ctx_axes)(
            y, deterministic=deterministic)
        if kv_cache is not None:
            return x + y, kv_cache
        return x + y


class GPTModel(nn.Module):
    """Token + (sharded-aware) position embeddings, pre-LN blocks, final
    norm. Returns hidden states; :class:`GPTLMHeadModel` adds the tied
    LM head."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True,
                 position_offset=0, kv_cache=None, block_tables=None,
                 cache_positions=None, seq_lens=None, write_start=None):
        cfg = self.cfg
        B, S_local = input_ids.shape
        wte = self.param("wte", _INIT, (cfg.vocab_size, cfg.hidden_size),
                         jnp.float32)
        wpe = self.param("wpe", _INIT,
                         (cfg.max_position_embeddings, cfg.hidden_size),
                         jnp.float32)
        if kv_cache is not None:
            # Serving path (paged KV-cache): single-device attention only
            # — the context-parallel backends re-shard the sequence axis,
            # which has no meaning for a one-token decode step. Position
            # embeddings are gathered per token (each sequence sits at
            # its own offset), not dynamic-sliced at a shared offset.
            if cfg.attention_backend in ("ring", "ulysses"):
                raise ValueError(
                    "kv_cache serving does not support the "
                    f"{cfg.attention_backend!r} context-parallel backend; "
                    "use attention_backend='flash'")
            if cfg.num_experts > 0:
                raise NotImplementedError(
                    "kv_cache serving does not support MoE blocks yet")
            if (block_tables is None or cache_positions is None
                    or seq_lens is None):
                raise ValueError(
                    "kv_cache requires block_tables, cache_positions, "
                    "and seq_lens")
            # clamp explicitly: verify-mode chunks carry PADDING
            # positions past a lane's real span (draft slots beyond its
            # proposal count, whose writes are suppressed and logits
            # ignored) which may run past the embedding table near the
            # sequence cap — the gather must not depend on jit's
            # implicit out-of-bounds clamping for its correctness story
            pos = jnp.take(
                wpe,
                jnp.minimum(cache_positions,
                            cfg.max_position_embeddings - 1),
                axis=0)                                    # [B, S, H]
            x = (wte[input_ids] + pos).astype(cfg.dtype)
            for i in range(cfg.num_layers):
                x, kv_cache = GPTBlock(cfg, False, name=f"h_{i}")(
                    x, deterministic, kv_cache, i, block_tables,
                    cache_positions, seq_lens, write_start)
            return _norm(cfg, "ln_f")(x), wte, kv_cache
        if cfg.attention_backend in ("ring", "ulysses"):
            # sequence-sharded: this shard's global positions. Validate
            # the table covers the GLOBAL sequence — dynamic_slice would
            # silently clamp and duplicate positions otherwise.
            cp = jax.lax.psum(1, cfg.context_axis)
            rank = jax.lax.axis_index(cfg.context_axis)
            static_off = (position_offset
                          if isinstance(position_offset, int) else 0)
            if isinstance(cp, int) and (static_off + cp * S_local
                                        > cfg.max_position_embeddings):
                raise ValueError(
                    f"global sequence ({cp} shards x {S_local} + offset "
                    f"{static_off}) exceeds max_position_embeddings "
                    f"({cfg.max_position_embeddings}); dynamic_slice "
                    "would silently clamp and duplicate positions")
            position_offset = position_offset + rank * S_local
        elif isinstance(position_offset, int) and (
                position_offset + S_local > cfg.max_position_embeddings):
            raise ValueError(
                f"sequence [{position_offset}, {position_offset + S_local}) "
                f"exceeds max_position_embeddings "
                f"({cfg.max_position_embeddings})")
        pos = jax.lax.dynamic_slice_in_dim(
            wpe, position_offset, S_local, axis=0)
        x = (wte[input_ids] + pos[None]).astype(cfg.dtype)
        x = _TPDropout(cfg.dropout, fused=cfg.fused_kernels,
                       fold_axes=_ctx_fold_axes(cfg))(
            x, deterministic=deterministic)

        dense_cls = moe_cls = GPTBlock
        if cfg.remat:
            dense_cls = remat_block(GPTBlock, (2,), cfg.remat_policy)
            # a block that routes recomputes everything: rows kept across
            # a recomputed routing can meet another order in the backward
            # pass (transformer/remat.py)
            moe_cls = remat_block(GPTBlock, (2,), "full")
        for i in range(cfg.num_layers):
            use_moe = (cfg.num_experts > 0
                       and i % max(cfg.moe_layer_freq, 1) == 0)
            block_cls = moe_cls if use_moe else dense_cls
            x = block_cls(cfg, use_moe, name=f"h_{i}")(x, deterministic)
        return _norm(cfg, "ln_f")(x), wte


class GPTLMHeadModel(nn.Module):
    """GPT with the weight-tied LM head (logits = hidden @ wte^T).

    With ``kv_cache=`` (plus ``block_tables``/``cache_positions``/
    ``seq_lens``, see :class:`GPTModel`) the call runs the serving path
    and returns ``(logits, new_kv_cache)`` instead of bare logits —
    the hook :class:`apex_tpu.serving.engine.InferenceEngine` drives.
    """

    cfg: GPTConfig

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True,
                 position_offset=0, kv_cache=None, block_tables=None,
                 cache_positions=None, seq_lens=None, write_start=None):
        if kv_cache is not None:
            x, wte, new_cache = GPTModel(self.cfg, name="transformer")(
                input_ids, deterministic, position_offset,
                kv_cache=kv_cache, block_tables=block_tables,
                cache_positions=cache_positions, seq_lens=seq_lens,
                write_start=write_start)
            with jax.named_scope(profiler.LM_HEAD):
                logits = jnp.einsum("bsh,vh->bsv", x, wte.astype(x.dtype),
                                    preferred_element_type=jnp.float32)
            return logits, new_cache
        x, wte = GPTModel(self.cfg, name="transformer")(
            input_ids, deterministic, position_offset)
        with jax.named_scope(profiler.LM_HEAD):
            return jnp.einsum("bsh,vh->bsv", x, wte.astype(x.dtype),
                              preferred_element_type=jnp.float32)


def moe_losses_total(collections):
    """Sum the sown MoE auxiliary losses from an ``apply(...,
    mutable=("losses",))`` result: ``logits, col = model.apply(...);
    loss = lm_loss(...) + moe_losses_total(col)``. Returns 0.0 for dense
    models (empty/missing collection)."""
    losses = collections.get("losses", {}) if collections else {}
    total = jnp.float32(0.0)
    for leaf in jax.tree.leaves(losses):
        total = total + jnp.sum(leaf)
    return total


@jax.named_scope(profiler.LM_LOSS)
def lm_loss(logits, labels, ignore_index: int = -1):
    """Shifted next-token cross-entropy via the fused logsumexp identity
    (same memory rationale as bert.pretraining_loss)."""
    lg = logits[:, :-1].astype(jnp.float32)
    tgt = labels[:, 1:]
    weights = (tgt != ignore_index).astype(jnp.float32)
    safe = jnp.maximum(tgt, 0)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
    per_token = (lse - picked) * weights
    return per_token.sum() / jnp.maximum(weights.sum(), 1.0)
