"""BERT on apex_tpu building blocks — the north-star flagship model.

The reference ships no models (apex is a library; its BERT lives in the
NVIDIA DeepLearningExamples MLPerf harness that BASELINE.json's
``configs[4]`` points at). This module provides the equivalent workload:
BERT-large pretraining (MLM + NSP) assembled from the framework's own
pieces — FusedLayerNorm (Pallas), FusedScaleMaskSoftmax (Pallas),
amp O2 + FusedLAMB + DDP at the training-step level — plus Megatron-style
TP and sequence parallelism via the tensor_parallel layers for multi-chip
meshes.

Layout notes (TPU-first): activations are batch-major ``(B, S, H)``;
under sequence parallelism the per-rank activation is ``(B, S/tp, H)``
and token-major ``(S, B)`` ordering is used across the first-dim
gather/reduce-scatter mappings (the reason Megatron is s,b,h internally).
Matmuls carry ``preferred_element_type=fp32`` so bf16 inputs hit the MXU
with fp32 accumulation. ``fused_kernels=False`` swaps the Pallas norm/
softmax for stock flax/jnp ops — the unfused baseline.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import profiler
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.transformer.functional import AttnMaskType, FusedScaleMaskSoftmax
from apex_tpu.transformer.remat import remat_block


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 1024          # bert-large
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: int = 4096
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layernorm_eps: float = 1e-12
    dtype: jnp.dtype = jnp.float32   # activation/compute dtype (bf16 for O2)
    remat: bool = True               # activation checkpointing per layer
    # what a checkpointed layer keeps (apex_tpu/transformer/remat.py):
    # "selective" keeps the matmul and flash-attention outputs and
    # recomputes only the elementwise ops (LayerNorm, GELU, dropout,
    # adds); "full" keeps the layer's input alone and recomputes the
    # whole forward pass - 19% more step time for 2.55 GiB less live
    # memory at 24 x 1024, S=512, B=16 on a v5e (PERF.md section 6,
    # PR 31): the way back for a job that fitted only under full
    # recomputation
    remat_policy: str = "selective"  # "selective" | "full"
    fused_kernels: bool = True       # Pallas LN/softmax vs stock ops
    # Pallas flash attention (reference: contrib fmha). Used when the
    # sequence is long enough to win (>= flash_min_seq; measured v5e
    # crossover); attention dropout is fused in-kernel (hardware PRNG),
    # so the training config keeps the flash path.
    flash_attention: bool = True
    flash_min_seq: int = 256
    # multi-chip: use tensor_parallel layers (requires bound "tensor" axis)
    use_tensor_parallel: bool = False
    sequence_parallel: bool = False

    @staticmethod
    def bert_large(**kw):
        return BertConfig(**kw)

    @staticmethod
    def bert_base(**kw):
        return BertConfig(hidden_size=768, num_layers=12, num_heads=12,
                          intermediate_size=3072, **kw)

    @staticmethod
    def tiny(**kw):
        """Test/dryrun config."""
        kw.setdefault("vocab_size", 128)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("max_position_embeddings", 64)
        return BertConfig(**kw)


_BERT_INIT = nn.initializers.normal(stddev=0.02)


def _dense(cfg, features, name):
    return nn.Dense(
        features,
        dtype=cfg.dtype,
        param_dtype=jnp.float32,
        kernel_init=_BERT_INIT,
        name=name,
    )


def _norm(cfg, name):
    if cfg.fused_kernels:
        return FusedLayerNorm(cfg.hidden_size, eps=cfg.layernorm_eps, name=name)
    return nn.LayerNorm(epsilon=cfg.layernorm_eps, dtype=cfg.dtype,
                        param_dtype=jnp.float32, name=name)


def _attn_softmax(cfg, scores, mask):
    scale = 1.0
    if cfg.fused_kernels:
        return FusedScaleMaskSoftmax(
            attn_mask_type=AttnMaskType.padding, scale=scale,
        )(scores, mask)
    xf = scores.astype(jnp.float32)
    if mask is not None:
        xf = jnp.where(mask, -30000.0, xf)
    return jax.nn.softmax(xf, axis=-1).astype(scores.dtype)


from apex_tpu.models._dropout import (  # noqa: E402 (model-shared)
    TPDropout as _TPDropout,
    dropout_seed as _dropout_seed,
)


# sequence-parallel layout helpers: (B, S_local, H) <-> (S_local*B, H)
# token-major so first-dim gather/scatter stacks along the sequence.

def _sp_enter(x):
    return x.transpose(1, 0, 2).reshape(-1, x.shape[-1])


def _sp_exit(t, batch):
    return t.reshape(-1, batch, t.shape[-1]).transpose(1, 0, 2)


class BertSelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, attention_mask, deterministic: bool = True):
        cfg = self.cfg
        h, nh = cfg.hidden_size, cfg.num_heads
        hd = h // nh
        B = x.shape[0]
        inv_sqrt = 1.0 / (hd ** 0.5)

        if cfg.use_tensor_parallel:
            from apex_tpu.transformer import parallel_state
            from apex_tpu.transformer.tensor_parallel import (
                ColumnParallelLinear,
                RowParallelLinear,
            )

            tp = parallel_state.get_tensor_model_parallel_world_size()
            nh_local, local_h = nh // tp, h // tp
            t = _sp_enter(x) if cfg.sequence_parallel else x.reshape(-1, h)
            qkv_t = ColumnParallelLinear(
                input_size=h, output_size=3 * h, gather_output=False,
                sequence_parallel_enabled=cfg.sequence_parallel,
                init_method=_BERT_INIT, name="qkv")(t)
            qkv = (_sp_exit(qkv_t, B) if cfg.sequence_parallel
                   else qkv_t.reshape(B, -1, 3 * local_h))
            # Megatron layout: this rank's shard is [q_loc | k_loc | v_loc]
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            # three flat (B, S, H) projections, NOT one fused qkv + split:
            # the split is a 3-way copy, and the flat layout feeds the
            # transpose-free flash entry directly (gradients come back
            # flat too — no concat in backward)
            q = _dense(cfg, h, "q")(x)
            k = _dense(cfg, h, "k")(x)
            v = _dense(cfg, h, "v")(x)
            nh_local, local_h = nh, h

        # under SP the block input is the (B, S/tp, H) LOCAL shard but
        # attention runs over the FULL sequence (q is full-S after the
        # SP gather inside ColumnParallelLinear) — gate on the full
        # length, not the shard
        full_seq = x.shape[1] * (tp if (cfg.use_tensor_parallel
                                        and cfg.sequence_parallel) else 1)
        use_flash = (
            cfg.fused_kernels and cfg.flash_attention
            and full_seq >= cfg.flash_min_seq
            # flash takes a BOOLEAN per-key padding mask; the (B, 1, 1, Sk)
            # convention from BertModel reduces to it exactly. Additive
            # float masks must go through the composed-softmax path.
            and (attention_mask is None
                 or (attention_mask.ndim == 4
                     and attention_mask.dtype == jnp.bool_
                     and attention_mask.shape[1] == 1
                     and attention_mask.shape[2] == 1))
        )
        if use_flash:
            from apex_tpu.ops.flash_attention import flash_attention_bsh

            key_mask = (None if attention_mask is None
                        else attention_mask[:, 0, 0, :])
            drop = (0.0 if deterministic else cfg.attention_dropout)
            # fused in-kernel dropout (reference fmha's Philox path);
            # heads are sharded under TP, so fold the TP rank in
            seed = (_dropout_seed(self, cfg.use_tensor_parallel)
                    if drop > 0.0 else None)
            # (B, S, H)-layout kernels: no head split/merge transposes
            # (falls back to the transposed entry off the single-tile
            # regime — see ops/flash_attention.py)
            ctx = flash_attention_bsh(q, k, v, key_mask, nh_local, False,
                                      inv_sqrt, drop, seed)
            ctx = ctx.astype(cfg.dtype)
        else:
            def heads(t):
                return t.reshape(B, -1, nh_local, hd).transpose(0, 2, 1, 3)

            qh, kh, vh = heads(q), heads(k), heads(v)
            scores = jnp.einsum("bnqd,bnkd->bnqk", qh, kh,
                                preferred_element_type=jnp.float32) * inv_sqrt
            probs = _attn_softmax(cfg, scores.astype(cfg.dtype), attention_mask)
            # attention probs are head-sharded under TP: per-rank masks
            probs = _TPDropout(cfg.attention_dropout,
                               tp_varying=cfg.use_tensor_parallel,
                               fused=cfg.fused_kernels)(
                probs, deterministic=deterministic)
            ctx = jnp.einsum("bnqk,bnkd->bnqd", probs.astype(cfg.dtype), vh,
                             preferred_element_type=jnp.float32)
            ctx = ctx.astype(cfg.dtype).transpose(0, 2, 1, 3).reshape(
                B, -1, local_h)

        if cfg.use_tensor_parallel:
            from apex_tpu.transformer.tensor_parallel import RowParallelLinear

            t = (_sp_enter(ctx) if cfg.sequence_parallel
                 else ctx.reshape(-1, local_h))
            out_t = RowParallelLinear(
                input_size=h, output_size=h, input_is_parallel=True,
                sequence_parallel_enabled=cfg.sequence_parallel,
                init_method=_BERT_INIT, name="out")(t)
            out = (_sp_exit(out_t, B) if cfg.sequence_parallel
                   else out_t.reshape(B, -1, h))
        else:
            out = _dense(cfg, h, "out")(ctx)
        return out.astype(cfg.dtype)


class BertLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, attention_mask, deterministic: bool = True):
        cfg = self.cfg
        B = x.shape[0]
        attn = BertSelfAttention(cfg, name="attention")(
            x, attention_mask, deterministic)
        # sequence-sharded under SP (per-rank tokens → per-rank masks);
        # replicated under plain TP (masks must agree across ranks)
        sp = cfg.use_tensor_parallel and cfg.sequence_parallel
        attn = _TPDropout(cfg.hidden_dropout, tp_varying=sp,
                          fused=cfg.fused_kernels)(
            attn, deterministic=deterministic)
        x = _norm(cfg, "attention_ln")(x + attn)

        if cfg.use_tensor_parallel:
            from apex_tpu.transformer.tensor_parallel import (
                ColumnParallelLinear,
                RowParallelLinear,
            )

            t = _sp_enter(x) if cfg.sequence_parallel else x.reshape(-1, cfg.hidden_size)
            hmid = ColumnParallelLinear(
                input_size=cfg.hidden_size, output_size=cfg.intermediate_size,
                gather_output=False,
                sequence_parallel_enabled=cfg.sequence_parallel,
                init_method=_BERT_INIT, name="mlp_in")(t)
            hmid = nn.gelu(hmid)
            mlp_t = RowParallelLinear(
                input_size=cfg.intermediate_size, output_size=cfg.hidden_size,
                input_is_parallel=True,
                sequence_parallel_enabled=cfg.sequence_parallel,
                init_method=_BERT_INIT, name="mlp_out")(hmid)
            mlp = (_sp_exit(mlp_t, B) if cfg.sequence_parallel
                   else mlp_t.reshape(B, -1, cfg.hidden_size)).astype(cfg.dtype)
        else:
            hmid = _dense(cfg, cfg.intermediate_size, "mlp_in")(x)
            hmid = nn.gelu(hmid)
            mlp = _dense(cfg, cfg.hidden_size, "mlp_out")(hmid)
        mlp = _TPDropout(cfg.hidden_dropout, tp_varying=sp,
                         fused=cfg.fused_kernels)(
            mlp, deterministic=deterministic)
        return _norm(cfg, "output_ln")(x + mlp)


class BertEmbeddings(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids, deterministic: bool = True):
        cfg = self.cfg
        if cfg.use_tensor_parallel:
            from apex_tpu.transformer.tensor_parallel import VocabParallelEmbedding

            word = VocabParallelEmbedding(
                num_embeddings=cfg.vocab_size, embedding_dim=cfg.hidden_size,
                name="word_embeddings")(input_ids)
        else:
            word = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                            embedding_init=nn.initializers.normal(0.02),
                            param_dtype=jnp.float32,
                            name="word_embeddings")(input_ids)
        S = input_ids.shape[-1]
        pos = self.param(
            "position_embeddings", nn.initializers.normal(0.02),
            (cfg.max_position_embeddings, cfg.hidden_size), jnp.float32)[:S]
        typ = nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                       embedding_init=nn.initializers.normal(0.02),
                       param_dtype=jnp.float32,
                       name="token_type_embeddings")(token_type_ids)
        x = word + pos[None, :, :] + typ
        x = _norm(cfg, "ln")(x.astype(cfg.dtype))
        return _TPDropout(cfg.hidden_dropout, fused=cfg.fused_kernels)(
            x, deterministic=deterministic)


class BertModel(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True):
        cfg = self.cfg
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = BertEmbeddings(cfg, name="embeddings")(
            input_ids, token_type_ids, deterministic)
        # (B, 1, 1, S) boolean: True = masked (reference convention)
        mask4d = None
        if attention_mask is not None:
            mask4d = (attention_mask == 0)[:, None, None, :]

        if cfg.use_tensor_parallel and cfg.sequence_parallel:
            # shard the sequence across TP ranks between blocks (Megatron-SP)
            from apex_tpu.transformer import parallel_state
            from apex_tpu.utils.collectives import mark_varying

            tp = parallel_state.get_tensor_model_parallel_world_size()
            rank = jax.lax.axis_index(parallel_state.TENSOR_AXIS)
            s_local = x.shape[1] // tp
            x = jax.lax.dynamic_slice_in_dim(
                mark_varying(x, parallel_state.TENSOR_AXIS),
                rank * s_local, s_local, axis=1)

        layer_cls = BertLayer
        if cfg.remat:
            layer_cls = remat_block(BertLayer, (3,), cfg.remat_policy)
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, name=f"layer_{i}")(x, mask4d, deterministic)

        if cfg.use_tensor_parallel and cfg.sequence_parallel:
            from apex_tpu.transformer.tensor_parallel import gather_along_first_dim

            B = x.shape[0]
            x = _sp_exit(gather_along_first_dim(_sp_enter(x)), B)

        pooled = jnp.tanh(_dense(cfg, cfg.hidden_size, "pooler")(x[:, 0]))
        return x, pooled


class BertForPreTraining(nn.Module):
    """MLM + NSP heads (the BASELINE configs[4] pretraining objective).

    ``masked_positions`` (B, P) int32: when given, the MLM head
    (transform + LN + vocab decoder) runs ONLY on the gathered masked
    positions — the MLPerf-BERT input format (max_predictions_per_seq),
    which is how the reference harness computes the head: at S=512 with
    P=76 the decoder matmul shrinks 6.7x. ``mlm_logits`` is then
    (B, P, V) and the loss takes the gathered (B, P) labels/weights.
    Without it the head runs over every position (round-3 behavior)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True, masked_positions=None):
        cfg = self.cfg
        x, pooled = BertModel(cfg, name="bert")(
            input_ids, token_type_ids, attention_mask, deterministic)
        with jax.named_scope(profiler.MLM_HEAD):
            if masked_positions is not None:
                x = jnp.take_along_axis(
                    x, masked_positions[..., None].astype(jnp.int32), axis=1)
            h = _dense(cfg, cfg.hidden_size, "mlm_transform")(x)
            h = nn.gelu(h)
            h = _norm(cfg, "mlm_ln")(h)
            if cfg.use_tensor_parallel:
                from apex_tpu.transformer.tensor_parallel import (
                    ColumnParallelLinear,
                )

                # local-vocab-shard logits, consumed by
                # vocab_parallel_cross_entropy
                mlm_logits = ColumnParallelLinear(
                    input_size=cfg.hidden_size, output_size=cfg.vocab_size,
                    gather_output=False, init_method=_BERT_INIT,
                    name="mlm_decoder",
                )(h.reshape(-1, cfg.hidden_size)).reshape(*h.shape[:-1], -1)
            else:
                mlm_logits = _dense(cfg, cfg.vocab_size, "mlm_decoder")(h)
        with jax.named_scope(profiler.NSP_HEAD):
            nsp_logits = _dense(cfg, 2, "nsp")(pooled)
        return mlm_logits, nsp_logits


@jax.named_scope(profiler.PRETRAINING_LOSS)
def pretraining_loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels,
                     mlm_weights=None, vocab_parallel: bool = False):
    """Masked-LM + next-sentence loss, fp32 (the MLPerf BERT objective).

    ``mlm_labels``: (B, S) with -1 (ignore) elsewhere. With
    ``vocab_parallel``, ``mlm_logits`` is the local vocab shard and the
    per-token loss comes from :func:`vocab_parallel_cross_entropy`.
    """
    labels = jnp.maximum(mlm_labels, 0)
    if mlm_weights is None:
        mlm_weights = (mlm_labels >= 0).astype(jnp.float32)
    if vocab_parallel:
        from apex_tpu.transformer.tensor_parallel import (
            vocab_parallel_cross_entropy,
        )

        per_token = vocab_parallel_cross_entropy(mlm_logits, labels)
    else:
        # fused logsumexp form (contrib xentropy identity): avoids
        # materializing the fp32 (B, S, V) log-prob tensor — at
        # BERT-large B=8 S=512 that intermediate alone is ~0.5 GB
        xf = mlm_logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(xf, axis=-1)
        picked = jnp.take_along_axis(xf, labels[..., None], axis=-1)[..., 0]
        per_token = lse - picked
    denom = jnp.maximum(mlm_weights.sum(), 1.0)
    mlm_loss = (per_token * mlm_weights).sum() / denom

    nsp_logp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
    nsp_loss = -jnp.take_along_axis(
        nsp_logp, nsp_labels[:, None], axis=-1).mean()
    return mlm_loss + nsp_loss
