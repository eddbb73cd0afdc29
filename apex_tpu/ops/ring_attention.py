"""Ring attention: context-parallel flash attention over ``ppermute``.

Long-context stretch target (SURVEY.md §5 long-context row): the
reference tops out at Megatron-SP + seq-length-limited fused kernels;
ring attention shards the SEQUENCE across a mesh axis and never
materializes more than one (S/cp)-block of keys/values per device —
sequence length scales linearly with the ring size.

TPU-native design: each device holds its (B, H, S/cp, D) shard of
q/k/v. A ``lax.scan`` runs ``cp`` steps; at each step the device
attends its queries against the CURRENT k/v block with the Pallas flash
kernel (which already returns per-row logsumexp), folds the block's
contribution into fp32 running (accumulator, lse) via the standard
log-sum-exp merge, and rotates k/v to the ring neighbor with
``ppermute`` — compute and the ICI transfer of the NEXT block overlap
under XLA's latency-hiding scheduler (the Ring Attention overlap,
scheduled by the compiler instead of by hand).

Causality across blocks uses the block-index relation (full / in-block
causal / skip via ``lax.switch``); gradients flow by autodiff — the
reverse of the scan replays the ring in the opposite direction
(AD of ppermute is the inverse permutation), with ``jax.checkpoint``
on the per-step body so only O(S/cp) activations persist per step.

Run inside ``shard_map`` with the context axis in scope; sequence
shards are contiguous: device i holds tokens [i*S/cp, (i+1)*S/cp).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops.flash_attention import (
    flash_attention_with_lse,
    mha_reference,
)


def _block_attend(q, k, v, key_mask, causal, scale,
                  dropout_rate=0.0, dropout_seed=None):
    """(out, lse) for one q-block vs one kv-block; lse is (B, H, 1, Sq)
    fp32. Differentiable on both paths — the flash kernel variant folds
    the lse cotangent into its recompute backward."""
    out, lse = flash_attention_with_lse(q, k, v, key_mask, causal, scale,
                                        dropout_rate, dropout_seed)
    return out.astype(jnp.float32), lse


def _block_seed(seed, q_block, kv_block, cp):
    """Per-(global q-block, global kv-block) dropout seed: the base seed
    hashed with the block-pair id (shared :func:`mix_seed` derivation).
    Every tile of the global attention matrix draws an independent PRNG
    stream, and backward replays the same mask because
    (q_block, kv_block) is recomputed identically on the reverse ring
    pass."""
    from apex_tpu.ops._common import mix_seed

    return mix_seed(seed, q_block.astype(jnp.uint32) * jnp.uint32(cp)
                    + kv_block.astype(jnp.uint32))


def ring_attention(q, k, v, key_mask=None, causal: bool = False,
                   scale: float = 1.0, axis_name: str = "context",
                   dropout_rate: float = 0.0, dropout_seed=None):
    """Context-parallel attention over the ring.

    Args:
      q, k, v: this device's (B, H, S_local, D) sequence shard.
      key_mask: optional (B, S_local) boolean padding mask for THIS
        device's keys (True = masked); rotates with k/v.
      causal: causal attention over GLOBAL positions (contiguous
        sharding: device i owns tokens [i*S_local, (i+1)*S_local)).
      scale: softmax temperature.
      axis_name: the context-parallel mesh axis.
      dropout_rate: attention-probability dropout, fused into the
        per-block flash kernels. Correctness across the lse merge: each
        block's kernel applies its keep-mask only to the ``p @ v``
        accumulation while (m, l, lse) stay pre-dropout, so the merged
        ``sum_i exp(lse_i - lse_total) * out_i`` equals composed
        dropout(softmax(s_global)) @ v exactly (the flash linearity
        argument extends across blocks — nothing is double-counted).
      dropout_seed: int32 scalar; per-block masks derive from it hashed
        with the GLOBAL (q-block=this rank, kv-block=source rank) pair
        id, so every tile of the global attention matrix gets an
        independent stream and the reverse ring pass replays the same
        masks. May be shared across ranks (the tile hash decorrelates).

    Returns:
      (B, H, S_local, D) attention outputs for this device's queries,
      in q's dtype.
    """
    from apex_tpu.utils.collectives import mark_varying

    cp = jax.lax.psum(1, axis_name)
    my_rank = jax.lax.axis_index(axis_name)
    B, H, S_local, D = q.shape

    # everything the ring touches is device-varying over the context axis
    # (plus whatever axes q/k/v already vary over)
    vma = frozenset({axis_name})
    for ref in (q, k, v):
        vma |= jax.typeof(ref).vma
    mark = tuple(vma)

    if key_mask is None:
        key_mask = jnp.zeros((B, S_local), bool)
    # the mask rotates through ppermute like k/v: its carry slot must be
    # device-varying even when the caller passed an invariant (or default
    # all-False) mask
    key_mask = mark_varying(key_mask, mark)

    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError(
            "ring_attention with dropout_rate > 0 requires dropout_seed")

    def step_body(q, kv_rank, k_blk, v_blk, mask_blk):
        seed = (None if dropout_rate == 0.0
                else _block_seed(dropout_seed, my_rank, kv_rank, cp))
        if not causal:
            return _block_attend(q, k_blk, v_blk, mask_blk, False, scale,
                                 dropout_rate, seed)

        def full(_):
            return _block_attend(q, k_blk, v_blk, mask_blk, False, scale,
                                 dropout_rate, seed)

        def diag(_):
            return _block_attend(q, k_blk, v_blk, mask_blk, True, scale,
                                 dropout_rate, seed)

        def skip(_):
            return (mark_varying(
                jnp.zeros((B, H, S_local, D), jnp.float32), mark),
                mark_varying(
                    jnp.full((B, H, 1, S_local), -jnp.inf, jnp.float32),
                    mark))

        # kv_rank < my_rank: every key precedes every query -> full;
        # equal: in-block causal; greater: all masked -> skip
        case = jnp.clip(jnp.sign(kv_rank - my_rank) + 1, 0, 2)
        return jax.lax.switch(case, [full, diag, skip], None)

    step_body = jax.checkpoint(step_body, static_argnums=())

    def tick(carry, i):
        acc, lse_acc, k_blk, v_blk, mask_blk = carry
        kv_rank = (my_rank - i) % cp  # block i arrived from rank my-i
        out_i, lse_i = step_body(q, kv_rank, k_blk, v_blk, mask_blk)

        # log-sum-exp merge of the block contribution
        new_lse = jnp.logaddexp(lse_acc, lse_i)
        # fully-masked rows: keep weights finite (0 contribution)
        w_old = jnp.where(jnp.isfinite(new_lse),
                          jnp.exp(lse_acc - new_lse), 0.0)
        w_new = jnp.where(jnp.isfinite(new_lse),
                          jnp.exp(lse_i - new_lse), 0.0)
        acc = acc * w_old[:, :, 0, :, None] + out_i * w_new[:, :, 0, :, None]

        # rotate k/v/mask to the next device for the following step
        n = jax.lax.psum(1, axis_name)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        mask_blk = jax.lax.ppermute(mask_blk, axis_name, perm)
        return (acc, new_lse, k_blk, v_blk, mask_blk), None

    # the running accumulators become device-varying from step 1 on
    # (they mix in ppermuted blocks); mark the init to keep the scan
    # carry type stable under shard_map's vma checking. k/v must be
    # marked too: a caller may pass context-INVARIANT tensors (cp=1
    # mesh, or replicated q/k/v) and the body's ppermute makes the
    # carry slots varying regardless.
    init = (
        mark_varying(jnp.zeros((B, H, S_local, D), jnp.float32), mark),
        mark_varying(jnp.full((B, H, 1, S_local), -jnp.inf, jnp.float32),
                     mark),
        mark_varying(k, mark), mark_varying(v, mark), key_mask,
    )
    (acc, lse, _, _, _), _ = jax.lax.scan(tick, init, jnp.arange(cp))
    return acc.astype(q.dtype)


def ring_attention_reference(q_full, k_full, v_full, key_mask=None,
                             causal=False, scale=1.0):
    """Unsharded reference (full attention) for parity tests."""
    return mha_reference(q_full, k_full, v_full, key_mask, causal, scale)
