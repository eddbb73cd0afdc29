"""What a rematerialised transformer block keeps for its backward pass.

One helper for the block stacks (``models/bert.py``, ``models/gpt.py``,
``models/lfm2.py``, and the Mamba and attention blocks of
``models/nemotron_h.py``): :func:`remat_block` wraps a block class in
``flax.linen.remat`` under one of two policies.

``"selective"`` (the default of ``BertConfig`` / ``GPTConfig``) keeps what
is expensive to compute twice and recomputes the rest: every matmul
output (``dots_with_no_batch_dims_saveable``) and the flash-attention
call's residuals, which the kernel's forward rules name
(:data:`apex_tpu.profiler.FLASH_RESIDUALS`), stay live from the forward
pass; LayerNorm, GELU, dropout, bias and residual adds are recomputed
from them. ``"full"`` keeps the block's input alone and recomputes the
whole forward pass in the backward: 18-19% more step time for 2.55-3.78
GiB less live memory at 8,192 tokens a chip over 24 layers of width 1024
on a v5e (``PERF.md`` section 6, PR 31). Megatron-LM's "selective
activation recomputation" is the same split.

A block that ROUTES (top-k experts) may keep rows only together with the
routing that ordered them: rows kept across a recomputed routing can meet
a recomputed order that differs by a bfloat16 rounding (``PERF.md``
section 6, PR 30). The caller decides from the block's type: ``"full"``
where the expert layer names nothing (``GPTBlock(use_moe=True)``), and
:func:`remat_routing_block` where it names its routing and its rows
(:class:`apex_tpu.transformer.moe.DroplessMoE`,
:data:`apex_tpu.profiler.MOE_RESIDUALS`): the backward pass then does the
router's scores, the row gather, the shared expert's up projection and the
elementwise ops again, and never a sort or a grouped matmul (``PERF.md``
section 6, PR 33).
"""

from __future__ import annotations

import flax.linen as nn
import jax

from apex_tpu import profiler

POLICIES = ("selective", "full")


def _policy(name):
    if name == "selective":
        cp = jax.checkpoint_policies
        return cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable,
            cp.save_only_these_names(*profiler.FLASH_RESIDUALS))
    if name == "full":
        return None
    raise ValueError(
        f"remat_policy must be one of {POLICIES}, got {name!r}")


def remat_block(block_cls, static_argnums, policy):
    """``block_cls`` wrapped in ``nn.remat`` under the named policy."""
    return nn.remat(block_cls, static_argnums=static_argnums,
                    policy=_policy(policy))


def remat_routing_block(block_cls, policy="full"):
    """``block_cls``, a block whose expert layer names its routing and the
    rows it ordered, wrapped in ``nn.remat`` so that those names are kept.
    Under ``"full"`` everything else is recomputed; under ``"selective"``
    the block's dense matmul outputs and flash attention's residuals are
    kept as well (a layer that holds a mixer beside its experts:
    ``models/lfm2.py``). No config names this policy: the caller picks it
    from the block's type."""
    names = jax.checkpoint_policies.save_only_these_names(
        *profiler.MOE_RESIDUALS)
    dense = _policy(policy)
    if dense is not None:
        names = jax.checkpoint_policies.save_from_both_policies(dense, names)
    return nn.remat(block_cls, policy=names)
