"""DistributedDataParallel-semantics gradient synchronization over ICI/DCN.

Rebuild of ``apex/parallel/distributed.py`` (SURVEY.md §3.4) on XLA
collectives. The reference registers backward hooks that flatten ready
gradients into ``message_size``-element buckets and allreduce each bucket
on a side CUDA stream (NCCL); ``delay_allreduce=True`` instead performs one
flat-buffer allreduce after the full backward.

TPU mapping: gradient synchronization is a pure function applied to the
grad pytree inside ``shard_map``/``pmap`` over a named mesh axis.
``jax.lax.psum`` over ICI replaces NCCL ring-allreduce, and XLA's
latency-hiding scheduler overlaps collectives with the backward
computation — the role of apex's side streams and hook-driven eager
buckets. The knobs keep their reference meaning:

- ``message_size``: bucket size in elements. Buckets are flattened in
  reverse leaf order (the reference fills buckets in reverse
  gradient-ready order, which approximates reverse forward order).
- ``delay_allreduce``: one flat buffer over all gradients (the
  "flat-buffer path" named in the north star).
- ``allreduce_always_fp32``: upcast bucket buffers to fp32 for the
  reduction, cast back after.
- ``gradient_predivide_factor`` / ``gradient_average``: pre-scale by
  ``1/predivide`` before the psum and post-scale by
  ``predivide/world_size`` after (net ``1/world_size`` when averaging) —
  the reference's overflow-resistant two-stage averaging.
- ``num_allreduce_streams``: accepted for parity; XLA schedules collective
  streams itself.

shard_map autodiff note: under ``jax.shard_map(check_vma=True)``,
differentiating wrt a *replicated* (``P()``) param pytree already yields
the cross-device SUM of per-device gradients — the transpose of the
implicit broadcast is a psum inserted by autodiff. Such gradients are
"unvarying" over the mesh axis (empty ``vma``); psum-ing them again would
multiply by the world size. Under ``check_vma=False`` (every in-repo
``shard_map``, see :func:`apex_tpu.utils.collectives.compat_shard_map`)
no varying-axes set is tracked, autodiff inserts no psum, and an empty
``vma`` says nothing: the same gradient is device-local. So
``allreduce_grads`` first asks whether the axis is tracked at all
(:func:`~apex_tpu.utils.collectives.vma_tracked`). Where it is, it reduces
only device-varying leaves; where it is not, it reduces every leaf. The
averaging divisor is applied either way — so it is correct both for
autodiff-produced grads and for manually assembled per-device values, in
both modes, and a gradient can never pass through unsummed.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu import profiler
from apex_tpu.utils.collectives import group_size, psum_groups, vma_tracked
from apex_tpu.utils.pytree import flatten_buckets, ravel_list, unravel_list


@dataclasses.dataclass(frozen=True)
class DistributedDataParallel:
    axis_name: str = "data"
    message_size: int = 10_000_000
    delay_allreduce: bool = False
    allreduce_always_fp32: bool = False
    gradient_average: bool = True
    gradient_predivide_factor: float = 1.0
    num_allreduce_streams: int = 1  # parity knob; XLA owns scheduling
    retain_allreduce_buffers: bool = False  # parity knob
    axis_index_groups: Optional[tuple] = None  # subgroup reduction support

    def _is_varying(self, x) -> bool:
        """True if ``x`` may still differ across the mesh axis (needs a
        psum): always where varying axes are not tracked, else by its
        ``vma`` — see module docstring."""
        if not vma_tracked(self.axis_name):
            return True
        return self.axis_name in jax.typeof(x).vma

    def _reduce_flat(self, flat, needs_psum: bool):
        orig_dtype = flat.dtype
        if self.allreduce_always_fp32:
            flat = flat.astype(jnp.float32)
        if self.gradient_predivide_factor != 1.0:
            flat = flat / self.gradient_predivide_factor
        if needs_psum:
            flat = psum_groups(flat, self.axis_name, self.axis_index_groups)
        if self.gradient_average:
            world = group_size(self.axis_index_groups, self.axis_name)
            post = self.gradient_predivide_factor / world
            flat = flat * post
        elif self.gradient_predivide_factor != 1.0:
            flat = flat * self.gradient_predivide_factor
        return flat.astype(orig_dtype)

    def allreduce_grads(self, grads):
        """Synchronize a gradient pytree across the ``axis_name`` mesh axis.

        Must be called inside ``shard_map``/``pmap`` where ``axis_name`` is
        bound. Returns the synchronized (averaged by default) grads.

        Leaves are segregated by varying-ness BEFORE any concatenation:
        mixing an already-summed (unvarying) leaf into a buffer with a
        varying one would promote it and psum it a second time.
        """
        leaves, treedef = jax.tree.flatten(grads)
        if not leaves:
            return grads

        out = [None] * len(leaves)
        # reverse leaf order approximates the reference's reverse-ready-
        # order bucket assembly
        rev_ids = list(range(len(leaves)))[::-1]
        for needs_psum in (True, False):
            group_ids = [i for i in rev_ids if self._is_varying(leaves[i]) == needs_psum]
            if not group_ids:
                continue
            group = [leaves[i] for i in group_ids]
            with jax.named_scope(profiler.DDP_FLATTEN):
                if self.delay_allreduce:
                    # flat-buffer path: one allreduce over the whole group
                    flat, meta = ravel_list(group)
                    buckets = [(range(len(group)), flat, meta)]
                else:
                    buckets = flatten_buckets(group, self.message_size)
            for indices, flat, meta in buckets:
                with jax.named_scope(profiler.DDP_ALLREDUCE):
                    flat = self._reduce_flat(flat, needs_psum)
                with jax.named_scope(profiler.DDP_UNFLATTEN):
                    pieces = unravel_list(flat, meta)
                for piece, pos in zip(pieces, indices):
                    out[group_ids[pos]] = piece
        return jax.tree.unflatten(treedef, out)

    def allreduce_accumulated(self, acc, accum_steps: int):
        """Single post-scan reduction: average an fp32 gradient
        accumulator over ``accum_steps`` microbatches, then synchronize
        ONCE across the mesh axis.

        This is the fused-train-step contract (``apex_tpu.train``): the
        scan accumulates local grads on-device and the collective runs
        once per GLOBAL step, not once per microbatch — at
        ``accum_steps=8`` that is 8x fewer allreduce launches for
        identical bytes. The divide happens BEFORE the psum (divide-
        then-reduce), which is bit-identical to the hand-wired
        accumulate / average / ``allreduce_grads`` reference loop —
        folding the 1/accum factor into the post-psum averaging multiply
        would save one multiply but change the rounding, breaking the
        fused-vs-reference certification."""
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        if accum_steps > 1:
            # true division, not a reciprocal multiply: 1/accum is
            # inexact for non-power-of-2 accum and would diverge from
            # the reference loop's ``acc / accum`` at the last bit
            acc = jax.tree.map(
                lambda a: (a / jnp.asarray(accum_steps, a.dtype)
                           if jnp.issubdtype(a.dtype, jnp.floating)
                           else a), acc)
        return self.allreduce_grads(acc)

    def __call__(self, grads):
        return self.allreduce_grads(grads)

    def value_and_grad(self, loss_fn, **vg_kwargs):
        """Convenience: ``jax.value_and_grad`` whose grads are synchronized
        (the wrapped-model UX of the reference DDP)."""
        vg = jax.value_and_grad(loss_fn, **vg_kwargs)

        def wrapped(*args, **kwargs):
            val, grads = vg(*args, **kwargs)
            return val, self.allreduce_grads(grads)

        return wrapped


def flat_dist_call(tensors, axis_name: str = "data", op: str = "sum"):
    """Parity helper for the reference's ``flat_dist_call``: flatten a list
    of arrays, apply one collective, unflatten."""
    flat, meta = ravel_list(list(tensors))
    if op == "sum":
        flat = jax.lax.psum(flat, axis_name)
    elif op == "mean":
        flat = jax.lax.pmean(flat, axis_name)
    elif op == "max":
        flat = jax.lax.pmax(flat, axis_name)
    else:
        raise ValueError(f"unsupported op {op!r}")
    return unravel_list(flat, meta)
