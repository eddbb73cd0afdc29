"""Collective helpers shared across the parallel/transformer layers."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def compat_shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-axes check OFF — the one
    wrapper every in-repo ``shard_map`` goes through.

    With ``check_vma=False`` JAX tracks no varying-axes sets inside the
    body: ``jax.typeof(x).vma`` is empty for every value, no ``pvary``
    is inserted at the boundary, and so autodiff inserts no psum either
    — the gradient of a replicated (``P()``) input is the DEVICE-LOCAL
    gradient. Callers therefore OWN their replication discipline: every
    in-repo user replicates state in, explicitly psums/pmeans/
    all_gathers anything device-varying before an ``out_specs=P()``
    output, and certifies the result in tests (tests/test_train_step.py
    drives the composed step on the 8-device mesh). Code that must
    behave under both settings asks :func:`vma_tracked` first. Do not
    route an ``out_specs=P()`` output through this wrapper without one
    of those collectives."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def vma_tracked(axis_name: str) -> bool:
    """Whether the enclosing ``shard_map`` tracks varying axes for
    ``axis_name`` (``check_vma=True``). ``axis_index`` differs on every
    device by construction, so its type carries the axis exactly when
    types carry axes at all; under ``check_vma=False`` every ``vma`` is
    empty and says nothing about the value."""
    return axis_name in jax.typeof(jax.lax.axis_index(axis_name)).vma


def mark_varying(x, axis_names):
    """Idempotent ``pcast(..., to='varying')`` over a pytree: only axes not
    already in a leaf's varying set are cast (raw pcast raises on
    already-varying input)."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)

    def one(a):
        vma = jax.typeof(a).vma
        missing = tuple(ax for ax in axis_names if ax not in vma)
        if not missing:
            return a
        return jax.lax.pcast(a, missing, to="varying")

    return jax.tree.map(one, x)


def axis_is_bound(axis_name: str) -> bool:
    """Whether ``axis_name`` is currently a bound collective axis
    (inside shard_map/pmap over it). Always returns a bool: if the
    axis-env introspection API moves (it is private), falls back to
    probing ``axis_index``, which raises NameError on unbound names."""
    try:
        from jax._src import core as _core

        return bool(_core.get_axis_env().axis_exists(axis_name))
    except Exception:
        pass
    try:
        jax.lax.axis_index(axis_name)
        return True
    except Exception:
        return False


def psum_groups(x, axis_name: str, groups: Optional[Sequence[Sequence[int]]] = None):
    """``lax.psum`` with subgroup support that works under ``shard_map``.

    ``axis_index_groups`` is the reference ``process_group`` analog
    (SyncBatchNorm subgroups, DDP partial worlds). This JAX version's
    shard_map lowering raises NotImplementedError for grouped psum of
    traced arrays, so when groups are given we fall back to an explicit
    all_gather + static 0/1 group-mask contraction — semantically
    identical, and XLA folds the mask multiply into the reduction.
    """
    if groups is None:
        return jax.lax.psum(x, axis_name)
    try:
        return jax.lax.psum(x, axis_name, axis_index_groups=groups)
    except NotImplementedError:
        pass
    world = jax.lax.psum(1, axis_name, axis_index_groups=None)
    membership = np.zeros((world, world), np.float32)
    for group in groups:
        for i in group:
            for j in group:
                membership[i, j] = 1.0
    gathered = jax.lax.all_gather(x, axis_name)  # (world, ...)
    mask = jnp.asarray(membership)[jax.lax.axis_index(axis_name)]
    return jnp.tensordot(mask, gathered.astype(jnp.float32), axes=1).astype(x.dtype)


def group_size(groups: Optional[Sequence[Sequence[int]]], axis_name: str):
    """Size of the caller's reduction group (static when groups are)."""
    if groups is None:
        return jax.lax.psum(1, axis_name)
    sizes = {len(g) for g in groups}
    if len(sizes) == 1:
        return sizes.pop()
    world = jax.lax.psum(1, axis_name)
    per_dev = np.zeros((world,), np.float32)
    for g in groups:
        for i in g:
            per_dev[i] = len(g)
    return jnp.asarray(per_dev)[jax.lax.axis_index(axis_name)]
