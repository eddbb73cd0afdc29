"""ZeRO-style sharded-data-parallel fused optimizers.

Rebuild of ``apex/contrib/optimizers/distributed_fused_adam.py`` and
``distributed_fused_lamb.py`` (SURVEY.md §2.3 "ZeRO-style sharded DP"):
the reference reduce-scatters gradients into per-rank fp32 master shards,
runs the fused update on the local shard only, and all-gathers the
updated parameters — optimizer state is sharded ``world_size``-ways, so
fp32 (master, m, v) cost drops from 12 bytes/param to 12/dp.

TPU-native design: the whole step is three collectives on a flat fp32
stream inside ``shard_map`` over the data-parallel mesh axis —

1. ``psum_scatter`` the flattened gradient (tiled): each rank receives
   the SUMMED gradient slice for its shard — the reduce-scatter the
   reference issues per bucket, here one XLA collective that rides ICI.
   ``predivide_grads`` (default) divides by dp for the DDP gradient mean.
2. the Adam/LAMB math on the rank's shard, DELEGATED to the same
   ``ops.multi_tensor`` update functions the unsharded optimizers use,
   so sharded and unsharded trajectories agree by construction. The
   shard is held as a LANE-shaped ``(shard/128, 128)`` 2-D buffer, not
   1-D: elementwise update streams over a huge 1-D vector invite XLA's
   horizontal [N,2] packing whose ``T(8,128)`` tiled layout pads the
   size-2 minor dim 64x (the 94 GB pathology documented in
   ``ops/multi_tensor.py``); a lane-major 2-D shape tiles natively.
   LAMB's per-tensor trust ratios are computed across shard boundaries:
   each rank segment-sums its shard's squared entries into per-tensor
   partials and one ``psum`` completes the exact norms — the analog of
   the reference's partial-norm + allreduce in
   ``distributed_fused_lamb._pipeline_block_reductions``. Segment ids
   come from a ``searchsorted`` over the static leaf-offset table, O(N/dp)
   per device (never a full-length N map).
3. ``all_gather`` (tiled) of the updated shard back to the full flat
   vector. When every parameter shares one low-precision dtype (the O2
   bf16 case) the shard is cast BEFORE the gather, halving the dominant
   per-step collective (the reference all-gathers in model dtype for the
   same reason); mixed-dtype models gather in fp32.

Unlike the CUDA version there are no overlap hooks, streams, or bucket
knobs to manage: XLA's latency-hiding scheduler overlaps the collectives
with surrounding compute, which is what the reference's
``overlap_reductions``/side-stream machinery hand-builds.

Both optimizers follow the functional ``init/step`` contract of
``apex_tpu.optimizers`` (skip_if = amp overflow no-op, lr override). Two
execution modes select how the three collectives are spelled:

- ``flat_mode="collective"`` (default): the explicit ``psum_scatter`` /
  ``psum`` / ``all_gather`` spelling above — must be called inside
  ``shard_map`` with ``process_group`` in scope.
- ``flat_mode="global"``: GLOBAL-math GSPMD spelling for the sharded
  fused train step (``build_train_step(mesh=...)``). State buffers hold
  the FULL padded flat stream as a lane-shaped ``(padded/128, 128)``
  array committed to ``P(process_group, None)`` over ``mesh`` — each
  rank materializes only its row block, the same 12/dp bytes/param as
  the collective mode — and ``with_sharding_constraint`` steers the XLA
  SPMD partitioner to insert the reduce+scatter and gather collectives.
  Two constraint placements are load-bearing (see
  ``_global_grad_rows``): gradients replicate BEFORE the flatten, and
  the flat stream materializes replicated before the ZeRO slice.
  Without a ``mesh`` the global mode degenerates to a world-of-1 local
  optimizer (the meshless arm of the (1,1) bit-identity certification).
  ``predivide_grads`` is ignored: global math is already mean-correct.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops._common import LANE, round_up
from apex_tpu.ops.multi_tensor import (
    ADAM_MODE_ADAMW,
    ADAM_MODE_L2,
    multi_tensor_adam,
    multi_tensor_lamb_stage1,
)
from apex_tpu.optimizers._base import FusedOptimizer
from apex_tpu.utils.pytree import ravel_list, tree_select, unravel_list


class _FlatMeta:
    """Static flattening metadata for a params pytree (trace-time only).

    The padded length is a multiple of ``world * LANE`` so every rank's
    shard reshapes exactly to ``(rows, LANE)`` (see module docstring on
    why the shard must be lane-shaped)."""

    def __init__(self, params, world_size: int):
        leaves = jax.tree.leaves(params)
        self.treedef = jax.tree.structure(params)
        self.meta = [(l.shape, l.dtype, l.size) for l in leaves]
        self.sizes = [m[2] for m in self.meta]
        self.dtypes = [m[1] for m in self.meta]
        self.total = sum(self.sizes)
        self.world = world_size
        self.padded = round_up(max(self.total, 1), world_size * LANE)
        self.shard = self.padded // world_size
        self.rows = self.shard // LANE
        self.num_leaves = len(leaves)
        # static cumulative end-offsets for per-tensor segment lookup
        self.offsets = np.cumsum(self.sizes).astype(np.int32)
        # gather in model dtype when it is a single low-precision dtype
        # (halves the all_gather); otherwise keep the fp32 master stream
        uniq = set(self.dtypes)
        if len(uniq) == 1 and jnp.dtype(next(iter(uniq))).itemsize < 4:
            self.gather_dtype = next(iter(uniq))
        else:
            self.gather_dtype = jnp.float32

    def flatten(self, tree):
        """apex_C.flatten analog (fp32 stream) + ZeRO padding."""
        flat, _ = ravel_list(
            [l.astype(jnp.float32) for l in jax.tree.leaves(tree)])
        if self.padded != self.total:
            flat = jnp.pad(flat, (0, self.padded - self.total))
        return flat

    def unflatten(self, flat):
        leaves = unravel_list(flat[:self.total], self.meta)
        return jax.tree.unflatten(self.treedef, leaves)

    def shard_segment_ids(self, rank):
        """(rows, LANE) int32 leaf index per shard element, computed
        arithmetically from the static offset table (O(shard), not O(N));
        the padding tail maps to the dummy bucket ``num_leaves``."""
        pos = rank * self.shard + jnp.arange(self.shard, dtype=jnp.int32)
        seg = jnp.searchsorted(jnp.asarray(self.offsets), pos, side="right")
        return seg.reshape(self.rows, LANE)

    def shard_slice(self, flat, rank):
        """This rank's lane-shaped shard of a (padded,) stream."""
        return jax.lax.dynamic_slice(
            flat, (rank * self.shard,), (self.shard,)
        ).reshape(self.rows, LANE)


class ShardedOptState(NamedTuple):
    step: jnp.ndarray
    exp_avg: jnp.ndarray      # (shard/128, 128) fp32
    exp_avg_sq: jnp.ndarray   # (shard/128, 128) fp32
    master: jnp.ndarray       # (shard/128, 128) fp32 master params


@dataclasses.dataclass(frozen=True)
class _DistributedFlatOptimizer(FusedOptimizer):
    """Shared reduce-scatter → shard-update → all-gather machinery."""

    process_group: str = "data"   # mesh axis the optimizer shards over
    group_size: int = 0           # 0 = resolve from parallel_state
    predivide_grads: bool = True  # divide the psum'd grad by dp (DDP mean)
    flat_mode: str = "collective"  # "collective" (shard_map) | "global"
    mesh: Any = None              # GSPMD mesh for flat_mode="global"

    def __post_init__(self):
        if self.flat_mode not in ("collective", "global"):
            raise ValueError(
                f"flat_mode must be 'collective' or 'global', "
                f"got {self.flat_mode!r}")
        if self.mesh is not None and self.flat_mode != "global":
            raise ValueError(
                "mesh= requires flat_mode='global' (the collective mode "
                "runs inside shard_map and never sees a Mesh object)")

    def _world(self) -> int:
        if self.mesh is not None:
            return int(self.mesh.shape[self.process_group])
        if self.flat_mode == "global":
            # meshless global math has no axis to shard over: a single
            # world-of-1 "shard" holding the whole padded stream
            if self.group_size not in (0, 1):
                raise ValueError(
                    f"flat_mode='global' without mesh= is the world-of-1 "
                    f"local optimizer; group_size={self.group_size} needs "
                    f"a mesh to shard over")
            return 1
        if self.group_size:
            return self.group_size
        from apex_tpu.transformer import parallel_state

        return parallel_state.get_data_parallel_world_size()

    def _meta(self, params) -> _FlatMeta:
        """The flattening metadata, computed ONCE per (world, treedef,
        leaf-shapes) and cached on the config object — the padding is
        counted a single time and :meth:`stats` reports it without
        recomputing (or disagreeing with) what init/step used."""
        leaves = jax.tree.leaves(params)
        key = (self._world(), jax.tree.structure(params),
               tuple((tuple(l.shape), jnp.dtype(l.dtype).name)
                     for l in leaves))
        cached = getattr(self, "_meta_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        meta = _FlatMeta(params, self._world())
        object.__setattr__(self, "_meta_cache", (key, meta))
        return meta

    def stats(self) -> dict:
        """Flat-buffer accounting of the LAST init/step geometry —
        ``flat_pad_elems`` is the ZeRO padding a donation-alias count or
        a memory account must take as real bytes (the padded tail
        lives in every master/m/v buffer). Raises before the first
        ``init``/``step`` call (no geometry has been built yet)."""
        cached = getattr(self, "_meta_cache", None)
        if cached is None:
            raise ValueError(
                "stats() before init()/step(): the flat-buffer geometry "
                "is built on first use")
        meta = cached[1]
        return {
            "flat_total_elems": int(meta.total),
            "flat_padded_elems": int(meta.padded),
            "flat_pad_elems": int(meta.padded - meta.total),
            "flat_shard_elems": int(meta.shard),
            "flat_world": int(meta.world),
            # fp32 master + exp_avg + exp_avg_sq per shard
            "opt_state_bytes_per_shard": int(meta.shard) * 4 * 3,
        }

    # -- GSPMD global-math spelling (flat_mode="global") -----------------

    def _zspec(self):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh,
                             PartitionSpec(self.process_group, None))

    def _rep(self):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec())

    def _global_grad_rows(self, grads, meta):
        """The reduce-scatter leg, GSPMD spelling: constrain the grad
        leaves REPLICATED before the flatten (so the reshape/concat
        into the flat stream is shard-local — straight into the ZeRO
        spec the partitioner reshards TP-sharded leaves with an
        all-to-all and, at combined (B, M) meshes on the XLA vintage we
        pin, mis-partitions the concat), then materialize the stream
        replicated and slice to ``P(process_group, None)`` — lowered as
        the cross-batch reduction + scatter of exactly one flat
        reduce-scatter (XLA:CPU spells it all-reduce + slice; the
        ``alt_min_ops`` contract accepts both). No predivide: global
        math already averages over the global batch."""
        if self.mesh is not None:
            grads = jax.tree.map(
                lambda l: jax.lax.with_sharding_constraint(l, self._rep()),
                grads)
        rows = meta.flatten(grads).reshape(meta.padded // LANE, LANE)
        if self.mesh is not None:
            rows = jax.lax.with_sharding_constraint(rows, self._rep())
            rows = jax.lax.with_sharding_constraint(rows, self._zspec())
        return rows

    def _global_gather_params(self, new_master, meta, params):
        """The all-gather leg: one replicated materialization of the
        updated flat stream (cast to ``gather_dtype`` first — the
        collective moves the smaller payload), then shard-local
        unflatten; the train step re-constrains the leaves to their
        tensor-parallel specs (a local slice, no second collective).

        Each unflattened leaf is pinned replicated too: left to
        propagation, GSPMD pulls the consumer's tensor-parallel spec
        backward into the 1-D slice and then reshards the reshape with
        an all-to-all / collective-permute chain per leaf; pinning
        keeps the slice+reshape shard-local so the only resharding is
        the free replicated→TP slice downstream."""
        full = new_master.astype(meta.gather_dtype)
        if self.mesh is not None:
            full = jax.lax.with_sharding_constraint(full, self._rep())
        leaves = meta.unflatten(full.reshape(-1))
        if self.mesh is not None:
            leaves = jax.tree.map(
                lambda l: jax.lax.with_sharding_constraint(l, self._rep()),
                leaves)
        return leaves

    def init(self, params) -> ShardedOptState:
        """Build the optimizer-state shard. ``flat_mode="collective"``
        must run inside ``shard_map`` with ``process_group`` in scope
        (uses ``axis_index``); ``flat_mode="global"`` runs eagerly and
        commits the full lane-shaped stream sharded over ``mesh``."""
        meta = self._meta(params)
        if self.flat_mode == "global":
            host = jax.tree.map(
                lambda x: jnp.asarray(jax.device_get(x)), params)
            rows_total = meta.padded // LANE
            master = meta.flatten(host).reshape(rows_total, LANE)
            # distinct zero buffers: a donated state must never hold the
            # same array twice (double-donation raises on XLA:CPU)
            m = jnp.zeros((rows_total, LANE), jnp.float32)
            v = jnp.zeros((rows_total, LANE), jnp.float32)
            step = jnp.zeros((), jnp.int32)
            if self.mesh is not None:
                zspec = self._zspec()
                master = jax.device_put(master, zspec)
                m = jax.device_put(m, zspec)
                v = jax.device_put(v, zspec)
                step = jax.device_put(step, self._rep())
            return ShardedOptState(step=step, exp_avg=m, exp_avg_sq=v,
                                   master=master)
        rank = jax.lax.axis_index(self.process_group)
        master = meta.shard_slice(meta.flatten(params), rank)
        zeros = jnp.zeros((meta.rows, LANE), jnp.float32)
        return ShardedOptState(
            step=jnp.zeros((), jnp.int32),
            exp_avg=zeros,
            exp_avg_sq=zeros,
            master=master,
        )

    def _grad_rows(self, grads, meta):
        if self.flat_mode == "global":
            return self._global_grad_rows(grads, meta)
        return self._reduce_scatter_grads(grads, meta)

    def _reduce_scatter_grads(self, grads, meta):
        flat_g = meta.flatten(grads)
        gshard = jax.lax.psum_scatter(
            flat_g, self.process_group, scatter_dimension=0, tiled=True)
        if self.predivide_grads:
            gshard = gshard / meta.world
        return gshard.reshape(meta.rows, LANE)

    def _gather(self, new_master, meta, params):
        if self.flat_mode == "global":
            return self._global_gather_params(new_master, meta, params)
        return self._gather_params(new_master, meta, params)

    def _gather_params(self, new_master, meta, params):
        full = jax.lax.all_gather(
            new_master.reshape(-1).astype(meta.gather_dtype),
            self.process_group, axis=0, tiled=True)
        return meta.unflatten(full)

    def _finish(self, skip_if, new_params, new_state, params, state):
        if skip_if is None:
            return new_params, new_state
        return (tree_select(skip_if, params, new_params),
                tree_select(skip_if, state, new_state))


@dataclasses.dataclass(frozen=True)
class DistributedFusedAdam(_DistributedFlatOptimizer):
    """Reference: ``apex.contrib.optimizers.DistributedFusedAdam`` —
    Adam/AdamW with ZeRO-sharded fp32 state over the data axis.

    The shard update IS ``multi_tensor_adam`` (the unsharded FusedAdam's
    math) applied to the lane-shaped shard, so trajectories agree with
    the unsharded optimizer to fp32 roundoff."""

    lr: float = 1e-3
    bias_correction: bool = True
    betas: Tuple[float, float] = (0.9, 0.999)
    eps: float = 1e-8
    adam_w_mode: bool = True
    weight_decay: float = 0.0

    def step(self, grads, state: ShardedOptState, params, skip_if=None,
             lr=None):
        lr = self.lr if lr is None else lr
        meta = self._meta(params)
        step = state.step + 1

        g = self._grad_rows(grads, meta)
        new_p_l, new_m_l, new_v_l = multi_tensor_adam(
            0, None,
            [[g], [state.master], [state.exp_avg], [state.exp_avg_sq]],
            lr, self.betas[0], self.betas[1], self.eps, step,
            ADAM_MODE_ADAMW if self.adam_w_mode else ADAM_MODE_L2,
            self.bias_correction, self.weight_decay,
        )
        new_master, m, v = new_p_l[0], new_m_l[0], new_v_l[0]

        new_params = self._gather(new_master, meta, params)
        new_state = ShardedOptState(step, m, v, new_master)
        return self._finish(skip_if, new_params, new_state, params, state)


@dataclasses.dataclass(frozen=True)
class DistributedFusedLAMB(_DistributedFlatOptimizer):
    """Reference: ``apex.contrib.optimizers.DistributedFusedLAMB`` —
    two-stage LAMB with ZeRO-sharded fp32 state.

    Stage 1 (clip + moments + update direction) delegates to
    ``multi_tensor_lamb_stage1`` on the lane-shaped shard with the
    psum-completed global grad norm. Stage 2 cannot delegate: per-tensor
    trust ratios need per-tensor norms across shard boundaries —
    computed via the arithmetic segment map + one psum (see module
    docstring).

    ``grad_averaging`` matches FusedLAMB (folds beta3 only); the DDP mean
    division is the separate ``predivide_grads`` knob."""

    lr: float = 1e-3
    bias_correction: bool = True
    betas: Tuple[float, float] = (0.9, 0.999)
    eps: float = 1e-6
    weight_decay: float = 0.01
    adam_w_mode: bool = True
    grad_averaging: bool = True
    max_grad_norm: float = 1.0
    use_nvlamb: bool = False

    def __post_init__(self):
        if not self.adam_w_mode:
            raise RuntimeError(
                "DistributedFusedLAMB only supports adam_w_mode, matching "
                "the reference kernel.")

    def step(self, grads, state: ShardedOptState, params, skip_if=None,
             lr=None):
        lr = self.lr if lr is None else lr
        meta = self._meta(params)
        step = state.step + 1
        nbuckets = meta.num_leaves + 1  # + dummy padding bucket
        if self.flat_mode == "global":
            # full-stream segment map: in global math every rank sees
            # the whole (padded/128, 128) buffer (sharded), so segment
            # ids cover all of it and no rank index exists
            pos = jnp.arange(meta.padded, dtype=jnp.int32)
            seg = jnp.searchsorted(jnp.asarray(meta.offsets), pos,
                                   side="right").reshape(-1, LANE)
        else:
            rank = jax.lax.axis_index(self.process_group)
            seg = meta.shard_segment_ids(rank)

        g = self._grad_rows(grads, meta)
        p = state.master

        # stage 0: global grad norm (partial on shard, psum completes
        # it; in global math the plain sum is already global — the
        # partitioner inserts the reduction)
        if self.flat_mode == "global":
            global_norm = jnp.sqrt(jnp.sum(g * g))
        else:
            global_norm = jnp.sqrt(
                jax.lax.psum(jnp.sum(g * g), self.process_group))

        # stage 1: clip + moments + update direction (shared math)
        updates, new_m, new_v = multi_tensor_lamb_stage1(
            0, None, [[g], [p], [state.exp_avg], [state.exp_avg_sq]],
            self.betas[0], self.betas[1], self.eps, step,
            self.bias_correction, self.weight_decay, self.grad_averaging,
            global_norm, self.max_grad_norm,
        )
        update, m, v = updates[0], new_m[0], new_v[0]

        # stage 2: exact per-tensor trust ratios across shard boundaries
        apply_ratio = self.use_nvlamb or self.weight_decay != 0.0
        if apply_ratio:
            w_sq = jnp.zeros((nbuckets,), jnp.float32).at[seg].add(p * p)
            u_sq = jnp.zeros((nbuckets,), jnp.float32).at[seg].add(
                update * update)
            if self.flat_mode == "global":
                w_norm, u_norm = jnp.sqrt(w_sq), jnp.sqrt(u_sq)
            else:
                w_norm = jnp.sqrt(jax.lax.psum(w_sq, self.process_group))
                u_norm = jnp.sqrt(jax.lax.psum(u_sq, self.process_group))
            ratio = jnp.where((w_norm > 0) & (u_norm > 0),
                              w_norm / jnp.where(u_norm > 0, u_norm, 1.0),
                              1.0)
            step_scale = ratio[seg]
        else:
            step_scale = jnp.float32(1.0)
        new_master = p - lr * step_scale * update

        new_params = self._gather(new_master, meta, params)
        new_state = ShardedOptState(step, m, v, new_master)
        return self._finish(skip_if, new_params, new_state, params, state)
