"""The fused train step: one donated dispatch per global optimizer step.

apex exists to make the training step one fused device pass — amp,
``multi_tensor_apply`` optimizers, and bucketed-allreduce DDP are all
pieces of that loop — but composing them by hand leaves the *step
structure* on the host: one dispatch per microbatch, a separate
optimizer dispatch, a host fetch of the loss every step, and a
transient second copy of params + moments because nothing is donated.
The serving engine already proved this stack is dispatch/host-sync
bound (fusing K decode steps per dispatch took CPU decode 880 -> 2835
tok/s); this module applies the same physics to training:

- **One dispatch per global step.** Forward, backward, loss-scale
  unscale + in-graph overflow skip, gradient accumulation, DDP
  allreduce, and the fused optimizer update compile into a single
  jitted program.
- **Scanned gradient accumulation.** The ``accum_steps`` microbatches
  run as a ``jax.lax.scan`` inside that program. Gradients accumulate
  on-device in fp32; the DDP collective runs ONCE after the scan
  (``DistributedDataParallel.allreduce_accumulated``), not once per
  microbatch.
- **Donated buffers.** The :class:`TrainState` argument is donated, so
  params, optimizer moments, and scaler state alias in place — no
  transient second copy of BERT-large params + moments. The compiled
  program's ``input_output_alias`` table is auditable via
  :meth:`TrainStep.alias_stats`
  (:func:`apex_tpu.utils.hlo_audit.input_output_alias_stats`), because
  XLA drops donation silently when a layout mismatches.
- **Deferred metrics.** Step metrics (loss, scale, skip counters) come
  back as device scalars; :class:`apex_tpu.train.TrainLoop` fetches
  step ``t-1``'s metrics after dispatching step ``t`` — the training
  analog of the serving engine's deferred sync — so the host never
  blocks the device.

Certification: :func:`build_reference_loop` builds the hand-wired
per-microbatch dispatch loop (one jitted program per microbatch plus an
apply program) from the SAME configuration with bit-identical math in
the same order; tests/test_train_step.py certifies the fused scan
against it the way tests/test_serving.py certifies cross-K decode.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu import profiler
from apex_tpu.amp.handle import AmpHandle
from apex_tpu.amp.scaler import LossScaler, ScalerState
from apex_tpu.utils.collectives import compat_shard_map
from apex_tpu.utils.pytree import all_finite, global_norm

try:  # jax.sharding is stable across the vintages we support
    from jax.sharding import PartitionSpec as _P
except ImportError:  # pragma: no cover
    _P = None


class TrainState(NamedTuple):
    """The donated carry of the fused step: everything that evolves.

    Treat a ``TrainState`` you passed into a donating step as CONSUMED —
    its buffers now back the returned state. Reading a donated array
    raises; keep only the returned state (see docs/training.md).
    """

    step: jnp.ndarray        # i32 — completed global optimizer steps
    params: Any
    opt_state: Any
    scaler_state: ScalerState


def _resolve_scaler(amp, loss_id: int):
    """(scaler, trace_wrapper) from an AmpHandle, a LossScaler, or None
    (None = static unity scale: unscale is exact, update only counts)."""
    if isinstance(amp, AmpHandle):
        return amp.scaler(loss_id), amp.traced
    if isinstance(amp, LossScaler):
        return amp, None
    if amp is None:
        return LossScaler(loss_scale=1.0), None
    raise TypeError(
        f"amp must be an AmpHandle, a LossScaler, or None; got {type(amp)}")


def _strip_leading_axis(spec):
    """Drop the leading (accumulation-axis) entry from a PartitionSpec
    or a pytree of them — the reference loop feeds one microbatch at a
    time, so its per-dispatch specs lose the accum axis the fused
    scan's specs carry."""
    if _P is not None and isinstance(spec, _P):
        return _P(*tuple(spec)[1:])
    return jax.tree.map(_strip_leading_axis, spec,
                        is_leaf=lambda s: isinstance(s, _P))


def _check_batch(batch, accum_steps: int):
    leaves = jax.tree.leaves(batch)
    if not leaves:
        raise ValueError("batch has no leaves")
    for leaf in leaves:
        shape = jnp.shape(leaf)
        if not shape or shape[0] != accum_steps:
            raise ValueError(
                f"every batch leaf needs a leading microbatch axis of "
                f"length accum_steps={accum_steps}; got shape {shape}. "
                f"Reshape [accum*B, ...] data to [accum, B, ...].")


def _is_flat_optimizer(optimizer) -> bool:
    from apex_tpu.contrib.optimizers.distributed_fused_adam import (
        _DistributedFlatOptimizer,
    )

    return isinstance(optimizer, _DistributedFlatOptimizer)


class _GspmdPlan:
    """The sharded train step's layout plan: one object owning every
    NamedSharding decision of the GSPMD path (``build_train_step`` with
    ``mesh=`` and no ``ddp=``) —

    - **params** follow ``pspec_fn(path)`` (default: the Megatron
      decomposition, :func:`apex_tpu.models.gpt.gpt_param_pspec`) —
      tensor-parallel activations fall out of GSPMD propagation;
    - **optimizer state**: a ZeRO flat optimizer's lane-shaped stream
      shards ``P("batch", None)`` (each rank owns its flat row block);
      per-leaf moments mirror their parameter's spec (``pspec_fn`` is
      applied by trailing path, which moment subtrees preserve);
    - **batch** leaves shard ``batch_spec`` (default ``P(None,
      "batch")``: accumulation axis unsharded, global batch split over
      the batch axis — the data-parallel leg, reductions inserted by
      the partitioner from the global-mean loss);
    - **scalars** (step counter, scaler state, metrics) replicate.

    The plan is applied twice per object: ``commit_state`` device_puts
    the initial state (committed inputs = stable jit cache keys), and
    ``constrain_state`` pins the OUTPUT layouts inside the jitted
    program — without the output pin GSPMD may hand back a
    differently-laid-out tree whose next dispatch recompiles, the same
    one-program contract the serving mesh pins with out_shardings.
    """

    def __init__(self, mesh, pspec_fn, batch_spec, zero: bool):
        from jax.sharding import NamedSharding

        self.mesh = mesh
        self.pspec_fn = pspec_fn
        self.batch_spec = batch_spec
        self.zero = zero
        self.rep = NamedSharding(mesh, _P())
        self.zspec = self._named(_P("batch", None))

    def _canon(self, spec):
        """Canonicalize a PartitionSpec the way GSPMD spells output
        shardings: drop axis names of mesh size 1, then strip trailing
        ``None`` entries (``P('model', None)`` → ``P('model')``,
        ``P(None, 'model')`` on a model=1 mesh → ``P()``). Committing
        inputs with the exact output spelling is what pins the jit
        cache at one entry — a semantically-equal-but-differently-
        spelled sharding is a cache MISS, and the second dispatch
        silently retraces."""
        shape = dict(self.mesh.shape)

        def live(entry):
            if entry is None:
                return None
            if isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if shape.get(a, 1) > 1)
                return kept if kept else None
            return entry if shape.get(entry, 1) > 1 else None

        entries = [live(e) for e in tuple(spec)]
        while entries and entries[-1] is None:
            entries.pop()
        return _P(*entries)

    def _named(self, spec):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, self._canon(spec))

    # -- shardings ------------------------------------------------------

    def param_shardings(self, params):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: self._named(self.pspec_fn(path)), params)

    def opt_shardings(self, opt_state):
        if self.zero:
            # ShardedOptState: scalar step + three lane-shaped streams
            return type(opt_state)(
                step=self.rep, exp_avg=self.zspec,
                exp_avg_sq=self.zspec, master=self.zspec)
        # per-leaf moments mirror params: the trailing (module, leaf)
        # path names survive the NamedTuple wrapper, so pspec_fn applies
        return jax.tree_util.tree_map_with_path(
            lambda path, x: (self._named(self.pspec_fn(path))
                             if jnp.ndim(x) else self.rep),
            opt_state)

    def batch_shardings(self, batch):
        if isinstance(self.batch_spec, _P):
            specs = jax.tree.map(lambda x: self.batch_spec, batch)
        else:
            specs = self.batch_spec
        axis_sizes = dict(self.mesh.shape)

        def check(x, spec):
            shape = jnp.shape(x)
            for dim, names in enumerate(tuple(spec)):
                if names is None:
                    continue
                names = names if isinstance(names, tuple) else (names,)
                div = 1
                for n in names:
                    div *= axis_sizes[n]
                if dim >= len(shape) or shape[dim] % div:
                    raise ValueError(
                        f"mesh axis {names} (size {div}) must divide "
                        f"batch dim {dim} of leaf shape {shape} — pad "
                        f"the per-step batch to a multiple of the mesh "
                        f"batch axis or shrink the mesh")
            return self._named(spec)

        return jax.tree.map(check, batch, specs)

    # -- placement ------------------------------------------------------

    @staticmethod
    def _put(x, sharding):
        return (jax.device_put(x, sharding) if hasattr(x, "ndim")
                or not isinstance(x, int) else x)

    @staticmethod
    def _pin(x, sharding):
        return (jax.lax.with_sharding_constraint(x, sharding)
                if hasattr(x, "ndim") or not isinstance(x, int) else x)

    def _place_state(self, state: TrainState, put) -> TrainState:
        rep_tree = lambda tree: jax.tree.map(  # noqa: E731
            lambda x: put(x, self.rep), tree)
        return TrainState(
            step=put(state.step, self.rep),
            params=jax.tree.map(put, state.params,
                                self.param_shardings(state.params)),
            opt_state=jax.tree.map(put, state.opt_state,
                                   self.opt_shardings(state.opt_state)),
            scaler_state=rep_tree(state.scaler_state),
        )

    def commit_state(self, state: TrainState) -> TrainState:
        return self._place_state(state, self._put)

    def constrain_state(self, state: TrainState) -> TrainState:
        return self._place_state(state, self._pin)

    def commit_batch(self, batch):
        return jax.tree.map(jax.device_put, batch,
                            self.batch_shardings(batch))

    def constrain_metrics(self, metrics):
        return jax.tree.map(
            lambda x: self._pin(x, self.rep), metrics)


class _StepCore:
    """Shared math of the fused step and the reference loop — ONE
    definition so the certification compares program structure, never
    two transcriptions of the update rule."""

    def __init__(self, loss_fn, optimizer, scaler, trace_wrapper, ddp,
                 accum_steps, has_aux, lr_schedule, with_grad_norm,
                 loss_id):
        self.loss_fn = loss_fn if trace_wrapper is None else trace_wrapper(loss_fn)
        self.optimizer = optimizer
        self.scaler = scaler
        self.ddp = ddp
        self.accum_steps = int(accum_steps)
        self.has_aux = has_aux
        self.lr_schedule = lr_schedule
        self.with_grad_norm = with_grad_norm
        self.loss_id = loss_id
        # GSPMD hook (set by TrainStep on the mesh path): constrain the
        # fp32 grad accumulator to the PARAM pspecs at every boundary —
        # the scan carry, and the reduced grads entering the optimizer.
        # Left to propagation, the partitioner gives backward-pass grad
        # leaves layouts that mismatch the committed moment buffers, and
        # reconciles each elementwise Adam op with an all-to-all (and
        # reshards the carry every scan iteration). A no-op when unset
        # and at a (1, 1) mesh — the bit-identity certifications hold.
        self.acc_constraint = None
        if self.accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    # -- per-microbatch accumulation (identical in fused and reference) --

    def microbatch(self, params, sst: ScalerState, carry, mb):
        """Accumulate one microbatch's unscaled fp32 grads into carry.

        carry = (acc_f32_tree, loss_sum_f32, inf_any_bool[, aux_slot]).
        The scaled value_and_grad + unscale + finite check is exactly
        what a hand-wired loop calls per microbatch
        (:meth:`LossScaler.value_and_grad`) — the fused scan must not
        change a single op of it.
        """
        acc, loss_sum, inf_any = carry[:3]
        vg = self.scaler.value_and_grad(
            lambda p: self.loss_fn(p, mb), sst, has_aux=self.has_aux)
        with jax.named_scope(profiler.TRAIN_FWD_BWD):
            if self.has_aux:
                (loss, found, aux), grads = vg(params)
            else:
                (loss, found), grads = vg(params)
                aux = None
        with jax.named_scope(profiler.TRAIN_ACCUMULATE):
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                               acc, grads)
            loss_sum = loss_sum + loss.astype(jnp.float32)
            inf_any = jnp.logical_or(inf_any, found)
        return (acc, loss_sum, inf_any), aux

    def zero_carry(self, params):
        with jax.named_scope(profiler.TRAIN_ACCUMULATE):
            acc = jax.tree.map(
                lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params)
            if self.acc_constraint is not None:
                acc = self.acc_constraint(acc)
            return acc, jnp.zeros((), jnp.float32), jnp.zeros((), bool)

    # -- post-accumulation tail (identical in fused and reference) -------

    def reduce_grads(self, acc):
        """Average over microbatches, then the single post-scan
        synchronization (when DDP is configured)."""
        if self.ddp is not None:
            return self.ddp.allreduce_accumulated(acc, self.accum_steps)
        if self.accum_steps > 1:
            acc = jax.tree.map(
                lambda a: a / jnp.asarray(self.accum_steps, a.dtype), acc)
        if self.acc_constraint is not None:
            acc = self.acc_constraint(acc)
        return acc

    def apply(self, state: TrainState, acc, loss_sum, inf_any, aux=None):
        """Reduce, globalize the overflow flag, optimizer update, scaler
        update, metrics. Returns ``(new_state, metrics)``."""
        with jax.named_scope(profiler.TRAIN_REDUCE):
            grads = self.reduce_grads(acc)
        # Globalize the skip decision: a non-finite grad on ANY device /
        # microbatch is already non-finite in the reduced tree (inf
        # survives both the fp32 accumulate and the psum), so this one
        # check makes every device skip in lockstep — per-device local
        # flags alone would let replicas diverge under DDP.
        with jax.named_scope(profiler.AMP_FOUND_INF):
            found = jnp.logical_or(inf_any,
                                   jnp.logical_not(all_finite(grads)))
        lr = (None if self.lr_schedule is None
              else self.lr_schedule(state.step))

        # The optimizer update runs as a real lax.cond branch on the
        # TRACED overflow flag, not a compute-both tree_select. Two
        # reasons. (1) Certification: a cond branch is its own HLO
        # computation, so XLA's fusion/FMA-contraction decisions inside
        # it cannot depend on the enclosing program — the fused step and
        # the reference apply dispatch compile the identical update
        # arithmetic identically (inlined, the p - lr*update chain
        # contracted differently between the two programs and drifted an
        # ulp by step 2). (2) Semantics: an overflow step now skips the
        # update work entirely, the in-graph form of apex's patched
        # optimizer.step() no-op.
        def _apply_branch(operands):
            g, ost, p = operands
            return self.optimizer.apply_gradients(g, ost, p,
                                                  skip_if=None, lr=lr)

        def _skip_branch(operands):
            _, ost, p = operands
            return p, ost

        with jax.named_scope(profiler.OPTIMIZER_UPDATE):
            new_params, new_opt = jax.lax.cond(
                found, _skip_branch, _apply_branch,
                (grads, state.opt_state, state.params))
        with jax.named_scope(profiler.AMP_UPDATE_SCALE):
            new_sst = self.scaler.update(state.scaler_state, found)
        with jax.named_scope(profiler.TRAIN_METRICS):
            loss = loss_sum / jnp.asarray(self.accum_steps, jnp.float32)
            if self.ddp is not None:
                loss = jax.lax.pmean(loss, self.ddp.axis_name)
            metrics = {
                "loss": loss,
                "loss_scale": state.scaler_state.loss_scale,  # scale USED
                "skipped": found,
                "steps_skipped": new_sst.steps_skipped,
                "step": state.step + 1,
            }
            if self.with_grad_norm:
                metrics["grad_norm"] = global_norm(grads)
            if aux is not None:
                if self.ddp is not None:
                    # aux is device-varying (per-example values of THIS
                    # device's shard); the metrics out_spec is replicated,
                    # so without a gather one undefined device's slice
                    # would silently survive. Gather to an explicit leading
                    # device axis: [world, accum, ...local] — lossless and
                    # shape-predictable for any user aux pytree.
                    aux = jax.tree.map(
                        lambda a: jax.lax.all_gather(a, self.ddp.axis_name),
                        aux)
                metrics["aux"] = aux
            new_state = TrainState(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                scaler_state=new_sst,
            )
        return new_state, metrics

    # -- the fused single-dispatch program -------------------------------

    def fused_step(self, state: TrainState, batch):
        params, sst = state.params, state.scaler_state

        def body(carry, mb):
            new_carry, aux = self.microbatch(params, sst, carry, mb)
            if self.acc_constraint is not None:
                acc_c, loss_c, inf_c = new_carry
                new_carry = (self.acc_constraint(acc_c), loss_c, inf_c)
            # Pin the reference loop's DISPATCH boundary: each hand-wired
            # microbatch ends a program, so nothing there cross-fuses the
            # backward into the next phase's arithmetic. When this scan
            # unrolls (accum_steps=1), XLA would fuse backward straight
            # into the optimizer update and shift the final params by an
            # ulp — breaking the fused-vs-loop bit-identity certification
            # for a "fusion" the baseline could never perform. The
            # barrier costs nothing at trip >= 2 (the scan boundary is
            # already a barrier) and keeps the certification honest.
            return jax.lax.optimization_barrier(new_carry), aux

        # the microbatch loop IS the accumulation: its own plumbing (carry
        # copies, microbatch slices) is filed under the accumulate scope
        with jax.named_scope(profiler.TRAIN_ACCUMULATE):
            (acc, loss_sum, inf_any), aux = jax.lax.scan(
                body, self.zero_carry(params), batch)
        if not self.has_aux:
            aux = None
        return self.apply(state, acc, loss_sum, inf_any, aux=aux)


class TrainStep:
    """A compiled global train step; build with :func:`build_train_step`.

    ``step(state, batch) -> (new_state, metrics)`` where ``batch``
    leaves are shaped ``[accum_steps, per_step_batch, ...]`` and
    ``metrics`` are DEVICE scalars (fetch deferred — see
    :class:`apex_tpu.train.TrainLoop`). ``state`` is donated when
    ``donate=True`` (default): the passed-in state is consumed.
    """

    def __init__(self, core: _StepCore, donate: bool, mesh, batch_spec,
                 param_pspec=None, num_heads: Optional[int] = None):
        self._core = core
        self.donate = donate
        self.accum_steps = core.accum_steps
        self._plan: Optional[_GspmdPlan] = None
        self.mesh_shape: Optional[tuple] = None
        fn = core.fused_step
        if mesh is not None and core.ddp is None:
            # GSPMD single-dispatch path: ZeRO + tensor parallel via
            # sharding annotation on the serving mesh, no shard_map
            from apex_tpu.serving.mesh import MESH_AXES, validate_mesh_shape

            if tuple(mesh.axis_names) != MESH_AXES:
                raise ValueError(
                    f"mesh= without ddp= is the GSPMD train path and "
                    f"needs the serving mesh axes {MESH_AXES} "
                    f"(serving.mesh.build_mesh); got {mesh.axis_names}")
            shape = (int(mesh.shape["batch"]), int(mesh.shape["model"]))
            validate_mesh_shape(shape, num_heads=num_heads, knob="mesh")
            zero = _is_flat_optimizer(core.optimizer)
            if zero and core.optimizer.group_size not in (0, shape[0]):
                raise ValueError(
                    f"the flat optimizer's group_size "
                    f"({core.optimizer.group_size}) must be 0 or the "
                    f"mesh batch axis ({shape[0]}): the ZeRO shard "
                    f"count IS the batch axis on the GSPMD path")
            if param_pspec is None:
                from apex_tpu.models.gpt import gpt_param_pspec
                param_pspec = gpt_param_pspec
            self.mesh_shape = shape
            self._mesh = mesh
            self._plan = plan = _GspmdPlan(
                mesh, param_pspec,
                batch_spec if batch_spec is not None else _P(None, "batch"),
                zero=zero)
            core.acc_constraint = lambda acc: jax.tree.map(
                plan._pin, acc, plan.param_shardings(acc))

            def fn(state, batch):
                new_state, metrics = core.fused_step(state, batch)
                return (plan.constrain_state(new_state),
                        plan.constrain_metrics(metrics))
        elif mesh is not None:
            # legacy 1-D shard_map path (ddp's axis over mesh)
            if batch_spec is None:
                batch_spec = _P(None, core.ddp.axis_name)
            fn = compat_shard_map(
                fn, mesh,
                in_specs=(_P(), batch_spec),
                out_specs=(_P(), _P()),
            )
        self._jitted = (jax.jit(fn, donate_argnums=(0,)) if donate
                        else jax.jit(fn))
        # the compile record files this program's trace, lower and
        # compile spans under ``profiler.TRAIN_STEP_PROGRAM``
        profiler.compile_record().claim(profiler.TRAIN_STEP_PROGRAM, fn)

    def init(self, params, scaler_state: Optional[ScalerState] = None
             ) -> TrainState:
        """Fresh :class:`TrainState` (step 0, zero moments, scaler at its
        initial scale — or carry in a checkpointed ``scaler_state``).
        On the GSPMD path the params are committed to their mesh layout
        first and the whole state comes back committed (stable jit
        cache keys; pass uncommitted host params freely). A
        ``train_init`` program span of the compile record."""
        with profiler.compile_record().program_span(profiler.TRAIN_INIT):
            if self._plan is not None:
                from apex_tpu.serving.mesh import shard_params

                params = shard_params(self._mesh, params,
                                      pspec_fn=self._plan.pspec_fn)
            state = TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=self._core.optimizer.init(params),
                scaler_state=(self._core.scaler.init()
                              if scaler_state is None else scaler_state),
            )
            if self._plan is not None:
                state = self._plan.commit_state(state)
            return state

    def step(self, state: TrainState, batch):
        _check_batch(batch, self.accum_steps)
        if self._plan is not None:
            batch = self._plan.commit_batch(batch)
        return self._jitted(state, batch)

    __call__ = step

    def lower(self, state: TrainState, batch):
        """AOT-lower the jitted step for ``state``/``batch`` (arrays or
        sharded ``ShapeDtypeStruct``s): nothing is dispatched and a
        donating step's state is not consumed. ``.compile()`` on the
        result gives the program's text and memory analysis. A
        ``train_lower`` program span of the compile record (the trace and
        the lowering nest under it; ``.compile()`` is the caller's)."""
        _check_batch(batch, self.accum_steps)
        with profiler.compile_record().program_span(profiler.TRAIN_LOWER):
            return self._jitted.lower(state, batch)

    @property
    def program(self):
        """The raw (unjitted, un-shard_mapped) step function
        ``(state, batch) -> (state, metrics)`` — for callers embedding
        the step in their own pmap/shard_map/pjit wrapper instead of
        passing ``mesh=``."""
        return self._core.fused_step

    def alias_stats(self, state: TrainState, batch):
        """Donation audit of the compiled program: the
        ``input_output_alias`` pairs XLA actually honored. A fused step
        doing its job aliases every param + optimizer-moment + scaler
        buffer; assert ``pairs >= n_param_leaves`` in tests (lowering
        does not execute or consume the donated state)."""
        from apex_tpu.utils.hlo_audit import lowered_alias_stats

        _check_batch(batch, self.accum_steps)
        return lowered_alias_stats(self._jitted, state, batch)

    def audit_collectives(self, state: TrainState, batch,
                          num_layers: Optional[int] = None) -> dict:
        """Certify the sharded step's compiled program against the
        per-mesh collective contract — the serving mesh's audit applied
        to training. AOT-lowers from abstract sharded ShapeDtypeStructs
        (no dispatch, no donated-buffer consumption, jit cache
        untouched) and asserts:

        - :func:`apex_tpu.serving.mesh.train_expected_collectives` for
          this mesh shape — zero collectives at (1, 1); the one
          reduce-scatter + all-gather ZeRO round trip (or XLA:CPU's
          all-reduce spelling, ``alt_min_ops``) when the batch axis
          shards a flat optimizer; ``>= 2 * num_layers`` all-reduces on
          the tensor-parallel leg; never an all-to-all;
        - donation alias pairs ``>=`` the sharded param + optimizer
          leaf count (XLA drops donation silently; the positive count
          is the certification signal).

        ``num_layers`` defaults to reading the GPT block count off
        ``state.params`` (:func:`~apex_tpu.models.gpt.gpt_num_layers`);
        pass it explicitly for non-GPT trees. Returns
        ``{"collectives", "alias", "contract", "sharded_leaves"}``.
        Raises ``AssertionError`` on any violation; requires the GSPMD
        ``mesh=`` path."""
        from apex_tpu.serving.mesh import train_expected_collectives
        from apex_tpu.utils.hlo_audit import (
            abstract_sharded,
            assert_collective_contract,
            collective_stats,
            input_output_alias_stats,
        )

        if self._plan is None:
            raise ValueError(
                "audit_collectives requires the GSPMD train step "
                "(build_train_step(mesh=...) without ddp=)")
        _check_batch(batch, self.accum_steps)
        specs = self._plan.batch_shardings(batch)
        abatch = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                jnp.shape(x), getattr(x, "dtype", jnp.asarray(x).dtype),
                sharding=s),
            batch, specs)
        txt = (self._jitted.lower(abstract_sharded(state), abatch)
               .compile().as_text())
        # exclude_degenerate: CSE-merged scalar-constant broadcasts
        # resharded across mixed-layout leaves lower as all-to-alls
        # of a constant — no data moves; counting them would fail
        # the no-all-to-all contract on an artifact
        stats = collective_stats(txt, exclude_degenerate=True)
        if num_layers is None:
            from apex_tpu.models.gpt import gpt_num_layers

            num_layers = gpt_num_layers(state.params) or None
        contract = train_expected_collectives(
            self.mesh_shape, num_layers=num_layers, zero=self._plan.zero)
        label = f"train_step@mesh{self.mesh_shape}"
        assert_collective_contract(stats, label=label, **contract)
        alias = input_output_alias_stats(txt)
        sharded_leaves = sum(
            1 for leaf in jax.tree.leaves((state.params, state.opt_state))
            if hasattr(leaf, "ndim"))
        if self.donate and alias["pairs"] < sharded_leaves:
            raise AssertionError(
                f"{label}: XLA honored {alias['pairs']} donation alias "
                f"pair(s) but the state carries {sharded_leaves} sharded "
                f"param + optimizer leaves — donation was dropped "
                f"(layout/dtype mismatch between a donated input and "
                f"its output)")
        return {"collectives": stats, "alias": alias,
                "contract": contract, "sharded_leaves": sharded_leaves}

    def loop(self, state: TrainState, **kwargs):
        """A deferred-metrics :class:`apex_tpu.train.TrainLoop` over this
        step, starting from ``state``; keyword arguments (fault plan,
        retry, watchdog, checkpoint knobs) forward to the loop."""
        from apex_tpu.train.loop import TrainLoop

        return TrainLoop(self, state, **kwargs)


def build_train_step(
    loss_fn: Callable,
    optimizer,
    amp=None,
    ddp=None,
    accum_steps: int = 1,
    has_aux: bool = False,
    lr_schedule: Optional[Callable] = None,
    with_grad_norm: bool = False,
    donate: bool = True,
    mesh=None,
    batch_spec=None,
    param_pspec=None,
    num_heads: Optional[int] = None,
    loss_id: int = 0,
) -> TrainStep:
    """Compile forward + backward + unscale/overflow-skip + accumulation
    + DDP allreduce + fused optimizer update into ONE donated dispatch.

    Args:
      loss_fn: ``loss_fn(params, microbatch) -> loss`` (or ``(loss,
        aux)`` with ``has_aux=True``); ``microbatch`` is one slice along
        the batch's leading accumulation axis.
      optimizer: a Fused* optimizer (anything with the
        ``apply_gradients`` donation-friendly surface of
        :class:`apex_tpu.optimizers._base.FusedOptimizer`).
      amp: an :class:`~apex_tpu.amp.handle.AmpHandle` from
        ``amp.initialize`` (threads its loss scaler AND its O1 autocast
        trace wrapper), a bare :class:`LossScaler`, or None (unity
        static scale).
      ddp: optional :class:`DistributedDataParallel`; its collective
        runs once per global step, after the scan.
      accum_steps: microbatches accumulated (scanned) per optimizer
        step. Batch leaves must be ``[accum_steps, ...]``.
      lr_schedule: optional ``lr_schedule(completed_steps_i32) -> lr``.
      with_grad_norm: include the post-reduction global grad norm in the
        metrics (one extra fused reduction pass).
      donate: donate the :class:`TrainState` (in-place aliased updates).
      mesh / batch_spec: with ``ddp``, wrap the program in ``shard_map``
        over ``mesh`` (the legacy 1-D data-parallel path; ``batch_spec``
        defaults to ``P(None, ddp.axis_name)``). WITHOUT ``ddp``, a
        ``mesh`` selects the GSPMD single-dispatch path: the serving
        ``("batch", "model")`` mesh (``serving.mesh.build_mesh``), with
        tensor-parallel params via ``param_pspec``, the global batch
        sharded ``P(None, "batch")``, and — when ``optimizer`` is a
        ``DistributedFused*`` flat optimizer — ZeRO state sharded over
        the batch axis, all inside ONE donated dispatch whose contract
        :meth:`TrainStep.audit_collectives` certifies. Mesh geometry is
        validated here, at construction, with named-knob errors.
        Without ``mesh`` the caller may shard_map the returned step
        themselves (via :attr:`TrainStep.program`).
      param_pspec: GSPMD path only — ``pspec_fn(path) -> PartitionSpec``
        for each param leaf (default
        :func:`apex_tpu.models.gpt.gpt_param_pspec`); also applied (by
        trailing path) to mirrored per-leaf optimizer moments.
      num_heads: GSPMD path only — when given, the mesh ``model`` axis
        must divide it (construction-time check; the trace would
        otherwise fail deep inside attention).
    """
    sharded = mesh is not None and ddp is None
    if _is_flat_optimizer(optimizer):
        if sharded:
            bsize = dict(mesh.shape).get("batch")
            if bsize is not None and optimizer.group_size not in (
                    0, int(bsize)):
                raise ValueError(
                    f"the flat optimizer's group_size "
                    f"({optimizer.group_size}) must be 0 or the mesh "
                    f"batch axis ({int(bsize)}): the ZeRO shard count "
                    f"IS the batch axis on the GSPMD path")
            optimizer = optimizer.replace(
                flat_mode="global", mesh=mesh,
                process_group="batch",
                group_size=int(bsize) if bsize else 0)
        elif mesh is None and optimizer.mesh is not None:
            raise ValueError(
                "the flat optimizer carries a mesh but build_train_step "
                "got mesh=None; pass the same mesh (or a fresh "
                "unconfigured optimizer)")
    scaler, trace_wrapper = _resolve_scaler(amp, loss_id)
    core = _StepCore(loss_fn, optimizer, scaler, trace_wrapper, ddp,
                     accum_steps, has_aux, lr_schedule, with_grad_norm,
                     loss_id)
    return TrainStep(core, donate, mesh, batch_spec,
                     param_pspec=param_pspec, num_heads=num_heads)


class ReferenceLoop:
    """The hand-wired per-microbatch dispatch loop the fused step
    replaces — SAME math, same order, one jitted program per microbatch
    plus a separate apply program. Exists as the certification baseline
    (bit-identity in tests/test_train_step.py) and as an honest
    what-it-cost-before arm; do not use it to train.
    """

    def __init__(self, core: _StepCore, mesh, batch_spec):
        self._core = core
        self._mesh = mesh
        self.accum_steps = core.accum_steps
        ddp = core.ddp

        if mesh is None:
            def grad_mb(params, sst, carry, mb):
                new_carry, _ = core.microbatch(params, sst, carry, mb)
                return new_carry

            def apply_fn(state, carry):
                acc, loss_sum, inf_any = carry
                return core.apply(state, acc, loss_sum, inf_any)
        else:
            if ddp is None:
                raise ValueError("mesh= without ddp=")
            if batch_spec is None:
                batch_spec = _P(None, ddp.axis_name)

            # Between dispatches the accumulator must stay DEVICE-LOCAL
            # (the fused scan's carry never leaves its device): it rides
            # a leading world axis sharded over the mesh so each dispatch
            # resumes its own device's partial sum — squeeze the length-1
            # local block off around the shared microbatch math.
            def grad_mb(params, sst, carry, mb):
                local = jax.tree.map(lambda x: x[0], carry)
                new_local, _ = core.microbatch(params, sst, local, mb)
                return jax.tree.map(lambda x: x[None], new_local)

            def apply_fn(state, carry):
                acc, loss_sum, inf_any = jax.tree.map(lambda x: x[0],
                                                      carry)
                return core.apply(state, acc, loss_sum, inf_any)

            acc_spec = _P(ddp.axis_name)
            carry_specs = (acc_spec, acc_spec, acc_spec)
            grad_mb = compat_shard_map(
                grad_mb, mesh,
                in_specs=(_P(), _P(), carry_specs,
                          _strip_leading_axis(batch_spec)),
                out_specs=carry_specs)
            apply_fn = compat_shard_map(
                apply_fn, mesh,
                in_specs=(_P(), carry_specs),
                out_specs=(_P(), _P()))
        self._grad_mb = jax.jit(grad_mb)
        self._apply = jax.jit(apply_fn)

    def init(self, params, scaler_state=None) -> TrainState:
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=self._core.optimizer.init(params),
            scaler_state=(self._core.scaler.init() if scaler_state is None
                          else scaler_state),
        )

    def _zero_carry(self, params):
        acc, loss_sum, inf_any = self._core.zero_carry(params)
        if self._mesh is not None:
            world = self._mesh.devices.size

            def widen(x):
                return jnp.zeros((world,) + jnp.shape(x), x.dtype)

            acc = jax.tree.map(widen, acc)
            loss_sum, inf_any = widen(loss_sum), widen(inf_any)
        return acc, loss_sum, inf_any

    def step(self, state: TrainState, batch):
        _check_batch(batch, self.accum_steps)
        carry = self._zero_carry(state.params)
        for i in range(self.accum_steps):
            mb = jax.tree.map(lambda x: x[i], batch)
            carry = self._grad_mb(state.params, state.scaler_state,
                                  carry, mb)
        return self._apply(state, carry)

    __call__ = step


def build_reference_loop(
    loss_fn: Callable,
    optimizer,
    amp=None,
    ddp=None,
    accum_steps: int = 1,
    lr_schedule: Optional[Callable] = None,
    with_grad_norm: bool = False,
    mesh=None,
    batch_spec=None,
    loss_id: int = 0,
) -> ReferenceLoop:
    """Build the hand-wired per-microbatch dispatch loop with the same
    configuration surface as :func:`build_train_step` (no ``donate`` —
    the pre-builder world didn't donate, that's the point)."""
    scaler, trace_wrapper = _resolve_scaler(amp, loss_id)
    core = _StepCore(loss_fn, optimizer, scaler, trace_wrapper, ddp,
                     accum_steps, False, lr_schedule, with_grad_norm,
                     loss_id)
    return ReferenceLoop(core, mesh, batch_spec)
