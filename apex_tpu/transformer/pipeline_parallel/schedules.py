"""Pipeline-parallel schedules as SPMD collective-permute pipelines.

Rebuild of ``apex/transformer/pipeline_parallel/schedules.py`` (SURVEY.md
§3.5): the reference drives 1F1B with explicit NCCL send/recv per
microbatch hop (warmup = ``pp_size - rank - 1`` forwards, steady-state
alternation, cooldown drain), because torch must schedule imperatively.

TPU design (SURVEY.md §7 hard part 4): the schedule is DATA FLOW, not
control flow. Every stage runs the same program: a ``lax.scan`` over
``num_microbatches + pp - 1`` ticks in which each device

  1. selects its current input (stage 0: the next microbatch; others: the
     activation received from the left neighbor),
  2. applies its stage's layer stack,
  3. ``ppermute``\\ s the activation to the right neighbor.

The last stage accumulates per-microbatch outputs/losses. Differentiating
through the scan gives the reverse pipeline (cooldown) automatically, with
activation rematerialization via ``jax.checkpoint`` on the stage fn; XLA's
latency-hiding scheduler overlaps the ppermute with compute — which is
exactly the role of the reference's explicit 1F1B interleaving. Microbatch
bookkeeping (SURVEY.md: ``apex/transformer/microbatches.py``) reduces to
the ``num_microbatches`` argument.

Used inside ``shard_map`` over the ``pipeline`` mesh axis, with each
device holding its stage's parameter shard (stack parameters along a
leading ``pp`` axis and shard it over ``pipeline``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from apex_tpu.transformer import parallel_state


def _axis():
    return parallel_state.PIPELINE_AXIS


def _size_of(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def pack_carry(x, carry_struct):
    """Pack an arbitrary-shaped stage boundary value into the fixed
    pipeline carry buffer (the shape-negotiation half the reference does
    with ``_communicate``'s shape handshake — SURVEY §2.3 PP row: NCCL
    can negotiate shapes per hop; an SPMD scan carry cannot, so
    shape-CHANGING stages flatten/pad into a carry sized for the largest
    boundary instead).

    Same-kind payloads (float into a float carry, int into an int carry)
    round-trip via ``astype`` (exact when the carry dtype is at least as
    wide); cross-kind payloads are BIT-cast, which requires a 4-byte
    carry dtype (f32/i32) — a 2-byte carry with an int payload raises
    rather than corrupting token ids."""
    flat = x.reshape(-1)
    x_int = jnp.issubdtype(x.dtype, jnp.integer)
    c_int = jnp.issubdtype(carry_struct.dtype, jnp.integer)
    if x_int == c_int:
        flat = flat.astype(carry_struct.dtype)
    else:
        if jnp.dtype(carry_struct.dtype).itemsize != 4:
            raise ValueError(
                f"pack_carry: cross-kind payload ({x.dtype} into "
                f"{carry_struct.dtype} carry) needs a 4-byte carry dtype "
                "(f32/i32) for a lossless bitcast")
        src = jnp.int32 if x_int else jnp.float32
        flat = jax.lax.bitcast_convert_type(flat.astype(src),
                                            carry_struct.dtype)
    size = _size_of(carry_struct.shape)
    if flat.size > size:
        raise ValueError(
            f"pack_carry: value of shape {x.shape} ({flat.size} elems) "
            f"exceeds the carry capacity {carry_struct.shape} ({size})")
    return jnp.pad(flat, (0, size - flat.size)).reshape(carry_struct.shape)


def unpack_carry(carry, shape, dtype):
    """Inverse of :func:`pack_carry`: slice the leading elements of the
    carry buffer back into ``(shape, dtype)``."""
    flat = carry.reshape(-1)[:_size_of(shape)]
    d_int = jnp.issubdtype(jnp.dtype(dtype), jnp.integer)
    c_int = jnp.issubdtype(carry.dtype, jnp.integer)
    if d_int == c_int:
        return flat.astype(dtype).reshape(shape)
    dst = jnp.int32 if d_int else jnp.float32
    return jax.lax.bitcast_convert_type(flat, dst).astype(dtype).reshape(shape)


def _shift_right(x, axis_name, pp):
    """Send to stage s+1; stage 0 receives stage pp-1's value (ignored)."""
    from apex_tpu.transformer.pipeline_parallel import p2p_communication

    return p2p_communication.send_forward(x, axis_name)


def _infer_carry_mark(fn, probe_params, microbatches, axis, name):
    """Varying-axes set for the scan carry + stage_fn shape validation.

    The carry is device-varying from tick 1 on (ppermute), and the stage
    fn may introduce MORE varying axes (e.g. TP collectives inside the
    stage make activations tensor-varying). The scan needs a stable
    carry type, so infer the fixed point of the stage fn's output
    varying-set via eval_shape (abstract — no compute is added). The
    first probe also validates the shape/dtype-preservation contract.
    """
    from apex_tpu.utils.collectives import mark_varying

    mb_shape = microbatches.shape[1:]
    mb_vma = jax.typeof(microbatches).vma
    vma = frozenset({axis}) | mb_vma  # injected microbatches carry their own
    converged = False
    for it in range(4):  # the varying-set only grows and mesh axes are few
        def _probe(vma=vma):
            x = mark_varying(jnp.zeros(mb_shape, microbatches.dtype),
                             tuple(vma))
            return fn(probe_params, x, jnp.int32(0))

        out_spec = jax.eval_shape(_probe)
        if it == 0 and (out_spec.shape, out_spec.dtype) != (
                mb_shape, microbatches.dtype):
            raise ValueError(
                f"{name} stage_fn must preserve the microbatch "
                f"shape/dtype (the scan carry): got {out_spec.shape}/"
                f"{out_spec.dtype} from input {mb_shape}/"
                f"{microbatches.dtype}. Fold shape-changing ops (embedding "
                "lookup, logit projection) inside the first/last stage's "
                "fn, gated on axis_index."
            )
        out_vma = frozenset(getattr(out_spec, "vma", None) or ()) | vma
        if out_vma == vma:
            converged = True
            break
        vma = out_vma
    if not converged:
        raise RuntimeError(
            f"{name} could not infer a stable varying-axes set for "
            f"the scan carry (last iterate: {sorted(vma)}). The stage_fn's "
            "output varying-set must reach a fixed point; check for "
            "collectives over axes not in the current mesh."
        )
    return tuple(vma)


def spmd_pipeline(
    stage_fn: Callable,
    stage_params,
    microbatches,
    *,
    num_microbatches: int,
    remat: bool = True,
    axis_name: Optional[str] = None,
    carry_struct: Optional[jax.ShapeDtypeStruct] = None,
):
    """Run a pipelined forward pass.

    Args:
      stage_fn: ``(params, x, microbatch_index) -> x`` — one stage's
        compute, applied by every device to its local params.
      stage_params: this device's stage parameters (inside shard_map these
        are the local shard of a pp-stacked pytree).
      microbatches: (num_microbatches, mb, ...) inputs, replicated across
        the pipeline axis (stage 0 reads them; other stages ignore).
      num_microbatches: M. Total ticks = M + pp - 1.
      remat: rematerialize stage activations in backward
        (``jax.checkpoint``), the reference's activation-recompute default
        for pipeline training.

    Returns:
      (num_microbatches, mb, ...) outputs as produced by the LAST stage
      (valid there; other stages hold garbage — reduce over the axis or
      read stage pp-1's shard).

    Shape-changing pipelines (the reference's ``_communicate`` negotiates
    shapes per NCCL hop; a scan carry cannot): pass ``carry_struct``, a
    ``jax.ShapeDtypeStruct`` sized for the LARGEST stage boundary. Then
    ``microbatches`` entries and every ``stage_fn`` output must be
    carry-shaped — use :func:`pack_carry` / :func:`unpack_carry` at each
    boundary (embedding ids → hidden → logits all travel in the one
    padded buffer; each stage unpacks the shape it knows, switched on
    ``axis_index``). Without ``carry_struct`` the carry is the
    microbatch shape/dtype and ``stage_fn`` must be shape- and
    dtype-preserving; violations raise immediately with the offending
    shapes rather than an opaque scan carry-type error.
    """
    axis = axis_name or _axis()
    if carry_struct is not None and (
            tuple(microbatches.shape[1:]) != tuple(carry_struct.shape)
            or microbatches.dtype != carry_struct.dtype):
        raise ValueError(
            f"with carry_struct {carry_struct.shape}/{carry_struct.dtype}, "
            f"microbatches must be pre-packed to that shape (got "
            f"{microbatches.shape[1:]}/{microbatches.dtype}); use "
            "pack_carry on each microbatch")
    pp = parallel_state.get_pipeline_model_parallel_world_size()
    stage = jax.lax.axis_index(axis)
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    mb_shape = microbatches.shape[1:]
    total_ticks = num_microbatches + pp - 1

    def tick(carry, t):
        state, outputs = carry
        mb_idx = t - stage  # microbatch this stage works on at tick t
        active = (mb_idx >= 0) & (mb_idx < num_microbatches)

        # stage 0 injects a fresh microbatch; others use the received state
        inject = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, num_microbatches - 1), keepdims=False
        )
        x_in = jnp.where(stage == 0, inject, state)

        y = fn(stage_params, x_in, mb_idx)
        # inactive ticks pass state through unchanged (keeps shapes static)
        y = jnp.where(active, y, state)

        # last stage records its finished microbatch
        out_idx = jnp.clip(t - (pp - 1), 0, num_microbatches - 1)
        record = (stage == pp - 1) & (t >= pp - 1)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(record, y, jax.lax.dynamic_index_in_dim(outputs, out_idx, keepdims=False)),
            out_idx,
            axis=0,
        )

        # ship activations rightward for the next tick
        state = _shift_right(y, axis, pp) if pp > 1 else y
        return (state, outputs), None

    from apex_tpu.utils.collectives import mark_varying

    mark = _infer_carry_mark(fn, stage_params, microbatches, axis,
                             "spmd_pipeline")

    init_state = mark_varying(jnp.zeros(mb_shape, microbatches.dtype), mark)
    init_out = mark_varying(
        jnp.zeros((num_microbatches,) + mb_shape, microbatches.dtype), mark)
    (_, outputs), _ = jax.lax.scan(
        tick, (init_state, init_out), jnp.arange(total_ticks)
    )
    return outputs


def _pipelined_loss_and_grad(pipeline_call, stage_params, *,
                             num_microbatches, loss_fn, axis):
    """Shared loss/grad wrapper for both schedules: per-microbatch loss on
    the last stage, mean over microbatches, psum-broadcast, value_and_grad
    through the scan (AD gives the reverse schedule)."""
    pp = parallel_state.get_pipeline_model_parallel_world_size()

    def pipeline_loss(params):
        outs = pipeline_call(params)
        per_mb = jax.vmap(loss_fn)(outs, jnp.arange(num_microbatches))
        local = jnp.mean(per_mb)
        stage = jax.lax.axis_index(axis)
        # only the last stage's loss is real; zero others then sum
        return jax.lax.psum(jnp.where(stage == pp - 1, local, 0.0), axis)

    return jax.value_and_grad(pipeline_loss)(stage_params)


def forward_backward_pipelining_without_interleaving(
    forward_step_fn: Callable,
    batch,
    stage_params,
    *,
    num_microbatches: int,
    loss_fn: Callable,
    remat: bool = True,
    axis_name: Optional[str] = None,
):
    """1F1B-equivalent pipelined loss + gradients (reference:
    ``forward_backward_pipelining_without_interleaving``).

    Args:
      forward_step_fn: ``(params, x, mb_idx) -> activation`` per stage.
      batch: (num_microbatches, mb, ...) microbatched inputs.
      stage_params: per-stage local params (pp-stacked, sharded).
      loss_fn: ``(last_stage_output, mb_idx) -> scalar`` per microbatch;
        evaluated on the last stage, mean-reduced over microbatches.

    Returns:
      (loss, grads) with loss replicated across stages and grads local to
      each stage's params — the reference returns per-stage losses and
      leaves grads in ``param.grad`` similarly.
    """
    axis = axis_name or _axis()
    return _pipelined_loss_and_grad(
        lambda params: spmd_pipeline(
            forward_step_fn, params, batch,
            num_microbatches=num_microbatches, remat=remat, axis_name=axis),
        stage_params, num_microbatches=num_microbatches,
        loss_fn=loss_fn, axis=axis)


def spmd_pipeline_interleaved(
    stage_fn: Callable,
    stage_params,
    microbatches,
    *,
    num_microbatches: int,
    num_model_chunks: int,
    remat: bool = True,
    axis_name: Optional[str] = None,
):
    """Interleaved (virtual-pipeline) forward pass as a CIRCULAR pipeline.

    Reference: the interleaved path of
    ``forward_backward_pipelining_with_interleaving`` — each device owns
    ``v = num_model_chunks`` model chunks; global stage ``c*pp + r``
    lives on device ``r``. The reference cuts the bubble from
    ``(pp-1)/m`` to ``(pp-1)/(v*m)`` by interleaving chunk compute; the
    SPMD dataflow analog is a circular schedule: microbatches travel the
    device ring ``v`` times, re-entering device 0 at the next chunk one
    tick after leaving device ``pp-1`` (the ppermute wraparound delivers
    exactly on time), in groups of ``pp`` so every device computes one
    (chunk, microbatch) pair per tick with no conflicts.

    Tick math (``u = t - stage``, the device's stream position):
    ``group = u // (v*pp)``, ``chunk = (u % (v*pp)) // pp``,
    ``mb = group*pp + u % pp``. Total ticks ``v*m + pp - 1`` — the
    bubble is ``pp - 1`` single-CHUNK units vs the non-interleaved
    schedule's ``pp - 1`` whole-stage (= v-chunk) units: the 1/v bubble
    reduction the reference's interleaving exists for.

    Args:
      stage_fn: ``(chunk_params, x, microbatch_index) -> x`` — ONE model
        chunk's compute (shape/dtype-preserving, as in spmd_pipeline).
      stage_params: pytree whose leaves carry a leading
        ``num_model_chunks`` axis: this device's v chunk params.
      microbatches: (num_microbatches, mb, ...); num_microbatches must
        be divisible by pp (the reference asserts the same for its
        interleaved schedule).

    Returns:
      (num_microbatches, mb, ...) final-chunk outputs, valid on the last
      stage (as in spmd_pipeline).
    """
    axis = axis_name or _axis()
    pp = parallel_state.get_pipeline_model_parallel_world_size()
    v = int(num_model_chunks)
    if v < 1:
        raise ValueError(f"num_model_chunks must be >= 1, got {v}")
    if num_microbatches % pp != 0:
        raise ValueError(
            f"interleaved schedule requires num_microbatches "
            f"({num_microbatches}) divisible by pipeline world size ({pp}), "
            "matching the reference assertion")
    stage = jax.lax.axis_index(axis)
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    chunk0 = jax.tree.map(
        lambda p: jax.lax.index_in_dim(p, 0, keepdims=False), stage_params)
    mb_shape = microbatches.shape[1:]
    total_ticks = v * num_microbatches + pp - 1

    def tick(carry, t):
        state, outputs = carry
        u = t - stage
        group = u // (v * pp)
        within = u % (v * pp)
        chunk = within // pp
        mb_idx = group * pp + u % pp
        active = (u >= 0) & (mb_idx >= 0) & (mb_idx < num_microbatches)

        chunk_params = jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(
                p, jnp.clip(chunk, 0, v - 1), keepdims=False),
            stage_params)

        inject = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.clip(mb_idx, 0, num_microbatches - 1),
            keepdims=False)
        x_in = jnp.where((stage == 0) & (chunk == 0), inject, state)

        y = fn(chunk_params, x_in, mb_idx)
        y = jnp.where(active, y, state)

        record = (stage == pp - 1) & (chunk == v - 1) & active
        out_idx = jnp.clip(mb_idx, 0, num_microbatches - 1)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(record, y,
                      jax.lax.dynamic_index_in_dim(outputs, out_idx,
                                                   keepdims=False)),
            out_idx,
            axis=0,
        )

        state = _shift_right(y, axis, pp) if pp > 1 else y
        return (state, outputs), None

    from apex_tpu.utils.collectives import mark_varying

    mark = _infer_carry_mark(fn, chunk0, microbatches, axis,
                             "spmd_pipeline_interleaved")

    init_state = mark_varying(jnp.zeros(mb_shape, microbatches.dtype), mark)
    init_out = mark_varying(
        jnp.zeros((num_microbatches,) + mb_shape, microbatches.dtype), mark)
    (_, outputs), _ = jax.lax.scan(
        tick, (init_state, init_out), jnp.arange(total_ticks)
    )
    return outputs


def forward_backward_pipelining_with_interleaving(
    forward_step_fn: Callable,
    batch,
    stage_params,
    *,
    num_microbatches: int,
    loss_fn: Callable,
    num_model_chunks: Optional[int] = None,
    remat: bool = True,
    axis_name: Optional[str] = None,
):
    """Interleaved 1F1B-equivalent loss + grads (reference name).

    ``stage_params`` leaves carry a leading ``num_model_chunks`` axis
    (inferred from the first leaf when not given). Loss is evaluated on
    the last stage over final-chunk outputs; AD through the circular
    scan produces the reverse interleaved schedule.
    """
    axis = axis_name or _axis()
    if num_model_chunks is None:
        num_model_chunks = jax.tree.leaves(stage_params)[0].shape[0]
    return _pipelined_loss_and_grad(
        lambda params: spmd_pipeline_interleaved(
            forward_step_fn, params, batch,
            num_microbatches=num_microbatches,
            num_model_chunks=num_model_chunks, remat=remat, axis_name=axis),
        stage_params, num_microbatches=num_microbatches,
        loss_fn=loss_fn, axis=axis)


def get_forward_backward_func(virtual_pipeline_model_parallel_size=None,
                              pipeline_model_parallel_size=None):
    """Reference dispatcher: ``virtual_pipeline_model_parallel_size``
    selects the interleaved (circular) schedule; otherwise the plain
    SPMD pipeline."""
    if (virtual_pipeline_model_parallel_size is not None
            and virtual_pipeline_model_parallel_size > 1):
        return forward_backward_pipelining_with_interleaving
    return forward_backward_pipelining_without_interleaving
