"""Dynamic loss scaling as a jit-carried state pytree.

TPU-native rebuild of the reference's ``apex/amp/scaler.py:LossScaler``
(SURVEY.md §3.2). The contract constants are preserved exactly:

- initial dynamic scale ``2**16``
- backoff: divide by 2 on overflow, reset the growth tracker
- growth: multiply by 2 after 2000 consecutive overflow-free steps
  (``scale_seq_len`` / growth interval)
- default ceiling ``max_loss_scale = 2**24``; optional ``min_loss_scale``

The key TPU design change (SURVEY.md §7 hard part 1): apex performs a host
readback of a CUDA ``noop_flag`` buffer and imperatively skips
``optimizer.step()``. Here the overflow flag is a traced boolean carried
through the step function, and the skip is an in-graph select — no host
sync, no retrace.

On overflow the reference prints
``Gradient overflow.  Skipping step, loss scaler <id> reducing loss scale to <s>``
(``apex/amp/_amp_state.py:maybe_print``, grep'd for by downstream scripts);
we emit the same line via ``jax.debug.print`` when verbosity allows.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from apex_tpu import profiler
from apex_tpu.amp import _amp_state
from apex_tpu.utils.pytree import all_finite, tree_select


class ScalerState(NamedTuple):
    """Traced loss-scaler state (a pytree; carry it through your jit)."""

    loss_scale: jnp.ndarray  # f32 scalar
    unskipped: jnp.ndarray   # i32 scalar: consecutive overflow-free steps
    steps_skipped: jnp.ndarray  # i32 scalar: lifetime skipped-step count
    # remaining consecutive-overflow tolerance before the scale backs off
    # (reference: csrc/update_scale_hysteresis.cu (U) — with the default
    # hysteresis of 1 every overflow backs off, the core-amp behavior)
    hysteresis: int = 1


@dataclasses.dataclass(frozen=True)
class LossScaler:
    """Static loss-scaler configuration.

    ``loss_scale="dynamic"`` reproduces apex's ``DynamicLossScaler``
    behavior; a float gives a static scale (``update`` is then a no-op),
    matching ``amp.initialize(loss_scale=N)``.
    """

    loss_scale: Union[str, float] = "dynamic"
    init_scale: float = 2.0 ** 16
    scale_factor: float = 2.0
    scale_seq_len: int = 2000  # apex: growth every 2000 unskipped steps
    # None (the reference default) = no floor: the scale may back off below
    # 1.0, which is how apex recovers when grads overflow even at scale 1.
    min_loss_scale: Optional[float] = None
    max_loss_scale: float = 2.0 ** 24
    loss_id: int = 0  # apex supports num_losses scalers, each with an id
    # back off only after this many consecutive overflow steps (each still
    # skipped); 1 = reference core-amp behavior. Mirrors the kernel-side
    # hysteresis of ``amp_C.update_scale_hysteresis`` (U).
    hysteresis: int = 1

    @property
    def dynamic(self) -> bool:
        return self.loss_scale == "dynamic"

    def init(self) -> ScalerState:
        scale = self.init_scale if self.dynamic else float(self.loss_scale)
        return ScalerState(
            loss_scale=jnp.asarray(scale, jnp.float32),
            unskipped=jnp.asarray(0, jnp.int32),
            steps_skipped=jnp.asarray(0, jnp.int32),
            hysteresis=jnp.asarray(self.hysteresis, jnp.int32),
        )

    # -- step pieces ------------------------------------------------------

    def scale(self, loss, state: ScalerState):
        """Multiply the loss by the current scale (apex ``scale_loss`` enter)."""
        return jax.tree.map(lambda l: l * state.loss_scale.astype(l.dtype), loss)

    def unscale(self, grads, state: ScalerState):
        """Unscale gradients and detect overflow in one fused pass.

        Analog of ``amp_C.multi_tensor_scale`` over all grads with the
        ``noop_flag`` inf/nan check (SURVEY.md §3.2): XLA fuses the
        multiply and the isfinite reduction over each buffer.

        Returns ``(unscaled_grads, found_inf)`` where ``found_inf`` is a
        traced bool. Non-finite grads are passed through unscaled-but-
        harmless; the caller must skip the step when ``found_inf``.
        """
        with jax.named_scope(profiler.AMP_UNSCALE):
            inv = (1.0 / state.loss_scale).astype(jnp.float32)
            found_inf = jnp.logical_not(all_finite(grads))
            unscaled = jax.tree.map(lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype), grads)
        return unscaled, found_inf

    def update(self, state: ScalerState, found_inf) -> ScalerState:
        """Advance scaler state given this step's overflow flag."""
        if not self.dynamic:
            return state._replace(
                steps_skipped=state.steps_skipped + found_inf.astype(jnp.int32)
            )
        # overflow branch: decrement the hysteresis tolerance; only when it
        # is used up does the scale actually back off (hysteresis=1, the
        # default, backs off on every overflow — the reference core-amp
        # contract; >1 mirrors amp_C.update_scale_hysteresis (U))
        hys = jnp.asarray(state.hysteresis, jnp.int32) - found_inf.astype(jnp.int32)
        back_off_now = jnp.logical_and(found_inf, hys <= 0)
        floor = self.min_loss_scale if self.min_loss_scale is not None else 0.0
        backed_off = jnp.maximum(state.loss_scale / self.scale_factor, floor)
        # clean branch
        unskipped = state.unskipped + 1
        grow = unskipped >= self.scale_seq_len
        grown = jnp.where(
            grow,
            jnp.minimum(state.loss_scale * self.scale_factor, self.max_loss_scale),
            state.loss_scale,
        )
        reset_hys = jnp.asarray(self.hysteresis, jnp.int32)
        new = ScalerState(
            loss_scale=jnp.where(
                found_inf, jnp.where(back_off_now, backed_off, state.loss_scale),
                grown),
            unskipped=jnp.where(found_inf, 0, jnp.where(grow, 0, unskipped)).astype(jnp.int32),
            steps_skipped=state.steps_skipped + found_inf.astype(jnp.int32),
            # EVERY clean step replenishes the tolerance to its full value
            # (the cited kernel zeroes then refills hysteresis_tracker on a
            # non-overflow step), so only *consecutive* overflows deplete
            # it: with hysteresis>1, spiky losses whose overflows are
            # separated by clean steps never back the scale off. Note this
            # differs from Megatron's DynamicGradScaler, which replenishes
            # only on a growth event. Clamp the overflow branch at 0 to
            # keep the <=0 test stable instead of drifting negative.
            hysteresis=jnp.where(
                found_inf, jnp.maximum(hys, 0), reset_hys
            ).astype(jnp.int32),
        )
        if _amp_state.ingraph_logging_enabled() and _amp_state.get_verbosity() >= 1:
            # The reference's contractual overflow line. Emitted via a host
            # callback — a device-to-host round trip inside the step, so
            # ingraph_logging_enabled() keeps it to the CPU backend unless
            # amp.set_ingraph_logging(True) asks for it.
            prefix = ("Gradient overflow.  Skipping step, loss scaler "
                      + str(self.loss_id))

            def _log_reduce(s):
                jax.debug.print(prefix + " reducing loss scale to {scale}",
                                scale=s)

            def _log_hold(s):
                # hysteresis held: skipped, but the scale did NOT change —
                # distinct wording so grep/parse consumers of the
                # "reducing" line never record a phantom reduction
                jax.debug.print(prefix + " hysteresis holding loss scale "
                                "at {scale}", scale=s)

            jax.lax.cond(
                found_inf,
                lambda s: jax.lax.cond(back_off_now, _log_reduce,
                                       _log_hold, s),
                lambda s: None,
                new.loss_scale,
            )
        return new

    # -- convenience ------------------------------------------------------

    def value_and_grad(self, loss_fn, state: ScalerState, has_aux: bool = False):
        """``jax.value_and_grad`` on the *scaled* loss, returning unscaled
        loss/grads plus the overflow flag.

        Usage::

            (loss, found_inf, aux), grads = scaler.value_and_grad(f, st)(params)
        """
        scaled_vg = self.scaled_value_and_grad(loss_fn, state,
                                               has_aux=has_aux)

        def wrapped(*args, **kwargs):
            out, scaled_grads = scaled_vg(*args, **kwargs)
            loss, aux = out if has_aux else (out, None)
            grads, found_inf = self.unscale(scaled_grads, state)
            if has_aux:
                return (loss, found_inf, aux), grads
            return (loss, found_inf), grads

        return wrapped

    def scaled_value_and_grad(self, loss_fn, state: ScalerState,
                              has_aux: bool = False):
        """``jax.value_and_grad`` of the scaled loss returning the SCALED
        gradients and unscaled loss — no unscale pass and no finite check
        here. Pair with an optimizer that folds the unscale into its own
        first gradient read (``FusedLAMB.step(grad_scale=...)``): one
        fewer full read+write of the gradient tree per step than
        :meth:`value_and_grad` + separate ``unscale``, with the overflow
        check riding the optimizer's existing global-norm reduction."""

        def scaled_fn(*args, **kwargs):
            out = loss_fn(*args, **kwargs)
            if has_aux:
                loss, aux = out
            else:
                loss, aux = out, None
            with jax.named_scope(profiler.AMP_SCALE_LOSS):
                scaled = self.scale(loss, state)
            return scaled, (loss, aux)

        vg = jax.value_and_grad(scaled_fn, has_aux=True)

        def wrapped(*args, **kwargs):
            (_, (loss, aux)), scaled_grads = vg(*args, **kwargs)
            if has_aux:
                return (loss, aux), scaled_grads
            return loss, scaled_grads

        return wrapped

    def maybe_apply(self, state: ScalerState, found_inf, updated_tree, old_tree):
        """Select ``updated_tree`` unless this step overflowed (in-graph
        step-skip), and advance the scaler. Returns ``(tree, new_state)``."""
        tree = tree_select(found_inf, old_tree, updated_tree)
        return tree, self.update(state, found_inf)

    # -- observability -----------------------------------------------------

    @staticmethod
    def metrics(state: ScalerState, grad_norm=None, loss=None) -> dict:
        """Per-step metrics dict (SURVEY.md §5 metrics row): the values a
        training harness logs each step. Traced values in, traced values
        out — call inside jit and log on the host after the step."""
        out = {
            "loss_scale": state.loss_scale,
            "unskipped": state.unskipped,
            "steps_skipped": state.steps_skipped,
        }
        if grad_norm is not None:
            out["grad_norm"] = grad_norm
        if loss is not None:
            out["loss"] = loss
        return out

    def host_overflow_report(self, prev_state: ScalerState,
                             new_state: ScalerState) -> bool:
        """Host-side fallback for the contractual overflow line.

        The in-graph ``jax.debug.print`` path in :meth:`update` is off
        by default on accelerators (it is a host callback inside the
        step), so there the line downstream scripts grep for would
        never print. Call this AFTER the step with the device states
        (one small host readback): if the step was skipped, it prints
        the reference's exact line and returns True. When the in-graph
        path already printed the line (dynamic scaler + callback-capable
        runtime), this only reports the boolean — no double line for
        grep-and-count consumers. Static scalers never print in-graph
        (``update`` early-returns), so their line always comes from here,
        and without the "reducing" clause (a static scale never backs
        off).
        """
        skipped = int(new_state.steps_skipped) > int(prev_state.steps_skipped)
        if not skipped:
            return False
        ingraph_already = (self.dynamic
                           and _amp_state.ingraph_logging_enabled())
        if not ingraph_already:
            if self.dynamic:
                # did the tracker back off this step? Mirror the in-graph
                # rule (prev tolerance depleted by this overflow) rather
                # than comparing scales: a back-off pinned at
                # min_loss_scale leaves the value unchanged but is still
                # the reference's "reducing" event.
                reduced = int(prev_state.hysteresis) <= 1
                if reduced:
                    _amp_state.maybe_print(
                        "Gradient overflow.  Skipping step, loss scaler "
                        f"{self.loss_id} reducing loss scale to "
                        f"{float(new_state.loss_scale)}"
                    )
                else:
                    # hysteresis held the scale: same skip event, distinct
                    # wording (no phantom reduction for grep consumers)
                    _amp_state.maybe_print(
                        "Gradient overflow.  Skipping step, loss scaler "
                        f"{self.loss_id} hysteresis holding loss scale at "
                        f"{float(new_state.loss_scale)}"
                    )
            else:
                _amp_state.maybe_print(
                    "Gradient overflow.  Skipping step, loss scaler "
                    f"{self.loss_id} static loss scale "
                    f"{float(new_state.loss_scale)} unchanged"
                )
        return True


# Backwards-handy aliases mirroring apex naming.
DynamicLossScaler = LossScaler
