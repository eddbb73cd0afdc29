"""Shared machinery for the fused optimizers.

The reference optimizers (``apex/optimizers/*``, SURVEY.md §2.1) are
torch ``Optimizer`` subclasses whose ``step()`` makes one
``multi_tensor_applier`` call. The rebuild keeps that shape as a
functional core: each optimizer is an immutable config object with

- ``init(params) -> state``   (state is a pytree: step count + moments
  [+ fp32 master params when ``master_weights``])
- ``step(grads, state, params, skip_if=None, lr=None) -> (params, state)``

``skip_if`` is the amp overflow flag: when True the returned params/state
are the inputs unchanged and the step counter does not advance —
the in-graph equivalent of apex's patched ``optimizer.step()`` no-op on
overflow (SURVEY.md §3.2). ``as_optax()`` adapts any of these to an
``optax.GradientTransformation`` for idiomatic JAX training loops.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu import profiler
from apex_tpu.utils.pytree import tree_select


def leaves_of(tree):
    return jax.tree.leaves(tree)


def like_tree(leaves, tree):
    return jax.tree.unflatten(jax.tree.structure(tree), leaves)


@dataclasses.dataclass(frozen=True)
class FusedOptimizer:
    """Base class: config dataclass + functional init/step."""

    lr: float = 1e-3
    weight_decay: float = 0.0
    master_weights: bool = False

    def with_master_weights(self, flag: bool = True):
        """Return a copy with fp32 master weights enabled (used by
        ``amp.initialize`` for O2, reference ``_process_optimizer``)."""
        return dataclasses.replace(self, master_weights=flag)

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)

    # subclasses implement init() and step()

    def _master_init(self, params):
        if not self.master_weights:
            return None

        def to_master(x):
            x = jnp.asarray(x)
            if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != jnp.float32:
                return x.astype(jnp.float32)
            # Already-f32 leaves (keep_batchnorm_fp32 norms) and integer
            # leaves MUST still get their own buffer: astype is a no-op
            # returning the same array, and a donated train state holding
            # (params, master) would then donate one buffer twice — a
            # runtime error on XLA:CPU/PJRT (and on a replicated mesh the
            # non-raising ranks hang at the next collective rendezvous).
            return jnp.array(x, copy=True)

        return jax.tree.map(to_master, params)

    # --- shared bf16-moments machinery (round 5): subclasses exposing a
    # ``moments_dtype`` field share the validation, dtype resolution,
    # and per-step stochastic-rounding key derivation ---

    def _validate_moments_dtype(self):
        try:
            mdt = jnp.dtype(getattr(self, "moments_dtype", "float32"))
        except TypeError:
            mdt = None
        if mdt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
            raise ValueError(
                f"moments_dtype must be float32 or bfloat16, got "
                f"{getattr(self, 'moments_dtype', None)!r}")

    @property
    def _moments_dtype(self):
        return jnp.dtype(getattr(self, "moments_dtype", "float32"))

    def _sr_key(self, step, seed):
        """Per-step SR key, or None when fp32 moments / SR disabled."""
        if (self._moments_dtype == jnp.dtype(jnp.bfloat16)
                and getattr(self, "stochastic_rounding", False)):
            return jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return None

    def _finish_step(self, skip_if, new_params, new_state, params, state):
        """Apply the overflow step-skip select (params, moments, AND the
        step counter stay untouched on skip)."""
        if skip_if is None:
            return new_params, new_state
        out_p = tree_select(skip_if, params, new_params)
        out_s = tree_select(skip_if, state, new_state)
        return out_p, out_s

    def apply_gradients(self, grads, state, params, *, skip_if=None,
                        lr=None, grad_scale=None):
        """Uniform, donation-friendly apply surface for step builders.

        Every fused optimizer's ``step`` keeps its own signature quirks
        (FusedLAMB grows a ``grad_scale`` kwarg and then returns a
        3-tuple; the others don't take it). A donated fused train step
        needs ONE entry point whose return is always ``(params, state)``
        and whose output leaves are bit-compatible (same shape + dtype)
        with the inputs — XLA only aliases a donated input buffer into
        an output of identical layout, and silently falls back to a
        copy otherwise. This method normalizes the signature, folds a
        ``grad_scale`` unscale into the step when the optimizer supports
        it natively (or pre-unscales when it doesn't), and raises at
        trace time if an optimizer update would break buffer aliasing.
        """
        import inspect

        if grad_scale is not None:
            if "grad_scale" in inspect.signature(self.step).parameters:
                out = self.step(grads, state, params, skip_if=skip_if,
                                lr=lr, grad_scale=grad_scale)
                new_params, new_state = out[0], out[1]
            else:
                from apex_tpu.utils.pytree import all_finite
                with jax.named_scope(profiler.AMP_UNSCALE):
                    inv = 1.0 / jnp.asarray(grad_scale, jnp.float32)
                    grads = jax.tree.map(
                        lambda g: (g.astype(jnp.float32) * inv
                                   ).astype(g.dtype), grads)
                    found = jnp.logical_not(all_finite(grads))
                skip_if = (found if skip_if is None
                           else jnp.logical_or(skip_if, found))
                new_params, new_state = self.step(grads, state, params,
                                                  skip_if=skip_if, lr=lr)
        else:
            new_params, new_state = self.step(grads, state, params,
                                              skip_if=skip_if, lr=lr)
        self._check_alias_compatible(params, new_params, "params")
        self._check_alias_compatible(state, new_state, "state")
        return new_params, new_state

    @staticmethod
    def _check_alias_compatible(old, new, what: str):
        """Raise if ``new``'s leaves can't alias ``old``'s donated
        buffers (shape/dtype drift = XLA drops donation with only a
        warning; tests need a hard signal)."""
        old_l, new_l = jax.tree.leaves(old), jax.tree.leaves(new)
        if len(old_l) != len(new_l):
            raise ValueError(
                f"optimizer step changed the {what} tree arity "
                f"({len(old_l)} -> {len(new_l)} leaves); donated buffers "
                f"cannot alias")
        for a, b in zip(old_l, new_l):
            a_shape, b_shape = jnp.shape(a), jnp.shape(b)
            a_dt = jnp.asarray(a).dtype if not hasattr(a, "dtype") else a.dtype
            b_dt = jnp.asarray(b).dtype if not hasattr(b, "dtype") else b.dtype
            if a_shape != b_shape or a_dt != b_dt:
                raise ValueError(
                    f"optimizer step changed a {what} leaf from "
                    f"{a_dt}{list(a_shape)} to {b_dt}{list(b_shape)}; a "
                    f"donated buffer can only alias an identically-"
                    f"shaped, identically-typed output")

    def as_optax(self):
        """Adapt to an ``optax.GradientTransformation``.

        The transformation's update returns ``new_params - params`` so it
        composes with ``optax.apply_updates``. Requires params.
        """
        import optax

        opt = self

        def init_fn(params):
            return opt.init(params)

        def update_fn(grads, state, params=None):
            if params is None:
                raise ValueError(f"{type(opt).__name__}.as_optax() requires params")
            new_params, new_state = opt.step(grads, state, params)
            updates = jax.tree.map(
                lambda n, p: (n.astype(jnp.float32) - p.astype(jnp.float32)).astype(p.dtype),
                new_params,
                params,
            )
            return updates, new_state

        return optax.GradientTransformation(init_fn, update_fn)
