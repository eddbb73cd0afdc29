"""FusedLAMB — two-stage fused LAMB (the BERT-large north-star optimizer).

Rebuild of ``apex/optimizers/fused_lamb.py`` (SURVEY.md §3.3): stage 1
computes the global gradient norm (``multi_tensor_l2norm``), clips, and
updates moments into per-tensor update directions
(``multi_tensor_lamb_stage_1``); stage 2 computes per-tensor trust ratios
``||p|| / ||update||`` and applies the step
(``multi_tensor_lamb_stage_2``). Knob parity: ``bias_correction``,
``betas``, ``eps``, ``weight_decay``, ``grad_averaging``,
``max_grad_norm``, ``adam_w_mode``, ``use_nvlamb``, ``master_weights``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from apex_tpu import profiler
from apex_tpu.multi_tensor_apply import multi_tensor_applier
from apex_tpu.ops.multi_tensor import (
    multi_tensor_l2norm,
    multi_tensor_lamb_stage1,
    multi_tensor_lamb_stage2,
)
from apex_tpu.optimizers._base import FusedOptimizer, leaves_of, like_tree


class LambState(NamedTuple):
    step: jnp.ndarray
    exp_avg: any
    exp_avg_sq: any
    master: any


@dataclasses.dataclass(frozen=True)
class FusedLAMB(FusedOptimizer):
    """Two-stage fused LAMB.

    ``moments_dtype="bfloat16"`` (round-5, opt-in — default keeps the
    reference's fp32 moments exactly) stores m/v in bf16 with
    stochastic rounding and switches to a recompute-update stage 2:
    instead of materializing a full fp32 update buffer between the
    trust-ratio reduction and the parameter step, stage 2 recomputes
    the update direction from the just-stored bf16 moments. HBM
    traffic per step at BERT-large (367M params, O2 masters) drops
    from ~14.7 GB to ~8.5 GB. Stochastic rounding keeps the bf16 EMAs
    unbiased (a (1-beta2)*g^2 increment below bf16's 8-bit mantissa
    rounds-to-nearest to zero and v stalls; SR preserves it in
    expectation)."""

    lr: float = 1e-3
    bias_correction: bool = True
    betas: Tuple[float, float] = (0.9, 0.999)
    eps: float = 1e-6
    weight_decay: float = 0.01
    amsgrad: bool = False
    adam_w_mode: bool = True
    grad_averaging: bool = True
    set_grad_none: bool = True
    max_grad_norm: float = 1.0
    use_nvlamb: bool = False
    master_weights: bool = False
    moments_dtype: str = "float32"
    stochastic_rounding: bool = True  # applies when moments_dtype=bf16

    def __post_init__(self):
        if self.amsgrad:
            raise RuntimeError("FusedLAMB does not support the AMSGrad variant.")
        if not self.adam_w_mode:
            raise RuntimeError(
                "FusedLAMB only supports adam_w_mode (decoupled weight decay), "
                "matching the reference kernel."
            )
        self._validate_moments_dtype()

    def init(self, params) -> LambState:
        mdt = self._moments_dtype
        return LambState(
            step=jnp.zeros((), jnp.int32),
            exp_avg=jax.tree.map(lambda p: jnp.zeros(p.shape, mdt), params),
            exp_avg_sq=jax.tree.map(lambda p: jnp.zeros(p.shape, mdt), params),
            master=self._master_init(params),
        )

    def step(self, grads, state: LambState, params, skip_if=None, lr=None,
             grad_scale=None):
        """One fused LAMB step. ``grad_scale``: when given, ``grads`` are
        LOSS-SCALED by this factor and the step unscales them for free
        inside its own reads (norm rescale + stage-1 pre-scale — no
        separate unscale pass) AND detects overflow from the norm it
        already computes. With ``grad_scale`` the return is
        ``(params, state, found_inf)`` (found_inf is folded into the
        skip); without it, ``(params, state)`` as before."""
        lr = self.lr if lr is None else lr
        step = state.step + 1

        g = leaves_of(grads)
        p_model = leaves_of(params)
        p_src = leaves_of(state.master) if self.master_weights else p_model
        m = leaves_of(state.exp_avg)
        v = leaves_of(state.exp_avg_sq)

        # Stage 0: global grad norm (one fused reduction pass).
        with jax.named_scope(profiler.LAMB_GRAD_NORM):
            global_norm, _ = multi_tensor_applier(
                multi_tensor_l2norm, None, [g], False
            )
        pre_scale = 1.0
        found_inf = None
        if grad_scale is not None:
            # inf/nan anywhere in the grads surfaces in the raw norm —
            # the amp overflow check rides this existing reduction
            found_inf = jnp.logical_not(jnp.isfinite(global_norm))
            pre_scale = (1.0 / jnp.asarray(grad_scale, jnp.float32))
            global_norm = global_norm * pre_scale
            skip_if = (found_inf if skip_if is None
                       else jnp.logical_or(skip_if, found_inf))

        if self._moments_dtype == jnp.dtype(jnp.bfloat16):
            return self._low_moments_tail(
                g, p_model, p_src, m, v, state, params, global_norm,
                pre_scale, step, lr, skip_if, found_inf)

        # Stage 1: clip + moments + update directions.
        with jax.named_scope(profiler.LAMB_STAGE1):
            updates, new_m, new_v = multi_tensor_applier(
                multi_tensor_lamb_stage1,
                None,
                [g, p_src, m, v],
                self.betas[0],
                self.betas[1],
                self.eps,
                step,
                self.bias_correction,
                self.weight_decay,
                self.grad_averaging,
                global_norm,
                self.max_grad_norm,
                pre_scale,
            )

        # Stage 2: per-tensor trust ratios + parameter step.
        lists = [p_model, updates]
        if self.master_weights:
            lists.append(p_src)
        with jax.named_scope(profiler.LAMB_STAGE2):
            out = multi_tensor_applier(
                multi_tensor_lamb_stage2, None, lists, lr, self.weight_decay,
                self.use_nvlamb,
            )
        if self.master_weights:
            new_p_leaves, new_master_leaves = out
            new_master = like_tree(new_master_leaves, state.master)
        else:
            new_p_leaves, new_master = out, None

        new_p = like_tree(new_p_leaves, params)
        new_state = LambState(
            step=step,
            exp_avg=like_tree(new_m, state.exp_avg),
            exp_avg_sq=like_tree(new_v, state.exp_avg_sq),
            master=new_master,
        )
        out_p, out_s = self._finish_step(skip_if, new_p, new_state, params,
                                         state)
        if found_inf is not None:
            return out_p, out_s, found_inf
        return out_p, out_s

    def _low_moments_tail(self, g, p_model, p_src, m, v, state, params,
                          global_norm, pre_scale, step, lr, skip_if,
                          found_inf):
        """bf16-moments stage 1+2 (see class docstring): stochastic-
        rounded bf16 m/v, and a recompute-update stage 2 — no fp32
        update buffer crosses HBM between the trust-ratio reduction and
        the parameter step; the update direction is recomputed from the
        just-stored rounded moments (the norms in stage 1 are taken of
        the SAME rounded-moment update, so the trust ratio matches the
        step exactly)."""
        from apex_tpu.ops.multi_tensor import (
            lamb_scalars,
            lamb_trust_ratio,
            lamb_update_direction,
            stochastic_round,
        )

        b1, b2 = self.betas
        clip, bc1, bc2, beta3 = lamb_scalars(
            b1, b2, step, self.bias_correction, self.grad_averaging,
            global_norm, self.max_grad_norm, pre_scale)
        key = self._sr_key(step, 0x5A17)
        mdt = self._moments_dtype

        def u_of(m_r, v_r, p32):
            return lamb_update_direction(
                m_r.astype(jnp.float32), v_r.astype(jnp.float32), p32,
                bc1, bc2, self.eps, self.weight_decay)

        # Pass A: moments (rounded) + per-tensor ||u||, ||p|| reductions
        with jax.named_scope(profiler.LAMB_STAGE1):
            new_m, new_v, u_sq, p_sq = [], [], [], []
            for i, (gi, pi, mi, vi) in enumerate(zip(g, p_src, m, v)):
                g32 = gi.astype(jnp.float32) * clip
                p32 = pi.astype(jnp.float32)
                m32 = b1 * mi.astype(jnp.float32) + beta3 * g32
                v32 = b2 * vi.astype(jnp.float32) + (1.0 - b2) * g32 * g32
                if key is not None:
                    mo = stochastic_round(m32, mdt,
                                          jax.random.fold_in(key, 2 * i))
                    vo = stochastic_round(v32, mdt,
                                          jax.random.fold_in(key, 2 * i + 1))
                else:
                    mo, vo = m32.astype(mdt), v32.astype(mdt)
                new_m.append(mo)
                new_v.append(vo)
                u32 = u_of(mo, vo, p32)
                u_sq.append(jnp.sum(u32 * u32))
                p_sq.append(jnp.sum(p32 * p32))

        with jax.named_scope(profiler.LAMB_STAGE2):
            apply_ratio = self.use_nvlamb or self.weight_decay != 0.0
            if apply_ratio:
                ratios = lamb_trust_ratio(jnp.sqrt(jnp.stack(p_sq)),
                                          jnp.sqrt(jnp.stack(u_sq)))
            else:
                ratios = jnp.ones((len(g),), jnp.float32)

            # Pass B: recompute u from the stored rounded moments + step
            new_p, new_master = [], []
            for i, pi in enumerate(p_src):
                p32 = pi.astype(jnp.float32)
                stepped = p32 - lr * ratios[i] * u_of(new_m[i], new_v[i], p32)
                new_p.append(stepped.astype(p_model[i].dtype))
                if self.master_weights:
                    new_master.append(stepped)

        new_state = LambState(
            step=step,
            exp_avg=like_tree(new_m, state.exp_avg),
            exp_avg_sq=like_tree(new_v, state.exp_avg_sq),
            master=(like_tree(new_master, state.master)
                    if self.master_weights else None),
        )
        out_p, out_s = self._finish_step(
            skip_if, like_tree(new_p, params), new_state, params, state)
        if found_inf is not None:
            return out_p, out_s, found_inf
        return out_p, out_s


@dataclasses.dataclass(frozen=True)
class FusedMixedPrecisionLamb(FusedLAMB):
    """Reference ``apex/optimizers/fused_mixed_precision_lamb.py`` (U):
    LAMB that keeps fp32 master weights and moments while the model
    (and its gradients) live in a reduced precision — exactly
    ``FusedLAMB(master_weights=True)`` here, since this rebuild's LAMB
    already runs all moment/trust-ratio math in fp32 and casts back to
    the model dtype (``reduced_precision_dtype`` is therefore inferred
    from the params rather than configured). Named alias so reference
    imports resolve."""

    master_weights: bool = True
