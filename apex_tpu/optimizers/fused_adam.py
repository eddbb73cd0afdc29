"""FusedAdam — single fused update over all parameters.

Rebuild of ``apex/optimizers/fused_adam.py`` + ``csrc/multi_tensor_adam.cu``
(SURVEY.md §3.3): the entire Adam/AdamW update for every parameter tensor
runs as one ``multi_tensor_adam`` call — per-leaf fp32 math that XLA fuses
into a handful of HBM-bound passes inside the jitted step, the TPU analog
of the reference's one-kernel-launch step. Knob parity: ``bias_correction``,
``betas``, ``eps``, ``adam_w_mode``, ``weight_decay``, ``amsgrad``
(rejected, like the reference), ``master_weights`` (fp32 masters for amp
O2), ``capturable`` (accepted and ignored: every jitted step is
"capturable" on XLA by construction).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu import profiler
from apex_tpu.multi_tensor_apply import multi_tensor_applier
from apex_tpu.ops.multi_tensor import (
    ADAM_MODE_ADAMW,
    ADAM_MODE_L2,
    multi_tensor_adam,
)
from apex_tpu.optimizers._base import FusedOptimizer, leaves_of, like_tree


class AdamState(NamedTuple):
    step: jnp.ndarray
    exp_avg: any
    exp_avg_sq: any
    master: any  # fp32 master params pytree, or None


@dataclasses.dataclass(frozen=True)
class FusedAdam(FusedOptimizer):
    """``moments_dtype="bfloat16"`` (round-5 opt-in, default fp32 =
    exact reference parity) stores m/v in bf16 with stochastic rounding
    (unbiased EMAs — see FusedLAMB's docstring for the stall physics),
    halving the optimizer-state HBM traffic and footprint."""

    lr: float = 1e-3
    bias_correction: bool = True
    betas: Tuple[float, float] = (0.9, 0.999)
    eps: float = 1e-8
    adam_w_mode: bool = True
    weight_decay: float = 0.0
    amsgrad: bool = False
    set_grad_none: bool = True  # parity knob; grads are inputs here
    capturable: bool = False
    master_weights: bool = False
    moments_dtype: str = "float32"
    stochastic_rounding: bool = True  # applies when moments_dtype=bf16

    def __post_init__(self):
        if self.amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        self._validate_moments_dtype()

    def init(self, params) -> AdamState:
        mdt = self._moments_dtype
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, mdt), params)
        zeros2 = jax.tree.map(lambda p: jnp.zeros(p.shape, mdt), params)
        return AdamState(
            step=jnp.zeros((), jnp.int32),
            exp_avg=zeros,
            exp_avg_sq=zeros2,
            master=self._master_init(params),
        )

    def step(self, grads, state: AdamState, params, skip_if=None, lr=None):
        lr = self.lr if lr is None else lr
        step = state.step + 1

        g = leaves_of(grads)
        p = leaves_of(params)
        m = leaves_of(state.exp_avg)
        v = leaves_of(state.exp_avg_sq)
        lists = [g, p, m, v]
        if self.master_weights:
            lists.append(leaves_of(state.master))

        sr_key = self._sr_key(step, 0xADA3)
        with jax.named_scope(profiler.ADAM_UPDATE):
            out = multi_tensor_applier(
                multi_tensor_adam,
                None,
                lists,
                lr,
                self.betas[0],
                self.betas[1],
                self.eps,
                step,
                ADAM_MODE_ADAMW if self.adam_w_mode else ADAM_MODE_L2,
                self.bias_correction,
                self.weight_decay,
                sr_key=sr_key,
            )
        new_p = like_tree(out[0], params)
        new_state = AdamState(
            step=step,
            exp_avg=like_tree(out[1], state.exp_avg),
            exp_avg_sq=like_tree(out[2], state.exp_avg_sq),
            master=like_tree(out[3], state.master) if self.master_weights else None,
        )
        return self._finish_step(skip_if, new_p, new_state, params, state)
