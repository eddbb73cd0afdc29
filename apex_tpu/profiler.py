"""Profiling hooks (SURVEY.md §5 tracing row; reference: ``apex.pyprof``
— deprecated upstream — plus the external torch-profiler workflow its
users migrated to).

The reference's pyprof parsed nvprof SQLite dumps to attribute kernels
to model ops. On TPU the equivalent workflow is ``jax.profiler``: a
capture holds one event per executed HLO op on the device plane and the
host's threads on the host plane, on one clock. What ties a device op
back to the code is the op's ``op_name`` metadata in the compiled HLO
(``jit(step)/<named scopes>/<flax module path>/<primitive>``) and, for
a Pallas kernel, the instruction's own name. The train path names both;
the names are this module's constants, so a reader of a trace has ONE
table to learn (docs/observability.md, "Training: scopes and
annotations"):

=================== ======================================== ==========================
scope               what runs under it                       emitted in
=================== ======================================== ==========================
train_fwd_bwd       forward, recomputed forward, backward of train/step.py (microbatch)
                    one microbatch. JAX itself marks the
                    backward ops ``transpose(jvp(...))`` and
                    the recomputed ones
                    ``checkpoint/rematted_computation``
train_accumulate    the zeroed fp32 accumulator, the          train/step.py
                    accumulate, the microbatch loop's own
                    plumbing
train_reduce        average over microbatches (+ DDP sync)   train/step.py (apply)
train_metrics       loss mean, gradient norm, aux gather,     train/step.py (apply)
                    the step counter
amp_scale_loss      loss x scale                              amp/scaler.py
amp_unscale         gradients / scale + finite check          amp/scaler.py
amp_found_inf       the global overflow flag                  train/step.py (apply)
amp_update_scale    the scaler's state update                 train/step.py (apply)
optimizer_update    the ``lax.cond`` and both its branches    train/step.py (apply)
lamb_grad_norm      LAMB stage 0: global gradient norm        optimizers/fused_lamb.py
lamb_stage1         LAMB clip + moments + update directions   optimizers/fused_lamb.py
lamb_stage2         LAMB trust ratios + parameter step        optimizers/fused_lamb.py
adam_update         the one fused Adam/AdamW update           optimizers/fused_adam.py
ddp_flatten         gradient leaves -> flat buffer(s)         parallel/distributed.py
ddp_allreduce       predivide, psum, average                  parallel/distributed.py
ddp_unflatten       flat buffer(s) -> gradient leaves         parallel/distributed.py
lm_head             GPT's tied vocabulary einsum              models/gpt.py
lm_loss             shifted cross-entropy (fp32 logsumexp)    models/gpt.py
mlm_head            BERT gather + transform + LN + decoder    models/bert.py
nsp_head            BERT next-sentence classifier             models/bert.py
pretraining_loss    MLM + NSP loss                            models/bert.py
ssm_in_proj         Mamba-2 input projection (z, x, B, C, dt) models/nemotron_h.py
ssm_conv            depthwise causal conv + SiLU              models/nemotron_h.py
ssm_scan            the chunked selective scan                ops/ssd_scan.py
ssm_out             gated grouped RMSNorm + output projection models/nemotron_h.py
moe_router          fp32 scores, top-k, normalised weights    transformer/moe.py
moe_dispatch        sort by expert, gather the held rows      transformer/moe.py
moe_experts         the held experts' grouped matmuls         transformer/moe.py
moe_shared          the shared expert (every token)           models/nemotron_h.py
moe_combine         weighted rows back to token order         transformer/moe.py
gqa_attention       q/k/v projections, grouped-query flash,   models/nemotron_h.py,
                    output projection                         models/lfm2.py
attn_qk_norm        per-head RMSNorm of q and of k            models/lfm2.py
attn_rope           rotary positions on q and k               models/lfm2.py
conv_in_proj        short convolution: input projection       models/lfm2.py
                    ``[B | C | x]``
conv_gate           the two gates and the causal taps:        ops/short_conv.py
                    ``C * conv(B * x)``
conv_out_proj       short convolution: output projection      models/lfm2.py
mlp_dense           the dense gated MLP                       models/lfm2.py
diffusion_noise     block diffusion: the seeded draw, the     models/sdar.py
                    noised row, the two copies
blockdiff_attention q/k/v projections, q/k norm, rotary,      models/sdar.py
                    block-masked grouped-query flash, output
                    projection
diffusion_loss      the masked positions' cross-entropy,      models/sdar.py
                    weighed by 1 / p (inside ``lm_loss``)
=================== ======================================== ==========================

The model scopes from ``ssm_in_proj`` down sit INSIDE ``train_fwd_bwd`` (a
phase reader files their ops by that ancestor); ``attn_qk_norm`` and
``attn_rope`` sit inside ``gqa_attention`` (``models/lfm2.py``) or
``blockdiff_attention`` (``models/sdar.py``) besides. ``models/lfm2.py``
and ``models/sdar.py`` reuse ``lm_head`` / ``lm_loss`` and the ``moe_*``
scopes of the expert layer they share with ``models/nemotron_h.py``.

Pallas kernels carry a stable ``name=`` that says kernel and direction,
never the caller (:data:`KERNEL_NAMES`); the name becomes the HLO
instruction's name and so the device event's: ``flash_fwd``,
``flash_bwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``, ``layer_norm_fwd``,
``layer_norm_bwd``, ``softmax_fwd``, ``softmax_bwd``, ``dropout_apply``,
``dropout_mask``, ``short_conv_fwd``, ``short_conv_bwd``; a flash call
under a ``score_mask`` description carries the description's tag, so that
a trace tells it from a causal call: ``flash_blockdiff_fwd``,
``flash_blockdiff_bwd``, ``flash_blockdiff_bwd_dq``,
``flash_blockdiff_bwd_dkv``. The dropless
expert layer runs the grouped-matmul kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.megablox``),
which name themselves: ``gmm`` (forward and the rows' gradient) and
``tgmm`` (the weights' gradient) (:data:`LIBRARY_KERNEL_NAMES`); they sit
under the ``moe_experts`` scope, which is what a reader should match.

The flash-attention forward rules tag the kernel's outputs with
``jax.ad_checkpoint.checkpoint_name`` (:data:`FLASH_RESIDUALS`:
``flash_out``, ``flash_lse``) so that a rematerialised block can keep them
by name (``apex_tpu/transformer/remat.py``); outside a ``remat`` with a
policy that reads names the tag is the identity and compiles to nothing.
The dropless expert layer tags what ITS backward pass needs beside the
block's input (:data:`MOE_RESIDUALS`): the routing - ``moe_chosen``,
``moe_perm`` with ``moe_weights`` in its order, ``moe_inv_perm``,
``moe_group_sizes`` - and the rows the routing ordered, ``moe_hidden`` (the
up projection's output); the expert mixer's ``moe_input`` (the normed
tokens) goes with them. Rows are kept only together with the routing that
ordered them.

A model may report step counters beside its loss
(``build_train_step(has_aux=True)``; they arrive with the loss in
``metrics["aux"]``, no extra sync): :data:`STEP_COUNTERS` (the expert
layers') and, for the block-diffusion objective,
``diffusion_masked_tokens`` (:data:`DIFFUSION_COUNTERS`: the positions the
step's draw masked, which are the positions its loss runs over).

Host annotations (``jax.profiler.TraceAnnotation``, on the host plane
of the same capture; a flag test when no capture runs):
``train_dispatch`` and ``train_fetch`` (:class:`apex_tpu.train.TrainLoop`)
and ``data_wait`` (the loaders of :mod:`apex_tpu.data.loader`).

A scope is a ``jax.named_scope`` (metadata only: the compiled arithmetic
is unchanged). Its name holds no ``.`` and no ``/``: a scope's last
component can become an HLO instruction's name.

The apex-shaped surface:

- :func:`trace`: context manager around ``jax.profiler.trace`` (the
  ``pyprof.nvtx.init()`` analog: one line around the training loop);
- :func:`annotate`: named host region (``torch.cuda.nvtx.range`` analog);
- :class:`StepTimer`: host-side per-step wall timing with warmup
  exclusion and a summary dict — the "per-step timing surface" SURVEY
  prescribes, usable on runtimes where the full profiler is unavailable.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax

from apex_tpu.observability.metrics import percentile

# -- the scope vocabulary of the train step (table in the docstring) ----------
TRAIN_FWD_BWD = "train_fwd_bwd"
TRAIN_ACCUMULATE = "train_accumulate"
TRAIN_REDUCE = "train_reduce"
TRAIN_METRICS = "train_metrics"
AMP_SCALE_LOSS = "amp_scale_loss"
AMP_UNSCALE = "amp_unscale"
AMP_FOUND_INF = "amp_found_inf"
AMP_UPDATE_SCALE = "amp_update_scale"
OPTIMIZER_UPDATE = "optimizer_update"
LAMB_GRAD_NORM = "lamb_grad_norm"
LAMB_STAGE1 = "lamb_stage1"
LAMB_STAGE2 = "lamb_stage2"
ADAM_UPDATE = "adam_update"
DDP_FLATTEN = "ddp_flatten"
DDP_ALLREDUCE = "ddp_allreduce"
DDP_UNFLATTEN = "ddp_unflatten"
LM_HEAD = "lm_head"
LM_LOSS = "lm_loss"
MLM_HEAD = "mlm_head"
NSP_HEAD = "nsp_head"
PRETRAINING_LOSS = "pretraining_loss"
SSM_IN_PROJ = "ssm_in_proj"
SSM_CONV = "ssm_conv"
SSM_SCAN = "ssm_scan"
SSM_OUT = "ssm_out"
MOE_ROUTER = "moe_router"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_SHARED = "moe_shared"
MOE_COMBINE = "moe_combine"
GQA_ATTENTION = "gqa_attention"
ATTN_QK_NORM = "attn_qk_norm"
ATTN_ROPE = "attn_rope"
CONV_IN_PROJ = "conv_in_proj"
CONV_GATE = "conv_gate"
CONV_OUT_PROJ = "conv_out_proj"
MLP_DENSE = "mlp_dense"
DIFFUSION_NOISE = "diffusion_noise"
BLOCKDIFF_ATTENTION = "blockdiff_attention"
DIFFUSION_LOSS = "diffusion_loss"

STEP_SCOPES = (TRAIN_FWD_BWD, TRAIN_ACCUMULATE, TRAIN_REDUCE, TRAIN_METRICS,
               AMP_SCALE_LOSS, AMP_UNSCALE, AMP_FOUND_INF, AMP_UPDATE_SCALE,
               OPTIMIZER_UPDATE)
OPTIMIZER_SCOPES = (LAMB_GRAD_NORM, LAMB_STAGE1, LAMB_STAGE2, ADAM_UPDATE)
DDP_SCOPES = (DDP_FLATTEN, DDP_ALLREDUCE, DDP_UNFLATTEN)
MODEL_SCOPES = (LM_HEAD, LM_LOSS, MLM_HEAD, NSP_HEAD, PRETRAINING_LOSS)
# scopes of single layers, always nested in ``train_fwd_bwd``: a reader
# of phases files their ops by that ancestor, so they are no phase of
# their own and stay out of ``SCOPES``
LAYER_SCOPES = (SSM_IN_PROJ, SSM_CONV, SSM_SCAN, SSM_OUT, MOE_ROUTER,
                MOE_DISPATCH, MOE_EXPERTS, MOE_SHARED, MOE_COMBINE,
                GQA_ATTENTION, ATTN_QK_NORM, ATTN_ROPE, CONV_IN_PROJ,
                CONV_GATE, CONV_OUT_PROJ, MLP_DENSE, DIFFUSION_NOISE,
                BLOCKDIFF_ATTENTION, DIFFUSION_LOSS)
SCOPES = STEP_SCOPES + OPTIMIZER_SCOPES + DDP_SCOPES + MODEL_SCOPES

# -- step metrics a model reports beside its loss (``has_aux``) ----------------
MOE_ASSIGNMENTS_HELD = "moe_assignments_held"
MOE_LOAD_MAX_OVER_MEAN = "moe_load_max_over_mean"
MOE_TOKENS_DROPPED = "moe_tokens_dropped"
STEP_COUNTERS = (MOE_ASSIGNMENTS_HELD, MOE_LOAD_MAX_OVER_MEAN,
                 MOE_TOKENS_DROPPED)
# the block-diffusion objective's own (``models/sdar.py``), beside the above
DIFFUSION_MASKED_TOKENS = "diffusion_masked_tokens"
DIFFUSION_COUNTERS = (DIFFUSION_MASKED_TOKENS,)

# -- Pallas kernel names (``pl.pallas_call(name=...)``) ------------------------
KERNEL_NAMES = ("flash_fwd", "flash_bwd", "flash_bwd_dq", "flash_bwd_dkv",
                "layer_norm_fwd", "layer_norm_bwd", "softmax_fwd",
                "softmax_bwd", "dropout_apply", "dropout_mask",
                "short_conv_fwd", "short_conv_bwd", "flash_blockdiff_fwd",
                "flash_blockdiff_bwd", "flash_blockdiff_bwd_dq",
                "flash_blockdiff_bwd_dkv")

# kernels of a library the train path calls (named by the library)
LIBRARY_KERNEL_NAMES = ("gmm", "tgmm")

# -- residuals a rematerialised block may keep (``checkpoint_name``) -----------
# the flash-attention forward rules name the kernel's outputs; the
# "selective" policy of ``apex_tpu.transformer.remat`` keeps them, so the
# backward pass does not run ``flash_fwd`` a second time
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"
FLASH_RESIDUALS = (FLASH_OUT, FLASH_LSE)
# the dropless expert layer (``transformer/moe.py: DroplessMoE``) names its
# routing (the choice of k, the sort by expert with the slots' weights in
# its order, the sort's inverse, the held groups' sizes) and the up
# projection's output; ``models/nemotron_h.py: ExpertMixer`` names its
# input (the block's normed tokens). A recomputed routing can differ from
# the forward pass's by a rounding of its input, so rows are kept only
# WITH the routing that ordered them
MOE_INPUT = "moe_input"
MOE_CHOSEN = "moe_chosen"
MOE_WEIGHTS = "moe_weights"
MOE_PERM = "moe_perm"
MOE_INV_PERM = "moe_inv_perm"
MOE_GROUP_SIZES = "moe_group_sizes"
MOE_HIDDEN = "moe_hidden"
MOE_RESIDUALS = (MOE_INPUT, MOE_CHOSEN, MOE_WEIGHTS, MOE_PERM, MOE_INV_PERM,
                 MOE_GROUP_SIZES, MOE_HIDDEN)

# -- host annotations ----------------------------------------------------------
TRAIN_DISPATCH = "train_dispatch"
TRAIN_FETCH = "train_fetch"
DATA_WAIT = "data_wait"
ANNOTATIONS = (TRAIN_DISPATCH, TRAIN_FETCH, DATA_WAIT)


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False):
    """Capture a profiler trace of the enclosed block into ``log_dir``
    (view with TensorBoard's profile plugin or Perfetto)."""
    with jax.profiler.trace(log_dir,
                            create_perfetto_link=create_perfetto_link):
        yield


def annotate(name: str):
    """Named host region inside a capture (a line of the host plane, on
    the device ops' clock); a flag test when no capture is running."""
    return jax.profiler.TraceAnnotation(name)


def start_server(port: int = 9012):
    """On-demand profiling server (``jax.profiler.start_server``):
    connect from TensorBoard's capture-profile button."""
    return jax.profiler.start_server(port)


class StepTimer:
    """Per-step wall-clock timing with device synchronization.

    Usage::

        timer = StepTimer(warmup=2)
        for batch in data:
            out = step(...)
            timer.tick(out)          # blocks on out, records dt
        print(timer.summary())       # {mean_ms, p50/p90/p99_ms, ...}

    Percentiles come from the shared interpolating helper
    (:func:`apex_tpu.observability.metrics.percentile` — the one the
    metrics histograms use), so
    a p50 here means the same thing everywhere. (The previous median
    was ``ts[n // 2]`` — the upper neighbor, not the median, for
    even n.)
    """

    def __init__(self, warmup: int = 2):
        self.warmup = warmup
        self._seen = 0
        self._times = []
        self._last: Optional[float] = None

    def tick(self, *sync_on):
        """Record one step boundary; blocks on ``sync_on`` arrays so the
        measurement covers the device work, not just dispatch."""
        if sync_on:
            jax.block_until_ready(sync_on)
        now = time.perf_counter()
        if self._last is not None:
            self._seen += 1
            if self._seen > self.warmup:
                self._times.append(now - self._last)
        self._last = now

    def summary(self) -> dict:
        if not self._times:
            return {"steps": 0}
        ts = sorted(self._times)
        n = len(ts)
        return {
            "steps": n,
            "mean_ms": 1e3 * sum(ts) / n,
            "p50_ms": 1e3 * percentile(ts, 50),
            "p90_ms": 1e3 * percentile(ts, 90),
            "p99_ms": 1e3 * percentile(ts, 99),
            "min_ms": 1e3 * ts[0],
            "max_ms": 1e3 * ts[-1],
        }

    def reset(self):
        self._seen = 0
        self._times.clear()
        self._last = None
