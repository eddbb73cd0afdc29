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
window_attention    a window layer's attention: q/k/v/gate    models/afmoe.py
                    projections, q/k norm, rotary, window-
                    masked grouped-query flash, gate, output
                    projection
global_attention    a global layer's attention: the same      models/afmoe.py
                    with no position term and causal flash
attn_gate           the output gate ``o * sigmoid(g)``        models/afmoe.py
mla_attention       latent attention: everything below        models/deepseek_v3.py
mla_q               the query projection                      models/deepseek_v3.py
mla_kv_down         the kv latent and the shared rotary key:  models/deepseek_v3.py
                    ``[c | k_r] = x W_kva``, RMSNorm of ``c``
mla_kv_up           the per-head keys and values from the     models/deepseek_v3.py
                    latent: ``[k_n | v] = c W_kvb``
mla_rope            rotary positions on ``q_r`` and ``k_r``,  models/deepseek_v3.py
                    and q and k assembled per head (``k_r``
                    broadcast over the heads)
mla_core            the causal flash call (``flash_mla_*``)   models/deepseek_v3.py
mla_out             the output projection                     models/deepseek_v3.py
=================== ======================================== ==========================

The model scopes from ``ssm_in_proj`` down sit INSIDE ``train_fwd_bwd`` (a
phase reader files their ops by that ancestor); ``attn_qk_norm`` and
``attn_rope`` sit inside ``gqa_attention`` (``models/lfm2.py``),
``blockdiff_attention`` (``models/sdar.py``) or ``window_attention`` /
``global_attention`` (``models/afmoe.py``, which puts ``attn_gate`` inside
both) besides; the six ``mla_*`` scopes sit inside ``mla_attention``.
``models/lfm2.py``, ``models/sdar.py``, ``models/afmoe.py`` and
``models/deepseek_v3.py`` reuse ``lm_head`` / ``lm_loss`` and the ``moe_*``
scopes of the expert layer they share with ``models/nemotron_h.py``
(``models/afmoe.py`` and ``models/deepseek_v3.py`` ``moe_shared`` and
``mlp_dense`` too).

Pallas kernels carry a stable ``name=`` that says kernel and direction,
never the caller (:data:`KERNEL_NAMES`); the name becomes the HLO
instruction's name and so the device event's: ``flash_fwd``,
``flash_bwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``, ``layer_norm_fwd``,
``layer_norm_bwd``, ``softmax_fwd``, ``softmax_bwd``, ``dropout_apply``,
``dropout_mask``, ``short_conv_fwd``, ``short_conv_bwd``; a flash call
under a ``score_mask`` description carries the description's tag, so that
a trace tells it from a causal call: ``flash_blockdiff_fwd``,
``flash_blockdiff_bwd``, ``flash_blockdiff_bwd_dq``,
``flash_blockdiff_bwd_dkv`` and ``flash_window_fwd``, ``flash_window_bwd``,
``flash_window_bwd_dq``, ``flash_window_bwd_dkv``; a call whose v is
narrower than q and k (latent attention) carries ``mla``:
``flash_mla_fwd``, ``flash_mla_bwd``, ``flash_mla_bwd_dq``,
``flash_mla_bwd_dkv``. The dropless
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
tokens) goes with them, and ``moe_output`` (the layer's output, where a
norm's backward pass reads it: ``models/afmoe.py``) too. Rows are kept only
together with the routing that ordered them.

A model may report step counters beside its loss
(``build_train_step(has_aux=True)``; they arrive with the loss in
``metrics["aux"]``, no extra sync): :data:`STEP_COUNTERS` (the expert
layers') and, for the block-diffusion objective,
``diffusion_masked_tokens`` (:data:`DIFFUSION_COUNTERS`: the positions the
step's draw masked, which are the positions its loss runs over).

Host annotations (``jax.profiler.TraceAnnotation``, on the host plane
of the same capture; a flag test when no capture runs):
``train_dispatch`` and ``train_fetch`` (:class:`apex_tpu.train.TrainLoop`)
and ``data_wait`` (the loaders of :mod:`apex_tpu.data.loader`);
``train_init`` and ``train_lower`` (:class:`apex_tpu.train.TrainStep`'s
``init`` and ``lower``).

The compile record (:func:`compile_record`, one per process, always on)
holds set-up under the program's own spans. Listeners on JAX's compile
events give a *stage* span for each ``trace`` (jaxpr), ``lower`` (MLIR
module; the Pallas kernels' Python lowering runs here) and ``compile``
(``compile_or_get_cached``: on a persistent-cache hit it is the read, and
the span says so), timed on ``time.perf_counter``; each nests under the
stage span it ran inside or the innermost *program* span open on its
thread: ``train_init``, ``train_lower``, and a ``train_dispatch`` under
which anything compiled (one that compiled nothing is not kept: a steady
step adds nothing to the record). A ``TrainStep`` claims its program
(:data:`TRAIN_STEP_PROGRAM`), so a reader asks
:meth:`CompileRecord.program_of` and matches no name of its own. The
record is bounded (the first :attr:`CompileRecord.CAPACITY` spans; later
ones are counted and dropped) and costs a few listener calls per compile
(:meth:`CompileRecord.stats`).

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
import functools
import sys
import threading
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
WINDOW_ATTENTION = "window_attention"
GLOBAL_ATTENTION = "global_attention"
ATTN_GATE = "attn_gate"
MLA_ATTENTION = "mla_attention"
MLA_Q = "mla_q"
MLA_KV_DOWN = "mla_kv_down"
MLA_KV_UP = "mla_kv_up"
MLA_ROPE = "mla_rope"
MLA_CORE = "mla_core"
MLA_OUT = "mla_out"

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
                BLOCKDIFF_ATTENTION, DIFFUSION_LOSS, WINDOW_ATTENTION,
                GLOBAL_ATTENTION, ATTN_GATE, MLA_ATTENTION, MLA_Q,
                MLA_KV_DOWN, MLA_KV_UP, MLA_ROPE, MLA_CORE, MLA_OUT)
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
                "flash_blockdiff_bwd_dkv", "flash_window_fwd",
                "flash_window_bwd", "flash_window_bwd_dq",
                "flash_window_bwd_dkv", "flash_mla_fwd", "flash_mla_bwd",
                "flash_mla_bwd_dq", "flash_mla_bwd_dkv")

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
# a sparse layer's output that a norm follows (``models/afmoe.py``): kept,
# or the norm's backward pass would run the down projection again
MOE_OUTPUT = "moe_output"
MOE_RESIDUALS = (MOE_INPUT, MOE_CHOSEN, MOE_WEIGHTS, MOE_PERM, MOE_INV_PERM,
                 MOE_GROUP_SIZES, MOE_HIDDEN, MOE_OUTPUT)

# -- host annotations ----------------------------------------------------------
TRAIN_DISPATCH = "train_dispatch"
TRAIN_FETCH = "train_fetch"
DATA_WAIT = "data_wait"
# program spans of the compile record (below), annotations as well
TRAIN_INIT = "train_init"
TRAIN_LOWER = "train_lower"
PROGRAM_SPANS = (TRAIN_INIT, TRAIN_LOWER, TRAIN_DISPATCH)
ANNOTATIONS = (TRAIN_DISPATCH, TRAIN_FETCH, DATA_WAIT, TRAIN_INIT,
               TRAIN_LOWER)

# -- the compile record: stages, and the program a TrainStep claims ------------
TRACE = "trace"
LOWER = "lower"
COMPILE = "compile"
STAGES = (TRACE, LOWER, COMPILE)
TRAIN_STEP_PROGRAM = "train_step"
# JAX's own compile events (``jax._src.dispatch``, ``jax._src.compiler``)
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE,
}
_CACHE_USED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}


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


# -- the compile record ----------------------------------------------------------

class CompileSpan:
    """One span of the compile record, on ``time.perf_counter``'s clock.

    A *stage* span (``stage`` in :data:`STAGES`) is one of JAX's own
    compile events: ``fun_name`` is JAX's name of the function traced
    (``fused_step``) or of the module lowered and compiled
    (``jit(fused_step)``); a ``compile`` span carries the persistent
    cache's ``cache`` outcome (``"hit"``, ``"miss"``, or ``"off"`` where
    the cache was not asked) and, on a hit, the seconds JAX reports for
    the read (``retrieval_s``) and saved (``saved_s``). A *program* span
    (``stage`` None) is one of :data:`PROGRAM_SPANS` around set-up the
    program does; ``end`` is None while it is open. ``parent`` is the
    ``seq`` of the span it nests in (a stage span: a jitted function
    traced inside its caller's trace; else the innermost program span
    open on its thread), ``step`` the :class:`~apex_tpu.train.TrainLoop`
    step being dispatched, or None."""

    __slots__ = ("seq", "name", "stage", "fun_name", "start", "end",
                 "parent", "thread", "step", "cache", "retrieval_s",
                 "saved_s")

    def __init__(self, seq, name, stage, fun_name, start, end, parent,
                 thread, step):
        self.seq, self.name, self.stage = seq, name, stage
        self.fun_name, self.start, self.end = fun_name, start, end
        self.parent, self.thread, self.step = parent, thread, step
        self.cache = self.retrieval_s = self.saved_s = None

    @property
    def seconds(self) -> float:
        return 0.0 if self.end is None else self.end - self.start


class _Frame:
    """A program span open on a thread. It enters the record once a stage
    opens under it (or at its end, if always kept); ``kids`` are the
    stage spans directly under it, closed (None while there are none)."""

    __slots__ = ("name", "start", "step", "up", "span", "kids")

    def __init__(self, name, start, step, up):
        self.name, self.start, self.step, self.up = name, start, step, up
        self.span = self.kids = None


class _Open:
    """A stage JAX has begun on a thread; its ``seq`` is taken at the
    start, so that what nests inside can name its parent."""

    __slots__ = ("seq", "event", "parent", "step")

    def __init__(self, seq, event, parent, step):
        self.seq, self.event, self.parent, self.step = (seq, event, parent,
                                                        step)


class CompileRecord:
    """Set-up under the program's own spans: every trace, lower and
    compile-or-cache-read JAX reports, nested under the span that caused
    it. One per process (:func:`compile_record`), its listeners on JAX's
    compile events registered once, on first use. In memory and bounded:
    the first :attr:`CAPACITY` spans are kept (set-up is what the record
    is for) and later ones are counted in ``dropped``; a program span
    sees its own stage spans (``kids``) either way. A dispatch that
    compiles nothing leaves nothing in the record."""

    CAPACITY = 65536

    def __init__(self):
        self._spans: list = []
        self._lock = threading.Lock()
        self._local = threading.local()
        self._seq = 0
        self._names = {}           # fun_name -> program label
        self.dropped = 0
        self.callbacks = 0         # listener calls, and their own time
        self.callback_s = 0.0
        self._installed = False

    # -- JAX's listeners ------------------------------------------------------
    def install(self) -> "CompileRecord":
        """Register the listeners (idempotent)."""
        with self._lock:
            if not self._installed:
                jax.monitoring.register_scalar_listener(self._on_start)
                jax.monitoring.register_event_duration_secs_listener(
                    self._on_duration)
                jax.monitoring.register_event_listener(self._on_event)
                self._installed = True
        return self

    def uninstall(self) -> None:
        with self._lock:
            if self._installed:
                jax.monitoring.unregister_scalar_listener(self._on_start)
                jax.monitoring.unregister_event_duration_listener(
                    self._on_duration)
                jax.monitoring.unregister_event_listener(self._on_event)
                self._installed = False

    def _stack(self) -> list:
        """This thread's open spans, outermost first."""
        local = self._local
        if not hasattr(local, "stack"):
            local.stack = []
            local.cache = None     # the open compile's cache outcome
        return local.stack

    def _seq_of(self, entry) -> int:
        """``seq`` of an open entry; a program span enters the record
        now if it is not in it yet (and so do its enclosing ones)."""
        if isinstance(entry, _Open):
            return entry.seq
        if entry.span is None:
            up = None if entry.up is None else self._seq_of(entry.up)
            self._seq += 1
            entry.span = CompileSpan(self._seq, entry.name, None, None,
                                     entry.start, None, up,
                                     threading.get_ident(), entry.step)
            self._append(entry.span)
        return entry.span.seq

    def _append(self, span) -> None:
        if len(self._spans) < self.CAPACITY:
            self._spans.append(span)
        else:
            self.dropped += 1

    def _on_start(self, event, value, **_):
        # JAX reports a stage's start as a scalar (``log_elapsed_time``)
        t = time.perf_counter()
        if event in _STAGE_EVENTS:
            stack = self._stack()
            up = stack[-1] if stack else None
            with self._lock:
                parent = None if up is None else self._seq_of(up)
                self._seq += 1
                stack.append(_Open(self._seq, event, parent,
                                   None if up is None else up.step))
        self.callbacks += 1
        self.callback_s += time.perf_counter() - t

    def _on_event(self, event, **_):
        t = time.perf_counter()
        if event in (_CACHE_USED, _CACHE_HIT, _CACHE_MISS):
            local = self._local
            self._stack()
            if event == _CACHE_HIT:
                local.cache = {"cache": "hit"}
            elif local.cache is None:
                local.cache = {"cache": "miss"}
        self.callbacks += 1
        self.callback_s += time.perf_counter() - t

    def _on_duration(self, event, duration, **kw):
        end = time.perf_counter()
        stage = _STAGE_EVENTS.get(event)
        if stage is not None:
            self._close(stage, event, str(kw.get("fun_name", "")),
                        end - float(duration), end)
        elif event in _CACHE_SECONDS:
            local = self._local
            self._stack()
            if local.cache is not None:
                local.cache[_CACHE_SECONDS[event]] = float(duration)
        self.callbacks += 1
        self.callback_s += time.perf_counter() - end

    def _close(self, stage, event, fun_name, start, end) -> None:
        stack = self._stack()
        with self._lock:
            if stack and isinstance(stack[-1], _Open) \
                    and stack[-1].event == event:
                opened = stack.pop()
                seq, parent, step = opened.seq, opened.parent, opened.step
            else:               # begun before the listeners were there
                up = stack[-1] if stack else None
                parent = None if up is None else self._seq_of(up)
                step = None if up is None else up.step
                self._seq += 1
                seq = self._seq
            span = CompileSpan(seq, stage, stage, fun_name, start, end,
                               parent, threading.get_ident(), step)
            if stage == COMPILE:
                got, self._local.cache = self._local.cache, None
                got = got or {"cache": "off"}
                span.cache = got["cache"]
                span.retrieval_s = got.get("retrieval_s")
                span.saved_s = got.get("saved_s")
            self._append(span)
        if stack and isinstance(stack[-1], _Frame):
            frame = stack[-1]
            if frame.kids is None:
                frame.kids = []
            frame.kids.append(span)

    @contextlib.contextmanager
    def program_span(self, name: str, step: Optional[int] = None,
                     keep: bool = True):
        """Open program span ``name`` (an :func:`annotate` region too) on
        this thread; ``step``: the loop step it dispatches (else its
        enclosing span's). ``keep=False``: it enters the record only if a
        stage opens under it. Yields the open frame (``kids``, ``span``)."""
        stack = self._stack()
        up = stack[-1] if stack else None
        frame = _Frame(name, time.perf_counter(),
                       step if step is not None or up is None else up.step,
                       up)
        stack.append(frame)
        try:
            with annotate(name):
                yield frame
        finally:
            end = time.perf_counter()
            stack.remove(frame)
            if frame.span is not None or keep:
                with self._lock:
                    self._seq_of(frame)
                    frame.span.end = end

    def claim(self, label: str, fn) -> None:
        """Say that the program ``jax.jit`` traces from ``fn`` is
        ``label``: its trace spans carry ``fn``'s name as JAX reads it,
        its lower and compile spans the module's, ``jit(<name>)``."""
        name = getattr(fn, "__name__", None)
        while name is None and isinstance(fn, functools.partial):
            fn = fn.func
            name = getattr(fn, "__name__", None)
        if name is not None:
            self._names[name] = label
            self._names[f"jit({name})"] = label

    # -- reading ----------------------------------------------------------------
    def spans(self) -> list:
        with self._lock:
            return list(self._spans)

    def program_of(self, span, by_seq) -> Optional[str]:
        """The label a :meth:`claim` gave the program a stage span
        belongs to: that of the outermost stage span enclosing it, walked
        up through ``by_seq`` (``{seq: span}`` of the spans read; ``{}``
        for a span known to nest in no stage)."""
        if span.stage is None:
            return None
        top = span
        while top.parent in by_seq and by_seq[top.parent].stage is not None:
            top = by_seq[top.parent]
        return self._names.get(top.fun_name)

    def split(self, spans, program: str = TRAIN_STEP_PROGRAM) -> dict:
        """Seconds by stage of ``program``'s outermost stage spans among
        ``spans`` (a nested span is in its caller's), and under
        ``"cache"`` the persistent cache's outcome of each of its
        compiles."""
        by_seq = {s.seq: s for s in spans}
        out = {stage: 0.0 for stage in STAGES}
        out["cache"] = []
        for s in spans:
            if s.stage is None or (s.parent in by_seq and
                                   by_seq[s.parent].stage is not None):
                continue
            if self.program_of(s, by_seq) == program:
                out[s.stage] += s.seconds
                if s.stage == COMPILE:
                    out["cache"].append(s.cache)
        return out

    def stats(self) -> dict:
        """What the record holds and what it cost: spans kept, dropped,
        the listener calls and their seconds, and the bytes held (the
        spans, their times and the list; names are JAX's own strings)."""
        spans = self.spans()
        held = sys.getsizeof(spans) + sum(
            sys.getsizeof(s) + sum(sys.getsizeof(getattr(s, k)) for k in
                                   ("start", "end", "retrieval_s", "saved_s")
                                   if getattr(s, k) is not None)
            for s in spans)
        return {"spans": len(spans), "dropped": self.dropped,
                "callbacks": self.callbacks,
                "callback_s": self.callback_s, "bytes": held}


_RECORD: Optional[CompileRecord] = None


def compile_record() -> CompileRecord:
    """The process's :class:`CompileRecord`, its listeners registered on
    the first call."""
    global _RECORD
    if _RECORD is None:
        _RECORD = CompileRecord().install()
    return _RECORD
