"""apex_tpu.train — the composed training step (docs/training.md).

The training-side dual of ``apex_tpu.serving``: where the serving
engine fuses K decode iterations into one dispatch with deferred host
sync, :func:`build_train_step` fuses the whole global optimizer step —
forward, backward, loss-scale unscale + in-graph overflow skip,
scanned gradient accumulation, one post-scan DDP allreduce, fused
optimizer update — into ONE donated-buffer dispatch, and
:class:`TrainLoop` defers every metrics fetch behind the next
dispatch.

``build_reference_loop`` builds the hand-wired per-microbatch dispatch
loop with bit-identical math — the certification baseline of
tests/test_train_step.py.
"""

from apex_tpu.train.loop import (  # noqa: F401
    NonFiniteLossError,
    TrainLoop,
    WatchdogConfig,
)
from apex_tpu.train.step import (  # noqa: F401
    ReferenceLoop,
    TrainState,
    TrainStep,
    build_reference_loop,
    build_train_step,
)
