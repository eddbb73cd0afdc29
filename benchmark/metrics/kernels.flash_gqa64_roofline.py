"""Grouped-query flash attention's share of its roofline in this cell (32
query heads on 8 key/value heads of 64, causal): operations and bytes of
the attention calls the step makes (``harness/flops.py: attention_call``
from the family's ``attention_shape``, per call, times the calls seen in
the trace) over the device time of the kernels that implement them
(``benchmark/patterns/kernels.flash_gqa64_roofline/``)."""

from benchmark.harness import flops, roofline


def read(ctx):
    if ctx["trace"] is None:
        return None
    rows, seq = ctx["traffic"]["rows_per_chip"], ctx["traffic"]["seq"]

    def work_of(kind, event):
        return flops.attention_call(ctx["config"], rows, seq, kind)

    return roofline.share(ctx, "kernels.flash_gqa64_roofline", work_of)
