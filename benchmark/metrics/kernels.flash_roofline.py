"""Flash attention's share of its roofline: operations and bytes of the
attention calls the step makes (from the cell's shapes, per call, times
the calls seen in the trace) over the device time of the events that
implement them (``benchmark/patterns/kernels.flash_roofline/``)."""

from benchmark.harness import flops, roofline


def read(ctx):
    if ctx["trace"] is None:
        return None
    rows, seq = ctx["traffic"]["rows_per_chip"], ctx["traffic"]["seq"]

    def work_of(kind, event):
        return flops.attention_call(ctx["config"], rows, seq, kind)

    return roofline.share(ctx, "kernels.flash_roofline", work_of)
