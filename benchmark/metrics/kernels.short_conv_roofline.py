"""The short convolution's gate's share of its roofline: the work of one
call (``counts/<family>.py: short_conv_call``: ``[B | C | x]`` in and ``y``
out in bfloat16, the backward its own count) for each call seen under the
``conv_gate`` scope (one per conv mixer and pass) over the device time of
the events under it. Matched by scope: a kernel that replaces the fusions
under it needs no new pattern."""

from benchmark.harness import flops, layers, roofline


def read(ctx):
    folded = layers.calls_as_events(ctx, "conv_gate")
    if folded is None:
        return None
    tokens = ctx["traffic"]["rows_per_chip"] * ctx["traffic"]["seq"]
    call = flops.counts(ctx["config"]).short_conv_call

    def work_of(kind, event):
        return call(ctx["config"], tokens, kind)

    return roofline.share(folded, "kernels.short_conv_roofline", work_of)
