"""The held experts' grouped matmuls' share of their roofline: the work
of an expert layer's grouped matmuls in one pass
(``counts/<family>.py: grouped_mm_call``) at the assignments the step
itself reports (``moe_assignments_held`` over the expert layers), for each
call seen under the ``moe_experts`` scope, over the device time of the
events under it (the activation between the two matmuls included)."""

from benchmark.harness import flops, layers, roofline


def read(ctx):
    held = layers.counter(ctx, "moe_assignments_held")
    folded = layers.calls_as_events(ctx, "moe_experts")
    if folded is None or not held:
        return None
    counts = flops.counts(ctx["config"])
    a_layer = held / counts.pattern(ctx["config"]).count("E")

    def work_of(kind, event):
        return counts.grouped_mm_call(ctx["config"], a_layer, kind)

    return roofline.share(folded, "kernels.grouped_mm_roofline", work_of)
