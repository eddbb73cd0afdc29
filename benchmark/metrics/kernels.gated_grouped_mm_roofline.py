"""The gated experts' grouped matmuls' share of their roofline: the work
of an expert layer's grouped matmuls in one pass
(``counts/<family>.py: grouped_mm_call``: forward one of width 2F and one
of F -> H, backward their four) at the assignments the step itself reports
(``moe_assignments_held`` over the expert layers), for each forward and
backward call seen under the ``moe_experts`` scope, over the device time of
the events under it (the gate and the weighing between the matmuls
included). A recomputed event holds no grouped matmul (the block keeps its
rows) and is credited with nothing: the patterns leave it out."""

from benchmark.harness import flops, layers, roofline


def read(ctx):
    held = layers.counter(ctx, "moe_assignments_held")
    folded = layers.calls_as_events(ctx, "moe_experts")
    if folded is None or not held:
        return None
    counts = flops.counts(ctx["config"])
    a_layer = held / len(counts.layers_of(ctx["config"], "moe"))

    def work_of(kind, event):
        return counts.grouped_mm_call(ctx["config"], a_layer, kind)

    return roofline.share(folded, "kernels.gated_grouped_mm_roofline",
                          work_of)
