"""Token-expert pairs a step computed on this chip, summed over the
softmax-routed expert layers: the step's own ``moe_assignments_held``,
mean over the window."""

from benchmark.harness import layers


def read(ctx):
    return layers.counter(ctx, "moe_assignments_held")
