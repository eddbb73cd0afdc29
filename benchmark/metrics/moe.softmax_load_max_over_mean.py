"""The fullest held expert's load over the mean load of the held experts
(the worst softmax-routed expert layer of a step): the step's own
``moe_load_max_over_mean``, mean over the window."""

from benchmark.harness import layers


def read(ctx):
    return layers.counter(ctx, "moe_load_max_over_mean")
