"""Positions a step's draw masked, which are the positions its loss runs
over: the step's own ``diffusion_masked_tokens``, mean over the window
(about half of the row's tokens: the schedule's ``t`` is uniform)."""

from benchmark.harness import layers


def read(ctx):
    return layers.counter(ctx, "diffusion_masked_tokens")
