"""Device time a step of the Mamba-2 blocks (``M`` of the pattern: norm,
projections, convolution, scan, gated norm, residual; all three passes),
by the blocks' flax path, over the traced slice's whole runs."""

from benchmark.harness import flops, layers


def read(ctx):
    pattern = flops.counts(ctx["config"]).pattern(ctx["config"])
    return layers.ms_a_step(ctx, layers.blocks_regex(pattern, "M"))
