"""Device time a step of the expert blocks (``E`` of the pattern: norm,
router, dispatch, the held experts, the shared expert, combine,
residual; all three passes), by the blocks' flax path, over the traced
slice's whole runs."""

from benchmark.harness import flops, layers


def read(ctx):
    pattern = flops.counts(ctx["config"]).pattern(ctx["config"])
    return layers.ms_a_step(ctx, layers.blocks_regex(pattern, "E"))
