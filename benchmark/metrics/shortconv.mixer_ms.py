"""Device time a step of the gated short-convolution mixers (``in_proj``,
the gates and taps, ``out_proj``; all three passes), by the mixers' flax
path (``layers_<i>/conv``), over the traced slice's whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("conv"))
