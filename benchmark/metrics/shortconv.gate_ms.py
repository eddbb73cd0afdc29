"""Device time a step under the program's ``conv_gate`` scope (the two
gates and the causal taps of every short-convolution mixer: ``C * conv(B *
x)``, forward, recomputed forward and backward), over the traced slice's
whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("conv_gate"))
