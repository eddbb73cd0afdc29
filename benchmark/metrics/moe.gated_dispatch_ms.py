"""Device time a step under the program's ``moe_dispatch`` scope in a cell
whose experts are gated (sort of the assignments by expert, group sizes,
the gather of the rows and its transpose), over the traced slice's whole
runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("moe_dispatch"))
