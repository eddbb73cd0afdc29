"""Device time a step under the program's ``moe_experts`` scope (the held
experts' grouped matmuls and their activation, all three passes), over
the traced slice's whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("moe_experts"))
