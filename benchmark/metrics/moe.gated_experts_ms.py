"""Device time a step under the program's ``moe_experts`` scope in a cell
whose experts are gated (the grouped matmuls of width 2F and F, SiLU times
the gate times the row's weight between them; all three passes), over the
traced slice's whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("moe_experts"))
