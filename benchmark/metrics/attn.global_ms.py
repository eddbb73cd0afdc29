"""Device time a step under the program's ``global_attention`` scope (a
global layer's q, k, v and gate projections, the per-head q/k norm, the
causal grouped-query flash kernels with no position term, the output gate
and projection; all three passes), over the traced slice's whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("global_attention"))
