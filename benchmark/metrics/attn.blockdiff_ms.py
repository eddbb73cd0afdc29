"""Device time a step under the program's ``blockdiff_attention`` scope
(q, k, v projections, the per-head q/k norm, rotary positions, the
block-masked grouped-query flash kernels, the output projection; all
three passes), over the traced slice's whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("blockdiff_attention"))
