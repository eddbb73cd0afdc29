"""Device time a step under the program's ``window_attention`` scope (a
window layer's q, k, v and gate projections, the per-head q/k norm, rotary
positions, the window-masked grouped-query flash kernels, the output gate
and projection; all three passes), over the traced slice's whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("window_attention"))
