"""Device time a step of the attention mixers (q, k, v projections, the
per-head q/k norm, rotary positions, grouped-query flash, the output
projection; all three passes), by the mixers' flax path
(``layers_<i>/self_attn``), over the traced slice's whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("self_attn"))
