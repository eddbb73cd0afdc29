"""Device time a step under the program's ``ssm_scan`` scope (the chunked
selective scan of every Mamba-2 block: forward, recomputed forward and
backward), over the traced slice's whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("ssm_scan"))
