"""Device time a step under the program's ``mla_attention`` scope (latent
attention: the q projection, the kv latent and its norm, the per-head keys
and values from it, rotary positions, the assembly of q and k, the causal
``flash_mla_*`` kernels and the output projection; all three passes), over
the traced slice's whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("mla_attention"))
