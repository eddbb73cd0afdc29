"""Device time a step under the program's ``mlp_dense`` scope (the leading
layers' dense gated MLP: the gate and up projections as one matmul, SiLU
times the gate, the down projection; all three passes), over the traced
slice's whole runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("mlp_dense"))
