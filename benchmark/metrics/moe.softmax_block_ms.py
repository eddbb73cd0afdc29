"""Device time a step of the softmax-routed gated expert feed-forwards
(router, dispatch, the held experts, combine; all three passes), by their
flax path (``layers_<i>/expert_ffn``), over the traced slice's whole
runs."""

from benchmark.harness import layers


def read(ctx):
    return layers.ms_a_step(ctx, layers.scope_regex("expert_ffn"))
