"""Device time a step of the first forward pass (under ``train_fwd_bwd``
with no backward or recomputation marker), heads and amp excluded, over
the traced slice."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "fwd")
