"""Device time a step of the backward pass (``transpose(`` in the op's
path), recomputed forward, heads and amp excluded, over the traced slice."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "bwd")
