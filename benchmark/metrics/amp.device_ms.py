"""Device time a step under the program's ``amp_*`` scopes (loss scaling
and its transpose, unscale + finiteness check, the global overflow flag,
the scaler's update), over the traced slice."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "amp")
