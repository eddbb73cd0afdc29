"""Device time a step under the program's ``optimizer_update`` scope (the
``lax.cond`` and both branches: LAMB's or Adam's stages), over the traced
slice; the phase table is ``benchmark/patterns/step.phases/phases.txt``."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "optimizer")
