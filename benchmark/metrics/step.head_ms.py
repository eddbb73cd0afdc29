"""Device time a step under the models' head and loss scopes (``lm_head``,
``lm_loss``, ``mlm_head``, ``nsp_head``, ``pretraining_loss``), forward and
backward together, over the traced slice."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "head")
