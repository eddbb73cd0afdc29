"""Device time a step that no row of the phase table claims: the fp32
accumulate, the reduce, the metrics, parameter copies, ops with no scope.
With the other phases it sums to the busy time of the traced slice."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "other")
