"""Device time a step under the program's ``ddp_*`` scopes (flatten, the
all-reduce, unflatten), over the traced slice, mean over chips. Busy
time, hidden or not; the exposed part is ``parallel.allreduce_exposed_ms``."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "allreduce")
