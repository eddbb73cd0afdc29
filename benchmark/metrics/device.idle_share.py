"""Share of the traced slice in which no op ran on the device: 1 - union
of the op intervals over first-start-to-last-end, of the idlest chip."""

from benchmark.harness import trace


def read(ctx):
    if ctx["trace"] is None:
        return None
    return 100.0 * max(trace.idle_share(ev)
                       for ev in ctx["trace"].ops.values())
