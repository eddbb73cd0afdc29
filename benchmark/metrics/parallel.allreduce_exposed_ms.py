"""Time per step in which an all-reduce runs on a chip and no other op
does (device trace; median over chips, mean over the traced steps)."""

import statistics

from benchmark.harness import trace


def read(ctx):
    steps = ctx["slice_steps"]
    if not steps or ctx["trace"] is None:
        return None
    per_chip = [trace.exposed(ev) for ev in ctx["trace"].ops.values()]
    if any(x is None for x in per_chip):
        return None
    return 1e3 * statistics.median(per_chip) / steps
