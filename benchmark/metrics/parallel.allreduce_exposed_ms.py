"""Time per step in which an all-reduce runs on a chip and no other op
does (device trace; median over chips; a step is a whole run of the
program in the capture, as for the phase metrics: the capture starts in
mid-step and its clipped first run holds an all-reduce too)."""

import statistics

from benchmark.harness import scopes, trace


def read(ctx):
    if ctx["trace"] is None:
        return None
    per_chip = []
    for chip, events in ctx["trace"].ops.items():
        events, steps = scopes.in_whole_runs(
            events, ctx["trace"].modules.get(chip, []), ctx["slice_steps"])
        alone = trace.exposed(events)
        if alone is None or not steps:
            return None
        per_chip.append(alone / steps)
    return 1e3 * statistics.median(per_chip)
