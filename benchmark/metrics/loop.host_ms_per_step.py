"""Host time ``TrainLoop.step()`` takes per step without the time it is
blocked fetching the previous step's metrics: dispatch and bookkeeping.
From the benchmark's spans around ``loop.step`` and around the loop's
fetch, over the steps before the traced slice."""


def read(ctx):
    lo, hi = ctx["window"][0], ctx["slice"][0] or ctx["window"][1]
    spans = ctx["spans"]
    n = spans.count("loop.step", lo, hi)
    if not n:
        return None
    host = spans.total("loop.step", lo, hi) - spans.total("fetch", lo, hi)
    return 1e3 * host / n
