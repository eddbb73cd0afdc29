"""Time a step waits for its batch: the benchmark's span around the
traffic's ``next`` (the program's loader, where the cell has one), per
step, over the steps before the traced slice."""


def read(ctx):
    lo, hi = ctx["window"][0], ctx["slice"][0] or ctx["window"][1]
    n = ctx["spans"].count("loader.next", lo, hi)
    if not n:
        return None
    return 1e3 * ctx["spans"].total("loader.next", lo, hi) / n
