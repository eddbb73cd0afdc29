"""setup.cache_misses: compiles before the window that the persistent
compilation cache did not hold, from the program's compile record
(``harness/setup_spans.py``)."""

from benchmark.harness import setup_spans


def read(ctx):
    return setup_spans.cache_misses(ctx)
