"""setup.step_lower_s: seconds of the train step's ``lower``
spans before the window, from the program's compile record
(``harness/setup_spans.py``)."""

from benchmark.harness import setup_spans


def read(ctx):
    return setup_spans.step_seconds(ctx, "lower")
