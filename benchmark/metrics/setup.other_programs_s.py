"""setup.other_programs_s: seconds of the trace, lower and compile spans
of every program but the train step before the window (the state's jit,
the batch placement, the norms), each nested span counted once in the
outermost, from the program's compile record (``harness/setup_spans.py``)."""

from benchmark.harness import setup_spans


def read(ctx):
    return setup_spans.other_programs_seconds(ctx)
