"""Share of the traced slice's busy time whose instruction is filed under
no scope of the program's vocabulary (the ``vocabulary:`` line of
``benchmark/patterns/step.phases/phases.txt``): the check on the tracing
itself. 100 for a program that names nothing."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.unscoped_share(ctx)
