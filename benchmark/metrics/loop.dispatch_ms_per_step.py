"""Host time of one dispatch of the compiled step: the mean of the
program's own ``train_dispatch`` annotations (``TrainLoop.step`` around
its guarded call) in the capture of the traced slice, read from the host
plane of the profile this run wrote. None where the program has no such
annotation."""

from benchmark.harness import scopes


def read(ctx):
    spans = scopes.annotations(ctx["manifest"].root, "train_dispatch")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
