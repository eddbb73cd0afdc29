"""Steps of the window that the loss scaler skipped for an overflow, from
the step's own metrics dict."""


def read(ctx):
    return float(sum(bool(m["skipped"]) for m in ctx["metrics"]))
