"""Bytes the compiled step's all-reduce ops carry, from its HLO text."""

from benchmark.harness import hlo


def read(ctx):
    text = ctx["program"].get("hlo")
    if text is None:
        return None
    n = hlo.collective_stats(text)["all-reduce"]["bytes"]
    return float(n) if n else None
