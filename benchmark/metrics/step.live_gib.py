"""Bytes the compiled step holds at once on one chip, by the compiler's
``memory_analysis()``: arguments + outputs - aliased + temporaries."""


def read(ctx):
    live = ctx["program"].get("live_bytes")
    return None if live is None else live / 2.0 ** 30
