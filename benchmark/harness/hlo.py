"""Collective ops and their bytes from a compiled program's HLO text.
(Copied from ``apex_tpu/utils/hlo_audit.py`` ``collective_stats``, so
that the yardstick cannot move with the program.)"""

from __future__ import annotations

import re

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1}
# `%name = <shapes> all-reduce(` or its async `-start(`; the `-done` line
# repeats no operand shapes of its own and is not counted
_LINE_RE = re.compile(
    r"=\s*(?P<shapes>.*?)\s+(?P<kind>"
    + "|".join(COLLECTIVE_KINDS) + r")(?:-start)?\(")
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\]")


def _shape_bytes(shapes: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shapes):
        if dtype not in _DTYPE_BYTES:
            raise ValueError(f"unknown HLO dtype {dtype!r} in {shapes!r}")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> dict:
    """``{kind: {"ops", "bytes"}}`` of every collective, by output bytes."""
    stats = {k: {"ops": 0, "bytes": 0} for k in COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if m:
            stats[m.group("kind")]["ops"] += 1
            stats[m.group("kind")]["bytes"] += _shape_bytes(m.group("shapes"))
    return stats
