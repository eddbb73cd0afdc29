"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: per chip the device's op events, their busy union, the idle
gaps, the time an all-reduce runs alone, and the events a list of name
patterns selects. Pure functions over plain event tuples, so they are
tested on hand-built events (``benchmark/tests``); only :func:`load`
touches the profile file.

What a v5e trace looks like is written down in ``PERF.md`` (section 3):
one plane ``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` holds one
event per executed HLO op (Pallas kernels appear under their custom-call
names) and whose line ``XLA Modules`` holds one event per program run;
host threads are lines of the ``/host:CPU`` plane, where the benchmark's
own ``bench:*`` annotations land.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
# an all-reduce is named by its HLO op, or `psum` where it comes from
# `lax.psum` inside `shard_map` (the DDP step, seen on four chips)
ALL_REDUCE = re.compile(r"^(all-reduce|psum)")


class Event(NamedTuple):
    name: str
    start: float        # seconds on the trace's clock
    end: float
    detail: str = ""    # a device op's whole HLO line


class Trace(NamedTuple):
    ops: dict           # chip -> [Event] of the XLA Ops line
    modules: dict       # chip -> [Event] of the XLA Modules line
    host: list          # [Event] of the benchmark's bench:* annotations
    busy_s: float       # mean over chips of the busy union
    window_s: float     # first op start to last op end, widest chip


# -- interval arithmetic --------------------------------------------------------

def union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the merged intervals ``a`` that no interval of the merged
    ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(events) -> float:
    return length(union((ev.start, ev.end) for ev in events))


def extent(events):
    return (min(ev.start for ev in events), max(ev.end for ev in events))


def idle_share(events) -> float:
    """1 - busy union / (first start .. last end)."""
    lo, hi = extent(events)
    return 1.0 - busy(events) / (hi - lo)


def gaps(events):
    """Idle ``[(start, end)]`` between the first start and the last end."""
    lo, hi = extent(events)
    return subtract([(lo, hi)], union((ev.start, ev.end) for ev in events))


def exposed(events, pattern=ALL_REDUCE) -> float:
    """Seconds in which an op matching ``pattern`` runs and no other op
    does; None where no op matches (nothing to read is not 0)."""
    mine = union((e.start, e.end) for e in events if pattern.search(e.name))
    if not mine:
        return None
    rest = union((e.start, e.end) for e in events
                 if not pattern.search(e.name))
    return length(subtract(mine, rest))


def select(events, patterns):
    """Events whose name matches any of the regular expressions. A list
    of patterns that selects nothing is an error: the kernel was renamed
    or left the path, and a roofline over no events is no number."""
    regs = [re.compile(p) for p in patterns]
    if not regs:
        raise LookupError("no name pattern given")
    chosen = [e for e in events if any(r.search(e.name) for r in regs)]
    if not chosen:
        raise LookupError(f"patterns {patterns} matched none of the "
                          f"{len(events)} device events")
    return chosen


def label(gap, host):
    """Name of the innermost benchmark span over the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    over = [h for h in host if h.start <= mid <= h.end]
    if not over:
        return "(no benchmark span)"
    return min(over, key=lambda h: h.end - h.start).name


# -- the profile file -----------------------------------------------------------------

def short_name(text: str) -> str:
    """An op event of a TPU trace is named by its whole HLO line,
    ``%attention.189 = (bf16[...], ...) custom-call(...)``: the short
    name is the instruction's, ``attention.189``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _events(line, hlo_names=False):
    out = []
    for ev in line.events:
        s = ev.start_ns * 1e-9
        name = short_name(ev.name) if hlo_names else ev.name
        detail = ev.name if hlo_names else ""
        out.append(Event(name, s, s + ev.duration_ns * 1e-9, detail))
    return out


def newest_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(trace_dir, chips: int) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(newest_xplane(trace_dir)))
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[chip] = _events(line, hlo_names=True)
                elif line.name == MODULES_LINE:
                    modules[chip] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line)
                         if e.name.startswith(SPAN_PREFIX)]
    ops = {c: ev for c, ev in ops.items() if ev}
    if len(ops) < chips:
        raise RuntimeError(f"the trace holds device ops of {len(ops)} "
                           f"chip(s), the cell uses {chips}")
    busy_s = sum(busy(ev) for ev in ops.values()) / len(ops)
    window_s = max(extent(ev)[1] - extent(ev)[0] for ev in ops.values())
    host = [Event(e.name[len(SPAN_PREFIX):], e.start, e.end) for e in host]
    return Trace(ops, modules, host, busy_s, window_s)


def describe(trace_dir, top: int = 25) -> str:
    """What a trace holds, for the first look by hand: planes and lines;
    per device-op line the op families (names without their number) by
    time, with one whole HLO line of every custom call and collective."""
    from collections import Counter
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(newest_xplane(trace_dir)))
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            device = bool(DEVICE_PLANE.match(plane.name))
            if not device and not any(e.name.startswith(SPAN_PREFIX)
                                      for e in evs):
                continue
            total, count, sample = Counter(), Counter(), {}
            for e in evs:
                fam = re.sub(r"\.\d+$", "", short_name(e.name))
                total[fam] += e.duration_ns
                count[fam] += 1
                sample.setdefault(fam, e.name)
            shown = total.most_common(top if not device else 10 ** 6)
            for fam, ns in shown:
                text = sample[fam]
                special = device and ("custom-call" in text
                                      or "all-reduce" in text)
                if device and not special and ns < 0.002 * sum(
                        total.values()):
                    continue
                out.append(f"    {ns * 1e-6:10.3f} ms x{count[fam]:<6} "
                           f"{fam!r}")
                if special:
                    out.append(f"        {text[:1200]}")
    return "\n".join(out)


def breakdown(trace: Trace, spans=None) -> dict:
    """The ten families of device ops (an op's name without its number:
    ``fusion``, ``attention``, ``conditional``) that took most time, in
    mean seconds per chip over the traced slice, and the ten longest idle
    gaps of the idlest chip, each named by the benchmark span that covers
    it. (``conditional`` is the optimizer's update branch and holds the
    ops inside it, which are listed as well.)"""
    total = {}
    for events in trace.ops.values():
        for e in events:
            fam = re.sub(r"\.\d+$", "", e.name)
            total[fam] = total.get(fam, 0.0) + (e.end - e.start)
    n = len(trace.ops)
    device_ops = sorted(([k, v / n] for k, v in total.items()),
                        key=lambda kv: -kv[1])[:10]
    worst = max(trace.ops.values(), key=idle_share)
    longest = sorted(gaps(worst), key=lambda g: g[0] - g[1])[:10]
    idle = {}
    for g in longest:
        name = label(g, trace.host)
        idle[name] = idle.get(name, 0.0) + (g[1] - g[0])
    idle_gaps = sorted(([k, v] for k, v in idle.items()),
                       key=lambda kv: -kv[1])
    return {"device_ops": device_ops, "idle_gaps": idle_gaps}
