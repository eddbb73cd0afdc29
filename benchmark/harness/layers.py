"""Device time of single layers from a traced slice: the events whose
``op_name`` carries one of the program's LAYER scopes (``ssm_scan``,
``moe_experts``, ...) or the flax path of one kind of block
(``layers_<i>``), by the phase readers' rules (``scopes.py``): each
instant to the innermost event, over the capture's whole runs, a step =
a whole run.

A layer scope holds many device events per call (a scan is a dozen
fusions). For a share of a roofline they are folded into ONE event per
call, named ``<scope>:<pass>`` (``fwd``, ``recompute``, ``bwd``), which
``roofline.share`` then matches against the metric's pattern files: a
kernel that later replaces the fusions under the same scope is read
without a new pattern.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from . import scopes, trace as trace_mod

_LAYER = re.compile(r"(?:^|[/(])layers_(\d+)(?:[/)]|$)")
_REMAT = re.compile(r"(^|/)rematted_computation(/|$)")
_BWD = re.compile(r"(^|/)transpose\(")


def scope_regex(scope: str) -> "re.Pattern":
    return re.compile(r"(^|[/(])" + scope + r"([/)]|$)")


def blocks_regex(pattern: str, letter: str):
    """Matches the flax path ``layers_<i>`` of the blocks of one kind: the
    positions of ``letter`` in the family's stack ``pattern`` (its count
    file says what the pattern is); None where it has none."""
    which = [str(i) for i, k in enumerate(pattern) if k == letter]
    if not which:
        return None
    return re.compile(r"(^|[/(])layers_(" + "|".join(which) + r")([/)]|$)")


def pass_of(path: str) -> str:
    if _REMAT.search(path):
        return "recompute"
    return "bwd" if _BWD.search(path) else "fwd"


def placed(ctx):
    """Per chip ``([(path, seconds as innermost event, run index)], whole
    runs)`` of the device events inside the capture's whole runs; kept on
    ``ctx``. None without a device trace or the compiled text."""
    if "layers.placed" not in ctx:
        text, trace = ctx["program"].get("hlo"), ctx.get("trace")
        if trace is None or not text:
            ctx["layers.placed"] = None
            return None
        program = scopes.parse_hlo(text)
        paths, out = {}, {}
        for chip, events in trace.ops.items():
            modules = trace.modules.get(chip, [])
            events, runs = scopes.in_whole_runs(events, modules,
                                                ctx["slice_steps"])
            starts = sorted(r.start for r in scopes.whole_runs(modules))
            rows = []
            for ev, seconds in zip(events, scopes.innermost(events)):
                if not seconds:
                    continue
                if ev.name not in paths:
                    paths[ev.name] = scopes.path_of(program, ev.name)
                run = bisect.bisect_right(starts, ev.start + 1e-6)
                rows.append((paths[ev.name], seconds, run))
            out[chip] = (rows, runs)
        ctx["layers.placed"] = out
    return ctx["layers.placed"]


def ms_a_step(ctx, regex):
    """Device ms a step of the events whose path matches, mean over
    chips; None where nothing matches (nothing to read is not 0)."""
    chips = placed(ctx)
    if not chips or regex is None:
        return None
    total = 0.0
    for rows, runs in chips.values():
        if runs:
            total += sum(s for p, s, _ in rows if regex.search(p)) / runs
    return 1e3 * total / len(chips) if total else None


def calls_as_events(ctx, scope: str):
    """``ctx``'s copy whose trace holds, per chip, one event per call of
    ``scope``: (run, block, pass) -> ``Event("<scope>:<pass>", 0, device
    seconds of the call)``. None where the scope ran nowhere."""
    chips = placed(ctx)
    if not chips:
        return None
    regex = scope_regex(scope)
    ops = {}
    for chip, (rows, _) in chips.items():
        calls = defaultdict(float)
        for path, seconds, run in rows:
            if regex.search(path):
                block = _LAYER.search(path)
                calls[(run, block.group(1) if block else "",
                       pass_of(path))] += seconds
        ops[chip] = [trace_mod.Event(f"{scope}:{key[2]}", 0.0, seconds)
                     for key, seconds in sorted(calls.items())]
    if not any(ops.values()):
        return None
    return {**ctx, "trace": ctx["trace"]._replace(ops=ops)}


def counter(ctx, name: str):
    """Mean over the window's steps of a counter the step reports beside
    its loss (``metrics["aux"][name]``); None where no step has it."""
    values = [float(m["aux"][name]) for m in ctx["metrics"]
              if isinstance(m.get("aux"), dict) and name in m["aux"]]
    return sum(values) / len(values) if values else None
