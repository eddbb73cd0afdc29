"""A kernel's share of its roofline from the device trace.

The work is the algorithm's for the cell's shapes (``flops.py``); which
trace events implement it is data: every file in
``benchmark/patterns/<metric>/`` holds one pattern,

    work: <kind of call>
    match: <regular expression over the ops' short names>
    detail: <optional regular expression over the ops' whole HLO lines>

so a PR that swaps a kernel adds a pattern file and the operations and
bytes stay as they are. The share is the least time the chip could take
for the calls seen (the larger of operations / peak FLOP/s and bytes /
peak bytes/s, per call) over the summed device time of their events.
"""

from __future__ import annotations

import re

from . import peaks, trace as trace_mod


def pattern_files(manifest, metric: str):
    """[(work kind, name pattern, detail pattern or None)] of a metric,
    one per file."""
    out = []
    for path in sorted((manifest.bench / "patterns" / metric).glob("*.txt")):
        fields = {k.strip(): v.strip() for k, v in (
            line.split(":", 1) for line in path.read_text().splitlines()
            if ":" in line)}
        out.append((fields["work"], fields["match"], fields.get("detail")))
    if not out:
        raise LookupError(f"{metric}: no pattern file under "
                          f"benchmark/patterns/{metric}/")
    return out


def share(ctx, metric: str, work_of, log=print) -> float:
    """``work_of(kind, event) -> (operations, bytes)`` of one call."""
    peak = peaks.peaks(ctx["device"]["kind"])
    files = pattern_files(ctx["manifest"], metric)
    least = spent = by_ops = by_bytes = 0.0
    calls = {}
    for events in ctx["trace"].ops.values():
        matched = 0
        for kind, regex, detail in files:
            try:
                chosen = trace_mod.select(events, [regex])
            except LookupError:
                continue
            if detail is not None:
                chosen = [e for e in chosen if re.search(detail, e.detail)]
            matched += len(chosen)
            calls[kind] = calls.get(kind, 0) + len(chosen)
            for ev in chosen:
                ops, nbytes = work_of(kind, ev)
                t_ops, t_bytes = ops / peak.bf16_flops, \
                    nbytes / peak.hbm_bytes_per_s
                least += max(t_ops, t_bytes)
                by_ops += t_ops
                by_bytes += t_bytes
                spent += ev.end - ev.start
        if not matched:
            raise LookupError(
                f"{metric}: the patterns {[f[1:] for f in files]} matched "
                f"no device event: the kernel was renamed or left the path")
    log(f"[{metric}] calls {calls}; bound by "
        f"{'operations' if by_ops >= by_bytes else 'bytes'} "
        f"(least time by operations {by_ops * 1e3:.3f} ms, by bytes "
        f"{by_bytes * 1e3:.3f} ms; device time {spent * 1e3:.3f} ms)")
    return 100.0 * least / spent
