"""Set-up as the program's own compile record holds it.

``apex_tpu.profiler.compile_record()`` keeps a span for every trace,
lower and compile-or-cache-read JAX reports (its listeners are the
program's, on from the first ``TrainStep``), nested under the program
span that caused it (``train_init``, ``train_lower``, a ``train_dispatch``
that compiled), on ``time.perf_counter``: the clock of ``ctx["window"]``.
A ``TrainStep`` claims its program, so the record says which spans are
the train step's (``program_of``); this file matches no name of its own.

This is the benchmark's one reader of the record, beside
``builders/common.py``, the other part of the benchmark that imports the
program. A program without the record gives ``None`` everywhere, and its
metrics are left out. Only spans that END before the window's first
dispatch are read; a stage span nested in another (a jitted function
traced inside its caller's trace) is counted once, in the outer one.
"""

from __future__ import annotations

import sys


def _read(ctx):
    """``(profiler module, record, spans before the window, {seq: span})``
    once per run (kept on ``ctx``); None without a record."""
    if "setup.record" not in ctx:
        from apex_tpu import profiler

        ctx["setup.record"] = None
        get = getattr(profiler, "compile_record", None)
        if get is not None:
            record = get()
            spans = record.spans()
            w0 = ctx["window"][0]
            by_seq = {s.seq: s for s in spans}
            ctx["setup.record"] = (
                profiler, record,
                [s for s in spans if s.end is not None and s.end <= w0],
                by_seq)
            _log(ctx["setup.record"], spans)
    return ctx["setup.record"]


def _top(read):
    """Stage spans before the window that no other stage span holds."""
    _, _, before, by_seq = read
    return [s for s in before if s.stage is not None and not (
        s.parent in by_seq and by_seq[s.parent].stage is not None)]


def step_seconds(ctx, stage: str):
    """Seconds of the train step's ``stage`` spans before the window;
    None where there are none."""
    read = _read(ctx)
    if read is None:
        return None
    profiler, record, _, by_seq = read
    got = [s.seconds for s in _top(read) if s.stage == stage
           and record.program_of(s, by_seq) == profiler.TRAIN_STEP_PROGRAM]
    return sum(got) if got else None


def other_programs_seconds(ctx):
    """Seconds of the trace, lower and compile spans of every other
    program before the window; None where there are none."""
    read = _read(ctx)
    if read is None:
        return None
    profiler, record, _, by_seq = read
    got = [s.seconds for s in _top(read)
           if record.program_of(s, by_seq) != profiler.TRAIN_STEP_PROGRAM]
    return sum(got) if got else None


def cache_misses(ctx):
    """Compiles before the window that the persistent cache did not
    hold; None where nothing compiled."""
    read = _read(ctx)
    if read is None:
        return None
    profiler, _, before, _ = read
    compiles = [s for s in before if s.stage == profiler.COMPILE]
    return sum(s.cache != "hit" for s in compiles) if compiles else None


def _log(read, spans) -> None:
    """One stderr line: the record's cost, and what the dispatches that
    compiled did (after a traced run's ahead-of-time compile, the first
    dispatch's spans say whether it traced, lowered or compiled again)."""
    profiler, record, before, _ = read
    cost = record.stats()
    seen = []
    kids_of = {}
    for s in spans:
        if s.stage is not None:
            kids_of.setdefault(s.parent, []).append(s)
    for d in spans:
        if d.name == profiler.TRAIN_DISPATCH and d.stage is None:
            kids = kids_of.get(d.seq, [])
            seen.append(f"step {d.step}: " + ", ".join(
                f"{s.stage} {s.fun_name} {s.seconds:.3f} s"
                + (f" ({s.cache})" if s.cache else "") for s in kids))
    print(f"[setup] compile record: {cost['spans']} spans "
          f"({cost['dropped']} dropped, {len(before)} before the window), "
          f"{cost['callbacks']} listener calls in "
          f"{cost['callback_s'] * 1e3:.1f} ms, {cost['bytes']} bytes held; "
          f"dispatches that compiled: {'; '.join(seen) or 'none'}",
          file=sys.stderr, flush=True)
