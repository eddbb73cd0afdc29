"""A traced slice's device time split by the phase of the train step.

The program names its phases from inside (``jax.named_scope`` in the
train step, amp, DDP, the optimizers and the models' heads; JAX's own
``transpose(`` and ``rematted_computation`` markers for backward and
recomputed forward): every instruction of the compiled HLO carries them
in its ``metadata={op_name="jit(step)/<scopes>/<module path>/<primitive>"}``.
A device event of the trace is named by its HLO instruction, so

    event -> instruction name -> op_name -> phase

needs nothing but the compiled text (``ctx["program"]["hlo"]``), the
device events (``ctx["trace"].ops``) and an ORDERED table kept as data,
``benchmark/patterns/step.phases/phases.txt``: ``phase:`` / ``match:``
pairs, a regular expression over ``op_name``, first match wins; what no
row claims is phase ``other``; the ``vocabulary:`` line is the
expression that says an op carries a scope of the program's vocabulary
at all. The table is the benchmark's own copy: it imports nothing from
``apex_tpu``.

Instructions the compiler put in carry no ``op_name`` (layout copies,
prefetches into fast memory, ``bitcast`` fusions). They are filed where
the work they serve is: a fusion under what its fused computation holds,
anything else under the nearest instruction downstream that has an
``op_name`` (a prefetch serves its consumer), failing that upstream.

Each instant of the busy union goes to the INNERMOST event covering it
(a ``conditional`` or a ``while`` encloses the ops inside it), so the
phases are a partition of the busy time. "A step" is a whole run of the
program in the capture (an event of the ``XLA Modules`` line): the
capture starts in mid-step, so its first run is clipped and holds a
step's tail only; the split is taken over the whole runs and divided by
their number, and the phases sum to the busy time of one step.

Pure functions over text and plain event tuples, tested on hand-built
events and a ten-line HLO snippet (``benchmark/tests/test_scopes.py``);
only :func:`annotations` touches a profile file.
"""

from __future__ import annotations

import math
import re
import statistics
from collections import Counter, deque
from pathlib import Path
from typing import NamedTuple

OTHER = "other"           # the phase of what no row of the table claims
UNSCOPED = "(unscoped)"   # key of the time no vocabulary scope covers
_INSTRUCTION = re.compile(
    r"^\s+(?P<root>ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<rest>.*)$")
_COMPUTATION = re.compile(
    r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


# -- compiled text -> instruction -> op_name -------------------------------------

class Program(NamedTuple):
    op_name: dict        # instruction -> its own op_name ("" where none)
    calls: dict          # fusion instruction -> its fused computation
    members: dict        # computation -> [instruction], in the text's order
    root: dict           # computation -> its ROOT instruction
    operands: dict       # instruction -> [instruction] it reads
    users: dict          # instruction -> [instruction] that read it


def _balanced(text: str, start: int) -> int:
    """Index just past the parenthesis that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if not depth:
                return i + 1
    return len(text)


def _arguments(rest: str) -> str:
    """The operand list of ``<shape> <opcode>(<operands>)<attributes>``;
    a tuple shape is parenthesised itself."""
    after_shape = _balanced(rest, 0) if rest.startswith("(") else 0
    opens = rest.find("(", rest.find(" ", after_shape))
    return rest[opens:_balanced(rest, opens)] if opens >= 0 else ""


def parse_hlo(text: str) -> Program:
    """Instruction names of a compiled module's text with their
    ``op_name``, their fused computation, and who reads whom."""
    prog = Program({}, {}, {}, {}, {}, {})
    comp = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            m = _COMPUTATION.match(line)
            if m is not None:
                comp = m.group("name")
                prog.members[comp] = []
            continue
        name, rest = m.group("name"), m.group("rest")
        # a parameter's "op_name" is its argument's name, not a path
        found = None if " parameter(" in rest else _OP_NAME.search(rest)
        prog.op_name[name] = found.group(1) if found else ""
        called = _CALLS.search(rest)
        if called:
            prog.calls[name] = called.group(1)
        reads = [r for r in _OPERAND.findall(_arguments(rest)) if r != name]
        prog.operands[name] = reads
        for r in reads:
            prog.users.setdefault(r, []).append(name)
        if comp is not None:
            prog.members[comp].append(name)
            if m.group("root"):
                prog.root[comp] = name
    return prog


def _own(program: Program, name: str) -> str:
    """An instruction's own ``op_name``; for a fusion without one, its
    root's, failing that the commonest inside its fused computation."""
    own = program.op_name.get(name, "")
    comp = program.calls.get(name)
    if own or comp is None:
        return own
    at_root = program.op_name.get(program.root.get(comp), "")
    if at_root:
        return at_root
    named = Counter(p for p in (program.op_name.get(i, "") for i in
                                program.members.get(comp, [])) if p)
    return named.most_common(1)[0][0] if named else ""


def path_of(program: Program, name: str) -> str:
    """The ``op_name`` an instruction is filed under: its own (or its
    fused computation's); else that of the nearest instruction
    downstream that has one (a prefetch serves its consumer), breadth
    first; else of the nearest upstream; else ``""``."""
    for links in (program.users, program.operands):
        queue, seen = deque([name]), {name}
        while queue:
            cur = queue.popleft()
            found = _own(program, cur)
            if found:
                return found
            for other in links.get(cur, []):
                if other not in seen:
                    seen.add(other)
                    queue.append(other)
    return ""


# -- the phase table -------------------------------------------------------------

class Table(NamedTuple):
    rows: list           # [(phase, compiled regex)], in the file's order
    vocabulary: "re.Pattern"

    def phase(self, path: str) -> str:
        for name, regex in self.rows:
            if regex.search(path):
                return name
        return OTHER

    def scoped(self, path: str) -> bool:
        return bool(path) and bool(self.vocabulary.search(path))


def parse_table(text: str) -> Table:
    rows, pending, vocabulary = [], None, None
    for line in text.splitlines():
        key, _, value = (s.strip() for s in line.partition(":"))
        if key == "phase":
            pending = value
        elif key == "match":
            if pending is None:
                raise ValueError("phase table: 'match:' before any 'phase:'")
            rows.append((pending, re.compile(value)))
            pending = None
        elif key == "vocabulary":
            vocabulary = re.compile(value)
    if not rows or vocabulary is None:
        raise ValueError("phase table: needs 'phase:'/'match:' rows and "
                         "one 'vocabulary:' line")
    return Table(rows, vocabulary)


def load_table(manifest) -> Table:
    path = manifest.bench / "patterns" / "step.phases" / "phases.txt"
    return parse_table(path.read_text())


# -- device events -> seconds per phase ------------------------------------------

def innermost(events) -> list:
    """Seconds of each event in which it is the innermost one running:
    every instant of the busy union goes to the covering event that
    started last. The list sums to the busy union."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    got = [0.0] * len(events)
    stack, cursor = [], -math.inf

    def close(until):
        nonlocal cursor
        while stack and events[stack[-1]].end <= until:
            top = stack.pop()
            if events[top].end > cursor:
                got[top] += events[top].end - cursor
                cursor = events[top].end

    for i in order:
        start = events[i].start
        close(start)
        if stack and start > cursor:
            got[stack[-1]] += start - cursor
        cursor = max(cursor, start)
        stack.append(i)
    close(math.inf)
    return got


def split(events, program: Program, table: Table) -> dict:
    """``{phase: seconds}`` of one chip's events, plus ``UNSCOPED``: the
    seconds of events filed under no scope of the vocabulary. The phases
    sum to the busy union."""
    out, known = {UNSCOPED: 0.0}, {}
    for event, seconds in zip(events, innermost(events)):
        if not seconds:
            continue
        if event.name not in known:
            path = path_of(program, event.name)
            known[event.name] = (table.phase(path), table.scoped(path))
        phase, scoped = known[event.name]
        out[phase] = out.get(phase, 0.0) + seconds
        if not scoped:
            out[UNSCOPED] += seconds
    return out


def whole_runs(modules) -> list:
    """The runs of the program (events of the ``XLA Modules`` line) that
    the capture holds from start to end. A capture that starts in
    mid-step clips its first run, one that stops early its last: a
    clipped run is shorter than the median run, and it holds the step's
    tail only (backward, optimizer), so it is left out of a per-step
    split. Fewer than three runs cannot be told apart and are kept."""
    runs = sorted(modules, key=lambda e: e.start)
    if len(runs) < 3:
        return runs
    median = statistics.median(e.end - e.start for e in runs)
    if runs[0].end - runs[0].start < 0.99 * median:
        runs = runs[1:]
    if runs[-1].end - runs[-1].start < 0.99 * median:
        runs = runs[:-1]
    return runs


def in_whole_runs(events, modules, steps):
    """(the events inside the capture's whole runs, their number); where
    the trace has no module line, all events and ``steps``."""
    runs = whole_runs(modules)
    if not runs:
        return events, steps
    slack = 1e-6
    return [e for e in events
            if any(r.start - slack <= e.start and e.end <= r.end + slack
                   for r in runs)], len(runs)


def per_step(events, modules, steps, program: Program, table: Table):
    """:func:`split` of one chip in seconds a step: over the events inside
    the capture's whole runs and their number."""
    events, steps = in_whole_runs(events, modules, steps)
    if not steps:
        return {}
    return {k: v / steps for k, v in split(events, program, table).items()}


def phases(ctx) -> dict:
    """``{phase: seconds a step}``, mean over chips of :func:`per_step`,
    once per run (kept on ``ctx``); None where there is no device trace
    or no compiled text."""
    if "step.phases" not in ctx:
        text = ctx["program"].get("hlo")
        trace = ctx.get("trace")
        if trace is None or not text:
            ctx["step.phases"] = None
        else:
            program, table = parse_hlo(text), load_table(ctx["manifest"])
            total = Counter()
            for chip, events in trace.ops.items():
                total.update(per_step(events, trace.modules.get(chip, []),
                                      ctx["slice_steps"], program, table))
            ctx["step.phases"] = {k: v / len(trace.ops)
                                  for k, v in total.items()}
    return ctx["step.phases"]


def phase_ms(ctx, phase: str):
    """Device time of a phase in ms a step; None where nothing ran under
    it (nothing to read is not 0)."""
    seconds = phases(ctx)
    if not seconds or not seconds.get(phase):
        return None
    return 1e3 * seconds[phase]


def unscoped_share(ctx):
    """Share (%) of the busy time filed under no scope of the
    vocabulary: 100 for a program that names nothing."""
    seconds = phases(ctx)
    if not seconds:
        return None
    busy = sum(v for k, v in seconds.items() if k != UNSCOPED)
    return 100.0 * seconds[UNSCOPED] / busy if busy else None


# -- the program's own host annotations ----------------------------------------------

def annotations(root, name: str):
    """[seconds] of the program's ``name`` host annotations in the newest
    ``.xplane.pb`` under ``<root>/benchmark_out/*/trace`` (the capture
    this process just wrote); ``[]`` where there is no capture."""
    from jax.profiler import ProfileData

    from .trace import newest_xplane

    found = []
    for trace_dir in Path(root, "benchmark_out").glob("*/trace"):
        try:
            found.append(newest_xplane(trace_dir))
        except FileNotFoundError:
            pass
    if not found:
        return []
    newest = max(found, key=lambda p: p.stat().st_mtime)
    data = ProfileData.from_file(str(newest))
    return [ev.duration_ns * 1e-9
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == name]
