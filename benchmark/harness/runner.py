"""One run of one cell: set-up, the first steps that the comparison
reads, the measured window, the plain reference, the result.

The window drives ``TrainLoop.step`` over the ``TrainStep`` that
``build_train_step`` returns; the very same loop object ran the first
three steps in set-up, through the same call and the same feed.
"""

from __future__ import annotations

import gc
import importlib
import json
import math
import os
import shutil
import statistics
import sys
import time
from pathlib import Path

import numpy as np

from . import check, masks as masks_mod, peaks as peaks_mod, \
    traffic as traffic_mod
from .manifest import Manifest

FIRST_STEPS = 3          # steps the reference follows
TRACED_STEPS = 5         # steps of the traced slice (traced runs only)


def log(msg: str) -> None:
    print(msg, flush=True)


def weights_key(seed: int):
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


class Spans:
    """Host spans around the calls into each layer, on the host clock."""

    def __init__(self):
        self.rows = []                      # (name, start_s, end_s)

    def add(self, name, start, end):
        self.rows.append((name, start, end))

    def total(self, name, lo=-math.inf, hi=math.inf):
        return sum(e - s for n, s, e in self.rows
                   if n == name and s >= lo and e <= hi)

    def count(self, name, lo=-math.inf, hi=math.inf):
        return sum(1 for n, s, e in self.rows
                   if n == name and s >= lo and e <= hi)


class _Span:
    """``with _Span(spans, name, annotate)``: a host span, and in a traced
    run the same span as a ``bench:<name>`` annotation in the profile."""

    def __init__(self, spans, name, annotate):
        self.spans, self.name = spans, name
        self.note = None
        if annotate:
            import jax

            self.note = jax.profiler.TraceAnnotation("bench:" + name)

    def __enter__(self):
        if self.note is not None:
            self.note.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.spans.add(self.name, self.start, time.perf_counter())
        if self.note is not None:
            self.note.__exit__(*exc)
        return False


def _compile_counter():
    """Counts backend compilations from now on (zero inside the window)."""
    import jax

    box = {"n": 0}

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return box


def family(config: dict):
    """(builder module, plain reference module) of a configuration."""
    builder = importlib.import_module(
        f"benchmark.builders.{config['builder']}")
    reference = importlib.import_module(
        f"benchmark.reference.{builder.REFERENCE}")
    return builder, reference


def data_parallel(traffic: dict, chips: int, devices):
    """(mesh, ddp) of a cell on several chips; (None, None) on one."""
    parallel = traffic.get("parallel")
    if not parallel:
        if chips != 1:
            raise ValueError(f"{chips} chips need a 'parallel' entry in "
                             f"the traffic file")
        return None, None
    import jax

    from apex_tpu.parallel import DistributedDataParallel

    mesh = jax.make_mesh((chips,), (parallel["axis"],),
                         devices=devices[:chips])
    return mesh, DistributedDataParallel(
        parallel["axis"], delay_allreduce=parallel["delay_allreduce"])


def _apply_rehearsal(config, traffic):
    config = {**config, **config.get("rehearsal", {})}
    traffic = {**traffic, **traffic.get("rehearsal", {})}
    return config, traffic


def run_cell(manifest: Manifest, cell_name: str, *, seed: int,
             seconds: float, trace: bool, t0: float, devices,
             rehearsal: bool = False, out_dir: Path | None = None,
             sabotage=None) -> dict:
    """Run the cell and return the result object. ``sabotage``, used
    only by the tests, breaks the timed path underneath: it is called as
    ``sabotage("before_build", None)`` and ``sabotage("after_build",
    built)`` (which returns the ``Built`` to go on with); the run must
    then come out not correct."""
    import jax

    cell = manifest.cell(cell_name)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell_name)
    if rehearsal:
        config, traffic = _apply_rehearsal(config, traffic)
    chips = cell["chips"]
    builder, reference = family(config)
    from benchmark.reference import train as reference_train

    compiles = _compile_counter()
    spans = Spans()
    now = time.perf_counter

    # -- set-up ---------------------------------------------------------------
    mesh, ddp = data_parallel(traffic, chips, devices)
    if sabotage is not None:
        sabotage("before_build", None)
    built = builder.build(config, traffic, reference, seed=seed, mesh=mesh,
                          ddp=ddp, key=weights_key(seed))
    if sabotage is not None:
        built = sabotage("after_build", built)
    tr = traffic_mod.Traffic(traffic, config["vocab_size"], seed, chips,
                             feed=built.feed)
    from apex_tpu.train import TrainLoop

    loop = TrainLoop(built.step, built.state, max_retries=0)
    log(f"[setup] {cell_name}: {built.n_params / 1e6:.1f}M parameters, "
        f"{tr.rows} rows x {tr.seq} tokens a step on {chips} chip(s); state "
        f"and traffic ready at {now() - t0:.1f} s")

    program = {}
    if trace:
        # the one program, compiled ahead of the first step (the jitted
        # call then reads it from the persistent cache): memory, HLO
        first = built.place(built.program_batch(_peek(tr)))
        compiled = built.step.lower(loop.state, first).compile()
        mem = compiled.memory_analysis()
        program = {"hlo": compiled.as_text(), "live_bytes": (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)}
        del compiled, first

    first_batches, first_metrics = [], []
    grad_finish = change_tree = None
    for t in range(FIRST_STEPS):
        tb = tr.batch(t)
        first_batches.append(tb)
        m = loop.step(built.place(built.program_batch(tb)))
        if m is not None:
            first_metrics.append(m)
        if t == 0:
            grad_finish = built.grad_norms(loop.state)
    first_metrics.append(loop.drain())
    change_tree = built.change_norms(loop.state, weights_key(seed))
    jax.block_until_ready(change_tree)
    log("[setup] first steps: losses "
        + " ".join(f"{m['loss']:.5f}" for m in first_metrics)
        + f"; skipped {[bool(m['skipped']) for m in first_metrics]}")

    # -- the window -----------------------------------------------------------
    if trace:
        import apex_tpu.train.loop as loop_module

        fetch = loop_module._to_host

        def timed_fetch(x):
            with _Span(spans, "fetch", True):
                return fetch(x)

        loop_module._to_host = timed_fetch
    trace_dir = None
    slice_t = [None, None]
    slice_steps = 0
    compiles_before = compiles["n"]
    done_at, metrics, failed = [], [], 0
    t = FIRST_STEPS
    # the set-up leaves tens of millions of objects behind (the traced
    # program); a full collection over them in mid-window stalls the
    # host for seconds. Collect now and keep them out of later passes.
    gc.collect()
    gc.freeze()
    setup_s = now() - t0
    w0 = now()
    try:
        while True:
            with _Span(spans, "loader.next", trace):
                tb = tr.batch(t)
            with _Span(spans, "place", trace):
                dev = built.place(built.program_batch(tb))
            with _Span(spans, "loop.step", trace):
                m = loop.step(dev)
            e = now()
            t += 1
            if m is not None:
                done_at.append(e)
                metrics.append(m)
            if trace and slice_t[0] is None and e - w0 >= 0.6 * seconds:
                trace_dir = Path(out_dir) / "trace"
                shutil.rmtree(trace_dir, ignore_errors=True)  # keep one
                jax.profiler.start_trace(str(trace_dir))
                slice_t[0], slice_steps = now(), t
            elif slice_t[0] is not None and slice_t[1] is None \
                    and t - slice_steps >= TRACED_STEPS:
                jax.block_until_ready(loop.state)
                slice_t[1] = now()
                with _Span(spans, "trace.stop", False):
                    jax.profiler.stop_trace()
                slice_steps = t - slice_steps
            if now() - w0 >= seconds:
                break
        with _Span(spans, "drain", trace):
            metrics.append(loop.drain())
            jax.block_until_ready(loop.state)
        w1 = now()
        done_at.append(w1)
    finally:
        if trace:
            loop_module._to_host = fetch
            if slice_t[0] is not None and slice_t[1] is None:
                jax.profiler.stop_trace()
        tr.close()
        gc.unfreeze()
    compiled_in_window = compiles["n"] - compiles_before
    steps = t - FIRST_STEPS
    window_s = w1 - w0
    for m in metrics:
        if not math.isfinite(m["loss"]) and not m["skipped"]:
            failed += 1
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:chips])
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": chips, "memory_peak_bytes": peak}
    intervals = np.diff(np.asarray([w0] + done_at))
    log(f"[window] {steps} steps in {window_s:.3f} s; {len(intervals)} "
        f"completion intervals, median {np.median(intervals) * 1e3:.2f} ms, "
        f"longest {intervals.max() * 1e3:.1f} ms; "
        f"compilations inside the window: {compiled_in_window}; steps "
        f"skipped by the scaler: {sum(bool(m['skipped']) for m in metrics)}")

    # -- what the program gave, then free it ----------------------------------
    prog = {"loss": [m["loss"] for m in first_metrics],
            "grad": grad_finish(first_metrics[0]),
            "change": built.to_reference(change_tree)}
    if tr.corpus is not None:      # a loader's rows must be the corpus's
        for tb in first_batches:
            tb["ids"] = tr.corpus[tr.rows_of_corpus(tb["ids"])]
    ref_batches = [built.reference_batch(tb) for tb in first_batches]
    optimizer = built.optimizer
    ctx = {"manifest": manifest, "config": config,
           "traffic": traffic, "chips": chips, "spans": spans,
           "steps": steps, "window": (w0, w1), "slice": tuple(slice_t),
           "slice_steps": slice_steps, "metrics": metrics,
           "program": program, "device": device,
           "tokens_per_step": tr.tokens_per_step}
    del loop, built, change_tree, grad_finish, tr
    gc.collect()

    # -- the plain reference, once the window has closed ------------------------
    r0 = now()
    ref = reference_train.run(
        reference, config, optimizer, weights_key(seed), ref_batches,
        masks_mod)
    verdict = check.compare(prog, ref, traffic["limits"])
    log(f"[reference] {FIRST_STEPS} steps in {now() - r0:.1f} s")
    ok = (verdict["correct"] and failed == 0 and compiled_in_window == 0)

    # -- the result ---------------------------------------------------------------
    values = {}
    if not trace:
        values["tokens_per_s"] = ctx["tokens_per_step"] * steps / window_s
        values["step_ms_p90"] = float(
            statistics.quantiles(intervals * 1e3, n=10)[-1])
        values["setup_s"] = setup_s
        wanted = manifest.end_to_end(cell_name)
    else:
        from . import trace as trace_mod

        if os.environ.get("BENCH_DESCRIBE_TRACE"):
            (Path(out_dir) / "trace_described.txt").write_text(
                trace_mod.describe(trace_dir))
            (Path(out_dir) / "program.hlo.txt").write_text(program["hlo"])
        # off the chip there is no device plane to reduce: the rehearsal
        # reads the spans and counters only
        ctx["trace"] = None if rehearsal else trace_mod.load(trace_dir,
                                                             chips)
        if ctx["trace"] is not None:
            device.update(busy_s=ctx["trace"].busy_s,
                          window_s=ctx["trace"].window_s)
        wanted = manifest.per_layer(cell_name)
        for entry in wanted:
            reader = manifest.module("metrics", entry["name"])
            try:
                value = reader.read(ctx)
            except peaks_mod.UnknownChip as e:
                if not rehearsal:     # on the chip an unknown peak is fatal
                    raise
                log(f"[rehearsal] {entry['name']} not read off the chip: {e}")
                value = None
            if value is not None:
                values[entry["name"]] = float(value)
    result = {
        "correct": bool(ok), "attempted": steps, "failed": failed,
        "metrics": {e["name"]: {"value": values[e["name"]],
                                "unit": e["unit"]}
                    for e in wanted if e["name"] in values},
        "device": device,
    }
    if trace and ctx["trace"] is not None:
        result["breakdown"] = trace_mod.breakdown(ctx["trace"], spans)
    compared = check.brief(verdict["numbers"])
    compared["compilations_in_window"] = {"value": compiled_in_window,
                                          "limit": 0}
    compared["steps_failed"] = {"value": failed, "limit": 0}
    result["compared"] = compared
    for name, rec in verdict["numbers"].items():
        print(f"compared {name}: {json.dumps(rec)}", file=sys.stderr)
    for name in ("compilations_in_window", "steps_failed"):
        print(f"compared {name}: {json.dumps(compared[name])}",
              file=sys.stderr)
    sys.stderr.flush()
    return result


def _peek(tr):
    """Step 0's batch without consuming a loader: shapes only matter."""
    if tr.corpus is None:
        return tr.batch(0)
    return {"ids": tr.corpus[:tr.rows], "seed": tr.dropout_seeds(0)}
