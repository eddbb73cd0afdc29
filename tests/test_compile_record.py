"""apex_tpu.profiler's compile record: set-up under the program's own spans.

JAX reports every trace, lowering and compile-or-cache-read of a program;
the record files each under the span that caused it (the train step's
``train_init`` / ``train_lower`` / ``train_dispatch``, or the stage span it
nests in), says which spans are the train step's, and keeps nothing for a
dispatch that compiled nothing. The benchmark's ``setup.*`` readers read
it (``benchmark/harness/setup_spans.py``).
"""

import json
import os
import re
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import profiler
from apex_tpu.observability import Observability
from apex_tpu.optimizers import FusedAdam
from apex_tpu.train import TrainLoop, build_train_step

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def record():
    """A fresh process record for the test (the process's own one may be
    full of other tests' spans); the old one is put back after."""
    old = profiler._RECORD
    if old is not None:
        old.uninstall()
    profiler._RECORD = None
    fresh = profiler.compile_record()
    yield fresh
    fresh.uninstall()
    profiler._RECORD = old
    if old is not None:
        old.install()


def _loss_fn(p, mb):
    return jnp.mean((mb["x"] @ p["w"] - mb["y"]) ** 2)


def _batch(rng, rows=3):
    return {"x": jnp.asarray(rng.randn(1, rows, 4), jnp.float32),
            "y": jnp.asarray(rng.randn(1, rows, 2), jnp.float32)}


def _step_and_state(donate=True):
    step = build_train_step(_loss_fn, FusedAdam(lr=1e-2), donate=donate)
    return step, step.init({"w": jnp.ones((4, 2), jnp.float32)})


def _top(spans):
    """Stage spans that no stage span holds."""
    by = {s.seq: s for s in spans}
    return [s for s in spans if s.stage is not None
            and not (s.parent in by and by[s.parent].stage is not None)]


def test_step_spans_nest_under_the_loops_first_dispatch(record):
    t0 = time.perf_counter()
    step, state = _step_and_state()
    rng = np.random.RandomState(0)
    loop = TrainLoop(step, state)
    for _ in range(3):
        loop.step(_batch(rng))
    loop.drain()
    t1 = time.perf_counter()
    spans = record.spans()
    by = {s.seq: s for s in spans}
    mine = [s for s in _top(spans)
            if record.program_of(s, by) == profiler.TRAIN_STEP_PROGRAM]
    assert [s.stage for s in mine] == list(profiler.STAGES)
    assert [s.fun_name for s in mine] == ["fused_step", "jit(fused_step)",
                                          "jit(fused_step)"]
    dispatch = by[mine[0].parent]
    assert dispatch.name == profiler.TRAIN_DISPATCH and dispatch.step == 1
    for s in mine:
        assert s.parent == dispatch.seq and s.step == 1
        assert t0 <= dispatch.start <= s.start <= s.end <= dispatch.end <= t1
    assert mine[2].cache in ("hit", "miss", "off")
    # the jitted helpers traced inside the step's trace nest in it, and
    # are counted once, under it
    nested = [s for s in spans if s.parent == mine[0].seq]
    assert nested and all(s.stage == profiler.TRACE and
                          mine[0].start <= s.start <= s.end <= mine[0].end
                          for s in nested)
    inits = [s for s in spans if s.name == profiler.TRAIN_INIT]
    assert len(inits) == 1 and inits[0].end is not None
    # only the first of the three dispatches compiled anything
    assert [s.step for s in spans if s.name == profiler.TRAIN_DISPATCH] == [1]
    assert loop.stats()["step_compiles"] == {1: 1}


def test_lower_is_a_program_span_and_the_first_dispatch_reuses_it(record):
    step, state = _step_and_state()
    batch = _batch(np.random.RandomState(1))
    step.lower(state, batch).compile()
    spans = record.spans()
    by = {s.seq: s for s in spans}
    lower = [s for s in spans if s.name == profiler.TRAIN_LOWER]
    assert len(lower) == 1
    under = [s.stage for s in _top(spans) if s.parent == lower[0].seq]
    assert under == [profiler.TRACE, profiler.LOWER]
    compiled = [s for s in _top(spans) if s.stage == profiler.COMPILE
                and record.program_of(s, by) == profiler.TRAIN_STEP_PROGRAM]
    assert len(compiled) == 1 and compiled[0].parent is None
    loop = TrainLoop(step, state)
    loop.step(batch)
    loop.drain()
    # the jitted call finds the ahead-of-time program: no second compile
    assert loop.stats()["step_compiles"] == {}


def test_steady_steps_add_nothing_to_the_record(record):
    step, state = _step_and_state()
    rng = np.random.RandomState(2)
    batches = [_batch(rng) for _ in range(53)]
    loop = TrainLoop(step, state)
    for b in batches[:3]:
        loop.step(b)
    n, calls = len(record.spans()), record.callbacks
    for b in batches[3:]:
        loop.step(b)
    loop.drain()
    assert len(record.spans()) == n
    assert record.callbacks == calls
    assert loop.stats()["step_compiles"] == {1: 1}


def test_a_new_batch_shape_is_a_compile_at_its_loop_step(record):
    obs = Observability()
    step, state = _step_and_state()
    rng = np.random.RandomState(3)
    loop = TrainLoop(step, state, obs=obs)
    for _ in range(4):
        loop.step(_batch(rng))
    loop.step(_batch(rng, rows=5))         # step 5: another shape
    loop.step(_batch(rng, rows=5))
    loop.drain()
    assert loop.stats()["step_compiles"] == {1: 1, 5: 1}
    values = obs.metrics.as_dict()
    assert values["train_compiles_total"] == 2
    assert values["train_cache_misses_total"] <= 2
    for name in ("train_trace_s", "train_lower_s", "train_compile_s"):
        assert values[name]["count"] == 2, name
    events = [e for e in obs.recorder.dump()["events"]
              if e["kind"] == "compile"]
    assert [(e["step"], e["fun_name"], e["program"]) for e in events] == [
        (1, "jit(fused_step)", profiler.TRAIN_STEP_PROGRAM),
        (5, "jit(fused_step)", profiler.TRAIN_STEP_PROGRAM)]
    assert all(e["seconds"] > 0 and e["cache"] for e in events)
    dispatches = [s for s in record.spans()
                  if s.name == profiler.TRAIN_DISPATCH]
    assert [s.step for s in dispatches] == [1, 5]


def test_nested_jits_name_their_callers_trace(record):
    @jax.jit
    def inner_of_record_test(x):
        return x * 2

    @jax.jit
    def outer_of_record_test(x):
        return inner_of_record_test(x) + 1

    outer_of_record_test(jnp.arange(3.0))
    spans = record.spans()
    by_name = {s.fun_name: s for s in spans if s.stage == profiler.TRACE}
    outer = by_name["outer_of_record_test"]
    inner = by_name["inner_of_record_test"]
    assert inner.parent == outer.seq and outer.parent is None
    assert outer.start <= inner.start <= inner.end <= outer.end
    lowered = [s for s in spans if s.stage == profiler.LOWER
               and s.fun_name == "jit(outer_of_record_test)"]
    assert len(lowered) == 1 and lowered[0].parent is None


def test_the_record_is_bounded(record, monkeypatch):
    monkeypatch.setattr(record, "CAPACITY", 4)
    fns = []
    for i in range(3):
        def f(x, i=i):
            return x + i
        f.__name__ = f"bounded_{i}"
        fns.append(jax.jit(f))
    for f in fns:
        f(jnp.ones(2))
    assert len(record.spans()) == 4
    assert record.dropped >= 5
    stats = record.stats()
    assert stats["spans"] == 4 and stats["bytes"] > 0
    assert stats["callbacks"] > 0 and stats["callback_s"] > 0


def _compiled_text(batch):
    step, state = _step_and_state(donate=False)
    return step.lower(state, batch).compile().as_text()


def test_the_compiled_step_is_the_same_with_and_without_listeners(record):
    """Tracing puts nothing into the program: the step compiled with the
    listeners on is the step compiled with them off, text and metadata."""
    batch = _batch(np.random.RandomState(4))
    texts, added = [], []
    for listening in (True, False):     # one call site: one source line
        if not listening:
            record.uninstall()
        n = len(record.spans())
        texts.append(_compiled_text(batch))
        added.append([s.name for s in record.spans()[n:]])
    assert profiler.COMPILE in added[0]
    # no stage span without them: the two program spans alone
    assert added[1] == [profiler.TRAIN_INIT, profiler.TRAIN_LOWER]
    assert texts[0] == texts[1]


_CACHE_CHILD = textwrap.dedent("""
    import json, jax, jax.numpy as jnp
    from apex_tpu import profiler
    record = profiler.compile_record()
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(8.0)).block_until_ready()
    print(json.dumps([[s.fun_name, s.cache, s.retrieval_s is not None]
                      for s in record.spans() if s.stage == "compile"]))
""")


def test_persistent_cache_miss_then_hit(tmp_path):
    """Two processes over one fresh cache directory: the first compiles
    and writes (a miss), the second reads (a hit, with its read time)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("XLA_FLAGS", None)
    seen = []
    for _ in range(2):
        done = subprocess.run([sys.executable, "-c", _CACHE_CHILD], env=env,
                              capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        seen.append({name: (cache, read) for name, cache, read in
                     json.loads(done.stdout.splitlines()[-1])})
    assert seen[0]["jit(<lambda>)"] == ("miss", False)
    assert seen[1]["jit(<lambda>)"] == ("hit", True)


# -- the benchmark's readers ------------------------------------------------------

def _setup_spans():
    sys.path.insert(0, str(ROOT))
    from benchmark.harness import setup_spans
    return setup_spans


def test_readers_read_the_set_up_before_the_window(record):
    setup_spans = _setup_spans()
    jax.jit(lambda x: x + 5)(jnp.ones(3))     # another program
    step, state = _step_and_state()
    loop = TrainLoop(step, state)
    loop.step(_batch(np.random.RandomState(5)))
    loop.drain()
    ctx = {"window": (time.perf_counter(), None)}
    got = {stage: setup_spans.step_seconds(ctx, stage)
           for stage in profiler.STAGES}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert setup_spans.other_programs_seconds(ctx) > 0
    assert setup_spans.cache_misses(ctx) >= 0
    # nothing ended before a window that opened first: nothing to read
    early = {"window": (0.0, None)}
    assert setup_spans.step_seconds(early, profiler.TRACE) is None
    assert setup_spans.other_programs_seconds(early) is None
    assert setup_spans.cache_misses(early) is None


def test_readers_give_none_for_a_program_without_the_record(monkeypatch):
    setup_spans = _setup_spans()
    monkeypatch.delattr(profiler, "compile_record")
    ctx = {"window": (time.perf_counter(), None)}
    assert setup_spans.step_seconds(ctx, "trace") is None
    assert setup_spans.other_programs_seconds(ctx) is None
    assert setup_spans.cache_misses(ctx) is None


def test_the_rehearsal_reports_the_setup_metrics():
    """``benchmark/run.py --rehearse`` of a cell, traced, in a child
    process: every ``setup.*`` metric is read (a reader's None would
    leave it out), and the record's line is on stderr."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         "bert_large.phase2", "--seed", "3800000001", "--seconds", "1",
         "--trace", "1", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    shape = json.loads(done.stdout.split(
        "REHEARSAL on the CPU, not a result: ")[1].splitlines()[0])
    assert shape["correct"] is True
    assert {"setup.step_trace_s", "setup.step_lower_s",
            "setup.step_compile_s", "setup.other_programs_s",
            "setup.cache_misses"} <= set(shape["metrics"])
    assert "[setup] compile record:" in done.stderr + done.stdout


def test_chip_smoke_prints_the_records_split(record, capsys):
    """``chip_smoke.py``'s train phase times its ahead-of-time compile by
    the record (trace, lower, compile and the cache's outcome), not by a
    timer of its own."""
    sys.path.insert(0, str(ROOT))
    import chip_smoke
    from apex_tpu.models import BertConfig

    chip_smoke.phase_train(BertConfig.tiny(dtype=jnp.bfloat16,
                                           fused_kernels=True),
                           batch=4, seq=32, n_pred=4, expect_kernels=False)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if "by the compile record" in ln]
    assert len(line) == 1
    got = {k: float(v) for k, v in re.findall(
        r"(trace|lower|compile) (\d+\.\d) s", line[0])}
    assert set(got) == set(profiler.STAGES) and got["trace"] > 0
    assert "persistent cache: " in line[0]
