"""The phase split on a ten-line HLO snippet and hand-built events."""

import pytest

from benchmark.harness import scopes, trace
from benchmark.harness.manifest import Manifest
from benchmark.harness.trace import Event as E, Trace

HLO = '''\
HloModule jit_fused_step, entry_computation_layout={()->f32[]}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0), metadata={op_name="state.params['w']"}
  ROOT %multiply.9 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(fused_step)/train_fwd_bwd/jvp(M)/h_0/mul"}
}

%branch_1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(fused_step)/optimizer_update/cond/branch_1_fun/adam_update/mul"}
}

ENTRY %main.1 (w: f32[8]) -> (f32[8], f32[8]) {
  %w = f32[8]{0} parameter(0), metadata={op_name="state.params['w']"}
  %fusion.1 = f32[8]{0:T(8)S(1)} fusion(%w), kind=kLoop, calls=%fused_computation.1
  %flash_fwd.3 = (f32[8]{0}, f32[8]{0}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(fused_step)/train_fwd_bwd/transpose(jvp(M))/checkpoint/rematted_computation/h_0/flash_fwd/pallas_call"}
  %flash_bwd.2 = f32[8]{0} custom-call(%flash_fwd.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(fused_step)/train_fwd_bwd/transpose(jvp(M))/checkpoint/h_0/flash_bwd/pallas_call"}
  %fusion.4 = f32[8]{0} fusion(%flash_bwd.2), kind=kLoop, calls=%fc.4, metadata={op_name="jit(fused_step)/train_fwd_bwd/transpose(jvp(lm_loss))/mul"}
  %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, calls=%fc.5, metadata={op_name="jit(fused_step)/train_fwd_bwd/transpose(jvp(amp_scale_loss))/mul"}
  %copy.6 = f32[8]{0} copy(%fusion.5)
  %conditional.8 = f32[8]{0} conditional(%pred, %copy.6, %copy.6), branch_computations={%branch_0, %branch_1}, metadata={op_name="jit(fused_step)/optimizer_update/cond"}
  %copy.9 = f32[8]{0} copy(%conditional.8)
  %add.10 = f32[8]{0} add(%w, %w), metadata={op_name="jit(fused_step)/while/body/add"}
  ROOT %tuple.11 = (f32[8]{0}, f32[8]{0}) tuple(%copy.9, %add.10)
}
'''


@pytest.fixture(scope="module")
def table():
    return scopes.load_table(Manifest())


def test_parser_reads_names_paths_fusions_and_readers():
    prog = scopes.parse_hlo(HLO)
    assert prog.op_name["flash_fwd.3"].endswith("flash_fwd/pallas_call")
    assert prog.op_name["fusion.1"] == "" and prog.op_name["copy.6"] == ""
    # a parameter's op_name is its argument's name, not a path
    assert prog.op_name["w"] == "" and prog.op_name["param_0.1"] == ""
    assert prog.calls["fusion.1"] == "fused_computation.1"
    assert prog.root["fused_computation.1"] == "multiply.9"
    assert prog.members["branch_1"] == ["p", "fusion.7"]
    assert prog.operands["conditional.8"] == ["pred", "copy.6", "copy.6"]
    assert prog.users["fusion.5"] == ["copy.6"]
    assert prog.operands["tuple.11"] == ["copy.9", "add.10"]
    # a tuple shape is parenthesised itself: the operands follow the opcode
    assert prog.operands["flash_bwd.2"] == ["flash_fwd.3"]
    assert prog.users["fusion.1"] == ["flash_fwd.3"]


def test_an_instruction_without_a_path_is_filed_by_its_neighbours():
    prog = scopes.parse_hlo(HLO)
    # a fusion: what its fused computation holds
    assert scopes.path_of(prog, "fusion.1").endswith("jvp(M)/h_0/mul")
    # a copy the compiler put in: its consumer, though its producer
    # (amp_scale_loss) is as near
    assert scopes.path_of(prog, "copy.6").endswith("optimizer_update/cond")
    # nothing downstream has a path (the root tuple): its producer
    assert scopes.path_of(prog, "copy.9").endswith("optimizer_update/cond")
    # a prefetch chain is filed with the op it feeds, not with its source
    chain = scopes.parse_hlo('''
ENTRY %main (w: f32[8]) -> f32[8] {
  %gte.1 = f32[8]{0} get-tuple-element(%w), index=0, metadata={op_name="jit(f)/shard_map"}
  %slice-start.2 = ((f32[8]{0}), f32[4]{0:S(1)}, s32[]{:S(2)}) slice-start(%gte.1), slice={[0:4]}
  %slice-done.2 = f32[4]{0:S(1)} slice-done(%slice-start.2)
  ROOT %fusion.3 = f32[4]{0} fusion(%slice-done.2), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/train_fwd_bwd/jvp(M)/dot"}
}
''')
    assert chain.operands["slice-start.2"] == ["gte.1"]
    assert scopes.path_of(chain, "slice-start.2").endswith("jvp(M)/dot")
    assert scopes.path_of(prog, "no_such_instruction") == ""


def test_first_match_wins_in_the_tables_order(table):
    prog = scopes.parse_hlo(HLO)
    phase = {n: table.phase(scopes.path_of(prog, n)) for n in prog.op_name}
    assert phase["fusion.1"] == "fwd"
    assert phase["flash_fwd.3"] == "recompute"   # before bwd: it has both
    assert phase["flash_bwd.2"] == "bwd"
    assert phase["fusion.4"] == "head"           # before bwd
    assert phase["fusion.5"] == "amp"            # before head, bwd, fwd
    assert phase["fusion.7"] == "optimizer"
    assert phase["add.10"] == scopes.OTHER
    assert table.phase("jit(s)/train_reduce/ddp_allreduce/psum") == \
        "allreduce"
    assert table.phase("jit(s)/optimizer_update/amp_unscale/mul") == \
        "optimizer"
    assert [name for name, _ in table.rows] == [
        "optimizer", "amp", "allreduce", "head", "recompute", "bwd", "fwd"]
    assert not table.scoped(prog.op_name["add.10"])
    assert table.scoped(prog.op_name["flash_bwd.2"])


def test_table_needs_rows_and_a_vocabulary():
    with pytest.raises(ValueError):
        scopes.parse_table("phase: a\nmatch: x\n")
    with pytest.raises(ValueError):
        scopes.parse_table("match: x\nvocabulary: y\n")
    t = scopes.parse_table("phase: a\nmatch: x\n\nphase: b\nmatch: .\n"
                           "vocabulary: x|y\n")
    assert t.phase("x") == "a" and t.phase("z") == "b"


def test_innermost_event_gets_the_instant_and_the_parts_sum_to_the_union():
    events = [E("conditional.8", 0.0, 10.0),     # encloses the next two
              E("fusion.7", 1.0, 4.0),
              E("inner", 2.0, 3.0),              # nested twice
              E("fusion.1", 12.0, 13.0),
              E("late", 12.5, 14.0),             # overlaps without nesting
              E("zero", 20.0, 20.0)]
    got = scopes.innermost(events)
    assert got == pytest.approx([7.0, 2.0, 1.0, 0.5, 1.5, 0.0])
    assert sum(got) == pytest.approx(trace.busy(events))
    assert scopes.innermost([]) == []


def _ctx(hlo, events, steps=2):
    ops = {0: events, 1: [E(e.name, e.start + 100, e.end + 100)
                          for e in events]}
    return {"program": {"hlo": hlo}, "slice_steps": steps,
            "manifest": Manifest(),
            "trace": Trace(ops, {}, [], busy_s=0.0, window_s=0.0)}


EVENTS = [E("fusion.1", 0.0, 1.0), E("flash_fwd.3", 1.0, 3.0),
          E("flash_bwd.2", 3.0, 6.0), E("fusion.4", 6.0, 6.5),
          E("fusion.5", 6.5, 6.75), E("copy.6", 6.75, 7.0),
          E("conditional.8", 7.0, 9.0), E("fusion.7", 7.5, 8.5),
          E("add.10", 9.5, 10.0)]


def test_phases_partition_the_busy_union():
    ctx = _ctx(HLO, EVENTS)
    ms = {p: scopes.phase_ms(ctx, p) for p in (
        "optimizer", "amp", "allreduce", "head", "recompute", "bwd", "fwd",
        "other")}
    assert ms == {"optimizer": pytest.approx(1125.0),   # cond + its copy
                  "amp": pytest.approx(125.0), "allreduce": None,
                  "head": pytest.approx(250.0),
                  "recompute": pytest.approx(1000.0),
                  "bwd": pytest.approx(1500.0), "fwd": pytest.approx(500.0),
                  "other": pytest.approx(250.0)}
    busy = trace.busy(EVENTS)
    assert sum(v for v in ms.values() if v) == pytest.approx(
        1e3 * busy / ctx["slice_steps"])      # no module line: all events
    # only add.10 (scan plumbing) carries no scope of the vocabulary
    assert scopes.unscoped_share(ctx) == pytest.approx(100 * 0.5 / busy)
    assert ctx["step.phases"] is scopes.phases(ctx)      # computed once


def test_a_step_is_a_whole_run_of_the_program():
    M = "jit_fused_step(1)"
    clipped = [E(M, 0.0, 6.0), E(M, 6.0, 16.0), E(M, 16.0, 26.0),
               E(M, 26.0, 36.0)]
    assert scopes.whole_runs(clipped) == clipped[1:]     # the first: tail
    assert scopes.whole_runs(clipped[1:] + [E(M, 36.0, 40.0)]) == clipped[1:]
    assert scopes.whole_runs(clipped[:2]) == clipped[:2]  # cannot tell
    assert scopes.whole_runs([]) == []
    # the clipped run holds an optimizer update and no forward: a split
    # over everything would file 3 updates under 2 "steps"
    ops = [E("fusion.7", 0.0, 2.0)]                       # the clipped tail
    for start in (10.0, 20.0):
        ops += [E(n, start + e.start, start + e.end) for n, e in
                (("fusion.1", E("", 0.0, 1.0)), ("fusion.7", E("", 8.0, 10.0)))]
    runs = [E(M, 0.0, 2.0), E(M, 10.0, 20.0), E(M, 20.0, 30.0)]
    prog, table = scopes.parse_hlo(HLO), scopes.load_table(Manifest())
    whole = scopes.per_step(ops, runs, 2, prog, table)
    assert whole == {"fwd": pytest.approx(1.0),
                     "optimizer": pytest.approx(2.0), scopes.UNSCOPED: 0.0}
    # no module line in the trace: all events over the harness's steps
    assert scopes.per_step(ops, [], 2, prog, table)["optimizer"] == \
        pytest.approx(3.0)
    assert scopes.per_step(ops, [], 0, prog, table) == {}


def test_exposed_all_reduce_is_per_whole_run_and_the_median_chip():
    """Four planes: the capture clips every chip's first run at another
    point, and the clipped tail holds an all-reduce too (it runs after
    the backward); the reader counts the whole runs only and gives the
    median chip's time, in ms a step."""
    M = "jit_fused_step(1)"
    reader = Manifest().module("metrics", "parallel.allreduce_exposed_ms")

    def chip(clip, alone):
        # a clipped run of `clip` s, then two whole runs of 10 s; in each
        # run the all-reduce is exposed for `alone` s and hidden for 1 s
        runs = [E(M, 10.0 - clip, 10.0), E(M, 10.0, 20.0), E(M, 20.0, 30.0)]
        ops = []
        for end in (10.0, 20.0, 30.0):
            ops += [E("fusion.1", end - 9.0, end - 3.0),
                    E("psum.12", end - 4.0, end - 3.0 + alone),
                    E("psum.13", end - 1.0, end - 0.5)]
        return ops, runs

    planes = {i: chip(clip, alone) for i, (clip, alone) in enumerate(
        [(5.0, 2.0), (6.0, 2.0), (4.5, 2.25), (7.0, 1.0)])}
    ctx = {"slice_steps": 2, "trace": Trace(
        {i: ops for i, (ops, _) in planes.items()},
        {i: runs for i, (_, runs) in planes.items()}, [], 0.0, 0.0)}
    # per chip (2.0 + 0.5), (2.0 + 0.5), (2.25 + 0.5), (1.0 + 0.5) s a step
    assert reader.read(ctx) == pytest.approx(2500.0)
    # over everything and the harness's 2 steps it would read 3 runs' worth
    assert 1e3 * trace.exposed(planes[0][0]) / 2 == pytest.approx(3750.0)
    no_collective = {**ctx, "trace": Trace({0: [E("fusion.1", 0.0, 1.0)]},
                                           {0: []}, [], 0.0, 0.0)}
    assert reader.read(no_collective) is None
    assert reader.read({**ctx, "trace": None}) is None


def test_a_program_without_scopes_reads_100_and_none_never_0():
    import re

    bare = re.sub(r'(train_fwd_bwd|optimizer_update|adam_update|'
                  r'amp_scale_loss|lm_loss)', "x", HLO)
    ctx = _ctx(bare, EVENTS)
    assert scopes.unscoped_share(ctx) == pytest.approx(100.0)
    for phase in ("optimizer", "amp", "allreduce", "head", "fwd"):
        assert scopes.phase_ms(ctx, phase) is None
    # JAX's own markers are still there, and everything else is "other"
    assert scopes.phase_ms(ctx, "recompute") == pytest.approx(1000.0)
    total = sum(scopes.phase_ms(ctx, p) or 0.0
                for p in ("recompute", "bwd", "other"))
    assert total == pytest.approx(1e3 * trace.busy(EVENTS) / 2)
    # no compiled text, or no device trace: nothing to read
    for broken in ({**ctx, "program": {}}, {**ctx, "trace": None}):
        broken.pop("step.phases")
        assert scopes.phase_ms(broken, "bwd") is None
        assert scopes.unscoped_share(broken) is None


def test_annotations_read_the_newest_capture(tmp_path):
    assert scopes.annotations(tmp_path, "train_dispatch") == []
    import jax
    import jax.numpy as jnp

    out = tmp_path / "benchmark_out" / "cell" / "trace"
    jax.profiler.start_trace(str(out))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("train_dispatch"):
            jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    spans = scopes.annotations(tmp_path, "train_dispatch")
    assert len(spans) == 3 and all(0 < s < 5 for s in spans)
    assert scopes.annotations(tmp_path, "no_such_annotation") == []
