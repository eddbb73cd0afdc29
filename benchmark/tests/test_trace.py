"""The trace reduction on hand-built events."""

import math

import pytest

from benchmark.harness import trace
from benchmark.harness.trace import Event, Trace

E = Event


def test_union_and_subtract():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (5, 5)]) == [(0, 2), (3, 4)]
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6)]) == [
        (0, 1), (2, 4), (6, 10)]
    assert trace.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert trace.subtract([(0, 2)], []) == [(0, 2)]


def test_busy_union_counts_overlap_once_and_idle_share():
    events = [E("a", 0.0, 2.0), E("b", 1.0, 3.0), E("c", 5.0, 6.0),
              E("nested", 5.2, 5.4)]
    assert trace.busy(events) == pytest.approx(4.0)
    assert trace.idle_share(events) == pytest.approx(1 - 4.0 / 6.0)
    assert trace.gaps(events) == [(3.0, 5.0)]


def test_exposed_all_reduce_is_only_the_part_nothing_else_covers():
    events = [E("fusion.1", 0.0, 4.0),
              E("all-reduce.7", 3.0, 7.0),        # 3..4 hidden, 4..7 alone
              E("fusion.2", 6.0, 6.5),            # hides half a second
              E("all-reduce-start.2", 9.0, 10.0)]
    assert trace.exposed(events) == pytest.approx(3.0 - 0.5 + 1.0)
    # inside shard_map the op is named after lax.psum
    assert trace.exposed([E("fusion.1", 0.0, 1.0), E("psum.3", 0.5, 2.0)]
                         ) == pytest.approx(1.0)
    assert trace.exposed([E("fusion.1", 0.0, 1.0)]) is None


def test_select_by_pattern_and_nothing_matched_raises():
    events = [E("ln_fwd.3", 0, 1), E("ln_bwd.3", 1, 2), E("fusion", 2, 3)]
    assert [e.name for e in trace.select(events, [r"^ln_fwd"])] == ["ln_fwd.3"]
    assert len(trace.select(events, [r"ln_fwd", r"ln_bwd"])) == 2
    with pytest.raises(LookupError, match="matched none"):
        trace.select(events, [r"flash"])
    with pytest.raises(LookupError):
        trace.select(events, [])


def test_gap_is_named_by_the_innermost_benchmark_span():
    host = [E("loop.step", 0.0, 10.0), E("fetch", 4.0, 6.0),
            E("loader.next", 10.0, 11.0)]
    assert trace.label((4.5, 5.5), host) == "fetch"
    assert trace.label((1.0, 2.0), host) == "loop.step"
    assert trace.label((20.0, 21.0), host) == "(no benchmark span)"


def test_breakdown_lists_top_ops_and_labelled_gaps():
    ops = {0: [E("big", 0.0, 5.0), E("small", 6.0, 6.5), E("big", 8.0, 9.0)],
           1: [E("big", 0.0, 6.0), E("small", 6.0, 9.0)]}
    t = Trace(ops, {}, [E("fetch", 5.0, 6.0), E("drain", 6.5, 8.0)],
              busy_s=0.0, window_s=9.0)
    out = trace.breakdown(t)
    assert out["device_ops"][0] == ["big", pytest.approx(6.0)]  # by family
    assert out["device_ops"][1] == ["small", pytest.approx(1.75)]
    assert dict(map(tuple, out["idle_gaps"])) == {
        "drain": pytest.approx(1.5), "fetch": pytest.approx(1.0)}
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert all(math.isfinite(v) for _, v in out["device_ops"])
