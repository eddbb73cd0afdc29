"""A kernel's roofline share from hand-built events and pattern files."""

import pytest

from benchmark.harness import roofline
from benchmark.harness.trace import Event, Trace


class _Manifest:
    def __init__(self, bench):
        self.bench = bench


def _ctx(tmp_path, events, files):
    d = tmp_path / "patterns" / "kernels.x_roofline"
    d.mkdir(parents=True)
    for name, text in files.items():
        (d / name).write_text(text)
    return {"manifest": _Manifest(tmp_path),
            "device": {"kind": "TPU v5 lite"},
            "trace": Trace({0: events}, {}, [], 0.0, 0.0)}


def test_share_is_least_time_over_device_time(tmp_path):
    events = [Event("attention.1", 0.0, 2e-3, "%attention.1 = (bf16[2], f32[2])"),
              Event("attention.2", 3e-3, 7e-3, "%attention.2 = (bf16[2], bf16[2], bf16[2])"),
              Event("fusion.9", 7e-3, 9e-3, "")]
    ctx = _ctx(tmp_path, events, {
        "fwd.txt": "work: fwd\nmatch: ^attention\\.\ndetail: f32\\[2\\]\\)$\n",
        "bwd.txt": "work: bwd\nmatch: ^attention\\.\ndetail: bf16\\[2\\]\\)$\n"})
    work = {"fwd": (197e12 * 1e-3, 0), "bwd": (0, 819e9 * 1e-3)}
    lines = []
    got = roofline.share(ctx, "kernels.x_roofline",
                         lambda kind, ev: work[kind], log=lines.append)
    # forward: 1 ms of operations in 2 ms; backward: 1 ms of bytes in 4 ms
    assert got == pytest.approx(100.0 * 2e-3 / 6e-3)
    assert "calls {'bwd': 1, 'fwd': 1}" in lines[0] or \
        "calls {'fwd': 1, 'bwd': 1}" in lines[0]


def test_patterns_that_match_nothing_are_an_error_not_zero(tmp_path):
    ctx = _ctx(tmp_path, [Event("fusion.1", 0.0, 1.0, "")],
               {"a.txt": "work: fwd\nmatch: ^attention\\.\n"})
    with pytest.raises(LookupError, match="matched no device event"):
        roofline.share(ctx, "kernels.x_roofline", lambda k, e: (1, 1))
    with pytest.raises(LookupError, match="no pattern file"):
        roofline.share({**ctx, "manifest": _Manifest(tmp_path / "none")},
                       "kernels.x_roofline", lambda k, e: (1, 1))
