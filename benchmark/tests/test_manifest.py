"""A configuration, a cell, a per-layer metric and a kernel-name pattern
that exist only as ADDED files are found by name: no file that is there
needs an edit."""

import json
import shutil

import pytest

from benchmark.harness import roofline
from benchmark.harness.manifest import ROOT, Manifest


@pytest.fixture
def grown(tmp_path):
    """A copy of the benchmark with one of each added as new files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = tmp_path / "benchmark"
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    # entries are appended; none is changed
    doc["configs"].append({
        "name": "bert_base", "source": "https://example.org/bert-base",
        "file": "benchmark/configs/bert_base.json", "reduced": [],
        "why": "added by the test"})
    doc["workloads"].append({
        "name": "bert_base.phase1", "config": "bert_base",
        "traffic": "phase1", "chips": 1, "why": "added by the test"})
    doc["per_layer"].append({
        "name": "kernels.softmax_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "tokens_per_s", "workloads": ["bert_base.phase1"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    base = json.loads((bench / "configs" / "bert_large.json").read_text())
    base.update(hidden_size=768, num_hidden_layers=12)
    (bench / "configs" / "bert_base.json").write_text(json.dumps(base))
    traffic = json.loads(
        (bench / "workloads" / "bert_large.phase2.json").read_text())
    traffic.update(seq=128, rows_per_chip=64)
    (bench / "workloads" / "bert_base.phase1.json").write_text(
        json.dumps(traffic))
    (bench / "metrics" / "kernels.softmax_roofline.py").write_text(
        "def read(ctx):\n    return 12.5\n")
    pats = bench / "patterns" / "kernels.softmax_roofline"
    pats.mkdir()
    (pats / "softmax.txt").write_text("work: softmax\nmatch: ^softmax_fwd\n")
    (bench / "patterns" / "kernels.flash_roofline" / "new_kernel.txt"
     ).write_text("work: attention_forward\nmatch: ^splash_fwd\n")
    return Manifest(tmp_path)


def test_added_files_are_found_by_name(grown):
    assert grown.cell("bert_base.phase1")["config"] == "bert_base"
    assert grown.config("bert_base")["hidden_size"] == 768
    assert grown.traffic("bert_base.phase1")["seq"] == 128
    names = [m["name"] for m in grown.per_layer("bert_base.phase1")]
    assert "kernels.softmax_roofline" in names
    # a metric without a "workloads" key is every cell's
    assert "step.mfu" in names
    assert "kernels.softmax_roofline" not in [
        m["name"] for m in grown.per_layer("bert_large.phase2")]
    assert grown.module("metrics", "kernels.softmax_roofline").read({}) == 12.5
    assert roofline.pattern_files(grown, "kernels.softmax_roofline") == [
        ("softmax", "^softmax_fwd", None)]
    assert ("attention_forward", "^splash_fwd", None) in roofline.pattern_files(
        grown, "kernels.flash_roofline")


def test_unknown_names_raise(grown):
    with pytest.raises(KeyError):
        grown.cell("no_such.cell")
    with pytest.raises(FileNotFoundError):
        grown.module("metrics", "no.such_metric")
    with pytest.raises(LookupError):
        roofline.pattern_files(grown, "kernels.no_such_roofline")


def test_every_named_file_of_the_committed_benchmark_exists():
    m = Manifest()
    for cell in m.doc["workloads"]:
        m.config(cell["config"])
        assert "limits" in m.traffic(cell["name"])
        for entry in m.per_layer(cell["name"]):
            assert callable(m.module("metrics", entry["name"]).read)
        assert any(e["name"] == "setup_s" for e in m.end_to_end(cell["name"]))
