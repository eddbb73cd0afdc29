"""A model family the harness has never heard of comes in by ADDED files
alone: a copy of the committed benchmark plus new files and new
``BENCHMARK.json`` entries (the program's own GPT at tiny widths under
another builder name, with a count file whose attention has fewer
key/value heads than query heads) runs a traced rehearsal of its cell
from that copy, ``step.mfu`` counts by the new family's file, and no
committed file of the copy differs from the tree's.

Each case is a whole rehearsal run in a child process (the copy's own
``run.py``, so every module of the harness is the copy's): about a
minute.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.harness.manifest import ROOT, Manifest

FAMILY = "gqa_decoder"
CELL = "tiny_gqa.lm128"
QUERY_HEADS, KV_HEADS = 4, 1

COUNTS = f'''"""A decoder whose keys and values have {KV_HEADS} head(s) for {QUERY_HEADS} query
heads: its own count, from its own configuration's keys."""


def forward_flops(config, traffic, rows):
    h, seq = config["n_embd"], traffic["seq"]
    d = h // config["n_head"]
    kv = config["n_kv_head"] * d
    tokens = rows * seq
    layer = (2 * 2 * tokens * h * h            # q, out
             + 2 * 2 * tokens * h * kv         # k, v at their own width
             + 2 * 2 * tokens * h * 4 * h      # the MLP
             + 2 * 2 * rows * seq * seq * h // 2)
    return config["n_layer"] * layer + 2 * rows * (seq - 1) * h * config[
        "vocab_size"]


def attention_shape(config):
    return {{"query_heads": config["n_head"], "kv_heads": config["n_kv_head"],
            "head_size": config["n_embd"] // config["n_head"],
            "causal": True}}
'''


def committed_files(bench: Path):
    return sorted(p.relative_to(bench) for p in bench.rglob("*")
                  if p.is_file() and "__pycache__" not in p.parts)


@pytest.fixture
def grown(tmp_path):
    """The committed benchmark, copied, plus the new family: files and
    entries are added, none is changed."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "benchmark"
    (bench / "builders" / f"{FAMILY}.py").write_text(
        "from . import gpt\n\n"
        f'REFERENCE = "{FAMILY}"\nbuild = gpt.build\n')
    (bench / "reference" / f"{FAMILY}.py").write_text(
        "from .gpt import init_weights, keeps_float32, loss  # noqa: F401\n")
    (bench / "counts" / f"{FAMILY}.py").write_text(COUNTS)
    config = json.loads((bench / "configs" / "gpt2_medium.json").read_text())
    config.update(config.pop("rehearsal"))
    config.update(builder=FAMILY, n_kv_head=KV_HEADS, n_head=QUERY_HEADS)
    (bench / "configs" / "tiny_gqa.json").write_text(json.dumps(config))
    traffic = json.loads(
        (bench / "workloads" / "gpt2_medium.lm1024.json").read_text())
    (bench / "workloads" / f"{CELL}.json").write_text(json.dumps(traffic))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "tiny_gqa", "source": "https://example.org/tiny-gqa",
        "file": "benchmark/configs/tiny_gqa.json", "reduced": [],
        "why": "added by the test"})
    doc["workloads"].append({
        "name": CELL, "config": "tiny_gqa", "traffic": "lm128", "chips": 1,
        "why": "added by the test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return tmp_path


def rehearse(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT))        # the program; the copy comes first
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", "2900000007", "--seconds", "1", "--trace", "1",
         "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)


def test_a_new_family_runs_by_added_files_alone(grown):
    done = rehearse(grown)
    assert done.returncode == 0, done.stderr[-3000:]
    out = done.stdout
    # the harness that ran is the copy's, and so is the count
    config = json.loads(
        (grown / "benchmark" / "configs" / "tiny_gqa.json").read_text())
    traffic = {**json.loads((grown / "benchmark" / "workloads"
                             / f"{CELL}.json").read_text())}
    traffic.update(traffic["rehearsal"])
    rows, seq, h = traffic["rows_per_chip"], traffic["seq"], config["n_embd"]
    kv = KV_HEADS * h // QUERY_HEADS
    tokens = rows * seq
    layer = (4 * tokens * h * h + 4 * tokens * h * kv + 16 * tokens * h * h
             + 2 * rows * seq * seq * h)
    forward = config["n_layer"] * layer + 2 * rows * (seq - 1) * h * config[
        "vocab_size"]
    assert (f"[step.mfu] {3 * forward} model operations a step "
            f"(benchmark/counts/{FAMILY}.py)") in out, out[-3000:]
    # the CPU has no published peak: pardoned in the rehearsal, by name
    assert "[rehearsal] step.mfu not read off the chip" in out
    shape = json.loads(out.split("REHEARSAL on the CPU, not a result: ")[1]
                       .splitlines()[0])
    assert shape["correct"] is True
    # every per-layer metric without a `workloads` list is this cell's too
    everyones = [m["name"] for m in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["per_layer"]
        if "workloads" not in m]
    assert "step.mfu" in everyones
    assert [m["name"] for m in Manifest(grown).per_layer(CELL)] == everyones
    # ... and those that have something to read off the chip were read
    assert set(shape["metrics"]) == {"loop.host_ms_per_step", "step.live_gib",
                                     "amp.steps_skipped"}
    # nothing the benchmark had was edited: files byte for byte, entries whole
    for rel in committed_files(ROOT / "benchmark"):
        assert (grown / "benchmark" / rel).read_bytes() == (
            ROOT / "benchmark" / rel).read_bytes(), rel
    added = set(committed_files(grown / "benchmark")) - set(
        committed_files(ROOT / "benchmark"))
    assert added == {Path("builders") / f"{FAMILY}.py",
                     Path("reference") / f"{FAMILY}.py",
                     Path("counts") / f"{FAMILY}.py",
                     Path("configs") / "tiny_gqa.json",
                     Path("workloads") / f"{CELL}.json"}
    was = json.loads((ROOT / "BENCHMARK.json").read_text())
    now = json.loads((grown / "BENCHMARK.json").read_text())
    for section, entries in was.items():
        head = now[section][:len(entries)] if isinstance(entries, list) \
            else now[section]
        assert head == entries, section


def test_a_family_without_a_count_file_fails_the_rehearsal_too(grown):
    (grown / "benchmark" / "counts" / f"{FAMILY}.py").unlink()
    done = rehearse(grown)
    assert done.returncode != 0
    assert f"add benchmark/counts/{FAMILY}.py" in done.stderr
    assert "REHEARSAL on the CPU" not in done.stdout
