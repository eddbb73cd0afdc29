"""Find what belongs to a cell by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one kernel-name pattern is a file of its own, so a
later PR adds files and edits none:

* configuration ``c``  -> the ``file`` its entry names (JSON of sizes);
  its ``builder`` key names ``benchmark/builders/<builder>.py``, which
  names the plain reference ``benchmark/reference/<REFERENCE>.py``;
* cell ``w``           -> ``benchmark/workloads/<w>.json`` (traffic
  parameters and the limits of its comparison);
* per-layer metric ``m`` -> ``benchmark/metrics/<m>.py`` with ``read(ctx)``;
* a family's operation and attention counts ->
  ``benchmark/counts/<builder>.py`` (``harness/flops.py`` looks it up);
* kernel-name patterns of a roofline metric ``m`` ->
  every ``benchmark/patterns/<m>/*.txt`` (one regular expression each).
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, label: str):
    """A file of the benchmark loaded by path: a metric's name may hold
    dots, which a package import would split."""
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "benchmark"
        self.doc = load_json(self.root / "BENCHMARK.json")

    def _entry(self, section: str, name: str) -> dict:
        for e in self.doc[section]:
            if e["name"] == name:
                return e
        known = [e["name"] for e in self.doc[section]]
        raise KeyError(f"{section} has no {name!r}; known: {known}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        return load_json(self.root / entry["file"])

    def traffic(self, cell_name: str) -> dict:
        return load_json(self.bench / "workloads" / f"{cell_name}.json")

    def end_to_end(self, cell_name: str) -> list:
        return [m for m in self.doc["end_to_end"]
                if cell_name in m.get("workloads", [cell_name])]

    def per_layer(self, cell_name: str) -> list:
        return [m for m in self.doc["per_layer"]
                if cell_name in m.get("workloads", [cell_name])]

    def module(self, kind: str, name: str):
        """``benchmark/<kind>/<name>.py``, loaded by path."""
        path = self.bench / kind / f"{name}.py"
        if not path.exists():
            raise FileNotFoundError(f"{kind} {name!r}: no {path}")
        return load_module(
            path, f"benchmark_{kind}_{name.replace('.', '_')}")

    def patterns(self, metric: str) -> list:
        """The regular expressions, one per file, that say which trace
        events implement the work metric ``metric`` divides."""
        d = self.bench / "patterns" / metric
        return sorted(p.read_text().strip() for p in d.glob("*.txt")
                      if p.read_text().strip())
