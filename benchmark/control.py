#!/usr/bin/env python3
"""Readings the limits of a cell's comparison are set from: the control
and the planted faults, each as the plain reference put in the program's
place and held against the float32 reference by the cell's own
comparison, at the cell's own size, on several seeds in one process.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--variants fp8,half_batch]

* ``fp8``: the reference with every matmul (forward and backward) rounded
  through float8_e4m3 - the nearest precision below the bfloat16 the
  configurations state. It has to come out NOT correct.
* ``half_batch``, ``no_exchange`` (cells on several chips),
  ``state_unchanged``: the faults a training step can have.
* ``program``: the program itself through its first steps, on every seed
  in this one process (one compiled step, a fresh state per seed): the
  sound runs the lower readings come from. Needs the cell's chips.

The benchmark's own runs never run this. It needs one chip at most (the
shards of a four-chip cell are taken one after the other), but for
``program``. So that a four-chip machine is held for the program alone,
``--no-reference --save DIR`` runs the named variants only and writes
each side's numbers to ``DIR/<cell>.s<seed>.<side>.json``; a later call
on one chip with ``--load DIR`` holds what it finds there against the
reference it computes.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def first_batches(config, params, seed, chips, n):
    """Reference batches of the first ``n`` steps of a cell's traffic."""
    from benchmark.harness import traffic as traffic_mod

    rows = params["rows_per_chip"] * chips
    if params["feed"] == "loader":
        # any rows of the corpus do for a reading: take them in order
        corpus = traffic_mod.corpus(seed, params["corpus_rows"],
                                    params["seq"], config["vocab_size"])
        out = []
        for t in range(n):
            ids = corpus[t * rows:(t + 1) * rows]
            out.append({"ids": ids.reshape(chips, -1, ids.shape[1]),
                        "seed": [7 + seed % 1000 + t * chips + s
                                 for s in range(chips)]})
        return out
    tr = traffic_mod.Traffic(params, config["vocab_size"], seed, chips)
    out = []
    for t in range(n):
        tb = tr.batch(t)
        out.append({k: (v.reshape(chips, -1, *v.shape[1:])
                        if k != "seed" else list(v))
                    for k, v in tb.items()})
    return out


class _Program:
    """The system under test, built once; ``first_steps`` gives what a
    run's comparison reads, from a fresh state per seed."""

    def __init__(self, config, params, builder, reference, chips):
        import jax

        from benchmark.harness import runner

        self.chips, self.runner = chips, runner
        mesh, ddp = runner.data_parallel(params, chips, jax.devices())
        self.built = builder.build(config, params, reference, seed=0,
                                   key=runner.weights_key(0), mesh=mesh,
                                   ddp=ddp)

    def first_steps(self, seed, ref_batches):
        from apex_tpu.train import TrainLoop

        built, key = self.built, self.runner.weights_key(seed)
        loop = TrainLoop(built.step, built.new_state(key), max_retries=0)
        metrics, finish = [], None
        for t, rb in enumerate(ref_batches):
            # a reference batch [shards, rows, ...] back to the program's
            pb = {k: (np.asarray(v, np.int32).reshape(1, self.chips)
                      if k == "seed" else v.reshape(1, -1, *v.shape[2:]))
                  for k, v in rb.items()}
            m = loop.step(built.place(pb))
            if m is not None:
                metrics.append(m)
            if t == 0:
                finish = built.grad_norms(loop.state)
        metrics.append(loop.drain())
        change = built.to_reference(built.change_norms(loop.state, key))
        return {"loss": [m["loss"] for m in metrics],
                "grad": finish(metrics[0]), "change": change}


def _save(directory, cell, seed, side, numbers):
    directory.mkdir(parents=True, exist_ok=True)
    plain = {"loss": [float(x) for x in numbers["loss"]]}
    for part in ("grad", "change"):
        plain[part] = {n: np.asarray(a).tolist()
                       for n, a in numbers[part].items()}
    (directory / f"{cell}.s{seed}.{side}.json").write_text(json.dumps(plain))


def _load(directory, cell, seed, side):
    path = directory / f"{cell}.s{seed}.{side}.json"
    if not path.exists():
        return None
    got = json.loads(path.read_text())
    for part in ("grad", "change"):
        got[part] = {n: np.asarray(a) for n, a in got[part].items()}
    return got


def _report(seed, side, verdict, seconds):
    numbers = verdict["numbers"]
    print(f"seed {seed} {side}: correct={verdict['correct']} "
          + json.dumps({n: r["value"] for n, r in numbers.items()})
          + f" worst leaves {numbers['grad_worst_leaf']['leaf']}"
          + f" {numbers['change_worst_leaf']['leaf']}"
          + f" ({seconds:.1f} s)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="fp8,half_batch,no_exchange")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--save", type=Path,
                    help="write each side's numbers there, one file each")
    ap.add_argument("--load", type=Path,
                    help="hold the program's numbers saved there against "
                         "the reference computed here")
    ap.add_argument("--no-reference", action="store_true",
                    help="run the named variants only (needs --save)")
    args = ap.parse_args(argv)
    if args.rehearse:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from benchmark.harness import check, masks, runner
    from benchmark.harness.manifest import Manifest
    from benchmark.reference import train

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("control: no TPU here", file=sys.stderr)
        return 3
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    params = manifest.traffic(args.workload)
    if args.rehearse:
        config, params = runner._apply_rehearsal(config, params)
    chips = cell["chips"]
    builder, reference = runner.family(config)
    variants = [v for v in args.variants.split(",")
                if v and (v != "no_exchange" or chips > 1)]
    program = None
    if "program" in variants:
        variants.remove("program")
        program = _Program(config, params, builder, reference, chips)
    now = time.perf_counter
    for seed in (int(s) for s in args.seeds.split(",")):
        batches = first_batches(config, params, seed, chips,
                                runner.FIRST_STEPS)
        key = runner.weights_key(seed)
        sides = {}          # side -> (its numbers, seconds it took)
        loaded = args.load and _load(args.load, args.workload, seed,
                                     "program")
        if program is not None:
            t0 = now()
            sides["program"] = (program.first_steps(seed, batches),
                                now() - t0)
        elif loaded:
            sides["program"] = (loaded, 0.0)
        ref = None
        if not args.no_reference:
            t0 = now()
            ref = train.run(reference, config, config["optimizer"], key,
                            batches, masks)
            print(f"seed {seed}: reference losses {ref['loss'].tolist()} "
                  f"({now() - t0:.1f} s)", flush=True)
            for v in variants:
                kw = {"precision": "fp8"} if v == "fp8" else {"fault": v}
                t0 = now()
                sides[v] = (train.run(reference, config, config["optimizer"],
                                      key, batches, masks, **kw), now() - t0)
        for side, (numbers, seconds) in sides.items():
            if args.save:
                _save(args.save, args.workload, seed, side, numbers)
            if ref is not None:
                _report(seed, side,
                        check.compare(numbers, ref, params["limits"]),
                        seconds)
            else:
                print(f"seed {seed} {side}: losses {list(numbers['loss'])} "
                      f"saved, not compared ({seconds:.1f} s)", flush=True)
        if args.save and ref is not None:
            _save(args.save, args.workload, seed, "reference", ref)
    return 0


if __name__ == "__main__":
    sys.exit(main())
