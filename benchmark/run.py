#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run; it holds the cell's chips itself. Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result: there is no CPU mode. ``--rehearse`` is the CPU rehearsal (tiny
widths, kernels interpreted, virtual devices): it exercises the control
flow and prints no result line a driver could record.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, in a traced run
``breakdown``, and last ``compared`` (each number of the comparison
beside its limit).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()     # set-up is counted from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"          # fixed: the path is in the key
OUT_DIR = ROOT / "benchmark_out"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny widths; prints no result")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from benchmark.harness.manifest import Manifest

    manifest = Manifest(ROOT)
    chips = manifest.cell(args.workload)["chips"]
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and (platform != "tpu" or len(devices) < chips):
        print(f"benchmark: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {platform} x{len(devices)}. There is no CPU mode "
              f"(--rehearse rehearses and prints no result).",
              file=sys.stderr)
        return 3
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))

    from benchmark.harness import runner

    result = runner.run_cell(
        manifest, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t0=T0, devices=devices,
        rehearsal=args.rehearse, out_dir=OUT_DIR / args.workload)
    if args.rehearse:
        # no device metric under its real name, no recordable result
        shape = {k: (sorted(v) if isinstance(v, dict) else v)
                 for k, v in result.items() if k != "device"}
        print("REHEARSAL on the CPU, not a result: "
              + json.dumps(shape, default=str))
        print("rehearsal done: control flow only, nothing measured")
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
