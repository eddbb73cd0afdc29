#!/usr/bin/env python3
"""Compile a cell's step program for a DESCRIBED v5e (no chip attached)
and print the compiler's memory analysis and collectives. Costs no chip
time; a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/compile_described.py <cell> [...]
"""

from __future__ import annotations

import importlib
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(cells) -> int:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
        SingleDeviceSharding

    from benchmark.harness import hlo, runner, traffic as traffic_mod
    from benchmark.harness.manifest import Manifest

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the kernels ask the backend whether to interpret; steer them here
    for name in ("dropout", "flash_attention", "layer_norm", "softmax"):
        mod = importlib.import_module(f"apex_tpu.ops.{name}")
        mod._interpret = lambda: False
    manifest = Manifest(ROOT)
    for cell_name in cells:
        cell = manifest.cell(cell_name)
        config = manifest.config(cell["config"])
        params = manifest.traffic(cell_name)
        chips = cell["chips"]
        builder, reference = runner.family(config)
        mesh = ddp = None
        if chips == 1:
            state_on = batch_on = SingleDeviceSharding(topo.devices[0])
        else:
            from apex_tpu.parallel import DistributedDataParallel

            par = params["parallel"]
            mesh = Mesh(np.array(topo.devices).reshape(chips), (par["axis"],))
            ddp = DistributedDataParallel(
                par["axis"], delay_allreduce=par["delay_allreduce"])
            state_on = NamedSharding(mesh, P())
            batch_on = NamedSharding(mesh, P(None, par["axis"]))
        built = builder.build(config, params, reference, seed=0,
                              key=runner.weights_key(0), mesh=mesh, ddp=ddp,
                              abstract_on=state_on)
        rows = params["rows_per_chip"] * chips
        if params["feed"] == "pool":
            tb = traffic_mod.mlm_batch(0, 0, rows, params["seq"],
                                       config["vocab_size"], params["mlm"])
        else:
            tb = {"ids": np.zeros((rows, params["seq"]), np.int32)}
        tb["seed"] = list(range(1, chips + 1))
        batch = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                           sharding=batch_on),
            built.program_batch(tb))
        t0 = time.perf_counter()
        compiled = built.step.lower(built.state, batch).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        gib = 2.0 ** 30
        live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"{cell_name}: compiled for described v5e x{chips} in "
              f"{time.perf_counter() - t0:.0f} s; per chip: arguments "
              f"{mem.argument_size_in_bytes / gib:.2f} GiB, aliased "
              f"{mem.alias_size_in_bytes / gib:.2f}, temporaries "
              f"{mem.temp_size_in_bytes / gib:.2f}, live {live / gib:.2f}; "
              f"tpu_custom_call x{text.count('tpu_custom_call')}; "
              f"all-reduce {hlo.collective_stats(text)['all-reduce']}; "
              f"{built.n_params / 1e6:.1f}M parameters", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
