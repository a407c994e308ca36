"""Compile every cell's bucket executors for a described, unattached v5e chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <cell> ...]

For each cell of ``BENCHMARK.json`` this builds the service as a run
does and ahead-of-time compiles, for a ``v5e:2x2`` topology, the very
executors its window drives: one per bucket size the cell's traffic
forms, on one chip, or on a mesh of the four chips for a four-chip cell.
It prints each program's ``memory_analysis()`` and whether it holds a
Mosaic kernel.  Nothing runs: a pass says the TPU compiler accepts the
programs and what they hold in memory, not that they are fast or right.
Run it before spending chip time on a changed cell.

The service picks its TPU path by asking JAX for the default backend,
which here is the CPU; this script tells the kernels' dispatch that it is
compiling for the TPU.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", nargs="*", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.kernels import ops
    from repro.serving.fft_service import FFTService, FFTServiceConfig

    jax.config.update("jax_enable_compilation_cache", False)
    ops.default_interpret = lambda: False     # compile the TPU path
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    names = args.workload or [w["name"] for w in spec["workloads"]]
    failures = 0
    for name in names:
        _, cell, config, mix = harness.cell_spec(name)
        chips = int(cell["chips"])
        kind, s = config["kind"], int(config["s"])
        cap = int(config["service"]["max_batch"])
        if chips > 1:
            mesh = Mesh(np.array(topo.devices[:chips]), ("workers",))
            where = NamedSharding(mesh, P())
        else:
            mesh = None
            where = SingleDeviceSharding(topo.devices[0])
        svc = FFTService(FFTServiceConfig(s=s, seed=0, **config["service"]),
                         mesh=mesh)
        for q in harness.warm_buckets(mix, cap):
            xb = svc._bucket_buffer(s, q, kind)
            mk = svc._full_masks(s, kind, q)
            shapes = [jax.ShapeDtypeStruct(a.shape, jnp.dtype(a.dtype),
                                           sharding=where)
                      for a in (xb, mk)]
            t0 = time.perf_counter()
            try:
                compiled = svc._runner_for(s, q, kind).lower(
                    *shapes).compile()
            except Exception as e:                # noqa: BLE001
                failures += 1
                print(f"[rehearse] {name} {kind} s={s} bucket={q} "
                      f"chips={chips}: REFUSED {type(e).__name__}: "
                      f"{str(e)[:2000]}", flush=True)
                continue
            mosaic = "tpu_custom_call" in compiled.as_text()
            print(f"[rehearse] {name} {kind} s={s} bucket={q} chips={chips}"
                  f" compile_s={time.perf_counter() - t0:.2f}"
                  f" mosaic_kernel={mosaic}"
                  f" memory={compiled.memory_analysis()}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
