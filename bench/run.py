"""Run one cell of the benchmark once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the TPU chips the cell
asks for.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a profiler trace of the window.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``, the numbers compared with their limits); the last lines
of standard error repeat the numbers compared.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""

import time

T_LAUNCH = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"[bench] FAIL: the program is not in this checkout "
              f"({ROOT / 'src' / 'repro'})", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    from bench import harness

    # a fixed directory inside the checkout: only a cell's first run here
    # compiles
    harness.CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(harness.CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_LAUNCH)
    except harness.BenchError as e:
        print(f"[bench] FAIL: {e}", file=sys.stderr)
        return 2
    for name, num in result["checks"].items():
        print(f"[check] {name} = {num['value']!r} "
              f"(limit: {num['rule']} {num['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
