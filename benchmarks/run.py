"""Benchmark aggregator: one module per paper claim.

``PYTHONPATH=src python -m benchmarks.run`` runs everything and prints a
single report (tee'd to bench_output.txt by the final deliverable step).
Individual modules run standalone too.
"""

from __future__ import annotations

import pathlib
import time
import traceback


def main() -> int:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(pathlib.Path(__file__).resolve().parents[1])
    from benchmarks import (
        bench_comm_load,
        bench_decode_scaling,
        bench_fault_tolerance,
        bench_kernels,
        bench_latency,
        bench_ndim,
        bench_recovery,
        bench_service,
    )

    modules = [
        ("recovery thresholds (Thm 1/2, Remark 4)", bench_recovery),
        ("straggler latency (shifted-exp model)", bench_latency),
        ("decode linearity in s (§III-C)", bench_decode_scaling),
        ("communication optimality (Remark 5)", bench_comm_load),
        ("n-D + multi-input (Thm 3/5)", bench_ndim),
        ("Byzantine fault tolerance (Remark 3)", bench_fault_tolerance),
        ("Pallas kernels vs oracle + roofline", bench_kernels),
        ("end-to-end FFT service", bench_service),
    ]
    failures = []
    for title, mod in modules:
        print("=" * 72)
        print(f"== {title}")
        print("=" * 72)
        t0 = time.perf_counter()
        try:
            for line in mod.run():
                print(line)
        except Exception:
            failures.append(title)
            traceback.print_exc()
        print(f"-- {time.perf_counter() - t0:.1f}s")
        print()
    if failures:
        print("FAILED:", failures)
        return 1
    print("all benchmarks complete")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
