"""Straggler latency: expected completion time under the shifted-exponential
model (the paper's motivating metric -- Fig. 1's 'don't wait for worker 1').

Each strategy processes workload w per worker and waits for its recovery
threshold k: completion = k-th order statistic of N shifted-exp finish
times.  Closed form E[T_(k)] = w (t0 + (H_N - H_{N-k}) / mu) plus Monte
Carlo confirmation.
"""

from __future__ import annotations

import pathlib

import numpy as np

from repro.core import coded_fft_threshold, repetition_threshold, short_dot_threshold
from repro.distributed.straggler import StragglerModel, empirical_completion


def run() -> list[str]:
    model = StragglerModel(t0=1.0, mu=1.0)
    rng = np.random.default_rng(0)
    trials = 2000
    lines = ["bench_latency: E[completion] (shifted-exp, t0=1, mu=1); "
             "analytic | monte-carlo x2000"]
    lines.append(f"{'N':>4} {'m':>3} | {'coded':>15} {'short-dot':>15} "
                 f"{'wait-all':>15}")
    for n, m in [(8, 4), (16, 8), (32, 8), (64, 16), (256, 16)]:
        w = 1.0 / m
        specs = {
            "coded": (coded_fft_threshold(n, m), w),
            "short-dot": (short_dot_threshold(n, m), w),
            "wait-all": (n, w),
        }
        cells = []
        for name, (k, wl) in specs.items():
            ana = model.expected_kth(n, k, wl)
            emp = np.mean([
                empirical_completion(model.sample(n, wl, rng), k)
                for _ in range(trials)])
            cells.append(f"{ana:6.3f}|{emp:6.3f}")
        lines.append(f"{n:>4} {m:>3} | " + " ".join(f"{c:>15}" for c in cells))
    lines.append("coded FFT waits for the m fastest only: latency stays flat "
                 "as N grows while wait-all degrades with H_N.")
    return lines


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(pathlib.Path(__file__).resolve().parents[1])
    print("\n".join(run()))
