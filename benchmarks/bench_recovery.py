"""Recovery threshold: coded FFT vs repetition vs short-dot (Remark 4).

Paper claim: coded FFT achieves K* = m (optimal, Thm 1/2); uncoded
repetition needs N - N/m^2 + 1 and short-dot N - N/m + m.  We print the
analytic thresholds for a sweep of (N, m) AND verify empirically that the
coded construction decodes from *every* (random) m-subset while repetition
fails on its worst-case subsets of the same size.
"""

from __future__ import annotations

import pathlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    CodedFFT,
    UncodedRepetitionFFT,
    coded_fft_threshold,
    repetition_threshold,
    short_dot_threshold,
)


def run() -> list[str]:
    lines = ["bench_recovery: thresholds (lower = more straggler-tolerant)"]
    lines.append(f"{'N':>4} {'m':>3} | {'coded (K*=m)':>12} {'repetition':>11} "
                 f"{'short-dot':>9}")
    for n, m in [(16, 2), (16, 4), (64, 4), (64, 8), (256, 8), (256, 16),
                 (512, 16)]:
        lines.append(
            f"{n:>4} {m:>3} | {coded_fft_threshold(n, m):>12} "
            f"{repetition_threshold(n, m):>11} {short_dot_threshold(n, m):>9}")

    # empirical: every random m-subset decodes exactly
    s, m, n = 512, 2, 16
    plan = CodedFFT(s=s, m=m, n_workers=n)
    key = jax.random.PRNGKey(0)
    x = (jax.random.normal(key, (s,)) + 1j * jax.random.normal(key, (s,))
         ).astype(jnp.complex64)
    ref = jnp.fft.fft(x)
    b = plan.worker_compute(plan.encode(x))
    worst = 0.0
    n_sub = 0
    for subset in itertools.combinations(range(n), m):
        out = plan.decode(b, subset=jnp.asarray(subset))
        worst = max(worst, float(jnp.max(jnp.abs(out - ref))))
        n_sub += 1
    lines.append(f"coded FFT: all {n_sub} possible {m}-subsets of {n} workers "
                 f"decode; worst abs err {worst:.2e}")

    # repetition: exhibits subsets of the same size that CANNOT decode
    rep = UncodedRepetitionFFT(s=s, m=m, n_workers=n)
    n_fail = 0
    for sub in itertools.combinations(range(n), m):
        mask = np.zeros(n, bool)
        mask[list(sub)] = True
        if not rep.decodable(mask):
            n_fail += 1
    lines.append(f"repetition: {n_fail}/{n_sub} {m}-subsets CANNOT decode "
                 f"(threshold {repetition_threshold(n, m)} > {m})")
    return lines


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(pathlib.Path(__file__).resolve().parents[1])
    print("\n".join(run()))
