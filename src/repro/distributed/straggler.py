"""Shifted-exponential straggler model + strategy completion times.

Standard model in the coded-computation literature (Lee et al. 2015, and
the model the paper's Remark 4 comparisons presume): a worker processing a
``w`` fraction of the input finishes at

    T_i = w * (t0 + X_i),    X_i ~ Exp(rate mu)   i.i.d.

``t0`` is the deterministic per-unit work, ``1/mu`` the expected tail.  A
strategy that waits for the k-th fastest of N workers completes at the
k-th order statistic; its expectation has the closed form

    E[T_(k)] = w * (t0 + (H_N - H_{N-k}) / mu),   H_n = sum_{i<=n} 1/i.

``t0`` optionally splits into compute and WIRE time: ``wire_frac`` is the
fraction of ``t0`` spent shipping the result shard back to the master
(Jeong et al. 1805.09891 show this master-side communication dominating
coded FFT at scale), and per-draw ``payload_scale`` scales only that
share.  The real-kind shards of DESIGN.md §7 ship half the c2c payload,
so the service charges them ``payload_scale=0.5``:

    T_i = w * (t0 * (1 - wire_frac + wire_frac * payload_scale) + X_i).

With the default ``payload_scale=1`` every formula reduces to the
literature model above, whatever ``wire_frac`` is.

The same model prices coded FFT (k=m, w=1/m) against uncoded (k=N
partitions, w=1/N) and the repetition / short-dot thresholds.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["StragglerModel", "harmonic", "expected_kth_completion",
           "empirical_completion"]


def harmonic(n: int) -> float:
    return float(np.sum(1.0 / np.arange(1, n + 1))) if n > 0 else 0.0


@dataclasses.dataclass(frozen=True)
class StragglerModel:
    t0: float = 1.0         # deterministic seconds per unit workload
    mu: float = 1.0         # exponential rate of the tail
    wire_frac: float = 0.25  # share of t0 that is result-shipping wire
    #                          time, scaled by each draw's payload_scale
    #                          (inert at payload_scale=1, the default)

    def _t0_eff(self, payload_scale: float) -> float:
        return self.t0 * (1.0 - self.wire_frac
                          + self.wire_frac * payload_scale)

    def sample(self, n, workload: float, rng: np.random.Generator,
               *, payload_scale: float = 1.0) -> np.ndarray:
        """Finish times of workers each processing ``workload`` units.

        ``n``: worker count or a shape tuple (e.g. ``(requests, workers)``
        for one vectorized draw per scheduler bucket).  ``payload_scale``
        scales the WIRE share of ``t0`` only (module docstring) -- e.g.
        0.5 for the half-payload real-kind shards.
        """
        return workload * (self._t0_eff(payload_scale)
                           + rng.exponential(1.0 / self.mu, size=n))

    def expected_kth(self, n: int, k: int, workload: float,
                     payload_scale: float = 1.0) -> float:
        return expected_kth_completion(
            self._t0_eff(payload_scale), self.mu, n, k, workload)


def expected_kth_completion(t0: float, mu: float, n: int, k: int,
                            workload: float) -> float:
    """E[k-th order statistic of n shifted-exponential finish times]."""
    if k > n:
        return float("inf")
    return workload * (t0 + (harmonic(n) - harmonic(n - k)) / mu)


def empirical_completion(latencies: np.ndarray, k: int) -> float:
    """Completion time waiting for the k fastest workers."""
    if k > latencies.shape[-1]:
        return float("inf")
    return float(np.sort(latencies, axis=-1)[..., k - 1])
