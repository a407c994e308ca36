"""Measured thread-pool worker runtime: real deadlines, retries, re-dispatch.

Everything else in the repo *simulates* worker timing; this module runs
the paper's master/worker protocol against actual wall-clock time.  Each
worker is a thread computing its coded shard ``b_k = fft(G[k] @ c)`` for
the whole bucket (numpy, so N workers genuinely overlap outside the GIL
inside the FFT); the master

1. dispatches all live workers and waits until ``threshold`` rows have
   ARRIVED or the deadline expires -- the deadline comes from the shared
   :class:`~repro.distributed.health.WorkerHealthTracker` (m-th-fastest
   EWMA estimate + slack), so the wait budget is learned from measured
   rounds, never assumed;
2. on a miss, re-dispatches the missing shard rows to the pool (any
   healthy thread computes a row -- the row is data, not an identity) and
   extends the window by ``retry_backoff``, up to ``max_retries`` times;
3. gives up with a typed reason: ``insufficient_workers`` when no healthy
   worker exists to re-dispatch to, ``retries_exhausted`` when the capped
   windows close without ``m`` rows.

``require_all=True`` is the UNCODED baseline: the master needs every row
(an uncoded partition has no slack), so one killed or delayed worker
stalls the round into the retry machinery.

Time is read from a :class:`Clock`: the host's clock by default, or one
slowed by a factor, which stretches every window, deadline and injected
delay of a round over that many host seconds so its outcome does not hang
on how busy the host is.

Fault injection rides the same :class:`~repro.distributed.faults
.FaultInjector` hook as the simulated path: killed workers never respond,
delayed workers sleep before responding, corrupt workers respond on time
with seeded garbage (caught downstream by ``verify="correct"``).

The runtime covers 1-D c2c plans (the measured-bench workload); the
simulated robust path in ``serving/fft_service.py`` covers every kind.
DESIGN.md §12.
"""

from __future__ import annotations

import queue as queue_mod
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

from repro.distributed.faults import FaultInjector, RoundFaults
from repro.distributed.health import WorkerHealthTracker

__all__ = ["Clock", "MeasuredRound", "MeasuredWorkerRuntime"]


class Clock:
    """The measured runtime's time source, in the runtime's seconds.

    ``slowdown``: host seconds per runtime second.  At 1 (the default) the
    runtime keeps the host's time; above 1 its windows, deadlines and
    injected delays all last ``slowdown`` times longer on the host, while
    the work a worker does takes the same host time -- so a round's
    arrivals are judged with that much more room for host scheduling.
    """

    def __init__(self, slowdown: float = 1.0):
        if slowdown <= 0:
            raise ValueError("slowdown must be > 0")
        self.slowdown = float(slowdown)

    def now(self) -> float:
        return time.perf_counter() / self.slowdown

    def sleep(self, seconds: float) -> None:
        time.sleep(seconds * self.slowdown)

    def host_seconds(self, seconds: float) -> float:
        """A span of runtime seconds on the host's clock."""
        return seconds * self.slowdown


class MeasuredRound:
    """One completed measured round (a plain result record)."""

    def __init__(self, b: np.ndarray, mask: np.ndarray, reason: Optional[str],
                 *, t_met: float, t_last: float, retries: int,
                 redispatched: int, times: np.ndarray):
        self.b = b                    # (q, N, ell) complex; missing rows 0
        self.mask = mask              # (N,) bool: rows that arrived in time
        self.reason = reason          # None | insufficient_workers |
        #                               retries_exhausted
        self.t_met = t_met            # seconds until threshold met (inf if not)
        self.t_last = t_last          # seconds until last arrival seen
        self.retries = retries
        self.redispatched = redispatched
        self.times = times            # (N,) per-worker arrival seconds (inf
        #                               = no response)

    @property
    def ok(self) -> bool:
        return self.reason is None


class MeasuredWorkerRuntime:
    """Thread-per-worker execution of one 1-D coded FFT plan.

    ``plan`` must be a c2c :class:`~repro.core.coded_fft.CodedFFT` (worker
    body = fft along the last axis).  ``health`` is shared with the owning
    service so deadlines learn across rounds.  ``min_deadline_s`` floors
    the wait budget against scheduler jitter at sub-millisecond compute.
    ``clock`` times the rounds (the host's clock unless given).
    """

    def __init__(self, plan, health: WorkerHealthTracker, *,
                 injector: Optional[FaultInjector] = None,
                 max_retries: int = 2, retry_backoff: float = 2.0,
                 require_all: bool = False, min_deadline_s: float = 2e-3,
                 threshold_extra: int = 0, clock: Optional[Clock] = None):
        self.plan = plan
        self.clock = clock if clock is not None else Clock()
        self.health = health
        self.injector = injector
        self.max_retries = int(max_retries)
        self.retry_backoff = float(retry_backoff)
        self.require_all = bool(require_all)
        self.min_deadline_s = float(min_deadline_s)
        # surplus responses to wait for beyond m: the Byzantine verifier
        # needs k > m rows (k = m + q detects q liars, corrects q//2)
        self.threshold_extra = int(threshold_extra)
        self.generator = np.asarray(plan.generator, dtype=np.complex128)
        self.pool = ThreadPoolExecutor(
            max_workers=plan.n_workers, thread_name_prefix="coded-worker")

    def close(self) -> None:
        self.pool.shutdown(wait=False, cancel_futures=True)

    def __enter__(self) -> "MeasuredWorkerRuntime":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------
    def round(self, xb: np.ndarray, round_idx: int,
              alive: Optional[np.ndarray] = None) -> MeasuredRound:
        """Run one bucket ``xb`` (``(q, s)`` complex) as a measured round."""
        plan = self.plan
        n, m = plan.n_workers, plan.m
        q, s = xb.shape
        ell = s // m
        alive = (np.ones(n, bool) if alive is None
                 else np.asarray(alive, bool).copy())
        rf = (self.injector.faults_for(round_idx)
              if self.injector is not None else RoundFaults())
        delay_map = rf.delay_map
        # interleaved message shards c[j] = x[j::m] -> (q, m, ell)
        c = np.ascontiguousarray(
            np.swapaxes(np.asarray(xb, np.complex128).reshape(q, ell, m),
                        -1, -2))
        threshold = (int(alive.sum()) if self.require_all
                     else min(m + self.threshold_extra, int(alive.sum())))
        resq: queue_mod.Queue = queue_mod.Queue()
        clock = self.clock
        t_start = clock.now()

        def compute_row(row: int) -> np.ndarray:
            a = np.tensordot(self.generator[row], c, axes=([0], [1]))  # (q, ell)
            return np.fft.fft(a, axis=-1)

        def worker(k: int) -> None:
            if k in rf.killed:
                return  # dead: never responds this round
            b_k = compute_row(k)
            if k in rf.corrupt and self.injector is not None:
                b_k = self.injector.corrupt_payload(b_k, k, round_idx)
            d = delay_map.get(k)
            if d:
                clock.sleep(d)
            resq.put((k, b_k, clock.now() - t_start))

        def redispatch(row: int) -> None:
            # a healthy thread recomputes the missing shard row: no fault
            # applies (the faulty worker is not the one computing it)
            b_k = compute_row(row)
            resq.put((row, b_k, clock.now() - t_start))

        for k in np.flatnonzero(alive):
            self.pool.submit(worker, int(k))

        got: dict[int, np.ndarray] = {}
        times = np.full(n, np.inf)
        t_met = np.inf
        # wait budget for the k-th-fastest response we actually need:
        # m for the coded path, m + quorum under verify, ALL alive rows
        # for the uncoded require_all baseline (else the 8th arrival is
        # judged against an m-th-fastest deadline and always misses)
        deadline = self.health.deadline(max(threshold, 1), alive=alive)
        if not np.isfinite(deadline):
            # too many never-responders for an m-th-fastest deadline:
            # budget off the slowest worker that HAS responded (retries
            # still extend from there), or the floor when nobody has
            est = self.health.estimates()[:n]
            fin = est[np.isfinite(est) & alive]
            deadline = (float(fin.max()) * (1.0 + self.health.slack_frac)
                        if fin.size else 0.0)
        window = max(deadline, self.min_deadline_s)
        retries = redispatched = 0
        healthy = alive & ~np.isin(np.arange(n), sorted(rf.killed))
        if self.health.byzantine.any():
            healthy &= ~self.health.byzantine
        reason: Optional[str] = None

        if int(alive.sum()) < m:
            reason = "insufficient_workers"
        else:
            while True:
                self._collect(resq, got, times, window, t_start, threshold)
                if len(got) >= threshold:
                    break
                if retries >= self.max_retries:
                    reason = "retries_exhausted"
                    break
                if not healthy.any():
                    reason = "insufficient_workers"
                    break
                missing = [k for k in np.flatnonzero(alive) if k not in got]
                for row in missing:
                    self.pool.submit(redispatch, int(row))
                redispatched += len(missing)
                retries += 1
                window *= self.retry_backoff
            if len(got) >= threshold:
                t_met = float(np.sort(times[np.isfinite(times)])[threshold - 1])

        b = np.zeros((q, n, ell), np.complex128)
        mask = np.zeros(n, bool)
        for k, row in got.items():
            b[:, k] = row
            mask[k] = True
        finite = times[np.isfinite(times)]
        t_last = float(finite.max()) if finite.size else np.inf
        self.health.observe_round(np.where(np.isfinite(times), times, np.nan))
        return MeasuredRound(b, mask, reason, t_met=t_met, t_last=t_last,
                             retries=retries, redispatched=redispatched,
                             times=times)

    def _collect(self, resq: queue_mod.Queue, got: dict, times: np.ndarray,
                 window: float, t_start: float, threshold: int) -> None:
        """Drain arrivals until ``threshold`` rows are in or the window
        closes (first arrival per row wins: an original beating its
        re-dispatched copy is kept)."""
        while len(got) < threshold:
            remaining = window - (self.clock.now() - t_start)
            if remaining <= 0:
                # non-blocking final sweep: arrivals already queued count
                try:
                    while True:
                        k, row, t = resq.get_nowait()
                        if k not in got and t <= window:
                            got[k] = row
                            times[k] = t
                except queue_mod.Empty:
                    return
                continue
            try:
                k, row, t = resq.get(
                    timeout=self.clock.host_seconds(remaining))
            except queue_mod.Empty:
                continue
            if k not in got:
                got[k] = row
                times[k] = t
