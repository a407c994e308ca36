"""Fault tolerance: Byzantine correction (Remark 3) + measured stragglers.

Two sections, selectable via ``BENCH_ONLY=byzantine|measured``:

* ``byzantine`` -- inject garbage into worker outputs and verify the
  Prony-style locator detects/corrects within the MDS bounds (detect
  ``k - m``, correct ``floor((k - m)/2)``), including BIT-consistency:
  the corrected output is byte-identical to the clean decode over the
  same clean responder subset (corrupted rows never enter the final
  decode), asserted over adversarial corruption patterns.

* ``measured`` -- the straggler-tolerance claim on MEASURED wall-clock
  time, not the shifted-exponential model: the thread-per-worker
  ``MeasuredWorkerRuntime`` service (N=8, m=4, so N - m = 4 slack) runs
  under seeded kill/delay fault plans at rates {0, 1/N, 2/N}.  Per-round
  time-to-threshold comes from actual thread arrival times against
  deadlines LEARNED by the health tracker.  Acceptance (asserted when not
  BENCH_SMOKE): coded p99 at fault rate 1/N stays within 1.5x the
  no-fault p99 and zero requests degrade -- while the uncoded baseline
  (``require_all=True``: every worker is load-bearing) FAILS rounds under
  the identical fault plan in the same run.

``BENCH_SMOKE=1`` shrinks rounds and skips the artifact; otherwise the
results append to ``BENCH_faults.json`` with the previous runs preserved
under ``history`` (oldest first), version-stamped like BENCH_service.json.
"""

from __future__ import annotations

import json
import os
import pathlib
import platform

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CodedFFT, RobustCodedFFT, robust_decode
from repro.distributed import FaultPlan
from repro.serving import DegradedResult, FFTService, FFTServiceConfig

SMOKE = os.environ.get("BENCH_SMOKE", "") == "1"
ONLY = os.environ.get("BENCH_ONLY", "")


def _want(section: str) -> bool:
    # the aggregator historically ran this module as one section ("faults")
    return not ONLY or ONLY in (section, "faults")


def _versions() -> dict:
    import jaxlib

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "machine": platform.machine(),
    }


# ---------------------------------------------------------------- byzantine
def _byzantine_section(lines: list[str]) -> dict:
    lines.append("  -- Byzantine errors (Remark 3) --")
    out: dict = {"cases": []}
    s, m, n = 1024, 4, 12
    plan = CodedFFT(s=s, m=m, n_workers=n, dtype=jnp.complex128)
    robust = RobustCodedFFT(plan, tol=1e-8)
    key = jax.random.PRNGKey(0)
    x = (jax.random.normal(key, (s,)) + 1j * jax.random.normal(key, (s,))
         ).astype(jnp.complex128)
    ref = jnp.fft.fft(x)
    rng = np.random.default_rng(0)
    b_clean = np.array(plan.worker_compute(plan.encode(x)))

    # adversarial sweep: every receive size x several corruption patterns
    # (rotating positions, adjacent pairs, the extremes of the subset)
    for k_recv in (8, 10, 12):
        max_corr = robust.max_correctable(k_recv)
        recv = np.sort(rng.choice(n, size=k_recv, replace=False))
        patterns = [rng.choice(recv, size=max_corr, replace=False)
                    for _ in range(3)]
        patterns.append(recv[:max_corr])          # lowest received indices
        patterns.append(recv[-max_corr:])         # highest received indices
        for bad in patterns:
            bad = np.sort(np.asarray(bad))
            b = b_clean.copy()
            b[bad] = rng.standard_normal((max_corr, s // m)) * 100.0
            res = robust_decode(plan, jnp.asarray(b), recv, tol=1e-8)
            err = float(np.max(np.abs(res.output - np.asarray(ref))))
            found = sorted(res.error_worker_indices.tolist())
            assert res.ok and err < 1e-5
            assert set(found) == set(bad.tolist())
            # BIT-consistency: decoding the clean rows over the same
            # subset robust_decode used must match byte-for-byte -- the
            # corrupted rows provably never entered the final decode
            clean = [int(i) for i in recv if i not in set(bad.tolist())]
            subset = jnp.asarray(clean[:m])
            want = np.asarray(plan.decode(jnp.asarray(b_clean),
                                          subset=subset))
            assert np.array_equal(np.asarray(res.output), want), \
                "corrected output not bit-identical to clean-subset decode"
            out["cases"].append({
                "k": int(k_recv), "corrupted": [int(w) for w in bad],
                "located": found, "corrected": int(res.n_errors_corrected),
                "output_err": err, "bit_consistent": True,
            })
        lines.append(
            f"  k={k_recv:>2}: {len(patterns)} adversarial patterns of "
            f"{max_corr} corrupt workers located+corrected, outputs "
            f"bit-consistent with clean-subset decode")
    # one past the bound: floor((k-m)/2)+1 errors must be REFUSED, not
    # silently mis-corrected
    recv = np.arange(8)
    over = rng.choice(recv, size=(8 - m) // 2 + 1, replace=False)
    b = b_clean.copy()
    b[np.sort(over)] = rng.standard_normal((over.shape[0], s // m)) * 100.0
    res = robust_decode(plan, jnp.asarray(b), recv, tol=1e-8)
    assert not res.ok
    out["over_bound_refused"] = True
    lines.append(f"  k= 8: {over.shape[0]} errors (> bound) refused, ok=False")
    lines.append(f"  bound: correct floor((k-m)/2), detect k-m (m={m})")
    return out


# ----------------------------------------------------------------- measured
_MEASURED_S = 65536
_WARMUP = 3          # cold rounds (deadline bootstrap, pool spin-up, jit)
#                      excluded from the latency percentiles


def _measured_service(rate: float, *, require_all: bool,
                      rounds: int, seed: int) -> tuple[FFTService, list]:
    n = 8
    # kill-only for the rate sweep: a killed worker frees its pool thread
    # immediately, so re-dispatch timing measures the PROTOCOL, not thread
    # starvation behind sleeping delay-fault workers (delays are covered
    # by the deadline-mask tests; masks handle them without retries)
    faults = (FaultPlan.random(n, rate, kinds=("kill",),
                               horizon=rounds + 8, seed=seed)
              if rate > 0 else None)
    # s large enough that per-worker FFT compute dominates thread-
    # scheduling jitter -- at tiny s the m-th-of-k order statistic is all
    # scheduler noise and the p99 ratio measures the OS, not the protocol
    s = _MEASURED_S
    svc = FFTService(FFTServiceConfig(
        s=s, m=4, n_workers=n, dtype=jnp.complex128, use_reference=True,
        autotune=False, seed=seed, measured=True, faults=faults,
        require_all=require_all, on_failure="degrade",
        max_retries=0 if require_all else 2))
    rng = np.random.default_rng(seed)
    xs = [(rng.normal(size=s) + 1j * rng.normal(size=s))
          for _ in range(rounds)]
    return svc, xs


def _run_measured(rate: float, *, require_all: bool, rounds: int) -> dict:
    svc, xs = _measured_service(rate, require_all=require_all,
                                rounds=rounds + _WARMUP, seed=7)
    lat, failed = [], 0
    for i, x in enumerate(xs):
        before = svc.stats.coded_latency
        y = svc.submit(jnp.asarray(x))
        # per-round MEASURED time-to-threshold (thread arrival clock),
        # via the stats delta -- not a model draw
        if i >= _WARMUP:
            lat.append(svc.stats.coded_latency - before)
        if isinstance(y, DegradedResult):
            if i >= _WARMUP:
                failed += 1
        else:
            assert np.abs(y - np.fft.fft(x)).max() < 1e-6
    lat_ms = np.sort(np.asarray(lat)) * 1e3
    return {
        "fault_rate": rate,
        "require_all": require_all,
        "rounds": rounds,
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p99_ms": float(np.percentile(lat_ms, 99)),
        "failed_rounds": failed,
        "retries": svc.stats.retries,
        "redispatched_shards": svc.stats.redispatched_shards,
    }


def _measured_section(lines: list[str]) -> dict:
    n = 8
    rounds = 10 if SMOKE else 120
    lines.append(f"  -- measured runtime (thread-per-worker, N={n} m=4, "
                 f"{rounds} rounds/point) --")
    out: dict = {"coded": [], "uncoded": []}
    for rate in (0.0, 1 / n, 2 / n):
        r = _run_measured(rate, require_all=False, rounds=rounds)
        out["coded"].append(r)
        lines.append(
            f"  coded   rate={rate:.3f}: p50 {r['p50_ms']:6.2f} ms, "
            f"p99 {r['p99_ms']:6.2f} ms, failed {r['failed_rounds']}, "
            f"retries {r['retries']}, redispatched {r['redispatched_shards']}")
    for rate in (0.0, 1 / n):
        r = _run_measured(rate, require_all=True, rounds=rounds)
        out["uncoded"].append(r)
        lines.append(
            f"  uncoded rate={rate:.3f}: p50 {r['p50_ms']:6.2f} ms, "
            f"p99 {r['p99_ms']:6.2f} ms, failed {r['failed_rounds']} "
            f"(require_all: every worker load-bearing)")

    p99_0 = out["coded"][0]["p99_ms"]
    p99_1 = out["coded"][1]["p99_ms"]
    ratio = p99_1 / p99_0
    unc_failed = out["uncoded"][1]["failed_rounds"]
    out["p99_ratio_rate_1_over_n"] = ratio
    lines.append(
        f"  coded p99 @ rate 1/N vs no-fault: {ratio:.2f}x "
        f"(acceptance <= 1.5x); uncoded failed {unc_failed}/{rounds} "
        f"rounds under the same plan")
    if not SMOKE:
        assert ratio <= 1.5, (
            f"coded p99 degraded {ratio:.2f}x under fault rate 1/N "
            f"(acceptance: <= 1.5x with N - m = 4 slack)")
        assert out["coded"][1]["failed_rounds"] == 0, \
            "coded path degraded requests at fault rate 1/N"
        assert unc_failed > 0, (
            "uncoded require_all baseline should fail rounds at fault "
            "rate 1/N -- fault plan never fired?")
    return out


def run() -> list[str]:
    with jax.experimental.enable_x64():
        return _run_x64()


def _run_x64() -> list[str]:
    lines = ["bench_fault_tolerance: Byzantine errors + measured stragglers"]
    result: dict = {}
    if _want("byzantine"):
        result["byzantine"] = _byzantine_section(lines)
    if _want("measured"):
        result["measured"] = _measured_section(lines)
    result["versions"] = _versions()
    if SMOKE or ONLY:
        lines.append("  [BENCH_SMOKE/BENCH_ONLY: artifact not written]")
        return lines
    out_path = pathlib.Path(__file__).resolve().parent.parent / "BENCH_faults.json"
    history: list = []
    if out_path.exists():
        try:
            prev = json.loads(out_path.read_text())
            history = prev.pop("history", [])
            history.append(prev)
        except (json.JSONDecodeError, AttributeError):
            pass
    result["history"] = history
    out_path.write_text(json.dumps(result, indent=2) + "\n")
    lines.append(f"  [written to {out_path}]")
    return lines


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(pathlib.Path(__file__).resolve().parents[1])
    print("\n".join(run()))
