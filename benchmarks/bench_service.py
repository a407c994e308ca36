"""End-to-end FFT service under straggler injection (the paper's Fig. 1
story): request latency waiting for the fastest m workers vs waiting for
all N, with decode correctness verified against jnp.fft on every request.

Also measures the batched scheduler (DESIGN.md §5): wall-clock throughput
of ``submit_batch`` (one jitted encode/decode per (s, m) bucket) vs the
sequential per-request path, emitted to ``BENCH_service.json`` for the
perf trajectory.

The ``open_loop`` section (DESIGN.md §11) is the SLO story: a Poisson
arrival trace drives the streaming front-end (deadline-aware continuous
batching + double-buffered staging) against the naive fill-only /
synchronous-staging baseline IN THE SAME RUN, reporting p50/p99 latency
vs offered load.  The acceptance claim -- streaming p99 at mid-load at
least 1.3x better than the baseline -- is asserted on every full run.
"""

from __future__ import annotations

import gc
import json
import os
import pathlib
import platform
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.straggler import StragglerModel
from repro.serving import FFTService, FFTServiceConfig, ServiceStats

# BENCH_SMOKE=1 (the CI bench-smoke job): few requests/reps, NO artifact
# write -- structural + correctness signal only, fast enough to gate PRs
SMOKE = os.environ.get("BENCH_SMOKE", "") == "1"
# BENCH_ONLY=<section> runs a single section (stragglers | batched |
# open_loop) for a focused CI signal; implies no artifact write
ONLY = os.environ.get("BENCH_ONLY", "")


def _want(section: str) -> bool:
    return not ONLY or ONLY == section


def _versions() -> dict:
    """Stamp each BENCH_service.json entry so trajectory rows are
    comparable across CI runners (jax/platform drift is the usual
    explanation for a mystery step in the curves)."""
    import jaxlib

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "machine": platform.machine(),
    }


def _requests(n, s, key):
    xs = []
    for _ in range(n):
        key, k1, k2 = jax.random.split(key, 3)
        xs.append((jax.random.normal(k1, (s,))
                   + 1j * jax.random.normal(k2, (s,))).astype(jnp.complex64))
    return xs, key


def _straggler_section(lines: list[str]) -> None:
    for mu in ((1.0,) if SMOKE else (2.0, 1.0, 0.5)):
        svc = FFTService(FFTServiceConfig(
            s=2048, m=4, n_workers=8,
            straggler=StragglerModel(t0=1.0, mu=mu), seed=0))
        key = jax.random.PRNGKey(0)
        xs, key = _requests(8 if SMOKE else 30, 2048, key)
        worst = 0.0
        for x in xs:
            y = svc.submit(x)
            worst = max(worst, float(jnp.max(jnp.abs(y - jnp.fft.fft(x)))))
        st = svc.stats.summary()
        lines.append(
            f"  mu={mu:<4} {len(xs)} reqs: coded "
            f"{st['mean_coded_latency']:.3f}s vs "
            f"uncoded {st['mean_uncoded_latency']:.3f}s "
            f"({st['speedup']:.2f}x), {st['stragglers_tolerated']} stragglers "
            f"tolerated, worst err {worst:.1e}")
        assert worst < 1e-2


def _batched_sections(result: dict, lines: list[str]) -> None:
    # ---- batched scheduler throughput (DESIGN.md §5/§8) ---------------------
    n_req, s = (16 if SMOKE else 64), 2048
    cfg = FFTServiceConfig(s=s, m=4, n_workers=8,
                           straggler=StragglerModel(t0=1.0, mu=1.0),
                           seed=0, max_batch=64)
    key = jax.random.PRNGKey(1)
    xs, key = _requests(n_req, s, key)

    seq = FFTService(cfg)
    jax.block_until_ready(seq.submit(xs[0]))           # compile warm-up
    seq.stats = ServiceStats()                         # stats = timed run only
    t0 = time.perf_counter()
    outs_seq = [seq.submit(x) for x in xs]
    jax.block_until_ready(outs_seq[-1])
    dt_seq = time.perf_counter() - t0

    bat = FFTService(cfg)
    jax.block_until_ready(bat.submit_batch(xs)[-1])    # compile warm-up
    bat.stats = ServiceStats()                         # stats = timed run only
    t0 = time.perf_counter()
    outs_bat = bat.submit_batch(xs)
    jax.block_until_ready(outs_bat[-1])
    dt_bat = time.perf_counter() - t0

    worst = max(float(jnp.max(jnp.abs(y - jnp.fft.fft(x))))
                for x, y in zip(xs, outs_bat))
    assert worst < 1e-2
    bat_stats = bat.stats.summary()
    result.update({
        "s": s,
        "m": cfg.m,
        "n_workers": cfg.n_workers,
        "n_requests": n_req,
        "sequential_s": dt_seq,
        "batched_s": dt_bat,
        "sequential_rps": n_req / dt_seq,
        "batched_rps": n_req / dt_bat,
        "batch_speedup": dt_seq / dt_bat,
        "batches": bat_stats["batches"],
        # the async-pipeline observables (DESIGN.md §8): dispatch vs sync
        # wall split and ONE device->host transfer per submit_batch call
        "dispatch_s": bat_stats["dispatch_s"],
        "sync_s": bat_stats["sync_s"],
        "host_transfers": bat_stats["host_transfers"],
        "decode_cache_misses": bat_stats["decode_cache_misses"],
    })

    # ---- real-input (r2c) bucket config (DESIGN.md §7) ----------------------
    # same shape, REAL traffic: half-payload worker shards through the
    # r2c executor vs serving the same signals as complex requests
    rng = np.random.default_rng(7)
    xs_real = [jnp.asarray(rng.normal(size=s).astype(np.float32))
               for _ in range(n_req)]
    rsvc = FFTService(cfg)
    outs_r = rsvc.submit_batch(xs_real, kind="r2c")     # compile warm-up
    worst_r = max(
        float(np.abs(y - np.fft.rfft(np.asarray(x))).max())
        for x, y in zip(xs_real, outs_r))
    assert worst_r < 1e-2
    xs_cplx = [x.astype(jnp.complex64) for x in xs_real]
    rsvc.submit_batch(xs_cplx)                          # compile warm-up
    t_r2c, t_c2c = [], []
    for r in range(4 if SMOKE else 10):
        order = ((("r2c",), t_r2c), (("c2c",), t_c2c))
        for (kind,), acc in (order if r % 2 == 0 else order[::-1]):
            t0 = time.perf_counter()
            if kind == "r2c":
                rsvc.submit_batch(xs_real, kind="r2c")
            else:
                rsvc.submit_batch(xs_cplx)
            acc.append(time.perf_counter() - t0)

    r_med, c_med = statistics.median(t_r2c), statistics.median(t_c2c)
    result["rfft"] = {
        "s": s, "m": cfg.m, "n_workers": cfg.n_workers,
        "n_requests": n_req,
        "r2c_rps": n_req / r_med,
        "c2c_on_real_rps": n_req / c_med,
        "speedup_vs_c2c_on_real": c_med / r_med,
        "worker_payload_bytes_r2c": (s // cfg.m // 2) * 8,
        "worker_payload_bytes_c2c": (s // cfg.m) * 8,
        "worst_abs_err": worst_r,
    }
    lines.append(
        f"  rfft bucket: {n_req} real reqs {r_med * 1e3:.1f} ms "
        f"({n_req / r_med:.0f} rps) vs c2c-on-real {c_med * 1e3:.1f} ms "
        f"({n_req / c_med:.0f} rps) -> "
        f"{c_med / r_med:.2f}x, worst err {worst_r:.1e}")

    # ---- n-D real (rfftn) buckets (DESIGN.md §9) ----------------------------
    # 2-D real fields served end-to-end through the rfftn kind: the service
    # buckets by the shape tuple and runs the generic jitted plan executor
    # (half-payload packed shards, per-request straggler masks)
    nd_shape = (32, 32) if SMOKE else (64, 64)
    nd_req = 8 if SMOKE else 32
    tsn = [jnp.asarray(rng.normal(size=nd_shape).astype(np.float32))
           for _ in range(nd_req)]
    ndsvc = FFTService(cfg)
    outs_n = ndsvc.submit_batch(tsn, kind="rfftn")      # compile warm-up
    axes = tuple(range(-len(nd_shape), 0))
    worst_n = max(
        float(np.abs(y - np.fft.rfftn(np.asarray(t, np.float64),
                                      axes=axes)).max())
        for t, y in zip(tsn, outs_n))
    assert worst_n < 1e-2
    ysn = [jnp.asarray(np.fft.rfftn(np.asarray(t)).astype(np.complex64))
           for t in tsn]
    ndsvc.submit_batch(ysn, kind="irfftn")              # compile warm-up
    t_nd = []
    for _ in range(4 if SMOKE else 8):
        t0 = time.perf_counter()
        ndsvc.submit_batch(tsn, kind="rfftn")
        t_nd.append(time.perf_counter() - t0)
    nd_med = statistics.median(t_nd)
    shard_elems = int(np.prod(
        ndsvc._plan_for(nd_shape, "rfftn").worker_shard_shape))
    result["rfftn"] = {
        "shape": list(nd_shape), "m": cfg.m, "n_workers": cfg.n_workers,
        "n_requests": nd_req,
        "rfftn_rps": nd_req / nd_med,
        "worker_payload_bytes_rfftn": shard_elems * 8,
        "worker_payload_bytes_c2cn": shard_elems * 2 * 8,
        "worst_abs_err": worst_n,
    }
    lines.append(
        f"  rfftn bucket: {nd_req} real {nd_shape} reqs "
        f"{nd_med * 1e3:.1f} ms ({nd_req / nd_med:.0f} rps), "
        f"payload {shard_elems * 8 // 1024}KiB vs "
        f"{shard_elems * 2 * 8 // 1024}KiB/worker shard (c2cn), "
        f"worst err {worst_n:.1e}")
    if SMOKE:
        lines.append(
            f"  batched scheduler (smoke): {n_req} reqs in {dt_bat * 1e3:.1f} "
            f"ms [BENCH_SMOKE=1: artifact not written]")
    else:
        lines.append(
            f"  batched scheduler: {n_req} reqs in {dt_bat * 1e3:.1f} ms "
            f"({result['batched_rps']:.0f} rps) vs sequential "
            f"{dt_seq * 1e3:.1f} ms ({result['sequential_rps']:.0f} rps) "
            f"-> {result['batch_speedup']:.2f}x")


def _open_loop_section(lines: list[str]) -> dict:
    """Poisson arrival trace -> p50/p99 latency vs offered load, streaming
    front-end vs the naive (fill-only, synchronous-staging) baseline
    measured in the SAME run (DESIGN.md §11)."""
    from repro.serving.streaming import (
        AdmissionError,
        StreamConfig,
        StreamingFFTService,
    )

    s = 512 if SMOKE else 2048
    cfg = FFTServiceConfig(s=s, m=4, n_workers=8,
                           straggler=StragglerModel(t0=1.0, mu=1.0),
                           seed=0, max_batch=32)
    svc = FFTService(cfg)
    # precompile every power-of-two bucket: a cold compile inside a
    # latency window would swamp the queueing signal being measured
    svc.warmup()
    rng = np.random.default_rng(11)
    pool = [(rng.normal(size=s)
             + 1j * rng.normal(size=s)).astype(np.complex64)
            for _ in range(32)]
    rates = [300] if SMOKE else [500, 1000, 2000]
    n_per = 40 if SMOKE else 600
    slack = 0.005
    modes = {
        "streaming": StreamConfig(slack_s=slack),
        # the before-this-PR story: dispatch only full buckets, stage
        # synchronously -- batch rps is identical, the tail is not
        "naive": StreamConfig(slack_s=slack, fill_only=True,
                              pipelined=False),
    }
    out = {"s": s, "m": cfg.m, "n_workers": cfg.n_workers,
           "max_batch": cfg.max_batch, "slack_ms": slack * 1e3,
           "n_per_rate": n_per, "curves": {}}
    for mode, scfg in modes.items():
        curve = []
        for rate in rates:
            svc.stats = ServiceStats()       # fresh window per drive
            stream = StreamingFFTService(svc, scfg)
            arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_per))
            futs, rejected = [], 0
            t0 = time.perf_counter()
            for i, t_arr in enumerate(arrivals):
                lag = t_arr - (time.perf_counter() - t0)
                if lag > 0:
                    time.sleep(lag)
                try:
                    futs.append((i, stream.submit(pool[i % len(pool)])))
                except AdmissionError:
                    rejected += 1
            stream.drain()
            stream.close()
            lats = np.asarray([f.latency_s for _, f in futs])
            worst = max(
                float(np.abs(f.result()
                             - np.fft.fft(pool[i % len(pool)])).max())
                for i, f in futs[:8])
            assert worst < 1e-2
            st = svc.stats.summary()
            # structural invariants of the streaming path: nothing lost,
            # ONE device->host transfer per dispatched bucket
            assert len(futs) + rejected == n_per
            assert st["host_transfers"] == st["batches"]
            assert st["latency"]["count"] == len(futs)
            curve.append({
                "offered_rps": rate,
                "n_offered": n_per,
                "completed": len(futs),
                "rejected": rejected,
                "p50_ms": float(np.percentile(lats, 50) * 1e3),
                "p99_ms": float(np.percentile(lats, 99) * 1e3),
                "mean_ms": float(lats.mean() * 1e3),
                "buckets": st["batches"],
                "fill_dispatches": st["fill_dispatches"],
                "deadline_dispatches": st["deadline_dispatches"],
                "drain_dispatches": st["drain_dispatches"],
                "queue_peak": st["queue_peak"],
            })
            lines.append(
                f"  open-loop[{mode}] {rate} rps: p50 "
                f"{curve[-1]['p50_ms']:.1f} ms, p99 "
                f"{curve[-1]['p99_ms']:.1f} ms "
                f"({curve[-1]['completed']}/{n_per} ok, "
                f"{rejected} rejected, "
                f"{st['deadline_dispatches']}/{st['fill_dispatches']}"
                f"/{st['drain_dispatches']} ddl/fill/drain)")
        out["curves"][mode] = curve
    mid = len(rates) // 2
    ratio = (out["curves"]["naive"][mid]["p99_ms"]
             / out["curves"]["streaming"][mid]["p99_ms"])
    out["mid_load_rps"] = rates[mid]
    out["p99_naive_over_streaming_mid_load"] = ratio
    lines.append(
        f"  open-loop p99 @ {rates[mid]} rps: naive/streaming = "
        f"{ratio:.2f}x (acceptance floor 1.3x)")
    if not SMOKE:
        assert ratio >= 1.3, (
            f"streaming p99 must beat the fill-only baseline by >=1.3x "
            f"at mid-load; measured {ratio:.2f}x")

    # ---- mixed-tier EDF scheduling (DESIGN.md §11) ----------------------
    # the SAME Poisson trace twice at equal offered load: single-tier
    # (everything at the standard slack) vs multi-tier EDF (interactive /
    # standard / batch classes).  The acceptance claim: the interactive
    # tier's p99 under EDF must not exceed the single-tier baseline p99.
    rate = 300 if SMOKE else 1000
    n_mix = 60 if SMOKE else 600
    tiers = {"interactive": 0.002, "standard": slack, "batch": 0.050}
    tier_names = np.asarray(["interactive", "standard", "batch"])
    draw = rng.choice(3, size=n_mix, p=[0.3, 0.5, 0.2])
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_mix))
    mixed = {"offered_rps": rate, "n_offered": n_mix,
             "tiers_ms": {k: v * 1e3 for k, v in tiers.items()},
             "tier_mix": {str(t): int((draw == i).sum())
                          for i, t in enumerate(tier_names)}}
    for mode in ("single_tier", "multi_tier"):
        svc.stats = ServiceStats()
        stream = StreamingFFTService(
            svc, StreamConfig(slack_s=slack, tiers=tiers))
        futs, rejected = [], 0
        # collector pause != queueing: at a 2 ms interactive slack, one
        # gen-2 GC sweep over the earlier sections' jaxpr graphs shows
        # up as a multi-ms p99 outlier, so sweep NOW and hold the
        # collector off for the (sub-second) timed drive
        gc.collect()
        gc.disable()
        t0 = time.perf_counter()
        for i, t_arr in enumerate(arrivals):
            lag = t_arr - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)
            tier = ("standard" if mode == "single_tier"
                    else str(tier_names[draw[i]]))
            try:
                futs.append((tier, stream.submit(pool[i % len(pool)],
                                                 tier=tier)))
            except AdmissionError:
                rejected += 1
        stream.drain()
        stream.close()
        gc.enable()
        st = svc.stats.summary()
        assert len(futs) + rejected == n_mix
        assert st["latency"]["count"] == len(futs)
        lats = {}
        for tier, f in futs:
            lats.setdefault(tier, []).append(f.latency_s)
        per_tier = {
            tier: {"count": len(v),
                   "p50_ms": float(np.percentile(v, 50) * 1e3),
                   "p99_ms": float(np.percentile(v, 99) * 1e3)}
            for tier, v in sorted(lats.items())}
        mixed[mode] = {
            "completed": len(futs), "rejected": rejected,
            "p99_all_ms": float(np.percentile(
                [f.latency_s for _, f in futs], 99) * 1e3),
            "per_tier": per_tier,
            # the histogram-side view (per-tier LatencyHistogram): counts
            # must agree with the exact per-future percentiles above
            "hist_tiers": {k: {"count": v["count"],
                               "p99_ms": v["p99_s"] * 1e3}
                           for k, v in st["tiers"].items()},
        }
        for tier, v in per_tier.items():
            assert st["tiers"][tier]["count"] == v["count"]
        lines.append(
            f"  mixed-tier[{mode}] {rate} rps: "
            + ", ".join(f"{t} p99 {v['p99_ms']:.1f} ms (n={v['count']})"
                        for t, v in per_tier.items()))
    gain = (mixed["single_tier"]["p99_all_ms"]
            / mixed["multi_tier"]["per_tier"]["interactive"]["p99_ms"])
    mixed["interactive_p99_gain_vs_single_tier"] = gain
    lines.append(
        f"  mixed-tier interactive p99 vs single-tier baseline p99 @ "
        f"{rate} rps: {gain:.2f}x (acceptance floor 1.0x)")
    if not SMOKE:
        assert (mixed["multi_tier"]["per_tier"]["interactive"]["p99_ms"]
                <= mixed["single_tier"]["p99_all_ms"]), (
            "interactive-tier p99 under EDF must not exceed the "
            "single-tier baseline p99 at equal offered load")
    out["mixed_tier"] = mixed
    return out


def run() -> list[str]:
    lines = ["bench_service: coded FFT serving with stragglers"]
    result: dict = {}
    if _want("stragglers"):
        _straggler_section(lines)
    if _want("batched"):
        _batched_sections(result, lines)
    if _want("open_loop"):
        result["open_loop"] = _open_loop_section(lines)
    result["versions"] = _versions()
    if SMOKE or ONLY:
        return lines
    # anchor to the repo root so the tracked artifact updates regardless of cwd
    out_path = pathlib.Path(__file__).resolve().parent.parent / "BENCH_service.json"
    # append to the perf trajectory rather than overwrite: the previous runs
    # move into "history" (oldest first), the current run stays top-level
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
