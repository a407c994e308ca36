"""Find the knee of an open-loop cell: the most streams it serves in time.

    python3 bench/sweep.py --workload stft_librosa.stream --seconds 5 \
        --streams 100 200 400 ...

On the chip, in one process: the cell's service is built and warmed once,
then the cell's mix is offered at each stream count in turn, each for
``--seconds``.  A count is sustained when the 95th percentile latency,
from due to resolved, is within the mix's ``latency_limit_s`` and the
backlog does not grow: the requests outstanding at the window's close
exceed those at its middle by less than one bucket.  The knee is the
highest count sustained; the cell's mix then takes 0.8 of it.  The sweep
stops after two counts in a row fail.  Every count tried is printed, and
the whole table written to ``.bench_out/sweep_<cell>.json`` in the
checkout.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def outstanding(rec, t: float) -> int:
    return int((rec.sent <= t).sum() - (rec.done <= t).sum())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--streams", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    from bench import generator, harness
    from repro.serving.streaming import StreamConfig, StreamingFFTService

    harness.CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(harness.CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _, cell, config, mix = harness.cell_spec(args.workload)
    if mix["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"JAX found {devices[0].platform!r}, not a TPU")
    kind, s = config["kind"], int(config["s"])
    cap = int(config["service"]["max_batch"])
    limit = float(mix["latency_limit_s"])
    rng = np.random.default_rng(args.seed)
    pool = generator.make_pool(config["data"], s, int(mix["pool"]), rng)
    svc = harness.build_service(config, args.seed, int(cell["chips"]),
                                devices)
    svc.warmup(lengths=[s], kinds=(kind,),
               buckets=harness.warm_buckets(mix, cap))
    keep = np.zeros(1, bool)
    rows, misses = [], 0
    for streams in args.streams:
        hooks = harness._Hooks(svc.stats, False)
        stream = StreamingFFTService(svc, StreamConfig(
            tiers={"bench": float(mix["slack_s"])}, default_tier="bench"))
        try:
            rec = generator.open_loop(
                stream, pool, kind, "bench", {**mix, "streams": streams},
                args.seconds, keep, np.random.default_rng(args.seed + streams),
                hooks)
        finally:
            stream.close()
        lat = np.where(np.isnan(rec.done), rec.gave_up, rec.done) - rec.due
        mid = outstanding(rec, rec.t_open + args.seconds / 2)
        end = outstanding(rec, rec.t_close)
        d = hooks.delta()
        row = {
            "streams": streams,
            "offered_per_s": streams / float(mix["period_s"]),
            "completed_per_s": float(((rec.done >= rec.t_open)
                                      & (rec.done < rec.t_close)).sum()
                                     / args.seconds),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "outstanding_mid": mid, "outstanding_close": end,
            "failed": int(rec.failed.sum()),
            "buckets": d["batches"],
            "mean_bucket": d["requests"] / max(d["batches"], 1),
            "late_p99_ms": float(np.percentile(rec.sent - rec.due, 99)
                                 * 1e3),
        }
        row["sustained"] = bool(row["p95_ms"] <= limit * 1e3
                                and end - mid < cap
                                and row["failed"] == 0)
        rows.append(row)
        print(f"[sweep] {json.dumps(row)}", flush=True)
        misses = 0 if row["sustained"] else misses + 1
        if misses >= 2:
            break
    ok = [r["streams"] for r in rows if r["sustained"]]
    knee = max(ok) if ok else None
    summary = {"workload": args.workload, "seconds": args.seconds,
               "limit_ms": limit * 1e3, "knee_streams": knee,
               "cell_streams": int(0.8 * knee) if knee else None,
               "device": devices[0].device_kind, "rows": rows}
    out = ROOT / ".bench_out"
    out.mkdir(exist_ok=True)
    (out / f"sweep_{args.workload}.json").write_text(
        json.dumps(summary, indent=2))
    print(f"[sweep] knee={knee} streams; cell at 0.8: "
          f"{summary['cell_streams']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
