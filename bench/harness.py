"""One run of one cell: set-up, the measured window, the check, the metrics.

Everything that belongs to one configuration, one traffic mix or one
metric is found by name: ``BENCHMARK.json`` names the cell's
configuration file and mix, the mix is ``bench/traffic/<mix>.json``, and
each metric is read by ``bench/metrics/<metric>.py``.  Nothing here
branches on a cell's name.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
from typing import Callable, Optional

import numpy as np

from bench import check, generator, trace as tr

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE = ROOT / ".bench_cache" / "jax"
STAT_FIELDS = ("requests", "batches", "dispatch_s", "sync_s",
               "fill_dispatches", "deadline_dispatches", "queue_peak")


class BenchError(RuntimeError):
    """A run that cannot be made: no chip, a missing file, a bad spec."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# -- the spec --------------------------------------------------------------
def load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise BenchError(f"missing {path}") from e


def cell_spec(name: str, root: pathlib.Path = ROOT) -> tuple:
    """``(spec, cell, config, mix)`` of one cell of ``BENCHMARK.json``."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, config, mix


def metrics_of(spec: dict, cell: str, traced: bool) -> list[dict]:
    """The cell's metrics: end-to-end untraced, per-layer traced."""
    entries = spec["per_layer" if traced else "end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def reader(name: str, root: pathlib.Path = ROOT) -> Callable:
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader for metric {name!r} at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peak_of(kind: str, root: pathlib.Path = ROOT) -> dict:
    peaks = load_json(root / "bench" / "peaks.json")
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


# -- what a metric reader sees ---------------------------------------------
@dataclasses.dataclass
class Run:
    """One finished run, as the metric readers see it."""

    config: dict
    setup_s: float
    record: generator.Record
    stats: dict               # ServiceStats deltas over the window
    reduced: Optional[tr.Reduced]
    peak: Optional[dict]

    @property
    def kind(self) -> str:
        return self.config["kind"]

    @property
    def s(self) -> int:
        return int(self.config["s"])

    @property
    def max_batch(self) -> int:
        return int(self.config["service"]["max_batch"])

    @property
    def window_s(self) -> float:
        return self.record.t_close - self.record.t_open

    @property
    def completed_in_window(self) -> int:
        r = self.record
        ok = ~r.failed & (r.done > r.t_open) & (r.done <= r.t_close)
        return int(ok.sum())

    @property
    def latencies_s(self) -> np.ndarray:
        """Due to resolved, for every request due in the window; one that
        never resolved counts until the loop stopped waiting."""
        r = self.record
        due_in = (r.due >= r.t_open) & (r.due < r.t_close)
        done = np.where(np.isnan(r.done) | r.failed, r.gave_up, r.done)
        return (done - r.due)[due_in]

    def per_bucket_ms(self, field: str) -> Optional[float]:
        b = self.stats["batches"]
        return self.stats[field] / b * 1e3 if b else None


def snapshot(stats) -> dict:
    return {f: getattr(stats, f) for f in STAT_FIELDS}


class _Hooks:
    """Opens and closes the window: stats snapshots and the trace span."""

    def __init__(self, stats, traced: bool):
        self.stats = stats
        self.traced = traced
        self.ann = None
        self.at_open = self.at_close = None
        self.compiles = 0
        self.in_window = False
        self.gc_t0 = 0.0
        self.gc_s: list[tuple[int, float]] = []   # (generation, seconds)

    def open(self, t: float) -> None:
        self.at_open = snapshot(self.stats)
        self.in_window = True
        if self.traced:
            import jax

            self.ann = jax.profiler.TraceAnnotation(tr.WINDOW)
            self.ann.__enter__()

    def close(self, t: float) -> None:
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        self.in_window = False
        self.at_close = snapshot(self.stats)

    def on_compile(self, event: str, duration: float, **_) -> None:
        if self.in_window and event in (
                "/jax/core/compile/backend_compile_duration",
                "/jax/core/compile/jaxpr_trace_duration"):
            self.compiles += 1

    def on_gc(self, phase: str, info: dict) -> None:
        """Times the garbage collector's passes in the window (a host
        stall the log names)."""
        if phase == "start":
            self.gc_t0 = time.perf_counter()
        elif self.in_window:
            self.gc_s.append((info["generation"],
                              time.perf_counter() - self.gc_t0))

    def delta(self) -> dict:
        d = {f: self.at_close[f] - self.at_open[f] for f in STAT_FIELDS}
        d["queue_peak"] = self.at_close["queue_peak"]
        return d


def warm_buckets(mix: dict, cap: int) -> list[int]:
    """The bucket sizes a mix forms: a closed loop fills every bucket; an
    open loop dispatches on deadlines, so every power of two up to the
    cap."""
    if mix["loop"] == "closed":
        return [cap]
    sizes, b = [], 1
    while b < cap:
        sizes.append(b)
        b *= 2
    return sizes + [cap]


def build_service(config: dict, seed: int, chips: int, devices):
    from jax.sharding import Mesh

    from repro.serving.fft_service import FFTService, FFTServiceConfig

    cfg = FFTServiceConfig(s=int(config["s"]), seed=seed,
                           **config["service"])
    mesh = (Mesh(np.array(devices[:chips]), ("workers",))
            if chips > 1 else None)
    return FFTService(cfg, mesh=mesh)


def uncoded(kind: str, pool: np.ndarray, cap: int) -> tuple[int, float]:
    """A plain ``jnp.fft`` of the same bucket on the same chip, under the
    trace span :data:`trace.UNCODED`: calls queued back to back, doubling
    their number until a batch spans a quarter second.  Returns the calls
    made in the span and the host-clock ms per call of the last batch."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(jnp.fft.rfft if kind == "r2c" else jnp.fft.fft)
    x = jnp.asarray(np.resize(pool, (cap,) + pool.shape[1:]))
    jax.block_until_ready(fn(x))
    reps, total = 1, 0
    with jax.profiler.TraceAnnotation(tr.UNCODED):
        while True:
            t0 = time.perf_counter()
            for _ in range(reps):
                y = fn(x)
            jax.block_until_ready(y)
            t = time.perf_counter() - t0
            total += reps
            if t >= 0.25:
                return total, t / reps * 1e3
            reps *= 2


def run_cell(name: str, seed: int, seconds: float, traced: bool,
             t_launch: float, *, root: pathlib.Path = ROOT,
             require_tpu: bool = True,
             patch: Optional[Callable] = None,
             overrides: Optional[dict] = None) -> dict:
    """Make one run; returns the result object the last line prints.

    ``patch(service)`` and ``overrides`` (``{"config": {...}, "mix":
    {...}}``, merged over the files) exist for the control and the fault
    tests, which put something else in the program's place."""
    import jax

    spec, cell, config, mix = cell_spec(name, root)
    overrides = overrides or {}
    config = {**config, **overrides.get("config", {})}
    mix = {**mix, **overrides.get("mix", {})}
    chips = int(cell["chips"])
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"JAX found platform {dev.platform!r} "
                         f"({dev.device_kind}), not a TPU")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips; JAX found "
                         f"{len(devices)}")
    peak = peak_of(dev.device_kind, root) if require_tpu else None

    from repro.serving.streaming import StreamConfig, StreamingFFTService

    kind, s = config["kind"], int(config["s"])
    cap = int(config["service"]["max_batch"])
    pay_rng, sched_rng, keep_rng = (
        np.random.default_rng(c) for c in np.random.SeedSequence(seed)
        .spawn(3))
    pool = generator.make_pool(config["data"], s, int(mix["pool"]), pay_rng)
    keep = keep_rng.random(1 << 20) < 1.0 / float(mix["check_every"])
    svc = build_service(config, seed, chips, devices)
    if patch is not None:
        patch(svc)
    buckets = warm_buckets(mix, cap)
    t0 = time.perf_counter()
    svc.warmup(lengths=[s], kinds=(kind,), buckets=buckets)
    log(f"warmed buckets {buckets} of {kind} s={s} in "
        f"{time.perf_counter() - t0:.3f} s")

    # what set-up made stays alive all run: leave it out of the collector's
    # full passes, which would otherwise walk it while requests wait
    gc.collect()
    gc.freeze()
    hooks = _Hooks(svc.stats, traced)
    jax.monitoring.register_event_duration_secs_listener(hooks.on_compile)
    gc.callbacks.append(hooks.on_gc)
    trace_dir = root / ".bench_out" / "trace"
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    tier = "bench"
    stream = StreamingFFTService(svc, StreamConfig(
        tiers={tier: float(mix["slack_s"])}, default_tier=tier))
    try:
        if mix["loop"] == "closed":
            rec = generator.closed_loop(stream, pool, kind, tier, mix,
                                        seconds, cap, keep, hooks)
        elif mix["loop"] == "open":
            rec = generator.open_loop(stream, pool, kind, tier, mix,
                                      seconds, keep, sched_rng, hooks)
        else:
            raise BenchError(f"unknown loop {mix['loop']!r}")
        stream.close()
        mem = [(d.memory_stats() or {}).get("peak_bytes_in_use")
               for d in devices[:chips]]
        gc.unfreeze()
        del svc
        gc.collect()
        if traced:
            plain_calls, plain_host_ms = uncoded(kind, pool, cap)
    finally:
        stream.close()
        if traced:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(hooks.on_compile)
        gc.callbacks.remove(hooks.on_gc)
        gc.unfreeze()
    setup_s = rec.t_open - t_launch
    mem_peak = max((m for m in mem if m is not None), default=None)
    stats = hooks.delta()

    failed = int(rec.failed.sum())
    numbers = check.compare(rec.samples, rec.pool_idx, pool,
                            config["reference"],
                            int((rec.failed & ~rec.refused).sum()),
                            config["check"])
    correct = check.passed(numbers)

    reduced = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": rec.n, "failed": failed}
    if traced:
        trace_data = tr.load(tr.find_xplane(str(trace_dir)))
        reduced = tr.reduce(trace_data)
        device["busy_s"] = tr.mean(reduced.busy_s)
        device["window_s"] = reduced.window_s
    run = Run(config, setup_s, rec, stats, reduced, peak)
    metrics = {}
    for m in metrics_of(spec, name, traced):
        value = reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if traced:
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
        coded = (sum(reduced.busy_s) / stats["batches"] * 1e3
                 if stats["batches"] else math.nan)
        plain = tr.span_busy_s(trace_data, tr.UNCODED) / plain_calls * 1e3
        log(f"device ms per bucket of {cap}: coded {coded!r} (window busy "
            f"/ buckets), uncoded jnp.fft {plain!r} ({plain_calls} calls; "
            f"host clock {plain_host_ms!r}); coded/uncoded "
            f"{coded / plain!r}")
    late = rec.sent - rec.due
    log(f"window {run.window_s!r} s: {rec.n} requests, "
        f"{run.completed_in_window} completed in the window, "
        f"{int(rec.refused.sum())} refused, "
        f"{stats['batches']} buckets (fill {stats['fill_dispatches']}, "
        f"deadline {stats['deadline_dispatches']}), queue peak "
        f"{stats['queue_peak']}, compiles in the window {hooks.compiles}, "
        f"collector passes in the window {len(hooks.gc_s)} (full "
        f"{sum(g == 2 for g, _ in hooks.gc_s)}, longest "
        f"{max((t for _, t in hooks.gc_s), default=0.0) * 1e3!r} ms), "
        f"generator late p99 {np.nanpercentile(late, 99) * 1e3!r} ms "
        f"max {np.nanmax(late) * 1e3!r} ms")
    result["checks"] = check.json_safe(numbers)
    return result
