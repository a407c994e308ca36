"""The program's spans, from a traced run.

:mod:`bench.trace` keeps ``(name, start_ns, end_ns)`` for every event.  The
per-layer metrics of the bucket service need more of the program's host
spans (``fft.*``, opened with ``jax.profiler.TraceAnnotation``): their
arguments (``bucket`` ties a bucket's spans together) and the thread each
ran on.  They are read here into :class:`Spans`.  A span counts in the
window when it **starts** inside it, and then counts whole, so a mean per
bucket is a mean over whole spans.

A reader gets its run's :class:`Spans` from :func:`of`.

    python3 -m bench.spans [.bench_out/trace]

prints the mean of each span in the window, one bucket's timeline on the
trace's clock, and the spans that cover each of the ten longest idle gaps
of the first device.
"""

from __future__ import annotations

import dataclasses
import os
import pathlib
import sys
from typing import Optional

from bench import trace as tr

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_out" / "trace"
PREFIX = "fft."           # the program's span names (serving/spans.py)


@dataclasses.dataclass
class Spans:
    window: tuple                 # (start_ns, end_ns) of ``bench.window``
    host: dict                    # span name -> [(start, end, args, line)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def started(sp: Spans, name: str) -> list:
    """The spans named ``name`` that start inside the window, whole:
    ``(start, end, args, line)``."""
    lo, hi = sp.window
    return [s for s in sp.host.get(name, []) if lo <= s[0] < hi]


def durations_s(sp: Spans, name: str) -> list[float]:
    """Seconds of each span named ``name`` that starts in the window."""
    return [(b - a) * 1e-9 for a, b, _, _ in started(sp, name)]


def mean_ms(sp: Optional[Spans], name: str) -> Optional[float]:
    """Mean of :func:`durations_s` in ms; None without such a span."""
    if sp is None:
        return None
    d = durations_s(sp, name)
    return sum(d) / len(d) * 1e3 if d else None


def load(path: str) -> Spans:
    """Read the window and the ``fft.*`` host spans of an ``.xplane.pb``
    (needs only JAX); ``line`` numbers the host thread a span ran on."""
    from jax.profiler import ProfileData

    window, host = None, {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name
                if name == tr.WINDOW and window is None:
                    window = (int(e.start_ns), int(e.end_ns))
                elif name.startswith(PREFIX):
                    host.setdefault(name, []).append(
                        (int(e.start_ns), int(e.end_ns), dict(e.stats), i))
    if window is None:
        raise ValueError(f"the trace holds no host span named {tr.WINDOW!r}")
    return Spans(window, host)


_cache: dict = {}


def of(run) -> Optional[Spans]:
    """The spans of a traced run, None for an untraced one.

    A run that carries its own ``spans`` is read as it is.  Otherwise the
    trace the harness left under ``.bench_out/trace`` is read once, and
    used only if its window is the run's: a trace of another run is never
    read in its place."""
    if getattr(run, "spans", None) is not None:
        return run.spans
    if run.reduced is None:
        return None
    try:
        path = tr.find_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = load(path)
    sp = _cache[key]
    return sp if abs(sp.window_s - run.reduced.window_s) < 1e-9 else None


# -- what ``python3 -m bench.spans`` prints --------------------------------
def timeline(sp: Spans, bucket: int) -> list[tuple]:
    """``(name, start_ns, end_ns, args)`` of one bucket's spans and the
    children that lie inside them on their thread."""
    out = []
    for name, spans in sp.host.items():
        for a, b, args, line in spans:
            if args.get("bucket") != bucket:
                continue
            out.append((name, a, b, args))
            for child, cspans in sp.host.items():
                out += [(child, c, d, cargs) for c, d, cargs, cl in cspans
                        if "bucket" not in cargs and cl == line and a <= c
                        and d <= b]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def covering(sp: Spans, a: int, b: int) -> tuple[float, list[str]]:
    """The share of ``[a, b]`` inside some program span, and the spans
    with a ``bucket`` that overlap it, in order, with the ms of overlap."""
    events = [(n, s, e) for n, spans in sp.host.items()
              for s, e, _, _ in spans]
    share = tr.busy_ns(tr.clip(events, a, b)) / (b - a)
    named = sorted((s, f"{n}[{args['bucket']}] "
                       f"{(min(b, e) - max(a, s)) * 1e-6:.1f}")
                   for n, spans in sp.host.items()
                   for s, e, args, _ in spans
                   if "bucket" in args and min(b, e) > max(a, s))
    return share, [label for _, label in named]


def main(argv: list[str]) -> int:
    log_dir = argv[0] if argv else str(TRACE_DIR)
    path = tr.find_xplane(log_dir)
    sp, trace = load(path), tr.load(path)
    lo, hi = sp.window
    print(f"window {sp.window_s!r} s; spans started in it (count, mean ms):")
    for name in sorted(sp.host):
        print(f"  {name:20s} {len(started(sp, name)):4d} "
              f"{mean_ms(sp, name)!r}")
    dev = trace.devices()[0]
    ops = sorted(tr.clip(trace.ops(dev), lo, hi), key=lambda e: e[1])
    forms = started(sp, "fft.bucket.form")
    if forms:
        bucket = forms[len(forms) // 2][2]["bucket"]
        rows = [(n, a, b, str(args)) for n, a, b, args in
                timeline(sp, bucket)]
        # the device's ops from this bucket's launch to the next launch
        launches = sorted(s for s, _, _, _ in sp.host["fft.stage.launch"])
        t0 = min(s for n, s, _, _ in rows if n == "fft.stage.launch")
        t1 = min((t for t in launches if t > t0), default=hi)
        mine = [e for e in ops if t0 <= e[1] < t1]
        if mine:
            busy = tr.busy_ns(mine) * 1e-6
            rows.append((f"device: {len(mine)} ops, busy {busy!r} ms",
                         mine[0][1], max(e[2] for e in mine), ""))
            rows += [(f"  {n}", a, b, "") for n, a, b in
                     sorted(mine, key=lambda e: e[1] - e[2])[:6]]
        print(f"bucket {bucket}, ms from the window's opening "
              "(start, end, length):")
        for n, a, b, args in sorted(rows, key=lambda r: r[1]):
            print(f"  {(a - lo) * 1e-6:11.3f} {(b - lo) * 1e-6:11.3f} "
                  f"{(b - a) * 1e-6:9.3f}  {n} {args}")
    idle = sorted(tr.gaps(tr.union(ops), lo, hi), key=lambda g: g[0] - g[1])
    print(f"ten longest idle gaps of {dev}: seconds, share inside program "
          "spans, the bucket spans that overlap it (ms):")
    for a, b in idle[:10]:
        share, named = covering(sp, a, b)
        print(f"  {(b - a) * 1e-9:.6f} {share * 100:6.2f}%  "
              + ", ".join(named))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
