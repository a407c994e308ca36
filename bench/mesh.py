"""What a mesh moves, from a traced run of a cell on several chips.

The mesh layer's per-layer metrics (``*.mesh``) read two things:

* the all-gathers' device time.  ``trace.reduce``'s ``allgather_s``
  counts the ops named ``all-gather``.  The TPU compiler splits a
  complex64 all-gather of ``DistributedCodedPlan.run`` into its two
  float32 parts and runs one of them asynchronously, as an
  ``async-collective-start`` and its ``async-collective-done``, which
  that name misses.  Here both forms count: per device, the union, inside
  the window, of the ``all-gather`` ops and of the span from each
  ``async-collective-start`` to its ``-done``.  The all-gather is the
  only collective that program runs: of the workers' results, and, where
  the bucket enters split over the devices (the launch's ``ingress``
  ``split``), of the message;
* the bytes each device receives in them, as the program reckons them
  from the arguments of its ``fft.stage.launch`` spans
  (``serving/spans.py``): ``gather_bytes``, and with a split ingress the
  device's share of ``broadcast_bytes`` (it receives (D-1)/D of the
  arguments, which the D devices together receive D-1 times).  A program
  without those arguments reports none.

A hand-made run may carry its own ``trace`` (a :class:`trace.Trace`) and
``spans``; otherwise the trace the harness left under
``.bench_out/trace`` is read once, and used only if its window is the
run's.
"""

from __future__ import annotations

import json
import os
import pathlib
import re
from typing import Optional

from bench import spans
from bench import trace as tr

ROOT = pathlib.Path(__file__).resolve().parents[1]
GATHER = re.compile(r"all-gather|async-collective")
LAUNCH = "fft.stage.launch"

_cache: dict = {}


def trace_of(run) -> Optional[tr.Trace]:
    """The run's device trace, None for an untraced run."""
    if getattr(run, "trace", None) is not None:
        return run.trace
    if run.reduced is None:
        return None
    try:
        path = tr.find_xplane(str(spans.TRACE_DIR))
    except FileNotFoundError:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = tr.load(path)
    trace = _cache[key]
    lo, hi = tr.window(trace)
    return trace if abs((hi - lo) * 1e-9 - run.reduced.window_s) < 1e-9 \
        else None


def gather_s(trace: tr.Trace) -> list[float]:
    """Seconds of all-gather inside the window, per device."""
    lo, hi = tr.window(trace)
    out = []
    for dev in trace.devices():
        ops = [e for e in tr.clip(trace.ops(dev), lo, hi)
               if GATHER.search(e[0])]
        out.append(tr.busy_ns(tr.collective_spans(ops)) * 1e-9)
    return out


def gather_ms_per_bucket(run) -> Optional[float]:
    """Mean over devices of the all-gather's ms, per bucket staged in the
    window; None without a trace, an all-gather or a bucket."""
    trace = trace_of(run)
    if trace is None or not run.stats.get("batches"):
        return None
    per_dev = gather_s(trace)
    if not per_dev or max(per_dev) <= 0:
        return None
    return tr.mean(per_dev) / run.stats["batches"] * 1e3


def received(args: dict) -> Optional[float]:
    """Bytes one device receives in one launch's all-gathers, from the
    launch span's arguments; None without them."""
    if "gather_bytes" not in args:
        return None
    got = float(args["gather_bytes"])
    if args.get("ingress") == "split":
        got += float(args["broadcast_bytes"]) / float(args["devices"])
    return got


def gather_bytes_per_bucket(run) -> Optional[float]:
    """Mean bytes a device receives in the all-gathers of the launches
    that start in the window; None where the program does not report
    them."""
    sp = spans.of(run)
    if sp is None:
        return None
    got = [b for b in (received(args) for _, _, args, _
                       in spans.started(sp, LAUNCH)) if b is not None]
    return sum(got) / len(got) if got else None


def ici_bytes_per_s(run, root: pathlib.Path = ROOT) -> Optional[float]:
    """The chip's interchip peak (``bench/ici_peaks.json``, keyed by
    device kind): the kind is the one whose ``bench/peaks.json`` entry is
    the run's peak."""
    if run.peak is None:
        return None
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    ici = json.loads((root / "bench" / "ici_peaks.json").read_text())
    for kind, peak in peaks.items():
        if peak == run.peak and kind in ici:
            return float(ici[kind]["bytes_per_s"])
    return None
