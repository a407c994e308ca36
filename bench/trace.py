"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

A trace is first turned into plain data, :class:`Trace`: for every plane a
mapping of line name to ``(event name, start_ns, end_ns)`` tuples.  Every
reduction below works on that data, so the tests can hand it a recorded
trace or a few intervals written out by hand.

* busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), cut to
  the traced window;
* per-op-name device time, summed over devices (for ``breakdown``);
* all-gather device time per device (an async all-gather counts from its
  start op to its done op);
* idle gaps on the first device, each named by the host event that
  overlaps it most.

The window is the host span named :data:`WINDOW` that the harness opens
and closes around the measured window.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, Optional

WINDOW = "bench.window"
UNCODED = "bench.uncoded"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ALLGATHER = re.compile(r"all-gather", re.IGNORECASE)
ASYNC_PART = re.compile(r"^(.*)-(start|done)((?:\.\d+)?)$")

Event = tuple  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    planes: dict  # plane name -> {line name -> [Event, ...]}

    def devices(self) -> list[str]:
        """Device plane names in device order."""
        names = [p for p in self.planes if DEVICE_PLANE.match(p)]
        return sorted(names, key=lambda p: int(DEVICE_PLANE.match(p)[1]))

    def ops(self, plane: str) -> list[Event]:
        return self.planes.get(plane, {}).get(OPS_LINE, [])

    def host_lines(self) -> dict:
        return self.planes.get(HOST_PLANE, {})


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def op_name(text: str) -> str:
    """A device op's name without its HLO text: ``%fusion.3 = (...)`` is
    ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into plain data (needs only JAX)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = [(op_name(e.name), int(e.start_ns),
                    int(e.start_ns + e.duration_ns)) for e in line.events]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        planes[plane.name] = lines
    return Trace(planes)


def window(trace: Trace, name: str = WINDOW) -> tuple[int, int]:
    """``(start_ns, end_ns)`` of the host span that marks the window."""
    for events in trace.host_lines().values():
        for ev in events:
            if ev[0] == name:
                return ev[1], ev[2]
    raise ValueError(f"the trace holds no host span named {name!r}")


def clip(events: Iterable[Event], lo: int, hi: int) -> list[Event]:
    """Events cut to ``[lo, hi]``; those outside it dropped."""
    out = []
    for name, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union(events: Iterable[Event]) -> list[tuple[int, int]]:
    """The union of the events' intervals, as sorted disjoint intervals."""
    spans = sorted((a, b) for _, a, b in events if b > a)
    merged: list[list[int]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: Iterable[Event]) -> int:
    return sum(b - a for a, b in union(events))


def collective_spans(events: list[Event]) -> list[Event]:
    """An async collective runs from its ``-start`` op to its ``-done``
    op: pair them (``all-gather-start.2`` with ``all-gather-done.2``, in
    order) into one span each; a collective that is one op stays as is."""
    spans, open_ = [], {}
    for name, a, b in sorted(events, key=lambda e: e[1]):
        m = ASYNC_PART.match(name)
        if m is None:
            spans.append((name, a, b))
        elif m[2] == "start":
            open_.setdefault(m[1] + m[3], []).append(a)
        elif open_.get(m[1] + m[3]):
            spans.append((m[1] + m[3], open_[m[1] + m[3]].pop(0), b))
    return spans


def span_busy_s(trace: Trace, name: str) -> float:
    """Device busy seconds, summed over devices, inside a host span."""
    lo, hi = window(trace, name)
    return sum(busy_ns(clip(trace.ops(d), lo, hi)) for d in trace.devices()
               ) * 1e-9


def op_seconds(trace: Trace, lo: int, hi: int) -> dict:
    """Device seconds per op name inside the window, summed over devices."""
    out: dict[str, float] = {}
    for dev in trace.devices():
        for name, a, b in clip(trace.ops(dev), lo, hi):
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def gaps(intervals: list[tuple[int, int]], lo: int, hi: int
         ) -> list[tuple[int, int]]:
    """The idle intervals of ``[lo, hi]`` between busy intervals."""
    out, t = [], lo
    for a, b in intervals:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def host_cause(trace: Trace, a: int, b: int, skip: str = WINDOW) -> str:
    """The host event that overlaps ``[a, b]`` most, as ``line:event``.

    Events that cover the whole gap are passed over when a shorter one
    overlaps it too: they say which thread was busy, not with what."""
    best, best_ov, whole = "", 0, ""
    for line, events in trace.host_lines().items():
        for name, s, e in events:
            if name == skip:
                continue
            ov = min(b, e) - max(a, s)
            if ov <= 0:
                continue
            label = f"{line}:{name}"
            if s <= a and e >= b:
                whole = whole or label
                continue
            if ov > best_ov:
                best, best_ov = label, ov
    return best or whole or "host idle"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: list            # per device, inside the window
    allgather_s: list       # per device, inside the window
    device_ops: list        # [[name, seconds], ...] the 10 largest
    idle_gaps: list         # [[host cause, seconds], ...] the 10 longest


def reduce(trace: Trace, name: str = WINDOW, top: int = 10) -> Reduced:
    lo, hi = window(trace, name)
    devs = trace.devices()
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    busy, gathered = [], []
    for dev in devs:
        ops = clip(trace.ops(dev), lo, hi)
        busy.append(busy_ns(ops) * 1e-9)
        gathered.append(busy_ns(collective_spans(
            [e for e in ops if ALLGATHER.search(e[0])])) * 1e-9)
    per_op = sorted(op_seconds(trace, lo, hi).items(), key=lambda kv: -kv[1])
    idle = gaps(union(clip(trace.ops(devs[0]), lo, hi)), lo, hi)
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy,
        allgather_s=gathered,
        device_ops=[[n, s] for n, s in per_op[:top]],
        idle_gaps=[[host_cause(trace, a, b), (b - a) * 1e-9]
                   for a, b in idle],
    )


def mean(xs: list) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None
