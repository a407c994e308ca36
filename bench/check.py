"""The comparison that decides ``correct``.

Once the window has closed, every kept answer (a sample of the requests,
drawn from the seed by the generator) is compared with the plain
reference of its configuration, ``bench/references/<reference>.py``, run
on the same payload.  The numbers compared, each against its limit (the
configuration's ``check`` entry; PERF.md gives the readings each limit
was set from):

* ``unanswered`` -- accepted requests that raised or whose answer never
  came within a minute of the window's close; limit 0.  Requests that
  admission control refused are not answers: they count as failed in
  the result and in the latency tails, not here;
* ``checked`` -- answers compared; at least 1;
* ``rel_l2_max`` -- the widest relative L2 error ``|y - y_ref| / |y_ref|``
  over the answers compared; an answer of the wrong shape or with a
  non-finite value reads ``inf``.  It catches an answer altered where it
  is produced, handed to the wrong request, or left out;
* ``rel_l2_min`` -- the smallest of the same errors.  The service decodes
  each request from the subset of workers its straggler mask leaves, and
  the error of f32 arithmetic is amplified by that subset's conditioning;
  the best-conditioned subsets show the arithmetic's own precision.  A
  transform computed one precision step lower (``bench/control.py``) has
  no such answer.
"""

from __future__ import annotations

import importlib
import math
import sys

import numpy as np


def rel_l2(got, want: np.ndarray) -> float:
    got = np.asarray(got)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return math.inf
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def reference(name: str):
    return importlib.import_module(f"bench.references.{name}").transform


def compare(samples: dict, pool_idx: np.ndarray, pool: np.ndarray,
            ref_name: str, unanswered: int, limits: dict) -> dict:
    """The numbers compared, each as ``{"value", "limit", "rule"}``."""
    transform = reference(ref_name)
    by_payload: dict[int, list] = {}
    for j, out in samples.items():
        by_payload.setdefault(int(pool_idx[j]), []).append(out)
    errs = []
    for idx in sorted(by_payload):
        want = transform(pool[idx])
        errs.extend(rel_l2(out, want) for out in by_payload[idx])
    if errs:
        q = np.quantile(errs, [0.0, 0.1, 0.25, 0.5, 0.9, 1.0])
        print("[bench] relative L2 errors of the kept answers: "
              f"n={len(errs)} min/p10/p25/p50/p90/max = "
              + " ".join(repr(float(v)) for v in q), file=sys.stderr)
    return {
        "unanswered": {"value": int(unanswered), "limit": 0, "rule": "<="},
        "checked": {"value": len(errs), "limit": 1, "rule": ">="},
        "rel_l2_max": {"value": max(errs, default=math.inf),
                       "limit": limits["rel_l2_max"], "rule": "<="},
        "rel_l2_min": {"value": min(errs, default=math.inf),
                       "limit": limits["rel_l2_min"], "rule": "<="},
    }


def passed(numbers: dict) -> bool:
    ok = True
    for num in numbers.values():
        v, lim = num["value"], num["limit"]
        ok &= (v >= lim) if num["rule"] == ">=" else (v <= lim)
    return bool(ok)


def json_safe(numbers: dict) -> dict:
    """inf is not JSON: an unreadable error is written as 1e300."""
    return {k: {**v, "value": (1e300 if isinstance(v["value"], float)
                               and not math.isfinite(v["value"])
                               else v["value"])}
            for k, v in numbers.items()}
