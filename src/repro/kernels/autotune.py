"""Per-shape kernel autotuner: measured block/variant tables with a JSON cache.

The ops-layer dispatchers used to pick block shapes from one hard-coded
heuristic (``_block_q`` / ``_block_l``) and the four-step always used the
balanced two-factor split.  Neither choice is stable across backends: on
CPU the platform FFT beats any dense-matmul factorization outright, in
interpret mode the best plan is "one giant block", and on TPU the right
(block_q, block_l) tiling depends on the bucket's VMEM working set.  This
module replaces the guesswork with a small measured table:

* **keys** -- ``"{kind}|k=v|..."`` with the shape params sorted, one table
  per execution mode (``direct`` / ``interpret`` / ``compiled``), one JSON
  cache file per jax backend (``autotune-{backend}.json``), so a table
  tuned on one machine class never leaks onto another.
* **entries** -- plain dicts: ``{"variant": "fused"|"two_pass"|"xla",
  "factors": [...], "block_q": int, "block_l": int, "bf16_ok": bool,
  "ms": float}``; every field optional, consumers take what they need.
* **search** -- :func:`tune_fourstep` / :func:`tune_bucket` time a handful
  of candidates (median of a few reps on real jitted calls) and record the
  winner.  Searches run from ``FFTService.warmup()`` or the bench harness,
  NEVER from a dispatcher: :func:`lookup` inside a jit trace is a pure
  dict read, so dispatch stays deterministic and trace-time cheap.
* **persistence** -- the winning table is written atomically after each
  search; the next process loads it and skips the search entirely (the
  warm path the autotune-cache round-trip test pins).

``REPRO_AUTOTUNE_CACHE`` overrides the cache directory (default
``~/.cache/coded-fft``); tests point it at a tmpdir.
"""

from __future__ import annotations

import json
import os
import pathlib
import tempfile
import time
import warnings
from typing import Callable, Optional

import jax
import numpy as np

__all__ = [
    "cache_path",
    "clear",
    "key_of",
    "lookup",
    "record",
    "load_table",
    "save_table",
    "searches_run",
    "candidate_factor_plans",
    "tune_fourstep",
    "ensure_fourstep",
    "tune_bucket",
    "ensure_bucket",
    "AutotuneError",
]

SCHEMA_VERSION = 1

# in-memory tables, keyed by jax backend name; each maps key -> entry dict
_TABLES: dict[str, dict[str, dict]] = {}
_LOADED: set[str] = set()
_SEARCHES = 0  # lifetime search count (tests/CI assert the warm-skip path)


def _backend() -> str:
    return jax.default_backend()


def cache_path(backend: Optional[str] = None) -> pathlib.Path:
    """The JSON cache file for ``backend`` (default: the active one)."""
    root = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if not root:
        root = os.path.join(os.path.expanduser("~"), ".cache", "coded-fft")
    return pathlib.Path(root) / f"autotune-{backend or _backend()}.json"


def searches_run() -> int:
    """Lifetime number of measured searches (cache hits do not count)."""
    return _SEARCHES


def clear(memory_only: bool = True, backend: Optional[str] = None) -> None:
    """Drop the in-memory table (and optionally the on-disk cache)."""
    b = backend or _backend()
    _TABLES.pop(b, None)
    _LOADED.discard(b)
    if not memory_only:
        try:
            cache_path(b).unlink()
        except FileNotFoundError:
            pass


def load_table(backend: Optional[str] = None) -> dict[str, dict]:
    """The (lazily disk-loaded) table for ``backend``."""
    b = backend or _backend()
    if b not in _LOADED:
        table: dict[str, dict] = {}
        try:
            blob = json.loads(cache_path(b).read_text())
            if blob.get("version") == SCHEMA_VERSION:
                table = {str(k): dict(v)
                         for k, v in blob.get("entries", {}).items()}
        except (FileNotFoundError, json.JSONDecodeError, OSError,
                AttributeError, TypeError):
            table = {}  # missing/corrupt cache: start cold, never crash
        _TABLES.setdefault(b, {}).update(
            {k: v for k, v in table.items() if k not in _TABLES.get(b, {})})
        _LOADED.add(b)
    return _TABLES.setdefault(b, {})


def save_table(backend: Optional[str] = None) -> pathlib.Path:
    """Atomically persist the in-memory table for ``backend``."""
    b = backend or _backend()
    path = cache_path(b)
    path.parent.mkdir(parents=True, exist_ok=True)
    blob = {"version": SCHEMA_VERSION, "backend": b,
            "entries": load_table(b)}
    fd, tmp = tempfile.mkstemp(dir=str(path.parent), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(blob, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
    return path


def key_of(kind: str, **params) -> str:
    """Canonical table key: kind plus sorted ``k=v`` shape params."""
    parts = [f"{k}={params[k]}" for k in sorted(params)]
    return "|".join([kind, *parts])


def lookup(kind: str, **params) -> Optional[dict]:
    """Pure table read (safe inside a jit trace -- no search, no I/O
    beyond the one lazy cache-file load per backend)."""
    return load_table().get(key_of(kind, **params))


def record(kind: str, entry: dict, persist: bool = True, **params) -> dict:
    """Store ``entry`` under the canonical key; persist unless told not."""
    load_table()[key_of(kind, **params)] = dict(entry)
    if persist:
        save_table()
    return entry


# ------------------------------------------------------------ measurement
class AutotuneError(RuntimeError):
    """Every candidate of a search failed to lower, compile or run."""


def _search(kind: str, cands: list, time_one: Callable) -> dict:
    """Time each ``(label, entry)`` candidate with ``time_one(entry)`` and
    return the fastest entry with its ``ms``.

    A candidate that fails is reported with its error (a warning) and
    skipped -- it never turns into a recorded default.  If every candidate
    fails the search raises, so a device that cannot run the kernel is
    seen at warmup instead of being hidden behind a guessed tiling.
    """
    best: Optional[dict] = None
    errors = []
    for label, entry in cands:
        try:
            ms = time_one(entry)
        except Exception as e:  # lowering/compile/runtime errors alike
            errors.append(f"{label}: {type(e).__name__}: {e}")
            warnings.warn(f"autotune {kind}: candidate {label} failed: "
                          f"{type(e).__name__}: {e}", RuntimeWarning,
                          stacklevel=3)
            continue
        if best is None or ms < best["ms"]:
            best = {**entry, "ms": ms}
    if best is None:
        raise AutotuneError(f"autotune {kind}: every candidate failed:\n"
                            + "\n".join(errors))
    return best


def _time_ms(fn: Callable, args: tuple, reps: int) -> float:
    out = jax.block_until_ready(fn(*args))  # compile + warm
    del out
    ts = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e3)


# -------------------------------------------------------- four-step plans
def _balanced_split(n: int) -> tuple[int, int]:
    a = int(np.sqrt(n))
    while a > 1 and n % a != 0:
        a -= 1
    return a, n // a


def _split_to_radix(n: int, radix: int) -> Optional[list[int]]:
    """Factor ``n`` into factors <= ``radix`` by greedily peeling the
    largest divisor; None when a prime factor exceeds the radix."""
    out: list[int] = []
    while n > 1:
        f = min(n, radix)
        while f > 1 and n % f != 0:
            f -= 1
        if f == 1:
            return None  # prime beyond the radix
        out.append(f)
        n //= f
    return out


def candidate_factor_plans(ell: int, max_plans: int = 5) -> list[list[int]]:
    """Candidate radix plans for a length-``ell`` multistep four-step.

    Always includes the classic balanced two-factor split; deeper plans
    cap the largest dense DFT factor at 64/32/16 (sum-of-factors is the
    flop count per element, smaller caps trade flops for more stages).
    """
    plans: list[list[int]] = []
    a, b = _balanced_split(ell)
    if a > 1:
        plans.append([a, b])
    for radix in (64, 32, 16):
        p = _split_to_radix(ell, radix)
        if p and len(p) >= 2 and p not in plans:
            plans.append(p)
    return plans[:max_plans] or [[1, ell]]


def tune_fourstep(ell: int, batch: int = 4, mode: str = "direct", *,
                  reps: int = 5, factor_plans: Optional[list] = None,
                  include_xla: Optional[bool] = None,
                  persist: bool = True) -> dict:
    """Measure four-step variants at length ``ell`` and record the winner.

    Candidates: ``("fused", factors)`` for each radix plan,
    ``("two_pass", None)``, and -- where the dispatcher may legally use the
    platform FFT, i.e. every non-Pallas path -- ``("xla", None)``.  The
    winning ``{"variant", "factors", "ms"}`` entry is recorded under
    ``fourstep|L=...|mode=...`` and (by default) persisted.
    """
    global _SEARCHES
    from repro.kernels import ops  # deferred: ops imports this module

    _SEARCHES += 1
    interpret = {"direct": None, "interpret": True, "compiled": False}[mode]
    if include_xla is None:
        include_xla = mode == "direct"
    rng = np.random.default_rng(0)
    xr = jax.numpy.asarray(rng.standard_normal((batch, ell)), jax.numpy.float32)
    xi = jax.numpy.asarray(rng.standard_normal((batch, ell)), jax.numpy.float32)

    cands: list[tuple[str, Optional[list[int]]]] = []
    for f in (factor_plans if factor_plans is not None
              else candidate_factor_plans(ell)):
        cands.append(("fused", list(f)))
    cands.append(("two_pass", None))
    if include_xla:
        cands.append(("xla", None))

    def time_one(entry):
        fn = jax.jit(_fourstep_candidate_fn(
            entry["variant"], entry.get("factors"), interpret))
        return _time_ms(fn, (xr, xi), reps)

    best = _search(f"fourstep L={ell} mode={mode}", [
        (f"{variant}{'' if factors is None else factors}",
         {"variant": variant,
          **({} if factors is None else {"factors": factors})})
        for variant, factors in cands], time_one)
    return record("fourstep", best, persist=persist, L=ell, mode=mode)


def _fourstep_candidate_fn(variant, factors, interpret):
    from repro.kernels import ops

    def fn(xr, xi):
        return ops.fourstep_planar(xr, xi, interpret=interpret,
                                   variant=variant, factors=factors)

    return fn


def ensure_fourstep(ell: int, batch: int = 4, mode: str = "direct",
                    **kw) -> dict:
    """Warm path: return the recorded entry, searching only on a miss."""
    ent = lookup("fourstep", L=ell, mode=mode)
    if ent is not None:
        return ent
    return tune_fourstep(ell, batch, mode, **kw)


# ------------------------------------------------------------ bucket tiles
def tune_bucket(kind: str, s: int, m: int, n: int, q: int = 4, *,
                mode: str = "interpret", reps: int = 3,
                block_qs: Optional[list[int]] = None,
                persist: bool = True) -> dict:
    """Measure candidate batch-block sizes for a whole-bucket kernel.

    ``kind``: ``"bucket" | "rbucket" | "irbucket"``.  Runs the masked
    whole-bucket dispatcher (the service hot path) with forced ``block_q``
    candidates and records the winner under
    ``{kind}|s=..|m=..|n=..|mode=..``.  Only meaningful for the Pallas
    modes -- the direct path has no grid -- but callable anywhere.
    """
    global _SEARCHES
    from repro.kernels import ops

    _SEARCHES += 1
    interpret = {"direct": None, "interpret": True, "compiled": False}[mode]
    rng = np.random.default_rng(0)
    jnp = jax.numpy
    masks = np.zeros((q, n), bool)
    masks[:, :m] = True
    masks = jnp.asarray(masks)
    gr = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
    gi = jnp.asarray(rng.standard_normal((n, m)), jnp.float32)
    if block_qs is None:
        block_qs = sorted({1, max(1, q // 2), q})

    def make(bq):
        if kind == "rbucket":
            xb = jnp.asarray(rng.standard_normal((q, s)), jnp.float32)
            fn = jax.jit(lambda x, mk: ops.coded_rbucket_masked(
                x, mk, gr, gi, s, interpret=interpret, block_q=bq))
            return fn, (xb, masks)
        if kind == "irbucket":
            sh = s // 2 + 1
            yr = jnp.asarray(rng.standard_normal((q, sh)), jnp.float32)
            yi = jnp.asarray(rng.standard_normal((q, sh)), jnp.float32)
            fn = jax.jit(lambda a, b, mk: ops.coded_irbucket_masked(
                a, b, mk, gr, gi, s, interpret=interpret, block_q=bq))
            return fn, (yr, yi, masks)
        xr = jnp.asarray(rng.standard_normal((q, s)), jnp.float32)
        xi = jnp.asarray(rng.standard_normal((q, s)), jnp.float32)
        fn = jax.jit(lambda a, b, mk: ops.coded_bucket_masked(
            a, b, mk, gr, gi, s, interpret=interpret, block_q=bq))
        return fn, (xr, xi, masks)

    def time_one(entry):
        fn, args = make(entry["block_q"])
        return _time_ms(fn, args, reps)

    best = _search(f"{kind} s={s} m={m} n={n} mode={mode}",
                   [(f"block_q={int(bq)}", {"block_q": int(bq)})
                    for bq in block_qs], time_one)
    return record(kind, best, persist=persist, s=s, m=m, n=n, mode=mode)


def ensure_bucket(kind: str, s: int, m: int, n: int, q: int = 4,
                  mode: str = "interpret", **kw) -> dict:
    """Warm path: recorded bucket entry, searching only on a miss."""
    ent = lookup(kind, s=s, m=m, n=n, mode=mode)
    if ent is not None:
        return ent
    return tune_bucket(kind, s, m, n, q, mode=mode, **kw)
