"""Communication load (Remark 5) + the beyond-MDS strategy race.

Two sections, selectable via ``BENCH_ONLY=comm_load|strategies``:

* ``comm_load`` -- the original cut-set-bound check: any scheme must move
  >= s field symbols from workers to master; coded FFT moves EXACTLY s
  (m workers x s/m symbols).  Counted analytically per strategy AND
  verified in the lowered shard_map program (the single all-gather
  carries exactly s complex symbols).

* ``strategies`` -- race the three served CodedPlan families (DESIGN.md
  §13) on the regimes each was built for:

  (a) MODELED round times (harmonic closed form) over a wire_frac grid:
      comm_efficient's folded 1/q payload wins when the wire dominates
      and loses when compute does (Jeong et al. 1805.09891 trade).
  (b) MONTE-CARLO slow-but-alive fleet: the (m*r)-th fragment arrives
      before the m-th full shard because prefixes from slowed workers
      count (Wang et al. 1804.09791).
  (c) SERVICE-MEASURED race through the ``strategy=`` config knob:
      same-seed services, accuracy vs numpy asserted, simulated
      coverage latencies showing both crossovers end to end.

  All three claims are asserted in-bench; results append to
  ``BENCH_strategies.json`` with prior runs preserved under ``history``
  (oldest first).  ``BENCH_SMOKE=1`` shrinks rounds and, like
  ``BENCH_ONLY``, skips the artifact write.
"""

from __future__ import annotations

import json
import os
import pathlib
import platform

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CodedFFT, coded_fft_threshold, repetition_threshold, short_dot_threshold
from repro.core.strategies import REGISTRY, make_strategy
from repro.distributed.straggler import StragglerModel
from repro.serving import FFTService, FFTServiceConfig

SMOKE = os.environ.get("BENCH_SMOKE", "") == "1"
ONLY = os.environ.get("BENCH_ONLY", "")


def _want(section: str) -> bool:
    # the aggregator historically ran this module as one section ("comm_load")
    return not ONLY or ONLY in (section, "comm_load")


def _versions() -> dict:
    import jaxlib

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "machine": platform.machine(),
    }


# ---------------------------------------------------------------- comm_load
def _comm_load_section(lines: list[str]) -> None:
    lines.append("  -- worker->master symbols (input length s, cut-set "
                 "bound = s) --")
    lines.append(f"  {'N':>4} {'m':>3} {'s':>7} | {'coded':>8} "
                 f"{'short-dot':>10} {'repetition':>11}")
    for n, m, s in [(16, 4, 1 << 14), (64, 8, 1 << 16), (256, 16, 1 << 20)]:
        coded = coded_fft_threshold(n, m) * (s // m)          # = s exactly
        sd = short_dot_threshold(n, m) * (s // m)
        rep = repetition_threshold(n, m) * (s // m)
        lines.append(f"  {n:>4} {m:>3} {s:>7} | {coded:>8} {sd:>10} {rep:>11}"
                     f"   (coded/s = {coded / s:.2f}, optimal)")

    # verify in the lowered distributed program (needs >= 2 local devices
    # only for mesh construction; with 1 device we lower a 1-axis mesh)
    ndev = jax.device_count()
    if ndev >= 2:
        from repro.distributed import DistributedCodedFFT, test_mesh

        s, m, n = 4096, 4, ndev
        mesh = test_mesh((ndev,), ("workers",))
        plan = CodedFFT(s=s, m=m, n_workers=n)
        d = DistributedCodedFFT(plan, mesh)
        txt = d.lower().compile().as_text()
        import re

        ag = re.findall(r"c64\[([0-9,]+)\][^ ]* all-gather", txt)
        tot = 0
        for dims in ag:
            prod = 1
            for x in dims.split(","):
                prod *= int(x)
            tot += prod
        lines.append(f"  lowered shard_map program: all-gather carries {tot} "
                     f"c64 symbols for s={s} (N x s/N view of the same s "
                     f"coded symbols; bound s={s})")
    else:
        lines.append("  (single device: skipping lowered-collective check; "
                     "see tests/test_coded_runtime.py)")


# --------------------------------------------------------------- strategies
_N, _M, _Q, _R = 8, 2, 2, 4
_MU = 4.0


def _modeled_race(lines: list[str]) -> dict:
    """Closed-form expected round times over the wire_frac grid."""
    lines.append(f"  -- modeled round time (N={_N} m={_M} q={_Q}, "
                 f"harmonic closed form) --")
    out = {"grid": [], "n": _N, "m": _M, "q": _Q, "mu": _MU}
    for wf in (0.0, 0.25, 0.5, 0.8):
        sm = StragglerModel(t0=1.0, mu=_MU, wire_frac=wf)
        t_mds = sm.expected_kth(_N, _M, 1.0 / _M)
        t_ce = sm.expected_kth(_N, _M * _Q, 1.0 / _M, payload_scale=1.0 / _Q)
        out["grid"].append({"wire_frac": wf, "mds": t_mds,
                            "comm_efficient": t_ce})
        win = "comm_efficient" if t_ce < t_mds else "mds"
        lines.append(f"  wire_frac={wf:.2f}: mds {t_mds:.4f}  "
                     f"comm_eff {t_ce:.4f}  -> {win}")
    g = {r["wire_frac"]: r for r in out["grid"]}
    assert g[0.8]["comm_efficient"] < g[0.8]["mds"], \
        "folded payload must win when the wire dominates"
    assert g[0.0]["comm_efficient"] > g[0.0]["mds"], \
        "the m*q-th order statistic must cost more when compute dominates"
    lines.append("  asserted: comm_efficient wins at wire_frac 0.8, loses "
                 "at 0.0")
    return out


def _partial_mc_race(lines: list[str]) -> dict:
    """Slow-but-alive fleet: fragment coverage vs the m-th order stat."""
    rounds = 60 if SMOKE else 400
    lines.append(f"  -- partial-work vs mds, half the fleet 3x slow but "
                 f"ALIVE (r={_R}, {rounds} rounds) --")
    rng = np.random.default_rng(5)
    sm = StragglerModel(t0=1.0, mu=1.0, wire_frac=0.0)
    slow = np.ones(_N)
    slow[: _N // 2] = 3.0
    frac = np.arange(1, _R + 1) / _R
    t_mds = t_part = 0.0
    for _ in range(rounds):
        lat = sm.sample(_N, 1.0 / _M, rng) * slow
        t_mds += float(np.sort(lat)[_M - 1])
        ft = np.sort((lat[:, None] * frac).ravel())
        t_part += float(ft[_M * _R - 1])
    out = {"rounds": rounds, "r": _R, "slow_factor": 3.0,
           "mean_mds": t_mds / rounds, "mean_partial": t_part / rounds,
           "speedup": t_mds / t_part}
    lines.append(f"  mean round: mds {out['mean_mds']:.4f}  partial "
                 f"{out['mean_partial']:.4f}  ({out['speedup']:.2f}x)")
    assert t_part < t_mds, \
        "prefix fragments from slowed workers must beat full-shard waits"
    lines.append("  asserted: partial beats mds with slow-but-alive "
                 "stragglers")
    return out


def _service_race(lines: list[str]) -> dict:
    """End-to-end through the ``strategy=`` knob: accuracy + coverage."""
    s = 4096
    rounds, batch = (2, 4) if SMOKE else (30, 8)
    lines.append(f"  -- service race via strategy= (s={s} N={_N} m={_M}, "
                 f"{rounds} rounds x batch {batch}) --")
    rng = np.random.default_rng(1)
    xs = [(rng.standard_normal((batch, s)) + 1j * rng.standard_normal(
        (batch, s))).astype(np.complex64) for _ in range(rounds)]
    refs = [np.fft.fft(xb, axis=-1) for xb in xs]
    out: dict = {"s": s, "rounds": rounds, "batch": batch, "points": []}
    for wf in (0.8, 0.0):
        row = {"wire_frac": wf}
        for strategy in ("mds", "partial", "comm_efficient"):
            svc = FFTService(FFTServiceConfig(
                s=s, m=_M, n_workers=_N, strategy=strategy,
                use_reference=True, autotune=False, seed=0,
                straggler=StragglerModel(t0=1.0, mu=_MU, wire_frac=wf)))
            err = 0.0
            for xb, ref in zip(xs, refs):
                ys = svc.submit_batch([jnp.asarray(x) for x in xb])
                got = np.stack([np.asarray(y) for y in ys])
                err = max(err, float(np.max(np.abs(got - ref))
                                     / np.max(np.abs(ref))))
            assert err < 5e-4, f"{strategy} service decode error {err:.2e}"
            mean_lat = svc.stats.coded_latency / svc.stats.requests
            row[strategy] = {"mean_latency": mean_lat, "max_rel_err": err,
                             "stragglers_tolerated":
                                 svc.stats.stragglers_tolerated}
            lines.append(f"  wire_frac={wf:.1f} {strategy:>15}: mean "
                         f"coverage {mean_lat:.4f}, max rel err {err:.2e}, "
                         f"tolerated {svc.stats.stragglers_tolerated}")
        out["points"].append(row)
    hi, lo = out["points"][0], out["points"][1]
    assert hi["comm_efficient"]["mean_latency"] < hi["mds"]["mean_latency"], \
        "service: folded payload must win at wire_frac 0.8"
    assert lo["comm_efficient"]["mean_latency"] > lo["mds"]["mean_latency"], \
        "service: m*q-th order statistic must lose at wire_frac 0.0"
    # same-seed draws: partial's (m*r)-th fragment coverage can never
    # trail the m-th full shard (m fully-done workers imply m*r fragments)
    for row in out["points"]:
        assert row["partial"]["mean_latency"] \
            <= row["mds"]["mean_latency"] + 1e-12
    lines.append("  asserted: comm_efficient crossover + partial <= mds "
                 "end to end")
    return out


def _strategies_section(lines: list[str]) -> dict:
    lines.append(f"  registered strategies: {sorted(REGISTRY)}")
    # one differential sanity pass so the race never reports timings for
    # plans that silently decode garbage
    x = (np.random.default_rng(9).standard_normal(256)
         + 0j).astype(np.complex64)
    ref = np.fft.fft(x)
    for name in ("mds", "partial", "comm_efficient"):
        plan = make_strategy(name, 256, _M, _N)
        got = np.asarray(plan.run(jnp.asarray(x)))
        assert np.max(np.abs(got - ref)) / np.max(np.abs(ref)) < 5e-4
    return {
        "modeled": _modeled_race(lines),
        "partial_monte_carlo": _partial_mc_race(lines),
        "service": _service_race(lines),
    }


def run() -> list[str]:
    lines = ["bench_comm_load: communication optimality + strategy race"]
    result: dict = {}
    if _want("comm_load"):
        _comm_load_section(lines)
    if _want("strategies"):
        result["strategies"] = _strategies_section(lines)
    if not result.get("strategies"):
        return lines
    result["versions"] = _versions()
    if SMOKE or ONLY:
        lines.append("  [BENCH_SMOKE/BENCH_ONLY: artifact not written]")
        return lines
    out_path = (pathlib.Path(__file__).resolve().parent.parent
                / "BENCH_strategies.json")
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
