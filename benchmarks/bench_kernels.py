"""Kernel hot path vs jnp oracle: parity + timing -> BENCH_kernels.json.

Three kernel-vs-oracle comparisons (DESIGN.md §6), each timed on the
DEFAULT dispatch path (compiled Pallas on TPU; the same kernel bodies as
straight XLA off-TPU) with strict parity asserts against the jnp oracle:

* **fourstep** -- the worker DFT: fused single-kernel vs two-pass
  (stage1/stage2) vs ``jnp.fft``;
* **encode_worker** -- fused encode+worker (MDS encode folded into the
  four-step stage-1 matmul; message shards transformed, an N/m flop
  saving) vs the separate encode-then-transform path vs the PR-1 oracle
  (``encode_dft`` + ``jnp.fft``), swept over s in {1k, 16k, 256k} x
  m in {4, 16, 64};
* **decode** -- per-mask scatter decode matrices applied as one batched
  MXU matmul (the service path) vs the dense per-request Vandermonde
  solve, same sweep;
* **cold_decode** -- NOVEL-mask decode-matrix production (DESIGN.md §8):
  the device-resident Lagrange build (cold == warm by construction) vs
  the host-LRU fallback cold (one inversion per miss) and warm;
* **streaming** -- the autotuned four-step dispatch (DESIGN.md §10):
  the tuner-routed default path vs the fixed fused / two-pass variants
  vs ``jnp.fft`` over L in {4k, 16k, 64k, 256k}, plus the bf16-plane
  fused variant.  TWO asserted acceptance claims: the tuned path sits
  within 1.5x of the jnp oracle at L=4096, and it never loses to its
  own two-pass fallback at any benched L (the pre-autotune default DID
  at L=4096 -- fused 0.42ms vs two-pass 0.32ms -- which is exactly the
  regression the tuner exists to catch);
* **rfft** -- the real-input (r2c) bucket vs the c2c bucket fed the same
  real signal as complex, at s in {16k, 256k}: half the worker-shard
  payload bytes and lower wall-clock (DESIGN.md §7);
* **rfftn** -- the n-D real plan (CodedRFFTN) vs the n-D c2c plan fed the
  same real field as complex (DESIGN.md §9): same per-axis code, half the
  worker payload;

plus the acceptance measurement: **batched service throughput** at the
``BENCH_service.json`` config (s=2048, m=4, N=8, 64 requests/bucket),
default (kernel) hot path vs the PR-1 jnp-oracle path
(``use_reference=True``).  Timings alternate A/B per repetition and report
medians -- this container's CPU throttles in bursts, so interleaving is
the only honest protocol.  Wall-clock here is CPU; the analytic v5e
roofline for each kernel shape is included for the TPU story.
"""

from __future__ import annotations

import json
import os
import pathlib
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mds
from repro.kernels import autotune, ops, ref
from repro.serving import FFTService, FFTServiceConfig
from repro.serving.decode_cache import DecodeMatrixCache

# BENCH_SMOKE=1 (the CI bench-smoke job): tiny shapes, few reps, NO JSON
# artifact -- a fast structural check that every perf path still runs and
# its parity asserts hold, so hot-path regressions fail PRs quickly
SMOKE = os.environ.get("BENCH_SMOKE", "") == "1"


def _roofline(flops: float, bytes_: float) -> str:
    ct = flops / 197e12
    mt = bytes_ / 819e9
    dom = "compute" if ct > mt else "memory"
    return (f"flops {flops:.2e}, bytes {bytes_:.2e}, AI "
            f"{flops / bytes_:6.1f} F/B -> {dom}-bound "
            f"(c {ct * 1e6:.1f}us vs m {mt * 1e6:.1f}us)")


def _randc(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        (rng.normal(size=shape) + 1j * rng.normal(size=shape))
        .astype(np.complex64))


def _relerr(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-12))


def _time_interleaved(variants: dict, reps: int = 8) -> dict:
    """Median seconds per call for each jitted variant, A/B-interleaved."""
    for fn, args in variants.values():
        jax.block_until_ready(fn(*args))
    times = {k: [] for k in variants}
    names = list(variants)
    for r in range(reps):
        order = names if r % 2 == 0 else names[::-1]
        for k in order:
            fn, args = variants[k]
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            times[k].append(time.perf_counter() - t0)
    return {k: statistics.median(v) for k, v in times.items()}


# ---------------------------------------------------------------- sections
def bench_fourstep(lines: list) -> list[dict]:
    rows = []
    for ell in ((4096,) if SMOKE else (4096, 16384, 65536)):
        batch = 4
        x = _randc((batch, ell), seed=ell)
        xr, xi = ref.planar(x)
        fused = jax.jit(lambda r, i: ops.fourstep_planar(r, i, fused=True))
        twop = jax.jit(lambda r, i: ops.fourstep_planar(r, i, fused=False))
        oracle = jax.jit(lambda z: jnp.fft.fft(z, axis=-1))
        want = np.fft.fft(np.asarray(x, np.complex128), axis=-1)
        err = _relerr(ref.unplanar(*fused(xr, xi)), want)
        assert err < 1e-3, err
        t = _time_interleaved({
            "fused": (fused, (xr, xi)),
            "two_pass": (twop, (xr, xi)),
            "jnp_oracle": (oracle, (x,)),
        })
        a, b = ops.split_factor(ell)
        flops = batch * 3 * 2 * ell * (a + b)
        bytes_ = batch * ell * 4 * 2 * 3
        rows.append({"L": ell, "batch": batch, "rel_err": err,
                     "fused_ms": t["fused"] * 1e3,
                     "two_pass_ms": t["two_pass"] * 1e3,
                     "jnp_oracle_ms": t["jnp_oracle"] * 1e3})
        lines.append(
            f"  fourstep L={ell} ({a}x{b}) rel err {err:.2e}; fused "
            f"{t['fused']*1e3:.2f}ms two-pass {t['two_pass']*1e3:.2f}ms "
            f"jnp {t['jnp_oracle']*1e3:.2f}ms; "
            + _roofline(float(flops), float(bytes_)))
    return rows


def bench_streaming(lines: list) -> list[dict]:
    """The autotuned four-step story (DESIGN.md §10).

    For each L the tuner measures fused / two-pass / platform-FFT (and,
    off the two-factor grid, multistep plans) once and records the winner;
    the ``tuned`` column is then the DEFAULT dispatch
    (``fourstep_planar(variant=None)``) reading that table.  Two timing
    asserts -- the ONLY timing asserts in this bench, both acceptance
    criteria with wide margins over the observed gap:

    * tuned <= 1.5x the jnp oracle at L=4096 (on CPU the tuner learns the
      platform FFT wins and routes to it, closing the 2.6x fused gap);
    * tuned <= 1.25x two-pass at EVERY benched L (the fused-by-default
      heuristic lost to its own fallback at L=4096; the table cannot, it
      measured both).

    The bf16 column times the fused variant with bfloat16 DFT/twiddle
    planes (f32 accumulation) and reports its error against the f64
    oracle -- the per-shape budget the service probe gates on.
    """
    mode = ops._mode(None)
    rows = []
    for ell in ((4096,) if SMOKE else (4096, 16384, 65536, 262144)):
        batch = 4
        x = _randc((batch, ell), seed=ell)
        xr, xi = ref.planar(x)
        ent = autotune.ensure_fourstep(ell, batch=batch, mode=mode,
                                       reps=2 if SMOKE else 5)
        tuned = jax.jit(lambda r, i: ops.fourstep_planar(r, i))
        fused = jax.jit(
            lambda r, i: ops.fourstep_planar(r, i, variant="fused"))
        twop = jax.jit(
            lambda r, i: ops.fourstep_planar(r, i, variant="two_pass"))
        bf16 = jax.jit(lambda r, i: ops.fourstep_planar(
            r, i, variant="fused", precision="bf16"))
        oracle = jax.jit(lambda z: jnp.fft.fft(z, axis=-1))
        want = np.fft.fft(np.asarray(x, np.complex128), axis=-1)
        err_t = _relerr(ref.unplanar(*tuned(xr, xi)), want)
        err_f = _relerr(ref.unplanar(*fused(xr, xi)), want)
        err_b = _relerr(ref.unplanar(*bf16(xr, xi)), want)
        assert err_t < 1e-3 and err_f < 1e-3, (ell, err_t, err_f)
        assert err_b < ops.BF16_RTOL, (ell, err_b)
        t = _time_interleaved({
            "tuned": (tuned, (xr, xi)),
            "fused": (fused, (xr, xi)),
            "two_pass": (twop, (xr, xi)),
            "bf16_fused": (bf16, (xr, xi)),
            "jnp_oracle": (oracle, (x,)),
        }, reps=4 if SMOKE else 8)
        assert t["tuned"] <= t["two_pass"] * 1.25, (
            f"L={ell}: tuned dispatch {t['tuned']*1e3:.2f}ms lost to its "
            f"own two-pass fallback {t['two_pass']*1e3:.2f}ms -- the "
            f"autotune table routed to a slower variant")
        # SMOKE runs 4 reps -- too few for a ratio this tight (the tuned
        # path is the platform FFT plus the planar<->complex casts, so
        # the margin over 1.5x is real but small); the acceptance claim
        # is about the full-rep artifact, where the median holds it.
        if ell == 4096 and not SMOKE:
            assert t["tuned"] <= t["jnp_oracle"] * 1.5, (
                f"tuned four-step {t['tuned']*1e3:.2f}ms not within 1.5x "
                f"of jnp oracle {t['jnp_oracle']*1e3:.2f}ms at L=4096")
        rows.append({
            "L": ell, "batch": batch, "mode": mode,
            "tuned_entry": ent,
            "rel_err_tuned": err_t, "rel_err_bf16": err_b,
            "tuned_ms": t["tuned"] * 1e3,
            "fused_ms": t["fused"] * 1e3,
            "two_pass_ms": t["two_pass"] * 1e3,
            "bf16_fused_ms": t["bf16_fused"] * 1e3,
            "jnp_oracle_ms": t["jnp_oracle"] * 1e3,
            "tuned_vs_oracle": t["tuned"] / t["jnp_oracle"],
            "fused_regressed_vs_two_pass": t["fused"] > t["two_pass"],
        })
        lines.append(
            f"  streaming L={ell}: tuned[{ent.get('variant')}] "
            f"{t['tuned']*1e3:.2f}ms fused {t['fused']*1e3:.2f}ms "
            f"two-pass {t['two_pass']*1e3:.2f}ms bf16 "
            f"{t['bf16_fused']*1e3:.2f}ms jnp {t['jnp_oracle']*1e3:.2f}ms "
            f"(tuned/oracle {t['tuned']/t['jnp_oracle']:.2f}x, bf16 err "
            f"{err_b:.1e})")
    return rows


def bench_encode_worker(lines: list) -> list[dict]:
    rows = []
    for s in ((1024,) if SMOKE else (1024, 16384, 262144)):
        for m in ((4,) if SMOKE else (4, 16, 64)):
            n = 2 * m
            ell = s // m
            q = 2 if s >= 262144 else 4
            c = _randc((q, m, ell), seed=s + m)
            g = mds.rs_generator(n, m, jnp.complex64)
            cr, ci = ref.planar(c)
            gr, gi = ref.planar(g)
            fused = jax.jit(
                lambda r, i: ops.encode_worker(r, i, gr, gi, fused=True))
            sep = jax.jit(
                lambda r, i: ops.encode_worker(r, i, gr, gi, fused=False))
            oracle = jax.jit(lambda z: jnp.fft.fft(
                jax.vmap(lambda u: mds.encode_dft(u, n))(z), axis=-1))
            wr, wi = ref.encode_worker_ref(cr, ci, g)
            err = _relerr(ref.unplanar(*fused(cr, ci)),
                          np.asarray(ref.unplanar(wr, wi)))
            assert err < 1e-3, (s, m, err)
            t = _time_interleaved({
                "fused": (fused, (cr, ci)),
                "separate": (sep, (cr, ci)),
                "oracle": (oracle, (c,)),
            }, reps=6 if s >= 262144 else 8)
            rows.append({"s": s, "m": m, "n": n, "L": ell, "batch": q,
                         "rel_err": err,
                         "fused_ms": t["fused"] * 1e3,
                         "separate_ms": t["separate"] * 1e3,
                         "oracle_ms": t["oracle"] * 1e3})
            lines.append(
                f"  encode+worker s={s} m={m} N={n}: fused "
                f"{t['fused']*1e3:.2f}ms separate {t['separate']*1e3:.2f}ms "
                f"oracle {t['oracle']*1e3:.2f}ms (rel err {err:.1e})")
    return rows


def bench_decode(lines: list) -> list[dict]:
    rows = []
    for s in ((1024,) if SMOKE else (1024, 16384, 262144)):
        for m in ((4,) if SMOKE else (4, 16, 64)):
            n = 2 * m
            ell = s // m
            q = 2 if s >= 262144 else 8
            b = _randc((q, n, ell), seed=s * m)
            g = mds.rs_generator(n, m, jnp.complex64)
            # per-request masks with uniformly-spread responders (rotated
            # every-other pattern): well-conditioned subsets at any m --
            # arbitrary half-subsets of the circle are intrinsically
            # ill-conditioned past m~16 (DESIGN.md §4), where BOTH decode
            # implementations degrade and a parity check is meaningless
            masks = np.stack([
                np.roll(np.arange(n) % 2 == 0, i) for i in range(q)])
            cache = DecodeMatrixCache(np.asarray(g))
            dmats = cache.matrices(masks)
            dr = jnp.asarray(dmats.real.astype(np.float32))
            di = jnp.asarray(dmats.imag.astype(np.float32))
            br, bi = ref.planar(b)
            subsets = jnp.asarray(np.stack(
                [DecodeMatrixCache.subset_of(row, m) for row in masks]))
            matmul = jax.jit(lambda r, i: ops.decode_apply(dr, di, r, i))
            solve = jax.jit(lambda z: jax.vmap(
                lambda bq, sq: mds.decode_from_subset(g, bq, sq))(z, subsets))
            got = ref.unplanar(*matmul(br, bi))
            want = solve(b)
            err = _relerr(got, np.asarray(want))
            assert err < 1e-3, (s, m, err)
            t = _time_interleaved({
                "matmul": (matmul, (br, bi)),
                "solve": (solve, (b,)),
            }, reps=6 if s >= 262144 else 8)
            rows.append({"s": s, "m": m, "n": n, "batch": q, "rel_err": err,
                         "matmul_ms": t["matmul"] * 1e3,
                         "solve_ms": t["solve"] * 1e3})
            lines.append(
                f"  decode s={s} m={m} N={n}: matmul {t['matmul']*1e3:.2f}ms "
                f"solve {t['solve']*1e3:.2f}ms (rel err {err:.1e})")
    return rows


def bench_rfft(lines: list) -> list[dict]:
    """The r2c acceptance measurement (DESIGN.md §7): real-input coded FFT
    vs the c2c pipeline fed the same real signal as complex, at
    s in {16k, 256k}.  Two wins claimed and asserted: HALF the worker-shard
    payload bytes on the wire, and lower wall-clock (half-length worker
    transforms) on the same bucket executor."""
    rows = []
    for s in ((16384,) if SMOKE else (16384, 262144)):
        m, n = 4, 8
        q = 2 if s >= 262144 else 4
        ell = s // m
        rng = np.random.default_rng(s)
        xb = rng.normal(size=(q, s)).astype(np.float32)
        g = mds.rs_generator(n, m, jnp.complex64)
        gr, gi = ref.planar(g)
        masks = np.stack([
            np.roll(np.arange(n) % 2 == 0, i) for i in range(q)])
        cache = DecodeMatrixCache(np.asarray(g))
        invs, subsets = cache.compact(masks)
        dvr = jnp.asarray(invs.real.astype(np.float32))
        dvi = jnp.asarray(invs.imag.astype(np.float32))
        subs = jnp.asarray(subsets)
        xr = jnp.asarray(xb)
        xi = jnp.zeros_like(xr)

        r2c = jax.jit(lambda a: ops.coded_rbucket_direct(
            a, dvr, dvi, subs, gr, gi, s))
        c2c = jax.jit(lambda a, b: ops.coded_bucket_direct(
            a, b, dvr, dvi, subs, gr, gi, s))

        want_half = np.fft.rfft(xb.astype(np.float64), axis=-1)
        err_r = _relerr(ref.unplanar(*r2c(xr)), want_half)
        assert err_r < 1e-3, err_r
        want_full = np.fft.fft(xb.astype(np.complex128), axis=-1)
        err_c = _relerr(ref.unplanar(*c2c(xr, xi)), want_full)
        assert err_c < 1e-3, err_c

        t = _time_interleaved({
            "r2c": (r2c, (xr,)),
            "c2c_on_real": (c2c, (xr, xi)),
        }, reps=6 if s >= 262144 else 8)
        # worker-shard payload: what ONE worker ships back to the master.
        # The payload claim is structural and asserted; the wall-clock
        # ratio is REPORTED (json + line) but never asserted -- a timing
        # comparison on a noisy shared CI runner would flake, and no other
        # bench assert is a timing check.
        r2c_bytes = (ell // 2) * 8          # L/2 complex64
        c2c_bytes = ell * 8                 # L complex64
        assert r2c_bytes * 2 == c2c_bytes
        rows.append({
            "s": s, "m": m, "n": n, "batch": q,
            "rel_err_r2c": err_r,
            "r2c_ms": t["r2c"] * 1e3,
            "c2c_on_real_ms": t["c2c_on_real"] * 1e3,
            "speedup": t["c2c_on_real"] / t["r2c"],
            "worker_payload_bytes_r2c": r2c_bytes,
            "worker_payload_bytes_c2c": c2c_bytes,
        })
        lines.append(
            f"  rfft s={s} m={m} N={n}: r2c {t['r2c']*1e3:.2f}ms vs "
            f"c2c-on-real {t['c2c_on_real']*1e3:.2f}ms "
            f"({t['c2c_on_real']/t['r2c']:.2f}x), payload "
            f"{r2c_bytes//1024}KiB vs {c2c_bytes//1024}KiB/worker shard "
            f"(rel err {err_r:.1e})")
    return rows


def bench_rfftn_nd(lines: list) -> list[dict]:
    """The n-D real acceptance measurement (DESIGN.md §9): CodedRFFTN vs
    the n-D c2c plan (CodedFFTND) fed the same real field as complex.
    Same (shape, m, N) code, same per-request masks, both through the
    jitted generic executor.  The structural claim -- HALF the worker
    payload elements -- is asserted; wall-clock is reported (same
    no-timing-assert protocol as the 1-D rfft section)."""
    from repro.core import CodedFFTND, CodedRFFTN
    from repro.core.coded_fft import plan_factors

    rows = []
    for shape in (((64, 64),) if SMOKE else ((128, 128), (256, 256))):
        m, n = 4, 8
        factors = plan_factors(shape, m)
        q = 4
        rplan = CodedRFFTN(shape=shape, factors=factors, n_workers=n)
        cplan = CodedFFTND(shape=shape, factors=factors, n_workers=n)
        rng = np.random.default_rng(shape[0])
        tb = jnp.asarray(rng.normal(size=(q,) + shape).astype(np.float32))
        masks = jnp.asarray(np.stack(
            [np.roll(np.arange(n) % 2 == 0, i) for i in range(q)]))
        r2c = jax.jit(lambda a: rplan.run(a, mask=masks))
        c2c = jax.jit(lambda a: cplan.run(a.astype(jnp.complex64),
                                          mask=masks))
        axes = tuple(range(-len(shape), 0))
        want_half = np.fft.rfftn(np.asarray(tb, np.float64), axes=axes)
        err_r = _relerr(r2c(tb), want_half)
        assert err_r < 1e-3, err_r
        err_c = _relerr(c2c(tb), np.fft.fftn(np.asarray(tb, np.complex128),
                                             axes=axes))
        assert err_c < 1e-3, err_c
        t = _time_interleaved({
            "rfftn": (r2c, (tb,)),
            "c2cn_on_real": (c2c, (tb,)),
        }, reps=6)
        r_elems = int(np.prod(rplan.worker_shard_shape))
        c_elems = int(np.prod(cplan.worker_shard_shape))
        assert 2 * r_elems == c_elems       # the communication claim
        rows.append({
            "shape": list(shape), "m": m, "n": n, "batch": q,
            "rel_err_rfftn": err_r,
            "rfftn_ms": t["rfftn"] * 1e3,
            "c2cn_on_real_ms": t["c2cn_on_real"] * 1e3,
            "speedup": t["c2cn_on_real"] / t["rfftn"],
            "worker_payload_bytes_rfftn": r_elems * 8,
            "worker_payload_bytes_c2cn": c_elems * 8,
        })
        lines.append(
            f"  rfftn shape={shape} m={m} N={n}: rfftn "
            f"{t['rfftn']*1e3:.2f}ms vs c2cn-on-real "
            f"{t['c2cn_on_real']*1e3:.2f}ms "
            f"({t['c2cn_on_real']/t['rfftn']:.2f}x), payload "
            f"{r_elems * 8 // 1024}KiB vs {c_elems * 8 // 1024}KiB/worker "
            f"shard (rel err {err_r:.1e})")
    return rows


def bench_service(lines: list) -> dict:
    """The acceptance measurement: default kernel path vs PR-1 oracle path
    on batched service throughput at the BENCH_service.json config."""
    s, m, n, n_req = 2048, 4, 8, (16 if SMOKE else 64)
    cfg = dict(s=s, m=m, n_workers=n, seed=0, max_batch=n_req)
    kernel = FFTService(FFTServiceConfig(**cfg))
    oracle = FFTService(FFTServiceConfig(**cfg, use_reference=True))
    rng = np.random.default_rng(3)
    xs = [(rng.normal(size=s) + 1j * rng.normal(size=s)).astype(np.complex64)
          for _ in range(n_req)]

    worst = max(
        float(np.max(np.abs(y - np.fft.fft(x))))
        for x, y in zip(xs, kernel.submit_batch(xs)))
    assert worst < 1e-2, worst
    # warm compiles (the kernel path needs no mask warm-up any more: decode
    # matrices are built in-jit, a novel mask costs what a repeat does)
    for _ in range(2 if SMOKE else 8):
        kernel.submit_batch(xs)
    oracle.submit_batch(xs)

    tk, to = [], []
    for r in range(6 if SMOKE else 30):
        pair = ((kernel, tk), (oracle, to))
        for svc, acc in (pair if r % 2 == 0 else pair[::-1]):
            t0 = time.perf_counter()
            svc.submit_batch(xs)
            acc.append(time.perf_counter() - t0)
    k_med, o_med = statistics.median(tk), statistics.median(to)
    result = {
        "s": s, "m": m, "n_workers": n, "n_requests": n_req,
        "kernel_ms_med": k_med * 1e3,
        "oracle_ms_med": o_med * 1e3,
        "kernel_rps": n_req / k_med,
        "oracle_rps": n_req / o_med,
        "speedup": o_med / k_med,
        "pairwise_win_rate": sum(a < b for a, b in zip(tk, to)) / len(tk),
        "decode_cache": {
            "hits": kernel.stats.decode_cache_hits,
            "misses": kernel.stats.decode_cache_misses,
        },
        "worst_abs_err": worst,
    }
    lines.append(
        f"  service s={s} m={m} N={n} x{n_req} reqs: kernel "
        f"{result['kernel_rps']:.0f} rps vs oracle {result['oracle_rps']:.0f} "
        f"rps -> {result['speedup']:.2f}x (win rate "
        f"{result['pairwise_win_rate']:.0%}, worst err {worst:.1e})")
    return result


def bench_cold_decode(lines: list) -> dict:
    """Novel-mask decode-matrix cost (the DESIGN.md §8 claim).

    Streams buckets of NEVER-REPEATED straggler masks through the three
    decode-matrix producers: the device-resident Lagrange build (one jitted
    call, masks in -> scatter planes out), the host LRU COLD (every mask a
    miss -> one complex128 inversion each), and the host LRU WARM (same
    masks every call -> pure hits, the pre-§8 steady-state best case).
    The claim: Lagrange pays no novel-mask penalty at all -- cold IS warm
    -- and sits within noise of the warm-LRU path end to end.  N=32 gives
    a mask space big enough that the cold stream never repeats.
    """
    m, n, q = 4, 32, 64
    reps = 4 if SMOKE else 16
    g = mds.rs_generator(n, m, jnp.complex64)
    rng = np.random.default_rng(0)

    def draw(count, rows=q, workers=n):
        out = rng.random((count, rows, workers)) < 0.6
        for b in range(count):
            for r in range(rows):
                while out[b, r].sum() < m:
                    out[b, r, rng.integers(workers)] = True
        return out

    novel = draw(2 * reps)          # distinct masks for every cold call
    fixed = draw(1)[0]              # one bucket reused for the warm path

    dev = jax.jit(lambda mk: ops.lagrange_scatter_planes(
        ops.mask_subsets(mk, m), n))
    # parity first: device planes == host matrices on the warm bucket
    cache = DecodeMatrixCache(np.asarray(g), maxsize=8192)
    want = cache.matrices(fixed)
    dr, di = dev(jnp.asarray(fixed))
    err = _relerr(np.asarray(dr) + 1j * np.asarray(di), want)
    assert err < 1e-3, err

    def host_call(masks):
        dmats = cache.matrices(masks)
        planes = np.stack([dmats.real, dmats.imag]).astype(np.float32)
        return jnp.asarray(planes)

    def dev_call(masks):
        return dev(jnp.asarray(masks))

    jax.block_until_ready(dev_call(fixed))
    host_call(fixed)
    t_dev_cold, t_dev_warm, t_host_cold, t_host_warm = [], [], [], []
    for r in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(dev_call(novel[2 * r]))
        t_dev_cold.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(host_call(novel[2 * r + 1]))   # all misses
        t_host_cold.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(dev_call(fixed))
        t_dev_warm.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(host_call(fixed))              # all hits
        t_host_warm.append(time.perf_counter() - t0)
    med = lambda ts: statistics.median(ts)
    result = {
        "m": m, "n": n, "bucket": q, "rel_err": err,
        "lagrange_novel_ms": med(t_dev_cold) * 1e3,
        "lagrange_warm_ms": med(t_dev_warm) * 1e3,
        "host_lru_cold_ms": med(t_host_cold) * 1e3,
        "host_lru_warm_ms": med(t_host_warm) * 1e3,
        "cold_penalty_lagrange": med(t_dev_cold) / med(t_dev_warm),
        "cold_penalty_host_lru": med(t_host_cold) / med(t_host_warm),
    }
    lines.append(
        f"  cold-mask decode m={m} N={n} x{q}: lagrange novel "
        f"{result['lagrange_novel_ms']:.3f}ms (warm "
        f"{result['lagrange_warm_ms']:.3f}ms) vs host LRU cold "
        f"{result['host_lru_cold_ms']:.3f}ms / warm "
        f"{result['host_lru_warm_ms']:.3f}ms -> novel-mask penalty "
        f"{result['cold_penalty_lagrange']:.2f}x vs "
        f"{result['cold_penalty_host_lru']:.2f}x")

    # -- end to end at the service config: novel-mask DEVICE bucket vs the
    # warm-LRU bucket (matrices all cache hits, the pre-§8 best case).
    # The Lagrange build fuses into the bucket executor, so its marginal
    # cost disappears into the bucket's own compute: novel masks no longer
    # pay a host inversion anywhere.
    s, n8, q8 = 2048, 8, (16 if SMOKE else 64)
    g8 = mds.rs_generator(n8, m, jnp.complex64)
    g8r, g8i = ref.planar(g8)
    xr, xi = ref.planar(_randc((q8, s), seed=1))

    @jax.jit
    def dev_bucket(xr_, xi_, mk):
        sub = ops.mask_subsets(mk, m)
        ivr, ivi = ops.lagrange_compact_planes(sub, n8)
        return ops.coded_bucket_direct(xr_, xi_, ivr, ivi, sub, g8r, g8i, s)

    @jax.jit
    def warm_bucket(xr_, xi_, dvr, dvi, sub):
        return ops.coded_bucket_direct(xr_, xi_, dvr, dvi, sub, g8r, g8i, s)

    cache8 = DecodeMatrixCache(np.asarray(g8), maxsize=512)
    fixed8 = draw(1, q8, n8)[0]
    novel8 = draw(reps, q8, n8)     # one fresh bucket per timed rep
    cache8.compact(fixed8)          # prime: the warm path is all hits

    def warm_call():
        invs, subs = cache8.compact(fixed8)
        planes = np.stack([invs.real, invs.imag]).astype(np.float32)
        return warm_bucket(xr, xi, jnp.asarray(planes[0]),
                           jnp.asarray(planes[1]), jnp.asarray(subs))

    jax.block_until_ready(dev_bucket(xr, xi, jnp.asarray(novel8[0])))
    jax.block_until_ready(warm_call())
    t_dev_e2e, t_warm_e2e = [], []
    for r in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(dev_bucket(xr, xi, jnp.asarray(novel8[r])))
        t_dev_e2e.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(warm_call())
        t_warm_e2e.append(time.perf_counter() - t0)
    result["bucket_e2e"] = {
        "s": s, "m": m, "n": n8, "bucket": q8,
        "lagrange_novel_ms": med(t_dev_e2e) * 1e3,
        "host_lru_warm_ms": med(t_warm_e2e) * 1e3,
        "novel_vs_warm": med(t_dev_e2e) / med(t_warm_e2e),
    }
    lines.append(
        f"  cold-mask bucket e2e s={s} m={m} N={n8} x{q8}: lagrange novel "
        f"{result['bucket_e2e']['lagrange_novel_ms']:.2f}ms vs warm-LRU "
        f"{result['bucket_e2e']['host_lru_warm_ms']:.2f}ms -> "
        f"{result['bucket_e2e']['novel_vs_warm']:.2f}x")
    return result


def bench_wkv(lines: list) -> None:
    """WKV recurrence kernel parity (unchanged from the seed bench)."""
    from repro.kernels.wkv import wkv_pallas
    from repro.models.rwkv6 import wkv_scan_reference

    b, h, t, kd = 1, 2, 64, 32
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    mk = lambda i, sh: jax.random.normal(ks[i], sh, jnp.float32)
    r, kk, vv = (mk(i, (b, t, h, kd)) for i in range(3))
    lw = jnp.maximum(-jnp.abs(mk(3, (b, t, h, kd))), -8.0)
    u = mk(4, (h, kd))
    s0 = mk(5, (b, h, kd, kd))
    fl = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, kd)
    o, _ = wkv_pallas(fl(r), fl(kk), fl(vv), fl(lw), jnp.tile(u, (b, 1)),
                      s0.reshape(b * h, kd, kd), interpret=True)
    o_ref, _ = wkv_scan_reference(r, kk, vv, lw, u, s0)
    err = float(jnp.max(jnp.abs(o - fl(o_ref))))
    assert err < 5e-3
    lines.append(f"  wkv (BH={b * h}, T={t}, K={kd}) abs err {err:.2e}")


def run() -> list[str]:
    lines = ["bench_kernels: Pallas hot path vs jnp oracle -> BENCH_kernels.json"]
    result = {
        "backend": jax.default_backend(),
        "fourstep": bench_fourstep(lines),
        "streaming": bench_streaming(lines),
        "encode_worker": bench_encode_worker(lines),
        "decode": bench_decode(lines),
        "cold_decode": bench_cold_decode(lines),
        "rfft": bench_rfft(lines),
        "rfftn": bench_rfftn_nd(lines),
        "service_throughput": bench_service(lines),
    }
    bench_wkv(lines)
    if SMOKE:
        lines.append("  [BENCH_SMOKE=1: tiny shapes, artifact not written]")
        return lines
    out_path = pathlib.Path(__file__).resolve().parent.parent / "BENCH_kernels.json"
    out_path.write_text(json.dumps(result, indent=2) + "\n")
    lines.append(f"  [written to {out_path}]")
    return lines


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(pathlib.Path(__file__).resolve().parents[1])
    print("\n".join(run()))
