"""Chip smoke: drive the coded-FFT service once on a TPU and check it.

Usage (from the repository root, on a machine with a TPU)::

    python chip_smoke.py               # one chip: FFTService + StreamingFFTService
    python chip_smoke.py --four-chips  # the DistributedCodedPlan mesh path only

Phases on one chip, all through the service's public entry points with
its own simulated straggler masks and random inputs made from ``--seed``:

* ``s=2^20`` c2c through ``FFTService``: one bucket of 16 requests
  (128 MiB of complex64 ingress), the over-VMEM streaming kernel;
* ``s=2^16`` c2c, r2c and c2r through ``FFTService``: the whole-bucket
  kernels;
* ``StreamingFFTService`` over the ``s=2^16`` service: 32 requests across
  two SLO tiers.

With ``--four-chips`` it runs only the mesh path: ``s=2^20`` c2c through
``FFTService(..., mesh=...)`` and ``DistributedCodedPlan.run_sharded`` on
a 4-device mesh (N=8 coded workers, two per chip).

Every output is compared with ``numpy.fft`` in float64:
``max|X - X_np| <= 1e-4 * max|X_np|``.  The per-bucket lines (kernel
variant, compile and set-up seconds, peak device bytes) are smoke
diagnostics, not benchmark numbers.  The last line of standard output is
one JSON object naming the device; any failure exits non-zero before it.
The script refuses to run anywhere but on a TPU.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
RTOL = 1e-4  # the f32 kernel tolerance of tests/test_kernel_pipeline.py
KERNELS = (
    "coded_fft_bucket_streaming_masked", "coded_fft_bucket_streaming",
    "coded_fft_bucket_masked", "coded_fft_bucket",
    "coded_rfft_bucket_masked", "coded_rfft_bucket",
    "coded_irfft_bucket_masked", "coded_irfft_bucket",
    "encode_fourstep_fused", "fourstep_fft_fused", "fourstep_fft_stage1",
    "fourstep_fft_stage2", "fourstep_fft_streaming", "fourstep_fft_multistep",
    "cmatmul", "bcmatmul", "recombine_twiddle_dft",
    "recombine_twiddle_dft_batched",
)


class SmokeError(RuntimeError):
    pass


def check(label: str, got, want) -> float:
    """Relative max error of one output against its float64 reference."""
    got = np.asarray(got)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise SmokeError(f"{label}: shape {got.shape} (want {want.shape}) "
                         f"or non-finite values")
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    if not err <= RTOL:
        raise SmokeError(f"{label}: max error {err:.3e} > {RTOL:.0e} "
                         f"of max |X|")
    return err


def kernels_of(fn, *args) -> list[str]:
    """The Pallas kernels one jitted call lowers to, in compiled mode."""
    import jax

    text = str(jax.make_jaxpr(fn)(*args))
    if "interpret=True" in text:
        raise SmokeError("a Pallas kernel would run in interpret mode")
    names = set(re.findall(r"\bname=(\w+)", text))
    return sorted(k for k in KERNELS if k in names)


def variant_of(kernels: list[str]) -> str:
    if not kernels:
        raise SmokeError("the bucket lowers to no Pallas kernel")
    if any("streaming" in k for k in kernels):
        return "streaming"
    if any("bucket" in k for k in kernels):
        return "fused"
    return "stage"


def peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def requests(rng, kind: str, s: int, q: int):
    """Random requests of one kind and their float64 numpy references."""
    if kind == "c2c":
        x = (rng.standard_normal((q, s)) + 1j * rng.standard_normal((q, s))
             ).astype(np.complex64)
        return x, np.fft.fft(x.astype(np.complex128), axis=-1)
    if kind == "r2c":
        x = rng.standard_normal((q, s)).astype(np.float32)
        return x, np.fft.rfft(x.astype(np.float64), axis=-1)
    t = rng.standard_normal((q, s))
    y = np.fft.rfft(t, axis=-1).astype(np.complex64)
    return y, np.fft.irfft(y.astype(np.complex128), n=s, axis=-1)


def serve_bucket(svc, kind: str, s: int, q: int, rng) -> None:
    """Warm one (s, kind, q) bucket, then serve q requests and check them."""
    import jax
    import jax.numpy as jnp

    if not svc._kernel_path(s, kind):
        raise SmokeError(f"{kind} s={s} is off the kernel path")
    t0 = time.perf_counter()
    xs, want = requests(rng, kind, s, q)
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    svc.warmup(lengths=[s], kinds=(kind,), buckets=[q])
    compile_s = time.perf_counter() - t0
    xb = jax.ShapeDtypeStruct(xs.shape, xs.dtype)
    mk = jax.ShapeDtypeStruct((q, svc.cfg.n_workers), jnp.bool_)
    kernels = kernels_of(svc._runner_for(s, q, kind), xb, mk)
    t0 = time.perf_counter()
    out = svc.submit_batch(list(xs), kind=kind)
    serve_s = time.perf_counter() - t0
    err = max(check(f"{kind} s={s} row {i}", o, w)
              for i, (o, w) in enumerate(zip(out, want)))
    print(f"[smoke] {kind} s={s} bucket={q} variant={variant_of(kernels)} "
          f"kernels={','.join(kernels)} setup_s={setup_s:.2f} "
          f"compile_s={compile_s:.2f} serve_s={serve_s:.2f} "
          f"max_rel_err={err:.3e} peak_bytes_in_use={peak_bytes()}",
          flush=True)


def streaming_front_end(svc, s: int, rng) -> None:
    """32 requests across two SLO tiers through StreamingFFTService."""
    from repro.serving.streaming import StreamConfig, StreamingFFTService

    t0 = time.perf_counter()
    svc.warmup(lengths=[s], kinds=("c2c",))   # every bucket size it may form
    compile_s = time.perf_counter() - t0
    xs, want = requests(rng, "c2c", s, 32)
    tiers = ("interactive", "batch")
    cfg = StreamConfig(tiers={"interactive": 0.002, "batch": 0.050},
                       default_tier="batch")
    stream = StreamingFFTService(svc, cfg)
    try:
        futs = [stream.submit(x, "c2c", tier=tiers[i % 2])
                for i, x in enumerate(xs)]
        outs = [f.result(timeout=600) for f in futs]
    finally:
        stream.close()
    err = max(check(f"stream c2c s={s} row {i}", o, w)
              for i, (o, w) in enumerate(zip(outs, want)))
    st = svc.stats.summary()
    print(f"[smoke] streaming front end s={s} requests=32 tiers={tiers} "
          f"compile_s={compile_s:.2f} dispatches fill/deadline/drain="
          f"{st['fill_dispatches']}/{st['deadline_dispatches']}/"
          f"{st['drain_dispatches']} max_rel_err={err:.3e} "
          f"peak_bytes_in_use={peak_bytes()}", flush=True)


def one_chip(seed: int) -> None:
    from repro.serving.fft_service import FFTService, FFTServiceConfig

    rng = np.random.default_rng(seed)
    # autotune=False: the tiling search is set-up time the benchmark
    # reports; the smoke runs the static dispatch
    big = FFTService(FFTServiceConfig(s=2 ** 20, m=4, n_workers=8,
                                      max_batch=16, seed=seed,
                                      autotune=False))
    serve_bucket(big, "c2c", 2 ** 20, 16, rng)
    del big
    svc = FFTService(FFTServiceConfig(s=2 ** 16, m=4, n_workers=8,
                                      max_batch=16, seed=seed,
                                      autotune=False))
    for kind in ("c2c", "r2c", "c2r"):
        serve_bucket(svc, kind, 2 ** 16, 16, rng)
    streaming_front_end(svc, 2 ** 16, rng)


def four_chips(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.distributed.coded_runtime import DistributedCodedPlan
    from repro.serving.fft_service import FFTService, FFTServiceConfig

    devs = jax.devices()[:4]
    if len(devs) < 4:
        raise SmokeError(f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.array(devs), ("workers",))
    s, q = 2 ** 20, 4
    rng = np.random.default_rng(seed)
    svc = FFTService(FFTServiceConfig(s=s, m=4, n_workers=8, max_batch=q,
                                      seed=seed, autotune=False), mesh=mesh)
    xs, want = requests(rng, "c2c", s, q)
    t0 = time.perf_counter()
    svc.warmup(lengths=[s], kinds=("c2c",), buckets=[q])
    compile_s = time.perf_counter() - t0
    out = svc.submit_batch(list(xs))
    err = max(check(f"mesh service row {i}", o, w)
              for i, (o, w) in enumerate(zip(out, want)))
    print(f"[smoke] mesh FFTService c2c s={s} bucket={q} workers=8 "
          f"devices={len(devs)} compile_s={compile_s:.2f} "
          f"max_rel_err={err:.3e}", flush=True)

    runtime = DistributedCodedPlan(svc.plan, mesh, "workers")
    mask = jnp.asarray(np.array([1, 0, 1, 1, 0, 1, 1, 0], bool))
    run = jax.jit(runtime.run_sharded)
    t0 = time.perf_counter()
    xmat = jax.block_until_ready(run(jnp.asarray(xs[0]), mask))
    compile_s = time.perf_counter() - t0
    placement = sorted(d.id for d in xmat.sharding.device_set)
    if len(placement) != 4:
        raise SmokeError(f"run_sharded output spans devices {placement}")
    err = check("run_sharded", np.asarray(xmat).reshape(s), want[0])
    print(f"[smoke] DistributedCodedPlan.run_sharded s={s} stragglers=3 "
          f"output devices={placement} sharding={xmat.sharding} "
          f"compile_s={compile_s:.2f} max_rel_err={err:.3e}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device DistributedCodedPlan phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU", file=sys.stderr)
        return 1
    from repro.kernels import ops

    if ops._mode(None) != "compiled":
        print(f"[smoke] FAIL: kernel mode {ops._mode(None)!r} on a TPU",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache(ROOT)
    print(f"[smoke] device={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeError as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
