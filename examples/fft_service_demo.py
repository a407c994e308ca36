"""The paper's own application end-to-end: a straggler-tolerant FFT service.

Submits a stream of transform requests; each request's workers draw
shifted-exponential latencies, the service answers after the fastest m,
and every answer is verified against jnp.fft.  With ``--mesh`` the worker
compute runs under shard_map across a device mesh sized from the devices
present: the most devices (up to 8) that divide the N=8 coded workers --
4 on a v5e host, two workers per chip.  Without it the same math runs
locally.

Run:  PYTHONPATH=src python examples/fft_service_demo.py
      XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
          PYTHONPATH=src python examples/fft_service_demo.py --mesh
"""

import argparse

import jax
import jax.numpy as jnp

from repro.distributed.straggler import StragglerModel
from repro.serving import FFTService, FFTServiceConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", action="store_true",
                    help="run workers under shard_map over the devices "
                         "present")
    ap.add_argument("--requests", type=int, default=12)
    args = ap.parse_args()

    n_workers = 8
    mesh = None
    if args.mesh:
        from repro.distributed import test_mesh

        p = max(d for d in (1, 2, 4, 8)
                if d <= jax.device_count() and n_workers % d == 0)
        mesh = test_mesh((p,), ("workers",))
        print(f"[demo] shard_map over {p} of {jax.device_count()} devices "
              f"({n_workers // p} coded workers per device)")

    svc = FFTService(
        FFTServiceConfig(s=4096, m=4, n_workers=n_workers,
                         straggler=StragglerModel(t0=1.0, mu=1.0)),
        mesh=mesh)

    # the batched scheduler: one jitted encode/decode per (s, m) bucket,
    # per-request straggler masks (DESIGN.md §5)
    key = jax.random.PRNGKey(0)
    xs = []
    for i in range(args.requests):
        key, k1, k2 = jax.random.split(key, 3)
        xs.append((jax.random.normal(k1, (4096,))
                   + 1j * jax.random.normal(k2, (4096,))).astype(jnp.complex64))
    for x, y in zip(xs, svc.submit_batch(xs)):
        err = float(jnp.max(jnp.abs(y - jnp.fft.fft(x))))
        assert err < 1e-2, err
    st = svc.stats.summary()
    print(f"[demo] {st['requests']} requests all correct "
          f"({st['batches']} scheduler batch(es))")
    print(f"[demo] mean latency: coded {st['mean_coded_latency']:.3f}s, "
          f"wait-for-all {st['mean_uncoded_latency']:.3f}s "
          f"-> {st['speedup']:.2f}x faster")
    print(f"[demo] stragglers tolerated (worker-requests never waited on): "
          f"{st['stragglers_tolerated']}")


if __name__ == "__main__":
    main()
