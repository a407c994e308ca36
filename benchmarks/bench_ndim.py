"""n-dimensional + multi-input coded FFT (Theorems 3 & 5).

Verifies K* = m for 2-D/3-D transforms and the q-input bundling strategy,
and times encode/worker/decode stages.
"""

from __future__ import annotations

import pathlib
import time

import jax
import jax.numpy as jnp

from repro.core import CodedFFTMultiInput, CodedFFTND, plan_factors


def _t(fn, *a):
    jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*a))
    return time.perf_counter() - t0


def run() -> list[str]:
    lines = ["bench_ndim: n-D and multi-input coded FFT (K* = m)"]
    key = jax.random.PRNGKey(0)

    for shape, m, n in [((64, 64), 4, 8), ((32, 32, 16), 4, 6),
                        ((128, 64), 8, 12)]:
        factors = plan_factors(shape, m)
        plan = CodedFFTND(shape=shape, factors=factors, n_workers=n)
        t = (jax.random.normal(key, shape) + 1j * jax.random.normal(key, shape)
             ).astype(jnp.complex64)
        ref = jnp.fft.fftn(t)
        mask = jnp.arange(n) % 2 == 0  # half the workers straggle...
        mask = mask.at[:m].set(True) if int(mask.sum()) < m else mask
        run_fn = jax.jit(lambda tt: plan.run(tt, mask=mask))
        out = run_fn(t)
        err = float(jnp.max(jnp.abs(out - ref)))
        dt = _t(run_fn, t)
        lines.append(f"  {len(shape)}-D {shape} m={m} (factors {factors}) "
                     f"N={n}: err {err:.2e}, {dt * 1e3:.1f} ms e2e, "
                     f"threshold {plan.recovery_threshold}")

    # multi-input (Thm 5): q inputs, bundled MDS (m = m_tilde * prod(factors))
    q, shape, n = 8, (64, 32), 8
    plan = CodedFFTMultiInput(q=q, shape=shape, m_tilde=2, factors=(2, 1),
                              n_workers=n)
    ts = (jax.random.normal(key, (q,) + shape)
          + 1j * jax.random.normal(key, (q,) + shape)).astype(jnp.complex64)
    refs = jnp.fft.fftn(ts, axes=(1, 2))
    mask = jnp.asarray([True, False, True, True, False, True, False, True])
    out = jax.jit(lambda xx: plan.run(xx, mask=mask))(ts)
    err = float(jnp.max(jnp.abs(out - refs)))
    lines.append(f"  multi-input q={q} {shape} m_tilde=2 factors=(2,1) "
                 f"(m={plan.m}) N={n}: err {err:.2e}, "
                 f"threshold {plan.recovery_threshold}")
    return lines


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(pathlib.Path(__file__).resolve().parents[1])
    print("\n".join(run()))
