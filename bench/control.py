"""The control: the plain transform, one precision step down, in the program's place.

The configurations state float32 arithmetic at full precision (on the TPU,
matmuls at ``Precision.HIGHEST``).  The step below it, the one a later
change could be tempted to take, is ``Precision.HIGH``: three bf16 passes.
:func:`dft` computes the transform as a four-step matmul DFT whose every
product is taken that way, written out (each f32 operand split into a
bf16 head and a bf16 tail, the tail-by-tail product dropped), so that it
reads the same on the CPU as on the chip.  With ``precision="highest"``
it is the same DFT at full f32 precision.

:func:`patch` puts it in the service's place: every bucket executor is
replaced by it (the straggler masks are ignored), and the rest of the run
is unchanged -- traffic, window, the kept sample and the check.  The
check then has to read ``correct: false``.

    python3 bench/control.py --workload <cell> --seconds 3 \
        --control-seeds 1 2 3 --program-seeds 4 5 6 ...

runs, in one process on the chip, the control and then the program
itself on the given seeds, and prints each run's numbers compared.  The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _bf16(a):
    import jax

    # reduce_precision, not a round trip through bfloat16: XLA may drop a
    # convert pair as excess precision, which on the TPU turned the three
    # passes into one
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    import jax.numpy as jnp

    a = jnp.asarray(a, jnp.float32)
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _mm(a, b, precision: str):
    import jax
    import jax.numpy as jnp

    full = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(a, b, precision=full)
    if precision == "high":
        ah, al = _split(a)
        bh, bl = _split(b)
        return (jnp.matmul(ah, bh, precision=full)
                + jnp.matmul(ah, bl, precision=full)
                + jnp.matmul(al, bh, precision=full))
    raise ValueError(f"unknown precision {precision!r}")


def _cmm(ar, ai, br, bi, precision):
    return (_mm(ar, br, precision) - _mm(ai, bi, precision),
            _mm(ar, bi, precision) + _mm(ai, br, precision))


def _dft_planes(n: int):
    k = np.arange(n)
    ang = -2.0 * np.pi * (np.outer(k, k) % n) / n
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def dft(xr, xi, precision: str):
    """Four-step DFT of ``(q, n)`` f32 planes, ``n = n1 * n2``: column
    DFTs, twiddles (from float64), row DFTs, transpose."""
    q, n = xr.shape
    n1 = 1 << (int(np.log2(n)) // 2)
    n2 = n // n1
    f1r, f1i = _dft_planes(n1)
    f2r, f2i = _dft_planes(n2)
    ang = -2.0 * np.pi * (np.outer(np.arange(n1), np.arange(n2)) % n) / n
    twr, twi = np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)
    ar, ai = _cmm(f1r, f1i, xr.reshape(q, n1, n2), xi.reshape(q, n1, n2),
                  precision)
    ar, ai = ar * twr - ai * twi, ar * twi + ai * twr
    br, bi = _cmm(ar, ai, f2r, f2i, precision)
    return (br.swapaxes(1, 2).reshape(q, n), bi.swapaxes(1, 2).reshape(q, n))


def patch(precision: str = "high"):
    """A ``run_cell`` patch that puts :func:`dft` in every bucket
    executor's place."""
    import jax
    import jax.numpy as jnp

    def apply(svc):
        made = {}

        def runner_for(s, bucket, kind="c2c"):
            def fn(xb, masks):
                if kind == "r2c":
                    yr, yi = dft(xb, jnp.zeros_like(xb), precision)
                    h = s // 2 + 1
                    return jax.lax.complex(yr[:, :h], yi[:, :h])
                yr, yi = dft(jnp.real(xb), jnp.imag(xb), precision)
                return jax.lax.complex(yr, yi)

            key = (s, bucket, kind)
            if key not in made:
                made[key] = jax.jit(fn)
            return made[key]

        svc._runner_for = runner_for

    return apply


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax

    from bench import harness

    harness.CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(harness.CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    runs = ([("control", s) for s in args.control_seeds]
            + [("program", s) for s in args.program_seeds])
    for who, seed in runs:
        res = harness.run_cell(
            args.workload, seed, args.seconds, False, time.perf_counter(),
            patch=patch() if who == "control" else None)
        nums = {k: v["value"] for k, v in res["checks"].items()}
        print(f"[{who}] workload={args.workload} "
              f"seed={seed} correct={res['correct']} "
              f"attempted={res['attempted']} {json.dumps(nums)}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
