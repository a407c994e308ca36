"""Time the host link on one TPU chip: complex64 against float32 words.

Usage (from the repository root, on a machine with a TPU)::

    python tools/transfer_probe.py [--reps 5]

For the bucket of each configuration in ``bench/configs/`` (its
``max_batch`` requests of its kind at its ``s``) it times, on the host
clock:

* ``jax.device_put`` of the bucket's complex64 array and of the same
  bytes as float32 words, each until the device holds it;
* ``jax.device_get`` of a ready complex64 array and of the same bytes as
  float32 words;
* the service's two conversions (``serving/fft_service.py``):
  ``to_words`` and ``from_words``, back to back (``*_ms``: device-bound
  at large buckets) and one call at a time from dispatch to ready
  (``*_call_ms``: what one bucket pays at small ones).

Every complex array is the bucket's complex side: the requests of a c2c
or c2r bucket, the answers of a c2c or r2c one.  Each line of standard
output is one JSON object per bucket; lists hold one reading per
repetition, in ms.  The script refuses to run anywhere but on a TPU: a
timing of another backend says nothing about the chip's link.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def bucket_shape(config: dict) -> tuple[int, int]:
    """``(rows, complex length)`` of one full bucket's complex side."""
    s, rows = int(config["s"]), int(config["service"]["max_batch"])
    return rows, (s if config["kind"] == "c2c" else s // 2 + 1)


def _ms(t0: float) -> float:
    return round((time.perf_counter() - t0) * 1e3, 3)


def probe(rows: int, k: int, reps: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.serving.fft_service import (_host_words, from_words,
                                           to_words)

    rng = np.random.default_rng(0)
    z = (rng.standard_normal((rows, k))
         + 1j * rng.standard_normal((rows, k))).astype(np.complex64)
    words = _host_words(z)
    bump = jax.jit(lambda a: a + 1)   # a fresh ready array for each copy
    res: dict = {"rows": rows, "k": k, "bytes": z.nbytes}
    for name, host in (("c64", z), ("f32_words", words)):
        puts, gets = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            dev = jax.block_until_ready(jax.device_put(host))
            puts.append(_ms(t0))
            dev = jax.block_until_ready(bump(dev))
            t0 = time.perf_counter()
            jax.device_get(dev)
            gets.append(_ms(t0))
        res[f"put_{name}_ms"], res[f"get_{name}_ms"] = puts, gets
    for name, fn, arg in (("to_words", to_words, jnp.asarray(z)),
                          ("from_words", from_words, jnp.asarray(words))):
        jax.block_until_ready(fn(arg))                    # compile
        n = 10
        runs, calls = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                out = fn(arg)
            jax.block_until_ready(out)
            runs.append(round(_ms(t0) / n, 3))
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arg))
            calls.append(_ms(t0))
        res[f"{name}_ms"], res[f"{name}_call_ms"] = runs, calls
        compiled = fn.lower(arg).compile()
        res[f"{name}_temp_bytes"] = compiled.memory_analysis(
        ).temp_size_in_bytes
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src")]

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"transfer_probe: JAX found {dev.platform!r} "
              f"({dev.device_kind}), not a TPU", file=sys.stderr)
        return 2
    for path in sorted((ROOT / "bench" / "configs").glob("*.json")):
        rows, k = bucket_shape(json.loads(path.read_text()))
        res = {"config": path.stem, "device": dev.device_kind,
               **probe(rows, k, args.reps)}
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
