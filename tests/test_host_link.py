"""The host link (DESIGN.md §8): complex bucket I/O crosses between host
and device as real words of the same bytes, rebuilt on the far side, and
no bit of a request or an answer changes on the way."""

import glob
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.kernels import ops
from repro.serving import (FFTService, FFTServiceConfig, StreamConfig,
                           StreamingFFTService, spans)
from repro.serving.fft_service import (_host_complex, _host_words,
                                       from_words, to_words)

S, CAP = 256, 4
SHAPE = (16, 16)        # n-D kinds: the time-domain shape


def _cfg(**kw):
    kw.setdefault("s", S)
    kw.setdefault("m", 4)
    kw.setdefault("n_workers", 8)
    kw.setdefault("seed", 0)
    kw.setdefault("max_batch", CAP)
    kw.setdefault("autotune", False)
    return FFTServiceConfig(**kw)


def _specials(shape, dtype, seed=0):
    """Random complex values with NaN, infinities, signed zeros and
    subnormals planted in both parts."""
    rng = np.random.default_rng(seed)
    z = (rng.normal(size=shape) + 1j * rng.normal(size=shape)).astype(dtype)
    flat = z.reshape(-1).view(np.finfo(dtype).dtype)
    tiny = np.finfo(flat.dtype).smallest_subnormal
    flat[:8] = [np.nan, np.inf, -np.inf, -0.0, 0.0, tiny, -tiny, 1.0]
    return z


@pytest.mark.parametrize("shape", [(3, 5), (2, 3, 1025)])
def test_words_round_trip_is_bit_exact(shape):
    """Both directions keep every bit, special values included, and the
    host sides are views of the same bytes."""
    z = _specials(shape, np.complex64)
    w = _host_words(z)
    assert w.dtype == np.float32 and w.shape == shape[:-1] + (2 * shape[-1],)
    assert np.shares_memory(w, z)
    back = jax.device_get(from_words(jnp.asarray(w)))
    assert back.dtype == np.complex64
    np.testing.assert_array_equal(back.view(np.uint32), z.view(np.uint32))
    words = jax.device_get(to_words(jnp.asarray(z)))
    assert words.dtype == np.float32
    np.testing.assert_array_equal(words.view(np.uint32), w.view(np.uint32))
    rows = _host_complex(words)
    assert rows.dtype == np.complex64 and rows.shape == shape
    assert np.shares_memory(rows, words)


@pytest.mark.parametrize("shape", [(2, 8192), (16, 4096)])
def test_interleave_kernel_is_bit_exact(shape):
    """The TPU interleave kernel, run through Pallas's interpreter, lays
    out exactly numpy's complex bytes, special values included."""
    z = _specials(shape, np.complex64, seed=1)
    got = ops.interleave_words(jnp.asarray(z.real), jnp.asarray(z.imag),
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  _host_words(z).view(np.uint32))


def _request(kind, seed):
    rng = np.random.default_rng(seed)
    if kind == "r2c":
        return rng.normal(size=S).astype(np.float32)
    if kind == "rfftn":
        return rng.normal(size=SHAPE).astype(np.float32)
    if kind == "c2r":
        return np.fft.rfft(rng.normal(size=S)).astype(np.complex64)
    if kind == "irfftn":
        return np.fft.rfftn(rng.normal(size=SHAPE)).astype(np.complex64)
    return (rng.normal(size=S) + 1j * rng.normal(size=S)).astype(
        np.complex64)


def _reference(kind, x):
    return {"c2c": np.fft.fft, "r2c": np.fft.rfft, "c2r": np.fft.irfft,
            "rfftn": np.fft.rfftn, "irfftn": np.fft.irfftn}[kind](x)


@pytest.mark.parametrize("kind", ["c2c", "r2c", "c2r", "rfftn", "irfftn"])
def test_bucket_io_crosses_as_float32(kind):
    """The staged request array and the launched result are float32 for
    every kind; a complex one holds twice the last axis, as words.  The
    counters add up the bytes that crossed."""
    svc = FFTService(_cfg())
    xs = [_request(kind, i) for i in range(CAP)]
    s = svc.bucket_key(xs[0], kind)
    bucket, args = svc.stage_bucket(s, kind, xs)
    x_in = args[0]
    assert x_in.dtype == jnp.float32
    last = xs[0].shape[-1] * (2 if np.iscomplexobj(xs[0]) else 1)
    assert x_in.shape == (bucket,) + xs[0].shape[:-1] + (last,)
    assert svc.stats.h2d_bytes == sum(a.nbytes for a in args)

    launched = svc.launch_bucket(s, bucket, kind, args)
    out = launched.out
    assert out.dtype == jnp.float32
    want = [_reference(kind, x) for x in xs]
    assert launched.words == np.iscomplexobj(want[0])
    last = want[0].shape[-1] * (2 if launched.words else 1)
    assert out.shape == (bucket,) + want[0].shape[:-1] + (last,)

    rows, errors = svc.fetch_bucket(launched)
    assert errors is None and svc.stats.d2h_bytes == out.nbytes
    assert rows.dtype == (np.complex64 if launched.words else np.float32)
    for y, ref in zip(rows, want):
        assert y.shape == ref.shape
        assert np.abs(y - ref).max() < 1e-2


def test_c2c_runner_still_donates_in_place():
    """The c2c executor still takes a complex array and gives its buffer
    to the same-shape result: the array it was handed is deleted after
    the call, and what crossed the link was float32."""
    svc = FFTService(_cfg())
    make, seen = svc._runner_for, []

    def runner_for(s, bucket, kind="c2c"):
        fn = make(s, bucket, kind)

        def call(x, *rest):
            seen.append(x)
            return fn(x, *rest)

        return call

    svc._runner_for = runner_for
    xs = [_request("c2c", i) for i in range(CAP)]
    bucket, args = svc.stage_bucket(S, "c2c", xs)
    assert args[0].dtype == jnp.float32
    rows, _ = svc.fetch_bucket(svc.launch_bucket(S, bucket, "c2c", args))
    (x,) = seen
    assert x.dtype == jnp.complex64 and x.shape == (bucket, S)
    assert x.is_deleted()
    for x, y in zip(xs, rows):
        assert np.abs(y - np.fft.fft(x)).max() < 1e-2


@pytest.mark.parametrize("front", ["submit_batch", "streaming"])
@pytest.mark.parametrize("kind", ["c2c", "r2c"])
def test_served_answers_match_numpy(front, kind):
    """Both front ends answer ``numpy.fft`` within the service's f32
    tolerance, with complex64 rows, over a partial bucket and a full
    one; every answer crossed as words."""
    svc = FFTService(_cfg())
    xs = [_request(kind, i) for i in range(CAP + 2)]
    if front == "submit_batch":
        ys = svc.submit_batch(xs, kind=kind)
    else:
        with StreamingFFTService(svc, StreamConfig(slack_s=30.0)) as stream:
            futs = [stream.submit(x, kind=kind) for x in xs]
            assert stream.drain(timeout=120)    # the 2 left over: a drain
            ys = [f.result(timeout=120) for f in futs]
    for x, y in zip(xs, ys):
        ref = _reference(kind, x)
        assert y.dtype == np.complex64 and y.shape == ref.shape
        assert np.abs(y - ref).max() < 1e-2
    assert svc.stats.d2h_bytes == sum(
        b * (S if kind == "c2c" else S // 2 + 1) * 8 for b in (CAP, 2))


def _c2c_reference():
    """The benchmark's plain float64 reference of the c2c transform."""
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench"
            / "references" / "c2c.py")
    spec = importlib.util.spec_from_file_location("bench_ref_c2c", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.transform


def _pack_spans(log_dir):
    """The arguments of every ``fft.stage.pack`` span in a trace."""
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [dict(e.stats) for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU" for line in plane.lines
            for e in line.events if e.name == spans.STAGE_PACK]


def test_staging_buffer_is_not_reused_while_its_bucket_is_in_flight(
        tmp_path):
    """Two buckets staged and launched before either is fetched each pack
    into a buffer of their own, and both answer the reference.  Once they
    are fetched, the next bucket packs into a reused buffer, and its pack
    span says so."""
    svc = FFTService(_cfg())
    transform = _c2c_reference()
    buckets = [[_request("c2c", 10 * b + i) for i in range(CAP)]
               for b in (1, 2)]
    launched = []
    for xs in buckets:
        bucket, args = svc.stage_bucket(S, "c2c", xs)
        launched.append(svc.launch_bucket(S, bucket, "c2c", args))
    assert svc.stats.staging_allocated == 2
    assert svc.stats.staging_reused == 0
    for xs, done in zip(buckets, launched):
        rows, _ = svc.fetch_bucket(done)
        for x, y in zip(xs, rows):
            ref = transform(x)
            assert np.linalg.norm(y - ref) <= 1e-4 * np.linalg.norm(ref)

    jax.profiler.start_trace(str(tmp_path))
    try:
        bucket, args = svc.stage_bucket(S, "c2c", buckets[0])
    finally:
        jax.profiler.stop_trace()
    assert svc.stats.staging_reused == 1
    assert svc.stats.staging_allocated == 2
    assert [bool(a["reused"]) for a in _pack_spans(tmp_path)] == [True]
    rows, _ = svc.fetch_bucket(svc.launch_bucket(S, bucket, "c2c", args))
    for x, y in zip(buckets[0], rows):
        ref = transform(x)
        assert np.linalg.norm(y - ref) <= 1e-4 * np.linalg.norm(ref)
