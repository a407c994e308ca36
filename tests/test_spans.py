"""Profiler spans of the serving path (serving/spans.py, DESIGN.md §11).

A small streaming run under a ``jax.profiler`` trace, read back with
``jax.profiler.ProfileData``: every span appears, children nest in their
parent on one thread, and the ``bucket`` argument ties a bucket's
scheduler, stager and syncer spans together.  Splitting the fetch into a
wait and a copy changes no row it returns, and carrying complex rows
across the host link as float32 words changes no bit of them."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.serving import (FFTService, FFTServiceConfig, StreamConfig,
                           StreamingFFTService)
from repro.serving import spans

S, CAP, N_REQ = 256, 4, 8
STAGE_CHILDREN = (spans.STAGE_PACK, spans.STAGE_H2D, spans.STAGE_LAUNCH)
FETCH_CHILDREN = (spans.FETCH_WAIT, spans.FETCH_COPY)
ALL_SPANS = (spans.BUCKET_FORM, spans.BUCKET_STAGE, spans.BUCKET_FETCH,
             spans.BUCKET_RESOLVE) + STAGE_CHILDREN + FETCH_CHILDREN


def _cfg(**kw):
    kw.setdefault("s", S)
    kw.setdefault("m", 4)
    kw.setdefault("n_workers", 8)
    kw.setdefault("seed", 0)
    kw.setdefault("max_batch", CAP)
    kw.setdefault("autotune", False)
    return FFTServiceConfig(**kw)


def _reqs(n, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=S) + 1j * rng.normal(size=S)).astype(
        np.complex64) for _ in range(n)]


def _events(log_dir):
    """``(name, start_ns, end_ns, stats, thread)`` of every ``fft.`` span
    on the host; ``thread`` is the span's line on the host plane."""
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("fft."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats), thread))
    return out


def _by_name(events, name):
    return [e for e in events if e[0] == name]


@pytest.fixture(scope="module", params=[True, False],
                ids=["pipelined", "serial"])
def traced(request, tmp_path_factory):
    """Spans and answers of 8 requests in two full buckets of 4, served
    by the pipelined stager/syncer or the serial baseline."""
    svc = FFTService(_cfg())
    svc.warmup(buckets=[CAP])
    log_dir = tmp_path_factory.mktemp("trace")
    xs = _reqs(N_REQ, seed=1)
    scfg = StreamConfig(slack_s=30.0, pipelined=request.param)
    jax.profiler.start_trace(str(log_dir))
    try:
        with StreamingFFTService(svc, scfg) as stream:
            futs = [stream.submit(x) for x in xs]
            rows = [f.result(timeout=120) for f in futs]
    finally:
        jax.profiler.stop_trace()
    for x, y in zip(xs, rows):
        assert np.abs(y - np.fft.fft(x)).max() < 1e-2
    return _events(log_dir), futs


def test_every_span_appears_and_children_nest(traced):
    events, _ = traced
    buckets = N_REQ // CAP
    for name in ALL_SPANS:
        assert len(_by_name(events, name)) == buckets, name
    for parent, children in ((spans.BUCKET_STAGE, STAGE_CHILDREN),
                             (spans.BUCKET_FETCH, FETCH_CHILDREN)):
        for child in children:
            for _, a, b, _, thread in _by_name(events, child):
                assert any(p[4] == thread and p[1] <= a and b <= p[2]
                           for p in _by_name(events, parent)), child


def test_bucket_arg_ties_spans_and_form_waits_match_latency(traced):
    events, futs = traced
    by_bucket = {}
    for name, a, b, stats, _ in events:
        if "bucket" in stats:
            by_bucket.setdefault(stats["bucket"], {})[name] = (a, b, stats)
    assert sorted(by_bucket) == [1, 2]
    for k, named in by_bucket.items():
        assert sorted(named) == sorted((spans.BUCKET_FORM, spans.BUCKET_STAGE,
                                        spans.BUCKET_FETCH,
                                        spans.BUCKET_RESOLVE))
        form = named[spans.BUCKET_FORM]
        stage, fetch, resolve = (named[n] for n in (
            spans.BUCKET_STAGE, spans.BUCKET_FETCH, spans.BUCKET_RESOLVE))
        # one bucket in order on one clock: formed, staged, fetched,
        # resolved
        assert form[0] <= stage[0] <= stage[1] <= fetch[0]
        assert fetch[1] <= resolve[0]
        assert form[2]["n"] == stage[2]["n"] == CAP
        assert form[2]["reason"] == "fill"
        # equal slack: EDF order is arrival order, so bucket k holds
        # requests 4(k-1) .. 4k-1; each one's latency is its queue wait
        # plus the same form-to-resolve time
        lat = np.array([f.latency_s for f in futs[CAP * (k - 1):CAP * k]])
        w_max, w_mean = (form[2]["wait_max_ms"] * 1e-3,
                         form[2]["wait_mean_ms"] * 1e-3)
        assert 0.0 <= w_mean <= w_max
        after = lat.max() - w_max
        assert lat.mean() - w_mean == pytest.approx(after, abs=1e-6)
        assert (resolve[0] - form[0]) * 1e-9 - 1e-3 <= after
        assert after <= (resolve[1] - form[0]) * 1e-9 + 1e-3


def test_submit_batch_opens_the_stage_children(tmp_path):
    """The children sit in the shared seams, so the closed-loop
    ``submit_batch`` path opens them too."""
    svc = FFTService(_cfg())
    svc.warmup(buckets=[CAP])
    xs = _reqs(CAP, seed=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        ys = svc.submit_batch(xs)
    finally:
        jax.profiler.stop_trace()
    events = _events(tmp_path)
    for name in STAGE_CHILDREN:
        assert len(_by_name(events, name)) == 1, name
    for x, y in zip(xs, ys):
        assert np.abs(y - np.fft.fft(x)).max() < 1e-2


def test_link_spans_carry_bytes_and_dtype(traced):
    """The host-to-device and device-to-host spans name what crossed: a
    bucket of complex64 requests goes as float32 words of the same bytes
    both ways (DESIGN.md §8)."""
    events, _ = traced
    payload = CAP * S * np.dtype(np.complex64).itemsize
    masks = CAP * 8 * np.dtype(bool).itemsize
    for name, want in ((spans.STAGE_H2D, payload + masks),
                       (spans.FETCH_COPY, payload)):
        got = _by_name(events, name)
        assert len(got) == N_REQ // CAP, name
        for _, _, _, stats, _ in got:
            assert stats["dtype"] == "float32", (name, stats)
            assert int(stats["bytes"]) == want, (name, stats)


def test_launch_span_names_its_runner(traced):
    """Without a mesh a bucket runs on one device, on the kernel executor
    that builds its decode matrices in the jit; no exchange is counted
    (the mesh's launch: tests/test_mesh_service.py)."""
    events, _ = traced
    got = _by_name(events, spans.STAGE_LAUNCH)
    assert len(got) == N_REQ // CAP
    for _, _, _, stats, _ in got:
        assert stats == {"devices": 1, "runner": "kernel_masked"}, stats


def _spy_runner(svc):
    """Record every bucket executor call's arguments and raw result."""
    make, calls = svc._runner_for, []

    def runner_for(s, bucket, kind="c2c"):
        fn = make(s, bucket, kind)

        def call(*args):
            out = fn(*args)
            calls.append((args, out))
            return out

        return call

    svc._runner_for = runner_for
    return calls


def _kind_reqs(kind, n, seed):
    rng = np.random.default_rng(seed)
    if kind == "r2c":
        return [rng.normal(size=S).astype(np.float32) for _ in range(n)]
    if kind == "c2r":
        return [np.fft.rfft(rng.normal(size=S)).astype(np.complex64)
                for _ in range(n)]
    return _reqs(n, seed)


def _numpy_fft(kind, x):
    return {"c2c": np.fft.fft, "r2c": np.fft.rfft,
            "c2r": np.fft.irfft}[kind](x)


@pytest.mark.parametrize("path,kind", [
    ("plain", "c2c"), ("plain", "r2c"), ("plain", "c2r"),
    ("robust", "c2c"), ("robust_host_rows", "c2c")])
def test_fetch_bucket_rows_match_device_get(path, kind):
    """Waiting, then copying the result as words, returns bit for bit
    what one ``jax.device_get`` of the bucket executor's own result
    returns, with its dtype and shape, on the plain and the fault
    paths."""
    kw = {"plain": {}, "robust": {"health": True},
          "robust_host_rows": {"verify": "detect"}}[path]
    svc = FFTService(_cfg(**kw))
    calls = _spy_runner(svc)
    xs = _kind_reqs(kind, CAP, seed=3)
    bucket, args = svc.stage_bucket(S, kind, xs)
    out = svc.launch_bucket(S, bucket, kind, args)
    rows, errors = svc.fetch_bucket(out)
    assert errors == out.errors
    if path == "plain":
        assert errors is None
    if path == "robust_host_rows":
        assert isinstance(out.out, np.ndarray) and not calls
        want = out.out
    else:
        (_, raw), = calls
        want = jax.device_get(raw)
    assert isinstance(rows, np.ndarray)
    assert rows.dtype == want.dtype and rows.shape == want.shape
    assert rows.dtype == (np.float32 if kind == "c2r" else np.complex64)
    np.testing.assert_array_equal(rows, want)
    for x, y in zip(xs, rows):
        ref = _numpy_fft(kind, x)
        assert np.abs(y - ref).max() < 1e-2
