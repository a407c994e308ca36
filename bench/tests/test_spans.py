"""The program's spans: the reductions on intervals written by hand,
every reader that uses them on a hand-made run, and the loader on a trace
recorded on the CPU."""

import importlib.util
import pathlib
import types

import pytest

from bench import spans
from bench import trace as tr

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
MS = 1_000_000


def hand_spans():
    """A window of [10, 110] ms; spans that start before it, inside it and
    at its end, on a stager (line 0) and a syncer (line 1) thread."""
    def span(a, b, line=0, **args):
        return (a * MS, b * MS, args, line)

    return spans.Spans(
        window=(10 * MS, 110 * MS),
        host={
            "fft.stage.pack": [span(5, 15), span(20, 30), span(60, 90),
                               span(105, 140)],
            "fft.stage.h2d": [span(30, 34), span(90, 96)],
            "fft.stage.launch": [span(2, 3), span(34, 35), span(96, 97)],
            "fft.bucket.stage": [span(20, 35, bucket=2, n=4)],
            "fft.bucket.fetch": [span(40, 71, 1, bucket=2)],
            "fft.fetch.wait": [span(40, 41, 1)],
            "fft.fetch.copy": [span(41, 71, 1)],
            "fft.bucket.resolve": [span(71, 73, 1, bucket=2),
                                   span(110, 111, 1, bucket=3)],
            "fft.bucket.form": [
                span(0, 1, 2, bucket=1, n=16, wait_mean_ms=99.0),
                span(19, 19, 2, bucket=2, n=4, wait_mean_ms=2.0),
                span(59, 59, 2, bucket=3, n=12, wait_mean_ms=6.0)],
        })


def read(metric, sp):
    path = METRICS / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "m_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(types.SimpleNamespace(spans=sp, reduced=None))


def test_spans_count_whole_by_their_start():
    sp = hand_spans()
    # [5, 15] starts before the window, [105, 140] inside it: counted whole
    assert spans.durations_s(sp, "fft.stage.pack") == pytest.approx(
        [0.010, 0.030, 0.035])
    assert spans.mean_ms(sp, "fft.stage.pack") == pytest.approx(25.0)
    # [110, 111] starts at the window's end: outside
    assert spans.durations_s(sp, "fft.bucket.resolve") == pytest.approx(
        [0.002])
    assert spans.mean_ms(sp, "fft.no.such.span") is None
    assert spans.mean_ms(None, "fft.stage.pack") is None


def test_a_buckets_timeline_holds_its_spans_and_their_children():
    names = [e[0] for e in spans.timeline(hand_spans(), 2)]
    # the pack at [60, 90] and the launch at [96, 97] are another bucket's
    assert names == ["fft.bucket.form", "fft.bucket.stage", "fft.stage.pack",
                     "fft.stage.h2d", "fft.stage.launch", "fft.bucket.fetch",
                     "fft.fetch.wait", "fft.fetch.copy",
                     "fft.bucket.resolve"]


def test_covering_gives_the_share_in_spans_and_the_buckets():
    share, named = spans.covering(hand_spans(), 35 * MS, 45 * MS)
    # [35, 40] lies in no span, [40, 45] in the fetch and its children
    assert share == pytest.approx(0.5)
    assert named == ["fft.bucket.fetch[2] 5.0"]


@pytest.mark.parametrize("metric,value", [
    ("pack_ms_per_bucket.batch", 25.0),
    ("h2d_ms_per_bucket.batch", 5.0),
    ("device_wait_ms_per_bucket.batch", 1.0),
    ("d2h_ms_per_bucket.batch", 30.0),
    ("resolve_ms_per_bucket.batch", 2.0),
    ("launch_ms_per_bucket.batch", 1.0),
    # (2 * 4 + 6 * 12) / 16 over the forms that start in the window
    ("queue_wait_ms.stream", 5.0),
])
def test_readers_on_a_hand_made_run(metric, value):
    assert read(metric, hand_spans()) == pytest.approx(value)


@pytest.mark.parametrize("metric", [
    "pack_ms_per_bucket.batch", "h2d_ms_per_bucket.batch",
    "device_wait_ms_per_bucket.batch", "d2h_ms_per_bucket.batch",
    "resolve_ms_per_bucket.batch", "launch_ms_per_bucket.batch",
    "queue_wait_ms.stream"])
def test_readers_give_none_without_their_spans(metric):
    """A program without the spans reports nothing."""
    bare = spans.Spans(window=(0, 100 * MS), host={})
    assert read(metric, bare) is None
    assert read(metric, None) is None


def test_load_reads_span_arguments_and_checks_the_window(tmp_path,
                                                         monkeypatch):
    jax = pytest.importorskip("jax")
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("fft.bucket.stage", bucket=7,
                                          n=16):
            pass
        with jax.profiler.TraceAnnotation("other"):
            pass
    jax.profiler.stop_trace()
    sp = spans.load(tr.find_xplane(str(tmp_path)))
    assert list(sp.host) == ["fft.bucket.stage"]
    (a, b, args, _), = spans.started(sp, "fft.bucket.stage")
    assert args == {"bucket": 7, "n": 16}
    assert sp.window[0] <= a <= b <= sp.window[1]

    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    run = types.SimpleNamespace(reduced=types.SimpleNamespace(
        window_s=sp.window_s))
    assert spans.of(run).host == sp.host
    run.reduced.window_s += 1e-3           # another run's trace: not read
    assert spans.of(run) is None
    assert spans.of(types.SimpleNamespace(reduced=None)) is None
