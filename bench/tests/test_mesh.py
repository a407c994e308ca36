"""The mesh layer's readers on hand-made runs: the all-gathers' device
time per bucket, and their share of the chip's interchip peak."""

import importlib.util
import json
import pathlib
import types

import pytest

from bench import mesh, spans
from bench import trace as tr

BENCH = pathlib.Path(__file__).resolve().parents[1]
METRICS = BENCH / "metrics"
PEAKS = json.loads((BENCH / "peaks.json").read_text())
ICI = json.loads((BENCH / "ici_peaks.json").read_text())
KIND = "TPU v5 lite"
MS = 1_000_000


def hand_trace(devices=2):
    """A window of [0, 100] ms; on each device one synchronous all-gather
    of 2 ms, an async one from its start at 20 to its done at 22, another
    op, and an all-gather after the window."""
    ops = [("all-gather.18", 10 * MS, 12 * MS),
           ("async-collective-start", 20 * MS, 20 * MS + MS // 2),
           ("fusion.3", 20 * MS + MS // 2, 21 * MS),
           ("async-collective-done", 21 * MS, 22 * MS),
           ("dot_general.113", 30 * MS, 40 * MS),
           ("all-gather.18", 120 * MS, 130 * MS)]
    planes = {tr.HOST_PLANE: {"python": [(tr.WINDOW, 0, 100 * MS)]}}
    for d in range(devices):
        planes[f"/device:TPU:{d}"] = {tr.OPS_LINE: list(ops)}
    return tr.Trace(planes)


def hand_spans(**args):
    """Two launches in the window and one after it, each with ``args``."""
    launch = [(a * MS, (a + 1) * MS, dict(args), 0) for a in (5, 55, 105)]
    return spans.Spans(window=(0, 100 * MS),
                       host={"fft.stage.launch": launch})


SPLIT = {"devices": 4, "runner": "mesh", "ingress": "split",
         "broadcast_bytes": 4e8, "gather_bytes": 1e8}


def hand_run(trace=None, sp=None, batches=2, peak=PEAKS[KIND]):
    return types.SimpleNamespace(trace=trace, spans=sp, reduced=None,
                                 stats={"batches": batches}, peak=peak)


def read(metric, run):
    path = METRICS / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "m_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run)


def test_gather_time_counts_both_forms_inside_the_window():
    # 2 ms synchronous + 2 ms from the async start to its done, per device
    assert mesh.gather_s(hand_trace()) == pytest.approx([0.004, 0.004])
    run = hand_run(hand_trace())
    assert read("allgather_ms_per_bucket.mesh", run) == pytest.approx(2.0)


def test_received_bytes_add_the_split_message():
    # a split ingress: 1e8 of results and a quarter of 4e8 of message
    assert mesh.received(SPLIT) == pytest.approx(2e8)
    first = dict(SPLIT, ingress="first")
    assert mesh.received(first) == pytest.approx(1e8)
    assert mesh.received({"devices": 1, "runner": "kernel_masked"}) is None
    assert mesh.gather_bytes_per_bucket(
        hand_run(sp=hand_spans(**SPLIT))) == pytest.approx(2e8)


def test_ici_share_is_received_bytes_over_time_over_the_peak():
    run = hand_run(hand_trace(), hand_spans(**SPLIT))
    # 2e8 bytes in 2 ms a bucket: 1e11 bytes/s of 200e9
    assert ICI[KIND]["bytes_per_s"] == 200e9
    assert read("allgather_ici_share.mesh", run) == pytest.approx(50.0)


@pytest.mark.parametrize("metric", ["allgather_ms_per_bucket.mesh",
                                    "allgather_ici_share.mesh"])
def test_mesh_readers_give_none_without_their_inputs(metric):
    # untraced, no bucket, no all-gather in the trace
    assert read(metric, hand_run()) is None
    assert read(metric, hand_run(hand_trace(), hand_spans(**SPLIT),
                                 batches=0)) is None
    no_gather = tr.Trace({tr.HOST_PLANE: {"python": [(tr.WINDOW, 0, MS)]},
                          "/device:TPU:0": {tr.OPS_LINE: [("fusion", 0, 1)]}})
    assert read(metric, hand_run(no_gather, hand_spans(**SPLIT))) is None


def test_ici_share_gives_none_without_the_programs_bytes_or_a_peak():
    trace = hand_trace()
    # a program whose launch spans carry no byte counts (one without them)
    assert read("allgather_ici_share.mesh",
                hand_run(trace, hand_spans(devices=4))) is None
    assert read("allgather_ici_share.mesh",
                hand_run(trace, hand_spans(**SPLIT), peak=None)) is None
    assert read("allgather_ici_share.mesh",
                hand_run(trace, hand_spans(**SPLIT),
                         peak={"flops_per_s": 1.0})) is None


def test_ici_peaks_name_their_source():
    for kind, peak in ICI.items():
        assert kind in PEAKS, kind
        assert peak["bytes_per_s"] > 0, kind
        assert peak["source"], kind
