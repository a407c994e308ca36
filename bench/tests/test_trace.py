"""The trace reduction, on intervals written by hand.

The event names and planes follow a trace recorded on a v5e chip: the
``XLA Ops`` line of ``/device:TPU:0``, HLO op text as event names, host
spans on ``/host:CPU``."""

import pytest

from bench import trace as tr


def hand_trace():
    ms = 1_000_000
    return tr.Trace({
        tr.HOST_PLANE: {
            "main": [(tr.WINDOW, 10 * ms, 110 * ms)],
            "stream-stager": [("pack", 20 * ms, 45 * ms),
                              ("whole-thread", 0, 200 * ms)],
        },
        "/device:TPU:0": {tr.OPS_LINE: [
            ("fusion.1", 0, 15 * ms),          # starts before the window
            ("kernel", 12 * ms, 18 * ms),      # overlaps fusion.1
            ("all-gather-start.1", 50 * ms, 52 * ms),
            ("all-gather-done.1", 58 * ms, 60 * ms),
            ("kernel", 100 * ms, 120 * ms),    # ends after the window
        ]},
        "/device:TPU:1": {tr.OPS_LINE: [
            ("all-gather.3", 50 * ms, 54 * ms),
        ]},
        "/device:CPU:0": {tr.OPS_LINE: [("ignored", 0, 200 * ms)]},
    })


def test_busy_is_the_union_cut_to_the_window():
    red = tr.reduce(hand_trace())
    assert red.window_s == pytest.approx(0.100)
    # TPU:0: [10, 18] + [50, 52] + [58, 60] + [100, 110] inside the window
    assert red.busy_s[0] == pytest.approx(0.022)
    assert red.busy_s[1] == pytest.approx(0.004)
    # the async all-gather spans its start to its done: [50, 60]
    assert red.allgather_s == pytest.approx([0.010, 0.004])


def test_per_op_time_sums_devices():
    ops = dict(map(tuple, tr.reduce(hand_trace()).device_ops))
    assert ops["kernel"] == pytest.approx(0.006 + 0.010)
    assert ops["fusion.1"] == pytest.approx(0.005)
    assert "ignored" not in ops


def test_idle_gaps_are_named_by_the_host():
    gaps = tr.reduce(hand_trace()).idle_gaps
    # TPU:0 idle in [18, 50], [52, 58] and [60, 100], longest first
    assert [g[1] for g in gaps] == pytest.approx([0.040, 0.032, 0.006])
    # [60, 100] overlaps only the thread-wide event; [18, 50] overlaps
    # "pack" for 25 ms
    assert [g[0] for g in gaps] == ["stream-stager:whole-thread",
                                    "stream-stager:pack",
                                    "stream-stager:whole-thread"]


def test_async_collectives_pair_start_with_done():
    spans = tr.collective_spans([
        ("all-gather-start.2", 10, 11), ("all-gather-start", 0, 1),
        ("all-gather-done", 4, 5), ("all-gather-done.2", 19, 20),
        ("all-gather.7", 30, 33)])
    assert sorted(spans) == [("all-gather", 0, 5), ("all-gather.2", 10, 20),
                             ("all-gather.7", 30, 33)]
    assert tr.op_name("%fusion.3 = (f32[2]) fusion(%p), kind=kLoop") == \
        "fusion.3"


def test_union_and_gaps_helpers():
    assert tr.union([("a", 0, 5), ("b", 3, 8), ("c", 10, 12)]) == \
        [(0, 8), (10, 12)]
    assert tr.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]


def test_a_trace_without_the_window_is_refused():
    t = hand_trace()
    t.planes[tr.HOST_PLANE]["main"] = []
    with pytest.raises(ValueError):
        tr.reduce(t)

