"""Operations and bytes of a transform against values worked out by hand."""

import json
import pathlib

import pytest

from bench import work

PEAKS = json.loads(
    (pathlib.Path(work.__file__).parent / "peaks.json").read_text())
V5E = PEAKS["TPU v5 lite"]


@pytest.mark.parametrize("kind,s,flops,nbytes", [
    # 5 * 2^20 * 20; complex64 in and out: 8 + 8 bytes a point
    ("c2c", 1 << 20, 104_857_600, 16_777_216),
    # 2.5 * 2048 * 11; float32 in (8192 B), 1025 complex64 bins out (8200 B)
    ("r2c", 2048, 56_320, 16_392),
    # the adjoint of r2c
    ("c2r", 2048, 56_320, 16_392),
    ("c2c", 4096, 245_760, 65_536),
])
def test_counts(kind, s, flops, nbytes):
    assert work.flops(kind, s) == flops
    assert work.bytes_moved(kind, s) == nbytes


def test_least_time_is_the_larger_bound():
    # 16 MiB at 819 GB/s = 20.485 us; 104.9 MFLOP at 197 TFLOP/s = 0.53 us
    t = work.least_seconds("c2c", 1 << 20, V5E)
    assert t == pytest.approx(16_777_216 / 819e9, rel=1e-12)
    flops_only = {"flops_per_s": 1e6, "bytes_per_s": 1e15}
    assert work.least_seconds("r2c", 2048, flops_only) == pytest.approx(
        56_320 / 1e6)


def test_unknown_kind_is_refused():
    with pytest.raises(ValueError):
        work.flops("rfftn", 64)
    with pytest.raises(ValueError):
        work.bytes_moved("dct", 64)


def test_peaks_name_their_source():
    for kind, peak in PEAKS.items():
        assert peak["flops_per_s"] > 0 and peak["bytes_per_s"] > 0, kind
        assert peak["source"], kind
