"""The control -- the plain DFT one precision step down (``Precision.HIGH``,
three bf16 passes), in the program's place -- has to read ``correct:
false``; the same DFT at full f32 precision has to pass.

This drives a whole run on the CPU with the control patched in, as
``bench/control.py`` does on the chip at each cell's own size: the
STFT cell at its own frame length (2048), the BL cell at 2^16 points
instead of 2^20, which a test run holds."""

import time

import pytest

from bench import control, harness

SIZES = {
    "stft_librosa.stream": {"mix": {"streams": 20, "check_every": 1}},
    "bl_hires.batch": {"config": {"s": 1 << 16},
                       "mix": {"check_every": 1, "in_flight": 16,
                               "pool": 16}},
}


def run(cell, precision, root):
    return harness.run_cell(cell, 2**31 + 17, 0.5, False, time.perf_counter(),
                            root=root, require_tpu=False,
                            patch=control.patch(precision),
                            overrides=SIZES[cell])


@pytest.mark.parametrize("cell", sorted(SIZES))
def test_control_reads_incorrect(cell, spec_root):
    res = run(cell, "high", spec_root)
    low = res["checks"]["rel_l2_min"]
    assert res["failed"] == 0
    assert not res["correct"]
    assert low["value"] > low["limit"], res["checks"]


@pytest.mark.parametrize("cell", sorted(SIZES))
def test_full_precision_dft_passes(cell, spec_root):
    res = run(cell, "highest", spec_root)
    assert res["correct"], res["checks"]
