"""The all-gathers' share of their roofline, in %: the bytes each device
receives in them per bucket (as the program reckons them, see
``bench/mesh.py``), over their device ms per bucket, over the chip's
interchip peak (``bench/ici_peaks.json``).  The peak counts all of a
chip's links, so received bytes over it can only understate the
share."""

from bench import mesh


def read(run):
    ms = mesh.gather_ms_per_bucket(run)
    nbytes = mesh.gather_bytes_per_bucket(run)
    peak = mesh.ici_bytes_per_s(run)
    if ms is None or nbytes is None or peak is None:
        return None
    return nbytes / (ms * 1e-3) / peak * 100.0
