"""Stager ms per bucket turning the bucket's arguments into device arrays:
the host-to-device copy (mean of the ``fft.stage.h2d`` spans that start
in the window)."""

from bench import spans


def read(run):
    return spans.mean_ms(spans.of(run), "fft.stage.h2d")
