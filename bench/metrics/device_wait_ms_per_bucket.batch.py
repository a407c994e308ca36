"""Syncer ms per bucket waiting for the device to finish the bucket (mean
of the ``fft.fetch.wait`` spans that start in the window)."""

from bench import spans


def read(run):
    return spans.mean_ms(spans.of(run), "fft.fetch.wait")
