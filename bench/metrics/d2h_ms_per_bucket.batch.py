"""Syncer ms per bucket copying the finished answer to the host (mean of
the ``fft.fetch.copy`` spans that start in the window)."""

from bench import spans


def read(run):
    return spans.mean_ms(spans.of(run), "fft.fetch.copy")
