"""Stager ms per bucket dispatching the jitted bucket call (mean of the
``fft.stage.launch`` spans that start in the window)."""

from bench import spans


def read(run):
    return spans.mean_ms(spans.of(run), "fft.stage.launch")
