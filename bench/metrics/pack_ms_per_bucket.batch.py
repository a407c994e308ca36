"""Stager ms per bucket in the pack: the straggler draw and the numpy pack
into the padded bucket buffer (mean of the ``fft.stage.pack`` spans that
start in the window)."""

from bench import spans


def read(run):
    return spans.mean_ms(spans.of(run), "fft.stage.pack")
