"""Syncer ms per bucket resolving its futures, the clients' done-callbacks
included (mean of the ``fft.bucket.resolve`` spans that start in the
window)."""

from bench import spans


def read(run):
    return spans.mean_ms(spans.of(run), "fft.bucket.resolve")
