"""The transforms' least time on the chip over device busy time, in %.

Numerator: over the transforms completed in the window, each one's
max(flops / peak FLOP/s, bytes / peak bytes/s) (``bench/work.py``).
Denominator: device busy time in the traced window, summed over the
devices (peaks are per chip)."""

from bench import work


def read(run):
    if run.reduced is None or run.peak is None:
        return None
    busy = sum(run.reduced.busy_s)
    if busy <= 0:
        return None
    least = run.completed_in_window * work.least_seconds(
        run.kind, run.s, run.peak)
    return least / busy * 100.0
