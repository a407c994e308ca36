"""Syncer seconds per bucket (``ServiceStats.sync_s / batches``): waiting
for the device and the copy back, over the window."""


def read(run):
    return run.per_bucket_ms("sync_s")
