"""Stager seconds per bucket (``ServiceStats.dispatch_s / batches``): host
packing, the copy to the device and the launch, over the window."""


def read(run):
    return run.per_bucket_ms("dispatch_s")
