"""Device ms per bucket in all-gathers (of the workers' results, and of
the message where a bucket enters split over the devices): the mean over
devices of their time in the traced window, over the buckets staged in
it (``bench/mesh.py``)."""

from bench import mesh


def read(run):
    return mesh.gather_ms_per_bucket(run)
