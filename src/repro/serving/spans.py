"""Names of the profiler spans the serving path opens (DESIGN.md §11).

Each span is a ``jax.profiler.TraceAnnotation``: always compiled in, it
records only while a profiler session runs, and then lands in the same
trace as the device's op lines, on one clock.  The ``bucket`` argument
(``_BucketPlan.seq``, numbered by the streaming scheduler) ties a
bucket's scheduler, stager and syncer spans together.  Stager spans nest
inside ``fft.bucket.stage`` and syncer spans inside ``fft.bucket.fetch``,
each on its own thread.
"""

from jax.profiler import TraceAnnotation as span

__all__ = ["BUCKET_FETCH", "BUCKET_FORM", "BUCKET_RESOLVE", "BUCKET_STAGE",
           "FETCH_COPY", "FETCH_WAIT", "STAGE_H2D", "STAGE_LAUNCH",
           "STAGE_PACK", "span"]

# the scheduler's dispatch decision: args bucket, reason, n, and the
# bucket's queue wait (dispatch - arrival) as wait_max_ms, wait_mean_ms
BUCKET_FORM = "fft.bucket.form"
# the stager's whole stage of one bucket (pack, h2d, launch); args bucket, n
BUCKET_STAGE = "fft.bucket.stage"
# straggler draw and numpy pack into the padded bucket buffer; arg
# reused, whether the buffer came from the service's free list
STAGE_PACK = "fft.stage.pack"
# host-to-device copy of the bucket's arguments; args bytes, dtype (of
# the request array: float32 words for complex, DESIGN.md §8)
STAGE_H2D = "fft.stage.h2d"
# dispatch of the jitted bucket call (the fault path's launch entire);
# args devices (the runner's device count) and runner ("mesh", or the
# local runner: kernel_masked, kernel, plan, robust); on a mesh also
# ingress (FFTServiceConfig.mesh_ingress) and broadcast_bytes and
# gather_bytes, the bucket's share of ServiceStats' counters of the same
# names
STAGE_LAUNCH = "fft.stage.launch"
# the syncer's whole fetch of one bucket (wait, copy); arg bucket
BUCKET_FETCH = "fft.bucket.fetch"
# block_until_ready on the device result: waiting for the device
FETCH_WAIT = "fft.fetch.wait"
# device_get of the ready result: the device-to-host copy; args bytes,
# dtype (float32 words for complex rows)
FETCH_COPY = "fft.fetch.copy"
# resolving the bucket's futures, client done-callbacks included; arg bucket
BUCKET_RESOLVE = "fft.bucket.resolve"
