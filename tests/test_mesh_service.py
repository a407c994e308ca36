"""The served mesh path (DESIGN.md §14): ``FFTService(mesh=...)`` under
``StreamingFFTService`` on four CPU devices, N=8 coded workers two per
device, with the service's own straggler masks.

One subprocess (the host platform needs its device count before JAX
starts) serves two full buckets under a profiler trace, then a
real-input bucket, then one bucket with the whole of it landing on the
first device, then two buckets in flight at once on the split ingress.  The answers match the plain reference of
``bench/references/c2c.py``; ``ServiceStats.broadcast_bytes`` and
``gather_bytes`` equal the bytes reckoned from the plan's shapes; each
``fft.stage.launch`` span names the mesh runner and its four devices."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
S, CAP, N, M, D = 1024, 4, 8, 4, 4

MESH_RUN = """
import glob, json, sys, tempfile
sys.path[:0] = [{root!r}, {src!r}]
import jax, numpy as np
from jax.profiler import ProfileData
from jax.sharding import Mesh
from bench.check import rel_l2
from bench.references import c2c
from repro.serving import (FFTService, FFTServiceConfig, StreamConfig,
                           StreamingFFTService)

s, cap = {s}, {cap}
mesh = Mesh(np.array(jax.devices()), ("workers",))
svc = FFTService(FFTServiceConfig(s=s, m={m}, n_workers={n}, seed=3,
                                  max_batch=cap, autotune=False), mesh=mesh)
svc.warmup(buckets=[cap])
rng = np.random.default_rng(5)
xs = [(rng.normal(size=s) + 1j * rng.normal(size=s)).astype(np.complex64)
      for _ in range(2 * cap)]
log_dir = tempfile.mkdtemp()
jax.profiler.start_trace(log_dir)
try:
    with StreamingFFTService(svc, StreamConfig(slack_s=30.0)) as stream:
        rows = [f.result(timeout=120)
                for f in [stream.submit(x) for x in xs]]
finally:
    jax.profiler.stop_trace()
out = {{"errors": [rel_l2(y, c2c.transform(x)) for x, y in zip(xs, rows)],
        "c2c": dict(svc.stats.summary(), latency=None, tiers=None)}}
path, = glob.glob(log_dir + "/**/*.xplane.pb", recursive=True)
out["launches"] = [dict(e.stats) for p in ProfileData.from_file(path).planes
                   if p.name == "/host:CPU" for line in p.lines
                   for e in line.events if e.name == "fft.stage.launch"]

reals = [rng.normal(size=s).astype(np.float32) for _ in range(cap)]
halves = svc.submit_batch(reals, kind="r2c")
out["r2c_errors"] = [rel_l2(y, np.fft.rfft(x.astype(np.float64)))
                     for x, y in zip(reals, halves)]
out["r2c"] = dict(svc.stats.summary(), latency=None, tiers=None)

# where a staged bucket's arguments land: split over the devices (the
# default), or whole on the first device
first = FFTService(FFTServiceConfig(s=s, m={m}, n_workers={n}, seed=3,
                                    max_batch=cap, autotune=False,
                                    mesh_ingress="first"), mesh=mesh)
out["placement"] = {{}}
for name, service in (("split", svc), ("first", first)):
    _, args = service.stage_bucket(s, "c2c", xs[:cap])
    out["placement"][name] = [sorted(tuple(sh.data.shape) + (sh.device.id,)
                                     for sh in a.addressable_shards)
                              for a in args]
rows = first.submit_batch(xs[:cap])
out["first_errors"] = [rel_l2(y, c2c.transform(x))
                       for x, y in zip(xs, rows)]
out["first"] = dict(first.stats.summary(), latency=None, tiers=None)

# two buckets in flight on the split ingress, where device_put may alias
# the host buffer: the second packs into a buffer of its own
held, staging = [], []
for chunk in (xs[:cap], xs[cap:]):
    bucket, args = svc.stage_bucket(s, "c2c", chunk)
    staging.append(args.staging)
    held.append(svc.launch_bucket(s, bucket, "c2c", args))
out["in_flight_shared"] = bool(np.shares_memory(*staging))
rows = [row for launched in held for row in svc.fetch_bucket(launched)[0]]
out["in_flight_errors"] = [rel_l2(y, c2c.transform(x))
                           for x, y in zip(xs, rows)]

print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def served():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={D}")
    code = MESH_RUN.format(root=str(ROOT), src=str(ROOT / "src"), s=S,
                           cap=CAP, m=M, n=N)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def gathered(buckets, payload, itemsize=8):
    """What each device receives in the all-gather: (D-1)/D of the N
    coded results of every request."""
    return buckets * (D - 1) * N * CAP * payload * itemsize // D


def test_served_mesh_answers_match_the_reference(served):
    stats = served["c2c"]
    assert stats["batches"] == 2
    # the service's own straggler draw: each request decodes from M of N
    assert stats["stragglers_tolerated"] == 2 * CAP * (N - M)
    assert max(served["errors"]) <= 1e-4
    assert max(served["r2c_errors"]) <= 1e-4


def test_exchange_counters_equal_the_reckoned_bytes(served):
    c2c, r2c = served["c2c"], served["r2c"]
    # the replicated words (CAP x S complex64) and masks (CAP x N bools)
    # sent to D-1 devices; each device receives 3/4 of 8 x 4 x S/M
    assert c2c["broadcast_bytes"] == 2 * (D - 1) * CAP * (S * 8 + N)
    assert c2c["gather_bytes"] == gathered(2, S // M)
    # a real bucket: S float32 in, pair-packed shards of S/M/2 complex64
    assert r2c["broadcast_bytes"] - c2c["broadcast_bytes"] == \
        (D - 1) * CAP * (S * 4 + N)
    assert r2c["gather_bytes"] - c2c["gather_bytes"] == \
        gathered(1, S // M // 2)


def test_launch_span_names_the_mesh_runner(served):
    launches = served["launches"]
    assert len(launches) == 2
    for args in launches:
        assert args["devices"] == D and args["runner"] == "mesh"
        assert args["ingress"] == "split"
        assert args["gather_bytes"] == gathered(1, S // M)
        assert args["broadcast_bytes"] == (D - 1) * CAP * (S * 8 + N)


def test_split_ingress_gives_each_device_its_rows(served):
    # a bucket's float32 words (2 words a complex point) and masks: split,
    # each device holds CAP / D rows; first, device 0 holds them all
    per = CAP // D
    assert served["placement"]["split"] == [
        [[per, 2 * S, d] for d in range(D)], [[per, N, d] for d in range(D)]]
    assert served["placement"]["first"] == [[[CAP, 2 * S, 0]],
                                            [[CAP, N, 0]]]


def test_first_device_ingress_matches_the_reference(served):
    assert max(served["first_errors"]) <= 1e-4
    # the same bytes move between the devices either way (one launch:
    # staging alone moves nothing)
    first = served["first"]
    assert first["broadcast_bytes"] == (D - 1) * CAP * (S * 8 + N)
    assert first["gather_bytes"] == gathered(1, S // M)


def test_buckets_in_flight_keep_their_staging_buffers(served):
    assert not served["in_flight_shared"]
    assert len(served["in_flight_errors"]) == 2 * CAP
    assert max(served["in_flight_errors"]) <= 1e-4
