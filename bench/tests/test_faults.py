"""A run with the timed path broken underneath has to read ``correct: false``.

Each test skips the harness's look for a chip and drives the rest of a
run on the CPU, at a size a test run holds, with one fault planted in the
bucket executors that the window drives.  The faults are those a cell of
this benchmark can have:

* ``unchanged`` -- the executor hands back its input as the answer;
* ``half_batch`` -- only the first half of each bucket is transformed and
  the rest get the mean of those answers;
* ``altered`` -- one answer of each bucket is altered where it is
  produced (its bins shifted by one);
* ``no_exchange`` -- on a four-device mesh, the all-gather of the
  workers' results is left out: each device decodes from copies of its
  own results.  No cell runs on four chips yet (PERF.md, Open
  questions); the test runs the BL configuration as one would.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests.conftest import ROOT

SMALL = {
    "bl_hires.batch": {"config": {"s": 4096}, "mix": {"check_every": 1}},
    "stft_librosa.stream": {"mix": {"streams": 20, "check_every": 1}},
}


def _unchanged(out, xb):
    import jax.numpy as jnp

    return xb[:, :out.shape[1]].astype(out.dtype) + 0 * jnp.sum(out)


def _half_batch(out, xb):
    import jax.numpy as jnp

    half = max(out.shape[0] // 2, 1)
    rest = jnp.broadcast_to(out[:half].mean(0), out[half:].shape)
    return jnp.concatenate([out[:half], rest], 0)


def _altered(out, xb):
    import jax.numpy as jnp

    return out.at[0].set(jnp.roll(out[0], 1))


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered}


def planted(fault):
    """A ``run_cell`` patch that wraps every bucket executor in ``fault``."""
    import jax

    def apply(svc):
        make, made = svc._runner_for, {}

        def runner_for(s, bucket, kind="c2c"):
            key = (s, bucket, kind)
            if key not in made:
                fn = make(s, bucket, kind)
                made[key] = jax.jit(
                    lambda xb, *rest: fault(fn(xb, *rest), xb))
            return made[key]

        svc._runner_for = runner_for

    return apply


def run(cell, root, patch=None):
    return harness.run_cell(cell, 2**31 + 11, 0.5, False, time.perf_counter(),
                            root=root, require_tpu=False, patch=patch,
                            overrides=SMALL[cell])


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_unbroken_run_is_correct(cell, spec_root):
    res = run(cell, spec_root)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["checks"]["checked"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(SMALL))
def test_fault_reads_incorrect(cell, fault, spec_root):
    res = run(cell, spec_root, planted(FAULTS[fault]))
    assert not res["correct"], res["checks"]
    assert res["checks"]["rel_l2_max"]["value"] > \
        res["checks"]["rel_l2_max"]["limit"]


MESH_RUN = """
import json, sys, time
import jax, jax.numpy as jnp
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness
if {no_exchange!r}:
    def local_only(x, axis_name, *, axis=0, tiled=False, **kw):
        n = jax.lax.psum(1, axis_name)
        return jnp.concatenate([x] * n, axis) if tiled else jnp.stack([x] * n, axis)
    jax.lax.all_gather = local_only
res = harness.run_cell("mesh", 2**31 + 13, 0.5, False, time.perf_counter(),
                       root=harness.pathlib.Path({spec_root!r}),
                       require_tpu=False,
                       overrides={{"config": {{"s": 4096}},
                                   "mix": {{"check_every": 1}}}})
print(json.dumps(res))
"""


@pytest.mark.parametrize("no_exchange", [False, True],
                         ids=["unbroken", "no_exchange"])
def test_mesh_exchange(no_exchange, spec_root):
    """The BL configuration and mix on a four-device mesh (N=8 coded
    workers, two per device), as a four-chip cell would run them."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH_RUN.format(root=str(ROOT), src=str(ROOT / "src"),
                           spec_root=str(spec_root), no_exchange=no_exchange)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is (not no_exchange), res["checks"]
