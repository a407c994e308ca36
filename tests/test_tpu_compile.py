"""TPU compile guard: the main-path kernels at real widths, for v5e.

Every other kernel test runs the Pallas bodies in interpret mode or as
straight XLA on the CPU, which accepts shapes and ops the TPU compiler
refuses (lane-splitting reshapes, ``rev``, blocks that break the (8, 128)
rule, VMEM overruns).  These tests hand the TPU compiler a described,
unattached ``v5e:2x2`` topology and AOT-compile the service's main-path
kernels with ``interpret=False`` at the widths the chip smoke serves.
Nothing runs: a pass says the compiler accepts the program, not that it
is fast or correct on the chip.

The topology is described inside a module fixture (never at import), so
under pytest-xdist only the worker given this file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mds
from repro.kernels import ops, ref

pytestmark = pytest.mark.kernels

M, N = 4, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_config():
    """Compile as the chip runs: 32-bit JAX (conftest turns x64 on for the
    decode-conditioning tests) and no persistent cache -- a compile for a
    described chip is written to the cache but cannot be read back
    without one."""
    from jax.experimental.compilation_cache import compilation_cache

    old = (jax.config.jax_enable_x64, jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_x64", old[0])
    jax.config.update("jax_enable_compilation_cache", old[1])
    compilation_cache.reset_cache()


def _generator():
    return ref.planar(mds.rs_generator(N, M, jnp.complex64))


def _c2c_masked(s, q):
    gr, gi = _generator()
    fn = lambda xr, xi, mk: ops.coded_bucket_masked(
        xr, xi, mk, gr, gi, s, interpret=False)
    return fn, [((q, s), jnp.float32)] * 2 + [((q, N), jnp.bool_)]


def _r2c_masked(s, q):
    gr, gi = _generator()
    fn = lambda xr, mk: ops.coded_rbucket_masked(
        xr, mk, gr, gi, s, interpret=False)
    return fn, [((q, s), jnp.float32), ((q, N), jnp.bool_)]


def _c2r_masked(s, q):
    gr, gi = _generator()
    fn = lambda yr, yi, mk: ops.coded_irbucket_masked(
        yr, yi, mk, gr, gi, s, interpret=False)
    return fn, [((q, s // 2 + 1), jnp.float32)] * 2 + [((q, N), jnp.bool_)]


def _fourstep(ell, q, variant):
    fn = lambda xr, xi: ops.fourstep_planar(xr, xi, interpret=False,
                                            variant=variant)
    return fn, [((q, ell), jnp.float32)] * 2


def _words(q, s):
    fn = lambda re, im: ops.interleave_words(re, im, interpret=False)
    return fn, [((q, s), jnp.float32)] * 2


CASES = {
    # (case constructor, kernel launch the program must contain)
    "c2c_masked_s65536": (lambda: _c2c_masked(1 << 16, 16),
                          "coded_fft_bucket_masked"),
    "c2c_masked_s4096": (lambda: _c2c_masked(1 << 12, 16),
                         "coded_fft_bucket_masked"),
    "c2c_streaming_masked_s1048576": (
        lambda: _c2c_masked(1 << 20, 16),
        "coded_fft_bucket_streaming_masked"),
    "r2c_masked_s65536": (lambda: _r2c_masked(1 << 16, 16),
                          "coded_rfft_bucket_masked"),
    "c2r_masked_s65536": (lambda: _c2r_masked(1 << 16, 16),
                          "coded_irfft_bucket_masked"),
    "fourstep_fused_L4096": (lambda: _fourstep(4096, 8, "fused"),
                             "fourstep_fft_fused"),
    "fourstep_two_pass_L4096": (lambda: _fourstep(4096, 8, "two_pass"),
                                "fourstep_fft_stage2"),
    "words_interleave_s1048576": (lambda: _words(16, 1 << 20),
                                  "interleave_words"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_main_path_kernel_compiles_for_v5e(case, one_chip, chip_config):
    build, kernel = CASES[case]
    fn, shapes = build()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    assert kernel in str(jax.make_jaxpr(fn)(*args))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("direction", ["to_words", "from_words"])
def test_host_link_conversion_stays_near_bucket_size(direction, one_chip,
                                                     chip_config,
                                                     monkeypatch):
    """The host link's conversions at the 2^20 c2c bucket (16 requests,
    128 MiB each way) keep their temporaries within twice the bucket:
    no pair axis of 2 padded to a full tile (XLA's own interleave takes
    8x the bucket).  The dispatch asks the default backend, which is the
    CPU here, so the test steers it to the chip's branch."""
    from repro.serving import fft_service

    monkeypatch.setattr(ops, "default_interpret", lambda: False)

    q, s = 16, 1 << 20
    shape, dtype = (((q, s), jnp.complex64) if direction == "to_words"
                    else ((q, 2 * s), jnp.float32))
    arg = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = getattr(fft_service, direction).lower(arg).compile()
    bucket = q * s * 8
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * bucket


def test_to_words_compiles_over_a_mesh(topo, chip_config, monkeypatch):
    """A mesh service's result spans the 2x2 mesh, where XLA cannot
    partition a Pallas kernel: ``to_words`` takes the XLA interleave
    there, and the chip's compiler accepts it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.serving import fft_service

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("workers",))
    arg = jax.ShapeDtypeStruct((16, 1 << 12), jnp.complex64,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    fft_service.to_words.lower(arg, on_mesh=True).compile()
