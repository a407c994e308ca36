"""Streaming (double-buffered DMA) kernel tests — DESIGN.md §10.

The streaming four-step and streaming bucket kernels keep only
(batch-block, shard-block) tiles VMEM-resident and stage tile k+1 while
tile k computes, so shapes past the fused VMEM budget stay ONE launch.
CPU CI cannot execute compiled Mosaic, so correctness is pinned two ways:
interpret-mode parity on shapes that genuinely exceed the budget (forcing
multi-tile grids through the real DMA machinery), and jaxpr launch-count
pins on the TPU-like dispatch (tracing never executes the kernel).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mds
from repro.kernels import ops, ref
from repro.kernels.coded_pipeline import (
    coded_fft_bucket_streaming_masked,
    subsets_from_masks_body,
)
from repro.kernels.fourstep_fft import fourstep_streaming, multistep_fused

pytestmark = pytest.mark.kernels


def _relerr(got, want):
    got = np.asarray(got)
    want = np.asarray(want)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


def _planes(x):
    return (jnp.asarray(x.real.astype(np.float32)),
            jnp.asarray(x.imag.astype(np.float32)))


# ------------------------------------------------- streaming four-step
@pytest.mark.parametrize("a,b,batch,bq,ba,bb", [
    (8, 16, 3, 2, 4, 4),     # multi-tile both phases, ragged batch
    (16, 16, 5, 2, 16, 16),  # single tile per phase (degenerate grid)
    (32, 8, 4, 4, 8, 2),     # tall A, narrow B tiles
])
def test_fourstep_streaming_parity(a, b, batch, bq, ba, bb):
    """Interpret-mode parity vs numpy on forced multi-tile grids: the
    double-buffered copy/compute interleave must be bit-equivalent to the
    monolithic four-step at every tiling."""
    ell = a * b
    rng = np.random.default_rng(ell + batch)
    x = rng.standard_normal((batch, ell)) + 1j * rng.standard_normal(
        (batch, ell))
    xr, xi = _planes(x)
    far, fai = ops._dft_planes(a)
    fbr, fbi = ops._dft_planes(b)
    wr, wi = ops._twiddle_planes(a, b)
    outr, outi = fourstep_streaming(
        xr.reshape(batch, a, b), xi.reshape(batch, a, b),
        far, fai, wr, wi, fbr, fbi,
        block_q=bq, block_a=ba, block_b=bb, interpret=True)
    got = (np.asarray(outr) + 1j * np.asarray(outi)).reshape(batch, ell)
    assert _relerr(got, np.fft.fft(x, axis=-1)) < 1e-5


def test_fourstep_streaming_over_vmem_budget():
    """A shape whose fused (A, B) working set exceeds _FUSED_MAX_ELEMS:
    the exact population the streaming grid exists for."""
    a = b = 1024                      # a*b = 1M > 512*512 budget
    ell = a * b
    assert a * b > ops._FUSED_MAX_ELEMS
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, ell)) + 1j * rng.standard_normal((1, ell))
    xr, xi = _planes(x)
    far, fai = ops._dft_planes(a)
    fbr, fbi = ops._dft_planes(b)
    wr, wi = ops._twiddle_planes(a, b)
    outr, outi = fourstep_streaming(
        xr.reshape(1, a, b), xi.reshape(1, a, b),
        far, fai, wr, wi, fbr, fbi,
        block_q=1, block_a=256, block_b=256, interpret=True)
    got = (np.asarray(outr) + 1j * np.asarray(outi)).reshape(1, ell)
    assert _relerr(got, np.fft.fft(x, axis=-1)) < 1e-4


def test_fourstep_streaming_one_launch_jaxpr():
    """TPU-like dispatch: variant="streaming" lowers to exactly ONE
    pallas_call -- both compute phases and every DMA live inside it."""
    batch, ell = 4, 4096

    def run(xr, xi):
        return ops.fourstep_planar(xr, xi, interpret=False,
                                   variant="streaming")

    args = [jax.ShapeDtypeStruct((batch, ell), jnp.float32)] * 2
    jaxpr = str(jax.make_jaxpr(run)(*args))
    assert jaxpr.count("fourstep_fft_streaming") == 1


# ------------------------------------------------- multistep (mixed radix)
@pytest.mark.parametrize("factors", [(4, 8), (4, 4, 4), (2, 4, 8), (8, 8, 8)])
def test_multistep_fused_parity(factors):
    """The mixed-radix fused kernel == numpy at every radix plan, through
    the ops dispatcher (which owns the digit-reversal unscramble)."""
    ell = int(np.prod(factors))
    rng = np.random.default_rng(ell)
    x = rng.standard_normal((3, ell)) + 1j * rng.standard_normal((3, ell))
    xr, xi = _planes(x)
    for interpret in (None, True):
        outr, outi = ops.fourstep_planar(
            xr, xi, interpret=interpret, variant="fused", factors=factors)
        got = np.asarray(outr) + 1j * np.asarray(outi)
        assert _relerr(got, np.fft.fft(x, axis=-1)) < 1e-5, (factors,
                                                             interpret)


def test_multistep_enables_over_budget_fused():
    """A three-factor plan keeps L = 2^18 on the fused kernel path even
    though its balanced 2-split (512, 512) busts the two-factor budget."""
    ell = 1 << 18
    factors = (64, 64, 64)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((1, ell)) + 1j * rng.standard_normal((1, ell))
    xr, xi = _planes(x)
    outr, outi = ops.fourstep_planar(xr, xi, interpret=None,
                                     variant="fused", factors=factors)
    got = np.asarray(outr) + 1j * np.asarray(outi)
    assert _relerr(got, np.fft.fft(x, axis=-1)) < 1e-4


# ------------------------------------------------- in-kernel first_available
def test_subsets_from_masks_matches_argsort_exhaustively():
    """The Mosaic-safe rank/one-hot subset selection == ops.mask_subsets
    (stable argsort) for EVERY mask pattern -- including short rows, whose
    filler slots must keep the Lagrange nodes distinct."""
    for n, m in [(6, 4), (8, 4), (7, 3), (5, 2)]:
        masks = np.stack([
            np.array([(k >> i) & 1 for i in range(n)], bool)
            for k in range(2 ** n)])
        want = np.asarray(ops.mask_subsets(jnp.asarray(masks), m))
        got = np.asarray(subsets_from_masks_body(
            jnp.asarray(masks).astype(jnp.float32), m))
        assert np.array_equal(want, got), (n, m)


# ------------------------------------------------- streaming bucket kernel
def _bucket_case(s, m, n, q, seed=0):
    rng = np.random.default_rng(seed)
    g = mds.rs_generator(n, m, jnp.complex64)
    gr, gi = ref.planar(g)
    x = rng.standard_normal((q, s)) + 1j * rng.standard_normal((q, s))
    xr, xi = _planes(x)
    masks = np.zeros((q, n), bool)
    for r in range(q):
        masks[r, rng.choice(n, size=min(n, m + 1), replace=False)] = True
    return g, gr, gi, x, xr, xi, masks


@pytest.mark.parametrize("s,m,n,q,bq,ba,bb", [
    (512, 4, 6, 3, 2, 4, 4),    # small shape, forced multi-tile grid
    (256, 2, 4, 5, 2, 2, 8),
])
def test_streaming_bucket_forced_multi_tile_parity(s, m, n, q, bq, ba, bb):
    """Direct kernel-level parity with tiny tiles: many grid steps per
    phase, ragged batch padding, subset selection and decode in the
    kernel."""
    g, gr, gi, x, xr, xi, masks = _bucket_case(s, m, n, q)
    ell = s // m
    a, b = ops.split_factor(ell)
    planes = (*ops._dft_planes(a), *ops._twiddle_planes(a, b),
              *ops._dft_planes(b),
              *ops._recombine_planes_scrambled(s, m, a, b))
    want = np.fft.fft(x, axis=-1)

    yr, yi = coded_fft_bucket_streaming_masked(
        xr, xi, jnp.asarray(masks), gr, gi, *planes,
        block_q=bq, block_a=ba, block_b=bb, interpret=True)
    assert _relerr(np.asarray(yr) + 1j * np.asarray(yi), want) < 1e-4


def test_streaming_bucket_over_vmem_parity():
    """The acceptance shape class: a bucket whose working set exceeds the
    fused VMEM gate runs the ONE-launch streaming path (dispatcher-routed)
    and still matches numpy through interpret mode."""
    s, m, n, q = 1 << 17, 2, 4, 2
    assert not ops.coded_bucket_fusable(s, m, n)
    assert ops.coded_bucket_streamable(s, m, n)
    g, gr, gi, x, xr, xi, masks = _bucket_case(s, m, n, q, seed=3)
    yr, yi = ops.coded_bucket_masked(xr, xi, jnp.asarray(masks), gr, gi, s,
                                     interpret=True)
    assert _relerr(np.asarray(yr) + 1j * np.asarray(yi),
                   np.fft.fft(x, axis=-1)) < 1e-3


def test_streaming_bucket_one_launch_jaxpr(monkeypatch):
    """Jaxpr pin (the acceptance criterion): on TPU-like dispatch an
    over-VMEM bucket lowers to exactly ONE pallas_call -- the streaming
    kernel -- with no stage-path fallback and no extra launches."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    s, m, n, q = 1 << 17, 2, 4, 2
    assert not ops.coded_bucket_fusable(s, m, n)
    g = mds.rs_generator(n, m, jnp.complex64)
    gr, gi = ref.planar(g)

    def run(xr, xi, masks):
        return ops.coded_bucket_masked(xr, xi, masks, gr, gi, s)

    args = [jax.ShapeDtypeStruct((q, s), jnp.float32)] * 2 + [
        jax.ShapeDtypeStruct((q, n), jnp.bool_)]
    jaxpr = str(jax.make_jaxpr(run)(*args))
    assert jaxpr.count("coded_fft_bucket_streaming_masked") == 1
    assert "coded_fft_bucket_masked" not in jaxpr.replace(
        "coded_fft_bucket_streaming_masked", "")


def test_service_routes_over_vmem_bucket_to_streaming(monkeypatch):
    """The serving layer inherits the routing: an over-VMEM c2c bucket's
    device-decode runner traces to the streaming kernel launch."""
    from repro.serving.fft_service import FFTService, FFTServiceConfig

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    s, m, n = 1 << 17, 2, 4
    svc = FFTService(FFTServiceConfig(s=s, m=m, n_workers=n, autotune=False))
    runner = svc._runner_for(s, 2, "c2c")
    xb = jax.ShapeDtypeStruct((2, s), jnp.complex64)
    masks = jax.ShapeDtypeStruct((2, n), jnp.bool_)
    jaxpr = str(jax.make_jaxpr(runner)(xb, masks))
    assert jaxpr.count("coded_fft_bucket_streaming_masked") == 1


def test_masked_bucket_ships_raw_masks(monkeypatch):
    """Zero decode metadata: the fused masked kernel's jaxpr consumes the
    (q, N) boolean masks directly -- no argsort, no host subsets."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    s, m, n, q = 256, 4, 8, 4
    g = mds.rs_generator(n, m, jnp.complex64)
    gr, gi = ref.planar(g)

    def run(xr, xi, masks):
        return ops.coded_bucket_masked(xr, xi, masks, gr, gi, s)

    args = [jax.ShapeDtypeStruct((q, s), jnp.float32)] * 2 + [
        jax.ShapeDtypeStruct((q, n), jnp.bool_)]
    jaxpr = str(jax.make_jaxpr(run)(*args))
    assert "coded_fft_bucket_masked" in jaxpr
    assert "argsort" not in jaxpr
