"""Pallas kernel validation: interpret-mode sweeps vs the jnp oracles.

Every kernel is swept over shapes/dtypes and asserted allclose against the
pure-jnp reference (ref.py).  f32 planar complex arithmetic bounds accuracy
to ~1e-5 relative for these reduction lengths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.core import CodedFFT, mds
from repro.kernels import (
    fft_fourstep,
    make_kernel_worker_fn,
    mds_apply,
    recombine_fused,
    split_factor,
)
from repro.kernels import ref
from repro.kernels.fourstep_fft import fourstep_fused, fourstep_stage1, fourstep_stage2
from repro.kernels.cmatmul import cmatmul
from repro.kernels.recombine import recombine_twiddle_dft

pytestmark = pytest.mark.kernels

RTOL = 2e-4  # f32 planar complex, reductions up to 4096
ATOL = 1e-3


def _randc(shape, seed=0, dtype=jnp.complex64):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.normal(size=shape) + 1j * rng.normal(size=shape), dtype=dtype
    )


def _relerr(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)


# ---------------------------------------------------------------- four-step
@pytest.mark.parametrize("ell", [64, 256, 1024, 4096])
@pytest.mark.parametrize("batch", [1, 3])
def test_fourstep_fft_matches_fft(ell, batch):
    x = _randc((batch, ell), seed=ell + batch)
    got = fft_fourstep(x, interpret=True)
    want = np.fft.fft(np.asarray(x, dtype=np.complex128), axis=-1)
    assert _relerr(got, want) < RTOL


@pytest.mark.parametrize("ell", [384, 1536])  # non-power-of-two, composite
def test_fourstep_fft_composite_lengths(ell, batch=2):
    x = _randc((batch, ell), seed=ell)
    got = fft_fourstep(x, interpret=True)
    want = np.fft.fft(np.asarray(x, dtype=np.complex128), axis=-1)
    assert _relerr(got, want) < RTOL


def test_fourstep_two_pass_matches_fused():
    """stage1+stage2 (large-size path) == fused kernel result."""
    batch, a, b = 2, 16, 64
    x = _randc((batch, a * b), seed=7)
    xr, xi = ref.planar(x)
    xr = xr.reshape(batch, a, b)
    xi = xi.reshape(batch, a, b)
    from repro.kernels.ops import _dft_planes, _twiddle_planes

    far, fai = _dft_planes(a)
    fbr, fbi = _dft_planes(b)
    wr, wi = _twiddle_planes(a, b)
    fr, fi2 = fourstep_fused(xr, xi, far, fai, wr, wi, fbr, fbi, interpret=True)
    t1r, t1i = fourstep_stage1(xr, xi, far, fai, wr, wi, block_b=32, interpret=True)
    sr, si = fourstep_stage2(t1r, t1i, fbr, fbi, block_a=8, interpret=True)
    np.testing.assert_allclose(np.asarray(sr), np.asarray(fr), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(si), np.asarray(fi2), rtol=1e-5, atol=1e-5)


def test_split_factor():
    # lane factor b: the smallest multiple of 128 >= sqrt(n) dividing n
    assert split_factor(4096) == (32, 128)
    assert split_factor(2048) == (16, 128)
    assert split_factor(65536) == (256, 256)
    assert split_factor(1 << 18) == (512, 512)
    assert split_factor(384) == (3, 128)
    # no lane-aligned split: as square as possible
    assert split_factor(96) == (8, 12)
    a, b = split_factor(1)
    assert a * b == 1


def test_fourstep_1d_input_promotion():
    x = _randc((256,), seed=3)
    got = fft_fourstep(x, interpret=True)
    assert got.shape == (256,)
    want = np.fft.fft(np.asarray(x, dtype=np.complex128))
    assert _relerr(got, want) < RTOL


# ---------------------------------------------------------------- cmatmul
@pytest.mark.parametrize("m,k,ell", [(8, 4, 64), (16, 16, 512), (4, 4, 1000), (64, 32, 2048)])
def test_cmatmul_sweep(m, k, ell):
    a = _randc((m, k), seed=m)
    b = _randc((k, ell), seed=ell)
    ar, ai = ref.planar(a)
    br, bi = ref.planar(b)
    cr, ci = cmatmul(ar, ai, br, bi, interpret=True)
    wr, wi = ref.cmatmul_ref(ar, ai, br, bi)
    np.testing.assert_allclose(np.asarray(cr), np.asarray(wr), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ci), np.asarray(wi), rtol=1e-4, atol=1e-4)


def test_mds_apply_matches_core_encode():
    g = mds.rs_generator(8, 4, jnp.complex64)
    c = _randc((4, 32, 8), seed=5)  # payload with extra dims
    got = mds_apply(g, c, interpret=True)
    want = mds.encode(g, c)
    assert _relerr(got, want) < RTOL


# ---------------------------------------------------------------- recombine
@pytest.mark.parametrize("m,ell", [(2, 64), (4, 256), (8, 1024), (16, 128)])
def test_recombine_kernel_sweep(m, ell):
    s = m * ell
    c_hat = _randc((m, ell), seed=s)
    got = recombine_fused(c_hat, s, interpret=True)
    from repro.core import recombine as core_recombine

    want = core_recombine(c_hat.astype(jnp.complex128), s)
    assert _relerr(got, want) < RTOL


# ------------------------------------------------------- end-to-end kernel path
def test_coded_fft_with_kernel_worker():
    """Full coded-FFT pipeline with the Pallas worker FFT plugged in."""
    s, m, n = 4096, 4, 6
    x = _randc((s,), seed=11)
    strat = CodedFFT(
        s=s, m=m, n_workers=n, dtype=jnp.complex64,
        worker_fn=make_kernel_worker_fn(interpret=True),
    )
    b = strat.worker_compute(strat.encode(x))
    got = strat.decode(b, subset=jnp.asarray([5, 1, 3, 0]))
    want = np.fft.fft(np.asarray(x, dtype=np.complex128))
    assert _relerr(got, want) < 5e-4


@settings(max_examples=10, deadline=None)
@given(
    log_ell=st.integers(6, 12),
    batch=st.integers(1, 4),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_fourstep_random(log_ell, batch, seed):
    ell = 2**log_ell
    rng = np.random.default_rng(seed)
    x = jnp.asarray(
        rng.normal(size=(batch, ell)) + 1j * rng.normal(size=(batch, ell)),
        dtype=jnp.complex64,
    )
    got = fft_fourstep(x, interpret=True)
    want = np.fft.fft(np.asarray(x, dtype=np.complex128), axis=-1)
    assert _relerr(got, want) < RTOL
