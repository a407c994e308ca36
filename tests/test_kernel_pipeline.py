"""Kernel-first hot path: fused pipeline parity, backend dispatch rules,
and decode-matrix LRU correctness (DESIGN.md §6).

Parity tests pin ``interpret=True`` so the fused kernels are exercised
through the real Pallas machinery on CPU in every PR (the CI
kernels-interpret job runs this module); dispatch tests cover the
``interpret=None`` default (direct kernel-body evaluation off-TPU) and
the plan/service backend-selection rules.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CodedFFT, CodedFFTND, mds
from repro.core.coded_fft import _default_fft
from repro.kernels import ops, ref
from repro.serving import FFTService, FFTServiceConfig
from repro.serving.decode_cache import DecodeMatrixCache
from repro.serving.fft_service import from_words

pytestmark = pytest.mark.kernels

RTOL = 3e-4


def _randc(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        (rng.normal(size=shape) + 1j * rng.normal(size=shape))
        .astype(np.complex64))


def _relerr(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)


def _random_masks(rng, q, n, m):
    """(q, n) responder masks, each with exactly m responders."""
    masks = np.zeros((q, n), bool)
    for row in masks:
        row[rng.choice(n, size=m, replace=False)] = True
    return masks


def _compact(cache, masks):
    """Host-LRU compact inverse planes and subsets, as device arrays."""
    invs, subsets = cache.compact(masks)
    return (jnp.asarray(invs.real.astype(np.float32)),
            jnp.asarray(invs.imag.astype(np.float32)), jnp.asarray(subsets))


def _scatter(cache, masks):
    """Host-LRU (q, m, N) scatter decode planes, as device arrays."""
    dmats = cache.matrices(masks)
    return (jnp.asarray(dmats.real.astype(np.float32)),
            jnp.asarray(dmats.imag.astype(np.float32)))


# --------------------------------------------- fused encode+worker parity
@pytest.mark.parametrize("m,n,ell", [
    (4, 8, 512),     # the service default shape (pow2)
    (4, 6, 384),     # non-power-of-two composite L
    (4, 6, 189),     # odd composite L (split_factor -> 9 x 21)
    (2, 5, 127),     # prime L: split_factor falls back to (1, L)
    (3, 7, 96),      # odd m
])
@pytest.mark.parametrize("fused", [True, False])
def test_encode_worker_parity_interpret(m, n, ell, fused):
    """Fused encode+worker == encode_dft + fft oracle, through Pallas
    interpret mode, for non-power-of-two and odd L (split_factor
    fallbacks) in both the fused and the two-pass (separate) paths."""
    c = _randc((3, m, ell), seed=ell + m)
    g = mds.rs_generator(n, m, jnp.complex64)
    cr, ci = ref.planar(c)
    gr, gi = ref.planar(g)
    br, bi = ops.encode_worker(cr, ci, gr, gi, interpret=True, fused=fused)
    wr, wi = ref.encode_worker_ref(cr, ci, g)
    assert _relerr(ref.unplanar(br, bi), ref.unplanar(wr, wi)) < RTOL
    # and the default dispatch (direct path off-TPU) is the same math
    # (not bit-identical: XLA may reassociate the f32 accumulations)
    br2, bi2 = ops.encode_worker(cr, ci, gr, gi, fused=fused)
    assert _relerr(ref.unplanar(br2, bi2), ref.unplanar(br, bi)) < 1e-5


def test_split_factor_prime_fallback():
    assert ops.split_factor(127) == (1, 127)
    a, b = ops.split_factor(189)
    assert a * b == 189 and 1 < a <= b


def test_degenerate_factorization_falls_back_to_platform_fft():
    """A large prime shard length must NOT build a dense (L, L) DFT matrix
    (regression: the default kernel worker at L=10007 would have allocated
    ~800 MB of DFT planes and run O(L^2) flops); fourstep_planar falls
    back to the platform FFT past the (B, B) budget and stays exact."""
    ell = 10007  # prime
    a, b = ops.split_factor(ell)
    assert b * b > ops._FUSED_MAX_ELEMS
    x = _randc((2, ell), seed=13)
    xr, xi = ref.planar(x)
    got = ref.unplanar(*ops.fourstep_planar(xr, xi))
    want = np.fft.fft(np.asarray(x, np.complex128), axis=-1)
    assert _relerr(got, want) < 1e-3
    # end-to-end through the default plan (s = m * L)
    plan = CodedFFT(s=4 * ell, m=4, n_workers=8)
    xs = _randc((4 * ell,), seed=14)
    y = plan.run(xs)
    assert _relerr(y, np.fft.fft(np.asarray(xs, np.complex128))) < 1e-3


# --------------------------------------------------- whole-bucket pipeline
@pytest.mark.parametrize("s,m,n", [(2048, 4, 8), (756, 4, 6), (254, 2, 5)])
def test_coded_bucket_kernel_parity(s, m, n):
    """One-launch bucket pipeline (interleave -> encode -> worker ->
    in-kernel decode -> recombine) == jnp.fft, via Pallas interpret,
    including odd and prime shard lengths."""
    assert ops.coded_bucket_fusable(s, m, n)
    q = 3
    xb = _randc((q, s), seed=s)
    g = mds.rs_generator(n, m, jnp.complex64)
    masks = jnp.asarray(_random_masks(np.random.default_rng(s), q, n, m))
    xr, xi = ref.planar(xb)
    gr, gi = ref.planar(g)
    yr, yi = ops.coded_bucket_masked(xr, xi, masks, gr, gi, s,
                                     interpret=True)
    want = np.fft.fft(np.asarray(xb, np.complex128), axis=-1)
    assert _relerr(ref.unplanar(yr, yi), want) < 1e-3
    # direct path (off-TPU default) computes the identical body
    # (not bit-identical: XLA may reassociate the f32 accumulations)
    yr2, yi2 = ops.coded_bucket_masked(xr, xi, masks, gr, gi, s)
    assert _relerr(ref.unplanar(yr2, yi2), ref.unplanar(yr, yi)) < 1e-5


@pytest.mark.parametrize("s,m,n", [(2048, 4, 8), (756, 4, 6)])
def test_coded_bucket_direct_matches_pallas_bucket(s, m, n):
    """The off-TPU direct executor (platform-FFT worker stage, gathered
    compact decode from the host LRU) == the Pallas bucket kernel (decode
    in the kernel from the same masks) == jnp.fft."""
    q = 3
    xb = _randc((q, s), seed=s + 1)
    g = mds.rs_generator(n, m, jnp.complex64)
    masks = _random_masks(np.random.default_rng(s), q, n, m)
    cache = DecodeMatrixCache(np.asarray(g))
    xr, xi = ref.planar(xb)
    gr, gi = ref.planar(g)
    yr, yi = ops.coded_bucket_direct(xr, xi, *_compact(cache, masks),
                                     gr, gi, s)
    want = np.fft.fft(np.asarray(xb, np.complex128), axis=-1)
    assert _relerr(ref.unplanar(yr, yi), want) < 1e-3
    kr, ki = ops.coded_bucket_masked(xr, xi, jnp.asarray(masks), gr, gi, s,
                                     interpret=True)
    assert _relerr(ref.unplanar(yr, yi), ref.unplanar(kr, ki)) < 1e-4


def test_bcmatmul_and_batched_recombine_parity():
    q, m, n, ell = 5, 4, 8, 96
    a = _randc((q, m, n), seed=1)
    b = _randc((q, n, ell), seed=2)
    from repro.kernels.cmatmul import bcmatmul
    from repro.kernels.recombine import recombine_twiddle_dft_batched

    ar, ai = ref.planar(a)
    br, bi = ref.planar(b)
    cr, ci = bcmatmul(ar, ai, br, bi, block_q=2, block_l=32, interpret=True)
    wr, wi = ref.bcmatmul_ref(ar, ai, br, bi)
    np.testing.assert_allclose(np.asarray(cr), np.asarray(wr), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(ci), np.asarray(wi), rtol=1e-4,
                               atol=1e-4)

    c = _randc((q, m, ell), seed=3)
    s = m * ell
    cr, ci = ref.planar(c)
    twr, twi, fr, fi = ops._recombine_planes(s, m)
    got = recombine_twiddle_dft_batched(
        cr, ci, twr, twi, fr, fi, block_q=2, block_l=32, interpret=True)
    want = ref.recombine_batched_ref(cr, ci, twr, twi, fr, fi)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------- backend dispatch rules
def test_backend_dispatch_rules():
    # c64 + default backend -> kernel engine
    plan = CodedFFT(s=256, m=4, n_workers=6)
    assert plan.backend == "kernel" and plan.resolved_backend == "kernel"
    # explicit reference backend wins
    assert CodedFFT(s=256, m=4, n_workers=6,
                    backend="reference").resolved_backend == "reference"
    # complex128 (numerics tier) always resolves to the jnp oracle
    p128 = CodedFFT(s=256, m=4, n_workers=6, dtype=jnp.complex128)
    assert p128.resolved_backend == "reference"
    # explicit worker_fn plug-in overrides the backend worker
    p = CodedFFT(s=256, m=4, n_workers=6, worker_fn=_default_fft)
    assert p.resolved_worker_fn is _default_fft


def test_kernel_backend_plan_run_matches_fft():
    """Default (kernel-backend) plan.run == jnp.fft, batched and unbatched,
    including NaN-poisoned stragglers under a mask."""
    plan = CodedFFT(s=756, m=4, n_workers=6)  # odd L = 189
    xb = _randc((3, 756), seed=5)
    out = plan.run(xb)
    want = np.fft.fft(np.asarray(xb, np.complex128), axis=-1)
    assert _relerr(out, want) < 1e-3
    b = plan.worker_compute(plan.encode(xb[0]))
    b = b.at[jnp.asarray([1, 4])].set(jnp.nan)
    mask = jnp.asarray([True, False, True, True, False, True])
    got = plan.decode(b, mask=mask)
    assert _relerr(got, want[0]) < 1e-3


def test_kernel_backend_nd_plan():
    plan = CodedFFTND(shape=(16, 12), factors=(2, 2), n_workers=6)
    assert plan.resolved_backend == "kernel"
    t = _randc((16, 12), seed=9)
    got = plan.run(t)
    want = np.fft.fft2(np.asarray(t, np.complex128))
    assert _relerr(got, want) < 1e-3


# ------------------------------------------------------- decode-matrix LRU
def test_decode_cache_hit_miss_and_eviction():
    g = np.asarray(mds.rs_generator(8, 4, jnp.complex64))
    cache = DecodeMatrixCache(g, maxsize=2)
    m1 = np.array([1, 1, 1, 1, 0, 0, 0, 0], bool)
    m2 = np.array([0, 1, 1, 1, 1, 0, 0, 0], bool)
    m3 = np.array([1, 0, 1, 0, 1, 0, 1, 0], bool)
    d1 = cache.matrix(m1)
    assert (cache.hits, cache.misses) == (0, 1)
    assert np.array_equal(cache.matrix(m1), d1)
    assert (cache.hits, cache.misses) == (1, 1)
    cache.matrix(m2)
    cache.matrix(m1)            # refresh m1 -> m2 is now LRU
    cache.matrix(m3)            # evicts m2
    assert len(cache) == 2
    assert (cache.hits, cache.misses) == (2, 3)
    cache.matrix(m2)            # recomputed after eviction, same value
    assert cache.misses == 4
    # matrices are the true scatter inverses regardless of cache churn
    for mask in (m1, m2, m3):
        d, inv, sub = cache._compute(mask)
        np.testing.assert_array_equal(sub, DecodeMatrixCache.subset_of(mask, 4))
        np.testing.assert_allclose(
            d[:, sub] @ g[sub, :].astype(np.complex128), np.eye(4),
            atol=1e-5)
        np.testing.assert_array_equal(d[:, sub], inv)
        assert np.all(d[:, [k for k in range(8) if k not in sub]] == 0)


def test_decode_cache_rejects_undecodable_mask():
    g = np.asarray(mds.rs_generator(8, 4, jnp.complex64))
    cache = DecodeMatrixCache(g)
    with pytest.raises(ValueError, match="responders"):
        cache.matrix(np.array([1, 1, 1, 0, 0, 0, 0, 0], bool))


def test_service_lru_churn_stays_correct(monkeypatch):
    """With a tiny decode cache, straggler-mask churn forces constant
    evictions; every request must still decode exactly.  Pins the host-LRU
    FALLBACK path (m above ``mds.LAGRANGE_MAX_M``, lowered here) -- the
    default path builds decode matrices in-jit and is covered by
    test_lagrange_decode.py."""
    monkeypatch.setattr(mds, "LAGRANGE_MAX_M", 2)
    svc = FFTService(FFTServiceConfig(
        s=256, m=4, n_workers=8, seed=11, decode_cache_size=2))
    rng = np.random.default_rng(0)
    worst = 0.0
    for _ in range(6):
        xs = [jnp.asarray((rng.normal(size=256) + 1j * rng.normal(size=256))
                          .astype(np.complex64)) for _ in range(8)]
        for x, y in zip(xs, svc.submit_batch(xs)):
            worst = max(worst, float(np.max(np.abs(y - np.fft.fft(x)))))
    assert worst < 1e-2, worst
    st = svc.stats.summary()
    # churn proof: far more misses than the cache can hold
    assert st["decode_cache_misses"] > 2
    assert st["requests"] == 48


# ----------------------------------------- real-input (r2c/c2r) kernel path
@pytest.mark.parametrize("s,m,n", [(2048, 4, 8), (768, 4, 6), (240, 2, 5),
                                   (96, 3, 7)])
def test_coded_rfft_bucket_kernel_parity(s, m, n):
    """One-launch r2c bucket (pack -> encode -> half-length worker ->
    in-kernel decode -> symmetry butterfly) == numpy.rfft via Pallas
    interpret, including odd shard lengths and odd m; direct path same
    math."""
    assert ops.coded_rbucket_fusable(s, m, n)
    q = 3
    rng = np.random.default_rng(s + m)
    xb = jnp.asarray(rng.normal(size=(q, s)).astype(np.float32))
    g = mds.rs_generator(n, m, jnp.complex64)
    masks = _random_masks(rng, q, n, m)
    gr, gi = ref.planar(g)
    want = np.fft.rfft(np.asarray(xb, np.float64), axis=-1)
    yr, yi = ops.coded_rbucket_masked(xb, jnp.asarray(masks), gr, gi, s,
                                      interpret=True)
    assert _relerr(ref.unplanar(yr, yi), want) < 1e-3
    yr2, yi2 = ops.coded_rbucket_masked(xb, jnp.asarray(masks), gr, gi, s)
    assert _relerr(ref.unplanar(yr2, yi2), ref.unplanar(yr, yi)) < 1e-5
    # gathered-compact direct executor (the off-TPU service path)
    cache = DecodeMatrixCache(np.asarray(g))
    yr3, yi3 = ops.coded_rbucket_direct(xb, *_compact(cache, masks),
                                        gr, gi, s)
    assert _relerr(ref.unplanar(yr3, yi3), want) < 1e-3


@pytest.mark.parametrize("s,m,n", [(2048, 4, 8), (240, 2, 5), (96, 3, 7)])
def test_coded_irbucket_direct_matches_numpy(s, m, n):
    """c2r direct bucket executor (adjoint message stage, packed ifft
    worker, compact decode, relabel unpack) == numpy.irfft."""
    q = 3
    rng = np.random.default_rng(s)
    xs = rng.normal(size=(q, s))
    yb = jnp.asarray(np.fft.rfft(xs, axis=-1).astype(np.complex64))
    g = mds.rs_generator(n, m, jnp.complex64)
    masks = _random_masks(rng, q, n, m)
    cache = DecodeMatrixCache(np.asarray(g))
    gr, gi = ref.planar(g)
    yr, yi = ref.planar(yb)
    out = ops.coded_irbucket_direct(yr, yi, *_compact(cache, masks),
                                    gr, gi, s)
    assert _relerr(out, xs) < 1e-3


@pytest.mark.parametrize("s,m,n", [(2048, 4, 8), (768, 4, 6), (240, 2, 5),
                                   (96, 3, 7)])
def test_coded_irfft_bucket_kernel_parity(s, m, n):
    """One-launch fused c2r bucket (adjoint message butterfly -> fused
    encode + half-length ifft worker -> in-kernel decode -> pair unpack)
    == numpy.irfft via Pallas interpret, over ADVERSARIAL byte-pattern
    masks -- pairs that select the same first-m responder subset but
    differ as byte patterns, plus exact-threshold scatters -- including
    odd shard lengths and odd m; direct path same math (DESIGN.md §9)."""
    assert ops.coded_irbucket_fusable(s, m, n)
    rng = np.random.default_rng(s + m)
    # adversarial mask family: same-subset-different-bytes pairs + the
    # all-alive row + exact-threshold random scatters
    masks = [np.zeros(n, bool) for _ in range(2)]
    masks[0][:m] = True                       # contiguous first-m ...
    masks[1][:m] = True
    masks[1][n - 1] = True                    # ... same subset, extra byte
    masks.append(np.ones(n, bool))
    for _ in range(2):
        row = np.zeros(n, bool)
        row[rng.choice(n, size=m, replace=False)] = True
        masks.append(row)
    masks = np.stack(masks)
    q = masks.shape[0]
    xs = rng.normal(size=(q, s))
    yb = jnp.asarray(np.fft.rfft(xs, axis=-1).astype(np.complex64))
    g = mds.rs_generator(n, m, jnp.complex64)
    gr, gi = ref.planar(g)
    yr, yi = ref.planar(yb)
    # raw masks in, subset selection + decode matrices built in-kernel
    out = ops.coded_irbucket_masked(yr, yi, jnp.asarray(masks), gr, gi, s,
                                    interpret=True)
    assert _relerr(out, xs) < 1e-3
    # direct path (off-TPU default) computes the identical body
    out2 = ops.coded_irbucket_masked(yr, yi, jnp.asarray(masks), gr, gi, s)
    assert _relerr(out2, np.asarray(out)) < 1e-5
    assert _relerr(out2, xs) < 1e-3
    # and the reference plan agrees (the acceptance cross-check)
    from repro.core import CodedIRFFT

    plan = CodedIRFFT(s=s, m=m, n_workers=n, dtype=jnp.complex64,
                      backend="reference")
    want_plan = plan.run(yb[0], mask=jnp.asarray(masks[0]))
    assert _relerr(np.asarray(out)[0], np.asarray(want_plan)) < 1e-3


def test_submit_irfft_routes_through_fused_c2r_kernel(monkeypatch):
    """Dispatch pin (the acceptance criterion): on the kernel backend with
    a non-interpret (TPU-like) dispatch, the c2r bucket runner lowers to
    the ONE-LAUNCH fused kernel -- the jaxpr carries the
    coded_irfft_bucket_masked pallas_call, not the stage-path composition.
    CI runs on CPU, so the TPU dispatch is pinned by patching
    ops.default_interpret; tracing never executes the kernel."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    s, m, n = 256, 4, 8
    svc = FFTService(FFTServiceConfig(s=s, m=m, n_workers=n))
    assert svc._kernel_path(s, "c2r") and svc._decode_in_jit()
    runner = svc._runner_for(s, 4, "c2r")
    yb = jax.ShapeDtypeStruct((4, s // 2 + 1), jnp.complex64)
    masks = jax.ShapeDtypeStruct((4, n), jnp.bool_)
    jaxpr = str(jax.make_jaxpr(runner)(yb, masks))
    assert "coded_irfft_bucket_masked" in jaxpr
    # decode planes from the host LRU: the runner takes the stage
    # composition (encode_worker -> decode_apply -> unpack) instead
    monkeypatch.setattr(mds, "LAGRANGE_MAX_M", 2)
    svc2 = FFTService(FFTServiceConfig(s=s, m=m, n_workers=n))
    runner2 = svc2._runner_for(s, 4, "c2r")
    dplane = jax.ShapeDtypeStruct((4, m, n), jnp.float32)
    jaxpr2 = str(jax.make_jaxpr(runner2)(yb, dplane, dplane))
    assert "coded_irfft_bucket" not in jaxpr2
    assert "encode_fourstep_fused" in jaxpr2 and "bcmatmul" in jaxpr2


def test_pack_real_planes_odd_shard_raises_documented_error():
    """Odd shard lengths on the real kernel path fail with the documented
    '2m | s' ValueError at trace time, not an opaque reshape error."""
    with pytest.raises(ValueError, match=r"2m \| s"):
        ops.pack_real_planes(jnp.zeros((2, 252), jnp.float32), 4)


@pytest.mark.parametrize("s,m,n", [(2048, 4, 8), (768, 4, 6), (240, 2, 5),
                                   (96, 3, 7)])
def test_tpu_stage_path_compositions_match_numpy(s, m, n):
    """Pin the TPU-only stage compositions the service's staged body
    runs, which CI's direct default never executes: c2c (interleave ->
    encode_worker -> decode_apply -> recombine), the r2c fallback (pack
    -> encode_worker -> decode_apply -> rfft_postdecode) and the c2r
    conj-trick ifft (encode_worker on negated planes, /n2 rescale).  Run
    them through the Pallas kernels in interpret mode against numpy."""
    q = 2
    rng = np.random.default_rng(s)
    xb = rng.normal(size=(q, s)).astype(np.float32)
    g = mds.rs_generator(n, m, jnp.complex64)
    gr, gi = ref.planar(g)
    masks = _random_masks(rng, q, n, m)
    dr, di = _scatter(DecodeMatrixCache(np.asarray(g)), masks)

    xc = _randc((q, s), seed=s + 2)
    yr, yi = ops.coded_bucket_staged(*ref.planar(xc), dr, di, gr, gi, s,
                                     interpret=True)
    want = np.fft.fft(np.asarray(xc, np.complex128), axis=-1)
    assert _relerr(ref.unplanar(yr, yi), want) < 1e-3

    yr, yi = ops.coded_rbucket_staged(jnp.asarray(xb), dr, di, gr, gi, s,
                                      interpret=True)
    want = np.fft.rfft(xb.astype(np.float64), axis=-1)
    assert _relerr(ref.unplanar(yr, yi), want) < 1e-3

    yb = np.fft.rfft(xb, axis=-1).astype(np.complex64)
    out = ops.coded_irbucket_staged(*ref.planar(jnp.asarray(yb)), dr, di,
                                    gr, gi, s, interpret=True)
    assert _relerr(out, xb) < 1e-3


def test_rfft_payload_is_half_of_c2c():
    """The acceptance geometry: r2c worker shards carry HALF the c2c
    payload elements (the communication-overhead win, DESIGN.md §7)."""
    from repro.core import CodedFFT, CodedRFFT

    s, m, n = 2048, 4, 8
    c2c = CodedFFT(s=s, m=m, n_workers=n)
    r2c = CodedRFFT(s=s, m=m, n_workers=n)
    assert r2c.worker_shard_shape[0] * 2 == c2c.worker_shard_shape[0]
    a = r2c.encode(jnp.zeros((s,), jnp.float32))
    assert a.shape == (n, s // m // 2)


def test_service_rfft_and_irfft_kinds(monkeypatch):
    """Service r2c/c2r buckets decode exactly under straggler churn and
    share ONE decode-matrix LRU across kinds (same (N, m) generator).
    Pinned to the host-LRU fallback path, which is what shares the LRU."""
    monkeypatch.setattr(mds, "LAGRANGE_MAX_M", 2)
    svc = FFTService(FFTServiceConfig(s=256, m=4, n_workers=8, seed=3))
    rng = np.random.default_rng(1)
    xs = [jnp.asarray(rng.normal(size=256).astype(np.float32))
          for _ in range(6)]
    for x, y in zip(xs, svc.submit_batch(xs, kind="r2c")):
        assert y.shape == (129,)
        assert float(np.abs(y - np.fft.rfft(np.asarray(x))).max()) < 1e-2
    ys = [jnp.asarray(np.fft.rfft(np.asarray(x)).astype(np.complex64))
          for x in xs]
    for x, z in zip(xs, svc.submit_batch(ys, kind="c2r")):
        assert z.shape == (256,)
        assert float(np.abs(z - np.asarray(x)).max()) < 1e-2
    # same-mask repeats across kinds hit the SHARED cache
    assert svc.stats.decode_cache_hits > 0
    assert len(svc._decode_cache_for()) <= svc.stats.decode_cache_misses


# --------------------------------------------- adversarial mask patterns
def test_masks_equal_as_subsets_do_not_collide():
    """Two masks selecting the SAME first-m responder subset but differing
    as byte patterns must occupy distinct cache entries (byte-keying), and
    both must decode correctly -- a subset-keyed cache would alias them,
    a value-keyed comparison would miss the second's tail responders."""
    g = np.asarray(mds.rs_generator(8, 4, jnp.complex64))
    cache = DecodeMatrixCache(g, maxsize=8)
    m1 = np.array([1, 1, 1, 1, 0, 0, 0, 0], bool)
    m2 = np.array([1, 1, 1, 1, 1, 0, 0, 0], bool)  # same first-4 subset
    np.testing.assert_array_equal(
        DecodeMatrixCache.subset_of(m1, 4), DecodeMatrixCache.subset_of(m2, 4))
    d1, d2 = cache.matrix(m1), cache.matrix(m2)
    assert len(cache) == 2                      # no collision
    assert cache.hits == 0 and cache.misses == 2
    np.testing.assert_allclose(d1, d2, atol=0)  # same VALUE, distinct keys
    # and the same byte pattern submitted from another (s, kind) bucket is
    # a pure hit: the service shares one LRU because the generator only
    # depends on (N, m)
    cache.matrix(m1)
    assert cache.hits == 1


def test_service_shares_decode_cache_across_buckets(monkeypatch):
    """Identical straggler masks arriving in different (s, kind) buckets
    must hit the one shared LRU, not rebuild per bucket (host-fallback
    path; the default in-jit decode has no cache to share)."""
    monkeypatch.setattr(mds, "LAGRANGE_MAX_M", 2)
    svc = FFTService(FFTServiceConfig(s=256, m=4, n_workers=8, seed=9,
                                      decode_cache_size=512))
    rng = np.random.default_rng(2)
    xs256 = [jnp.asarray((rng.normal(size=256) + 1j * rng.normal(size=256))
                         .astype(np.complex64)) for _ in range(4)]
    xs128 = [jnp.asarray((rng.normal(size=128) + 1j * rng.normal(size=128))
                         .astype(np.complex64)) for _ in range(4)]
    svc.submit_batch(xs256)
    misses_after_first = svc.stats.decode_cache_misses
    # same service RNG stream continues, but ANY repeat mask from the 128
    # bucket or the r2c bucket hits the same store; with 70 masks over a
    # small C(8, >=4) pattern space repeats are guaranteed
    for _ in range(4):
        svc.submit_batch(xs128)
        svc.submit_batch([jnp.real(x) for x in xs256], kind="r2c")
    assert svc.stats.decode_cache_hits > 0
    assert len(svc._decode_cache_for()) == svc.stats.decode_cache_misses
    assert svc.stats.decode_cache_misses >= misses_after_first


def test_service_lru_churn_with_real_kinds_stays_correct(monkeypatch):
    """LRU eviction under churn across c2c + r2c + c2r buckets keeps
    parity: a tiny cache forces constant evictions; every request of every
    kind must still decode exactly (extends the c2c churn test above)."""
    monkeypatch.setattr(mds, "LAGRANGE_MAX_M", 2)
    svc = FFTService(FFTServiceConfig(
        s=128, m=4, n_workers=8, seed=13, decode_cache_size=2))
    rng = np.random.default_rng(5)
    worst = 0.0
    for _ in range(4):
        xr = [jnp.asarray(rng.normal(size=128).astype(np.float32))
              for _ in range(4)]
        for x, y in zip(xr, svc.submit_batch(xr, kind="r2c")):
            worst = max(worst, float(
                np.abs(y - np.fft.rfft(np.asarray(x))).max()))
        ys = [jnp.asarray(np.fft.rfft(np.asarray(x)).astype(np.complex64))
              for x in xr]
        for x, z in zip(xr, svc.submit_batch(ys, kind="c2r")):
            worst = max(worst, float(np.abs(z - np.asarray(x)).max()))
        xc = [jnp.asarray((rng.normal(size=128) + 1j * rng.normal(size=128))
                          .astype(np.complex64)) for _ in range(4)]
        for x, y in zip(xc, svc.submit_batch(xc)):
            worst = max(worst, float(
                np.abs(y - np.fft.fft(np.asarray(x))).max()))
    assert worst < 1e-2, worst
    assert svc.stats.decode_cache_misses > 2  # churn proof


# ----------------------------------------------- service path selection
def test_service_default_uses_kernel_path_with_reference_escape():
    svc = FFTService(FFTServiceConfig(s=256, m=4, n_workers=8))
    assert svc._kernel_path(256)
    assert svc.plan.resolved_backend == "kernel"
    ref_svc = FFTService(FFTServiceConfig(
        s=256, m=4, n_workers=8, use_reference=True))
    assert not ref_svc._kernel_path(256)
    assert ref_svc.plan.resolved_backend == "reference"
    # explicit worker plug-in or pinned decode method -> plan.run executor
    plug = FFTService(FFTServiceConfig(
        s=256, m=4, n_workers=8,
        worker_fn=ops.make_kernel_worker_fn(interpret=True)))
    assert not plug._kernel_path(256)
    pinned = FFTService(FFTServiceConfig(
        s=256, m=4, n_workers=8, decode_method="solve"))
    assert not pinned._kernel_path(256)


def test_service_kernel_vs_reference_same_results():
    """Same seed => same straggler draws => kernel and reference executors
    must agree to f32 tolerance on every request."""
    cfgs = [FFTServiceConfig(s=512, m=4, n_workers=8, seed=7,
                             use_reference=flag) for flag in (False, True)]
    rng = np.random.default_rng(2)
    xs = [jnp.asarray((rng.normal(size=512) + 1j * rng.normal(size=512))
                      .astype(np.complex64)) for _ in range(5)]
    outs = [FFTService(c).submit_batch(xs) for c in cfgs]
    for yk, yr in zip(*outs):
        assert float(np.max(np.abs(yk - yr))) < 1e-3


# ------------------------------------------------- the kernel path's routes
_ROUTE_KERNELS = {"c2c": "coded_fft_bucket_masked",
                  "r2c": "coded_rfft_bucket_masked",
                  "c2r": "coded_irfft_bucket_masked"}


@pytest.mark.parametrize("dispatch", ["direct", "tpu"])
@pytest.mark.parametrize("decode", ["in_jit", "host"])
@pytest.mark.parametrize("kind", ["c2c", "r2c", "c2r"])
def test_kernel_runner_route_table(monkeypatch, kind, decode, dispatch):
    """The route table of the kernel path's one runner builder: the decode
    source (in the jit from the raw masks, or planes from the host LRU)
    and the body (direct off the TPU; on a TPU-like dispatch the
    one-launch masked kernel where the decode is in the jit, the stage
    kernels where it is not).  Traced over the arguments the stager ships;
    tracing never executes a kernel."""
    if dispatch == "tpu":
        monkeypatch.setattr(ops, "default_interpret", lambda: False)
    if decode == "host":
        monkeypatch.setattr(mds, "LAGRANGE_MAX_M", 2)
    s, q = 256, 4
    svc = FFTService(FFTServiceConfig(s=s, m=4, n_workers=8))
    body = {"direct": "direct", "tpu": "whole" if decode == "in_jit"
            else "staged"}[dispatch]
    assert svc._bucket_body(s, kind) == body
    assert svc._runner_name(s, kind) == ("kernel_masked" if decode == "in_jit"
                                         else "kernel")
    xb = svc._bucket_buffer(s, q, kind)
    x, *dec = svc._bucket_args(s, kind, xb, svc._full_masks(s, kind, q))
    if kind != "r2c":
        x = from_words(x)
    jaxpr = str(jax.make_jaxpr(svc._runner_for(s, q, kind))(x, *dec))
    # the decode matrices: built in the trace from the masks, or inputs
    assert bool(re.search(r"\bsort\[", jaxpr)) == (
        decode == "in_jit" and body != "whole")
    assert (_ROUTE_KERNELS[kind] in jaxpr) == (body == "whole")
    assert ("bcmatmul" in jaxpr) == (body == "staged")
    assert ("pallas_call" in jaxpr) == (body != "direct")
    assert svc.stats.decode_cache_misses == (decode == "host")
