"""Device-resident Lagrange decode + async bucket pipeline (DESIGN.md §8).

Covers the structured decode stack end to end: closed-form
``mds.lagrange_inverse`` parity against the host ``linalg.inv`` over
adversarial byte-pattern masks at m in {4, 16, 64}, the
``m > LAGRANGE_MAX_M`` host-LRU fallback boundary (pinned by jaxpr
inspection: in-trace weight construction present on one side, absent on
the other), the pipelined service scheduler (mixed kinds in one call, one
device->host transfer per submit_batch, dispatch/sync stats split), and
the wire-scaled straggler arrivals of the real kinds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mds
from repro.distributed.straggler import StragglerModel
from repro.kernels import ops
from repro.serving import FFTService, FFTServiceConfig
from repro.serving.decode_cache import DecodeMatrixCache
from repro.serving.fft_service import from_words

pytestmark = pytest.mark.kernels


def _adversarial_masks(n: int, m: int) -> np.ndarray:
    """Byte-pattern adversarial mask set for an (n, m) code.

    Stresses the KEYING/PLUMBING corners, not just numerics: masks equal
    as first-m subsets but different as byte patterns (aliasing tails),
    block stragglers at head and tail, alternating and rotated spreads,
    and random >= m-alive draws.
    """
    rng = np.random.default_rng(0)
    masks = [np.ones(n, bool)]                       # everyone responded
    first = np.zeros(n, bool)
    first[:m] = True
    masks.append(first)                              # exactly the first m
    tail = first.copy()
    tail[-1] = True
    masks.append(tail)                               # same subset, new bytes
    masks.append(~first if (~first).sum() >= m
                 else np.ones(n, bool))              # head block straggles
    alt = np.arange(n) % 2 == 0
    masks.append(alt)                                # alternating spread
    masks.append(np.roll(alt, 1))                    # ... rotated
    for _ in range(2):                               # random >= m alive
        r = rng.random(n) < 0.75
        while r.sum() < m:
            r[rng.integers(n)] = True
        masks.append(r)
    for _ in range(2):                               # spread w/ random swaps
        r = alt.copy()                               # (stays conditioned at
        sw = rng.integers(0, n // 2, size=max(2, n // 16))  # any m)
        r[2 * sw] = False
        r[2 * sw + 1] = True
        while r.sum() < m:
            r[rng.integers(n)] = True
        masks.append(r)
    return np.stack(masks)


# --------------------------------------------------- closed-form inversion
@pytest.mark.parametrize("m", [4, 16, 64])
def test_lagrange_inverse_matches_host_inverse(m):
    """``lagrange_inverse`` == ``np.linalg.inv`` of the subset generator to
    within the subset's own interpolation conditioning, for every
    adversarial byte pattern.  Subsets whose conditioning exceeds what
    float64 itself can carry are excluded -- BOTH implementations return
    conditioning-limited garbage there, which is exactly why
    ``LAGRANGE_MAX_M`` (and the m=64 host fallback) exists.
    """
    n = 2 * m
    g = np.asarray(mds.rs_generator(n, m, jnp.complex128))
    checked = 0
    for mask in _adversarial_masks(n, m):
        subset = DecodeMatrixCache.subset_of(mask, m)
        v = g[subset]
        cond = np.linalg.cond(v)
        if cond > 1e12:
            continue
        want = np.linalg.inv(v)
        got = np.asarray(mds.lagrange_inverse(
            jnp.asarray(subset), n, jnp.complex128))
        rel = np.abs(got - want).max() / np.abs(want).max()
        assert rel < max(1e-9, cond * 1e-12), (m, cond, rel)
        checked += 1
    assert checked >= 4  # spread/random patterns stay well-conditioned


def test_lagrange_decode_matrices_match_cache_exhaustively():
    """Scatter matrices from the device path == the host LRU's, for EVERY
    decodable mask of the (8, 4) service-default code (163 patterns)."""
    n, m = 8, 4
    g = np.asarray(mds.rs_generator(n, m, jnp.complex128))
    cache = DecodeMatrixCache(g, maxsize=256)
    masks = np.stack([
        np.array([(k >> i) & 1 for i in range(n)], bool)
        for k in range(2 ** n)
        if bin(k).count("1") >= m])
    want = cache.matrices(masks)                      # complex64 host path
    got = np.asarray(mds.lagrange_decode_matrices(
        jnp.asarray(masks), m, jnp.complex128))
    assert np.abs(got - want).max() < 1e-5
    # and the f32-plane form the kernels consume agrees
    subsets = ops.mask_subsets(jnp.asarray(masks), m)
    dr, di = ops.lagrange_scatter_planes(subsets, n)
    planes = np.asarray(dr) + 1j * np.asarray(di)
    assert np.abs(planes - want).max() < 1e-4


def test_lagrange_inverse_jit_vmap_composable():
    """The construction must be jit/vmap-safe (it runs inside the bucket
    executor): one fused trace over a batch of masks, no host callbacks."""
    n, m = 8, 4
    masks = jnp.asarray(_adversarial_masks(n, m))

    @jax.jit
    def build(mk):
        return mds.lagrange_decode_matrices(mk, m)

    d = build(masks)
    assert d.shape == (masks.shape[0], m, n)
    g = np.asarray(mds.rs_generator(n, m, jnp.complex64))
    # D @ G == I on every request: the defining decode property
    eye = np.asarray(d) @ g
    assert np.abs(eye - np.eye(m)[None]).max() < 1e-4


# -------------------------------------------- masked Pallas bucket kernels
@pytest.mark.parametrize("s,m,n", [(2048, 4, 8), (768, 4, 6), (96, 3, 7)])
def test_coded_bucket_masked_kernel_parity(s, m, n):
    """The masked whole-bucket kernel (decode matrices built IN the kernel
    body from responder subsets) == numpy.fft through the real Pallas
    machinery (interpret=True) AND the direct body -- guards the 15-input
    BlockSpec wiring the CPU service path never executes."""
    from repro.kernels import ref

    g = mds.rs_generator(n, m, jnp.complex64)
    gr, gi = ref.planar(g)
    masks = _adversarial_masks(n, m)[:5]
    rng = np.random.default_rng(s + m)
    xb = (rng.normal(size=(len(masks), s))
          + 1j * rng.normal(size=(len(masks), s))).astype(np.complex64)
    xr, xi = ref.planar(jnp.asarray(xb))
    want = np.fft.fft(xb.astype(np.complex128), axis=-1)
    for itp in (True, None):
        yr, yi = ops.coded_bucket_masked(xr, xi, jnp.asarray(masks), gr, gi,
                                         s, interpret=itp)
        got = np.asarray(ref.unplanar(yr, yi))
        rel = np.abs(got - want).max() / np.abs(want).max()
        assert rel < 3e-4, (itp, rel)


@pytest.mark.parametrize("s,m,n", [(2048, 4, 8), (768, 4, 6)])
def test_coded_rbucket_masked_kernel_parity(s, m, n):
    """r2c twin of the masked-kernel parity pin: real requests -> half
    spectra with in-VMEM Lagrange weights, interpret + direct modes."""
    from repro.kernels import ref

    g = mds.rs_generator(n, m, jnp.complex64)
    gr, gi = ref.planar(g)
    masks = _adversarial_masks(n, m)[:5]
    rng = np.random.default_rng(s * m)
    xb = rng.normal(size=(len(masks), s)).astype(np.float32)
    want = np.fft.rfft(xb.astype(np.float64), axis=-1)
    for itp in (True, None):
        yr, yi = ops.coded_rbucket_masked(jnp.asarray(xb), jnp.asarray(masks),
                                          gr, gi, s, interpret=itp)
        got = np.asarray(ref.unplanar(yr, yi))
        rel = np.abs(got - want).max() / np.abs(want).max()
        assert rel < 3e-4, (itp, rel)


# ------------------------------------------------- fallback boundary (§8)
def _runner_jaxpr(svc: FFTService, bucket: int = 2) -> str:
    """The jaxpr of the service's compiled bucket executor at its default
    (s, c2c) key, traced over the exact argument layout the scheduler
    feeds it: the staged arguments, the requests rebuilt from the words
    that crossed the host link."""
    cfg = svc.cfg
    runner = svc._runner_for(cfg.s, bucket, "c2c")
    xb = svc._bucket_buffer(cfg.s, bucket, "c2c")
    masks = np.ones((bucket, cfg.n_workers), bool)
    words, *rest = svc._bucket_args(cfg.s, "c2c", xb, masks)
    return str(jax.make_jaxpr(lambda *a: runner(*a))(from_words(words),
                                                     *rest))


def test_device_decode_below_boundary_builds_weights_in_trace():
    """m == LAGRANGE_MAX_M must run the device path: the executor takes the
    raw masks and its jaxpr contains the in-trace weight construction
    (trig node powers + the responder argsort) -- and the service never
    touches the host LRU."""
    m = mds.LAGRANGE_MAX_M
    svc = FFTService(FFTServiceConfig(s=64 * m, m=m, n_workers=2 * m))
    assert svc._decode_in_jit()
    jaxpr = _runner_jaxpr(svc)
    assert "cos" in jaxpr and "sort" in jaxpr
    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=64 * m).astype(np.complex64))
    svc.submit(x)
    assert not svc._decode_caches  # no capacity ever instantiated a host LRU
    assert svc.stats.decode_cache_misses == 0


def test_above_boundary_falls_back_to_host_lru():
    """m > LAGRANGE_MAX_M flips to the host complex128 LRU: the executor
    jaxpr carries NO in-trace weight construction (matrices arrive as
    inputs), and novel masks pay host inversions (cache misses)."""
    m = 64
    assert m > mds.LAGRANGE_MAX_M
    svc = FFTService(FFTServiceConfig(s=32 * m, m=m, n_workers=2 * m))
    assert not svc._decode_in_jit()
    jaxpr = _runner_jaxpr(svc)
    assert "cos" not in jaxpr
    x = jnp.asarray(np.random.default_rng(0)
                    .normal(size=32 * m).astype(np.complex64))
    svc.submit(x)
    assert svc.stats.decode_cache_misses > 0


def test_device_and_host_paths_serve_identical_results(monkeypatch):
    """Same seed (hence same simulated straggler masks): the device-decode
    service and the host-LRU fallback service (m above a lowered
    ``LAGRANGE_MAX_M``) must agree request for request -- and both must
    match numpy."""
    rng = np.random.default_rng(7)
    xs = [jnp.asarray((rng.normal(size=512) + 1j * rng.normal(size=512))
                      .astype(np.complex64)) for _ in range(9)]
    cfg = FFTServiceConfig(s=512, m=4, n_workers=8, seed=21)
    dev = FFTService(cfg)
    out_d = dev.submit_batch(xs)
    with monkeypatch.context() as patch:
        patch.setattr(mds, "LAGRANGE_MAX_M", 2)
        host = FFTService(cfg)
        out_h = host.submit_batch(xs)
    for x, yd, yh in zip(xs, out_d, out_h):
        want = np.fft.fft(np.asarray(x, np.complex128))
        assert np.abs(yd - want).max() < 1e-2
        assert np.abs(yd - yh).max() < 1e-3
    assert dev.stats.decode_cache_misses == 0
    assert host.stats.decode_cache_misses > 0


# ----------------------------------------------- async pipelined scheduler
def test_one_host_transfer_per_submit_batch():
    """The pipelined scheduler syncs ONCE per submit_batch call, however
    many (s, kind) buckets the call spans, and accounts dispatch vs sync
    wall time separately."""
    svc = FFTService(FFTServiceConfig(s=256, m=4, n_workers=8, seed=2,
                                      max_batch=4))
    rng = np.random.default_rng(3)
    xs = [jnp.asarray((rng.normal(size=s) + 1j * rng.normal(size=s))
                      .astype(np.complex64))
          for s in (256, 256, 256, 256, 256, 128, 128)]
    svc.submit_batch(xs)                  # 2 s=256 buckets + 1 s=128 bucket
    st = svc.stats.summary()
    assert st["batches"] == 3
    assert st["host_transfers"] == 1
    assert st["dispatch_s"] > 0.0 and st["sync_s"] > 0.0
    svc.submit_batch(xs[:2])
    assert svc.stats.host_transfers == 2


def test_mixed_kinds_bucket_in_one_call():
    """submit_batch accepts per-request kinds: one call carrying c2c + r2c
    + c2r traffic buckets by (s, kind) and returns every result in
    submission order."""
    svc = FFTService(FFTServiceConfig(s=256, m=4, n_workers=8, seed=5))
    rng = np.random.default_rng(4)
    xc = [jnp.asarray((rng.normal(size=256) + 1j * rng.normal(size=256))
                      .astype(np.complex64)) for _ in range(2)]
    xr = [jnp.asarray(rng.normal(size=256).astype(np.float32))
          for _ in range(2)]
    yh = [jnp.asarray(np.fft.rfft(np.asarray(x)).astype(np.complex64))
          for x in xr]
    reqs = [xc[0], xr[0], yh[0], xc[1], xr[1], yh[1]]
    kinds = ["c2c", "r2c", "c2r"] * 2
    outs = svc.submit_batch(reqs, kind=kinds)
    for i, x in enumerate(xc):
        assert np.abs(outs[3 * i] - np.fft.fft(np.asarray(x))).max() < 1e-2
    for i, x in enumerate(xr):
        assert np.abs(outs[3 * i + 1]
                      - np.fft.rfft(np.asarray(x))).max() < 1e-2
        assert np.abs(outs[3 * i + 2] - np.asarray(x)).max() < 1e-2
    assert svc.stats.batches == 3          # one bucket per kind
    assert svc.stats.host_transfers == 1   # still one sync
    with pytest.raises(ValueError):
        svc.submit_batch(reqs, kind=["c2c"])           # length mismatch
    with pytest.raises(ValueError):
        svc.submit_batch(reqs[:1], kind=["c2x"])       # unknown kind


def test_warmup_keys_executables_once():
    """After warmup, steady-state traffic adds no new executables (and no
    compiles) for the covered (s, kind, bucket) keys."""
    svc = FFTService(FFTServiceConfig(s=256, m=4, n_workers=8, seed=1,
                                      max_batch=8))
    compiled = svc.warmup()
    assert compiled == 4                   # buckets 1, 2, 4, 8
    n_runners = len(svc._runners)
    rng = np.random.default_rng(6)
    for batch in (1, 3, 8):
        xs = [jnp.asarray((rng.normal(size=256) + 1j
                           * rng.normal(size=256)).astype(np.complex64))
              for _ in range(batch)]
        svc.submit_batch(xs)
    assert len(svc._runners) == n_runners


# --------------------------------------------- wire-scaled straggler model
def test_wire_frac_scales_only_the_wire_share():
    model = StragglerModel(t0=2.0, mu=1.0, wire_frac=0.5)
    rng = np.random.default_rng(0)
    full = model.sample((20000,), 1.0, rng, payload_scale=1.0)
    rng = np.random.default_rng(0)
    half = model.sample((20000,), 1.0, rng, payload_scale=0.5)
    # same tail draws, deterministic part shrinks by wire_frac * (1-scale)
    np.testing.assert_allclose(full - half, 2.0 * 0.5 * 0.5, atol=1e-12)
    # payload_scale=1 reduces to the literature model whatever wire_frac is
    assert model.expected_kth(8, 4, 1.0) == pytest.approx(
        StragglerModel(t0=2.0, mu=1.0, wire_frac=0.0).expected_kth(8, 4, 1.0))
    assert (model.expected_kth(8, 4, 1.0, payload_scale=0.5)
            < model.expected_kth(8, 4, 1.0))


def test_service_charges_real_kinds_half_wire_time():
    """r2c/c2r buckets simulate arrivals at payload_scale=0.5: with a
    wire-heavy model their coded latency must run measurably below c2c's
    on the same seed."""
    model = StragglerModel(t0=1.0, mu=4.0, wire_frac=0.8)
    mk = lambda: FFTService(FFTServiceConfig(
        s=256, m=4, n_workers=8, straggler=model, seed=17))
    lat_c, _ = mk()._simulate_arrivals(4000, "c2c")
    lat_r, _ = mk()._simulate_arrivals(4000, "r2c")
    lat_i, _ = mk()._simulate_arrivals(4000, "c2r")
    assert lat_r.mean() < lat_c.mean()
    assert lat_i.mean() < lat_c.mean()
    # exactly the wire share: same rng stream, deterministic offset
    np.testing.assert_allclose(
        (lat_c - lat_r).mean(), (1.0 / 4) * 1.0 * 0.8 * 0.5, atol=1e-9)
