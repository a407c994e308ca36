"""Property-based differential suite: EVERY CodedPlan vs numpy.fft.

One harness, all strategies (1-D, n-D, multi-input, uncoded repetition,
and the real/inverse plans of DESIGN.md §7), drawing

    (config, batch, dtype/backend tier, straggler mask)

and asserting end-to-end parity against the numpy oracle under ANY
``k >= recovery_threshold``-subset of responders, with straggler rows
NaN-poisoned to prove decode never reads them.  This supersedes the
per-plan ad-hoc example parity tests (the remaining example tests pin
shapes, protocol details, and dispatch rules, not parity).

Runs with or without hypothesis installed (tests/_hypothesis_shim.py);
the CI property job pins ``--hypothesis-seed`` and the default example
budget stays small for PR latency -- the ``slow``-marked sweep at the
bottom buys the full budget.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import HAVE_HYPOTHESIS, given, prop_settings, st

from repro.core import (
    REGISTRY,
    CodedFFT,
    CodedFFTMultiInput,
    CodedFFTND,
    CodedIFFT,
    CodedIRFFT,
    CodedIRFFTN,
    CodedPartialFFT,
    CodedRFFT,
    CodedRFFTN,
    UncodedRepetitionFFT,
)

# Example budget: small by default (PR latency); PROP_MAX_EXAMPLES
# overrides for local deep runs, the slow sweep below multiplies it.
MAX_EXAMPLES = int(os.environ.get("PROP_MAX_EXAMPLES", "8"))

# Enumerated valid configs keep the draw space dense in constructible
# plans (m | s, 2m | s for the real kinds, N >= m); drawing raw integers
# would reject almost everything.
CONFIGS_1D = [
    (32, 2, 5),
    (48, 4, 6),
    (64, 4, 8),
    (96, 3, 7),
    (120, 4, 9),
]
CONFIGS_ND = [
    ((8, 8), (2, 2), 6),
    ((16, 4), (4, 1), 5),
    ((12, 6), (2, 3), 8),
]
# n-D real configs additionally need an even LAST shard axis
# (2*factors[-1] | shape[-1], DESIGN.md §9)
CONFIGS_RND = [
    ((8, 8), (2, 2), 6),
    ((16, 4), (4, 1), 5),
    ((12, 8), (3, 2), 8),
    ((6, 4, 8), (3, 1, 2), 7),
    ((24,), (4,), 6),
]
CONFIGS_MI = [
    (4, (8,), 2, (2,), 6),
    (2, (4, 6), 2, (1, 2), 5),
    (6, (8,), 3, (1,), 4),
]
# (backend, dtype, rtol): the kernel tier computes in f32 planes; the
# reference tier is the c128 numerics oracle.
TIERS = [
    ("kernel", jnp.complex64, 5e-3),
    ("reference", jnp.complex64, 5e-3),
    ("reference", jnp.complex128, 1e-8),
]
BATCHES = (0, 1, 3)


def _mask(n: int, k: int, seed: int) -> np.ndarray:
    """A uniformly random availability pattern with exactly k responders."""
    rng = np.random.default_rng(seed)
    mask = np.zeros(n, bool)
    mask[rng.choice(n, size=k, replace=False)] = True
    return mask


def _arc_mask(n: int, k: int, seed: int) -> np.ndarray:
    """A contiguous-mod-n responder arc: the mask family the §4 ifft
    fast-decode dispatch routes to for small m."""
    start = seed % n
    mask = np.zeros(n, bool)
    mask[(start + np.arange(k)) % n] = True
    return mask


def _masks(n: int, threshold: int, batch: int, seed: int,
           contiguous: bool = False) -> np.ndarray:
    rng = np.random.default_rng(seed)
    rows = max(batch, 1)
    ks = rng.integers(threshold, n + 1, size=rows)
    make = _arc_mask if contiguous else _mask
    out = np.stack([make(n, int(k), seed + 17 * r + 1)
                    for r, k in enumerate(ks)])
    return out if batch else out[0]

def _rand(shape, seed, *, dtype):
    rng = np.random.default_rng(seed)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
        data = rng.normal(size=shape) + 1j * rng.normal(size=shape)
    else:
        data = rng.normal(size=shape)
    return jnp.asarray(data.astype(dtype))


def _poisoned_run(plan, x, mask, *, fragment_mask=None):
    """encode -> worker -> NaN-poison stragglers -> masked decode.

    With ``fragment_mask`` (partial-work plans, DESIGN.md §13) the poison
    is per-FRAGMENT: an unfinished fragment row holds NaN even when other
    fragments of the same worker are live, proving decode reads exactly
    the claimed coverage set.
    """
    b = plan.worker_compute(plan.encode(x))
    if fragment_mask is not None:
        fm = jnp.asarray(fragment_mask)
        shield = fm.reshape(fm.shape + (1,) * (b.ndim - fm.ndim))
        b = jnp.where(shield, b, jnp.nan)
        return plan.decode(b, fragment_mask=fm)
    mk = jnp.asarray(mask)
    shield = mk.reshape(mk.shape + (1,) * (b.ndim - mk.ndim))
    b = jnp.where(shield, b, jnp.nan)
    return plan.decode(b, mask=mk)


def _check(got, want, rtol, label):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (label, got.shape, want.shape)
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)
    assert err < rtol, (label, err)


# ------------------------------------------------------------ MDS plan kinds
@prop_settings(max_examples=MAX_EXAMPLES)
@given(cfg=st.sampled_from(CONFIGS_1D), tier=st.sampled_from(TIERS),
       batch=st.sampled_from(BATCHES), seed=st.integers(0, 10**6))
def test_coded_fft_matches_numpy(cfg, tier, batch, seed):
    s, m, n = cfg
    backend, dtype, rtol = tier
    plan = CodedFFT(s=s, m=m, n_workers=n, dtype=dtype, backend=backend)
    shape = ((batch, s) if batch else (s,))
    x = _rand(shape, seed, dtype=dtype)
    mask = _masks(n, m, batch, seed)
    _check(_poisoned_run(plan, x, mask),
           np.fft.fft(np.asarray(x, np.complex128), axis=-1), rtol, cfg)


@prop_settings(max_examples=MAX_EXAMPLES)
@given(cfg=st.sampled_from(CONFIGS_1D), tier=st.sampled_from(TIERS),
       batch=st.sampled_from(BATCHES), seed=st.integers(0, 10**6))
def test_coded_rfft_matches_numpy(cfg, tier, batch, seed):
    s, m, n = cfg
    backend, dtype, rtol = tier
    plan = CodedRFFT(s=s, m=m, n_workers=n, dtype=dtype, backend=backend)
    shape = ((batch, s) if batch else (s,))
    x = _rand(shape, seed, dtype=plan.real_dtype)
    mask = _masks(n, m, batch, seed)
    _check(_poisoned_run(plan, x, mask),
           np.fft.rfft(np.asarray(x, np.float64), axis=-1), rtol, cfg)


@prop_settings(max_examples=MAX_EXAMPLES)
@given(cfg=st.sampled_from(CONFIGS_1D), tier=st.sampled_from(TIERS),
       batch=st.sampled_from(BATCHES), seed=st.integers(0, 10**6))
def test_coded_ifft_matches_numpy(cfg, tier, batch, seed):
    s, m, n = cfg
    backend, dtype, rtol = tier
    plan = CodedIFFT(s=s, m=m, n_workers=n, dtype=dtype, backend=backend)
    shape = ((batch, s) if batch else (s,))
    x = _rand(shape, seed, dtype=dtype)
    mask = _masks(n, m, batch, seed)
    _check(_poisoned_run(plan, x, mask),
           np.fft.ifft(np.asarray(x, np.complex128), axis=-1), rtol, cfg)


@prop_settings(max_examples=MAX_EXAMPLES)
@given(cfg=st.sampled_from(CONFIGS_1D), tier=st.sampled_from(TIERS),
       batch=st.sampled_from(BATCHES), seed=st.integers(0, 10**6))
def test_coded_irfft_matches_numpy(cfg, tier, batch, seed):
    s, m, n = cfg
    backend, dtype, rtol = tier
    plan = CodedIRFFT(s=s, m=m, n_workers=n, dtype=dtype, backend=backend)
    # draw the half spectrum of a REAL signal so the request is exactly
    # Hermitian-consistent (numpy drops endpoint imag parts; so do we --
    # pinned separately below)
    shape = ((batch, s) if batch else (s,))
    xt = np.random.default_rng(seed).normal(size=shape)
    y = jnp.asarray(np.fft.rfft(xt, axis=-1).astype(dtype))
    mask = _masks(n, m, batch, seed)
    _check(_poisoned_run(plan, y, mask),
           np.fft.irfft(np.asarray(y, np.complex128), n=s, axis=-1),
           rtol, cfg)


def test_irfft_endpoint_imag_discarded_like_numpy():
    """Non-Hermitian endpoint bins: parity with numpy.fft.irfft exactly."""
    s, m, n = 64, 4, 8
    rng = np.random.default_rng(0)
    y = np.fft.rfft(rng.normal(size=s)).astype(np.complex128)
    y[0] += 0.7j
    y[-1] -= 0.3j
    plan = CodedIRFFT(s=s, m=m, n_workers=n, dtype=jnp.complex128,
                      backend="reference")
    _check(plan.run(jnp.asarray(y)), np.fft.irfft(y, n=s), 1e-8, "endpoints")


@prop_settings(max_examples=MAX_EXAMPLES)
@given(cfg=st.sampled_from(CONFIGS_ND), tier=st.sampled_from(TIERS),
       batch=st.sampled_from(BATCHES), seed=st.integers(0, 10**6))
def test_coded_fft_nd_matches_numpy(cfg, tier, batch, seed):
    shape, factors, n = cfg
    backend, dtype, rtol = tier
    plan = CodedFFTND(shape=shape, factors=factors, n_workers=n,
                      dtype=dtype, backend=backend)
    full = ((batch,) + shape if batch else shape)
    t = _rand(full, seed, dtype=dtype)
    mask = _masks(n, plan.m, batch, seed)
    _check(_poisoned_run(plan, t, mask),
           np.fft.fftn(np.asarray(t, np.complex128),
                       axes=tuple(range(-len(shape), 0))), rtol, cfg)


@prop_settings(max_examples=MAX_EXAMPLES)
@given(cfg=st.sampled_from(CONFIGS_RND), tier=st.sampled_from(TIERS),
       batch=st.sampled_from(BATCHES), seed=st.integers(0, 10**6))
def test_coded_rfftn_matches_numpy(cfg, tier, batch, seed):
    """n-D real forward (DESIGN.md §9): pair-packed half-payload shards,
    per-axis worker sweep, generalized split postdecode == numpy.rfftn
    under NaN-poisoned straggler masks."""
    shape, factors, n = cfg
    backend, dtype, rtol = tier
    plan = CodedRFFTN(shape=shape, factors=factors, n_workers=n,
                      dtype=dtype, backend=backend)
    full = ((batch,) + shape if batch else shape)
    t = _rand(full, seed, dtype=plan.real_dtype)
    mask = _masks(n, plan.m, batch, seed)
    axes = tuple(range(-len(shape), 0))
    _check(_poisoned_run(plan, t, mask),
           np.fft.rfftn(np.asarray(t, np.float64), axes=axes), rtol, cfg)


@prop_settings(max_examples=MAX_EXAMPLES)
@given(cfg=st.sampled_from(CONFIGS_RND), tier=st.sampled_from(TIERS),
       batch=st.sampled_from(BATCHES), seed=st.integers(0, 10**6))
def test_coded_irfftn_matches_numpy(cfg, tier, batch, seed):
    """n-D real inverse: the adjoint pipeline (symmetrize -> per-axis
    fold -> pack -> ifftn workers) == numpy.irfftn on Hermitian-consistent
    draws (the inconsistent-endpoint contract is pinned in
    tests/test_rfftn.py)."""
    shape, factors, n = cfg
    backend, dtype, rtol = tier
    plan = CodedIRFFTN(shape=shape, factors=factors, n_workers=n,
                       dtype=dtype, backend=backend)
    full = ((batch,) + shape if batch else shape)
    axes = tuple(range(-len(shape), 0))
    xt = np.random.default_rng(seed).normal(size=full)
    y = jnp.asarray(np.fft.rfftn(xt, axes=axes).astype(dtype))
    mask = _masks(n, plan.m, batch, seed)
    _check(_poisoned_run(plan, y, mask),
           np.fft.irfftn(np.asarray(y, np.complex128), s=shape, axes=axes),
           rtol, cfg)


@prop_settings(max_examples=MAX_EXAMPLES)
@given(cfg=st.sampled_from(CONFIGS_MI), tier=st.sampled_from(TIERS),
       batch=st.sampled_from(BATCHES), seed=st.integers(0, 10**6))
def test_multi_input_matches_numpy(cfg, tier, batch, seed):
    q, shape, m_tilde, factors, n = cfg
    backend, dtype, rtol = tier
    plan = CodedFFTMultiInput(q=q, shape=shape, m_tilde=m_tilde,
                              factors=factors, n_workers=n, dtype=dtype,
                              backend=backend)
    full = ((batch, q) + shape if batch else (q,) + shape)
    t = _rand(full, seed, dtype=dtype)
    mask = _masks(n, plan.m, batch, seed)
    _check(_poisoned_run(plan, t, mask),
           np.fft.fftn(np.asarray(t, np.complex128),
                       axes=tuple(range(-len(shape), 0))), rtol, cfg)


# ----------------------------------------------------- strategy registry
# Every registered strategy (core.strategies.REGISTRY) runs the SAME
# differential harness: applicability-filtered configs, its OWN recovery
# threshold, NaN-poisoned straggler draws.  A new strategy registered with
# a factory + applicability predicate is verified here with zero new test
# code (DESIGN.md §13).

# extend the 1-D pool so the repetition entry (m^2 | N) draws non-trivial
# configs too
CONFIGS_REGISTRY = CONFIGS_1D + [(32, 2, 8), (64, 2, 4), (48, 2, 12)]


def _fragment_masks(n: int, r: int, need: int, batch: int,
                    seed: int) -> np.ndarray:
    """Random sequential-prefix fragment patterns meeting the coverage
    condition: worker w finished ``p_w`` fragments (0..r), total >= need."""
    rng = np.random.default_rng(seed)
    rows = max(batch, 1)
    out = np.zeros((rows, n, r), bool)
    for b in range(rows):
        prefix = rng.integers(0, r + 1, size=n)
        while prefix.sum() < need:
            w = int(rng.integers(n))
            prefix[w] = min(r, prefix[w] + 1)
        for w, p in enumerate(prefix):
            out[b, w, :p] = True
    return out if batch else out[0]


@prop_settings(max_examples=MAX_EXAMPLES)
@given(name=st.sampled_from(sorted(REGISTRY)),
       cfg=st.sampled_from(CONFIGS_REGISTRY), tier=st.sampled_from(TIERS),
       batch=st.sampled_from(BATCHES), seed=st.integers(0, 10**6))
def test_registry_strategy_matches_numpy(name, cfg, tier, batch, seed):
    """Differential-vs-numpy over the whole strategy registry, worker-mask
    draws at each strategy's own threshold (m for mds/partial, m*q for
    comm_efficient, N - N/m^2 + 1 for repetition)."""
    s, m, n = cfg
    backend, dtype, rtol = tier
    ent = REGISTRY[name]
    if not ent.applicable(s, m, n, None):
        return          # the registry's own applicability filter
    if not ent.kernel_ok:
        backend = "reference"   # the planar kernels are (N, m) MDS layouts
    plan = ent.build(s, m, n, dtype=dtype, backend=backend)
    if name == "repetition" and batch:
        batch = 0       # the baseline's host-side decode is checked 1-D
    shape = ((batch, s) if batch else (s,))
    x = _rand(shape, seed, dtype=dtype)
    mask = _masks(n, int(plan.recovery_threshold), batch, seed)
    _check(_poisoned_run(plan, x, mask),
           np.fft.fft(np.asarray(x, np.complex128), axis=-1), rtol,
           (name, cfg))


@prop_settings(max_examples=MAX_EXAMPLES)
@given(cfg=st.sampled_from(CONFIGS_REGISTRY), r=st.sampled_from([2, 3]),
       tier=st.sampled_from(TIERS), batch=st.sampled_from(BATCHES),
       seed=st.integers(0, 10**6))
def test_partial_fragment_prefixes_match_numpy(cfg, r, tier, batch, seed):
    """Partial-work decode from RAGGED fragment prefixes: random per-worker
    progress 0..r meeting the m*r coverage condition, unfinished fragment
    rows NaN-poisoned -- stragglers contribute prefixes, not holes."""
    s, m, n = cfg
    backend, dtype, rtol = tier
    if s % (m * r) or m * r > 8:
        return          # keep the decode width inside the tier rtols
    plan = CodedPartialFFT(s=s, m=m, n_workers=n, r=r, dtype=dtype,
                           backend="reference")
    shape = ((batch, s) if batch else (s,))
    x = _rand(shape, seed, dtype=dtype)
    fmask = _fragment_masks(n, r, plan.fragments_needed, batch, seed)
    _check(_poisoned_run(plan, x, None, fragment_mask=fmask),
           np.fft.fft(np.asarray(x, np.complex128), axis=-1), rtol,
           (cfg, r))


# -------------------------------------------------------- non-MDS baseline
@prop_settings(max_examples=MAX_EXAMPLES)
@given(cfg=st.sampled_from([(32, 2, 8), (64, 2, 4), (48, 2, 12)]),
       seed=st.integers(0, 10**6))
def test_uncoded_repetition_matches_numpy(cfg, seed):
    """The repetition baseline decodes from any mask at or above ITS
    (higher, Remark-4) threshold -- same differential harness, non-MDS
    decode."""
    s, m, n = cfg
    plan = UncodedRepetitionFFT(s=s, m=m, n_workers=n, dtype=jnp.complex128)
    x = _rand((s,), seed, dtype=jnp.complex128)
    k = int(np.random.default_rng(seed).integers(
        plan.recovery_threshold, n + 1))
    mask = _mask(n, k, seed + 1)
    got = plan.decode(plan.worker_compute(plan.encode(x)), mask=mask)
    _check(got, np.fft.fft(np.asarray(x, np.complex128)), 1e-8, cfg)


# ------------------------------------------------------------- deep sweep
@pytest.mark.slow
@prop_settings(max_examples=4 * MAX_EXAMPLES)
@given(cfg=st.sampled_from(CONFIGS_1D),
       kind=st.sampled_from(["c2c", "r2c", "c2r", "inv"]),
       tier=st.sampled_from(TIERS), batch=st.sampled_from(BATCHES),
       contiguous=st.booleans(), seed=st.integers(0, 10**6))
def test_full_budget_sweep(cfg, kind, tier, batch, contiguous, seed):
    """The full-budget pass over every 1-D kind (slow marker: deselected
    from the PR-latency CI property job, included in tier-1).  The
    ``contiguous`` draw alternates scattered responder masks with
    contiguous arcs -- the family §4's ifft fast decode dispatches to."""
    s, m, n = cfg
    backend, dtype, rtol = tier
    shape = ((batch, s) if batch else (s,))
    mask = _masks(n, m, batch, seed, contiguous=contiguous)
    if kind == "c2c":
        plan = CodedFFT(s=s, m=m, n_workers=n, dtype=dtype, backend=backend)
        x = _rand(shape, seed, dtype=dtype)
        want = np.fft.fft(np.asarray(x, np.complex128), axis=-1)
    elif kind == "inv":
        plan = CodedIFFT(s=s, m=m, n_workers=n, dtype=dtype, backend=backend)
        x = _rand(shape, seed, dtype=dtype)
        want = np.fft.ifft(np.asarray(x, np.complex128), axis=-1)
    elif kind == "r2c":
        plan = CodedRFFT(s=s, m=m, n_workers=n, dtype=dtype, backend=backend)
        x = _rand(shape, seed, dtype=plan.real_dtype)
        want = np.fft.rfft(np.asarray(x, np.float64), axis=-1)
    else:
        plan = CodedIRFFT(s=s, m=m, n_workers=n, dtype=dtype,
                          backend=backend)
        xt = np.random.default_rng(seed).normal(size=shape)
        x = jnp.asarray(np.fft.rfft(xt, axis=-1).astype(dtype))
        want = np.fft.irfft(np.asarray(x, np.complex128), n=s, axis=-1)
    _check(_poisoned_run(plan, x, mask), want, rtol, (cfg, kind))


def test_shim_mode_reported():
    """Pin that the suite ran (collection smoke) and report which sampler
    backed it -- the deterministic shim or real hypothesis."""
    assert MAX_EXAMPLES >= 1
    assert HAVE_HYPOTHESIS in (True, False)


# --------------------------------------------------- bf16 plane precision
# The opt-in bf16 twiddle/DFT planes (f32 accumulation) must stay inside
# ops.BF16_RTOL of the float64 oracle -- the same budget the service's
# per-(s, m, kind) warmup probe enforces before enabling the mode.
BF16_CONFIGS = [(64, 2, 5), (96, 3, 7), (256, 4, 8), (2048, 4, 8)]


@pytest.mark.parametrize("cfg", BF16_CONFIGS)
def test_bf16_bucket_planes_within_error_budget(cfg):
    from repro.core import mds
    from repro.kernels import ops, ref

    s, m, n = cfg
    q = 3
    rng = np.random.default_rng(s)
    g = mds.rs_generator(n, m, jnp.complex64)
    gr, gi = ref.planar(g)
    x = rng.standard_normal((q, s)) + 1j * rng.standard_normal((q, s))
    xr = jnp.asarray(x.real.astype(np.float32))
    xi = jnp.asarray(x.imag.astype(np.float32))
    masks = np.zeros((q, n), bool)
    for r in range(q):
        masks[r, rng.choice(n, size=m, replace=False)] = True
    want = np.fft.fft(x, axis=-1)
    for itp in (None, True):
        yr, yi = ops.coded_bucket_masked(
            xr, xi, jnp.asarray(masks), gr, gi, s,
            interpret=itp, precision="bf16")
        got = np.asarray(yr) + 1j * np.asarray(yi)
        rel = np.abs(got - want).max() / np.abs(want).max()
        assert rel < ops.BF16_RTOL, (cfg, itp, rel)
        # and bf16 must actually differ from the f32 planes (the knob is
        # live, not silently ignored)
        fr, fi = ops.coded_bucket_masked(
            xr, xi, jnp.asarray(masks), gr, gi, s,
            interpret=itp, precision="f32")
        assert np.abs(np.asarray(fr) - np.asarray(yr)).max() > 0


@pytest.mark.parametrize("ell", [256, 4096])
def test_bf16_fourstep_within_error_budget(ell):
    from repro.kernels import ops

    rng = np.random.default_rng(ell)
    x = rng.standard_normal((2, ell)) + 1j * rng.standard_normal((2, ell))
    xr = jnp.asarray(x.real.astype(np.float32))
    xi = jnp.asarray(x.imag.astype(np.float32))
    want = np.fft.fft(x, axis=-1)
    for variant in ("fused", "two_pass"):
        outr, outi = ops.fourstep_planar(xr, xi, variant=variant,
                                         precision="bf16")
        got = np.asarray(outr) + 1j * np.asarray(outi)
        rel = np.abs(got - want).max() / np.abs(want).max()
        assert rel < ops.BF16_RTOL, (ell, variant, rel)


def test_bf16_probe_auto_disables_per_shape(monkeypatch, tmp_path):
    """cfg.precision="bf16" is gated per (s, m, kind): a failing probe
    records ok=False in the autotune table and the runner stays f32."""
    from repro.kernels import autotune
    from repro.serving.fft_service import FFTService, FFTServiceConfig

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path))
    saved = dict(autotune._TABLES)
    saved_loaded = set(autotune._LOADED)
    autotune._TABLES.clear()
    autotune._LOADED.clear()
    try:
        cfg = FFTServiceConfig(s=64, m=2, n_workers=4, precision="bf16",
                               autotune=False)
        svc = FFTService(cfg)
        monkeypatch.setattr(FFTService, "_probe_bf16",
                            lambda self, s, kind: False)
        assert svc._precision_for(64, "c2c") == "f32"
        ent = autotune.lookup("bf16", s=64, m=2, k="c2c",
                              mode=__import__("repro.kernels.ops",
                                              fromlist=["ops"])._mode(None))
        assert ent == {"ok": False}
        # the verdict is sticky: a healthy probe later still reads f32
        monkeypatch.setattr(FFTService, "_probe_bf16",
                            lambda self, s, kind: True)
        assert svc._precision_for(64, "c2c") == "f32"
    finally:
        autotune._TABLES.clear()
        autotune._TABLES.update(saved)
        autotune._LOADED.clear()
        autotune._LOADED.update(saved_loaded)


def test_bf16_probe_catches_only_the_bf16_variant(monkeypatch):
    """The probe may find the bf16 planes unsupported (a warning and
    ``False``); a failure of the f32 twin it compares against is a real
    fault of the production kernel and propagates."""
    from repro.kernels import ops
    from repro.serving.fft_service import FFTService, FFTServiceConfig

    svc = FFTService(FFTServiceConfig(s=64, m=2, n_workers=4,
                                      autotune=False))
    real = ops.coded_bucket_masked

    def bf16_refused(*args, precision="f32", **kwargs):
        if precision == "bf16":
            raise RuntimeError("bf16 refused")
        return real(*args, precision=precision, **kwargs)

    monkeypatch.setattr(ops, "coded_bucket_masked", bf16_refused)
    with pytest.warns(RuntimeWarning, match="bf16 refused"):
        assert svc._probe_bf16(64, "c2c") is False

    def f32_refused(*args, **kwargs):
        raise RuntimeError("f32 refused")

    monkeypatch.setattr(ops, "coded_bucket_masked", f32_refused)
    with pytest.raises(RuntimeError, match="f32 refused"):
        svc._probe_bf16(64, "c2c")
