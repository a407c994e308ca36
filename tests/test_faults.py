"""Fault-injected worker runtime (DESIGN.md §12): seeded fault plans,
health tracking + deadline-derived masks, retry/degraded decode with typed
reasons, Byzantine verification in the service path, elastic membership,
and the measured thread-per-worker runtime."""

import numpy as np
import pytest

from repro.core import mds
from repro.core.coded_fft import CodedFFT
from repro.core.fault_tolerance import correct_errors, robust_decode
from repro.distributed import (
    ElasticWorkerPool,
    FaultInjector,
    FaultPlan,
    MeasuredWorkerRuntime,
    StragglerModel,
    WorkerHealthTracker,
)
from repro.distributed.worker_runtime import Clock
from repro.serving import (
    FAILURE_REASONS,
    DegradedResult,
    FFTService,
    FFTServiceConfig,
    ServiceError,
)

import jax.numpy as jnp


def _cfg(**kw):
    kw.setdefault("s", 256)
    kw.setdefault("m", 4)
    kw.setdefault("n_workers", 8)
    kw.setdefault("seed", 0)
    kw.setdefault("autotune", False)
    return FFTServiceConfig(**kw)


def _x(s=256, seed=0, dtype=np.complex64):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=s) + 1j * rng.normal(size=s)).astype(dtype)


# A near-deterministic straggler model: every worker completes in ~t0 *
# workload, so deadline-derived masks admit the whole fleet and k > m
# surplus (the Byzantine verifier's precondition) holds by construction.
_TIGHT = StragglerModel(t0=1.0, mu=1e6)


# ------------------------------------------------------------- fault plans
def test_fault_plan_builders_and_projection():
    plan = (FaultPlan(seed=5)
            .kill(0, start_round=2, rounds=3)
            .delay(3, 0.25, rounds=2)
            .corrupt(1, start_round=1, rounds=10))
    r0 = plan.faults_for(0)
    assert r0.killed == frozenset() and dict(r0.delays) == {3: 0.25}
    r2 = plan.faults_for(2)
    assert r2.killed == {0} and r2.corrupt == {1} and not r2.delays
    assert plan.faults_for(99).any is False
    assert plan.horizon == 11
    # immutability: builders return NEW plans
    assert len(FaultPlan().faults) == 0


def test_fault_plan_random_is_seeded_and_rate_scaled():
    a = FaultPlan.random(8, 1 / 8, horizon=64, seed=3)
    b = FaultPlan.random(8, 1 / 8, horizon=64, seed=3)
    assert a == b                               # bit-identical schedules
    assert FaultPlan.random(8, 0.0, seed=1).faults == ()
    dense = FaultPlan.random(8, 1.0, horizon=4, kinds=("kill",), seed=0)
    assert len(dense.faults) == 32              # every (round, worker) hit
    # rate=1/N means ~one faulty worker per round on average
    avg = len(a.faults) / 64
    assert 0.3 <= avg <= 2.5


def test_injector_corruption_is_seeded_and_axis_aware():
    inj = FaultInjector(FaultPlan(seed=9).corrupt(2))
    b = (np.arange(2 * 8 * 4) + 1j).reshape(2, 8, 4).astype(np.complex128)
    c1 = inj.corrupt_array(b, [2], 0, worker_axis=1)
    c2 = inj.corrupt_array(b, [2], 0, worker_axis=1)
    np.testing.assert_array_equal(c1, c2)       # keyed by (seed, round, w)
    c3 = inj.corrupt_array(b, [2], 1, worker_axis=1)
    assert not np.array_equal(c1[:, 2], c3[:, 2])   # distinct per round
    # only the targeted worker row changes, and changes BIG (Byzantine,
    # not noise)
    clean = np.delete(c1, 2, axis=1)
    np.testing.assert_array_equal(clean, np.delete(b, 2, axis=1))
    assert np.abs(c1[:, 2] - b[:, 2]).max() > np.abs(b).max()
    # the caller's buffer is never corrupted in place
    assert b[0, 2, 0] == np.arange(2 * 8 * 4).reshape(2, 8, 4)[0, 2, 0] + 1j


def test_injector_latency_perturbation():
    inj = FaultInjector(FaultPlan().kill(1).delay(4, 0.5))
    lat = np.full((3, 8), 1.0)
    out = inj.perturb_latencies(lat, 0)
    assert np.isinf(out[:, 1]).all()
    np.testing.assert_allclose(out[:, 4], 1.5)
    np.testing.assert_allclose(out[:, 0], 1.0)
    # no active faults -> identity (same object allowed)
    np.testing.assert_array_equal(inj.perturb_latencies(lat, 50), lat)


# ------------------------------------------------------- health + deadlines
def test_health_tracker_deadline_and_dead_worker_estimates():
    h = WorkerHealthTracker(4, slack_frac=0.5)
    h.observe_round([0.1, 0.2, 0.3, np.inf])
    h.observe_round([0.1, 0.2, 0.3, np.inf])
    est = h.estimates()
    np.testing.assert_allclose(est[:3], [0.1, 0.2, 0.3])
    # a slot that has only ever missed must NOT keep the fast prior: it
    # would drag the deadline below what live workers can meet
    assert np.isinf(est[3])
    assert h.deadline(2) == pytest.approx(0.2 * 1.5)
    assert h.deadline(4) == np.inf              # 4th fastest is the dead one
    assert np.isinf(h.deadline(2, alive=np.array([True, False, False, False])))
    mask = h.mask_from_times(np.array([0.1, 0.4, np.inf, np.nan]), 0.31)
    np.testing.assert_array_equal(mask, [True, False, False, False])


def test_health_tracker_calibration_recovers_straggler_model():
    true = StragglerModel(t0=0.8, mu=2.5)
    rng = np.random.default_rng(0)
    h = WorkerHealthTracker(8)
    w = 0.25
    for _ in range(400):
        h.observe_round(true.sample(8, w, rng))
    fit = h.calibrate(workload=w)
    assert fit.t0 == pytest.approx(true.t0, rel=0.05)
    assert fit.mu == pytest.approx(true.mu, rel=0.2)
    with pytest.raises(ValueError):
        WorkerHealthTracker(2).calibrate()


def test_health_tracker_byzantine_flags_and_grow():
    h = WorkerHealthTracker(4)
    h.observe_round([0.1, 0.2, 0.3, 0.4])
    h.flag_byzantine(2)
    assert h.byzantine.tolist() == [False, False, True, False]
    h.grow(6)
    assert h.n_workers == 6 and h.byzantine.shape == (6,)
    np.testing.assert_allclose(h.estimates()[:4], [0.1, 0.2, 0.3, 0.4])
    h.clear_byzantine(2)
    assert not h.byzantine.any()


# ---------------------------------------------------- robust decode satellite
def test_correct_errors_returns_indices_single_prony_pass():
    plan = CodedFFT(s=64, m=4, n_workers=8, dtype=np.complex128,
                    backend="reference")
    x = _x(64, 3, np.complex128)
    b = np.asarray(plan.worker_compute(plan.encode(jnp.asarray(x))),
                   np.complex128)
    nodes = np.asarray(mds.rs_nodes(8, jnp.complex128))
    bad = b.copy()
    bad[5] += 11.0 - 3j
    out = correct_errors(nodes, bad, 4)
    assert out is not None
    corrected, idx = out
    assert idx.tolist() == [5]
    np.testing.assert_allclose(corrected, b, atol=1e-8)
    # clean rows: empty index vector, rows returned as-is
    _, idx0 = correct_errors(nodes, b, 4)
    assert idx0.shape == (0,)


def test_robust_decode_nd_shards_and_bit_consistency():
    """robust_decode accepts (N, *shard) rows and its corrected output is
    BIT-IDENTICAL to the clean decode over the same clean subset (the
    corrupted rows never enter the final decode)."""
    plan = CodedFFT(s=64, m=4, n_workers=8, dtype=np.complex128,
                    backend="reference")
    x = _x(64, 4, np.complex128)
    b = np.asarray(plan.worker_compute(plan.encode(jnp.asarray(x))),
                   np.complex128)
    inj = FaultInjector(FaultPlan(seed=1).corrupt(1).corrupt(6))
    bad = inj.corrupt_array(b[None], [1, 6], 0, worker_axis=1)[0]
    recv = np.arange(8)                         # k=8: correct up to 2
    res = robust_decode(plan, bad, recv)
    assert res.ok and res.n_errors_corrected == 2
    assert sorted(res.error_worker_indices.tolist()) == [1, 6]
    clean_subset = jnp.asarray([0, 2, 3, 4])    # first m clean rows
    want = np.asarray(plan.decode(jnp.asarray(b), subset=clean_subset))
    np.testing.assert_array_equal(res.output, want)   # bitwise
    # 3 corrupt > floor((8-4)/2): uncorrectable, typed not-ok
    bad3 = inj.corrupt_array(b[None], [1, 3, 6], 0, worker_axis=1)[0]
    bad3[3] += 17.0
    assert not robust_decode(plan, bad3, recv).ok


# ------------------------------------------------------- service fault path
def test_service_deadline_masks_serve_correctly_without_faults():
    svc = FFTService(_cfg(health=True))
    x = _x()
    for seed in range(4):
        xi = _x(seed=seed)
        y = svc.submit(xi)
        assert np.abs(y - np.fft.fft(xi)).max() < 1e-2
    assert svc.stats.requests == 4 and svc.stats.degraded == 0
    assert svc.health.rounds == 4
    # measured-timings calibration is reachable from the service tracker
    fit = svc.health.calibrate(workload=1 / 4)
    assert fit.t0 > 0 and fit.mu > 0


def test_service_kill_faults_recover_with_retry_and_redispatch():
    plan = FaultPlan().kill(0, rounds=999).kill(1, rounds=999)
    svc = FFTService(_cfg(faults=plan, on_failure="degrade"))
    for seed in range(10):
        xi = _x(seed=seed)
        y = svc.submit(xi)
        assert isinstance(y, np.ndarray)
        assert np.abs(y - np.fft.fft(xi)).max() < 1e-2
    assert svc.stats.degraded == 0
    s = svc.stats.summary()
    assert s["retries"] >= 0 and s["redispatched_shards"] >= 0


def test_service_insufficient_workers_typed_error_and_degrade():
    pool = ElasticWorkerPool(8, 4)
    for w in range(5):
        pool.leave(w)
    svc = FFTService(_cfg(on_failure="degrade"), pool=pool)
    r = svc.submit(_x())
    assert isinstance(r, DegradedResult)
    assert r.reason == "insufficient_workers" and not r.ok
    assert svc.stats.degraded == 1
    # on_failure="raise" surfaces the same reason as an exception
    svc2 = FFTService(_cfg(), pool=pool)
    with pytest.raises(ServiceError) as ei:
        svc2.submit(_x())
    assert ei.value.reason == "insufficient_workers"
    assert ei.value.reason in FAILURE_REASONS


def test_service_retries_exhausted_typed_error():
    plan = FaultPlan()
    for w in range(5):
        plan = plan.kill(w, rounds=999)
    svc = FFTService(_cfg(faults=plan, max_retries=0, on_failure="degrade"))
    r = svc.submit(_x())
    assert isinstance(r, DegradedResult) and r.reason == "retries_exhausted"


def test_service_verify_detect_catches_corruption():
    plan = FaultPlan(seed=2).corrupt(3, rounds=999)
    svc = FFTService(_cfg(straggler=_TIGHT, faults=plan, verify="detect",
                          on_failure="degrade"))
    r = svc.submit(_x())
    assert isinstance(r, DegradedResult)
    assert r.reason == "corrupt_uncorrectable"
    assert svc.stats.detected >= 1 and svc.stats.corrected == 0


def test_service_verify_off_corruption_poisons_output():
    """The negative control: without verification a Byzantine worker's
    rows reach the decode and the output is visibly wrong."""
    plan = FaultPlan(seed=2).corrupt(0, rounds=999)   # worker 0: always in
    #                                                   the first-m subset
    svc = FFTService(_cfg(straggler=_TIGHT, faults=plan, verify="off",
                          on_failure="degrade", dtype=np.complex128,
                          use_reference=True))
    x = _x(dtype=np.complex128)
    y = svc.submit(x)
    assert np.abs(y - np.fft.fft(x)).max() > 1.0


def test_service_verify_correct_bit_consistent_at_capacity():
    """verify="correct" recovers the transform with floor((k - m)/2) = 2
    corrupt workers out of k = 8 responders, over ADVERSARIAL patterns:
    the corrupt pair rotates every round.  (Bit-consistency with the
    same-subset clean decode is asserted at the robust_decode level.)"""
    plan = FaultPlan(seed=4)
    pairs = [(0, 1), (2, 5), (6, 7), (3, 4)]
    for r, (a, b) in enumerate(pairs):
        plan = plan.corrupt(a, start_round=r).corrupt(b, start_round=r)
    svc = FFTService(_cfg(straggler=_TIGHT, faults=plan, verify="correct",
                          dtype=np.complex128, use_reference=True))
    for r in range(len(pairs)):
        x = _x(seed=10 + r, dtype=np.complex128)
        y = svc.submit(x)
        np.testing.assert_allclose(y, np.fft.fft(x), atol=1e-8)
    assert svc.stats.corrected == 2 * len(pairs)
    assert svc.stats.detected == svc.stats.corrected
    assert svc.stats.degraded == 0
    # offenders are flagged into the health tracker
    assert set(svc.health.summary()["byzantine"]) == {0, 1, 2, 3, 4, 5, 6, 7}


def test_service_verify_correct_overwhelmed_fails_typed():
    plan = FaultPlan(seed=6)
    for w in (1, 4, 7):                          # 3 > floor((8-4)/2)
        plan = plan.corrupt(w, rounds=999)
    svc = FFTService(_cfg(straggler=_TIGHT, faults=plan, verify="correct",
                          on_failure="degrade", dtype=np.complex128,
                          use_reference=True))
    r = svc.submit(_x(dtype=np.complex128))
    assert isinstance(r, DegradedResult)
    assert r.reason == "corrupt_uncorrectable"


# ----------------------------------------------------------- elastic pool
def test_elastic_pool_membership_invariants():
    pool = ElasticWorkerPool(8, m=4)
    assert pool.capacity == 8 and pool.n_live == 8 and pool.can_decode()
    pool.leave(3)
    pool.leave(3)                                # idempotent
    assert pool.n_live == 7 and pool.version == 1
    assert not pool.is_live(3) and pool.capacity == 8
    # join refills the LOWEST departed slot: same RS node, same capacity
    pool.leave(1)
    assert pool.join() == 1
    assert pool.capacity == 8
    # no departed slot left after refilling 3: join GROWS the code
    assert pool.join() == 3
    assert pool.join() == 8 and pool.capacity == 9
    assert pool.summary()["n_live"] == 9
    with pytest.raises(ValueError):
        ElasticWorkerPool(3, m=4)
    with pytest.raises(IndexError):
        pool.leave(99)


def test_service_elastic_membership_live_changes():
    """Workers leave/join between rounds while m stays fixed: departures
    mask rows, slot refills reuse the cached plan, capacity growth keys a
    NEW plan (roots-of-unity codes are capacity-specific)."""
    pool = ElasticWorkerPool(8, m=4)
    svc = FFTService(_cfg(on_failure="degrade"), pool=pool)
    x = _x()
    assert np.abs(svc.submit(x) - np.fft.fft(x)).max() < 1e-2
    pool.leave(2)
    pool.leave(5)
    assert np.abs(svc.submit(x) - np.fft.fft(x)).max() < 1e-2
    n_plans = len(svc._plans)
    pool.join()                                  # refill slot 2: cache hit
    assert len(svc._plans) == n_plans
    assert np.abs(svc.submit(x) - np.fft.fft(x)).max() < 1e-2
    pool.join()                                  # refill slot 5
    grown = pool.join()                          # growth: capacity 9
    assert grown == 8 and svc._n_workers() == 9
    assert np.abs(svc.submit(x) - np.fft.fft(x)).max() < 1e-2
    assert len(svc._plans) > n_plans             # new capacity, new code
    assert svc.health.n_workers == 9             # tracker grew with it


# ------------------------------------------------------- measured runtime
# The measured tests keep the runtime's own seconds on a clock slowed
# 1000x: its 2 ms window floor lasts 2 s on the host, so which rows arrive
# inside a window does not hang on how busy the test host is.
_SLOW = Clock(slowdown=1000.0)


def _slow_measured(svc, s=64):
    """The service's measured runtime for length ``s``, on the slow clock."""
    svc._measured_for(s).clock = _SLOW
    return svc


def test_measured_runtime_round_completes_and_decodes():
    plan = CodedFFT(s=64, m=4, n_workers=8, dtype=np.complex128,
                    backend="reference")
    h = WorkerHealthTracker(8)
    x = np.stack([_x(64, s, np.complex128) for s in range(3)])
    with MeasuredWorkerRuntime(plan, h, clock=_SLOW) as rt:
        res = rt.round(x, 0)
    assert res.ok and res.mask.sum() >= 4
    assert np.isfinite(res.t_met) and res.t_met <= res.t_last
    for i in range(3):
        y = np.asarray(plan.decode(jnp.asarray(res.b[i]),
                                   mask=jnp.asarray(res.mask)))
        np.testing.assert_allclose(y, np.fft.fft(x[i]), atol=1e-8)
    assert h.rounds == 1                          # deadlines learn from it


def test_measured_runtime_kill_faults_and_insufficient():
    plan = CodedFFT(s=64, m=4, n_workers=8, dtype=np.complex128,
                    backend="reference")
    h = WorkerHealthTracker(8)
    inj = FaultInjector(FaultPlan().kill(0, rounds=999).kill(7, rounds=999))
    x = _x(64, 1, np.complex128)[None]
    with MeasuredWorkerRuntime(plan, h, injector=inj, clock=_SLOW) as rt:
        warm = rt.round(x, 0)                    # learn live-worker times
        assert warm.ok and not warm.mask[0]
        res = rt.round(x, 1)
        assert res.ok
        y = np.asarray(plan.decode(jnp.asarray(res.b[0]),
                                   mask=jnp.asarray(res.mask)))
        np.testing.assert_allclose(y, np.fft.fft(x[0]), atol=1e-8)
        # fewer than m live workers: typed failure, not a hang
        alive = np.zeros(8, bool)
        alive[:3] = True
        bad = rt.round(x, 2, alive=alive)
        assert not bad.ok and bad.reason == "insufficient_workers"


def test_measured_service_corrects_byzantine_workers():
    """End-to-end measured path: worker THREADS inject the corruption and
    verify="correct" still recovers the exact transform (quorum k = m + 4
    corrects 2 liars)."""
    plan = FaultPlan(seed=8).corrupt(2, rounds=999).corrupt(5, rounds=999)
    svc = _slow_measured(FFTService(_cfg(
        s=64, measured=True, faults=plan, verify="correct", verify_quorum=4,
        dtype=np.complex128, use_reference=True)))
    x = _x(64, 2, np.complex128)
    y = svc.submit(x)
    np.testing.assert_allclose(y, np.fft.fft(x), atol=1e-8)
    assert svc.stats.corrected >= 2
    assert set(svc.health.summary()["byzantine"]) == {2, 5}


def test_measured_uncoded_baseline_requires_every_worker():
    """require_all=True is the uncoded baseline: one killed worker forces
    the full retry ladder (an uncoded partition has no slack)."""
    plan = FaultPlan().kill(3, rounds=999)
    svc = _slow_measured(FFTService(_cfg(
        s=64, measured=True, require_all=True, faults=plan, max_retries=0,
        on_failure="degrade", dtype=np.complex128, use_reference=True)))
    r = svc.submit(_x(64, 0, np.complex128))
    assert isinstance(r, DegradedResult) and r.reason == "retries_exhausted"
    # the coded service under the SAME fault plan just ... works
    svc2 = _slow_measured(FFTService(_cfg(
        s=64, measured=True, faults=plan, dtype=np.complex128,
        use_reference=True)))
    x = _x(64, 0, np.complex128)
    np.testing.assert_allclose(svc2.submit(x), np.fft.fft(x), atol=1e-8)
    assert svc2.stats.degraded == 0
