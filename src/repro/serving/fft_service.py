"""The paper's own end-to-end application: a straggler-tolerant FFT service.

Clients submit transform requests; the service executes them under a coded
computation plan and answers as soon as the fastest ``m`` of ``N`` workers
respond.  The straggler simulator assigns each worker a shifted-exponential
latency per request; the service's reported latency is the m-th order
statistic, and ``ServiceStats`` keeps beside it the latency of waiting for
all N (uncoded).

The scheduler is batched (DESIGN.md §5): submitted requests are bucketed by
``(s, m, kind)`` with ``kind in {c2c, r2c, c2r, rfftn, irfftn}`` (forward
complex, real forward, inverse real -- DESIGN.md §7 -- and the n-D real
pair -- §9), stacked along a leading batch axis, padded to a power-of-two
bucket size, and pushed through ONE jitted encode -> worker -> decode call
per bucket with a per-request straggler mask -- master-side work (MDS
encode/decode, recombine) amortizes across the whole bucket instead of
being paid per request.  ``submit`` is the batch-of-one special case;
``submit_rfft`` / ``submit_irfft`` / ``submit_rfftn`` / ``submit_irfftn``
are the real-kind conveniences.  Real buckets (1-D and n-D) ship HALF the
worker payload (pair-packed shards) and all kinds share one decode-matrix
LRU (the (N, m) generator is length- and kind-independent).  n-D kinds
bucket by the full time-domain shape tuple and run the generic jitted
``plan.run`` executor.

The default bucket executor is the Pallas kernel pipeline (DESIGN.md §6):
requests are split to f32 real/imag planes ONCE at ingress, run through
one body -- the one-launch masked bucket kernel on the TPU where the shape
fits it, the stage kernels where it does not, the direct executor off the
TPU -- and joined to complex ONCE at egress.
``use_reference=True`` is the escape hatch back to the jnp-oracle
``plan.run`` executor (as is any config the kernel path does not cover:
a mesh, an explicit ``worker_fn`` plug-in, a pinned ``decode_method``, or
a non-complex64 dtype).

The submit-to-result path is DEVICE-RESIDENT and ASYNCHRONOUS
(DESIGN.md §8).  Decode matrices are built inside the jitted bucket
executor from each request's straggler mask via the closed-form Lagrange
inversion (``mds.lagrange_inverse``) -- no host ``linalg.inv``, no LRU
side channel, a novel mask costs exactly what a repeated one does.  The
host-side :class:`~repro.serving.decode_cache.DecodeMatrixCache` remains
only as the fallback for ``m > mds.LAGRANGE_MAX_M``.  ``submit_batch``
DISPATCHES every (s, m, kind) bucket before any host sync -- ingress
buffers are donated to XLA (``donate_argnums``) -- then performs ONE
device->host transfer for the whole call.  ``ServiceStats`` splits
dispatch vs sync wall time and counts host transfers so the async win is
observable.

With a mesh, worker compute runs under ``DistributedCodedPlan`` (shard_map,
batch axis threaded through the collectives); without one, it runs on the
local device with identical semantics.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
import warnings
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import mds
from repro.core.coded_fft import CodedFFT, plan_factors
from repro.core.fault_tolerance import detect_errors, robust_decode
from repro.core.rfft import CodedIRFFT, CodedRFFT
from repro.core.rfftn import CodedIRFFTN, CodedRFFTN
from repro.core.strategies import REGISTRY, make_strategy
from repro.distributed.coded_runtime import DistributedCodedPlan
from repro.distributed.elastic import ElasticWorkerPool
from repro.distributed.faults import FaultInjector, FaultPlan, RoundFaults
from repro.distributed.health import WorkerHealthTracker
from repro.distributed.straggler import StragglerModel
from repro.distributed.worker_runtime import MeasuredWorkerRuntime
from repro.kernels import autotune, ops, ref
from repro.kernels.words import interleave_body
from repro.serving.batching import LatencyHistogram, bucket_size
from repro.serving.decode_cache import DecodeMatrixCache
from repro.serving.spans import (FETCH_COPY, FETCH_WAIT, STAGE_H2D,
                                 STAGE_LAUNCH, STAGE_PACK, span)

__all__ = ["DegradedResult", "FAILURE_REASONS", "FFTService",
           "FFTServiceConfig", "ServiceError", "ServiceStats"]

# machine-readable per-request failure reasons (DESIGN.md §12)
FAILURE_REASONS = ("insufficient_workers", "retries_exhausted",
                   "corrupt_uncorrectable")


class ServiceError(RuntimeError):
    """Typed per-request failure from the fault-tolerant service path.

    ``reason`` is one of :data:`FAILURE_REASONS`:

    * ``insufficient_workers`` -- fewer than ``m`` live workers exist (or
      none are healthy enough to re-dispatch to), so the MDS threshold is
      unreachable no matter how long the master waits.
    * ``retries_exhausted`` -- ``m`` responses never arrived inside the
      capped retry windows (``max_retries`` x ``retry_backoff``).
    * ``corrupt_uncorrectable`` -- the Byzantine syndrome check failed and
      correction was impossible (``verify="detect"``, or more than
      ``floor((k - m)/2)`` corrupt responders under ``verify="correct"``).

    Surfaces as a raised exception from ``submit_batch``
    (``on_failure="raise"``), a :class:`DegradedResult` slot
    (``on_failure="degrade"``), and a per-request Future exception on the
    streaming path -- never as a dead scheduler thread.
    """

    def __init__(self, reason: str, detail: str = ""):
        if reason not in FAILURE_REASONS:
            raise ValueError(f"unknown failure reason {reason!r}")
        super().__init__(f"request failed: {reason}"
                         + (f" ({detail})" if detail else ""))
        self.reason = reason
        self.detail = detail


@dataclasses.dataclass(frozen=True)
class DegradedResult:
    """Graceful-degradation slot value (``on_failure="degrade"``).

    Takes the place of the transform result for a request the fault path
    could not serve; ``reason``/``detail`` mirror :class:`ServiceError`.
    """

    reason: str
    detail: str = ""

    @property
    def ok(self) -> bool:
        return False


class _Launched:
    """A launched bucket: its rows, on the device or the host, and the
    per-row errors of the fault path (``None`` elsewhere).

    ``words``: ``out`` is complex rows carried as real words
    (:func:`to_words`), so the fetched host array is viewed back.
    ``staging``: the host buffer the bucket was packed in, handed back to
    the service's free list once ``out`` is ready (DESIGN.md §11)."""

    __slots__ = ("out", "errors", "words", "staging")

    def __init__(self, out, errors=None, words=False):
        self.out = out          # device array or host ndarray (verify path)
        self.errors = errors    # per-bucket-row Optional[ServiceError]
        self.words = words
        self.staging = None

    def rows(self, host: np.ndarray) -> np.ndarray:
        """The bucket's host rows from the fetched ``out``."""
        return _host_complex(host) if self.words else host


class _StagedArgs(tuple):
    """A staged bucket's arguments for :meth:`FFTService.launch_bucket`,
    carrying the host buffer they were packed in as ``staging``."""

    def __new__(cls, args, staging: np.ndarray):
        self = super().__new__(cls, args)
        self.staging = staging
        return self


# the three bodies of a kernel-path bucket, by kind (FFTService._bucket_body)
_BUCKET_BODIES = {
    "c2c": {"direct": ops.coded_bucket_direct,
            "whole": ops.coded_bucket_masked,
            "staged": ops.coded_bucket_staged},
    "r2c": {"direct": ops.coded_rbucket_direct,
            "whole": ops.coded_rbucket_masked,
            "staged": ops.coded_rbucket_staged},
    "c2r": {"direct": ops.coded_irbucket_direct,
            "whole": ops.coded_irbucket_masked,
            "staged": ops.coded_irbucket_staged},
}

# staging buffers kept per (shape, dtype) for reuse: the streaming pipeline
# holds at most three buckets between pack and fetch (DESIGN.md §11)
STAGING_KEEP = 3


# -- the host link (DESIGN.md §8) ----------------------------------------
# No complex array crosses between host and device as complex: on a TPU
# v5e a complex64 copy runs ~10x slower than float32 of the same bytes
# (PERF.md §5).  It crosses as real words, (re, im) interleaved as numpy
# lays complex out, so both host sides are views; the two conversions run
# on the device, each its own jitted call around the unchanged runners.
@functools.partial(jax.jit, static_argnames="on_mesh")
def to_words(z: jax.Array, on_mesh: bool = False) -> jax.Array:
    """Egress, on the device: ``complex[..., k]`` -> ``real[..., 2k]``.
    ``on_mesh``: ``z`` spans a mesh, where XLA cannot partition the
    interleave kernel, so the plain XLA interleave runs there."""
    if on_mesh:
        return interleave_body(z.real, z.imag)
    return ops.interleave_words(z.real, z.imag)


@jax.jit
def from_words(w: jax.Array) -> jax.Array:
    """Ingress, on the device: ``real[..., 2k]`` -> ``complex[..., k]``.
    Two strided slices (``w[..., 0::2]`` would lower to a gather)."""
    n = w.ndim
    step = (1,) * (n - 1) + (2,)
    re = lax.slice(w, (0,) * n, w.shape, step)
    im = lax.slice(w, (0,) * (n - 1) + (1,), w.shape, step)
    return lax.complex(re, im)


def _host_words(x: np.ndarray) -> np.ndarray:
    """Ingress, on the host: a complex buffer's bytes as real words."""
    return x.view(np.finfo(x.dtype).dtype) if np.iscomplexobj(x) else x


def _host_complex(w: np.ndarray) -> np.ndarray:
    """Egress, on the host: fetched words viewed as complex rows."""
    return w.view(np.result_type(w.dtype, np.complex64))


@dataclasses.dataclass(frozen=True)
class FFTServiceConfig:
    s: int = 4096                 # default transform length
    m: int = 4                    # storage fraction 1/m
    n_workers: int = 8
    dtype: jnp.dtype = jnp.complex64
    straggler: StragglerModel = StragglerModel(t0=1.0, mu=1.0)
    seed: int = 0
    worker_fn: Optional[object] = None   # explicit worker plug-in (overrides
    #                                      the default kernel dispatch)
    use_reference: bool = False   # escape hatch: jnp-oracle hot path
    max_batch: int = 64           # scheduler bucket cap per (s, m)
    decode_method: str = "auto"   # MDS decode dispatch (DESIGN.md §4);
    #                               non-"auto" pins the reference executor
    decode_cache_size: int = 512  # LRU size of per-mask decode matrices
    #                               (the m > mds.LAGRANGE_MAX_M fallback;
    #                               past the C(N, k) mask-pattern
    #                               count for small fleets, so steady state
    #                               is all-hit)
    precision: str = "f32"        # kernel plane precision: "bf16" casts the
    #                               DFT/twiddle planes to bfloat16 (f32
    #                               accumulation); a per-(s, m, kind) probe
    #                               against the f32 twin auto-disables any
    #                               shape whose error exceeds ops.BF16_RTOL
    autotune: bool = True         # measure candidate tilings/variants at
    #                               warmup() and persist the winning table
    #                               to the backend-keyed JSON cache
    #                               (kernels/autotune.py); dispatch falls
    #                               back to the static heuristics when off
    autotune_reps: int = 3        # timing repetitions per candidate
    # -- fault-tolerant runtime (opt-in; DESIGN.md §12) -----------------
    faults: Optional[FaultPlan] = None  # seeded kill/delay/corrupt schedule;
    #                               None leaves every code path byte-identical
    #                               to the fault-free build
    health: bool = False          # track per-worker EWMAs and derive each
    #                               round's availability mask from a DEADLINE
    #                               (m-th-fastest estimate + slack) instead of
    #                               a straggler draw's k-th order statistic
    deadline_slack: float = 0.5   # deadline = (1 + slack) * m-th-fastest
    max_retries: int = 2          # re-dispatch rounds for missing shards
    retry_backoff: float = 2.0    # wait-window multiplier per retry
    verify: str = "off"           # Byzantine check on surplus responses when
    #                               k > m arrive: "off" | "detect" | "correct"
    #                               (paper Remark 3: detect k-m, correct
    #                               floor((k-m)/2))
    verify_quorum: int = 2        # measured path only: extra rows beyond m
    #                               the master waits for when verify is on
    #                               (k = m + q detects q, corrects q//2)
    on_failure: str = "raise"     # "raise" ServiceError from submit_batch, or
    #                               "degrade" to a DegradedResult slot
    measured: bool = False        # run buckets on the thread-per-worker
    #                               MeasuredWorkerRuntime (real wall-clock
    #                               deadlines/retries; c2c kinds only)
    require_all: bool = False     # measured path waits for ALL live workers
    #                               (the uncoded baseline for the fault bench)
    # -- computation strategy (DESIGN.md §13) ---------------------------
    strategy: str = "mds"         # registered strategy serving the c2c
    #                               buckets: "mds" (the paper's code),
    #                               "partial" (Wang 1804.09791: r fragments
    #                               per worker, decode from any m*r),
    #                               "comm_efficient" (Jeong 1805.09891:
    #                               1/q payload at threshold m*q), or
    #                               "repetition".  Non-"mds" strategies are
    #                               c2c-only and run the jnp executor.
    strategy_param: Optional[int] = None  # the strategy's own knob (r for
    #                               partial, q for comm_efficient); None
    #                               means the registry entry's default
    # -- a mesh's ingress (DESIGN.md §14) -------------------------------
    mesh_ingress: str = "split"   # how a bucket's arguments reach a mesh:
    #                               "split", each device's host link takes
    #                               its rows and the runner all-gathers them
    #                               over the interconnect; "first", the whole
    #                               bucket lands on the first device, which
    #                               copies it to the others before the runner


@dataclasses.dataclass
class ServiceStats:
    requests: int = 0
    batches: int = 0               # jitted scheduler invocations
    coded_latency: float = 0.0     # sum of m-th order statistics
    uncoded_latency: float = 0.0   # sum of "wait for everyone" latencies
    stragglers_tolerated: int = 0
    decode_cache_hits: int = 0     # decode-matrix LRU hits (fallback path)
    decode_cache_misses: int = 0   # ... and misses (host inversions paid);
    #                                both stay 0 on the device-decode path
    dispatch_s: float = 0.0        # wall time staging + launching buckets
    sync_s: float = 0.0            # wall time blocked on device results
    host_transfers: int = 0        # device->host fetches (1 per submit_batch
    #                                call; 1 per bucket on the streaming path)
    h2d_bytes: int = 0             # bucket arguments copied host->device
    d2h_bytes: int = 0             # ... and results copied device->host
    #                                (complex crosses as real words, §8)
    staging_reused: int = 0        # buckets packed into a staging buffer
    #                                from the free list (DESIGN.md §11)
    staging_allocated: int = 0     # ... and into a newly allocated one
    # -- what a mesh moves between its devices, summed over launched
    #    buckets; DistributedCodedPlan.exchange_bytes reckons it from the
    #    plan's shapes.  Both stay 0 without a mesh --------------------
    broadcast_bytes: int = 0       # what the devices copy among themselves
    #                                to replicate the bucket's arguments:
    #                                (D-1) x their bytes, whether one device
    #                                holds them or each a slice
    gather_bytes: int = 0          # bytes EACH device receives in the
    #                                all-gather: (D-1)/D x N x bucket x payload
    # -- open-loop streaming observables (serving/streaming.py, §11) ----
    queue_peak: int = 0            # high-water mark of undispatched requests
    rejected: int = 0              # admission-control rejections (both
    #                                "queue_full" and "closed" reasons)
    cancelled: int = 0             # futures the caller cancelled before
    #                                resolution (the bucket still computed)
    fill_dispatches: int = 0       # buckets dispatched because they filled
    deadline_dispatches: int = 0   # ... because the earliest deadline
    #                                across bucket heads expired (EDF)
    drain_dispatches: int = 0      # ... flushed by drain()/close()
    # -- fault-tolerant runtime observables (§12) -----------------------
    retries: int = 0               # retry rounds performed (window extensions)
    redispatched_shards: int = 0   # shard computations re-dispatched to
    #                                healthy workers after a missed deadline
    degraded: int = 0              # requests that failed with a typed reason
    detected: int = 0              # corrupt workers caught by the syndrome
    #                                check (verify="detect"/"correct")
    corrected: int = 0             # ... of those, corrected (verify="correct")
    latency: LatencyHistogram = dataclasses.field(
        default_factory=LatencyHistogram)  # per-request arrival->result
    tier_latency: dict = dataclasses.field(default_factory=dict)
    #                              # per-SLO-tier LatencyHistogram, keyed by
    #                                tier name (streaming front-end only)

    def summary(self) -> dict:
        n = max(self.requests, 1)
        return {
            "requests": self.requests,
            "batches": self.batches,
            "mean_coded_latency": self.coded_latency / n,
            "mean_uncoded_latency": self.uncoded_latency / n,
            "speedup": (self.uncoded_latency / self.coded_latency
                        if self.coded_latency > 0 else float("nan")),
            "stragglers_tolerated": self.stragglers_tolerated,
            "decode_cache_hits": self.decode_cache_hits,
            "decode_cache_misses": self.decode_cache_misses,
            "dispatch_s": self.dispatch_s,
            "sync_s": self.sync_s,
            "host_transfers": self.host_transfers,
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "staging_reused": self.staging_reused,
            "staging_allocated": self.staging_allocated,
            "broadcast_bytes": self.broadcast_bytes,
            "gather_bytes": self.gather_bytes,
            "queue_peak": self.queue_peak,
            "rejected": self.rejected,
            "cancelled": self.cancelled,
            "fill_dispatches": self.fill_dispatches,
            "deadline_dispatches": self.deadline_dispatches,
            "drain_dispatches": self.drain_dispatches,
            "retries": self.retries,
            "redispatched_shards": self.redispatched_shards,
            "degraded": self.degraded,
            "detected": self.detected,
            "corrected": self.corrected,
            "latency": self.latency.summary(),
            "tiers": {name: hist.summary()
                      for name, hist in sorted(self.tier_latency.items())},
        }


class FFTService:
    """Batched straggler-tolerant FFT frontend over ``CodedPlan`` execution.

    Requests of any length with ``m | s`` are accepted; each distinct
    ``(s, m)`` gets its own cached plan, decode-matrix LRU, and jitted
    bucket executors.
    """

    KINDS = ("c2c", "r2c", "c2r", "rfftn", "irfftn")
    # half-payload kinds: workers ship pair-packed shards with a halved
    # (last) axis, so their wire time is charged at payload_scale=0.5
    REAL_KINDS = ("r2c", "c2r", "rfftn", "irfftn")
    # n-D kinds bucket by the full TIME-domain shape tuple instead of a
    # scalar length and run the generic jitted ``plan.run`` executor (the
    # fused planar bucket kernels are 1-D layouts)
    ND_KINDS = ("rfftn", "irfftn")

    def __init__(self, cfg: FFTServiceConfig, mesh: Optional[Mesh] = None,
                 axis: str = "workers",
                 pool: Optional[ElasticWorkerPool] = None):
        if cfg.verify not in ("off", "detect", "correct"):
            raise ValueError(
                f'verify must be "off"|"detect"|"correct", got {cfg.verify!r}')
        if cfg.on_failure not in ("raise", "degrade"):
            raise ValueError(
                f'on_failure must be "raise"|"degrade", got {cfg.on_failure!r}')
        if cfg.strategy not in REGISTRY:
            raise ValueError(
                f"unknown strategy {cfg.strategy!r}; "
                f"registered: {sorted(REGISTRY)}")
        if cfg.strategy != "mds":
            # the Byzantine verifier and the measured runtime speak the
            # (N, m) MDS row code; the worker plug-in contract is the MDS
            # c2c worker
            if cfg.verify != "off" or cfg.measured:
                raise ValueError(
                    f"strategy {cfg.strategy!r} does not compose with "
                    f"verify/measured (MDS-row machinery)")
            if cfg.worker_fn is not None:
                raise ValueError(
                    f"worker_fn plug-ins apply to the mds strategy only, "
                    f"got strategy {cfg.strategy!r}")
            if cfg.strategy == "repetition":
                # its replication decode is host-side block assembly, not
                # the jittable masked-subset protocol the bucket executors
                # speak
                raise ValueError(
                    "the repetition baseline is not subset-decodable; the "
                    "service serves subset-decodable strategies")
        if mesh is not None and not REGISTRY[cfg.strategy].mesh_ok:
            raise ValueError(
                f"strategy {cfg.strategy!r} does not compose with a mesh")
        if cfg.mesh_ingress not in ("split", "first"):
            raise ValueError(
                f'mesh_ingress must be "split"|"first", '
                f'got {cfg.mesh_ingress!r}')
        if pool is not None and pool.m != cfg.m:
            raise ValueError(
                f"pool threshold m={pool.m} must match cfg.m={cfg.m}")
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.pool = pool
        self.rng = np.random.default_rng(cfg.seed)
        self.stats = ServiceStats()
        # keyed by (s, m, kind, N); s is a scalar length for 1-D kinds and
        # the time-domain shape tuple for the n-D kinds.  N rides in the
        # key because an ElasticWorkerPool can GROW capacity live -- each
        # capacity is a distinct roots-of-unity code (DESIGN.md §12)
        self._plans: dict[tuple, object] = {}
        self._runtimes: dict[tuple, DistributedCodedPlan] = {}
        self._runners: dict[tuple, object] = {}
        # ONE decode-matrix LRU for the whole service: the (N, m) generator
        # -- hence every per-mask decode matrix -- is independent of both
        # the transform length s and the bucket kind, so c2c/r2c/c2r
        # buckets at every length share hits (DESIGN.md §7).  Keyed by N
        # (dict) only because elastic growth changes the generator.
        self._decode_caches: dict[int, DecodeMatrixCache] = {}
        # free staging buffers by (shape, dtype): taken by the stager,
        # handed back by the syncer once their bucket's result is ready
        self._staging_free: dict[tuple, list[np.ndarray]] = {}
        self._staging_lock = threading.Lock()
        # -- fault-tolerant runtime state (DESIGN.md §12) ---------------
        self._robust = (cfg.faults is not None or cfg.health
                        or cfg.verify != "off" or cfg.measured
                        or pool is not None)
        self.injector = (FaultInjector(cfg.faults)
                         if cfg.faults is not None else None)
        self.health = (WorkerHealthTracker(
            self._n_workers(), slack_frac=cfg.deadline_slack)
            if self._robust else None)
        self._measured: dict[tuple, MeasuredWorkerRuntime] = {}
        self._round = 0                # monotone fault/health round counter
        if self._robust and mesh is not None:
            raise ValueError("the fault-tolerant service path is host-"
                             "orchestrated; it does not compose with a mesh")
        # default-config plan/runtime, kept as attributes for introspection
        # (and reused by the executor cache for default-length requests)
        self.plan = self._plan_for(cfg.s)
        self.runtime = self._runtime_for(cfg.s) if mesh is not None else None

    def _n_workers(self) -> int:
        """Current code size N: pool capacity when elastic, else static."""
        return self.pool.capacity if self.pool is not None else self.cfg.n_workers

    # -- plan / compiled-executor caches --------------------------------
    def _plan_for(self, s, kind: str = "c2c"):
        """The plan serving ``(s, m, kind)`` buckets (DESIGN.md §7/§9).

        ``kind``: ``c2c`` forward complex, ``r2c`` real forward, ``c2r``
        inverse real, ``rfftn``/``irfftn`` the n-D real pair.  ``s`` is
        always the TIME-domain extent: a scalar length for the 1-D kinds,
        the full shape tuple for the n-D kinds (whose interleave factors
        come from :func:`repro.core.coded_fft.plan_factors`).
        """
        if kind not in self.KINDS:
            raise ValueError(f"unknown bucket kind {kind!r}")
        cfg = self.cfg
        n = self._n_workers()
        key = (s, cfg.m, kind, n)
        if key not in self._plans:
            if cfg.strategy != "mds":
                if kind != "c2c":
                    # the real/n-D pipelines (pair packing, Hermitian
                    # recombine) are built on the (N, m) MDS row code
                    raise ValueError(
                        f"strategy {cfg.strategy!r} serves c2c buckets "
                        f"only; got a {kind!r} request")
                ent = REGISTRY[cfg.strategy]
                if not ent.applicable(s, cfg.m, n, cfg.strategy_param):
                    raise ValueError(
                        f"strategy {cfg.strategy!r} is not applicable at "
                        f"(s={s}, m={cfg.m}, N={n}, "
                        f"param={cfg.strategy_param})")
                # always the jnp executor: the fused planar bucket kernels
                # are (N, m) MDS layouts (StrategyEntry.kernel_ok)
                self._plans[key] = make_strategy(
                    cfg.strategy, s, cfg.m, n, dtype=cfg.dtype,
                    backend="reference", param=cfg.strategy_param)
                return self._plans[key]
            if cfg.worker_fn is not None and kind != "c2c":
                # the plug-in contract is the c2c worker (fft along the
                # last axis); silently serving real-kind traffic without
                # it would un-instrument fault-injection setups
                raise ValueError(
                    f"worker_fn plug-ins only apply to c2c buckets; "
                    f"got a {kind!r} request on a worker_fn service")
            backend = "reference" if cfg.use_reference else "kernel"
            if kind in self.ND_KINDS:
                shape = tuple(int(d) for d in s)
                # even_last_shard biases the factor placement so any
                # shape with a valid real-kind factorization is served
                # (a kind-agnostic greedy split can land a factor on the
                # last axis and leave an odd shard spuriously)
                factors = plan_factors(shape, cfg.m, even_last_shard=True)
                cls = CodedRFFTN if kind == "rfftn" else CodedIRFFTN
                self._plans[key] = cls(
                    shape=shape, factors=factors, n_workers=n,
                    dtype=cfg.dtype, backend=backend)
                return self._plans[key]
            common = dict(s=s, m=cfg.m, n_workers=n,
                          dtype=cfg.dtype, backend=backend)
            if kind == "r2c":
                self._plans[key] = CodedRFFT(**common)
            elif kind == "c2r":
                self._plans[key] = CodedIRFFT(**common)
            else:
                kwargs = {}
                if cfg.worker_fn is not None:
                    kwargs["worker_fn"] = cfg.worker_fn
                self._plans[key] = CodedFFT(**common, **kwargs)
        return self._plans[key]

    def _runtime_for(self, s: int, kind: str = "c2c") -> DistributedCodedPlan:
        key = (s, self.cfg.m, kind, self._n_workers())
        if key not in self._runtimes:
            self._runtimes[key] = DistributedCodedPlan(
                self._plan_for(s, kind), self.mesh, self.axis)
        return self._runtimes[key]

    def _decode_cache_for(self) -> DecodeMatrixCache:
        n = self._n_workers()
        if n not in self._decode_caches:
            self._decode_caches[n] = DecodeMatrixCache(
                np.asarray(self._plan_for(self.cfg.s).generator),
                maxsize=self.cfg.decode_cache_size)
        return self._decode_caches[n]

    def _kernel_path(self, s, kind: str = "c2c") -> bool:
        """Does this bucket run the fused planar kernel executor?

        The kernel path owns the default local config; anything it does not
        cover -- a mesh (the distributed runtime executes instead), an
        explicit ``worker_fn`` plug-in, a pinned ``decode_method``, a
        reference request, a non-c64 dtype, or an n-D kind (the planar
        bucket executors are 1-D layouts; rfftn/irfftn run the generic
        jitted ``plan.run``, whose encode/worker stages still dispatch to
        the Pallas kernels) -- falls back to ``plan.run``.
        """
        cfg = self.cfg
        return (kind not in self.ND_KINDS
                and cfg.strategy == "mds"
                and self.mesh is None
                and not cfg.use_reference
                and cfg.worker_fn is None
                and cfg.decode_method == "auto"
                and self._plan_for(s, kind).resolved_backend == "kernel")

    def _decode_in_jit(self) -> bool:
        """The kernel path's decode source: are the decode matrices built
        inside the jitted executor from the raw masks?

        Yes for ``m <= mds.LAGRANGE_MAX_M`` (the closed-form Lagrange
        inversion, DESIGN.md §8); past that the f32 planes cannot carry
        adversarial-subset conditioning and the host complex128 LRU
        supplies them (:meth:`_bucket_args`).
        """
        return self.cfg.m <= mds.LAGRANGE_MAX_M

    def _bucket_body(self, s: int, kind: str) -> str:
        """The kernel path's body for one bucket (DESIGN.md §6): ``direct``
        off the TPU; ``whole``, the one-launch masked kernel, where the
        shape passes its gate and the decode is done in the jit;
        ``staged``, the stage kernels, otherwise."""
        if ops.default_interpret():
            return "direct"
        m, n = self.cfg.m, self._n_workers()
        fits = {"c2c": (ops.coded_bucket_fusable(s, m, n)
                        or ops.coded_bucket_streamable(s, m, n)),
                "r2c": ops.coded_rbucket_fusable(s, m, n),
                "c2r": ops.coded_irbucket_fusable(s, m, n)}[kind]
        return "whole" if fits and self._decode_in_jit() else "staged"

    def _precision_for(self, s, kind: str) -> str:
        """Resolved kernel plane precision for one bucket family.

        ``cfg.precision="bf16"`` is a REQUEST, not a guarantee: the first
        bucket of each (s, m, kind) probes the bf16 pipeline against its
        f32 twin and auto-disables the shape (verdict recorded in the
        autotune table, so it persists with the tiling entries) whenever
        the relative error exceeds ``ops.BF16_RTOL`` -- the same budget
        the property suite enforces.
        """
        cfg = self.cfg
        if cfg.precision != "bf16" or kind in self.ND_KINDS or \
                not isinstance(s, int):
            return "f32"
        mode = ops._mode(None)
        ent = autotune.lookup("bf16", s=s, m=cfg.m, k=kind, mode=mode)
        if ent is None:
            ent = autotune.record(
                "bf16", {"ok": bool(self._probe_bf16(s, kind))},
                s=s, m=cfg.m, k=kind, mode=mode)
        return "bf16" if ent.get("ok") else "f32"

    def _probe_bf16(self, s: int, kind: str) -> bool:
        """Does the bf16-plane pipeline stay inside the f32 error budget
        at this (s, m, kind)?  Compares one small bucket against the f32
        run of the SAME masked executor (full-responder masks)."""
        plan = self._plan_for(s, kind)
        m, n = plan.m, plan.n_workers
        gr, gi = ref.planar(plan.generator)
        rng = np.random.default_rng(0)
        q = 2
        masks = jnp.asarray(np.ones((q, n), bool))
        f32 = np.float32
        if kind == "r2c":
            xb = jnp.asarray(rng.standard_normal((q, s)).astype(f32))
            run = lambda p: ops.coded_rbucket_masked(
                xb, masks, gr, gi, s, precision=p)
        elif kind == "c2r":
            yr = jnp.asarray(rng.standard_normal((q, s // 2 + 1)).astype(f32))
            yi = jnp.asarray(rng.standard_normal((q, s // 2 + 1)).astype(f32))
            run = lambda p: ops.coded_irbucket_masked(
                yr, yi, masks, gr, gi, s, precision=p)
        else:
            xr = jnp.asarray(rng.standard_normal((q, s)).astype(f32))
            xi = jnp.asarray(rng.standard_normal((q, s)).astype(f32))
            run = lambda p: ops.coded_bucket_masked(
                xr, xi, masks, gr, gi, s, precision=p)
        want = run("f32")   # the production twin: a failure here is real
        try:
            got = run("bf16")
        except Exception as e:  # only the bf16 variant may be unsupported
            warnings.warn(f"bf16 planes unavailable at s={s} kind={kind}: "
                          f"{type(e).__name__}: {e}", RuntimeWarning)
            return False
        want = want if isinstance(want, tuple) else (want,)
        got = got if isinstance(got, tuple) else (got,)
        scale = max(float(jnp.max(jnp.abs(w))) for w in want) or 1.0
        err = max(float(jnp.max(jnp.abs(g - w)))
                  for g, w in zip(got, want)) / scale
        return err <= ops.BF16_RTOL

    def _runner_for(self, s, bucket: int, kind: str = "c2c"):
        """One jitted batched encode->worker->decode per (s, m, kind,
        bucket).  The executables persist for the service lifetime --
        :meth:`warmup` keys them once so steady state never compiles.
        n-D kinds always take the generic ``plan.run`` branch."""
        kernel = self._kernel_path(s, kind)
        prec = self._precision_for(s, kind) if kernel else "f32"
        key = (s, self.cfg.m, kind, bucket, kernel, prec, self._n_workers())
        if key not in self._runners:
            if kernel:
                self._runners[key] = self._make_kernel_runner(s, kind)
            else:
                plan = self._plan_for(s, kind)
                run = (self._runtime_for(s, kind).run if self.mesh is not None
                       else plan.run)
                # a partial-work strategy stages per-fragment (bucket, N, r)
                # masks
                arg = ("fragment_mask" if getattr(plan, "fragments", 1) > 1
                       else "mask")
                method = self.cfg.decode_method
                self._runners[key] = jax.jit(lambda xb, masks: run(
                    xb, method=method, **{arg: masks}))
        return self._runners[key]

    def _make_kernel_runner(self, s: int, kind: str):
        """The kernel path's bucket executor: ``(requests, *decode) ->
        result``, split into f32 planes once at ingress and joined once at
        egress.

        ``decode`` is what the body (:meth:`_bucket_body`) takes: the raw
        ``(bucket, N)`` masks for the one-launch masked kernel, else decode
        planes -- shipped from the host LRU (:meth:`_bucket_args`), or,
        when the decode is in the jit (:meth:`_decode_in_jit`), built here
        from the masks the runner is given instead.  The ingress buffer is
        donated: c2c output aliases it, and for the real kinds it frees
        early for the encode/worker temporaries.
        """
        plan = self._plan_for(s, kind)
        m, n = plan.m, plan.n_workers
        gr, gi = ref.planar(plan.generator)
        body = self._bucket_body(s, kind)
        run = _BUCKET_BODIES[kind][body]
        build = self._decode_in_jit() and body != "whole"
        kw = {"precision": self._precision_for(s, kind)} \
            if body == "whole" else {}

        def fn(x, *decode):
            # an r2c request is its own real plane
            planes = (x,) if kind == "r2c" else ref.planar(x)
            if build:       # the body's decode operands, from the masks
                subsets = ops.mask_subsets(decode[0], m)
                decode = ((*ops.lagrange_compact_planes(subsets, n), subsets)
                          if body == "direct"
                          else ops.lagrange_scatter_planes(subsets, n))
            y = run(*planes, *decode, gr, gi, s, **kw)
            return y if kind == "c2r" else ref.unplanar(*y)

        # the real kinds change shape, so their donated buffer cannot be
        # aliased and jax says so per executable: expected, filtered
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return jax.jit(fn, donate_argnums=0)

    # ------------------------------------------------------------------
    def _wire_scale(self, kind: str) -> float:
        """Per-shard wire payload relative to the c2c MDS shard.

        Real-kind shards (r2c/c2r, mds-only) ship HALF the c2c payload
        (pair packing, DESIGN.md §7); non-mds strategies charge their own
        ``payload_scale`` (1/q for comm_efficient, 1 for partial)."""
        base = 0.5 if kind in self.REAL_KINDS else 1.0
        return base * float(getattr(self.plan, "payload_scale", 1.0))

    def _simulate_arrivals(self, n_requests: int, kind: str = "c2c"
                           ) -> tuple[np.ndarray, np.ndarray]:
        """Per-request worker latencies + availability masks at decode time.

        One vectorized draw per bucket -- a per-request sampling loop costs
        more host time than the whole decode at service bucket sizes.
        Real-kind shards (r2c/c2r) ship HALF the c2c wire payload
        (DESIGN.md §7), so their wire-time share is charged at
        ``payload_scale=0.5``; the comm_efficient strategy's folded shards
        at 1/q.  The mds/comm_efficient mask admits the k-th-order-statistic
        responders (k = the plan's recovery threshold); the partial strategy
        returns a per-FRAGMENT mask ``(n, N, r)`` admitting fragments until
        the coverage condition (m*r finished fragments) is met.
        """
        cfg = self.cfg
        plan = self.plan
        k = int(getattr(plan, "recovery_threshold", cfg.m))
        lat = cfg.straggler.sample(
            (n_requests, cfg.n_workers), 1.0 / cfg.m, self.rng,
            payload_scale=self._wire_scale(kind))
        if int(getattr(plan, "fragments", 1)) > 1:
            # fragment f of worker w lands at lat * fractions[f]; admit
            # fragments until m*r (across all workers) have arrived
            ft = lat[:, :, None] * np.asarray(plan.fragment_fractions)
            need = int(plan.fragments_needed)
            t_done = np.sort(ft.reshape(n_requests, -1), -1)[:, need - 1]
            return lat, ft <= t_done[:, None, None]
        t_done = np.sort(lat, axis=-1)[:, k - 1]
        mask = lat <= t_done[:, None]
        return lat, mask

    def _account(self, lat: np.ndarray, mask: np.ndarray) -> None:
        cfg = self.cfg
        plan = self.plan
        lat_sorted = np.sort(lat, axis=-1)
        self.stats.requests += lat.shape[0]
        if mask.ndim == 3:
            # partial strategy: coded latency = fragment-coverage time;
            # a tolerated straggler = a worker whose LAST fragment the
            # master did not wait for
            ft = lat[:, :, None] * np.asarray(plan.fragment_fractions)
            need = int(plan.fragments_needed)
            t_cov = np.sort(ft.reshape(lat.shape[0], -1), -1)[:, need - 1]
            self.stats.coded_latency += float(t_cov.sum())
            self.stats.stragglers_tolerated += int((~mask[..., -1]).sum())
        else:
            k = int(getattr(plan, "recovery_threshold", cfg.m))
            self.stats.coded_latency += float(lat_sorted[:, k - 1].sum())
            self.stats.stragglers_tolerated += int((~mask).sum())
        self.stats.uncoded_latency += float(lat_sorted[:, -1].sum())

    # -- fault-tolerant bucket path (opt-in; DESIGN.md §12) --------------
    def _fault_arrivals(self, n_live: int, kind: str):
        """The deadline/retry state machine for one robust bucket.

        Ground truth is still a per-(request, worker) completion-time draw
        (plus injected kill=inf / delay=+d), but the MASK is no longer "the
        m fastest of the draw": the master only admits workers whose time
        beats the LEARNED deadline (m-th-fastest health estimate + slack).
        Requests below the threshold go through capped retry rounds --
        late originals count, missing shards are re-dispatched to healthy
        workers with fresh draws, the window backs off geometrically --
        and requests that still miss get a typed ServiceError.

        Returns ``(masks, errors, t_comp, lat, round_faults, round_idx)``.

        Strategy-generic (DESIGN.md §13): the worker-count threshold and
        wire payload come from the configured plan (``m`` for mds,
        ``m*q`` for comm_efficient), and the partial strategy swaps the
        per-worker masks for per-FRAGMENT masks ``(n_live, N, r)`` --
        the deadline gates each fragment separately
        (:meth:`WorkerHealthTracker.fragment_mask_from_times`), ``met``
        counts fragments against the m*r coverage condition, and a
        re-dispatched shard lands all r fragments at once.
        """
        cfg = self.cfg
        n = self._n_workers()
        plan = self.plan
        need = int(getattr(plan, "recovery_threshold", cfg.m))
        nf = int(getattr(plan, "fragments", 1))
        frac = (np.asarray(plan.fragment_fractions, np.float64)
                if nf > 1 else None)
        # fragments needed for decode; in worker units it is `need`
        need_units = int(getattr(plan, "fragments_needed", need))
        if self.health.n_workers < n:
            self.health.grow(n)       # elastic capacity growth keeps history
        round_idx = self._round
        self._round += 1
        rf = (self.injector.faults_for(round_idx)
              if self.injector is not None else RoundFaults())
        alive = (self.pool.mask() if self.pool is not None
                 else np.ones(n, bool))
        scale = self._wire_scale(kind)
        lat = cfg.straggler.sample((n_live, n), 1.0 / cfg.m, self.rng,
                                   payload_scale=scale)
        if self.injector is not None:
            lat = self.injector.perturb_latencies(lat, round_idx)
        lat = np.where(alive[None, :], lat, np.inf)
        errors: list = [None] * n_live
        mshape = (n_live, n) if nf == 1 else (n_live, n, nf)
        masks = np.zeros(mshape, bool)
        t_comp = np.full(n_live, np.inf)

        def units(mk):
            """Decodable-progress count for ONE request's mask."""
            return int(mk.sum())

        def admit(times, window):
            """Per-worker (or per-fragment) arrivals inside ``window``."""
            if nf > 1:
                return (self.health.fragment_mask_from_times(
                    times, window, frac) & alive[..., :, None])
            return self.health.mask_from_times(times, window) & alive

        def coverage_time(lat_rows):
            """Per-request completion: need-th worker (need_units-th
            fragment for partial) order statistic."""
            if nf > 1:
                ft = np.sort((lat_rows[:, :, None] * frac)
                             .reshape(lat_rows.shape[0], -1), axis=1)
                return ft[:, need_units - 1]
            return np.sort(lat_rows, axis=1)[:, need - 1]

        if int(alive.sum()) < need:
            err = ServiceError(
                "insufficient_workers",
                f"{int(alive.sum())} live workers < threshold {need}")
            errors = [err] * n_live
            self.stats.degraded += n_live
            masks[:] = True   # padding decode stays well-posed; never surfaced
            return masks, errors, t_comp, lat, rf, round_idx

        if self.health.rounds == 0:
            # cold start: no learned estimates yet -- bootstrap from this
            # round's own threshold-order statistics
            kth = coverage_time(lat)
            kth = kth[np.isfinite(kth)]
            deadline = (float(kth.max()) * (1.0 + cfg.deadline_slack)
                        if kth.size else float("inf"))
        else:
            deadline = self.health.deadline(need, alive=alive)
        masks = admit(lat, deadline)
        met = masks.reshape(n_live, -1).sum(axis=1) >= need_units
        t_comp[met] = coverage_time(lat)[met]

        killed = np.zeros(n, bool)
        for w in rf.killed:
            if w < n:
                killed[w] = True
        healthy = alive & ~killed & ~self.health.byzantine[:n]
        window = deadline
        for _ in range(cfg.max_retries):
            if met.all():
                break
            prev = window
            window *= cfg.retry_backoff
            self.stats.retries += 1
            for i in np.flatnonzero(~met):
                # late originals land inside the extended window (for
                # partial: the late worker's finished fragment PREFIX)
                masks[i] |= admit(lat[i], window)
                done = masks[i] if nf == 1 else masks[i].all(axis=-1)
                missing = np.flatnonzero(alive & ~done)
                if missing.size and healthy.any():
                    # re-dispatch the missing shard rows to healthy workers:
                    # fresh work issued when the previous window closed,
                    # racing the extension (a shard row is data, not a
                    # worker identity -- any healthy thread recomputes it)
                    redraw = cfg.straggler.sample(
                        missing.size, 1.0 / cfg.m, self.rng,
                        payload_scale=scale)
                    masks[i][missing[prev + redraw <= window]] = True
                    self.stats.redispatched_shards += int(missing.size)
                if units(masks[i]) >= need_units:
                    met[i] = True
                    t_comp[i] = window   # conservative: met at window close
        for i in np.flatnonzero(~met):
            if not healthy.any():
                reason = "insufficient_workers"
                detail = "no healthy workers to re-dispatch to"
            else:
                unit = "fragments" if nf > 1 else "shards"
                detail = (f"{units(masks[i])}/{need_units} {unit} after "
                          f"{cfg.max_retries} retries")
                reason = "retries_exhausted"
            errors[i] = ServiceError(reason, detail)
            self.stats.degraded += 1
            masks[i] = True
        # feed the tracker: per-worker mean measured time this round
        col = np.where(np.isfinite(lat), lat, np.nan)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", category=RuntimeWarning)
            col_mean = np.nanmean(col, axis=0)
        self.health.observe_round(np.where(np.isnan(col_mean), np.inf,
                                           col_mean))
        return masks, errors, t_comp, lat, rf, round_idx

    def _account_robust(self, t_comp: np.ndarray, lat: np.ndarray,
                        masks: np.ndarray, errors: list) -> None:
        self.stats.requests += int(t_comp.shape[0])
        finite = lat[np.isfinite(lat)]
        cap = float(finite.max()) if finite.size else 0.0
        coded = np.where(np.isfinite(t_comp), t_comp, cap)
        self.stats.coded_latency += float(coded.sum())
        unc = np.where(np.isfinite(lat), lat, cap).max(axis=1)
        self.stats.uncoded_latency += float(unc.sum())
        ok = np.array([e is None for e in errors], bool)
        if ok.any():
            self.stats.stragglers_tolerated += int((~masks[ok]).sum())

    def _robust_launch(self, s, bucket: int, kind: str, xb: np.ndarray,
                       n_live: int) -> "_Launched":
        """Launch one staged bucket through the fault-tolerant path."""
        cfg = self.cfg
        n = self._n_workers()
        if cfg.measured:
            if kind != "c2c":
                raise ValueError(
                    "measured=True serves c2c buckets only "
                    "(MeasuredWorkerRuntime is a 1-D c2c runtime)")
            return self._measured_launch(s, bucket, xb, n_live)
        masks, errors, t_comp, lat, rf, round_idx = \
            self._fault_arrivals(n_live, kind)
        self._account_robust(t_comp, lat, masks, errors)
        full = np.ones((bucket,) + masks.shape[1:], bool)
        full[:n_live] = masks
        errors = errors + [None] * (bucket - n_live)
        live_corrupt = [w for w in sorted(rf.corrupt) if w < n]
        if cfg.verify == "off" and not live_corrupt:
            # fault-free data path: reuse the jitted bucket executor with
            # the deadline-derived masks
            launched = self._execute(s, bucket, kind,
                                     self._bucket_args(s, kind, xb, full))
            launched.errors = errors
            return launched
        # instrumented path: corruption must land in real worker rows and
        # verification must see them, so execute host-visibly
        rows, errors = self._verify_execute(s, kind, xb, full, errors,
                                            round_idx, rf, n_live)
        return _Launched(rows, errors)

    def _verify_execute(self, s, kind: str, xb: np.ndarray,
                        masks: np.ndarray, errors: list, round_idx: int,
                        rf: RoundFaults, n_live: int) -> tuple[np.ndarray, list]:
        """Instrumented bucket execution: real worker rows, injected
        corruption, per-request Byzantine verification + decode."""
        plan = self._plan_for(s, kind)
        b = np.asarray(
            plan.worker_compute(plan.encode(jnp.asarray(xb))), np.complex128)
        live_corrupt = [w for w in sorted(rf.corrupt) if w < plan.n_workers]
        if live_corrupt and self.injector is not None:
            b = self.injector.corrupt_array(b, live_corrupt, round_idx,
                                            worker_axis=1)
        return self._decode_collected(s, kind, b, masks, errors, n_live)

    def _decode_collected(self, s, kind: str, b: np.ndarray,
                          masks: np.ndarray, errors: list, n_live: int
                          ) -> tuple[np.ndarray, list]:
        """Per-request decode of collected worker rows ``(bucket, N, ...)``,
        with the configured Byzantine check on surplus responses.

        ``verify="detect"``: k > m responses run the generalized-RS
        syndrome check (catches up to k - m liars); a hit fails the request
        (detection cannot say WHO lied with that budget).
        ``verify="correct"``: Prony error location corrects up to
        floor((k - m)/2) corrupt rows, flags the offenders into the health
        tracker (excluded from future re-dispatch), and decodes from clean
        rows -- bit-identical to the same-subset clean decode.
        """
        cfg = self.cfg
        plan = self._plan_for(s, kind)
        m, n = plan.m, plan.n_workers
        bucket = b.shape[0]
        nodes_all = np.asarray(mds.rs_nodes(n, jnp.complex128))
        rows: list = [None] * bucket
        for i in range(min(bucket, n_live)):   # padding rows never decode
            if errors[i] is not None:
                continue
            recv = np.flatnonzero(masks[i])
            k = int(recv.size)
            if cfg.verify != "off" and k > m:
                if cfg.verify == "detect":
                    flat = b[i][recv].reshape(k, -1)
                    if detect_errors(nodes_all[recv], flat, m):
                        self.stats.detected += 1
                        self.stats.degraded += 1
                        errors[i] = ServiceError(
                            "corrupt_uncorrectable",
                            f"syndrome check failed over {k} responses "
                            f'(verify="detect" cannot correct)')
                        continue
                    y = plan.decode(jnp.asarray(b[i]).astype(plan.dtype),
                                    subset=jnp.asarray(recv[:m]))
                else:
                    res = robust_decode(plan, b[i], recv)
                    if not res.ok:
                        self.stats.detected += 1
                        self.stats.degraded += 1
                        errors[i] = ServiceError(
                            "corrupt_uncorrectable",
                            f"more than {(k - m) // 2} corrupt rows among "
                            f"{k} responses")
                        continue
                    if res.n_errors_corrected:
                        self.stats.detected += res.n_errors_corrected
                        self.stats.corrected += res.n_errors_corrected
                        for w in np.asarray(
                                res.error_worker_indices).tolist():
                            self.health.flag_byzantine(int(w))
                    y = res.output
            elif int(getattr(plan, "fragments", 1)) > 1:
                y = plan.decode(jnp.asarray(b[i]).astype(plan.dtype),
                                fragment_mask=jnp.asarray(masks[i]))
            else:
                y = plan.decode(jnp.asarray(b[i]).astype(plan.dtype),
                                mask=jnp.asarray(masks[i]))
            rows[i] = np.asarray(y)
        zero = self._zero_row(s, kind)
        out = np.stack([zero if r is None else r for r in rows])
        return out, errors

    def _zero_row(self, s, kind: str) -> np.ndarray:
        """All-zeros result row (the slot value under a per-row error)."""
        plan = self._plan_for(s, kind)
        cdt = np.dtype(self.cfg.dtype)
        rdt = np.real(np.zeros(1, cdt)).dtype
        dt = rdt if kind in ("c2r", "irfftn") else cdt
        return np.zeros(tuple(plan.output_shape), dt)

    def _measured_for(self, s: int) -> MeasuredWorkerRuntime:
        cfg = self.cfg
        key = (s, self._n_workers())
        if key not in self._measured:
            self._measured[key] = MeasuredWorkerRuntime(
                self._plan_for(s, "c2c"), self.health,
                injector=self.injector, max_retries=cfg.max_retries,
                retry_backoff=cfg.retry_backoff,
                require_all=cfg.require_all,
                threshold_extra=(0 if cfg.verify == "off"
                                 else cfg.verify_quorum))
        return self._measured[key]

    def _measured_launch(self, s: int, bucket: int, xb: np.ndarray,
                         n_live: int) -> "_Launched":
        """Run one bucket on the thread-per-worker measured runtime."""
        cfg = self.cfg
        n = self._n_workers()
        rt = self._measured_for(s)
        round_idx = self._round
        self._round += 1
        alive = self.pool.mask() if self.pool is not None else None
        res = rt.round(np.asarray(xb, np.complex128), round_idx, alive)
        self.stats.retries += res.retries
        self.stats.redispatched_shards += res.redispatched
        self.stats.requests += n_live
        t_last = res.t_last if np.isfinite(res.t_last) else 0.0
        self.stats.uncoded_latency += t_last * n_live
        errors: list = [None] * bucket
        if not res.ok:
            err = ServiceError(res.reason, f"measured round {round_idx}")
            for i in range(n_live):
                errors[i] = err
            self.stats.degraded += n_live
            self.stats.coded_latency += t_last * n_live
            rows = np.stack([self._zero_row(s, "c2c")] * bucket)
            return _Launched(rows, errors)
        self.stats.coded_latency += float(res.t_met) * n_live
        alive_arr = np.ones(n, bool) if alive is None else alive
        self.stats.stragglers_tolerated += \
            int((alive_arr & ~res.mask).sum()) * n_live
        masks = np.ones((bucket, n), bool)
        masks[:n_live] = res.mask[None, :]
        # corruption was already injected by the worker threads inside
        # res.b, so the shared decode/verify step runs as-is
        return _Launched(*self._decode_collected(s, "c2c", res.b, masks,
                                                 errors, n_live))

    def fetch_bucket(self, launched: _Launched
                     ) -> tuple[np.ndarray, Optional[list]]:
        """Host rows + per-row errors for one launched bucket.

        The streaming syncer calls this instead of ``jax.device_get`` so
        the robust path's per-row :class:`ServiceError` objects never go
        through a device transfer (host rows pass straight through).  It
        waits for the device, then copies: the copy cannot start before
        the result is ready either way, and the two steps are spans of
        their own.  Complex rows arrive as real words and are viewed
        back (DESIGN.md §8)."""
        out = launched.out
        if isinstance(out, np.ndarray):
            self._release(launched)
            return out, launched.errors
        with span(FETCH_WAIT):
            jax.block_until_ready(out)
        self._release(launched)
        with span(FETCH_COPY, bytes=out.nbytes, dtype=out.dtype.name):
            host = jax.device_get(out)
        self.stats.d2h_bytes += host.nbytes
        return launched.rows(host), launched.errors

    # ------------------------------------------------------------------
    def submit(self, x: jax.Array) -> np.ndarray:
        """One request: returns F{x}, never waiting for stragglers."""
        return self.submit_batch([x])[0]

    def submit_rfft(self, x: jax.Array) -> np.ndarray:
        """One REAL request: returns the half spectrum ``rfft(x)``
        (``s//2 + 1`` bins) from half-payload worker shards."""
        return self.submit_batch([x], kind="r2c")[0]

    def submit_irfft(self, y: jax.Array) -> np.ndarray:
        """One half-spectrum request: returns the real ``irfft(y)`` of
        length ``2*(len(y) - 1)``."""
        return self.submit_batch([y], kind="c2r")[0]

    def submit_rfftn(self, t: jax.Array) -> np.ndarray:
        """One n-D REAL request: returns ``numpy.fft.rfftn(t)`` -- the
        half spectrum over the last axis (``t.shape[:-1] + (last//2+1,)``)
        -- from half-payload worker shards (DESIGN.md §9).  The last axis
        must satisfy the real-kind ``2m | s`` constraint after
        ``plan_factors`` splits ``m`` across the axes."""
        return self.submit_batch([t], kind="rfftn")[0]

    def submit_irfftn(self, y: jax.Array) -> np.ndarray:
        """One n-D half-spectrum request: returns the real
        ``numpy.fft.irfftn(y)`` of shape
        ``y.shape[:-1] + (2*(y.shape[-1]-1),)``."""
        return self.submit_batch([y], kind="irfftn")[0]

    def submit_batch(self, xs: Sequence[jax.Array],
                     kind: Union[str, Sequence[str]] = "c2c"
                     ) -> list[np.ndarray]:
        """Serve a batch of requests, bucketed by ``(s, m, kind)``.

        Master-side encode/decode for each bucket runs as ONE jitted call
        over the stacked requests; each request still gets its own
        simulated straggler pattern, and results come back in submission
        order as host arrays.

        ``kind`` selects the transform (DESIGN.md §7/§9): ``"c2c"``
        complex forward (default), ``"r2c"`` real input -> half spectrum,
        ``"c2r"`` half spectrum -> real output, ``"rfftn"`` n-D real
        input -> last-axis half spectrum, ``"irfftn"`` its inverse --
        either ONE kind for the whole call or a PER-REQUEST sequence
        (mixed traffic buckets by (s, kind), so a client no longer splits
        its stream by kind).  Buckets are keyed by the TIME-domain extent
        ``s`` -- a scalar length for 1-D kinds (a c2r request of ``h``
        bins lands in the ``s = 2*(h-1)`` bucket) and the full shape
        tuple for n-D kinds (an irfftn request's last axis is
        ``2*(bins-1)``).

        The call is PIPELINED (DESIGN.md §8): every bucket is dispatched
        before any host sync -- the jitted calls are asynchronous, so
        bucket k+1's host-side staging overlaps bucket k's device compute
        -- then ONE device->host transfer fetches all results.
        """
        kinds = ([kind] * len(xs) if isinstance(kind, str) else list(kind))
        if len(kinds) != len(xs):
            raise ValueError(
                f"per-request kinds: got {len(kinds)} kinds "
                f"for {len(xs)} requests")
        cfg = self.cfg
        results: list[Optional[np.ndarray]] = [None] * len(xs)
        by_bucket: dict[tuple, list[int]] = {}
        for i, (x, k) in enumerate(zip(xs, kinds)):
            by_bucket.setdefault((self.bucket_key(x, k), k), []).append(i)

        # phase 1 -- dispatch: stage + launch every bucket, no host sync
        t0 = time.perf_counter()
        pending: list[tuple[list[int], _Launched]] = []
        for (s, k), idxs in by_bucket.items():
            for start in range(0, len(idxs), cfg.max_batch):
                chunk = idxs[start:start + cfg.max_batch]
                pending.append((chunk, self._dispatch_bucket(s, chunk, xs, k)))
        self.stats.dispatch_s += time.perf_counter() - t0

        # phase 2 -- sync: ONE device->host transfer for the whole call
        # (numpy rows of the verify path pass through device_get unchanged)
        t0 = time.perf_counter()
        outs = [launched.out for _, launched in pending]
        fetched = jax.device_get(outs)
        for _, launched in pending:
            self._release(launched)
        self.stats.host_transfers += 1
        self.stats.d2h_bytes += sum(h.nbytes for o, h in zip(outs, fetched)
                                    if not isinstance(o, np.ndarray))
        self.stats.sync_s += time.perf_counter() - t0
        for (chunk, launched), host in zip(pending, fetched):
            rows, errors = launched.rows(host), launched.errors
            for row, i in enumerate(chunk):
                err = errors[row] if errors is not None else None
                if err is not None:
                    if cfg.on_failure == "raise":
                        raise err
                    results[i] = DegradedResult(err.reason, err.detail)
                else:
                    results[i] = rows[row]
        return results  # type: ignore[return-value]

    def warmup(self, lengths: Optional[Sequence[int]] = None,
               kinds: Sequence[str] = ("c2c",),
               buckets: Optional[Sequence[int]] = None) -> int:
        """Precompile the bucket executables so steady state never compiles.

        Keys one persistent executable per (s, kind, bucket-size) --
        default: the config length, c2c, every power-of-two bucket up to
        ``max_batch``.  ``lengths`` entries may be scalar lengths (1-D
        kinds) or shape tuples (``rfftn``/``irfftn``); each entry is
        paired only with the kinds it fits (scalars with 1-D kinds,
        tuples with n-D kinds), so one call can warm mixed traffic.
        Returns the number of executables compiled.  On the fallback
        (host-LRU) path this also primes the all-alive mask entry.

        With ``cfg.autotune`` (the default) this is also when the tiling
        search runs: per warmed (s, kind) on the kernel path the autotuner
        times the candidate four-step variants and bucket block_q tilings
        and persists the winners to the backend-keyed JSON table
        (kernels/autotune.py), so the executables compiled below already
        bake the measured plan in -- and the NEXT process skips the search
        entirely (warm table).
        """
        cfg = self.cfg
        lengths = [cfg.s] if lengths is None else list(lengths)
        if buckets is None:
            buckets, b = [], 1
            while b < cfg.max_batch:
                buckets.append(b)
                b *= 2
            buckets.append(cfg.max_batch)
        if cfg.autotune:
            kind_keys = {"c2c": "bucket", "r2c": "rbucket", "c2r": "irbucket"}
            mode = ops._mode(None)
            qmax = max(buckets)
            for s in lengths:
                for k in kinds:
                    if (isinstance(s, (tuple, list)) or k not in kind_keys
                            or not self._kernel_path(s, k)):
                        continue
                    ell = s // cfg.m if k == "c2c" else s // cfg.m // 2
                    autotune.ensure_fourstep(
                        ell, mode=mode, reps=cfg.autotune_reps)
                    autotune.ensure_bucket(
                        kind_keys[k], s, cfg.m, self._n_workers(), q=qmax,
                        mode=mode, reps=cfg.autotune_reps)
        outs, staged = [], []
        for s in lengths:
            if isinstance(s, (tuple, list)):
                s = tuple(int(d) for d in s)      # hashable bucket key
            for k in kinds:
                if isinstance(s, tuple) != (k in self.ND_KINDS):
                    continue        # scalar<->1-D, tuple<->n-D only
                for b in sorted(set(buckets)):
                    xb, _ = self._take_staging(s, b, k)
                    staged.append(xb)
                    masks = self._full_masks(s, k, b)
                    # always the FAST executors: the robust path reuses
                    # them whenever no corruption/verification is in play,
                    # so precompiling here serves both modes
                    outs.append(self._execute(
                        s, b, k, self._bucket_args(s, k, xb, masks)).out)
        jax.block_until_ready(outs)
        for xb in staged:
            self._give_back(xb)
        return len(outs)

    def _ingress_dtype(self, kind: str) -> np.dtype:
        """A kind's request dtype: real requests stay a single real plane
        end-to-end, the rest take the service dtype (NOT the first
        request's -- a real-valued request must not narrow the whole
        bucket's buffer)."""
        cdt = np.dtype(self.cfg.dtype)
        return np.finfo(cdt).dtype if kind in ("r2c", "rfftn") else cdt

    def _bucket_layout(self, s, bucket: int, kind: str
                       ) -> tuple[tuple, np.dtype]:
        """Shape and dtype of one bucket's request buffer, in the kind's
        ingress dtype.  ``s`` is the scalar time-domain length (1-D kinds)
        or shape tuple (n-D kinds)."""
        if kind in self.ND_KINDS:
            shape = tuple(s) if kind == "rfftn" else (
                tuple(s[:-1]) + (s[-1] // 2 + 1,))
        else:
            shape = (s // 2 + 1,) if kind == "c2r" else (s,)
        return (bucket,) + shape, self._ingress_dtype(kind)

    def _bucket_buffer(self, s, bucket: int, kind: str) -> np.ndarray:
        """A new, zeroed request buffer for one bucket."""
        return np.zeros(*self._bucket_layout(s, bucket, kind))

    def _take_staging(self, s, bucket: int, kind: str
                      ) -> tuple[np.ndarray, bool]:
        """A request buffer for one bucket from the free list, else a new
        one; and whether it was reused.  A reused buffer still holds an
        earlier bucket's requests.  A new 128 MiB buffer is a fresh
        mapping whose pages fault on first touch, most of the pack's time
        (PERF.md §5); a reused one faults none."""
        shape, dtype = self._bucket_layout(s, bucket, kind)
        with self._staging_lock:
            free = self._staging_free.get((shape, dtype))
            if free:
                return free.pop(), True
        return np.zeros(shape, dtype), False

    def _give_back(self, xb: np.ndarray) -> None:
        """Put a staging buffer on the free list, up to
        :data:`STAGING_KEEP` per shape; beyond that it is dropped."""
        with self._staging_lock:
            free = self._staging_free.setdefault((xb.shape, xb.dtype), [])
            if len(free) < STAGING_KEEP:
                free.append(xb)

    def _release(self, launched: _Launched) -> None:
        """Hand a bucket's staging buffer back once its result is ready.
        Not sooner: on the CPU ``device_put`` may alias the host buffer,
        and a transfer to a chip may still read it, until the computation
        that consumed the arguments has finished (DESIGN.md §11)."""
        xb, launched.staging = launched.staging, None
        if xb is not None:
            self._give_back(xb)

    def _full_masks(self, s, kind: str, bucket: int) -> np.ndarray:
        """All-responders mask block for one bucket: ``(bucket, N)``, or
        ``(bucket, N, r)`` per-fragment for partial-work strategies."""
        plan = self._plan_for(s, kind)
        nf = int(getattr(plan, "fragments", 1))
        shape = (bucket, self._n_workers()) + ((nf,) if nf > 1 else ())
        return np.ones(shape, bool)

    def _bucket_args(self, s: int, kind: str, xb: np.ndarray,
                     masks: np.ndarray) -> tuple:
        """Device arguments for one bucket invocation.

        Device-decode path: the requests and the raw boolean masks -- two
        int words of decode metadata per request cross the host boundary,
        everything else happens in-jit (DESIGN.md §8).  Fallback kernel
        path (``m > mds.LAGRANGE_MAX_M``): per-mask matrices from the
        host LRU, shared across every (s, kind) bucket -- compact
        inverses and subsets for the direct body, scatter planes for the
        stage kernels.
        A complex request buffer crosses as real words; the executor gets
        it back as complex in :meth:`_execute`.
        """
        host: tuple = (masks,)
        if self._kernel_path(s, kind) and not self._decode_in_jit():
            cache = self._decode_cache_for()
            h0, m0 = cache.hits, cache.misses
            f32 = np.float32
            if self._bucket_body(s, kind) == "direct":
                invs, subsets = cache.compact(masks)
                host = (invs.real.astype(f32), invs.imag.astype(f32), subsets)
            else:
                dmats = cache.matrices(masks)
                host = (dmats.real.astype(f32), dmats.imag.astype(f32))
            # deltas, not lifetime cache totals: every other ServiceStats
            # field accumulates, so a stats reset must window these too
            self.stats.decode_cache_hits += cache.hits - h0
            self.stats.decode_cache_misses += cache.misses - m0
        host = (_host_words(xb),) + host
        nbytes = sum(a.nbytes for a in host)
        with span(STAGE_H2D, bytes=nbytes, dtype=host[0].dtype.name):
            args = tuple(self._to_device(a) for a in host)
        self.stats.h2d_bytes += nbytes
        return args

    def _to_device(self, a: np.ndarray) -> jax.Array:
        """One bucket argument onto the device(s).  Without a mesh, or with
        ``mesh_ingress="first"``, it lands on the default device (a mesh
        runner's call then copies it to the others).  With ``"split"``
        each device takes its slice of the leading (bucket) axis over its
        own host link, and the runner all-gathers the slices; a bucket
        that does not divide over the devices goes whole to each."""
        if self.mesh is None or self.cfg.mesh_ingress == "first":
            return jnp.asarray(a)
        rows = a.shape[0] % self.mesh.shape[self.axis] == 0
        return jax.device_put(
            a, NamedSharding(self.mesh, P(self.axis) if rows else P()))

    # -- staging seam (shared with serving/streaming.py, DESIGN.md §11) --
    def bucket_key(self, x, kind: str):
        """The bucket extent ``s`` one request lands in: the scalar
        TIME-domain length for 1-D kinds (a c2r request of ``h`` bins maps
        to ``s = 2*(h-1)``), the full time-domain shape tuple for the n-D
        kinds.  Validates the kind and minimal half-spectrum width."""
        if kind not in self.KINDS:
            raise ValueError(f"unknown bucket kind {kind!r}")
        n_last = int(x.shape[-1])
        if kind in ("c2r", "irfftn") and n_last < 2:
            raise ValueError(
                f"{kind} requests need >= 2 half-spectrum bins "
                f"(s = 2*(bins-1) > 0), got {n_last}")
        if kind in self.ND_KINDS:
            # n-D kinds bucket by the full TIME-domain shape tuple
            time_last = 2 * (n_last - 1) if kind == "irfftn" else n_last
            return tuple(int(d) for d in x.shape[:-1]) + (time_last,)
        return 2 * (n_last - 1) if kind == "c2r" else n_last

    def stage_bucket(self, s, kind: str, reqs: Sequence) -> tuple:
        """Host-side staging for one bucket of same-``(s, kind)`` requests.

        Everything that costs host time lives here -- the straggler draw,
        the numpy pack into the padded bucket buffer, and the host->device
        argument conversion -- so the streaming front-end can run it on a
        staging thread while the previous bucket computes (DESIGN.md §11).
        Returns ``(bucket, args)`` for :meth:`launch_bucket`.
        """
        cfg = self.cfg
        n_live = len(reqs)
        bucket = bucket_size(n_live, cfg.max_batch)
        self.stats.batches += 1

        with span(STAGE_PACK) as ann:
            xb, reused = self._take_staging(s, bucket, kind)
            ann.set_metadata(reused=reused)
            if reused:
                self.stats.staging_reused += 1
            else:
                self.stats.staging_allocated += 1
            # every live row is overwritten; padding rows must read zero
            xb[n_live:] = 0
            real_in = kind in ("r2c", "rfftn")
            for row, x in enumerate(reqs):
                x = np.asarray(x)
                xb[row] = x.real if real_in and np.iscomplexobj(x) else x
            if self._robust:
                # fault path: masks are derived at LAUNCH time -- the
                # deadline/retry state machine mutates health + round
                # state, which the launch step owns (stager-thread-confined
                # on the streaming path, exactly like the non-robust
                # service internals)
                return bucket, _StagedArgs((xb, n_live), xb)
            lat, mask = self._simulate_arrivals(n_live, kind)
            self._account(lat, mask)
            # padded rows: every worker "responds" so decode stays
            # well-posed
            masks = self._full_masks(s, kind, bucket)
            masks[:n_live] = mask
        return bucket, _StagedArgs(self._bucket_args(s, kind, xb, masks), xb)

    def launch_bucket(self, s, bucket: int, kind: str, args: tuple
                      ) -> _Launched:
        """Launch one staged bucket; returns the UNSYNCED result.

        The jitted calls return immediately (async dispatch), so callers
        can launch every bucket before blocking once on all of them.  The
        return value is a :class:`_Launched` (device rows, or host rows on
        the verify path, + per-row errors on the fault path); fetch it
        with :meth:`fetch_bucket`, which hands the bucket's staging buffer
        back.
        """
        with span(STAGE_LAUNCH, **self._account_launch(s, bucket, kind,
                                                       args)):
            if self._robust:
                xb, n_live = args
                launched = self._robust_launch(s, bucket, kind, xb, n_live)
            else:
                launched = self._execute(s, bucket, kind, args)
        launched.staging = getattr(args, "staging", None)
        return launched

    def _account_launch(self, s, bucket: int, kind: str, args: tuple
                        ) -> dict:
        """The ``fft.stage.launch`` span's arguments: how many devices the
        bucket's runner spans and which runner it is; on a mesh also its
        ingress and the bytes the launch moves between the devices, which
        are added to :class:`ServiceStats` here."""
        if self.mesh is None:
            return {"devices": 1, "runner": self._runner_name(s, kind)}
        runtime = self._runtime_for(s, kind)
        # an argument that came whole to every device from the host moves
        # nothing between them; any other is replicated by the devices
        held = [a for a in args if not (a.sharding.is_fully_replicated
                                        and len(a.sharding.device_set) > 1)]
        bcast, gather = runtime.exchange_bytes(
            bucket, sum(a.nbytes for a in held))
        self.stats.broadcast_bytes += bcast
        self.stats.gather_bytes += gather
        return {"devices": runtime.n_devices, "runner": "mesh",
                "ingress": self.cfg.mesh_ingress,
                "broadcast_bytes": bcast, "gather_bytes": gather}

    def _runner_name(self, s, kind: str) -> str:
        """The local runner a bucket takes: the fault path, the kernel
        executor with in-jit decode matrices (``kernel_masked``) or with
        host ones (``kernel``), or the jitted ``plan.run``."""
        if self._robust:
            return "robust"
        if not self._kernel_path(s, kind):
            return "plan"
        return "kernel_masked" if self._decode_in_jit() else "kernel"

    def _execute(self, s, bucket: int, kind: str, args: tuple) -> _Launched:
        """The bucket executor between the host link's two conversions
        (DESIGN.md §8): complex requests arrive as real words and are
        rebuilt on the device, a complex result leaves as real words.
        The executor keeps its complex signature, so the c2c donation
        alias holds and whatever wraps :meth:`_runner_for` sees complex
        arrays."""
        x, *rest = args
        if self._ingress_dtype(kind).kind == "c":
            x = from_words(x)
        out = self._runner_for(s, bucket, kind)(x, *rest)
        if jnp.iscomplexobj(out):
            return _Launched(to_words(out, on_mesh=self.mesh is not None),
                             words=True)
        return _Launched(out)

    def _dispatch_bucket(self, s, idxs: list[int], xs,
                         kind: str = "c2c") -> _Launched:
        """Stage + launch one bucket (the closed-loop submit_batch path)."""
        bucket, args = self.stage_bucket(s, kind, [xs[i] for i in idxs])
        return self.launch_bucket(s, bucket, kind, args)
