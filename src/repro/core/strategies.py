"""Baseline computation strategies compared against coded FFT (Remark 4).

The paper's comparison:

* **coded FFT** (this work):          K* = m
* **uncoded repetition**:             K  = N - N/m^2 + 1
* **short-dot / short-MDS [9],[13]**: K  = N - N/m + m

Uncoded repetition is implemented in full: without exploiting the DFT's
recursive structure, the generic approach block-partitions the DFT *matrix*
into an m x m grid -- worker w stores one contiguous input chunk ``x_j``
(1/m of the input) and returns one partial product ``P_ij = F_ij @ x_j``
(s/m outputs).  The master must collect ALL m^2 distinct blocks; with each
block replicated N/m^2 times, an adversary can erase every copy of one
block using only N/m^2 erasures, so the worst-case threshold is
``N - N/m^2 + 1`` exactly.

Short-dot is reported analytically (the sparse-code construction of Dutta
et al. [13]; we cite the threshold rather than re-implement that paper).

``UncodedRepetitionFFT`` implements the :class:`repro.core.plan.CodedPlan`
protocol (shape metadata, leading batch axes through encode/worker/decode)
but NOT ``MDSPlan`` -- its replication code is not subset-decodable, which
is exactly the Remark-4 gap.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coded_fft import CodedFFT
from repro.core.comm_efficient import CodedCommEffFFT
from repro.core.partial import CodedPartialFFT
from repro.core.plan import batch_shape

__all__ = [
    "UncodedRepetitionFFT",
    "CodedPartialFFT",
    "CodedCommEffFFT",
    "StrategyEntry",
    "REGISTRY",
    "register_strategy",
    "make_strategy",
    "coded_fft_threshold",
    "repetition_threshold",
    "short_dot_threshold",
]


def coded_fft_threshold(n: int, m: int) -> int:
    """Theorem 1: K* = m."""
    return m


def repetition_threshold(n: int, m: int) -> int:
    """Remark 4: uncoded repetition needs N - N/m^2 + 1 (worst case)."""
    assert n % (m * m) == 0, "repetition baseline needs m^2 | N"
    return n - n // (m * m) + 1


def short_dot_threshold(n: int, m: int) -> int:
    """Remark 4: short-dot / short-MDS [9],[13] needs N - N/m + m."""
    assert n % m == 0
    return n - n // m + m


@dataclasses.dataclass(frozen=True)
class UncodedRepetitionFFT:
    """Generic block-partitioned DFT with replication (no coding).

    N workers, m^2 | N.  Worker ``w`` is assigned block
    ``(i, j) = divmod(w % m^2, m)`` -- it stores input chunk ``x_j``
    (contiguous, length s/m) and computes ``P_ij = F[i-block, j-block] @ x_j``.
    """

    s: int
    m: int
    n_workers: int
    dtype: jnp.dtype = jnp.complex64

    def __post_init__(self):
        if self.s % self.m != 0:
            raise ValueError("m | s required")
        if self.n_workers % (self.m * self.m) != 0:
            raise ValueError("m^2 | N required for the repetition baseline")

    @property
    def shard_len(self) -> int:
        return self.s // self.m

    @property
    def n_blocks(self) -> int:
        return self.m * self.m

    @property
    def replicas(self) -> int:
        return self.n_workers // self.n_blocks

    # -- CodedPlan shape metadata --------------------------------------------
    @property
    def input_shape(self) -> tuple[int, ...]:
        return (self.s,)

    @property
    def output_shape(self) -> tuple[int, ...]:
        return (self.s,)

    @property
    def worker_shard_shape(self) -> tuple[int, ...]:
        return (self.shard_len,)

    @property
    def recovery_threshold(self) -> int:
        """Worst-case threshold (Remark 4) -- contrast with MDS plans' m."""
        return self.worst_case_threshold()

    def block_of_worker(self, w: int) -> tuple[int, int]:
        return divmod(w % self.n_blocks, self.m)

    def _dft_block(self, i: int, j: int) -> jax.Array:
        ell = self.shard_len
        rows = jnp.arange(i * ell, (i + 1) * ell)
        cols = jnp.arange(j * ell, (j + 1) * ell)
        return jnp.exp(-2j * jnp.pi * jnp.outer(rows, cols) / self.s).astype(self.dtype)

    @functools.cached_property
    def _worker_blocks(self) -> jax.Array:
        """Stacked per-worker DFT blocks, shape (N, s/m, s/m)."""
        return jnp.stack(
            [self._dft_block(*self.block_of_worker(w))
             for w in range(self.n_workers)])

    @functools.cached_property
    def _chunk_of_worker(self) -> jax.Array:
        return jnp.asarray(
            [self.block_of_worker(w)[1] for w in range(self.n_workers)])

    def encode(self, x: jax.Array) -> jax.Array:
        """Worker storage ``(*B, N, s/m)`` -- worker w stores chunk x_{j_w}."""
        chunks = x.astype(self.dtype).reshape(
            x.shape[:-1] + (self.m, self.shard_len))
        return jnp.take(chunks, self._chunk_of_worker, axis=-2)

    def worker_compute(self, a: jax.Array) -> jax.Array:
        """Worker w returns F_{i_w, j_w} @ x_{j_w}; leading axes map through."""
        return jnp.einsum("nij,...nj->...ni", self._worker_blocks, a)

    def decodable(self, mask: np.ndarray) -> bool:
        """Master can finish iff every (i, j) block has >= 1 live replica."""
        got = set()
        for w in np.nonzero(np.asarray(mask))[0]:
            got.add(self.block_of_worker(int(w)))
        return len(got) == self.n_blocks

    def decode(self, b: jax.Array, subset: Optional[np.ndarray] = None,
               mask: Optional[np.ndarray] = None) -> jax.Array:
        """Assemble X from one live replica per block (host-side numpy).

        ``b``: ``(*B, N, s/m)`` worker results; ``mask``: ``(N,)`` or
        ``(*B, N)`` availability (``subset`` of responder ids is accepted
        for protocol uniformity and converted to a mask).  Raises if any
        block lost all replicas.
        """
        if subset is not None:
            if mask is not None:
                raise ValueError("pass at most one of subset / mask")
            mask = np.zeros(self.n_workers, bool)
            mask[np.asarray(subset)] = True
        if mask is None:
            mask = np.ones(self.n_workers, bool)
        batch = batch_shape(b, 2, "worker results")
        if batch:
            bf = np.asarray(b).reshape((-1,) + b.shape[len(batch):])
            mf = np.broadcast_to(
                np.asarray(mask), batch + (self.n_workers,)
            ).reshape(bf.shape[0], -1)
            out = np.stack([np.asarray(self._decode1(bi, mi))
                            for bi, mi in zip(bf, mf)])
            return jnp.asarray(out.reshape(batch + (self.s,)))
        return self._decode1(b, np.asarray(mask))

    def _decode1(self, b: jax.Array, mask: np.ndarray) -> jax.Array:
        if not self.decodable(mask):
            raise ValueError("not enough workers responded: some block missing")
        ell = self.shard_len
        x_out = jnp.zeros((self.s,), self.dtype)
        seen = set()
        for w in np.nonzero(mask)[0]:
            i, j = self.block_of_worker(int(w))
            if (i, j) in seen:
                continue
            seen.add((i, j))
            x_out = x_out.at[i * ell : (i + 1) * ell].add(b[..., int(w), :])
        return x_out

    def run(self, x: jax.Array, subset: Optional[np.ndarray] = None,
            mask: Optional[np.ndarray] = None) -> jax.Array:
        return self.decode(self.worker_compute(self.encode(x)),
                           subset=subset, mask=mask)

    # -- empirical threshold verification ------------------------------------
    def worst_case_threshold(self) -> int:
        """Smallest k such that EVERY k-subset is decodable.

        Exact by construction: the adversary kills all replicas of one block
        (N/m^2 workers); with those gone, N - N/m^2 responders still miss a
        block, so threshold = N - N/m^2 + 1.  Verified empirically for small
        N in tests via exhaustive subsets.
        """
        return self.n_workers - self.replicas + 1

    def is_k_recoverable(self, k: int, subsets: Optional[Iterable] = None) -> bool:
        """Check decodability of every k-subset (exhaustive -- small N only)."""
        if subsets is None:
            subsets = itertools.combinations(range(self.n_workers), k)
        for sub in subsets:
            mask = np.zeros(self.n_workers, bool)
            mask[list(sub)] = True
            if not self.decodable(mask):
                return False
        return True


# -- strategy registry (DESIGN.md §13) ----------------------------------------
#
# One name -> (factory, applicability) table for every computation strategy,
# so new plans auto-enroll everywhere a strategy choice exists: the
# registry-parametrized property suite differentially verifies each entry
# against numpy.fft under drawn configs/masks with zero new test code,
# and `FFTService(strategy=...)` resolves its bucket plans here.


@dataclasses.dataclass(frozen=True)
class StrategyEntry:
    """One computation strategy the runtime can execute.

    ``factory(s, m, n_workers, *, dtype, backend, param)`` builds the plan
    (``param`` is the strategy's own knob -- ``r`` fragments for partial,
    ``q`` fold for comm-efficient -- ``None`` means the entry's default).
    ``applicable(s, m, n_workers, param)`` is the cheap per-(s, m, N)
    predicate the service's bucket selection and the test parametrization
    filter on; the factory's own ValueError stays the authoritative (and
    explanatory) gate.
    """

    name: str
    factory: Callable
    applicable: Callable[[int, int, int, Optional[int]], bool]
    default_param: Optional[int] = None
    kernel_ok: bool = False
    mesh_ok: bool = True
    description: str = ""

    def build(self, s: int, m: int, n_workers: int, *,
              dtype=jnp.complex64, backend: str = "reference",
              param: Optional[int] = None):
        return self.factory(s, m, n_workers, dtype=dtype, backend=backend,
                            param=self.default_param if param is None
                            else param)


REGISTRY: dict[str, StrategyEntry] = {}


def register_strategy(entry: StrategyEntry) -> StrategyEntry:
    if entry.name in REGISTRY:
        raise ValueError(f"strategy {entry.name!r} already registered")
    REGISTRY[entry.name] = entry
    return entry


def make_strategy(name: str, s: int, m: int, n_workers: int, *,
                  dtype=jnp.complex64, backend: str = "reference",
                  param: Optional[int] = None):
    """Build a registered strategy's plan; raises KeyError on unknown
    names and the plan's own ValueError on inapplicable (s, m, N)."""
    if name not in REGISTRY:
        raise KeyError(
            f"unknown strategy {name!r}; registered: {sorted(REGISTRY)}")
    return REGISTRY[name].build(s, m, n_workers, dtype=dtype,
                                backend=backend, param=param)


register_strategy(StrategyEntry(
    name="mds",
    factory=lambda s, m, n, *, dtype, backend, param: CodedFFT(
        s, m, n, dtype=dtype, backend=backend),
    applicable=lambda s, m, n, param: s % m == 0 and n >= m,
    kernel_ok=True,
    mesh_ok=True,
    description="the paper's (N, m) MDS code: threshold m (optimal), "
                "full s/m payload per worker",
))

register_strategy(StrategyEntry(
    name="partial",
    factory=lambda s, m, n, *, dtype, backend, param: CodedPartialFFT(
        s, m, n, r=param, dtype=dtype, backend=backend),
    applicable=lambda s, m, n, param:
        s % (m * (param or 2)) == 0 and n >= m,
    default_param=2,
    kernel_ok=False,
    mesh_ok=True,
    description="Wang et al. 1804.09791: r sequentially-useful fragments "
                "per worker, decode from any m*r fragments -- slow-but-"
                "alive workers contribute prefixes",
))

register_strategy(StrategyEntry(
    name="comm_efficient",
    factory=lambda s, m, n, *, dtype, backend, param: CodedCommEffFFT(
        s, m, n, q=param, dtype=dtype, backend=backend),
    applicable=lambda s, m, n, param:
        s % m == 0 and (s // m) % (param or 2) == 0
        and n >= m * (param or 2),
    default_param=2,
    kernel_ok=False,
    mesh_ok=True,
    description="Jeong et al. 1805.09891: ship a 1/q folded payload "
                "(payload_scale 1/q) at threshold m*q -- wins when the "
                "wire dominates",
))

register_strategy(StrategyEntry(
    name="repetition",
    factory=lambda s, m, n, *, dtype, backend, param: UncodedRepetitionFFT(
        s, m, n, dtype=dtype),
    applicable=lambda s, m, n, param: s % m == 0 and n % (m * m) == 0,
    kernel_ok=False,
    mesh_ok=False,
    description="Remark-4 uncoded baseline: block-partitioned DFT with "
                "replication, worst-case threshold N - N/m^2 + 1",
))
