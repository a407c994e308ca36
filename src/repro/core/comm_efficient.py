"""Communication-efficient coded FFT: trade recovery threshold for wire.

Jeong et al. (arXiv 1805.09891) observe that in the MDS construction each
worker ships its FULL transformed shard (s/m symbols) even though the
master only needs s total -- when the wire, not the FLOPs, is the
bottleneck, the coded round pays an m-fold communication overhead.  Their
fix: each worker FOLDS its result before shipping, sending ``1/q`` of the
payload, at the price of a higher recovery threshold ``m*q``.

Construction, on top of the standard (N, m) coded-FFT pipeline:

  1. encode exactly as :class:`~repro.core.coded_fft.CodedFFT`: worker
     ``k`` stores coded shard ``a_k = sum_i omega_N^{ki} c_i`` (length
     ``L = s/m``);
  2. worker ``k`` computes the full transform ``b_k = fft(a_k)`` (same
     FLOPs as the base plan), splits it into ``q`` contiguous blocks
     ``b_k^{(t)}`` of length ``L/q``, and ships only the fold

        ``d_k = sum_t omega_N^{k*m*t} b_k^{(t)}``        (L/q symbols);

  3. because ``b_k^{(t)} = sum_i omega_N^{ki} C_i^{(t)}`` with
     ``C_i = fft(c_i)``, the fold's exponents ``{i + m*t}`` sweep
     ``0..m*q-1`` bijectively, so ``d_k`` is row ``k`` of the WIDER
     ``(N, m*q)`` RS code on message ``u_{i+m*t} = C_i^{(t)}``;
  4. the master decodes ``u`` from ANY ``m*q`` responders (every
     ``m*q``-subset of the roots-of-unity Vandermonde is invertible,
     needs ``m*q <= N``), un-permutes ``u -> C`` (a reshape/transpose),
     and recombines as usual.

``q = 1`` degenerates to the base MDS plan.  Per-worker wire payload is
``L/q`` -- ``payload_scale = 1/q`` under :class:`~repro.distributed
.straggler.StragglerModel`'s wire model -- while the threshold rises from
``m`` to ``m*q``: the plan wins exactly when ``wire_frac`` is high and
loses when compute dominates (the master now waits for the ``m*q``-th
order statistic).

Decode is inherited wholesale from :class:`~repro.core.plan.MDSPlanBase`
via the ``decode_generator`` / ``decode_width`` hooks -- the fold changed
*which* linear system the responders are rows of, not the shape of the
decode problem.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import mds
from repro.core.interleave import interleave
from repro.core.plan import MDSPlanBase
from repro.core.recombine import recombine

__all__ = ["CodedCommEffFFT"]


@dataclasses.dataclass(frozen=True)
class CodedCommEffFFT(MDSPlanBase):
    """1-D coded FFT shipping a ``1/q`` folded payload per worker.

    Args:
      s: transform length.
      m: storage fraction parameter -- each worker stores/computes s/m.
      n_workers: N >= m*q workers (the widened code needs m*q rows).
      q: fold factor; per-worker wire payload is ``s/(m*q)`` and the
        recovery threshold is ``m*q``.
      dtype: complex dtype of the computation.
      backend: ``"reference"`` (default) or ``"kernel"`` -- the fused
        bucket kernels assume the ship-the-full-shard MDS layout, so this
        plan runs the jnp path by default; ``"kernel"`` still routes the
        worker DFT through the Pallas four-step for c64.
    """

    s: int
    m: int
    n_workers: int
    q: int = 2
    dtype: jnp.dtype = jnp.complex64
    backend: str = "reference"

    def __post_init__(self):
        if self.q < 1:
            raise ValueError(f"need q >= 1, got q={self.q}")
        if self.s % self.m != 0:
            raise ValueError(f"m={self.m} must divide s={self.s}")
        if (self.s // self.m) % self.q != 0:
            raise ValueError(
                f"q={self.q} must divide the shard length "
                f"s/m={self.s // self.m} (the fold splits it into q blocks)")
        if self.n_workers < self.m * self.q:
            raise ValueError(
                f"need N >= m*q for recoverability, got N={self.n_workers} "
                f"m*q={self.m * self.q}")

    # -- code geometry -------------------------------------------------------
    @property
    def shard_len(self) -> int:
        """Symbols each worker stores and transforms: s/m (unchanged)."""
        return self.s // self.m

    @property
    def payload_len(self) -> int:
        """Symbols each worker SHIPS: s/(m*q)."""
        return self.shard_len // self.q

    @property
    def input_shape(self) -> tuple[int, ...]:
        return (self.s,)

    @property
    def output_shape(self) -> tuple[int, ...]:
        return (self.s,)

    @property
    def worker_shard_shape(self) -> tuple[int, ...]:
        """What a worker SHIPS (the folded payload) -- the master-side
        decode shape contract."""
        return (self.payload_len,)

    @property
    def stored_shard_shape(self) -> tuple[int, ...]:
        """What a worker STORES and transforms (the full coded shard) --
        distributed executors size per-device buffers from this."""
        return (self.shard_len,)

    @property
    def recovery_threshold(self) -> int:
        """The traded-away optimum: m*q responders instead of m."""
        return self.m * self.q

    @property
    def payload_scale(self) -> float:
        """The purchased win: 1/q of the MDS wire payload per worker."""
        return 1.0 / self.q

    @property
    def generator(self) -> jax.Array:
        """The ``(N, m)`` ENCODE generator -- worker storage is unchanged
        from the base MDS plan."""
        return mds.rs_generator(self.n_workers, self.m, self.dtype)

    @property
    def decode_generator(self) -> jax.Array:
        """The widened ``(N, m*q)`` system the folded responses are rows
        of (same roots-of-unity nodes, more columns)."""
        return mds.rs_generator(self.n_workers, self.m * self.q, self.dtype)

    @property
    def decode_width(self) -> int:
        return self.m * self.q

    @property
    def worker_encode_tensor(self) -> jax.Array:
        """Per-worker encode rows ``(N, 1, m)`` for the distributed
        runtime's generic contraction (one stored fragment per worker)."""
        return self.generator[:, None, :]

    @functools.cached_property
    def fold_weights(self) -> jax.Array:
        """``(N, q)`` fold coefficients ``omega_N^{k*m*t}`` -- read off the
        decode generator's columns ``m*t`` so the root convention can
        never drift from the system decode solves."""
        return self.decode_generator[:, :: self.m]

    # -- stage cores ---------------------------------------------------------
    def _message1(self, x: jax.Array) -> jax.Array:
        return interleave(x.astype(self.dtype), self.m)

    def _encode1(self, x: jax.Array) -> jax.Array:
        c = self._message1(x)
        return mds.encode_dft(c, self.n_workers).astype(self.dtype)

    def encode(self, x: jax.Array) -> jax.Array:
        """Input -> stored worker shards ``(*B, N, s/m)`` -- always the
        O(N log N) DFT encode (the base kernel branch folds payload
        through the (N, m) generator matmul, which is fine, but its
        output-shape bookkeeping assumes ``worker_shard_shape`` == stored
        shape; this plan ships a different shape than it stores)."""
        return self._map_batched(
            self._encode1, x, len(self.input_shape), "plan input")

    def worker_compute(self, a: jax.Array) -> jax.Array:
        """Full per-shard DFT, then the 1/q fold: ``(*B, N, s/m) ->
        (*B, N, s/(m*q))``.

        Unlike the base plans this is worker-INDEX-aware (the fold weight
        is ``omega^{kmt}``), so the worker axis must be at -2 spanning all
        N workers; use :meth:`worker_compute_rows` for a device holding a
        subset of rows.
        """
        return self.worker_compute_rows(a, jnp.arange(self.n_workers))

    def worker_compute_rows(self, a: jax.Array, rows: jax.Array) -> jax.Array:
        """:meth:`worker_compute` for the workers in ``rows`` only --
        ``a``: ``(n_rows, *B, s/m)`` or ``(*B, n_rows, s/m)`` with the
        row axis at -2; returns the same layout with the last axis folded
        to ``s/(m*q)``."""
        b = self._fft1_worker(a)
        blocks = b.reshape(b.shape[:-1] + (self.q, self.payload_len))
        w = jnp.take(self.fold_weights, rows, axis=0)
        return jnp.einsum("...nql,nq->...nl", blocks,
                          w.astype(blocks.dtype))

    def _postdecode1(self, u: jax.Array) -> jax.Array:
        # u[i + m*t] = C_i^{(t)}: un-permute the widened message back into
        # the m shard transforms, then the standard twiddle recombine
        c_hat = (u.reshape(self.q, self.m, self.payload_len)
                 .transpose(1, 0, 2)
                 .reshape(self.m, self.shard_len))
        return recombine(c_hat, self.s)

    # decode / decodable / run: inherited from MDSPlanBase -- the
    # decode_generator / decode_width hooks point the shared machinery at
    # the widened system, and recovery_threshold drives decodable().
