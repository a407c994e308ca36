"""The ``CodedPlan`` protocol: one interface for every coded strategy.

The paper's pipeline (interleave -> MDS-encode -> worker DFT -> MDS-decode
-> recombine) is a single coded-linear-transform family; ``CodedFFT``,
``CodedFFTND``, ``CodedFFTMultiInput`` and ``UncodedRepetitionFFT`` are all
instances of it.  This module defines the shared contract (DESIGN.md §2)
plus ``MDSPlanBase``, the batch-aware implementation the three MDS-coded
strategies build on.

Canonical shapes (``B* = any leading batch axes``, usually one):

* ``encode``          : ``(*B, *input_shape)  -> (*B, N, *worker_shard_shape)``
* ``worker_compute``  : ``(*B, N, *shard)     -> (*B, N, *shard)`` -- the
  transform acts on the trailing ``worker_shard_shape`` axes only, so any
  leading layout (batch, worker, or both) maps through unchanged.
* ``decode``          : ``(*B, N, *shard)     -> (*B, *output_shape)`` with
  per-request straggler ``mask`` ``(*B, N)`` / ``subset`` ``(*B, m)``.

MDS plans additionally split the master's two stages so distributed
executors can fuse them per device (DESIGN.md §3):

* ``message``    : input -> the ``m`` uncoded message shards (interleave);
* ``postdecode`` : decoded message shards -> final output (recombine).

``encode = encode_dft(message(x))`` and
``decode = postdecode(mds_subset_decode(b))`` by construction.
"""

from __future__ import annotations

import math
from typing import Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mds
from repro.kernels import ops

__all__ = ["CodedPlan", "MDSPlan", "MDSPlanBase", "batch_shape"]


def batch_shape(arr: jax.Array, core_ndim: int, what: str) -> tuple[int, ...]:
    """Leading batch dims of ``arr`` given its core (unbatched) rank."""
    extra = arr.ndim - core_ndim
    if extra < 0:
        raise ValueError(
            f"{what} must have rank >= {core_ndim}, got shape {arr.shape}")
    return arr.shape[:extra]


@runtime_checkable
class CodedPlan(Protocol):
    """Minimal contract every computation strategy satisfies.

    Instances: ``CodedFFT`` / ``CodedFFTND`` / ``CodedFFTMultiInput``
    (complex), ``CodedRFFT`` / ``CodedIFFT`` / ``CodedIRFFT`` (1-D real /
    inverse, DESIGN.md §7), ``CodedRFFTN`` / ``CodedIRFFTN`` (n-D real,
    §9), and ``UncodedRepetitionFFT`` (the non-MDS Remark-4 baseline).
    """

    n_workers: int

    @property
    def recovery_threshold(self) -> int:
        """How many responders the master must wait for (``m`` for every
        MDS plan -- the paper's optimum)."""
        ...

    @property
    def input_shape(self) -> tuple[int, ...]:
        """Core (unbatched) request shape."""
        ...

    @property
    def output_shape(self) -> tuple[int, ...]:
        """Core (unbatched) result shape -- real kinds differ from
        ``input_shape`` (half-spectrum vs time domain)."""
        ...

    @property
    def worker_shard_shape(self) -> tuple[int, ...]:
        """Per-worker payload shape: what ONE worker stores, transforms,
        and ships (the real kinds' is HALF the complex plans')."""
        ...

    def encode(self, x: jax.Array) -> jax.Array:
        """Input -> coded worker shards ``(*B, N, *worker_shard_shape)``."""
        ...

    def worker_compute(self, a: jax.Array) -> jax.Array:
        """The per-worker transform over the trailing shard axes; any
        leading (batch / worker) axes map through unchanged."""
        ...

    def decode(self, b, subset=None, mask=None):
        """Worker results -> output from any ``recovery_threshold``-subset
        of responders (``subset`` indices or boolean ``mask``)."""
        ...

    def run(self, x, subset=None, mask=None):
        """``decode(worker_compute(encode(x)))`` -- the single-process
        end-to-end reference path."""
        ...


@runtime_checkable
class MDSPlan(CodedPlan, Protocol):
    """A plan whose code is the (N, m) complex-RS MDS code: decodable from
    ANY ``m`` responders, and factorable into per-device encode (generator
    row x message) for mesh execution."""

    @property
    def m(self) -> int:
        """The storage-fraction parameter: each worker holds ``1/m`` of
        the input; also the recovery threshold."""
        ...

    @property
    def generator(self) -> jax.Array:
        """The ``(N, m)`` RS generator ``G[k, i] = omega_N^{ki}`` --
        independent of the transform length and kind, which is why one
        decode-matrix cache serves every service bucket."""
        ...

    def message(self, x: jax.Array) -> jax.Array:
        """Input -> the ``m`` uncoded message shards (interleave; plus
        the pack/fold stages of the real kinds)."""
        ...

    def postdecode(self, c_hat: jax.Array) -> jax.Array:
        """Decoded message-shard transforms -> final output (recombine;
        plus the split/unpack stages of the real kinds)."""
        ...


class MDSPlanBase:
    """Shared batched encode/decode/run for MDS-coded strategies.

    Subclasses provide the dataclass fields (``n_workers``, ``dtype``, ...,
    and ``backend``), the ``m`` / ``generator`` / shape properties, the
    unbatched stage cores ``_message1`` / ``_postdecode1``, and a
    trailing-axes ``worker_compute``.

    Backend dispatch (DESIGN.md §6): plans are constructed with
    ``backend="kernel"`` by default, which routes encode / worker /
    decode-apply through the Pallas kernel stack (interpret mode off-TPU).
    The rules:

    * kernels compute in f32 planes, so only ``complex64`` plans resolve to
      the kernel backend -- ``complex128`` (the numerics/reference tier)
      always resolves to the jnp oracle;
    * ``backend="reference"`` forces the jnp path at any dtype;
    * vmapped per-request decode keeps the jnp solve (the batched service
      decodes through its own decode-matrix cache instead, §6).
    """

    # -- stage cores supplied by the concrete plan ---------------------------
    def _message1(self, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    def _postdecode1(self, c_hat: jax.Array) -> jax.Array:
        raise NotImplementedError

    # -- decode-system hooks (DESIGN.md §13) ---------------------------------
    # For the plain MDS plans the decode system IS the encode system: the
    # (N, m) generator, solvable from any m responders.  Beyond-MDS
    # strategies reuse the whole batched decode machinery below by
    # overriding just these two: the communication-efficient plan's fold
    # makes each worker a row of the WIDER (N, m*q) code, so it decodes
    # against a different generator than it encodes with.
    @property
    def decode_generator(self) -> jax.Array:
        """Generator of the linear system decode solves (default: the
        encode generator)."""
        return self.generator

    @property
    def decode_width(self) -> int:
        """Number of responder rows decode needs -- the column count of
        ``decode_generator`` (default: ``m``)."""
        return self.m

    def decodable(self, mask=None) -> bool:
        """Host-side check: can the master finish from these responders?
        For (any-subset-decodable) MDS-style codes this is a pure count
        against ``recovery_threshold``."""
        if mask is None:
            return self.n_workers >= self.recovery_threshold
        return int(np.asarray(mask).sum()) >= self.recovery_threshold

    # -- backend dispatch ----------------------------------------------------
    @property
    def resolved_backend(self) -> str:
        """The execution engine this plan actually runs on: ``"kernel"``
        only when requested AND the dtype is kernel-eligible (c64)."""
        backend = getattr(self, "backend", "reference")
        if backend == "kernel" and ops.kernel_backend_supported(self.dtype):
            return "kernel"
        return "reference"

    def _fftn_worker(self, a: jax.Array, nd: int) -> jax.Array:
        """Backend-dispatched n-D FFT over the trailing ``nd`` axes --
        the shared worker body of the n-D and multi-input plans."""
        if self.resolved_backend == "kernel":
            return ops.make_kernel_fftn_fn(nd)(a)
        return jnp.fft.fftn(a, axes=tuple(range(-nd, 0)))

    def _ifftn_worker(self, a: jax.Array, nd: int) -> jax.Array:
        """Backend-dispatched n-D inverse FFT over the trailing ``nd``
        axes -- the worker body of the n-D real-output plan (DESIGN.md
        §9).  On the kernel backend it rides the forward four-step sweep
        via ``ifftn(a) = conj(fftn(conj(a))) / prod(L)``: sign flips on
        the imaginary plane, same kernels."""
        if self.resolved_backend == "kernel":
            scale = math.prod(a.shape[-nd:])
            return jnp.conj(
                ops.make_kernel_fftn_fn(nd)(jnp.conj(a))) / scale
        return jnp.fft.ifftn(a, axes=tuple(range(-nd, 0)))

    def _fft1_worker(self, a: jax.Array, inverse: bool = False) -> jax.Array:
        """Backend-dispatched 1-D (i)FFT along the last axis -- the shared
        worker body of the 1-D forward/real/inverse plans (DESIGN.md §7)."""
        if self.resolved_backend == "kernel":
            return ops.make_kernel_worker_fn(inverse=inverse)(a)
        fn = jnp.fft.ifft if inverse else jnp.fft.fft
        return fn(a, axis=-1)

    # -- batch plumbing ------------------------------------------------------
    def _map_batched(self, fn, arr: jax.Array, core_ndim: int, what: str):
        batch = batch_shape(arr, core_ndim, what)
        if not batch:
            return fn(arr)
        flat = arr.reshape((-1,) + arr.shape[len(batch):])
        out = jax.vmap(fn)(flat)
        return out.reshape(batch + out.shape[1:])

    # -- public pipeline -----------------------------------------------------
    def message(self, x: jax.Array) -> jax.Array:
        """Input -> uncoded message shards ``(*B, m, *worker_shard_shape)``."""
        return self._map_batched(
            self._message1, x, len(self.input_shape), "plan input")

    def encode(self, x: jax.Array) -> jax.Array:
        """Input -> coded worker shards.

        Reference backend: the O(N log N) zero-padded DFT encode.  Kernel
        backend: ONE Pallas ``G @ c`` matmul with the whole batch folded
        into the payload columns (no vmap-over-pallas, one launch per
        batch).
        """
        if self.resolved_backend == "kernel":
            c = self.message(x)                       # (*B, m, *shard)
            shard = tuple(self.worker_shard_shape)
            batch = c.shape[:c.ndim - 1 - len(shard)]
            payload = math.prod(shard) if shard else 1
            flat = c.reshape((-1, self.m, payload))
            folded = jnp.swapaxes(flat, 0, 1).reshape(self.m, -1)
            coded = ops.mds_apply(self.generator, folded)
            out = jnp.swapaxes(
                coded.reshape(self.n_workers, flat.shape[0], payload), 0, 1)
            return out.reshape(batch + (self.n_workers,) + shard)
        return self._map_batched(
            self._encode1, x, len(self.input_shape), "plan input")

    def _encode1(self, x: jax.Array) -> jax.Array:
        c = self._message1(x)
        return mds.encode_dft(c, self.n_workers).astype(self.dtype)

    def encode_dense(self, x: jax.Array) -> jax.Array:
        """Reference O(N*m) matrix encode (the tests' check of ``encode``)."""
        return self._map_batched(
            lambda xi: mds.encode(self.generator, self._message1(xi)),
            x, len(self.input_shape), "plan input")

    def postdecode(self, c_hat: jax.Array) -> jax.Array:
        return self._map_batched(
            self._postdecode1, c_hat, 1 + len(self.worker_shard_shape),
            "decoded shards")

    def decode(
        self,
        b: jax.Array,
        subset: Optional[jax.Array] = None,
        mask: Optional[jax.Array] = None,
        *,
        method: str = "auto",
    ) -> jax.Array:
        """Worker results -> output, per-request straggler handling.

        Exactly one of ``subset`` (responder indices, ``(*B, m)`` or shared
        ``(m,)``) or ``mask`` (availability, ``(*B, N)`` or shared ``(N,)``)
        may be given.  ``method`` selects the MDS decode path (DESIGN.md §4).
        """
        if subset is not None and mask is not None:
            raise ValueError("pass at most one of subset / mask")
        m = self.decode_width
        core = 1 + len(self.worker_shard_shape)
        batch = batch_shape(b, core, "worker results")
        use_kernel = self.resolved_backend == "kernel"
        if not batch:
            if subset is None:
                subset = (mds.first_available(jnp.asarray(mask), m)
                          if mask is not None else jnp.arange(m))
            return self._decode1(b, jnp.asarray(subset), method,
                                 use_kernel=use_kernel)

        flat = b.reshape((-1,) + b.shape[len(batch):])
        nb = flat.shape[0]
        if nb == 1:
            # batch of one (the service's single-submit bucket): skip vmap
            # so decode_auto's dispatch stays a real branch/static choice
            if subset is None:
                subset = (mds.first_available(
                    jnp.asarray(mask).reshape(-1)[-self.n_workers:], m)
                    if mask is not None else jnp.arange(m))
            out = self._decode1(flat[0], jnp.asarray(subset).reshape(m),
                                method, use_kernel=use_kernel)
            return out.reshape(batch + out.shape)
        # per-request subsets are traced under vmap, where decode_auto's
        # lax.cond would lower to a select that EXECUTES both decode paths
        # per request -- resolve auto to the backward-stable solve instead
        per_request_method = "solve" if method == "auto" else method
        if mask is not None:
            masks = jnp.broadcast_to(
                jnp.asarray(mask), batch + (self.n_workers,)).reshape(nb, -1)
            subsets = jax.vmap(lambda mk: mds.first_available(mk, m))(masks)
        elif subset is None:
            # shared contiguous default: keep it concrete so the fast-decode
            # dispatch stays static under vmap
            shared = jnp.arange(m)
            out = jax.vmap(lambda bi: self._decode1(bi, shared, method))(flat)
            return out.reshape(batch + out.shape[1:])
        else:
            subset = jnp.asarray(subset)
            if subset.ndim == 1:
                out = jax.vmap(
                    lambda bi: self._decode1(bi, subset, method))(flat)
                return out.reshape(batch + out.shape[1:])
            subsets = subset.reshape(nb, m)
        out = jax.vmap(
            lambda bi, si: self._decode1(bi, si, per_request_method))(
                flat, subsets)
        return out.reshape(batch + out.shape[1:])

    def _decode1(self, b: jax.Array, subset: jax.Array, method: str,
                 *, use_kernel: bool = False) -> jax.Array:
        if use_kernel and method == "auto":
            # kernel backend: decode-apply as an MXU matmul -- invert the
            # subset generator once (payload-independent) and stream the
            # responder rows through the Pallas cmatmul.  Rows outside the
            # subset are never read (straggler garbage stays out).
            rows = jnp.take(b, subset, axis=0)
            dmat = mds.subset_decode_matrix(
                self.decode_generator, subset).astype(self.dtype)
            c_hat = ops.mds_apply(dmat, rows)
            return self._postdecode1(c_hat)
        c_hat = mds.decode_auto(self.decode_generator, b, subset,
                                method=method)
        return self._postdecode1(c_hat)

    def run(
        self,
        x: jax.Array,
        subset: Optional[jax.Array] = None,
        mask: Optional[jax.Array] = None,
        *,
        method: str = "auto",
    ) -> jax.Array:
        b = self.worker_compute(self.encode(x))
        return self.decode(b, subset=subset, mask=mask, method=method)
