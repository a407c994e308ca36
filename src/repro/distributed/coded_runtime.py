"""shard_map execution of any MDS coded plan over a device mesh.

The paper's master/worker topology mapped to SPMD (DESIGN.md §3):

* **encode** -- each device holds the (replicated) message shards, computes
  only ITS coded shards: ``a_k = sum_i G[k,i] c_i`` (no collective; G rows
  are selected by ``axis_index``).  The message is produced host-side by
  ``plan.message`` (interleave), so the runtime works for every
  :class:`repro.core.plan.MDSPlan` -- 1-D, n-D, multi-input.
* **worker compute** -- per-device transform of its own shards, the hot
  loop.  ``plan.worker_compute`` acts on trailing shard axes, so the
  (batch, n_local) leading layout maps through unchanged.  Complex64 plans
  dispatch to the Pallas four-step kernel by default (interpret mode
  off-TPU, DESIGN.md §6); complex128 plans run the jnp oracle.
* **straggler mask** -- an explicit boolean input, per request when the
  input carries a batch axis.  In production the launcher populates it from
  collective timeouts; in tests/benchmarks the straggler simulator does.
  Masked workers' outputs are overwritten with ``masked_fill`` (0 by
  default; NaN in tests to *prove* decode never reads them).
* **decode** -- all-gather the worker results along the axis (the paper's
  fan-in to the master: exactly s coded symbols on the wire, the cut-set
  optimum of Remark 5), then every device runs the same masked MDS decode
  (fast-path dispatch per DESIGN.md §4; batched requests build per-mask
  Lagrange decode matrices IN-TRACE for ``m <= LAGRANGE_MAX_M``,
  DESIGN.md §8) + recombine.  Replicated decode wastes no wall-clock vs a
  physical master because the all-gather is the critical path either way.

``n_local = N // axis_size`` coded shards live on each device, so N need
not equal the device count (e.g. N=8 code on a 4-device axis).

The runtime is plan-generic by construction: every stage touches only
``plan.message`` / ``plan.worker_compute`` / ``plan.postdecode`` and the
``worker_shard_shape`` metadata, so the real-input and inverse plans of
DESIGN.md §7 (``CodedRFFT``/``CodedIFFT``/``CodedIRFFT``) and their n-D
generalizations of §9 (``CodedRFFTN``/``CodedIRFFTN``) run UNCHANGED:
their half-size packed shard shapes and per-request masks thread
through both shard_map stages exactly like the complex plans' (the real
kinds' wire payload per worker is half the c2c plan's at the same
``(s, m)``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial, wraps
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import mds
from repro.core.coded_fft import CodedFFT
from repro.core.plan import batch_shape
from repro.distributed.faults import FaultInjector, FaultPlan

__all__ = ["DistributedCodedPlan", "DistributedCodedFFT"]


def _full_f32_matmuls(method):
    """Trace ``method`` with full-f32 matmuls.

    TPU dots default to one bf16 pass, which puts the encode, decode and
    recombine contractions of a coded plan above its f32 error budget;
    CPU dots are unaffected."""
    @wraps(method)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return method(*args, **kwargs)

    return traced


@dataclasses.dataclass(frozen=True)
class DistributedCodedPlan:
    """Run any ``MDSPlan`` across a mesh axis with straggler masking.

    ``masked_fill`` is the value written into masked-out workers' result
    rows before they leave the device; the decode provably ignores those
    rows, which tests assert by setting it to NaN.
    """

    plan: object  # any repro.core.plan.MDSPlan
    mesh: Mesh
    axis: str = "workers"
    masked_fill: float = 0.0

    def __post_init__(self):
        size = self.mesh.shape[self.axis]
        if self.plan.n_workers % size != 0:
            raise ValueError(
                f"N={self.plan.n_workers} must be a multiple of axis "
                f"size {size}")

    @property
    def n_devices(self) -> int:
        return self.mesh.shape[self.axis]

    @property
    def n_local(self) -> int:
        return self.plan.n_workers // self.n_devices

    def exchange_bytes(self, batch: int, arg_bytes: int) -> tuple[int, int]:
        """``(broadcast, gather)``: the bytes one :meth:`run` over
        ``batch`` requests moves between devices, reckoned from the plan's
        shapes.  ``broadcast``: what the devices copy among themselves to
        replicate the arguments (``arg_bytes``), (D-1) x ``arg_bytes``
        whether one device holds them or each a slice.  ``gather``: what
        each device receives in the all-gather, (D-1)/D of the N coded
        results of every request."""
        d = self.n_devices
        results = (self.plan.n_workers * batch
                   * math.prod(self.plan.worker_shard_shape)
                   * jnp.dtype(self.plan.dtype).itemsize)
        return (d - 1) * arg_bytes, results * (d - 1) // d

    # ------------------------------------------------------------------
    @_full_f32_matmuls
    def run(self, x: jax.Array, mask: Optional[jax.Array] = None,
            *, fragment_mask: Optional[jax.Array] = None,
            method: str = "auto",
            faults: Optional[object] = None, round_idx: int = 0
            ) -> jax.Array:
        """End-to-end coded transform of ``x`` under the mesh.

        ``x``: ``(*B, *input_shape)``; ``mask``: bool ``(*B, N)`` or shared
        ``(N,)`` worker availability.  Default: all up.  Returns
        ``(*B, *output_shape)``.

        ``fragment_mask`` (plans with ``fragments > 1``, DESIGN.md §13):
        bool ``(*B, N, F)`` / ``(N, F)`` per-fragment availability -- a
        slow-but-alive worker contributes its finished prefix.  Combines
        with ``mask`` (a masked worker loses all its fragments).

        The strategy hooks (all optional, the base MDS plans use none):
        ``worker_encode_tensor`` ``(N, F, W)`` replaces per-worker
        generator rows, ``stored_shard_shape`` sizes the per-device
        buffer when a plan ships less than it stores, ``worker_compute_
        rows`` is the worker-index-aware compute (the comm-efficient
        fold), and ``decode_generator`` is the (possibly wider) system
        the master solves -- the gathered ``(N, F)`` results flatten to
        its ``N*F`` rows in ``f*N + w`` order.

        ``faults`` (opt-in hook, DESIGN.md §12): a
        :class:`~repro.distributed.faults.FaultPlan` or ``FaultInjector``
        projected onto ``round_idx``.  Kills fold into the availability
        mask host-side (a dead worker IS a masked worker); corrupt workers
        keep their mask bit but their device rows are algebraically
        garbled IN-TRACE before leaving the worker stage, so an unmasked
        decode that reads them yields visibly wrong output (what the
        Byzantine verifier exists to catch).  Delays are a no-op here: the
        all-gather is a synchronous collective that already waits for
        every participant.  With ``faults=None`` the trace is unchanged.
        """
        plan = self.plan
        n = plan.n_workers
        nf = getattr(plan, "fragments", 1)
        out_shard = tuple(plan.worker_shard_shape)
        stored = tuple(getattr(plan, "stored_shard_shape", out_shard))
        # what one decoded row / shipped fragment carries
        post_shard = out_shard[1:] if nf > 1 else out_shard
        payload = math.prod(post_shard)
        enc_t = getattr(plan, "worker_encode_tensor", None)
        if enc_t is None:
            enc_t = plan.generator[:, None, :]                # (N, 1, m)
        width = enc_t.shape[2]
        dec_g = getattr(plan, "decode_generator", None)
        if dec_g is None:
            dec_g = plan.generator
        k = dec_g.shape[1]
        n_rows = n * nf
        wc_rows = getattr(plan, "worker_compute_rows", None)

        batch = batch_shape(x, len(plan.input_shape), "plan input")
        if mask is None:
            mask = jnp.ones(batch + (n,), bool)
        corrupt = jnp.zeros((n,), bool)
        if faults is not None:
            injector = (FaultInjector(faults)
                        if isinstance(faults, FaultPlan) else faults)
            rf = injector.faults_for(round_idx)
            if rf.killed:
                dead = jnp.asarray([w in rf.killed for w in range(n)])
                mask = jnp.asarray(mask) & ~dead
            if rf.corrupt:
                corrupt = jnp.asarray(injector.corrupt_flags(n, round_idx))

        # host-side interleave -> (B, W, payload) flat message symbols
        c = plan.message(x).reshape((-1, width, math.prod(stored) // nf))
        nb = c.shape[0]
        wmask = jnp.broadcast_to(jnp.asarray(mask), batch + (n,)).reshape(nb, n)
        if fragment_mask is None:
            fmask = jnp.broadcast_to(wmask[:, :, None], (nb, n, nf))
        else:
            fmask = jnp.broadcast_to(
                jnp.asarray(fragment_mask), batch + (n, nf)
            ).reshape(nb, n, nf) & wmask[:, :, None]
        fill = jnp.asarray(self.masked_fill, c.dtype)

        # the worker axis stays LEADING through both shard_map stages: the
        # all-gather then tiles axis 0, which XLA:CPU's fft thunk tolerates
        # (gathering a non-leading axis forces a transposed layout onto the
        # worker FFT and trips its dim0-major RET_CHECK)
        @partial(
            shard_map, mesh=self.mesh,
            in_specs=(P(), P(), P()),
            out_specs=P(self.axis, None, None, None),
            check_vma=False,
        )
        def workers(c_rep, fmask_rep, corrupt_rep):
            # per-device fused encode+compute: each device forms only its
            # own coded shards from the replicated message symbols
            idx = jax.lax.axis_index(self.axis)
            rows = idx * self.n_local + jnp.arange(self.n_local)
            g_rows = jnp.take(enc_t, rows, axis=0)        # (n_local, F, W)
            a = jnp.einsum("nfw,bwp->nbfp", g_rows.astype(c_rep.dtype),
                           c_rep)
            a = a.reshape((self.n_local, nb) + stored)
            if wc_rows is not None:
                # worker-index-aware compute (the comm-efficient fold
                # weights depend on k): its contract puts the row axis at
                # -2 over the trailing 1-D shard
                b = jnp.moveaxis(
                    wc_rows(jnp.moveaxis(a, 0, -2), rows), -2, 0)
            else:
                b = plan.worker_compute(a)
            b = b.reshape(self.n_local, nb, nf, payload)
            # Byzantine rows: deterministic in-trace garbage (affine warp
            # of the true values -- "arbitrarily wrong", not just scaled,
            # and jit-stable, unlike a traced RNG draw would be)
            bad = jnp.take(corrupt_rep, rows)                 # (n_local,)
            b = jnp.where(bad[:, None, None, None], b * (-3.7) + 11.3, b)
            alive = jnp.take(fmask_rep, rows, axis=1)     # (nb, n_local, F)
            return jnp.where(
                jnp.moveaxis(alive, 0, 1)[:, :, :, None], b, fill)

        b = workers(c, fmask, corrupt)                    # (N, nb, F, payload)

        @partial(
            shard_map, mesh=self.mesh,
            in_specs=(P(self.axis, None, None, None), P()),
            out_specs=P(),
            check_vma=False,
        )
        def master(b_local, fmask_rep):
            # the paper's fan-in: gather the coded results to the master,
            # then flatten fragments into decode-system row order f*N + w
            b_all = jax.lax.all_gather(b_local, self.axis, tiled=True)
            b_all = jnp.moveaxis(b_all, 0, 2)             # (nb, F, N, p)
            b_all = b_all.reshape(nb, n_rows, payload)
            rmask = jnp.swapaxes(fmask_rep, 1, 2).reshape(nb, n_rows)

            def decode1(bi, mk, mth):
                subset = mds.first_available(mk, k)
                c_hat = mds.decode_auto(dec_g, bi, subset, method=mth)
                return plan.postdecode(c_hat.reshape((k,) + post_shard))

            if nb == 1:
                # single request: decode_auto's lax.cond stays a real branch
                return decode1(b_all[0], rmask[0], method)[None]
            if method == "auto" and k <= mds.LAGRANGE_MAX_M:
                # batched mask-to-weights (DESIGN.md §8): per-request
                # decode matrices from the closed-form Lagrange inversion,
                # built in-trace -- no vmapped linalg.solve, no host work
                # per novel mask.  The k responder rows are GATHERED before
                # the contraction, so the masked_fill rows (NaN in tests)
                # are provably never read.
                subsets = jax.vmap(
                    lambda mk: mds.first_available(mk, k))(rmask)
                inv = jax.vmap(
                    lambda sub: mds.lagrange_inverse(sub, n_rows,
                                                     b_all.dtype)
                )(subsets)
                rows = jnp.take_along_axis(
                    b_all, subsets[:, :, None], axis=1)
                c_hat = inv @ rows                        # (nb, k, payload)
                return jax.vmap(
                    lambda ch: plan.postdecode(
                        ch.reshape((k,) + post_shard))
                )(c_hat)
            # batched, pinned method: under vmap decode_auto's cond would
            # select-execute BOTH decode paths per request -- resolve auto
            # to the solve instead
            mth = "solve" if method == "auto" else method
            return jax.vmap(lambda bi, mk: decode1(bi, mk, mth))(
                b_all, rmask)

        out = master(b, fmask)                                # (nb, *out_shape)
        if not batch:
            return out[0]
        return out.reshape(batch + tuple(plan.output_shape))

    # ------------------------------------------------------------------
    @_full_f32_matmuls
    def run_sharded(self, x: jax.Array, mask: Optional[jax.Array] = None,
                    *, method: str = "auto") -> jax.Array:
        """Optimized 1-D pipeline (§Perf cell C): sharded-output decode.

        The baseline ``run`` realizes the paper's master literally: every
        chip all-gathers all N coded results (N/m x s symbols per chip)
        and runs the full decode.  But no consumer needs X replicated --
        so instead each chip receives only its OUTPUT COLUMNS of every
        worker's result via one all-to-all (s symbols total per chip,
        N/m x less wire), decodes the (m, L/P) column block, and
        recombines locally (twiddles depend on the absolute column index,
        taken from ``axis_index``).

        Specific to the 1-D :class:`CodedFFT` layout (column-sharded
        Cooley-Tukey output); other plans raise.  Returns the output
        matrix ``Xmat`` of shape ``(m, s/m)``, column-sharded over the
        worker axis; ``X = Xmat.reshape(s)`` (row-major), since
        ``Xmat[j, i] = X[j*(s/m) + i]``.
        """
        plan = self.plan
        if not isinstance(plan, CodedFFT):
            raise NotImplementedError(
                "run_sharded implements the 1-D Cooley-Tukey output layout; "
                f"got {type(plan).__name__} -- use run()")
        p_sz = self.mesh.shape[self.axis]
        ell = plan.shard_len
        if ell % p_sz != 0:
            raise ValueError(f"s/m={ell} must divide over {p_sz} devices")
        if mask is None:
            mask = jnp.ones((plan.n_workers,), bool)

        from repro.core.recombine import dft_matrix

        @partial(
            shard_map, mesh=self.mesh,
            in_specs=(P(), P()),
            out_specs=P(None, self.axis),
            check_vma=False,
        )
        def pipeline(x_rep, mask_rep):
            # fused interleave+encode: c[i, l] = x[i + l*m] is just the
            # transposed view of x.reshape(L, m), so the coded shard is one
            # strided einsum over x -- the materialized interleave copy
            # (2x s symbols of pure data movement) never exists (§Perf C2)
            idx = jax.lax.axis_index(self.axis)
            rows = idx * self.n_local + jnp.arange(self.n_local)
            g_rows = jnp.take(plan.generator, rows, axis=0)   # (n_local, m)
            xr = x_rep.astype(plan.dtype).reshape(ell, plan.m)
            a_local = jnp.einsum("lm,nm->nl", xr, g_rows.astype(plan.dtype))
            b_local = plan.resolved_worker_fn(a_local)        # (n_local, L)
            alive = jnp.take(mask_rep, rows)
            b_local = jnp.where(alive[:, None], b_local,
                                jnp.asarray(self.masked_fill, plan.dtype))
            # row-shards -> column-shards: THE one collective of the
            # optimized path (s symbols per chip vs N/m x s for all-gather)
            b_cols = jax.lax.all_to_all(
                b_local, self.axis, split_axis=1, concat_axis=0, tiled=True
            )                                                  # (N, L/P)
            subset = mds.first_available(mask_rep, plan.m)
            c_cols = mds.decode_auto(
                plan.generator, b_cols, subset, method=method)
            idx = jax.lax.axis_index(self.axis)
            cols = idx * (ell // p_sz) + jnp.arange(ell // p_sz)
            ki = jnp.outer(jnp.arange(plan.m), cols)
            w = jnp.exp(-2j * jnp.pi * ki / plan.s).astype(c_cols.dtype)
            f_m = dft_matrix(plan.m, c_cols.dtype)
            return f_m @ (c_cols * w)                          # (m, L/P)

        return pipeline(x.astype(plan.dtype), mask)

    # ------------------------------------------------------------------
    def lower(self, s_dtype=jnp.complex64, *, sharded: bool = False):
        """Lower for compile inspection (collective accounting)."""
        x = jax.ShapeDtypeStruct(tuple(self.plan.input_shape), s_dtype)
        mask = jax.ShapeDtypeStruct((self.plan.n_workers,), jnp.bool_)
        fn = self.run_sharded if sharded else self.run
        return jax.jit(fn).lower(x, mask)


# The 1-D name the seed exposed; the class has been generic since the
# CodedPlan refactor, so this is a pure alias.
DistributedCodedFFT = DistributedCodedPlan
