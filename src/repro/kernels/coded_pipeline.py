"""Pallas TPU kernel: the WHOLE coded-FFT bucket in one launch.

The batched service hot path (DESIGN.md §5/§6) is, per request,

    interleave -> MDS encode -> worker DFT -> MDS decode -> recombine

and every stage is either a (shared-matrix) matmul, a batched matmul
against per-request decode matrices, or an elementwise twiddle.  For
bucket shapes that fit VMEM there is no reason for ANY intermediate to
touch HBM: this kernel runs the coded core per batch block --

    t   = ((F_A @ c) * W) @ F_B               (four-step worker DFT of the
                                               m MESSAGE shards)
    b   = G @ t                               (MDS encode; commutes with
                                               the DFT, N/m flop saving)
    c^  = D_q @ b                             (per-request scatter decode
                                               matrices, stragglers = zero
                                               columns)
    X   = F_m @ (c^ * W_s)                    (c2c recombine butterfly)

-- every contraction keeps each shard's (A, B) tile layout, so the body
lowers inside a Mosaic kernel.  The relabelings around the core (the c2c
interleave ``c_i[j] = x[i + j*m]``, the r2c pair packing, the c2r
adjoint message butterfly, and the final unscramble of the four-step
order) run as XLA ops in the same jitted executor: they move the shard
index out of the lane axis, which a TPU kernel cannot do cheaply.

Stage-level kernels (fourstep_fft.py, cmatmul.py, recombine.py) remain the
fallback for bucket shapes whose working set exceeds VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cmatmul import bcmatmul_body, cmatmul_body
from repro.kernels.fourstep_fft import (
    COMPILER_PARAMS,
    _HIGHEST,
    _even_divisor,
    encode_fourstep_body,
    shard_contract,
    stage1_body,
    stage2_body,
)

__all__ = [
    "lagrange_planes_body",
    "subsets_from_masks_body",
    "interleave_planes",
    "unscramble_planes",
    "coded_core_body",
    "bucket_body",
    "bucket_body_masked",
    "bucket_body_fftworker",
    "coded_fft_bucket_masked",
    "coded_fft_bucket_streaming_masked",
    "pack_real_planes",
    "half_postdecode_body",
    "rbucket_body",
    "rbucket_body_masked",
    "rbucket_body_fftworker",
    "coded_rfft_bucket_masked",
    "ir_message_body",
    "ir_unpack_body",
    "irbucket_body",
    "irbucket_body_masked",
    "irbucket_body_fftworker",
    "coded_irfft_bucket_masked",
]

# ================================== device-resident decode matrices (§8)
#
# The closed-form Lagrange inversion of core/mds.py restated on f32 planes
# with ONLY Mosaic-expressible ops -- iota, elementwise trig, lane slices
# and lane broadcasts of (bq, m) rows, one static-unrolled m-step product
# -- so the bucket kernels can build every request's decode matrix IN VMEM
# from its responder subset.  No gathers: node powers come from the
# root-of-unity closed form, the deflation is synthetic division row by
# row, and the scatter a subset-vs-iota one-hot sum.


@functools.lru_cache(maxsize=None)
def _locator_perm(m: int) -> np.ndarray:
    # balanced (shuffled static) multiplication order keeps the locator's
    # partial products O(1) -- same argument as mds.lagrange_decode_coeffs
    return np.random.default_rng(0).permutation(m)


def lagrange_planes_body(subsets, n):
    """Per-request decode matrices from responder subsets, on planes.

    ``subsets``: ``(bq, m)`` int32 -- each request's first-m available
    workers.  Returns ``(ivr, ivi, dr, di)``: the compact ``(bq, m, m)``
    inverse planes (the gathered-decode form the direct executor wants) and
    the scatter ``(bq, m, n)`` planes with zero straggler columns (the MXU
    form the fused kernels contract against).  O(m^2 n) work per request;
    every op works on ``(bq, m)`` / ``(bq, n)`` rows and lowers inside a
    Mosaic kernel body.
    """
    bq, m = subsets.shape
    f32 = jnp.float32
    subsets = subsets.astype(jnp.int32)
    tau = 2.0 * np.pi / n
    angn = (-tau) * (subsets % n).astype(f32)
    nr, ni = jnp.cos(angn), jnp.sin(angn)                   # nodes (bq, m)
    # locator A(z) = prod (z - x_j): m static-unrolled shift-multiply steps
    ar = jnp.concatenate([jnp.ones((bq, 1), f32), jnp.zeros((bq, m), f32)], 1)
    ai = jnp.zeros((bq, m + 1), f32)
    zero = jnp.zeros((bq, 1), f32)
    for i in _locator_perm(m):
        sr = jnp.concatenate([zero, ar[:, :m]], axis=1)     # z * A(z)
        si = jnp.concatenate([zero, ai[:, :m]], axis=1)
        xr_, xi_ = nr[:, i:i + 1], ni[:, i:i + 1]
        ar, ai = sr - (xr_ * ar - xi_ * ai), si - (xr_ * ai + xi_ * ar)
    # synthetic division: row i of q holds coefficient i of A(z)/(z - x_j)
    # for every node j at once -- q_{m-1} = a_m, q_{i-1} = a_i + x_j q_i
    qr = [None] * m
    qi = [None] * m
    qr[m - 1] = jnp.broadcast_to(ar[:, m:], (bq, m))
    qi[m - 1] = jnp.broadcast_to(ai[:, m:], (bq, m))
    for i in range(m - 1, 0, -1):
        qr[i - 1] = ar[:, i:i + 1] + (nr * qr[i] - ni * qi[i])
        qi[i - 1] = ai[:, i:i + 1] + (nr * qi[i] + ni * qr[i])
    # A'(x_j) = Q_j(x_j) = sum_i q[i, j] x_j^i with exact node powers
    # x_j^i = omega^(subset_j * i mod n)
    apr = jnp.zeros((bq, m), f32)
    api = jnp.zeros((bq, m), f32)
    for i in range(m):
        ang = (-tau) * ((subsets * i) % n).astype(f32)
        pr, pi_ = jnp.cos(ang), jnp.sin(ang)
        apr = apr + (qr[i] * pr - qi[i] * pi_)
        api = api + (qr[i] * pi_ + qi[i] * pr)
    den = apr * apr + api * api
    cr, ci = apr / den, -api / den                          # 1 / A'(x_j)
    # scatter inverse columns to worker slots: D[:, subset_j] = inv[:, j]
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (bq, n), 1)
    onehot = [(subsets[:, j:j + 1] == k_iota).astype(f32) for j in range(m)]
    ivr, ivi, dr, di = [], [], [], []
    for i in range(m):
        vr = qr[i] * cr - qi[i] * ci
        vi = qr[i] * ci + qi[i] * cr                        # inv row i
        ivr.append(vr)
        ivi.append(vi)
        dr.append(sum(vr[:, j:j + 1] * onehot[j] for j in range(m)))
        di.append(sum(vi[:, j:j + 1] * onehot[j] for j in range(m)))
    stack = functools.partial(jnp.stack, axis=1)
    return stack(ivr), stack(ivi), stack(dr), stack(di)


def subsets_from_masks_body(masks, m):
    """First-m-available responder subsets from raw masks, Mosaic-safe.

    ``masks``: ``(bq, n)`` availability planes (any dtype; nonzero =
    responded).  Returns ``(bq, m)`` int32 -- each request's first m
    available worker indices in ascending order, matching the host-side
    ``ops.mask_subsets`` (stable argsort).  No sort/cumsum primitives:
    the running count of available workers before slot k is one
    triangular-ones matmul, selection is a rank-vs-iota one-hot, and the
    index extraction a masked reduction -- every op lowers in a kernel
    body, so the host ships raw masks and ZERO decode metadata.
    Short rows (fewer than m available) mirror the argsort contract
    exactly: slots past the responder count fill with the FIRST
    non-responders in index order, keeping the Lagrange nodes distinct
    (the whole-bucket kernel computes every worker spectrum anyway, so
    such a row still decodes the true transform -- masks are simulated
    straggler metadata, not missing data).
    """
    bq, n = masks.shape
    f32 = jnp.float32
    dot = functools.partial(jnp.dot, precision=_HIGHEST,
                            preferred_element_type=f32)
    mk = (masks.astype(f32) > 0.5).astype(f32)
    kp = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    kk = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    tri = (kp < kk).astype(f32)                  # strictly-lower ones
    rank = dot(mk, tri)                          # (bq, n) availables before k
    rank_nr = dot(1.0 - mk, tri)                 # ... and unavailables
    cnt = jnp.sum(mk, axis=1)[:, None, None]     # (bq, 1, 1) responder count
    jj = jax.lax.broadcasted_iota(jnp.int32, (bq, m, n), 1).astype(f32)
    kidx = jax.lax.broadcasted_iota(jnp.int32, (bq, m, n), 2).astype(f32)
    sel = (rank[:, None, :] == jj).astype(f32) * mk[:, None, :]
    sel += ((rank_nr[:, None, :] == jj - cnt).astype(f32)
            * (1.0 - mk[:, None, :]))
    return jnp.sum(sel * kidx, axis=2).astype(jnp.int32)


def _masked_decode_planes(masks, m, n):
    """Raw ``(bq, n)`` masks -> scatter decode planes, in-body."""
    _, _, dr, di = lagrange_planes_body(subsets_from_masks_body(masks, m), n)
    return dr, di


# ======================================================= layout relabels
def interleave_planes(xr, xi, m, a, b):
    """Request planes ``(q, s)`` -> message planes ``(q, m, A, B)``.

    ``c_i[j] = x[i + j*m]`` viewed as the four-step matrix
    ``M_i[a, b] = c_i[a*B + b]``: a pure relabeling, run as XLA before the
    kernel launch so the shard index never sits in the lane axis.
    """
    q = xr.shape[0]
    view = lambda t: jnp.swapaxes(t.reshape(q, a * b, m), 1, 2).reshape(
        q, m, a, b)
    return view(xr), view(xi)


def unscramble_planes(yr):
    """Scrambled four-step planes ``(q, k, A, B)`` -- ``out[c, d] =
    X[c + d*A]`` -- to natural flat order ``(q, k, A*B)``."""
    q, k, a, b = yr.shape
    return jnp.swapaxes(yr, -1, -2).reshape(q, k, a * b)


# ================================================== the coded core (c2c)
def coded_core_body(cr, ci, dr, di, gr, gi, far, fai, wr, wi, fbr, fbi,
                    inverse=False):
    """Encode -> worker DFT -> decode on one block of message planes.

    ``cr, ci``: (bq, m, A, B) message planes; ``dr, di``: (bq, m, N)
    per-request scatter decode planes; ``gr, gi``: (N, m) generator.
    Returns the decoded (bq, m, A, B) shard spectra in the scrambled
    four-step order (decode only mixes the shard axis, so the payload
    order is carried through untouched).  ``inverse=True`` makes the
    worker an ifft through the conj trick on planes -- the caller passes
    conjugated message and generator planes and the worker output is
    conjugated and scaled by 1/(A*B) here, before the decode.
    """
    er, ei = encode_fourstep_body(cr, ci, gr, gi, far, fai, wr, wi,
                                  fbr, fbi)                 # (bq, n, a, b)
    if inverse:
        n2 = cr.shape[2] * cr.shape[3]
        er, ei = er / n2, ei / (-n2)
    return shard_contract(dr, di, er, ei)


def recombine_shards_body(hr, hi, twr, twi, fmr, fmi):
    """c2c recombine on scrambled planes: twiddle ``twr`` (m, A, B), then
    the length-m DFT across the shard axis."""
    bq, m = hr.shape[:2]
    ur = hr * twr - hi * twi
    ui = hr * twi + hi * twr
    return shard_contract(jnp.broadcast_to(fmr, (bq, m, m)),
                          jnp.broadcast_to(fmi, (bq, m, m)), ur, ui)


def bucket_body(cr, ci, dr, di, gr, gi, far, fai, wr, wi, fbr, fbi,
                twr, twi, fmr, fmi):
    """The full c2c pipeline on one (bq, m, A, B) block of message planes.

    Shared between the Pallas kernel (one block per grid step, everything
    VMEM-resident) and the off-TPU direct path (full batch as straight
    XLA, DESIGN.md §6).  ``twr/twi`` is the recombine twiddle
    pre-permuted to the scrambled payload order and viewed (m, A, B)
    (``ops`` builds it), so the only unscramble is the one at the output.
    Returns scrambled (bq, m, A, B) output planes: ``X_q[j*L + c + d*A] =
    out[q, j, c, d]``.
    """
    hr, hi = coded_core_body(cr, ci, dr, di, gr, gi, far, fai, wr, wi,
                             fbr, fbi)
    return recombine_shards_body(hr, hi, twr, twi, fmr, fmi)


def bucket_body_masked(cr, ci, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
                       twr, twi, fmr, fmi):
    """:func:`bucket_body` with the decode matrices built IN the body.

    Takes each request's raw ``(n,)`` responder mask instead of
    precomputed decode planes: the first-m subset is selected in-kernel
    (:func:`subsets_from_masks_body`) and the Lagrange weights formed in
    VMEM (DESIGN.md §8) and contracted immediately -- neither the subset
    indices nor the ``(bq, m, N)`` matrices exist outside the kernel's
    working set.
    """
    n, m = gr.shape
    dr, di = _masked_decode_planes(masks, m, n)
    return bucket_body(cr, ci, dr, di, gr, gi, far, fai, wr, wi, fbr, fbi,
                       twr, twi, fmr, fmi)


def _core_kernel(inverse, n_rec, *refs):
    cr_ref, ci_ref = refs[:2]
    mk = refs[2][...]
    rest = refs[3:]
    planes = [r[...] for r in rest[:8 + n_rec]]
    or_ref, oi_ref = rest[8 + n_rec:]
    gr, gi = planes[:2]
    n, m = gr.shape
    dr, di = _masked_decode_planes(mk.reshape(mk.shape[0], n), m, n)
    hr, hi = coded_core_body(cr_ref[...], ci_ref[...], dr, di, *planes[:8],
                             inverse=inverse)
    if n_rec:
        hr, hi = recombine_shards_body(hr, hi, *planes[8:])
    or_ref[...] = hr
    oi_ref[...] = hi


def _core_call(cr, ci, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
               recombine=(), *, inverse=False, block_q=1, interpret=False,
               name):
    """One Pallas launch of the coded core over (q, m, A, B) message planes.

    ``masks``: (q, N) raw responder masks; each grid step builds its
    requests' decode planes from them in VMEM (DESIGN.md §8).
    ``recombine``: the c2c ``(twr, twi, fmr, fmi)`` planes, or empty for
    the real kinds, whose butterflies run as XLA around the launch.
    Every block keeps the batch in a leading axis and the (A, B) tile
    whole, which is what the TPU block-shape rule needs.
    """
    q, m, a, b = cr.shape
    n = gr.shape[0]
    masks = masks.astype(cr.dtype).reshape(q, 1, n)
    block_q = max(1, min(block_q, q))

    def blk(*shape):
        return pl.BlockSpec((block_q, *shape),
                            lambda i, r=len(shape): (i,) + (0,) * r)

    def const(x):
        return pl.BlockSpec(x.shape, lambda i, r=x.ndim: (0,) * r)

    shared = [gr, gi, far, fai, wr, wi, fbr, fbi, *recombine]
    return pl.pallas_call(
        functools.partial(_core_kernel, inverse, len(recombine)),
        grid=(pl.cdiv(q, block_q),),
        in_specs=[blk(m, a, b)] * 2 + [blk(1, n)]
        + [const(x) for x in shared],
        out_specs=[blk(m, a, b)] * 2,
        out_shape=[jax.ShapeDtypeStruct((q, m, a, b), cr.dtype)] * 2,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name=name,
    )(cr, ci, masks, *shared)


def _bucket_call(xr, xi, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
                 twr, twi, fmr, fmi, block_q, interpret, name):
    q, s = xr.shape
    m = gr.shape[1]
    a, b = far.shape[0], fbr.shape[0]
    cr, ci = interleave_planes(xr, xi, m, a, b)
    rec = (twr.reshape(m, a, b), twi.reshape(m, a, b), fmr, fmi)
    outr, outi = _core_call(cr, ci, masks, gr, gi, far, fai, wr, wi,
                            fbr, fbi, rec, block_q=block_q,
                            interpret=interpret, name=name)
    return (unscramble_planes(outr).reshape(q, s),
            unscramble_planes(outi).reshape(q, s))


def coded_fft_bucket_masked(xr, xi, masks, gr, gi, far, fai, wr, wi,
                            fbr, fbi, twr, twi, fmr, fmi, *, block_q: int = 1,
                            interpret: bool = False):
    """Fused bucket pipeline: request planes -> output spectrum planes.

    ``xr, xi``: (q, s) request planes; ``masks``: (q, N) raw responder
    masks; ``gr, gi``: (N, m) generator; ``far/wr/fbr``: four-step
    DFT/twiddle planes for L = s/m = A*B; ``twr``: (m, L) recombine
    twiddle in the scrambled payload order; ``fmr``: (m, m) DFT.  Returns
    (q, s) planes of ``fft(x, axis=-1)`` decoded from each request's
    first-m responders.  Subset selection AND the per-request Lagrange
    decode matrices run INSIDE the kernel (VMEM-resident, DESIGN.md §8),
    so the host ships the availability bits it already has.  The
    interleave and the final unscramble are XLA relabels around the one
    kernel launch.
    """
    return _bucket_call(xr, xi, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
                        twr, twi, fmr, fmi, block_q, interpret,
                        "coded_fft_bucket_masked")


def bucket_body_fftworker(xr, xi, dvr, dvi, subsets, gr, gi,
                          twr, twi, fmr, fmi):
    """Direct-mode (off-TPU) bucket pipeline.

    Identical stage structure to :func:`bucket_body` -- planar ingress,
    fused encode-after-transform on the m MESSAGE shards, per-request
    decode matrices, fused recombine -- with two platform-appropriate
    lowerings the Mosaic kernel cannot express:

    * the worker DFT runs on the host FFT (``jnp.fft``) instead of the
      four-step matmul factorization, which trades ~2x the flops for MXU
      shape on TPU but has no business on CPU scalar units;
    * decode gathers the m responder rows (``subsets``) and applies the
      COMPACT ``(m, m)`` inverses ``dvr/dvi`` -- dynamic gathers are cheap
      here and halve the decode contraction vs the scatter form.

    On TPU the Pallas bucket kernel above runs instead (DESIGN.md §6).
    """
    bq, s = xr.shape
    n, m = gr.shape
    ell = s // m
    # interleave on planes: c_i[j] = x[i + j*m]
    cr = jnp.transpose(xr.reshape(bq, ell, m), (0, 2, 1))
    ci = jnp.transpose(xi.reshape(bq, ell, m), (0, 2, 1))
    # worker DFT of the m message shards (linear -> commutes with encode)
    spec = jnp.fft.fft(cr + 1j * ci, axis=-1)
    sr = jnp.real(spec).astype(xr.dtype)
    si = jnp.imag(spec).astype(xr.dtype)
    # MDS encode: one shared matmul, batch folded into the columns
    tr = jnp.transpose(sr, (1, 0, 2)).reshape(m, bq * ell)
    ti = jnp.transpose(si, (1, 0, 2)).reshape(m, bq * ell)
    er, ei = cmatmul_body(gr, gi, tr, ti)
    er = jnp.transpose(er.reshape(n, bq, ell), (1, 0, 2))  # (bq, N, L)
    ei = jnp.transpose(ei.reshape(n, bq, ell), (1, 0, 2))
    # decode: gather each request's m responder rows, compact batched matmul
    idx = subsets[:, :, None]
    rr = jnp.take_along_axis(er, idx, axis=1)              # (bq, m, L)
    ri = jnp.take_along_axis(ei, idx, axis=1)
    hr, hi = bcmatmul_body(dvr, dvi, rr, ri)
    # recombine twiddle (natural order) + length-m DFT
    ur = hr * twr[None] - hi * twi[None]
    ui = hr * twi[None] + hi * twr[None]
    ur = jnp.transpose(ur, (1, 0, 2)).reshape(m, bq * ell)
    ui = jnp.transpose(ui, (1, 0, 2)).reshape(m, bq * ell)
    outr, outi = cmatmul_body(fmr, fmi, ur, ui)
    return (jnp.transpose(outr.reshape(m, bq, ell), (1, 0, 2)).reshape(bq, s),
            jnp.transpose(outi.reshape(m, bq, ell), (1, 0, 2)).reshape(bq, s))


# ===================================================== real-input (r2c) path
#
# The r2c bucket (DESIGN.md §7) carries HALF-length payloads through the
# identical stage structure: the real request is relabeled into pair-packed
# message shards z_i[j] = x[i + 2jm] + 1j*x[i + (2j+1)m] (free on planes --
# the real input IS the plane), the fused encode+worker transforms L/2-point
# shards, decode is the same batched matmul (the coded core above), and the
# one NEW stage is the symmetry-aware postdecode: split each packed spectrum into the rfft of its
# real shard (conjugation = a sign flip on the imag plane, real-linear),
# Hermitian-extend, and recombine only the m//2+1 butterfly rows that feed
# the non-redundant bins X[0..s/2].


def pack_real_planes(xr, m):
    """Real request plane -> packed message planes, pure relabeling.

    ``(bq, s)`` real -> ``((bq, m, L/2), (bq, m, L/2))`` planes of
    ``z_i[j] = x[i + 2jm] + 1j*x[i + (2j+1)m]``.
    """
    bq, s = xr.shape
    if s < 2 * m or s % (2 * m) != 0:
        # same documented contract as core.rfft.require_even_shards (the
        # kernel layer never imports upward into repro.core) -- fail the
        # trace with the constraint instead of an opaque reshape error
        raise ValueError(
            f"real packing needs 2m | s (an even shard length s/m): "
            f"got s={s}, m={m}")
    n2 = s // m // 2
    x3 = xr.reshape(bq, n2, 2, m)
    zr = jnp.transpose(x3[:, :, 0, :], (0, 2, 1))
    zi = jnp.transpose(x3[:, :, 1, :], (0, 2, 1))
    return zr, zi


def half_postdecode_body(hr, hi, swr, swi, twr, twi, fhr, fhi, s):
    """Decoded packed spectra -> half-spectrum output planes.

    ``hr, hi``: ``(bq, m, L/2)`` NATURAL-order planes of ``fft(z_i)``;
    ``swr, swi``: ``(1, L/2+1)`` split twiddle ``omega_L^p``; ``twr, twi``:
    ``(m, L)`` recombine twiddle; ``fhr, fhi``: ``(m//2+1, m)`` DFT rows.
    Returns ``(bq, s//2+1)`` planes of ``rfft(x)``.  Conjugation is a sign
    flip on the imag plane, so every step is f32-plane-native.
    """
    bq, m, n2 = hr.shape
    ell = 2 * n2
    # split butterfly: Zext[p] = Z[p mod n2], Zrev[p] = conj(Zext[n2-p])
    hre = jnp.concatenate([hr, hr[..., :1]], axis=-1)
    hie = jnp.concatenate([hi, hi[..., :1]], axis=-1)
    rre = jnp.flip(hre, axis=-1)
    rie = -jnp.flip(hie, axis=-1)
    er = 0.5 * (hre + rre)
    ei = 0.5 * (hie + rie)
    our = 0.5 * (hie - rie)
    oui = -0.5 * (hre - rre)
    sw_r = swr[0][None, None, :]
    sw_i = swi[0][None, None, :]
    cr = er + our * sw_r - oui * sw_i            # C = E + O * omega_L^p
    ci = ei + our * sw_i + oui * sw_r            # (bq, m, n2+1)
    # Hermitian extension: C[L-p] = conj(C[p])
    cfr = jnp.concatenate([cr, jnp.flip(cr[..., 1:n2], axis=-1)], axis=-1)
    cfi = jnp.concatenate([ci, -jnp.flip(ci[..., 1:n2], axis=-1)], axis=-1)
    # recombine twiddle + the m//2+1 non-redundant DFT rows
    ur = cfr * twr[None] - cfi * twi[None]
    ui = cfr * twi[None] + cfi * twr[None]
    ur = jnp.transpose(ur, (1, 0, 2)).reshape(m, bq * ell)
    ui = jnp.transpose(ui, (1, 0, 2)).reshape(m, bq * ell)
    outr, outi = cmatmul_body(fhr, fhi, ur, ui)  # (m//2+1, bq*L)
    rows = m // 2 + 1
    sh = s // 2 + 1
    outr = outr.reshape(rows, bq, ell).transpose(1, 0, 2).reshape(bq, -1)
    outi = outi.reshape(rows, bq, ell).transpose(1, 0, 2).reshape(bq, -1)
    return outr[:, :sh], outi[:, :sh]


def _packed_views(xr, m, a, b):
    zr, zi = pack_real_planes(xr, m)
    q = xr.shape[0]
    return zr.reshape(q, m, a, b), zi.reshape(q, m, a, b)


def rbucket_body(xr, dr, di, gr, gi, far, fai, wr, wi, fbr, fbi,
                 swr, swi, twr, twi, fhr, fhi, s):
    """The full r2c pipeline on a (bq, s) block of REAL requests.

    The c2c coded core on half-length payloads (L/2 = A*B four-step
    planes) between the pair-packing relabel and the symmetry postdecode.
    The scrambled four-step order is undone BEFORE the butterfly -- the
    split needs natural reversed indexing.  The compiled path runs the
    same three steps with the core as one Pallas launch
    (:func:`coded_rfft_bucket_masked`).
    """
    m = gr.shape[1]
    a, b = far.shape[0], fbr.shape[0]
    zr, zi = _packed_views(xr, m, a, b)
    hr, hi = coded_core_body(zr, zi, dr, di, gr, gi, far, fai, wr, wi,
                             fbr, fbi)
    return half_postdecode_body(unscramble_planes(hr), unscramble_planes(hi),
                                swr, swi, twr, twi, fhr, fhi, s)


def rbucket_body_masked(xr, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
                        swr, swi, twr, twi, fhr, fhi, s):
    """:func:`rbucket_body` with in-kernel subset selection + in-VMEM
    Lagrange decode matrices (cf. :func:`bucket_body_masked`)."""
    n, m = gr.shape
    dr, di = _masked_decode_planes(masks, m, n)
    return rbucket_body(xr, dr, di, gr, gi, far, fai, wr, wi, fbr, fbi,
                        swr, swi, twr, twi, fhr, fhi, s)


def rbucket_body_fftworker(xr, dvr, dvi, subsets, gr, gi,
                           swr, swi, twr, twi, fhr, fhi, s):
    """Direct-mode (off-TPU) r2c bucket: platform-FFT worker on the packed
    half-length shards, gathered compact decode (cf.
    :func:`bucket_body_fftworker`), symmetry postdecode."""
    bq, s_ = xr.shape
    n, m = gr.shape
    n2 = s // m // 2
    zr, zi = pack_real_planes(xr, m)                   # (bq, m, n2)
    spec = jnp.fft.fft(zr + 1j * zi, axis=-1)
    sr = jnp.real(spec).astype(xr.dtype)
    si = jnp.imag(spec).astype(xr.dtype)
    tr = jnp.transpose(sr, (1, 0, 2)).reshape(m, bq * n2)
    ti = jnp.transpose(si, (1, 0, 2)).reshape(m, bq * n2)
    er, ei = cmatmul_body(gr, gi, tr, ti)
    er = jnp.transpose(er.reshape(n, bq, n2), (1, 0, 2))   # (bq, N, n2)
    ei = jnp.transpose(ei.reshape(n, bq, n2), (1, 0, 2))
    idx = subsets[:, :, None]
    rr = jnp.take_along_axis(er, idx, axis=1)
    ri = jnp.take_along_axis(ei, idx, axis=1)
    hr, hi = bcmatmul_body(dvr, dvi, rr, ri)
    return half_postdecode_body(hr, hi, swr, swi, twr, twi, fhr, fhi, s)


def _rbucket_call(xr, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
                  swr, swi, twr, twi, fhr, fhi, s, block_q, interpret, name):
    m = gr.shape[1]
    a, b = far.shape[0], fbr.shape[0]
    zr, zi = _packed_views(xr, m, a, b)
    hr, hi = _core_call(zr, zi, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
                        block_q=block_q, interpret=interpret, name=name)
    return half_postdecode_body(unscramble_planes(hr), unscramble_planes(hi),
                                swr, swi, twr, twi, fhr, fhi, s)


def coded_rfft_bucket_masked(xr, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
                             swr, swi, twr, twi, fhr, fhi, s, *,
                             block_q: int = 1, interpret: bool = False):
    """Fused r2c bucket pipeline: real request planes -> half-spectrum
    planes, the coded core as one Pallas launch.

    ``xr``: (q, s) REAL request plane (no imag plane exists); ``masks``:
    (q, N) raw responder masks, decoded in VMEM per grid step (DESIGN.md
    §8); ``far/wr/fbr``: four-step planes for the HALF length L/2 = A*B;
    ``swr``: (1, L/2+1) split twiddle; ``twr``: (m, L) recombine twiddle;
    ``fhr``: (m//2+1, m) DFT rows.  Returns (q, s//2+1) planes of
    ``rfft(x, axis=-1)``.  The pair packing and the symmetry butterfly
    (index reversal, odd-length edges) run as XLA around the launch.
    """
    return _rbucket_call(xr, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
                         swr, swi, twr, twi, fhr, fhi, s, block_q, interpret,
                         "coded_rfft_bucket_masked")


# ===================================================== real-output (c2r) path
def ir_message_body(yr, yi, fpr, fpi, ctwr, ctwi, pwr, pwi, s, m):
    """c2r message stage on planes (the ADJOINT of the r2c postdecode).

    ``yr, yi``: (bq, s//2+1) half-spectrum request planes.  Hermitian-
    extends (endpoint imag parts dropped, matching numpy.irfft), applies
    the adjoint recombine butterfly (``fpr``: (m, m) +sign DFT planes,
    ``ctwr``: (m, L) conjugate twiddle), and packs each per-shard Hermitian
    half spectrum (``pwr``: (1, L/2+1) pack twiddle ``omega_L^{-p}``
    conjugate) into the (bq, m, L/2) packed message planes workers ifft.
    """
    bq, h = yr.shape
    ell = s // m
    n2 = ell // 2
    zeros = jnp.zeros((bq, 1), yr.dtype)
    midr, midi = yr[:, 1:h - 1], yi[:, 1:h - 1]
    fullr = jnp.concatenate(
        [yr[:, :1], midr, yr[:, h - 1:], jnp.flip(midr, axis=-1)], axis=-1)
    fulli = jnp.concatenate(
        [zeros, midi, zeros, -jnp.flip(midi, axis=-1)], axis=-1)   # (bq, s)
    xr3 = jnp.transpose(fullr.reshape(bq, m, ell), (1, 0, 2)).reshape(m, -1)
    xi3 = jnp.transpose(fulli.reshape(bq, m, ell), (1, 0, 2)).reshape(m, -1)
    fr_, fi_ = cmatmul_body(fpr, fpi, xr3, xi3)            # +sign m-DFT
    foldr = jnp.transpose(fr_.reshape(m, bq, ell), (1, 0, 2))
    foldi = jnp.transpose(fi_.reshape(m, bq, ell), (1, 0, 2))
    tr = foldr * ctwr[None] - foldi * ctwi[None]
    ti = foldr * ctwi[None] + foldi * ctwr[None]           # (bq, m, L)
    # pack_half on planes: E + 1j * (0.5*(M - conj(M_rev)) * omega_L^{+p})
    mr, mi = tr[..., :n2 + 1], ti[..., :n2 + 1]
    rvr = jnp.flip(mr, axis=-1)
    rvi = -jnp.flip(mi, axis=-1)
    er = 0.5 * (mr + rvr)
    ei = 0.5 * (mi + rvi)
    dr_ = 0.5 * (mr - rvr)
    di_ = 0.5 * (mi - rvi)
    pw_r = pwr[0][None, None, :]
    pw_i = pwi[0][None, None, :]
    our = dr_ * pw_r - di_ * pw_i
    oui = dr_ * pw_i + di_ * pw_r
    zr = (er - oui)[..., :n2]
    zi = (ei + our)[..., :n2]
    return zr, zi                                          # (bq, m, L/2)


def ir_unpack_body(hr, hi):
    """Decoded packed interleave planes -> real output plane.

    ``hr, hi``: (bq, m, L/2) planes of ``ifft(z_i)`` where
    ``z_i[j] = o_i[2j] + 1j*o_i[2j+1]`` times ``m``.  Returns (bq, s).
    """
    bq, m, n2 = hr.shape
    ell = 2 * n2
    op = jnp.stack([hr, hi], axis=-1).reshape(bq, m, ell) / m
    return jnp.transpose(op, (0, 2, 1)).reshape(bq, m * ell)


def _ir_message_views(yr, yi, fpr, fpi, ctwr, ctwi, pwr, pwi, s, m, a, b):
    # conj of the packed message planes: the first half of the conj trick
    # ifft(G @ z) = conj(fft(conj(G) @ conj(z))) / (L/2)
    zr, zi = ir_message_body(yr, yi, fpr, fpi, ctwr, ctwi, pwr, pwi, s, m)
    q = yr.shape[0]
    return zr.reshape(q, m, a, b), (-zi).reshape(q, m, a, b)


def irbucket_body(yr, yi, dr, di, gr, gi, far, fai, wr, wi, fbr, fbi,
                  fpr, fpi, ctwr, ctwi, pwr, pwi, s):
    """The full c2r pipeline on a (bq, s//2+1) block of half-spectrum
    requests (DESIGN.md §9).

    Same stage skeleton as :func:`rbucket_body` run in reverse: adjoint
    message butterfly (:func:`ir_message_body`), the coded core with a
    HALF-length ifft worker, relabel unpack.  The ifft worker rides the
    forward four-step planes via the conj trick on planes -- two sign
    flips of imaginary planes around the forward core plus one rescale
    (``coded_core_body(inverse=True)``), so no inverse DFT planes exist
    anywhere.  Returns ONE real (bq, s) plane.
    """
    m = gr.shape[1]
    a, b = far.shape[0], fbr.shape[0]
    zr, zi = _ir_message_views(yr, yi, fpr, fpi, ctwr, ctwi, pwr, pwi,
                               s, m, a, b)
    hr, hi = coded_core_body(zr, zi, dr, di, gr, -gi, far, fai, wr, wi,
                             fbr, fbi, inverse=True)
    return ir_unpack_body(unscramble_planes(hr), unscramble_planes(hi))


def irbucket_body_masked(yr, yi, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
                         fpr, fpi, ctwr, ctwi, pwr, pwi, s):
    """:func:`irbucket_body` with in-kernel subset selection + in-VMEM
    Lagrange decode matrices (cf. :func:`bucket_body_masked`)."""
    n, m = gr.shape
    dr, di = _masked_decode_planes(masks, m, n)
    return irbucket_body(yr, yi, dr, di, gr, gi, far, fai, wr, wi, fbr, fbi,
                         fpr, fpi, ctwr, ctwi, pwr, pwi, s)


def irbucket_body_fftworker(yr, yi, dvr, dvi, subsets, gr, gi,
                            fpr, fpi, ctwr, ctwi, pwr, pwi, s):
    """Direct-mode (off-TPU) c2r bucket: message stage on planes, platform
    ifft worker on packed half-length shards, gathered compact decode,
    relabel unpack.  Returns ONE real plane (bq, s)."""
    n, m = gr.shape
    n2 = s // m // 2
    bq = yr.shape[0]
    zr, zi = ir_message_body(yr, yi, fpr, fpi, ctwr, ctwi, pwr, pwi, s, m)
    tr = jnp.transpose(zr, (1, 0, 2)).reshape(m, bq * n2)
    ti = jnp.transpose(zi, (1, 0, 2)).reshape(m, bq * n2)
    ar_, ai_ = cmatmul_body(gr, gi, tr, ti)
    coded = (ar_ + 1j * ai_).reshape(n, bq, n2)
    spec = jnp.fft.ifft(coded, axis=-1)
    er = jnp.transpose(jnp.real(spec).astype(yr.dtype), (1, 0, 2))
    ei = jnp.transpose(jnp.imag(spec).astype(yr.dtype), (1, 0, 2))
    idx = subsets[:, :, None]
    rr = jnp.take_along_axis(er, idx, axis=1)
    ri = jnp.take_along_axis(ei, idx, axis=1)
    hr, hi = bcmatmul_body(dvr, dvi, rr, ri)
    return ir_unpack_body(hr, hi)


def _irbucket_call(yr, yi, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
                   fpr, fpi, ctwr, ctwi, pwr, pwi, s, block_q, interpret, name):
    m = gr.shape[1]
    a, b = far.shape[0], fbr.shape[0]
    zr, zi = _ir_message_views(yr, yi, fpr, fpi, ctwr, ctwi, pwr, pwi,
                               s, m, a, b)
    hr, hi = _core_call(zr, zi, masks, gr, -gi, far, fai, wr, wi, fbr, fbi,
                        inverse=True, block_q=block_q, interpret=interpret,
                        name=name)
    return ir_unpack_body(unscramble_planes(hr), unscramble_planes(hi))


def coded_irfft_bucket_masked(yr, yi, masks, gr, gi, far, fai, wr, wi,
                              fbr, fbi, fpr, fpi, ctwr, ctwi, pwr, pwi, s, *,
                              block_q: int = 1, interpret: bool = False):
    """Fused c2r bucket pipeline: half-spectrum request planes -> ONE real
    output plane, the coded core as one Pallas launch (DESIGN.md §9).

    ``yr, yi``: (q, s//2+1) request planes; ``masks``: (q, N) raw
    responder masks, decoded in VMEM per grid step (DESIGN.md §8);
    ``far/wr/fbr``: four-step planes for the HALF length L/2 = A*B;
    ``fpr``: (m, m) +sign DFT planes and ``ctwr``: (m, L) conjugate
    twiddle of the adjoint message butterfly; ``pwr``: (1, L/2+1) pack
    twiddle.  Returns the (q, s) real plane of ``irfft(y, n=s, axis=-1)``.
    """
    return _irbucket_call(yr, yi, masks, gr, gi, far, fai, wr, wi,
                          fbr, fbi, fpr, fpi, ctwr, ctwi, pwr, pwi, s,
                          block_q, interpret, "coded_irfft_bucket_masked")


# ===================== streaming bucket: one launch beyond the VMEM budget
#
# The fused bucket kernel needs the whole (bq, m, A, B) working set
# VMEM-resident; past ~1M elements the ops layer used to FALL BACK to the
# multi-launch stage path.  The streaming kernel keeps the ONE-launch
# contract for arbitrarily large (s, m): payload and the inter-stage
# scratch live in HBM (ANY memory space) and the kernel hand-rolls
# double-buffered DMA over column tiles (stage 1 + twiddle, column-local)
# then row tiles (stage 3 + encode + decode + recombine, all row-local on
# the scrambled payload), staging tile k+1 while tile k computes.  The
# message planes arrive as (q, m, A, B) from the same XLA interleave as
# the fused kernel, and the scrambled (q, m, A, B) output is unscrambled
# by XLA.  Only the c2c bucket streams: the r2c split butterfly pairs bin
# p with n2-p, which is not column-local, so the real kinds keep the
# stage fallback for over-budget shapes.


def _streaming_bucket_kernel(nbt, nat, block_q, block_a, block_b, *refs):
    xr_hbm, xi_hbm, mk_ref = refs[:3]
    rest = refs[3:]
    (gr_ref, gi_ref, far_ref, fai_ref, wr_ref, wi_ref, fbr_ref, fbi_ref,
     twr_ref, twi_ref, fmr_ref, fmi_ref) = rest[:12]
    (or_hbm, oi_hbm, t1r_hbm, t1i_hbm,
     abr, abi, t1s_r, t1s_i, bbr, bbi, obr, obi,
     sem_a, sem_t1, sem_b, sem_o) = rest[12:]

    n, m = gr_ref.shape
    a = far_ref.shape[0]
    b = fbr_ref.shape[0]
    bq = block_q
    q0 = pl.program_id(0) * block_q

    # per-request decode planes, once per batch block (tiny: (bq, m, n))
    mk = mk_ref[...]
    dr, di = _masked_decode_planes(mk.reshape(bq, n), m, n)

    # ---- phase A: stage 1 + twiddle over B-column tiles -> t1 HBM scratch
    def a_copies(j, slot):
        cols = pl.ds(j * block_b, block_b)
        return (
            pltpu.make_async_copy(
                xr_hbm.at[pl.ds(q0, bq), :, :, cols], abr.at[slot],
                sem_a.at[slot, 0]),
            pltpu.make_async_copy(
                xi_hbm.at[pl.ds(q0, bq), :, :, cols], abi.at[slot],
                sem_a.at[slot, 1]),
        )

    for c in a_copies(0, 0):
        c.start()
    far = far_ref[...]
    fai = fai_ref[...]

    def phase_a(j, carry):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nbt)
        def _():
            for c in a_copies(j + 1, jax.lax.rem(j + 1, 2)):
                c.start()

        for c in a_copies(j, slot):
            c.wait()
        # column DFT per message shard: contract A, the B tile stays lanes
        cols = pl.ds(pl.multiple_of(j * block_b, block_b), block_b)
        t2r, t2i = stage1_body(
            abr[slot].reshape(bq * m, a, block_b),
            abi[slot].reshape(bq * m, a, block_b),
            far, fai, wr_ref[:, cols], wi_ref[:, cols])
        t1s_r[...] = t2r.reshape(bq, m, a, block_b)
        t1s_i[...] = t2i.reshape(bq, m, a, block_b)
        outs = (
            pltpu.make_async_copy(
                t1s_r, t1r_hbm.at[pl.ds(q0, bq), :, :, cols], sem_t1.at[0]),
            pltpu.make_async_copy(
                t1s_i, t1i_hbm.at[pl.ds(q0, bq), :, :, cols], sem_t1.at[1]),
        )
        for c in outs:
            c.start()
        for c in outs:
            c.wait()
        return carry

    jax.lax.fori_loop(0, nbt, phase_a, 0)

    # ---- phase B: stage 3 + encode + decode + recombine over A-row tiles
    def b_copies(i, slot):
        rows = pl.ds(i * block_a, block_a)
        return (
            pltpu.make_async_copy(
                t1r_hbm.at[pl.ds(q0, bq), :, rows, :], bbr.at[slot],
                sem_b.at[slot, 0]),
            pltpu.make_async_copy(
                t1i_hbm.at[pl.ds(q0, bq), :, rows, :], bbi.at[slot],
                sem_b.at[slot, 1]),
        )

    for c in b_copies(0, 0):
        c.start()
    gr = jnp.broadcast_to(gr_ref[...], (bq, n, m))
    gi = jnp.broadcast_to(gi_ref[...], (bq, n, m))
    fbr = fbr_ref[...]
    fbi = fbi_ref[...]
    fmr = fmr_ref[...]
    fmi = fmi_ref[...]

    def phase_b(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < nat)
        def _():
            for c in b_copies(i + 1, jax.lax.rem(i + 1, 2)):
                c.start()

        for c in b_copies(i, slot):
            c.wait()
        # row DFT per shard: contract B with (bq, m, a-tile) folded in rows
        s3r, s3i = stage2_body(bbr[slot].reshape(bq * m, block_a, b),
                               bbi[slot].reshape(bq * m, block_a, b),
                               fbr, fbi)
        s3r = s3r.reshape(bq, m, block_a, b)
        s3i = s3i.reshape(bq, m, block_a, b)
        # MDS encode, then the per-request decode (scrambled order carried)
        er, ei = shard_contract(gr, gi, s3r, s3i)
        hr, hi = shard_contract(dr, di, er, ei)
        # recombine: the scrambled payload rows of tile i pair with the
        # same rows of the pre-scrambled (m, A, B) twiddle
        rows = pl.ds(pl.multiple_of(i * block_a, block_a), block_a)
        outr, outi = recombine_shards_body(
            hr, hi, twr_ref[:, rows, :], twi_ref[:, rows, :], fmr, fmi)
        obr[...] = outr
        obi[...] = outi
        outs = (
            pltpu.make_async_copy(
                obr, or_hbm.at[pl.ds(q0, bq), :, rows, :], sem_o.at[0]),
            pltpu.make_async_copy(
                obi, oi_hbm.at[pl.ds(q0, bq), :, rows, :], sem_o.at[1]),
        )
        for c in outs:
            c.start()
        for c in outs:
            c.wait()
        return carry

    jax.lax.fori_loop(0, nat, phase_b, 0)


def _streaming_bucket_call(xr, xi, masks, gr, gi, far, fai,
                           wr, wi, fbr, fbi, twr, twi, fmr, fmi,
                           block_q, block_a, block_b, interpret, name):
    q, s = xr.shape
    n, m = gr.shape
    a = far.shape[0]
    b = fbr.shape[0]
    f32 = xr.dtype
    cr, ci = interleave_planes(xr, xi, m, a, b)
    block_q = max(1, min(block_q, q))
    pad = (-q) % block_q
    if pad:  # DMA tile sizes are static: round the batch up
        cr = jnp.concatenate([cr, jnp.zeros((pad, m, a, b), f32)])
        ci = jnp.concatenate([ci, jnp.zeros((pad, m, a, b), f32)])
        # all-available filler keeps the Lagrange nodes distinct
        masks = jnp.concatenate([masks, jnp.ones((pad, n), f32)])
    qp = q + pad
    block_a = _even_divisor(a, block_a)
    block_b = _even_divisor(b, block_b)
    nat = a // block_a
    nbt = b // block_b

    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    def vspec(*shape):
        return pl.BlockSpec(shape, lambda i, r=len(shape): (0,) * r)

    masks = masks.reshape(qp, 1, n)
    in_specs = [any_spec, any_spec,
                pl.BlockSpec((block_q, 1, n), lambda i: (i, 0, 0)),
                vspec(n, m), vspec(n, m), vspec(a, a), vspec(a, a),
                vspec(a, b), vspec(a, b), vspec(b, b), vspec(b, b),
                vspec(m, a, b), vspec(m, a, b), vspec(m, m), vspec(m, m)]
    out_shape = [
        jax.ShapeDtypeStruct((qp, m, a, b), f32),   # scrambled output
        jax.ShapeDtypeStruct((qp, m, a, b), f32),
        jax.ShapeDtypeStruct((qp, m, a, b), f32),   # t1 HBM scratch
        jax.ShapeDtypeStruct((qp, m, a, b), f32),
    ]
    scratch = [
        pltpu.VMEM((2, block_q, m, a, block_b), f32),   # phase A in (x2)
        pltpu.VMEM((2, block_q, m, a, block_b), f32),
        pltpu.VMEM((block_q, m, a, block_b), f32),      # phase A staging
        pltpu.VMEM((block_q, m, a, block_b), f32),
        pltpu.VMEM((2, block_q, m, block_a, b), f32),   # phase B in (x2)
        pltpu.VMEM((2, block_q, m, block_a, b), f32),
        pltpu.VMEM((block_q, m, block_a, b), f32),      # phase B staging
        pltpu.VMEM((block_q, m, block_a, b), f32),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    outs = pl.pallas_call(
        functools.partial(_streaming_bucket_kernel, nbt, nat,
                          block_q, block_a, block_b),
        grid=(qp // block_q,),
        in_specs=in_specs,
        out_specs=[any_spec, any_spec, any_spec, any_spec],
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name=name,
    )(cr, ci, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
      twr.reshape(m, a, b), twi.reshape(m, a, b), fmr, fmi)
    return (unscramble_planes(outs[0][:q]).reshape(q, s),
            unscramble_planes(outs[1][:q]).reshape(q, s))


def coded_fft_bucket_streaming_masked(xr, xi, masks, gr, gi, far, fai, wr, wi,
                                      fbr, fbi, twr, twi, fmr, fmi, *,
                                      block_q: int = 1, block_a: int = 256,
                                      block_b: int = 256,
                                      interpret: bool = False):
    """One-launch streaming c2c bucket for shapes beyond the VMEM budget.

    Same contract as :func:`coded_fft_bucket_masked` (including the
    pre-scrambled ``twr/twi``) but only (block_q, A, block_b, m) /
    (block_q, block_a, B, m) tiles are VMEM-resident, double-buffered
    against HBM; subset selection and the Lagrange decode run in-kernel
    (DESIGN.md §8)."""
    masks = masks.astype(xr.dtype)
    return _streaming_bucket_call(
        xr, xi, masks, gr, gi, far, fai, wr, wi, fbr, fbi,
        twr, twi, fmr, fmi, block_q, block_a, block_b, interpret,
        "coded_fft_bucket_streaming_masked")
