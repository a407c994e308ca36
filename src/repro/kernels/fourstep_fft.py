"""Pallas TPU kernels: batched four-step (Bailey) FFT + fused MDS encode.

The per-worker hot loop of coded FFT is a length-L DFT of the worker's coded
shard (paper §III-B step 3).  On TPU we do NOT port a butterfly-network FFT
(a GPU/CPU idiom that starves the MXU); instead we factor ``L = A * B`` and
compute

    out[c, d] = ( (F_A @ M) * W ) @ F_B,     M[a, b] = x[a*B + b]
    X[c + d*A] = out[c, d]

i.e. two dense DFT-matrix matmuls (MXU work) plus one elementwise twiddle
(VPU work).  Complex arithmetic is planar: separate f32 real/imag planes,
4-real-matmul complex products with f32 accumulation.

Every kernel here blocks over the BATCH as well (``block_q`` elements per
grid step) with the batch block folded into the matmul row/column dims, so
one grid step issues the same two big MXU contractions regardless of
``block_q``.  Off-TPU (interpret mode) the ops-layer collapses the whole
batch into one grid step, which lowers to plain XLA matmuls with no
per-element loop — that is what makes the kernel path the *default* engine
rather than a TPU-only demo (DESIGN.md §6).

Kernels:

* ``fourstep_fused`` — whole (A, B) matrix per element resident in VMEM.
  VMEM footprint ~ 2*(bq*A*B + A*A + B*B + A*B) * 4 bytes.
* ``fourstep_stage1 / fourstep_stage2`` two-pass — stage 1 blocks over
  B-columns (column DFT + twiddle are column-local), stage 2 blocks over
  A-rows (row DFT is row-local); supports sizes whose full matrix would
  not fit VMEM.
* ``encode_fourstep_fused`` — the coded-FFT stage-1 fusion: the MDS encode
  ``a = G @ c`` is itself a (roots-of-unity) matmul across the shard axis
  and commutes with the per-shard DFT, so the kernel transforms the ``m``
  MESSAGE shards (not the ``N`` coded ones — an N/m flop saving) and
  applies the generator contraction in VMEM.  Coded shards never
  round-trip through HBM between encode and worker compute.
* ``multistep_fused`` — the mixed-radix generalization: ``L = f1 * ... * fk``
  with one dense-DFT matmul + twiddle per factor.  Flops per element scale
  with ``sum(f_i)`` instead of ``A + B = 2*sqrt(L)``, so deeper plans win at
  large L; the autotuner picks the plan per backend (autotune.py).
* ``fourstep_streaming`` — one-launch four-step for shapes whose full
  (A, B) matrix exceeds VMEM: the kernel keeps x/out/t1 in HBM (ANY memory
  space) and hand-rolls double-buffered DMA over column tiles (stage 1+2)
  then row tiles (stage 3), staging tile k+1 while tile k computes.  The
  output is written in NATURAL order (batch, B, A) via an in-VMEM tile
  transpose, so no XLA unscramble pass follows.

The jit wrappers with layout pack/unpack live in ops.py; the jnp oracles in
ref.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "fourstep_body",
    "fourstep_fused",
    "stage1_body",
    "stage2_body",
    "fourstep_stage1",
    "fourstep_stage2",
    "encode_fourstep_body",
    "encode_fourstep_fused",
    "shard_contract",
    "multistep_body",
    "multistep_fused",
    "fourstep_streaming",
]


# Scoped-VMEM cap of the four-step and bucket kernels.  The compiler's
# default scope (16 MiB on v5e) is below the working set of one
# double-buffered 512 x 512 block with its DFT planes; v5e has 128 MiB of
# VMEM per core.
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)


# f32 matmuls on the MXU default to a single bf16 pass on TPU; the DFT and
# coding contractions need the full-f32 multi-pass product to meet the
# f32 error budget the tests and the chip smoke hold them to.
_HIGHEST = jax.lax.Precision.HIGHEST


def _cmul_mm(ar, ai, br, bi):
    """Complex matmul on planes with f32 accumulation (4 real matmuls)."""
    dot = functools.partial(jnp.dot, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
    return dot(ar, br) - dot(ai, bi), dot(ar, bi) + dot(ai, br)


def _cmul_einsum(spec, ar, ai, br, bi):
    """Complex einsum on planes (4 real contractions, f32 accumulation)."""
    ein = functools.partial(jnp.einsum, spec, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
    return ein(ar, br) - ein(ai, bi), ein(ar, bi) + ein(ai, br)


def _column_dft(xr, xi, far, fai):
    """``F_A @ M`` for every (A, B) slab of a (p, A, B) stack.

    The contraction runs over the sublane axis with the lane axis B left
    in place, so no relayout of the payload is needed inside a Mosaic
    kernel (folding the batch into the lane axis is not lowerable when B
    is not a multiple of 128)."""
    return _cmul_einsum("ca,pab->pcb", far, fai, xr, xi)


def _row_dft(xr, xi, fbr, fbi):
    """``M @ F_B`` for every slab of a (p, A, B) stack: the leading axes
    fold into the matmul rows, a layout-free merge."""
    p, a, b = xr.shape
    tr, ti = _cmul_mm(xr.reshape(p * a, b), xi.reshape(p * a, b), fbr, fbi)
    return tr.reshape(p, a, b), ti.reshape(p, a, b)


def fourstep_body(xr, xi, far, fai, wr, wi, fbr, fbi):
    """The four-step math on one (bq, A, B) block: ((F_A @ M) * W) @ F_B.

    Shared between the Pallas kernel (one block per grid step) and the
    off-TPU direct path, which evaluates the body on the full batch as
    straight XLA (DESIGN.md §6).  Stage 1 contracts A per slab, stage 3
    folds the batch into the rows of one dense matmul.
    """
    t1r, t1i = _column_dft(xr, xi, far, fai)
    t2r = t1r * wr - t1i * wi
    t2i = t1r * wi + t1i * wr
    return _row_dft(t2r, t2i, fbr, fbi)


def _fused_kernel(xr_ref, xi_ref, far_ref, fai_ref, wr_ref, wi_ref,
                  fbr_ref, fbi_ref, or_ref, oi_ref):
    or_ref[...], oi_ref[...] = fourstep_body(
        xr_ref[...], xi_ref[...], far_ref[...], fai_ref[...],
        wr_ref[...], wi_ref[...], fbr_ref[...], fbi_ref[...])


def fourstep_fused(xr, xi, far, fai, wr, wi, fbr, fbi, *, block_q: int = 1,
                   interpret=False):
    """Batched fused four-step FFT.

    ``xr, xi``: (batch, A, B) planes of M[a,b] = x[a*B+b].
    Returns planes of out[c, d] with X[c + d*A] = out[c, d].
    ``block_q`` batch elements are processed per grid step (the ops layer
    collapses the grid entirely in interpret mode).
    """
    batch, a, b = xr.shape
    block_q = max(1, min(block_q, batch))
    spec_x = pl.BlockSpec((block_q, a, b), lambda i: (i, 0, 0))
    spec_fa = pl.BlockSpec((a, a), lambda i: (0, 0))
    spec_w = pl.BlockSpec((a, b), lambda i: (0, 0))
    spec_fb = pl.BlockSpec((b, b), lambda i: (0, 0))
    out_shape = [
        jax.ShapeDtypeStruct((batch, a, b), xr.dtype),
        jax.ShapeDtypeStruct((batch, a, b), xr.dtype),
    ]
    return pl.pallas_call(
        _fused_kernel,
        grid=(pl.cdiv(batch, block_q),),
        in_specs=[spec_x, spec_x, spec_fa, spec_fa, spec_w, spec_w, spec_fb, spec_fb],
        out_specs=[spec_x, spec_x],
        out_shape=out_shape,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="fourstep_fft_fused",
    )(xr, xi, far, fai, wr, wi, fbr, fbi)


def encode_fourstep_body(cr, ci, gr, gi, far, fai, wr, wi, fbr, fbi):
    """Fused MDS-encode + four-step worker DFT on MESSAGE shards.

    ``c`` block: (bq, m, A, B) message planes; ``g``: (n, m) generator
    planes.  The DFT stages act per shard and the generator contraction
    acts across shards, so they commute: transforming the m message shards
    first saves an N/m factor of DFT flops, and the encode is one batched
    (n, m) x (m, A, B) contraction on VMEM-resident data.  Returns
    (bq, n, A, B) planes in the scrambled four-step order.
    """
    bq, m, a, b = cr.shape
    n = gr.shape[0]
    tr, ti = fourstep_body(cr.reshape(bq * m, a, b), ci.reshape(bq * m, a, b),
                           far, fai, wr, wi, fbr, fbi)
    return shard_contract(jnp.broadcast_to(gr, (bq, n, m)),
                          jnp.broadcast_to(gi, (bq, n, m)),
                          tr.reshape(bq, m, a, b), ti.reshape(bq, m, a, b))


def shard_contract(dr, di, xr, xi):
    """Per-request ``(k, i) x (i, A, B)`` contraction across the shard axis.

    ``dr, di``: (bq, k, i) planes (a generator, a decode matrix or a DFT
    across shards, one per request); ``xr, xi``: (bq, i, A, B).  The
    payload keeps its (A, B) tile layout, so this lowers inside a Mosaic
    kernel for any shard count.
    """
    return _cmul_einsum("qki,qicd->qkcd", dr, di, xr, xi)


def _encode_fused_kernel(cr_ref, ci_ref, gr_ref, gi_ref, far_ref, fai_ref,
                         wr_ref, wi_ref, fbr_ref, fbi_ref, or_ref, oi_ref):
    or_ref[...], oi_ref[...] = encode_fourstep_body(
        cr_ref[...], ci_ref[...], gr_ref[...], gi_ref[...],
        far_ref[...], fai_ref[...], wr_ref[...], wi_ref[...],
        fbr_ref[...], fbi_ref[...])


def encode_fourstep_fused(cr, ci, gr, gi, far, fai, wr, wi, fbr, fbi, *,
                          block_q: int = 1, interpret=False):
    """Fused encode + worker DFT: message planes -> coded worker spectra.

    ``cr, ci``: (batch, m, A, B) planes of the m message shards,
    M_i[a, b] = c_i[a*B+b]; ``gr, gi``: (n, m) generator planes.
    Returns (batch, n, A, B) planes of out[k, c, d] with
    ``B_k[c + d*A] = out[k, c, d]`` -- the same scrambled four-step order
    as :func:`fourstep_fused`, unscrambled by the ops layer.
    """
    batch, m, a, b = cr.shape
    n = gr.shape[0]
    block_q = max(1, min(block_q, batch))
    spec_c = pl.BlockSpec((block_q, m, a, b), lambda i: (i, 0, 0, 0))
    spec_g = pl.BlockSpec((n, m), lambda i: (0, 0))
    spec_fa = pl.BlockSpec((a, a), lambda i: (0, 0))
    spec_w = pl.BlockSpec((a, b), lambda i: (0, 0))
    spec_fb = pl.BlockSpec((b, b), lambda i: (0, 0))
    spec_o = pl.BlockSpec((block_q, n, a, b), lambda i: (i, 0, 0, 0))
    out_shape = [
        jax.ShapeDtypeStruct((batch, n, a, b), cr.dtype),
        jax.ShapeDtypeStruct((batch, n, a, b), cr.dtype),
    ]
    return pl.pallas_call(
        _encode_fused_kernel,
        grid=(pl.cdiv(batch, block_q),),
        in_specs=[spec_c, spec_c, spec_g, spec_g, spec_fa, spec_fa,
                  spec_w, spec_w, spec_fb, spec_fb],
        out_specs=[spec_o, spec_o],
        out_shape=out_shape,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="encode_fourstep_fused",
    )(cr, ci, gr, gi, far, fai, wr, wi, fbr, fbi)


def stage1_body(xr, xi, far, fai, wr, wi):
    """Column-blocked: out = (F_A @ M_block) * W_block, batch folded in."""
    t1r, t1i = _column_dft(xr, xi, far, fai)
    return t1r * wr - t1i * wi, t1r * wi + t1i * wr


def _stage1_kernel(xr_ref, xi_ref, far_ref, fai_ref, wr_ref, wi_ref,
                   or_ref, oi_ref):
    or_ref[...], oi_ref[...] = stage1_body(
        xr_ref[...], xi_ref[...], far_ref[...], fai_ref[...],
        wr_ref[...], wi_ref[...])


def fourstep_stage1(xr, xi, far, fai, wr, wi, *, block_q: int = 1,
                    block_b=256, interpret=False):
    """Stage 1+2 of the four-step FFT, blocked over columns of B."""
    batch, a, b = xr.shape
    block_b = min(block_b, b)
    block_q = max(1, min(block_q, batch))
    grid = (pl.cdiv(batch, block_q), pl.cdiv(b, block_b))
    spec_x = pl.BlockSpec((block_q, a, block_b), lambda i, j: (i, 0, j))
    spec_fa = pl.BlockSpec((a, a), lambda i, j: (0, 0))
    spec_w = pl.BlockSpec((a, block_b), lambda i, j: (0, j))
    out_shape = [
        jax.ShapeDtypeStruct((batch, a, b), xr.dtype),
        jax.ShapeDtypeStruct((batch, a, b), xr.dtype),
    ]
    return pl.pallas_call(
        _stage1_kernel,
        grid=grid,
        in_specs=[spec_x, spec_x, spec_fa, spec_fa, spec_w, spec_w],
        out_specs=[spec_x, spec_x],
        out_shape=out_shape,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="fourstep_fft_stage1",
    )(xr, xi, far, fai, wr, wi)


def stage2_body(tr, ti, fbr, fbi):
    """Row-blocked: out = T_block @ F_B, batch folded into the rows."""
    return _row_dft(tr, ti, fbr, fbi)


def _stage2_kernel(tr_ref, ti_ref, fbr_ref, fbi_ref, or_ref, oi_ref):
    or_ref[...], oi_ref[...] = stage2_body(
        tr_ref[...], ti_ref[...], fbr_ref[...], fbi_ref[...])


def fourstep_stage2(tr, ti, fbr, fbi, *, block_q: int = 1, block_a=256,
                    interpret=False):
    """Stage 3 of the four-step FFT, blocked over rows of A."""
    batch, a, b = tr.shape
    block_a = min(block_a, a)
    block_q = max(1, min(block_q, batch))
    grid = (pl.cdiv(batch, block_q), pl.cdiv(a, block_a))
    spec_t = pl.BlockSpec((block_q, block_a, b), lambda i, j: (i, j, 0))
    spec_fb = pl.BlockSpec((b, b), lambda i, j: (0, 0))
    out_shape = [
        jax.ShapeDtypeStruct((batch, a, b), tr.dtype),
        jax.ShapeDtypeStruct((batch, a, b), tr.dtype),
    ]
    return pl.pallas_call(
        _stage2_kernel,
        grid=grid,
        in_specs=[spec_t, spec_t, spec_fb, spec_fb],
        out_specs=[spec_t, spec_t],
        out_shape=out_shape,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="fourstep_fft_stage2",
    )(tr, ti, fbr, fbi)


# --------------------------------------------------------------------------
# mixed-radix (multistep) four-step
# --------------------------------------------------------------------------
def _parse_stage_planes(factors, planes):
    """Group the flat plane list into per-stage (fr, fi, twr, twi) tuples.

    The flat order is per stage: DFT planes (f, f), then — for every stage
    but the last, whose ``rest`` is 1 and whose twiddle is identically
    one — twiddle planes (f, rest).
    """
    stages = []
    idx = 0
    for i, _ in enumerate(factors):
        fr, fi = planes[idx], planes[idx + 1]
        idx += 2
        twr = twi = None
        if i + 1 < len(factors):
            twr, twi = planes[idx], planes[idx + 1]
            idx += 2
        stages.append((fr, fi, twr, twi))
    return stages


def multistep_body(xr, xi, stages):
    """Mixed-radix four-step on one (bq, L) block.

    ``stages``: per-factor (fr, fi, twr, twi) planes from
    :func:`_parse_stage_planes`; ``fr`` is the dense (f, f) DFT matrix and
    ``twr`` the (f, rest) twiddle (None on the last stage).  Each stage is
    the classic four-step stage 1 applied recursively: split the remaining
    length as ``f * rest``, contract ``f`` with one dense matmul (batch and
    already-processed digits folded into the columns), twiddle, and push the
    new digit onto the lead axis.  After all k stages the result is the
    scrambled spectrum with digit order (bq, c1, ..., ck) and
    ``X[c1 + f1*c2 + f1*f2*c3 + ...]`` — for two factors this is exactly
    :func:`fourstep_body`'s ``out[c, d] = X[c + d*A]``.  The ops layer
    unscrambles with one reversed-axes transpose.
    """
    bq, total = xr.shape
    lead = bq
    tr, ti = xr, xi
    for fr, fi, twr, twi in stages:
        f = fr.shape[0]
        rest = total // f
        mr = tr.reshape(lead, f, rest).transpose(1, 0, 2).reshape(f, lead * rest)
        mi = ti.reshape(lead, f, rest).transpose(1, 0, 2).reshape(f, lead * rest)
        t1r, t1i = _cmul_mm(fr, fi, mr, mi)
        t1r = t1r.reshape(f, lead, rest)
        t1i = t1i.reshape(f, lead, rest)
        if twr is not None:
            wr_ = twr[:, None, :]
            wi_ = twi[:, None, :]
            t1r, t1i = t1r * wr_ - t1i * wi_, t1r * wi_ + t1i * wr_
        tr = t1r.transpose(1, 0, 2).reshape(lead * f, rest)
        ti = t1i.transpose(1, 0, 2).reshape(lead * f, rest)
        lead *= f
        total = rest
    return tr.reshape(bq, -1), ti.reshape(bq, -1)


def _multistep_kernel(factors, *refs):
    n_planes = 4 * len(factors) - 2
    xr_ref, xi_ref = refs[:2]
    plane_refs = refs[2:2 + n_planes]
    or_ref, oi_ref = refs[2 + n_planes:]
    stages = _parse_stage_planes(factors, [r[...] for r in plane_refs])
    or_ref[...], oi_ref[...] = multistep_body(xr_ref[...], xi_ref[...], stages)


def multistep_fused(xr, xi, planes, factors, *, block_q: int = 1,
                    interpret=False):
    """Batched mixed-radix four-step FFT (one launch, k dense stages).

    ``xr, xi``: (batch, L) planes of x in natural order; ``planes``: flat
    per-stage DFT/twiddle planes (see :func:`_parse_stage_planes`);
    ``factors``: the radix plan with ``prod(factors) == L``.  Returns
    (batch, L) planes in the multistep scrambled digit order.
    """
    batch, ell = xr.shape
    block_q = max(1, min(block_q, batch))
    spec_x = pl.BlockSpec((block_q, ell), lambda i: (i, 0))
    in_specs = [spec_x, spec_x]
    for p in planes:
        in_specs.append(
            pl.BlockSpec(p.shape, lambda i, r=p.ndim: (0,) * r))
    out_shape = [
        jax.ShapeDtypeStruct((batch, ell), xr.dtype),
        jax.ShapeDtypeStruct((batch, ell), xr.dtype),
    ]
    return pl.pallas_call(
        functools.partial(_multistep_kernel, tuple(factors)),
        grid=(pl.cdiv(batch, block_q),),
        in_specs=in_specs,
        out_specs=[spec_x, spec_x],
        out_shape=out_shape,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="fourstep_fft_multistep",
    )(xr, xi, *planes)


# --------------------------------------------------------------------------
# streaming four-step: one launch with double-buffered HBM<->VMEM DMA
# --------------------------------------------------------------------------
def _streaming_kernel(nbt, nat, block_q, block_a, block_b,
                      xr_hbm, xi_hbm, far_ref, fai_ref, wr_ref, wi_ref,
                      fbr_ref, fbi_ref,
                      or_hbm, oi_hbm, t1r_hbm, t1i_hbm,
                      abr, abi, t1s_r, t1s_i, bbr, bbi, obr, obi,
                      sem_a, sem_t1, sem_b, sem_o):
    """Two sequential phases inside ONE kernel launch.

    Phase A walks B-column tiles (stage 1 + twiddle are column-local):
    DMA x tile in, compute, DMA the t1 tile out to an HBM scratch.  Phase B
    walks A-row tiles (stage 3 is row-local): DMA t1 tile in, contract F_B,
    transpose the tile in VMEM and DMA it to the NATURAL-order output
    (batch, B, A).  Input DMAs are double-buffered — tile k+1 streams while
    tile k computes; the (smaller) result write-backs block, which keeps a
    single staging buffer per phase and still hides the dominant read
    latency.  Phase B only starts after every phase-A write-back has waited,
    so the t1 scratch is consistent without an explicit barrier.
    """
    q0 = pl.program_id(0) * block_q

    def a_copies(j, slot):
        cols = pl.ds(j * block_b, block_b)
        return (
            pltpu.make_async_copy(
                xr_hbm.at[pl.ds(q0, block_q), :, cols], abr.at[slot],
                sem_a.at[slot, 0]),
            pltpu.make_async_copy(
                xi_hbm.at[pl.ds(q0, block_q), :, cols], abi.at[slot],
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
        cols = pl.ds(pl.multiple_of(j * block_b, block_b), block_b)
        tr, ti = stage1_body(abr[slot], abi[slot], far, fai,
                             wr_ref[:, cols], wi_ref[:, cols])
        t1s_r[...] = tr
        t1s_i[...] = ti
        outs = (
            pltpu.make_async_copy(
                t1s_r, t1r_hbm.at[pl.ds(q0, block_q), :, cols],
                sem_t1.at[0]),
            pltpu.make_async_copy(
                t1s_i, t1i_hbm.at[pl.ds(q0, block_q), :, cols],
                sem_t1.at[1]),
        )
        for c in outs:
            c.start()
        for c in outs:
            c.wait()
        return carry

    jax.lax.fori_loop(0, nbt, phase_a, 0)

    def b_copies(i, slot):
        rows = pl.ds(i * block_a, block_a)
        return (
            pltpu.make_async_copy(
                t1r_hbm.at[pl.ds(q0, block_q), rows, :], bbr.at[slot],
                sem_b.at[slot, 0]),
            pltpu.make_async_copy(
                t1i_hbm.at[pl.ds(q0, block_q), rows, :], bbi.at[slot],
                sem_b.at[slot, 1]),
        )

    for c in b_copies(0, 0):
        c.start()
    fbr = fbr_ref[...]
    fbi = fbi_ref[...]

    def phase_b(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < nat)
        def _():
            for c in b_copies(i + 1, jax.lax.rem(i + 1, 2)):
                c.start()

        for c in b_copies(i, slot):
            c.wait()
        t3r, t3i = stage2_body(bbr[slot], bbi[slot], fbr, fbi)
        # out[c, d] = X[c + d*A]: tile rows are c's, so the transposed tile
        # lands at output[:, :, c-tile] of the natural (batch, B, A) layout.
        obr[...] = jnp.transpose(t3r, (0, 2, 1))
        obi[...] = jnp.transpose(t3i, (0, 2, 1))
        cols = pl.ds(i * block_a, block_a)
        outs = (
            pltpu.make_async_copy(
                obr, or_hbm.at[pl.ds(q0, block_q), :, cols], sem_o.at[0]),
            pltpu.make_async_copy(
                obi, oi_hbm.at[pl.ds(q0, block_q), :, cols], sem_o.at[1]),
        )
        for c in outs:
            c.start()
        for c in outs:
            c.wait()
        return carry

    jax.lax.fori_loop(0, nat, phase_b, 0)


def _even_divisor(n: int, cap: int) -> int:
    d = max(1, min(cap, n))
    while n % d:
        d -= 1
    return d


def fourstep_streaming(xr, xi, far, fai, wr, wi, fbr, fbi, *,
                       block_q: int = 1, block_a: int = 256,
                       block_b: int = 256, interpret=False):
    """One-launch four-step FFT for shapes exceeding the VMEM budget.

    Same plane inputs as :func:`fourstep_fused` but x/out/t1 stay in HBM;
    only (block_q, A, block_b) / (block_q, block_a, B) tiles are VMEM
    resident at a time (x2 for double buffering).  Returns (batch, B, A)
    planes in NATURAL order — ``out[:, d, c] = X[d*A + c]`` — so callers
    reshape (free) instead of transposing.
    """
    batch, a, b = xr.shape
    block_q = max(1, min(block_q, batch))
    pad = (-batch) % block_q
    if pad:  # DMA tile sizes are static: round the batch up
        z = jnp.zeros((pad, a, b), xr.dtype)
        xr = jnp.concatenate([xr, z])
        xi = jnp.concatenate([xi, z])
    batchp = batch + pad
    block_a = _even_divisor(a, block_a)
    block_b = _even_divisor(b, block_b)
    nat = a // block_a
    nbt = b // block_b
    f32 = xr.dtype

    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    def vspec(*shape):
        return pl.BlockSpec(shape, lambda i, r=len(shape): (0,) * r)

    out_shape = [
        jax.ShapeDtypeStruct((batchp, b, a), f32),   # natural-order output
        jax.ShapeDtypeStruct((batchp, b, a), f32),
        jax.ShapeDtypeStruct((batchp, a, b), f32),   # t1 HBM scratch
        jax.ShapeDtypeStruct((batchp, a, b), f32),
    ]
    scratch = [
        pltpu.VMEM((2, block_q, a, block_b), f32),   # phase A in (x2 slots)
        pltpu.VMEM((2, block_q, a, block_b), f32),
        pltpu.VMEM((block_q, a, block_b), f32),      # phase A out staging
        pltpu.VMEM((block_q, a, block_b), f32),
        pltpu.VMEM((2, block_q, block_a, b), f32),   # phase B in (x2 slots)
        pltpu.VMEM((2, block_q, block_a, b), f32),
        pltpu.VMEM((block_q, b, block_a), f32),      # phase B out staging
        pltpu.VMEM((block_q, b, block_a), f32),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    outs = pl.pallas_call(
        functools.partial(_streaming_kernel, nbt, nat, block_q, block_a,
                          block_b),
        grid=(batchp // block_q,),
        in_specs=[any_spec, any_spec, vspec(a, a), vspec(a, a),
                  vspec(a, b), vspec(a, b), vspec(b, b), vspec(b, b)],
        out_specs=[any_spec, any_spec, any_spec, any_spec],
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="fourstep_fft_streaming",
    )(xr, xi, far, fai, wr, wi, fbr, fbi)
    return outs[0][:batch], outs[1][:batch]
