"""Pallas TPU kernel: fused twiddle + length-m DFT recombination.

The master's second decode stage (paper eq. 24) is

    X[i + j*(s/m)] = sum_k C[k, i] * omega_s^{ik} * omega_m^{jk}

= an elementwise twiddle ``T = C * W`` (VPU) fused with a dense length-m DFT
``F_m @ T`` (MXU), streaming the payload axis ``i`` through VMEM in blocks.
Fusing avoids materializing T in HBM -- the twiddle is applied in VMEM right
before the matmul consumes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "recombine_body",
    "recombine_twiddle_dft",
    "recombine_batched_body",
    "recombine_twiddle_dft_batched",
]


def recombine_body(cr, ci, wr, wi, fr, fi):
    """One recombine block: twiddle in VMEM (never hits HBM) + m-DFT."""
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    tr = cr * wr - ci * wi
    ti = cr * wi + ci * wr
    return dot(fr, tr) - dot(fi, ti), dot(fr, ti) + dot(fi, tr)


def _kernel(cr_ref, ci_ref, wr_ref, wi_ref, fr_ref, fi_ref, or_ref, oi_ref):
    or_ref[...], oi_ref[...] = recombine_body(
        cr_ref[...], ci_ref[...], wr_ref[...], wi_ref[...],
        fr_ref[...], fi_ref[...])


def recombine_twiddle_dft(
    cr, ci, wr, wi, fr, fi, *, block_l: int = 512, interpret: bool = False
):
    """Fused ``F @ (C * W)`` on planar (m, L) data, blocked over L."""
    m, ell = cr.shape
    assert wr.shape == (m, ell) and fr.shape == (m, m)
    block_l = min(block_l, ell)
    grid = (pl.cdiv(ell, block_l),)
    spec_c = pl.BlockSpec((m, block_l), lambda j: (0, j))
    spec_f = pl.BlockSpec((m, m), lambda j: (0, 0))
    out_shape = [
        jax.ShapeDtypeStruct((m, ell), cr.dtype),
        jax.ShapeDtypeStruct((m, ell), cr.dtype),
    ]
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec_c, spec_c, spec_c, spec_c, spec_f, spec_f],
        out_specs=[spec_c, spec_c],
        out_shape=out_shape,
        interpret=interpret,
        name="recombine_twiddle_dft",
    )(cr, ci, wr, wi, fr, fi)


def recombine_batched_body(cr, ci, wr, wi, fr, fi):
    """Batched recombine block: the twiddle/DFT planes are shared across
    the bucket, so the batch block folds into the matmul columns."""
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    bq, m, bl = cr.shape
    wr = wr[None]                              # (1, m, bl)
    wi = wi[None]
    tr = cr * wr - ci * wi
    ti = cr * wi + ci * wr
    tr = jnp.transpose(tr, (1, 0, 2)).reshape(m, bq * bl)
    ti = jnp.transpose(ti, (1, 0, 2)).reshape(m, bq * bl)
    outr = dot(fr, tr) - dot(fi, ti)
    outi = dot(fr, ti) + dot(fi, tr)
    return (jnp.transpose(outr.reshape(m, bq, bl), (1, 0, 2)),
            jnp.transpose(outi.reshape(m, bq, bl), (1, 0, 2)))


def _bkernel(cr_ref, ci_ref, wr_ref, wi_ref, fr_ref, fi_ref, or_ref, oi_ref):
    or_ref[...], oi_ref[...] = recombine_batched_body(
        cr_ref[...], ci_ref[...], wr_ref[...], wi_ref[...],
        fr_ref[...], fi_ref[...])


def recombine_twiddle_dft_batched(
    cr, ci, wr, wi, fr, fi, *, block_q: int = 1, block_l: int = 512,
    interpret: bool = False
):
    """Batched fused ``F @ (C * W)`` on planar (q, m, L) data.

    ``wr/wi`` (m, L) and ``fr/fi`` (m, m) are shared across the bucket;
    blocked over the batch q and payload columns L (both collapsed in
    interpret mode by the ops layer).
    """
    q, m, ell = cr.shape
    assert wr.shape == (m, ell) and fr.shape == (m, m)
    block_l = min(block_l, ell)
    block_q = max(1, min(block_q, q))
    grid = (pl.cdiv(q, block_q), pl.cdiv(ell, block_l))
    spec_c = pl.BlockSpec((block_q, m, block_l), lambda i, j: (i, 0, j))
    spec_w = pl.BlockSpec((m, block_l), lambda i, j: (0, j))
    spec_f = pl.BlockSpec((m, m), lambda i, j: (0, 0))
    out_shape = [
        jax.ShapeDtypeStruct((q, m, ell), cr.dtype),
        jax.ShapeDtypeStruct((q, m, ell), cr.dtype),
    ]
    return pl.pallas_call(
        _bkernel,
        grid=grid,
        in_specs=[spec_c, spec_c, spec_w, spec_w, spec_f, spec_f],
        out_specs=[spec_c, spec_c],
        out_shape=out_shape,
        interpret=interpret,
        name="recombine_twiddle_dft_batched",
    )(cr, ci, wr, wi, fr, fi)
