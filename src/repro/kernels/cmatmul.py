"""Pallas TPU kernels: planar complex matmul (MDS encode / decode-apply).

MDS encoding is ``a = G @ c`` with tiny ``G`` (N x m, m <= 64) against a wide
payload ``c`` (m, L) -- and decode-apply is the same shape with the inverted
subset matrix.  The generator stays VMEM-resident while the payload streams
through in column blocks; each grid step does one (N, m) x (m, block_l)
complex matmul = 4 real MXU matmuls.

``bcmatmul`` is the per-request variant the batched service decode uses:
every request in a bucket carries its OWN (m, N) decode matrix (selected by
its straggler mask, DESIGN.md §6), so the contraction is a batched
``(q, m, N) @ (q, N, L)`` with the q axis blocked across the grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["cmatmul", "cmatmul_body", "bcmatmul", "bcmatmul_body"]


def cmatmul_body(ar, ai, br, bi):
    """One complex matmul block: 4 real MXU matmuls, f32 accumulation."""
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    return dot(ar, br) - dot(ai, bi), dot(ar, bi) + dot(ai, br)


def _kernel(ar_ref, ai_ref, br_ref, bi_ref, cr_ref, ci_ref):
    cr_ref[...], ci_ref[...] = cmatmul_body(
        ar_ref[...], ai_ref[...], br_ref[...], bi_ref[...])


def cmatmul(ar, ai, br, bi, *, block_l: int = 512, interpret: bool = False):
    """Planar complex matmul: (M, K) @ (K, L) -> (M, L), blocked over L.

    Shapes follow the MDS-coding use case: M, K small (codes), L large
    (payload columns).  Returns (cr, ci).
    """
    m, k = ar.shape
    k2, ell = br.shape
    assert k == k2, (ar.shape, br.shape)
    block_l = min(block_l, ell)
    grid = (pl.cdiv(ell, block_l),)
    spec_a = pl.BlockSpec((m, k), lambda j: (0, 0))
    spec_b = pl.BlockSpec((k, block_l), lambda j: (0, j))
    spec_c = pl.BlockSpec((m, block_l), lambda j: (0, j))
    out_shape = [
        jax.ShapeDtypeStruct((m, ell), ar.dtype),
        jax.ShapeDtypeStruct((m, ell), ar.dtype),
    ]
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec_a, spec_a, spec_b, spec_b],
        out_specs=[spec_c, spec_c],
        out_shape=out_shape,
        interpret=interpret,
        name="cmatmul",
    )(ar, ai, br, bi)


def bcmatmul_body(ar, ai, br, bi):
    """One batched complex matmul block: per-element left matrices."""
    dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return dot(ar, br) - dot(ai, bi), dot(ar, bi) + dot(ai, br)


def _bkernel(ar_ref, ai_ref, br_ref, bi_ref, cr_ref, ci_ref):
    cr_ref[...], ci_ref[...] = bcmatmul_body(
        ar_ref[...], ai_ref[...], br_ref[...], bi_ref[...])


def bcmatmul(ar, ai, br, bi, *, block_q: int = 1, block_l: int = 512,
             interpret: bool = False):
    """Batched planar complex matmul: (q, M, K) @ (q, K, L) -> (q, M, L).

    Per-element left matrices (the decode-matrix use case: one (m, N)
    scatter-inverse per request).  Blocked over the batch q and the payload
    columns L; the ops layer collapses both blocks in interpret mode.
    """
    q, m, k = ar.shape
    q2, k2, ell = br.shape
    assert (q, k) == (q2, k2), (ar.shape, br.shape)
    block_l = min(block_l, ell)
    block_q = max(1, min(block_q, q))
    grid = (pl.cdiv(q, block_q), pl.cdiv(ell, block_l))
    spec_a = pl.BlockSpec((block_q, m, k), lambda i, j: (i, 0, 0))
    spec_b = pl.BlockSpec((block_q, k, block_l), lambda i, j: (i, 0, j))
    spec_c = pl.BlockSpec((block_q, m, block_l), lambda i, j: (i, 0, j))
    out_shape = [
        jax.ShapeDtypeStruct((q, m, ell), ar.dtype),
        jax.ShapeDtypeStruct((q, m, ell), ar.dtype),
    ]
    return pl.pallas_call(
        _bkernel,
        grid=grid,
        in_specs=[spec_a, spec_a, spec_b, spec_b],
        out_specs=[spec_c, spec_c],
        out_shape=out_shape,
        interpret=interpret,
        name="bcmatmul",
    )(ar, ai, br, bi)
