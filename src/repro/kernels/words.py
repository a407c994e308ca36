"""Pallas TPU kernel: interleave two real planes into (re, im) words.

The host link carries a complex result as real words laid out as numpy
lays out complex, ``w[2j] = re[j]``, ``w[2j + 1] = im[j]`` (DESIGN.md §8).
XLA builds that interleave on a TPU by moving the pair axis through a
padded tile layout: 1 GiB of temporaries for one 128 MiB bucket.  This
kernel does it in VMEM instead.  The interleave is elementwise along the
flattened arrays, so any shape is viewed as ``(rows, 128)`` lane tiles:
each tile is transposed so its lanes become sublanes, the two planes are
merged on the sublane axis (``re[j]``, ``im[j]`` on sublanes ``2j``,
``2j + 1``), and the ``(256, rows)`` result is transposed back.  Pure
data movement: every bit of both planes arrives unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["LANES", "interleave_body", "interleave_words"]

LANES = 128


def interleave_body(re: jax.Array, im: jax.Array) -> jax.Array:
    """``(..., k)`` planes -> ``(..., 2k)`` words, as straight XLA."""
    return jnp.stack([re, im], -1).reshape(*re.shape[:-1], 2 * re.shape[-1])


def _kernel(re_ref, im_ref, o_ref):
    rows = re_ref.shape[0]
    cols = jnp.stack([re_ref[...].T, im_ref[...].T], axis=1)
    o_ref[...] = cols.reshape(2 * LANES, rows).T


def interleave_words(re: jax.Array, im: jax.Array, *, block: int,
                     interpret: bool = False) -> jax.Array:
    """Interleave same-shape f32 planes; ``block`` rows of 128 lanes per
    grid step, and ``block * 128`` must divide ``re.size``."""
    rows = re.size // LANES
    out = pl.pallas_call(
        _kernel,
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((block, LANES), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((block, 2 * LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 2 * LANES), re.dtype),
        interpret=interpret,
        name="interleave_words",
    )(re.reshape(rows, LANES), im.reshape(rows, LANES))
    return out.reshape(*re.shape[:-1], 2 * re.shape[-1])
