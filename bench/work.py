"""The work of one transform: operations by the FFT convention, bytes moved.

The work is that of the transform, not of the kernels that implement it:
encode, decode, recombine and padded rows do not count, so a roofline
share built from these numbers cannot pass 100% whatever the program
runs in their place.
"""

from __future__ import annotations

import math

# bytes of one element of the transform's input and output
_IO_BYTES = {
    "c2c": (8, 8),    # complex64 in, complex64 out
    "r2c": (4, 8),    # float32 in, complex64 half spectrum out
    "c2r": (8, 4),    # complex64 half spectrum in, float32 out
}


def flops(kind: str, s: int) -> float:
    """5 s log2 s for a complex transform of length ``s``; half for the
    real kinds."""
    full = 5.0 * s * math.log2(s)
    if kind == "c2c":
        return full
    if kind in ("r2c", "c2r"):
        return full / 2.0
    raise ValueError(f"unknown transform kind {kind!r}")


def bytes_moved(kind: str, s: int) -> int:
    """Input and output, each read or written once."""
    if kind not in _IO_BYTES:
        raise ValueError(f"unknown transform kind {kind!r}")
    b_in, b_out = _IO_BYTES[kind]
    half = s // 2 + 1
    n_in = half if kind == "c2r" else s
    n_out = half if kind == "r2c" else s
    return b_in * n_in + b_out * n_out


def least_seconds(kind: str, s: int, peak: dict) -> float:
    """The least time one chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops(kind, s) / peak["flops_per_s"],
               bytes_moved(kind, s) / peak["bytes_per_s"])
