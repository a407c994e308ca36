"""Backend-dispatch layer: jit'd wrappers around the Pallas kernels.

These are the public entry points the core plans and the FFT service route
through (DESIGN.md §6).  They accept/return either natural complex arrays
or planar f32 planes, handle the planar split, and pick factorizations and
block sizes.

Execution-mode policy (the reason the kernel path is the *default* engine
and not a TPU-only demo).  Every kernel's math lives in a pure
``*_body`` function shared by two callers:

* **pallas** -- ``pl.pallas_call`` with VMEM-sized blocks; compiled on
  TPU, ``interpret=True`` elsewhere.  The parity tests pin
  ``interpret=True`` so every body is exercised through the real Pallas
  machinery on CPU in every PR.
* **direct** -- the body evaluated on the full batch as straight XLA.
  This is the off-TPU default (``interpret=None``): the interpret-mode
  grid emulation pays per-call buffer-copy overhead (~ms per bucket at
  service sizes) that would hand the hot path back to the jnp oracle,
  while the direct body is the identical math (bit-identical results)
  at zero overhead.

``interpret=None`` therefore means "compiled pallas on TPU, direct body
elsewhere"; an explicit ``interpret=True/False`` forces the Pallas call.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune, ref, words
from repro.kernels.cmatmul import (
    bcmatmul,
    bcmatmul_body,
    cmatmul,
    cmatmul_body,
)
from repro.kernels.coded_pipeline import (
    bucket_body_fftworker,
    bucket_body_masked,
    coded_fft_bucket_masked,
    coded_fft_bucket_streaming_masked,
    coded_irfft_bucket_masked,
    coded_rfft_bucket_masked,
    half_postdecode_body,
    interleave_planes,
    ir_message_body,
    ir_unpack_body,
    irbucket_body_fftworker,
    irbucket_body_masked,
    lagrange_planes_body,
    pack_real_planes,
    rbucket_body_fftworker,
    rbucket_body_masked,
    unscramble_planes,
)
from repro.kernels.fourstep_fft import (
    _parse_stage_planes,
    encode_fourstep_body,
    encode_fourstep_fused,
    fourstep_body,
    fourstep_fused,
    fourstep_stage1,
    fourstep_stage2,
    fourstep_streaming,
    multistep_body,
    multistep_fused,
    stage1_body,
    stage2_body,
)
from repro.kernels.recombine import (
    recombine_batched_body,
    recombine_body,
    recombine_twiddle_dft,
    recombine_twiddle_dft_batched,
)

__all__ = [
    "default_interpret",
    "kernel_backend_supported",
    "split_factor",
    "fft_fourstep",
    "fourstep_planar",
    "encode_worker",
    "decode_apply",
    "recombine_planar",
    "mask_subsets",
    "lagrange_compact_planes",
    "lagrange_scatter_planes",
    "coded_bucket_direct",
    "coded_bucket_fusable",
    "coded_bucket_streamable",
    "coded_bucket_masked",
    "coded_bucket_staged",
    "coded_rbucket_direct",
    "coded_rbucket_fusable",
    "coded_rbucket_masked",
    "coded_rbucket_staged",
    "coded_irbucket_direct",
    "coded_irbucket_fusable",
    "coded_irbucket_masked",
    "coded_irbucket_staged",
    "pack_real_planes",
    "mds_apply",
    "recombine_fused",
    "interleave_words",
    "make_kernel_worker_fn",
    "make_kernel_fftn_fn",
]

# VMEM budget heuristic (TPU, compiled): fused kernel keeps ~4 (A,B) planes
# + 2 (A,A) + 2 (B,B) + 2 (A,B) twiddle planes resident; cap the fused path
# at the size where that stays under ~12 MB of the 16 MB VMEM.
_FUSED_MAX_ELEMS = 512 * 512
# Interpret-mode (host) block budget: collapse the batch into one grid step
# whenever a block stays under ~32 MB/plane -- the collapsed call traces the
# kernel body once and lowers to plain fused XLA matmuls.
_INTERPRET_BLOCK_ELEMS = 1 << 23

# bf16-plane mode: DFT/twiddle constants in bfloat16 with f32 payload and
# f32 accumulation (mixed-dtype dots promote).  Halves the constant-plane
# VMEM footprint; the relative error budget the property suite holds the
# mode to -- a plan size that exceeds it gets bf16 auto-disabled per (s, m)
# by the service warmup probe.
BF16_RTOL = 2e-2


def _plane_dtype(precision: str):
    if precision == "bf16":
        return jnp.bfloat16  # ml_dtypes.bfloat16: numpy-compatible
    if precision in (None, "f32", "float32"):
        return np.float32
    raise ValueError(f"unknown plane precision {precision!r}")


def default_interpret() -> bool:
    """Pallas interpret mode everywhere except real TPU backends."""
    return jax.default_backend() != "tpu"


def _mode(interpret: bool | None) -> str:
    """Resolve the execution mode: ``"compiled"`` | ``"interpret"`` |
    ``"direct"`` (see module docstring)."""
    if interpret is None:
        return "direct" if default_interpret() else "compiled"
    return "interpret" if interpret else "compiled"


def kernel_backend_supported(dtype) -> bool:
    """The planar kernels compute in f32 planes: complex64 plans only.

    complex128 plans (the numerics/reference tier) resolve to the jnp
    backend -- the dispatch rule in DESIGN.md §6.
    """
    return jnp.dtype(dtype) == jnp.dtype(jnp.complex64)


def split_factor(n: int) -> tuple[int, int]:
    """Factor ``n = a * b`` for the four-step kernels (a <= b).

    MXU- and tile-friendliness: when 128 divides ``n`` the lane factor
    ``b`` is the smallest multiple of 128 that is at least ``sqrt(n)`` and
    divides ``n`` -- a lane-dense (A, B) tile is what lets every kernel
    body lower on a TPU without relayouts.  Otherwise ``a, b`` are as
    close as possible; primes fall back to (1, n): stage 1 degenerates to
    the identity and stage 2 is one dense DFT matmul.
    """
    if n >= 128 and n % 128 == 0:
        k = max(1, -(-math.isqrt(n) // 128))
        while n % (128 * k):
            k += 1
        return n // (128 * k), 128 * k
    a = int(math.isqrt(n))
    while a > 1 and n % a != 0:
        a -= 1
    return a, n // a


def _block_q(batch: int, per_elem: int, interpret: bool) -> int:
    """Batch elements per grid step under the active memory budget."""
    budget = _INTERPRET_BLOCK_ELEMS if interpret else _FUSED_MAX_ELEMS
    return max(1, min(batch, budget // max(per_elem, 1)))


def _block_l(total: int, rows: int, interpret: bool) -> int:
    """Payload columns per grid step for the streaming matmul kernels."""
    if interpret:
        return max(1, min(total, _INTERPRET_BLOCK_ELEMS // max(rows, 1)))
    return min(total, 512)


def _tuned_block_q(kind: str, q: int, per_elem: int, mode: str,
                   **params) -> int:
    """Measured batch-block size, falling back to the VMEM heuristic.

    Every dispatcher routes through this: a ``lookup`` into the autotune
    table (populated by ``FFTService.warmup()`` / the bench harness, keyed
    per backend+mode+shape) is a pure dict read, so dispatch stays
    trace-time cheap; a miss degrades to the old :func:`_block_q` rule.
    """
    ent = autotune.lookup(kind, mode=mode, **params)
    if ent and "block_q" in ent:
        return max(1, min(q, int(ent["block_q"])))
    return _block_q(q, per_elem, mode == "interpret")


# Twiddle/DFT planes are computed with NUMPY and memoized: called inside a
# jit trace they embed as concrete constants, so the cos/sin construction
# is paid once per (shape) at trace time -- XLA:CPU does NOT constant-fold
# a traced transcendental table, and rebuilding the (m, L) recombine planes
# per bucket call used to cost about as much as the decode matmul itself.
@functools.lru_cache(maxsize=None)
def _dft_planes(n: int, dtype=np.float32, sign: float = -1.0):
    # sign=-1 forward DFT; sign=+1 the adjoint (c2r fold, DESIGN.md §7)
    jk = np.outer(np.arange(n), np.arange(n))
    ang = sign * 2.0 * np.pi * (jk % n) / n
    return np.cos(ang).astype(dtype), np.sin(ang).astype(dtype)


@functools.lru_cache(maxsize=None)
def _twiddle_planes(a: int, b: int, dtype=np.float32):
    # W[c, b] = omega_{a*b}^{c*b}
    cb = np.outer(np.arange(a), np.arange(b))
    ang = -2.0 * np.pi * (cb % (a * b)) / (a * b)
    return np.cos(ang).astype(dtype), np.sin(ang).astype(dtype)


@functools.lru_cache(maxsize=None)
def _recombine_planes(s: int, m: int, dtype=np.float32, sign: float = -1.0):
    # recombine twiddle W[k, i] = omega_s^{ik} plus the length-m DFT planes;
    # sign=+1 gives the adjoint pair (conjugate twiddle, F+) the c2r
    # message stage uses
    ki = np.outer(np.arange(m), np.arange(s // m))
    ang = sign * 2.0 * np.pi * (ki % s) / s
    return (np.cos(ang).astype(dtype), np.sin(ang).astype(dtype),
            *_dft_planes(m, dtype, sign))


@functools.lru_cache(maxsize=None)
def _half_dft_planes(m: int, dtype=np.float32):
    # the m//2 + 1 non-redundant butterfly rows of the length-m DFT
    jk = np.outer(np.arange(m // 2 + 1), np.arange(m))
    ang = -2.0 * np.pi * (jk % m) / m
    return np.cos(ang).astype(dtype), np.sin(ang).astype(dtype)


@functools.lru_cache(maxsize=None)
def _split_planes(ell: int, dtype=np.float32, sign: float = -1.0):
    # r2c split twiddle exp(sign*2j*pi*p/L), p <= L/2, as (1, L/2+1);
    # sign=+1 is the c2r pack twiddle (the inverse butterfly's)
    ang = sign * 2.0 * np.pi * np.arange(ell // 2 + 1) / ell
    return (np.cos(ang)[None, :].astype(dtype),
            np.sin(ang)[None, :].astype(dtype))


@functools.lru_cache(maxsize=None)
def _recombine_planes_scrambled(s: int, m: int, a: int, b: int,
                                dtype=np.float32):
    """Recombine planes with the twiddle permuted to the four-step payload
    order ``l' = c*B + d`` for natural ``l = c + d*A`` -- the bucket kernel
    carries that order through decode and unscrambles only at the output
    (kernels/coded_pipeline.py)."""
    twr, twi, fr, fi = _recombine_planes(s, m, dtype)
    perm = lambda t: np.ascontiguousarray(
        t.reshape(m, b, a).transpose(0, 2, 1).reshape(m, a * b))
    return perm(twr), perm(twi), fr, fi


@functools.lru_cache(maxsize=None)
def _multistep_planes(factors: tuple, dtype=np.float32):
    """Flat plane list for the mixed-radix multistep kernel.

    Per stage: the (f, f) DFT planes, then (for every stage but the last)
    the (f, rest) inter-stage twiddle where ``rest`` is the product of the
    remaining factors -- exactly the ordering
    ``fourstep_fft._parse_stage_planes`` regroups.
    """
    rest = 1
    for f in factors:
        rest *= f
    planes: list = []
    for idx, f in enumerate(factors):
        rest //= f
        planes.extend(_dft_planes(f, dtype))
        if idx < len(factors) - 1:
            planes.extend(_twiddle_planes(f, rest, dtype))
    return tuple(planes)


# ---------------------------------------------------------------- four-step
def fourstep_planar(xr: jax.Array, xi: jax.Array, *,
                    interpret: bool | None = None,
                    fused: bool | None = None,
                    variant: str | None = None,
                    factors=None,
                    precision: str = "f32"):
    """Batched planar FFT along the last axis via the four-step kernels.

    ``xr, xi``: (batch, L) f32 planes.  Returns natural-order (batch, L)
    planes of ``fft(x, axis=-1)``.

    ``variant`` selects the execution plan explicitly: ``"fused"`` (one
    launch; mixed-radix multistep when ``factors`` has > 2 entries),
    ``"two_pass"`` (stage1/stage2 kernels), ``"streaming"`` (double-
    buffered DMA grid, natural-order output), or ``"xla"`` (platform FFT).
    ``variant=None`` consults the autotune table for this (L, mode) and
    falls back to the VMEM heuristic on a miss: fused when the (A, B)
    matrix fits the budget, else two-pass; degenerate factorizations
    (prime or near-prime L, where the dense (B, B) DFT factor would dwarf
    an FFT's flops AND its plane would not fit VMEM) take the platform
    FFT.  The legacy ``fused`` bool maps onto fused/two_pass.

    ``precision="bf16"`` casts the DFT/twiddle planes to bfloat16 while the
    matmuls still accumulate in f32 (``preferred_element_type``); gate on
    :data:`BF16_RTOL` -- see ``FFTService.warmup``'s per-shape probe.
    """
    mode = _mode(interpret)
    batch, ell = xr.shape
    a, b = split_factor(ell)
    if variant is None and fused is not None:
        variant = "fused" if fused else "two_pass"
    if variant is None:
        ent = autotune.lookup("fourstep", L=ell, mode=mode)
        if ent:
            variant = ent.get("variant")
            if factors is None and ent.get("factors"):
                factors = tuple(ent["factors"])
    if variant is None:
        if b * b > _FUSED_MAX_ELEMS:
            variant = "xla"
        elif a * b <= _FUSED_MAX_ELEMS:
            variant = "fused"
        else:
            variant = "two_pass"
    if variant != "xla" and b * b > _FUSED_MAX_ELEMS and not (
            variant == "fused" and factors is not None and len(factors) > 2):
        # degenerate split: the dense (B, B) plane cannot fit -- the only
        # honest kernels are a multistep plan or the platform FFT
        variant = "xla"
    if variant == "xla":
        z = jnp.fft.fft(xr + 1j * xi, axis=-1)
        return jnp.real(z).astype(xr.dtype), jnp.imag(z).astype(xr.dtype)
    dt = _plane_dtype(precision)
    itp = mode == "interpret"
    if variant == "fused" and factors is not None and len(factors) > 2:
        factors = tuple(int(f) for f in factors)
        planes = _multistep_planes(factors, dt)
        if mode == "direct":
            outr, outi = multistep_body(
                xr, xi, _parse_stage_planes(factors, planes))
        else:
            bq = _tuned_block_q("fourstep", batch, ell, mode, L=ell)
            outr, outi = multistep_fused(
                xr, xi, planes, factors, block_q=bq, interpret=itp)
        # digit-reversed output X[c1 + f1*c2 + ...] -> reverse the axes
        k = len(factors)
        outr = outr.reshape(batch, *factors).transpose(
            (0,) + tuple(range(k, 0, -1))).reshape(batch, ell)
        outi = outi.reshape(batch, *factors).transpose(
            (0,) + tuple(range(k, 0, -1))).reshape(batch, ell)
        return outr, outi
    if factors is not None and len(factors) == 2:
        a, b = int(factors[0]), int(factors[1])
    far, fai = _dft_planes(a, dt)
    fbr, fbi = _dft_planes(b, dt)
    wr, wi = _twiddle_planes(a, b, dt)
    if variant == "streaming" and mode != "direct":
        bq, ba, bb = _streaming_blocks("fourstep", mode, L=ell)
        outr, outi = fourstep_streaming(
            xr.reshape(batch, a, b), xi.reshape(batch, a, b),
            far, fai, wr, wi, fbr, fbi,
            block_q=bq, block_a=ba, block_b=bb, interpret=itp)
        # natural-order (batch, B, A) output: flat X, no unscramble
        return outr.reshape(batch, ell), outi.reshape(batch, ell)
    if variant == "streaming":
        variant = "two_pass"  # direct mode has no DMA grid to stream
    xr = xr.reshape(batch, a, b)
    xi = xi.reshape(batch, a, b)
    if mode == "direct":
        if variant == "fused":
            outr, outi = fourstep_body(xr, xi, far, fai, wr, wi, fbr, fbi)
        else:
            t1r, t1i = stage1_body(xr, xi, far, fai, wr, wi)
            outr, outi = stage2_body(t1r, t1i, fbr, fbi)
    else:
        bq = _tuned_block_q("fourstep", batch, a * b, mode, L=ell)
        if variant == "fused":
            outr, outi = fourstep_fused(
                xr, xi, far, fai, wr, wi, fbr, fbi,
                block_q=bq, interpret=itp)
        else:
            t1r, t1i = fourstep_stage1(
                xr, xi, far, fai, wr, wi, block_q=bq, interpret=itp)
            outr, outi = fourstep_stage2(
                t1r, t1i, fbr, fbi, block_q=bq, interpret=itp)
    # out[c, d] holds X[c + d*A]  ->  transpose to (d, c) then flatten
    outr = jnp.swapaxes(outr, -1, -2).reshape(batch, ell)
    outi = jnp.swapaxes(outi, -1, -2).reshape(batch, ell)
    return outr, outi


@functools.partial(jax.jit, static_argnames=("interpret", "fused"))
def _fft_fourstep_impl(x, interpret, fused):
    xr, xi = ref.planar(x)
    outr, outi = fourstep_planar(xr, xi, interpret=interpret, fused=fused)
    return ref.unplanar(outr, outi)


def fft_fourstep(x: jax.Array, *, interpret: bool | None = None,
                 fused: bool | None = None) -> jax.Array:
    """Batched FFT along the last axis via the Pallas four-step kernel.

    ``x``: (..., L) complex; L is factored automatically.  Non-batched
    inputs are promoted.  Output matches ``jnp.fft.fft(x, axis=-1)`` up to
    f32 planar precision.
    """
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None]
    batch_shape = x.shape[:-1]
    ell = x.shape[-1]
    out = _fft_fourstep_impl(
        x.reshape(-1, ell), interpret, fused
    ).reshape(batch_shape + (ell,))
    return out[0] if squeeze else out


# ------------------------------------------------- fused encode + worker
def encode_worker(cr: jax.Array, ci: jax.Array,
                  gr: jax.Array, gi: jax.Array, *,
                  interpret: bool | None = None,
                  fused: bool | None = None):
    """Message planes -> coded worker spectra: ``B = fft(G @ c, axis=-1)``.

    ``cr, ci``: (q, m, L) planes of the message shards; ``gr, gi``: (n, m)
    generator planes.  Returns natural-order (q, n, L) planes.

    ``fused=None`` picks the single-kernel fused path (encode contraction
    in VMEM, m-shard DFTs -- an N/m flop saving over transforming coded
    shards) when the per-element footprint fits the VMEM budget, else the
    two-pass fallback: streamed cmatmul encode, then the four-step worker
    on the coded rows.
    """
    mode = _mode(interpret)
    q, m, ell = cr.shape
    n = gr.shape[0]
    a, b = split_factor(ell)
    if fused is None:
        # degenerate factorization (b*b over budget): two-pass, whose
        # four-step stage falls back to the platform FFT
        fused = ((m + n) * a * b <= 2 * _FUSED_MAX_ELEMS
                 and b * b <= _FUSED_MAX_ELEMS)
    if fused:
        planes = (*_dft_planes(a), *_twiddle_planes(a, b), *_dft_planes(b))
        if mode == "direct":
            br_, bi_ = encode_fourstep_body(
                cr.reshape(q, m, a, b), ci.reshape(q, m, a, b), gr, gi,
                *planes)
        else:
            itp = mode == "interpret"
            bq = _block_q(q, (m + n) * a * b, itp)
            br_, bi_ = encode_fourstep_fused(
                cr.reshape(q, m, a, b), ci.reshape(q, m, a, b), gr, gi,
                *planes, block_q=bq, interpret=itp)
        br_ = jnp.swapaxes(br_, -1, -2).reshape(q, n, ell)
        bi_ = jnp.swapaxes(bi_, -1, -2).reshape(q, n, ell)
        return br_, bi_
    # two-pass: encode via the streaming cmatmul (batch folded into the
    # payload columns -- G is shared), then the planar four-step worker
    tr = jnp.transpose(cr, (1, 0, 2)).reshape(m, q * ell)
    ti = jnp.transpose(ci, (1, 0, 2)).reshape(m, q * ell)
    if mode == "direct":
        er, ei = cmatmul_body(gr, gi, tr, ti)
    else:
        itp = mode == "interpret"
        bl = _block_l(q * ell, m + n, itp)
        er, ei = cmatmul(gr, gi, tr, ti, block_l=bl, interpret=itp)
    ar = jnp.transpose(er.reshape(n, q, ell), (1, 0, 2)).reshape(q * n, ell)
    ai = jnp.transpose(ei.reshape(n, q, ell), (1, 0, 2)).reshape(q * n, ell)
    br_, bi_ = fourstep_planar(ar, ai, interpret=interpret)
    return br_.reshape(q, n, ell), bi_.reshape(q, n, ell)


# ------------------------------------------------------------ decode apply
def decode_apply(dr: jax.Array, di: jax.Array,
                 br: jax.Array, bi: jax.Array, *,
                 interpret: bool | None = None):
    """Per-request decode matrices applied as one batched MXU matmul.

    ``dr, di``: (q, m, N) planes of scatter decode matrices (zero columns
    for stragglers -- DESIGN.md §6); ``br, bi``: (q, N, L) worker-result
    planes.  Returns (q, m, L) decoded sub-transform planes.
    """
    mode = _mode(interpret)
    if mode == "direct":
        return bcmatmul_body(dr, di, br, bi)
    itp = mode == "interpret"
    q, m, n = dr.shape
    ell = br.shape[-1]
    bq = _block_q(q, (m + n) * ell, itp)
    bl = _block_l(ell, m + n, itp)
    return bcmatmul(dr, di, br, bi, block_q=bq, block_l=bl, interpret=itp)


# --------------------------------------- device-resident decode matrices
def mask_subsets(masks: jax.Array, m: int) -> jax.Array:
    """First-``m`` responder indices per request, in-trace.

    ``masks``: bool ``(B, N)``.  Returns ``(B, m)`` int32 -- the traced
    twin of ``DecodeMatrixCache.subset_of`` / ``mds.first_available``
    (stable argsort keeps arrival order), kept inline so the kernel layer
    never imports upward into ``repro.core``.
    """
    order = jnp.argsort(jnp.logical_not(jnp.asarray(masks)),
                        axis=-1, stable=True)
    return order[..., :m].astype(jnp.int32)


def lagrange_compact_planes(subsets: jax.Array, n: int):
    """Per-request compact ``(B, m, m)`` inverse planes from subsets --
    the gathered-decode form of the direct (off-TPU) bucket executors,
    built in-trace with no host inversion (DESIGN.md §8)."""
    ivr, ivi, _, _ = lagrange_planes_body(subsets, n)
    return ivr, ivi


def lagrange_scatter_planes(subsets: jax.Array, n: int):
    """Per-request scatter ``(B, m, N)`` decode planes (zero straggler
    columns) from subsets -- the MXU form :func:`decode_apply` and the
    stage-path kernels contract against."""
    _, _, dr, di = lagrange_planes_body(subsets, n)
    return dr, di


# -------------------------------------------------------------- recombine
def recombine_planar(cr: jax.Array, ci: jax.Array, s: int, *,
                     interpret: bool | None = None):
    """Batched master recombination on planes: (q, m, s/m) -> (q, s)."""
    mode = _mode(interpret)
    q, m, ell = cr.shape
    wr, wi, fr, fi = _recombine_planes(s, m)
    if mode == "direct":
        outr, outi = recombine_batched_body(cr, ci, wr, wi, fr, fi)
    else:
        itp = mode == "interpret"
        bq = _block_q(q, 2 * m * ell, itp)
        bl = _block_l(ell, 2 * m, itp)
        outr, outi = recombine_twiddle_dft_batched(
            cr, ci, wr, wi, fr, fi, block_q=bq, block_l=bl, interpret=itp)
    return outr.reshape(q, s), outi.reshape(q, s)


# ---------------------------------------------------- fused bucket pipeline
def coded_bucket_fusable(s: int, m: int, n: int) -> bool:
    """Does the whole-bucket pipeline fit one kernel's VMEM working set?

    Per batch element the kernel keeps the request, the m message shards,
    the N coded spectra, the decoded shards and the output resident:
    roughly ``2 * (2*s + (m + n) * L)`` f32 values.  Degenerate
    factorizations (dense (B, B) DFT factor over budget) are excluded --
    the stage path's four-step falls back to the platform FFT there.
    """
    ell = s // m
    a, b = split_factor(ell)
    return ((2 * s + (m + n) * ell) <= 2 * _FUSED_MAX_ELEMS
            and b * b <= _FUSED_MAX_ELEMS)


def coded_bucket_streamable(s: int, m: int, n: int) -> bool:
    """Can the over-VMEM c2c bucket run as the ONE-launch streaming grid?

    The streaming kernel keeps only (block_q, A, block_b, m) /
    (block_q, block_a, B, m) tiles resident, so the batch working set
    drops out of the gate; what must still fit are the shared planes --
    the (A, A)/(B, B) DFT factors and the (m, s) pre-scrambled recombine
    twiddle -- plus a non-degenerate split (A > 1, else there is nothing
    to tile over).
    """
    ell = s // m
    a, b = split_factor(ell)
    return (a > 1
            and a * a <= _FUSED_MAX_ELEMS
            and b * b <= _FUSED_MAX_ELEMS
            and m * ell <= 4 * _FUSED_MAX_ELEMS)


# Default streaming tile edge: the Mosaic compile time of the streaming
# kernels grows with the tile's volume (about a minute at 128 for
# s = 2^20, three at 256, on the v5e compiler).
_STREAM_TILE = 128


def _streaming_blocks(kind: str, mode: str, **params):
    """(block_q, block_a, block_b) for a streaming launch: tuned entry if
    the autotune table has one, else the default tile."""
    ent = autotune.lookup(kind, mode=mode, **params) or {}
    return (max(1, int(ent.get("block_q", 1) or 1)),
            int(ent.get("block_a", _STREAM_TILE) or _STREAM_TILE),
            int(ent.get("block_b", _STREAM_TILE) or _STREAM_TILE))


def coded_bucket_masked(xr: jax.Array, xi: jax.Array, masks: jax.Array,
                        gr: jax.Array, gi: jax.Array, s: int, *,
                        interpret: bool | None = None,
                        block_q: int | None = None,
                        precision: str = "f32"):
    """The service's whole-bucket c2c hot path as ONE Pallas launch.

    ``xr, xi``: (q, s) request planes; ``masks``: (q, N) responder masks,
    shipped RAW -- subset selection (first-m responders) happens inside
    the kernel (``subsets_from_masks_body``), then the Lagrange weights are
    built in VMEM per grid step and contracted immediately (DESIGN.md §8);
    ``gr, gi``: (N, m) generator planes.  Returns (q, s) output planes --
    interleave, fused encode+worker, decode and recombine with no HBM
    round-trips between stages (DESIGN.md §6).  Shapes beyond
    :func:`coded_bucket_fusable` route to the streaming double-buffered
    grid when :func:`coded_bucket_streamable` allows; ``block_q=None``
    consults the autotune table, ``precision="bf16"`` casts the shared
    planes (f32 accumulation throughout).
    """
    mode = _mode(interpret)
    q, _ = xr.shape
    n, m = gr.shape
    ell = s // m
    a, b = split_factor(ell)
    dt = _plane_dtype(precision)
    planes = (*_dft_planes(a, dt), *_twiddle_planes(a, b, dt),
              *_dft_planes(b, dt), *_recombine_planes_scrambled(s, m, a, b, dt))
    if mode == "direct":
        # the body on the full batch as straight XLA, between the same
        # interleave and unscramble the kernels run around their launch
        twr, twi = (p.reshape(m, a, b) for p in planes[6:8])
        cr, ci = interleave_planes(xr, xi, m, a, b)
        yr, yi = bucket_body_masked(cr, ci, masks, gr, gi, *planes[:6],
                                    twr, twi, *planes[8:])
        return (unscramble_planes(yr).reshape(q, s),
                unscramble_planes(yi).reshape(q, s))
    itp = mode == "interpret"
    if not coded_bucket_fusable(s, m, n) and coded_bucket_streamable(s, m, n):
        bq, ba, bb = _streaming_blocks("bucket", mode, s=s, m=m, n=n)
        return coded_fft_bucket_streaming_masked(
            xr, xi, masks, gr, gi, *planes,
            block_q=(block_q or bq), block_a=ba, block_b=bb, interpret=itp)
    if block_q is None:
        block_q = _tuned_block_q("bucket", q, 2 * s + (m + n) * ell, mode,
                                 s=s, m=m, n=n)
    return coded_fft_bucket_masked(
        xr, xi, masks, gr, gi, *planes, block_q=block_q, interpret=itp)


def coded_bucket_direct(xr: jax.Array, xi: jax.Array,
                        dvr: jax.Array, dvi: jax.Array,
                        subsets: jax.Array,
                        gr: jax.Array, gi: jax.Array, s: int):
    """The off-TPU bucket executor: same fused pipeline, host lowerings.

    Same stage structure as :func:`coded_bucket_masked`, with the worker DFT on
    the platform FFT and the decode as gathered compact ``(m, m)``
    matmuls (``dvr/dvi`` inverses + ``subsets`` responder indices from
    ``DecodeMatrixCache.compact``) -- the lowerings a Mosaic kernel cannot
    express but a CPU wants (DESIGN.md §6).  No VMEM gate: valid at any
    bucket shape.
    """
    m = gr.shape[1]
    return bucket_body_fftworker(
        xr, xi, dvr, dvi, subsets, gr, gi, *_recombine_planes(s, m))


def coded_bucket_staged(xr: jax.Array, xi: jax.Array,
                        dr: jax.Array, di: jax.Array,
                        gr: jax.Array, gi: jax.Array, s: int, *,
                        interpret: bool | None = None):
    """The c2c bucket as stage kernels, for shapes past both one-launch
    gates or decode planes from the host: interleave on planes
    (``c_i[j] = x[i + j*m]``), fused encode+worker, one batched decode
    matmul against the ``(q, m, N)`` scatter planes ``dr, di``, fused
    twiddle+DFT recombine.  Returns (q, s) output planes."""
    q = xr.shape[0]
    m = gr.shape[1]
    ell = s // m
    cr = jnp.swapaxes(xr.reshape(q, ell, m), -1, -2)
    ci = jnp.swapaxes(xi.reshape(q, ell, m), -1, -2)
    br, bi = encode_worker(cr, ci, gr, gi, interpret=interpret)
    hr, hi = decode_apply(dr, di, br, bi, interpret=interpret)
    return recombine_planar(hr, hi, s, interpret=interpret)


# ------------------------------------------------- real-input (r2c) buckets
def coded_rbucket_fusable(s: int, m: int, n: int) -> bool:
    """VMEM gate for the fused r2c bucket kernel.

    Same accounting as :func:`coded_bucket_fusable` with HALF-length
    payloads (packed shards of L/2): the r2c working set is the real
    request + half spectra + (m + n) packed shards.
    """
    n2 = s // m // 2
    a, b = split_factor(n2)
    return ((2 * s + (m + n) * n2) <= 2 * _FUSED_MAX_ELEMS
            and b * b <= _FUSED_MAX_ELEMS)


def _r2c_postdecode_planes(s: int, m: int, dtype=np.float32):
    n2 = s // m // 2
    return (*_split_planes(2 * n2, dtype), *_recombine_planes(s, m, dtype)[:2],
            *_half_dft_planes(m, dtype))


def coded_rbucket_masked(xr: jax.Array, masks: jax.Array,
                         gr: jax.Array, gi: jax.Array, s: int, *,
                         interpret: bool | None = None,
                         block_q: int | None = None,
                         precision: str = "f32"):
    """The r2c whole-bucket hot path (DESIGN.md §7) as ONE Pallas launch.

    ``xr``: (q, s) REAL request plane; ``masks``: (q, N) raw responder
    masks, decoded in the kernel (cf. :func:`coded_bucket_masked`);
    ``gr, gi``: (N, m) generator planes.  Returns (q, s//2+1)
    half-spectrum planes.  Caller checks :func:`coded_rbucket_fusable`
    (the packed-butterfly pairing couples column p with n2-p, so the r2c
    pipeline has no column-local streaming variant -- see DESIGN.md §10).
    """
    mode = _mode(interpret)
    q, _ = xr.shape
    n, m = gr.shape
    n2 = s // m // 2
    a, b = split_factor(n2)
    dt = _plane_dtype(precision)
    planes = (*_dft_planes(a, dt), *_twiddle_planes(a, b, dt),
              *_dft_planes(b, dt), *_r2c_postdecode_planes(s, m, dt))
    if mode == "direct":
        return rbucket_body_masked(xr, masks, gr, gi, *planes, s)
    itp = mode == "interpret"
    if block_q is None:
        block_q = _tuned_block_q("rbucket", q, 2 * s + (m + n) * n2, mode,
                                 s=s, m=m, n=n)
    return coded_rfft_bucket_masked(xr, masks, gr, gi, *planes, s,
                                    block_q=block_q, interpret=itp)


def coded_rbucket_direct(xr: jax.Array, dvr: jax.Array, dvi: jax.Array,
                         subsets: jax.Array,
                         gr: jax.Array, gi: jax.Array, s: int):
    """Off-TPU r2c bucket executor: platform-FFT worker on the packed
    half-length shards, gathered compact decode, symmetry postdecode
    (cf. :func:`coded_bucket_direct`)."""
    m = gr.shape[1]
    return rbucket_body_fftworker(
        xr, dvr, dvi, subsets, gr, gi, *_r2c_postdecode_planes(s, m), s)


def coded_rbucket_staged(xr: jax.Array, dr: jax.Array, di: jax.Array,
                         gr: jax.Array, gi: jax.Array, s: int, *,
                         interpret: bool | None = None):
    """The r2c bucket as stage kernels (cf. :func:`coded_bucket_staged`):
    pair packing, fused encode+worker on the half-length shards, decode
    matmul, then the symmetry postdecode -- an elementwise butterfly and
    one (m//2+1, m) contraction, straight XLA in every mode.  Returns
    (q, s//2+1) planes."""
    m = gr.shape[1]
    zr, zi = pack_real_planes(xr, m)
    br, bi = encode_worker(zr, zi, gr, gi, interpret=interpret)
    hr, hi = decode_apply(dr, di, br, bi, interpret=interpret)
    return half_postdecode_body(hr, hi, *_r2c_postdecode_planes(s, m), s)


# ------------------------------------------------ real-output (c2r) buckets
def _c2r_message_planes(s: int, m: int, dtype=np.float32):
    ctwr, ctwi, fpr, fpi = _recombine_planes(s, m, dtype, sign=1.0)
    pwr, pwi = _split_planes(s // m, dtype, sign=1.0)
    return fpr, fpi, ctwr, ctwi, pwr, pwi


def coded_irbucket_fusable(s: int, m: int, n: int) -> bool:
    """VMEM gate for the fused c2r bucket kernel.

    The c2r working set mirrors the r2c one (half-spectrum request +
    Hermitian intermediate + (m + n) packed half-length shards + real
    output), so the accounting is shared with
    :func:`coded_rbucket_fusable`.
    """
    return coded_rbucket_fusable(s, m, n)


def coded_irbucket_masked(yr: jax.Array, yi: jax.Array, masks: jax.Array,
                          gr: jax.Array, gi: jax.Array, s: int, *,
                          interpret: bool | None = None,
                          block_q: int | None = None,
                          precision: str = "f32"):
    """The c2r whole-bucket hot path (DESIGN.md §9) as ONE Pallas launch.

    ``yr, yi``: (q, s//2+1) half-spectrum request planes; ``masks``:
    (q, N) raw responder masks, decoded in the kernel (cf.
    :func:`coded_bucket_masked`); ``gr, gi``: (N, m) generator planes.
    Returns the (q, s) REAL output plane -- adjoint message butterfly,
    fused encode + half-length ifft worker (conj trick on planes), decode
    and pair unpack with no HBM round-trips between stages.  Caller checks
    :func:`coded_irbucket_fusable`.
    """
    mode = _mode(interpret)
    q, _ = yr.shape
    n, m = gr.shape
    n2 = s // m // 2
    a, b = split_factor(n2)
    dt = _plane_dtype(precision)
    planes = (*_dft_planes(a, dt), *_twiddle_planes(a, b, dt),
              *_dft_planes(b, dt), *_c2r_message_planes(s, m, dt))
    if mode == "direct":
        return irbucket_body_masked(yr, yi, masks, gr, gi, *planes, s)
    itp = mode == "interpret"
    if block_q is None:
        block_q = _tuned_block_q("irbucket", q, 2 * s + (m + n) * n2, mode,
                                 s=s, m=m, n=n)
    return coded_irfft_bucket_masked(yr, yi, masks, gr, gi, *planes, s,
                                     block_q=block_q, interpret=itp)


def coded_irbucket_direct(yr: jax.Array, yi: jax.Array,
                          dvr: jax.Array, dvi: jax.Array,
                          subsets: jax.Array,
                          gr: jax.Array, gi: jax.Array, s: int):
    """Off-TPU c2r bucket executor: adjoint message stage on planes,
    platform-ifft worker on the packed half-length shards, gathered
    compact decode, relabel unpack.  Returns ONE real plane (q, s)."""
    m = gr.shape[1]
    return irbucket_body_fftworker(
        yr, yi, dvr, dvi, subsets, gr, gi, *_c2r_message_planes(s, m), s)


def coded_irbucket_staged(yr: jax.Array, yi: jax.Array,
                          dr: jax.Array, di: jax.Array,
                          gr: jax.Array, gi: jax.Array, s: int, *,
                          interpret: bool | None = None):
    """The c2r bucket as stage kernels (cf. :func:`coded_bucket_staged`):
    adjoint message stage, the ifft worker as
    ``ifft(G @ z) = conj(fft(conj(G) @ conj(z))) / n2`` through the same
    fused encode+worker kernel, decode matmul, relabel unpack.  Returns
    ONE real plane (q, s)."""
    m = gr.shape[1]
    n2 = s // m // 2
    zr, zi = ir_message_body(yr, yi, *_c2r_message_planes(s, m), s, m)
    br, bi = encode_worker(zr, -zi, gr, -gi, interpret=interpret)
    br, bi = br / n2, -bi / n2
    hr, hi = decode_apply(dr, di, br, bi, interpret=interpret)
    return ir_unpack_body(hr, hi)


# ----------------------------------------------------- complex entry points
@functools.partial(jax.jit, static_argnames=("interpret",))
def _mds_apply_impl(g, c, interpret):
    mode = _mode(interpret)
    gr, gi = ref.planar(g)
    payload = c.shape[1:]
    flat = c.reshape(c.shape[0], -1)
    cr, ci = ref.planar(flat)
    if mode == "direct":
        outr, outi = cmatmul_body(gr, gi, cr, ci)
    else:
        itp = mode == "interpret"
        bl = _block_l(flat.shape[1], g.shape[0] + g.shape[1], itp)
        outr, outi = cmatmul(gr, gi, cr, ci, block_l=bl, interpret=itp)
    return ref.unplanar(outr, outi).reshape((g.shape[0],) + payload)


def mds_apply(g: jax.Array, c: jax.Array, *, interpret: bool | None = None):
    """Kernel-backed ``G @ c`` for MDS encode / decode-apply.

    ``g``: (n, m) complex code matrix; ``c``: (m, *payload).
    """
    return _mds_apply_impl(g, c, interpret)


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def _recombine_impl(c_hat, s, interpret):
    mode = _mode(interpret)
    m, ell = c_hat.shape
    cr, ci = ref.planar(c_hat)
    wr, wi, fr, fi = _recombine_planes(s, m)
    if mode == "direct":
        outr, outi = recombine_body(cr, ci, wr, wi, fr, fi)
    else:
        itp = mode == "interpret"
        bl = _block_l(ell, 2 * m, itp)
        outr, outi = recombine_twiddle_dft(
            cr, ci, wr, wi, fr, fi, block_l=bl, interpret=itp)
    return ref.unplanar(outr, outi).reshape(s)


def recombine_fused(c_hat: jax.Array, s: int, *, interpret: bool | None = None):
    """Kernel-backed master recombination: (m, s/m) decoded C -> X (s,)."""
    return _recombine_impl(c_hat, s, interpret)


def _words_block(n: int) -> int | None:
    """Rows of 128 lanes per grid step of the interleave kernel for ``n``
    elements, or None where its tiling does not fit: whole multiples of
    128 rows (the transposed tile's lanes), up to 512."""
    if n % (words.LANES * 128):
        return None
    rows = n // words.LANES
    return next(b for b in (512, 256, 128) if rows % b == 0)


def interleave_words(re: jax.Array, im: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """``(..., k)`` f32 planes -> ``(..., 2k)`` (re, im) words
    (kernels/words.py): the Pallas kernel where its tiling fits, the
    direct body elsewhere (small buckets, where XLA's padded interleave
    costs little)."""
    mode = _mode(interpret)
    block = _words_block(re.size)
    if mode == "direct" or block is None or re.dtype != jnp.float32:
        return words.interleave_body(re, im)
    return words.interleave_words(re, im, block=block,
                                  interpret=mode == "interpret")


# ------------------------------------------------------------- worker fns
def make_kernel_worker_fn(interpret: bool | None = None,
                          inverse: bool = False):
    """A ``CodedFFT.worker_fn`` that uses the Pallas four-step kernel.

    Satisfies the ``CodedPlan`` worker contract: transforms the LAST axis
    and maps over arbitrary leading axes.  All leading axes -- (workers,),
    (batch, workers) from the batched service scheduler, or (batch,
    n_local) under the distributed runtime -- are collapsed into the
    kernel's batch dimension, so a bucket of requests costs one Pallas
    launch instead of one per request.

    ``inverse=True`` yields the ifft worker of the inverse plans
    (DESIGN.md §7) via ``ifft(a) = conj(fft(conj(a))) / L`` -- one extra
    pair of sign flips on the imaginary plane, same kernel.
    """

    def worker_fn(a: jax.Array) -> jax.Array:
        lead, ell = a.shape[:-1], a.shape[-1]
        flat = a.reshape(-1, ell)
        if inverse:
            out = jnp.conj(
                fft_fourstep(jnp.conj(flat), interpret=interpret)) / ell
        else:
            out = fft_fourstep(flat, interpret=interpret)
        return out.reshape(lead + (ell,))

    return worker_fn


def make_kernel_fftn_fn(nd: int, interpret: bool | None = None):
    """An n-D worker fn: the four-step kernel swept over the last ``nd``
    axes (separability of the multidimensional DFT).  Used by the n-D and
    multi-input plans when the kernel backend is active."""
    worker_1d = make_kernel_worker_fn(interpret)

    def worker_fn(a: jax.Array) -> jax.Array:
        for ax in range(a.ndim - nd, a.ndim):
            a = jnp.moveaxis(worker_1d(jnp.moveaxis(a, ax, -1)), -1, ax)
        return a

    return worker_fn
