"""Fused dequantize -> scale -> weighted-accumulate combine (TPU).

The compressed data plane's server hot spot: N clients post int8
per-chunk-quantized packed delta buffers; the Model Aggregator must
dequantize each (q * per-chunk scale) and fold the cohort into one
weighted f32 delta. Fusing the dequant with the reduction means the f32
expansion of each client's buffer never round-trips to HBM — per
(N, BT) VMEM tile the kernel reads N int8 rows plus a small scale
block and writes one f32 output row, an ~4x HBM-read saving over a
separate dequant pass at int8.

Grid: (cdiv(T, BT),), BT a multiple of 8 quantization chunks. Block:
q (N, BT) int8; scales chunk-major (BT/CHUNK, N) f32 with the client
weights folded in, so the TPU's (8, 128) block rule holds for any N.
Per chunk, the weighted reduction is a (1, N) x (N, CHUNK) matmul on
the MXU, like the masked combine in ``kernels/secure_agg``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import EXACT_F32

CHUNK = 1024          # quantization chunk: one f32 scale per 1024 floats
SCALE_ROWS = 8        # a scale block spans >= 8 chunk rows (sublane rule)
DEFAULT_BT = 32 * CHUNK   # tile width — a SCALE_ROWS * CHUNK multiple


def _tile_width(bt: int, t: int) -> int:
    """Columns per grid step. The scale operands are laid out chunk-major
    (one row per chunk), so a tile's scale block is (bt // CHUNK, ·): the
    TPU needs that row count to be a multiple of 8 or the whole array.
    Hence ``bt`` is a ``SCALE_ROWS * CHUNK`` multiple, or all of T."""
    if t % CHUNK:
        raise ValueError(f"T={t} must be a multiple of CHUNK={CHUNK}")
    if bt % (SCALE_ROWS * CHUNK):
        raise ValueError(
            f"tile width {bt} must be a multiple of {SCALE_ROWS * CHUNK}")
    return min(bt, t)


def _scale_chunks(x, s_ref, o_ref):
    """o[:, chunk j] = x[:, chunk j] * s[j] for the tile's chunks — a
    static loop of lane-aligned 1024-wide slices, so no lane->sublane
    reshape is ever asked of the TPU."""
    for j in range(s_ref.shape[0]):
        lo = j * CHUNK
        o_ref[:, lo:lo + CHUNK] = x[:, lo:lo + CHUNK] * s_ref[j:j + 1, :]


def _dequant_reduce_kernel(q_ref, ws_ref, o_ref):
    """q_ref: (N, BT) int8; ws_ref: (BT/CHUNK, N) f32 — row j holds every
    client's weight times its chunk-j scale; o_ref: (1, BT) f32.

    The int8 -> f32 widening runs on the VPU; per chunk, the scaled
    weighted accumulate across clients is one (1, N) x (N, CHUNK) MXU
    matmul.
    """
    for j in range(ws_ref.shape[0]):
        lo = j * CHUNK
        q = q_ref[:, lo:lo + CHUNK].astype(jnp.float32)
        o_ref[:, lo:lo + CHUNK] = jnp.dot(
            ws_ref[j:j + 1, :], q, precision=EXACT_F32,
            preferred_element_type=jnp.float32)


def dequant_reduce_flat(q, scales, weights, *, bt: int = DEFAULT_BT,
                        interpret: bool = True):
    """q: (N, T) int8, T a CHUNK multiple; scales: (N, T/CHUNK) f32;
    weights: (N,) f32 -> (T,) f32 weighted dequantized sum.

    The grid covers T in ``bt``-wide tiles; a last partial tile reads
    past T, but every output column depends only on its own input
    column, and writes past T are dropped — so no padded copy of the
    (N, T) cohort is ever made.
    """
    n, t = q.shape
    bt = _tile_width(bt, t)
    ws = (weights.astype(jnp.float32)[:, None]
          * scales.astype(jnp.float32)).T              # (T/CHUNK, N)
    out = pl.pallas_call(
        _dequant_reduce_kernel,
        grid=(pl.cdiv(t, bt),),
        in_specs=[pl.BlockSpec((n, bt), lambda i: (0, i)),
                  pl.BlockSpec((bt // CHUNK, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, bt), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, t), jnp.float32),
        interpret=interpret,
    )(q, ws)
    return out[0]


# ---------------------------------------------------------------------------
# masked variant (DESIGN.md §Composable privacy): modular integer sum ->
# centered decode -> common-grid dequant, mirroring kernels/secure_agg's
# masked_sum / masked_sum_corrected pair.
# ---------------------------------------------------------------------------
def _wrapping_sum(ref):
    """Column sum of an (N, BT) uint32 block, wrapping mod 2**32, as an
    int32 bit pattern. The TPU has no reductions over unsigned integers,
    so the rows are added as int32: two's-complement addition wraps to
    the same bits as the uint32 sum."""
    x = jax.lax.bitcast_convert_type(ref[...], jnp.int32)
    return jnp.sum(x, axis=0, keepdims=True)


def _centered(s, modulus_bits: int):
    """Modular residue -> signed value on the VPU.

    ``s`` is the bit pattern of the cohort's uint32 wrap-around sum, held
    as int32; M = 2**modulus_bits divides 2**32 so masking with M-1
    yields the exact residue. For M = 2**32 the bit pattern already is
    the two's-complement centered value; narrower moduli center by
    subtracting M above the half-range (the residue fits int32 exactly).
    """
    if modulus_bits == 32:
        return s
    r = s & jnp.int32((1 << modulus_bits) - 1)
    return r - jnp.where(r >= jnp.int32(1 << (modulus_bits - 1)),
                         jnp.int32(1 << modulus_bits), jnp.int32(0))


def _masked_dequant_reduce_kernel(z_ref, s_ref, o_ref, *,
                                  modulus_bits: int):
    """z_ref: (N, BT) uint32; s_ref: (BT/CHUNK, 1) f32; o_ref: (1, BT) f32.

    The modular sum, residue extraction and centering run on the VPU in
    integer arithmetic (this is where cancellation is bit-exact); only
    the final common-grid scale touches floats.
    """
    c = _centered(_wrapping_sum(z_ref), modulus_bits).astype(jnp.float32)
    _scale_chunks(c, s_ref, o_ref)


def _masked_dequant_reduce_corr_kernel(z_ref, c_ref, s_ref, o_ref, *,
                                       modulus_bits: int):
    """Dropout-repair variant: subtract the survivors' summed integer
    corrections inside the tile before the residue decode — exactly the
    ``masked_sum_corrected`` pattern, in modular arithmetic (wrap-around
    subtraction preserves residues mod M)."""
    s = _wrapping_sum(z_ref) - _wrapping_sum(c_ref)
    c = _centered(s, modulus_bits).astype(jnp.float32)
    _scale_chunks(c, s_ref, o_ref)


def masked_dequant_reduce_flat(z, scales, *, modulus_bits: int,
                               corr=None, bt: int = DEFAULT_BT,
                               interpret: bool = True):
    """z: (N, T) uint masked residue streams (T a CHUNK multiple);
    scales: (T/CHUNK,) f32 cohort-common grid; optional corr: (N, T)
    uint repair corrections -> (T,) f32 decoded cohort *sum*.

    Unlike ``dequant_reduce_flat`` there are no per-client weights: a
    weighted modular sum would destroy mask cancellation, so weighting is
    pre-applied client-side before quantization (the caller divides the
    decoded sum by the cohort's total weight). Tiled like
    ``dequant_reduce_flat``: a last partial tile needs no padded copy.
    """
    n, t = z.shape
    bt = _tile_width(bt, t)
    z = z.astype(jnp.uint32)
    s2d = scales.astype(jnp.float32).reshape(t // CHUNK, 1)
    row_spec = pl.BlockSpec((n, bt), lambda i: (0, i))
    s_spec = pl.BlockSpec((bt // CHUNK, 1), lambda i: (i, 0))
    if corr is None:
        kernel = partial(_masked_dequant_reduce_kernel,
                         modulus_bits=int(modulus_bits))
        in_specs, operands = [row_spec, s_spec], (z, s2d)
    else:
        kernel = partial(_masked_dequant_reduce_corr_kernel,
                         modulus_bits=int(modulus_bits))
        in_specs = [row_spec, row_spec, s_spec]
        operands = (z, corr.astype(jnp.uint32), s2d)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(t, bt),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bt), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, t), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[0]
