"""Fused dequantize -> weighted-sum secure-aggregation combine (TPU).

The FL Model Aggregator's data-plane hot spot: combining N clients' int8
quantized, pairwise-masked updates into the new global tensor. Fusing the
dequant with the reduction means the f32 expansion of each update never
round-trips to HBM — per (8, 4096)-ish VMEM tile the kernel reads N int8
rows and writes one f32 row.

Grid: (T / BT,). Block: q (N, BT) int8; scales/weights (N, 1) f32
(broadcast); out (BT,) f32. The combine is a (1, N) x (N, BT) matmul on the
MXU with the per-client scale folded into the left operand.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import EXACT_F32

DEFAULT_BT = 4096


def _combine_kernel(q_ref, ws_ref, o_ref):
    """q_ref: (N, BT) int8; ws_ref: (1, N) f32 (= weights*scales);
    o_ref: (1, BT) f32."""
    q = q_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.dot(ws_ref[...], q, precision=EXACT_F32,
                         preferred_element_type=jnp.float32)


def _combine_call(q, ws, *, bt: int, interpret: bool, corr=None):
    """Shared pallas_call: (N, T) rows x (1, N) row weights -> (T,) f32.

    With ``corr`` (same (N, T) shape as ``q``) the corrected kernel body
    subtracts it row-wise inside the combine tile — one tiling
    implementation for both the plain and the dropout-repair path.
    """
    N, T = q.shape
    bt = min(bt, T)
    pad = (-T) % bt
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad)))
        if corr is not None:
            corr = jnp.pad(corr, ((0, 0), (0, pad)))
    Tp = T + pad
    row_spec = pl.BlockSpec((N, bt), lambda i: (0, i))
    w_spec = pl.BlockSpec((1, N), lambda i: (0, 0))
    kernel, operands = ((_combine_kernel, (q, ws)) if corr is None
                        else (_combine_corrected_kernel, (q, corr, ws)))
    out = pl.pallas_call(
        kernel,
        grid=(Tp // bt,),
        in_specs=[row_spec] * (len(operands) - 1) + [w_spec],
        out_specs=pl.BlockSpec((1, bt), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Tp), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[0, :T]


def secure_agg_combine_flat(q, scales, weights, *, bt: int = DEFAULT_BT,
                            interpret: bool = True):
    """q: (N, T) int8; scales/weights: (N,) f32 -> (T,) f32."""
    N = q.shape[0]
    ws = (weights.astype(jnp.float32)
          * scales.astype(jnp.float32)).reshape(1, N)
    return _combine_call(q, ws, bt=bt, interpret=interpret)


def masked_sum_flat(x, weights, *, bt: int = DEFAULT_BT,
                    interpret: bool = True):
    """Full-precision combine for the packed secure-agg data plane.

    x: (N, T) f32 pairwise-masked packed updates; weights: (N,) f32 ->
    (T,) f32 weighted sum. Same (1, N) x (N, BT) MXU matmul as the int8
    path, minus the dequant — masks must cancel bit-for-bit up to fp32
    accumulation order, so the masked plane stays in f32 end to end.
    """
    N = x.shape[0]
    ws = weights.astype(jnp.float32).reshape(1, N)
    return _combine_call(x.astype(jnp.float32), ws, bt=bt,
                         interpret=interpret)


def _combine_corrected_kernel(x_ref, c_ref, ws_ref, o_ref):
    """x_ref/c_ref: (N, BT) f32; ws_ref: (1, N) f32; o_ref: (1, BT) f32.

    The subtraction runs on the VPU while the weighted reduction stays on
    the MXU — the (N, BT) correction tile never round-trips to HBM as a
    separate "repaired updates" matrix.
    """
    d = x_ref[...] - c_ref[...]
    o_ref[...] = jnp.dot(ws_ref[...], d, precision=EXACT_F32,
                         preferred_element_type=jnp.float32)


def masked_sum_corrected_flat(x, corr, weights, *, bt: int = DEFAULT_BT,
                              interpret: bool = True):
    """Dropout-repair combine: sum_i weights_i * (x_i - corr_i).

    x: (N, T) f32 survivors' masked packed updates; corr: (N, T) f32 the
    survivors' re-derived pairwise-mask corrections against the dropped
    peers; weights: (N,) f32 -> (T,) f32. Fusing the correction subtract
    into the combine tile keeps the repair a single pass: per VMEM tile
    the kernel reads N masked rows and N correction rows and writes one
    f32 output row.
    """
    N = x.shape[0]
    ws = weights.astype(jnp.float32).reshape(1, N)
    return _combine_call(x.astype(jnp.float32), ws, bt=bt,
                         interpret=interpret,
                         corr=corr.astype(jnp.float32))
