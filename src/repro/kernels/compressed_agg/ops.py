"""Public compressed-aggregation combine: fused dequantize-scale-accumulate
over a cohort of int8 per-chunk-quantized packed delta buffers."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import on_tpu
from repro.kernels.compressed_agg import kernel as _k
from repro.kernels.compressed_agg import ref as _ref

CHUNK = _k.CHUNK


@partial(jax.jit, static_argnames=("interpret",))
def dequant_reduce(q, scales, weights, *, interpret: bool = None):
    """q: (N, T) int8 (T a CHUNK multiple); scales: (N, T/CHUNK) f32;
    weights: (N,) f32 -> (T,) f32.

    ``sum_i weights_i * dequant(q_i, scales_i)`` — the server-side
    reduction of the compressed data plane (DESIGN.md §Compressed data
    plane). On a TPU this is always the fused Pallas combine. On other
    backends ``interpret=None`` runs the jnp oracle in ``ref.py``, which
    is also the definition the kernel is parity-tested against
    (tests/test_compression.py); ``interpret=True`` (tests) runs the
    kernel body through the Pallas interpreter.
    """
    if interpret is None:
        if not on_tpu():
            return _ref.dequant_reduce_ref(q, scales, weights)
        interpret = False
    return _k.dequant_reduce_flat(q, scales, weights, interpret=interpret)


@partial(jax.jit, static_argnames=("modulus_bits", "interpret"))
def masked_dequant_reduce(z, scales, *, modulus_bits: int, corr=None,
                          interpret: bool = None):
    """z: (N, T) uint masked residue streams (T a CHUNK multiple);
    scales: (T/CHUNK,) f32 cohort-common grid; optional corr: (N, T)
    uint repair corrections -> (T,) f32 decoded cohort sum.

    The masked twin of ``dequant_reduce`` (DESIGN.md §Composable
    privacy): the integer sum wraps mod 2**modulus_bits so pairwise
    masks cancel bit-exactly before the centered decode and the
    common-grid dequant. No per-client weights — weighting is
    pre-applied client-side, exactly like the packed fp32 secure plane.
    On a TPU this is always the fused Pallas combine; off it,
    ``interpret=None`` runs the jnp oracle it is parity-tested against
    (tests/test_composable_privacy.py).
    """
    if interpret is None:
        if not on_tpu():
            return _ref.masked_dequant_reduce_ref(z, scales, modulus_bits,
                                                  corr=corr)
        interpret = False
    return _k.masked_dequant_reduce_flat(z, scales,
                                         modulus_bits=modulus_bits,
                                         corr=corr, interpret=interpret)
