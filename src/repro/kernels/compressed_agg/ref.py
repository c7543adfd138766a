"""Oracle for the fused dequantize-scale-accumulate combine.

dequant_reduce(q, scales, weights) =
    sum_i weights_i * (q_i * expand(scales_i))

q: (n_clients, T) int8 — per-client quantized packed delta buffers,
    T a multiple of ``CHUNK`` (the compression layer pads)
scales: (n_clients, T // CHUNK) f32 — per-chunk symmetric dequant scales
    (one scale per 1024-float chunk, DESIGN.md §Compressed data plane)
weights: (n_clients,) f32 — aggregation weights (FedAvg-normalized by
    the caller; NOT normalized here, mirroring ``masked_sum``)

``expand`` broadcasts each chunk scale over its 1024 elements. This is
the definition the Pallas kernel is tested against, and the
interpret-mode production fallback on CPU hosts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import EXACT_F32
from repro.kernels.compressed_agg.kernel import CHUNK


def dequant_reduce_ref(q, scales, weights):
    n, t = q.shape
    c = t // CHUNK
    deq = (q.astype(jnp.float32).reshape(n, c, CHUNK)
           * scales.astype(jnp.float32)[:, :, None]).reshape(n, t)
    return jnp.tensordot(weights.astype(jnp.float32), deq, axes=(0, 0),
                         precision=EXACT_F32)


def masked_dequant_reduce_ref(z, scales, modulus_bits: int, corr=None):
    """Oracle for the masked combine (DESIGN.md §Composable privacy):

    masked_dequant_reduce(z, scales) =
        expand(scales) * center((sum_i z_i - sum_i corr_i) mod M)

    z: (n_clients, T) uint — per-client masked residue streams mod
        M = 2**modulus_bits (T a CHUNK multiple)
    scales: (T // CHUNK,) f32 — the cohort-common fixed quantization
        grid (per-client scales cannot survive a modular sum)
    corr: optional (n_clients, T) uint — survivors' integer repair
        corrections against dropped peers, subtracted mod M

    The sum runs in uint32 (wrap-around = mod 2**32; M divides 2**32 so
    residues are preserved), the residue is centered into a signed value
    and only then scaled — mask cancellation is bit-exact in the integer
    domain, before any float touches the data. This is the definition
    the Pallas kernel is parity-tested against, and the interpret-mode
    production fallback on CPU hosts.
    """
    s = jnp.sum(z.astype(jnp.uint32), axis=0, dtype=jnp.uint32)
    if corr is not None:
        s = s - jnp.sum(corr.astype(jnp.uint32), axis=0,
                        dtype=jnp.uint32)
    r = s & jnp.uint32((1 << modulus_bits) - 1)
    if modulus_bits == 32:
        c = jax.lax.bitcast_convert_type(r, jnp.int32)
    else:
        ri = r.astype(jnp.int32)
        c = ri - jnp.where(ri >= jnp.int32(1 << (modulus_bits - 1)),
                           jnp.int32(1 << modulus_bits), jnp.int32(0))
    t = z.shape[1]
    return (c.astype(jnp.float32).reshape(t // CHUNK, CHUNK)
            * scales.astype(jnp.float32)[:, None]).reshape(-1)
