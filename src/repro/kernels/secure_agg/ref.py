"""Oracle for the fused secure-aggregation combine.

combine(q, scales, weights) = sum_i weights_i * (q_i * scales_i)

q: (n_clients, T) int8 — per-client quantized (masked) updates
scales: (n_clients,) f32 — per-client symmetric dequant scales
weights: (n_clients,) f32 — FedAvg weights (sum to 1)
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import EXACT_F32


def secure_agg_ref(q, scales, weights):
    deq = q.astype(jnp.float32) * scales[:, None]
    return jnp.tensordot(weights.astype(jnp.float32), deq, axes=(0, 0),
                         precision=EXACT_F32)


def masked_sum_ref(x, weights):
    """Full-precision oracle for the packed masked combine:

    masked_sum(x, weights) = sum_i weights_i * x_i

    x: (n_clients, T) f32 — per-client packed, pairwise-masked updates
    weights: (n_clients,) f32 — aggregation weights

    Also serves as the interpret-mode production fallback on CPU hosts,
    where running the Pallas kernel through the interpreter at real model
    sizes is orders of magnitude slower than this single XLA matvec.
    """
    return jnp.tensordot(weights.astype(jnp.float32),
                         x.astype(jnp.float32), axes=(0, 0),
                         precision=EXACT_F32)


def masked_sum_corrected_ref(x, corr, weights):
    """Oracle for the dropout-repair combine:

    masked_sum_corrected(x, corr, weights) = sum_i weights_i * (x_i - corr_i)

    x: (n_survivors, T) f32 — survivors' packed, pairwise-masked updates
    corr: (n_survivors, T) f32 — each survivor's re-derived sum of masks
        against the dropped peers (``secure_agg.repair_correction``)
    weights: (n_survivors,) f32 — aggregation weights

    Subtracting a survivor's correction removes exactly its mask terms
    toward dropped clients, so the survivor-only sum telescopes again.
    """
    return jnp.tensordot(weights.astype(jnp.float32),
                         x.astype(jnp.float32) - corr.astype(jnp.float32),
                         axes=(0, 0),
                         precision=EXACT_F32)
