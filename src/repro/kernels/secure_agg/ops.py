"""Public secure-agg combine: quantize a pytree of client updates and fuse
the dequant+weighted-sum on TPU. Also exposes the pytree-level helper used
by the launch-layer FedAvg variant."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.secure_agg import kernel as _k
from repro.kernels.secure_agg import ref as _ref


@partial(jax.jit, static_argnames=("interpret",))
def secure_agg_combine(q, scales, weights, *, interpret: bool = None):
    """q: (N, T) int8; scales, weights: (N,) f32 -> (T,) f32."""
    if interpret is None:
        interpret = not on_tpu()
    return _k.secure_agg_combine_flat(q, scales, weights,
                                      interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def masked_sum(x, weights, *, interpret: bool = None):
    """Weighted sum of packed fp32 masked updates: (N, T), (N,) -> (T,).

    On a TPU this is always the fused Pallas MXU combine. On other
    backends ``interpret=None`` runs the jnp oracle in ``ref.py`` —
    interpreting the kernel block-by-block at 10M+ parameter sizes is
    prohibitively slow on CPU, and the oracle is the definition the
    kernel is tested against anyway. ``interpret=True`` (tests) runs the
    kernel body through the Pallas interpreter.
    """
    if interpret is None:
        if not on_tpu():
            return _ref.masked_sum_ref(x, weights)
        interpret = False
    return _k.masked_sum_flat(x, weights, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def masked_sum_corrected(x, corr, weights, *, interpret: bool = None):
    """Dropout-repair combine: (N, T), (N, T), (N,) -> (T,).

    ``sum_i weights_i * (x_i - corr_i)`` — survivors' masked updates minus
    their re-derived corrections against the dropped peers, fused into one
    Pallas tile pass on TPU (the correction subtract rides the VPU inside
    the combine tile, no repaired (N, T) intermediate in HBM). Off the
    TPU, ``interpret=None`` runs the jnp oracle for the same reason
    ``masked_sum`` does.
    """
    if interpret is None:
        if not on_tpu():
            return _ref.masked_sum_corrected_ref(x, corr, weights)
        interpret = False
    return _k.masked_sum_corrected_flat(x, corr, weights,
                                        interpret=interpret)


def quantize_update(update_flat: jnp.ndarray):
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    scale = jnp.max(jnp.abs(update_flat)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(update_flat / scale), -127, 127).astype(jnp.int8)
    return q, scale


def combine_pytrees(updates, weights, *, interpret: bool = None):
    """Aggregate a list of pytrees through the fused kernel."""
    flats = []
    for u in updates:
        leaves = jax.tree.leaves(u)
        flats.append(jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in leaves]))
    qs, scales = zip(*[quantize_update(f) for f in flats])
    q = jnp.stack(qs)
    out = secure_agg_combine(q, jnp.stack(scales),
                             jnp.asarray(weights, jnp.float32),
                             interpret=interpret)
    # unflatten back into the first update's structure
    leaves, treedef = jax.tree_util.tree_flatten(updates[0])
    res, off = [], 0
    for l in leaves:
        n = l.size
        res.append(out[off:off + n].reshape(l.shape))
        off += n
    return jax.tree_util.tree_unflatten(treedef, res)
