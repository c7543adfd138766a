"""Model Aggregator strategies (paper §V; robust options per [8]).

Two planes:
  * pytree plane — lists of client parameter pytrees (host-level control
    plane; small cohorts, readability first).
  * packed plane — an (N, T) fp32 matrix of flattened client updates
    (``repro.core.packing``); ``aggregate_packed`` reduces the whole
    cohort in one pass (FedAvg through the fused Pallas combine) and
    unpacks into the parameter structure exactly once, after reduction.
    This is the path masked rounds use (DESIGN.md §Packed data plane).

The TPU data plane equivalent is ``repro.training.steps.fedavg_pod_params``
(collective over the pod axis) and the fused Pallas ``secure_agg`` kernel.
"""
from __future__ import annotations

from typing import Optional, Sequence


import jax
import jax.numpy as jnp

from repro.core.packing import PackedLayout, as_matrix, unpack_pytree
from repro.kernels.secure_agg.ops import masked_sum


def _stack(updates: Sequence):
    return jax.tree.map(lambda *xs: jnp.stack(
        [jnp.asarray(x, jnp.float32) for x in xs]), *updates)


def fedavg(updates: Sequence, weights: Optional[Sequence[float]] = None):
    """Weighted mean (McMahan et al. [2]); weights default to uniform."""
    if weights is None:
        weights = [1.0] * len(updates)
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    stacked = _stack(updates)
    return jax.tree.map(lambda s: jnp.tensordot(w, s, axes=(0, 0)), stacked)


def trimmed_mean(updates: Sequence, trim: int = 1, **_):
    """Coordinate-wise trimmed mean — robust to ``trim`` outliers per side."""
    if 2 * trim >= len(updates):
        raise ValueError("trim too large for cohort size")
    stacked = _stack(updates)

    def agg(s):
        s = jnp.sort(s, axis=0)
        return jnp.mean(s[trim:s.shape[0] - trim], axis=0)

    return jax.tree.map(agg, stacked)


def coordinate_median(updates: Sequence, **_):
    stacked = _stack(updates)
    return jax.tree.map(lambda s: jnp.median(s, axis=0), stacked)


AGGREGATORS = {
    "fedavg": fedavg,
    "trimmed_mean": trimmed_mean,
    "median": coordinate_median,
}


def aggregate(name: str, updates: Sequence,
              weights: Optional[Sequence[float]] = None, **kw):
    fn = AGGREGATORS[name]
    if name == "fedavg":
        return fn(updates, weights)
    return fn(updates, **kw)


# ---------------------------------------------------------------------------
# packed plane
# ---------------------------------------------------------------------------
def aggregate_packed(name: str, buffers,
                     weights: Optional[Sequence[float]] = None, *,
                     layout: Optional[PackedLayout] = None,
                     interpret: Optional[bool] = None, **kw):
    """Aggregate (N, T) packed fp32 client buffers in one reduction.

    ``buffers`` is an (N, T) array or a list of (T,) buffers. FedAvg goes
    through the fused Pallas combine (jnp oracle off the TPU) with
    weights *normalized* to a weighted mean (masked rounds instead use
    ``secure_agg.aggregate_masked_packed``, whose weights stay raw so
    pre-scaled protocols can sum); the robust strategies sort/median on
    the stacked matrix directly. If ``layout`` is given the reduced (T,)
    buffer is unpacked into the parameter pytree — the single unpack of
    the round.
    """
    x = as_matrix(buffers)
    n = x.shape[0]
    if name == "fedavg":
        w = (jnp.full((n,), 1.0 / n, jnp.float32) if weights is None
             else jnp.asarray(weights, jnp.float32))
        w = w / jnp.sum(w)
        out = masked_sum(x, w, interpret=interpret)
    elif name == "trimmed_mean":
        trim = kw.get("trim", 1)
        if 2 * trim >= n:
            raise ValueError("trim too large for cohort size")
        s = jnp.sort(x, axis=0)
        out = jnp.mean(s[trim:n - trim], axis=0)
    elif name == "median":
        out = jnp.median(x, axis=0)
    else:
        raise KeyError(name)
    return unpack_pytree(out, layout) if layout is not None else out
