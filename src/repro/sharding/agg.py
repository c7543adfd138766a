"""T-axis mesh sharding for the server aggregation kernel trios.

DESIGN.md §Sharded streaming aggregation: the server-side reductions
(``masked_sum`` / ``masked_sum_corrected`` / ``dequant_reduce`` /
``masked_dequant_reduce``) are embarrassingly parallel over the packed
parameter axis T — every output element depends on one column of the
(N, T) cohort matrix. This module maps each op over a 1-D ``("shard",)``
mesh with ``jax.shard_map``: inputs arrive column-sharded
``P(None, "shard")``, per-client scalars replicated ``P()``, and each
device runs the *unsharded* op on its T/n_shards slab — no collective
at all, the output stays sharded ``P("shard")`` until the host gathers
it.

Partitioning rules (the module's contract):

* only T is ever sharded — the client axis N stays whole on every device,
  so cohort sizes need no relation to the mesh (N=5 on 4 devices is fine);
* T is zero-padded up to ``n_shards * chunk`` (``chunk`` = the op's
  column granule: the 1024-float quantization CHUNK for the dequant pair,
  a 128-lane tile for the fp32 pair). Zero columns are exact identities
  for every op: 0-weighted sums, 0-residues centering to 0;
* everything degrades to the plain single-device op when no mesh is
  available (``agg_mesh() is None``) — correctness first, same as
  ``sharding/specs.py``.

CPU CI exercises the multi-device path with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``
(``benchmarks/_env.py``).
"""
from __future__ import annotations

from functools import lru_cache
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.compressed_agg import ops as _comp_ops
from repro.kernels.secure_agg import ops as _sec_ops

AXIS = "shard"
CHUNK = _comp_ops.CHUNK      # dequant column granule (1024 floats)
LANE = 128                   # fp32 column granule (TPU lane width)


def agg_mesh(devices=None, *, min_devices: int = 2) -> Optional[Mesh]:
    """1-D aggregation mesh over the host's devices, or ``None`` when
    there is nothing to shard over (the caller then uses the plain op).
    Deliberately NOT cached: tests construct meshes over device subsets.
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    if len(devs) < min_devices:
        return None
    return Mesh(np.array(devs), (AXIS,))


def _pad_cols(arr, pad: int):
    """Zero-pad the trailing (column) axis of a 1-D or 2-D operand."""
    if pad == 0:
        return arr
    width = [(0, 0)] * (arr.ndim - 1) + [(0, pad)]
    return jnp.pad(jnp.asarray(arr), width)


def _slab_map(local_fn, mesh: Mesh, in_specs):
    """Jit ``local_fn`` (whole operands -> (T,) output) so that each
    device runs it on its own T-slab: T-sharded columns in, T-sharded
    output out, no collectives. ``check_vma=False``: the Pallas kernels
    inside carry no varying-axis annotations."""
    return jax.jit(jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(AXIS), check_vma=False))


# --- cached jitted entry points (one compile per op x mesh x shape) ------
@lru_cache(maxsize=None)
def _masked_sum_sharded(mesh: Mesh, interpret: Optional[bool]):
    return _slab_map(
        lambda x, w: _sec_ops.masked_sum(x, w, interpret=interpret),
        mesh, (P(None, AXIS), P()))


@lru_cache(maxsize=None)
def _masked_sum_corrected_sharded(mesh: Mesh, interpret: Optional[bool]):
    return _slab_map(
        lambda x, c, w: _sec_ops.masked_sum_corrected(
            x, c, w, interpret=interpret),
        mesh, (P(None, AXIS), P(None, AXIS), P()))


@lru_cache(maxsize=None)
def _dequant_reduce_sharded(mesh: Mesh, interpret: Optional[bool]):
    return _slab_map(
        lambda q, s, w: _comp_ops.dequant_reduce(q, s, w,
                                                 interpret=interpret),
        mesh, (P(None, AXIS), P(None, AXIS), P()))


@lru_cache(maxsize=None)
def _masked_dequant_reduce_sharded(mesh: Mesh, modulus_bits: int,
                                   with_corr: bool,
                                   interpret: Optional[bool]):
    if with_corr:
        return _slab_map(
            lambda z, c, s: _comp_ops.masked_dequant_reduce(
                z, s, modulus_bits=modulus_bits, corr=c,
                interpret=interpret),
            mesh, (P(None, AXIS), P(None, AXIS), P(AXIS)))
    return _slab_map(
        lambda z, s: _comp_ops.masked_dequant_reduce(
            z, s, modulus_bits=modulus_bits, interpret=interpret),
        mesh, (P(None, AXIS), P(AXIS)))


def _placed(mesh, spec, *arrs):
    sh = NamedSharding(mesh, spec)
    return tuple(jax.device_put(jnp.asarray(a), sh) for a in arrs)


def _t_pad(t: int, n_shards: int, chunk: int) -> int:
    granule = n_shards * chunk
    return (-t) % granule


# ---------------------------------------------------------------------------
# public sharded ops — same math as the kernels/..../ops versions, padded
# and placed for the mesh; each returns the (T,) result *unsliced* only
# internally, callers get exactly the input T.
# ---------------------------------------------------------------------------
def sharded_masked_sum(x, weights, *, mesh: Mesh,
                       interpret: Optional[bool] = None):
    """(N, T) f32 x (N,) f32 -> (T,) f32, T sharded over the mesh."""
    x = jnp.asarray(x, jnp.float32)
    t = x.shape[1]
    pad = _t_pad(t, mesh.shape[AXIS], LANE)
    (xp,) = _placed(mesh, P(None, AXIS), _pad_cols(x, pad))
    (w,) = _placed(mesh, P(), jnp.asarray(weights, jnp.float32))
    out = _masked_sum_sharded(mesh, interpret)(xp, w)
    return out[:t]


def sharded_masked_sum_corrected(x, corr, weights, *, mesh: Mesh,
                                 interpret: Optional[bool] = None):
    """Dropout-repair combine with both (N, T) operands T-sharded."""
    x = jnp.asarray(x, jnp.float32)
    t = x.shape[1]
    pad = _t_pad(t, mesh.shape[AXIS], LANE)
    xp, cp = _placed(mesh, P(None, AXIS), _pad_cols(x, pad),
                     _pad_cols(jnp.asarray(corr, jnp.float32), pad))
    (w,) = _placed(mesh, P(), jnp.asarray(weights, jnp.float32))
    out = _masked_sum_corrected_sharded(mesh, interpret)(xp, cp, w)
    return out[:t]


def sharded_dequant_reduce(q, scales, weights, *, mesh: Mesh,
                           interpret: Optional[bool] = None):
    """(N, T) int8 x (N, T/CHUNK) x (N,) -> (T,) f32, T sharded.

    T must already be a CHUNK multiple (the compression layer pads);
    this pads further to ``n_shards * CHUNK`` so every shard's slab
    stays chunk-aligned, extending ``scales`` with zeros (the padded
    columns are zero anyway).
    """
    q = jnp.asarray(q, jnp.int8)
    t = q.shape[1]
    if t % CHUNK:
        raise ValueError(f"T={t} must be a multiple of CHUNK={CHUNK}")
    pad = _t_pad(t, mesh.shape[AXIS], CHUNK)
    qp = _pad_cols(q, pad)
    sp = _pad_cols(jnp.asarray(scales, jnp.float32), pad // CHUNK)
    qp, = _placed(mesh, P(None, AXIS), qp)
    sp, = _placed(mesh, P(None, AXIS), sp)
    (w,) = _placed(mesh, P(), jnp.asarray(weights, jnp.float32))
    out = _dequant_reduce_sharded(mesh, interpret)(qp, sp, w)
    return out[:t]


def sharded_masked_dequant_reduce(z, scales, *, modulus_bits: int,
                                  corr=None, mesh: Mesh,
                                  interpret: Optional[bool] = None):
    """(N, T) uint32 residues mod 2**modulus_bits -> (T,) f32, T sharded.

    Zero-padded columns decode to exactly 0.0 (residue 0 centers to 0),
    so the modular cancellation stays bit-exact per shard.
    """
    z = jnp.asarray(z).astype(jnp.uint32)
    t = z.shape[1]
    if t % CHUNK:
        raise ValueError(f"T={t} must be a multiple of CHUNK={CHUNK}")
    pad = _t_pad(t, mesh.shape[AXIS], CHUNK)
    zp, = _placed(mesh, P(None, AXIS), _pad_cols(z, pad))
    sp, = _placed(mesh, P(AXIS),
                  _pad_cols(jnp.asarray(scales, jnp.float32),
                            pad // CHUNK))
    if corr is None:
        out = _masked_dequant_reduce_sharded(
            mesh, int(modulus_bits), False, interpret)(zp, sp)
    else:
        cp, = _placed(mesh, P(None, AXIS),
                      _pad_cols(jnp.asarray(corr).astype(jnp.uint32), pad))
        out = _masked_dequant_reduce_sharded(
            mesh, int(modulus_bits), True, interpret)(zp, cp, sp)
    return out[:t]
