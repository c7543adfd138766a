"""Public SSD-scan op used by models/ssm.py when impl="pallas"."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import on_tpu
from repro.kernels.ssd_scan import kernel as _k


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = None):
    if interpret is None:
        interpret = not on_tpu()
    import jax.numpy as jnp
    b, S, H, P = x.shape
    Q = min(chunk, S)
    S_orig = S
    if S % Q:
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y, state = _k.ssd_scan_chunked(x, dt, A, B, C, chunk=Q,
                                   interpret=interpret)
    return y[:, :S_orig], state
