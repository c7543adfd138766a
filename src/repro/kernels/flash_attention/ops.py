"""Public flash-attention op: (B,S,H,D) layout used by the models."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import on_tpu
from repro.kernels.flash_attention import kernel as _k


@partial(jax.jit, static_argnames=("causal", "window", "logit_softcap",
                                   "scale", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    logit_softcap: float = 0.0, scale: float = None,
                    interpret: bool = None):
    """q: (B,S,H,D); k/v: (B,S,Hkv,D) -> (B,S,H,D)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = not on_tpu()
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = _k.flash_attention_bhsd(qt, kt, vt, scale=scale, causal=causal,
                                  window=int(window), softcap=logit_softcap,
                                  interpret=interpret)
    return out.swapaxes(1, 2)
