"""Flash attention (online softmax) for TPU.

Supports: causal masking, sliding windows, Gemma-2 logit softcap, GQA
(q-head -> kv-head mapping happens in the BlockSpec index_map, so kv blocks
are fetched once per kv-head, not per q-head).

Tiling: grid (batch, q_heads, Sq / BQ). Each program holds one q block
(BQ, D) in VMEM plus this (b, kv_head) pair's K/V (S, D); the kv dimension
is walked in BK-sized VMEM sub-tiles with an in-kernel loop (splash-style
inner tiling), accumulating the online-softmax state in registers. BQ/BK
are 128-multiples to line up with the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.3819763e38

DEFAULT_BQ = 128
DEFAULT_BK = 128


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, window,
                 softcap, bk, seq_k):
    """q_ref: (BQ, D); k_ref/v_ref: (S, D); o_ref: (BQ, D)."""
    qi = pl.program_id(2)
    bq = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[...].astype(jnp.float32) * scale
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    n_k = seq_k // bk

    def body(ki, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.ds(ki * bk, bk), :]
        v = v_ref[pl.ds(ki * bk, bk), :]
        s = jnp.dot(q, k.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)      # (BQ, BK)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window > 0:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, v.astype(jnp.float32),
                                    preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_k, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, scale: float, causal: bool, window: int,
                         softcap: float, bq: int = DEFAULT_BQ,
                         bk: int = DEFAULT_BK, interpret: bool = True):
    """q: (B, H, Sq, D); k/v: (B, Hkv, Sk, D). Returns (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = H // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)

    kernel = functools.partial(_attn_kernel, scale=scale, causal=causal,
                               window=window, softcap=softcap, bk=bk,
                               seq_k=Sk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, Sq // bq),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), lambda b, h, i: (b, h, i, 0)),
            # GQA: q-head h reads kv-head h // G
            pl.BlockSpec((None, None, Sk, D),
                         lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((None, None, Sk, D),
                         lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, D),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
