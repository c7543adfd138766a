"""Operations and bytes the algorithm needs, from the configuration's
shapes. Recomputed (rematerialized) work does not count."""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Weights that take part in a matmul per token: the projections of
    every layer and the (tied) output embedding; the input lookup is no
    matmul."""
    d, f = m["d_model"], m["d_ff"]
    hd = m["n_heads"] * m["head_dim"]
    kvd = m["n_kv_heads"] * m["head_dim"]
    per_layer = d * hd + 2 * d * kvd + hd * d + 3 * d * f
    return m["n_layers"] * per_layer + d * m["vocab"]


def forward_flops(m: dict, batch: int, seq: int) -> float:
    """One forward pass: 2 per weight per token, plus the attention
    scores and their weighted sum, counted over the whole square
    (the PaLM convention)."""
    tokens = batch * seq
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * seq
    return float(tokens * (2 * matmul_params(m) + attn))


def train_step_flops(m: dict, batch: int, seq: int) -> float:
    """Forward and backward: three times the forward."""
    return 3.0 * forward_flops(m, batch, seq)


def masked_sum_bytes(n_rows: int, t: int) -> int:
    """Least HBM traffic of the fp32 combine: N rows of T in, one out."""
    return (n_rows + 1) * t * 4
