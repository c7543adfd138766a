"""Plain reference of fedforecast-100m: a decoder-only forecaster over a
4096-symbol vocabulary, as the configuration's ``model`` block states it.

Pre-norm blocks: RMSNorm (scale ``1 + w``), multi-head causal
self-attention with rotary positions (each head's first half rotated
against its second half), SwiGLU MLP; the input embedding, scaled by
``sqrt(d_model)``, is also the output projection. The loss is the mean
next-token cross-entropy over the first ``S - 1`` positions.

Precision as the configuration states it: float32 weights; activations
and every matmul operand in ``compute_dtype``, products accumulated in
float32; RMSNorm, RoPE, the attention softmax and the loss in float32.
A ``compute_dtype`` of one byte (``float8_e4m3fn``) computes as fp8
training does: every matmul operand rounded to e4m3 in the forward pass,
the gradient of every matmul's output rounded to e5m2 (scaled per tensor)
before the backward matmuls, activations kept in bfloat16. That is the
control's precision, never a cell's.

Written from those semantics alone, in a layout of its own: a Python
loop over the layers, no rematerialization, the whole sequence's logits
at once. Nothing here imports the program. ``init`` draws the
benchmark's weights from a key: the harness hands the same weights to
the program, and this module draws them again for the comparison.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def param_shapes(m: dict) -> dict:
    """Parameter tree of the model ``m`` (the config's ``model`` block)."""
    d, f, v, n = m["d_model"], m["d_ff"], m["vocab"], m["n_layers"]
    hd = m["n_heads"] * m["head_dim"]
    kvd = m["n_kv_heads"] * m["head_dim"]
    return {
        "embed": (v, d),
        "final_norm": (d,),
        "stack": {
            "norm_attn": (n, d),
            "attn": {"wq": (n, d, hd), "wk": (n, d, kvd), "wv": (n, d, kvd),
                     "wo": (n, hd, d)},
            "norm_mlp": (n, d),
            "mlp": {"w_gate": (n, d, f), "w_up": (n, d, f),
                    "w_down": (n, f, d)},
        },
    }


def init(m: dict, key):
    """Seeded float32 weights: embedding N(0, 0.02), projections
    N(0, 1/fan_in), norm scales N(0, 0.02) around the implicit 1."""
    shapes = param_shapes(m)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, shape in zip(keys, leaves):
        std = 1.0 / math.sqrt(shape[1]) if len(shape) == 3 else 0.02
        out.append(jax.random.normal(k, shape, jnp.float32) * std)
    return jax.tree_util.tree_unflatten(treedef, out)


@jax.custom_vjp
def _grad_e5m2(y):
    """Identity whose gradient is rounded to float8 e5m2, scaled so that
    its largest magnitude sits at 2**15 (e5m2 reaches 57344)."""
    return y


def _grad_e5m2_fwd(y):
    return y, None


def _grad_e5m2_bwd(_, g):
    amax = jnp.max(jnp.abs(g))
    scale = jnp.where(amax > 0, 2.0 ** 15 / amax, 1.0)
    return ((g * scale).astype(jnp.float8_e5m2).astype(g.dtype) / scale,)


_grad_e5m2.defvjp(_grad_e5m2_fwd, _grad_e5m2_bwd)


class _Precision:
    """Activation dtype and matmul operands of one ``compute_dtype``."""

    def __init__(self, compute_dtype: str):
        dt = jnp.dtype(compute_dtype)
        self.act = jnp.dtype(jnp.bfloat16) if dt.itemsize == 1 else dt
        self.operand_dtype = dt if dt.itemsize == 1 else None

    def operand(self, x):
        x = x.astype(self.act)
        if self.operand_dtype is None:
            return x
        rounded = x.astype(self.operand_dtype).astype(self.act)
        return x + jax.lax.stop_gradient(rounded - x)

    def matmul(self, spec: str, a, b):
        out = jnp.einsum(spec, self.operand(a), self.operand(b),
                         preferred_element_type=jnp.float32)
        return out if self.operand_dtype is None else _grad_e5m2(out)


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return x32 * scale * (1.0 + w)


def rotate(x, cos, sin):
    """Rotary positions on (B, S, H, D): first half against second half."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def loss(m: dict, params, batch):
    """``(loss, {"ce"})`` of ``batch["tokens"]`` (B, S) int32."""
    pr = _Precision(m["compute_dtype"])
    tokens = batch["tokens"]
    b, s = tokens.shape
    nh, nkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    group = nh // nkv

    freq = m["rope_theta"] ** (-jnp.arange(hd // 2, dtype=jnp.float32)
                               * 2.0 / hd)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    causal = jnp.tril(jnp.ones((s, s), bool))

    x = (params["embed"][tokens].astype(pr.act)
         * jnp.asarray(math.sqrt(m["d_model"]), pr.act))
    st = params["stack"]
    for layer in range(m["n_layers"]):
        h = rms_norm(x, st["norm_attn"][layer], m["norm_eps"])
        at = {name: w[layer] for name, w in st["attn"].items()}
        q = pr.matmul("bsd,de->bse", h, at["wq"]).reshape(b, s, nh, hd)
        k = pr.matmul("bsd,de->bse", h, at["wk"]).reshape(b, s, nkv, hd)
        v = pr.matmul("bsd,de->bse", h, at["wv"]).reshape(b, s, nkv, hd)
        q = rotate(q.astype(pr.act).astype(jnp.float32), cos, sin)
        k = rotate(k.astype(pr.act).astype(jnp.float32), cos, sin)
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v.astype(pr.act), group, axis=2)
        scores = pr.matmul("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = pr.matmul("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nh * hd)
        x = x + pr.matmul("bse,ed->bsd", ctx, at["wo"]).astype(pr.act)

        h = rms_norm(x, st["norm_mlp"][layer], m["norm_eps"])
        ml = {name: w[layer] for name, w in st["mlp"].items()}
        gate = pr.matmul("bsd,df->bsf", h, ml["w_gate"]).astype(pr.act)
        up = pr.matmul("bsd,df->bsf", h, ml["w_up"]).astype(pr.act)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32))
        x = x + pr.matmul("bsf,fd->bsd", act, ml["w_down"]).astype(pr.act)

    h = rms_norm(x, params["final_norm"], m["norm_eps"])
    logits = pr.matmul("bsd,vd->bsv", h[:, :-1], params["embed"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    ce = -jnp.mean(gold)
    return ce, {"ce": ce}
