"""Pallas kernel allclose sweeps vs the pure-jnp oracles (deliverable c).

Each kernel is swept over shapes and dtypes in interpret mode (TPU is the
target; CPU validates the kernel bodies exactly).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.secure_agg.ops import combine_pytrees, secure_agg_combine
from repro.kernels.secure_agg.ref import secure_agg_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.models.ssm import ssd_chunked


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
FLASH_CASES = [
    # B, S, H, Hkv, D, causal, window, softcap
    (2, 256, 4, 2, 64, True, 0, 0.0),
    (1, 256, 4, 4, 64, True, 64, 50.0),     # window + softcap (gemma2)
    (2, 128, 8, 2, 32, False, 0, 0.0),      # bidirectional (encoder)
    (1, 512, 2, 1, 64, True, 128, 0.0),     # MQA
    (1, 384, 6, 3, 128, True, 0, 30.0),     # non-pow2 seq, 128 head dim
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, S, H, Hkv, D, causal, window, cap = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          logit_softcap=cap)
    ref = attention_ref(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                        scale=D ** -0.5, causal=causal, window=window,
                        softcap=cap).swapaxes(1, 2)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# SSD scan (chunked jnp path AND pallas kernel vs sequential oracle)
# ---------------------------------------------------------------------------
SSD_CASES = [
    # b, S, H, P, N, chunk
    (2, 64, 4, 8, 16, 16),
    (1, 128, 2, 16, 8, 32),
    (2, 96, 3, 8, 4, 32),       # padding path (96 % 32 == 0 but b,H odd)
    (1, 80, 2, 8, 16, 32),      # non-divisible -> ops.py pads
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_kernel_and_chunked_match_oracle(case):
    b, S, H, P, N, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (b, S, N))
    C = jax.random.normal(ks[4], (b, S, N))
    y_ref, h_ref = ssd_ref(x, dt, A, B, C)
    y_k, h_k = ssd_scan(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_ref),
                               atol=2e-4, rtol=2e-4)
    if S % chunk == 0:
        y_c, h_c = ssd_chunked(x, dt, A, B, C, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_ref),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_ref),
                                   atol=2e-4, rtol=2e-4)


def test_ssd_state_continuation():
    """Final state from prefill must continue the recurrence exactly."""
    b, S, H, P, N = 1, 64, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (b, S, N))
    C = jax.random.normal(ks[4], (b, S, N))
    _, h_full = ssd_ref(x, dt, A, B, C)
    _, h_half = ssd_scan(x[:, :32], dt[:, :32], A, B[:, :32], C[:, :32],
                         chunk=16)
    # continue: one manual recurrence over the second half
    h = h_half
    for t in range(32, S):
        dA = jnp.exp(dt[:, t] * A)
        h = (h * dA[..., None, None]
             + jnp.einsum("bh,bhp,bn->bhpn", dt[:, t], x[:, t], B[:, t]))
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# secure aggregation combine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("N,T", [(4, 1000), (8, 8192), (3, 5000), (2, 127)])
def test_secure_agg_matches_ref(N, T):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.randint(ks[0], (N, T), -127, 128).astype(jnp.int8)
    scales = jax.random.uniform(ks[1], (N,), minval=1e-4, maxval=1e-2)
    w = jax.nn.softmax(jax.random.normal(ks[2], (N,)))
    out = secure_agg_combine(q, scales, w)
    ref = secure_agg_ref(q, scales, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_combine_pytrees_quantization_error_bounded():
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    trees = [{"a": jax.random.normal(k, (33,)),
              "b": jax.random.normal(k, (4, 7))} for k in keys]
    agg = combine_pytrees(trees, jnp.full((4,), 0.25))
    ref = jax.tree.map(lambda *xs: sum(xs) / 4.0, *trees)
    for a, r in zip(jax.tree.leaves(agg), jax.tree.leaves(ref)):
        # int8 symmetric quantization: |err| <= scale/2 per client
        max_scale = max(float(jnp.max(jnp.abs(l))) / 127.0
                        for t in trees for l in jax.tree.leaves(t))
        assert float(jnp.max(jnp.abs(a - r))) <= max_scale


# ---------------------------------------------------------------------------
# kernel choice by platform
# ---------------------------------------------------------------------------
def _combine_calls(n=1):
    """Each op at cohort size ``n``. The ops pick their branch while they
    are traced, and a trace is cached per shape, so each test that
    changes the platform uses an ``n`` of its own."""
    from repro.kernels.compressed_agg import ops as comp_ops
    from repro.kernels.secure_agg import ops as sec_ops
    t, tc = 3 * 1024 + 256 * 3, 4 * 1024
    x = jnp.ones((n, t), jnp.float32)
    w = jnp.ones((n,), jnp.float32)
    q = jnp.ones((n, tc), jnp.int8)
    s = jnp.ones((n, tc // 1024), jnp.float32)
    z = jnp.ones((n, tc), jnp.uint32)
    return {
        "masked_sum": (sec_ops, lambda: sec_ops.masked_sum(x, w)),
        "masked_sum_corrected": (
            sec_ops, lambda: sec_ops.masked_sum_corrected(x, x, w)),
        "dequant_reduce": (
            comp_ops, lambda: comp_ops.dequant_reduce(q, s, w)),
        "masked_dequant_reduce": (
            comp_ops, lambda: comp_ops.masked_dequant_reduce(
                z, s[0], modulus_bits=16)),
    }


@pytest.mark.parametrize("name", sorted(_combine_calls()))
def test_combine_op_runs_oracle_off_tpu(name):
    """Off the TPU, ``interpret=None`` lowers to plain XLA (the oracle):
    no Pallas call is traced at all."""
    _, call = _combine_calls(3)[name]
    assert "pallas_call" not in str(jax.make_jaxpr(call)())


@pytest.mark.parametrize("name", sorted(_combine_calls()))
def test_combine_op_on_tpu_never_returns_oracle(name, monkeypatch):
    """Where the platform is a TPU, the op traces the compiled Pallas
    kernel (``interpret=False``), never the oracle and never the
    interpreter."""
    ops, call = _combine_calls(5)[name]
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    jaxpr = str(jax.make_jaxpr(call)())
    assert "pallas_call" in jaxpr
    assert "interpret=False" in jaxpr
