"""Packed secure-aggregation data plane: telescoping + kernel-path checks."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import secure_agg
from repro.core.aggregation import aggregate_packed
from repro.core.packing import pack_many, pack_pytree
from repro.kernels.secure_agg.kernel import masked_sum_flat
from repro.kernels.secure_agg.ops import masked_sum
from repro.kernels.secure_agg.ref import masked_sum_ref


@pytest.mark.parametrize("n,t", [(2, 100), (4, 1000), (7, 513)])
def test_masked_sum_over_cohort_equals_plain_sum(n, t):
    """Telescoping on packed buffers: mean of masked == mean of plain."""
    cohort = [f"client-{i}" for i in range(n)]
    rng = np.random.default_rng(0)
    bufs = [rng.normal(size=(t,)).astype(np.float32) for _ in range(n)]
    masked = [secure_agg.mask_packed(b, c, cohort, b"secret", scale=5.0)
              for b, c in zip(bufs, cohort)]
    # each individual buffer is far from its plaintext...
    assert float(jnp.abs(masked[0] - bufs[0]).max()) > 0.1
    # ...but the cohort mean telescopes the masks away (fp32 accumulation)
    agg = secure_agg.aggregate_masked_packed(jnp.stack(masked))
    np.testing.assert_allclose(np.asarray(agg), np.mean(bufs, axis=0),
                               atol=5e-5 * n, rtol=1e-5)


def test_pair_masks_are_antisymmetric():
    """The two endpoints of a pair derive bit-identical opposite masks."""
    cohort = ["a", "b"]
    zero = jnp.zeros(64)
    m_a = secure_agg.mask_packed(zero, "a", cohort, b"s")
    m_b = secure_agg.mask_packed(zero, "b", cohort, b"s")
    np.testing.assert_array_equal(np.asarray(m_a), -np.asarray(m_b))
    assert float(jnp.abs(m_a).max()) > 0


def test_mask_depends_on_cohort_and_secret():
    buf = jnp.ones(32)
    m1 = secure_agg.mask_packed(buf, "c0", ["c0", "c1"], b"s")
    m2 = secure_agg.mask_packed(buf, "c0", ["c0", "c2"], b"s")
    m3 = secure_agg.mask_packed(buf, "c0", ["c0", "c1"], b"t")
    assert float(jnp.abs(m1 - m2).max()) > 0
    assert float(jnp.abs(m1 - m3).max()) > 0
    # deterministic: same inputs -> same mask
    np.testing.assert_array_equal(
        np.asarray(m1),
        np.asarray(secure_agg.mask_packed(buf, "c0", ["c0", "c1"], b"s")))


def test_threefry_prg_also_telescopes():
    """The cryptographic-stream option cancels the same way."""
    cohort = ["a", "b", "c"]
    bufs = [jnp.full((50,), float(i)) for i in range(3)]
    masked = [secure_agg.mask_packed(b, cid, cohort, b"s", 2.0, "threefry")
              for b, cid in zip(bufs, cohort)]
    assert float(jnp.abs(masked[0] - bufs[0]).max()) > 0.01
    agg = secure_agg.aggregate_masked_packed(jnp.stack(masked))
    np.testing.assert_allclose(np.asarray(agg), 1.0, atol=1e-5)


def test_singleton_cohort_is_identity():
    buf = jnp.arange(16, dtype=jnp.float32)
    out = secure_agg.mask_packed(buf, "only", ["only"], b"s")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(buf))


def test_pytree_wrappers_match_packed_plane():
    """mask_update/aggregate_masked are exactly pack -> packed op -> unpack."""
    cohort = ["c0", "c1", "c2"]
    trees = [{"w": np.full((2, 3), float(i), np.float32),
              "b": {"x": np.array([i, -i], np.float32)}}
             for i in range(3)]
    masked_trees = [secure_agg.mask_update(t, c, cohort, b"s")
                    for t, c in zip(trees, cohort)]
    agg_tree = secure_agg.aggregate_masked(masked_trees)
    np.testing.assert_allclose(np.asarray(agg_tree["w"]), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(agg_tree["b"]["x"]),
                               [1.0, -1.0], atol=1e-5)
    # same numbers as doing it by hand on the packed plane
    stacked, layout = pack_many(masked_trees)
    by_hand = secure_agg.aggregate_masked_packed(stacked)
    buf, _ = pack_pytree(agg_tree, layout)
    np.testing.assert_allclose(np.asarray(buf), np.asarray(by_hand),
                               atol=1e-7)


# ---------------------------------------------------------------------------
# kernel path vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,t", [(4, 1000), (8, 8192), (3, 5000), (2, 127)])
def test_masked_sum_kernel_matches_ref(n, t):
    """The Pallas kernel body (interpret mode) must match the jnp oracle."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (n, t), jnp.float32)
    w = jax.nn.softmax(jax.random.normal(ks[1], (n,)))
    out = masked_sum_flat(x, w, interpret=True)
    ref = masked_sum_ref(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_masked_sum_op_interpret_fallback_matches_kernel():
    """ops.masked_sum off the TPU (the oracle) == kernel body (interpret
    mode)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 700), jnp.float32)
    w = jnp.full((5,), 0.2)
    np.testing.assert_allclose(np.asarray(masked_sum(x, w)),
                               np.asarray(masked_sum_flat(x, w,
                                                          interpret=True)),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# packed aggregation strategies
# ---------------------------------------------------------------------------
def test_aggregate_packed_fedavg_and_unpack_once():
    trees = [{"w": np.full((2, 2), v, np.float32)} for v in (1.0, 3.0)]
    stacked, layout = pack_many(trees)
    out = aggregate_packed("fedavg", stacked, layout=layout)
    np.testing.assert_allclose(np.asarray(out["w"]), 2.0)
    out_w = aggregate_packed("fedavg", stacked, weights=[3.0, 1.0],
                             layout=layout)
    np.testing.assert_allclose(np.asarray(out_w["w"]), 1.5)


def test_aggregate_packed_robust_strategies():
    bufs = np.stack([np.full(4, v, np.float32)
                     for v in (1.0, 2.0, 1000.0)])
    np.testing.assert_allclose(
        np.asarray(aggregate_packed("median", bufs)), 2.0)
    np.testing.assert_allclose(
        np.asarray(aggregate_packed("trimmed_mean", bufs, trim=1)), 2.0)
    with pytest.raises(ValueError):
        aggregate_packed("trimmed_mean", bufs[:2], trim=1)
    with pytest.raises(KeyError):
        aggregate_packed("nope", bufs)


# ---------------------------------------------------------------------------
# end-to-end: one masked FL round over the packed plane
# ---------------------------------------------------------------------------
def test_masked_round_posts_packed_buffers():
    """A secure consortium round posts (T,) buffers, not pytrees, and the
    aggregate matches a plain-FedAvg shadow computation."""
    from repro.core import Consortium
    from repro.data import make_silo_datasets

    con = Consortium(["a", "b"], seed=0)
    contract = con.negotiate({"arch": "fedforecast-100m", "rounds": 1,
                              "local_steps": 1, "batch_size": 2,
                              "data_schema": None,
                              "secure_aggregation": True})
    job = con.server.job_creator.from_contract(contract)
    ds = make_silo_datasets(2, vocab=512, seq_len=32, seed=0)
    run_id = con.start(job, ds)
    phase = con.run_to_completion()
    assert phase == "done"
    # the posted update resources decrypt to packed buffers
    base = f"runs/{run_id}/round/0/0"
    for node in con.nodes:
        msg = con.server.comm.collect(f"{base}/update/{node.client_id}",
                                      node.client_id)
        assert "packed" in msg and "params" not in msg
        assert np.asarray(msg["packed"]).ndim == 1
        assert msg["packed"].dtype == np.float32
