"""Compressed-update data plane (DESIGN.md §Compressed data plane).

Round-trip error bounds, error-feedback telescoping, fused Pallas
dequant-reduce kernel vs jnp oracle, the JobCreator compatibility
matrix, and e2e compressed sync/async runs tracking their uncompressed
twins — including the bytes-on-wire reduction the plane exists for.
"""
import numpy as np
import pytest

from repro.core import compression
from repro.core.compression import (ErrorFeedback, compress, decompress,
                                    reduce_compressed)
from repro.core.jobs import JobCreator
from repro.core.metadata import MetadataStore
from repro.kernels.compressed_agg.kernel import CHUNK, dequant_reduce_flat
from repro.kernels.compressed_agg.ref import dequant_reduce_ref

# ---------------------------------------------------------------------------
# round-trip properties
# ---------------------------------------------------------------------------


def test_int8_roundtrip_error_bounded_by_quant_step():
    rng = np.random.default_rng(0)
    x = rng.normal(size=4000).astype(np.float32) * 0.01
    msg = compress(x, "int8", rng=np.random.default_rng(1))
    err = np.abs(decompress(msg) - x)
    # per-chunk symmetric scale bounds the stochastic-rounding error by
    # one quant step of the *local* chunk range
    scales = np.asarray(msg["scales"])
    for c in range(scales.size):
        lo, hi = c * CHUNK, min((c + 1) * CHUNK, x.size)
        assert err[lo:hi].max() <= scales[c] + 1e-7


def test_int8_low_bit_widths_round_trip():
    rng = np.random.default_rng(2)
    x = rng.normal(size=1500).astype(np.float32)
    for bits in (2, 4, 8):
        msg = compress(x, "int8", bits=bits, rng=np.random.default_rng(3))
        qmax = (1 << (bits - 1)) - 1
        assert np.abs(compression.quantized_values(msg)
                      .astype(np.int64)).max() <= qmax
        scales = np.asarray(msg["scales"])
        err = np.abs(decompress(msg) - x)
        assert err.max() <= scales.max() + 1e-6


def test_topk_keeps_largest_coordinates():
    x = np.arange(-50, 50, dtype=np.float32)
    msg = compress(x, "topk", ratio=0.1)
    dec = decompress(msg)
    k = msg["idx"].size
    assert k == 10
    # the kept coordinates are exactly the largest-|x| ones, bit-exact
    kept = np.sort(np.abs(x))[-k:]
    np.testing.assert_array_equal(np.sort(np.abs(dec[dec != 0])), kept)
    assert np.count_nonzero(dec) == k
    np.testing.assert_array_equal(dec[msg["idx"]], x[msg["idx"]])


def test_roundtrip_property_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(st.integers(1, 5000), st.integers(0, 2 ** 31 - 1),
           st.sampled_from(["topk", "int8"]))
    def run(t, seed, scheme):
        rng = np.random.default_rng(seed)
        x = (rng.normal(size=t) * rng.uniform(1e-4, 10)).astype(np.float32)
        msg = compress(x, scheme, ratio=0.25, rng=np.random.default_rng(1))
        dec = decompress(msg)
        assert dec.shape == x.shape
        if scheme == "int8":
            # error below one quant step of the worst chunk
            assert np.abs(dec - x).max() <= np.asarray(
                msg["scales"]).max() + 1e-6
        else:
            # kept values exact; dropped values bounded by smallest kept
            kept = np.asarray(msg["idx"], np.int64)
            np.testing.assert_array_equal(dec[kept], x[kept])
            dropped = np.setdiff1d(np.arange(t), kept)
            if dropped.size and kept.size:
                assert (np.abs(x[dropped]).max()
                        <= np.abs(x[kept]).min() + 1e-7)

    run()


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------


def test_error_feedback_telescopes_exactly():
    """Invariant: sum of everything decompressed server-side + current
    residual == sum of the true deltas. Compression delays mass, never
    drops it."""
    rng = np.random.default_rng(4)
    for scheme in ("topk", "int8"):
        ef = ErrorFeedback(scheme, ratio=0.1, seed=7)
        deltas = [rng.normal(size=3000).astype(np.float32) * 0.1
                  for _ in range(6)]
        received = np.zeros(3000, np.float64)
        for d in deltas:
            received += decompress(ef.step(d)).astype(np.float64)
        total = np.sum(np.asarray(deltas, np.float64), axis=0)
        np.testing.assert_allclose(received + ef.residual, total,
                                   atol=1e-4)


def test_error_feedback_residual_flushes_to_zero():
    """Posting zero deltas drains the residual: top-k keeps emitting the
    largest leftover coordinates, int8 shrinks the residual by ~qmax per
    round (scale is max|residual|/qmax) — both telescope to zero."""
    rng = np.random.default_rng(5)
    for scheme, rounds in (("topk", 40), ("int8", 6)):
        ef = ErrorFeedback(scheme, ratio=0.1, seed=8)
        ef.step(rng.normal(size=2000).astype(np.float32))
        r0 = np.abs(ef.residual).max()
        assert r0 > 0
        for _ in range(rounds):
            ef.step(np.zeros(2000, np.float32))
        assert np.abs(ef.residual).max() < 1e-5 * max(r0, 1.0)


def test_error_feedback_reset_and_scheme_guard():
    ef = ErrorFeedback("topk", ratio=0.5)
    ef.step(np.ones(10, np.float32))
    assert ef.residual is not None
    ef.reset()
    assert ef.residual is None
    with pytest.raises(ValueError):
        ErrorFeedback("none")


# ---------------------------------------------------------------------------
# fused kernel vs oracle, and the cohort reduction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,c", [(1, 1), (3, 2), (4, 8), (7, 13)])
def test_dequant_reduce_kernel_matches_oracle(n, c):
    rng = np.random.default_rng(6)
    t = c * CHUNK
    q = rng.integers(-127, 128, size=(n, t)).astype(np.int8)
    scales = rng.uniform(1e-6, 1e-2, size=(n, c)).astype(np.float32)
    w = rng.uniform(0.0, 1.0, size=n).astype(np.float32)
    ref = np.asarray(dequant_reduce_ref(q, scales, w))
    # one whole-T tile, and 8-chunk tiles with a partial last tile (c=13)
    for bt in (8 * CHUNK, 32 * CHUNK):
        out = np.asarray(dequant_reduce_flat(q, scales, w, bt=bt,
                                             interpret=True))
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_reduce_compressed_matches_dense_weighted_sum():
    rng = np.random.default_rng(7)
    t = 3000
    for scheme in ("topk", "int8"):
        msgs = [compress(rng.normal(size=t).astype(np.float32), scheme,
                         ratio=0.2, rng=np.random.default_rng(i))
                for i in range(4)]
        w = rng.uniform(0.1, 1.0, size=4).astype(np.float32)
        dense = np.sum([wi * decompress(m).astype(np.float64)
                        for wi, m in zip(w, msgs)], axis=0)
        out = reduce_compressed(msgs, w)
        assert out.shape == (t,)
        np.testing.assert_allclose(out, dense, atol=1e-5)
        # the single-pass norms match the standalone wire-dict measure
        out2, norms = reduce_compressed(msgs, w, return_norms=True)
        np.testing.assert_allclose(out2, out, atol=1e-6)
        for m, n in zip(msgs, norms):
            assert n == pytest.approx(compression.update_norm(m), rel=1e-6)


def test_reduce_compressed_rejects_mixed_cohorts():
    a = compress(np.ones(100, np.float32), "topk")
    b = compress(np.ones(100, np.float32), "int8")
    with pytest.raises(ValueError, match="mixed"):
        reduce_compressed([a, b], [0.5, 0.5])
    c = compress(np.ones(200, np.float32), "topk")
    with pytest.raises(ValueError, match="size"):
        reduce_compressed([a, c], [0.5, 0.5])


def test_wire_bytes_and_update_norm():
    rng = np.random.default_rng(8)
    x = rng.normal(size=10_000).astype(np.float32)
    topk = compress(x, "topk", ratio=0.1)
    int8 = compress(x, "int8", rng=rng)
    # topk: ~8 bytes per kept coordinate vs 4 bytes per raw float
    assert compression.wire_bytes(topk) == pytest.approx(0.2 * x.nbytes)
    # int8: ~1 byte per float + 4 bytes per 1024-chunk scale
    assert compression.wire_bytes(int8) < 0.27 * x.nbytes
    for msg in (topk, int8):
        assert compression.update_norm(msg) == pytest.approx(
            float(np.linalg.norm(decompress(msg))), rel=1e-6)


# ---------------------------------------------------------------------------
# JobCreator compatibility matrix
# ---------------------------------------------------------------------------


BASE = {"arch": "fedforecast-100m", "rounds": 1, "local_steps": 1,
        "batch_size": 2, "lr": 1e-3, "data_schema": None}


def make_job(**extra):
    jc = JobCreator(MetadataStore())
    return jc.from_admin("admin", {**BASE, **extra})


def test_job_matrix_accepts_supported_combinations():
    for extra in (
            {"secure_aggregation": False, "compression": "int8"},
            {"secure_aggregation": False, "compression": "topk",
             "compression_ratio": 0.05},
            {"secure_aggregation": False, "compression": "int8",
             "protocol": "async_buff"},
            # composable privacy: integer-domain masks compose with int8
            {"secure_aggregation": True, "compression": "int8"},
            {"secure_aggregation": True, "compression": "int8",
             "dp_epsilon": 8.0},
            {"secure_aggregation": False, "compression": "int8",
             "dp_epsilon": 4.0, "dp_clip": 0.5},
            {"secure_aggregation": True, "compression": "none"}):
        job = make_job(**extra)
        assert job.compression == extra["compression"]


def test_job_matrix_rejects_unsupported_combinations():
    # secure+topk stays rejected: the index set leaks the update support
    with pytest.raises(ValueError, match="secure_aggregation"):
        make_job(secure_aggregation=True, compression="topk")
    with pytest.raises(ValueError, match="aggregation"):
        make_job(secure_aggregation=False, compression="topk",
                 aggregation="median")
    with pytest.raises(ValueError, match="unknown compression"):
        make_job(secure_aggregation=False, compression="gzip")
    with pytest.raises(ValueError, match="compression_ratio"):
        make_job(secure_aggregation=False, compression="topk",
                 compression_ratio=0.0)
    with pytest.raises(ValueError, match="quant_bits"):
        make_job(secure_aggregation=False, compression="int8",
                 quant_bits=16)
    # the DP noise stage rides the quantized integer plane, synchronously
    with pytest.raises(ValueError, match="dp_epsilon"):
        make_job(secure_aggregation=False, compression="topk",
                 dp_epsilon=8.0)
    with pytest.raises(ValueError, match="dp_epsilon"):
        make_job(secure_aggregation=False, compression="int8",
                 protocol="async_buff", dp_epsilon=8.0)
    with pytest.raises(ValueError, match="dp_delta"):
        make_job(secure_aggregation=True, compression="int8",
                 dp_epsilon=8.0, dp_delta=1.5)
    with pytest.raises(ValueError, match="dp_clip"):
        make_job(secure_aggregation=True, compression="int8",
                 dp_epsilon=8.0, dp_clip=0.0)


def test_compression_is_a_negotiable_default_decision():
    from repro.core.governance import DEFAULT_DECISIONS
    assert DEFAULT_DECISIONS["compression"] == "none"
    assert "compression_ratio" in DEFAULT_DECISIONS
    assert "quant_bits" in DEFAULT_DECISIONS


# ---------------------------------------------------------------------------
# end-to-end: compressed runs track their uncompressed twins
# ---------------------------------------------------------------------------


def run_twin(compression_scheme, protocol="sync", seed=0, rounds=2,
             **extra):
    from repro.core import Consortium
    from repro.data import make_silo_datasets
    con = Consortium(["windco", "solarx", "gridpower"], seed=seed)
    decisions = {**BASE, "rounds": rounds, "local_steps": 2,
                 "secure_aggregation": False, "protocol": protocol,
                 "compression": compression_scheme, **extra}
    job = con.server.job_creator.from_admin("server-admin", decisions)
    datasets = make_silo_datasets(3, vocab=512, seq_len=32, seed=seed)
    run_id = con.start(job, datasets)
    phase = con.run_to_completion()
    return con, run_id, phase


def update_post_bytes(con, run_id):
    board = con.server.board
    return sum(board.stat(p)["bytes"]
               for p in board.list(f"runs/{run_id}/round/*/update/*"))


def test_e2e_sync_compressed_matches_uncompressed_twin():
    con_u, run_u, phase_u = run_twin("none")
    con_c, run_c, phase_c = run_twin("int8")
    assert phase_u == phase_c == "done"
    # identical seeds/data: the int8 twin's quality tracks the raw twin
    # to quantization noise (error feedback carries the rest forward)
    eval_u = con_u.server.run.history[-1]["mean_eval_loss"]
    eval_c = con_c.server.run.history[-1]["mean_eval_loss"]
    assert abs(eval_u - eval_c) < 0.05
    # the wire shrank: posted update resources are >= 3.5x smaller, and
    # the board's client-byte counter agrees (bytes-on-wire assertion)
    assert update_post_bytes(con_u, run_u) > 3.5 * update_post_bytes(
        con_c, run_c)
    assert (con_u.server.board.stats["bytes_posted_clients"]
            > 2.5 * con_c.server.board.stats["bytes_posted_clients"])
    # the negotiated scheme rode the provenance chain with the job
    starts = con_c.server.metadata.query(kind="experiment",
                                         event="run_start")
    assert starts and starts[-1]["job"]["compression"] == "int8"
    assert con_c.server.metadata.verify_chain()


def test_e2e_sync_topk_completes_and_sparsifies_the_wire():
    con_u, run_u, _ = run_twin("none")
    con_c, run_c, phase = run_twin("topk", compression_ratio=0.1)
    assert phase == "done"
    assert all(np.isfinite(h["mean_train_loss"])
               for h in con_c.server.run.history)
    # 10% of coordinates at 8 bytes/coordinate ~ 5x smaller than raw fp32
    assert update_post_bytes(con_u, run_u) > 4.0 * update_post_bytes(
        con_c, run_c)


def test_e2e_async_buffered_consumes_dequantized_deltas():
    con_u, _, phase_u = run_twin("none", protocol="async_buff", rounds=3,
                                 async_buffer_size=2)
    con_c, _, phase_c = run_twin("int8", protocol="async_buff", rounds=3,
                                 async_buffer_size=2)
    assert phase_u == phase_c == "done"
    eval_u = con_u.server.run.history[-1]["mean_eval_loss"]
    eval_c = con_c.server.run.history[-1]["mean_eval_loss"]
    assert abs(eval_u - eval_c) < 0.05
    # async updates are overwritten in place: compare the resource size
    board_u = con_u.server.board
    board_c = con_c.server.board
    for path in board_u.list("runs/*/async/update/*"):
        assert board_u.stat(path)["bytes"] > 0
    bytes_u = sum(board_u.stat(p)["bytes"]
                  for p in board_u.list("runs/*/async/update/*"))
    bytes_c = sum(board_c.stat(p)["bytes"]
                  for p in board_c.list("runs/*/async/update/*"))
    assert bytes_u > 3.0 * bytes_c
    assert con_c.server.metadata.verify_chain()


def test_e2e_weighted_sync_compressed_small_silo():
    """Weighted FedAvg + compression: a small silo's declared n_examples
    caps its weight, and the compressed plane reduces with those weights."""
    from repro.core import Consortium
    from repro.data import make_silo_datasets
    con = Consortium(["big", "small"], seed=1)
    datasets = make_silo_datasets(2, vocab=512, seq_len=32, seed=1)
    datasets[1].n_examples = 1          # tiny silo: ~zero FedAvg weight
    decisions = {**BASE, "rounds": 2, "local_steps": 2,
                 "secure_aggregation": False, "compression": "int8"}
    job = con.server.job_creator.from_admin("server-admin", decisions)
    run_id = con.start(job, datasets)
    assert con.run_to_completion() == "done"
    rounds = con.server.metadata.query(kind="experiment", event="round")
    contrib = rounds[-1]["contributions"]["data_size"]
    cids = sorted(contrib, key=contrib.get)
    assert contrib[cids[-1]] > 0.7      # the big silo dominates
    assert run_id
