"""Composable privacy (DESIGN.md §Composable privacy).

Secure aggregation over *compressed* updates: pairwise masks drawn over
the quantized integer domain cancel bit-exactly under the server's
modular sum, so int8 coding and masking compose without decoding either.
This suite pins the properties the composition rests on:

  * integer-domain mask cancellation is BIT-EXACT (zero tolerance) —
    both at the PRG level (offsets sum to 0 mod M) and through the
    production wire path (masked_compress -> reduce_masked)
  * dropout repair telescopes orphaned masks out, still bit-exact
  * error-feedback telescoping survives masking (nothing is lost to
    quantization across rounds, only delayed)
  * the masked Pallas kernel matches its jnp oracle exactly
  * the JobCreator compatibility matrix over the full
    {secure} x {compression} x {protocol} x {aggregation} cross-product
    matches a golden table, and every rejection lands a provenance
    event carrying the reason AND the full offending combination
  * e2e: a secure+int8 run matches its plain-int8 twin to <= 1e-4,
    including through a mid-round dropout repair
  * DP noise stage: fixed seeds reproduce runs exactly, and the noise
    never leaks into the error-feedback residual

Each hypothesis property has a plain always-running sibling so the
invariants execute even where hypothesis is not installed.
"""
import numpy as np
import pytest

import jax

from repro.core import compression
from repro.core.compression import (DEFAULT_QUANT_RANGE, ErrorFeedback,
                                    dp_sigma_total, masked_compress,
                                    reduce_masked, wire_bytes)
from repro.core.jobs import JobCreator
from repro.core.metadata import MetadataStore
from repro.core.secure_agg import (int_mask_offset, int_repair_correction,
                                   mask_modulus_bits)
from repro.kernels.compressed_agg.kernel import (CHUNK,
                                                 masked_dequant_reduce_flat)
from repro.kernels.compressed_agg.ref import masked_dequant_reduce_ref

SECRET = b"consortium-pair-secret"


# ---------------------------------------------------------------------------
# integer-domain mask cancellation: bit-exact, zero tolerance
# ---------------------------------------------------------------------------


def _cohort(n):
    return [f"silo-{i}" for i in range(n)]


def _mod_sum(arrays, mbits):
    """Wrap-around uint32 sum reduced mod 2**mbits — the server's sum."""
    acc = np.zeros_like(np.asarray(arrays[0], np.uint32))
    for a in arrays:
        acc = acc + np.asarray(a, np.uint32)      # uint32 wraps = mod 2**32
    return acc & np.uint32((1 << mbits) - 1)


def _check_offsets_cancel(n, size, mbits):
    cohort = _cohort(n)
    offs = [np.asarray(int_mask_offset(size, c, cohort, SECRET, mbits),
                       np.uint32) for c in cohort]
    total = _mod_sum(offs, mbits)
    np.testing.assert_array_equal(total, np.zeros(size, np.uint32))


def test_int_mask_offsets_cancel_bit_exact():
    for n, size, mbits in ((2, CHUNK, 16), (3, 2 * CHUNK, 16),
                           (5, CHUNK, 32), (7, 3 * CHUNK, 32)):
        _check_offsets_cancel(n, size, mbits)


def test_int_mask_offsets_cancel_property_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=30, deadline=None)
    @given(st.integers(2, 9), st.integers(1, 3000),
           st.sampled_from([16, 32]))
    def run(n, size, mbits):
        _check_offsets_cancel(n, size, mbits)

    run()


def test_single_client_cohort_has_zero_mask():
    off = np.asarray(int_mask_offset(CHUNK, "only", ["only"], SECRET, 16))
    np.testing.assert_array_equal(off, np.zeros(CHUNK, np.uint32))


def test_mask_modulus_bits_tracks_cohort_headroom():
    # span = 4 * N * qmax must fit the modulus: small cohorts ride a
    # 2-byte wire, big ones widen to 4 bytes
    assert mask_modulus_bits(4, 8) == 16
    assert mask_modulus_bits(8, 8) == 16
    assert mask_modulus_bits(200, 8) == 32
    assert mask_modulus_bits(2, 2) == 16


def _masked_cohort_messages(n, t, seed=0, grid=None):
    """Quantize+mask n random buffers through the production path."""
    grid = grid if grid is not None else DEFAULT_QUANT_RANGE / 127
    cohort = _cohort(n)
    rng = np.random.default_rng(seed)
    msgs, deqs = [], []
    for cid in cohort:
        buf = (rng.normal(size=t) * 0.004).astype(np.float32)
        msg, deq = masked_compress(buf, grid=grid, client_id=cid,
                                   cohort=cohort, pair_secret=SECRET,
                                   rng=np.random.default_rng(hash(cid)
                                                             % 2 ** 31))
        msgs.append(msg)
        deqs.append(deq)
    return cohort, msgs, deqs, grid


def _assert_decode_is_exact_integer_sum(msgs, deqs, grid,
                                        corrections=None, keep=None):
    """The decoded cohort total, in grid units, equals the exact integer
    sum of the per-client quantized streams — zero tolerance."""
    keep = keep if keep is not None else range(len(msgs))
    total = reduce_masked([msgs[i] for i in keep],
                          corrections=corrections, interpret=True)
    got = np.rint(np.asarray(total, np.float64) / grid).astype(np.int64)
    want = np.zeros_like(got)
    for i in keep:
        want += np.rint(np.asarray(deqs[i], np.float64) / grid
                        ).astype(np.int64)
    np.testing.assert_array_equal(got, want)


def test_wire_path_mask_cancellation_bit_exact():
    for n, t in ((2, 100), (3, CHUNK), (5, 2 * CHUNK + 17)):
        _, msgs, deqs, grid = _masked_cohort_messages(n, t, seed=n)
        _assert_decode_is_exact_integer_sum(msgs, deqs, grid)


def test_wire_path_cancellation_property_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(st.integers(2, 6), st.integers(1, 3 * CHUNK),
           st.integers(0, 2 ** 31 - 1))
    def run(n, t, seed):
        _, msgs, deqs, grid = _masked_cohort_messages(n, t, seed=seed)
        _assert_decode_is_exact_integer_sum(msgs, deqs, grid)

    run()


def test_small_cohort_rides_uint16_wire():
    _, msgs, _, _ = _masked_cohort_messages(3, CHUNK)
    assert msgs[0]["mbits"] == 16
    assert msgs[0]["z"].dtype == np.uint16
    assert wire_bytes(msgs[0]) == 2 * CHUNK      # 2 B/value, padded length


def test_masked_message_cannot_be_decompressed_alone():
    _, msgs, _, _ = _masked_cohort_messages(2, 64)
    with pytest.raises(ValueError, match="masked_int8"):
        compression.decompress(msgs[0])
    with pytest.raises(ValueError, match="norm"):
        compression.update_norm(msgs[0])


def test_cohorts_disagreeing_on_contract_are_refused():
    _, msgs_a, _, _ = _masked_cohort_messages(2, 64, grid=1e-4)
    _, msgs_b, _, _ = _masked_cohort_messages(2, 64, grid=2e-4)
    with pytest.raises(ValueError, match="contract"):
        reduce_masked([msgs_a[0], msgs_b[1]], interpret=True)


# ---------------------------------------------------------------------------
# dropout repair in the integer domain
# ---------------------------------------------------------------------------


def _check_repair_bit_exact(n, t, n_drop, seed=0):
    cohort, msgs, deqs, grid = _masked_cohort_messages(n, t, seed=seed)
    dropped = cohort[:n_drop]
    survivors = [i for i, c in enumerate(cohort) if c not in dropped]
    mbits = msgs[0]["mbits"]
    tpad = t + (-t) % CHUNK
    corr = [np.asarray(int_repair_correction(tpad, cohort[i], dropped,
                                             SECRET, mbits), np.uint32)
            for i in survivors]
    _assert_decode_is_exact_integer_sum(msgs, deqs, grid,
                                        corrections=corr, keep=survivors)


def test_dropout_repair_removes_orphaned_masks_bit_exact():
    _check_repair_bit_exact(5, 2 * CHUNK + 5, 1, seed=1)
    _check_repair_bit_exact(5, CHUNK, 2, seed=2)   # two dropouts at once
    _check_repair_bit_exact(3, 77, 1, seed=3)


def test_dropout_repair_property_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(st.integers(3, 7), st.integers(1, 2 * CHUNK),
           st.integers(1, 2), st.integers(0, 2 ** 31 - 1))
    def run(n, t, n_drop, seed):
        _check_repair_bit_exact(n, t, min(n_drop, n - 1), seed=seed)

    run()


# ---------------------------------------------------------------------------
# error-feedback telescoping survives masking
# ---------------------------------------------------------------------------


def test_ef_telescoping_survives_masking():
    """Across R masked rounds, the sum of everything the cohort decode
    recovered equals the sum of the true weighted deltas minus the
    residuals still in flight — quantization delays mass, never drops
    it, and masking does not change that."""
    n, t, rounds = 3, 2 * CHUNK + 9, 4
    cohort = _cohort(n)
    efs = {c: ErrorFeedback("int8", seed=i, quant_range=DEFAULT_QUANT_RANGE)
           for i, c in enumerate(cohort)}
    rng = np.random.default_rng(7)
    recovered = np.zeros(t, np.float64)
    true_sum = np.zeros(t, np.float64)
    for _ in range(rounds):
        msgs = []
        for c in cohort:
            delta = (rng.normal(size=t) * 0.003).astype(np.float32)
            true_sum += delta
            msgs.append(efs[c].step_masked(delta, weight=1.0, client_id=c,
                                           cohort=cohort,
                                           pair_secret=SECRET))
        recovered += np.asarray(reduce_masked(msgs, interpret=True),
                                np.float64)
    in_flight = sum(np.asarray(efs[c].residual, np.float64) for c in cohort)
    np.testing.assert_allclose(recovered, true_sum - in_flight, atol=2e-5)


def test_ef_residual_bounded_by_grid():
    # with everything in range, the residual is pure rounding error
    ef = ErrorFeedback("int8", seed=0, quant_range=DEFAULT_QUANT_RANGE)
    delta = (np.random.default_rng(0).normal(size=500) * 1e-3
             ).astype(np.float32)
    ef.step_masked(delta, weight=1.0, client_id="a", cohort=["a", "b"],
                   pair_secret=SECRET)
    assert np.abs(ef.residual).max() <= ef.grid + 1e-7


# ---------------------------------------------------------------------------
# masked Pallas kernel vs jnp oracle
# ---------------------------------------------------------------------------


def _kernel_case(n, tp, mbits, seed, with_corr):
    rng = np.random.default_rng(seed)
    z = rng.integers(0, 1 << mbits, size=(n, tp)).astype(np.uint32)
    scales = (rng.uniform(1e-5, 1e-3, tp // CHUNK)).astype(np.float32)
    corr = (rng.integers(0, 1 << mbits, size=(n, tp)).astype(np.uint32)
            if with_corr else None)
    return z, scales, corr


@pytest.mark.parametrize("mbits", [16, 32])
@pytest.mark.parametrize("with_corr", [False, True])
def test_masked_kernel_matches_ref(mbits, with_corr):
    # whole-T tiles, then 8-chunk tiles with a partial last tile
    for n, tp, bt in ((2, CHUNK, 32 * CHUNK), (4, 8 * CHUNK, 32 * CHUNK),
                      (3, 13 * CHUNK, 8 * CHUNK)):
        z, scales, corr = _kernel_case(n, tp, mbits, n, with_corr)
        got = np.asarray(masked_dequant_reduce_flat(
            z, scales, modulus_bits=mbits, corr=corr, bt=bt,
            interpret=True))
        want = np.asarray(masked_dequant_reduce_ref(
            z, scales, mbits, corr=corr))
        # integer sums are order-independent; the only float op is the
        # final per-element scale — identical in both, so bit-equal
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# the compatibility matrix, pinned cell by cell
# ---------------------------------------------------------------------------

R_SECURE_AGG = "secure_aggregation requires fedavg"
R_ASYNC_SECURE = "async_buff requires secure_aggregation=False"
R_ASYNC_AGG = "async_buff requires fedavg"
R_SECURE_TOPK = ("secure_aggregation composes with int8 only: topk "
                 "index sets leak the update support")
R_COMP_AGG = "compression requires fedavg"

# golden table over the full cross-product: None = accepted, else the
# exact provenance reason. A literal table, not a re-derivation of the
# validator's logic: flipping any cell must be a deliberate edit here.
GOLDEN = {}
for _agg in ("trimmed_mean", "median"):
    for _comp in ("none", "topk", "int8"):
        for _proto in ("sync", "async_buff"):
            GOLDEN[(True, _comp, _proto, _agg)] = R_SECURE_AGG
    GOLDEN[(False, "none", "sync", _agg)] = None
    GOLDEN[(False, "topk", "sync", _agg)] = R_COMP_AGG
    GOLDEN[(False, "int8", "sync", _agg)] = R_COMP_AGG
    for _comp in ("none", "topk", "int8"):
        GOLDEN[(False, _comp, "async_buff", _agg)] = R_ASYNC_AGG
for _comp in ("none", "topk", "int8"):
    GOLDEN[(True, _comp, "async_buff", "fedavg")] = R_ASYNC_SECURE
    GOLDEN[(False, _comp, "sync", "fedavg")] = None
    GOLDEN[(False, _comp, "async_buff", "fedavg")] = None
GOLDEN[(True, "none", "sync", "fedavg")] = None
GOLDEN[(True, "int8", "sync", "fedavg")] = None      # the tentpole cell
GOLDEN[(True, "topk", "sync", "fedavg")] = R_SECURE_TOPK

BASE = {"arch": "fedforecast-100m", "rounds": 1, "local_steps": 1,
        "batch_size": 2, "lr": 1e-3, "data_schema": None}


@pytest.mark.parametrize("secure,comp,proto,agg", sorted(
    GOLDEN, key=str))
def test_compatibility_matrix_matches_golden_table(secure, comp, proto,
                                                   agg):
    assert len(GOLDEN) == 36        # full cross-product, no cell missing
    meta = MetadataStore()
    jc = JobCreator(meta)
    decisions = {**BASE, "secure_aggregation": secure, "compression": comp,
                 "protocol": proto, "aggregation": agg,
                 "compression_ratio": 0.1}
    expected = GOLDEN[(secure, comp, proto, agg)]
    if expected is None:
        job = jc.from_admin("admin", decisions)
        assert (job.secure_aggregation, job.compression, job.protocol,
                job.aggregation) == (secure, comp, proto, agg)
        assert not [r for r in meta.query(kind="provenance")
                    if r["outcome"] == "rejected"]
    else:
        with pytest.raises(ValueError):
            jc.from_admin("admin", decisions)
        rej = [r for r in meta.query(kind="provenance")
               if r["operation"] == "create_job"
               and r["outcome"] == "rejected"]
        assert len(rej) == 1
        assert rej[0]["details"]["reason"] == expected
        # the provenance event carries the FULL offending combination
        combo = rej[0]["details"]["decisions"]
        assert combo["secure_aggregation"] == secure
        assert combo["compression"] == comp
        assert combo["protocol"] == proto
        assert combo["aggregation"] == agg


def test_rejection_provenance_includes_dp_and_hp_flags():
    meta = MetadataStore()
    jc = JobCreator(meta)
    with pytest.raises(ValueError, match="dp_epsilon"):
        jc.from_admin("admin", {**BASE, "secure_aggregation": False,
                                "compression": "topk", "dp_epsilon": 4.0})
    rej = [r for r in meta.query(kind="provenance")
           if r["outcome"] == "rejected"][0]
    d = rej["details"]["decisions"]
    assert set(d) == {"secure_aggregation", "compression", "protocol",
                      "aggregation", "dp_epsilon",
                      "hyperparameter_search"}
    assert d["dp_epsilon"] == 4.0


# ---------------------------------------------------------------------------
# e2e: secure+int8 twin-equivalence
# ---------------------------------------------------------------------------


def _run(extra, drop_at=None, seed=0):
    from repro.core import Consortium
    from repro.data import make_silo_datasets
    con = Consortium(["windco", "solarx", "gridpower"], seed=seed)
    decisions = {**BASE, "rounds": 2, "local_steps": 2,
                 "round_deadline_ticks": 3, **extra}
    job = con.server.job_creator.from_admin("server-admin", decisions)
    datasets = make_silo_datasets(3, vocab=512, seq_len=32, seed=seed)
    con.start(job, datasets)
    phase = con.run_to_completion(**({"drop_at": drop_at}
                                     if drop_at else {}))
    return con, phase


def _final(con):
    return con.server.store.get(con.server.run.global_digest)


def _max_diff(a, b):
    return max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.slow
def test_e2e_secure_int8_matches_plain_int8_twin():
    """Acceptance: masking changes NOTHING about the learning dynamics —
    a secure+int8 run and a plain int8 run on the same fixed grid land
    on the same model to <= 1e-4 (fp32 reduction ordering aside)."""
    con_s, ph_s = _run({"secure_aggregation": True, "compression": "int8"})
    con_p, ph_p = _run({"secure_aggregation": False, "compression": "int8",
                        "quant_range": DEFAULT_QUANT_RANGE})
    assert ph_s == ph_p == "done"
    assert _max_diff(_final(con_s), _final(con_p)) <= 1e-4


@pytest.mark.slow
def test_e2e_secure_int8_dropout_repair_matches_twin():
    """A client dropped mid-collect: the survivors' integer corrections
    telescope its orphaned masks out, and the repaired run still matches
    the plain twin that lost the same client."""
    drop = {"solarx": ("collect", 1)}
    con_s, ph_s = _run({"secure_aggregation": True, "compression": "int8"},
                       drop_at=dict(drop))
    con_p, ph_p = _run({"secure_aggregation": False, "compression": "int8",
                        "quant_range": DEFAULT_QUANT_RANGE},
                       drop_at=dict(drop))
    assert ph_s == ph_p == "done"
    assert len(con_s.server.run.dropped) == 1
    # the server published the dropout and both survivors posted
    # epoch-stamped integer corrections
    pubs = [r for r in con_s.server.metadata.query(kind="provenance")
            if r["operation"] == "publish_dropout"]
    assert len(pubs) == 1
    posts = con_s.server.board.list(
        f"runs/{con_s.server.run.run_id}/round/*/repair/*/*")
    assert len(posts) == 2                       # both survivors posted
    assert _max_diff(_final(con_s), _final(con_p)) <= 1e-4


def test_e2e_masked_wire_is_uncompressed_integers():
    """Masked residues are uniform — no entropy coding; the wire is the
    raw 2-byte stream for a 3-silo cohort."""
    con_s, _ = _run({"secure_aggregation": True, "compression": "int8"})
    r = con_s.server.run
    board = con_s.server.board
    paths = board.list(f"runs/{r.run_id}/round/*/update/*")
    assert paths
    fp32_plane = 4 * sum(np.asarray(l).size
                         for l in jax.tree.leaves(_final(con_s)))
    for p in paths:
        # 2 B/value + framing: well under half the fp32 masked plane
        assert board.stat(p)["bytes"] < fp32_plane / 1.9


# ---------------------------------------------------------------------------
# DP noise stage
# ---------------------------------------------------------------------------


def test_dp_sigma_total_gaussian_mechanism():
    sigma = dp_sigma_total(8.0, 1e-5, 1.0)
    assert sigma == pytest.approx(
        np.sqrt(2 * np.log(1.25 / 1e-5)) / 8.0)
    with pytest.raises(ValueError):
        dp_sigma_total(0.0, 1e-5, 1.0)
    with pytest.raises(ValueError):
        dp_sigma_total(8.0, 2.0, 1.0)


def test_dp_noise_excluded_from_residual():
    """The EF residual must absorb clip+quantization error ONLY: noise
    folded into the residual would telescope away over rounds, silently
    cancelling the privacy mechanism."""
    delta = (np.random.default_rng(3).normal(size=2000) * 1e-3
             ).astype(np.float32)
    huge_noise = {"epsilon": 0.01, "delta": 1e-5, "clip": 10.0,
                  "sigma_total": dp_sigma_total(0.01, 1e-5, 10.0)}
    ef = ErrorFeedback("int8", seed=0, quant_range=DEFAULT_QUANT_RANGE,
                       dp=huge_noise, dp_seed=1)
    ef.step_masked(delta, weight=1.0, client_id="a", cohort=["a", "b"],
                   pair_secret=SECRET)
    # sigma_total here is ~hundreds of grid steps; a leaked residual
    # would be orders of magnitude above one grid step
    assert np.abs(ef.residual).max() <= ef.grid + 1e-7


@pytest.mark.slow
def test_dp_fixed_seed_runs_are_identical():
    extra = {"secure_aggregation": True, "compression": "int8",
             "dp_epsilon": 8.0, "dp_clip": 1.0, "dp_seed": 17}
    con_a, ph_a = _run(extra)
    con_b, ph_b = _run(extra)
    assert ph_a == ph_b == "done"
    assert _max_diff(_final(con_a), _final(con_b)) == 0.0


def test_dp_run_records_accounting_provenance():
    con, ph = _run({"secure_aggregation": True, "compression": "int8",
                    "dp_epsilon": 8.0, "dp_clip": 1.0})
    assert ph == "done"
    recs = [r for r in con.server.metadata.query(kind="provenance")
            if r["operation"] == "dp_accounting"]
    assert len(recs) == 1
    det = recs[0]["details"]
    assert det["epsilon"] == 8.0
    assert det["epsilon_total_naive"] == 8.0 * 2     # naive R*eps, 2 rounds
    assert det["sigma_round"] == pytest.approx(
        dp_sigma_total(8.0, 1e-5, 1.0))
