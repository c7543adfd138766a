"""Dropout-tolerant rounds: deadlines, cohort shrinking, mask repair.

Covers DESIGN.md §Dropout-tolerant rounds end to end: protocol-level
mask-repair algebra (corrections cancel exactly the orphaned masks), the
weighted pre-scaled reduction, the fused corrected-combine kernel vs its
oracle, and full consortium runs where clients vanish mid-collect /
mid-evaluate (masked and unmasked), including the pause-below-min_cohort
path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Consortium, secure_agg
from repro.data import make_silo_datasets
from repro.kernels.secure_agg.kernel import masked_sum_corrected_flat
from repro.kernels.secure_agg.ops import masked_sum_corrected
from repro.kernels.secure_agg.ref import masked_sum_corrected_ref


# ---------------------------------------------------------------------------
# protocol level: repair algebra on packed buffers
# ---------------------------------------------------------------------------
def _masked_cohort(bufs, cohort, secret=b"s", scale=1.0):
    return [secure_agg.mask_packed(b, c, cohort, secret, scale=scale)
            for b, c in zip(bufs, cohort)]


def test_repair_correction_cancels_orphaned_masks():
    """1-of-5 dropout: survivors' corrected mean == plain survivor mean
    to <= 1e-4 max abs error (the acceptance criterion, protocol level)."""
    cohort = [f"c{i}" for i in range(5)]
    rng = np.random.default_rng(0)
    bufs = [rng.normal(size=(4096,)).astype(np.float32) for _ in cohort]
    masked = _masked_cohort(bufs, cohort)
    dropped, survivors = cohort[2:3], cohort[:2] + cohort[3:]
    surv_idx = [cohort.index(c) for c in survivors]
    corr = [secure_agg.repair_correction(4096, c, dropped, b"s", scale=1.0)
            for c in survivors]
    # without repair the survivor mean is corrupted by the orphaned masks
    broken = secure_agg.aggregate_masked_packed(
        jnp.stack([masked[i] for i in surv_idx]))
    plain = np.mean([bufs[i] for i in surv_idx], axis=0)
    assert float(np.abs(np.asarray(broken) - plain).max()) > 0.01
    # with corrections folded into the reduction it telescopes again
    repaired = secure_agg.aggregate_masked_packed(
        jnp.stack([masked[i] for i in surv_idx]), corrections=jnp.stack(corr))
    assert float(np.abs(np.asarray(repaired) - plain).max()) <= 1e-4


def test_repair_weighted_prescaled_protocol():
    """Unequal weights: clients pre-scale before masking; the corrected
    uniform sum divided by the survivors' total weight is exact weighted
    FedAvg over the survivors."""
    cohort = [f"silo-{i}" for i in range(4)]
    weights = [1.0, 3.0, 0.5, 2.0]
    rng = np.random.default_rng(1)
    bufs = [rng.normal(size=(513,)).astype(np.float32) for _ in cohort]
    masked = [secure_agg.mask_packed(np.float32(w) * b, c, cohort, b"k",
                                     scale=1.0)
              for b, c, w in zip(bufs, cohort, weights)]
    dropped = [cohort[3]]
    surv = [0, 1, 2]
    corr = [secure_agg.repair_correction(513, cohort[i], dropped, b"k",
                                         scale=1.0) for i in surv]
    total = secure_agg.aggregate_masked_packed(
        jnp.stack([masked[i] for i in surv]),
        np.ones(len(surv), np.float32), corrections=jnp.stack(corr))
    denom = sum(weights[i] for i in surv)
    expect = sum(weights[i] * bufs[i] for i in surv) / denom
    np.testing.assert_allclose(np.asarray(total) / denom, expect, atol=1e-4)


def test_repair_property_random_cohorts_and_dropsets():
    """Hypothesis: for any cohort/dropout split the repaired survivor sum
    matches the plain survivor mean to fp32 tolerance."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 6), st.data())
    def check(n, data):
        cohort = [f"c{i}" for i in range(n)]
        n_drop = data.draw(st.integers(1, n - 1))
        drop_idx = data.draw(st.permutations(list(range(n))))[:n_drop]
        dropped = sorted(cohort[i] for i in drop_idx)
        surv = [c for c in cohort if c not in dropped]
        rng = np.random.default_rng(n)
        bufs = {c: rng.normal(size=(64,)).astype(np.float32)
                for c in cohort}
        masked = {c: secure_agg.mask_packed(bufs[c], c, cohort, b"s",
                                            scale=2.0) for c in surv}
        corr = {c: secure_agg.repair_correction(64, c, dropped, b"s",
                                                scale=2.0) for c in surv}
        out = secure_agg.aggregate_masked_packed(
            jnp.stack([masked[c] for c in surv]),
            corrections=jnp.stack([corr[c] for c in surv]))
        plain = np.mean([bufs[c] for c in surv], axis=0)
        np.testing.assert_allclose(np.asarray(out), plain, atol=1e-4)

    check()


def test_repair_correction_empty_dropset_is_zero():
    out = secure_agg.repair_correction(32, "a", [], b"s")
    np.testing.assert_array_equal(np.asarray(out), np.zeros(32, np.float32))


# ---------------------------------------------------------------------------
# kernel: fused corrected combine vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,t", [(4, 1000), (3, 5000), (2, 127), (7, 513)])
def test_masked_sum_corrected_kernel_matches_ref(n, t):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (n, t), jnp.float32)
    c = jax.random.normal(ks[1], (n, t), jnp.float32)
    w = jax.nn.softmax(jax.random.normal(ks[2], (n,)))
    out = masked_sum_corrected_flat(x, c, w, interpret=True)
    ref = masked_sum_corrected_ref(x, c, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_masked_sum_corrected_op_fallback_matches_kernel():
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 700), jnp.float32)
    c = jax.random.normal(jax.random.PRNGKey(2), (5, 700), jnp.float32)
    w = jnp.full((5,), 0.2)
    np.testing.assert_allclose(
        np.asarray(masked_sum_corrected(x, c, w)),     # oracle off TPU
        np.asarray(masked_sum_corrected_flat(x, c, w, interpret=True)),
        atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# end to end: consortium runs that lose clients
# ---------------------------------------------------------------------------
def _run(orgs, decisions, drop_at=None, seed=0):
    con = Consortium(orgs, seed=seed)
    base = {"arch": "fedforecast-100m", "rounds": 1, "local_steps": 1,
            "batch_size": 2, "lr": 1e-3, "data_schema": None,
            "round_deadline_ticks": 3}
    base.update(decisions)
    contract = con.negotiate(base)
    job = con.server.job_creator.from_contract(contract)
    ds = make_silo_datasets(len(orgs), vocab=512, seq_len=32, seed=seed)
    run_id = con.start(job, ds)
    phase = con.run_to_completion(drop_at=drop_at)
    return con, run_id, phase


FIVE = ["a", "b", "c", "d", "e"]


def test_masked_dropout_mid_collect_completes_and_matches_plain():
    """Acceptance: a masked round with 1 of 5 clients dropped completes,
    and its aggregate matches the plain (unmasked) weighted FedAvg of the
    4 survivors to <= 1e-4 — asserted by running a deterministic twin
    consortium with secure aggregation off and the same dropout."""
    drop = {"c": ("collect", 0)}
    con_s, _, phase_s = _run(FIVE, {"secure_aggregation": True},
                             drop_at=dict(drop))
    con_p, _, phase_p = _run(FIVE, {"secure_aggregation": False},
                             drop_at=dict(drop))
    assert phase_s == "done" and phase_p == "done"
    dropped_cid = con_s.client_ids["c"]
    assert con_s.server.run.dropped == [dropped_cid]
    assert len(con_s.server.run.cohort) == 4
    # the repair round ran and was traced
    repairs = [r for r in con_s.server.metadata.query(kind="provenance")
               if r["operation"] == "publish_dropout"]
    assert len(repairs) == 1
    # masked aggregate == plain twin aggregate (same seeds, same dropout)
    g_s = con_s.server.store.get(con_s.server.run.history[-1]["digest"])
    g_p = con_p.server.store.get(con_p.server.run.history[-1]["digest"])
    err = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
              for a, b in zip(jax.tree.leaves(g_s), jax.tree.leaves(g_p)))
    assert err <= 1e-4, f"repaired masked aggregate off by {err}"


def test_unmasked_dropout_mid_collect_shrinks_cohort():
    con, run_id, phase = _run(
        ["w", "x", "y"], {"secure_aggregation": False, "rounds": 2},
        drop_at={"x": ("collect", 0)})
    assert phase == "done"
    assert con.server.run.dropped == [con.client_ids["x"]]
    assert len(con.server.run.history) == 2       # both rounds completed
    drops = [r for r in con.server.metadata.query(kind="provenance")
             if r["operation"] == "client_dropped"]
    assert [d["subject"] for d in drops] == [con.client_ids["x"]]


def test_masked_dropout_during_evaluate():
    """A client that vanishes after posting its update but before its
    eval: no mask repair needed, eval proceeds over survivors, and the
    next masked round runs on the shrunk cohort."""
    con, run_id, phase = _run(
        ["p", "q", "r"], {"secure_aggregation": True, "rounds": 2},
        drop_at={"q": ("evaluate", 0)})
    assert phase == "done"
    assert con.server.run.dropped == [con.client_ids["q"]]
    assert len(con.server.run.history) == 2
    # no repair round: the dropped client's update was already aggregated
    assert not [r for r in con.server.metadata.query(kind="provenance")
                if r["operation"] == "publish_dropout"]
    # round 1's cohort (published with the global) excludes the dropped
    glob1 = con.nodes[0].comm.fetch(f"runs/{run_id}/round/0/1/global",
                                    broadcast=True)
    assert con.client_ids["q"] not in glob1["cohort"]
    assert len(glob1["cohort"]) == 2


def test_cohort_below_min_cohort_pauses_with_provenance():
    con, run_id, phase = _run(
        ["w", "x", "y"], {"secure_aggregation": True, "min_cohort": 3},
        drop_at={"y": ("collect", 0)})
    assert phase == "paused"
    assert "min_cohort" in con.server.run.pause_reason
    pauses = [r for r in con.server.metadata.query(kind="provenance")
              if r["operation"] == "pause_run" and r["outcome"] == "paused"]
    assert pauses and con.client_ids["y"] in pauses[0]["details"]["dropped"]
    # clients were notified through the status resource
    assert any("paused" in n for node in con.nodes
               for n in node.notifications)


def test_admin_resume_after_dropout_pause_reruns_round():
    """Resuming a dropout-paused run re-runs the interrupted round with
    the surviving cohort: stale updates (masked against the old cohort)
    are cleared and clients retrain, so no repair round is needed."""
    con, run_id, phase = _run(
        ["w", "x", "y"], {"secure_aggregation": True, "min_cohort": 3},
        drop_at={"y": ("collect", 0)})
    assert phase == "paused"
    con.server.admin_resume("server-admin")
    phase = con.run_to_completion(drop_at={"y": 0})   # y stays gone
    assert phase == "done"
    assert len(con.server.run.history) == 1
    assert np.isfinite(con.server.run.history[0]["mean_eval_loss"])
    # the re-run collected fresh survivor updates — no mask repair
    assert not [r for r in con.server.metadata.query(kind="provenance")
                if r["operation"] == "publish_dropout"]


def test_admin_resume_after_evaluate_pause_does_not_reaggregate():
    """A pause during evaluate hits *after* the round's aggregate was
    committed: resume must continue into evaluate, not re-run (and
    double-apply) the round."""
    con, run_id, phase = _run(
        ["w", "x", "y"], {"secure_aggregation": True, "min_cohort": 3},
        drop_at={"y": ("evaluate", 0)})
    assert phase == "paused"
    assert len(con.server.run.history) == 1       # aggregate committed
    digest = con.server.run.history[0]["digest"]
    con.server.admin_resume("server-admin")
    assert con.server.run.phase == "evaluate"
    phase = con.run_to_completion(drop_at={"y": 0})
    assert phase == "done"
    hist = con.server.run.history
    assert [h["round"] for h in hist] == [0]      # no duplicate round
    assert hist[0]["digest"] == digest            # not re-aggregated
    assert np.isfinite(hist[0]["mean_eval_loss"])


def test_weighted_masked_fedavg_with_small_silo_matches_plain():
    """A silo declaring fewer examples than the round budget carries a
    weight < 1 end to end: the masked pre-scaled aggregate must match the
    plain weighted-FedAvg twin run, dropout included."""
    def build(secure):
        con = Consortium(FIVE[:3], seed=0)
        contract = con.negotiate({
            "arch": "fedforecast-100m", "rounds": 1, "local_steps": 2,
            "batch_size": 2, "lr": 1e-3, "data_schema": None,
            "secure_aggregation": secure, "round_deadline_ticks": 3})
        job = con.server.job_creator.from_contract(contract)
        ds = make_silo_datasets(3, vocab=512, seq_len=32, seed=0)
        ds[0].n_examples = 1                  # tiny silo: weight 1/4
        con.start(job, ds)
        phase = con.run_to_completion(drop_at={FIVE[2]: ("collect", 0)})
        assert phase == "done"
        return con
    con_s, con_p = build(True), build(False)
    assert con_s.server.run.dropped == [con_s.client_ids[FIVE[2]]]
    g_s = con_s.server.store.get(con_s.server.run.history[-1]["digest"])
    g_p = con_p.server.store.get(con_p.server.run.history[-1]["digest"])
    err = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
              for a, b in zip(jax.tree.leaves(g_s), jax.tree.leaves(g_p)))
    assert err <= 1e-4, f"weighted masked aggregate off by {err}"


def test_no_deadline_means_no_dropout_handling():
    """round_deadline_ticks=0 preserves the old wait-forever contract."""
    con, run_id, phase = _run(["a", "b"], {"round_deadline_ticks": 0,
                                           "secure_aggregation": True})
    assert phase == "done"
    assert con.server.run.dropped == []
