"""Micro-benchmarks for the FL-APU control/data plane components."""
from __future__ import annotations

import hashlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _env import force_host_devices  # noqa: E402

force_host_devices()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

_REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _time_us(fn, *args, n=20, warmup=2, **kw):
    for _ in range(warmup):
        fn(*args, **kw)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kw)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return (time.perf_counter() - t0) / n * 1e6


def _tree(n_leaves=8, size=50_000, seed=0):
    rng = np.random.default_rng(seed)
    return {f"w{i}": rng.normal(size=(size,)).astype(np.float32)
            for i in range(n_leaves)}


def bench_aggregation(rows):
    from repro.core.aggregation import coordinate_median, fedavg, trimmed_mean
    ups = [_tree(seed=i) for i in range(4)]
    n_floats = sum(l.size for l in jax.tree.leaves(ups[0]))
    us = _time_us(lambda: jax.block_until_ready(fedavg(ups)), n=5)
    rows.append(("aggregation.fedavg_4x400k", us,
                 f"{n_floats*4/us:.0f} floats/us"))
    us = _time_us(lambda: jax.block_until_ready(trimmed_mean(ups, trim=1)),
                  n=5)
    rows.append(("aggregation.trimmed_mean_4x400k", us, ""))
    us = _time_us(lambda: jax.block_until_ready(coordinate_median(ups)), n=5)
    rows.append(("aggregation.median_4x400k", us, ""))


def bench_secure_masking(rows):
    from repro.core import secure_agg
    cohort = [f"c{i}" for i in range(4)]
    u = _tree(n_leaves=4, size=50_000)
    us = _time_us(secure_agg.mask_update, u, "c0", cohort, b"s", n=5)
    rows.append(("secure_agg.mask_update_200k_4clients", us, ""))
    masked = [secure_agg.mask_update(u, c, cohort, b"s") for c in cohort]
    us = _time_us(secure_agg.aggregate_masked, masked, n=5)
    rows.append(("secure_agg.aggregate_masked", us, "masks cancel"))


# ---------------------------------------------------------------------------
# masked-round benchmark: packed data plane vs the seed numpy masking
# ---------------------------------------------------------------------------
def _seed_mask_update_numpy(update, client_id, cohort, pair_secret,
                            scale=1e-2):
    """Frozen copy of the pre-packed-plane implementation (per-leaf,
    per-pair numpy loop) — kept here as the benchmark baseline only."""
    leaves, treedef = jax.tree_util.tree_flatten(update)
    masked = []
    for idx, leaf in enumerate(leaves):
        arr = np.asarray(leaf, np.float32).copy()
        for other in cohort:
            if other == client_id:
                continue
            lo, hi = sorted([client_id, other])
            h = hashlib.sha256(
                pair_secret + f"{lo}|{hi}|{idx}".encode()).digest()
            rng = np.random.default_rng(int.from_bytes(h[:8], "little"))
            mask = rng.standard_normal(arr.shape).astype(np.float32) * scale
            sign = 1.0 if client_id < other else -1.0
            arr += sign * mask
        masked.append(arr)
    return jax.tree_util.tree_unflatten(treedef, masked)


def _time_s(fn, *args, n=1, warmup=1, **kw):
    for _ in range(warmup):
        out = fn(*args, **kw)
        if hasattr(out, "block_until_ready"):
            out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kw)
        if hasattr(out, "block_until_ready"):
            out.block_until_ready()
    return (time.perf_counter() - t0) / n


def bench_masked_round(rows, *, n_params=10_000_000,
                       cohorts=(4, 16, 64), seed_baseline_cohort=16,
                       stream_cohorts=(64, 128, 256), write_json=True):
    """Packed secure-agg data plane at >=10M params, cohorts 4/16/64.

    Per cohort: one client's full-buffer masking pass (the client hot path,
    cost ~ (cohort-1) PRG draws over the buffer) and the server-side
    (N, T) -> (T,) reduction through the kernel ops path. The seed numpy
    masking is replayed once at ``seed_baseline_cohort`` for the speedup
    record written to BENCH_secure_agg.json.

    The streaming section then folds ``stream_cohorts`` (up to 256)
    through the O(T) accumulator sinks — single-device and, when >=2 JAX
    devices are visible, T-axis mesh-sharded — recording aggregate wall
    time, the peak accumulator working set (flat in cohort size by
    construction) and the streamed-vs-stacked parity error. The stacked
    path cannot even run at cohort 256 x 10M params (10GB materialized);
    the stream path never holds more than batch+1 rows.
    """
    from repro.core import secure_agg, streaming
    from repro.sharding.agg import agg_mesh

    if seed_baseline_cohort not in cohorts:
        raise ValueError(
            f"seed_baseline_cohort {seed_baseline_cohort} must be one of "
            f"cohorts {cohorts} (the speedup compares like for like)")
    report = {"model_params": n_params, "cohorts": {},
              "seed_baseline": {}, "notes": {
                  "mask_s": "one client masking one packed buffer",
                  "aggregate_s": "server (N,T)->(T,) reduction, "
                                 "kernel ops path (jnp oracle off the "
                                 "TPU)",
                  "stream_aggregate_s": "same reduction through the "
                                        "streaming sink (fold-on-arrival, "
                                        "O(T) accumulator), full fold "
                                        "loop + finalize",
                  "peak_accumulator_bytes": "sink working-set high-water "
                                            "mark: accumulator + staged "
                                            "rows; flat in cohort size"}}
    rng = np.random.default_rng(0)
    buf = rng.standard_normal(n_params, dtype=np.float32)

    # --- seed baseline: per-leaf per-pair numpy loops, 10 equal leaves ---
    cohort = [f"c{i:02d}" for i in range(seed_baseline_cohort)]
    leaf = max(1, n_params // 10)
    tree = {f"w{i}": buf[i * leaf:(i + 1) * leaf].copy()
            for i in range(10)}
    t_seed = _time_s(_seed_mask_update_numpy, tree, cohort[0], cohort,
                     b"bench", n=1, warmup=0)
    report["seed_baseline"] = {"cohort": seed_baseline_cohort,
                               "numpy_mask_update_s": t_seed}
    rows.append((f"secure_agg.seed_numpy_mask_10M_c{seed_baseline_cohort}",
                 t_seed * 1e6, "pre-packed-plane baseline"))

    for c in cohorts:
        cohort = [f"c{i:02d}" for i in range(c)]
        jbuf = jnp.asarray(buf)
        t_mask = _time_s(
            secure_agg.mask_packed, jbuf, cohort[0], cohort, b"bench", n=1)
        # aggregation timing: values don't affect cost, random rows
        # suffice; f32 draws avoid a transient (c, T) f64 (5GB at c=64)
        stacked = jnp.asarray(
            rng.standard_normal((c, n_params), dtype=np.float32))
        t_agg = _time_s(secure_agg.aggregate_masked_packed, stacked, n=1)
        del stacked
        report["cohorts"][str(c)] = {"mask_s": t_mask, "aggregate_s": t_agg}
        rows.append((f"secure_agg.packed_mask_10M_c{c}", t_mask * 1e6, ""))
        rows.append((f"secure_agg.packed_aggregate_10M_c{c}", t_agg * 1e6,
                     ""))

    # --- telescoping sanity at cohort 4 on the full 10M buffer ----------
    cohort4 = [f"c{i}" for i in range(4)]
    masked = [np.asarray(secure_agg.mask_packed(jnp.asarray(buf), cid,
                                                cohort4, b"bench"))
              for cid in cohort4]
    agg = np.asarray(secure_agg.aggregate_masked_packed(np.stack(masked)))
    err = float(np.abs(agg - buf).max())
    report["telescoping_max_abs_err_cohort4"] = err
    assert err < 1e-4, f"masks failed to cancel: {err}"

    base_mask = report["cohorts"][str(seed_baseline_cohort)]["mask_s"]
    report["speedup_vs_seed_numpy_cohort16"] = t_seed / base_mask
    rows.append(("secure_agg.packed_vs_seed_speedup_c16",
                 t_seed / base_mask, "x faster (mask path)"))

    # --- streaming accumulation: O(T) memory, cohorts up to 256 ---------
    pool_n = streaming.DEFAULT_STREAM_BATCH
    pool = [rng.standard_normal(n_params, dtype=np.float32)
            for _ in range(pool_n)]
    modes = {"1dev": None}
    mesh = agg_mesh()
    if mesh is not None:
        modes["mesh"] = mesh
    report["streaming"] = {"batch": pool_n,
                           "devices": len(jax.devices()), "modes": {}}
    for mode, m in modes.items():
        per = {}
        for c in stream_cohorts:
            # warmup compiles the flush/finalize shapes for this mode
            wsink = streaming.MaskedF32Sink(n_params, batch=pool_n, mesh=m)
            for i in range(min(c, 2 * pool_n)):
                wsink.fold(pool[i % pool_n])
            wsink.finalize()
            sink = streaming.MaskedF32Sink(n_params, batch=pool_n, mesh=m)
            t0 = time.perf_counter()
            for i in range(c):
                sink.fold(pool[i % pool_n])
            sink.finalize()
            t = time.perf_counter() - t0
            per[str(c)] = {"stream_aggregate_s": t,
                           "peak_accumulator_bytes": sink.peak_bytes,
                           "fold_batches": sink.fold_batches}
            rows.append((f"secure_agg.stream_aggregate_c{c}_{mode}",
                         t * 1e6,
                         f"peak {sink.peak_bytes / 1e6:.0f}MB, "
                         f"{sink.fold_batches} flushes"))
        entry = {"cohorts": per}
        cs = sorted(int(k) for k in per)
        if len(cs) >= 2:
            ts = [per[str(k)]["stream_aggregate_s"] for k in cs]
            entry["scaling_exponent"] = float(
                np.polyfit(np.log(cs), np.log(ts), 1)[0])
        # parity vs the stacked kernel path at a size both can afford
        tpar = min(n_params, 1_000_000)
        cpar = min(stream_cohorts)
        pbufs = [p[:tpar] for p in pool][: max(2, min(cpar, pool_n))]
        ref = np.asarray(
            secure_agg.aggregate_masked_packed(np.stack(pbufs)))
        got = streaming.stream_masked_packed(pbufs, batch=3, mesh=m)
        entry["stream_vs_stacked_max_abs_err"] = float(
            np.abs(got - ref).max())
        report["streaming"]["modes"][mode] = entry
    e1 = report["streaming"]["modes"]["1dev"].get("scaling_exponent")
    if e1 is not None:
        report["stream_scaling_exponent_1dev"] = e1
        rows.append(("secure_agg.stream_scaling_exponent_1dev", e1,
                     "log-log slope over stream cohorts (1.0 = linear)"))
    if write_json:
        path = os.path.join(_REPO_ROOT, "BENCH_secure_agg.json")
        with open(path, "w") as f:
            json.dump(report, f, indent=2)
    return report


# ---------------------------------------------------------------------------
# dropout-round benchmark: mask-repair cost vs cohort size
# ---------------------------------------------------------------------------
def bench_dropout_round(rows, *, n_params=5_000_000, cohorts=(4, 16, 64),
                        n_dropped=1, write_json=True):
    """Cost of surviving a dropout in a masked round (BENCH_dropout.json).

    Per cohort size: one survivor's correction derivation (client hot
    path, cost ~ n_dropped PRG draws over the buffer), the server's
    corrected (S, T) -> (T,) reduction through the fused kernel path, and
    the plain no-dropout reduction as the baseline the repair overhead is
    measured against. Ends with a bit-exactness check: the repaired
    survivor mean must match the plain survivor mean.

    The streaming fields separate two honest numbers the stacked path
    conflates. *Total work* for a repaired round is ~2x plain — an
    information bound, corrections double the bytes folded. But the
    protocol folds updates AND corrections on arrival, during the window
    it is already waiting on the board, so the round-latency cost of
    repair is the *commit path* only: the partial-batch flush + finalize
    after the last arrival. ``stream_repair_overhead_x`` gates that
    commit-path ratio (~1x, vs >5x for the stacked rebuild).
    """
    from repro.core import secure_agg, streaming

    report = {"model_params": n_params, "n_dropped": n_dropped,
              "cohorts": {}, "notes": {
                  "correction_s": "one survivor deriving its packed "
                                  "correction against the dropped peers",
                  "aggregate_repaired_s": "(S, T) corrected reduction, "
                                          "kernel ops path",
                  "aggregate_plain_s": "no-dropout (S, T) reduction "
                                       "baseline",
                  "stream_aggregate_*_s": "streaming sink total work: "
                                          "every fold + finalize "
                                          "(repaired folds 2x the bytes "
                                          "— information bound)",
                  "stream_commit_*_s": "commit-path latency only: "
                                       "partial flush + finalize after "
                                       "the last on-arrival fold",
                  "stream_repair_overhead_x": "commit repaired / commit "
                                              "plain — what a round "
                                              "actually pays for repair "
                                              "under fold-on-arrival"}}
    rng = np.random.default_rng(0)
    buf = rng.standard_normal(n_params, dtype=np.float32)
    for c in cohorts:
        cohort = [f"c{i:02d}" for i in range(c)]
        dropped = cohort[c - n_dropped:]
        survivors = cohort[:c - n_dropped]
        t_corr = _time_s(secure_agg.repair_correction, n_params,
                         survivors[0], dropped, b"bench", n=1)
        stacked = jnp.asarray(rng.standard_normal(
            (len(survivors), n_params), dtype=np.float32))
        corrs = jnp.asarray(rng.standard_normal(
            (len(survivors), n_params), dtype=np.float32))
        t_plain = _time_s(secure_agg.aggregate_masked_packed, stacked, n=1)
        t_rep = _time_s(lambda: secure_agg.aggregate_masked_packed(
            stacked, corrections=corrs), n=1)
        del stacked, corrs
        report["cohorts"][str(c)] = {
            "correction_s": t_corr, "aggregate_repaired_s": t_rep,
            "aggregate_plain_s": t_plain,
            "repair_overhead_x": t_rep / max(t_plain, 1e-12)}
        rows.append((f"secure_agg.repair_correction_c{c}", t_corr * 1e6,
                     f"{n_dropped} dropped"))
        rows.append((f"secure_agg.repaired_aggregate_c{c}", t_rep * 1e6,
                     f"{t_rep / max(t_plain, 1e-12):.2f}x plain"))

        # --- streaming: total work vs commit-path latency ---------------
        s = len(survivors)
        pool_n = streaming.DEFAULT_STREAM_BATCH
        spool = [rng.standard_normal(n_params, dtype=np.float32)
                 for _ in range(pool_n)]

        def fold_all(repaired, s=s):
            sink = streaming.MaskedF32Sink(n_params, batch=pool_n,
                                           mesh=None)
            for i in range(s):
                sink.fold(spool[i % pool_n])
            if repaired:
                for i in range(s):
                    sink.fold_correction(spool[(i + 3) % pool_n])
            return sink

        fold_all(False).finalize()           # warmup: plain flush shapes
        fold_all(True).finalize()            # warmup: repaired tail shape
        t0 = time.perf_counter()
        fold_all(False).finalize()
        t_sp = time.perf_counter() - t0
        t0 = time.perf_counter()
        fold_all(True).finalize()
        t_sr = time.perf_counter() - t0

        def commit(repaired):
            sink = fold_all(repaired)        # on-arrival folds, untimed
            t0 = time.perf_counter()
            sink.finalize()
            return time.perf_counter() - t0

        commit(False), commit(True)          # warmup partial-flush shapes
        t_cp = commit(False)
        t_cr = commit(True)
        report["cohorts"][str(c)].update({
            "stream_aggregate_plain_s": t_sp,
            "stream_aggregate_repaired_s": t_sr,
            "stream_total_repair_overhead_x": t_sr / max(t_sp, 1e-12),
            "stream_commit_plain_s": t_cp,
            "stream_commit_repaired_s": t_cr,
            "stream_repair_overhead_x": t_cr / max(t_cp, 1e-12)})
        rows.append((f"secure_agg.stream_commit_repaired_c{c}",
                     t_cr * 1e6,
                     f"{t_cr / max(t_cp, 1e-12):.2f}x plain commit "
                     f"({t_sr / max(t_sp, 1e-12):.2f}x total work)"))

    if "64" in report["cohorts"]:
        report["stream_repair_overhead_x_cohort64"] = \
            report["cohorts"]["64"]["stream_repair_overhead_x"]

    # --- repaired telescoping sanity: small cohort, real masks ----------
    t = min(n_params, 100_000)
    cohort = [f"c{i}" for i in range(5)]
    small = buf[:t]
    masked = [np.asarray(secure_agg.mask_packed(jnp.asarray(small), cid,
                                                cohort, b"bench"))
              for cid in cohort]
    surv = cohort[:4]
    corrs = np.stack([np.asarray(secure_agg.repair_correction(
        t, cid, cohort[4:], b"bench")) for cid in surv])
    agg = np.asarray(secure_agg.aggregate_masked_packed(
        np.stack(masked[:4]), corrections=corrs))
    err = float(np.abs(agg - small).max())
    report["repair_max_abs_err_1of5"] = err
    assert err < 1e-4, f"mask repair failed to cancel: {err}"
    if write_json:
        path = os.path.join(_REPO_ROOT, "BENCH_dropout.json")
        with open(path, "w") as f:
            json.dump(report, f, indent=2)
    return report


def bench_communicator(rows):
    from repro.core import crypto
    from repro.core.serialization import pack
    tree = _tree(n_leaves=4, size=50_000)
    key = crypto.derive_key(b"m" * 32, "bench")
    blob = pack(tree)
    us_p = _time_us(pack, tree, n=10)
    enc = crypto.encrypt(key, blob)
    us_e = _time_us(crypto.encrypt, key, blob, n=5)
    us_d = _time_us(crypto.decrypt, key, enc, n=5)
    rows.append(("communicator.pack_800KB", us_p,
                 f"{len(blob)/1e3:.0f}KB"))
    rows.append(("communicator.encrypt", us_e,
                 f"ratio={len(enc)/len(blob):.2f}"))
    rows.append(("communicator.decrypt+verify", us_d, ""))
    # auto-compression on a masked-update-sized incompressible payload:
    # the probe skips zlib entirely instead of grinding level 1 over
    # near-random fp32 bytes for ~1% savings
    weights = np.random.default_rng(0).standard_normal(
        2 ** 21).astype(np.float32).tobytes()          # 8MB, incompressible
    us_forced = _time_us(crypto.encrypt, key, weights, n=3,
                         compress=True)
    us_auto = _time_us(crypto.encrypt, key, weights, n=3)
    rows.append(("communicator.encrypt_8MB_fp32_forced_zlib", us_forced, ""))
    rows.append(("communicator.encrypt_8MB_fp32_auto", us_auto,
                 f"{us_forced / us_auto:.1f}x faster (probe skips zlib)"))


def bench_kernels(rows):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.secure_agg.ops import secure_agg_combine
    from repro.kernels.ssd_scan.ops import ssd_scan
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    us = _time_us(flash_attention, q, k, v, n=3)
    rows.append(("kernels.flash_attention_256_interpret", us,
                 "interpret=True (CPU oracle mode)"))
    x = jax.random.normal(ks[0], (1, 128, 4, 16))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 128, 4))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (4,)) * 0.3)
    B = jax.random.normal(ks[3], (1, 128, 16))
    C = jax.random.normal(ks[4], (1, 128, 16))
    us = _time_us(lambda: jax.block_until_ready(
        ssd_scan(x, dt, A, B, C, chunk=32)[0]), n=3)
    rows.append(("kernels.ssd_scan_128_interpret", us, ""))
    qq = jax.random.randint(ks[0], (4, 65536), -127, 128).astype(jnp.int8)
    sc = jnp.full((4,), 1e-3)
    w = jnp.full((4,), 0.25)
    us = _time_us(secure_agg_combine, qq, sc, w, n=3)
    rows.append(("kernels.secure_agg_combine_4x64k", us,
                 "fused dequant+wsum"))


def bench_fl_round(rows):
    """Control-plane overhead: one full FL round vs bare local training."""
    from repro.core import Consortium, DataSchema
    from repro.data import make_silo_datasets
    con = Consortium(["a", "b"], seed=0)
    schema = DataSchema(vocab=512, seq_len=32)
    contract = con.negotiate({"arch": "fedforecast-100m", "rounds": 1,
                              "local_steps": 1, "batch_size": 2,
                              "data_schema": schema.to_dict()})
    job = con.server.job_creator.from_contract(contract)
    ds = make_silo_datasets(2, vocab=512, seq_len=32, seed=0)
    t0 = time.perf_counter()
    con.start(job, ds)
    phase = con.run_to_completion()
    total = time.perf_counter() - t0
    posts = con.server.board.stats["posts"]
    rows.append(("fl_round.e2e_1round_2silos", total * 1e6,
                 f"phase={phase} posts={posts} "
                 f"bytes={con.server.board.stats['bytes_posted']/1e6:.1f}MB"))


def run_smoke(rows=None):
    """Tiny-shape pass over every benchmark entry point.

    Run by CI so bench code cannot rot: exercises the same code paths as
    the real benchmarks (including the JSON report assembly and the
    repair bit-exactness assertion) at shapes that finish in seconds.
    """
    rows = [] if rows is None else rows
    bench_aggregation(rows)
    bench_secure_masking(rows)
    bench_communicator(rows)
    bench_kernels(rows)
    bench_masked_round(rows, n_params=50_000, cohorts=(4,),
                       seed_baseline_cohort=4, stream_cohorts=(4, 12),
                       write_json=False)
    bench_dropout_round(rows, n_params=50_000, cohorts=(4,),
                        write_json=False)
    bench_fl_round(rows)
    return rows


if __name__ == "__main__":
    import argparse
    sys.path.insert(0, os.path.join(_REPO_ROOT, "src"))
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shape smoke pass over all entry points")
    args = ap.parse_args()
    _rows = []
    if args.smoke:
        run_smoke(_rows)
        print("name,us_per_call,derived")
        for _name, _us, _derived in _rows:
            print(f"{_name},{_us:.1f},{_derived}")
    else:
        print(json.dumps(bench_masked_round(_rows), indent=2))
        print(json.dumps(bench_dropout_round(_rows), indent=2))
