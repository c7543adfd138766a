"""Benchmark harness: one section per paper table/figure + substrate micro-
benches. Prints ``name,us_per_call,derived`` CSV (spec format).

Sections:
  saam.*         — the paper's own evaluation (Tables I+II) executed live
  aggregation.*  — Model Aggregator strategies (paper §V)
  secure_agg.*   — §VII privacy path (masking + fused kernel)
  communicator.* — §V Communicator (pack/encrypt/decrypt)
  kernels.*      — Pallas kernels (interpret mode on CPU, compiled on TPU)
  fl_round.*     — end-to-end round: control-plane overhead
  roofline.*     — dry-run roofline summaries (if artifacts exist)
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    rows = []

    from benchmarks.saam_coverage import run_saam
    saam = run_saam(verbose=False)
    n_ok = sum(r["ok"] for r in saam)
    rows.append(("saam.tasks_pass", float(n_ok), f"of {len(saam)} "
                 "(paper SVIII: all 40 are direct tasks)"))

    from benchmarks import bench_core
    bench_core.bench_aggregation(rows)
    bench_core.bench_secure_masking(rows)
    bench_core.bench_masked_round(rows)
    bench_core.bench_dropout_round(rows)
    bench_core.bench_communicator(rows)
    bench_core.bench_kernels(rows)
    bench_core.bench_fl_round(rows)

    try:
        from benchmarks import roofline
        roofline.summarize(rows)
    except Exception as e:  # noqa: BLE001 — artifacts may not exist yet
        rows.append(("roofline.skipped", 0.0, repr(e)))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
