"""Readings of the control and of the faults, for setting a cell's limits.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

For each seed, the reference is run once as stated and once for each
departure, each put in the program's place, and compared with the
sound reference by the cell's own numbers at the cell's own size:

* the control: the configuration's ``control`` variant, one precision
  below one the configuration states (``compute_below``: the matmuls;
  ``wire_int4``: the update wire);
* ``half_batch``: every local step trains on half of its batch;
* ``altered_update``: one silo's posted update is altered (its delta
  doubled) where it is produced;
* ``unchanged``: the global is never updated (reads 1 by construction);
* with ``--also``, further variants of ``fedbench.reference``.

Each row carries ``correct``: the cell's limits judged on its numbers,
as ``fedbench.checks`` judges a run.

Needs no measured window: the rounds the cell compares are run by the
reference alone, on the batches the traffic would serve. Prints one JSON
line per seed and departure; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def batches_by_round(cell, seed: int, n_rounds: int) -> list:
    """The batches the program's training draws in rounds ``0..n-1``:
    each silo draws its local steps, then its evaluation batches."""
    from fedbench.harness import reference_batches
    steps, evals = cell.traffic["local_steps"], cell.traffic["eval_batches"]
    n_silos = len(cell.config["federation"]["organizations"])
    calls = {(i, r): list(range(r * (steps + evals),
                                r * (steps + evals) + steps))
             for i in range(n_silos) for r in range(n_rounds)}
    return reference_batches(cell.config, cell.traffic, seed, calls,
                             range(n_rounds))


def rounds_compared(traffic: dict) -> int:
    """Rounds up to the one whose update the window posts first."""
    where = traffic["window_opens"]
    return where["round"] + (2 if where.get("posted") == "all" else 1)


def readings(cell, seed: int, also=()) -> list:
    """One row per departure: its numbers against the sound reference,
    and the verdict of the cell's limits on them."""
    from fedbench import checks
    from fedbench.reference import Reference
    batches = batches_by_round(cell, seed, rounds_compared(cell.traffic))
    sound = Reference(cell.config).follow(seed, batches)
    kept = checks.kept_leaves(sound["grad_norms"])
    runs = []
    for variant in (cell.config["control"], "half_batch", "altered_update",
                    *also):
        runs.append((variant, Reference(cell.config, variant).follow(
            seed, batches)))
    runs.append(("unchanged", dict(
        sound, globals=[sound["init"]] * len(sound["globals"]))))
    out = []
    for variant, got in runs:
        numbers = checks.compare(got, sound, kept)
        verdict = checks.judge(numbers, cell.limits())
        out.append({"seed": seed, "departure": variant, **numbers,
                    "correct": checks.passed(verdict)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--also", nargs="*", default=[],
                    help="further reference variants to read")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    from fedbench import device, spec
    from run import use_compile_cache
    cell = spec.load(ROOT, args.workload)
    use_compile_cache()
    devices = device.require_accelerator(cell.chips)
    for seed in args.seeds:
        for row in readings(cell, seed, args.also):
            row["device"] = device.describe(devices)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
