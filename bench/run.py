"""FL-APU benchmark: one run of one cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout (``BENCHMARK.json`` beside ``bench/``
and the program under ``src/``). The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: every number compared with the reference, beside its limit.
The same numbers are the last lines of standard error.

Without a TPU, with fewer chips than the cell asks for, or without the
program beside the benchmark, the run exits non-zero and prints no
result. JAX's persistent compilation cache lives in ``.jax_cache/`` at
the root of the checkout unless ``JAX_COMPILATION_CACHE_DIR`` says
otherwise.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def use_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program of the round, however small, is found again next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    from fedbench import device, harness, spec

    cell = spec.load(ROOT, args.workload)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"bench: the program is not beside the benchmark ({src})")
    sys.path.insert(0, src)
    use_compile_cache()
    devices = device.require_accelerator(cell.chips)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices=devices, t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
