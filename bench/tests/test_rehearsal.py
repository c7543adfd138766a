"""The harness end to end on the CPU, at a tiny size that is never a cell.

``JAX_PLATFORMS=cpu``; the tiny configurations are the program's reduced
fedforecast-100m (2 layers, d_model 256, vocab 512, float32), the
traffic the cells' own mixes with 2 local steps. Each run skips only the
harness's look for a chip. A sound run is correct; a run with the timed
path broken underneath is not, once for each fault the cells can have;
the control reads above the limits. The command line refuses to run
without a chip, and without the program beside it.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
HERE = os.path.join(BENCH, "tests")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from fedbench import checks, harness, spec  # noqa: E402

SEED = 2**31 + 12345


def tiny_cell(config: str, traffic: str) -> spec.Cell:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(HERE, config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, traffic + ".json")) as f:
        trf = json.load(f)
    return spec.Cell(name=f"{config}.{traffic}", config=cfg, traffic=trf,
                     chips=1, end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])


def run(cell, trace=False, seconds=0.0):
    import jax
    return harness.run_cell(cell, SEED, seconds, trace,
                            devices=jax.devices(), t_start=time.perf_counter())


TAIL = ("tiny-sec-f32", "tiny-tail")
UPLOAD = ("tiny-sec-int8", "tiny-upload")


@pytest.mark.parametrize("cell", [TAIL, UPLOAD], ids=["tail", "upload"])
def test_sound_run_is_correct(cell):
    out = run(tiny_cell(*cell))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"update_s", "setup_s"}
    assert out["checks"]["window_compiles"]["value"] == 0
    assert list(out)[-1] == "checks"


def test_traced_run_reports_span_metrics():
    out = run(tiny_cell(*UPLOAD), trace=True)
    assert out["correct"], out["checks"]
    assert {"wire_s", "encode_s", "train_s"} <= set(out["metrics"])
    # no device plane on the CPU: the device's metrics stay silent
    assert "idle_frac" not in out["metrics"]
    assert "breakdown" in out and "window_s" in out["device"]


def _unchanged_global(monkeypatch):
    from repro.optim import OUTER_REGISTRY
    from repro.optim.outer import OuterOptimizer
    keep = OuterOptimizer("fedavg", lambda p: {},
                          lambda g, agg, st: (g, st))
    monkeypatch.setitem(OUTER_REGISTRY, "fedavg", lambda: keep)


def _half_batch(monkeypatch):
    from repro.core.client import FLClientNode
    full = FLClientNode._batch_from

    def half(self, dataset):
        batch = full(self, dataset)
        return {k: v[: v.shape[0] // 2] for k, v in batch.items()}
    monkeypatch.setattr(FLClientNode, "_batch_from", half)


def _altered_update(monkeypatch):
    import jax
    from repro.core.client import FLClientNode
    fit = FLClientNode._fit

    def altered(self, dataset, base, lr):
        params, loss, n = fit(self, dataset, base, lr)
        if self.client_id == min(self.cohort):
            params = jax.tree.map(lambda p, b: b + 2.0 * (p - b),
                                  params, base)
        return params, loss, n
    monkeypatch.setattr(FLClientNode, "_fit", altered)


FAULTS = {"unchanged": _unchanged_global, "half_batch": _half_batch,
          "altered_update": _altered_update}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", [TAIL, UPLOAD], ids=["tail", "upload"])
def test_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    out = run(tiny_cell(*cell))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", [TAIL, UPLOAD], ids=["tail", "upload"])
def test_control_fails_the_limits(cell):
    import control
    c = tiny_cell(*cell)
    rows = control.readings(c, SEED)
    assert [r["departure"] for r in rows] == [
        c.config["control"], "half_batch", "altered_update", "unchanged"]
    for row in rows:
        verdict = checks.judge({k: row[k] for k in checks.NUMBERS},
                               c.limits())
        assert not checks.passed(verdict), row


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ff100m-sec-f32.tail",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_without_a_chip_prints_no_result():
    proc = _cli(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_cli_without_the_program_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
