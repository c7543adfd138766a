"""The trace reduction and the FLOP and byte counters, on a synthetic
trace (CPU; no profiler runs)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fedbench import flops  # noqa: E402
from fedbench import trace as tr  # noqa: E402

D0, D1 = "/device:TPU:0", "/device:TPU:1"


def ev(device, line, name, t0, t1):
    return tr.Event(device, line, name, t0, t1)


@pytest.fixture
def events():
    return [
        # device 0: two overlapping ops, a gap, one op past the window
        ev(D0, "ops", "fusion.1", 1.0, 3.0),
        ev(D0, "ops", "fusion.2", 2.0, 4.0),
        ev(D0, "ops", "copy.3", 6.0, 7.0),
        ev(D0, "ops", "fusion.1", 9.0, 12.0),
        ev(D0, "modules", "jit_masked_sum(7)", 6.0, 7.0),
        ev(D0, "modules", "jit_masked_sum_corrected(8)", 7.0, 8.0),
        ev(D0, "modules", "jit_train_step(3)", 1.0, 4.0),
        # device 1: busy 2 s inside the window
        ev(D1, "ops", "fusion.1", 0.0, 2.0),
    ]


def test_union_merges_and_clips():
    assert tr.union([(1, 3), (2, 4), (6, 7), (-1, 0.5)], 0, 6.5) == [
        (0, 0.5), (1, 4), (6, 6.5)]


def test_busy_is_union_averaged_over_devices(events):
    # device 0 inside [0, 10]: [1, 4] + [6, 7] + [9, 10] = 5 s; device 1: 2 s
    assert tr.busy_s(events, 0.0, 10.0) == pytest.approx(3.5)
    assert tr.devices(events) == [D0, D1]


def test_module_time_by_stable_name(events):
    assert tr.module_s(events, "jit_masked_sum", 0.0, 10.0) == pytest.approx(1.0)
    assert tr.module_s(events, "jit_masked_sum", 6.5, 10.0) == pytest.approx(0.5)
    assert tr.module_s(events, "jit_nothing", 0.0, 10.0) == 0.0


def test_top_ops_sum_by_name_inside_window(events):
    top = tr.top_ops(events, 0.0, 10.0)
    assert top[0][0] == "fusion.1"
    assert top[0][1] == pytest.approx(2 + 2 + 1)     # both devices, clipped
    assert [n for n, _ in top] == ["fusion.1", "fusion.2", "copy.3"]


def test_idle_gaps_named_by_innermost_span(events):
    spans = [("phase:collect", 0.0, 10.0), ("client.post", 4.0, 6.0)]
    gaps = tr.idle_gaps(events, 0.0, 10.0, spans)
    # gaps on device 0: [0, 1], [4, 6], [7, 9]; longest first
    assert [round(s, 6) for _, s in gaps] == [2.0, 2.0, 1.0]
    assert ["client.post", 2.0] in [[n, round(s, 6)] for n, s in gaps]
    assert gaps[-1][0] == "phase:collect"
    assert tr.idle_gaps([], 0.0, 1.0, spans) == []


MODEL = {"n_layers": 12, "d_model": 768, "n_heads": 12, "n_kv_heads": 12,
         "head_dim": 64, "d_ff": 3072, "vocab": 4096}


def test_matmul_params_of_fedforecast():
    # 12 x (4 x 768^2 + 3 x 768 x 3072) + 768 x 4096 (tied output)
    assert flops.matmul_params(MODEL) == 116_391_936


def test_train_step_flops_of_fedforecast():
    per_token = 6 * 116_391_936 + 12 * 12 * 12 * 64 * 512
    assert flops.train_step_flops(MODEL, 8, 512) == pytest.approx(
        8 * 512 * per_token)
    assert flops.forward_flops(MODEL, 8, 512) == pytest.approx(
        flops.train_step_flops(MODEL, 8, 512) / 3)


def test_masked_sum_bytes():
    assert flops.masked_sum_bytes(3, 116_411_136) == 4 * 116_411_136 * 4


def test_short_name_drops_hlo_text():
    assert tr.short_name("%fusion.12 = bf16[8]{0} fusion(%p), kind=kLoop") == "fusion.12"
    assert tr.short_name("jit_masked_sum(123)") == "jit_masked_sum(123)"
