"""The trace-to-metric reduction, on a hand-made trace and on a small
trace recorded on one TPU v5e (``data/trace_small.json``)."""
import json
import os

import pytest

from benchlib import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e6  # ns


def _synthetic():
    # window 0..100 ms; ops busy 10-30 and 50-60 (with an overlap) on
    # device 0, 0-100 on device 1 (full); two programs.
    return {
        "devices": {
            "/device:TPU:0": {
                "ops": [[10 * MS, 15 * MS, "fusion.1"],
                        [20 * MS, 10 * MS, "fusion.2"],
                        [50 * MS, 10 * MS, "all-reduce.3"]],
                "modules": [[10 * MS, 20 * MS, "jit__step"],
                            [50 * MS, 10 * MS, "jit__step"]]},
            "/device:TPU:1": {
                "ops": [[0.0, 100 * MS, "fusion.1"]],
                "modules": [[0.0, 100 * MS, "jit__labels"]]},
        },
        "host": [[0.0, 100 * MS, "bench.window"],
                 [30 * MS, 25 * MS, "bench.job"],
                 [60 * MS, 40 * MS, "bench.drain"],
                 [200 * MS, 10 * MS, "bench.job"]],
    }


def test_busy_idle_and_programs():
    s = trace.reduce(_synthetic())
    assert s["window_s"] == pytest.approx(0.1)
    d0 = s["devices"]["/device:TPU:0"]
    assert d0["busy_s"] == pytest.approx(0.030)
    assert d0["idle_share"] == pytest.approx(0.7)
    assert s["devices"]["/device:TPU:1"]["idle_share"] == pytest.approx(0.0)
    assert s["busy_s"] == pytest.approx((0.030 + 0.1) / 2)
    assert trace.idle_share(s) == pytest.approx(0.35)
    assert trace.module_mean_s(s, "jit__step") == pytest.approx(0.015)
    assert trace.module_mean_s(s, "jit__nothing") is None


def test_gaps_named_by_host_span():
    s = trace.reduce(_synthetic())
    gaps = dict((round(sec * 1e3), name) for name, sec in s["idle_gaps"])
    assert gaps == {40: "bench.drain", 20: "bench.job", 10: "no bench span"}
    ops = dict(s["device_ops"])
    assert ops["fusion.1"] == pytest.approx((0.015 + 0.1) / 2)


def test_events_outside_the_window_are_cut():
    t = _synthetic()
    t["devices"]["/device:TPU:0"]["ops"].append([150 * MS, 5 * MS, "x"])
    assert trace.reduce(t)["devices"]["/device:TPU:0"]["busy_s"] == \
        pytest.approx(0.030)


def test_recorded_chip_trace():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        norm = json.load(f)
    s = trace.reduce(norm)
    assert s["devices"], "the recorded trace has device ops"
    assert 0.0 < s["busy_s"] <= s["window_s"]
    for d in s["devices"].values():
        assert 0.0 <= d["idle_share"] <= 1.0
    assert sum(sec for _, sec in s["idle_gaps"]) <= s["window_s"] + 1e-9
    with open(os.path.join(DATA, "trace_small.expected.json")) as f:
        want = json.load(f)
    assert s["busy_s"] == pytest.approx(want["busy_s"])
    assert s["window_s"] == pytest.approx(want["window_s"])
    for prefix, mean in want["module_mean_s"].items():
        assert trace.module_mean_s(s, prefix) == pytest.approx(mean)
