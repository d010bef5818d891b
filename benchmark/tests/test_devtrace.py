"""The trace reduction, on a hand-built trace and on a small recorded one
(tests/fixtures/trace_small.json, cut from a chip run's --trace 1)."""

import json
import os

import pytest

import devtrace

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def hand_trace():
    # window 0..1000 ns; device ops [100,300) and [250,400) overlap -> busy
    # [100,400) and [600,700): 400 ns busy, 600 idle. Gaps: [0,100) under
    # host "prep", [400,600) under "PjitFunction(k)" nested in "sim"
    # (innermost wins), [700,1000) under "sim" only.
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["bench.window", 0, 1000], ["sim", 0, 1000],
                                          ["prep", 0, 120], ["PjitFunction(k)", 350, 300]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_k(12)", 100, 300], ["jit_other(3)", 600, 100]]},
            {"name": "XLA Ops", "events": [["fusion.1", 100, 200], ["fusion.2", 250, 150],
                                           ["copy.3", 600, 100]]}]},
    ]}


def test_hand_built_trace():
    r = devtrace.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["idle_share"] == pytest.approx(0.6)
    assert dict(r["device_ops"]) == pytest.approx({"jit_k": 300e-9, "jit_other": 100e-9})
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"prep": 100e-9, "PjitFunction(k)": 200e-9, "sim": 300e-9})
    assert devtrace.kernel_seconds(r, "k") == pytest.approx(300e-9)
    assert devtrace.kernel_seconds(r, "other") == pytest.approx(100e-9)
    assert devtrace.kernel_seconds(r, "none") == 0


def test_window_and_device_are_required():
    t = hand_trace()
    t["planes"][0]["lines"][0]["events"] = t["planes"][0]["lines"][0]["events"][1:]
    with pytest.raises(ValueError):
        devtrace.reduce(t)
    with pytest.raises(ValueError):
        devtrace.reduce({"planes": hand_trace()["planes"][:1]})


def test_recorded_chip_trace():
    """40 ms of a sched-perf-5k.batch --trace 1 run on one v5e (my chip run,
    PR 22), op names cut to 48 characters, the window span cut to match."""
    with open(os.path.join(FIX, "trace_small.json")) as f:
        r = devtrace.reduce(json.load(f))
    assert r["window_s"] == pytest.approx(0.04)
    assert r["busy_s"] == pytest.approx(0.009757435)
    assert r["idle_share"] == pytest.approx(0.756064125)
    ops = dict(r["device_ops"])
    assert ops == pytest.approx({"jit_schedule_affinity_wave": 0.008641334,
                                 "jit_schedule_wave": 0.001146633})
    # module time and op-union time describe the same work
    assert sum(ops.values()) == pytest.approx(r["busy_s"], rel=0.01)
    gaps = dict(r["idle_gaps"])
    assert max(gaps, key=gaps.get) == "bench.simulation"
    # the ten largest gap groups hold all but a few hundred ns of idle time
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-4)
    assert devtrace.kernel_seconds(r, "schedule_affinity_wave") == pytest.approx(0.008641334)
