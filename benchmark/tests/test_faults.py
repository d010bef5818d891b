"""Drive whole runs on the CPU at a small size, skipping only the harness's
look for a chip, with the timed path broken underneath: each fault a cell
can have must turn `correct` false (the unbroken run must read true).

One chip per cell, so "the exchange between chips left out" does not apply.
"""

import argparse
import copy
import json
import os
import time

import numpy as np
import pytest

import cluster
import common

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Small stand-ins: requests at or above the scheduler's non-zero defaults,
# where the program's scoring agrees with the reference (PERF.md, Open
# questions), so a broken path is the only source of a wrong answer.
BATCH_CFG = {
    "name": "tiny-batch", "namespaces": 2, "namespace_fmt": "ns-{0}",
    "nodes": {"count": 30, "name_fmt": "node-{0:05d}",
              "template": cluster.load_config("cl2-load-5k")["nodes"]["template"]},
    "phases": [{"name": "load", "order": "shuffle", "groups": [
        {"name": "big", "units_per_namespace": 2, "replicas": 20, "unit_label": "name", "template": "p"},
        {"name": "small", "units_per_namespace": 6, "replicas": 3, "unit_label": "name", "template": "p"}]}],
    "templates": {"p": {"apiVersion": "v1", "kind": "Pod", "metadata": {"labels": {"group": "load"}},
                        "spec": {"containers": [{"name": "c", "image": "pause", "resources": {
                            "requests": {"cpu": "150m", "memory": "300Mi"}}}]}}},
}


def whatif_cfg():
    cfg = copy.deepcopy(cluster.load_config("sched-perf-5k"))
    cfg["nodes"]["count"] = 40
    init, measured = cfg["phases"]
    for g, r in zip(init["groups"], (40, 10)):
        g["replicas"] = r
    for g, (u, r) in zip(measured["groups"], [(4, 5), (2, 5)]):
        g["units_per_namespace"], g["replicas"] = u, r
    return cfg


def make_ctx(workload, traffic_over, seconds=1.0):
    import jax

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = common.cell_named(bench, workload)
    with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    traffic.update(traffic_over)
    args = argparse.Namespace(workload=workload, seed=2**33 + 5, seconds=seconds, trace=0)
    ctx = common.Context(args, bench, cell, traffic, time.time())
    ctx.use_devices(jax.devices())  # the one step skipped: no chip needed
    return ctx


def driver(name):
    from run import load_module

    return load_module(os.path.join(BENCH, "drivers", f"{name}.py"), f"test_{name}")


# ------------------------------------------------------------------ batch --

def _batch_run(monkeypatch, simulate_fn=None):
    monkeypatch.setattr(cluster, "load_config", lambda name: BATCH_CFG)
    drv = driver("batch")
    ctx = make_ctx("sched-perf-5k.batch", {"inputs": 2, "check": 2}, 0.5)
    out = drv.run(ctx, simulate_fn or drv.simulate)
    return out


def _broken(kind):
    from open_simulator_tpu.simulator.engine import Simulator

    def simulate(ns, ps):
        sim = Simulator(ns)
        if kind == "half_left_out":
            sim.schedule_pods(ps[:len(ps) // 2])
        elif kind != "state_unchanged":  # unchanged: "ran", committed nothing
            sim.schedule_pods(ps)
        nodes = np.array(ps.node_rows(), np.int64)
        if kind == "answer_altered":
            nodes[0] = (nodes[0] + 1) % len(ns)
        return int((nodes >= 0).sum()), nodes
    return simulate


def test_batch_unbroken_is_correct(monkeypatch):
    out = _batch_run(monkeypatch)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("kind", ["state_unchanged", "half_left_out", "answer_altered"])
def test_batch_fault_is_caught(monkeypatch, kind):
    out = _batch_run(monkeypatch, _broken(kind))
    assert not out["correct"]
    assert out["checks"][0][1] > 0


# ----------------------------------------------------------------- whatif --

def _break_kernel(image_mod, fault):
    """The fan-out kernel's round (kernel + fetch) broken underneath the
    answer assembly, in the window only."""
    orig = image_mod.ResidentImage._wave_round

    def broken(self, carry_np, active_s, g_s, m_s, cap1_s, block, kmax):
        if not getattr(self, "_bench_window", False):
            return orig(self, carry_np, active_s, g_s, m_s, cap1_s, block, kmax)
        if fault == "half_left_out":  # half of each lane's replicas placed
            placed, req = orig(self, carry_np, active_s, g_s, m_s // 2,
                               cap1_s, block, kmax)
            return placed + (m_s - m_s // 2), req
        placed, req = orig(self, carry_np, active_s, g_s, m_s, cap1_s, block, kmax)
        req = np.array(req)
        base = np.asarray(self._seeds[0])
        if fault == "state_unchanged":  # the kernel's carry comes back as it went in
            req[:] = base[None]
        elif fault == "wrong_node":  # one lane's replicas on another node
            for li in range(len(req)):
                hit = np.flatnonzero((req[li] != base).any(axis=1))
                if len(hit):
                    n = hit[0]
                    m = int(np.flatnonzero((req[li] != req[li, n]).any(axis=1))[0])
                    req[li, [n, m]] = req[li, [m, n]]
        return placed, req
    return broken


def _whatif_run(monkeypatch, fault=None):
    cfg = whatif_cfg()
    monkeypatch.setattr(cluster, "load_config", lambda name: cfg)
    from open_simulator_tpu.serve import image as image_mod

    if fault == "answer_altered":  # an answer altered where it is assembled
        orig = image_mod.ResidentImage._responses

        def altered(self, *a):
            out = orig(self, *a)
            if getattr(self, "_bench_window", False):
                for resp in out:
                    resp["utilization"] = dict(
                        resp["utilization"], cpu_used=resp["utilization"]["cpu_used"] + 10)
            return out
        monkeypatch.setattr(image_mod.ResidentImage, "_responses", altered)
    elif fault is not None:
        monkeypatch.setattr(image_mod.ResidentImage, "_wave_round",
                            _break_kernel(image_mod, fault))
    drv = driver("whatif")
    ctx = make_ctx("sched-perf-5k.whatif", {"rate_per_s": 20, "pool": 8, "check": 20}, 1.0)
    start = ctx.start_window

    def start_window():  # break only the window's dispatches, not the warm-up
        image_mod.ResidentImage._bench_window = True
        return start()
    monkeypatch.setattr(ctx, "start_window", start_window)
    try:
        return drv.run(ctx)
    finally:
        image_mod.ResidentImage._bench_window = False


def test_whatif_unbroken_is_correct(monkeypatch):
    out = _whatif_run(monkeypatch)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out", "wrong_node",
                                   "answer_altered"])
def test_whatif_fault_is_caught(monkeypatch, fault):
    out = _whatif_run(monkeypatch, fault)
    assert not out["correct"]
    assert dict((n, v) for n, v, _ in out["checks"])["wrong_answers"] > 0
