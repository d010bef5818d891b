"""The control (the reference with one stated guarantee broken) must read
as not correct. The chip runs of control.py at each cell's size are in
PERF.md; here the batch control runs at a small size and the what-if
control, which is host arithmetic only, at the cell's own size."""

import copy
import json
import os

import control
import cluster
from test_faults import BATCH_CFG

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_batch_control_misplaces(monkeypatch):
    for name in ("cl2-load-5k", "sched-perf-5k"):
        cfg = copy.deepcopy(cluster.load_config(name))
        cfg["nodes"]["count"] = 50
        for ph in cfg["phases"]:
            for g in ph["groups"]:
                g["replicas"] = max(3, g["replicas"] // 50)
                g["units_per_namespace"] = max(1, g["units_per_namespace"] // 10)
        cfg["namespaces"] = min(2, cfg["namespaces"])
        assert control.batch_control(cfg, 2**33 + 1) > 0
    assert control.batch_control(BATCH_CFG, 7) > 0


def test_whatif_control_at_cell_size():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "traffic", "whatif.json")) as f:
        traffic = json.load(f)
    cfg = cluster.load_config("sched-perf-5k")
    assert control.whatif_control(cfg, traffic, 2**33 + 2, bench["run_seconds"]) > 0
