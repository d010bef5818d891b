"""A configuration that brings configs/<name>.py has its generator, program
inputs and reference taken from there; one without keeps the defaults."""

import json

import cluster
import reference

MODULE = '''
import cluster

def generate(config, seed, i):
    c = cluster.Cluster(config, 3, ["a", "b", "c"], {}, None, [], None, [])
    c.made_by = "own"
    return c

class Reference:
    def __init__(self, c):
        self.c = c
'''


def test_config_module_takes_over(tmp_path, monkeypatch):
    (tmp_path / "configs").mkdir()
    cfg = {"name": "own-cfg"}
    (tmp_path / "configs" / "own-cfg.json").write_text(json.dumps(cfg))
    (tmp_path / "configs" / "own-cfg.py").write_text(MODULE)
    monkeypatch.setattr(cluster, "HERE", str(tmp_path))
    c = cluster.generate(cluster.load_config("own-cfg"), 1, 0)
    assert c.made_by == "own" and c.n_nodes == 3
    assert cluster.reference_for(cfg).__module__.startswith("bench_config_")


def test_plain_config_keeps_defaults():
    cfg = cluster.load_config("sched-perf-5k")
    assert cluster.reference_for(cfg) is reference.Reference
    assert cluster.generate(cfg, 1, 0).n_nodes == 5000


def test_whatif_binds_evenly_or_as_scheduled():
    import numpy as np
    import run

    whatif = run.load_module(f"{cluster.HERE}/drivers/whatif.py", "test_bind_whatif")
    c = cluster.generate(cluster.load_config("cl2-load-5k"), 2**33 + 3, 0)
    assert (np.bincount(whatif.bind(c, 2**33 + 3, "even"), minlength=c.n_nodes) == 30).all()
    c = cluster.generate(cluster.load_config("sched-perf-5k"), 5, 0)
    where = whatif.bind(c, 5, "scheduled")
    assert len(where) == c.n_pods and (where >= 0).all()
