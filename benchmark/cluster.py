"""The one generator of cluster inputs: a configuration file plus (seed, i)
gives the i-th simulation's nodes and pod units.

A configuration (configs/<name>.json) is data: a node template and count,
an optional zone label (each value on an equal share of the nodes, which
nodes drawn from the seed), and phases of pod groups. A group is
`units_per_namespace` units in each namespace, each unit `replicas` copies
of one pod template; `unit_label` gives every unit its own label value (a
Deployment's selector). A phase's units run in the listed order
("sequential"), interleaved across its groups in proportion to their unit
counts ("interleave"), or in an order drawn from the seed ("shuffle").
Every seed gives the same units and sizes; only names, zones and a
shuffled order move with it.

`generate` returns plain data (dicts, lists, numpy arrays): the reference
reads that. `program_inputs` turns it into the program's columnar
NodeStore/PodStore, the form the engine schedules.

A configuration this schema cannot state (mixed node pools, GPUs, priority
classes) adds configs/<name>.py beside its JSON: a module that defines any
of `generate(config, seed, i)`, `program_inputs(cluster)` and `Reference`
(a class with reference.Reference's methods) takes their place for that
configuration alone, so adding it edits no file here.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import os
import string
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Unit:
    name: str
    template: dict   # complete pod template: namespace and labels set
    count: int


@dataclass
class Cluster:
    config: dict
    n_nodes: int
    node_names: List[str]
    node_template: dict
    zone_key: Optional[str]
    zone_values: List[str]
    node_zone: Optional[np.ndarray]  # [N] index into zone_values
    units: List[Unit]

    @property
    def n_pods(self) -> int:
        return sum(u.count for u in self.units)


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json"), encoding="utf-8") as f:
        return json.load(f)


def _own(config: dict, attr: str):
    """configs/<name>.py's `attr`, or None where the configuration has no
    module of its own or the module does not define it."""
    path = os.path.join(HERE, "configs", f"{config['name']}.py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "bench_config_" + config["name"].replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr, None)


def reference_for(config: dict):
    """The plain reference class for this configuration."""
    import reference

    return _own(config, "Reference") or reference.Reference


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """Seeds are any whole number (the driver's exceed 32 bits)."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (1 << 64), *salt]))


_LETTERS = np.array(list(string.ascii_lowercase))


def _letters(rng: np.random.Generator, n: int) -> str:
    return "".join(_LETTERS[rng.integers(0, 26, n)])


def _interleave(groups: List[List[Unit]]) -> List[Unit]:
    """Each next unit from the group furthest behind its share."""
    total = sum(len(g) for g in groups)
    taken = [0] * len(groups)
    out: List[Unit] = []
    for k in range(total):
        j = min((j for j, g in enumerate(groups) if taken[j] < len(g)),
                key=lambda j: ((taken[j] + 1) / len(groups[j]), j))
        out.append(groups[j][taken[j]])
        taken[j] += 1
    return out


def generate(config: dict, seed: int, i: int) -> Cluster:
    own = _own(config, "generate")
    if own is not None:
        return own(config, seed, i)
    rng = rng_for(seed, 1, i)
    nodes = config["nodes"]
    n = int(nodes["count"])
    zl = nodes.get("zone_label")
    node_zone = None
    zone_values: List[str] = []
    if zl:
        zone_values = list(zl["values"])
        node_zone = (np.arange(n) % len(zone_values))[rng.permutation(n)]
    names = [nodes["name_fmt"].format(k) for k in range(n)]
    n_ns = int(config.get("namespaces", 1))
    ns_fmt = config.get("namespace_fmt", "default")
    units: List[Unit] = []
    for phase in config["phases"]:
        per_group: List[List[Unit]] = []
        for grp in phase["groups"]:
            batch: List[Unit] = []
            per_group.append(batch)
            base = config["templates"][grp["template"]]
            for ns_i in range(n_ns):
                ns = ns_fmt.format(ns_i)
                for j in range(int(grp["units_per_namespace"])):
                    uname = f"{grp['name']}-{ns_i}-{j}"
                    # units share the template's spec (nothing writes to it);
                    # metadata is each unit's own
                    md = copy.deepcopy(base.get("metadata") or {})
                    md["namespace"] = ns
                    if grp.get("unit_label"):
                        uname = f"{grp['name']}-deployment-{j}-{_letters(rng, 5)}"
                        md.setdefault("labels", {})[grp["unit_label"]] = uname
                    t = {**base, "metadata": md}
                    batch.append(Unit(f"{ns}.{uname}", t, int(grp["replicas"])))
        order = phase.get("order", "sequential")
        if order == "interleave":
            units.extend(_interleave(per_group))
            continue
        batch = [u for b in per_group for u in b]
        if order == "shuffle":
            batch = [batch[k] for k in rng.permutation(len(batch))]
        units.extend(batch)
    return Cluster(config, n, names, nodes["template"],
                   zl["key"] if zl else None, zone_values, node_zone, units)


def zone_runs(c: Cluster) -> List[Tuple[int, int, Optional[str]]]:
    """(start, count, zone value) runs of consecutive nodes in one zone."""
    if c.node_zone is None:
        return [(0, c.n_nodes, None)]
    z = c.node_zone
    cut = np.flatnonzero(np.diff(z) != 0) + 1
    starts = np.concatenate([[0], cut]).tolist()
    ends = np.concatenate([cut, [c.n_nodes]]).tolist()
    return [(s, e - s, c.zone_values[int(z[s])]) for s, e in zip(starts, ends)]


def program_inputs(c: Cluster):
    """(NodeStore, PodStore) for Simulator.schedule_pods."""
    own = _own(c.config, "program_inputs")
    if own is not None:
        return own(c)
    from open_simulator_tpu.simulator.store import NodeStore, PodStore

    ns = NodeStore()
    fmt = c.config["nodes"]["name_fmt"]
    for start, count, zone in zone_runs(c):
        ns.add_block(c.node_template, count, name_fmt=fmt,
                     labels={c.zone_key: zone} if zone is not None else None)
    ps = PodStore()
    for u in c.units:
        ps.add_block(u.template, u.count, name_fmt=u.name + "-{0}",
                     name_start=0)
    return ns, ps
