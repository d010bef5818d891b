"""Scheduling classes: templates that differ only in labels no selector
reads are one pod to every filter and score, so the engine encodes and
dispatches them as one class (one group, one wave) while each pod keeps its
own template. Every case here runs a ClusterLoader2-shaped input, built
through the benchmark's generator at a small scale, through
Simulator(NodeStore).schedule_pods(PodStore) or through the dict-list
entries (schedule_app's Deployments, run_cluster's pods), and holds the
placements pod by pod to the program's serial scan (use_waves False, the
parity oracle) and, where it models the case, to the plain reference in
benchmark/reference.py."""

from __future__ import annotations

import copy
import os
import sys

import numpy as np
import pytest

from open_simulator_tpu import native
from open_simulator_tpu.core.types import AppResource, ResourceTypes
from open_simulator_tpu.models import workloads
from open_simulator_tpu.simulator.engine import Simulator
from open_simulator_tpu.simulator.store import PodStore
from open_simulator_tpu.utils import trace

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import cluster  # noqa: E402
import reference  # noqa: E402

HOSTNAME = "kubernetes.io/hostname"


def cl2_small() -> dict:
    """cl2-load-5k's schema at 40 nodes and 2 namespaces: Deployments of
    25/3/1 replicas, 3/25/150 per namespace, so 1/4, 1/4 and 1/2 of the
    pods as in ClusterLoader2's load test, in one shuffled order."""
    cfg = copy.deepcopy(cluster.load_config("cl2-load-5k"))
    cfg["nodes"]["count"] = 40
    cfg["namespaces"] = 2
    for grp, (units, reps) in zip(cfg["phases"][0]["groups"],
                                  ((3, 25), (25, 3), (150, 1))):
        grp["units_per_namespace"] = units
        grp["replicas"] = reps
    return cfg


def _requests(tmpl: dict, requests) -> None:
    c = tmpl["spec"]["containers"][0]
    if requests is None:
        c.pop("resources", None)
    else:
        c["resources"] = {"requests": dict(requests)}


def _anti_unit(c, target: str, count: int) -> cluster.Unit:
    """A Deployment whose required hostname anti-affinity selects another
    Deployment's `name` label."""
    ns = c.units[0].template["metadata"]["namespace"]
    tmpl = copy.deepcopy(c.units[0].template)
    tmpl["metadata"] = {"namespace": ns, "labels": {"group": "late"}}
    tmpl["spec"]["affinity"] = {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [{
            "labelSelector": {"matchLabels": {"name": target}},
            "topologyKey": HOSTNAME}]}}
    return cluster.Unit(f"{ns}.late", tmpl, count)


def _store(units) -> PodStore:
    ps = PodStore()
    for u in units:
        ps.add_block(u.template, u.count, name_fmt=u.name + "-{0}",
                     name_start=0)
    return ps


def _keyed(groups: int) -> int:
    """encode.classes' `keyed_native` for a call that folds `groups`
    signature groups: all of them where the extension is built."""
    return groups if native.class_sigs_fn() is not None else 0


def _spans(roots, name: str) -> list:
    """The meta of every span called `name` under `roots`."""
    out = []

    def walk(sp):
        if sp.name == name:
            out.append(dict(sp.meta))
        for ch in sp.children:
            walk(ch)

    for r in roots:
        walk(r)
    return out


def _run(c, batches, waves: bool, services=()):
    """Per-pod nodes (-1 = unschedulable) over every batch in order, and the
    encode.classes payloads of the run."""
    ns, _ = cluster.program_inputs(c)
    sim = Simulator(ns)
    sim.use_waves = waves
    if services:
        sim.register_cluster_objects(
            ResourceTypes(services=copy.deepcopy(list(services))))
    nodes, payloads = [], []
    trace.start_collection()
    try:
        for units in batches:
            ps = _store(units)
            sim.schedule_pods(ps)
            nodes.append(np.array(ps.node_rows(), np.int64))
    finally:
        roots = trace.stop_collection()
    return np.concatenate(nodes), _spans(roots, "encode.classes")


CASES = {
    # CL2's own pods: 10m / 10M, each request below the scoring default
    "requests_10m": dict(requests={"cpu": "10m", "memory": "10M"}),
    # an explicit 0 counts as written (no default)
    "requests_zero": dict(requests={"cpu": "0", "memory": "0"}),
    # unset requests score at the default 100m / 200Mi
    "requests_unset": dict(requests=None),
    # the init container outweighs the containers in fit and in scoring
    "init_container": dict(requests={"cpu": "10m", "memory": "10M"},
                           init={"cpu": "300m", "memory": "1Gi"}),
    # a later batch's required anti-affinity selects one earlier
    # Deployment's name: that Deployment's pods must count as its own
    "later_selector": dict(requests={"cpu": "10m", "memory": "10M"},
                           later=True),
    # a Service over one Deployment's label makes SelectorSpread read `name`
    "service": dict(requests={"cpu": "10m", "memory": "10M"}, service=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_cl2_classes_match_serial_and_reference(case):
    spec = CASES[case]
    cfg = cl2_small()
    tmpl = cfg["templates"]["deployment"]
    _requests(tmpl, spec["requests"])
    if "init" in spec:
        tmpl["spec"]["initContainers"] = [{
            "name": "init", "image": "busybox",
            "resources": {"requests": dict(spec["init"])}}]
    c = cluster.generate(cfg, 20_262_026, 0)
    batches = [c.units]
    services = ()
    target = c.units[3]  # a Deployment from the middle of the order
    target_name = target.template["metadata"]["labels"]["name"]
    if spec.get("later"):
        batches.append([_anti_unit(c, target_name, 30)])
    if spec.get("service"):
        services = [{"apiVersion": "v1", "kind": "Service",
                     "metadata": {"name": "svc", "namespace":
                                  target.template["metadata"]["namespace"]},
                     "spec": {"selector": {"name": target_name}}}]

    got, payloads = _run(c, batches, True, services)
    want, _ = _run(c, batches, False, services)
    assert got.shape == want.shape
    assert np.array_equal(got, want), (
        f"{int((got != want).sum())} of {len(want)} pods differ from the "
        f"serial scan")

    n_units = len(c.units)
    if spec.get("service"):
        # SelectorSpread reads `name`, so no two Deployments share a class
        assert payloads[0] == {"groups": n_units, "classes": n_units,
                               "keyed_native": _keyed(n_units)}
    else:
        assert payloads[0] == {"groups": n_units, "classes": 1,
                               "keyed_native": _keyed(n_units)}
    if spec.get("later"):
        # the anti-affinity batch never lands beside the selected Deployment
        offs = np.cumsum([0] + [u.count for u in c.units])
        k = c.units.index(target)
        hosts = set(got[offs[k]:offs[k + 1]].tolist())
        late = got[offs[-1]:]
        assert (late >= 0).all()
        assert not hosts & set(late.tolist())

    if spec.get("service") or "init" in spec:
        # the reference has no Services, and it ignores init containers
        return
    units = [u for b in batches for u in b]
    ref_cluster = cluster.Cluster(c.config, c.n_nodes, c.node_names,
                                  c.node_template, c.zone_key, c.zone_values,
                                  c.node_zone, units)
    ref = np.concatenate(reference.Reference(ref_cluster).schedule_all())
    assert np.array_equal(got, ref), (
        f"{int((got != ref).sum())} of {len(ref)} pods differ from the "
        f"reference")


def test_class_is_not_stored_under_member_signatures():
    """The class group is interned under its own template's signature: a
    member's signature stays unregistered, so a later encode of that member
    builds its own group from its own labels."""
    c = cluster.generate(cl2_small(), 7, 0)
    ns, ps = cluster.program_inputs(c)
    sim = Simulator(ns)
    sim.schedule_pods(ps)
    b = ps.base
    assert not any(s in sim.encoder.groups for s in b.sigs)
    # every pod's placed record is its own template's
    placed_pods = sum(sum(pg.node_counts.values())
                      for pg in sim.placed.values())
    assert placed_pods == len(ps)
    assert {pg.sig for pg in sim.placed.values()} == set(b.sigs)


def _avoid_nodes() -> list:
    """Six nodes, the first three of which ask to avoid ReplicaSet rs-b."""
    import json

    from fixtures import make_node

    avoid = json.dumps({"preferAvoidPods": [{"podSignature": {
        "podController": {"kind": "ReplicaSet", "uid": "rs-b",
                          "controller": True}}}]})
    return [make_node(f"n{i}", cpu="4", memory="8Gi",
                      annotations={
                          "scheduler.alpha.kubernetes.io/preferAvoidPods":
                              avoid} if i < 3 else None)
            for i in range(6)]


def _owned_template(app: str) -> dict:
    """A pod of app `app` whose controller is ReplicaSet rs-<app>."""
    from fixtures import make_pod

    p = make_pod(f"{app}-0", cpu="100m", memory="128Mi",
                 labels={"name": app})
    p["metadata"]["ownerReferences"] = [{
        "kind": "ReplicaSet", "name": f"{app}-rs", "uid": f"rs-{app}",
        "controller": True}]
    return p


def _partition_case(case: str):
    """(a builder of the case's nodes, its units, the classes expected)."""
    if case == "avoid_owner":
        units = [cluster.Unit(app, _owned_template(app), 10)
                 for app in ("a", "b", "c")]
        return _avoid_nodes, units, 3
    c = cluster.generate(cl2_small(), 20_262_026, 0)

    def nodes():
        return cluster.program_inputs(c)[0]

    if case == "cl2":
        return nodes, c.units, 1
    # a selector reads `tier`, which a third of the Deployments set to
    # front and a third to back, and scopes to a namespace: three classes
    # in each of the two namespaces, and the selector's own
    units = [cluster.Unit(u.name, copy.deepcopy(u.template), u.count)
             for u in c.units]
    for i, u in enumerate(units):
        if i % 3 < 2:
            u.template["metadata"]["labels"]["tier"] = ("front", "back")[i % 3]
    late = _anti_unit(c, "unused", 5)
    late.template["spec"]["affinity"]["podAntiAffinity"][
        "requiredDuringSchedulingIgnoredDuringExecution"][0][
        "labelSelector"] = {"matchLabels": {"tier": "front"}}
    return nodes, units + [late], 7


@pytest.mark.parametrize("case", ["cl2", "selector_splits", "avoid_owner"])
def test_partition_is_the_same_with_and_without_the_native_pass(
        case, monkeypatch):
    """Simulator._classes keys every group in one native call where the
    extension is built, and class_template + scheduling_signature per
    group where it is not: both give each group the same class, and each
    class the same template and signature to intern under. The
    encode.classes payload counts the groups the native pass keyed."""
    if native.class_sigs_fn() is None:
        pytest.skip("native extension unavailable (no compiler?)")
    nodes, units, n_classes = _partition_case(case)
    reps = [u.template for u in units]
    got, nodes_of, payloads = {}, {}, {}
    for on in (True, False):
        if not on:
            monkeypatch.setattr(native, "_class_sigs", None)
        got[on] = Simulator(nodes())._classes(reps)
        sim = Simulator(nodes())
        ps = _store(units)
        trace.start_collection()
        try:
            sim.schedule_pods(ps)
        finally:
            roots = trace.stop_collection()
        nodes_of[on] = np.asarray(ps.node_rows()).tolist()
        payloads[on] = _spans(roots, "encode.classes")
    cls_of, cls_tmpl, keyed = got[True]
    assert (cls_of, cls_tmpl) == got[False][:2]
    assert len(cls_tmpl) == n_classes
    assert (keyed, got[False][2]) == (len(reps), 0)
    assert nodes_of[True] == nodes_of[False]
    for on in (True, False):
        assert payloads[on] == [{"groups": len(reps), "classes": n_classes,
                                 "keyed_native": len(reps) if on else 0}]


def test_prefer_avoid_controller_stays_out_of_the_class():
    """NodePreferAvoidPods reads a pod's ReplicaSet uid, which no signature
    holds: where a node names one, Deployments that differ only in labels
    and owner still land pod by pod where the serial scan puts them."""
    got = {}
    for waves in (True, False):
        sim = Simulator(_avoid_nodes())
        sim.use_waves = waves
        ps = PodStore()
        for app in ("a", "b", "c"):
            ps.add_block(_owned_template(app), 10, name_fmt=app + "-{0}",
                         name_start=0)
        sim.schedule_pods(ps)
        got[waves] = np.array(ps.node_rows()).tolist()
    assert got[True] == got[False]
    b_nodes = set(got[True][10:20])
    assert b_nodes.isdisjoint({0, 1, 2})  # the avoided ReplicaSet


def _deployments(c) -> list:
    """Each unit of `c` as a Deployment, in the units' order."""
    out = []
    for u in c.units:
        md = u.template["metadata"]
        out.append({
            "apiVersion": "apps/v1", "kind": "Deployment",
            "metadata": {"name": md["labels"]["name"],
                         "namespace": md["namespace"]},
            "spec": {"replicas": u.count,
                     "selector": {"matchLabels": dict(md["labels"])},
                     "template": copy.deepcopy(u.template)}})
    return out


def _entry_run(c, entry: str, waves: bool):
    """Per-pod nodes in the units' order through a dict-list entry, the
    encode.classes payloads, and the kinds of the segments dispatched."""
    ns, _ = cluster.program_inputs(c)
    sim = Simulator(ns)
    sim.use_waves = waves
    trace.start_collection()
    try:
        if entry == "schedule_app":
            workloads.reset_name_counter()
            res = sim.schedule_app(AppResource(
                "cl2", ResourceTypes(deployments=_deployments(c))))
            assert not res.unscheduled_pods
            # a pod's uid counts up in expansion order, the units' order
            placed = sorted((p["metadata"]["uid"], p["spec"]["nodeName"])
                            for st in res.node_status for p in st.pods)
            nodes = [sim.na.index[n] for _, n in placed]
        else:
            pods = []
            for u in c.units:
                for k in range(u.count):
                    p = copy.deepcopy(u.template)
                    p["metadata"]["name"] = f"{u.name}-{k}"
                    pods.append(p)
            res = sim.run_cluster(ResourceTypes(pods=pods))
            assert not res.unscheduled_pods
            nodes = [sim.na.index[p["spec"]["nodeName"]] for p in pods]
    finally:
        roots = trace.stop_collection()
    kinds = [sp.name for r in roots for sp in _walk(r)
             if sp.name.startswith("dispatch.")]
    return np.array(nodes, np.int64), _spans(roots, "encode.classes"), kinds


def _walk(sp):
    yield sp
    for ch in sp.children:
        yield from _walk(ch)


@pytest.mark.parametrize("entry", ["schedule_app", "run_cluster"])
def test_cl2_dict_entries_fold_and_match_serial_and_reference(entry):
    """The entries that hand schedule_pods a list of pod dicts fold the
    same way: `simon apply`'s Deployment expansion registers no
    ReplicaSet, so CL2's Deployments are one class, one plain wave."""
    c = cluster.generate(cl2_small(), 20_262_026, 1)
    got, payloads, kinds = _entry_run(c, entry, True)
    want, _, _ = _entry_run(c, entry, False)
    assert np.array_equal(got, want), (
        f"{int((got != want).sum())} of {len(want)} pods differ from the "
        f"serial scan")
    assert payloads == [{"groups": len(c.units), "classes": 1,
                         "keyed_native": _keyed(len(c.units))}]
    assert kinds == ["dispatch.wave"]
    ref = np.concatenate(reference.Reference(c).schedule_all())
    assert np.array_equal(got, ref), (
        f"{int((got != ref).sum())} of {len(ref)} pods differ from the "
        f"reference")
