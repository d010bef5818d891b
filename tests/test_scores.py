"""Numeric score parity: one exact hand-computed integer assertion per score
plugin, derived from the vendored formulas (NOT from this repo's code), so a
systematic error shared by both engine paths cannot pass. Each test isolates
its plugin with a ScoreWeights vector that zeroes every other weight — the
weight machinery itself is under test in test_schedconfig.py.

Sources (all under /root/reference/vendor/k8s.io/kubernetes/pkg/scheduler/
framework/plugins/ unless noted):
- noderesources/least_allocated.go:93-115  (per-resource floor, /2 floor)
- noderesources/balanced_allocation.go:96-120
- imagelocality/image_locality.go:60-112   (spread scaling, thresholds)
- interpodaffinity/scoring.go              (weighted counts, zero-init min/max)
- nodeaffinity (preferred weights, DefaultNormalizeScore)
- nodepreferavoidpods (0/100 by controller signature)
- podtopologyspread/scoring.go:270-289     (cnt*ln(size+2)+maxSkew-1;
  100*(max+min-s)/max integer division)
- tainttoleration (intolerable PreferNoSchedule count, reverse normalize)
- selectorspread/selector_spread.go:104-160
- /root/reference/pkg/simulator/plugin/simon.go:45-101 (max-share + min-max)
"""

import copy

import numpy as np
import pytest

from open_simulator_tpu.ops import kernels
from open_simulator_tpu.simulator.engine import Simulator

from fixtures import make_node, make_pod

ZERO = {f: 0.0 for f in kernels.ScoreWeights._fields}


def iso(**kw):
    """ScoreWeights with every plugin off except the given ones."""
    return kernels.ScoreWeights(**{**ZERO, **kw})


def plugin_scores(nodes, seed_pods, probe, w):
    """Exact per-node score vector for `probe` under weight vector w, after
    committing seed_pods (which must be pre-bound)."""
    import jax.numpy as jnp

    sim = Simulator(copy.deepcopy(nodes))
    if seed_pods:
        failed = sim.schedule_pods(copy.deepcopy(seed_pods))
        assert not failed
    bt = sim.encode_batch([copy.deepcopy(probe)])
    tables, carry = sim._to_device(bt)
    g = int(bt.pod_group[0])
    feasible, _ = kernels.feasibility_jit(
        tables, carry, jnp.int32(g), jnp.int32(-1), jnp.asarray(True))
    sc = kernels.scores(tables, carry, jnp.int32(g), feasible, bt.n_zones,
                        enable_storage=False, w=w)
    return np.asarray(sc)[: len(nodes)]


def bound(name, node, cpu="1", memory="1Gi", **kw):
    return make_pod(name, cpu=cpu, memory=memory, node_name=node, **kw)


def test_least_allocated_exact():
    """A: 10cpu/10Gi seeded 3cpu/4Gi; probe 1cpu/1Gi ->
    cpu floor((10000-4000)*100/10000)=60, mem floor((10-5)*100/10)=50,
    floor((60+50)/2)=55. B: 20cpu/20Gi empty -> floor((95+95)/2)=95."""
    nodes = [make_node("a", cpu="10", memory="10Gi"),
             make_node("b", cpu="20", memory="20Gi")]
    seeds = [bound("s0", "a", cpu="3", memory="4Gi")]
    got = plugin_scores(nodes, seeds, make_pod("p", cpu="1", memory="1Gi"),
                        iso(least=1.0))
    assert got.tolist() == [55.0, 95.0]


def test_balanced_allocation_exact():
    """A: 8cpu/8Gi seeded 1cpu/5Gi; probe 1cpu/1Gi -> cf=2/8=.25, mf=6/8=.75,
    floor((1-.5)*100)=50. B empty: cf=mf=1/8 -> 100."""
    nodes = [make_node("a", cpu="8", memory="8Gi"),
             make_node("b", cpu="8", memory="8Gi")]
    seeds = [bound("s0", "a", cpu="1", memory="5Gi")]
    got = plugin_scores(nodes, seeds, make_pod("p", cpu="1", memory="1Gi"),
                        iso(balanced=1.0))
    assert got.tolist() == [50.0, 100.0]


def test_simon_max_share_exact():
    """share = max_r req/(alloc-req), x100 floored, then min-max over feasible:
    A 8cpu/8Gi: 1/(8-1) -> floor(14.28)=14; B 16cpu/16Gi: 1/15 -> 6.
    normalize: A floor((14-6)*100/8)=100, B 0."""
    nodes = [make_node("a", cpu="8", memory="8Gi"),
             make_node("b", cpu="16", memory="16Gi")]
    got = plugin_scores(nodes, [], make_pod("p", cpu="1", memory="1Gi"),
                        iso(simon=1.0))
    assert got.tolist() == [100.0, 0.0]


def test_taint_toleration_exact():
    """Intolerable PreferNoSchedule taints counted, reverse-normalized:
    A 2 taints, B 0 -> A: 100-floor(2*100/2)=0, B: 100."""
    taints = [
        {"key": "k1", "value": "v", "effect": "PreferNoSchedule"},
        {"key": "k2", "value": "v", "effect": "PreferNoSchedule"},
    ]
    nodes = [make_node("a", taints=taints), make_node("b")]
    got = plugin_scores(nodes, [], make_pod("p", cpu="1", memory="1Gi"),
                        iso(taint=1.0))
    assert got.tolist() == [0.0, 100.0]


def test_node_affinity_preferred_exact():
    """Terms weight 3 (matches A) and 5 (matches B): raw [3, 5] ->
    A floor(3*100/5)=60, B 100 (DefaultNormalizeScore, reverse=false)."""
    nodes = [make_node("a", labels={"disk": "ssd"}),
             make_node("b", labels={"net": "fast"})]
    aff = {"nodeAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": 3, "preference": {"matchExpressions": [
            {"key": "disk", "operator": "In", "values": ["ssd"]}]}},
        {"weight": 5, "preference": {"matchExpressions": [
            {"key": "net", "operator": "In", "values": ["fast"]}]}},
    ]}}
    got = plugin_scores(nodes, [], make_pod("p", cpu="1", memory="1Gi", affinity=aff),
                        iso(nodeaff=1.0))
    assert got.tolist() == [60.0, 100.0]


def test_interpod_affinity_preferred_exact():
    """Preferred affinity weight 4 to app=anchor on hostname; anchors: A x2,
    B x1, C x0 -> raw [8, 4, 0]; zero-initialized min/max -> floor(100*raw/8):
    [100, 50, 0]."""
    nodes = [make_node(n) for n in ("a", "b", "c")]
    seeds = [bound("an0", "a", labels={"app": "anchor"}),
             bound("an1", "a", labels={"app": "anchor"}),
             bound("an2", "b", labels={"app": "anchor"})]
    aff = {"podAffinity": {"preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": 4, "podAffinityTerm": {
            "labelSelector": {"matchLabels": {"app": "anchor"}},
            "topologyKey": "kubernetes.io/hostname"}}
    ]}}
    got = plugin_scores(nodes, seeds, make_pod("p", cpu="1", memory="1Gi", affinity=aff),
                        iso(interpod=1.0))
    assert got.tolist() == [100.0, 50.0, 0.0]


def test_selector_spread_exact():
    """Service selects app=web; placed web pods A:2 B:1 C:0; no zones ->
    floor(100*(max-cnt)/max) = [0, 50, 100]."""
    nodes = [make_node(n) for n in ("a", "b", "c")]
    sim_seeds = [bound(f"w{i}", "a", labels={"app": "web"}) for i in range(2)]
    sim_seeds += [bound("w2", "b", labels={"app": "web"})]
    svc = {"kind": "Service", "apiVersion": "v1",
           "metadata": {"name": "web", "namespace": "default"},
           "spec": {"selector": {"app": "web"}}}

    import jax.numpy as jnp

    sim = Simulator(copy.deepcopy(nodes))
    sim.model.services.append(svc)
    failed = sim.schedule_pods(copy.deepcopy(sim_seeds))
    assert not failed
    probe = make_pod("p", cpu="1", memory="1Gi", labels={"app": "web"})
    bt = sim.encode_batch([probe])
    tables, carry = sim._to_device(bt)
    g = int(bt.pod_group[0])
    feasible, _ = kernels.feasibility_jit(
        tables, carry, jnp.int32(g), jnp.int32(-1), jnp.asarray(True))
    sc = kernels.scores(tables, carry, jnp.int32(g), feasible, bt.n_zones,
                        enable_storage=False, w=iso(ss=1.0))
    assert np.asarray(sc)[:3].tolist() == [0.0, 50.0, 100.0]


def test_pod_topology_spread_score_exact():
    """ScheduleAnyway maxSkew=1 over zones z1={a,b}, z2={c}; matching seeds
    z1:3 z2:1. size=2 -> tpw=ln(4); raw=int(cnt*tpw): [4,4,1];
    normalize 100*(4+1-s)/4 int division: [25, 25, 100]."""
    nodes = [make_node("a", labels={"zone": "z1"}),
             make_node("b", labels={"zone": "z1"}),
             make_node("c", labels={"zone": "z2"})]
    seeds = [bound("s0", "a", labels={"app": "s"}),
             bound("s1", "a", labels={"app": "s"}),
             bound("s2", "b", labels={"app": "s"}),
             bound("s3", "c", labels={"app": "s"})]
    probe = make_pod("p", cpu="1", memory="1Gi", labels={"app": "s"})
    probe["spec"]["topologySpreadConstraints"] = [{
        "maxSkew": 1, "topologyKey": "zone",
        "whenUnsatisfiable": "ScheduleAnyway",
        "labelSelector": {"matchLabels": {"app": "s"}},
    }]
    got = plugin_scores(nodes, seeds, probe, iso(pts=1.0))
    assert got.tolist() == [25.0, 25.0, 100.0]


def test_image_locality_exact():
    """523MB image on A only, 2 nodes -> scaled = 523MB*(1/2) = 274202624;
    score = 100*(274202624-24117248)//(1048576000-24117248) = 24. B: 0."""
    mb = 1024 * 1024
    nodes = [make_node("a"), make_node("b")]
    nodes[0]["status"]["images"] = [
        {"names": ["registry/app:1"], "sizeBytes": 523 * mb}]
    nodes[1]["status"]["images"] = [
        {"names": ["registry/other:1"], "sizeBytes": 100 * mb}]
    probe = make_pod("p", cpu="1", memory="1Gi")
    probe["spec"]["containers"][0]["image"] = "registry/app:1"
    got = plugin_scores(nodes, [], probe, iso(image=1.0))
    assert got.tolist() == [24.0, 0.0]


def test_node_prefer_avoid_pods_exact():
    """A's preferAvoidPods annotation targets the pod's ReplicaSet controller
    -> 0 on A, 100 on B (node_prefer_avoid_pods.go)."""
    import json

    anno = json.dumps({"preferAvoidPods": [
        {"podSignature": {"podController": {
            "kind": "ReplicaSet", "name": "web-rs", "uid": "u1"}}}]})
    nodes = [
        make_node("a", annotations={
            "scheduler.alpha.kubernetes.io/preferAvoidPods": anno}),
        make_node("b"),
    ]
    probe = make_pod("p", cpu="1", memory="1Gi")
    probe["metadata"]["ownerReferences"] = [{
        "kind": "ReplicaSet", "name": "web-rs", "uid": "u1", "controller": True}]
    got = plugin_scores(nodes, [], probe, iso(avoid=1.0))
    assert got.tolist() == [0.0, 100.0]


def test_planted_off_by_one_would_fail():
    """Sanity on the harness itself: shifting any plugin's expected vector by
    one must not match (the tests have discriminating power)."""
    nodes = [make_node("a", cpu="10", memory="10Gi"),
             make_node("b", cpu="20", memory="20Gi")]
    seeds = [bound("s0", "a", cpu="3", memory="4Gi")]
    got = plugin_scores(nodes, seeds, make_pod("p", cpu="1", memory="1Gi"),
                        iso(least=1.0))
    assert got.tolist() != [56.0, 96.0]


def _nz_pod(containers, init=()):
    def ctr(req):
        c = {"name": "c", "image": "busybox"}
        if req is not None:
            c["resources"] = {"requests": dict(req)}
        return c

    spec = {"containers": [ctr(r) for r in containers]}
    if init:
        spec["initContainers"] = [ctr(r) for r in init]
    return {"metadata": {"name": "p"}, "spec": spec}


MI = 1024 * 1024


@pytest.mark.parametrize("containers,init,want", [
    # a set request counts as written, even below the default
    ([{"cpu": "10m", "memory": "10M"}], (), (10, 10_000_000)),
    # an explicit 0 counts as 0 (kube overrides only an unset request)
    ([{"cpu": "0", "memory": "0"}], (), (0, 0)),
    # unset: the defaults, 100m and 200Mi
    ([None], (), (100, 200 * MI)),
    ([{"cpu": "250m"}], (), (250, 200 * MI)),
    # per container, then summed
    ([{"cpu": "50m", "memory": "64Mi"}, None], (), (150, 264 * MI)),
    # init containers: a per-resource max against the sum
    ([{"cpu": "10m", "memory": "10M"}], [{"cpu": "500m", "memory": "1Gi"}],
     (500, 1024 * MI)),
    ([{"cpu": "300m", "memory": "1Gi"}], [{"cpu": "100m", "memory": "1Mi"}],
     (300, 1024 * MI)),
    # an init container with no request takes the defaults into the max
    ([{"cpu": "10m", "memory": "10M"}], [None], (100, 200 * MI)),
])
def test_pod_nonzero_cpu_mem_is_kube_rule(containers, init, want):
    """pkg/scheduler/util/non_zero.go GetNonzeroRequestForResource (v1.20):
    the default only where the request is unset; framework/types.go
    calculateResource sums containers and takes the max with each init
    container."""
    from open_simulator_tpu.ops.resources import pod_nonzero_cpu_mem

    got = pod_nonzero_cpu_mem(_nz_pod(containers, init))
    assert tuple(got.tolist()) == want
