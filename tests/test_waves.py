"""Wave scheduling == serial scan, placement for placement.

The wave kernel (ops/kernels.py schedule_wave) must reproduce the serial
one-pod-per-scan-step process exactly: every test runs the same pod sequence
through a waves-on and a waves-off Simulator and compares the per-(node,
workload) placement census and the per-group failure counts. Pods within one
scheduling group are interchangeable (the reference's selectHost tie-break is
random anyway, generic_scheduler.go:188), so the census — not pod names — is
the equality that matters.
"""

import copy

import pytest

from open_simulator_tpu.core import constants as C
from open_simulator_tpu.simulator.engine import Simulator
from open_simulator_tpu.utils.objutil import annotations_of, labels_of, name_of

from fixtures import make_node, make_pod, master_taint, master_toleration


def census_of(sim: Simulator):
    # keyed by scheduling signature, not app label: constraint-distinct pods
    # sharing one label must count as disagreements when the paths swap them
    from open_simulator_tpu.simulator.encode import scheduling_signature

    out = {}
    for i, pods in enumerate(sim.pods_on_node):
        for p in pods:
            key = (i, scheduling_signature(p))
            out[key] = out.get(key, 0) + 1
    return out


def run_both(nodes, batches, extract=None, services=None):
    """batches: list of pod lists scheduled via consecutive schedule_pods calls.
    Returns (wave_census, serial_census, wave_failed, serial_failed) plus, when
    `extract` is given, its per-sim result appended for each path."""
    results = []
    for waves in (True, False):
        sim = Simulator(copy.deepcopy(nodes))
        sim.use_waves = waves
        if services:
            from open_simulator_tpu.core.types import ResourceTypes

            sim.register_cluster_objects(
                ResourceTypes(services=copy.deepcopy(services)))
        failed = []
        for batch in batches:
            failed.extend(sim.schedule_pods(copy.deepcopy(batch)))
        fail_count = {}
        for up in failed:
            key = labels_of(up.pod).get("app") or name_of(up.pod)
            fail_count[key] = fail_count.get(key, 0) + 1
        results.append((census_of(sim), fail_count, extract(sim) if extract else None))
    (wc, wf, wx), (sc, sf, sx) = results
    if extract is None:
        return wc, sc, wf, sf
    return wc, sc, wf, sf, wx, sx


def replicas(name, n, start=0, **kw):
    kw.setdefault("labels", {"app": name})
    return [make_pod(f"{name}-{i}", **kw) for i in range(start, start + n)]


def anti_affinity(app):
    return {
        "podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {
                    "labelSelector": {"matchLabels": {"app": app}},
                    "topologyKey": "kubernetes.io/hostname",
                }
            ]
        }
    }


def test_wave_homogeneous_big_run():
    nodes = [make_node(f"n{i}", cpu="16", memory="32Gi") for i in range(12)]
    pods = replicas("web", 150, cpu="500m", memory="512Mi")
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf == {}
    assert sum(wc.values()) == 150


def test_wave_heterogeneous_nodes_and_exhaustion():
    # mixed capacities; pods overflow the cluster so the tail fails — the wave
    # path must fail the same NUMBER per group as serial
    nodes = (
        [make_node(f"big{i}", cpu="16", memory="32Gi") for i in range(3)]
        + [make_node(f"mid{i}", cpu="8", memory="8Gi") for i in range(4)]
        + [make_node(f"small{i}", cpu="2", memory="2Gi") for i in range(5)]
    )
    pods = replicas("fat", 80, cpu="2", memory="3Gi")
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc
    assert wf == sf
    assert wf.get("fat", 0) > 0  # the scenario actually overflows


def test_wave_taints_selectors_and_preferred_affinity():
    nodes = [
        make_node("master-1", taints=[master_taint()]),
        make_node("master-2", taints=[master_taint()]),
        make_node("gpuish-1", labels={"disk": "ssd", "zone-ish": "a"}),
        make_node("gpuish-2", labels={"disk": "ssd", "zone-ish": "b"}),
        make_node("plain-1"),
        make_node("plain-2", cpu="4", memory="4Gi"),
    ]
    pref = {
        "nodeAffinity": {
            "preferredDuringSchedulingIgnoredDuringExecution": [
                {"weight": 10,
                 "preference": {"matchExpressions": [
                     {"key": "disk", "operator": "In", "values": ["ssd"]}]}}
            ]
        }
    }
    batches = [
        replicas("tol", 16, cpu="200m", memory="256Mi",
                 tolerations=[master_toleration()]),
        replicas("ssdlover", 24, cpu="250m", memory="256Mi", affinity=pref),
        replicas("picky", 12, cpu="100m", memory="128Mi",
                 node_selector={"disk": "ssd"}),
    ]
    wc, sc, wf, sf = run_both(nodes, batches)
    assert wc == sc and wf == sf


def test_wave_hostname_anti_affinity_cap1():
    nodes = [make_node(f"n{i}") for i in range(10)]
    pods = replicas("spread", 14, cpu="100m", memory="128Mi",
                    affinity=anti_affinity("spread"))
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc
    # at most one per node; 4 pods cannot land
    assert all(v == 1 for v in wc.values())
    assert wf == sf == {"spread": 4}


def test_wave_anti_affinity_against_seeded_pods():
    # nodes already hosting app=spread pods are blocked from the start
    nodes = [make_node(f"n{i}") for i in range(6)]
    seed = [make_pod("pre-0", labels={"app": "spread"}, node_name="n2"),
            make_pod("pre-1", labels={"app": "spread"}, node_name="n4")]
    pods = replicas("spread", 6, cpu="100m", memory="128Mi",
                    affinity=anti_affinity("spread"))
    wc, sc, wf, sf = run_both(nodes, [seed, pods])
    assert wc == sc and wf == sf
    assert wf == {"spread": 2}  # 6 nodes - 2 seeded = 4 free slots


def test_wave_mixed_eligible_and_ineligible_runs():
    # hostPort pods are serial-only; they interleave with two eligible runs and
    # contend for the same capacity
    nodes = [make_node(f"n{i}", cpu="4", memory="8Gi") for i in range(8)]
    a = replicas("alpha", 24, cpu="300m", memory="512Mi")
    b = replicas("porty", 6, cpu="300m", memory="512Mi", host_ports=[8080])
    c = replicas("omega", 24, cpu="300m", memory="512Mi")
    wc, sc, wf, sf = run_both(nodes, [a + b + c])
    assert wc == sc and wf == sf


def test_wave_pod_affinity_to_other_group():
    # required pod affinity whose selector matches a DIFFERENT app: the counter
    # never matches the group itself, so the run stays wave-eligible
    nodes = [make_node(f"n{i}") for i in range(6)]
    anchors = [make_pod("anchor-0", labels={"app": "anchor"}, node_name="n1"),
               make_pod("anchor-1", labels={"app": "anchor"}, node_name="n3")]
    aff = {
        "podAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [
                {"labelSelector": {"matchLabels": {"app": "anchor"}},
                 "topologyKey": "kubernetes.io/hostname"}
            ]
        }
    }
    pods = replicas("follower", 12, cpu="100m", memory="128Mi", affinity=aff)
    wc, sc, wf, sf = run_both(nodes, [anchors, pods])
    assert wc == sc and wf == sf
    landed = {k[0] for k, v in wc.items() if k[1] == "follower"}
    assert landed <= {1, 3}


def test_wave_small_runs_stay_serial():
    # runs below WAVE_MIN ride the scan; behavior identical either way
    nodes = [make_node(f"n{i}") for i in range(4)]
    batches = [replicas(f"app{k}", 3, cpu="200m", memory="256Mi") for k in range(5)]
    wc, sc, wf, sf = run_both(nodes, [sum(batches, [])])
    assert wc == sc and wf == sf


def test_wave_depth_truncation_flat_scores():
    # one huge node whose score column is flat far beyond the kernel's table
    # depth (WAVE_BLOCK), next to small nodes: serial keeps filling the huge
    # node past depth-B, so the wave must not fall back to the small nodes'
    # lower-scored entries (the hidden-continuation guard)
    nodes = [make_node("huge", cpu="2000", memory="4000Gi", pods="5000")] + [
        make_node(f"small{i}", cpu="2", memory="2Gi") for i in range(4)
    ]
    pods = replicas("tiny", 400, cpu="10m", memory="16Mi")
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf


def test_wave_two_flat_columns_tie():
    # two equally huge nodes with identical flat columns: serial alternates on
    # integer score drops with lowest-index tie-break; waves must reproduce it
    nodes = [make_node(f"huge{i}", cpu="1000", memory="2000Gi", pods="4000")
             for i in range(2)]
    pods = replicas("tiny", 500, cpu="10m", memory="16Mi")
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf


def _hog(i, mem_gi):
    # a pre-bound pod holding memory but almost no cpu: the node's
    # BalancedAllocation column rises as cpu-heavy copies land on it
    return make_pod(f"hog-{i}", cpu="100m", memory=f"{mem_gi}Gi",
                    labels={"app": "hog"}, node_name=f"r{i}")


def _store_of(pods):
    from open_simulator_tpu.simulator.store import PodStore

    ps = PodStore()
    for p in pods:
        ps.add_block(p, 1)
    return ps


def _labelled_fold(n):
    # three templates that differ only in a label no selector reads
    return [make_pod(f"{app}-{i}", cpu="200m", memory="256Mi",
                     labels={"app": app})
            for app in ("a", "b", "c") for i in range(n)]


ORDER_CASES = {
    # a flat column past the table depth (the depth-truncation shape)
    "flat_past_depth": (
        [make_node("huge", cpu="2000", memory="4000Gi", pods="5000")]
        + [make_node(f"small{i}", cpu="2", memory="2Gi") for i in range(4)],
        [], lambda: replicas("tiny", 400, cpu="10m", memory="16Mi"), False),
    # two equal flat columns: ties go to the lower node index
    "two_flat_columns": (
        [make_node(f"huge{i}", cpu="1000", memory="2000Gi", pods="4000")
         for i in range(2)],
        [], lambda: replicas("tiny", 500, cpu="10m", memory="16Mi"), False),
    # rising BalancedAllocation columns on memory-heavy nodes
    "rising_balanced": (
        [make_node(f"r{i}", cpu="16", memory="32Gi") for i in range(6)],
        [_hog(i, 4 * i + 4) for i in range(6)],
        lambda: replicas("cpu", 60, cpu="1", memory="64Mi"), False),
    # a capacity-1 wave (hostname self anti-affinity)
    "cap1": (
        [make_node(f"n{i}") for i in range(10)], [],
        lambda: replicas("spread", 14, cpu="100m", memory="128Mi",
                         affinity=anti_affinity("spread")), False),
    # three templates folded into one class, dispatched as one wave
    "fold_three_templates": (
        [make_node(f"n{i}", cpu="4", memory="8Gi") for i in range(7)], [],
        lambda: _labelled_fold(20), True),
}


@pytest.mark.parametrize("case", sorted(ORDER_CASES))
def test_wave_order_matches_serial_per_pod(case):
    """schedule_wave's `order` gives each pod the node the serial scan gives
    that pod, not just the same per-node counts."""
    nodes, seeded, make, store = ORDER_CASES[case]
    got = {}
    for waves in (True, False):
        sim = Simulator(copy.deepcopy(nodes))
        sim.use_waves = waves
        if seeded:
            sim.schedule_pods(copy.deepcopy(seeded))
        pods = make()
        batch = _store_of(pods) if store else pods
        failed = sim.schedule_pods(batch)
        if store:
            rows = batch.node_rows()
            got[waves] = [sim.na.names[int(r)] if r >= 0 else None
                          for r in rows]
        else:
            got[waves] = [(p.get("spec") or {}).get("nodeName")
                          for p in batch]
            for up in failed:
                got[waves][batch.index(up.pod)] = None
        if waves:
            kinds = [s[0] for s in sim._segments(sim._last_tables,
                                                 len(pods))]
            assert kinds == ["wave"], kinds
    assert got[True] == got[False]


def test_wave_segments_split():
    # direct check of the segmentation: eligible big run + tiny run + forced pod
    nodes = [make_node(f"n{i}") for i in range(4)]
    sim = Simulator(copy.deepcopy(nodes))
    pods = (replicas("big", 10, cpu="100m", memory="128Mi")
            + replicas("tiny", 2, cpu="100m", memory="128Mi"))
    bt = sim.encode_batch(copy.deepcopy(pods))
    segs = sim._segments(bt, len(pods))
    kinds = [s[0] for s in segs]
    assert kinds == ["wave", "serial"]
    assert segs[0][1:3] == (0, 10)
    assert segs[1][1:3] == (10, 2)


# ---------------------------------------------------------------- spread waves ----
#
# DoNotSchedule topology-spread groups are wave-eligible via the kernel's live
# filter + inertness cut (schedule_wave dns_live). Every scenario below runs the
# same pods through waves-on and waves-off engines; censuses must match exactly,
# including when the constraint binds hard, when domains are blocked from the
# start, and when the min-domain count rises mid-run.


def spread(app, key="zone", max_skew=1):
    return [{
        "maxSkew": max_skew,
        "topologyKey": key,
        "whenUnsatisfiable": "DoNotSchedule",
        "labelSelector": {"matchLabels": {"app": app}},
    }]


def zoned_nodes(counts, **kw):
    """counts: pods-per-zone node counts, e.g. [4, 2, 1] builds 7 nodes in 3 zones."""
    nodes = []
    for z, c in enumerate(counts):
        for i in range(c):
            nodes.append(make_node(f"z{z}-n{i}", labels={"zone": f"zone-{z}"}, **kw))
    return nodes


def spread_replicas(app, n, max_skew=1, key="zone", start=0, **kw):
    pods = replicas(app, n, start=start, **kw)
    for p in pods:
        p["spec"]["topologySpreadConstraints"] = spread(app, key=key, max_skew=max_skew)
    return pods


def test_spread_wave_balanced_zones():
    nodes = zoned_nodes([3, 3, 3])
    pods = spread_replicas("web", 60, cpu="100m", memory="128Mi")
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf == {}


def test_spread_wave_skewed_zones_constraint_binds():
    # zone-2 has one node: once it fills, skew blocks the big zones — the wave
    # must cut exactly where serial's feasible set changes
    nodes = zoned_nodes([6, 3, 1], cpu="4", memory="8Gi")
    pods = spread_replicas("skew", 80, max_skew=1, cpu="200m", memory="256Mi")
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf
    assert sum(wc.values()) < 80  # the single-node zone caps total placements


def test_spread_wave_blocked_at_start_then_min_rise():
    # seed zone-0 far above the others: zone-0 starts blocked and is re-admitted
    # only when the min rises (the (b) cut direction)
    nodes = zoned_nodes([2, 2, 2], cpu="16", memory="32Gi")
    seed = [make_pod(f"seed-{i}", labels={"app": "riser"}, node_name="z0-n0",
                     cpu="100m", memory="128Mi") for i in range(5)]
    for p in seed:
        p["spec"]["topologySpreadConstraints"] = spread("riser")
    pods = spread_replicas("riser", 40, max_skew=2, cpu="100m", memory="128Mi")
    wc, sc, wf, sf = run_both(nodes, [seed, pods])
    assert wc == sc and wf == sf


def test_spread_wave_maxskew_1_tight():
    nodes = zoned_nodes([1, 1, 1, 1], cpu="16", memory="32Gi")
    pods = spread_replicas("tight", 37, max_skew=1, cpu="50m", memory="64Mi")
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf == {}


def test_spread_wave_missing_topo_key_nodes():
    # two nodes lack the zone label entirely: they are never eligible domains and
    # the spread filter must keep excluding them on both paths
    nodes = zoned_nodes([2, 2]) + [make_node(f"plain{i}") for i in range(2)]
    pods = spread_replicas("keyed", 30, cpu="100m", memory="128Mi")
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf
    landed_plain = {k for k in wc if k[0] >= 4}
    assert not landed_plain


def test_spread_wave_hostname_key():
    # hostname-keyed spread: every node is its own domain, so maxSkew=1 caps the
    # per-node difference at one — a much larger domain count than zones
    nodes = [make_node(f"n{i}") for i in range(7)]
    pods = spread_replicas("host", 40, key="kubernetes.io/hostname",
                           cpu="100m", memory="128Mi")
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf


def test_spread_wave_non_self_matching_static():
    # the constraint tracks a DIFFERENT app: counters never move during the run,
    # so the group rides the plain (dns-static) wave path
    nodes = zoned_nodes([2, 2])
    anchors = [make_pod("anchor-0", labels={"app": "anchor"}, node_name="z0-n0"),
               make_pod("anchor-1", labels={"app": "anchor"}, node_name="z1-n0")]
    pods = replicas("obs", 20, cpu="100m", memory="128Mi")
    for p in pods:
        p["spec"]["topologySpreadConstraints"] = spread("anchor", max_skew=3)
    wc, sc, wf, sf = run_both(nodes, [anchors, pods])
    assert wc == sc and wf == sf


def test_spread_wave_two_constraints():
    # zone + hostname constraints together on one group
    nodes = zoned_nodes([3, 2], cpu="8", memory="16Gi")
    pods = spread_replicas("dual", 25, max_skew=2, cpu="100m", memory="128Mi")
    for p in pods:
        p["spec"]["topologySpreadConstraints"] += spread(
            "dual", key="kubernetes.io/hostname", max_skew=2)
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf


def test_spread_wave_segments_are_waves():
    # the segmentation routes a self-matching dns group onto the epoch-batched
    # affinity wave (any topology cardinality since the multi-round epochs)
    nodes = zoned_nodes([2, 2])
    sim = Simulator(copy.deepcopy(nodes))
    pods = spread_replicas("seg", 12, cpu="100m", memory="128Mi")
    bt = sim.encode_batch(copy.deepcopy(pods))
    segs = sim._segments(bt, len(pods))
    assert [s[0] for s in segs] == ["affinity"]


# ------------------------------------------------------------------- gpu waves ----
#
# Shared-GPU groups (no pre-assigned gpu-index) are wave-eligible: depletion is
# unit-countable so capacity is closed-form, and the aggregate commit replays
# the per-node allocator exactly (schedule_wave gpu_live). Censuses, failure
# counts, AND the per-device ledgers must match the serial path.

GI = 1 << 30


def wave_gpu_node(name, count=2, total_mem=32 * GI, cpu="64", memory="256Gi"):
    caps = {"alibabacloud.com/gpu-count": str(count),
            "alibabacloud.com/gpu-mem": str(total_mem)}
    return make_node(name, cpu=cpu, memory=memory, extra_resources=caps)


def wave_gpu_replicas(app, n, mem_gi=4, count=1, **kw):
    pods = replicas(app, n, cpu="500m", memory="1Gi", **kw)
    for p in pods:
        p["metadata"]["annotations"] = {
            "alibabacloud.com/gpu-mem": f"{mem_gi}Gi",
            "alibabacloud.com/gpu-count": str(count),
        }
    return pods


def run_both_gpu(nodes, batches):
    """run_both + per-device ledger comparison."""
    return run_both(nodes, batches, extract=lambda sim: [
        tuple(s.dev_used) if s else None for s in sim.gpu_host.states
    ])


def test_gpu_wave_single_gpu_binpack():
    nodes = [wave_gpu_node(f"g{i}", count=4, total_mem=64 * GI) for i in range(6)]
    pods = wave_gpu_replicas("trainer", 50, mem_gi=4)
    wc, sc, wf, sf, wl, sl = run_both_gpu(nodes, [pods])
    assert wc == sc and wf == sf == {}
    assert wl == sl


def test_gpu_wave_exhaustion_and_ledger():
    # 2 devices x 8Gi per node, 3Gi pods: 2 units per device with 2Gi stranded —
    # the floor() unit math and the tightest-fit replay both matter here
    nodes = [wave_gpu_node(f"g{i}", count=2, total_mem=16 * GI, cpu="128",
                           memory="512Gi") for i in range(4)]
    pods = wave_gpu_replicas("tight", 30, mem_gi=3)
    wc, sc, wf, sf, wl, sl = run_both_gpu(nodes, [pods])
    assert wc == sc and wf == sf
    assert wf.get("tight", 0) > 0  # 4 nodes * 4 units = 16 < 30
    assert wl == sl


def test_gpu_wave_multi_gpu_greedy():
    nodes = [wave_gpu_node(f"g{i}", count=4, total_mem=32 * GI) for i in range(3)]
    pods = wave_gpu_replicas("dual", 16, mem_gi=4, count=2)
    wc, sc, wf, sf, wl, sl = run_both_gpu(nodes, [pods])
    assert wc == sc and wf == sf
    assert wl == sl


def test_gpu_wave_mixed_with_plain_pods():
    nodes = [wave_gpu_node(f"g{i}", count=2, total_mem=16 * GI, cpu="8",
                           memory="16Gi") for i in range(5)]
    a = wave_gpu_replicas("gp", 12, mem_gi=2)
    b = replicas("plain", 20, cpu="250m", memory="512Mi")
    wc, sc, wf, sf, wl, sl = run_both_gpu(nodes, [a + b])
    assert wc == sc and wf == sf
    assert wl == sl


def test_gpu_wave_preassigned_index_stays_serial():
    nodes = [wave_gpu_node(f"g{i}") for i in range(3)]
    sim = Simulator(copy.deepcopy(nodes))
    pods = wave_gpu_replicas("pre", 10)
    for p in pods:
        p["metadata"]["annotations"]["alibabacloud.com/gpu-index"] = "1"
    bt = sim.encode_batch(copy.deepcopy(pods))
    segs = sim._segments(bt, len(pods))
    assert [s[0] for s in segs] == ["serial"]


def test_gpu_wave_segments_are_waves():
    nodes = [wave_gpu_node(f"g{i}") for i in range(3)]
    sim = Simulator(copy.deepcopy(nodes))
    pods = wave_gpu_replicas("seg", 10)
    bt = sim.encode_batch(copy.deepcopy(pods))
    segs = sim._segments(bt, len(pods))
    assert [s[0] for s in segs] == ["wave"]
    assert segs[0][5] is True  # gpu_live


def test_wave_host_ports_cap1():
    # a run of identical host-port pods is a capacity-1-per-node wave: first
    # copy claims the port, placements spread one per node, surplus fails
    nodes = [make_node(f"hp{i}") for i in range(6)]
    pods = replicas("hp", 9, cpu="100m", memory="128Mi", host_ports=[8080])
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf
    assert sum(wc.values()) == 6 and wf == {"hp": 3}


def test_wave_host_ports_block_later_groups():
    # the wave's aggregate commit must write the port bits: a later group
    # wanting the same port only fits nodes the first group left free
    def app_census(sim):
        out = {}
        for pods in sim.pods_on_node:
            for p in pods:
                app = labels_of(p).get("app")
                out[app] = out.get(app, 0) + 1
        return out

    # first group length >= WAVE_MIN so it truly runs as a WAVE segment: this
    # is the test that the wave's aggregate commit writes the port bits the
    # second group's filter then reads
    nodes = [make_node(f"hpx{i}") for i in range(12)]
    first = replicas("first", 8, cpu="100m", memory="128Mi", host_ports=[9090])
    second = replicas("second", 8, cpu="100m", memory="128Mi", host_ports=[9090])
    wc, sc, wf, sf, wapps, sapps = run_both(nodes, [first + second],
                                            extract=app_census)
    assert wc == sc and wf == sf
    assert wapps == sapps == {"first": 8, "second": 4}
    assert wf == {"second": 4}


def test_wave_host_ports_disabled_filter_unbounded(tmp_path):
    # with the NodePorts plugin disabled, host ports are inert: no cap1, no
    # conflicts — every pod schedules (and waves must agree with serial)
    import yaml

    from open_simulator_tpu.api.schedconfig import parse_scheduler_config

    cfg_path = tmp_path / "sched.yaml"
    cfg_path.write_text(yaml.safe_dump({
        "apiVersion": "kubescheduler.config.k8s.io/v1beta1",
        "kind": "KubeSchedulerConfiguration",
        "profiles": [{"plugins": {"filter": {"disabled": [{"name": "NodePorts"}]}}}],
    }))
    cfg = parse_scheduler_config(str(cfg_path))
    nodes = [make_node(f"hpd{i}") for i in range(3)]
    pods = replicas("hpd", 9, cpu="100m", memory="128Mi", host_ports=[7070])
    results = []
    for waves in (True, False):
        sim = Simulator(copy.deepcopy(nodes), sched_config=cfg)
        sim.use_waves = waves
        failed = sim.schedule_pods(copy.deepcopy(pods))
        results.append((census_of(sim), len(failed)))
    assert results[0] == results[1]
    assert results[0][1] == 0 and sum(results[0][0].values()) == 9


def _service(name, selector, namespace="default"):
    return {"apiVersion": "v1", "kind": "Service",
            "metadata": {"name": name, "namespace": namespace},
            "spec": {"selector": dict(selector)}}


def _run_both_with_services(nodes, services, batches):
    wc, sc, wf, sf, wn, sn = run_both(
        nodes, batches, services=services,
        extract=lambda sim: [len(p) for p in sim.pods_on_node])
    return wc, sc, sum(wf.values()), sum(sf.values()), wn, sn


def test_ss_live_service_backed_deployment_waves():
    # a service-backed workload spreads against its own per-node/zone counts
    # (live SelectorSpread) — routed through the fused group-serial kernel,
    # which must match the pure serial scan placement for placement
    nodes = [make_node(f"ss{i}", labels={"topology.kubernetes.io/zone": f"z{i % 3}"})
             for i in range(9)]
    svc = _service("web-svc", {"app": "web"})
    pods = replicas("web", 24, cpu="200m", memory="256Mi",
                    labels={"app": "web"})
    wc, sc, wf, sf, wn, sn = _run_both_with_services(nodes, [svc], [pods])
    assert wc == sc and wf == sf
    assert sum(wn) == 24 and wf == 0
    # SelectorSpread actually spreads: per-node counts stay near-balanced
    assert max(wn) - min(wn) <= 2


def test_ss_live_seeded_counts_respected():
    # pods of the same service already placed (earlier batch) must seed the
    # live per-node counts: the second batch avoids the loaded nodes first
    nodes = [make_node(f"ssb{i}") for i in range(4)]
    svc = _service("api-svc", {"app": "api"})
    first = replicas("api", 4, cpu="100m", memory="128Mi", labels={"app": "api"})
    second = replicas("api", 8, start=4, cpu="100m", memory="128Mi",
                      labels={"app": "api"})
    wc, sc, wf, sf, wn, sn = _run_both_with_services(nodes, [svc], [first, second])
    assert wc == sc and wf == sf
    assert sum(wn) == 12 and max(wn) == 3 and min(wn) == 3


def test_ss_live_zero_weight_rides_plain_wave(tmp_path):
    # SelectorSpread weight 0 via scheduler config makes the term inert: the
    # group becomes plain-wave eligible and must still match serial
    import yaml

    from open_simulator_tpu.api.schedconfig import parse_scheduler_config
    from open_simulator_tpu.core.types import ResourceTypes

    cfg_path = tmp_path / "sched.yaml"
    cfg_path.write_text(yaml.safe_dump({
        "apiVersion": "kubescheduler.config.k8s.io/v1beta1",
        "kind": "KubeSchedulerConfiguration",
        "profiles": [{"plugins": {"score": {"disabled": [{"name": "SelectorSpread"}]}}}],
    }))
    cfg = parse_scheduler_config(str(cfg_path))
    nodes = [make_node(f"ssz{i}") for i in range(4)]
    svc = _service("z-svc", {"app": "z"})
    pods = replicas("z", 12, cpu="100m", memory="128Mi", labels={"app": "z"})
    results = []
    for waves in (True, False):
        sim = Simulator(copy.deepcopy(nodes), sched_config=cfg)
        sim.use_waves = waves
        sim.register_cluster_objects(ResourceTypes(services=[copy.deepcopy(svc)]))
        failed = sim.schedule_pods(copy.deepcopy(pods))
        results.append((census_of(sim), len(failed)))
        if waves:
            # eligibility: plain wave (not the spread kernel), ss_live False
            segs = {s[0] for s in sim._segments(sim._last_tables, 12)}
            assert segs == {"wave"}
    assert results[0] == results[1]


def test_ss_live_with_self_anti_affinity_cap1():
    # service + hostname self-anti-affinity: live SelectorSpread AND cap1
    nodes = [make_node(f"ssa{i}") for i in range(6)]
    svc = _service("a-svc", {"app": "a"})
    pods = replicas("a", 9, cpu="100m", memory="128Mi",
                    labels={"app": "a"}, affinity=anti_affinity("a"))
    wc, sc, wf, sf, wn, sn = _run_both_with_services(nodes, [svc], [pods])
    assert wc == sc and wf == sf
    assert sum(wn) == 6 and wf == 3 and max(wn) == 1


def test_spread_epoch_wave_hostname_topology():
    # hostname-level self spread (one domain per node) routes through the
    # epoch-batched spread wave (>=64 domains) and must match serial exactly
    nodes = [make_node(f"ep{i}", pods="4") for i in range(80)]
    pods = replicas("ep", 200, cpu="50m", memory="64Mi", labels={"app": "ep"})
    for p in pods:
        p["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 2, "topologyKey": "kubernetes.io/hostname",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "ep"}},
        }]
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf
    # skew bound actually held: per-node counts within maxSkew of each other
    per_node = {}
    for (n, _), c in wc.items():
        per_node[n] = per_node.get(n, 0) + c
    assert max(per_node.values()) - min(per_node.get(i, 0) for i in range(80)) <= 2


def test_spread_epoch_wave_hostname_maxskew1_tight():
    # maxSkew=1 hostname spread at overflow: the strictest budget shape
    nodes = [make_node(f"et{i}") for i in range(70)]
    pods = replicas("et", 100, cpu="50m", memory="64Mi", labels={"app": "et"})
    for p in pods:
        p["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "et"}},
        }]
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf


def _sa_constraint(app, max_skew=1, topo="topology.kubernetes.io/zone"):
    return {"maxSkew": max_skew, "topologyKey": topo,
            "whenUnsatisfiable": "ScheduleAnyway",
            "labelSelector": {"matchLabels": {"app": app}}}


def test_sa_live_soft_spread_waves():
    # ScheduleAnyway soft spread: score-only, counters move with placements —
    # routed through the fused kernel, must match the pure serial scan
    nodes = [make_node(f"sa{i}", labels={"topology.kubernetes.io/zone": f"z{i % 3}"})
             for i in range(9)]
    pods = replicas("soft", 21, cpu="300m", memory="256Mi", labels={"app": "soft"})
    for p in pods:
        p["spec"]["topologySpreadConstraints"] = [_sa_constraint("soft")]
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf


def test_sa_live_nodes_missing_topology_key():
    # nodes without the topology key are score-ignored (pts=0) but remain
    # schedulable — the sentinel-masked counter update must keep parity
    nodes = [make_node(f"sam{i}", labels={"topology.kubernetes.io/zone": f"z{i % 2}"})
             for i in range(4)]
    nodes += [make_node(f"sam-nokey{i}") for i in range(2)]
    pods = replicas("softm", 18, cpu="500m", memory="512Mi", labels={"app": "softm"})
    for p in pods:
        p["spec"]["topologySpreadConstraints"] = [_sa_constraint("softm", max_skew=2)]
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf


def test_sa_live_mixed_with_dns_constraint():
    # one soft + one hard constraint on the same pods: dns filter state and
    # sa score state both live in the fused kernel
    nodes = [make_node(f"sad{i}", labels={"topology.kubernetes.io/zone": f"z{i % 3}"})
             for i in range(6)]
    pods = replicas("mix", 15, cpu="300m", memory="256Mi", labels={"app": "mix"})
    for p in pods:
        p["spec"]["topologySpreadConstraints"] = [
            _sa_constraint("mix", max_skew=2),
            {"maxSkew": 3, "topologyKey": "topology.kubernetes.io/zone",
             "whenUnsatisfiable": "DoNotSchedule",
             "labelSelector": {"matchLabels": {"app": "mix"}}},
        ]
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf


def test_wave_host_ports_cap1_survives_fit_disabled(tmp_path):
    # NodeResourcesFit disabled + NodePorts enabled: capacity is unbounded but
    # the port clamp must survive — waves may not stack same-port copies
    import yaml

    from open_simulator_tpu.api.schedconfig import parse_scheduler_config

    cfg_path = tmp_path / "sched.yaml"
    cfg_path.write_text(yaml.safe_dump({
        "apiVersion": "kubescheduler.config.k8s.io/v1beta1",
        "kind": "KubeSchedulerConfiguration",
        "profiles": [{"plugins": {
            "filter": {"disabled": [{"name": "NodeResourcesFit"}]}}}],
    }))
    cfg = parse_scheduler_config(str(cfg_path))
    nodes = [make_node(f"hpf{i}") for i in range(6)]
    pods = replicas("hpf", 9, cpu="100m", memory="128Mi", host_ports=[8081])
    results = []
    for waves in (True, False):
        sim = Simulator(copy.deepcopy(nodes), sched_config=cfg)
        sim.use_waves = waves
        failed = sim.schedule_pods(copy.deepcopy(pods))
        results.append((census_of(sim), len(failed)))
    assert results[0] == results[1]
    assert results[0][1] == 3 and sum(results[0][0].values()) == 6


@pytest.mark.parametrize("seed", [7, 23, 101, 555, 1234, 9999])
def test_wave_fuzz_mixed_workloads(seed):
    """Randomized waves-vs-serial sweep: random node shapes (zones, taints,
    GPU annotations, tight capacities) and random workload blocks cycling
    plain / tolerating / self-anti-affinity / zone-spread / shared-GPU /
    host-port pods, scheduled across two batches. Census + failure equality
    must hold for every seed — this is the guard that the wave eligibility
    split, the adaptive block depth, and the hidden-continuation logic stay
    exact under shapes no hand-written case anticipated."""
    import random

    rng = random.Random(seed)
    n_nodes = rng.randint(6, 14)
    n_zones = rng.choice([0, 2, 3])
    nodes = []
    for i in range(n_nodes):
        labels = {}
        if n_zones:
            labels["topology.kubernetes.io/zone"] = f"z{i % n_zones}"
        taints = (
            [{"key": "dedicated", "value": "batch", "effect": "NoSchedule"}]
            if rng.random() < 0.25 else None
        )
        annotations = None
        if rng.random() < 0.4:
            annotations = {}
        node = make_node(
            f"fz{i}",
            cpu=f"{rng.randint(2000, 9000)}m",
            memory=str(rng.randint(4, 12) << 30),
            pods=str(rng.randint(8, 40)),
            labels=labels,
            taints=taints,
            annotations=annotations,
        )
        if rng.random() < 0.35:  # GPU node (gpushare extended resource)
            for sect in ("capacity", "allocatable"):
                node["status"][sect]["alibabacloud.com/gpu-count"] = "2"
                node["status"][sect]["alibabacloud.com/gpu-mem"] = str(2 * 8 << 30)
        nodes.append(node)

    def block(bi, kind, n):
        app = f"fz-app{bi}"
        # one constraint flavor per block, so replicas stay one group (runs
        # >= WAVE_MIN actually reach the batched kernels)
        when = rng.choice(["DoNotSchedule", "ScheduleAnyway"]) if kind == 3 else None
        skew = rng.choice([1, 2])
        pods = []
        for i in range(n):
            kw = dict(labels={"app": app},
                      cpu=f"{rng.randint(50, 800)}m",
                      memory=str(rng.randint(64, 1024) << 20))
            if kind == 1:
                kw["tolerations"] = [{"key": "dedicated", "operator": "Exists",
                                      "effect": "NoSchedule"}]
            elif kind == 2:
                kw["affinity"] = anti_affinity(app)
            elif kind == 3 and n_zones:
                pass  # spread added below
            elif kind == 4:
                kw["annotations"] = {"alibabacloud.com/gpu-mem": str(4 << 30),
                                     "alibabacloud.com/gpu-count": "1"}
            elif kind == 5:
                kw["host_ports"] = [30000 + bi]
            p = make_pod(f"{app}-{i}", **kw)
            if kind == 3 and n_zones:
                p["spec"]["topologySpreadConstraints"] = [{
                    "maxSkew": skew,
                    "topologyKey": "topology.kubernetes.io/zone",
                    "whenUnsatisfiable": when,
                    "labelSelector": {"matchLabels": {"app": app}},
                }]
            pods.append(p)
        return pods

    all_pods = []
    services = []
    for bi in range(rng.randint(4, 8)):
        kind = rng.randint(0, 5)
        all_pods.extend(block(bi, kind, rng.randint(2, 30)))
        # ~1/3 of blocks are service-backed → live SelectorSpread coverage
        if kind != 3 and rng.random() < 0.35:
            services.append(_service(f"svc-{bi}", {"app": f"fz-app{bi}"}))
    cut = rng.randint(0, len(all_pods))
    wc, sc, wf, sf = run_both(nodes, [all_pods[:cut], all_pods[cut:]],
                              services=services)
    assert wc == sc
    assert wf == sf


def test_wave_f32_ulp_stress():
    # odd capacities and request sizes drive cumulative f32 rounding close to
    # ULP boundaries; the wave score table multiplies (j * req) where serial
    # accumulates one pod at a time, so census equality here guards the eps
    # slack in the NodeResourcesFit bound (ADVICE r2: ULP stress)
    nodes = [make_node(f"odd{i}", cpu=f"{3001 + 7 * i}m",
                       memory=str((7 << 30) + 4097 * i)) for i in range(9)]
    pods = replicas("ulp", 260, cpu="77m", memory=str((333 << 20) + 13))
    wc, sc, wf, sf = run_both(nodes, [pods])
    assert wc == sc and wf == sf


def test_spread_epoch_wave_preloaded_nodes_budget_checked():
    """Regression (code review repro): 64 of 67 identical nodes pre-loaded via
    bound pods, 14 hostname maxSkew=1 spread pods. The skipping epoch must
    never take sorted-tail entries whose budgets were not evaluated — the bug
    stacked 3/3/4 pods on the empty nodes where serial placed 1 per node."""
    nodes = [make_node(f"pre{i}", cpu="4") for i in range(67)]
    preload = []
    for i in range(64):
        preload.append(make_pod(f"seed-{i}", cpu="1", memory="128Mi",
                                node_name=f"pre{i}"))
    pods = replicas("tight", 14, cpu="100m", memory="64Mi",
                    labels={"app": "tight"})
    for p in pods:
        p["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "tight"}},
        }]
    wc, sc, wf, sf = run_both(nodes, [preload + pods])
    assert wc == sc and wf == sf
    # maxSkew=1 must hold: no (node, signature) census bucket exceeds 1 pod —
    # seeds are bound one per node and spread pods may not stack either
    assert all(c <= 1 for c in wc.values())


def test_spread_wave_threshold_env_knob(monkeypatch):
    """OPEN_SIMULATOR_SPREAD_WAVE_MIN_DOMAINS is the break-even fallback:
    live-DNS groups below the threshold reroute onto the fused group-serial
    scan — placements must not change (routing is purely a performance
    choice), and malformed values fall back silently to the default (0 =
    the affinity wave always runs)."""
    nodes = [make_node(f"kn{i}", labels={"topology.kubernetes.io/zone": f"z{i % 3}"})
             for i in range(9)]
    pods = replicas("kn", 18, cpu="200m", memory="256Mi", labels={"app": "kn"})
    for p in pods:
        p["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 2, "topologyKey": "topology.kubernetes.io/zone",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "kn"}},
        }]

    def run(env):
        if env is not None:
            monkeypatch.setenv("OPEN_SIMULATOR_SPREAD_WAVE_MIN_DOMAINS", env)
        else:
            monkeypatch.delenv("OPEN_SIMULATOR_SPREAD_WAVE_MIN_DOMAINS",
                               raising=False)
        sim = Simulator(copy.deepcopy(nodes))
        failed = sim.schedule_pods(copy.deepcopy(pods))
        return census_of(sim), len(failed), sim._wave_eligibility(0).kind

    default_c, default_f, default_route = run(None)
    assert default_route == "affinity"  # default 0: the wave always runs
    high_c, high_f, high_route = run("64")
    assert high_route == "spread"       # 3 zones < 64: fused scan fallback
    assert (high_c, high_f) == (default_c, default_f)  # placements identical
    bad_c, bad_f, bad_route = run("not-a-number")
    assert bad_route == "affinity"      # malformed → default threshold
    assert (bad_c, bad_f) == (default_c, default_f)
