"""Double-encode parity + behavior of the columnar host path
(simulator/store.py): a Simulator fed a PodStore/NodeStore must encode
BIT-IDENTICAL BatchTables and produce bit-identical placements to the same
workload as plain dicts — including the workloads that route OFF the bulk
path (gpushare, local storage, pre-bound pods, armed preemption), where the
store transparently materializes. The lazy read-back boundary, bulk-commit
rollback, streaming chunk equivalence, and the serve image staged from a
store are covered here too (ISSUE 15 acceptance)."""

from __future__ import annotations

import copy
import dataclasses
import json
import os

import numpy as np
import pytest

from open_simulator_tpu.resilience import faults
from open_simulator_tpu.simulator.encode import scheduling_signature
from open_simulator_tpu.simulator.engine import Simulator
from open_simulator_tpu.simulator.store import (
    EncodedRows,
    NodeStore,
    PodStore,
)
from open_simulator_tpu.utils.synth import (
    synth_cluster,
    synth_cluster_store,
    synth_node,
    synth_pod,
)


def assert_tables_equal(a, b):
    """BatchTables bit-identity: every field, dtype and shape included."""
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, np.ndarray):
            assert va.dtype == vb.dtype, f.name
            assert va.shape == vb.shape, f.name
            assert np.array_equal(va, vb), f.name
        else:
            assert va == vb, f.name


def census_of(sim):
    out = {}
    for i, pods in enumerate(sim.pods_on_node):
        for p in pods:
            key = (i, scheduling_signature(p))
            out[key] = out.get(key, 0) + 1
    return out


def fail_names(failed):
    return sorted(u.pod["metadata"]["name"] for u in failed)


def pod_template(**kw):
    t = synth_pod(0, **kw)
    t["metadata"].pop("name", None)
    return t


def run_both(nodes, pods, store_nodes, store_pods, use_waves=True):
    """Schedule the dict form and the store form; assert encode + placement
    bit-identity; return the two simulators."""
    simd = Simulator(nodes, use_mesh=False)
    simd.use_waves = use_waves
    sims = Simulator(store_nodes, use_mesh=False)
    sims.use_waves = use_waves
    btd = simd.encode_batch(copy.deepcopy(pods))
    bts = sims.encode_batch(store_pods[:])
    assert_tables_equal(btd, bts)
    simd2 = Simulator(nodes, use_mesh=False)
    simd2.use_waves = use_waves
    sims2 = Simulator(store_nodes, use_mesh=False)
    sims2.use_waves = use_waves
    failed_d = simd2.schedule_pods(copy.deepcopy(pods))
    failed_s = sims2.schedule_pods(store_pods)
    assert census_of(simd2) == census_of(sims2)
    assert fail_names(failed_d) == fail_names(failed_s)
    return simd2, sims2


# ------------------------------------------------------ double-encode parity --


def test_parity_plain():
    nodes, pods = synth_cluster(64, 600)
    ns, ps = synth_cluster_store(64, 600)
    run_both(nodes, pods, ns, ps)


def test_parity_hard_predicates():
    # zones + taints + tolerations + self anti-affinity + zone spread:
    # wave, affinity-wave, spread, and serial segments all exercised
    nodes, pods = synth_cluster(48, 400, hard_predicates=True)
    ns, ps = synth_cluster_store(48, 400, hard_predicates=True)
    run_both(nodes, pods, ns, ps)


def test_parity_hard_serial_oracle():
    nodes, pods = synth_cluster(32, 200, hard_predicates=True)
    ns, ps = synth_cluster_store(32, 200, hard_predicates=True)
    run_both(nodes, pods, ns, ps, use_waves=False)


def gpu_cluster(n_nodes, n_pods):
    nodes = []
    for i in range(n_nodes):
        n = synth_node(i)
        for sect in ("capacity", "allocatable"):
            n["status"][sect]["alibabacloud.com/gpu-count"] = "4"
            n["status"][sect]["alibabacloud.com/gpu-mem"] = str(4 * 16 << 30)
        nodes.append(n)
    pods = []
    for i in range(n_pods):
        p = synth_pod(i)
        p["metadata"].setdefault("annotations", {})[
            "alibabacloud.com/gpu-mem"] = str(4 << 30)
        p["metadata"]["annotations"]["alibabacloud.com/gpu-count"] = "1"
        pods.append(p)
    return nodes, pods


def test_parity_gpushare():
    # gpu state forces the store off every fast path (NodeStore materializes
    # at ctor, commits go per-pod through reserve()) — parity must still be
    # exact, annotations included
    nodes, pods = gpu_cluster(16, 80)
    node_tmpl = copy.deepcopy(nodes[0])
    node_tmpl["metadata"] = {}
    ns = NodeStore().add_block(node_tmpl, 16, name_fmt="node-{0:05d}",
                               index_labels=("node-index",))
    pod_tmpl = copy.deepcopy(pods[0])
    pod_tmpl["metadata"].pop("name")
    ps = PodStore().add_block(pod_tmpl, 80, name_fmt="pod-{0:06d}")
    simd, sims = run_both(nodes, pods, ns, ps)
    # reserve() wrote per-pod gpu-index annotations on materialized dicts
    pd = simd.pods_on_node[0][0]
    pss = sims.pods_on_node[0][0]
    assert (pd["metadata"]["annotations"].get("alibabacloud.com/gpu-index")
            == pss["metadata"]["annotations"].get(
                "alibabacloud.com/gpu-index"))


def test_parity_local_storage():
    from open_simulator_tpu.utils.storage import VG, NodeStorage

    st = NodeStorage(vgs=[VG("vg0", 200 << 30)], devices=[])
    sc = {"apiVersion": "storage.k8s.io/v1", "kind": "StorageClass",
          "metadata": {"name": "open-local-lvm"},
          "provisioner": "local.csi.aliyun.com",
          "parameters": {"volumeType": "LVM"}}
    nodes = []
    for i in range(8):
        n = synth_node(i)
        n["metadata"].setdefault("annotations", {})[
            "simon/node-local-storage"] = st.to_json()
        nodes.append(n)
    pods = []
    for i in range(24):
        p = synth_pod(i)
        p["metadata"].setdefault("annotations", {})[
            "simon/pod-local-storage"] = json.dumps({"volumes": [
                {"size": str(1 << 30), "kind": "LVM",
                 "scName": "open-local-lvm"}]})
        pods.append(p)
    node_tmpl = copy.deepcopy(nodes[0])
    node_tmpl["metadata"].pop("name")
    node_tmpl["metadata"].pop("labels")
    ns = NodeStore().add_block(node_tmpl, 8, name_fmt="node-{0:05d}",
                               index_labels=("node-index",))
    pod_tmpl = copy.deepcopy(pods[0])
    pod_tmpl["metadata"].pop("name")
    ps = PodStore().add_block(pod_tmpl, 24, name_fmt="pod-{0:06d}")

    from open_simulator_tpu.core.types import ResourceTypes

    simd = Simulator(nodes, use_mesh=False)
    simd.register_cluster_objects(ResourceTypes(storage_classes=[sc]))
    sims = Simulator(ns, use_mesh=False)
    sims.register_cluster_objects(ResourceTypes(storage_classes=[sc]))
    assert sims.local_host.enabled  # store fell back to materialized dicts
    failed_d = simd.schedule_pods(copy.deepcopy(pods))
    failed_s = sims.schedule_pods(ps)
    assert census_of(simd) == census_of(sims)
    assert fail_names(failed_d) == fail_names(failed_s)


def test_parity_pre_bound():
    nodes, _ = synth_cluster(16, 0)
    pods = [synth_pod(i) for i in range(40)]
    bound = synth_pod(99)
    bound["metadata"]["name"] = "bound-one"
    bound["spec"]["nodeName"] = "node-00003"
    homeless = synth_pod(98)
    homeless["metadata"]["name"] = "homeless-one"
    homeless["spec"]["nodeName"] = "node-nowhere"
    dict_pods = pods[:20] + [bound] + pods[20:] + [homeless]

    ps = PodStore()
    ps.add_block(pod_template(), 20, name_fmt="pod-{0:06d}")
    ps.add_pod(copy.deepcopy(bound))
    tail = pod_template()
    ps.add_block(tail, 20, name_fmt="pod-{0:06d}", name_start=20)
    ps.add_pod(copy.deepcopy(homeless))
    # names must line up with the dict form for the fail/census comparison
    simd = Simulator(nodes, use_mesh=False)
    sims = Simulator(copy.deepcopy(nodes), use_mesh=False)
    failed_d = simd.schedule_pods(copy.deepcopy(dict_pods))
    failed_s = sims.schedule_pods(ps)
    assert census_of(simd) == census_of(sims)
    assert fail_names(failed_d) == fail_names(failed_s)
    assert len(simd.homeless) == len(sims.homeless) == 1


def test_parity_preemption_mixed_priorities():
    # mixed priorities arm the PostFilter: the store falls back to the
    # per-pod commit path (bulk is gated off) and must match exactly
    nodes = [synth_node(i, cpu_milli=1000, pods=8) for i in range(4)]
    low = pod_template(cpu_milli=400)
    low["spec"]["priority"] = 0
    high = pod_template(cpu_milli=400)
    high["spec"]["priority"] = 100
    dict_pods = []
    for i in range(8):
        p = copy.deepcopy(low)
        p["metadata"]["name"] = f"low-{i:02d}"
        dict_pods.append(p)
    for i in range(4):
        p = copy.deepcopy(high)
        p["metadata"]["name"] = f"high-{i:02d}"
        dict_pods.append(p)
    ps = PodStore()
    ps.add_block(copy.deepcopy(low), 8, name_fmt="low-{0:02d}", name_start=0)
    ps.add_block(copy.deepcopy(high), 4, name_fmt="high-{0:02d}",
                 name_start=0)
    simd = Simulator(nodes, use_mesh=False)
    sims = Simulator(copy.deepcopy(nodes), use_mesh=False)
    failed_d = simd.schedule_pods(copy.deepcopy(dict_pods))
    failed_s = sims.schedule_pods(ps)
    assert census_of(simd) == census_of(sims)
    assert fail_names(failed_d) == fail_names(failed_s)
    assert len(simd.preempted) == len(sims.preempted)


def test_parity_preemption_after_bulk_commit():
    # call 1: uniform priority → BULK commit; call 2: higher priority pods
    # arrive, arm preemption, and evict bulk-committed victims — the
    # _sig_rec fallback must resolve their signature/seq from the columns
    nodes = [synth_node(i, cpu_milli=1000, pods=8) for i in range(4)]
    low = pod_template(cpu_milli=400)
    low["spec"]["priority"] = 0
    high = pod_template(cpu_milli=400)
    high["spec"]["priority"] = 100
    dict_low = []
    for i in range(8):
        p = copy.deepcopy(low)
        p["metadata"]["name"] = f"low-{i:02d}"
        dict_low.append(p)
    dict_high = []
    for i in range(4):
        p = copy.deepcopy(high)
        p["metadata"]["name"] = f"high-{i:02d}"
        dict_high.append(p)
    ps_low = PodStore().add_block(copy.deepcopy(low), 8,
                                  name_fmt="low-{0:02d}", name_start=0)
    ps_high = PodStore().add_block(copy.deepcopy(high), 4,
                                   name_fmt="high-{0:02d}", name_start=0)
    simd = Simulator(nodes, use_mesh=False)
    sims = Simulator(copy.deepcopy(nodes), use_mesh=False)
    simd.schedule_pods(copy.deepcopy(dict_low))
    sims.schedule_pods(ps_low)
    failed_d = simd.schedule_pods(copy.deepcopy(dict_high))
    failed_s = sims.schedule_pods(ps_high)
    assert census_of(simd) == census_of(sims)
    assert fail_names(failed_d) == fail_names(failed_s)
    assert len(simd.preempted) == len(sims.preempted)
    if sims.preempted:
        victims = sorted(p["pod"]["metadata"]["name"]
                         for p in sims.preempted)
        victims_d = sorted(p["pod"]["metadata"]["name"]
                           for p in simd.preempted)
        assert victims == victims_d


# ---------------------------------------------------------- lazy read-back --


def test_lazy_readback_boundary():
    ns, ps = synth_cluster_store(32, 300)
    sim = Simulator(ns, use_mesh=False)
    sim.schedule_pods(ps)
    assert len(ps.base.cache) == 0  # nothing read back yet
    assert sim.pods_on_node.total() == 300  # counting never materializes
    assert len(ps.base.cache) == 0
    pod = sim.pods_on_node[0][0]  # flattening one node materializes it only
    assert pod["spec"]["nodeName"] == "node-00000"
    assert pod["status"] == {"phase": "Running"}
    assert 0 < len(ps.base.cache) <= len(sim.pods_on_node[0])
    # identity is stable across reads
    assert sim.pods_on_node[0][0] is pod


def test_materialized_before_commit_is_patched():
    ns, ps = synth_cluster_store(16, 50)
    early = ps[3]  # materialized BEFORE scheduling
    assert "nodeName" not in early.get("spec", {})
    sim = Simulator(ns, use_mesh=False)
    sim.schedule_pods(ps)
    # the bulk commit patched the already-materialized dict in place
    assert early["spec"].get("nodeName", "").startswith("node-")
    assert early.get("status") == {"phase": "Running"}


# -------------------------------------------------------- rollback / faults --


def test_bulk_commit_rollback_on_fault():
    ns, ps = synth_cluster_store(16, 120)
    early = ps[5]
    sim = Simulator(ns, use_mesh=False)
    faults.install_plan(faults.FaultPlan.parse("site=commit,attempt=100"))
    try:
        with pytest.raises(Exception):
            sim.schedule_pods(ps)
    finally:
        faults.clear_plan()
    # full rollback: no placements, columns reset, cached dict clean
    assert sim.pods_on_node.total() == 0
    assert not sim.placed or all(
        not pg.node_counts for pg in sim.placed.values())
    assert int((ps.node_rows() >= 0).sum()) == 0
    assert "nodeName" not in early.get("spec", {})
    assert "status" not in early
    # and the SAME store schedules cleanly afterwards
    sim2 = Simulator(ns, use_mesh=False)
    sim2.schedule_pods(ps)
    assert sim2.pods_on_node.total() == 120


def test_bulk_fault_arrivals_replay_equal():
    # maybe_fail_bulk must fire the same arrival a per-event loop would
    plan_a = faults.FaultPlan.parse("site=commit,attempt=7")
    for k in (3, 4):
        try:
            plan_a.on_arrivals("commit", k)
        except Exception:
            break
    plan_b = faults.FaultPlan.parse("site=commit,attempt=7")
    fired_at = None
    for i in range(1, 8):
        try:
            plan_b.on_arrival("commit")
        except Exception:
            fired_at = i
            break
    assert plan_a.trace == plan_b.trace
    assert fired_at == 7


# ----------------------------------------------------------------- streaming --


def test_streaming_chunks_bit_identical():
    nodes, pods = synth_cluster(48, 900, hard_predicates=True)
    base = Simulator(nodes, use_mesh=False)
    base_failed = base.schedule_pods(copy.deepcopy(pods))
    os.environ["OPEN_SIMULATOR_STREAM_PODS"] = "128"
    try:
        streamed = Simulator(nodes, use_mesh=False)
        assert streamed._stream_chunk == 128
        st_failed = streamed.schedule_pods(copy.deepcopy(pods))
    finally:
        os.environ.pop("OPEN_SIMULATOR_STREAM_PODS", None)
    assert census_of(base) == census_of(streamed)
    assert fail_names(base_failed) == fail_names(st_failed)
    from open_simulator_tpu.obs import REGISTRY

    assert REGISTRY.values().get("simon_stream_chunks_total", 0) > 0


def test_streaming_store_chunks_bit_identical():
    ns, ps = synth_cluster_store(32, 700)
    nodes, pods = synth_cluster(32, 700)
    base = Simulator(nodes, use_mesh=False)
    base.schedule_pods(pods)
    os.environ["OPEN_SIMULATOR_STREAM_PODS"] = "96"
    try:
        streamed = Simulator(ns, use_mesh=False)
        # store batches stream at a coarser floor — force it down for the
        # test by driving the chunk directly
        streamed._stream_chunk = 96
        failed = streamed._schedule_run_streaming(ps, 96)
    finally:
        os.environ.pop("OPEN_SIMULATOR_STREAM_PODS", None)
    assert not failed
    assert census_of(base) == census_of(streamed)


# ------------------------------------------------------------------- probing --


def test_probe_store_parity():
    nodes, pods = synth_cluster(24, 300)
    ns, ps = synth_cluster_store(24, 300)
    simd = Simulator(nodes, use_mesh=False)
    sims = Simulator(ns, use_mesh=False)
    assert simd.probe_pods(pods) == sims.probe_pods(ps)
    # probes never commit: the store's columns stay untouched
    assert int((ps.node_rows() >= 0).sum()) == 0


# ------------------------------------------------------------------- serving --


def test_serve_image_staged_from_store():
    from open_simulator_tpu.serve.image import ResidentImage

    ns, _ = synth_cluster_store(32, 0)
    nodes, _ = synth_cluster(32, 0)
    img_s = ResidentImage.try_build(ns)
    img_d = ResidentImage.try_build(nodes)
    assert img_s is not None and img_d is not None
    request = [synth_pod(i, cpu_milli=500) for i in range(6)]
    rs = img_s.session(copy.deepcopy(request)).run()
    rd = img_d.session(copy.deepcopy(request)).run()
    # staged-from-store == staged-from-dicts == resident contract fields
    for k in ("scheduled", "total", "unscheduled", "utilization"):
        assert rs[k] == rd[k], (k, rs, rd)
    assert rs["scheduled"] == 6 and rs["path"] != "fresh"


def test_serve_session_rides_store_batch():
    from open_simulator_tpu.serve.image import ResidentImage

    ns, _ = synth_cluster_store(16, 0)
    img = ResidentImage.try_build(ns)
    assert img is not None
    req = PodStore().add_block(pod_template(cpu_milli=300), 5,
                               name_fmt="req-{0:02d}", name_start=0)
    session = img.session(req)
    assert isinstance(session.batch, EncodedRows)
    assert img.eligible(session.batch, req) is None
    out = session.run()
    assert out["scheduled"] == 5 and out["path"] != "fresh"


# ------------------------------------------------------------- store basics --


def test_store_views_share_commit_state():
    ns, ps = synth_cluster_store(8, 40)
    view = ps[10:30]
    assert len(view) == 20
    assert view[0]["metadata"]["name"] == "pod-000010"
    dup = copy.deepcopy(ps)
    sim = Simulator(ns, use_mesh=False)
    sim.schedule_pods(ps)
    assert int((ps.node_rows() >= 0).sum()) == 40
    # the deepcopy took its own columns: still uncommitted
    assert int((dup.node_rows() >= 0).sum()) == 0


def test_encoded_rows_sequence_protocol():
    rows = EncodedRows(np.array([3, 3, 5], np.int32),
                       np.array([-1, -1, 2], np.int32))
    assert len(rows) == 3
    assert list(rows) == [(3, -1), (3, -1), (5, 2)]
    assert rows[0] == (3, -1)
    assert rows[2] == (5, 2)
    sub = rows[1:]
    assert isinstance(sub, EncodedRows) and len(sub) == 2


# ------------------------------------------------------------ review fixes --


def test_bulk_fault_window_preserves_later_specs():
    # two specs inside one bulk window: the counter must stop AT the firing
    # arrival (the serial loop died there), so a failover replay's window
    # still contains the second spec
    plan = faults.FaultPlan.parse(
        "site=commit,attempt=5;site=commit,attempt=8")
    with pytest.raises(Exception):
        plan.on_arrivals("commit", 10)   # fires @5, counter stops at 5
    assert plan.arrivals["commit"] == 5
    with pytest.raises(Exception):
        plan.on_arrivals("commit", 10)   # replay window (5, 15] fires @8
    assert [t[:2] for t in plan.trace] == [("commit", 5), ("commit", 8)]


def test_bulk_rollback_restores_prior_status():
    # an explicit pod with a pre-existing status rides the store, gets bulk
    # committed, and a rollback must restore the ORIGINAL status object —
    # the per-pod commit log's caller-owned-dict contract
    nodes, _ = synth_cluster(8, 0)
    ns = NodeStore()
    t = synth_node(0)
    t["metadata"] = {}
    ns.add_block(t, 8, name_fmt="node-{0:05d}", index_labels=("node-index",))
    prior_status = {"phase": "Pending"}
    special = synth_pod(7)
    special["status"] = prior_status
    ps = PodStore()
    ps.add_block(pod_template(), 10, name_fmt="pod-{0:06d}")
    ps.add_pod(special)
    sim = Simulator(ns, use_mesh=False)
    faults.install_plan(faults.FaultPlan.parse("site=fetch,attempt=1"))
    try:
        with pytest.raises(Exception):
            sim.schedule_pods(ps)
    finally:
        faults.clear_plan()
    assert special.get("status") is prior_status
    assert "nodeName" not in special.get("spec", {})
    # and a clean re-run commits it with Running like any other pod
    sim2 = Simulator(ns, use_mesh=False)
    assert not sim2.schedule_pods(ps)
    assert special["status"] == {"phase": "Running"}


def test_pods_on_node_snapshot_prunes_read_registrations():
    ns, ps = synth_cluster_store(64, 100)
    sim = Simulator(ns, use_mesh=False)
    sim.schedule_pods(ps)
    for _ in sim.pods_on_node:   # read-side full iteration registers empties
        pass
    assert len(sim.pods_on_node._lists) == 64
    snap = sim.pods_on_node.snapshot()
    # snapshot pruned the empty registrations back to touched nodes only
    assert len(sim.pods_on_node._lists) == len(snap["lists"])
    assert len(snap["lists"]) < 64 or sim.pods_on_node.total() == 100


def test_nodestore_capacity_only_resources():
    # a template advertising an extended resource only under status.capacity
    # must intern the axis exactly like the dict path (node_allocatable's
    # capacity fallback)
    t = {"apiVersion": "v1", "kind": "Node", "metadata": {}, "spec": {},
         "status": {"capacity": {"cpu": "4000m", "memory": str(8 << 30),
                                 "pods": "32", "example.com/widget": "2"}}}
    ns = NodeStore().add_block(t, 4, name_fmt="node-{0:05d}")
    sim = Simulator(ns, use_mesh=False)
    assert "example.com/widget" in sim.axis.names
    p = pod_template()
    p["spec"]["containers"][0]["resources"]["requests"][
        "example.com/widget"] = "1"
    failed = sim.schedule_pods(PodStore().add_block(p, 8,
                                                    name_fmt="pod-{0:06d}"))
    assert not failed  # 2 widgets x 4 nodes covers 8 one-widget pods


# ------------------------------------------------ many-block node stores --

ZONE = "topology.kubernetes.io/zone"
REGION = "topology.kubernetes.io/region"
DEDICATED = {"key": "synth/dedicated", "value": "batch",
             "effect": "NoSchedule"}


def node_tmpl(cpu_milli=32000, unschedulable=False, taints=None):
    t = synth_node(0, cpu_milli=cpu_milli)
    t["metadata"] = {}
    if unschedulable:
        t["spec"]["unschedulable"] = True
    if taints:
        t["spec"]["taints"] = taints
    return t


def store_and_dicts(blocks):
    """A NodeStore from (template, count, add_block options) recipes, and
    the same nodes written out as dicts node by node."""
    ns = NodeStore()
    nodes = []
    for tmpl, count, kw in blocks:
        ns.add_block(tmpl, count, name_fmt="node-{0:05d}", **kw)
        for _ in range(count):
            i = len(nodes)
            name = f"node-{i:05d}"
            labels = dict(kw.get("labels") or {})
            labels["kubernetes.io/hostname"] = name
            for k in kw.get("index_labels", ()):
                labels[k] = str(i)
            if kw.get("zone_cycle"):
                key, fmt, mod = kw["zone_cycle"]
                labels[key] = fmt.format(i % mod)
            n = copy.deepcopy(tmpl)
            n["metadata"] = {"name": name, "labels": labels}
            taint = kw.get("taint")
            if taint and i % taint[1] == 0:
                n["spec"]["taints"] = [dict(taint[0])]
            nodes.append(n)
    return ns, nodes


def decoded_columns(na):
    """Every NodeArrays column with interned ids decoded to their values."""
    def dec(table, ids):
        return [table.value(int(v)) if v else None for v in ids]

    return {
        "names": na.names,
        "name_ids": dec(na.values, na.name_ids),
        "labels": {k: dec(na.values, col) for k, col in na.label_vals.items()},
        "alloc": (na.alloc.dtype, na.alloc.tolist()),
        "zones": dec(na.zones, na.zone_id),
        "zone_ids": na.zone_id.tolist(),
        "taints": na.taints,
        "unschedulable": na.unschedulable.tolist(),
    }


def _zone_runs(seed, n):
    rng = np.random.default_rng(seed)
    t = node_tmpl()
    out, made = [], 0
    while made < n:
        c = int(min(rng.integers(1, 4), n - made))
        out.append((t, c, {"labels": {ZONE: f"moon-{rng.integers(1, 4)}"}}))
        made += c
    return out


def _two_templates():
    a, b = node_tmpl(), node_tmpl(cpu_milli=16000, unschedulable=True)
    return [(a if k % 2 == 0 else b, 3 + k % 4,
             {"labels": {ZONE: f"zone-{k % 3}"}}) for k in range(24)]


def _regions():
    t = node_tmpl()
    return [(t, 4, {"labels": {REGION: f"r{k % 2}", ZONE: f"z{k % 3}"}})
            for k in range(12)] + [
        (t, 5, {"labels": {"failure-domain.beta.kubernetes.io/zone": "old"}}),
        (t, 3, {"labels": {REGION: "r0", ZONE: ""}}),
        (t, 4, {})]


def _blank_labels():
    t = node_tmpl()
    beta_region = "failure-domain.beta.kubernetes.io/region"
    beta_zone = "failure-domain.beta.kubernetes.io/zone"
    # an empty value falls through to the beta label; a constant hostname
    # label gives way to the node's name
    return [(t, 3, {"labels": {REGION: "", beta_region: "rb", ZONE: "",
                               beta_zone: "zb"}}),
            (t, 2, {"labels": {ZONE: ""}}),
            (t, 4, {"labels": {"kubernetes.io/hostname": "pinned",
                               ZONE: "z1"}}),
            (t, 3, {"labels": {beta_zone: "zb", beta_region: "rb"}})]


def _zone_cycle():
    t = node_tmpl()
    # the cycle starts mid-period and a short one covers part of it: zone
    # ids must follow the nodes' first appearances
    return [(t, 5, {"labels": {ZONE: "zone-a"}}),
            (t, 13, {"zone_cycle": (ZONE, "zone-{0}", 4)}),
            (t, 7, {"labels": {ZONE: "zone-1"}}),
            (t, 3, {"zone_cycle": (ZONE, "zone-{0}", 8),
                    "labels": {"pool": "x"}}),
            (t, 9, {"labels": {ZONE: "zone-a"}}),
            (t, 6, {"zone_cycle": (REGION, "r{0}", 2),
                    "labels": {ZONE: "zone-1"}})]


def _index_labels():
    t = node_tmpl()
    return [(t, 10, {"index_labels": ("node-index",),
                     "labels": {ZONE: "zone-0"}}),
            (t, 6, {"labels": {ZONE: "zone-1"}}),
            (t, 10, {"index_labels": ("node-index", "rack"),
                     "labels": {ZONE: "zone-0"}}),
            (t, 4, {"index_labels": (ZONE,)})]


def _taints():
    t = node_tmpl()
    prefer = node_tmpl(taints=[{"key": "soft", "value": "",
                                "effect": "PreferNoSchedule"}])
    return [(t, 7, {"taint": (DEDICATED, 3), "labels": {ZONE: "zone-0"}}),
            (prefer, 5, {"labels": {ZONE: "zone-1"}}),
            (t, 11, {"taint": (DEDICATED, 3), "labels": {ZONE: "zone-1"}}),
            (prefer, 8, {"taint": (DEDICATED, 3), "labels": {ZONE: "zone-2"}})]


def _one_block():
    return [(node_tmpl(), 40, {"index_labels": ("node-index",),
                               "zone_cycle": (ZONE, "zone-{0}", 8),
                               "taint": (DEDICATED, 10)})]


@pytest.mark.parametrize("blocks,kinds", [
    pytest.param(lambda: _zone_runs(7, 600), 3, id="zone_runs"),
    pytest.param(_two_templates, 6, id="two_templates"),
    pytest.param(_regions, 9, id="regions"),
    pytest.param(_blank_labels, 4, id="blank_labels"),
    pytest.param(_zone_cycle, 4, id="zone_cycle"),
    pytest.param(_index_labels, 3, id="index_labels"),
    pytest.param(_taints, 4, id="taints"),
    pytest.param(_one_block, 1, id="one_block"),
])
def test_parity_many_block_store(blocks, kinds):
    ns, nodes = store_and_dicts(blocks())
    _, pods = synth_cluster(len(nodes), 150, hard_predicates=True)
    _, ps = synth_cluster_store(len(nodes), 150, hard_predicates=True)
    simd, sims = run_both(nodes, pods, ns, ps)
    assert decoded_columns(sims.na) == decoded_columns(simd.na)
    assert sims.na.n_kinds == kinds


def test_node_store_work_counts(monkeypatch):
    from open_simulator_tpu.ops import resources
    from open_simulator_tpu.utils import trace

    calls = []
    real = resources.node_allocatable

    def counted(node):
        calls.append(id(node))
        return real(node)

    monkeypatch.setattr(resources, "node_allocatable", counted)
    t = node_tmpl()
    ns = NodeStore()
    for k in range(3000):
        ns.add_block(t, 1 + k % 2, name_fmt="node-{0:05d}",
                     labels={ZONE: f"moon-{k % 3}"})
    trace.start_collection()
    Simulator(ns, use_mesh=False)
    roots = trace.stop_collection()
    assert calls == [id(t)]
    init = [s for s in roots if s.name == "init"][-1]
    nodes_span = next(c for c in init.children if c.name == "init.nodes")
    assert nodes_span.meta == {"blocks": 3000, "kinds": 3}


def test_store_node_arrays_extend_matches_dicts():
    ns, nodes = store_and_dicts(_taints())
    added = synth_node(len(nodes), n_zones=3, taint_every=1)
    sims = Simulator(ns, use_mesh=False)
    simd = Simulator(nodes, use_mesh=False)
    sims.na.extend([copy.deepcopy(added)])
    simd.na.extend([copy.deepcopy(added)])
    assert decoded_columns(sims.na) == decoded_columns(simd.na)
    assert sims.na.nodes[len(nodes)] == simd.na.nodes[len(nodes)]
