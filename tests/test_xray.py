"""simonxray flight-recorder tests.

The acceptance contract: recording must be a pure OBSERVER — placements,
failure reasons, and probe counts bit-identical with recording on vs off on
every kernel route (wave / affinity / group-serial spread / serial / probe /
preemption) — while every unscheduled pod yields a kube-parity reason whose
per-reason node counts sum to the node count, unknown pods are clean
errors, and records survive a mid-run guard failover with the backend_path
attached.
"""

import copy
import json
import os

import pytest

from open_simulator_tpu.obs import xray
from open_simulator_tpu.resilience import guard
from open_simulator_tpu.simulator.encode import scheduling_signature
from open_simulator_tpu.simulator.engine import Simulator

from fixtures import make_node, make_pod

ZONE = "topology.kubernetes.io/zone"


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    xray.disable()
    yield
    xray.disable()


@pytest.fixture()
def recorder(tmp_path):
    rec = xray.enable(str(tmp_path / "trace"))
    yield rec
    xray.disable()


def census_of(sim):
    out = {}
    for i, pods in enumerate(sim.pods_on_node):
        for p in pods:
            key = (i, scheduling_signature(p))
            out[key] = out.get(key, 0) + 1
    return out


def run_pair(nodes, batches, tmp_path, use_waves=True):
    """Schedule the same batches with recording OFF then ON; assert the
    census and failure reasons are bit-identical; return (sim_on, failed_on,
    recorder)."""
    results = []
    for on in (False, True):
        if on:
            rec = xray.enable(str(tmp_path / "trace"))
        sim = Simulator(copy.deepcopy(nodes))
        sim.use_waves = use_waves
        failed = []
        for batch in batches:
            failed.extend(sim.schedule_pods(copy.deepcopy(batch)))
        results.append((sim, failed))
    (sim_off, failed_off), (sim_on, failed_on) = results
    assert census_of(sim_on) == census_of(sim_off)
    assert [u.reason for u in failed_on] == [u.reason for u in failed_off]
    return sim_on, failed_on, rec


def zoned(n, n_zones, **kw):
    return [make_node(f"n{i}", labels={ZONE: f"z{i % n_zones}"}, **kw)
            for i in range(n)]


def replicas(name, n, **kw):
    kw.setdefault("labels", {"app": name})
    return [make_pod(f"{name}-{i}", **kw) for i in range(n)]


def with_spread(pods, app, when="DoNotSchedule", topo=ZONE):
    for p in pods:
        p["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 1, "topologyKey": topo, "whenUnsatisfiable": when,
            "labelSelector": {"matchLabels": {"app": app}}}]
    return pods


def test_component_names_match_kernel_order():
    # xray.COMPONENT_NAMES is duplicated from kernels.COMPONENT_ORDER so the
    # offline explain path never imports jax; they must never drift
    from open_simulator_tpu.ops.kernels import COMPONENT_ORDER

    assert tuple(xray.COMPONENT_NAMES) == tuple(COMPONENT_ORDER)


# --------------------------------------------------- bit-identity per route ---


def test_wave_route_bit_identical_and_recorded(tmp_path):
    nodes = [make_node(f"n{i}", cpu="8") for i in range(6)]
    sim, _, rec = run_pair(nodes, [replicas("web", 40, cpu="200m")], tmp_path)
    exp = rec.explain("default/web-0")
    assert exp["result_name"] == "scheduled"
    assert exp["segment"]["kind"] == "wave"
    assert exp["node_name"] == sim.na.names[exp["node"]]
    assert exp["node_scores"]["components"]  # per-plugin breakdown present


def test_affinity_route_bit_identical_with_epoch_stats(tmp_path):
    nodes = zoned(8, 4, cpu="8")
    pods = with_spread(replicas("dns", 24, cpu="100m", memory="128Mi"), "dns")
    sim, _, rec = run_pair(nodes, [pods], tmp_path)
    exp = rec.explain("default/dns-3")
    assert exp["segment"]["kind"] == "affinity"
    st = exp["segment"]["stats"]  # the PR 6 fast path is attributable
    assert st["epochs"] >= 1 and st["rounds"] + st["head_fallbacks"] >= 1


def test_spread_route_bit_identical(tmp_path):
    # ScheduleAnyway terms route to the fused group-serial scan
    nodes = zoned(6, 3, cpu="8")
    pods = with_spread(replicas("sa", 20, cpu="100m", memory="128Mi"), "sa",
                       when="ScheduleAnyway")
    _, _, rec = run_pair(nodes, [pods], tmp_path)
    exp = rec.explain("default/sa-0")
    assert exp["segment"]["kind"] == "spread"


def test_serial_route_bit_identical(tmp_path):
    nodes = [make_node(f"n{i}", cpu="8") for i in range(5)]
    pods = [make_pod(f"mix-{i}", cpu=f"{100 + 7 * (i % 9)}m")
            for i in range(30)]  # distinct specs: runs shorter than WAVE_MIN
    _, _, rec = run_pair(nodes, [pods], tmp_path)
    exp = rec.explain("default/mix-11")
    assert exp["segment"]["kind"] == "serial"
    assert exp["result_name"] == "scheduled"


def test_probe_route_bit_identical(tmp_path):
    nodes = [make_node(f"n{i}", cpu="4") for i in range(4)]
    pods = replicas("probe", 30, cpu="900m")

    def probe(on):
        if on:
            xray.enable(str(tmp_path / "trace"))
        sim = Simulator(copy.deepcopy(nodes))
        return sim.probe_pods(copy.deepcopy(pods))

    off = probe(False)
    on = probe(True)
    assert on == off
    # the probe left NO pod rows (probes never materialize placements) but
    # one summary record
    rec = xray.active()
    assert rec.counts()["pods"] == 0
    xray.disable()
    tr = xray.XrayTrace.load(str(tmp_path / "trace"))
    assert tr.probes and tr.probes[0]["scheduled"] == off[0]
    assert tr.probes[0]["total"] == off[1]


def test_preemption_route_bit_identical_with_victim_chain(tmp_path):
    nodes = [make_node("n0", cpu="4")]
    low = replicas("low", 2, cpu="2")
    for p in low:
        p["spec"]["priority"] = 0
    hi = make_pod("hi", cpu="4")
    hi["spec"]["priority"] = 100
    sim, failed, rec = run_pair(nodes, [low + [hi]], tmp_path)
    assert [e["pod"]["metadata"]["name"] for e in sim.preempted] == [
        "low-0", "low-1"]
    exp = rec.explain("default/hi")
    assert exp["result_name"] == "unschedulable"
    assert exp["nominated_node"] == "n0"
    assert exp["victims"] == ["default/low-0", "default/low-1"]
    assert sum(exp["reasons"].values()) == 1  # the one (full) node
    victim = rec.explain("default/low-0")
    assert victim["result_name"] == "preempted"
    assert victim["preempted_by"] == "default/hi"


def test_bound_and_homeless_pods_recorded(tmp_path):
    nodes = [make_node("n0", cpu="8")]
    pods = [make_pod("pinned", node_name="n0"),
            make_pod("lost", node_name="ghost-node"),
            make_pod("free", cpu="100m")]
    _, _, rec = run_pair(nodes, [pods], tmp_path)
    assert rec.explain("default/pinned")["result_name"] == "bound"
    assert rec.explain("default/lost")["result_name"] == "homeless"
    free = rec.explain("default/free")
    assert free["result_name"] == "scheduled"
    # the decision set is attributed to the DISPATCH batch, not the earlier
    # direct-commit batch the bound/homeless rows landed in
    assert free["set_record"]["batch"] == free["batch"]
    assert rec.explain("default/pinned")["batch"] != free["batch"]


# ---------------------------------------------------- reason-count invariant --


def test_every_unscheduled_reason_sums_to_node_count(tmp_path):
    """Mixed fixture: resource exhaustion, taints, unmatched node selector —
    every unscheduled pod's per-reason node counts must sum to N (the kube
    FitError invariant) and its string must render '0/N nodes are
    available'."""
    nodes = ([make_node(f"n{i}", cpu="2") for i in range(4)]
             + [make_node("tainted", cpu="16", taints=[{
                 "key": "dedicated", "value": "infra",
                 "effect": "NoSchedule"}])])
    pods = (replicas("fill", 8, cpu="1")
            + [make_pod("too-big", cpu="64"),
               make_pod("nowhere", cpu="100m",
                        node_selector={"disk": "ssd"}),
               make_pod("both", cpu="64", node_selector={"disk": "ssd"})])
    _, failed, rec = run_pair(nodes, [pods], tmp_path)
    unscheduled = {u.pod["metadata"]["name"] for u in failed}
    assert {"too-big", "nowhere", "both"} <= unscheduled
    n = len(nodes)
    for name in unscheduled:
        exp = rec.explain(f"default/{name}")
        assert exp is not None, name
        reasons = exp["set_record"]["reasons"]
        assert sum(reasons.values()) == n, (name, reasons)
        assert f"0/{n} nodes are available" in exp["reason"]


def test_reasons_reconcile_with_filter_rejection_counters(tmp_path):
    from open_simulator_tpu.obs import REGISTRY

    def rejections():
        out = {}
        prefix = 'simon_filter_rejections_total{reason="'
        for key, val in REGISTRY.values().items():
            if key.startswith(prefix):
                out[key[len(prefix):-2]] = float(val)
        return out

    nodes = [make_node(f"n{i}", cpu="2") for i in range(3)]
    pods = replicas("fill", 4, cpu="1") + [make_pod("big", cpu="64")]
    before = rejections()
    xray.enable(str(tmp_path / "trace"))
    sim = Simulator(copy.deepcopy(nodes))
    sim.schedule_pods(copy.deepcopy(pods))
    delta = {k: int(v - before.get(k, 0.0)) for k, v in rejections().items()
             if v - before.get(k, 0.0)}
    totals = {}
    rec = xray.active()
    exp = rec.explain("default/big")
    for label, count in exp["set_record"]["reasons"].items():
        totals[label] = totals.get(label, 0) + count
    assert totals == delta


# ------------------------------------------------------------- trace queries --


def test_unknown_pod_is_clean_error(tmp_path, capsys):
    nodes = [make_node("n0")]
    _, _, rec = run_pair(nodes, [[make_pod("real")]], tmp_path)
    assert rec.explain("default/ghost") is None
    xray.disable()
    from open_simulator_tpu.cli.main import main

    rc = main(["explain", "default/ghost",
               "--trace", str(tmp_path / "trace")])
    assert rc == 1
    assert "no decision record" in capsys.readouterr().err
    rc = main(["explain", "missing", "--trace", str(tmp_path / "nothere")])
    assert rc == 1


def test_trace_round_trip_matches_in_memory(tmp_path):
    nodes = zoned(6, 3, cpu="4")
    pods = (with_spread(replicas("dns", 12, cpu="100m"), "dns")
            + [make_pod("big", cpu="64")])
    _, _, rec = run_pair(nodes, [pods], tmp_path)
    mem = rec.explain("default/dns-0")
    xray.disable()
    tr = xray.XrayTrace.load(str(tmp_path / "trace"))
    disk = tr.explain("default/dns-0")
    assert disk["node_name"] == mem["node_name"]
    assert disk["segment"] == mem["segment"]
    assert disk["set_record"] == mem["set_record"]
    assert disk["node_scores"] == mem["node_scores"]  # via the npz sidecar
    assert os.path.exists(str(tmp_path / "trace.npz"))
    # the unscheduled summary survives the round trip too
    assert ({r["pod"] for r in tr.unscheduled_summary()}
            == {"default/big"})
    # bare-name lookup resolves when unambiguous
    assert tr.explain("big")["result_name"] == "unschedulable"


def test_explain_cli_renders_kube_parity_event(tmp_path, capsys):
    nodes = [make_node("n0", cpu="2")]
    _, _, _rec = run_pair(nodes, [[make_pod("huge", cpu="64")]], tmp_path)
    xray.disable()
    from open_simulator_tpu.cli.main import main

    rc = main(["explain", "default/huge", "--trace", str(tmp_path / "trace")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "FailedScheduling: 0/1 nodes are available: 1 Insufficient cpu." in out
    rc = main(["explain", "--unscheduled", "--trace",
               str(tmp_path / "trace")])
    assert rc == 0
    assert "default/huge" in capsys.readouterr().out


# -------------------------------------------------------- failover survival ---


def test_recording_survives_guard_failover(tmp_path):
    """A watchdog wedge mid-run fails over to the CPU fallback and replays;
    the committed records must be the REPLAY's (no phantom rows from the
    rolled-back attempt) and must carry the full backend_path."""
    from open_simulator_tpu.resilience import FaultPlan, install_plan, clear_plan
    from open_simulator_tpu.resilience.faults import FaultSpec

    guard.reset_for_tests()
    nodes = [make_node(f"n{i}", cpu="8") for i in range(4)]
    pods = replicas("fo", 12, cpu="200m")
    xray.enable(str(tmp_path / "trace"))
    try:
        install_plan(FaultPlan([FaultSpec("watchdog_wedge", 1)]))
        sim = Simulator(copy.deepcopy(nodes))
        failed = sim.schedule_pods(copy.deepcopy(pods))
    finally:
        clear_plan()
        guard.reset_for_tests()
    assert not failed
    assert sim.backend_path.count("cpu") >= 2  # initial + failover
    rec = xray.active()
    assert rec.counts()["pods"] == len(pods)  # exactly one row per pod
    exp = rec.explain("default/fo-0")
    assert exp["backend_path"] == sim.backend_path
    assert exp["result_name"] == "scheduled"


# ----------------------------------------------------------- server surface ---


def test_server_explain_endpoint(tmp_path):
    import http.client
    import threading

    from open_simulator_tpu.core.types import ResourceTypes
    from open_simulator_tpu.server.http import ClusterSnapshot, Server

    snap = ClusterSnapshot(
        ResourceTypes(nodes=[make_node("n1", cpu="8")]), [], [], [])
    server = Server(snapshot_fn=lambda: snap, xray=True)
    httpd = server.build_httpd(port=0, host="127.0.0.1")
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        body = {"pods": [make_pod("api-0", cpu="100m"),
                         make_pod("whale", cpu="900")]}
        conn.request("POST", "/api/deploy-apps", json.dumps(body),
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 200
        conn.request("GET", "/explain/default/whale")
        resp = conn.getresponse()
        assert resp.status == 200
        doc = json.loads(resp.read())
        assert "FailedScheduling" in doc["rendered"]
        assert doc["explanation"]["result_name"] == "unschedulable"
        conn.request("GET", "/explain/default/ghost")
        resp = conn.getresponse()
        assert resp.status == 404
        assert "no decision record" in json.loads(resp.read())["error"]
        conn.request("GET", "/debug/vars")
        doc = json.loads(conn.getresponse().read())
        assert doc["xray"]["pods"] >= 2
        assert doc["xray"]["unscheduled"] >= 1  # the total count survives
        assert any(r["pod"] == "default/whale"
                   for r in doc["xray"]["unscheduled_sample"])
    finally:
        httpd.shutdown()


def test_server_explain_404_when_xray_off():
    import http.client
    import threading

    from open_simulator_tpu.core.types import ResourceTypes
    from open_simulator_tpu.server.http import ClusterSnapshot, Server

    snap = ClusterSnapshot(ResourceTypes(nodes=[make_node("n1")]), [], [], [])
    server = Server(snapshot_fn=lambda: snap, xray=False)
    httpd = server.build_httpd(port=0, host="127.0.0.1")
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/explain/default/x")
        resp = conn.getresponse()
        assert resp.status == 404
        assert "xray recording is off" in json.loads(resp.read())["error"]
    finally:
        httpd.shutdown()


# ------------------------------------------------------------ chrome / spans --


def test_schedule_run_span_carries_decision_records(tmp_path, recorder):
    from open_simulator_tpu.obs.chrome import chrome_trace
    from open_simulator_tpu.utils.trace import start_collection, stop_collection

    nodes = zoned(6, 3, cpu="8")
    pods = with_spread(replicas("dns", 16, cpu="100m"), "dns")
    start_collection()
    sim = Simulator(copy.deepcopy(nodes))
    sim.schedule_pods(copy.deepcopy(pods))
    spans = stop_collection()
    # schedule_run is a phase of the schedule_pods root span
    runs = [c for s in spans if s.name == "schedule_pods"
            for c in s.children if c.name == "schedule_run"]
    assert runs and "xray" in runs[0].meta
    meta = runs[0].meta["xray"]
    assert meta["pods"] == len(pods)
    assert meta["segments"][0]["kind"] == "affinity"
    assert "stats" in meta["segments"][0]  # epoch attribution rides along
    # the Chrome export carries it as event args, and each affinity
    # segment's dispatch span carries its epoch counters
    doc = chrome_trace(spans)
    ev = next(e for e in doc["traceEvents"]
              if e["name"] == "schedule_run" and e["args"].get("xray"))
    assert ev["args"]["xray"]["decision_sets"] >= 1
    aff = [e["args"]["affinity"] for e in doc["traceEvents"]
           if e["name"] == "dispatch.affinity"]
    assert aff and all(a["epochs"] >= 1 for a in aff)
    assert aff[0] == {"group": meta["segments"][0]["group"],
                      **meta["segments"][0]["stats"]}


# -------------------------------------------------------------- metrics diff --


def test_metrics_diff_flags_regressions(tmp_path, capsys):
    a = {"simon_commits_total": {
            "type": "counter", "help": "", "label_names": [],
            "samples": [{"labels": {}, "value": 10}]},
         "simon_compile_cache_misses_total": {
            "type": "counter", "help": "", "label_names": ["kernel", "shape"],
            "samples": [{"labels": {"kernel": "k", "shape": "s"},
                         "value": 0}]}}
    b = copy.deepcopy(a)
    b["simon_commits_total"]["samples"][0]["value"] = 12
    b["simon_compile_cache_misses_total"]["samples"][0]["value"] = 3
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(a))
    pb.write_text(json.dumps(b))
    from open_simulator_tpu.cli.main import main

    rc = main(["metrics", "--diff", str(pa), str(pb)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "simon_commits_total  10 -> 12  (+2)" in out
    assert "REGRESSION" in out
    assert "2 metric(s) changed, 1 regression(s)" in out
    rc = main(["metrics", "--diff", "--fail-on-regression",
               str(pa), str(pb)])
    capsys.readouterr()
    assert rc == 1
    # reversed direction: the miss counter going backwards is flagged too
    rc = main(["metrics", "--diff", str(pb), str(pa)])
    out = capsys.readouterr().out
    assert rc == 0 and "counter went backwards" in out


# ------------------------------------------------------------- zero-cost off --


def test_recording_off_adds_no_dispatch_signatures():
    """With recording off the engine must not touch the recorder, move xray
    counters, or register explain/stats dispatch signatures — the
    byte-identical-metrics half of the zero-cost gate (delta-checked: the
    process registry may carry counters from earlier recorded tests)."""
    from open_simulator_tpu.obs import REGISTRY

    def slice_of(v):
        return {k: x for k, x in v.items()
                if "xray" in k or "explain_pod" in k or "stats=True" in k}

    before = slice_of(REGISTRY.values())
    nodes = [make_node(f"n{i}", cpu="8") for i in range(4)]
    sim = Simulator(copy.deepcopy(nodes))
    sim.schedule_pods([make_pod(f"z-{i}", cpu="100m") for i in range(12)])
    assert slice_of(REGISTRY.values()) == before
    assert sim._xray_run is None
