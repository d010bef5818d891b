"""simonmetrics: registry semantics, Prometheus rendering, Chrome export,
and the engine integration invariants the CI smoke also enforces."""

import json
import threading

import pytest

from open_simulator_tpu.obs.chrome import chrome_trace
from open_simulator_tpu.obs.metrics import (
    Registry,
    render_text_from_snapshot,
)
from open_simulator_tpu.utils.trace import Span

from pathlib import Path

GOLDEN = Path(__file__).parent / "golden" / "metrics.prom"


def _golden_registry() -> Registry:
    """A deterministic registry exercising every metric type, labels, label
    escaping, and histogram bucket arithmetic — the golden-file subject."""
    reg = Registry()
    c = reg.counter("demo_requests_total", "Requests served.", ("code", "verb"))
    c.labels(code="200", verb="GET").inc()
    c.labels(code="200", verb="GET").inc(2)
    c.labels(code="503", verb="POST").inc()
    g = reg.gauge("demo_queue_depth", "Items queued.")
    g.set(7)
    g.inc(1.5)
    h = reg.histogram("demo_latency_seconds", "Latencies.",
                      buckets=(0.1, 0.5, 1.0))
    for v in (0.05, 0.1, 0.3, 0.5, 0.9, 1.0, 4.0):
        h.observe(v)
    esc = reg.counter("demo_reasons_total", "Labels needing escaping.",
                      ("reason",))
    esc.labels(reason='node(s) had taint {k: "v"}, unhandled').inc(3)
    return reg


# ---------------------------------------------------------------- registry ---


def test_counter_get_or_create_and_type_guard():
    reg = Registry()
    a = reg.counter("x_total", "x", ("l",))
    b = reg.counter("x_total", "x again", ("l",))
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("x_total", "now a gauge", ("l",))
    with pytest.raises(ValueError):
        reg.counter("x_total", "other labels", ("other",))


def test_counter_rejects_negative_and_bad_labels():
    reg = Registry()
    c = reg.counter("y_total", "y", ("l",))
    with pytest.raises(ValueError):
        c.labels(l="a").inc(-1)
    with pytest.raises(ValueError):
        c.labels(wrong="a")
    with pytest.raises(ValueError):
        c.inc()  # labeled family needs .labels()


def test_concurrent_increments_from_threads():
    reg = Registry()
    c = reg.counter("t_total", "t")
    h = reg.histogram("t_seconds", "t", buckets=(0.5,))
    child = c.labels()

    def work():
        for _ in range(10_000):
            child.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert child.value == 80_000
    sample = reg.snapshot()["t_seconds"]["samples"][0]
    assert sample["count"] == 80_000
    assert sample["buckets"][0][1] == 80_000  # all in le=0.5
    assert sample["sum"] == pytest.approx(20_000.0)


def test_histogram_bucket_edges_are_inclusive():
    reg = Registry()
    h = reg.histogram("edge_seconds", "e", buckets=(1.0, 2.0))
    h.observe(1.0)   # == bound -> le=1.0 (Prometheus: le is inclusive)
    h.observe(2.0)   # == bound -> le=2.0
    h.observe(2.0001)  # past the last bound -> +Inf only
    s = reg.snapshot()["edge_seconds"]["samples"][0]
    assert s["buckets"] == [[1.0, 1], [2.0, 1], ["+Inf", 1]]
    # rendered counts are CUMULATIVE
    text = reg.render_text()
    assert 'edge_seconds_bucket{le="1"} 1' in text
    assert 'edge_seconds_bucket{le="2"} 2' in text
    assert 'edge_seconds_bucket{le="+Inf"} 3' in text
    assert "edge_seconds_count 3" in text


def test_histogram_rejects_unsorted_buckets():
    reg = Registry()
    with pytest.raises(ValueError):
        reg.histogram("bad", "b", buckets=(2.0, 1.0))


# ----------------------------------------------------------- prometheus text --


def test_prometheus_rendering_matches_golden():
    text = _golden_registry().render_text()
    assert text == GOLDEN.read_text()


def test_snapshot_roundtrips_through_json_to_same_text():
    reg = _golden_registry()
    snap = json.loads(json.dumps(reg.snapshot()))
    assert render_text_from_snapshot(snap) == reg.render_text()


def test_values_flat_view():
    v = _golden_registry().values()
    assert v['demo_requests_total{code="200",verb="GET"}'] == 3
    assert v["demo_queue_depth"] == 8.5
    assert v["demo_latency_seconds_count"] == 7


# -------------------------------------------------------------- chrome trace --


def _make_span_tree():
    with Span("root", log_if_longer=99.0) as root:
        with Span("prep"):
            pass
        with Span("child", log_if_longer=99.0):
            with Span("inner"):
                pass
        try:
            with Span("boom", log_if_longer=99.0):
                raise RuntimeError("x")
        except RuntimeError:
            pass
    return root


def test_chrome_trace_roundtrips_through_json():
    root = _make_span_tree()
    assert [c.name for c in root.children] == ["prep", "child", "boom"]
    assert root.children[2].failed and not root.children[1].failed

    doc = json.loads(json.dumps(chrome_trace([root], metrics={"m": 1})))
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and doc["metadata"]["metrics"] == {"m": 1}
    by_name = {e["name"]: e for e in evs}
    assert {"root", "child", "boom", "prep", "inner"} <= set(by_name)
    for e in evs:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], (int, float)) and e["dur"] >= 0
        assert e["pid"] and e["tid"]
    # children nest inside the root's [ts, ts+dur) window
    r = by_name["root"]
    for name in ("child", "boom", "prep"):
        e = by_name[name]
        assert e["ts"] >= r["ts"]
        assert e["ts"] + e["dur"] <= r["ts"] + r["dur"] + 1e-3
    assert by_name["boom"]["args"] == {"failed": True}


# -------------------------------------------------------- engine integration --


def test_engine_emits_core_counters_and_warm_run_adds_no_misses():
    import copy

    from open_simulator_tpu.obs import REGISTRY
    from open_simulator_tpu.simulator.engine import Simulator

    from fixtures import make_node, make_pod

    nodes = [make_node(f"m{i}") for i in range(4)]
    pods = [make_pod(f"p{i}", cpu="100m", memory="128Mi") for i in range(24)]

    def run():
        sim = Simulator(copy.deepcopy(nodes))
        assert sim.schedule_pods(copy.deepcopy(pods)) == []

    def total(values, prefix):
        return sum(v for k, v in values.items() if k.startswith(prefix))

    v0 = REGISTRY.values()
    run()
    v1 = REGISTRY.values()
    run()
    v2 = REGISTRY.values()

    att = "simon_scheduling_attempts_total"
    assert total(v1, att) - total(v0, att) == len(pods)
    assert total(v2, att) - total(v1, att) == len(pods)
    miss = "simon_compile_cache_misses_total"
    assert total(v2, miss) == total(v1, miss), \
        "identical warm run must not register new compile shape buckets"
    assert total(v2, "simon_commits_total") - total(v1, "simon_commits_total") \
        == len(pods)
    assert total(v2, "simon_device_transfer_bytes_total") > 0
    assert total(v2, "simon_segments_total") > total(v1, "simon_segments_total")


def test_preemption_commits_reconcile_via_rollbacks():
    """The rewind/replay pass re-commits pods and evictions remove committed
    pods; commits - rollbacks - victims must equal the placements actually
    materialized on cluster state."""
    from open_simulator_tpu.obs import REGISTRY
    from open_simulator_tpu.simulator.engine import Simulator

    from fixtures import make_node, make_pod

    def prio_pod(name, prio, cpu="1"):
        p = make_pod(name, cpu=cpu, memory="128Mi")
        p["spec"]["priority"] = prio
        return p

    nodes = [make_node("n0", cpu="4")]
    pods = [prio_pod(f"low{i}", 0) for i in range(4)] + [
        prio_pod("high", 100, cpu="2")]

    def total(values, prefix):
        return sum(v for k, v in values.items() if k.startswith(prefix))

    v0 = REGISTRY.values()
    sim = Simulator(nodes)
    sim.schedule_pods(pods)
    v1 = REGISTRY.values()
    live = sum(len(l) for l in sim.pods_on_node)
    commits = total(v1, "simon_commits_total") - total(v0, "simon_commits_total")
    rollbacks = (total(v1, "simon_commit_rollbacks_total")
                 - total(v0, "simon_commit_rollbacks_total"))
    victims = (total(v1, "simon_preemption_victims_total")
               - total(v0, "simon_preemption_victims_total"))
    assert rollbacks > 0  # the preemption pass rewound at least once
    assert victims == len(sim.preempted) > 0
    assert commits - rollbacks - victims == live
    assert (total(v1, "simon_preemption_attempts_total")
            - total(v0, "simon_preemption_attempts_total")) >= 1
