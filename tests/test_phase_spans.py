"""The engine's host phases as utils/trace Spans on the profiler clock: a
small columnar simulation under `jax.profiler.trace` shows every phase as a
`simon.*` event on the host plane, each child inside its parent, and the
pulse phases and histograms read the very Span totals (one clock, each phase
timed once)."""

from __future__ import annotations

import glob
import os

import pytest

from open_simulator_tpu.obs import REGISTRY
from open_simulator_tpu.obs import instruments as obs
from open_simulator_tpu.obs import pulse
from open_simulator_tpu.simulator.engine import Simulator
from open_simulator_tpu.utils.synth import synth_cluster_store
from open_simulator_tpu.utils.trace import start_collection, stop_collection

# (child, parent): the minimum phase set and where each phase nests
NESTING = [
    ("simon.init.nodes", "simon.init"),
    ("simon.init.encoder", "simon.init"),
    ("simon.init.plugins", "simon.init"),
    ("simon.schedule_pods.snapshot", "simon.schedule_pods"),
    ("simon.schedule_pods.priorities", "simon.schedule_pods"),
    ("simon.schedule_run", "simon.schedule_pods"),
    ("simon.encode", "simon.schedule_run"),
    ("simon.encode.ids", "simon.encode"),
    ("simon.encode.table_build", "simon.encode"),
    ("simon.encode.pads", "simon.encode"),
    ("simon.route", "simon.schedule_run"),
    ("simon.to_device", "simon.schedule_run"),
    ("simon.dispatch", "simon.schedule_run"),
    ("simon.dispatch.wave", "simon.dispatch"),
    ("simon.dispatch.affinity", "simon.dispatch"),
    ("simon.fetch", "simon.schedule_run"),
    ("simon.commit", "simon.schedule_run"),
]
ROOTS = ("simon.init", "simon.schedule_pods", "simon.readback")


def _host_events(log_dir):
    """{name: [(start_ns, end_ns, line)]} of the host plane's events."""
    import jax

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    assert paths, "the profiler wrote no trace"
    out = {}
    for p in paths:
        for plane in jax.profiler.ProfileData.from_file(p).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    s = int(e.start_ns)
                    out.setdefault(e.name, []).append(
                        (s, s + int(e.duration_ns), line.name))
    return out


def _walk(spans):
    for sp in spans:
        yield sp
        yield from _walk(sp.children)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One simulation (plain waves plus zone-spread affinity segments) under
    the profiler, with pulse on and the span tree collected."""
    import jax

    ns, ps = synth_cluster_store(48, 400, hard_predicates=True)
    Simulator(ns, use_mesh=False).schedule_pods(ps[:])  # compile outside
    log_dir = str(tmp_path_factory.mktemp("prof"))
    pulse.reset_for_tests()
    pulse.enable()
    before = {
        "pulse_encode": obs.PULSE_PHASE_SECONDS.labels(phase="encode").value,
        "pulse_commit": obs.PULSE_PHASE_SECONDS.labels(phase="commit").value,
        "encode_hist": REGISTRY.values()["simon_encode_seconds_sum"],
        "commit_hist": REGISTRY.values()["simon_host_commit_seconds_sum"],
    }
    start_collection()
    try:
        with jax.profiler.trace(log_dir):
            sim = Simulator(ns, use_mesh=False)
            sim.schedule_pods(ps)
            ps.node_rows()
    finally:
        spans = stop_collection()
        after = {
            "pulse_encode": obs.PULSE_PHASE_SECONDS.labels(phase="encode").value,
            "pulse_commit": obs.PULSE_PHASE_SECONDS.labels(phase="commit").value,
            "encode_hist": REGISTRY.values()["simon_encode_seconds_sum"],
            "commit_hist": REGISTRY.values()["simon_host_commit_seconds_sum"],
        }
        pulse.reset_for_tests()
    return {"events": _host_events(log_dir), "spans": spans,
            "delta": {k: after[k] - before[k] for k in before}}


@pytest.mark.parametrize("name", ROOTS + tuple(c for c, _ in NESTING))
def test_phase_is_on_the_host_plane(traced_run, name):
    assert traced_run["events"].get(name), sorted(
        n for n in traced_run["events"] if n.startswith("simon."))


@pytest.mark.parametrize("child,parent", NESTING)
def test_child_phase_lies_inside_its_parent(traced_run, child, parent):
    ev = traced_run["events"]
    for s, e, line in ev[child]:
        assert any(ps <= s and e <= pe and pl == line
                   for ps, pe, pl in ev[parent]), (child, parent)


def test_one_dispatch_span_per_segment(traced_run):
    ev = traced_run["events"]
    seg_spans = sum(len(v) for k, v in ev.items()
                    if k.startswith("simon.dispatch."))
    runs = [c for s in traced_run["spans"] if s.name == "schedule_pods"
            for c in _walk([s]) if c.name == "dispatch"]
    assert seg_spans == sum(len(r.children) for r in runs) > 0


@pytest.mark.parametrize("phase,hist", [("encode", "encode_hist"),
                                        ("commit", "commit_hist")])
def test_pulse_and_histogram_read_the_span_total(traced_run, phase, hist):
    totals = [sp.total for sp in _walk(traced_run["spans"]) if sp.name == phase]
    assert totals
    d = traced_run["delta"]
    assert d[f"pulse_{phase}"] == pytest.approx(sum(totals), rel=1e-9, abs=1e-12)
    assert d[hist] == pytest.approx(sum(totals), rel=1e-9, abs=1e-12)
    # and the profiler's event spans the same extent
    (s, e, _), = [x for x in traced_run["events"][f"simon.{phase}"]]
    assert abs((e - s) / 1e9 - totals[0]) < 1e-3


def test_serve_dispatch_emits_both_spans(tmp_path):
    """A dispatched what-if puts its kernel dispatch and its fetch on the
    profiler clock as simon.serve.dispatch and simon.serve.fetch."""
    import jax

    from open_simulator_tpu.serve.image import ResidentImage

    from fixtures import make_node, make_pod

    img = ResidentImage.try_build(
        [make_node(f"n-{i}", cpu="8", memory="16Gi") for i in range(8)])
    req = [make_pod(f"wi-{i}", cpu="1", memory="1Gi", labels={"app": "wi"})
           for i in range(4)]
    img.session(req).run()  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        got = img.session(req).run()
    assert got["scheduled"] == 4
    ev = _host_events(str(tmp_path))
    assert ev.get("simon.serve.dispatch") and ev.get("simon.serve.fetch")
    (ds, de, _), = ev["simon.serve.dispatch"][:1]
    (fs, fe, _), = ev["simon.serve.fetch"][:1]
    assert de <= fs  # the fetch follows the dispatch
