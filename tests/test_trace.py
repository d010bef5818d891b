"""Phase tracing + progress reporting (the reference's utiltrace spans with
LogIfLong thresholds, core.go:67-73, and the pterm progress bar,
simulator.go:311-321)."""

import io
import logging

from open_simulator_tpu.utils.trace import Progress, Span, recent_spans

from fixtures import make_node, make_pod


def test_span_logs_only_over_threshold(caplog):
    with caplog.at_level(logging.WARNING, logger="open_simulator_tpu.trace"):
        with Span("fast phase", log_if_longer=10.0):
            with Span("fast phase.a"):
                pass
        assert not caplog.records
        with Span("slow phase", log_if_longer=0.0):
            # a child never logs, whatever its threshold; the root's line
            # carries the child's time
            with Span("slow phase.b", log_if_longer=0.0):
                pass
        msgs = [r.getMessage() for r in caplog.records]
        assert len(msgs) == 1
        assert "slow phase" in msgs[0] and "slow phase.b: " in msgs[0]
    spans = recent_spans()
    assert spans[0]["name"] == "slow phase" and spans[0]["logged"]
    assert spans[0]["children"][0]["name"] == "slow phase.b"
    assert not spans[0]["children"][0]["logged"]
    assert spans[1]["name"] == "fast phase" and not spans[1]["logged"]


def test_span_nesting_attaches_children_to_parent():
    with Span("outer", log_if_longer=99.0) as outer:
        with Span("inner", log_if_longer=99.0):
            with Span("inner.work"):
                pass
        with Span("inner2", log_if_longer=99.0):
            pass
    assert [c.name for c in outer.children] == ["inner", "inner2"]
    spans = recent_spans()
    # only the ROOT registers in the ring; children nest under it
    assert spans[0]["name"] == "outer"
    assert [c["name"] for c in spans[0]["children"]] == ["inner", "inner2"]
    assert spans[0]["children"][0]["children"][0]["name"] == "inner.work"
    assert all(s["name"] != "inner" for s in spans)
    # a child lies inside its parent on the shared clock
    inner = outer.children[0]
    assert outer.t0 <= inner.t0
    assert inner.t0 + inner.total <= outer.t0 + outer.total


def test_span_exception_safety_records_partial_and_failed():
    import pytest

    with pytest.raises(RuntimeError):
        with Span("outer", log_if_longer=99.0):
            with pytest.raises(RuntimeError):
                with Span("dies", log_if_longer=99.0):
                    with Span("dies.before"):
                        pass
                    raise RuntimeError("boom")
            raise RuntimeError("outer dies too")
    spans = recent_spans()
    assert spans[0]["name"] == "outer" and spans[0]["failed"]
    child = spans[0]["children"][0]
    assert child["name"] == "dies" and child["failed"]
    # partial children survive
    assert [c["name"] for c in child["children"]] == ["dies.before"]
    assert not child["children"][0]["failed"]
    # the active-span stack unwound: a fresh span is a root again
    with Span("clean", log_if_longer=99.0):
        pass
    assert recent_spans()[0]["name"] == "clean"
    assert not recent_spans()[0]["failed"]


def test_span_collection_for_trace_export():
    from open_simulator_tpu.utils.trace import start_collection, stop_collection

    start_collection()
    with Span("collected", log_if_longer=99.0):
        with Span("kid", log_if_longer=99.0):
            pass
    out = stop_collection()
    assert [s.name for s in out] == ["collected"]
    assert [c.name for c in out[0].children] == ["kid"]
    # collection is off again: nothing accumulates
    with Span("later", log_if_longer=99.0):
        pass
    assert stop_collection() == []


def test_simulate_emits_span():
    from open_simulator_tpu.core.types import AppResource, ResourceTypes
    from open_simulator_tpu.simulator.core import simulate

    cluster = ResourceTypes()
    cluster.nodes = [make_node("n0")]
    cluster.pods = [make_pod("p0", cpu="1", memory="1Gi")]
    simulate(cluster, [])
    names = [s["name"] for s in recent_spans()]
    assert "Simulate" in names
    sim_span = next(s for s in recent_spans() if s["name"] == "Simulate")
    phase_names = [c["name"] for c in sim_span["children"]]
    assert "Simulate.expand_workloads" in phase_names
    assert "Simulate.sync_cluster" in phase_names
    # the Simulator's construction is a phase of Simulate too
    assert "init" in phase_names


def test_span_without_jax_stays_jax_free():
    """utils/trace never imports jax: a Span in a process that has not loaded
    it is host-clock only, and still nests."""
    import os
    import subprocess
    import sys

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "open_simulator_tpu", "utils", "trace.py")
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('trace_alone', {path!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "with m.Span('root', log_if_longer=99.0) as root:\n"
        "    with m.Span('root.child'):\n"
        "        pass\n"
        "assert [c.name for c in root.children] == ['root.child']\n"
        "assert root.total > 0\n"
        "assert 'jax' not in sys.modules, 'Span imported jax'\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_progress_renders_and_closes():
    buf = io.StringIO()
    pr = Progress("Scheduling pods", 4, enabled=True, stream=buf)
    pr.advance(2)
    pr.advance(2)
    pr.close()
    out = buf.getvalue()
    assert "Scheduling pods 4/4 (100%)" in out
    assert out.endswith("\n")


def test_progress_disabled_is_silent():
    buf = io.StringIO()
    pr = Progress("x", 4, enabled=False, stream=buf)
    pr.advance(4)
    pr.close()
    assert buf.getvalue() == ""


def test_engine_progress_wiring():
    """disable_progress=False must actually render (the round-2 gap: a dead
    parameter)."""
    import contextlib
    import copy
    import io as _io
    import sys

    from open_simulator_tpu.simulator.engine import Simulator

    nodes = [make_node("n0")]
    pods = [make_pod(f"p{i}", cpu="100m", memory="128Mi") for i in range(12)]
    sim = Simulator(copy.deepcopy(nodes), disable_progress=False)
    buf = _io.StringIO()
    with contextlib.redirect_stderr(buf):
        sim.schedule_pods(copy.deepcopy(pods))
    assert "Scheduling pods 12/12" in buf.getvalue()


def test_server_debug_vars():
    import json
    import threading
    import urllib.request

    from open_simulator_tpu.server.http import Server

    srv = Server(snapshot_fn=lambda: None)  # endpoint needs no cluster client
    httpd = srv.build_httpd(port=0, host="127.0.0.1")
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/debug/vars") as r:
            data = json.loads(r.read())
        assert "uptime_seconds" in data and "recent_traces" in data
        assert "max_rss_kb" in data
    finally:
        httpd.shutdown()


def test_server_debug_pprof_profile_samples_other_threads():
    """The sampler must see application work on OTHER threads — the bug this
    replaces: cProfile around a sleep only ever profiled the sleeping
    handler thread, so dumps were empty of application work."""
    import threading
    import urllib.request

    from open_simulator_tpu.server.http import Server

    stop = threading.Event()

    def busy_app_work():
        while not stop.is_set():
            sum(i * i for i in range(1000))

    worker = threading.Thread(target=busy_app_work, daemon=True)
    worker.start()
    srv = Server(snapshot_fn=lambda: None)
    httpd = srv.build_httpd(port=0, host="127.0.0.1")
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/pprof/profile?seconds=0.3") as r:
            text = r.read().decode()
    finally:
        stop.set()
        httpd.shutdown()
    assert "stack samples:" in text
    assert "busy_app_work" in text  # the application thread was captured


def test_sample_stacks_excludes_caller_and_counts():
    from open_simulator_tpu.server.http import sample_stacks

    text = sample_stacks(0.05, interval=0.01)
    assert text.startswith("stack samples:")
    # the profiling thread itself never appears
    assert "sample_stacks" not in text.split("\n", 1)[1]
