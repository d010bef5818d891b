"""simonguard tests: mid-run device-failure containment.

Covers the four containment behaviors end to end against the real engine:
OOM batch bisection (split-vs-unsplit placements bit-identical, odd sizes
included, floor-hit structured failure), watchdog wedge → quarantine → CPU
failover resuming from the committed prefix, the crash-consistent
capacity-search journal (resume skips completed probes; digest mismatch
rejected; torn tails ignored), and the preemption replay cap."""

import copy
import json
import time

import pytest

from open_simulator_tpu.apply.applier import CapacityPlanner
from open_simulator_tpu.obs import REGISTRY
from open_simulator_tpu.resilience import (
    Deadline,
    DeadlineExceeded,
    FaultPlan,
    FaultSpec,
    JournalMismatch,
    OOMBisectionExhausted,
    SearchJournal,
    installed,
)
from open_simulator_tpu.resilience import guard
from open_simulator_tpu.simulator.encode import scheduling_signature
from open_simulator_tpu.simulator.engine import Simulator

from fixtures import make_node, make_pod


@pytest.fixture(autouse=True)
def _clean_guard_state():
    guard.reset_for_tests()
    yield
    guard.reset_for_tests()


def _census(sim):
    out = {}
    for i, nps in enumerate(sim.pods_on_node):
        for p in nps:
            k = (i, scheduling_signature(p))
            out[k] = out.get(k, 0) + 1
    return out


def _metric(prefix):
    return sum(v for k, v in REGISTRY.values().items() if k.startswith(prefix))


def _cluster(n_nodes=6, n_pods=17):
    nodes = [make_node(f"n{i}", cpu="4000m", memory=str(8 << 30), pods="20")
             for i in range(n_nodes)]
    pods = [make_pod(f"p{j}", cpu="300m", memory=str(256 << 20),
                     labels={"app": f"a{j % 3}"})
            for j in range(n_pods)]
    return nodes, pods


# ------------------------------------------------------------ OOM bisection --


@pytest.mark.parametrize("site", ["oom_dispatch", "oom_to_device"])
@pytest.mark.parametrize("n_pods", [16, 17])  # even and odd batch sizes
def test_oom_bisection_bit_identity(site, n_pods):
    """An injected device OOM splits the batch in halves; the split run's
    placements are bit-identical to the unsplit fault-free run."""
    nodes, pods = _cluster(n_pods=n_pods)
    sim0 = Simulator(copy.deepcopy(nodes))
    failed0 = sim0.schedule_pods(copy.deepcopy(pods))
    baseline = _census(sim0)

    sim = Simulator(copy.deepcopy(nodes))
    p = copy.deepcopy(pods)
    b0 = _metric("simon_guard_oom_bisections_total")
    with installed(FaultPlan([FaultSpec(site, 1)])):
        failed = sim.schedule_pods(p)
    assert _census(sim) == baseline
    assert len(failed) == len(failed0)
    assert _metric("simon_guard_oom_bisections_total") > b0
    # the containment is visible, not silent
    assert any(e[0] == "oom_bisect" for e in guard.events())
    # bisection contains without a failover: the run stays on its backend
    assert sim.backend_path == ["cpu"]


def test_oom_bisection_nested_split():
    """An OOM that re-fires inside the first half forces a nested split —
    still bit-identical."""
    nodes, pods = _cluster(n_pods=17)
    sim0 = Simulator(copy.deepcopy(nodes))
    sim0.schedule_pods(copy.deepcopy(pods))
    baseline = _census(sim0)

    sim = Simulator(copy.deepcopy(nodes))
    with installed(FaultPlan([FaultSpec("oom_dispatch", 1),
                              FaultSpec("oom_dispatch", 2)])):
        sim.schedule_pods(copy.deepcopy(pods))
    assert _census(sim) == baseline
    assert sum(1 for e in guard.events() if e[0] == "oom_bisect") == 2


def test_oom_floor_hit_structured_failure():
    """OOM persisting down to the floor — and through the CPU failover —
    surfaces as OOMBisectionExhausted with a clean rollback."""
    nodes, pods = _cluster(n_nodes=4, n_pods=8)
    sim = Simulator(copy.deepcopy(nodes))
    p = copy.deepcopy(pods)
    pre = copy.deepcopy(p)
    plan = FaultPlan([FaultSpec("oom_dispatch", k) for k in range(1, 200)])
    with installed(plan):
        with pytest.raises(OOMBisectionExhausted) as ei:
            sim.schedule_pods(p)
    assert ei.value.batch == ei.value.floor == 1
    assert p == pre, "rollback left pod-dict residue"
    assert all(not l for l in sim.pods_on_node), "rollback left census residue"
    # the failed-over attempts are on record
    assert sim.backend_path.count("cpu") >= 2


# ------------------------------------------------------- wedge and failover --


def test_watchdog_wedge_failover_resumes_from_committed_prefix():
    """A wedge in the SECOND schedule call must not disturb the first call's
    committed placements: the transaction rolls back only the failing call,
    and the CPU replay converges to the fault-free final state."""
    nodes, pods = _cluster(n_pods=16)
    first, second = pods[:7], pods[7:]

    sim0 = Simulator(copy.deepcopy(nodes))
    sim0.schedule_pods(copy.deepcopy(first))
    committed = _census(sim0)
    sim0.schedule_pods(copy.deepcopy(second))
    baseline = _census(sim0)

    f0 = _metric("simon_guard_failovers_total")
    sim = Simulator(copy.deepcopy(nodes))
    sim.schedule_pods(copy.deepcopy(first))
    assert _census(sim) == committed
    with installed(FaultPlan([FaultSpec("watchdog_wedge", 1)])):
        sim.schedule_pods(copy.deepcopy(second))
    assert _census(sim) == baseline
    assert sim.backend_path == ["cpu", "cpu"]  # initial backend, then failover
    assert guard.quarantined(), "wedge must quarantine the backend"
    assert _metric("simon_guard_failovers_total") > f0
    kinds = [e[0] for e in guard.events()]
    assert kinds == ["wedge", "failover"]


def test_quarantine_routes_later_simulators_to_fallback():
    nodes, pods = _cluster(n_pods=8)
    sim = Simulator(copy.deepcopy(nodes))
    with installed(FaultPlan([FaultSpec("watchdog_wedge", 1)])):
        sim.schedule_pods(copy.deepcopy(pods))
    assert guard.quarantined()
    # a later simulator starts directly on the fallback: no new failover
    f0 = _metric("simon_guard_failovers_total")
    sim2 = Simulator(copy.deepcopy(nodes))
    sim2.schedule_pods(copy.deepcopy(pods))
    assert sim2.backend_path == ["cpu"]
    assert _metric("simon_guard_failovers_total") == f0
    assert _census(sim2) == _census(sim)


def test_supervised_keeps_the_callers_device_scope():
    """The watchdog worker is another thread, and jax.default_device is
    thread-local: a dispatch made under a caller's device scope must still
    land on that device (the CPU reference run, or the failover's
    fallback_scope), and current_backend must name its platform."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices("cpu")[-1]
    assert dev != jax.devices()[0]
    with jax.default_device(dev):
        out = guard.supervised(lambda: jnp.zeros(3) + 1, site="dispatch")
        assert guard.current_backend() == "cpu"
    assert out.devices() == {dev}
    assert guard.supervised(lambda: jnp.zeros(3), site="dispatch").devices() == {
        jax.devices()[0]}


def test_supervised_real_timeout_declares_wedge(monkeypatch):
    monkeypatch.setenv("OPEN_SIMULATOR_WATCHDOG_BASE_S", "0.2")
    monkeypatch.setenv("OPEN_SIMULATOR_WATCHDOG_PER_POD_S", "0")
    with pytest.raises(guard.BackendWedged):
        guard.supervised(lambda: time.sleep(3), site="dispatch", pods=0)
    assert "cpu" in guard.quarantined()


def test_supervised_prefers_deadline_over_wedge(monkeypatch):
    """When the CALLER's Deadline expires during the wait, that is a budget
    expiry, not a device wedge: no quarantine."""
    monkeypatch.setenv("OPEN_SIMULATOR_WATCHDOG_BASE_S", "30")
    with Deadline(0.15):
        with pytest.raises(DeadlineExceeded):
            guard.supervised(lambda: time.sleep(3), site="dispatch", pods=0)
    assert guard.quarantined() == {}


def test_supervised_reraises_worker_errors_transparently():
    with pytest.raises(ZeroDivisionError):
        guard.supervised(lambda: 1 // 0, site="dispatch", pods=0)


def test_backend_path_on_simulate_result():
    from open_simulator_tpu.core.types import ResourceTypes
    from open_simulator_tpu.simulator.core import simulate

    nodes, pods = _cluster(n_pods=4)
    res = simulate(ResourceTypes(nodes=nodes, pods=pods), [])
    assert res.backend_path == ["cpu"]


# ----------------------------------------------------- capacity-search journal


def _planner_inputs():
    """lb-inexact fragmentation workload: 10 pods of 3000m on 4000m nodes —
    the arithmetic bound says 6 added nodes, the truth is 8, so the search
    runs several probe rounds (a journal with real content)."""
    base = [make_node(f"b{i}", cpu="4000m", memory=str(8 << 30), pods="20")
            for i in range(2)]
    template = make_node("tmpl", cpu="4000m", memory=str(8 << 30), pods="20")
    pods = [make_pod(f"w{j}", cpu="3000m", memory=str(128 << 20))
            for j in range(10)]
    return base, template, pods


def test_journal_resume_skips_completed_probes(tmp_path):
    path = str(tmp_path / "search.jsonl")
    base, template, pods = _planner_inputs()

    p1 = CapacityPlanner(base, template, copy.deepcopy(pods))
    p1.attach_journal(path)
    found1, n1, _ = p1.search()
    assert found1 and p1.stats["dispatches"] > 0

    assert p1.journal._f is None, "search must close the journal fd"

    p2 = CapacityPlanner(base, template, copy.deepcopy(pods))
    p2.attach_journal(path)
    found2, n2, _ = p2.search()
    assert (found2, n2) == (found1, n1)
    assert p2.stats["dispatches"] == 0, \
        "a fully journaled search must not re-run any probe"
    assert p2.stats["journal_hits"] > 0

    # no-journal control: same answer
    p3 = CapacityPlanner(base, template, copy.deepcopy(pods))
    found3, n3, _ = p3.search()
    assert (found3, n3) == (found1, n1)


def test_reused_planner_keeps_journaling_after_close(tmp_path):
    """search() closes the journal fd when it finishes; a REUSED planner's
    next search must keep journaling (append to the valid file), not crash
    on the closed handle."""
    path = str(tmp_path / "search.jsonl")
    base, template, pods = _planner_inputs()
    p = CapacityPlanner(base, template, copy.deepcopy(pods))
    p.attach_journal(path)
    found1, n1, _ = p.search()
    found2, n2, _ = p.search()  # second search on the SAME planner
    assert (found2, n2) == (found1, n1)
    # and a journal record on the reused planner hits disk, fsync'd
    p.journal.record(999, True, 0)
    assert SearchJournal.open(path, p.options_digest()).lookup(999) == (True, 0)


def test_journal_digest_mismatch_rejected(tmp_path):
    path = str(tmp_path / "search.jsonl")
    base, template, pods = _planner_inputs()
    p1 = CapacityPlanner(base, template, copy.deepcopy(pods))
    p1.attach_journal(path)
    p1.search()
    # a DIFFERENT search (one more pod) must refuse the stale journal
    other = copy.deepcopy(pods) + [make_pod("extra", cpu="3000m",
                                            memory=str(128 << 20))]
    p2 = CapacityPlanner(base, template, other)
    with pytest.raises(JournalMismatch):
        p2.attach_journal(path)
    # same names but materially different cluster (node allocatable shrunk)
    # must ALSO refuse: the digest covers object contents, not identities
    base2 = copy.deepcopy(base)
    base2[0]["status"]["allocatable"]["cpu"] = "2000m"
    p3 = CapacityPlanner(base2, template, copy.deepcopy(pods))
    with pytest.raises(JournalMismatch):
        p3.attach_journal(path)
    # and a same-name pod with different requests
    pods2 = copy.deepcopy(pods)
    pods2[0]["spec"]["containers"][0]["resources"]["requests"]["cpu"] = "100m"
    p4 = CapacityPlanner(base, template, pods2)
    with pytest.raises(JournalMismatch):
        p4.attach_journal(path)


def test_journal_write_fault_leaves_resumable_prefix(tmp_path):
    path = str(tmp_path / "search.jsonl")
    base, template, pods = _planner_inputs()

    p0 = CapacityPlanner(base, template, copy.deepcopy(pods))
    found0, n0, _ = p0.search()  # fault-free answer

    p1 = CapacityPlanner(base, template, copy.deepcopy(pods))
    p1.attach_journal(path)
    with installed(FaultPlan([FaultSpec("journal_write", 2)])):
        with pytest.raises(Exception):
            p1.search()

    # the journal's valid prefix survives and resumes to the same answer
    p2 = CapacityPlanner(base, template, copy.deepcopy(pods))
    p2.attach_journal(path)
    found2, n2, _ = p2.search()
    assert (found2, n2) == (found0, n0)
    assert p2.stats["journal_hits"] >= 1


def test_journal_ignores_torn_tail(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = SearchJournal.open(path, "sha256:x")
    j.record(3, False, 2)
    j.close()
    with open(path, "a") as f:
        f.write('{"n": 9, "ok"')  # SIGKILL mid-write
    j2 = SearchJournal.open(path, "sha256:x")
    assert j2.lookup(3) == (False, 2)
    assert j2.lookup(9) is None
    j2.record(9, True, 0)  # and stays appendable
    j2.close()
    assert SearchJournal.open(path, "sha256:x").lookup(9) == (True, 0)


def test_journal_torn_tail_with_invalid_utf8_truncates_byte_exact(tmp_path):
    """A SIGKILL can tear a write at any byte, leaving invalid utf-8 in the
    tail; the repair must truncate at the BYTE offset of the valid prefix
    (a replace-decoded round trip would widen each bad byte to a 3-byte
    U+FFFD and overshoot)."""
    path = str(tmp_path / "j.jsonl")
    j = SearchJournal.open(path, "sha256:x")
    j.record(3, False, 2)
    j.close()
    import os as _os

    good = _os.path.getsize(path)
    with open(path, "ab") as f:
        f.write(b'{"n": 9, "ok\xff\xfe\xfd')  # torn mid-write, non-utf8 bytes
    j2 = SearchJournal.open(path, "sha256:x")
    assert j2.lookup(3) == (False, 2)
    assert j2.lookup(9) is None
    assert _os.path.getsize(path) == good, "repair must cut exactly the tail"
    j2.record(9, True, 0)
    j2.close()
    with open(path, "rb") as f:
        for line in f.read().splitlines():  # no garbage survived the repair
            json.loads(line)
    assert SearchJournal.open(path, "sha256:x").lookup(9) == (True, 0)


def test_journal_torn_header_treated_as_empty(tmp_path):
    """A crash mid-HEADER-write leaves an unterminated PREFIX of the header
    this search would write: no verdict can follow it, so the journal is
    empty — resume rewrites it instead of failing with JournalMismatch and
    demanding manual deletion."""
    path = str(tmp_path / "j.jsonl")
    full_header = json.dumps(
        {"kind": SearchJournal.KIND, "v": SearchJournal.VERSION,
         "digest": "sha256:x"}, sort_keys=True)
    with open(path, "w") as f:
        f.write(full_header[:-7])  # SIGKILL mid-header (no newline)
    j = SearchJournal.open(path, "sha256:x")
    assert j.verdicts == {}
    j.record(1, True, 0)
    j.close()
    assert SearchJournal.open(path, "sha256:x").lookup(1) == (True, 0)
    # a TERMINATED non-journal first line is a different file, not a torn
    # header: still rejected
    with open(path, "w") as f:
        f.write("not a journal at all\n")
    with pytest.raises(JournalMismatch):
        SearchJournal.open(path, "sha256:x")
    # an UNTERMINATED line that is NOT a prefix of this search's header is
    # someone else's file (typo'd --resume-journal path, another search's
    # torn header): rejected UNTOUCHED, never clobbered
    with open(path, "w") as f:
        f.write("v1.2.3-some-users-version-file")  # no trailing newline
    with pytest.raises(JournalMismatch):
        SearchJournal.open(path, "sha256:x")
    with open(path) as f:
        assert f.read() == "v1.2.3-some-users-version-file", \
            "a rejected file must not be modified"
    # ...including a torn header from a DIFFERENT search's digest
    other = json.dumps(
        {"kind": SearchJournal.KIND, "v": SearchJournal.VERSION,
         "digest": "sha256:OTHER"}, sort_keys=True)
    with open(path, "w") as f:
        f.write(other[:-7])
    with pytest.raises(JournalMismatch):
        SearchJournal.open(path, "sha256:x")


def test_probe_session_build_declines_on_quarantined_backend():
    """A session built after quarantine would upload device tables to the
    wedged backend (this path has no fallback routing): try_build must
    decline so the search runs fresh, CPU-routed probes."""
    from open_simulator_tpu.simulator.probe import ProbeSession

    base, template, pods = _planner_inputs()
    guard.quarantine("cpu", "watchdog_wedge@dispatch")
    assert ProbeSession.try_build(base, template, copy.deepcopy(pods)) is None
    p = CapacityPlanner(base, template, copy.deepcopy(pods))
    found, n, _ = p.search()
    assert found and p.stats["path"] == "fresh"


def test_probe_session_refuses_dispatch_after_midlife_quarantine():
    """A session whose tables were uploaded BEFORE another simulator
    quarantined the backend must not re-dispatch on it (committed arrays
    override jax.default_device): the containable wedge classification
    surfaces immediately instead of burning a watchdog timeout."""
    from open_simulator_tpu.simulator.probe import ProbeSession

    base, template, pods = _planner_inputs()
    session = ProbeSession.try_build(base, template, copy.deepcopy(pods))
    assert session is not None
    guard.quarantine("cpu", "watchdog_wedge@dispatch")
    with pytest.raises(guard.BackendWedged):
        session.probe_many([1])
    with pytest.raises(guard.BackendWedged):
        session.ensure_capacity(session.n_new + 1)


def test_search_contains_wedge_by_falling_back_to_fresh_probes():
    """A wedge mid-incremental-search is contained: the search falls back to
    fresh probes (on the quarantine-routed backend) and finds the same n."""
    base, template, pods = _planner_inputs()
    p0 = CapacityPlanner(base, template, copy.deepcopy(pods))
    found0, n0, _ = p0.search()
    assert p0.stats["path"] == "incremental"

    guard.reset_for_tests()
    p1 = CapacityPlanner(base, template, copy.deepcopy(pods))
    with installed(FaultPlan([FaultSpec("watchdog_wedge", 1)])):
        found1, n1, _ = p1.search()
    assert (found1, n1) == (found0, n0)
    assert p1.stats["path"] == "fresh"
    assert any(e[0] == "failover" and e[2] == "capacity_search"
               for e in guard.events())


# ---------------------------------------------------- preemption replay cap --


def _preempt_cluster():
    node = make_node("n1", cpu="2000m", memory=str(4 << 30), pods="10")
    pods = [make_pod("low-0", cpu="900m", memory=str(1 << 30)),
            make_pod("low-1", cpu="900m", memory=str(1 << 30)),
            make_pod("high-0", cpu="1800m", memory=str(2 << 30))]
    pods[2]["spec"]["priority"] = 100
    return [node], pods


def test_preemption_replay_cap_skips_attempts(monkeypatch):
    monkeypatch.setenv("OPEN_SIMULATOR_MAX_PREEMPTION_REPLAYS", "0")
    nodes, pods = _preempt_cluster()
    c0 = _metric("simon_preemption_attempts_total")
    sim = Simulator(copy.deepcopy(nodes))
    failed = sim.schedule_pods(copy.deepcopy(pods))
    assert sim.preempted == [], "capped run must not evict"
    assert len(failed) == 1  # the high-prio pod stays failed, conservatively
    snap = REGISTRY.values()
    capped = sum(v for k, v in snap.items()
                 if k.startswith("simon_preemption_attempts_total")
                 and 'outcome="capped"' in k)
    assert capped >= 1
    assert _metric("simon_preemption_attempts_total") > c0


def test_preemption_uncapped_still_preempts(monkeypatch):
    monkeypatch.setenv("OPEN_SIMULATOR_MAX_PREEMPTION_REPLAYS", "512")
    nodes, pods = _preempt_cluster()
    sim = Simulator(copy.deepcopy(nodes))
    sim.schedule_pods(copy.deepcopy(pods))
    assert len(sim.preempted) == 2
