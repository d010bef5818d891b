"""simonpulse tests: the per-dispatch performance ledger (obs/pulse.py).

The contract under test (ISSUE 18 acceptance):
- the ring is bounded: records past capacity evict the oldest and count the
  eviction (ledger drops are observable, never silent);
- ledger dispatch records reconcile EXACTLY with the
  simon_compile_cache_{hits,misses}_total census and run-record pods with
  simon_scheduling_attempts_total on a real Simulator run (record_dispatch
  is the single definition of "one dispatch happened");
- records are keyed by the simonaudit digest family: same (kernel, dims) →
  same 16-hex digest == analysis.hlo.dispatch_digest; a forced recompile
  (new shape bucket) shows up as a NEW digest with a cold record;
- pulse off is bit-identical: same placements/reasons, zero movement in any
  simon_pulse_* metric;
- an injected slow warm dispatch trips the MAD drift detector against the
  PRIOR window (the outlier cannot raise its own baseline);
- the static roofline covers every HOT_KERNELS entry at both audit buckets
  on 1/2/8-shard meshes (cost fields in the audit goldens);
- the JSONL spill rotates at the size cap and round-trips through
  summarize_records (the `simon pulse --jsonl` path).
"""

import copy
import json
import re

import pytest

from open_simulator_tpu.analysis.hlo import dispatch_digest
from open_simulator_tpu.obs import REGISTRY, instruments, pulse
from open_simulator_tpu.ops import kernels
from open_simulator_tpu.resilience import guard
from open_simulator_tpu.simulator.engine import Simulator
from open_simulator_tpu.utils.synth import synth_cluster


@pytest.fixture(autouse=True)
def _clean_pulse_and_guard():
    pulse.reset_for_tests()
    guard.reset_for_tests()
    yield
    pulse.reset_for_tests()
    guard.reset_for_tests()


def _vals():
    return REGISTRY.values()


def _sum(values, prefix):
    return sum(v for k, v in values.items() if k.startswith(prefix))


def _pulse_deltas(v0, v1):
    keys = {k for k in v0 if k.startswith("simon_pulse_")} | {
        k for k in v1 if k.startswith("simon_pulse_")}
    return {k: v1.get(k, 0) - v0.get(k, 0) for k in keys
            if v1.get(k, 0) != v0.get(k, 0)}


def _commit(p, kernel="schedule_wave", dims=None, cold=False,
            wall_s=1e-3, site="dispatch", pods=4):
    """One synthetic attributed dispatch: park a note the way
    obs.record_dispatch's hook does, then drain it the way guard.supervised
    does after the unit returns."""
    pulse.note_dispatch(kernel, dims if dims is not None else
                        {"N": 8, "P": 4}, cold)
    p.commit_unit(site=site, pods=pods, wall_s=wall_s)


def run_once(nodes, pods):
    sim = Simulator(copy.deepcopy(nodes))
    failed = sim.schedule_pods(copy.deepcopy(pods))
    placements = {}
    for i, node_pods in enumerate(sim.pods_on_node):
        for p in node_pods:
            placements[p["metadata"]["name"]] = i
    reasons = {u.pod["metadata"]["name"]: u.reason for u in failed}
    return placements, reasons


@pytest.fixture(scope="module")
def small_cluster():
    return synth_cluster(16, 60, hard_predicates=True)


# ------------------------------------------------------------- ring bounds ---


def test_ring_bounds_and_drop_accounting():
    v0 = _vals()
    p = pulse.enable(capacity=4)
    assert instruments._DISPATCH_HOOK is pulse.note_dispatch
    for i in range(7):
        _commit(p, wall_s=1e-3 * (i + 1))
    recs = p.records()
    assert len(recs) == 4
    # the ring keeps the NEWEST records; seq is monotone
    assert [r["seq"] for r in recs] == [4, 5, 6, 7]
    s = p.summary()
    assert s["records_total"] == 7
    assert s["records_dropped"] == 3
    assert s["ring_len"] == 4 and s["capacity"] == 4
    v1 = _vals()
    assert _sum(v1, "simon_pulse_records_total") - _sum(
        v0, "simon_pulse_records_total") == 7
    assert _sum(v1, "simon_pulse_records_dropped_total") - _sum(
        v0, "simon_pulse_records_dropped_total") == 3
    pulse.disable()
    assert instruments._DISPATCH_HOOK is None
    assert pulse.active() is None


def test_commit_without_notes_records_nothing():
    p = pulse.enable(capacity=8)
    p.commit_unit(site="fetch", pods=0, wall_s=1e-3)
    assert p.records() == []
    assert p.summary()["records_total"] == 0


# ------------------------------------------------- real-run reconciliation ---


def test_ledger_reconciles_with_census_on_real_run(small_cluster):
    nodes, pods = small_cluster
    run_once(nodes, pods)                     # cold compiles, pulse off
    run_once(nodes, pods)                     # warm oracle
    p = pulse.enable(capacity=4096)
    run_once(nodes, pods)                     # ledger warm-up
    before = len(p.records())
    v0 = _vals()
    run_once(nodes, pods)
    v1 = _vals()
    new = p.records()[before:]
    disp = [r for r in new if r["kind"] == "dispatch"]
    runs = [r for r in new if r["kind"] == "run"]

    d_census = (_sum(v1, "simon_compile_cache_hits_total")
                - _sum(v0, "simon_compile_cache_hits_total")
                + _sum(v1, "simon_compile_cache_misses_total")
                - _sum(v0, "simon_compile_cache_misses_total"))
    d_attempts = (_sum(v1, "simon_scheduling_attempts_total")
                  - _sum(v0, "simon_scheduling_attempts_total"))
    assert disp, "real run produced no attributed dispatch records"
    assert len(disp) == d_census
    assert sum(r["pods"] for r in runs) == d_attempts == len(pods)
    assert (_sum(v1, "simon_pulse_records_total")
            - _sum(v0, "simon_pulse_records_total")) == len(new)
    for r in disp:
        assert r["kernel"] and re.fullmatch(r"[0-9a-f]{16}", r["digest"])
        assert r["site"] in ("dispatch", "fetch")
        assert r["cold"] is False          # everything warmed above
        assert "run" in r                  # attributed to an enclosing run
    for r in runs:
        # table_build is a SLICE of encode (the ROADMAP-5 per-chunk
        # instrument), so it is excluded from the disjoint-phase sum
        disjoint = sum(v for k, v in r["phases"].items()
                       if k != "table_build")
        assert disjoint <= r["wall_s"] * 1.001 + 1e-6
        assert r["phases"].get("table_build", 0.0) <= r["phases"]["encode"]
        assert "dispatch" in r["phases"]


def test_pulse_off_is_bit_identical(small_cluster):
    nodes, pods = small_cluster
    run_once(nodes, pods)                     # warm
    v0 = _vals()
    placed_off, reasons_off = run_once(nodes, pods)
    assert _pulse_deltas(v0, _vals()) == {}, (
        "pulse-off run moved simon_pulse_* samples")
    pulse.enable(capacity=4096)
    placed_on, reasons_on = run_once(nodes, pods)
    assert placed_on == placed_off
    assert reasons_on == reasons_off


# ----------------------------------------------------------- digest keying ---


def test_digest_keying_is_stable_and_audit_compatible():
    p = pulse.enable(capacity=64)
    dims_a = {"N": 8, "P": 4, "mesh": ""}
    dims_b = {"N": 16, "P": 4, "mesh": ""}
    _commit(p, dims=dict(dims_a), cold=True)
    _commit(p, dims=dict(dims_a), cold=False)
    _commit(p, dims=dict(dims_b), cold=True)   # forced recompile: new bucket
    a1, a2, b1 = p.records()
    assert a1["digest"] == a2["digest"]
    assert a1["digest"] != b1["digest"]
    # the ledger key IS the simonaudit runtime digest — one digest family
    assert a1["digest"] == dispatch_digest("schedule_wave", dims_a)
    assert b1["digest"] == dispatch_digest("schedule_wave", dims_b)
    assert (a1["cold"], a2["cold"], b1["cold"]) == (True, False, True)
    rows = {r["digest"]: r for r in p.summary()["kernels"]}
    assert rows[a1["digest"]]["n"] == 2
    assert rows[a1["digest"]]["cold"] == 1
    assert rows[b1["digest"]]["n"] == 1


def test_recompile_on_new_shape_is_cold_under_new_digest(small_cluster):
    nodes, pods = small_cluster
    run_once(nodes, pods)                     # warm the small shape
    p = pulse.enable(capacity=4096)
    run_once(nodes, pods)
    warm_keys = {(r["kernel"], r["digest"]) for r in p.records()
                 if r["kind"] == "dispatch"}
    assert all(not r["cold"] for r in p.records()
               if r["kind"] == "dispatch")
    before = len(p.records())
    big_nodes, big_pods = synth_cluster(128, 60, hard_predicates=True)
    run_once(big_nodes, big_pods)             # new node bucket → recompiles
    new = [r for r in p.records()[before:] if r["kind"] == "dispatch"]
    cold = [r for r in new if r["cold"]]
    assert cold, "new shape bucket produced no cold dispatch records"
    for r in cold:
        assert (r["kernel"], r["digest"]) not in warm_keys, (
            "a recompile reused a warm digest — digest not keyed on shape")


# ------------------------------------------------------------- MAD drift -----


def test_mad_flags_injected_slow_dispatch():
    v0 = _vals()
    p = pulse.enable(capacity=64, mad_window=16, mad_min=8, mad_k=5.0)
    for _ in range(9):
        _commit(p, wall_s=1e-3)
    assert all("regression" not in r for r in p.records())
    _commit(p, wall_s=0.1)                    # ~100x the warm baseline
    slow = p.records()[-1]
    assert slow.get("regression") is True
    assert slow["baseline_med_s"] == pytest.approx(1e-3)
    s = p.summary()
    assert s["regressions_total"] == 1
    (row,) = s["kernels"]
    assert row["regressions"] == 1
    assert row["warm_med_s"] == pytest.approx(1e-3)
    v1 = _vals()
    assert _sum(v1, "simon_pulse_regressions_total") - _sum(
        v0, "simon_pulse_regressions_total") == 1


def test_mad_needs_min_window_before_flagging():
    p = pulse.enable(capacity=64, mad_window=16, mad_min=8, mad_k=5.0)
    for _ in range(5):                        # below mad_min: never flags
        _commit(p, wall_s=1e-3)
    _commit(p, wall_s=0.5)
    assert all("regression" not in r for r in p.records())
    assert p.summary()["regressions_total"] == 0


V5E = "TPU v5 lite"


def test_achieved_roofline_fraction_on_warm_dispatch(monkeypatch):
    monkeypatch.setattr(pulse, "device_kind", lambda: V5E)
    p = pulse.enable(capacity=64)
    dims = {"N": 8, "P": 4}
    key = ("schedule_wave", dispatch_digest("schedule_wave", dims))
    cost = {"flops": 5e10, "bytes_accessed": 2e10}
    with p._lock:
        p._costs[key] = cost                  # as _harvest_cost would
    opt = pulse.model_optimal_s(cost, pulse.PEAKS[V5E])
    assert opt == pytest.approx(2e10 / 819e9)  # HBM-bound on v5e
    _commit(p, dims=dims, wall_s=2.0 * opt)
    rec = p.records()[-1]
    assert rec["model_optimal_s"] == pytest.approx(opt)
    assert rec["achieved_frac"] == pytest.approx(0.5, abs=1e-6)
    s = p.summary()
    (row,) = s["kernels"]
    assert row["flops"] == cost["flops"]
    assert row["bytes_accessed"] == cost["bytes_accessed"]
    assert row["achieved_frac"] == pytest.approx(0.5, abs=1e-6)
    assert s["peaks"] == {"device_kind": V5E, "gflops": 197e3, "gbs": 819.0}


def test_unknown_device_has_null_roofline(monkeypatch):
    """A device without published peaks gets no roofline share, never a
    default one."""
    monkeypatch.setattr(pulse, "device_kind", lambda: "cpu")
    p = pulse.enable(capacity=64)
    dims = {"N": 8, "P": 4}
    key = ("schedule_wave", dispatch_digest("schedule_wave", dims))
    with p._lock:
        p._costs[key] = {"flops": 5e10, "bytes_accessed": 2e10}
    _commit(p, dims=dims, wall_s=1e-3)
    rec = p.records()[-1]
    assert "achieved_frac" not in rec and "model_optimal_s" not in rec
    s = p.summary()
    assert s["kernels"][0]["model_optimal_s"] is None
    assert s["peaks"] == {"device_kind": "cpu", "gflops": None, "gbs": None}
    assert pulse.peak_rates(None) is None


# ------------------------------------------------------- static roofline -----


def test_roofline_table_covers_all_hot_kernels():
    rows = pulse.roofline_table(kind=V5E)
    assert rows, "audit goldens carry no cost fields (run simon audit --update)"
    have = set()
    for r in rows:
        m = re.search(r"(\d+)$", r["mesh"])
        assert m, r
        have.add((r["kernel"], r["bucket"], int(m.group(1))))
        assert r["flops"] >= 0.0 and r["bytes_accessed"] >= 0.0
        assert r["model_optimal_s"] > 0.0
    need = {(k, b, s) for k in kernels.HOT_KERNELS
            for b in ("s16x32", "m48x96") for s in (1, 2, 8)}
    missing = need - have
    assert not missing, f"roofline holes: {sorted(missing)[:6]}"
    assert "GB/s (TPU v5 lite)" in pulse.format_roofline(rows, V5E)
    unknown = pulse.roofline_table(kind="cpu")
    assert all(r["model_optimal_s"] is None for r in unknown)
    assert "no published peaks" in pulse.format_roofline(unknown, "cpu")


# --------------------------------------------------------- runs and phases ---


def test_run_window_attributes_dispatches_and_phases():
    v0 = _vals()
    p = pulse.enable(capacity=64)
    with pulse.run_window(pods=5) as run:
        assert run is not None
        pulse.phase("encode", 0.01)
        pulse.phase("dispatch", 0.02)
        pulse.phase("encode", 0.005)
        _commit(p, pods=5)
    disp, runrec = p.records()
    assert disp["run"] == runrec["run"] == run["id"]
    assert runrec["pods"] == 5
    assert runrec["phases"]["encode"] == pytest.approx(0.015)
    assert runrec["phases"]["dispatch"] == pytest.approx(0.02)
    s = p.summary()
    assert s["runs"] == {"n": 1, "pods": 5}
    assert s["phase_seconds"]["encode"] == pytest.approx(0.015)
    v1 = _vals()
    assert _sum(v1, "simon_pulse_phase_seconds_total") - _sum(
        v0, "simon_pulse_phase_seconds_total") == pytest.approx(0.035)


def test_run_window_and_phase_are_noops_when_off():
    v0 = _vals()
    with pulse.run_window(pods=5) as run:
        assert run is None
        pulse.phase("encode", 1.0)
    pulse.note_dispatch("schedule_wave", {"N": 8}, False)  # hookless park
    assert _pulse_deltas(v0, _vals()) == {}


# ------------------------------------------------------------- JSONL spill ---


def test_jsonl_spill_round_trips_through_summarize_records(tmp_path):
    path = tmp_path / "ledger.jsonl"
    p = pulse.enable(capacity=64, jsonl=str(path))      # default size cap
    for i in range(6):
        _commit(p, dims={"N": 8, "P": 4, "i": i % 2}, wall_s=1e-3)
    with pulse.run_window(pods=5):
        pulse.phase("encode", 0.01)
        _commit(p, pods=5)
    live = p.summary()
    pulse.disable()                           # closes the spill file
    spilled = [json.loads(l) for l in
               path.read_text(encoding="utf-8").splitlines() if l]
    assert len(spilled) == live["records_total"] == 8
    offline = pulse.summarize_records(spilled)
    assert offline["records_total"] == 8
    assert offline["runs"] == live["runs"] == {"n": 1, "pods": 5}
    assert offline["phase_seconds"]["encode"] == pytest.approx(0.01)
    live_n = {(r["kernel"], r["digest"]): r["n"] for r in live["kernels"]}
    off_n = {(r["kernel"], r["digest"]): r["n"] for r in offline["kernels"]}
    assert live_n == off_n


def test_jsonl_spill_rotates_at_size_cap(tmp_path):
    path = tmp_path / "ledger.jsonl"
    # ~500-byte cap: ~300-byte records force rotation. Rotation keeps ONE
    # previous generation by design, so the surviving files hold a
    # contiguous SUFFIX of the record stream ending at the newest record.
    p = pulse.enable(capacity=64, jsonl=str(path), jsonl_max_mb=0.0005)
    for i in range(6):
        _commit(p, dims={"N": 8, "P": 4, "i": i}, wall_s=1e-3)
    total = p.summary()["records_total"]
    pulse.disable()
    assert (tmp_path / "ledger.jsonl.1").exists(), "size cap never rotated"
    spilled = []
    for f in (tmp_path / "ledger.jsonl.1", path):
        if f.exists():
            spilled += [json.loads(l) for l in
                        f.read_text(encoding="utf-8").splitlines() if l]
    assert spilled, "rotation left no surviving records"
    seqs = [r["seq"] for r in spilled]
    assert seqs == list(range(seqs[0], total + 1)), (
        f"survivors are not a contiguous suffix ending at {total}: {seqs}")
