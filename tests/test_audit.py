"""simonaudit tests: the HLO parsers, certificate extraction on real
kernels, regression detection against goldens, the wave-chain boundary
invariant, and the CI negative control (doctored fixture golden MUST fail).

The heavyweight full-matrix check (every kernel x bucket x mesh, ~1-2 min of
CPU compiles) is slow-marked; CI runs it via `python tools/run_audit.py`."""

import copy
import json
from pathlib import Path

import pytest

from open_simulator_tpu.analysis import hlo
from open_simulator_tpu.analysis.rules import _DISPATCH_KERNELS
from open_simulator_tpu.ops import kernels

GOLDEN = Path(__file__).parent / "golden" / "audit"
GOLDEN_FIXTURE = Path(__file__).parent / "golden" / "audit_fixture"


# ------------------------------------------------------------ registry ----


def test_registry_covers_every_dispatch_kernel():
    """The audit registry and simonlint's naked-dispatch kernel set must
    name the SAME hot kernels: a kernel the watchdog guards is a kernel the
    auditor certifies."""
    assert set(kernels.HOT_KERNELS) == set(_DISPATCH_KERNELS)


def test_every_registered_kernel_has_a_golden():
    for name in list(kernels.HOT_KERNELS) + [hlo.CHAIN_TARGET]:
        doc = hlo.load_golden(str(GOLDEN), name)
        assert doc is not None, f"no golden certificate file for {name}"
        # every kernel is certified at >= 2 mesh shapes per bucket
        meshes = {k.split("/")[1] for k in doc["certs"]}
        assert len(meshes) >= 2 or name == hlo.CHAIN_TARGET, (name, meshes)


# --------------------------------------------------------- HLO parsers ----

_FAKE_HLO = (
    'HloModule jit_k, is_scheduled=true, input_output_alias={ {0}: (31, {}, '
    'may-alias), {1}: (32, {}, may-alias) }, entry_computation_layout=...\n'
    '  %ar = f32[4,8]{1,0} all-reduce(%x), replica_groups={}\n'
    '  %ags = (f32[2,4]{1,0}, f32[4,4]{1,0}) all-gather-start(%y)\n'
    '  %agd = f32[4,4]{1,0} all-gather-done(%ags)\n'
    '  %use = f32[4,8]{1,0} add(%ar, %ar)\n'
    '  %cc = f32[1]{0} custom-call(%use), custom_call_target="TopK"\n'
    '  %cb = f32[1]{0} custom-call(%use), '
    'custom_call_target="xla_python_cpu_callback"\n'
)


def test_collective_census_counts_and_bytes():
    census = hlo.collective_census(_FAKE_HLO)
    assert census["all-reduce"] == {"count": 1, "bytes": 4 * 8 * 4}
    # -start counted once (tuple bytes summed), -done not double-counted
    assert census["all-gather"]["count"] == 1
    assert census["all-gather"]["bytes"] == (2 * 4 + 4 * 4) * 4
    assert "all-to-all" not in census


def test_alias_count_balances_nested_braces():
    assert hlo._alias_count(_FAKE_HLO) == 2
    assert hlo._alias_count("HloModule jit_k, entry_computation_layout=x\n") == 0


def test_escape_census_splits_host_callbacks():
    custom, host = hlo.escape_census(_FAKE_HLO)
    assert custom == ["TopK"]
    assert host == ["xla_python_cpu_callback"]


# ------------------------------------------------- live certificates ----


def test_schedule_wave_certificate_matches_golden():
    cert = hlo.audit_kernel("schedule_wave", "s16x32", 2)
    assert cert["collective_count"] > 0  # the wave genuinely reduces
    assert cert["donation"] == {"declared": 8, "aliased": 8, "held": True,
                                "image_leaf_aliased": 0}
    assert cert["host_callbacks"] == []
    assert cert["carry_promotions"] == []
    golden = hlo.load_golden(str(GOLDEN), "schedule_wave")
    gcert = golden["certs"]["s16x32/nodes2"]
    assert hlo.check_cert(cert, gcert) == []
    assert cert["static_digest"] == gcert["static_digest"]


def test_single_device_certificate_has_no_collectives():
    cert = hlo.audit_kernel("schedule_wave", "s16x32", 1)
    assert cert["collectives"] == {}
    assert cert["donation"]["held"]


def test_diagnostics_kernels_never_donate():
    cert = hlo.audit_kernel("feasibility_jit", "s16x32", 1)
    assert cert["donation"]["declared"] == 0
    assert cert["carry_promotions"] == []


def test_wave_chain_boundary_inserts_nothing_and_donation_holds():
    """The acceptance invariant: the mesh8 wave-chain certificate
    independently confirms zero boundary collectives (the static proof
    behind reshard_bytes == 0) with the chained carry still donated."""
    cert = hlo.audit_wave_chain("s16x32", 8)
    assert cert["boundary_collectives"] == 0
    assert cert["collective_count"] == 2 * cert["single_collective_count"]
    assert cert["donation"]["held"]
    golden = hlo.load_golden(str(GOLDEN), hlo.CHAIN_TARGET)
    assert hlo.check_cert(cert, golden["certs"]["s16x32/nodes8"]) == []


# ------------------------------------------------- regression gating ----


def _golden_cert():
    return copy.deepcopy(
        hlo.load_golden(str(GOLDEN), "schedule_wave")["certs"]["s16x32/nodes8"])


@pytest.mark.parametrize("mutate,needle", [
    (lambda c: c["collectives"].setdefault(
        "all-to-all", {"count": 1, "bytes": 64}), "NEW collective kind"),
    (lambda c: c["collectives"]["all-reduce"].__setitem__(
        "count", c["collectives"]["all-reduce"]["count"] + 1), "count grew"),
    (lambda c: c.__setitem__("static_digest", "0" * 16), "signature drift"),
    (lambda c: c["donation"].update(aliased=3, held=False),
     "donation dropped"),
    (lambda c: c.__setitem__("host_callbacks", ["xla_python_cpu_callback"]),
     "host callbacks escape"),
    (lambda c: c.__setitem__("carry_promotions",
                             [{"leaf": "requested", "in": "float32",
                               "out": "float64"}]), "dtype promotion"),
])
def test_check_cert_flags_each_regression_class(mutate, needle):
    golden = _golden_cert()
    live = copy.deepcopy(golden)
    mutate(live)
    live["collective_count"] = sum(
        v["count"] for v in live["collectives"].values())
    msgs = hlo.check_cert(live, golden)
    assert any(needle in m for m in msgs), msgs


def test_check_cert_clean_on_identical():
    golden = _golden_cert()
    assert hlo.check_cert(copy.deepcopy(golden), golden) == []


def test_missing_golden_is_a_regression(tmp_path):
    cert = _golden_cert()
    regressions, _ = hlo.check_certs([cert], str(tmp_path))
    assert regressions and "no golden certificate" in regressions[0]


def test_fixture_gate_fails_against_doctored_golden():
    """The CI negative control: the deliberately-regressing fixture kernel
    (one extra all-reduce vs its checked-in golden) MUST fail --check."""
    cert = hlo.audit_fixture(8)
    assert cert["collectives"]["all-reduce"]["count"] == 2
    regressions, _ = hlo.check_certs([cert], str(GOLDEN_FIXTURE))
    assert any("all-reduce count grew 1 -> 2" in r for r in regressions)
    assert any("exceeds budget" in r for r in regressions)


# --------------------------------------------------------------- CLI ----


def test_cli_rejects_unknown_targets_and_buckets():
    with pytest.raises(SystemExit):
        hlo.run_audit(["--select", "no-such-kernel"])
    with pytest.raises(SystemExit):
        hlo.run_audit(["--buckets", "no-such-bucket"])


def test_cli_check_fixture_exit_codes(capsys):
    rc = hlo.run_audit(["--check", "--select", hlo.FIXTURE_TARGET,
                        "--golden-dir", str(GOLDEN_FIXTURE)])
    assert rc == 1
    out = capsys.readouterr()
    assert "REGRESSION" in out.err


def test_update_roundtrip_is_stable(tmp_path):
    """--update into a fresh dir, then check against it: zero regressions
    and a byte-identical second write (the digest is deterministic)."""
    cert = hlo.audit_kernel("schedule_wave", "s16x32", 2)
    hlo.write_goldens(str(tmp_path), [cert])
    first = (tmp_path / "schedule_wave.json").read_text()
    cert2 = hlo.audit_kernel("schedule_wave", "s16x32", 2)
    regressions, notes = hlo.check_certs([cert2], str(tmp_path))
    assert regressions == []
    hlo.write_goldens(str(tmp_path), [cert2])
    assert (tmp_path / "schedule_wave.json").read_text() == first
    assert json.loads(first)["certs"]["s16x32/nodes2"]["schema"] == hlo.SCHEMA


@pytest.mark.slow
def test_full_matrix_matches_goldens():
    """Every registered hot kernel at every canonical bucket x mesh shape
    agrees with its golden certificate (the CI gate, in-process)."""
    certs = hlo.run_targets(None, hlo.DEFAULT_BUCKETS, hlo.DEFAULT_SHARDS)
    # + the wave chain (nodes 1/8) + the affinity epoch (every mesh shape)
    assert len(certs) == len(kernels.HOT_KERNELS) * 2 * 3 + 2 + 2 * 3
    regressions, _ = hlo.check_certs(certs, str(GOLDEN))
    assert regressions == [], "\n".join(regressions)


def test_lowerable_rejects_stats_on_non_affinity_kernels():
    from open_simulator_tpu.parallel.mesh import make_node_mesh, sharded_kernels

    sk = sharded_kernels(make_node_mesh(1))
    with pytest.raises(ValueError, match="no stats variant"):
        sk.lowerable("schedule_wave", stats=True)


def test_selected_chain_without_multishard_mesh_is_an_error():
    # the chain target needs a multi-shard mesh; selecting it with only
    # 1-shard meshes must refuse loudly, never silently skip the target
    # (alone OR alongside other targets) and report a green gate
    with pytest.raises(SystemExit):
        hlo.run_audit(["--check", "--select", hlo.CHAIN_TARGET,
                       "--shards", "1"])
    with pytest.raises(SystemExit):
        hlo.run_audit(["--check", "--shards", "1",
                       "--select", f"{hlo.CHAIN_TARGET},schedule_wave"])


def test_full_update_prunes_stale_goldens(tmp_path):
    stale = {"schema": hlo.SCHEMA, "kernel": "removed_kernel", "certs": {}}
    (tmp_path / "removed_kernel.json").write_text(json.dumps(stale))
    cert = hlo.audit_fixture(8)
    live = copy.deepcopy(cert)
    live["mesh"] = "nodes2"  # a mesh key no longer produced
    hlo.write_goldens(str(tmp_path), [live])
    # partial write merges; full write regenerates and prunes
    hlo.write_goldens(str(tmp_path), [cert], full=True)
    assert not (tmp_path / "removed_kernel.json").exists()
    doc = json.loads((tmp_path / f"{hlo.FIXTURE_TARGET}.json").read_text())
    assert list(doc["certs"]) == ["fixture/nodes8"]  # stale key dropped


def test_update_preserves_hand_tightened_budgets(tmp_path):
    """--update must never silently loosen a pinned golden budget: the
    stricter bound and the hand-written note survive regeneration, and only
    a hand edit of the golden file can relax them."""
    cert = hlo.audit_fixture(8)
    hlo.write_goldens(str(tmp_path), [cert])
    doc = json.loads((tmp_path / f"{hlo.FIXTURE_TARGET}.json").read_text())
    key = "fixture/nodes8"
    doc["certs"][key]["budget"]["max_collective_count"] = 1  # hand-tightened
    doc["certs"][key]["budget"]["note"] = "pinned: one reduction only"
    (tmp_path / f"{hlo.FIXTURE_TARGET}.json").write_text(json.dumps(doc))
    hlo.write_goldens(str(tmp_path), [hlo.audit_fixture(8)], full=True)
    after = json.loads((tmp_path / f"{hlo.FIXTURE_TARGET}.json").read_text())
    assert after["certs"][key]["budget"]["max_collective_count"] == 1
    assert after["certs"][key]["budget"]["note"] == "pinned: one reduction only"
