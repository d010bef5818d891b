"""End-to-end GSPMD sharding (parallel/mesh.py ShardedKernels): placements
bit-identical to single-device at every shard count and on every kernel
route, zero recompiles on a warm second dispatch, carry donation actually
frees the old buffers, chained dispatches never reshard the carry, and the
phantom padding / node-axis growth invariants survive donation and reuse."""

import copy
import re

import numpy as np
import pytest

import jax

from fixtures import make_node, make_pod
from open_simulator_tpu.models.fakenode import new_fake_nodes
from open_simulator_tpu.obs import REGISTRY
from open_simulator_tpu.ops import kernels
from open_simulator_tpu.parallel.mesh import (
    carry_reshard_bytes,
    carry_shardings,
    make_node_mesh,
    make_scenario_mesh,
    sharded_kernels,
    table_shardings,
    to_device_sharded,
)
from open_simulator_tpu.simulator.encode import scheduling_signature
from open_simulator_tpu.simulator.engine import Simulator
from open_simulator_tpu.simulator.probe import ProbeSession


def _census(sim):
    out = {}
    for i, nps in enumerate(sim.pods_on_node):
        for p in nps:
            key = (i, scheduling_signature(p))
            out[key] = out.get(key, 0) + 1
    return out


def _mixed_workload():
    """One batch exercising every engine route: wave (identical pods),
    cap1 wave (host ports), affinity (self-matching hostname DNS spread),
    and serial (runs shorter than WAVE_MIN with alternating groups)."""
    nodes = [make_node(f"n{i}", cpu="16", memory="32Gi", pods="24")
             for i in range(26)]  # 26: not divisible by 8 → phantom padding
    pods = [make_pod(f"web-{i}", cpu="250m", memory="256Mi",
                     labels={"app": "web"}) for i in range(40)]
    for i in range(30):
        p = make_pod(f"sp-{i}", cpu="100m", memory="64Mi",
                     labels={"app": "sp"})
        p["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 2, "topologyKey": "kubernetes.io/hostname",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "sp"}}}]
        pods.append(p)
    pods += [make_pod(f"porty-{i}", cpu="100m", memory="64Mi",
                      labels={"app": "porty"}, host_ports=[9090])
             for i in range(10)]
    for i in range(6):  # alternating singletons → serial scan segment
        pods.append(make_pod(f"a-{i}", cpu="300m", memory="128Mi"))
        pods.append(make_pod(f"b-{i}", cpu="100m", memory="512Mi"))
    return nodes, pods


def _run(nodes, pods, mesh=None):
    sim = Simulator(copy.deepcopy(nodes), use_mesh=mesh is not None)
    if mesh is not None:
        sim._mesh = mesh  # pin the shard count (auto would take all devices)
    failed = sim.schedule_pods(copy.deepcopy(pods))
    return sim, _census(sim), len(failed)


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_placements_bit_identical_across_shard_counts(shards):
    nodes, pods = _mixed_workload()
    _, want, want_failed = _run(nodes, pods, mesh=None)
    sim, got, got_failed = _run(nodes, pods, mesh=make_node_mesh(shards))
    kinds = {s[0] for s in sim._segments(sim._last_tables,
                                         len(sim._last_tables.valid))}
    assert got == want and got_failed == want_failed
    # the batch really covered the wave/affinity/serial routes
    assert {"wave", "affinity", "serial"} <= kinds


def _hard_affinity_workload():
    """Hard-predicate affinity batch: required self-anti-affinity on
    hostname (one per node, the overflow must FAIL), required self-affinity
    (the bootstrap-then-pack path), and DoNotSchedule spread — the gates
    the epoch-amortized sharded affinity kernel folds into its stacked
    per-epoch all-reduce and must reproduce bit-for-bit."""
    nodes = [make_node(f"h{i}", cpu="16", memory="32Gi", pods="24")
             for i in range(26)]  # 26: not divisible by 8 → phantom padding
    pods = []
    for i in range(30):  # 26 can place, 4 must fail identically
        p = make_pod(f"anti-{i}", cpu="100m", memory="64Mi",
                     labels={"app": "anti"})
        p["spec"]["affinity"] = {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "kubernetes.io/hostname",
                "labelSelector": {"matchLabels": {"app": "anti"}}}]}}
        pods.append(p)
    for i in range(12):  # required self-affinity: bootstrap a node, pack it
        p = make_pod(f"pack-{i}", cpu="100m", memory="64Mi",
                     labels={"app": "pack"})
        p["spec"]["affinity"] = {"podAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "kubernetes.io/hostname",
                "labelSelector": {"matchLabels": {"app": "pack"}}}]}}
        pods.append(p)
    for i in range(20):  # hard spread: DoNotSchedule at maxSkew 1
        p = make_pod(f"hs-{i}", cpu="100m", memory="64Mi",
                     labels={"app": "hs"})
        p["spec"]["topologySpreadConstraints"] = [{
            "maxSkew": 1, "topologyKey": "kubernetes.io/hostname",
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"app": "hs"}}}]
        pods.append(p)
    return nodes, pods


@pytest.mark.parametrize("shards", [2, 8])
def test_hard_predicate_affinity_bit_identical_across_shards(shards):
    """The epoch-amortized collective path (ONE stacked all-reduce + ONE
    payload all-gather per epoch, selection replicated post-gather) must
    not perturb a single placement on the hard-predicate wave: required
    anti-affinity overflow fails identically, the self-affinity bootstrap
    picks the same node, and hard spread balances identically."""
    nodes, pods = _hard_affinity_workload()
    _, want, want_failed = _run(nodes, pods, mesh=None)
    assert want_failed == 4  # the hard predicate really bites
    sim, got, got_failed = _run(nodes, pods, mesh=make_node_mesh(shards))
    kinds = {s[0] for s in sim._segments(sim._last_tables,
                                         len(sim._last_tables.valid))}
    assert "affinity" in kinds  # the batch really drove the affinity kernel
    assert got == want and got_failed == want_failed


def test_zero_recompiles_on_warm_second_dispatch():
    """Two Simulators over EQUAL meshes share one sharded-executable set:
    the second run must not trigger a single XLA backend compile
    (simon_xla_backend_compiles_total is jax.monitoring ground truth)."""
    nodes, pods = _mixed_workload()
    _run(nodes, pods, mesh=make_node_mesh(8))  # pays every compile
    before = REGISTRY.values().get("simon_xla_backend_compiles_total", 0)
    _run(nodes, pods, mesh=make_node_mesh(8))  # fresh EQUAL mesh, same shapes
    after = REGISTRY.values().get("simon_xla_backend_compiles_total", 0)
    assert after == before, "warm second dispatch recompiled"


def _encode_unconstrained(n_nodes=26, n_pods=32):
    nodes = [make_node(f"n{i}", cpu="16", memory="32Gi")
             for i in range(n_nodes)]
    pods = [make_pod(f"p-{i}", cpu="500m", memory="256Mi",
                     labels={"app": "w"}) for i in range(n_pods)]
    sim = Simulator(nodes)
    return sim, sim.encode_batch(pods)


def test_donation_gated_off_on_multi_device_cpu_mesh():
    """Dispatching donated executables on a multi-device CPU mesh is unsound
    under the XLA:CPU async runtime (intermittent in-place corruption — see
    parallel.mesh.donation_runtime_safe), so the factory must downgrade a
    donate=True request to the undonated view: inputs stay alive. The
    donated artifact itself is still certified (AOT, never executed) by
    simonaudit's goldens — donation.aliased == 8/8 for every engine kernel."""
    from open_simulator_tpu.parallel.mesh import donation_runtime_safe

    mesh = make_node_mesh(8)
    assert not donation_runtime_safe(mesh)  # 8 virtual CPU devices
    sim, bt = _encode_unconstrained()
    tables, carry, bt = to_device_sharded(bt, mesh)
    sk = sharded_kernels(mesh, donate=True)  # downgraded by the factory
    assert sk.donate is False
    final, choices = sk.schedule_batch(
        tables, carry, bt.pod_group, bt.forced_node, bt.valid,
        n_zones=bt.n_zones, enable_gpu=False, enable_storage=False)
    jax.block_until_ready(final)
    assert not carry.requested.is_deleted(), "carry donated despite the gate"
    assert not tables.alloc.is_deleted()  # tables are never donated

    # the explicit undonated view is the same object (shared jit cache)
    assert sharded_kernels(mesh, donate=False) is sk


def test_chained_dispatches_zero_reshard():
    """Wave N's output carry must already BE in wave N+1's declared input
    sharding — per-leaf equivalence, the carry_reshard_bytes audit, and the
    engine's simon_reshard_bytes_total all agree on zero."""
    mesh = make_node_mesh(8)
    sim, bt = _encode_unconstrained()
    tables, carry, bt = to_device_sharded(bt, mesh)
    sk = sharded_kernels(mesh, donate=False)
    declared = carry_shardings(mesh)
    c = carry
    for _ in range(2):  # chain two dispatches through the same executable
        c, _j, _p, _o = sk.schedule_wave(
            tables, c, np.int32(0), np.int32(8), np.bool_(False))
        for leaf, want in zip(c, declared):
            assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
    assert carry_reshard_bytes(c, sk.carry_sh) == 0

    # engine-level: a full multi-segment mesh run keeps the counter at zero
    before = REGISTRY.values().get("simon_reshard_bytes_total", 0)
    nodes, pods = _mixed_workload()
    _run(nodes, pods, mesh=make_node_mesh(8))
    assert REGISTRY.values().get("simon_reshard_bytes_total", 0) == before == 0


def _collective_count(compiled_text):
    return len(re.findall(
        r"\b(?:all-reduce|all-gather|reduce-scatter|collective-permute"
        r"|all-to-all)\b", compiled_text))


def test_chained_hlo_adds_no_boundary_collectives():
    """Compile one wave and a two-wave chain under the SAME in/out
    shardings: the chained program may contain at most 2x the single
    program's collectives — i.e. the dispatch boundary itself inserts zero
    resharding collectives."""
    mesh = make_node_mesh(8)
    sim, bt = _encode_unconstrained()
    tables, carry, bt = to_device_sharded(bt, mesh)
    ts, cs = table_shardings(mesh), carry_shardings(mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    node_sh = NamedSharding(mesh, P("nodes"))
    raw = kernels.schedule_wave.__wrapped__

    def single(tb, cry, g, m, cap1):
        c, j, p, _ = raw(tb, cry, g, m, cap1)
        return c, j, p

    def chain(tb, cry, g, m, cap1):
        c1, j1, p1, _ = raw(tb, cry, g, m, cap1)
        c2, j2, p2, _ = raw(tb, c1, g, m, cap1)
        return c2, j1 + j2, p1 + p2

    args = (tables, carry, np.int32(0), np.int32(8), np.bool_(False))
    shard_kw = dict(in_shardings=(ts, cs, rep, rep, rep),
                    out_shardings=(cs, node_sh, rep))
    n1 = _collective_count(
        jax.jit(single, **shard_kw).lower(*args).compile().as_text())
    n2 = _collective_count(
        jax.jit(chain, **shard_kw).lower(*args).compile().as_text())
    assert n1 > 0  # the wave genuinely reduces across shards
    assert n2 <= 2 * n1, (
        f"chained program has {n2} collectives vs {n1} for one wave: "
        f"the dispatch boundary inserted resharding collectives")


def test_phantom_nodes_unwinnable_under_donation_and_reuse():
    """26 real nodes over 8 shards leave 6 phantom columns. Two back-to-back
    batches on ONE mesh simulator (donated carry chain, reused executables)
    under hard capacity pressure: every placement lands on a real node, the
    overflow fails instead of spilling onto phantoms, and the phantom carry
    rows stay untouched."""
    nodes = [make_node(f"n{i}", cpu="4", memory="8Gi", pods="8")
             for i in range(26)]  # 104 cpu-capacity pods cluster-wide
    mk = lambda i: make_pod(f"p-{i}", cpu="1", memory="128Mi",
                            labels={"app": "w"})
    sim = Simulator(nodes, use_mesh=True)
    sim._mesh = make_node_mesh(8)
    failed1 = sim.schedule_pods([mk(i) for i in range(80)])
    failed2 = sim.schedule_pods([mk(100 + i) for i in range(80)])
    assert len(failed1) == 0
    assert len(failed2) == 80 - (104 - 80)  # only real capacity remains
    assert sum(len(p) for p in sim.pods_on_node) == 104
    # the carry's phantom rows never accumulated anything
    req = np.asarray(sim._last_carry.requested)
    assert req.shape[0] >= 32 and not req[26:].any()
    # and the single-device engine agrees exactly
    sim1 = Simulator(nodes, use_mesh=False)
    f1 = sim1.schedule_pods([mk(i) for i in range(80)])
    f2 = sim1.schedule_pods([mk(100 + i) for i in range(80)])
    assert (len(f1), len(f2)) == (len(failed1), len(failed2))
    assert _census(sim1) == _census(sim)


def test_probe_fanout_scenario_mesh_matches_unsharded_session():
    """The capacity prober's fan-out on a ('scenarios','nodes') mesh — the
    sharded probe_*_fanout executables — must return the same counts and
    utilization as the unsharded session and fresh probes."""
    base = [make_node(f"base-{i}", cpu="8", memory="16Gi") for i in range(2)]
    template = make_node("tpl", cpu="8", memory="16Gi")
    pods = [make_pod(f"p-{i}", cpu="2", memory="2Gi") for i in range(40)]
    s_mesh = ProbeSession.try_build(base, template, pods, n_new=12,
                                    mesh=make_scenario_mesh(4))
    s_plain = ProbeSession.try_build(base, template, pods, n_new=12)
    assert s_mesh is not None and s_plain is not None
    ns = [0, 3, 5, 7, 11]
    assert s_mesh.probe_many(ns) == s_plain.probe_many(ns)


def test_device_extension_matches_host_reupload():
    """ensure_capacity's shard-local growth: the device-extended tables must
    be BIT-identical to a host re-upload of the extended host mirror, with
    zero bytes staged host→device for the table set."""
    base = [make_node(f"base-{i}", cpu="8", memory="16Gi") for i in range(2)]
    template = make_node("tpl", cpu="8", memory="16Gi")
    pods = [make_pod(f"p-{i}", cpu="2", memory="2Gi") for i in range(40)]
    session = ProbeSession.try_build(base, template, pods, n_new=2)
    assert session is not None
    assert not session._host_counters and not session._host_carriers
    before = REGISTRY.values().get("simon_device_transfer_bytes_total", 0)
    session.ensure_capacity(20)  # crosses the padding bucket → extension
    assert session.extensions == 1
    after = REGISTRY.values().get("simon_device_transfer_bytes_total", 0)
    assert after == before, "device extension staged table bytes from host"
    # bit-identity against the host path
    from open_simulator_tpu.parallel.mesh import tables_from_batch

    host = tables_from_batch(session._bt)
    for name, dev, want in zip(kernels.Tables._fields, session._tables, host):
        np.testing.assert_array_equal(
            np.asarray(dev), np.asarray(want), err_msg=name)
    # and probe results still match fresh probes at the extended size
    sim = Simulator(base + new_fake_nodes(template, 20))
    fresh = sim.probe_pods(list(pods))
    got = session.probe_many([20])[20]
    assert (got[0], got[1]) == fresh


def test_hostname_rows_fall_back_to_host_reupload():
    """Required self-anti-affinity on hostname gives the session
    hostname-keyed carrier/counter rows: extension must take the host
    re-upload path (per-node fresh domains) and stay exact."""
    base = [make_node(f"base-{i}", cpu="8", memory="16Gi") for i in range(2)]
    template = make_node("tpl", cpu="8", memory="16Gi")
    pods = []
    for i in range(12):
        p = make_pod(f"a-{i}", cpu="2", memory="2Gi", labels={"app": "anti"})
        p["spec"]["affinity"] = {"podAntiAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "kubernetes.io/hostname",
                "labelSelector": {"matchLabels": {"app": "anti"}}}]}}
        pods.append(p)
    session = ProbeSession.try_build(base, template, pods, n_new=2)
    assert session is not None
    assert session._host_counters or session._host_carriers
    tb = REGISTRY.values().get("simon_device_transfer_bytes_total", 0)
    session.ensure_capacity(20)
    assert REGISTRY.values().get(
        "simon_device_transfer_bytes_total", 0) > tb  # host path re-staged
    got = session.probe_many([14])[14]
    sim = Simulator(base + new_fake_nodes(template, 14))
    assert (got[0], got[1]) == sim.probe_pods(list(pods))


def test_probe_fanout_utilization_stable_across_repeated_sessions():
    """Regression (found while goldening simonaudit's donation certificates):
    a DONATED fan-out dispatch of the [S, N, R] carry on a scenario mesh
    intermittently corrupted the fetched `requested` leaf on the XLA:CPU
    runtime (~1/3 of dispatches under a warm compile cache) — garbage
    utilization with correct placed counts. The probe path now dispatches
    the undonated view; several fresh sessions must agree exactly."""
    base = [make_node(f"base-{i}", cpu="8", memory="16Gi") for i in range(2)]
    template = make_node("tpl", cpu="8", memory="16Gi")
    pods = [make_pod(f"p-{i}", cpu="2", memory="2Gi") for i in range(40)]
    ns = [0, 3, 5, 7, 11]
    plain = ProbeSession.try_build(base, template, list(pods), n_new=12)
    want = plain.probe_many(ns)
    for _ in range(4):
        s = ProbeSession.try_build(base, template, list(pods), n_new=12,
                                   mesh=make_scenario_mesh(4))
        assert s.probe_many(ns) == want


def test_donation_still_frees_carry_on_single_device_mesh():
    """Where donation stays ENABLED (donation_runtime_safe: single-device
    meshes, accelerators), a donated dispatch must actually free its input
    carry — the end-to-end donation behavior the audit's AOT certificates
    cannot observe. A dispatch-time regression that stops donating would
    pass the goldens but fail here."""
    from open_simulator_tpu.parallel.mesh import donation_runtime_safe

    mesh = make_node_mesh(1)
    assert donation_runtime_safe(mesh)
    sim, bt = _encode_unconstrained()
    tables, carry, bt = to_device_sharded(bt, mesh)
    sk = sharded_kernels(mesh, donate=True)
    assert sk.donate is True
    final, choices = sk.schedule_batch(
        tables, carry, bt.pod_group, bt.forced_node, bt.valid,
        n_zones=bt.n_zones, enable_gpu=False, enable_storage=False)
    jax.block_until_ready(final)
    assert carry.requested.is_deleted(), "donated carry buffer still alive"
    assert not tables.alloc.is_deleted()  # tables are never donated
