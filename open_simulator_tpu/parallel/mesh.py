"""Device-mesh sharding for the batched scheduler (the framework's TP/DP story).

The reference is a single-process Go binary whose only concurrency is a 16-way
goroutine fan-out over nodes inside findNodesThatFitPod
(vendor/.../generic_scheduler.go:333) — see SURVEY.md §2.3. The TPU-native
equivalent is a `jax.sharding.Mesh`:

- **node axis ("tensor parallelism")**: every [*, N] table and [N, *] carry row is
  sharded over the `nodes` mesh axis. Filtering and per-node scoring are then fully
  local to each shard; only the normalizers (max/min over the feasible set), the
  zone sums, and the winner argmax need cross-shard communication, which XLA inserts
  automatically (all-reduce over ICI) from the sharding annotations — no hand-written
  collectives, exactly the scaling-book recipe.
- **scenario axis ("data parallelism")**: independent what-if simulations (e.g. the
  capacity-planning add-node search evaluating several candidate node counts) are
  vmapped over a leading `scenarios` axis and sharded across it.

N must divide the shard count; `pad_batch_tables` appends infeasible phantom nodes
(static_mask=False everywhere) so placements can never land on padding.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import kernels
from ..simulator.encode import BatchTables, pad_batch_tables as _pad_batch_tables, plugin_flags

NODE_AXIS = "nodes"

# wave kernels that take a trailing `mesh` static: on a node-sharding mesh
# they run their epoch loop inside one shard_map region with exactly one
# all-reduce + one all-gather per epoch (see ops/kernels.py)
_MESH_STATIC_KERNELS = ("schedule_wave", "schedule_affinity_wave")
SCENARIO_AXIS = "scenarios"


def make_node_mesh(
    n_devices: Optional[int] = None, scenario_axis: int = 1, devices=None
) -> Mesh:
    """Mesh over the first `n_devices` devices. 1-D ('nodes') by default; pass
    scenario_axis>1 for a 2-D ('scenarios', 'nodes') mesh. `devices` overrides the
    default device list (e.g. jax.devices('cpu') for a virtual mesh)."""
    devs = list(devices) if devices is not None else jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    devs = np.asarray(devs[:n])
    if scenario_axis > 1:
        if n % scenario_axis:
            raise ValueError(f"{n} devices not divisible by scenario axis {scenario_axis}")
        return Mesh(devs.reshape(scenario_axis, n // scenario_axis),
                    (SCENARIO_AXIS, NODE_AXIS))
    return Mesh(devs, (NODE_AXIS,))


# Node-axis padding lives with the encoder (numpy-only); re-exported here because
# the mesh path is its main consumer.
pad_batch_tables = _pad_batch_tables


def table_shardings(mesh: Mesh) -> kernels.Tables:
    """PartitionSpec per Tables field: node axis sharded, everything else replicated."""
    n = P(None, NODE_AXIS)   # [G, N] / [T, N] / [Tc, N]
    r = P()                  # replicated

    def s(spec):
        return NamedSharding(mesh, spec)

    return kernels.Tables(
        alloc=s(P(NODE_AXIS, None)),
        node_zone=s(P(NODE_AXIS)),
        static_mask=s(n), mask_taint=s(n), mask_unsched=s(n), mask_aff=s(n),
        mask_extra=s(n),
        simon_raw=s(n), nodeaff_raw=s(n), taint_raw=s(n), avoid_raw=s(n),
        image_raw=s(n), extra_raw=s(n),
        grp_requests=s(r), grp_nonzero=s(r), grp_unknown=s(r), grp_ports=s(r),
        counter_dom=s(n), counter_topo=s(r), topo_dom=s(n),
        counter_sel_match_g=s(r),
        req_aff_t=s(r), grp_aff_self=s(r), req_anti_t=s(r),
        pref_t=s(r), pref_w=s(r),
        dns_t=s(r), dns_maxskew=s(r), dns_self=s(r), dns_edom=s(r),
        sa_t=s(r), sa_maxskew=s(r), sa_self=s(r),
        ss_t=s(r), ss_skip=s(r),
        carr_dom=s(n), carr_topo=s(r),
        carr_anti_t=s(r), carr_w_t=s(r), carr_w_w=s(r),
        grp_carries=s(r),
        grp_gpu_mem=s(r), grp_gpu_num=s(r), grp_gpu_pre=s(r), grp_gpu_take=s(r),
        dev_total=s(P(NODE_AXIS, None)),
        grp_lvm_size=s(r), grp_lvm_vg=s(r), grp_sdev_size=s(r), grp_sdev_media=s(r),
        vg_cap=s(P(NODE_AXIS, None)), vg_nameid=s(P(NODE_AXIS, None)),
        sdev_cap=s(P(NODE_AXIS, None)), sdev_media=s(P(NODE_AXIS, None)),
    )


def carry_shardings(mesh: Mesh) -> kernels.Carry:
    def s(spec):
        return NamedSharding(mesh, spec)

    return kernels.Carry(
        requested=s(P(NODE_AXIS, None)),
        nonzero=s(P(NODE_AXIS, None)),
        port_used=s(P(NODE_AXIS, None)),
        counter=s(P()),   # [T, D+1] domain counters are global state → replicated
        carrier=s(P()),
        dev_used=s(P(NODE_AXIS, None)),
        vg_req=s(P(NODE_AXIS, None)),
        sdev_alloc=s(P(NODE_AXIS, None)),
    )


def to_device_sharded(
    bt: BatchTables, mesh: Mesh
) -> Tuple[kernels.Tables, kernels.Carry, BatchTables]:
    """Pad to the mesh's node-shard count and device_put with shardings committed, so
    the sharded kernel executables (`sharded_kernels`) receive inputs already in
    their declared layout — the pad is a no-op when the encoder pre-aligned the
    node axis (engine.encode_batch), and the batched device_put pre-partitions
    every table in one host→device staging pass."""
    shards = mesh.shape[NODE_AXIS]
    bt = pad_batch_tables(bt, shards)
    ts, cs = table_shardings(mesh), carry_shardings(mesh)
    # ONE batched transfer per struct: device_put over the (arrays, shardings)
    # pytree pair stages every pre-partitioned leaf together instead of paying
    # a dispatch per table
    tables = jax.device_put(
        kernels.Tables(*(np.asarray(v) for v in tables_from_batch(bt))), ts)
    carry = jax.device_put(
        kernels.Carry(
            requested=bt.seed_requested,
            nonzero=bt.seed_nonzero,
            port_used=bt.seed_port_used,
            counter=bt.seed_counter,
            carrier=bt.seed_carrier,
            dev_used=bt.seed_dev_used,
            vg_req=bt.seed_vg_req,
            sdev_alloc=bt.seed_sdev_alloc,
        ), cs)
    return tables, carry, bt


def schedule_batch_on_mesh(bt: BatchTables, mesh: Mesh):
    """Run one schedulePods batch with the node axis sharded over `mesh`,
    through the explicitly-sharded executable set (carry donated into the
    scan's output where dispatching donated executables is sound — see
    donation_runtime_safe; multi-device CPU meshes downgrade to the
    undonated view).

    Returns (final_carry, choices[P] int32). Choices index the ORIGINAL node list —
    phantom padding is infeasible by construction, so indices never exceed the real N.
    """
    tables, carry, bt = to_device_sharded(bt, mesh)
    enable_gpu, enable_storage = plugin_flags(bt)
    sk = sharded_kernels(mesh)
    final, choices = sk.schedule_batch(
        tables, carry,
        bt.pod_group, bt.forced_node, bt.valid,
        n_zones=bt.n_zones,
        enable_gpu=enable_gpu,
        enable_storage=enable_storage,
    )
    return final, choices


def schedule_scenarios_on_mesh(bt: BatchTables, mesh: Mesh, seed_requested_s: np.ndarray):
    """DP analog: evaluate S independent what-if scenarios (same cluster + pod batch,
    different starting utilization, e.g. candidate add-node states in the capacity
    planner) in one compiled program. `seed_requested_s` is [S, N, R]; the scenario
    axis shards over the mesh's 'scenarios' axis, the node axis over 'nodes'.
    Returns choices [S, P]."""
    if SCENARIO_AXIS not in mesh.shape:
        raise ValueError("mesh has no scenario axis; build with make_node_mesh(n, scenario_axis=k)")
    shards = mesh.shape[NODE_AXIS]
    bt = pad_batch_tables(bt, shards)
    # Tables are scenario-invariant: same shardings as the 1-D path (node axis
    # sharded, rest replicated over every mesh axis including 'scenarios').
    ts = table_shardings(mesh)
    tables = kernels.Tables(*(
        jax.device_put(np.asarray(v), s) for v, s in zip(tables_from_batch(bt), ts)
    ))
    S = seed_requested_s.shape[0]
    n_pad = bt.seed_requested.shape[0]
    if seed_requested_s.shape[1] > n_pad:
        raise ValueError(
            f"seed_requested_s node axis {seed_requested_s.shape[1]} exceeds the "
            f"padded node count {n_pad}; build seeds against the unpadded cluster "
            f"(or pad_batch_tables(bt, {shards}))"
        )
    if seed_requested_s.shape[1] < n_pad:
        seed_requested_s = np.pad(
            seed_requested_s, ((0, 0), (0, n_pad - seed_requested_s.shape[1]), (0, 0))
        )

    def rep(a):  # broadcast a seed over scenarios
        return np.broadcast_to(a[None], (S,) + a.shape).copy()

    def sh(spec):
        return NamedSharding(mesh, spec)

    carry = kernels.Carry(
        requested=jax.device_put(seed_requested_s.astype(np.float32),
                                 sh(P(SCENARIO_AXIS, NODE_AXIS, None))),
        nonzero=jax.device_put(rep(bt.seed_nonzero), sh(P(SCENARIO_AXIS, NODE_AXIS, None))),
        port_used=jax.device_put(rep(bt.seed_port_used), sh(P(SCENARIO_AXIS, NODE_AXIS, None))),
        counter=jax.device_put(rep(bt.seed_counter), sh(P(SCENARIO_AXIS, None, None))),
        carrier=jax.device_put(rep(bt.seed_carrier), sh(P(SCENARIO_AXIS, None, None))),
        dev_used=jax.device_put(rep(bt.seed_dev_used), sh(P(SCENARIO_AXIS, NODE_AXIS, None))),
        vg_req=jax.device_put(rep(bt.seed_vg_req), sh(P(SCENARIO_AXIS, NODE_AXIS, None))),
        sdev_alloc=jax.device_put(rep(bt.seed_sdev_alloc), sh(P(SCENARIO_AXIS, NODE_AXIS, None))),
    )
    enable_gpu, enable_storage = plugin_flags(bt)
    vmapped = jax.vmap(
        # simonlint: ignore[naked-dispatch] -- multichip dry-run harness, not
        # an engine hot path: callers own the wedge exposure (bench/tests)
        lambda c: kernels.schedule_batch(
            tables, c,
            jax.numpy.asarray(bt.pod_group),
            jax.numpy.asarray(bt.forced_node),
            jax.numpy.asarray(bt.valid),
            n_zones=bt.n_zones,
            enable_gpu=enable_gpu,
            enable_storage=enable_storage,
        )
    )
    with mesh:
        _, choices = vmapped(carry)
    return choices


def tables_from_batch(bt: BatchTables) -> kernels.Tables:
    """Assemble a kernels.Tables from a BatchTables BY FIELD NAME — the single place
    that maps between the two structs, immune to field reordering."""
    return kernels.Tables(**{f: getattr(bt, f) for f in kernels.Tables._fields})


# ----------------------------------------------------------------------------
# Multi-candidate probe fan-out (kernels.probe_*_fanout): the capacity
# planner's candidate lanes are independent what-if scenarios, so the vmapped
# [S] axis shards over the 'scenarios' mesh axis — one candidate node count
# per device — while the tables stay node-sharded/replicated as usual.
# ----------------------------------------------------------------------------


def make_scenario_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """Pure-DP mesh ('scenarios' = n, 'nodes' = 1) for the capacity prober's
    multi-candidate fan-out: each candidate lane lands on its own device and
    no cross-device collectives are needed within a lane."""
    devs = list(devices) if devices is not None else jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    return make_node_mesh(n, scenario_axis=n, devices=devs)


def fanout_shardings(mesh: Mesh):
    """(tables_sharding, carry_s_sharding, active_s_sharding) for the
    probe_*_fanout kernels: tables as in table_shardings (node axis sharded —
    trivially replicated on a pure-scenario mesh), carry leaves and the active
    mask sharded over their leading [S] candidate axis."""

    def s(spec):
        return NamedSharding(mesh, spec)

    carry_s = kernels.Carry(
        requested=s(P(SCENARIO_AXIS, NODE_AXIS, None)),
        nonzero=s(P(SCENARIO_AXIS, NODE_AXIS, None)),
        port_used=s(P(SCENARIO_AXIS, NODE_AXIS, None)),
        counter=s(P(SCENARIO_AXIS, None, None)),
        carrier=s(P(SCENARIO_AXIS, None, None)),
        dev_used=s(P(SCENARIO_AXIS, NODE_AXIS, None)),
        vg_req=s(P(SCENARIO_AXIS, NODE_AXIS, None)),
        sdev_alloc=s(P(SCENARIO_AXIS, NODE_AXIS, None)),
    )
    return table_shardings(mesh), carry_s, s(P(SCENARIO_AXIS, NODE_AXIS))


def put_fanout_inputs(mesh: Mesh, bt: BatchTables, carry_s_np, active_s_np):
    """device_put the probe fan-out inputs with their mesh shardings: returns
    (tables, carry_s, active_s) ready for kernels.probe_*_fanout inside a
    `with mesh:` block. carry_s_np leaves carry a leading [S] axis; S must be
    divisible by the mesh's scenario-axis size."""
    ts, cs, as_ = fanout_shardings(mesh)
    tables = jax.device_put(
        kernels.Tables(*(np.asarray(v) for v in tables_from_batch(bt))), ts)
    carry_s = jax.device_put(
        kernels.Carry(*(np.ascontiguousarray(v) for v in carry_s_np)), cs)
    return tables, carry_s, jax.device_put(np.asarray(active_s_np), as_)


# ----------------------------------------------------------------------------
# Sharded kernel executables: explicit in/out shardings end-to-end.
#
# Committing shardings only at to_device_sharded leaves every jit free to
# re-infer (and silently re-shard) its outputs per call; chained per-segment
# dispatches then round-trip the carry through whatever layout XLA picked.
# These wrappers pin BOTH sides of every hot kernel: inputs arrive in the
# table/carry shardings, outputs leave in the SAME carry shardings, so wave
# N's output feeds wave N+1 with zero resharding collectives at the boundary
# — and the carry buffers are donated, so the per-segment/per-epoch loop
# updates cluster state in place instead of allocating a fresh [N, R] set per
# dispatch. One executable set is cached per (mesh, donate) and shared by
# every Simulator/ProbeSession over an equal mesh: a warm second dispatch is
# zero recompiles.
# ----------------------------------------------------------------------------


def _unwrap(fn):
    """The undecorated kernel (jax.jit stores it on __wrapped__): re-jitting
    the wrapped form avoids nesting one jit inside another."""
    return getattr(fn, "__wrapped__", fn)


def _mesh_key(mesh: Mesh) -> tuple:
    return (mesh.axis_names, tuple(mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


_SHARDED_CACHE: Dict[tuple, "ShardedKernels"] = {}


def donation_runtime_safe(mesh: Mesh) -> bool:
    """Whether DISPATCHING donated executables is sound on this mesh.

    On multi-device CPU meshes the XLA:CPU async runtime intermittently
    corrupts the in-place-aliased carry of a donated dispatch (~1/3 of
    dispatches under a warm compile cache: garbage leaves with otherwise
    correct outputs, and — worse — a watchdog-abandoned zombie dispatch
    keeps writing into donated buffers the engine still owns, which is how
    the wedge-failover smoke intermittently diverged). Observed on the
    probe fan-out, the one-shot batch helper, AND the engine chain;
    device-side copies and block_until_ready before the fetch still read
    garbage, pinning it to the aliased execution itself. Donation stays on
    for accelerator meshes (the production perf story) and single-device
    meshes; LOWERING a donated executable is always safe — simonaudit
    certifies the donated artifact without ever executing it."""
    devs = list(mesh.devices.flat)
    return len(devs) <= 1 or any(d.platform != "cpu" for d in devs)


def sharded_kernels(mesh: Mesh, donate: bool = True) -> "ShardedKernels":
    """The cached sharded-executable set for `mesh`. Instances with equal
    meshes share one jit cache (ShardedKernels caches its jitted callables
    per (kernel, donate), and jax.jit keys on sharding equality), so every
    engine batch / probe round over the same mesh reuses warm executables.

    Donation requests are downgraded to the undonated view whenever
    dispatching donated executables is unsound on this mesh
    (donation_runtime_safe): same layouts, same jit cache, inputs kept
    alive."""
    key = _mesh_key(mesh)
    got = _SHARDED_CACHE.get(key)
    if got is None:
        got = _SHARDED_CACHE[key] = ShardedKernels(mesh)
    return got if (donate and donation_runtime_safe(mesh)) else got.undonated()


class ShardedKernels:
    """Jitted variants of every hot kernel with explicit in_shardings /
    out_shardings built from table_shardings/carry_shardings (and the fan-out
    shardings on a scenario mesh), plus donate_argnums on the carry.

    Call signatures are identical to the `kernels` module functions, so the
    engine's dispatch loop and the probe fan-out swap between the two by
    swapping the namespace. Donation is an instance-level mode:
    `sharded_kernels(mesh, donate=False)` returns a view sharing this
    instance's jit cache whose dispatches keep their input carry alive (the
    xray recorder reads segment-start carries after the fact)."""

    def __init__(self, mesh: Mesh, _shared=None, _donate: bool = True) -> None:
        self.mesh = mesh
        self.donate = _donate
        self._built: Dict[tuple, object] = (
            _shared if _shared is not None else {})
        self.table_sh = table_shardings(mesh)
        self.carry_sh = carry_shardings(mesh)
        self.rep = NamedSharding(mesh, P())
        self.node_sh = NamedSharding(mesh, P(NODE_AXIS))
        if SCENARIO_AXIS in mesh.shape:
            _, self.carry_s_sh, self.active_sh = fanout_shardings(mesh)
            self.lane_sh = NamedSharding(mesh, P(SCENARIO_AXIS))
            # sweep fan-out outputs: [S, K, N] per-segment placement counts
            # and [S, P] per-lane pod choices, both lane-sharded so every
            # scenario's results stay on its own device until the fetch
            self.lane_sn_sh = NamedSharding(mesh, P(SCENARIO_AXIS, None,
                                                    NODE_AXIS))
            self.lane_p_sh = NamedSharding(mesh, P(SCENARIO_AXIS, None))
        else:
            self.carry_s_sh = self.active_sh = self.lane_sh = None
            self.lane_sn_sh = self.lane_p_sh = None

    def undonated(self) -> "ShardedKernels":
        """A view over the same jit cache whose carry inputs survive the
        dispatch (donation off) — used while the xray recorder is active."""
        view = self._built.get("__undonated_view__")
        if view is None:
            view = ShardedKernels(self.mesh, _shared=self._built,
                                  _donate=False)
            self._built["__undonated_view__"] = view
        return view

    def _jit(self, name, build, shared: bool = False):
        # `shared`: donation-independent executables (diagnostics never
        # donate), so the donating and undonated views reuse one jit
        key = name if shared else (name, self.donate)
        fn = self._built.get(key)
        if fn is None:
            fn = self._built[key] = build()
        return fn

    def _sched_jit(self, name, n_dyn, n_static, out_tail, donate_ok=True,
                   in_head=None):
        """jit one kernel with explicit shardings. pjit forbids kwargs once
        in_shardings is set, so statics are positional (static_argnums) and
        every wrapper below calls in the kernel's declared argument order.
        `n_dyn` dynamic args follow the (tables, carry) pair (or the fan-out
        (tables, carry_s, active_s) triple when in_head is given)."""
        head = in_head if in_head is not None else (self.table_sh,
                                                    self.carry_sh)
        first_static = len(head) + n_dyn
        donate = (1,) if (self.donate and donate_ok) else ()
        return jax.jit(
            _unwrap(getattr(kernels, name)),
            static_argnums=tuple(range(first_static, first_static + n_static)),
            in_shardings=head + (self.rep,) * n_dyn,
            out_shardings=out_tail,
            donate_argnums=donate,
        )

    def _tail_shardings(self, symbols):
        """Resolve a HOT_KERNELS out-tail symbol tuple to shardings."""
        table = {"carry": self.carry_sh, "carry_s": self.carry_s_sh,
                 "node": self.node_sh, "lane": self.lane_sh, "rep": self.rep,
                 "lane_sn": self.lane_sn_sh, "lane_p": self.lane_p_sh}
        return tuple(table[s] for s in symbols)

    def _kernel_jit(self, name, stats=False):
        """The cached explicitly-sharded jit for registry kernel `name` —
        the single source of truth the wrapper methods AND the simonaudit
        lowering path (analysis/hlo.py via `lowerable`) share, so the audited
        executable is byte-for-byte the one the engine dispatches."""
        if stats and name != "schedule_affinity_wave":
            # only the affinity wave has a stats output variant; silently
            # widening another kernel's out-tail would cache a wrong-arity
            # executable under its plain key
            raise ValueError(f"{name} has no stats variant")
        spec = kernels.HOT_KERNELS[name]
        n_static = len(spec.statics(2))
        if name in _MESH_STATIC_KERNELS:
            n_static += 1  # trailing static: kernel-internal shard_map mesh
        if spec.out is None:  # diagnostics: never donated, no out_shardings
            return self._jit(name, lambda: self._sched_jit(
                name, 3, n_static, None, donate_ok=False), shared=True)
        tail = spec.out + (("rep",) if stats else ())
        # the stats flag changes the output arity -> one executable per value
        key = f"{name}:{bool(stats)}" if name == "schedule_affinity_wave" \
            else name
        head = self._fanout_head(name) if spec.fanout else None
        return self._jit(key, lambda: self._sched_jit(
            name, 3, n_static, self._tail_shardings(tail), in_head=head))

    def lowerable(self, name, *, n_zones=2, stats=False):
        """(jit_fn, spec, meta) for simonaudit: the sharded executable
        builder for `name` plus everything the auditor needs to lower it
        abstractly — canonical statics, head arity, and where donation is
        declared. The jit object is the SAME cached one the dispatch
        wrappers use."""
        spec = kernels.HOT_KERNELS[name]
        statics = spec.statics(n_zones)
        if name == "schedule_affinity_wave":
            statics = statics[:-1] + (bool(stats),)
        if name in _MESH_STATIC_KERNELS:
            statics = statics + (self._wave_mesh(),)
        donated = (1,) if (spec.out is not None and self.donate) else ()
        meta = {"head": 3 if spec.fanout else 2, "statics": statics,
                "donate_argnums": donated}
        return self._kernel_jit(name, stats=stats), spec, meta

    # ------------------------------------------------- engine dispatches ----

    def _wave_mesh(self):
        """The kernel-internal shard_map mesh for the wave kernels: this
        mesh itself when its single node axis actually shards (>1 device) —
        the epoch-amortized collective path in ops/kernels.py — else None
        (serial lowering). Scenario fan-out meshes stay None: their node
        axis replicates, so there is nothing to amortize."""
        if (self.mesh.axis_names == (NODE_AXIS,)
                and self.mesh.shape[NODE_AXIS] > 1):
            return self.mesh
        return None

    def schedule_wave(self, tb, cry, g, m, cap1, *, gpu_live=False,
                      w=kernels.DEFAULT_WEIGHTS, filters=kernels.DEFAULT_FILTERS,
                      block=kernels.WAVE_BLOCK, kmax=0, order_len=0):
        fn = self._kernel_jit("schedule_wave")
        return fn(tb, cry, g, m, cap1, gpu_live, w, filters, block, kmax,
                  order_len, self._wave_mesh())

    def schedule_affinity_wave(self, tb, cry, g, m, cap1, *, ss_live=False,
                               w=kernels.DEFAULT_WEIGHTS,
                               filters=kernels.DEFAULT_FILTERS,
                               block=kernels.WAVE_BLOCK, n_zones=2,
                               stats=False):
        fn = self._kernel_jit("schedule_affinity_wave", stats=stats)
        return fn(tb, cry, g, m, cap1, ss_live, w, filters, block, n_zones,
                  stats, self._wave_mesh())

    def schedule_group_serial(self, tb, cry, g, valid, cap1, *,
                              w=kernels.DEFAULT_WEIGHTS,
                              filters=kernels.DEFAULT_FILTERS,
                              ss_live=False, sa_live=False, n_zones=2):
        fn = self._kernel_jit("schedule_group_serial")
        return fn(tb, cry, g, valid, cap1, w, filters, ss_live, sa_live,
                  n_zones)

    def schedule_batch(self, tb, cry, pod_group, forced_node, valid, *,
                       n_zones, enable_gpu=True, enable_storage=True,
                       w=kernels.DEFAULT_WEIGHTS,
                       filters=kernels.DEFAULT_FILTERS):
        fn = self._kernel_jit("schedule_batch")
        return fn(tb, cry, pod_group, forced_node, valid, n_zones, enable_gpu,
                  enable_storage, w, filters)

    # ------------------------------------------------------- diagnostics ----
    # in_shardings only (out_shardings=None): both are one-shot
    # fetch-to-host diagnostics whose outputs are never chained into another
    # dispatch, and some output leaves are scalars (inert score components),
    # which a node-axis out-sharding prefix cannot describe. Never donated:
    # the engine re-reads the same carry for every (group, forced, segment)
    # key.

    def feasibility_jit(self, tb, cry, g, forced, valid, *, enable_gpu=True,
                        enable_storage=True, include_dns=True,
                        include_interpod=True,
                        filters=kernels.DEFAULT_FILTERS):
        fn = self._kernel_jit("feasibility_jit")
        return fn(tb, cry, g, forced, valid, enable_gpu, enable_storage,
                  include_dns, include_interpod, filters)

    def explain_jit(self, tb, cry, g, forced, valid, *, n_zones,
                    enable_gpu=True, enable_storage=True,
                    w=kernels.DEFAULT_WEIGHTS,
                    filters=kernels.DEFAULT_FILTERS):
        fn = self._kernel_jit("explain_jit")
        return fn(tb, cry, g, forced, valid, n_zones, enable_gpu,
                  enable_storage, w, filters)

    # ------------------------------------------- probe fan-out dispatches ----
    # Scenario-mesh only (make_scenario_mesh): the [S] candidate axis shards
    # over SCENARIO_AXIS -- devices buy probe breadth, not replication -- and
    # the [S]-carry chains donated between segments exactly like the engine's.

    def _fanout_head(self, name):
        if self.carry_s_sh is None:
            raise ValueError(
                f"{name} needs a mesh with a '{SCENARIO_AXIS}' axis "
                f"(make_scenario_mesh); this mesh has {self.mesh.axis_names}")
        return (self.table_sh, self.carry_s_sh, self.active_sh)

    def probe_wave_fanout(self, tb, cry_s, active_s, g, m, cap1, *,
                          gpu_live=False, w=kernels.DEFAULT_WEIGHTS,
                          filters=kernels.DEFAULT_FILTERS,
                          block=kernels.WAVE_BLOCK, kmax=0):
        fn = self._kernel_jit("probe_wave_fanout")
        return fn(tb, cry_s, active_s, g, m, cap1, gpu_live, w, filters,
                  block, kmax)

    def probe_affinity_wave_fanout(self, tb, cry_s, active_s, g, m, cap1, *,
                                   ss_live=False, w=kernels.DEFAULT_WEIGHTS,
                                   filters=kernels.DEFAULT_FILTERS,
                                   block=kernels.WAVE_BLOCK, n_zones=2):
        fn = self._kernel_jit("probe_affinity_wave_fanout")
        return fn(tb, cry_s, active_s, g, m, cap1, ss_live, w, filters,
                  block, n_zones)

    def probe_group_serial_fanout(self, tb, cry_s, active_s, g, valid, cap1,
                                  *, w=kernels.DEFAULT_WEIGHTS,
                                  filters=kernels.DEFAULT_FILTERS,
                                  ss_live=False, sa_live=False, n_zones=2):
        fn = self._kernel_jit("probe_group_serial_fanout")
        return fn(tb, cry_s, active_s, g, valid, cap1, w, filters, ss_live,
                  sa_live, n_zones)

    def probe_serial_fanout(self, tb, cry_s, active_s, pod_group, forced_node,
                            valid, *, n_zones, enable_gpu=True,
                            enable_storage=True, w=kernels.DEFAULT_WEIGHTS,
                            filters=kernels.DEFAULT_FILTERS):
        fn = self._kernel_jit("probe_serial_fanout")
        return fn(tb, cry_s, active_s, pod_group, forced_node, valid,
                  n_zones, enable_gpu, enable_storage, w, filters)

    def serve_whatif_fanout(self, tb, cry_s, active_s, pod_group, forced_node,
                            valid_s, *, n_zones, enable_gpu=True,
                            enable_storage=True, w=kernels.DEFAULT_WEIGHTS,
                            filters=kernels.DEFAULT_FILTERS):
        fn = self._kernel_jit("serve_whatif_fanout")
        return fn(tb, cry_s, active_s, pod_group, forced_node, valid_s,
                  n_zones, enable_gpu, enable_storage, w, filters)

    def serve_wave_fanout(self, tb, cry_s, active_s, g_s, m_s, cap1_s, *,
                          w=kernels.DEFAULT_WEIGHTS,
                          filters=kernels.DEFAULT_FILTERS,
                          block=kernels.WAVE_BLOCK, kmax=0):
        fn = self._kernel_jit("serve_wave_fanout")
        return fn(tb, cry_s, active_s, g_s, m_s, cap1_s, w, filters, block,
                  kmax)

    def sweep_wave_fanout(self, tb, cry_s, active_s, g_sk, m_sk, cap1_sk, *,
                          w=kernels.DEFAULT_WEIGHTS,
                          filters=kernels.DEFAULT_FILTERS,
                          block=kernels.WAVE_BLOCK, kmax=0):
        fn = self._kernel_jit("sweep_wave_fanout")
        return fn(tb, cry_s, active_s, g_sk, m_sk, cap1_sk, w, filters,
                  block, kmax)

    def sweep_whatif_fanout(self, tb, cry_s, active_s, pod_group_s,
                            forced_node_s, valid_s, *, n_zones,
                            enable_gpu=True, enable_storage=True,
                            w=kernels.DEFAULT_WEIGHTS,
                            filters=kernels.DEFAULT_FILTERS):
        fn = self._kernel_jit("sweep_whatif_fanout")
        return fn(tb, cry_s, active_s, pod_group_s, forced_node_s, valid_s,
                  n_zones, enable_gpu, enable_storage, w, filters)


def carry_reshard_bytes(carry, shardings) -> int:
    """Bytes a chained dispatch would move to reconcile `carry`'s actual
    layout with the declared carry shardings — the regression signal behind
    simon_reshard_bytes_total and the bench mesh rows' `reshard_bytes` stat.
    With the sharded executables pinning out_shardings this is provably 0;
    anything nonzero means a dispatch path dropped its explicit shardings and
    XLA re-inferred a different layout."""
    total = 0
    for leaf, want in zip(carry, shardings):
        sh = getattr(leaf, "sharding", None)
        if sh is None:
            continue
        if not sh.is_equivalent_to(want, leaf.ndim):
            total += leaf.nbytes
    return total


# ----------------------------------------------------------------------------
# Shard-local node-axis growth: the incremental prober's template-column
# extension without a host round-trip. Every appended column is a verbatim
# copy of the template column ALREADY RESIDENT on the device (verified
# bit-identical at session build), and phantom re-padding writes constants —
# so the whole extension is one compiled concat per table, shard-local under
# the mesh shardings, transferring zero bytes from the host. Only valid when
# extend_node_axis would not widen the domain axis (no hostname-keyed
# counter/carrier rows); probe.ProbeSession falls back to the host re-upload
# otherwise.
# ----------------------------------------------------------------------------

# Phantom fills mirror pad_batch_tables exactly: a padded column must be
# indistinguishable from one it would have produced.
_EXT_GN_FILL = (
    ("static_mask", False), ("mask_taint", False), ("mask_unsched", False),
    ("mask_aff", False), ("mask_extra", False),
    ("simon_raw", 0), ("nodeaff_raw", 0), ("taint_raw", 0), ("avoid_raw", 0),
    ("image_raw", 0), ("extra_raw", 0),
)
_EXT_DOM_FIELDS = ("counter_dom", "topo_dom", "carr_dom")
_EXT_NROW_FILL = (
    ("alloc", 0), ("dev_total", 0), ("vg_cap", 0), ("vg_nameid", 0),
    ("sdev_cap", 0), ("sdev_media", 0),
)


def _extend_tables_impl(tb: kernels.Tables, n_real: int, k: int,
                        template_col: int, n_pad_new: int,
                        sentinel: int) -> kernels.Tables:
    import jax.numpy as jnp

    pad = n_pad_new - n_real - k

    def cols(a, fill):  # [*, N_old_pad] -> [*, n_pad_new] along the last axis
        parts = [a[..., :n_real],
                 jnp.repeat(a[..., template_col:template_col + 1], k, axis=-1)]
        if pad:
            parts.append(jnp.full(a.shape[:-1] + (pad,), fill, a.dtype))
        return jnp.concatenate(parts, axis=-1)

    def rows(a, fill):  # [N_old_pad, *] -> [n_pad_new, *]
        parts = [a[:n_real],
                 jnp.repeat(a[template_col:template_col + 1], k, axis=0)]
        if pad:
            parts.append(jnp.full((pad,) + a.shape[1:], fill, a.dtype))
        return jnp.concatenate(parts, axis=0)

    upd = {f: cols(getattr(tb, f), fill) for f, fill in _EXT_GN_FILL}
    upd.update({f: cols(getattr(tb, f), sentinel) for f in _EXT_DOM_FIELDS})
    upd.update({f: rows(getattr(tb, f), fill) for f, fill in _EXT_NROW_FILL})
    upd["node_zone"] = cols(tb.node_zone, 0)
    return tb._replace(**upd)


_EXTEND_JITS: Dict[object, object] = {}


def extend_tables_on_device(tables: kernels.Tables, *, n_real: int, k: int,
                            template_col: int, n_pad_new: int, sentinel: int,
                            mesh: Optional[Mesh] = None) -> kernels.Tables:
    """Grow device-resident Tables by k template-column copies (+ phantom
    re-pad to n_pad_new), entirely on device. `n_real` is the current real
    column count (old phantom columns are overwritten), `sentinel` the padded
    domain sentinel id (unchanged by gate). With `mesh`, the program runs
    under the table shardings so each shard grows locally."""
    key = _mesh_key(mesh) if mesh is not None else None
    fn = _EXTEND_JITS.get(key)
    if fn is None:
        if mesh is None:
            fn = jax.jit(_extend_tables_impl,
                         static_argnums=(1, 2, 3, 4, 5))
        else:
            ts = table_shardings(mesh)
            fn = jax.jit(_extend_tables_impl,
                         static_argnums=(1, 2, 3, 4, 5),
                         in_shardings=(ts,), out_shardings=ts)
        _EXTEND_JITS[key] = fn
    return fn(tables, n_real, k, template_col, n_pad_new, sentinel)
