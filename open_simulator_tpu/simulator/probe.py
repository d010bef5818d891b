"""Incremental capacity-probe session: encode once, probe many candidates.

The capacity planner's search asks one question repeatedly: "would this pod
batch schedule on base + n copies of the template node?" for a sequence of
candidate n. The reference re-simulates the whole workload per candidate
(apply.go:203-259); the previous fast path (Simulator.probe_pods) already
skipped placement materialization but still built a fresh Simulator per
candidate — re-deep-copying nodes, re-discovering the resource axis,
re-encoding the (possibly 100k-pod) batch, and re-transferring every table to
the device, even though successive candidates differ only in how many copies
of ONE identical node template exist.

This module pays all of that exactly once per search:

- **Encode once.** One Simulator is built over base + n_max template copies
  (n_max sized to the node-padding bucket, so the phantom pad columns the
  engine would have added anyway become real template columns at zero extra
  memory). Bound pods commit once; the unbound run is encoded once
  (engine.encode_batch_raw); the tables transfer to the device once.
- **Candidate = mask flip.** A candidate n activates the base nodes plus the
  first n template columns; the rest stay inactive. The probe kernels fold the
  active mask into static_mask, which makes an inactive node exactly a
  pad_batch_tables phantom: infeasible for every pod, excluded from every
  feasibility-set normalizer, owner of zero placed pods and zero counter
  counts. Within one padding bucket, every candidate shares one XLA shape.
- **Multi-candidate fan-out.** kernels.probe_*_fanout evaluate S active masks
  in one dispatch (vmap over carry+mask), so the search's doubling phase and
  each refinement round are single device round-trips. With more than one
  visible device the [S] axis shards over a ('scenarios', 'nodes') mesh
  (parallel/mesh.py fan-out machinery), one candidate lane per device.
- **Node-axis extension.** When the search outgrows the encoded bucket,
  encode.extend_node_axis appends k copies of the pre-encoded template column
  (fresh hostname domains, zero seeds) instead of rebuilding
  NodeArrays/Encoder from raw node dicts.

Provable-equivalence gates (`try_build` returns None and the planner keeps
its fresh-Simulator probes when any fails):

- the node-census-dependent score/filter inputs must be candidate-invariant:
  no topologySpreadConstraints on any batch group (the DoNotSchedule eligible-
  domain minimum and the ScheduleAnyway relevant sets depend on which nodes
  exist, not just which are feasible) and no node-advertised images
  (ImageLocality's spread-scaled fraction divides by the total node count);
- no open-local storage (as in CapacityPlanner.try_build) and no pre-bound
  pod after an unbound one (probe order-inequivalence, same guard);
- every encoded template column must be bit-identical across copies (verified
  at build over the real Encoder's output, not assumed: a pathological pod
  that selects on a randomly generated simon-* name would fail this check),
  and template columns must carry zero seeds.

The existing provable-equivalence guard stays in place above this module: the
Applier re-validates the search's answer with one full fresh-`Simulator`
simulation and falls back to the reference-style full-simulation search on
any divergence (applier._plan) — the incremental path can therefore never
change an answer, only the time it takes to find it. The equivalence tests
and the CI smoke additionally re-validate answers with fresh-Simulator
probes.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..models.fakenode import new_fake_nodes
from ..obs import instruments as obs
from ..resilience import faults
from ..resilience import guard
from ..ops.resources import CPU_I, MEM_I
from .encode import (
    HOSTNAME,
    BatchTables,
    bucket_capped,
    extend_node_axis,
    pad_batch_tables,
    pad_encoder_axes,
    plugin_flags,
)
from .store import PodStore

_jnp = None


def _jax():
    global _jnp
    if _jnp is None:
        import jax.numpy as jnp

        _jnp = jnp
    return _jnp


# [G, N] tables whose template columns must be copy-invariant (and are
# replicated verbatim by extend_node_axis).
_GN_FIELDS = (
    "static_mask", "mask_taint", "mask_unsched", "mask_aff", "mask_extra",
    "simon_raw", "nodeaff_raw", "taint_raw", "avoid_raw", "image_raw",
    "extra_raw",
)
# [N, *] node matrices with the same invariant.
_NROW_FIELDS = ("alloc", "dev_total", "vg_cap", "vg_nameid", "sdev_cap",
                "sdev_media")
# [N, *] seed rows that must be ZERO on template columns (no bound pod can
# name a randomly generated fake node).
_NSEED_FIELDS = ("seed_requested", "seed_nonzero", "seed_port_used",
                 "seed_dev_used", "seed_vg_req", "seed_sdev_alloc")


class ProbeSession:
    """Device-resident incremental prober for one (base, template, pods) search."""

    def __init__(self) -> None:  # built via try_build only
        raise TypeError("use ProbeSession.try_build")

    # ------------------------------------------------------------- build ------

    @classmethod
    def try_build(cls, base_nodes: List[dict], new_node: Optional[dict],
                  pods, cluster_objects=None,
                  app_objects: Sequence = (), sched_config=None,
                  n_new: int = 2, fanout: int = 8,
                  mesh=None) -> Optional["ProbeSession"]:
        """Build a session able to probe up to (at least) n_new template
        copies, or None when the workload fails an equivalence gate.
        `pods`: a PodStore or pod dicts (PodStore.of)."""
        from .engine import Simulator

        pods = PodStore.of(pods)
        if new_node is None:
            return None
        if guard.default_quarantined():
            # the session uploads device-resident tables to the DEFAULT
            # backend (no fallback routing on this path): with it
            # quarantined, decline so the search runs fresh probes, which
            # the engine routes to the CPU fallback
            return None
        t0 = time.perf_counter()
        n_base = len(base_nodes)
        # Size the template axis to the engine's node-padding bucket: the
        # phantom pad columns a fresh probe would carry anyway become real,
        # probe-able template columns for free.
        n0 = max(2, int(n_new))
        n0 = bucket_capped(n_base + n0, 1024) - n_base
        sim = Simulator(base_nodes + new_fake_nodes(new_node, n0),
                        sched_config=sched_config, use_mesh=False)
        if cluster_objects is not None:
            sim.register_cluster_objects(cluster_objects)
        for rt in app_objects:
            sim.register_app_objects(rt)
        if sim.local_host.enabled:
            return None  # open-local envelope accounting (planner gate too)
        if any((n.get("status") or {}).get("images") for n in sim.na.nodes):
            return None  # ImageLocality divides by the TOTAL node count

        # The rest mutates caller-owned pods (bound commits write status) and
        # runs the faultable encode/upload path: transactional, so a failure
        # mid-build rolls the pods back before propagating (crash
        # consistency for the capacity search).
        with sim._transaction():
            return cls._try_build_encoded(sim, t0, n_base, n0, pods, fanout,
                                          mesh)

    @classmethod
    def _try_build_encoded(cls, sim, t0, n_base, n0, pods, fanout, mesh):
        # Bound pods commit once (they are cluster state every candidate
        # shares); the unbound remainder becomes the one encoded run.
        from ..utils.objutil import pod_resource_requests

        bound_scheduled = 0
        bound_cpu = bound_mem = 0.0
        homeless = 0
        for run, bound in pods.split_bound():  # simonlint: ignore[per-pod-host-loop] -- O(runs of pre-bound rows): one (run view, row range) pair each
            if not bound:
                break
            if len(run):
                return None  # bound-after-unbound: probe order-inequivalent
            for pod, ni in sim._commit_bound(pods, bound):  # simonlint: ignore[per-pod-host-loop] -- pre-bound rows only: each commits on its own
                if ni < 0:
                    homeless += 1
                else:
                    bound_scheduled += 1
                    req = pod_resource_requests(pod)
                    bound_cpu += req.get("cpu", 0.0)
                    bound_mem += req.get("memory", 0.0)

        self = object.__new__(cls)
        self._sim = sim
        self.fanout = int(fanout)
        self.n_base = n_base
        self.n_new = n0
        self.bound_scheduled = bound_scheduled
        self._bound_cpu = bound_cpu
        self._bound_mem = bound_mem
        self.total_known = len(pods) - homeless
        self._run_len = len(run)
        self.encodes = 0
        self.extensions = 0
        self._alloc = np.array(sim.na.alloc, np.float64)  # simonlint: ignore[dtype-drift] -- host-side envelope sums, mirrors probe_utilization
        self._mesh = mesh if mesh is not None else self._auto_mesh(fanout)

        if not run:
            # trivial probes: host arithmetic only (mirrors probe_pods' early
            # return, whose probe_utilization then reads a None carry as zeros)
            self._bt_raw = None
            self._segs = []
            self.encode_s = time.perf_counter() - t0
            self._record_build()
            return self

        # cheap census gate BEFORE the (dominant) batch encode: spread
        # constraints reject the session anyway, so don't pay a 100k-pod
        # encode just to discover that (the group-level check below stays as
        # the authoritative backstop)
        tmpls = run.base.templates
        if any((tmpls[t].get("spec") or {}).get("topologySpreadConstraints")
               for t in run.templates_present()):
            return None

        bt_raw = sim.encode_batch_raw(run)
        self.encodes = 1
        P = len(run)
        for gi in set(np.asarray(bt_raw.pod_group[:P]).tolist()):
            g = sim.encoder.group_list[gi]
            if g.spread_dns or g.spread_sa:
                return None  # eligible-domain sets depend on the node census
        enc = sim.encoder
        self._host_counters = [t for t, cs in enumerate(enc.counter_list)
                               if cs.topo_key == HOSTNAME]
        self._host_carriers = [t for t, cs in enumerate(enc.carrier_list)
                               if cs.topo_key == HOSTNAME]
        if not _template_columns_uniform(bt_raw, n_base, self._host_counters,
                                         self._host_carriers):
            return None
        self._bt_raw = bt_raw
        self._segs = (sim._segments(bt_raw, P) if sim.use_waves
                      else [("serial", 0, P)])
        self._upload()
        self.encode_s = time.perf_counter() - t0
        self._record_build()
        return self

    def _record_build(self) -> None:
        """Session-build accounting into the metrics registry (satellite to
        the planner's stats dict: the registry survives the search, so
        `capacity` CLI runs and the server report the same numbers)."""
        obs.PROBE_SESSIONS.inc()
        obs.PROBE_ENCODES.inc(self.encodes)
        obs.PROBE_ENCODE_SECONDS.inc(self.encode_s)

    @staticmethod
    def _auto_mesh(fanout: int):
        """Scenario mesh over all visible devices when >1 is up and divides the
        fan-out; same OPEN_SIMULATOR_MESH=0/1 override as the engine's mesh."""
        import os

        env = os.environ.get("OPEN_SIMULATOR_MESH", "")
        if env in ("0", "false", "no"):
            return None
        if guard.default_quarantined():
            return None  # degraded mode: no shardings over a wedged backend
        import jax

        n = len(jax.devices())
        if n <= 1:
            return None  # _dispatch pads lane counts to a shard multiple,
        # so any device count works once there is more than one
        from ..parallel.mesh import make_scenario_mesh

        return make_scenario_mesh(n)

    # ------------------------------------------------------------ upload ------

    def _upload(self) -> None:
        """(Re-)pad and transfer the tables; rebuild per-segment batch arrays."""
        faults.maybe_fail("to_device")
        faults.maybe_fail("oom_to_device")
        jnp = _jax()
        from .engine import batch_tables_nbytes

        bt = pad_encoder_axes(self._bt_raw)
        bt = pad_batch_tables(bt, bucket_capped(self.n_base + self.n_new, 1024))
        obs.TRANSFER_BYTES.inc(batch_tables_nbytes(bt))
        self._bt = bt
        self._n_pad = bt.alloc.shape[0]
        from ..parallel.mesh import tables_from_batch

        if self._mesh is not None:
            import jax

            from ..parallel.mesh import fanout_shardings

            ts, self._carry_sh, self._active_sh = fanout_shardings(self._mesh)
            self._tables = type(ts)(*(
                jax.device_put(np.asarray(v), s)
                for v, s in zip(tables_from_batch(bt), ts)))
        else:
            from ..ops import kernels

            self._tables = kernels.Tables(
                *(jnp.asarray(v) for v in tables_from_batch(bt)))
        # seed carry stays host-side; each dispatch broadcasts it over S lanes
        self._seeds = (bt.seed_requested, bt.seed_nonzero, bt.seed_port_used,
                       bt.seed_counter, bt.seed_carrier, bt.seed_dev_used,
                       bt.seed_vg_req, bt.seed_sdev_alloc)
        self._flags = plugin_flags(bt)

    # ---------------------------------------------------------- extension -----

    def _check_backend(self) -> None:
        """A backend quarantined AFTER this session uploaded its tables must
        not be touched again: device-resident arrays (and any mesh
        shardings) are committed to it and override jax.default_device.
        Raise the containable wedge classification so the capacity search
        falls back to fresh probes — which the engine routes to the CPU
        fallback — WITHOUT burning a watchdog timeout re-dispatching here."""
        if self._segs and guard.default_quarantined():
            raise guard.BackendWedged("dispatch", guard.current_backend(),
                                      injected=False)

    def ensure_capacity(self, n: int) -> None:
        """Grow the template axis to cover candidate n via the node-axis
        extension path (append pre-encoded template columns; no re-encode).

        When the session holds device-resident tables and the extension
        cannot widen the domain axis (no hostname-keyed counter/carrier
        rows), the growth happens SHARD-LOCALLY on the device
        (mesh.extend_tables_on_device): the template column is already
        resident, so no table bytes round-trip through the host — only the
        (numpy) host mirror is rebuilt for seeds and dispatch dims. Hostname
        rows fall back to the full host re-upload."""
        if n <= self.n_new:
            return
        self._check_backend()  # the paths below touch the session backend
        target = bucket_capped(self.n_base + n, 1024)
        k = target - (self.n_base + self.n_new)
        n_real_old = self.n_base + self.n_new
        if self._bt_raw is not None:
            self._bt_raw = extend_node_axis(
                self._bt_raw, k, self.n_base,
                self._host_counters, self._host_carriers)
        self._alloc = np.concatenate(
            [self._alloc,
             np.repeat(self._alloc[self.n_base:self.n_base + 1], k, axis=0)])
        self.n_new += k
        self.extensions += 1
        obs.PROBE_EXTENSIONS.inc()
        if self._bt_raw is None:
            return
        if not self._host_counters and not self._host_carriers:
            self._extend_device(k, n_real_old)
        else:
            self._upload()

    def _extend_device(self, k: int, n_real_old: int) -> None:
        """Shard-local growth: re-pad the HOST mirror (numpy only — seeds and
        dispatch dims read it) and extend the device tables in place from
        their own template column. simon_device_transfer_bytes_total does not
        move: zero table bytes cross the host boundary."""
        faults.maybe_fail("to_device")
        faults.maybe_fail("oom_to_device")
        from ..parallel.mesh import extend_tables_on_device

        bt = pad_encoder_axes(self._bt_raw)
        bt = pad_batch_tables(bt, bucket_capped(self.n_base + self.n_new, 1024))
        sentinel = bt.seed_counter.shape[1] - 1
        if sentinel != self._bt.seed_counter.shape[1] - 1:
            # the no-hostname gate makes this unreachable (the domain axis
            # cannot widen); if an encoder change ever breaks that, fall back
            # to the host path rather than corrupt the resident tables
            self._upload()
            return
        self._bt = bt
        self._n_pad = bt.alloc.shape[0]
        self._tables = extend_tables_on_device(
            self._tables, n_real=n_real_old, k=k, template_col=self.n_base,
            n_pad_new=self._n_pad, sentinel=sentinel, mesh=self._mesh)
        self._seeds = (bt.seed_requested, bt.seed_nonzero, bt.seed_port_used,
                       bt.seed_counter, bt.seed_carrier, bt.seed_dev_used,
                       bt.seed_vg_req, bt.seed_sdev_alloc)
        self._flags = plugin_flags(bt)

    # ------------------------------------------------------------ probing -----

    def batch_totals(self) -> Tuple[float, float, int]:
        """(cpu_used, mem_used, n_pods) over the pods the simulation accounts
        (known-bound + unbound; homeless excluded) — the planner's lower-bound
        inputs, derived from the encoded groups (one f64 template-request
        lookup per GROUP, scaled by replica counts) instead of the planner's
        100k-iteration per-pod host loop. Requests within a group are
        identical by signature, so the sums are exact."""
        from ..utils.objutil import pod_resource_requests

        cpu, mem = self._bound_cpu, self._bound_mem
        if self._bt_raw is not None and self._run_len:
            groups = self._sim.encoder.group_list
            counts = np.bincount(
                np.asarray(self._bt_raw.pod_group[:self._run_len]),
                minlength=len(groups))
            for gi, c in enumerate(counts.tolist()):
                if not c:
                    continue
                req = pod_resource_requests(groups[gi].template)
                cpu += c * req.get("cpu", 0.0)
                mem += c * req.get("memory", 0.0)
        return cpu, mem, self.total_known

    def probe_many(self, ns: Sequence[int]) -> Dict[int, Tuple[int, int, Dict[str, float]]]:
        """Evaluate candidate node counts in ONE device dispatch. Returns
        {n: (scheduled, total, utilization)} with the same semantics as
        Simulator.probe_pods + probe_utilization on a fresh simulator at n.
        len(set(ns)) must be <= fanout and every n <= current capacity."""
        order: List[int] = []
        for n in ns:
            if n not in order:
                order.append(n)
        if not order:
            return {}
        if len(order) > self.fanout:
            raise ValueError(f"{len(order)} candidates > fanout {self.fanout}")
        bad = [n for n in order if n > self.n_new]
        if bad:
            raise ValueError(f"candidates {bad} exceed capacity {self.n_new}")

        obs.PROBE_PROBES.inc(len(order))
        if not self._segs:  # no unbound pods: pure host arithmetic
            return {n: (self.bound_scheduled, self.total_known,
                        self._utilization(n, None)) for n in order}
        self._check_backend()  # never re-dispatch on a now-quarantined backend

        # Lanes cost near-linearly, so a lone lower-bound probe (the common
        # exact-arithmetic case) must not pay for fanout-1 padded copies —
        # but every distinct S is a fresh XLA compile of the whole pipeline,
        # so lane counts quantize to powers of two (1, 2, 4, 8): at most
        # log2(fanout)+1 compiled shapes per bucket, surplus lanes repeat the
        # last candidate and are sliced off.
        S = 1
        while S < len(order):
            S *= 2
        obs.PROBE_DISPATCHES.inc()
        obs.PROBE_FANOUT.observe(S)
        lanes = order + [order[-1]] * (S - len(order))
        active_s = np.zeros((S, self._n_pad), bool)
        for i, n in enumerate(lanes):
            active_s[i, :self.n_base + n] = True
        placed_s, requested_s = self._dispatch(active_s)
        out: Dict[int, Tuple[int, int, Dict[str, float]]] = {}
        for i, n in enumerate(order):
            scheduled = self.bound_scheduled + int(placed_s[i])
            out[n] = (scheduled, self.total_known,
                      self._utilization(n, requested_s[i]))
        self._xray_probes(out)
        return out

    def _xray_probes(self, out) -> None:
        """simonxray ride-along: one probe record per candidate evaluated by
        this fan-out dispatch (counts only — sessions never materialize
        placements), tagged with the session's backend."""
        from ..obs import xray

        run = xray.begin_run("probe_session")
        if run is None:
            return
        for n, (scheduled, total, _) in sorted(out.items()):
            run.add_probe(scheduled, total, candidate=n)
        xray.commit_run(run, [guard.current_backend()])

    def _dispatch(self, active_s: np.ndarray):
        from ..obs import scope

        S = active_s.shape[0]
        sc = scope.active()
        cm = (sc.span("probe.fanout", cat="dispatch", lanes=int(S))
              if sc is not None else contextlib.nullcontext())
        # The whole fan-out round — lane padding, seed broadcast, every
        # segment dispatch, the one fetch — runs as ONE supervised unit: the
        # mesh context is thread-local, so it must be entered inside the
        # watchdog's worker thread, and a wedge anywhere in the round
        # classifies the same way (the search then falls back to fresh
        # probes on the surviving backend).
        with cm:
            placed_s, requested_s = guard.supervised(
                functools.partial(self._dispatch_round, active_s),
                site="dispatch", pods=self._run_len * max(1, S))
        return placed_s[:S], requested_s[:S]

    def _dispatch_round(self, active_s: np.ndarray):
        jnp = _jax()
        from ..ops import kernels

        if self._mesh is not None:
            # the scenario axis shards evenly: round the lane count up to a
            # multiple of the mesh's device count (padding repeats the last
            # candidate; the surplus lanes are sliced off by the caller)
            from ..parallel.mesh import SCENARIO_AXIS

            shards = self._mesh.shape[SCENARIO_AXIS]
            extra = (-active_s.shape[0]) % shards
            if extra:
                active_s = np.concatenate(
                    [active_s, np.repeat(active_s[-1:], extra, axis=0)])
        carry_np = tuple(
            np.broadcast_to(a, (active_s.shape[0],) + a.shape)
            for a in self._seeds)
        if self._mesh is not None:
            import jax

            carry_s = kernels.Carry(*(
                jax.device_put(np.ascontiguousarray(v), s)
                for v, s in zip(carry_np, self._carry_sh)))
            active = jax.device_put(active_s, self._active_sh)
            ctx = self._mesh
        else:
            import contextlib

            carry_s = kernels.Carry(*(jnp.asarray(v) for v in carry_np))
            active = jnp.asarray(active_s)
            ctx = contextlib.nullcontext()

        sim, bt = self._sim, self._bt
        enable_gpu, enable_storage = self._flags
        n_real = self.n_base + self.n_new
        dims = {"S": int(active_s.shape[0]), "N": int(self._n_pad),
                "G": int(bt.static_mask.shape[0]),
                "T": int(bt.counter_dom.shape[0]),
                "mesh": self._mesh is not None,
                # w/filters are jit statics on the fan-out kernels too
                "cfg": f"{hash((sim.score_w, sim.filter_flags)) & 0xffffffff:08x}"}
        if self._mesh is not None:
            # the mesh's sharded-executable set: explicit in/out shardings
            # keep the [S]-carry in its scenario layout across chained
            # segments (zero resharding), and the donated [S]-carry chain
            # updates in place — except where dispatching donated
            # executables is unsound (multi-device CPU meshes: the factory
            # downgrades to the undonated view; see
            # parallel.mesh.donation_runtime_safe, found when this path
            # intermittently fetched garbage `requested` leaves)
            from ..parallel.mesh import donation_runtime_safe, sharded_kernels

            kns = sharded_kernels(self._mesh, donate=True)
            dims["donate"] = donation_runtime_safe(self._mesh)
        else:
            kns = kernels
        placed_parts = []
        with ctx:
            for seg in self._segs:
                faults.maybe_fail("dispatch")
                faults.maybe_fail("oom_dispatch")
                if seg[0] == "serial":
                    _, start, length = seg
                    pad = bucket_capped(length, 2048)
                    pg = np.zeros(pad, np.int32)
                    pg[:length] = bt.pod_group[start:start + length]
                    fn = np.full(pad, -1, np.int32)
                    fn[:length] = bt.forced_node[start:start + length]
                    vd = np.zeros(pad, bool)
                    vd[:length] = True
                    obs.record_dispatch(
                        "probe_serial_fanout", P=pad, zones=bt.n_zones,
                        gpu=enable_gpu, storage=enable_storage, **dims)
                    carry_s, placed = kns.probe_serial_fanout(
                        self._tables, carry_s, active,
                        jnp.asarray(pg), jnp.asarray(fn), jnp.asarray(vd),
                        n_zones=bt.n_zones, enable_gpu=enable_gpu,
                        enable_storage=enable_storage,
                        w=sim.score_w, filters=sim.filter_flags,
                    )
                elif seg[0] == "spread":
                    # dns/sa groups are gated out at build: only a live
                    # SelectorSpread counter routes here (ss_live)
                    _, start, length, g, cap1, ss_live, sa_live = seg
                    pad = bucket_capped(length, 2048)
                    vd = np.zeros(pad, bool)
                    vd[:length] = True
                    obs.record_dispatch(
                        "probe_group_serial_fanout", P=pad, ss=ss_live,
                        sa=sa_live, zones=bt.n_zones if ss_live else 2, **dims)
                    carry_s, placed = kns.probe_group_serial_fanout(
                        self._tables, carry_s, active,
                        jnp.int32(g), jnp.asarray(vd), jnp.asarray(cap1),
                        w=sim.score_w, filters=sim.filter_flags,
                        ss_live=ss_live, sa_live=sa_live,
                        n_zones=bt.n_zones if ss_live else 2,
                    )
                elif seg[0] == "affinity":
                    # counter-live predicates (dns spread is gated out at
                    # build, so: live SelectorSpread and affinity/anti gates)
                    _, start, length, g, cap1, ss_live = seg
                    block = kernels.wave_block_for(length, n_real)
                    obs.record_dispatch(
                        "probe_affinity_wave_fanout", block=block, ss=ss_live,
                        zones=bt.n_zones if ss_live else 2, **dims)
                    carry_s, placed = kns.probe_affinity_wave_fanout(
                        self._tables, carry_s, active,
                        jnp.int32(g), jnp.int32(length), jnp.asarray(cap1),
                        ss_live=ss_live, w=sim.score_w,
                        filters=sim.filter_flags, block=block,
                        n_zones=bt.n_zones if ss_live else 2,
                    )
                else:
                    _, start, length, g, cap1, gpu_live = seg
                    block = kernels.wave_block_for(length, n_real)
                    kmax = kernels.wave_kmax(length, n_real, block)
                    obs.record_dispatch("probe_wave_fanout", block=block,
                                        k=kmax, gpu_live=gpu_live, **dims)
                    carry_s, placed = kns.probe_wave_fanout(
                        self._tables, carry_s, active,
                        jnp.int32(g), jnp.int32(length), jnp.asarray(cap1),
                        kmax=kmax, gpu_live=gpu_live, w=sim.score_w,
                        filters=sim.filter_flags,
                        block=block,
                    )
                placed_parts.append(placed)
            faults.maybe_fail("fetch")
            if self._mesh is not None:
                from ..parallel.mesh import carry_reshard_bytes

                b = carry_reshard_bytes(carry_s, kns.carry_s_sh)
                if b:
                    obs.RESHARD_BYTES.inc(b)
            placed_s = np.asarray(jnp.sum(jnp.stack(placed_parts), axis=0))
            requested_s = np.asarray(carry_s.requested)
        return placed_s, requested_s

    def _utilization(self, n: int, requested_row: Optional[np.ndarray]) -> Dict[str, float]:
        """probe_utilization's aggregate totals for candidate n: f64 host sums
        over the identical per-node values a fresh probe would fetch (inactive
        and phantom columns hold zero and are sliced off anyway)."""
        m = self.n_base + n
        if requested_row is None:
            used = np.zeros((m, self._alloc.shape[1]), np.float64)  # simonlint: ignore[dtype-drift] -- host-side accumulator, mirrors probe_utilization
        else:
            used = requested_row[:m].astype(np.float64)  # simonlint: ignore[dtype-drift] -- host-side accumulator, mirrors probe_utilization
        alloc = self._alloc[:m]
        return {
            "cpu_used": float(used[:, CPU_I].sum()),
            "cpu_alloc": float(alloc[:, CPU_I].sum()),
            "mem_used": float(used[:, MEM_I].sum()),
            "mem_alloc": float(alloc[:, MEM_I].sum()),
        }


def _template_columns_uniform(bt: BatchTables, n_base: int,
                              host_counters: Sequence[int],
                              host_carriers: Sequence[int]) -> bool:
    """Verify every encoded table treats the template copies identically:
    columns n_base.. of each [G, N]/[N, *] table equal the first template
    column (hostname-keyed domain rows excepted — those are per-node by
    construction), and template seed rows are zero. This turns "fake copies
    are indistinguishable" from an argument into a checked invariant."""
    b = n_base
    for f in _GN_FIELDS:
        a = getattr(bt, f)
        if not (a[:, b + 1:] == a[:, b:b + 1]).all():
            return False
    for f in _NROW_FIELDS:
        a = getattr(bt, f)
        if not (a[b + 1:] == a[b:b + 1]).all():
            return False
    if not (bt.node_zone[b + 1:] == bt.node_zone[b]).all():
        return False
    for dom, host_rows in ((bt.counter_dom, host_counters),
                           (bt.carr_dom, host_carriers)):
        rest = np.ones(dom.shape[0], bool)
        rest[list(host_rows)] = False
        if not (dom[rest][:, b + 1:] == dom[rest][:, b:b + 1]).all():
            return False
    for f in _NSEED_FIELDS:
        if getattr(bt, f)[b:].any():
            return False
    return True
