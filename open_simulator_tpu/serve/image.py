"""simonserve: the persistent device-resident cluster image.

The reference's server mode rebuilds and re-simulates the whole cluster from
scratch on every request (pkg/server/server.go:166,233). This module keeps ONE
encoded image of the live cluster resident on the device and current:

- **Stage once.** One Simulator owns the cluster; bound pods commit once; the
  node-side tables encode once and device_put once (sharded over the scenario
  mesh when >1 device is visible). The host keeps the carry SEEDS (small
  [N, *] / [T, D+1] arrays) — every what-if dispatch broadcasts them over its
  request lanes, so the image itself is never an input a dispatch could
  mutate.
- **Delta ingest, not re-encode.** Live watch events apply columnar deltas:
  a `pod_add`/`pod_delete` churn event touches the placed-pod registry and
  re-aggregates the carry seeds (zero device bytes move — the [G, N] tables
  are placed-independent by construction); a `node_add` extends the columnar
  NodeArrays in place (one node dict parsed, not 10k re-parsed) and re-derives
  the node-axis tables; a `node_drain` flips one bit in the live-node mask
  and evicts the node's pods from the seeds — no table bytes move at all.
- **Epoch counter.** Every applied event batch bumps `seq`; a from-scratch
  re-encode (an event the delta path cannot express) bumps `generation`.
  Sessions capture the epoch at build: a generation move invalidates their
  encoded group ids, and the service re-encodes them instead of dispatching
  a stale view — stale sessions are detected, not wrong.
- **Structurally non-donatable.** The image's device buffers are only ever
  passed as the `tables` head of a dispatch, which no kernel declares
  donation on (parallel/mesh.py donates argnum 1 — the per-request carry —
  exclusively); the simonaudit `image_leaf_aliased` census certifies that at
  compile time for every registered kernel, and `assert_image_alive` verifies
  after every serve dispatch that no buffer was consumed at runtime (the
  PR 9 zombie-write hazard applied to long-lived shared state).

Provable-equivalence gates (mirrors simulator/probe.py): the image declines
clusters with node-advertised images (ImageLocality divides by the total node
count), open-local storage, or gpu-share state (host-mirrored ledgers the
delta path does not replay); per-request gates route census-dependent
workloads (topology spread, live SelectorSpread, gpu/storage requests,
pre-bound pods) to the fresh-simulation path instead. Within those gates, a
masked-inactive node is exactly a pad_batch_tables phantom, so resident
probes are bit-identical to a fresh encode of the final cluster state —
tests/test_serve.py asserts it property-style over seeded event traces.
"""

from __future__ import annotations

import contextlib
import copy
import functools
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import instruments as obs
from ..obs import scope
from ..ops.resources import CPU_I, MEM_I
from ..resilience import faults
from ..resilience import guard
from ..utils.objutil import name_of, namespaced_name as pod_key
from ..utils.trace import Span
from ..simulator.encode import (
    BatchTables,
    bucket_capped,
    build_node_axis_tables,
    build_pod_axis_tables,
    pad_batch_tables,
    pad_encoder_axes,
)
from ..simulator.store import EncodedRows, PodStore

_jnp = None


def _jax():
    global _jnp
    if _jnp is None:
        import jax.numpy as jnp

        _jnp = jnp
    return _jnp


class StaleImageError(RuntimeError):
    """A session encoded against an image generation that no longer exists
    (the image re-encoded from scratch underneath it)."""


class ImageDonatedError(AssertionError):
    """A dispatch consumed (donated/deleted) a shared cluster-image buffer —
    the structurally-forbidden aliasing of long-lived state."""


class WhatIfSession:
    """One copy-on-write what-if overlay on a shared ResidentImage: the
    request's pods (encoded to group ids) and request-local node drains,
    captured at an image epoch. Sessions never mutate the image — the overlay
    is an active-mask row plus a per-lane valid mask plus (for drains) a
    privately adjusted seed copy, all assembled at dispatch time."""

    def __init__(self, image: "ResidentImage", pods,
                 drains: Sequence[str]) -> None:
        self.image = image
        self.pods = PodStore.of(pods)  # re-encodes read the store
        self.drains = tuple(drains)
        self.generation = image.generation
        self.seq = image.seq
        self.batch = image.encode_request(self.pods)

    def ensure_current(self) -> None:
        """Re-encode after a generation move (group ids are only meaningful
        within one generation); seq moves are fine — dispatch always reads
        the image's CURRENT staged tables, and append-only interning keeps
        group ids valid across seq bumps."""
        if self.generation != self.image.generation:
            obs.SERVE_STALE_SESSIONS.inc()
            self.generation = self.image.generation
            self.seq = self.image.seq
            self.batch = self.image.encode_request(self.pods)

    def run(self) -> dict:
        """Probe this session alone (one lane). The micro-batching service
        (serve/batch.py) is the production path; this is the direct API —
        and it REFUSES a stale generation instead of silently re-encoding,
        so programmatic callers see staleness explicitly."""
        if self.generation != self.image.generation:
            raise StaleImageError(
                f"image re-encoded (generation {self.image.generation} != "
                f"session {self.generation}); rebuild the session")
        return self.image.dispatch_sessions([self])[0]


class ResidentImage:
    """Device-resident encoded cluster state + delta ingest. Build via
    try_build; None means an equivalence gate declined (serve then runs
    every request on the fresh-simulation path)."""

    def __init__(self) -> None:  # built via try_build only
        raise TypeError("use ResidentImage.try_build")

    # ------------------------------------------------------------- build ------

    @classmethod
    def try_build(cls, nodes: List[dict], cluster_objects=None,
                  pods: Sequence[dict] = (), sched_config=None,
                  mesh=None) -> Optional["ResidentImage"]:
        from ..simulator.engine import Simulator

        if guard.default_quarantined():
            return None  # the image commits device buffers to the default
            # backend; with it wedged, serve runs fresh probes on the fallback
        from ..simulator.store import NodeStore

        t0 = time.perf_counter()
        # a columnar NodeStore passes through whole (the engine adopts its
        # columns); list() would materialize N dicts just to hand them over
        sim = Simulator(nodes if isinstance(nodes, NodeStore) else list(nodes),
                        sched_config=sched_config, use_mesh=False)
        if cluster_objects is not None:
            sim.register_cluster_objects(cluster_objects)
        if sim.local_host.enabled or sim.gpu_host.enabled:
            return None  # host-mirrored storage/gpu ledgers: the delta path
            # does not replay reserve()/seed_pod() bookkeeping
        lazy_store = getattr(sim.na.nodes, "store", None)
        if lazy_store is not None:
            has_images = lazy_store.has_images
        else:
            has_images = any((n.get("status") or {}).get("images")
                             for n in sim.na.nodes)
        if has_images:
            return None  # ImageLocality divides by the TOTAL node count

        self = object.__new__(cls)
        self._sim = sim
        self._lock = threading.RLock()
        self.generation = 1
        # simonlint: ignore[race-unguarded-attr] -- construction: the instance
        # is not published until try_build returns; no concurrent reader yet
        self.seq = 0
        self._pod_index: Dict[str, Tuple[dict, int]] = {}
        self.drained: set = set()
        self._mesh = mesh if mesh is not None else self._auto_mesh()
        for pod in pods:  # simonlint: ignore[per-pod-host-loop] -- identity-keyed pod index: delta ingest removes pods BY dict, so staging materializes by design
            node_name = (pod.get("spec") or {}).get("nodeName")
            if not node_name:
                # unbound snapshot pods are request material, not cluster
                # state: the image's baseline is the BOUND set (callers
                # probing deploy-apps semantics include pending pods in
                # their request)
                continue
            ni = sim.na.index.get(node_name)
            if ni is None:
                sim.homeless.append(pod)
            else:
                sim._commit_pod(pod, ni, scheduled=False)
                self._pod_index[pod_key(pod)] = (pod, ni)
        self._restage(cause=None)
        self.build_s = time.perf_counter() - t0
        # simonscope pool attribution: registration is a WeakSet add (cheap,
        # leak-free); the runtime sampler only reads it when scope is on
        scope.register_pools(self)
        return self

    @staticmethod
    def _auto_mesh():
        """Scenario mesh over all visible devices (same OPEN_SIMULATOR_MESH
        override and quarantine rules as the probe session's)."""
        import os

        if os.environ.get("OPEN_SIMULATOR_MESH", "") in ("0", "false", "no"):
            return None
        if guard.default_quarantined():
            return None
        import jax

        n = len(jax.devices())
        if n <= 1:
            return None
        from ..parallel.mesh import make_scenario_mesh

        return make_scenario_mesh(n)

    # ------------------------------------------------------------ staging -----

    def _stage_sig(self) -> tuple:
        enc = self._sim.encoder
        return (len(enc.group_list), len(enc.counter_list),
                len(enc.carrier_list), len(enc.ports), self._sim.na.D,
                self._sim.na.N)

    def _restage(self, cause: Optional[str]) -> None:
        """Rebuild the host mirror and re-upload the device tables. `cause`
        None = initial build (uncounted)."""
        faults.maybe_fail("to_device")
        sim = self._sim
        btp = pad_batch_tables(pad_encoder_axes(self._unpadded_bt()),
                               bucket_capped(sim.na.N, 1024))
        self._bt = btp
        self._n_pad = btp.alloc.shape[0]
        self._staged_sig = self._stage_sig()
        self._upload_tables(btp)
        self._set_seeds(btp)
        self._carry_devcache: Dict[int, object] = {}
        self._alloc = np.array(sim.na.alloc, np.float64)  # simonlint: ignore[dtype-drift] -- host-side envelope sums, mirrors probe_utilization
        active = np.zeros(self._n_pad, bool)
        active[:sim.na.N] = True
        for name in self.drained:
            ni = sim.na.index.get(name)
            if ni is not None:
                active[ni] = False
        self.active = active
        if cause is not None:
            obs.SERVE_RESTAGES.labels(cause=cause).inc()

    def _upload_tables(self, btp: BatchTables) -> None:
        from ..simulator.engine import batch_tables_nbytes
        from ..parallel.mesh import tables_from_batch

        obs.TRANSFER_BYTES.inc(batch_tables_nbytes(btp))
        if self._mesh is not None:
            import jax

            from ..parallel.mesh import fanout_shardings

            ts, self._carry_sh, self._active_sh = fanout_shardings(self._mesh)
            self._tables = type(ts)(*(
                jax.device_put(np.asarray(v), s)
                for v, s in zip(tables_from_batch(btp), ts)))
        else:
            jnp = _jax()
            from ..ops import kernels

            self._tables = kernels.Tables(
                *(jnp.asarray(v) for v in tables_from_batch(btp)))

    def _set_seeds(self, btp: BatchTables) -> None:
        self._seeds = (btp.seed_requested, btp.seed_nonzero,
                       btp.seed_port_used, btp.seed_counter, btp.seed_carrier,
                       btp.seed_dev_used, btp.seed_vg_req,
                       btp.seed_sdev_alloc)

    def _refresh_seeds(self) -> None:
        """Pod-churn refresh: the [G, N] tables are placed-independent by
        construction (build_node_axis_tables derives them from the encoder's
        group statics alone), so only the carry seeds re-aggregate from the
        placed registry — zero device bytes move."""
        sim = self._sim
        btp = pad_batch_tables(pad_encoder_axes(self._unpadded_bt()),
                               bucket_capped(sim.na.N, 1024))
        self._bt = btp
        self._set_seeds(btp)
        self._carry_devcache = {}
        obs.SERVE_SEED_REFRESHES.inc()

    def _unpadded_bt(self) -> BatchTables:
        """The cluster's tables with no pod rows (requests ride lanes)."""
        sim = self._sim
        no_pods = EncodedRows(np.zeros(0, np.int32), np.zeros(0, np.int32))
        return BatchTables(
            **build_pod_axis_tables(sim.encoder, no_pods, pad_to=8),
            **build_node_axis_tables(sim.encoder, sim.placed,
                                     sim.match_cache))

    def ensure_staged(self) -> None:
        """Re-upload the device tables when the encoder axes moved since the
        stage (a request interned a new group/counter/port — the staged
        [G, N] rows lack it). Warm serving (every group already interned)
        never lands here."""
        with self._lock:
            if self._stage_sig() != self._staged_sig:
                self._restage(cause="groups")

    # ---------------------------------------------------------- telemetry -----

    def device_pool_bytes(self) -> Dict[str, int]:
        """simonscope pool attribution: live device bytes owned by this
        image, by pool — the staged cluster tables vs. the cached per-lane
        base-seed carries. Holds the image lock only long enough to snapshot
        the leaf references; nbytes reads never block on device work."""
        with self._lock:
            tables = list(self._tables)
            carries = [leaf for c in self._carry_devcache.values()
                       for leaf in c]
        return {
            "image_tables": sum(int(getattr(v, "nbytes", 0) or 0)
                                for v in tables),
            "carry_cache": sum(int(getattr(v, "nbytes", 0) or 0)
                               for v in carries),
        }

    # -------------------------------------------------------------- epoch -----

    @property
    def epoch(self) -> str:
        # simonlint: ignore[race-unguarded-attr] -- epoch stamp: GIL-atomic
        # int read; racing apply_events yields the previous epoch, which is a
        # consistent published state
        return f"{self.generation}.{self.seq}"

    @property
    def n_nodes(self) -> int:
        """Live (non-drained) node count."""
        return int(self.active[:self._sim.na.N].sum())

    # ---------------------------------------------------------- sync view -----

    def has_pod(self, key: str) -> bool:
        """True when a committed pod with this "namespace/name" key is
        resident. Watch-sync presence dedup: a re-delivered pod_add for a
        resident key is a duplicate, not a new commit."""
        with self._lock:
            return key in self._pod_index

    def node_state(self, name: str) -> str:
        """"live" | "drained" | "absent" — the store's view of one node
        name, without materializing the node object."""
        with self._lock:
            ni = self._sim.na.index.get(name)
            if ni is None or ni >= self.active.shape[0]:
                return "absent"
            return "live" if bool(self.active[ni]) else "drained"

    def sync_snapshot(self) -> Tuple[Dict[str, Optional[str]], set]:
        """Columnar view for watch-sync relist reconciliation: a
        ({pod_key: node_name}, {live node names}) pair read straight off the
        index structures — no per-object dict materialization."""
        with self._lock:
            pods = {key: (pod.get("spec") or {}).get("nodeName")
                    for key, (pod, _) in self._pod_index.items()}
            na = self._sim.na
            nodes = {name for name, i in na.index.items()
                     if i < self.active.shape[0] and bool(self.active[i])}
            return pods, nodes

    # ------------------------------------------------------------- ingest -----

    def apply_events(self, events: Sequence[dict]) -> dict:
        """Apply one batch of live watch-event deltas; bumps the epoch once.
        Event kinds (each a dict with "type"):

        - pod_add:    {"pod": {... spec.nodeName set}} — a pod was scheduled
                      on the live cluster; commits into the seeds.
        - pod_delete: {"namespace": ..., "name": ...} — a pod left.
        - node_add:   {"node": {...}} — columnar NodeArrays extension + node
                      table re-derive + device re-stage.
        - node_drain: {"name": ...} — the node leaves the schedulable set;
                      its pods are evicted from the seeds (kube drain
                      semantics: the node AND its pods leave the cluster).

        Returns {"epoch", "applied", "skipped", "restaged"}. Events the
        delta path cannot express (unknown resource axes, duplicate node
        names) force a from-scratch re-encode (generation bump) rather than
        an approximation."""
        applied = skipped = 0
        with self._lock:
            seeds_dirty = False
            restage_cause: Optional[str] = None
            rebuild = False
            try:
                for ev in events:
                    kind = ev.get("type", "")
                    ok, sd, rc, rb = self._apply_one(kind, ev)
                    applied += 1 if ok else 0
                    skipped += 0 if ok else 1
                    seeds_dirty |= sd
                    rebuild |= rb
                    if rc:
                        restage_cause = rc
                    if ok:
                        obs.SERVE_INGEST_EVENTS.labels(kind=kind or "?").inc()
                self.seq += 1
                if rebuild:
                    self._rebuild()
                elif restage_cause is not None:
                    self._restage(cause=restage_cause)
                elif seeds_dirty:
                    self._refresh_seeds()
            except BaseException:
                # a mid-batch failure must not leave a half-applied image
                # (host state mutated, staged tables stale): re-encode from
                # the current host truth before propagating, so every later
                # request sees a consistent (if partially-ingested) cluster
                self.seq += 1
                self._rebuild()
                raise
            return {"epoch": self.epoch, "applied": applied,
                    "skipped": skipped,
                    "restaged": rebuild or restage_cause is not None}

    def _apply_one(self, kind: str, ev: dict):
        """(applied, seeds_dirty, restage_cause, rebuild)"""
        sim = self._sim
        if kind == "pod_add":
            pod = ev.get("pod") or {}
            node_name = (pod.get("spec") or {}).get("nodeName")
            ni = sim.na.index.get(node_name) if node_name else None
            if ni is None or not self.active[ni]:
                sim.homeless.append(pod)
                return False, False, None, False
            sim._commit_pod(pod, ni, scheduled=False)
            self._pod_index[pod_key(pod)] = (pod, ni)
            return True, True, None, False
        if kind == "pod_delete":
            key = ev.get("key") or f"{ev.get('namespace', 'default')}/{ev.get('name', '')}"
            got = self._pod_index.pop(key, None)
            if got is None:
                return False, False, None, False
            self._remove_pod(*got)
            return True, True, None, False
        if kind == "node_add":
            node = ev.get("node") or {}
            name = name_of(node)
            if not name or name in sim.na.index:
                return True, False, None, True  # duplicate/unnamed: rebuild
            alloc = ((node.get("status") or {}).get("allocatable") or {})
            if any(k not in sim.axis.names for k in alloc):
                return True, False, None, True  # new resource axis: rebuild
            self._extend_nodes([node])
            # keep the live mask current WITHIN the batch: a later event in
            # this same batch (pod_add onto / drain of the new node) must see
            # it live — _restage rebuilds the padded mask afterwards anyway
            ni = sim.na.index[name]
            if ni < self.active.shape[0]:
                self.active[ni] = True
            else:
                self.active = np.append(self.active, True)
            return True, False, "nodes", False
        if kind in ("node_drain", "node_delete"):
            name = ev.get("name", "")
            ni = sim.na.index.get(name)
            if ni is None or not self.active[ni]:
                return False, False, None, False
            self.active[ni] = False
            self.drained.add(name)
            for pod in list(sim.pods_on_node[ni]):
                self._pod_index.pop(pod_key(pod), None)
                self._remove_pod(pod, ni)
            return True, True, None, False
        return False, False, None, False

    def _remove_pod(self, pod: dict, node_i: int) -> None:
        sim = self._sim
        got = sim._sig_of.pop(id(pod), None)
        if got is None:
            return
        sig = got[0]
        pg = sim.placed.get(sig)
        if pg is not None:
            c = pg.node_counts.get(node_i, 0)
            if c <= 1:
                pg.node_counts.pop(node_i, None)
            else:
                pg.node_counts[node_i] = c - 1
        try:
            sim.pods_on_node[node_i].remove(pod)
        except ValueError:
            pass

    def _extend_nodes(self, nodes: List[dict]) -> None:
        """Delta node-add: extend the columnar node store in place and
        re-derive every group's node-axis statics; the following _restage
        rebuilds the [*, N] tables from them (the vectorized numpy half —
        the raw-dict parsing is paid for ONE node, not the cluster)."""
        sim = self._sim
        sim.na.extend(copy.deepcopy(nodes))
        sim.encoder.rebuild_group_axes()
        sim.pods_on_node.extend([] for _ in nodes)
        # per-(counter, sig) selector matches depend on pod templates only —
        # the cache stays valid across node growth (see rebuild_group_axes)

    def _rebuild(self) -> None:
        """From-scratch re-encode (generation bump): the delta path declined
        an event. Sessions from the old generation re-encode on next use."""
        from ..core.types import ResourceTypes
        from ..simulator.engine import Simulator

        old = self._sim
        nodes = [copy.deepcopy(n) for i, n in enumerate(old.na.nodes)
                 if self.active[i]]
        sim = Simulator(nodes, sched_config=old.sched_config,
                        use_mesh=False)
        rt = ResourceTypes(
            services=list(old.model.services),
            replication_controllers=list(old.model.replication_controllers),
            replica_sets=list(old.model.replica_sets),
            stateful_sets=list(old.model.stateful_sets),
            storage_classes=list(old.model.storage_classes),
            config_maps=list(old.model.config_maps),
            pod_disruption_budgets=list(old.model.pdbs),
            persistent_volume_claims=list(old.model.pvcs),
        )
        sim.register_cluster_objects(rt)
        self._sim = sim
        index: Dict[str, Tuple[dict, int]] = {}
        for key, (pod, _) in self._pod_index.items():
            ni = sim.na.index.get((pod.get("spec") or {}).get("nodeName"))
            if ni is None:
                sim.homeless.append(pod)
                continue
            sim._commit_pod(pod, ni, scheduled=False)
            index[key] = (pod, ni)
        self._pod_index = index
        self.drained = set()
        self.generation += 1
        self._restage(cause="rebuild")

    # ----------------------------------------------------------- requests -----

    def encode_request(self, pods) -> EncodedRows:
        """Pod-axis encode of one request (a PodStore or pod dicts) against
        the shared encoder: (group_id, forced_node) per pod. Warm path (every
        signature already interned) is a dict hit per template; a fresh group
        triggers ensure_staged at the next dispatch."""
        with self._lock:
            return self._sim.encode_batch_ids(pods)

    def session(self, pods,
                drains: Sequence[str] = ()) -> WhatIfSession:
        return WhatIfSession(self, pods, drains)

    def eligible(self, batch: EncodedRows, pods) -> Optional[str]:
        """None when the request can ride the resident micro-batched path;
        otherwise the gate name routing it to the fresh-simulation path.
        Census-dependent inputs (topology spread eligible-domain sets, live
        SelectorSpread) are computed over the node CENSUS at encode time, so
        a masked-inactive node is not equivalent to an absent one for them;
        gpu/storage groups carry host-mirrored state the image declines.
        `pods`: the request as a PodStore (a session's `pods`) or dicts."""
        if PodStore.of(pods).bound_mask() is not None:
            return "pre-bound pod"
        with self._lock:
            enc = self._sim.encoder
            for gi, _ in batch:  # simonlint: ignore[per-pod-host-loop] -- small request batches; the rows are already encoded ids
                if gi >= len(enc.group_list):
                    # the image re-encoded from scratch under the caller:
                    # conservative fresh routing (dispatch_sessions would
                    # re-encode, but the caller's gate answer must be safe)
                    return "stale image generation"
                g = enc.group_list[gi]
                if g.spread_dns or g.spread_sa:
                    return "topology spread (census-dependent eligible domains)"
                if g.ss_counter >= 0:
                    return "live SelectorSpread (census-dependent)"
                if g.gpu_mem > 0 or g.lvm_sizes or g.sdev_sizes:
                    return "gpu/local-storage request"
        return None

    def lane_overlay(self, session: WhatIfSession,
                     activate: Sequence[str] = ()):
        """One sweep lane's copy-on-write overlay: lane_inputs' (active row,
        seed copy) plus ACTIVATION of currently-drained nodes by name — the
        nodepool-mix family pre-encodes its pool nodes into the image (built
        drained) and each scenario lane flips k of them live. Activation
        never touches the seeds: a pool node has no pods, so its seed rows
        are zero by construction and a masked-live node is exactly a fresh
        encode's extra node."""
        active, seeds = self.lane_inputs(session)
        for name in activate:
            ni = self._sim.na.index.get(name)
            if ni is not None:
                active[ni] = True
        return active, seeds

    def lane_inputs(self, session: WhatIfSession):
        """(active_row [n_pad] bool, seeds tuple) for one session's overlay:
        the image's live mask minus the request's drains, and — when drains
        are present — a privately adjusted seed copy with the drained nodes'
        pods evicted (per-node rows zeroed, their counter/carrier domain
        contributions subtracted), so the lane is bit-equivalent to a fresh
        encode of the cluster without those nodes and their pods."""
        active = self.active.copy()
        if not session.drains:
            return active, self._seeds
        sim = self._sim
        drain_idx = []
        for name in session.drains:
            ni = sim.na.index.get(name)
            if ni is not None and active[ni]:
                active[ni] = False
                drain_idx.append(ni)
        if not drain_idx:
            return active, self._seeds
        (requested, nonzero, port_used, counter, carrier,
         dev_used, vg_req, sdev_alloc) = (v.copy() for v in self._seeds)
        requested[drain_idx] = 0.0
        nonzero[drain_idx] = 0.0
        port_used[drain_idx] = False
        bt = self._bt
        for pg in sim.placed.values():
            nis = [ni for ni in drain_idx if ni in pg.node_counts]
            if not nis:
                continue
            for ni in nis:
                cnt = float(pg.node_counts[ni])
                for t, cs in enumerate(sim.encoder.counter_list):
                    m = sim.match_cache.get((t, pg.sig))
                    if m is None:
                        m = sim.match_cache[(t, pg.sig)] = cs.matches_pod(pg.pod)
                    if m:
                        d = int(bt.counter_dom[t, ni])
                        if d < counter.shape[1] - 1:
                            counter[t, d] -= cnt
                for cid in pg.carrier_ids:
                    d = int(bt.carr_dom[cid, ni])
                    if d < carrier.shape[1] - 1:
                        carrier[cid, d] -= cnt
        return active, (requested, nonzero, port_used, counter, carrier,
                        dev_used, vg_req, sdev_alloc)

    # ----------------------------------------------------------- dispatch -----

    def check_backend(self) -> None:
        """Mirror of ProbeSession._check_backend: device-resident arrays are
        committed to the default backend; once it quarantines, refuse to
        touch them again (the service then routes requests to the fresh
        path, which the engine runs on the CPU fallback)."""
        if guard.default_quarantined():
            raise guard.BackendWedged("dispatch", guard.current_backend(),
                                      injected=False)

    def assert_image_alive(self) -> None:
        """Runtime half of the non-donation contract: no dispatch may have
        consumed a shared image buffer. A deleted leaf here means a donating
        executable took the tables head — the compile-time image_leaf_aliased
        audit census exists to make this unreachable."""
        for name, leaf in zip(type(self._tables)._fields, self._tables):
            if getattr(leaf, "is_deleted", None) is not None and leaf.is_deleted():
                raise ImageDonatedError(
                    f"shared cluster-image buffer '{name}' was consumed by a "
                    f"dispatch — image tables are structurally non-donatable")

    def dispatch_sessions(self, sessions: List[WhatIfSession]) -> List[dict]:
        """Micro-batched dispatch over the sessions; returns one response
        dict per session, in order. Sessions partition into the WAVE lane
        (uniform-replica requests — one group, no pin: one fused
        feasibility/score pass + top-k commit per lane via
        serve_wave_fanout, provably identical to the serial placements) and
        the SERIAL lane (mixed-pod requests — the union-batch
        serve_whatif_fanout scan). Callers (serve/batch.py) own eligibility;
        every session must be current (ensure_current) and non-empty."""
        with self._lock:
            # re-validate UNDER the lock: a rebuild-forcing ingest may have
            # swapped the generation between the caller's eligibility check
            # and here — gen-k group ids must never index gen-k+1 tables
            for s in sessions:
                s.ensure_current()
            self.ensure_staged()
            self.check_backend()
            wave: List[Tuple[int, WhatIfSession, tuple]] = []
            serial: List[Tuple[int, WhatIfSession]] = []
            for i, s in enumerate(sessions):
                route = self._wave_route(s)
                if route is not None:
                    wave.append((i, s, route))
                else:
                    serial.append((i, s))
            out: List[Optional[dict]] = [None] * len(sessions)
            lanes = len(sessions)
            if wave:
                for (i, _, _), resp in zip(
                        wave, self._dispatch_wave(
                            [s for _, s, _ in wave],
                            [r for _, _, r in wave], lanes)):
                    out[i] = resp
            if serial:
                for (i, _), resp in zip(
                        serial, self._dispatch_serial(
                            [s for _, s in serial], lanes)):
                    out[i] = resp
            self._xray_sessions(out)
            return out

    def _wave_route(self, session: WhatIfSession):
        """(g, m, cap1) when the whole request is m unpinned replicas of ONE
        wave-eligible group (the engine's own routing decides — counter-live
        or preferred-score-live groups stay on the exact serial scan)."""
        batch = session.batch
        g0, f0 = batch[0]
        if f0 >= 0 or any(b != (g0, -1) for b in batch):
            return None
        route = self._sim._wave_eligibility(g0)
        if route.kind != "wave" or route.gpu_live:
            return None
        return (g0, len(batch), route.cap1)

    def _lane_arrays(self, sessions: List[WhatIfSession],
                     activates: Optional[Sequence[Sequence[str]]] = None):
        """(S, active_s [S, n_pad], carry_np) — lane quantization (pow2,
        then the mesh shard multiple; surplus lanes repeat lane 0 and are
        sliced off) plus each lane's active overlay and seed copy. carry_np
        is None when every lane uses the UNMODIFIED base seeds (no drains) —
        the staging path then reuses the per-(epoch, S) device-resident
        carry instead of re-stacking and re-transferring it per dispatch.
        `activates` (aligned with sessions) routes through lane_overlay —
        the sweep runner's nodepool-activation lanes share this exact
        assembly (ONE home for the quantization + base-carry-cache logic,
        the area the PR 9 donation fix patched)."""
        S = 1
        while S < len(sessions):
            S *= 2
        if self._mesh is not None:
            from ..parallel.mesh import SCENARIO_AXIS

            S += (-S) % self._mesh.shape[SCENARIO_AXIS]
        active_s = np.zeros((S, self._n_pad), bool)
        lane_seeds = []
        all_base = True
        for li, s in enumerate(sessions):
            if activates is None:
                active, seeds = self.lane_inputs(s)
            else:
                active, seeds = self.lane_overlay(s, activates[li])
            active_s[li] = active
            lane_seeds.append(seeds)
            all_base &= seeds is self._seeds
        for li in range(len(sessions), S):
            active_s[li] = active_s[0]
            lane_seeds.append(lane_seeds[0])
        if all_base and self._carry_cacheable():
            return S, active_s, None
        carry_np = tuple(
            np.ascontiguousarray(
                np.stack([lane_seeds[li][k] for li in range(S)]))
            for k in range(len(lane_seeds[0])))
        return S, active_s, carry_np

    def _carry_cacheable(self) -> bool:
        """The input carry survives a dispatch only when the executable does
        not donate it: single-device module kernels never donate, and
        multi-device CPU meshes downgrade donation (donation_runtime_safe);
        an accelerator mesh donates, so its carries are never cached."""
        if self._mesh is None:
            return True
        from ..parallel.mesh import donation_runtime_safe

        return not donation_runtime_safe(self._mesh)

    def _base_carry(self, S: int):
        """Device-resident [S]-lane broadcast of the base seeds, cached per
        lane count and invalidated by every ingest/restage (the caller holds
        the image lock)."""
        got = self._carry_devcache.get(S)
        if got is not None:
            return got
        jnp = _jax()
        from ..ops import kernels

        carry_np = tuple(
            np.ascontiguousarray(np.broadcast_to(v, (S,) + v.shape))
            for v in self._seeds)
        if self._mesh is not None:
            import jax

            carry = kernels.Carry(*(
                jax.device_put(v, sh)
                for v, sh in zip(carry_np, self._carry_sh)))
        else:
            carry = kernels.Carry(*(jnp.asarray(v) for v in carry_np))
        self._carry_devcache[S] = carry
        return carry

    def _dims(self, S: int, **extra):
        sim, btp = self._sim, self._bt
        dims = {"S": S, "N": self._n_pad,
                "G": int(btp.static_mask.shape[0]),
                "T": int(btp.counter_dom.shape[0]),
                "mesh": self._mesh is not None,
                "cfg": f"{hash((sim.score_w, sim.filter_flags)) & 0xffffffff:08x}",
                **extra}
        if self._mesh is not None:
            from ..parallel.mesh import donation_runtime_safe

            dims["donate"] = donation_runtime_safe(self._mesh)
        return dims

    def _dispatch_wave(self, sessions: List[WhatIfSession], routes: List[tuple],
                       lanes: int) -> List[dict]:
        from ..ops import kernels

        S, active_s, carry_np = self._lane_arrays(sessions)
        g_s = np.zeros(S, np.int32)
        m_s = np.zeros(S, np.int32)
        cap1_s = np.zeros(S, bool)
        for li, (g, m, cap1) in enumerate(routes):
            g_s[li], m_s[li], cap1_s[li] = g, m, cap1
        g_s[len(routes):], m_s[len(routes):], cap1_s[len(routes):] = (
            g_s[0], m_s[0], cap1_s[0])
        max_m = int(m_s.max())
        block = kernels.wave_block_for(max_m, self._sim.na.N)
        kmax = kernels.wave_kmax(max_m, self._sim.na.N, block)
        obs.SERVE_BATCHES.inc()
        obs.SERVE_LANES.observe(len(sessions))
        obs.record_dispatch("serve_wave_fanout", zones=self._bt.n_zones,
                            block=block, k=kmax, **self._dims(S))
        placed_s, requested_s = guard.supervised(
            functools.partial(self._wave_round, carry_np, active_s, g_s, m_s,
                              cap1_s, block, kmax),
            site="dispatch", pods=max_m * S)
        self.assert_image_alive()
        return self._responses(sessions, [m for _, m, _ in routes], placed_s,
                               requested_s, active_s, lanes)

    def _dispatch_serial(self, sessions: List[WhatIfSession],
                         lanes: int) -> List[dict]:
        S, active_s, carry_np = self._lane_arrays(sessions)
        # union pod batch: each session's rows stay contiguous and in order
        union: List[Tuple[int, int]] = []
        spans: List[Tuple[int, int]] = []
        for s in sessions:
            spans.append((len(union), len(s.batch)))
            union.extend(s.batch)
        P = max(1, len(union))
        P_pad = bucket_capped(P, 2048)
        pod_group = np.zeros(P_pad, np.int32)
        forced_node = np.full(P_pad, -1, np.int32)
        for i, (g, f) in enumerate(union):
            pod_group[i] = g
            forced_node[i] = f
        valid_s = np.zeros((S, P_pad), bool)
        for li, (start, length) in enumerate(spans):
            valid_s[li, start:start + length] = True
        valid_s[len(sessions):] = valid_s[0]
        obs.SERVE_BATCHES.inc()
        obs.SERVE_LANES.observe(len(sessions))
        obs.record_dispatch("serve_whatif_fanout", zones=self._bt.n_zones,
                            P=P_pad, **self._dims(S))
        placed_s, requested_s = guard.supervised(
            functools.partial(self._serial_round, carry_np, active_s,
                              pod_group, forced_node, valid_s),
            site="dispatch", pods=P * S)
        self.assert_image_alive()
        return self._responses(sessions, [n for _, n in spans], placed_s,
                               requested_s, active_s, lanes)

    def _stage_lane_inputs(self, carry_np, active_s):
        """(kns, carry_s, active, ctx) — device staging for one fan-out
        round; runs inside the watchdog's worker thread (the mesh context is
        thread-local). carry_np None = all lanes ride the cached
        device-resident base-seed carry (_base_carry)."""
        jnp = _jax()
        from ..ops import kernels

        if self._mesh is not None:
            import jax

            from ..parallel.mesh import sharded_kernels

            kns = sharded_kernels(self._mesh, donate=True)
            if carry_np is None:
                carry_s = self._base_carry(active_s.shape[0])
            else:
                carry_s = kernels.Carry(*(
                    jax.device_put(v, sh)
                    for v, sh in zip(carry_np, self._carry_sh)))
            active = jax.device_put(active_s, self._active_sh)
            return kns, carry_s, active, self._mesh
        import contextlib

        if carry_np is None:
            carry_s = self._base_carry(active_s.shape[0])
        else:
            carry_s = kernels.Carry(*(jnp.asarray(v) for v in carry_np))
        return kernels, carry_s, jnp.asarray(active_s), contextlib.nullcontext()

    def _wave_round(self, carry_np, active_s, g_s, m_s, cap1_s, block, kmax):
        jnp = _jax()
        sim = self._sim
        sc = scope.active()
        kns, carry_s, active, ctx = self._stage_lane_inputs(carry_np, active_s)
        with ctx:
            faults.maybe_fail("dispatch")
            faults.maybe_fail("oom_dispatch")
            # phase marks + spans run on the watchdog WORKER thread: the
            # copied contextvars carry both the batcher's sink and the trace
            # ctx here, so the trace shows dispatch/fetch on the thread that
            # actually blocked on them (the serve.* Spans put the same two
            # phases on the profiler clock)
            scope.mark("kernel_begin")
            with Span("serve.dispatch"), (
                    sc.span("kernel:serve_wave_fanout", cat="dispatch")
                    if sc is not None else contextlib.nullcontext()):
                carry_s, placed = kns.serve_wave_fanout(
                    self._tables, carry_s, active,
                    jnp.asarray(g_s), jnp.asarray(m_s), jnp.asarray(cap1_s),
                    w=sim.score_w, filters=sim.filter_flags, block=block,
                    kmax=kmax)
            scope.mark("kernel_end")
            faults.maybe_fail("fetch")
            with Span("serve.fetch"), (
                    sc.span("fetch:serve_wave_fanout", cat="dispatch")
                    if sc is not None else contextlib.nullcontext()):
                out = np.asarray(placed), np.asarray(carry_s.requested)
            scope.mark("fetch_end")
            return out

    def _serial_round(self, carry_np, active_s, pod_group, forced_node,
                      valid_s):
        jnp = _jax()
        sim, btp = self._sim, self._bt
        sc = scope.active()
        kns, carry_s, active, ctx = self._stage_lane_inputs(carry_np, active_s)
        with ctx:
            faults.maybe_fail("dispatch")
            faults.maybe_fail("oom_dispatch")
            scope.mark("kernel_begin")
            # enable_gpu/enable_storage pinned False: the image gates decline
            # gpu/storage clusters AND requests, so the inert subgraphs
            # compile away and an ineligible interned group can never flip
            # the staged flags (and the compiled signature) underneath us
            with Span("serve.dispatch"), (
                    sc.span("kernel:serve_whatif_fanout", cat="dispatch")
                    if sc is not None else contextlib.nullcontext()):
                carry_s, placed = kns.serve_whatif_fanout(
                    self._tables, carry_s, active,
                    jnp.asarray(pod_group), jnp.asarray(forced_node),
                    jnp.asarray(valid_s),
                    n_zones=btp.n_zones, enable_gpu=False,
                    enable_storage=False,
                    w=sim.score_w, filters=sim.filter_flags)
            scope.mark("kernel_end")
            faults.maybe_fail("fetch")
            with Span("serve.fetch"), (
                    sc.span("fetch:serve_whatif_fanout", cat="dispatch")
                    if sc is not None else contextlib.nullcontext()):
                out = np.asarray(placed), np.asarray(carry_s.requested)
            scope.mark("fetch_end")
            return out

    def _responses(self, sessions, totals, placed_s, requested_s, active_s,
                   lanes: int) -> List[dict]:
        out = []
        for li, (s, total) in enumerate(zip(sessions, totals)):
            placed = int(placed_s[li])
            out.append({
                "scheduled": placed,
                "total": total,
                "unscheduled": total - placed,
                "utilization": self._utilization(active_s[li],
                                                 requested_s[li]),
                # simonlint: ignore[race-unguarded-attr] -- epoch stamp:
                # GIL-atomic int read, same contract as the epoch property
                "epoch": f"{s.generation}.{self.seq}",
                "lanes": lanes,
                "path": "batched",
            })
        return out

    def _utilization(self, active_row: np.ndarray,
                     requested_row: np.ndarray) -> Dict[str, float]:
        """probe_utilization's aggregate totals for one lane: f64 host sums
        over the lane's live nodes — masked rows (drained nodes, phantom
        padding) are excluded, so the compacted sequence equals the fresh
        encode's node order and the sums are bit-identical."""
        N = self._sim.na.N
        mask = active_row[:N]
        used = requested_row[:N][mask].astype(np.float64)  # simonlint: ignore[dtype-drift] -- host-side accumulator, mirrors probe_utilization
        alloc = self._alloc[:N][mask]
        return {
            "cpu_used": float(used[:, CPU_I].sum()),
            "cpu_alloc": float(alloc[:, CPU_I].sum()),
            "mem_used": float(used[:, MEM_I].sum()),
            "mem_alloc": float(alloc[:, MEM_I].sum()),
        }

    def _xray_sessions(self, responses: List[dict]) -> None:
        """simonxray ride-along: one probe record per micro-batched request
        (counts only — serve never materializes placements)."""
        from ..obs import xray

        run = xray.begin_run("serve")
        if run is None:
            return
        for r in responses:
            run.add_probe(r["scheduled"], r["total"])
        xray.commit_run(run, [guard.current_backend()])

    # ---------------------------------------------------------- slow path -----

    def current_nodes(self, extra_drains: Sequence[str] = (),
                      include: Sequence[str] = ()) -> List[dict]:
        """Deep copies of the live (non-drained) nodes, order preserved.
        `include` names currently-drained nodes to treat as live (the sweep
        nodepool activation overlay)."""
        skip = set(extra_drains)
        add = set(include)
        return [copy.deepcopy(n) for i, n in enumerate(self._sim.na.nodes)
                if (self.active[i] or name_of(n) in add)
                and name_of(n) not in skip]

    def cluster_pods(self, extra_drains: Sequence[str] = ()) -> List[dict]:
        """Deep copies of the committed (bound) pods on live nodes, in commit
        order — the prebound prefix a fresh probe replays."""
        skip = set(extra_drains)
        out = []
        for pod, ni in self._pod_index.values():
            if self.active[ni] and self._sim.na.names[ni] not in skip:
                out.append(copy.deepcopy(pod))
        return out

    def fresh_simulator(self, drains: Sequence[str] = (),
                        include: Sequence[str] = ()):
        """(sim, bound_pods, epoch): a fresh Simulator over the current live
        cluster state minus `drains` (and those nodes' pods) plus the named
        currently-drained nodes in `include` (sweep nodepool activation),
        with the image's cluster objects registered. `bound_pods` are deep
        copies of the committed pods in commit order — the prebound prefix
        the from-scratch oracle replays before the request. Shared by
        fresh_probe and the sweep runner's serial oracle."""
        from ..core.types import ResourceTypes
        from ..simulator.engine import Simulator

        with self._lock:
            nodes = self.current_nodes(drains, include)
            bound = self.cluster_pods(drains)
            model = self._sim.model
            rt = ResourceTypes(
                services=list(model.services),
                replication_controllers=list(model.replication_controllers),
                replica_sets=list(model.replica_sets),
                stateful_sets=list(model.stateful_sets),
                storage_classes=list(model.storage_classes),
                config_maps=list(model.config_maps),
                pod_disruption_budgets=list(model.pdbs),
                persistent_volume_claims=list(model.pvcs),
            )
            sched_config = self._sim.sched_config
            epoch = self.epoch
        sim = Simulator(nodes, sched_config=sched_config)
        sim.register_cluster_objects(rt)
        return sim, bound, epoch

    def fresh_probe(self, pods, drains: Sequence[str] = ()) -> dict:
        """The from-scratch oracle AND the fresh-path route: build a fresh
        Simulator over the current cluster state (minus request drains and
        those nodes' pods), replay the bound pods, probe the request. This
        is byte-for-byte what the resident path must reproduce — the parity
        suite compares the two on every seeded trace. `pods`: the request
        as a PodStore (a session's `pods`, whose signatures its copies
        carry) or dicts."""
        sim, bound, epoch = self.fresh_simulator(drains)
        request = PodStore.of(pods).signed_copies()
        scheduled, total = sim.probe_pods(bound + request)
        return {
            "scheduled": scheduled - len(bound),
            "total": total - len(bound),
            "unscheduled": total - scheduled,
            "utilization": sim.probe_utilization(),
            "epoch": epoch,
            "lanes": 1,
            "path": "fresh",
        }
