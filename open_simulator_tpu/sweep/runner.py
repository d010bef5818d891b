"""simonsweep: the batched scenario-sweep runner.

N independent cluster futures evaluated as lanes on the scenario axis of one
(or a few bucketed) fan-out dispatches, against ONE shared device-resident
cluster image (serve/image.py):

- **Stage once, overlay per lane.** The base cluster (plus the union
  nodepool, built drained) encodes and device-stages once; every scenario
  becomes a copy-on-write overlay — an active-mask row (drains off, pool
  activations on) and, only when drains evict committed pods, a private seed
  copy (ResidentImage.lane_overlay). Zero per-scenario table bytes.
- **Route like the engine.** A scenario whose batch is entirely contiguous
  runs of wave-eligible groups (the engine's own _wave_eligibility) rides
  sweep_wave_fanout: each lane is a lax.scan CHAIN of schedule_wave segments
  — K fused waves instead of P serial steps, the same fast lane the engine's
  segmented dispatch uses. Anything else batched rides sweep_whatif_fanout
  (per-lane serial scans, exact by construction). Census-dependent workloads
  (topology spread, live SelectorSpread, gpu/storage, pre-bound pods) and
  clusters the image declines run the fresh single-scenario path.
- **Standing parity fuzzer.** Every batched lane (or a seeded sample) is
  re-run on a fresh serial Simulator over that scenario's cluster and the
  per-(node, scheduling-signature) placement censuses must match EXACTLY —
  pods of one group are interchangeable (the engine's own stitching rule),
  so census equality is placement bit-identity. A mismatch raises; it never
  degrades silently (simon_sweep_parity_mismatches_total).

On the 1-core bench host this is a pure work-reduction story: one encode +
one jitted fan-out replaces N full serial simulations' worth of Python
encode/dispatch overhead — not a parallelism story (see BENCH_DETAIL.json
notes). On a real scenario mesh the [S] axis shards one lane per device.
"""

from __future__ import annotations

import contextlib
import copy
import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ..obs import instruments as obs
from ..resilience import faults
from ..resilience import guard
from ..simulator.encode import bucket_capped, scheduling_signature
from ..utils.objutil import name_of
from .families import (
    TIER_LABEL,
    Scenario,
    build_base,
    compile_families,
)
from .spec import SweepSpec

_jnp = None


def _jax():
    global _jnp
    if _jnp is None:
        import jax.numpy as jnp

        _jnp = jnp
    return _jnp


PARITY_MODES = ("full", "sample", "off")

# census: {(node_name | "" for unscheduled, scheduling_signature): count}
Census = Dict[Tuple[str, str], int]


class SweepParityError(AssertionError):
    """A batched lane's placement census diverged from the fresh serial
    oracle — the invariant the sweep exists to fuzz. Never swallowed."""


class ScenarioResult(NamedTuple):
    scenario: Scenario
    route: str                   # wave | scan | fresh
    scheduled: int
    total: int
    census: Census
    tiers: Dict[str, int]        # tier -> scheduled count
    utilization: Dict[str, float]
    nodes_live: int
    gate: str = ""               # fresh-route reason, "" on batched routes


class _WaveSeg(NamedTuple):
    g: int
    m: int
    cap1: bool
    start: int                   # offset into scenario.pods
    sig: str
    tier: str


class SweepRunner:
    """One sweep execution: compile -> stage -> route -> batch-dispatch ->
    parity -> report. Build once, run() once."""

    def __init__(self, spec: SweepSpec, seed: Optional[int] = None,
                 parity: str = "full", parity_sample: int = 8,
                 fanout: int = 64, mesh=None) -> None:
        if parity not in PARITY_MODES:
            raise ValueError(f"parity must be one of {PARITY_MODES}")
        self.spec = spec
        self.seed = spec.seed if seed is None else int(seed)
        self.parity = parity
        self.parity_sample = max(1, int(parity_sample))
        self.fanout = max(1, int(fanout))
        self._mesh = mesh
        self.image = None
        self.scenarios: List[Scenario] = []
        self.results: Dict[int, ScenarioResult] = {}
        self.dispatches: Dict[str, int] = {}
        self.parity_checked = 0
        self._base_nodes: List[dict] = []
        self._bound: List[dict] = []
        self._pool_nodes: List[dict] = []

    # --------------------------------------------------------------- run -----

    def run(self) -> Dict[int, ScenarioResult]:
        """Evaluate every scenario; returns {sid: ScenarioResult} (also kept
        on self.results). Raises SweepParityError on any census mismatch."""
        self._base_nodes, self._bound = build_base(self.spec)
        compiled = compile_families(self.spec, self.seed, self._base_nodes)
        self.scenarios = compiled.scenarios
        self._pool_nodes = compiled.pool_nodes
        self._build_image()
        wave: List[Tuple[Scenario, object, List[_WaveSeg]]] = []
        scan: List[Tuple[Scenario, object]] = []
        fresh: List[Tuple[Scenario, str]] = []
        for sc in self.scenarios:
            route = self._route(sc)
            if route[0] == "wave":
                wave.append((sc, route[1], route[2]))
            elif route[0] == "scan":
                scan.append((sc, route[1]))
            else:
                fresh.append((sc, route[1]))
        # Shape-bucketed chunking: lanes sharing one dispatch share its
        # STATIC shapes (K, block, kmax / P_pad), so one storm-sized lane
        # in a chunk would inflate every lane's score table and top-k
        # width. Bucketing by shape keeps the common chunks at their own
        # natural sizes — on the 1-core host this is also the cache story:
        # a [S, N, B] table for a modest S stays resident where one sized
        # for the outlier thrashes.
        from ..obs import scope as scope_mod

        scope_ = scope_mod.active()  # simonscope: sweep chunks become spans
        #          in the same trace buffer the serve path fills — None-check
        #          only when off (a `simon sweep` under a scoped server
        #          shares the perfetto timeline)
        for _, chunk_lanes in sorted(_grouped(wave, self._wave_shape_key)):
            for chunk in _chunks(chunk_lanes, self.fanout):
                with (scope_.span("sweep.wave_chunk", cat="dispatch",
                                  lanes=len(chunk))
                      if scope_ is not None else contextlib.nullcontext()):
                    self._run_contained(chunk, self._dispatch_wave_chunk)
        for _, chunk_lanes in sorted(_grouped(
                scan, lambda item: bucket_capped(
                    max(1, len(item[1].batch)), 2048))):
            for chunk in _chunks(chunk_lanes, self.fanout):
                with (scope_.span("sweep.scan_chunk", cat="dispatch",
                                  lanes=len(chunk))
                      if scope_ is not None else contextlib.nullcontext()):
                    self._run_contained(chunk, self._dispatch_scan_chunk)
        for sc, gate in fresh:
            self._finish(self._serial_result(sc, route="fresh", gate=gate))
        self._check_parity()
        self._xray_results()
        return self.results

    def _build_image(self) -> None:
        from ..serve.image import ResidentImage

        self.image = ResidentImage.try_build(
            self._base_nodes + self._pool_nodes, pods=self._bound,
            mesh=self._mesh)
        if self.image is not None and self._pool_nodes:
            # the union nodepool stages INTO the image but starts drained:
            # each nodepool_mix lane re-activates its k pool columns (zero
            # seed bytes — a fresh pool node holds no pods)
            self.image.apply_events([
                {"type": "node_drain", "name": name_of(n)}
                for n in self._pool_nodes])

    # ----------------------------------------------------------- routing -----

    def _route(self, sc: Scenario):
        """('wave', session, segs) | ('scan', session) | ('fresh', gate)."""
        if self.image is None:
            return ("fresh", "image declined (cluster gate)")
        session = self.image.session(sc.pods, drains=sc.drains)
        gate = self.image.eligible(session.batch, session.pods)
        if gate is not None:
            return ("fresh", gate)
        segs = self._wave_segments(sc, session.batch)
        if segs is not None:
            return ("wave", session, segs)
        return ("scan", session)

    def _wave_segments(self, sc: Scenario,
                       batch) -> Optional[List[_WaveSeg]]:
        """The scenario's batch as a chain of wave segments — one per
        contiguous (group, unpinned) run, every run wave-eligible by the
        engine's OWN routing — or None (the scan lane is the exact
        fallback, mirroring the engine's serial segments)."""
        sim = self.image._sim
        segs: List[_WaveSeg] = []
        start = 0
        while start < len(batch):
            g, f = batch[start]
            end = start
            while end < len(batch) and batch[end] == (g, f):
                end += 1
            if f >= 0:
                return None
            route = sim._wave_eligibility(g)
            if route.kind != "wave" or route.gpu_live:
                return None
            pod = sc.pods[start]
            segs.append(_WaveSeg(
                g=g, m=end - start, cap1=bool(route.cap1), start=start,
                sig=scheduling_signature(pod),
                tier=pod["metadata"]["labels"].get(TIER_LABEL, "baseline")))
            start = end
        return segs

    def _wave_shape_key(self, item) -> tuple:
        """The static dispatch shape a wave lane compiles under: (K, block,
        kmax). Lanes grouped by this key share one dispatch without any
        lane paying for another's outlier segment sizes."""
        from ..ops import kernels

        segs = item[2]
        K = 1
        while K < max(1, len(segs)):
            K *= 2
        max_m = max((s.m for s in segs), default=1)
        n_real = self.image._sim.na.N
        block = kernels.wave_block_for(max(max_m, 1), n_real)
        return (K, block, kernels.wave_kmax(max(max_m, 1), n_real, block))

    # ------------------------------------------------------ lane assembly ----

    def _lane_arrays(self, lanes: List[Tuple[Scenario, object]]):
        """(S, active_s, carry_np): the image's shared lane assembly (pow2
        quantization, mesh shard multiple, base-seed device-cache reuse)
        with each lane's copy-on-write overlay routed through lane_overlay
        for the nodepool activations."""
        return self.image._lane_arrays(
            [session for _, session in lanes],
            activates=[sc.activates for sc, _ in lanes])

    def _run_contained(self, chunk, dispatch) -> None:
        """One batched dispatch, with contained device failures (watchdog
        wedge, OOM) failing the chunk over to per-scenario fresh serial runs
        — never silent (simon_guard_failovers_total moves)."""
        if not chunk:
            return
        try:
            for res in dispatch(chunk):
                self._finish(res)
        except BaseException as e:
            cause = guard.containment_cause(e)
            if cause is None:
                raise
            guard.count_failover(cause, "sweep")
            for item in chunk:
                sc = item[0]
                self._finish(self._serial_result(
                    sc, route="fresh", gate=f"contained failure: {cause}"))

    def _finish(self, res: ScenarioResult) -> None:
        self.results[res.scenario.sid] = res
        obs.SWEEP_SCENARIOS.labels(family=res.scenario.family,
                                   route=res.route).inc()

    # ---------------------------------------------------- wave dispatch -----

    def _dispatch_wave_chunk(self, chunk) -> List[ScenarioResult]:
        from ..ops import kernels

        image = self.image
        with image._lock:
            for _, session, _ in chunk:
                session.ensure_current()
            image.ensure_staged()
            image.check_backend()
            S, active_s, carry_np = self._lane_arrays(
                [(sc, session) for sc, session, _ in chunk])
            K = 1
            max_segs = max((len(segs) for _, _, segs in chunk), default=1)
            while K < max_segs:
                K *= 2
            g_sk = np.zeros((S, K), np.int32)
            m_sk = np.zeros((S, K), np.int32)
            cap1_sk = np.zeros((S, K), bool)
            total_pods = 0
            for li, (_, _, segs) in enumerate(chunk):
                for k, seg in enumerate(segs):
                    g_sk[li, k], m_sk[li, k] = seg.g, seg.m
                    cap1_sk[li, k] = seg.cap1
                    total_pods += seg.m
            g_sk[len(chunk):] = g_sk[0]
            m_sk[len(chunk):] = m_sk[0]
            cap1_sk[len(chunk):] = cap1_sk[0]
            max_m = int(m_sk.max()) if m_sk.size else 0
            n_real = image._sim.na.N
            block = kernels.wave_block_for(max(max_m, 1), n_real)
            kmax = kernels.wave_kmax(max(max_m, 1), n_real, block)
            self._count_dispatch("sweep_wave_fanout", len(chunk))
            obs.record_dispatch("sweep_wave_fanout", K=K, block=block,
                                k=kmax, **image._dims(S))
            counts_skn, requested_s = guard.supervised(
                functools.partial(self._wave_round, carry_np, active_s,
                                  g_sk, m_sk, cap1_sk, block, kmax),
                site="dispatch", pods=max(1, total_pods))
            image.assert_image_alive()
            out = []
            for li, (sc, _, segs) in enumerate(chunk):
                out.append(self._wave_result(sc, segs, counts_skn[li],
                                             requested_s[li], active_s[li]))
            return out

    def _wave_round(self, carry_np, active_s, g_sk, m_sk, cap1_sk, block,
                    kmax):
        jnp = _jax()
        image = self.image
        sim = image._sim
        kns, carry_s, active, ctx = image._stage_lane_inputs(
            carry_np, active_s)
        with ctx:
            faults.maybe_fail("dispatch")
            faults.maybe_fail("oom_dispatch")
            carry_s, counts = kns.sweep_wave_fanout(
                image._tables, carry_s, active,
                jnp.asarray(g_sk), jnp.asarray(m_sk), jnp.asarray(cap1_sk),
                w=sim.score_w, filters=sim.filter_flags, block=block,
                kmax=kmax)
            faults.maybe_fail("fetch")
            return np.asarray(counts), np.asarray(carry_s.requested)

    def _wave_result(self, sc: Scenario, segs: List[_WaveSeg], counts_kn,
                     requested, active_row) -> ScenarioResult:
        image = self.image
        names = image._sim.na.names
        N = image._sim.na.N
        census: Census = {}
        tiers: Dict[str, int] = {}
        scheduled = 0
        for k, seg in enumerate(segs):
            row = counts_kn[k][:N]
            placed = int(row.sum())
            scheduled += placed
            tiers[seg.tier] = tiers.get(seg.tier, 0) + placed
            for ni in np.flatnonzero(row):
                key = (names[int(ni)], seg.sig)
                census[key] = census.get(key, 0) + int(row[ni])
            if seg.m - placed:
                key = ("", seg.sig)
                census[key] = census.get(key, 0) + seg.m - placed
        return ScenarioResult(
            scenario=sc, route="wave", scheduled=scheduled,
            total=len(sc.pods), census=census, tiers=tiers,
            utilization=image._utilization(active_row, requested),
            nodes_live=int(active_row[:N].sum()))

    # ---------------------------------------------------- scan dispatch -----

    def _dispatch_scan_chunk(self, chunk) -> List[ScenarioResult]:
        image = self.image
        with image._lock:
            for _, session in chunk:
                session.ensure_current()
            image.ensure_staged()
            image.check_backend()
            S, active_s, carry_np = self._lane_arrays(list(chunk))
            P = max(len(sc.pods) for sc, _ in chunk)
            P_pad = bucket_capped(max(P, 1), 2048)
            pod_group_s = np.zeros((S, P_pad), np.int32)
            forced_node_s = np.full((S, P_pad), -1, np.int32)
            valid_s = np.zeros((S, P_pad), bool)
            total_pods = 0
            for li, (sc, session) in enumerate(chunk):
                for i, (g, f) in enumerate(session.batch):
                    pod_group_s[li, i] = g
                    forced_node_s[li, i] = f
                valid_s[li, :len(session.batch)] = True
                total_pods += len(session.batch)
            pod_group_s[len(chunk):] = pod_group_s[0]
            forced_node_s[len(chunk):] = forced_node_s[0]
            valid_s[len(chunk):] = valid_s[0]
            self._count_dispatch("sweep_whatif_fanout", len(chunk))
            obs.record_dispatch("sweep_whatif_fanout", P=P_pad,
                                zones=image._bt.n_zones, **image._dims(S))
            choices_s, requested_s = guard.supervised(
                functools.partial(self._scan_round, carry_np, active_s,
                                  pod_group_s, forced_node_s, valid_s),
                site="dispatch", pods=max(1, total_pods))
            image.assert_image_alive()
            out = []
            for li, (sc, _) in enumerate(chunk):
                out.append(self._scan_result(sc, choices_s[li],
                                             requested_s[li], active_s[li]))
            return out

    def _scan_round(self, carry_np, active_s, pod_group_s, forced_node_s,
                    valid_s):
        jnp = _jax()
        image = self.image
        sim = image._sim
        kns, carry_s, active, ctx = image._stage_lane_inputs(
            carry_np, active_s)
        with ctx:
            faults.maybe_fail("dispatch")
            faults.maybe_fail("oom_dispatch")
            # gpu/storage pinned False: the image gates decline those
            # clusters AND requests (same reasoning as serve's serial round)
            carry_s, choices = kns.sweep_whatif_fanout(
                image._tables, carry_s, active,
                jnp.asarray(pod_group_s), jnp.asarray(forced_node_s),
                jnp.asarray(valid_s),
                n_zones=image._bt.n_zones, enable_gpu=False,
                enable_storage=False, w=sim.score_w,
                filters=sim.filter_flags)
            faults.maybe_fail("fetch")
            return np.asarray(choices), np.asarray(carry_s.requested)

    def _scan_result(self, sc: Scenario, choices, requested,
                     active_row) -> ScenarioResult:
        image = self.image
        names = image._sim.na.names
        N = image._sim.na.N
        census: Census = {}
        tiers: Dict[str, int] = {}
        scheduled = 0
        for i, pod in enumerate(sc.pods):
            sig = scheduling_signature(pod)
            tier = pod["metadata"]["labels"].get(TIER_LABEL, "baseline")
            ni = int(choices[i])
            if ni >= 0:
                scheduled += 1
                tiers[tier] = tiers.get(tier, 0) + 1
                key = (names[ni], sig)
            else:
                key = ("", sig)
            census[key] = census.get(key, 0) + 1
        return ScenarioResult(
            scenario=sc, route="scan", scheduled=scheduled,
            total=len(sc.pods), census=census, tiers=tiers,
            utilization=image._utilization(active_row, requested),
            nodes_live=int(active_row[:N].sum()))

    def _count_dispatch(self, kernel: str, lanes: int) -> None:
        self.dispatches[kernel] = self.dispatches.get(kernel, 0) + 1
        obs.SWEEP_DISPATCHES.labels(kernel=kernel).inc()
        obs.SWEEP_LANES.observe(lanes)

    # ------------------------------------------------------ serial oracle ----

    def _fresh_sim(self, sc: Scenario):
        """(sim, bound_pods) — the scenario's cluster from scratch: live
        nodes minus drains plus activated pool nodes, bound pods replayed
        (minus the drained nodes'), the image's cluster objects registered."""
        if self.image is not None:
            sim, bound, _ = self.image.fresh_simulator(
                drains=sc.drains, include=sc.activates)
            return sim, bound
        from ..simulator.engine import Simulator

        skip = set(sc.drains)
        act = set(sc.activates)
        nodes = [copy.deepcopy(n) for n in self._base_nodes
                 if name_of(n) not in skip]
        nodes += [copy.deepcopy(n) for n in self._pool_nodes
                  if name_of(n) in act]
        bound = [copy.deepcopy(p) for p in self._bound
                 if (p.get("spec") or {}).get("nodeName") not in skip]
        return Simulator(nodes), bound

    def serial_result(self, sc: Scenario, route: str = "serial",
                      gate: str = "") -> ScenarioResult:
        """One scenario evaluated the reference way: a fresh Simulator over
        that scenario's cluster, the full engine path (its own wave
        segmentation and all). This is BOTH the fresh route and the parity
        oracle — and what the bench's serial loop times."""
        sim, bound = self._fresh_sim(sc)
        request = [copy.deepcopy(p) for p in sc.pods]
        # signatures snapshot BEFORE scheduling: a commit writes
        # spec.nodeName (part of the signature subtree) and the entry
        # consumes the memo, so a post-schedule signature would be
        # node-dependent and never match the batched lane's pre-schedule
        # census keys
        sig_of = {(p["metadata"].get("namespace", "default"),
                   p["metadata"]["name"]): scheduling_signature(p)
                  for p in request}
        failed = sim.schedule_pods(bound + request)

        def req_key(pod):
            md = pod.get("metadata") or {}
            return (md.get("namespace", "default"), md.get("name"))

        census: Census = {}
        tiers: Dict[str, int] = {}
        scheduled = 0
        for ni, pods in enumerate(sim.pods_on_node):
            nname = sim.na.names[ni]
            for pod in pods:
                sig = sig_of.get(req_key(pod))
                if sig is None:
                    continue  # a bound pod, not request material
                scheduled += 1
                tier = (pod["metadata"].get("labels") or {}).get(
                    TIER_LABEL, "baseline")
                tiers[tier] = tiers.get(tier, 0) + 1
                key = (nname, sig)
                census[key] = census.get(key, 0) + 1
        for u in failed:
            sig = sig_of.get(req_key(u.pod))
            if sig is not None:
                key = ("", sig)
                census[key] = census.get(key, 0) + 1
        return ScenarioResult(
            scenario=sc, route=route, scheduled=scheduled,
            total=len(sc.pods), census=census, tiers=tiers,
            utilization=sim.probe_utilization(), nodes_live=sim.na.N,
            gate=gate)

    def _serial_result(self, sc: Scenario, route: str,
                       gate: str) -> ScenarioResult:
        return self.serial_result(sc, route=route, gate=gate)

    # ------------------------------------------------------------ parity -----

    def _parity_lanes(self) -> List[int]:
        batched = sorted(sid for sid, r in self.results.items()
                         if r.route in ("wave", "scan"))
        if self.parity == "off" or not batched:
            return []
        if self.parity == "full" or len(batched) <= self.parity_sample:
            return batched
        rng = np.random.default_rng(
            np.random.SeedSequence(entropy=[self.seed, 0x9A617]))
        pick = rng.choice(len(batched), size=self.parity_sample,
                          replace=False)
        return sorted(batched[i] for i in pick)

    def _check_parity(self) -> None:
        mismatches: List[str] = []
        for sid in self._parity_lanes():
            res = self.results[sid]
            oracle = self.serial_result(res.scenario)
            self.parity_checked += 1
            obs.SWEEP_PARITY_CHECKS.inc()
            if (res.census != oracle.census
                    or res.scheduled != oracle.scheduled
                    or res.utilization != oracle.utilization):
                obs.SWEEP_PARITY_MISMATCHES.inc()
                mismatches.append(self._describe_mismatch(res, oracle))
        if mismatches:
            raise SweepParityError(
                f"{len(mismatches)} sweep lane(s) diverged from the fresh "
                f"serial oracle:\n" + "\n".join(mismatches))

    @staticmethod
    def _describe_mismatch(res: ScenarioResult,
                           oracle: ScenarioResult) -> str:
        diff = []
        keys = set(res.census) | set(oracle.census)
        for key in sorted(keys):
            a, b = res.census.get(key, 0), oracle.census.get(key, 0)
            if a != b:
                diff.append(f"{key[0] or '<unscheduled>'}: "
                            f"batched={a} serial={b}")
                if len(diff) >= 6:
                    break
        return (f"  scenario {res.scenario.sid} ({res.scenario.label}, "
                f"route={res.route}): scheduled {res.scheduled} vs "
                f"{oracle.scheduled}; " + "; ".join(diff))

    # -------------------------------------------------------------- xray -----

    def _xray_results(self) -> None:
        """simonxray ride-along: one probe record per swept scenario."""
        from ..obs import xray

        run = xray.begin_run("sweep")
        if run is None:
            return
        for sid in sorted(self.results):
            r = self.results[sid]
            run.add_probe(r.scheduled, r.total, candidate=sid)
        xray.commit_run(run, [guard.current_backend()])


def _chunks(items: List, size: int):
    for i in range(0, len(items), size):
        yield items[i:i + size]


def _grouped(items: List, key):
    """[(key, lanes)] preserving scenario order within each group."""
    out: Dict[object, List] = {}
    for item in items:
        out.setdefault(key(item), []).append(item)
    return list(out.items())
