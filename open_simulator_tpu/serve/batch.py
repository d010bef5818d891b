"""simonserve: the cross-request micro-batching dispatcher.

Concurrent what-if requests arriving within a short window coalesce onto the
scenario axis of ONE serve_whatif_fanout dispatch (ops/kernels.py): the
requests' pods are union-encoded into a single padded batch, each request
becomes one lane with its own node-active overlay and valid mask, and the
results demux back to the waiting callers. Lane padding repeats lane 0 and is
sliced off, the per-lane valid masks make union rows outside a request
provable no-ops, and the shared image's device tables are read-only inputs —
so a micro-batched response is bit-identical to the same request probed
serially from a fresh encode (the determinism contract PARITY.md documents
and tests/test_serve.py asserts).

Failure semantics: a contained device failure (watchdog wedge, OOM — see
resilience/guard.py) fails the whole batch over to the fresh-simulation path
per request, which the engine routes to the CPU fallback; nothing is silent
(simon_guard_failovers_total moves, responses carry path="fresh"). Ineligible
requests (census-dependent predicates, pre-bound pods, gpu/storage) never
enter a batch — they run the fresh path directly.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence

from ..obs import instruments as obs
from ..obs import scope
from ..resilience import guard
from .ha import AdmissionController
from .image import ResidentImage, WhatIfSession

# requests larger than this ride the fresh path: big batches want the
# engine's wave segmentation, not S copies of a long serial scan
MAX_BATCHED_PODS = 512


class _Pending:
    """One enqueued request and its rendezvous. `tm` is the simonscope
    timing/trace record (None with scope off — the zero-cost contract):
    the request's TraceCtx + flow id, the phase-boundary timestamps the
    dispatcher/kernel threads stamp in, and the attempt list a failover
    replay appends to. One trace ID covers every attempt."""

    __slots__ = ("session", "done", "response", "error", "tm")

    def __init__(self, session: WhatIfSession,
                 tm: Optional[dict] = None) -> None:
        self.session = session
        self.done = threading.Event()
        self.response: Optional[dict] = None
        self.error: Optional[BaseException] = None
        self.tm = tm


class WhatIfService:
    """The serving facade: submit() blocks until the request's micro-batch
    (or fresh fallback) resolves. One daemon dispatcher thread owns batch
    formation; handler threads only enqueue and wait."""

    def __init__(self, image: ResidentImage, window_ms: float = 2.0,
                 fanout: int = 8,
                 admission: Optional[AdmissionController] = None) -> None:
        self.image = image
        self.window_s = max(0.0, float(window_ms)) / 1000.0
        self.fanout = max(1, int(fanout))
        # simonha admission control (None = the historical unbounded-admit
        # behavior; `simon serve` always wires a controller). The queue
        # list itself stays a list — the BOUND lives in admission.admit,
        # checked before any enqueue.
        self.admission = admission
        # backpressure: sustained queue growth halves the batching window
        # (drain faster, coalesce less) down to this floor; a drained queue
        # grows it back — see _take_batch
        self._window_scale = 1.0
        self._window_floor = 0.125
        self._growth_rounds = 0
        self._queue: List[_Pending] = []
        self._cv = threading.Condition()
        self._stopped = False
        self._thread = threading.Thread(
            target=self._loop, name="simon-serve-dispatch", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- client -----

    def submit(self, pods: List[dict], drains: Sequence[str] = (),
               tenant: str = "default",
               deadline_s: Optional[float] = None) -> dict:
        """Serve one what-if request: {"scheduled", "total", "unscheduled",
        "utilization", "epoch", "lanes", "path"}. May raise ha.ShedError
        BEFORE any queue/device work when admission control is wired
        (bounded queue, per-tenant-route buckets, deadline-aware shed)."""
        if not pods:
            raise ValueError("what-if request has no pods")
        # simonlint: ignore[race-unguarded-attr] -- racy fast-fail: _submit
        # re-checks under _cv before enqueueing, so a stale False here only
        # defers the rejection to that locked check
        if self._stopped:
            raise RuntimeError("serve dispatcher is stopped")
        if self.admission is not None:
            # simonlint: ignore[race-unguarded-attr] -- shed BEFORE the
            # encode: a rejected request must cost nothing downstream.
            # len() is GIL-atomic and the queue bound tolerates one
            # in-flight enqueue of slack, so the off-lock read only ever
            # shifts the shed boundary by a single request
            self.admission.admit("whatif", tenant, len(self._queue),
                                 deadline_s)
        sc = scope.active()
        if sc is None:  # the zero-cost contract: one None-check, old path
            return self._submit(pods, drains, None)
        # join the edge's trace (HTTP/gRPC handler minted one) or mint here
        # (in-process callers: loadgen, tests, embedding code)
        ctx = scope.current_ctx() or sc.mint_trace("whatif")
        tm = {"ctx": ctx, "flow": sc.mint_flow(),
              "tid": threading.get_ident(),
              "t_sub": time.perf_counter(), "attempts": []}
        token = scope._CTX.set(ctx)  # inline use_ctx: this is THE hot path
        try:
            resp = self._submit(pods, drains, tm)
        except BaseException:
            self._finish_scope(sc, tm, None, error=True)
            raise
        finally:
            scope._CTX.reset(token)
        self._finish_scope(sc, tm, resp)
        return resp

    def _submit(self, pods: List[dict], drains: Sequence[str],
                tm: Optional[dict]) -> dict:
        if len(pods) > MAX_BATCHED_PODS or guard.default_quarantined():
            return self._fresh(pods, drains, tm)
        session = self.image.session(pods, drains)
        gate = self.image.eligible(session.batch, session.pods)
        if gate is not None:
            if tm is not None:
                tm["gate"] = gate
            return self._fresh(session.pods, drains, tm)
        item = _Pending(session, tm)
        t_enq = time.monotonic()
        with self._cv:
            # re-check UNDER the lock: a stop() racing the encode above must
            # not let this item enqueue after the dispatcher exited — nothing
            # would ever set its event and the caller would hang forever
            if self._stopped:
                raise RuntimeError("serve dispatcher is stopped")
            if tm is not None:
                tm["t_enq"] = time.perf_counter()
            self._queue.append(item)
            self._cv.notify_all()
        item.done.wait()
        if self.admission is not None:
            # the observed queue+dispatch wall the deadline shed compares
            # remaining Deadlines against
            self.admission.observe_wall(time.monotonic() - t_enq)
        if item.error is not None:
            raise item.error
        obs.SERVE_REQUESTS.labels(path=item.response["path"]).inc()
        return item.response

    def _fresh(self, pods, drains: Sequence[str],
               tm: Optional[dict] = None) -> dict:
        obs.SERVE_REQUESTS.labels(path="fresh").inc()
        if tm is None:
            return self.image.fresh_probe(pods, drains)
        # the detour expands to a 'fresh_detour' span from these marks; the
        # engine's own probe span (engine.probe_pods) nests inside it via
        # the bound trace ctx
        tm["attempts"].append("fresh")
        tm["t_fresh0"] = time.perf_counter()
        resp = self.image.fresh_probe(pods, drains)
        tm["t_fresh1"] = time.perf_counter()
        return resp

    def _finish_scope(self, sc, tm: dict, resp: Optional[dict],
                      error: bool = False) -> None:
        """Feed the SLO engine and append the request's raw trace record
        (one lock + one append — the span tree expands lazily off the
        serving path). The `total_s` float on the expanded root span is the
        SAME float observed into the histogram, so trace and SLO sums
        reconcile exactly (the acceptance criterion tests/test_scope.py
        asserts)."""
        now = time.perf_counter()
        total = now - tm["t_sub"]
        route = "error" if error else (resp or {}).get("path", "error")
        phases: Dict[str, float] = {"total": total}
        t_enq, t_batch = tm.get("t_enq"), tm.get("t_batch")
        ke, fe = tm.get("kernel_end"), tm.get("fetch_end")
        if t_enq is not None and t_batch is not None:
            phases["queue"] = t_batch - t_enq
        if t_batch is not None and ke is not None:
            phases["dispatch"] = ke - t_batch
        if ke is not None and fe is not None:
            phases["fetch"] = fe - ke
        if tm.get("t_fresh0") is not None and tm.get("t_fresh1") is not None:
            # fresh path / failover replay: the probe IS the dispatch phase
            phases.setdefault("dispatch", tm["t_fresh1"] - tm["t_fresh0"])
        sc.record_request("whatif", tm, now, total, route)
        sc.slo.record("whatif", route, phases, error=error)

    def stop(self) -> None:
        """Drain: wake the dispatcher and fail still-queued requests fast
        (an in-flight batch completes normally)."""
        with self._cv:
            self._stopped = True
            for item in self._queue:
                item.error = RuntimeError("serve dispatcher is stopped")
                item.done.set()
            self._queue.clear()
            self._cv.notify_all()

    # --------------------------------------------------------- dispatcher -----

    def _loop(self) -> None:
        while True:
            batch = self._take_batch()
            if batch is None:
                return
            try:
                self._dispatch(batch)
            except BaseException as e:  # a dispatcher crash must never hang
                for item in batch:      # callers on .wait() forever
                    if not item.done.is_set():
                        item.error = e
                        item.done.set()

    def _take_batch(self) -> Optional[List[_Pending]]:
        """Block for the first request, then hold the window open (or until
        `fanout` lanes fill); None once stopped and drained."""
        with self._cv:
            while not self._queue:
                if self._stopped:
                    return None
                self._cv.wait()
            deadline = time.monotonic() + self.window_s * self._window_scale
            while (len(self._queue) < self.fanout and not self._stopped):
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._cv.wait(timeout=left)
            batch = self._queue[:self.fanout]
            del self._queue[:self.fanout]
            # backpressure: a full fanout leaving a full fanout still
            # waiting, twice running, means arrivals outpace dispatch —
            # shrink the batching window (drain faster, coalesce less);
            # recover once the queue fully drains
            if len(self._queue) >= self.fanout:
                self._growth_rounds += 1
                if (self._growth_rounds >= 2
                        and self._window_scale > self._window_floor):
                    self._window_scale = max(self._window_floor,
                                             self._window_scale * 0.5)
                    self._growth_rounds = 0
                    obs.SERVE_BACKPRESSURE.labels(action="shrink").inc()
            else:
                self._growth_rounds = 0
                if not self._queue and self._window_scale < 1.0:
                    self._window_scale = min(1.0, self._window_scale * 2.0)
                    obs.SERVE_BACKPRESSURE.labels(action="recover").inc()
            return batch

    def _dispatch(self, batch: List[_Pending]) -> None:
        # staleness is revalidated by dispatch_sessions UNDER the image lock
        # (a racing rebuild between here and there would invalidate any
        # check made outside it)
        sc = scope.active()
        tms = [item.tm for item in batch if item.tm is not None]
        sink: dict = {}
        if sc is not None and tms:
            t_batch = time.perf_counter()
            tid = threading.get_ident()
            for tm in tms:
                tm["t_batch"] = t_batch
                tm["batch_tid"] = tid
                tm["lanes"] = len(batch)
                tm["attempts"].append("batched")
        try:
            if sc is not None and tms:
                with scope.collect_phases(sink), sc.span(
                        "serve_batch", cat="serve", lanes=len(batch)):
                    responses = self.image.dispatch_sessions(
                        [item.session for item in batch])
                # stamp the kernel-thread phase marks (guard.supervised's
                # copied contextvars carried the sink reference into the
                # watchdog worker) into every scoped request — on SUCCESS
                # only, and before any done.set(): a failed attempt's
                # partial marks must not masquerade as the fresh replay's
                # dispatch phase, and stamping after wake-up would race
                # the submitter threads reading tm in _finish_scope
                for tm in tms:
                    for k in ("kernel_begin", "kernel_end", "fetch_end"):
                        if k in sink:
                            tm[k] = sink[k]
            else:
                responses = self.image.dispatch_sessions(
                    [item.session for item in batch])
        except BaseException as e:
            if guard.containment_cause(e) is None:
                raise
            # contained device failure: the batch fails over to per-request
            # fresh probes (the engine routes those to the CPU fallback)
            guard.count_failover(guard.containment_cause(e), "serve")
            cause = guard.containment_cause(e)
            for item in batch:
                try:
                    if sc is not None and item.tm is not None:
                        # the replay keeps the REQUEST's trace id: one trace
                        # shows the wedged batched attempt and its fresh
                        # replacement end to end
                        item.tm["attempts"].append("fresh_replay")
                        item.tm["t_fresh0"] = time.perf_counter()
                        with sc.use_ctx(item.tm["ctx"]), sc.span(
                                "fresh_replay", cat="serve", cause=cause):
                            item.response = self.image.fresh_probe(
                                item.session.pods, item.session.drains)
                        item.tm["t_fresh1"] = time.perf_counter()
                    else:
                        item.response = self.image.fresh_probe(
                            item.session.pods, item.session.drains)
                except BaseException as fe:
                    import logging

                    # surfaced to the caller via item.error AND logged: a
                    # request failing on the fallback path too is never silent
                    logging.getLogger("open_simulator_tpu").warning(
                        "serve: fresh-path fallback failed after a contained "
                        "device failure: %r", fe)
                    item.error = fe
                item.done.set()
            return
        for item, resp in zip(batch, responses):
            item.response = resp
            item.done.set()

    # -------------------------------------------------------------- stats -----

    def stats(self) -> Dict[str, object]:
        img = self.image
        return {
            "epoch": img.epoch,
            "generation": img.generation,
            "nodes": img.n_nodes,
            "drained": sorted(img.drained),
            "window_ms": self.window_s * 1000.0,
            # simonlint: ignore[race-unguarded-attr] -- monitoring snapshot
            "window_scale": self._window_scale,
            "sheds": self.admission.sheds if self.admission else 0,
            "fanout": self.fanout,
            "mesh": img._mesh is not None,
            # simonlint: ignore[race-unguarded-attr] -- monitoring snapshot:
            # len() is GIL-atomic and the gauge tolerates one-batch staleness
            "queued": len(self._queue),
        }
