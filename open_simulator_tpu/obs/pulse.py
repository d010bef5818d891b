"""simonpulse: roofline cost accounting + the per-dispatch performance ledger.

The fourth observability layer (metrics → xray → scope → **pulse**): the
first three answer *what happened* (counters), *why this pod* (decisions),
and *where a request's latency went* (traces); pulse answers *was the device
work as fast as it should have been* — continuously, per dispatch, against
the compiled cost model. Clipper's argument (PAPERS.md) is that latency
objectives are only enforceable when every request's cost is continuously
attributed per model/endpoint; here the unit is one kernel dispatch per
static-shape bucket per mesh.

Three parts:

1. **Performance ledger.** Every `guard.supervised` kernel dispatch appends
   one bounded-ring-buffer record: kernel, dispatch digest (the
   static-shape-bucket identity, sha256 over the same (kernel, static dims)
   payload family simonaudit certificates digest — analysis/hlo.py
   `dispatch_digest`), mesh label, pod count, supervised unit wall,
   warm/cold compile flag, and the enclosing run id whose record carries
   the encode / table_build / to_device / dispatch / fetch / commit wall
   decomposition, each phase the total of the engine's Span of that
   name (utils/trace: the same extents the profiler shows as simon.*). A
   digest change across a slowdown means "executable changed"; the same
   digest means "same executable, slower environment". Optional JSONL spill with size
   rotation keeps every record; the ring keeps the most recent
   OPEN_SIMULATOR_PULSE_CAP and counts every eviction
   (simon_pulse_records_dropped_total — never silent).

2. **Roofline cost model.** `compiled.cost_analysis()` FLOPs / bytes
   accessed are harvested (a) statically for every HOT_KERNELS entry at the
   canonical audit buckets × 1/2/8-shard meshes — the `cost` field of the
   simonaudit certificates, read back by `roofline_table()` — and (b)
   optionally at dispatch time (OPEN_SIMULATOR_PULSE_ROOFLINE=1) on each
   COLD dispatch at the real shape, giving per-(kernel, digest)
   model-optimal seconds `max(flops/peak_flops, bytes/peak_bw)` and an
   achieved-fraction gauge per warm dispatch. Peaks come from `PEAKS`, the
   device's published numbers keyed by jax's `device_kind`; a device not in
   the table has no roofline (null), never a default.

3. **Drift detection.** Rolling per-(kernel, digest) warm-wall windows with
   MAD outlier flagging: a warm dispatch slower than
   `median + k·1.4826·MAD` (k = OPEN_SIMULATOR_PULSE_MAD_K, with an
   absolute floor so deterministic µs-scale walls cannot false-positive)
   increments `simon_pulse_regressions_total{kernel,bucket}` and flags the
   record. Surfaced via `simon pulse`, `GET /v1/pulse`, and perfetto
   counter tracks merged into the scope trace dump.

Attribution contract (the part that must not drift): `record_dispatch`
(obs/instruments.py) is THE definition of one kernel dispatch; pulse hooks
it (`_DISPATCH_HOOK`) and parks each note on a contextvar pending list.
`guard.supervised` calls `ensure_window()` BEFORE copying the context —
the list object itself crosses into the worker thread by reference (the
scope phase-sink pattern), so sites that note inside the supervised body
(simulator/probe.py's multi-segment rounds) land in the caller-visible
list — and drains it into ledger records after the unit returns, cold or
warm, success or failure. Sites therefore pair `record_dispatch` with the
`guard.supervised` that dispatches it; the simonlint `unattributed-dispatch`
rule warns on hot-kernel dispatches outside this pairing.

Off by default. Pulse off costs one global read per dispatch and moves ZERO
metric samples: every simon_pulse_* family is labeled, and an untouched
labeled family renders no samples, so placements AND /metrics stay
bit-identical to pre-pulse builds (tests/test_pulse.py proves both).
Host-side only; no jax imports, ever.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import json
import os
import statistics
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from . import instruments
from .instruments import (
    PULSE_ACHIEVED,
    PULSE_DROPPED,
    PULSE_PHASE_SECONDS,
    PULSE_RECORDS,
    PULSE_REGRESSIONS,
)

DEFAULT_CAP = 4096
DEFAULT_MAD_K = 5.0
DEFAULT_MAD_WINDOW = 64
DEFAULT_MAD_MIN = 8
DEFAULT_JSONL_MAX_MB = 64.0
# Published per-chip peaks (FLOP/s, HBM bytes/s), keyed by jax's
# `device_kind`. TPU v5e: 197 TFLOP/s bf16 and 819 GB/s HBM (Google Cloud
# documentation, "TPU v5e").
PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),
}

RUN_PHASES = ("encode", "table_build", "to_device", "dispatch", "fetch",
              "commit")


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        return default


# ------------------------------------------------------------ roofline math ---


def device_kind() -> Optional[str]:
    """jax's `device_kind` of the first device, or None while this process
    has not imported jax (pulse itself never imports it)."""
    jax = sys.modules.get("jax")
    return None if jax is None else jax.devices()[0].device_kind


def peak_rates(kind: Optional[str]) -> Optional[Tuple[float, float]]:
    """(peak FLOP/s, peak bytes/s) of `kind`, or None when it has no
    published peaks in `PEAKS`."""
    return PEAKS.get(kind) if kind else None


def normalize_cost(raw) -> Optional[Dict[str, float]]:
    """cost_analysis() output → {"flops", "bytes_accessed"}, or None.

    Bytes may be keyed "bytes accessed" or split per operand ("bytes
    accessed operand 0 {}" etc. — the total key wins when present)."""
    if not isinstance(raw, dict):
        return None
    flops = float(raw.get("flops", 0.0) or 0.0)
    by = raw.get("bytes accessed", raw.get("bytes_accessed"))
    if by is None:
        by = sum(float(v) for k, v in raw.items()
                 if isinstance(k, str) and k.startswith("bytes accessed"))
    by = float(by or 0.0)
    if flops <= 0.0 and by <= 0.0:
        return None
    return {"flops": flops, "bytes_accessed": by}


def peaks_doc(kind: Optional[str]) -> dict:
    """The summary's peaks block: the device and its published GFLOP/s and
    GB/s, null where the table has none."""
    peaks = peak_rates(kind)
    return {"device_kind": kind,
            "gflops": None if peaks is None else peaks[0] / 1e9,
            "gbs": None if peaks is None else peaks[1] / 1e9}


def model_optimal_s(cost: Dict[str, float],
                    peaks: Optional[Tuple[float, float]]) -> Optional[float]:
    """Roofline model-optimal seconds: the kernel cannot run faster than its
    FLOPs at peak compute nor its bytes at peak bandwidth — whichever wall
    it hits first is the model optimum. None without published peaks."""
    if peaks is None:
        return None
    pf, pb = peaks
    return max(cost.get("flops", 0.0) / pf, cost.get("bytes_accessed", 0.0) / pb)


def roofline_table(golden_dir: Optional[str] = None,
                   kind: Optional[str] = None) -> List[dict]:
    """The static roofline: one row per (kernel, bucket, mesh) audit
    certificate carrying a `cost` field — {kernel, bucket, mesh, flops,
    bytes_accessed, model_optimal_s}, the last against `kind`'s published
    peaks (None for a device without them). Reads the checked-in simonaudit
    goldens; no jax, no compilation."""
    if golden_dir is None:
        from ..analysis.hlo import _default_golden_dir

        golden_dir = _default_golden_dir()
    peaks = peak_rates(kind)
    rows: List[dict] = []
    if not os.path.isdir(golden_dir):
        return rows
    for fname in sorted(os.listdir(golden_dir)):
        if not fname.endswith(".json"):
            continue
        try:
            with open(os.path.join(golden_dir, fname), encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        for key in sorted(doc.get("certs", {})):
            cert = doc["certs"][key]
            cost = normalize_cost(cert.get("cost"))
            if cost is None:
                continue
            rows.append({
                "kernel": cert.get("kernel", fname[:-5]),
                "bucket": cert.get("bucket", ""),
                "mesh": cert.get("mesh", ""),
                "flops": cost["flops"],
                "bytes_accessed": cost["bytes_accessed"],
                "model_optimal_s": model_optimal_s(cost, peaks),
            })
    return rows


# ------------------------------------------------- attribution contextvars ----

# The pending list: (kernel, dims, cold) notes parked between record_dispatch
# and the guard.supervised unit that dispatches them. The list OBJECT is
# shared by reference into supervised's copied context (ensure_window runs
# before copy_context), so worker-side notes land in the caller's list.
_PENDING: contextvars.ContextVar[Optional[list]] = contextvars.ContextVar(
    "simon_pulse_pending", default=None)

# The enclosing scheduling run (dict with id / pods / phases), if any.
_RUN: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "simon_pulse_run", default=None)


def note_dispatch(kernel: str, dims: Dict[str, Any], cold: bool) -> None:
    """The instruments._DISPATCH_HOOK target: park one dispatch note for the
    supervised unit that will execute it. No-op overhead path lives in
    record_dispatch itself (hook is None when pulse is off)."""
    pending = _PENDING.get()
    if pending is None:
        pending = []
        _PENDING.set(pending)
    pending.append((kernel, dims, cold))


def ensure_window() -> Optional[list]:
    """Make the pending list exist in THIS context before guard.supervised
    copies it into a worker thread, so worker-side record_dispatch calls
    (probe rounds) append to the caller-visible list by reference."""
    pending = _PENDING.get()
    if pending is None:
        pending = []
        _PENDING.set(pending)
    return pending


# ---------------------------------------------------------------- the ledger --


class Pulse:
    """Process-wide performance ledger + drift detector. Build via
    `enable()`; `active()` is the zero-cost gate every site starts from."""

    def __init__(self, capacity: int = 0, jsonl: Optional[str] = None,
                 jsonl_max_mb: float = 0.0, mad_k: float = 0.0,
                 mad_window: int = DEFAULT_MAD_WINDOW,
                 mad_min: int = DEFAULT_MAD_MIN,
                 roofline_dispatch: Optional[bool] = None) -> None:
        self.capacity = capacity or _env_int("OPEN_SIMULATOR_PULSE_CAP",
                                             DEFAULT_CAP)
        self.mad_k = mad_k or _env_float("OPEN_SIMULATOR_PULSE_MAD_K",
                                         DEFAULT_MAD_K)
        self.mad_window = mad_window
        self.mad_min = mad_min
        if roofline_dispatch is None:
            roofline_dispatch = os.environ.get(
                "OPEN_SIMULATOR_PULSE_ROOFLINE", "") not in ("", "0", "false")
        self.roofline_dispatch = roofline_dispatch
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self.n_total = 0
        self.n_dropped = 0
        self._seq = 0
        self._run_seq = 0
        # per-(kernel, digest): rolling warm walls, regression counts,
        # harvested dispatch-shape costs, digest memo
        self._windows: Dict[Tuple[str, str], deque] = {}
        self._reg_counts: Dict[Tuple[str, str], int] = {}
        self._costs: Dict[Tuple[str, str], Dict[str, float]] = {}
        self._digests: Dict[tuple, str] = {}
        self._phase_totals: Dict[str, float] = {}
        # JSONL spill (complete record stream; the ring is the bounded view)
        self._jsonl_path = jsonl if jsonl is not None else os.environ.get(
            "OPEN_SIMULATOR_PULSE_JSONL", "") or None
        self._jsonl_max = (jsonl_max_mb or _env_float(
            "OPEN_SIMULATOR_PULSE_JSONL_MAX_MB", DEFAULT_JSONL_MAX_MB)) * 1e6
        self._jsonl_f = None
        self._jsonl_warned = False
        self._kind: Optional[str] = None  # device_kind, resolved at first use

    # ----------------------------------------------------------- appending --

    def _append(self, rec: dict) -> None:
        kind = rec["kind"]
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            if len(self._ring) == self._ring.maxlen:
                self.n_dropped += 1
                PULSE_DROPPED.labels(kind=self._ring[0]["kind"]).inc()
            self._ring.append(rec)
            self.n_total += 1
        PULSE_RECORDS.labels(kind=kind).inc()
        self._spill(rec)

    def _spill(self, rec: dict) -> None:
        try:
            with self._lock:
                path = self._jsonl_path
                if not path:
                    return
                line = json.dumps(rec, sort_keys=True) + "\n"
                f = self._jsonl_f
                if f is None:
                    f = self._jsonl_f = open(path, "a", encoding="utf-8")
                f.write(line)
                if f.tell() >= self._jsonl_max:
                    # one rotation level: the previous generation is enough
                    # to cover "the slowdown started before the current file"
                    f.close()
                    self._jsonl_f = None
                    os.replace(path, path + ".1")
        except OSError:
            # a full disk must never fail a scheduling call; stop spilling
            # loudly once (the ring + counters keep working). The `with
            # self._lock` above released on the way out, so re-acquire.
            with self._lock:
                self._jsonl_path = None
                self._jsonl_f = None
            if not self._jsonl_warned:
                self._jsonl_warned = True
                import logging

                logging.getLogger("open_simulator_tpu").exception(
                    "pulse: JSONL spill failed; disabling spill for this "
                    "process (in-memory ledger unaffected)")

    # --------------------------------------------------------------- digest --

    def _digest_for(self, kernel: str, dims: Dict[str, Any]) -> str:
        key = (kernel,) + tuple(sorted((k, repr(v)) for k, v in dims.items()))
        d = self._digests.get(key)
        if d is None:
            from ..analysis.hlo import dispatch_digest

            d = self._digests[key] = dispatch_digest(kernel, dims)
        return d

    # ------------------------------------------------------- unit lifecycle --

    def commit_unit(self, *, site: str, pods: int, wall_s: float,
                    ok: bool = True, fn=None) -> None:
        """Drain this context's pending dispatch notes into ledger records,
        all sharing the supervised unit's wall. Called by guard.supervised
        after the unit returns (cold or warm, success or failure); a unit
        with no notes (fetch units, un-instrumented callables) records
        nothing."""
        pending = _PENDING.get()
        if not pending:
            return
        entries = list(pending)
        del pending[:]
        n = len(entries)
        run = _RUN.get()
        now = time.time()
        # multi-dispatch units (probe rounds) share one wall; the per-entry
        # share keeps warm baselines comparable across unit groupings
        share = wall_s / n
        for kernel, dims, cold in entries:
            digest = self._digest_for(kernel, dims)
            rec: dict = {
                "kind": "dispatch",
                "t": round(now, 6),
                "kernel": kernel,
                "digest": digest,
                "mesh": str(dims.get("mesh", "")),
                "site": site,
                "pods": int(dims.get("P", pods) or pods),
                "n_in_unit": n,
                "unit_wall_s": round(wall_s, 9),
                "wall_s": round(share, 9),
                "cold": bool(cold),
                "ok": bool(ok),
                "dims": {k: (v if isinstance(v, (int, float, bool, str))
                             else repr(v)) for k, v in sorted(dims.items())},
            }
            if run is not None:
                rec["run"] = run["id"]
            key = (kernel, digest)
            if cold:
                if self.roofline_dispatch and n == 1 and fn is not None:
                    cost = self._harvest_cost(fn)
                    if cost is not None:
                        with self._lock:
                            self._costs[key] = cost
            elif ok:
                self._warm_stats(key, share, rec)
            self._append(rec)

    def _warm_stats(self, key: Tuple[str, str], wall_s: float,
                    rec: dict) -> None:
        """MAD drift check + achieved-roofline fraction for one warm wall.
        The new wall is checked against the PRIOR window, then appended —
        an injected slow dispatch cannot raise its own baseline."""
        kernel, digest = key
        with self._lock:
            win = self._windows.get(key)
            if win is None:
                win = self._windows[key] = deque(maxlen=self.mad_window)
            samples = list(win)
            win.append(wall_s)
            cost = self._costs.get(key)
        if len(samples) >= self.mad_min:
            med = statistics.median(samples)
            mad = statistics.median(abs(x - med) for x in samples)
            thresh = med + self.mad_k * 1.4826 * mad
            # absolute + relative floors: deterministic µs-scale walls have
            # MAD ~ 0, and scheduler jitter alone reaches ~1.5x median
            thresh = max(thresh, med * 1.5, med + 1e-4)
            if wall_s > thresh:
                rec["regression"] = True
                rec["baseline_med_s"] = round(med, 9)
                PULSE_REGRESSIONS.labels(kernel=kernel, bucket=digest).inc()
                with self._lock:
                    self._reg_counts[key] = self._reg_counts.get(key, 0) + 1
        if cost is not None and wall_s > 0.0:
            opt = model_optimal_s(cost, peak_rates(self.device_kind()))
            if opt is not None and opt > 0.0:
                frac = min(1.0, opt / wall_s)
                rec["achieved_frac"] = round(frac, 6)
                rec["model_optimal_s"] = round(opt, 9)
                PULSE_ACHIEVED.labels(kernel=kernel, bucket=digest).set(
                    round(frac, 6))

    def device_kind(self) -> Optional[str]:
        """The device this ledger's dispatches ran on (resolved once a
        dispatch has imported jax)."""
        if self._kind is None:
            self._kind = device_kind()
        return self._kind

    def _harvest_cost(self, fn) -> Optional[Dict[str, float]]:
        """Dispatch-shape cost_analysis harvest, cold dispatches only
        (OPEN_SIMULATOR_PULSE_ROOFLINE=1): when the supervised callable is a
        partial over a lowerable jit (the single-device kernels), lower at
        the REAL arguments and read the compiled cost model. Re-lowering
        roughly doubles the cold dispatch's cost, never the warm path;
        wrapper methods (sharded kernel namespaces) and multi-dispatch units
        are skipped — their static costs come from the audit goldens."""
        if not isinstance(fn, functools.partial):
            return None
        lower = getattr(fn.func, "lower", None)
        if lower is None:
            return None
        try:
            compiled = lower(*fn.args, **fn.keywords).compile()
            return normalize_cost(compiled.cost_analysis())
        # simonlint: ignore[swallowed-exception] -- best-effort cost probe on
        # a DIAGNOSTICS path; any lowering quirk (non-jit callable, abstract
        # mismatch) must never fail the dispatch that already succeeded
        except Exception:
            return None

    # -------------------------------------------------------- run lifecycle --

    def run_begin(self, pods: int, kind: str = "schedule") -> tuple:
        with self._lock:
            self._run_seq += 1
            rid = self._run_seq
        run = {"id": rid, "kind": kind, "pods": int(pods), "phases": {},
               "t0": time.perf_counter()}
        token = _RUN.set(run)
        return token, run

    def run_end(self, token, run: dict) -> None:
        _RUN.reset(token)
        wall = time.perf_counter() - run.pop("t0")
        rec = {
            "kind": "run",
            "t": round(time.time(), 6),
            "run": run["id"],
            "run_kind": run["kind"],
            "pods": run["pods"],
            "wall_s": round(wall, 9),
            "phases": {k: round(v, 9) for k, v in sorted(run["phases"].items())},
        }
        self._append(rec)
        self._emit_scope_counters()

    def phase(self, name: str, seconds: float) -> None:
        PULSE_PHASE_SECONDS.labels(phase=name).inc(seconds)
        with self._lock:
            self._phase_totals[name] = (
                self._phase_totals.get(name, 0.0) + seconds)
        run = _RUN.get()
        if run is not None:
            run["phases"][name] = run["phases"].get(name, 0.0) + seconds

    def _emit_scope_counters(self) -> None:
        """Merge pulse into the scope trace as perfetto counter tracks:
        cumulative per-phase wall + the regression count, sampled once per
        run end (cheap, and exactly when the values move)."""
        from . import scope

        sc = scope.active()
        if sc is None:
            return
        now = time.perf_counter()
        with self._lock:
            phases = dict(self._phase_totals)
            regressions = sum(self._reg_counts.values())
            records = self.n_total
        if phases:
            sc.emit_counter("pulse_phase_seconds", now, phases)
        sc.emit_counter("pulse_ledger", now, {
            "records": records, "regressions": regressions,
        })

    # --------------------------------------------------------------- views ---

    def records(self) -> List[dict]:
        with self._lock:
            return [dict(r) for r in self._ring]

    def summary(self) -> dict:
        """The `simon pulse` / GET /v1/pulse document: ledger totals, one
        row per (kernel, digest) with warm-wall baseline stats, and the run
        phase decomposition."""
        with self._lock:
            recs = [dict(r) for r in self._ring]
            windows = {k: list(v) for k, v in self._windows.items()}
            reg_counts = dict(self._reg_counts)
            costs = {k: dict(v) for k, v in self._costs.items()}
            phase_totals = dict(self._phase_totals)
            n_total, n_dropped = self.n_total, self.n_dropped
        by_key: Dict[Tuple[str, str], dict] = {}
        runs = {"n": 0, "pods": 0}
        for r in recs:
            if r["kind"] == "run":
                runs["n"] += 1
                runs["pods"] += r["pods"]
                continue
            key = (r["kernel"], r["digest"])
            row = by_key.get(key)
            if row is None:
                row = by_key[key] = {
                    "kernel": key[0], "digest": key[1], "mesh": r["mesh"],
                    "n": 0, "cold": 0, "warm": 0, "pods": 0,
                    "wall_s": 0.0, "last_wall_s": 0.0,
                }
            row["n"] += 1
            row["pods"] += r["pods"]
            row["wall_s"] += r["wall_s"]
            row["last_wall_s"] = r["wall_s"]
            row["cold" if r["cold"] else "warm"] += 1
            if "achieved_frac" in r:
                row["achieved_frac"] = r["achieved_frac"]
        for key, row in by_key.items():
            win = windows.get(key) or []
            if win:
                med = statistics.median(win)
                row["warm_med_s"] = round(med, 9)
                row["warm_mad_s"] = round(
                    statistics.median(abs(x - med) for x in win), 9)
            row["regressions"] = reg_counts.get(key, 0)
            cost = costs.get(key)
            if cost is not None:
                row["flops"] = cost["flops"]
                row["bytes_accessed"] = cost["bytes_accessed"]
                opt = model_optimal_s(cost, peak_rates(self.device_kind()))
                row["model_optimal_s"] = None if opt is None else round(opt, 9)
            row["wall_s"] = round(row["wall_s"], 9)
        return {
            "records_total": n_total,
            "records_dropped": n_dropped,
            "ring_len": len(recs),
            "capacity": self.capacity,
            "regressions_total": sum(reg_counts.values()),
            "peaks": peaks_doc(self.device_kind()),
            "phase_seconds": {k: round(v, 9)
                              for k, v in sorted(phase_totals.items())},
            "runs": runs,
            "kernels": [by_key[k] for k in sorted(by_key)],
        }

    def close(self) -> None:
        with self._lock:
            if self._jsonl_f is not None:
                try:
                    self._jsonl_f.close()
                except OSError:
                    pass
                self._jsonl_f = None


# ----------------------------------------------------------- module surface ---

_PULSE: Optional[Pulse] = None


def active() -> Optional[Pulse]:
    """The enabled Pulse, or None. THE zero-cost check: every
    instrumentation site starts here."""
    return _PULSE


def enable(**kw) -> Pulse:
    """Enable simonpulse process-wide (idempotent) and install the
    record_dispatch attribution hook."""
    global _PULSE
    if _PULSE is None:
        _PULSE = Pulse(**kw)
        instruments._DISPATCH_HOOK = note_dispatch
    return _PULSE


def disable() -> None:
    """Disable and tear down (hook removed; spill file closed; ring
    dropped). Any notes still pending in live contexts are discarded — with
    the hook gone they can never be committed."""
    global _PULSE
    p = _PULSE
    _PULSE = None
    instruments._DISPATCH_HOOK = None
    if p is not None:
        p.close()


def env_enabled(default: bool = False) -> bool:
    """The OPEN_SIMULATOR_PULSE switch ('' keeps the caller's default)."""
    raw = os.environ.get("OPEN_SIMULATOR_PULSE", "")
    if raw == "":
        return default
    return raw not in ("0", "false", "no", "off")


def maybe_enable_from_env() -> Optional[Pulse]:
    """Engine/serve bootstrap: enable iff OPEN_SIMULATOR_PULSE says so."""
    if env_enabled(default=False):
        return enable()
    return active()


@contextlib.contextmanager
def run_window(pods: int, kind: str = "schedule"):
    """One scheduling run: dispatch records inside reference the run id;
    the run record carries the phase decomposition. No-op when pulse is
    off (and when it flips mid-run, the begin-time decision wins)."""
    p = _PULSE
    if p is None:
        yield None
        return
    token, run = p.run_begin(pods, kind)
    try:
        yield run
    finally:
        p.run_end(token, run)


def phase(name: str, seconds: float) -> None:
    """Attribute `seconds` of wall to a run phase (module-level convenience;
    no-op when pulse is off)."""
    p = _PULSE
    if p is not None:
        p.phase(name, seconds)


def reset_for_tests() -> None:
    """Tear down pulse AND forget context-local state. Tests only."""
    disable()
    try:
        _PENDING.set(None)
        _RUN.set(None)
    except LookupError:  # pragma: no cover
        pass


# ------------------------------------------------------------- CLI rendering --


def summarize_records(recs: List[dict]) -> dict:
    """Offline aggregation of raw ledger records (a JSONL spill read back,
    or Pulse.records()) into the same document shape summary() produces —
    minus live-only fields (ring capacity, regression counters, harvested
    costs), which only exist on a running Pulse."""
    by_key: Dict[Tuple[str, str], dict] = {}
    runs = {"n": 0, "pods": 0}
    phase_totals: Dict[str, float] = {}
    warm_walls: Dict[Tuple[str, str], List[float]] = {}
    n_reg = 0
    for r in recs:
        if r.get("kind") == "run":
            runs["n"] += 1
            runs["pods"] += r.get("pods", 0)
            for k, v in (r.get("phases") or {}).items():
                phase_totals[k] = phase_totals.get(k, 0.0) + v
            continue
        key = (r.get("kernel", "?"), r.get("digest", "?"))
        row = by_key.get(key)
        if row is None:
            row = by_key[key] = {
                "kernel": key[0], "digest": key[1],
                "mesh": r.get("mesh"), "n": 0, "cold": 0, "warm": 0,
                "pods": 0, "wall_s": 0.0, "regressions": 0,
            }
        row["n"] += 1
        row["pods"] += r.get("pods", 0)
        row["wall_s"] += r.get("wall_s", 0.0)
        row["cold" if r.get("cold") else "warm"] += 1
        if r.get("regression"):
            row["regressions"] += 1
            n_reg += 1
        if "achieved_frac" in r:
            row["achieved_frac"] = r["achieved_frac"]
        if not r.get("cold") and r.get("ok", True):
            warm_walls.setdefault(key, []).append(r.get("wall_s", 0.0))
    for key, row in by_key.items():
        win = warm_walls.get(key) or []
        if win:
            med = statistics.median(win)
            row["warm_med_s"] = round(med, 9)
            row["warm_mad_s"] = round(
                statistics.median(abs(x - med) for x in win), 9)
        row["wall_s"] = round(row["wall_s"], 9)
    return {
        "records_total": len(recs),
        "records_dropped": 0,
        "ring_len": len(recs),
        "capacity": 0,
        "regressions_total": n_reg,
        "peaks": peaks_doc(None),  # a JSONL spill does not name its device
        "phase_seconds": {k: round(v, 9)
                          for k, v in sorted(phase_totals.items())},
        "runs": runs,
        "kernels": [by_key[k] for k in sorted(by_key)],
    }


def format_summary(doc: dict) -> str:
    """Human table for `simon pulse` from a summary() document."""
    out: List[str] = []
    out.append(
        f"pulse ledger: {doc.get('records_total', 0)} records "
        f"({doc.get('ring_len', 0)} in ring / cap {doc.get('capacity', 0)}, "
        f"{doc.get('records_dropped', 0)} evicted), "
        f"{doc.get('regressions_total', 0)} regressions flagged")
    runs = doc.get("runs") or {}
    if runs.get("n"):
        out.append(f"runs: {runs['n']} ({runs['pods']} pods)")
    phases = doc.get("phase_seconds") or {}
    if phases:
        dec = "  ".join(f"{k}={v * 1e3:.1f}ms" for k, v in phases.items())
        out.append(f"phase wall: {dec}")
    rows = doc.get("kernels") or []
    if rows:
        out.append("")
        hdr = (f"{'kernel':<28} {'digest':<16} {'n':>5} {'cold':>4} "
               f"{'warm med':>10} {'mad':>9} {'roofline':>8} {'regr':>4}")
        out.append(hdr)
        out.append("-" * len(hdr))
        for r in rows:
            med = r.get("warm_med_s")
            mad = r.get("warm_mad_s")
            frac = r.get("achieved_frac")
            out.append(
                f"{r['kernel']:<28} {r['digest']:<16} {r['n']:>5} "
                f"{r['cold']:>4} "
                f"{(f'{med * 1e3:.2f}ms' if med is not None else '-'):>10} "
                f"{(f'{mad * 1e6:.0f}us' if mad is not None else '-'):>9} "
                f"{(f'{frac * 100:.1f}%' if frac is not None else '-'):>8} "
                f"{r.get('regressions', 0):>4}")
    return "\n".join(out)


def format_roofline(rows: List[dict], kind: Optional[str]) -> str:
    """Human table for `simon pulse --roofline` from roofline_table()."""
    peaks = peak_rates(kind)
    if peaks is None:
        out = [f"no published peaks for device kind {kind!r}: "
               f"model-optimal times not available"]
    else:
        out = [f"roofline @ {peaks[0] / 1e9:g} GFLOP/s, {peaks[1] / 1e9:g} "
               f"GB/s ({kind})"]
    hdr = (f"{'kernel':<28} {'bucket':<8} {'mesh':<10} {'GFLOP':>10} "
           f"{'MB':>10} {'optimal':>10} {'bound':>5}")
    out.append(hdr)
    out.append("-" * len(hdr))
    for r in rows:
        opt = r["model_optimal_s"]
        if opt is None:
            opt_s, bound = "-", "-"
        else:
            flop_s = r["flops"] / peaks[0]
            opt_s = f"{opt * 1e6:.1f}us"
            bound = "flop" if flop_s >= opt - 1e-18 and flop_s > 0 else "mem"
        out.append(
            f"{r['kernel']:<28} {r['bucket']:<8} {r['mesh']:<10} "
            f"{r['flops'] / 1e9:>10.4f} {r['bytes_accessed'] / 1e6:>10.3f} "
            f"{opt_s:>11} {bound:>5}")
    return "\n".join(out)
