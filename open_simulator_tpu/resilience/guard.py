"""simonguard: mid-run device-failure containment.

PR 4 (simonfault) made host state crash-consistent: any failure rolls a
scheduling call back to its pre-call state. This module is the layer ABOVE
that transactional core — it decides what happens NEXT, so a wedged
accelerator or a device OOM degrades the run instead of killing it:

- **Watchdog-supervised dispatch** (`supervised`): every device computation
  (kernel dispatch, result fetch, probe fan-out round) runs in a worker
  thread under a deadline scaled by batch size and tightened by the
  contextvar `Deadline` (resilience/policy.py). On expiry the backend is
  classified *wedged*, quarantined for the process, and
  `BackendWedged` is raised — which the engine's failover loop catches. The
  blocked worker thread is a daemon and is abandoned (a dispatch stuck in a
  driver ioctl cannot be interrupted from Python); the quarantine is exactly
  what prevents a second thread from following it.
- **OOM classification** (`oom_site` / `containment_cause`): jaxlib
  RESOURCE_EXHAUSTED errors (and the injected `oom_to_device` /
  `oom_dispatch` faults that stand in for them in tests) are recognized so
  the engine can retry by bisecting the pod batch instead of dying.
- **Quarantine registry**: process-global backend → cause map. Once a
  backend is quarantined every later Simulator in the process starts
  directly on the CPU fallback (`fallback_scope`), so one wedge costs one
  watchdog expiry, not one per run.
- **Crash-consistent capacity-search journal** (`SearchJournal`): fsync'd
  JSONL of probe verdicts with an options-digest header, so a SIGKILLed
  capacity search resumed via `simon apply --resume-journal` skips every
  completed probe — and a journal written by a DIFFERENT search is rejected
  (`JournalMismatch`) instead of silently corrupting the answer.

Every decision is observable: `simon_guard_watchdog_expiries_total{site}`,
`simon_guard_oom_bisections_total{site}`, `simon_guard_failovers_total{cause}`,
`simon_guard_quarantined{backend}`, `simon_journal_*` (obs/instruments.py),
the `events()` trace (replay-equal across identical seeded runs — the
fault-smoke CI criterion), `state()` on the server's /debug/vars, and the
result's `backend_path` (e.g. ``["tpu", "cpu"]``). Nothing fails over
silently.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple, TypeVar

from ..obs import instruments as obs
from ..obs import pulse
from . import faults
from .policy import check_deadline, deadline_remaining

T = TypeVar("T")

# Failover cause labels (simon_guard_failovers_total{cause}).
CAUSE_WEDGE = "watchdog_wedge"
CAUSE_OOM_EXHAUSTED = "oom_exhausted"
CAUSE_OOM = "oom"


class GuardError(RuntimeError):
    """Base of the containable device-failure classifications."""


class BackendWedged(GuardError):
    """A supervised device computation blew its watchdog deadline: the
    backend is presumed hung (driver deadlock, lost device) and has been
    quarantined for the process."""

    def __init__(self, site: str, backend: str, injected: bool = False) -> None:
        super().__init__(
            f"backend {backend!r} wedged at {site} "
            f"({'injected' if injected else 'watchdog deadline expired'}); "
            f"quarantined for this process")
        self.site = site
        self.backend = backend
        self.injected = injected


class OOMBisectionExhausted(GuardError):
    """Device OOM persisted all the way down to the bisection floor: the
    batch cannot be made to fit by splitting. The engine fails the run over
    to the CPU backend; if THAT also exhausts, the error propagates."""

    def __init__(self, site: str, batch: int, floor: int) -> None:
        super().__init__(
            f"device OOM at {site} persisted at batch size {batch} "
            f"(bisection floor {floor}); batch cannot be split further")
        self.site = site
        self.batch = batch
        self.floor = floor


class JournalMismatch(ValueError):
    """A --resume-journal file was written by a different search (options
    digest mismatch) or is not a capacity-search journal at all."""


# ------------------------------------------------------------------ knobs -----


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:  # tuning knob: fall back, don't crash the run
        return default


def watchdog_enabled() -> bool:
    return os.environ.get("OPEN_SIMULATOR_WATCHDOG", "").lower() not in (
        "0", "off", "false", "no")


def watchdog_budget(pods: int) -> float:
    """Seconds a supervised computation may take before it is declared
    wedged: a base generous enough for a cold XLA compile plus a per-pod
    term so giant batches are never misclassified. Env-tunable."""
    base = _env_float("OPEN_SIMULATOR_WATCHDOG_BASE_S", 120.0)
    per_pod = _env_float("OPEN_SIMULATOR_WATCHDOG_PER_POD_S", 0.005)
    return max(1.0, base + per_pod * max(0, int(pods)))


def oom_bisect_floor() -> int:
    """Smallest pod-batch size the OOM bisection will retry at (>= 1)."""
    try:
        return max(1, int(os.environ.get("OPEN_SIMULATOR_OOM_BISECT_FLOOR",
                                         "1")))
    except ValueError:
        return 1


# ----------------------------------------------------------- event trace ------

# Guard decisions in firing order: ("wedge", site, backend),
# ("oom_bisect", site, batch), ("failover", cause, where). Bounded; the
# fault-smoke CI resets it per run and asserts two identical seeded runs
# produce identical traces (the replay-equality criterion for the new sites).
_EVENTS: List[Tuple] = []
_EVENTS_MAX = 1024
_STATE_LOCK = threading.Lock()


def record_event(*event) -> None:
    with _STATE_LOCK:
        if len(_EVENTS) < _EVENTS_MAX:
            _EVENTS.append(tuple(event))


def events() -> List[Tuple]:
    with _STATE_LOCK:
        return list(_EVENTS)


# ------------------------------------------------------------- quarantine -----

_QUARANTINED: Dict[str, str] = {}  # backend platform -> cause


def quarantine(backend: str, cause: str) -> None:
    """Quarantine `backend` for the rest of the process."""
    with _STATE_LOCK:
        _QUARANTINED.setdefault(backend, cause)
    obs.GUARD_QUARANTINED.labels(backend=backend).set(1)


def quarantined() -> Dict[str, str]:
    with _STATE_LOCK:
        return dict(_QUARANTINED)


def current_backend() -> str:
    """The platform this thread's JAX work lands on: the device a caller's
    `jax.default_device` scope names, else the default backend."""
    import jax

    dev = jax.config.jax_default_device
    if dev is not None:
        return dev if isinstance(dev, str) else dev.platform
    return jax.default_backend()


def default_quarantined() -> bool:
    """True when the process's default backend is quarantined (device work
    must route to the CPU fallback). Never touches jax when nothing is
    quarantined — the common case stays import-free."""
    with _STATE_LOCK:
        if not _QUARANTINED:
            return False
        q = dict(_QUARANTINED)
    return current_backend() in q


def _cpu_device():
    import jax

    return jax.local_devices(backend="cpu")[0]


@contextlib.contextmanager
def fallback_scope():
    """Context manager placing all JAX work inside it on the CPU fallback
    device (the degraded-mode execution target after a wedge/OOM).
    `supervised` carries the scope into its worker thread."""
    import jax

    with jax.default_device(_cpu_device()):
        yield


def _caller_device():
    """The device a `jax.default_device` scope on THIS thread names, or None.
    JAX config scopes are thread-local and copy_context() does not carry
    them, so `supervised` reads this on the caller's thread and re-enters it
    in its worker: a run under `jax.default_device(cpu)` (the failover's
    fallback_scope, or a caller's own) must not dispatch on the default
    backend just because the dispatch happens in another thread."""
    import sys

    jax = sys.modules.get("jax")
    return None if jax is None else jax.config.jax_default_device


def _call_in_scope(fn: Callable[[], T], device) -> T:
    """Run `fn` in the CURRENT thread under the caller's device scope."""
    if device is None:
        return fn()
    import jax

    with jax.default_device(device):
        return fn()


def reset_for_tests() -> None:
    """Clear process-global guard state (quarantine + events). Tests and the
    fault-smoke CI only — production never un-quarantines."""
    with _STATE_LOCK:
        for b in _QUARANTINED:
            obs.GUARD_QUARANTINED.labels(backend=b).set(0)
        _QUARANTINED.clear()
        del _EVENTS[:]


def state() -> dict:
    """The /debug/vars view of the guard: quarantine map, watchdog/bisection
    configuration, and the recent containment events."""
    return {
        "quarantined": quarantined(),
        "watchdog": {
            "enabled": watchdog_enabled(),
            "base_s": _env_float("OPEN_SIMULATOR_WATCHDOG_BASE_S", 120.0),
            "per_pod_s": _env_float("OPEN_SIMULATOR_WATCHDOG_PER_POD_S", 0.005),
        },
        "oom_bisect_floor": oom_bisect_floor(),
        "events": [list(e) for e in events()[-64:]],
    }


# ------------------------------------------------------ supervised dispatch ---


def supervised(fn: Callable[[], T], *, site: str, pods: int = 0) -> T:
    """Run one device computation under the dispatch watchdog.

    `fn` executes in a daemon worker thread (contextvars copied, so the
    Deadline and any test-installed state propagate); the caller waits at
    most `watchdog_budget(pods)` seconds, further tightened by the contextvar
    Deadline. Expiry quarantines the current backend and raises
    `BackendWedged`; if the caller's own Deadline ran out during the wait,
    `DeadlineExceeded` is raised instead (a spent budget is not a wedge).
    Exceptions from `fn` re-raise transparently. The `watchdog_wedge` fault
    site fires here, so a wedge is deterministically injectable without
    actually blocking a thread."""
    try:
        faults.maybe_fail("watchdog_wedge")
    except faults.FaultInjected as e:
        raise _declare_wedged(site, injected=True) from e
    # simonpulse ledger: the window must exist in THIS context before
    # copy_context below — the pending-list object crosses into the worker
    # by reference, so dispatch notes made inside fn (probe rounds) land in
    # the list this caller drains at commit_unit. One global read when off.
    pl = pulse.active()
    if pl is not None:
        pulse.ensure_window()
        t_pulse = time.perf_counter()
    if not watchdog_enabled():
        if pl is None:
            return fn()
        try:
            result = fn()
        except BaseException:
            pl.commit_unit(site=site, pods=pods,
                           wall_s=time.perf_counter() - t_pulse, ok=False,
                           fn=fn)
            raise
        pl.commit_unit(site=site, pods=pods,
                       wall_s=time.perf_counter() - t_pulse, fn=fn)
        return result
    budget = watchdog_budget(pods)
    if deadline_remaining() is not None:
        check_deadline(site)
        budget = min(budget, deadline_remaining())
    box: dict = {}
    done = threading.Event()
    ctx = contextvars.copy_context()
    device = _caller_device()

    def worker() -> None:
        try:
            # the copied context does not carry the thread-local jax device
            # scope: re-enter it so the dispatch lands where its caller chose
            box["result"] = ctx.run(_call_in_scope, fn, device)
        # simonlint: ignore[swallowed-exception] -- not swallowed: the boxed
        # error re-raises in the supervising caller the moment done is set
        except BaseException as we:  # noqa: BLE001
            box["error"] = we
        finally:
            done.set()

    t = threading.Thread(target=worker, name=f"simon-guard-{site}",
                         daemon=True)
    t.start()
    if not done.wait(budget):
        check_deadline(site)  # the caller's budget expired, not the device
        if pl is not None:
            pl.commit_unit(site=site, pods=pods,
                           wall_s=time.perf_counter() - t_pulse, ok=False,
                           fn=fn)
        raise _declare_wedged(site, injected=False)
    if pl is not None:
        pl.commit_unit(site=site, pods=pods,
                       wall_s=time.perf_counter() - t_pulse,
                       ok="error" not in box, fn=fn)
    if "error" in box:
        raise box["error"]
    return box["result"]


def _declare_wedged(site: str, injected: bool) -> BackendWedged:
    backend = current_backend()
    quarantine(backend, f"{CAUSE_WEDGE}@{site}")
    obs.GUARD_WATCHDOG_EXPIRIES.labels(site=site).inc()
    record_event("wedge", site, backend)
    return BackendWedged(site, backend, injected=injected)


# -------------------------------------------------------- OOM classification --


def oom_site(e: BaseException) -> Optional[str]:
    """The dispatch stage an error OOM'd at ("to_device" / "dispatch"), or
    None when the error is not an out-of-memory condition. Injected
    `oom_to_device`/`oom_dispatch` faults classify exactly like the real
    jaxlib RESOURCE_EXHAUSTED they stand in for."""
    site = getattr(e, "site", None)
    if (isinstance(e, faults.FaultInjected) and isinstance(site, str)
            and site.startswith("oom_")):
        return site[len("oom_"):]
    if type(e).__name__ == "XlaRuntimeError":
        msg = str(e)
        if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
            # real OOMs do not carry the phase; attribute to dispatch (the
            # stage whose retry semantics — bisection — apply either way)
            return "dispatch"
    return None


def containment_cause(e: BaseException) -> Optional[str]:
    """Failover cause label for a containable error, or None when the error
    must propagate (deadline expiries, injected non-OOM faults, real bugs)."""
    if isinstance(e, BackendWedged):
        return CAUSE_WEDGE
    if isinstance(e, OOMBisectionExhausted):
        return CAUSE_OOM_EXHAUSTED
    if oom_site(e) is not None:
        return CAUSE_OOM
    return None


def count_failover(cause: str, where: str) -> None:
    """One failover decision: counter + event trace (callers log the rest)."""
    obs.GUARD_FAILOVERS.labels(cause=cause).inc()
    record_event("failover", cause, where)


# ------------------------------------------------- capacity-search journal ----


class SearchJournal:
    """Fsync'd JSONL journal of capacity-search probe verdicts.

    Line 1 is a header carrying the search's options digest; every later line
    is one verdict ``{"n": ..., "ok": ..., "n_failed": ...}``. `record` is
    write → flush → fsync, so a SIGKILL between probes loses at most the
    probe in flight; a torn trailing line (killed mid-write) is ignored on
    load — the valid prefix IS the journal. `open` rejects a file whose
    digest does not match the current search (`JournalMismatch`): a stale
    journal can steer a DIFFERENT search to a wrong answer, which is strictly
    worse than re-probing. The `journal_write` fault site fires before the
    write, so crash-during-journaling is deterministically testable."""

    KIND = "simon-capacity-journal"
    VERSION = 1

    def __init__(self, path: str, digest: str) -> None:
        self.path = path
        self.digest = digest
        self.verdicts: Dict[int, Tuple[bool, int]] = {}
        self.replayed = 0  # lookup hits served without a device probe
        self._f = None

    @classmethod
    def open(cls, path: str, digest: str) -> "SearchJournal":
        self = cls(path, digest)
        raw = b""
        if os.path.exists(path) and os.path.getsize(path) > 0:
            with open(path, "rb") as f:
                raw = f.read()
        if raw:
            # All offsets below are BYTE offsets into the raw file — a torn
            # tail can hold invalid utf-8, and a replace-decoded round trip
            # (U+FFFD is 3 bytes where the bad byte was 1) would make a
            # char-counted truncate land in the wrong place.
            nl = raw.find(b"\n")
            if nl < 0:
                # Unterminated first line. Rewrite ONLY when it is a byte-
                # prefix of the exact header THIS search would write — i.e.
                # our own crash torn mid-header-write, after which no verdict
                # can exist. Any other newline-less file (a typo'd
                # --resume-journal path at someone's digest/VERSION file, a
                # different search's torn header) is refused untouched.
                expected = (json.dumps(
                    {"kind": cls.KIND, "v": cls.VERSION, "digest": digest},
                    sort_keys=True) + "\n").encode()
                if expected.startswith(raw):
                    self._start_fresh(path, digest)
                    return self
                raise JournalMismatch(
                    f"{path} is not a capacity-search journal "
                    f"(unparsable header)")
            try:
                head = json.loads(raw[:nl])
            except ValueError:
                raise JournalMismatch(
                    f"{path} is not a capacity-search journal "
                    f"(unparsable header)") from None
            if not isinstance(head, dict) or head.get("kind") != cls.KIND:
                raise JournalMismatch(
                    f"{path} is not a capacity-search journal")
            if head.get("digest") != digest:
                raise JournalMismatch(
                    f"journal {path} was written by a different search "
                    f"(journal digest {head.get('digest')!r} != current "
                    f"{digest!r}); refusing to resume — delete it or point "
                    f"--resume-journal elsewhere")
            valid_bytes = pos = nl + 1
            while True:
                nl = raw.find(b"\n", pos)
                if nl < 0:
                    # a record the crash left unterminated doesn't count as
                    # durable even if it happens to parse: neither served
                    # from memory nor kept on disk (the truncation drops it)
                    break
                body = raw[pos:nl].strip()
                try:
                    if body:
                        rec = json.loads(body)
                        self.verdicts[int(rec["n"])] = (
                            bool(rec["ok"]), int(rec["n_failed"]))
                except (ValueError, KeyError, TypeError):
                    break  # torn tail from a crash: the valid prefix ends here
                valid_bytes = pos = nl + 1
            self._f = open(path, "a")
            if valid_bytes < len(raw):
                # repair: drop the torn tail so the next append starts a
                # fresh line instead of extending the garbage
                self._f.truncate(valid_bytes)
                self._f.flush()
                os.fsync(self._f.fileno())
        else:
            self._start_fresh(path, digest)
        return self

    def _start_fresh(self, path: str, digest: str) -> None:
        self._f = open(path, "w")
        self._append({"kind": self.KIND, "v": self.VERSION, "digest": digest})

    def _append(self, doc: dict) -> None:
        self._f.write(json.dumps(doc, sort_keys=True) + "\n")
        self._f.flush()
        os.fsync(self._f.fileno())

    def lookup(self, n: int) -> Optional[Tuple[bool, int]]:
        hit = self.verdicts.get(int(n))
        if hit is not None:
            self.replayed += 1
            obs.JOURNAL_REPLAYS.inc()
        return hit

    def record(self, n: int, ok: bool, n_failed: int) -> None:
        faults.maybe_fail("journal_write")
        if self._f is None:
            # the planner closes the fd when a search finishes; a REUSED
            # planner's next search appends to the (cleanly closed, fully
            # valid) file rather than crashing on the closed handle
            self._f = open(self.path, "a")
        self._append({"n": int(n), "ok": bool(ok), "n_failed": int(n_failed)})
        self.verdicts[int(n)] = (bool(ok), int(n_failed))
        obs.JOURNAL_RECORDS.inc()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None
