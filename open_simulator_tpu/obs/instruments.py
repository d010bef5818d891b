"""The metric catalog: every counter/gauge/histogram the engine family emits.

One module owns the names so the README catalog, the PARITY.md mapping to
kube-scheduler's metrics, and the call sites cannot drift apart. Everything
here is host-side and jax-free at import; the one JAX touchpoint
(`install_jax_monitoring`) is called lazily from Simulator.__init__, after
the engine has already decided to import jax.

kube-scheduler parity (PARITY.md "Metrics parity" for the full table):
`simon_scheduling_attempts_total{result}` ↔ `schedule_attempts_total`,
`simon_e2e_scheduling_duration_seconds` ↔ `e2e_scheduling_duration_seconds`,
`simon_filter_rejections_total{reason}` ↔ the per-extension-point failure
accounting behind `PodUnschedulable` events; the compile-cache / transfer /
segment metrics are XLA-native with no k8s analog.
"""

from __future__ import annotations

import threading
from typing import Dict, Set, Tuple

from .metrics import PODS_BUCKETS, SECONDS_BUCKETS, counter, gauge, histogram

# ------------------------------------------------------------------ engine ----

SCHED_ATTEMPTS = counter(
    "simon_scheduling_attempts_total",
    "Pod scheduling attempts by outcome (kube-scheduler "
    "schedule_attempts_total). bound = pre-bound direct commit; homeless = "
    "bound to an unknown node (dropped from reports, reference parity).",
    ("result",))  # scheduled | unschedulable | bound | homeless
E2E_SECONDS = histogram(
    "simon_e2e_scheduling_duration_seconds",
    "Wall seconds per schedule_pods call, end to end "
    "(kube-scheduler e2e_scheduling_duration_seconds).",
    buckets=SECONDS_BUCKETS)
ENCODE_SECONDS = histogram(
    "simon_encode_seconds",
    "Host-side batch encode time (pods -> device tables) per scheduling run.",
    buckets=SECONDS_BUCKETS)
HOST_COMMIT_SECONDS = histogram(
    "simon_host_commit_seconds",
    "Host-side commit time per scheduling run: placements applied to the "
    "placed census / per-node registry / pod state after the device fetch "
    "(the encode/commit/device decomposition of "
    "simon_e2e_scheduling_duration_seconds — ROADMAP item 2's 60%-of-wall "
    "slice, now measured on every run).",
    buckets=SECONDS_BUCKETS)
ENCODE_BYTES = counter(
    "simon_encode_bytes_total",
    "Host bytes of encoded batch tables + carry seeds produced per "
    "scheduling/probe run (batch_tables_nbytes at encode time; the "
    "device-transfer counter tracks the same bytes at staging).")
STREAM_CHUNKS = counter(
    "simon_stream_chunks_total",
    "Scheduling runs dispatched as streaming chunks "
    "(OPEN_SIMULATOR_STREAM_PODS): host encode of chunk k+1 overlaps the "
    "device dispatch of chunk k.")
BATCH_PODS = histogram(
    "simon_batch_pods",
    "Pods per contiguous unbound scheduling run handed to the device.",
    buckets=PODS_BUCKETS)
SEGMENTS = counter(
    "simon_segments_total",
    "Device dispatch segments by kind (wave / affinity / spread / serial).",
    ("kind",))
SEGMENT_PODS = counter(
    "simon_segment_pods_total",
    "Pods carried by device dispatch segments, by segment kind.",
    ("kind",))
TRANSFER_BYTES = counter(
    "simon_device_transfer_bytes_total",
    "Host->device bytes staged for scheduling/probe table uploads.")
RESHARD_BYTES = counter(
    "simon_reshard_bytes_total",
    "Bytes of carry state whose post-dispatch sharding layout diverged from "
    "the declared carry shardings — what a chained dispatch would have to "
    "move across ICI to reconcile. The sharded executables pin out_shardings "
    "to in_shardings, so this stays 0; nonzero means a mesh dispatch path "
    "dropped its explicit shardings (parallel/mesh.py carry_reshard_bytes).")
COMMITS = counter(
    "simon_commits_total",
    "Pods committed onto nodes (placements materialized on cluster state). "
    "Monotonic reconciliation: commits - simon_commit_rollbacks_total - "
    "simon_preemption_victims_total = placements currently live.")
COMMIT_ROLLBACKS = counter(
    "simon_commit_rollbacks_total",
    "Commits undone by preemption rewinds (the replay then re-commits and "
    "re-counts them; see simon_commits_total for the reconciliation).")
FILTER_REJECTIONS = counter(
    "simon_filter_rejections_total",
    "Per-node filter-stage rejections behind failed pods, keyed by the "
    "FitError reason label (_reasons_from_stages) — the per-extension-point "
    "failure accounting of kube-scheduler's framework metrics.",
    ("reason",))

# compile-cache accounting: a dispatch whose static shape signature was seen
# before in this process hits the jit cache; a fresh signature compiles (or
# loads the persistent XLA cache). Ground truth backend compiles come from
# install_jax_monitoring below.
COMPILE_HITS = counter(
    "simon_compile_cache_hits_total",
    "Kernel dispatches whose static shape bucket was already compiled.",
    ("kernel",))
COMPILE_MISSES = counter(
    "simon_compile_cache_misses_total",
    "Kernel dispatches that triggered a fresh compile, with the shape "
    "bucket that triggered it.",
    ("kernel", "shape"))
XLA_COMPILES = counter(
    "simon_xla_backend_compiles_total",
    "XLA backend compiles observed via jax.monitoring (all programs).")
XLA_COMPILE_SECONDS = counter(
    "simon_xla_backend_compile_seconds_total",
    "Total XLA backend compile wall seconds (jax.monitoring).")

# ------------------------------------------------------------------- probe ----

PROBE_SESSIONS = counter(
    "simon_probe_sessions_total",
    "Incremental ProbeSessions built (encode-once capacity probing).")
PROBE_PROBES = counter(
    "simon_probe_candidates_total",
    "Candidate node counts evaluated through ProbeSession.probe_many.")
PROBE_DISPATCHES = counter(
    "simon_probe_dispatches_total",
    "Device round-trips spent on capacity probing (fan-out dispatches).")
PROBE_ENCODES = counter(
    "simon_probe_encodes_total",
    "Pod-batch encodes paid by probe sessions (1 per session on the "
    "incremental path).")
PROBE_ENCODE_SECONDS = counter(
    "simon_probe_encode_seconds_total",
    "One-time session build/encode wall seconds.")
PROBE_EXTENSIONS = counter(
    "simon_probe_extensions_total",
    "Template-column node-axis extensions (bucket outgrown mid-search).")
PROBE_FANOUT = histogram(
    "simon_probe_fanout_width",
    "Candidate lanes per fan-out dispatch (post power-of-two quantization).",
    buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0))

# ------------------------------------------------------------------- serve ----
# simonserve (serve/): resident what-if serving — one persistent
# device-resident cluster image, delta ingest, micro-batched request fan-out.

SERVE_REQUESTS = counter(
    "simon_serve_whatif_requests_total",
    "What-if requests served, by route: 'batched' rode a micro-batched "
    "serve_whatif_fanout lane on the resident image, 'fresh' re-simulated "
    "from a fresh encode (ineligible request or contained device failure).",
    ("path",))
SERVE_BATCHES = counter(
    "simon_serve_batches_total",
    "Micro-batched serve dispatches (one device round-trip each; lane "
    "width in simon_serve_batch_lanes).")
SERVE_LANES = histogram(
    "simon_serve_batch_lanes",
    "Requests coalesced per serve dispatch (pre lane-padding).",
    buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0))
SERVE_INGEST_EVENTS = counter(
    "simon_serve_ingest_events_total",
    "Live watch-event deltas applied to the resident cluster image, by "
    "kind (node_add / node_drain / pod_add / pod_delete).",
    ("kind",))
SERVE_RESTAGES = counter(
    "simon_serve_restages_total",
    "Resident-image device re-stages (full table re-upload), by cause: "
    "'groups' (a request interned a new pod group -> new [G, N] rows), "
    "'nodes' (delta node-add extended the node axis), 'rebuild' (an event "
    "the delta path cannot express forced a from-scratch re-encode). Pod "
    "churn never lands here — it refreshes the host-side carry seeds only.",
    ("cause",))
SERVE_SEED_REFRESHES = counter(
    "simon_serve_seed_refreshes_total",
    "Pod-churn seed rebuilds: the host-side carry seeds were re-aggregated "
    "from the placed registry with ZERO device table bytes moved.")
SERVE_STALE_SESSIONS = counter(
    "simon_serve_stale_sessions_total",
    "What-if sessions detected stale (the image generation moved under "
    "them) and transparently re-encoded before dispatch.")

# simonha (serve/ha.py): crash-consistent serving — ingest WAL +
# checkpoint/restore, overload admission control, bounded-staleness
# degraded mode. Labeled families render no samples until touched (the
# byte-identity contract for a serve that never enables --state-dir); the
# two tripwire counters below are deliberately UNLABELED so they always
# render 0 and the bench gate can pin them to zero.

SERVE_WAL_OPS = counter(
    "simon_serve_wal_ops_total",
    "Ingest write-ahead-log operations, by op: 'append' (one fsync'd "
    "record written BEFORE the image mutates), 'replay' (one record "
    "re-applied on restart), 'skip' (replay record at-or-below the "
    "checkpoint seq — the idempotence path), 'truncate' (a torn tail "
    "dropped on open), 'rotate' (the WAL reset after a compaction "
    "checkpoint sealed its records).",
    ("op",))
SERVE_CHECKPOINTS = counter(
    "simon_serve_checkpoints_total",
    "Resident-image checkpoint operations, by op: 'write' (compaction "
    "snapshot sealed via tmp-file + atomic rename), 'restore' (a restart "
    "rebuilt the image from the checkpoint + WAL tail).",
    ("op",))
SERVE_SHEDS = counter(
    "simon_serve_sheds_total",
    "Requests shed by admission control before any queue/device work, by "
    "reason: 'queue_full' (bounded admission queue at capacity), "
    "'deadline' (remaining Deadline cannot cover the observed p95 "
    "queue+dispatch wall), 'rate_limit' (per-tenant-route token bucket "
    "empty), 'payload' (in-flight ingest payload byte cap). Every shed is "
    "a structured 429/413 with Retry-After, never a downstream timeout.",
    ("reason",))
SERVE_BACKPRESSURE = counter(
    "simon_serve_backpressure_total",
    "Micro-batch window adaptations under load, by action: 'shrink' "
    "(sustained queue growth halved the batching window), 'recover' (the "
    "queue drained and the window grew back toward its configured width).",
    ("action",))
SERVE_DEGRADED = gauge(
    "simon_serve_degraded",
    "1 while serving in bounded-staleness degraded mode (ingest stalled, "
    "WAL append failing, or backend quarantined mid-rebuild): answers "
    "keep flowing against the last consistent epoch with staleness_s "
    "stamped on each; 0 when ingest is healthy.")
SERVE_STALENESS = gauge(
    "simon_serve_staleness_seconds",
    "Seconds since the last consistent ingest while degraded (0 when "
    "healthy). Crossing the configured ceiling flips /healthz to 503.")
SERVE_WRONG_EPOCH = counter(
    "simon_serve_wrong_epoch_answers_total",
    "Answers that would have been stamped with an epoch other than the "
    "serving image's consistent epoch. Never nonzero: the HA layer fails "
    "the request loudly instead of lying about its epoch (bench-gate "
    "MUST_BE_ZERO pin).")
SERVE_WAL_MISMATCHES = counter(
    "simon_serve_wal_parity_mismatches_total",
    "WAL/checkpoint lineage-digest mismatches or replay parity failures "
    "detected on restore. Never nonzero: a mismatch refuses the state dir "
    "loudly rather than serving from doubted state (bench-gate "
    "MUST_BE_ZERO pin).")

# -------------------------------------------------------------------- sync ----
# simonsync (live/sync.py): resilient watch ingest keeping the resident
# image consistent against an unreliable delta source.

SYNC_EVENTS = counter(
    "simon_sync_events_total",
    "Watch events seen by the sync loop, by disposition. 'applied' rode a "
    "delta batch into the image; 'duplicate' was already present (informer "
    "cache semantics); 'stale' lost the per-(kind,name) resourceVersion "
    "race; 'skipped' expressed no change the image tracks.",
    ("outcome",))
SYNC_RECONNECTS = counter(
    "simon_sync_reconnects_total",
    "Watch stream teardowns survived by reconnecting from the bookmark "
    "with the seeded backoff schedule.")
SYNC_RELISTS = counter(
    "simon_sync_relists_total",
    "410-Gone recoveries: the sync listed current state and reconciled it "
    "against the resident stores via columnar diff, emitting only delta "
    "events for the gap window.")
SYNC_FULL_REBUILDS = counter(
    "simon_sync_full_rebuilds_total",
    "Relist reconciliations that found an inexpressible change and had to "
    "fall back to a generation-bumping rebuild. Never nonzero in the chaos "
    "gate's traces (bench-gate MUST_BE_ZERO pin).")
SYNC_PARITY = counter(
    "simon_sync_parity_mismatches_total",
    "Post-reconcile parity failures: the resident image's node/pod sets "
    "disagreed with the freshly listed state after applying the diff. "
    "Never nonzero: reconciliation is exact by construction (bench-gate "
    "MUST_BE_ZERO pin).")
SYNC_BOOKMARK_RV = gauge(
    "simon_sync_bookmark_rv",
    "The resourceVersion high-water mark the watch would resume from "
    "after a reconnect or restart.")

# ------------------------------------------------------------------- sweep ----
# simonsweep (sweep/): batched scenario sweeps — Monte-Carlo what-if fleets
# coalesced onto the scenario axis of the sweep_*_fanout kernels.

SWEEP_SCENARIOS = counter(
    "simon_sweep_scenarios_total",
    "Sweep scenarios evaluated, by family and route: 'wave' rode the "
    "per-lane wave-chain fast lane (sweep_wave_fanout), 'scan' the "
    "per-lane serial-scan lane (sweep_whatif_fanout), 'fresh' a "
    "single-scenario fresh Simulator run (census-dependent gate or "
    "contained device failure).",
    ("family", "route"))
SWEEP_DISPATCHES = counter(
    "simon_sweep_dispatches_total",
    "Batched sweep dispatches (one device round-trip per scenario chunk), "
    "by kernel.",
    ("kernel",))
SWEEP_LANES = histogram(
    "simon_sweep_batch_lanes",
    "Scenario lanes coalesced per sweep dispatch (pre lane-padding).",
    buckets=(1.0, 4.0, 16.0, 64.0, 256.0, 1024.0))
SWEEP_PARITY_CHECKS = counter(
    "simon_sweep_parity_checks_total",
    "Sweep lanes re-run on a fresh serial Simulator and census-compared "
    "against the batched placements (the standing parity fuzzer).")
SWEEP_PARITY_MISMATCHES = counter(
    "simon_sweep_parity_mismatches_total",
    "Sweep lanes whose batched placement census diverged from the fresh "
    "serial run. Never nonzero: a mismatch fails the sweep loudly.")

# -------------------------------------------------------------- preemption ----

PREEMPT_ATTEMPTS = counter(
    "simon_preemption_attempts_total",
    "PostFilter runs for failed pods, by outcome (kube-scheduler "
    "preemption_attempts_total).",
    ("outcome",))  # nominated | no_candidates
PREEMPT_VICTIMS = counter(
    "simon_preemption_victims_total",
    "Pods evicted by preemption (kube-scheduler preemption_victims).")
PREEMPT_REPLAY_PODS = counter(
    "simon_preemption_replay_pods_total",
    "Pods re-scheduled by rewind/replay passes — the simulator-specific "
    "cost of exact mid-batch preemption (PARITY.md cost envelope).")

# ---------------------------------------------------------------- resilience --

RETRIES = counter(
    "simon_retries_total",
    "Retried attempts by fault site (resilience/policy.py RetryPolicy; "
    "counts each retry, not first attempts).",
    ("site",))
DEADLINE_EXCEEDED = counter(
    "simon_deadline_exceeded_total",
    "Operations abandoned because the contextvar deadline budget ran out, "
    "by the site that noticed.",
    ("site",))
BREAKER_STATE = gauge(
    "simon_breaker_state",
    "Circuit-breaker state: 0 closed, 1 half-open, 2 open "
    "(resilience/policy.py CircuitBreaker).",
    ("name",))
FAULTS_INJECTED = counter(
    "simon_faults_injected_total",
    "Injected failures fired by the active FaultPlan, by site "
    "(resilience/faults.py; zero in production).",
    ("site",))
HTTP_ERRORS = counter(
    "simon_http_errors_total",
    "Server request failures by endpoint and HTTP status code "
    "(structured JSON error bodies, server/http.py).",
    ("endpoint", "code"))

# ------------------------------------------------------------------- guard ----
# simonguard (resilience/guard.py): mid-run device-failure containment. The
# acceptance contract is "no silent degradation" — every watchdog expiry,
# bisection, failover, and quarantine moves one of these.

GUARD_WATCHDOG_EXPIRIES = counter(
    "simon_guard_watchdog_expiries_total",
    "Supervised device computations declared wedged (watchdog deadline "
    "expired or injected watchdog_wedge fault), by dispatch site.",
    ("site",))
GUARD_OOM_BISECTIONS = counter(
    "simon_guard_oom_bisections_total",
    "Pod-batch halvings performed to contain a device OOM, by the stage "
    "that OOM'd (to_device / dispatch).",
    ("site",))
GUARD_FAILOVERS = counter(
    "simon_guard_failovers_total",
    "Mid-run backend failovers to the CPU fallback, by cause "
    "(watchdog_wedge / oom_exhausted / oom). Each also appends to the "
    "result's backend_path.",
    ("cause",))
GUARD_QUARANTINED = gauge(
    "simon_guard_quarantined",
    "1 while the labeled backend is quarantined for this process "
    "(wedged mid-run; all later device work routes to the CPU fallback).",
    ("backend",))
JOURNAL_RECORDS = counter(
    "simon_journal_records_total",
    "Probe verdicts appended (write+flush+fsync) to a capacity-search "
    "journal (resilience/guard.py SearchJournal).")
JOURNAL_REPLAYS = counter(
    "simon_journal_replayed_probes_total",
    "Capacity-search probes skipped because a resumed journal already "
    "held their verdict.")

# ------------------------------------------------------------------- xray -----
# simonxray (obs/xray.py): both counters are LABELED on purpose — an
# untouched labeled family renders no samples, so a recording-off run's
# /metrics and --metrics-out output stays byte-identical to pre-xray builds.

XRAY_RECORDS = counter(
    "simon_xray_records_total",
    "Flight-recorder records committed, by kind (batch / pod / set / "
    "preempt / probe). Zero unless --xray / OPEN_SIMULATOR_XRAY=1.",
    ("kind",))
XRAY_DROPPED = counter(
    "simon_xray_dropped_total",
    "Flight-recorder records dropped by the bounded-memory caps, by kind "
    "(set: OPEN_SIMULATOR_XRAY_MAX_SETS; pod_index: the in-memory explain "
    "index, the JSONL trace keeps everything). Never silent: the first "
    "drop logs a warning.",
    ("kind",))

# ------------------------------------------------------------------- scope ----
# simonscope (obs/scope.py): request tracing + SLO engine + device-runtime
# telemetry. Every family here is LABELED on purpose (the xray contract): an
# untouched labeled family renders no samples, so a scope-off run's /metrics
# and --metrics-out output stays byte-identical to pre-scope builds.

SCOPE_REQUESTS = counter(
    "simon_scope_requests_total",
    "Requests finished under simonscope SLO accounting, by endpoint and "
    "route (batched / fresh / error). Zero unless scope is on "
    "(`simon serve`'s default; OPEN_SIMULATOR_SCOPE=1 elsewhere).",
    ("endpoint", "route"))
SCOPE_PHASE_SECONDS = histogram(
    "simon_scope_request_phase_seconds",
    "Cumulative per-request latency decomposition (queue-wait in the "
    "micro-batch dispatcher / kernel dispatch / device fetch / total), by "
    "endpoint and phase — the Clipper-style breakdown that makes the "
    "batching window tunable. The rolling-window quantiles live in "
    "simon_scope_latency_ms.",
    ("endpoint", "phase"), buckets=SECONDS_BUCKETS)
SCOPE_SLO_VIOLATIONS = counter(
    "simon_scope_slo_violations_total",
    "Requests that violated their endpoint's SLO target (latency over the "
    "p99 target, or an error response), by endpoint.",
    ("endpoint",))
SCOPE_QUANTILE_MS = gauge(
    "simon_scope_latency_ms",
    "Rolling-window latency quantiles per endpoint and phase "
    "(refreshed on each scoped /metrics or /v1/serve/stats read).",
    ("endpoint", "phase", "quantile"))
SCOPE_BUDGET_BURN = gauge(
    "simon_scope_error_budget_burn",
    "Error-budget burn rate per endpoint: (bad-request fraction) / "
    "(allowed fraction from the availability target); >1 means the budget "
    "is burning faster than the SLO allows.",
    ("endpoint",))
SCOPE_TRACE_EVENTS = counter(
    "simon_scope_trace_events_total",
    "Trace events recorded into the in-memory buffer, by kind "
    "(span / flow / counter).",
    ("kind",))
SCOPE_TRACE_DROPPED = counter(
    "simon_scope_trace_dropped_total",
    "Trace events dropped because the bounded buffer was full, by kind. "
    "Never silent: a full buffer drops NEW events and counts every one.",
    ("kind",))
SCOPE_POOL_BYTES = gauge(
    "simon_scope_device_pool_bytes",
    "Live device-buffer bytes attributed to a pool by the runtime sampler "
    "(image_tables / carry_cache / scratch) — the Orca-style resident-state "
    "footprint track that makes image leaks under churn visible.",
    ("pool",))
SCOPE_COMPILE_DELTA = gauge(
    "simon_scope_compile_cache_delta",
    "Compile-cache hit/miss deltas over the sampler's last interval, by "
    "kind; a nonzero 'misses' track during steady serving means requests "
    "are minting fresh shape buckets.",
    ("kind",))
SCOPE_TRANSFER_RATE = gauge(
    "simon_scope_transfer_bytes_per_s",
    "Host->device transfer rate over the sampler's last interval, by "
    "direction (steady serving on a warm image should hold this at ~0).",
    ("direction",))
SCOPE_SAMPLES = counter(
    "simon_scope_runtime_samples_total",
    "Telemetry ticks completed by the device-runtime sampler thread, by "
    "kind.",
    ("kind",))
SCOPE_SAMPLER_ERRORS = counter(
    "simon_scope_sampler_errors_total",
    "Telemetry tick failures (a pool provider raised, live-array walk "
    "failed). The sampler keeps running; failures are counted, not silent.",
    ("kind",))

# ------------------------------------------------------------------- pulse ----
# simonpulse (obs/pulse.py): roofline cost accounting + the per-dispatch
# performance ledger. Every family here is LABELED on purpose (the xray/scope
# contract): an untouched labeled family renders no samples, so a pulse-off
# run's /metrics and --metrics-out output stays byte-identical to pre-pulse
# builds.

PULSE_RECORDS = counter(
    "simon_pulse_records_total",
    "Performance-ledger records appended, by kind (dispatch / run). Zero "
    "unless pulse is on (OPEN_SIMULATOR_PULSE=1 or pulse.enable()).",
    ("kind",))
PULSE_DROPPED = counter(
    "simon_pulse_records_dropped_total",
    "Ledger records evicted from the bounded ring buffer, by kind "
    "(OPEN_SIMULATOR_PULSE_CAP; the JSONL spill, when configured, keeps "
    "every record). Never silent: every eviction is counted here.",
    ("kind",))
PULSE_REGRESSIONS = counter(
    "simon_pulse_regressions_total",
    "Warm dispatches flagged as MAD outliers against their rolling "
    "per-(kernel, dispatch-digest) warm-wall baseline — 'same executable, "
    "slower environment' drift (OPEN_SIMULATOR_PULSE_MAD_K).",
    ("kernel", "bucket"))
PULSE_PHASE_SECONDS = counter(
    "simon_pulse_phase_seconds_total",
    "Scheduling-run wall seconds by phase (encode / table_build / to_device "
    "/ dispatch / fetch / commit) — the per-run decomposition of "
    "simon_e2e_scheduling_duration_seconds the ledger's run records carry. "
    "table_build is the node-axis [*, N] table construction inside encode, "
    "counted per chunk on the streaming path (ROADMAP item 5).",
    ("phase",))
PULSE_ACHIEVED = gauge(
    "simon_pulse_achieved_fraction",
    "Most recent achieved fraction of the roofline model-optimal time per "
    "warm dispatch: model_optimal_s / measured wall, from cost_analysis "
    "FLOPs/bytes at the device's published peaks (obs/pulse.py PEAKS).",
    ("kernel", "bucket"))

# ---------------------------------------------------------- capacity search ---

CAPACITY_SEARCHES = counter(
    "simon_capacity_searches_total",
    "Add-node capacity-planner searches, by probe path.",
    ("path",))  # incremental | fresh
CAPACITY_ROUNDS = counter(
    "simon_capacity_search_rounds_total",
    "Search rounds (device dispatches) spent by capacity searches.")

# ------------------------------------------------- dispatch shape tracking ----

_SEEN_SHAPES: Set[Tuple] = set()
_SEEN_LOCK = threading.Lock()

# simonpulse attribution hook: pulse.enable() installs its note_dispatch here
# so every record_dispatch call (THE definition of "one kernel dispatch")
# also lands in the performance ledger; None keeps pulse-off dispatches at
# exactly one extra global read. instruments never imports pulse — the hook
# direction keeps the catalog import-light.
_DISPATCH_HOOK = None


def record_dispatch(kernel: str, **dims) -> bool:
    """Count one kernel dispatch against the compile cache: the first time a
    (kernel, static-shape) signature is seen in this process it is a miss
    (XLA compiles or loads the persistent cache), afterwards a hit. `dims`
    must contain exactly the dispatch's static/shape-defining parts — traced
    values never belong here. Returns True on miss (fresh compile)."""
    key = (kernel,) + tuple(sorted(dims.items()))
    with _SEEN_LOCK:
        miss = key not in _SEEN_SHAPES
        if miss:
            _SEEN_SHAPES.add(key)
    if miss:
        shape = ",".join(f"{k}={v}" for k, v in sorted(dims.items()))
        COMPILE_MISSES.labels(kernel=kernel, shape=shape).inc()
    else:
        COMPILE_HITS.labels(kernel=kernel).inc()
    hook = _DISPATCH_HOOK
    if hook is not None:
        hook(kernel, dims, miss)
    return miss


def record_filter_reasons(reasons: Dict[str, int]) -> None:
    """Fold one failed pod's FitError reason counts (label -> node count)
    into the rejection counters."""
    for label, n in reasons.items():
        FILTER_REJECTIONS.labels(reason=label).inc(n)


_jaxmon_installed = False


def install_jax_monitoring() -> None:
    """Register the jax.monitoring listener that counts real XLA backend
    compiles (idempotent; safe when jax is absent/old). Called from
    Simulator.__init__, which has already committed to importing jax."""
    global _jaxmon_installed
    if _jaxmon_installed:
        return
    _jaxmon_installed = True
    try:
        from jax import monitoring

        def _on_duration(event: str, duration: float, **kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                XLA_COMPILES.inc()
                XLA_COMPILE_SECONDS.inc(duration)

        monitoring.register_event_duration_secs_listener(_on_duration)
    # simonlint: ignore[swallowed-exception] -- diagnostics-only listener; a
    # jax too old for monitoring must never break the engine, and there is
    # nothing to count into (this IS the metrics bootstrap)
    except Exception:
        pass
