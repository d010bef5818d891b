"""simonfault + simonguard: first-party robustness layer — policies, fault
injection, crash-consistent simulation state, and mid-run device-failure
containment.

The reference inherits its failure behavior from client-go and kube-scheduler
for free (informer relists, rate-limited retries, the scheduler's error
funnel); this rebuild owns every network call and device dispatch itself, so
it owns the failure semantics too. Four parts:

- `policy` — composable `RetryPolicy` (exponential backoff, deterministic
  seeded jitter, max-attempts/max-elapsed), `Deadline` (contextvar-propagated
  budget that callees slice), and a `CircuitBreaker` for the live-cluster
  client. All instrumented via obs/instruments.py.
- `faults` — named fault sites threaded through the hot paths with a seeded
  `FaultPlan` (fail arrival k at site s with error class e), activatable from
  tests, `simon apply --fault-plan`, and the server's /debug/fault-plan
  endpoint. Injection is reproducible bit-for-bit: a seeded plan fires the
  same (site, arrival) pairs on every replay.
- crash consistency lives in the engine itself (simulator/engine.py
  `Simulator._transaction`): any failure — injected or real — after partial
  device work rolls host-visible state (placements, census, commit/rollback
  metric reconciliation) back to exactly the pre-call state.
- `guard` (simonguard) — what happens NEXT after the rollback: watchdog-
  supervised dispatch (wedged backends are quarantined and the run fails
  over to CPU, resuming from the last committed segment), device-OOM
  containment by pod-batch bisection (split-vs-unsplit placements are
  bit-identical), and a crash-consistent fsync'd capacity-search journal
  (`simon apply --resume-journal` skips completed probes; a digest guard
  rejects a stale journal).

Fault-site catalog (the injection error class and the invariant the tests
assert for each; README "Failure handling" carries the same table):

  site            injected as            invariant asserted
  --------------  ---------------------  ------------------------------------
  live_get        Transient/Auth/        retried per policy (Retry-After
                  Protocol error         floors honored); 401 never retried
  encode          FaultInjected          rollback: census/pod dicts/metric
                                         reconciliation bit-identical
  to_device       FaultInjected          same rollback invariant
  dispatch        FaultInjected          same rollback invariant
  fetch           FaultInjected          same rollback invariant
  commit          FaultInjected          partial batch (k-1 commits) fully
                                         rolled back, counters reconciled
  preempt_evict   FaultInjected          evictions undone, victims restored
  watchdog_wedge  BackendWedged (via     quarantine + CPU failover resumes
                  guard.supervised)      from the committed prefix; final
                                         placements == fault-free run
  oom_to_device   FaultInjected,         batch bisected in halves; split
                  classified as OOM      placements bit-identical to unsplit
  oom_dispatch    FaultInjected,         same bisection invariant; floor
                  classified as OOM      exhaustion fails over to CPU
  journal_write   FaultInjected          journal's valid prefix survives; a
                                         resumed search reaches the same
                                         nodes_added without re-probing
"""

from .faults import (
    SITES,
    FaultInjected,
    FaultPlan,
    FaultSpec,
    active_plan,
    clear_plan,
    install_plan,
    installed,
    maybe_fail,
)
from .guard import (
    BackendWedged,
    GuardError,
    JournalMismatch,
    OOMBisectionExhausted,
    SearchJournal,
    containment_cause,
    oom_site,
    supervised,
)
from .policy import (
    BreakerOpen,
    CircuitBreaker,
    Deadline,
    DeadlineExceeded,
    RetryPolicy,
    check_deadline,
    deadline_remaining,
)

__all__ = [
    "SITES",
    "FaultInjected",
    "FaultPlan",
    "FaultSpec",
    "active_plan",
    "clear_plan",
    "install_plan",
    "installed",
    "maybe_fail",
    "BackendWedged",
    "GuardError",
    "JournalMismatch",
    "OOMBisectionExhausted",
    "SearchJournal",
    "containment_cause",
    "oom_site",
    "supervised",
    "BreakerOpen",
    "CircuitBreaker",
    "Deadline",
    "DeadlineExceeded",
    "RetryPolicy",
    "check_deadline",
    "deadline_remaining",
]
