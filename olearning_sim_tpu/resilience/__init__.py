"""Resilience layer: fault injection, retry/backoff, quarantine, event log.

The subsystem the reference platform gets for free from subprocess isolation
and Ray actor restarts, rebuilt as first-class components for the compiled
TPU engine (docs/resilience.md):

- :mod:`faults` — deterministic seed-driven fault injection at named points;
- :mod:`retry` — generic exponential-backoff retry policy for transient I/O
  and RPC failures;
- :mod:`quarantine` — exclusion + probationary re-admission of clients that
  produce non-finite updates;
- :mod:`policy` — operator-level failure policies (fail_task / skip_round /
  retry) and the runner's resilience configuration;
- :mod:`events` — counters + structured events surfaced through the
  performance manager and the task status API.
"""

from olearning_sim_tpu.resilience.events import (
    CHECKPOINT_FALLBACK,
    CLIENT_FLAGGED,
    CLIENT_QUARANTINED,
    CLIENT_READMITTED,
    CRASH_LOOP,
    DEADLINE_MISS,
    FAULT_INJECTED,
    LEASE_EXPIRED,
    OUTBOUND_DEGRADED,
    QUARANTINE,
    READMIT,
    RETRY,
    RETRY_EXHAUSTED,
    ROLLBACK,
    SKIP_ROUND,
    TASK_RESUMED,
    ResilienceEvent,
    ResilienceLog,
    global_log,
)
from olearning_sim_tpu.resilience.faults import (
    ChaosClock,
    FaultError,
    FaultInjector,
    FaultPlan,
    FaultSpec,
    HostPreemption,
    active_injector,
    chaos,
    fire,
    inject,
    install,
)
from olearning_sim_tpu.resilience.policy import FailurePolicy, ResilienceConfig
from olearning_sim_tpu.resilience.quarantine import (
    QuarantineManager,
    parse_quarantine_params,
)
from olearning_sim_tpu.resilience.retry import (
    NO_RETRY,
    RetryPolicy,
    fast_test_policy,
)

__all__ = [
    "CHECKPOINT_FALLBACK",
    "CLIENT_FLAGGED",
    "CLIENT_QUARANTINED",
    "CLIENT_READMITTED",
    "CRASH_LOOP",
    "DEADLINE_MISS",
    "FAULT_INJECTED",
    "LEASE_EXPIRED",
    "OUTBOUND_DEGRADED",
    "QUARANTINE",
    "READMIT",
    "RETRY",
    "RETRY_EXHAUSTED",
    "ROLLBACK",
    "SKIP_ROUND",
    "TASK_RESUMED",
    "ChaosClock",
    "FailurePolicy",
    "FaultError",
    "FaultInjector",
    "FaultPlan",
    "FaultSpec",
    "HostPreemption",
    "NO_RETRY",
    "QuarantineManager",
    "ResilienceConfig",
    "ResilienceEvent",
    "ResilienceLog",
    "RetryPolicy",
    "active_injector",
    "chaos",
    "fast_test_policy",
    "fire",
    "global_log",
    "inject",
    "install",
    "parse_quarantine_params",
]
