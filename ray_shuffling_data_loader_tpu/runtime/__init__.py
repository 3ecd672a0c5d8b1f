"""Runtime health & flow control: watchdogs, release events, policy.

Three pillars, each importable on its own (all stdlib except the
watchdog's stats hookup):

- :mod:`.watchdog` — progress/deadline supervision for pipeline stages
  (heartbeat registration, escalating stall reports into
  ``stats.watchdog_stats()``); the bulk device-rebatch path uses it to
  detect a wedged ``device_put`` and auto-degrade to per-batch
  transfers instead of hanging.
- :mod:`.release` — an explicit release-event channel on the native
  buffer ledger (decref/trim -> condition notify) that replaced the
  ``gc.collect()`` polling cadence in the shuffle's epoch-launch
  budget wait.
- :mod:`.policy` — the degradation-policy registry (env-var + kwargs
  resolution) that makes mitigations like ``RSDL_DEVICE_REBATCH=0``
  library defaults with per-component overrides.
- :mod:`.retry` — the ONE bounded/jittered :class:`RetryPolicy` every
  retry loop in the pipeline routes through (executor task retries,
  transport redial, remote-queue fetch, lineage recompute).
- :mod:`.faults` — seeded, deterministic fault injection
  (``RSDL_CHAOS_SPEC``) with named sites threaded through the hot
  paths, plus the :class:`QuarantinedFile` report vocabulary.
- :mod:`.telemetry` — the structured-event flight recorder (ring
  buffer, JSONL/SIGUSR1 dumps with named-thread stacks) and the online
  per-batch bottleneck attribution every stage reports through.
- :mod:`.metrics` — the typed counter/gauge/histogram registry with
  Prometheus text-format exposition (file + localhost HTTP).
- :mod:`.locksan` — the opt-in (``RSDL_LOCKSAN=1``) runtime lock
  sanitizer: wraps package-allocated locks to record the actual
  acquisition-order graph and held-while-blocking events, emitted as
  the JSON artifact that ``rsdl-lint --concurrency --locksan-graph``
  cross-checks against the static lock-order graph.
"""

from ray_shuffling_data_loader_tpu.runtime import (  # noqa: F401
    faults, locksan, metrics, policy, release, retry, telemetry, watchdog)
from ray_shuffling_data_loader_tpu.runtime.faults import (  # noqa: F401
    InjectedFault, QuarantinedFile)
from ray_shuffling_data_loader_tpu.runtime.retry import (  # noqa: F401
    RetryPolicy)
from ray_shuffling_data_loader_tpu.runtime.watchdog import (  # noqa: F401
    StallReport, Watchdog, get_watchdog)

__all__ = ["faults", "locksan", "metrics", "policy", "release", "retry",
           "telemetry", "watchdog", "InjectedFault", "QuarantinedFile",
           "RetryPolicy", "StallReport", "Watchdog", "get_watchdog"]
