"""Degradation-policy registry: one resolution surface for runtime knobs.

Operational mitigations (forcing the per-batch transfer path, deadlines
that were module constants) are LIBRARY behavior: every runtime knob
resolves through this module, with one precedence order everywhere::

    explicit kwarg > RSDL_<COMPONENT>_<KEY> env > RSDL_<KEY> env
                   > registered component default > library default

Components are short names for the subsystem consulting the policy
(``jax_dataset``, ``shuffle``, ``spill``). Example: a host
whose bulk device transfers stall exports ``RSDL_DEVICE_REBATCH=0`` and
every loader in every process uses per-batch transfers, while
``RSDL_JAX_DATASET_BULK_TRANSFER_DEADLINE_S=5`` tightens only the
loader's bulk-transfer watchdog.

Stdlib-only on purpose: policy must be importable before (and without)
jax/pyarrow, and from the native layer without cycles.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional

_FALSE_WORDS = frozenset({"0", "false", "no", "off"})


def _parse_bool(raw: str) -> bool:
    return raw.strip().lower() not in _FALSE_WORDS


def _parse_tristate(raw: str):
    """``"auto"`` stays the string sentinel; anything else parses as bool."""
    word = raw.strip().lower()
    if word == "auto":
        return "auto"
    return _parse_bool(word)


#: key -> (library default, parser for env-var strings). The parser also
#: normalizes programmatic overrides where cheap (bools stay bools).
_KEYS: Dict[str, "tuple[Any, Callable[[str], Any]]"] = {
    # Bulk device-rebatch mode: "auto" / True / False. A False here makes
    # the per-batch path the default for every loader in the process.
    "device_rebatch": ("auto", _parse_tristate),
    # Progress watchdog over the bulk transfer/carve path.
    "watchdog": (True, _parse_bool),
    # Seconds a single bulk chunk device_put/carve may run before the
    # watchdog declares a stall. Generous by default: a miss is meant to
    # catch wedged transports, not slow ones.
    "bulk_transfer_deadline_s": (30.0, float),
    # What a stall does: "degrade" (drop to the per-batch path and keep
    # going), "warn" (log + stats only), "raise" (fail the producer).
    "stall_action": ("degrade", str),
    # How long an epoch launch waits for consumers to release tables when
    # over max_inflight_bytes before proceeding with a warning.
    "budget_wait_timeout_s": (30.0, float),
    # Upper bound between predicate re-checks in release-event waits — a
    # safety heartbeat, not a polling cadence (releases wake waiters
    # immediately).
    "release_heartbeat_s": (0.25, float),
    # Free-list trim cooldown under sustained budget pressure (spill.py).
    "trim_cooldown_s": (1.0, float),
    # Watchdog monitor thread poll interval.
    "watchdog_poll_interval_s": (0.05, float),
    # Shared RetryPolicy defaults (runtime/retry.py): total attempts,
    # decorrelated-jitter backoff bounds, and a wall-clock deadline for
    # the whole call-plus-retries (<= 0 means no deadline). Resolved per
    # component, so e.g. RSDL_TRANSPORT_RETRY_MAX_ATTEMPTS=60 deepens
    # only the transport's connect redial budget.
    "retry_max_attempts": (3, int),
    "retry_initial_backoff_s": (0.05, float),
    "retry_max_backoff_s": (2.0, float),
    "retry_deadline_s": (0.0, float),
    # Telemetry spine (runtime/telemetry.py): flight-recorder on/off,
    # ring capacity (events), and where escalation/SIGUSR1 dumps land
    # ("" = the system temp dir).
    "telemetry": (True, _parse_bool),
    "telemetry_capacity": (4096, int),
    "telemetry_dump_dir": ("", str),
    # Causal tracing (runtime/trace.py): when set, EVERY process dumps
    # its flight recorder into this directory at exit (and dump()
    # defaults there), so `tools/rsdl_trace.py <dir>` can merge the
    # multi-process story. Child processes (supervised queue servers)
    # inherit it through the environment.
    "trace_dir": ("", str),
    # Continuous sampling profiler (runtime/profiler.py): stdlib stack
    # sampling over named threads + per-thread CPU attribution, started
    # by its caller (an incident capsule's burst); the interval bounds
    # its overhead (~1 stack walk per thread per tick).
    "profiler_interval_s": (0.01, float),
    # Batch-wait share of wall clock above which the per-epoch verdict
    # names a producer stage instead of train_step (the <=10% stall
    # contract's mirror image).
    "bottleneck_stall_threshold_pct": (10.0, float),
    # Metrics exposition (runtime/metrics.py): Prometheus text file path
    # ("" = off), localhost HTTP port (0 = off), file rewrite cadence.
    "metrics_file": ("", str),
    "metrics_port": (0, int),
    "metrics_interval_s": (5.0, float),
    # Multi-process metrics federation (runtime/metrics.py): when set,
    # EVERY process (driver, procpool workers, supervised queue servers)
    # periodically writes a per-pid exposition shard into this directory
    # (same inherit-via-env pattern as RSDL_TRACE_DIR), and the driver's
    # exposition file / HTTP endpoint / rsdl_top merge the shards into
    # cluster-wide totals with a per-pid view.
    "telemetry_dir": ("", str),
    "metrics_shard_interval_s": (2.0, float),
    # Time-series history ring (runtime/history.py): periodic registry
    # snapshots in fixed memory, ticked from the watchdog monitor thread.
    "history_interval_s": (1.0, float),
    "history_capacity": (600, int),
    # Health/SLO detector engine (runtime/health.py): detectors evaluate
    # on every history tick with hysteresis (breach must persist
    # `health_fire_ticks` ticks to fire; `health_clear_ticks` clean ticks
    # re-arm it) so a noisy tick cannot flap a verdict.
    "health": (True, _parse_bool),
    "health_fire_ticks": (3, int),
    "health_clear_ticks": (5, int),
    # SLO thresholds (RSDL_SLO_* via the generic env rung; component
    # form RSDL_HEALTH_SLO_* wins over it). Detector semantics live in
    # runtime/health.py next to each detector.
    "slo_droop_pct": (60.0, float),        # rate below (100-x)% of peak
    "slo_droop_floor_eps": (2.0, float),   # min peak (events/s) to judge
    "slo_droop_window_ticks": (8, int),    # smoothing window for rates
    "slo_stall_pct": (95.0, float),        # consumer batch-wait share
    "slo_creep_mb_per_min": (512.0, float),  # ledger/RSS growth slope
    "slo_queue_depth": (100000.0, float),  # per-queue item saturation
    "slo_lease_churn_per_min": (3.0, float),
    "slo_straggler_drift_x": (4.0, float),  # straggler vs rolling median
    # Delivery-latency plane (runtime/latency.py): windowed p99 of the
    # end-to-end birth->delivered hop above which delivery_latency_breach
    # fires, and the effective freshness age (newest payload's birth age
    # at the consumer's final hop, PLUS how long that gauge has been
    # frozen) above which freshness_stall fires.
    "slo_delivery_p99_s": (30.0, float),
    "slo_freshness_s": (120.0, float),
    # Incident capsules (runtime/health.py): where capsule directories
    # land ("" = trace_dir, else telemetry_dump_dir, else temp dir), how
    # long the profiler burst samples, and how long capture waits for
    # sibling processes to land their signal-driven trace dumps.
    "incident_dir": ("", str),
    "incident_profile_s": (0.25, float),
    "incident_wait_s": (2.0, float),
    # Cross-process queue service (multiqueue_service.py) socket hygiene:
    # recv timeout applied to BOTH serve_queue connections and
    # RemoteQueue dials (0 = no timeout — a deliberate infinite wait;
    # with protocol v2 a timed-out response is reconnected-and-replayed,
    # never lost), and TCP_NODELAY on both ends.
    "queue_timeout_s": (300.0, float),
    "queue_nodelay": (True, _parse_bool),
    # Per-queue replay-buffer byte budget: unacked frames held for
    # reconnect replay. At the budget the server stops popping new items
    # (backpressure) rather than dropping unacked data.
    "queue_replay_bytes": (256 << 20, int),
    # Consumer lease: seconds without a heartbeat/request before a
    # consumer is declared dead. Client heartbeats run at a third of it.
    "queue_lease_timeout_s": (30.0, float),
    # Weighted-fair tenancy (tenancy/fairshare.py): the DRR replenish
    # quantum (each round hands a tenant quantum*weight bytes of pop
    # credit) and the activity window after which an idle tenant's
    # share redistributes to the rest (work conservation).
    "tenant_drr_quantum_bytes": (1 << 20, int),
    "tenant_active_window_s": (1.0, float),
    # Pace of the one-frame-per-GET liveness floor while the scheduler
    # is denying a tenant: the denied GET is delayed this long before
    # its floor frame pops. Without it a fast-RTT consumer's floor
    # alone out-runs the DRR grants and the weights shape nothing.
    # 0 disables pacing (floor at raw round-trip rate).
    "tenant_floor_pace_s": (0.002, float),
    # Serving-plane table delivery (multiqueue_service v3): "auto"
    # (consumers on a loopback address offer shm-handle delivery and the
    # server sends segment handles instead of streaming table bytes;
    # cross-host consumers stream), "handle" (offer handles regardless
    # of address — containers sharing a shm mount), "stream" (always
    # stream bytes; the v2 wire exactly).
    "queue_delivery": ("auto", str),
    # Frame compression for STREAMED table payloads (handle frames are
    # ~100 bytes and never compressed): "off" | "zlib" | "zstd" | "lz4".
    # zstd/lz4 degrade to zlib with a warning when the codec module is
    # not installed. CRC is computed pre-compression, so corruption
    # detection and NACK/replay semantics are unchanged.
    "queue_compression": ("off", str),
    # Streamed payloads below this size skip compression (header + CPU
    # overhead dwarfs the saving on small frames).
    "queue_compression_min_bytes": (4096, int),
    # Serving-plane shard count consulted by the serve helpers when the
    # caller does not pass one explicitly (1 = the pre-PR-10 topology).
    "queue_shards": (1, int),
    # What the server does when a consumer's lease expires
    # (RSDL_QUEUE_ON_DEAD_CONSUMER): "fail_fast" (down the server so the
    # pipeline fails loudly), "drain" (free the dead rank's queues so
    # producers are unblocked and memory is released), "redistribute"
    # (reroute its undelivered tables to a surviving consumer).
    "on_dead_consumer": ("fail_fast", str),
    # Executor data-plane backend (executor.py / procpool.py): "thread"
    # (GIL-releasing thread pool, the historical default), "process"
    # (supervised worker subprocesses with shared-memory Arrow handoff),
    # or "auto" (process when the host has >1 core, a writable shared-
    # memory dir, and the workload's transforms are picklable; thread
    # otherwise). shuffle() consults this only when it owns the pool.
    "executor_backend": ("auto", str),
    # Worker count for the pool (0 = one per host CPU).
    "executor_workers": (0, int),
    # Where process-backend shm segments live ("" = /dev/shm when
    # writable, else the system temp dir — which silently degrades
    # zero-copy to page-cache-backed mmap, still correct).
    "executor_shm_dir": ("", str),
    # Byte budget for decoded-table segments cached across epochs in the
    # process backend's shm arena (0 = half the free bytes of the shm
    # filesystem at pool creation).
    "executor_shm_bytes": (0, int),
    # Map-stage partition plan: "fused" (one native kernel emits
    # partition indices straight from a counter-based splitmix64 stream;
    # bit-identical NumPy fallback) or "philox" (legacy two-stage
    # numpy Philox draw + counting sort). Both are deterministic in
    # (seed, epoch, file); the streams differ, so flipping this knob
    # mid-checkpoint changes the shuffle order.
    "partition_plan": ("fused", str),
    # Streaming map pipeline (RSDL_SHUFFLE_FUSED_PIPELINE): fuse
    # decode->partition->gather at the map stage — Parquet record batches
    # scatter straight into per-reducer output buffers, no intermediate
    # decoded-table materialization. "auto"/True enable it wherever it
    # preserves the caching and bit-identity contracts (cache-less reads,
    # primitive null-free columns, elementwise transforms); False forces
    # the legacy read-then-plan path everywhere. The partition stream is
    # the SAME (seed, epoch, file) splitmix64 stream either way, so
    # flipping this knob never changes the shuffle order.
    "shuffle_fused_pipeline": ("auto", _parse_tristate),
    # CRC backend for every checksummed path (wire frames, spill files,
    # shm segments, watermark journals): "auto" (native kernel when the
    # library is loaded), "native", "zlib". Output is zlib.crc32-
    # compatible in all cases — recorded checksums survive backend flips.
    "crc_backend": ("auto", str),
    # Scatter-gather wire sends (RSDL_QUEUE_SENDMSG): coalesce a GET
    # response's batch header + per-frame headers + payloads into one
    # sendmsg() syscall instead of one sendall() per piece. Wire bytes
    # are identical; only the syscall count changes.
    "queue_sendmsg": (True, _parse_bool),
    # Codec pool for RSDL_QUEUE_COMPRESSION: compression runs on this
    # many background threads so the serving thread never stalls on
    # codec work (0 = compress inline on the serving thread).
    "queue_codec_threads": (1, int),
    # Double-buffered device staging (jax_dataset.py): convert batch N+1
    # on a staging thread while batch N's host->device transfer is in
    # flight. Delivery order is unchanged (single staging lane, FIFO).
    "device_double_buffer": (True, _parse_bool),
    # Epoch-plan scheduler (plan/scheduler.py). Speculative re-execution
    # of stragglers: off by default (duplicate attempts are bit-identical
    # by the lineage contract, but they absorb injected chaos faults and
    # burn idle capacity, so racing them is an explicit operator choice —
    # RSDL_PLAN_SPECULATION=1). A backup launches when a running task
    # exceeds max(plan_speculation_min_s, multiplier x rolling per-stage
    # median) and an idle lane exists; first completion wins.
    "plan_speculation": (False, _parse_bool),
    "plan_speculation_multiplier": (4.0, float),
    "plan_speculation_min_s": (1.0, float),
    # Straggler-check cadence of the plan driver thread (only paid while
    # speculation is on; off, the driver blocks on completion events).
    "plan_speculation_check_s": (0.05, float),
    # Work-stealing placement: an idle lane pulls ready nodes from the
    # longest sibling queue instead of waiting on its static round-robin
    # assignment. On by default (outputs are placement-independent).
    "plan_stealing": (True, _parse_bool),
    # What shuffle_map does with a corrupt/unreadable input file after
    # read retries are exhausted: "raise" (fail the map task; lineage
    # recovery then retries it, and only exhausted recovery poisons the
    # run) or "skip" (quarantine the file into a structured
    # QuarantinedFile report and shuffle the remaining files).
    "on_bad_file": ("raise", str),
    # Storage plane (storage/): which StorageSource dataset reads resolve
    # to when nothing is installed programmatically — "local" (direct
    # filesystem/fsspec reads, the historical behavior), "sim" (the
    # hermetic SimulatedObjectStore over local files, for tests).
    "storage_backend": ("local", str),
    # Plan-driven cache warming: when the active file cache exposes a
    # prefetcher, the plan scheduler issues prefetch tasks on idle lanes
    # (below steal/speculation priority, canceled when real work lands).
    "storage_prefetch": (True, _parse_bool),
    # SimulatedObjectStore shape (RSDL_STORAGE_SIM_*): first-byte latency
    # (ms), sustained bandwidth (MB/s), multiplicative jitter (+/- pct,
    # seeded), transient error rate (fraction of fetches raising OSError
    # — absorbed by the storage RetryPolicy), and the draw seed. All
    # draws are a pure function of (seed, path, attempt-count), so a
    # fixed seed reproduces byte-identical timing/error sequences.
    "storage_sim_first_byte_ms": (2.0, float),
    "storage_sim_mb_per_s": (512.0, float),
    "storage_sim_jitter_pct": (10.0, float),
    "storage_sim_error_rate": (0.0, float),
    "storage_sim_seed": (0, int),
    # Cache-thrash detector (runtime/health.py): fires when the tiered
    # cache's eviction rate exceeds this many evictions/min while its
    # hit rate sits below slo_cache_hit_pct — the signature of a disk
    # tier smaller than the working set re-fetching every epoch.
    "slo_cache_evictions_per_min": (120.0, float),
    "slo_cache_hit_pct": (10.0, float),
    # Streaming windows (streaming/window.py, RSDL_STREAM_WINDOW_*): a
    # window seals at the FIRST bound hit — admitted file count, admitted
    # payload bytes, or stream-time age since the window's first event
    # (the watermark bound). 0 disables a bound (file count falls back
    # to 1 if every bound is disabled: a window must be closable). Late
    # arrivals — events whose stream timestamp precedes the journaled
    # ingest watermark — follow window_late_policy: "admit" rolls them
    # into the NEXT window (bounded disorder, nothing lost), "quarantine"
    # excludes them into a structured report (the on_bad_file idiom).
    "window_max_files": (4, int),
    "window_max_bytes": (0, int),
    "window_max_wait_s": (0.0, float),
    "window_late_policy": ("admit", str),
    # Elastic membership (membership/): the failure detector's probe
    # cadence (RSDL_MEMBER_HEARTBEAT_S — heartbeats ride every data
    # frame too, the prober only covers idle links), the silence after
    # which a quiet rank is declared down (RSDL_MEMBER_SUSPECT_S), and
    # the phi-style suspicion threshold (elapsed silence measured in
    # smoothed inter-arrival units; crossing it marks the rank SUSPECT
    # before the hard suspect_s deadline downs it). Hysteresis: a rank
    # that flaps (suspect -> alive -> suspect inside one suspect_s
    # window) re-arms silently — one flapping link emits one
    # member_suspect, not a storm.
    "member_heartbeat_s": (0.5, float),
    "member_suspect_s": (3.0, float),
    "member_phi": (8.0, float),
    # watermark_lag detector (runtime/health.py): how far the serve
    # watermark (stream time fully drained to trainers) may trail the
    # ingest watermark (stream time sealed into closed windows) before
    # the stream is declared stale — the streaming analog of
    # slo_freshness_s, measured in seconds of stream time.
    "slo_watermark_lag_s": (300.0, float),
    # Self-healing rebalancer (rebalance/, RSDL_REBALANCE_*): the
    # per-tenant delivery-p99 SLO above which the tenant_delivery_slo
    # detector declares a sustained breach (the trigger for a journaled
    # placement decision), the cooldown after a committed move before
    # the controller will consider another (lets the post-move p99
    # window drain so one hot tenant does not ping-pong between
    # shards), and the max committed moves per decision window.
    "rebalance_slo_p99_s": (30.0, float),
    "rebalance_cooldown_s": (60.0, float),
    "rebalance_max_moves": (1, int),
}

_lock = threading.Lock()
#: component -> {key -> default} registered by subsystems at import time.
_component_defaults: Dict[str, Dict[str, Any]] = {}


def register_defaults(component: str, **defaults: Any) -> None:
    """Override library defaults for one component (kwargs surface for
    embedding applications; env vars still win over these)."""
    for key in defaults:
        if key not in _KEYS:
            raise ValueError(f"unknown policy key {key!r} "
                             f"(known: {sorted(_KEYS)})")
    with _lock:
        _component_defaults.setdefault(component, {}).update(defaults)


def _env_raw(component: str, key: str) -> Optional[str]:
    for name in (f"RSDL_{component.upper()}_{key.upper()}",
                 f"RSDL_{key.upper()}"):
        raw = os.environ.get(name)
        if raw is not None and raw.strip() != "":
            return raw
    return None


def resolve(component: str, key: str, override: Any = None,
            default: Any = None) -> Any:
    """Resolve one policy key for a component (see module docstring for
    the precedence order). ``override`` is the explicit-kwarg rung;
    ``None`` means "not given". ``default`` replaces the LIBRARY default
    (the lowest rung) — for call sites whose baseline lives in a module
    constant that must stay patchable at runtime."""
    if key not in _KEYS:
        raise ValueError(f"unknown policy key {key!r} "
                         f"(known: {sorted(_KEYS)})")
    library_default, parser = _KEYS[key]
    if override is not None:
        return parser(override) if isinstance(override, str) else override
    raw = _env_raw(component, key)
    if raw is not None:
        return parser(raw)
    with _lock:
        component_default = _component_defaults.get(component, {})
        if key in component_default:
            return component_default[key]
    return library_default if default is None else default


def resolve_all(component: str, **overrides: Any) -> Dict[str, Any]:
    """Resolve every key for a component; ``overrides`` are explicit
    kwargs (unknown keys raise, so typos fail loudly)."""
    unknown = set(overrides) - set(_KEYS)
    if unknown:
        raise ValueError(f"unknown policy keys: {sorted(unknown)} "
                         f"(known: {sorted(_KEYS)})")
    return {key: resolve(component, key, overrides.get(key))
            for key in _KEYS}


def describe(component: str = "library") -> Dict[str, Any]:
    """Resolved snapshot for diagnostics (bug reports)."""
    return resolve_all(component)
