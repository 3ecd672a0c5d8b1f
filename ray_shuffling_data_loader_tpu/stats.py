"""Stats / observability subsystem.

Capability parity with the reference's stats pipeline (reference:
stats.py:22-60 dataclass model, :68-200 epoch collector, :202-253 trial
collector, :255-574 CSV report writers, :580-648 humanizers + object-store
sampler), re-based on threads instead of Ray actors: collectors are
thread-safe in-process objects (the shuffle's map/reduce/consume tasks are
host threads here, so a lock replaces the actor mailbox).

Report schema: the trial CSV and epoch CSV column sets reproduce the
reference's exactly (reference: stats.py:305-355,468-505) so downstream
tooling reads either; the trial CSV additionally APPENDS the
watchdog/stall columns (``watchdog_events``, ``stall_escalations``,
``fallbacks_engaged``), the fault/recovery columns
(``faults_injected``, ``fault_retries``, ``fault_recomputes``,
``fault_quarantines``, ``fault_recoveries_exhausted``) and the
telemetry bottleneck columns (``bottleneck_stage``,
``telemetry_stall_pct``, per-stage ``p95_<stage>_ms`` — computed by
runtime/telemetry.py from flight-recorder events) — process totals at
write time — which position-indexed reference tooling never sees.
Memory utilization sampling replaces the raylet gRPC store probe
(reference: stats.py:598-632) with host RSS + native buffer-pool bytes
+ optional TPU HBM via ``device.memory_stats()``.

The watchdog/fault recorders below no longer own private integer
counters: their counts ARE typed counters in the runtime metrics
registry (runtime/metrics.py), so the Prometheus exposition and these
snapshot dicts read the same cells — ``snapshot()`` is
now a *reader* of the registry, kept for its stable dict schema.
"""

from __future__ import annotations

import csv
import datetime
import os
import threading
import time
import timeit
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.utils import fileio
from ray_shuffling_data_loader_tpu.utils.humanize import (
    human_readable_big_num, human_readable_size)
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)


# ---------------------------------------------------------------------------
# Stats model (reference: stats.py:22-60)
# ---------------------------------------------------------------------------


@dataclass
class StageStats:
    task_durations: List[float]
    stage_duration: float


@dataclass
class MapStats(StageStats):
    read_durations: List[float]


@dataclass
class ReduceStats(StageStats):
    pass


@dataclass
class ConsumeStats(StageStats):
    consume_times: List[float]


@dataclass
class ThrottleStats:
    wait_duration: float


@dataclass
class EpochStats:
    duration: float
    map_stats: MapStats
    reduce_stats: ReduceStats
    consume_stats: ConsumeStats
    throttle_stats: ThrottleStats


@dataclass
class TrialStats:
    epoch_stats: List[EpochStats]
    duration: float


@dataclass
class MemorySample:
    """One utilization sample (replaces the raylet object-store sample)."""
    timestamp: float
    rss_bytes: int
    pool_bytes: int
    hbm_bytes: int = 0
    # Reclaimable bytes the pool's free list holds for reuse — real RSS,
    # but not in-flight data.
    pool_cached_bytes: int = 0

    @property
    def object_store_bytes_used(self) -> int:
        """Reference-compatible accessor (reference: stats.py:266)."""
        return self.pool_bytes if self.pool_bytes else self.rss_bytes


# ---------------------------------------------------------------------------
# Collectors (reference: stats.py:68-253, actors -> thread-safe objects)
# ---------------------------------------------------------------------------


class EpochStatsCollector:
    """Per-epoch stage-span collector with first-start/last-done edge
    detection (reference: stats.py:68-200)."""

    def __init__(self, num_maps: int, num_reduces: int, num_consumes: int):
        self._num_maps = num_maps
        self._num_reduces = num_reduces
        self._num_consumes = num_consumes
        self._lock = threading.Lock()
        self._epoch_start_time: Optional[float] = None
        self._duration: Optional[float] = None
        self._maps_started = 0
        self._maps_done = 0
        self._map_durations: List[float] = []
        self._read_durations: List[float] = []
        self._reduces_started = 0
        self._reduces_done = 0
        self._reduce_durations: List[float] = []
        self._consumes_started = 0
        self._consumes_done = 0
        self._consume_durations: List[float] = []
        self._consume_times: List[float] = []
        self._throttle_duration = 0.0
        self._stage_start: Dict[str, Optional[float]] = {
            "map": None, "reduce": None, "consume": None}
        self._stage_duration: Dict[str, Optional[float]] = {
            "map": None, "reduce": None, "consume": None}
        self._done_event = threading.Event()
        if num_reduces == 0:
            # A host owning zero reducers (more hosts than reducers in the
            # distributed plan) has nothing to wait for: its epochs are
            # born complete, otherwise get_stats would block forever.
            self._duration = 0.0
            self._done_event.set()

    def epoch_start(self) -> None:
        with self._lock:
            self._epoch_start_time = timeit.default_timer()

    def map_start(self) -> None:
        self._stage_task_start("map")

    def map_done(self, duration: float, read_duration: float) -> None:
        with self._lock:
            self._maps_done += 1
            self._map_durations.append(duration)
            self._read_durations.append(read_duration)
            # ">=": a retried task (Executor task_retries) may re-record a
            # completion; the last-done edge extends to the latest one.
            if self._maps_done >= self._num_maps:
                self._stage_done_locked("map")

    def reduce_start(self) -> None:
        self._stage_task_start("reduce")

    def reduce_done(self, duration: float) -> None:
        with self._lock:
            self._reduces_done += 1
            self._reduce_durations.append(duration)
            if self._reduces_done >= self._num_reduces:
                self._stage_done_locked("reduce")
                # Epoch "shuffle done" edge = last reduce done
                # (reference: stats.py:152-156); a retried reduce extends it.
                assert self._epoch_start_time is not None
                self._duration = (timeit.default_timer()
                                  - self._epoch_start_time)
                self._done_event.set()

    def consume_start(self) -> None:
        self._stage_task_start("consume")

    def consume_done(self, duration: float,
                     trial_time_to_consume: float) -> None:
        with self._lock:
            self._consumes_done += 1
            self._consume_durations.append(duration)
            self._consume_times.append(trial_time_to_consume)
            if self._consumes_done >= self._num_consumes:
                self._stage_done_locked("consume")

    def throttle_done(self, duration: float) -> None:
        with self._lock:
            self._throttle_duration += duration

    def _stage_task_start(self, stage: str) -> None:
        with self._lock:
            counter = {"map": "_maps_started", "reduce": "_reduces_started",
                       "consume": "_consumes_started"}[stage]
            if getattr(self, counter) == 0:
                self._stage_start[stage] = timeit.default_timer()
            setattr(self, counter, getattr(self, counter) + 1)

    def _stage_done_locked(self, stage: str) -> None:
        start = self._stage_start[stage]
        assert start is not None, f"{stage} stage never started"
        self._stage_duration[stage] = timeit.default_timer() - start

    def wait_until_done(self, timeout: Optional[float] = None) -> bool:
        return self._done_event.wait(timeout)

    def get_stats(self) -> EpochStats:
        with self._lock:
            # ">=": task retries may record extra completions.
            assert self._maps_done >= self._num_maps, (
                f"epoch incomplete: {self._maps_done}/{self._num_maps} maps")
            assert self._reduces_done >= self._num_reduces, (
                f"epoch incomplete: {self._reduces_done}/{self._num_reduces}"
                " reduces")
            return EpochStats(
                duration=self._duration or 0.0,
                map_stats=MapStats(list(self._map_durations),
                                   self._stage_duration["map"] or 0.0,
                                   list(self._read_durations)),
                reduce_stats=ReduceStats(list(self._reduce_durations),
                                         self._stage_duration["reduce"] or 0.0),
                consume_stats=ConsumeStats(list(self._consume_durations),
                                           self._stage_duration["consume"]
                                           or 0.0,
                                           list(self._consume_times)),
                throttle_stats=ThrottleStats(self._throttle_duration))


class TrialStatsCollector:
    """Whole-trial collector: one EpochStatsCollector per epoch plus trial
    wall-clock (reference: stats.py:202-253)."""

    def __init__(self, num_epochs: int, num_maps: int, num_reduces: int,
                 num_consumes: int):
        self._num_epochs = num_epochs
        self._epochs = [
            EpochStatsCollector(num_maps, num_reduces, num_consumes)
            # The caller DECLARED this finite count (stats collection is
            # per-bounded-trial by contract; streaming runs pass no
            # collector), so pre-sizing is the static shape, not an
            # assumption: rsdl-lint: disable=static-epoch-assumption
            for _ in range(num_epochs)
        ]
        self._trial_start_time: Optional[float] = None
        self._trial_duration: Optional[float] = None
        self._lock = threading.Lock()

    def trial_start(self) -> None:
        with self._lock:
            self._trial_start_time = timeit.default_timer()

    @property
    def trial_start_time(self) -> float:
        assert self._trial_start_time is not None
        return self._trial_start_time

    def epoch(self, epoch: int) -> EpochStatsCollector:
        return self._epochs[epoch]

    # Per-task hooks mirroring the reference actor's method surface
    # (reference: shuffle.py:204-263 call sites).
    def epoch_start(self, epoch: int) -> None:
        self._epochs[epoch].epoch_start()

    def map_start(self, epoch: int) -> None:
        self._epochs[epoch].map_start()

    def map_done(self, epoch: int, duration: float,
                 read_duration: float) -> None:
        self._epochs[epoch].map_done(duration, read_duration)

    def reduce_start(self, epoch: int) -> None:
        self._epochs[epoch].reduce_start()

    def reduce_done(self, epoch: int, duration: float) -> None:
        self._epochs[epoch].reduce_done(duration)

    def consume_start(self, epoch: int) -> None:
        self._epochs[epoch].consume_start()

    def consume_done(self, epoch: int, duration: float,
                     trial_time_to_consume: float) -> None:
        self._epochs[epoch].consume_done(duration, trial_time_to_consume)

    def throttle_done(self, epoch: int, duration: float) -> None:
        self._epochs[epoch].throttle_done(duration)

    def trial_done(self) -> None:
        with self._lock:
            assert self._trial_start_time is not None
            self._trial_duration = (timeit.default_timer()
                                    - self._trial_start_time)

    def get_stats(self, timeout: Optional[float] = None) -> TrialStats:
        for collector in self._epochs:
            collector.wait_until_done(timeout)
        with self._lock:
            duration = self._trial_duration
        if duration is None:
            assert self._trial_start_time is not None
            duration = timeit.default_timer() - self._trial_start_time
        return TrialStats(
            epoch_stats=[c.get_stats() for c in self._epochs],
            duration=duration)


# ---------------------------------------------------------------------------
# Batch-wait tracking (the north-star stall metric,
# reference: examples/horovod/ray_torch_shuffle.py:186-218)
# ---------------------------------------------------------------------------


@dataclass
class BatchWaitStats:
    wait_times: List[float] = field(default_factory=list)

    def record(self, wait_s: float) -> None:
        self.wait_times.append(wait_s)

    def reset(self) -> None:
        """Drop recorded waits (e.g. to exclude warm-up/compile epochs)."""
        self.wait_times.clear()

    def summary(self) -> Dict[str, float]:
        if not self.wait_times:
            return {"mean": 0.0, "std": 0.0, "max": 0.0, "min": 0.0,
                    "total": 0.0, "count": 0}
        arr = np.asarray(self.wait_times)
        return {
            "mean": float(arr.mean()), "std": float(arr.std()),
            "max": float(arr.max()), "min": float(arr.min()),
            "total": float(arr.sum()), "count": int(len(arr)),
        }


# ---------------------------------------------------------------------------
# Watchdog / stall reporting (runtime/watchdog.py files structured reports
# here; the CSV writers read the process totals)
# ---------------------------------------------------------------------------


class WatchdogStats:
    """Process-wide sink for structured stall reports and degradation
    decisions.

    ``runtime.watchdog`` records every deadline miss (escalation 1 = the
    first miss of a watch, 2+ = the stall persisting across further
    deadline multiples); subsystems record each automatic degradation
    (e.g. the bulk-transfer path dropping to per-batch). Totals are
    monotonic — snapshot before/after a run to measure that run's
    events, the same protocol as ``spill.process_spill_totals``.
    """

    _RECENT = 32  # ring of most recent stalls kept for diagnostics

    def __init__(self):
        self._lock = threading.Lock()
        # Counts live in the metrics registry (one set of cells per
        # process — a second WatchdogStats instance shares them); only
        # the recent-stall diagnostic ring is per-instance.
        self._events = rt_metrics.counter(
            "rsdl_watchdog_events_total", "watchdog deadline misses")
        self._escalations = rt_metrics.counter(
            "rsdl_watchdog_escalations_total",
            "stalls persisting past further deadline multiples")
        self._fallbacks = rt_metrics.counter(
            "rsdl_watchdog_fallbacks_total",
            "automatic degradations engaged")
        self._recent: List[Dict[str, Any]] = []

    def record_stall(self, report) -> None:
        """``report`` is a ``runtime.watchdog.StallReport`` (duck-typed:
        name/waited_s/deadline_s/escalation/detail/timestamp)."""
        entry = {
            "name": report.name,
            "waited_s": float(report.waited_s),
            "deadline_s": float(report.deadline_s),
            "escalation": int(report.escalation),
            "detail": report.detail,
            "timestamp": float(report.timestamp),
        }
        self._events.inc()
        if report.escalation > 1:
            self._escalations.inc()
        rt_metrics.counter("rsdl_watchdog_stalls_total",
                           "deadline misses by watch name",
                           name=report.name).inc()
        rt_telemetry.record("watchdog_stall", name=report.name,
                            escalation=int(report.escalation),
                            waited_s=float(report.waited_s),
                            detail=report.detail)
        with self._lock:
            self._recent.append(entry)
            del self._recent[:-self._RECENT]

    def record_fallback(self, component: str, reason: str) -> None:
        self._fallbacks.inc()
        rt_telemetry.record("fallback", component=component, reason=reason)
        with self._lock:
            self._recent.append({
                "name": f"{component}:fallback",
                "detail": reason,
                "timestamp": time.time(),
            })
            del self._recent[:-self._RECENT]

    def snapshot(self) -> Dict[str, Any]:
        by_name: Dict[str, int] = {}
        family = rt_metrics.get("rsdl_watchdog_stalls_total")
        if family is not None and hasattr(family, "children"):
            for labels, metric in family.children().items():
                by_name[dict(labels).get("name", "?")] = int(metric.value)
        with self._lock:
            recent = list(self._recent)
        return {
            "watchdog_events": int(self._events.value),
            "stall_escalations": int(self._escalations.value),
            "fallbacks_engaged": int(self._fallbacks.value),
            "stalls_by_name": by_name,
            "recent_stalls": recent,
        }


_watchdog_stats = WatchdogStats()


def watchdog_stats() -> WatchdogStats:
    """THE process-wide watchdog/stall recorder."""
    return _watchdog_stats


# ---------------------------------------------------------------------------
# Fault / recovery accounting (runtime/faults.py injects, runtime/retry.py
# and the shuffle's lineage recovery record; the trial CSV reads the
# process totals)
# ---------------------------------------------------------------------------


class FaultStats:
    """Process-wide sink for fault-injection and recovery events.

    Counters are monotonic — snapshot before/after a run to measure that
    run's activity (the ``watchdog_stats``/``process_spill_totals``
    protocol). ``recomputes`` counts tasks re-executed successfully after
    a failure (lineage map recomputes AND in-task reduce/transfer
    re-runs); ``retries`` counts every RetryPolicy backoff taken;
    ``exhausted`` counts recoveries that ran out of attempts (the only
    failures that reach the ``ShuffleFailure`` poison pill).
    """

    _RECENT = 32

    def __init__(self):
        self._lock = threading.Lock()
        # Counts live in the metrics registry (shared per process, like
        # WatchdogStats); the quarantine report ring is per-instance.
        self._injected = rt_metrics.counter(
            "rsdl_faults_injected_total", "chaos faults fired")
        self._retries = rt_metrics.counter(
            "rsdl_fault_retries_total", "RetryPolicy backoffs taken")
        self._recomputes = rt_metrics.counter(
            "rsdl_fault_recomputes_total",
            "tasks re-executed successfully after a failure")
        self._quarantines = rt_metrics.counter(
            "rsdl_fault_quarantines_total",
            "input files dropped by on_bad_file='skip'")
        self._exhausted = rt_metrics.counter(
            "rsdl_fault_exhausted_total",
            "recoveries that ran out of attempts")
        self._recovery_latency = rt_metrics.histogram(
            "rsdl_fault_recovery_seconds", "recompute/recovery latency")
        self._recovery_latency_max = rt_metrics.gauge(
            "rsdl_fault_recovery_max_seconds",
            "largest single recovery latency")
        self._recent_quarantines: List[Dict[str, Any]] = []

    def record_injected(self, site: str, epoch=None, task=None) -> None:
        self._injected.inc()
        rt_metrics.counter("rsdl_faults_injected_by_site_total",
                           "chaos faults fired by site", site=site).inc()

    def record_retry(self, component: str) -> None:
        self._retries.inc()
        rt_telemetry.record("fault_retry", component=component)

    def record_recompute(self, component: str, latency_s: float) -> None:
        self._recomputes.inc()
        self._recovery_latency.observe(latency_s)
        self._recovery_latency_max.max(latency_s)
        rt_telemetry.record("fault_recompute", component=component,
                            latency_s=latency_s)

    def record_quarantine(self, report) -> None:
        """``report`` is a ``runtime.faults.QuarantinedFile`` (duck-typed:
        ``as_dict()``)."""
        self._quarantines.inc()
        rt_telemetry.record("fault_quarantine",
                            epoch=getattr(report, "epoch", None),
                            task=getattr(report, "file_index", None))
        with self._lock:
            self._recent_quarantines.append(report.as_dict())
            del self._recent_quarantines[:-self._RECENT]

    def record_exhausted(self, component: str) -> None:
        self._exhausted.inc()
        rt_telemetry.record("fault_exhausted", component=component)

    def snapshot(self) -> Dict[str, Any]:
        by_site: Dict[str, int] = {}
        family = rt_metrics.get("rsdl_faults_injected_by_site_total")
        if family is not None and hasattr(family, "children"):
            for labels, metric in family.children().items():
                by_site[dict(labels).get("site", "?")] = int(metric.value)
        with self._lock:
            recent = list(self._recent_quarantines)
        return {
            "injected": int(self._injected.value),
            "retries": int(self._retries.value),
            "recomputes": int(self._recomputes.value),
            "quarantines": int(self._quarantines.value),
            "exhausted": int(self._exhausted.value),
            "recovery_latency_total_s": self._recovery_latency.sum,
            "recovery_latency_max_s": self._recovery_latency_max.value,
            "injected_by_site": by_site,
            "recent_quarantines": recent,
        }

    def __getitem__(self, key: str):
        """Mapping-style access to the current totals
        (``fault_stats()["recomputes"]``)."""
        return self.snapshot()[key]


_fault_stats = FaultStats()


def fault_stats() -> FaultStats:
    """THE process-wide fault/recovery recorder."""
    return _fault_stats


# ---------------------------------------------------------------------------
# Memory utilization sampler (reference: stats.py:598-648, raylet gRPC ->
# host/pool/HBM introspection)
# ---------------------------------------------------------------------------


def _read_rss_bytes() -> int:
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, IndexError, ValueError):
        return 0


def get_memory_stats(sample_hbm: bool = False) -> MemorySample:
    """One utilization sample: process RSS, pipeline pool bytes, optional
    HBM. ``pool_bytes`` is the buffer ledger's total — file-cache tables,
    in-flight reducer outputs, and transport recv buffers (the reference's
    plasma store-utilization columns, reference: stats.py:263-270)."""
    from ray_shuffling_data_loader_tpu import native
    ledger = native.buffer_ledger()
    pool_bytes = ledger.bytes_in_use()
    pool_cached = ledger.freelist_bytes()
    hbm = 0
    if sample_hbm:
        try:
            import jax
            for dev in jax.local_devices():
                stats = dev.memory_stats()
                if stats:
                    hbm += stats.get("bytes_in_use", 0)
        except Exception:  # noqa: BLE001 - sampling must never kill a trial
            hbm = 0
    return MemorySample(timestamp=time.time(), rss_bytes=_read_rss_bytes(),
                        pool_bytes=pool_bytes, hbm_bytes=hbm,
                        pool_cached_bytes=pool_cached)


def collect_store_stats(stats_list: List[Tuple[float, MemorySample]],
                        done_event: threading.Event,
                        sample_period_s: float = 5.0,
                        sample_hbm: bool = False) -> None:
    """Sampler loop body: append (timestamp, sample) until done_event is set
    (reference: stats.py:635-648). Run it in a daemon thread."""
    while not done_event.is_set():
        sample = get_memory_stats(sample_hbm=sample_hbm)
        stats_list.append((sample.timestamp, sample))
        done_event.wait(sample_period_s)


def start_store_stats_sampler(
        stats_list: List[Tuple[float, MemorySample]],
        sample_period_s: float = 5.0,
        sample_hbm: bool = False) -> threading.Event:
    """Spawn the sampler thread; returns the event that stops it
    (reference: shuffle.py:32-37 thread wiring)."""
    done = threading.Event()
    thread = threading.Thread(
        target=collect_store_stats,
        args=(stats_list, done, sample_period_s, sample_hbm),
        daemon=True, name="rsdl-store-stats")
    thread.start()
    return done


# ---------------------------------------------------------------------------
# CSV report writers (reference: stats.py:255-574; identical column sets)
# ---------------------------------------------------------------------------


def _spread(prefix: str, values: List[float]) -> Dict[str, float]:
    arr = np.asarray(values) if values else np.asarray([0.0])
    return {
        f"avg_{prefix}": float(arr.mean()),
        f"std_{prefix}": float(arr.std()),
        f"max_{prefix}": float(arr.max()),
        f"min_{prefix}": float(arr.min()),
    }


TRIAL_FIELDNAMES = [
    "num_files", "num_row_groups_per_file", "num_reducers", "num_trainers",
    "num_epochs", "max_concurrent_epochs", "trial", "duration",
    "row_throughput", "batch_throughput", "batch_throughput_per_trainer",
    "avg_object_store_utilization", "max_object_store_utilization",
    "avg_epoch_duration", "std_epoch_duration", "max_epoch_duration",
    "min_epoch_duration",
    "avg_map_stage_duration", "std_map_stage_duration",
    "max_map_stage_duration", "min_map_stage_duration",
    "avg_reduce_stage_duration", "std_reduce_stage_duration",
    "max_reduce_stage_duration", "min_reduce_stage_duration",
    "avg_consume_stage_duration", "std_consume_stage_duration",
    "max_consume_stage_duration", "min_consume_stage_duration",
    "avg_map_task_duration", "std_map_task_duration",
    "max_map_task_duration", "min_map_task_duration",
    "avg_read_duration", "std_read_duration", "max_read_duration",
    "min_read_duration",
    "avg_reduce_task_duration", "std_reduce_task_duration",
    "max_reduce_task_duration", "min_reduce_task_duration",
    "avg_consume_task_duration", "std_consume_task_duration",
    "max_consume_task_duration", "min_consume_task_duration",
    "avg_time_to_consume", "std_time_to_consume", "max_time_to_consume",
    "min_time_to_consume",
    # Appended past the reference's column set (see module docstring).
    "watchdog_events", "stall_escalations", "fallbacks_engaged",
    # Fault/recovery totals (fault_stats(); process totals at write time).
    "faults_injected", "fault_retries", "fault_recomputes",
    "fault_quarantines", "fault_recoveries_exhausted",
    # Telemetry bottleneck verdict (runtime/telemetry.py run summary at
    # write time: stage with the largest work share when the consumer's
    # batch-wait share exceeds the stall threshold, else train_step).
    "bottleneck_stage", "telemetry_stall_pct",
    "p95_map_read_ms", "p95_reduce_ms", "p95_queue_wait_ms",
    "p95_fetch_ms", "p95_convert_ms", "p95_device_transfer_ms",
    "p95_train_step_ms",
    # Process-crash recovery totals (multiqueue_service v2 +
    # runtime/supervisor.py; process totals at write time): frames
    # re-sent from the server replay buffer, supervised queue-server
    # restarts, and consumer-lease expiries.
    "queue_frames_replayed", "queue_server_restarts",
    "queue_lease_expiries",
    # Serving-plane byte honesty (multiqueue_service v3; process totals
    # at write time): actual socket payload bytes vs shm-handle
    # deliveries, the compression win, and the shard count — so a wire
    # regression is attributable to the serving layer from the CSV
    # alone, not inferred from end-to-end rates.
    "queue_bytes_on_wire", "queue_handle_hits", "queue_handle_misses",
    "queue_compression_ratio", "serve_shards",
]


def _counter_total(name: str) -> int:
    """Process-lifetime total of a registry counter (0 if never made)."""
    family = rt_metrics.get(name)
    if family is None:
        return 0
    if hasattr(family, "children"):
        return int(sum(m.value for m in family.children().values()))
    return int(family.value)


def process_recovery_totals() -> Dict[str, int]:
    """Queue-service crash-recovery counters (monotonic; snapshot
    before/after a run — the ``watchdog_stats`` protocol)."""
    return {
        "queue_frames_replayed": _counter_total(
            "rsdl_queue_frames_replayed_total"),
        "queue_server_restarts": _counter_total(
            "rsdl_queue_server_restarts_total"),
        "queue_lease_expiries": _counter_total(
            "rsdl_queue_lease_expiries_total"),
        "queue_frames_nacked": _counter_total(
            "rsdl_queue_frames_nacked_total"),
        "queue_frames_corrupt": _counter_total(
            "rsdl_queue_frames_corrupt_total"),
        "queue_client_reconnects": _counter_total(
            "rsdl_queue_client_reconnects_total"),
    }


def queue_serve_totals() -> Dict[str, Any]:
    """Serving-plane byte/handle accounting (multiqueue_service v3;
    monotonic process totals — snapshot before/after a run to attribute
    a window). ``queue_compression_ratio`` is logical-over-wire for the
    streamed frames compression actually touched (1.0 = off/no win)."""
    payload = _counter_total("rsdl_queue_payload_bytes_total")
    wire = _counter_total("rsdl_queue_bytes_on_wire_total")
    saved = _counter_total("rsdl_queue_compression_saved_bytes_total")
    compressed_wire = None
    ratio = 1.0
    if saved:
        # saved = logical - wire over exactly the compressed frames, so
        # the compressed share of the wire is recoverable from totals
        # only when every streamed byte was compressed; report the
        # conservative whole-stream ratio instead.
        compressed_wire = max(1, wire)
        ratio = (wire + saved) / compressed_wire
    return {
        "queue_payload_bytes": payload,
        "queue_bytes_on_wire": wire,
        "queue_handle_hits": _counter_total(
            "rsdl_queue_handle_hits_total"),
        "queue_handle_misses": _counter_total(
            "rsdl_queue_handle_misses_total"),
        "queue_compression_saved_bytes": saved,
        "queue_compression_ratio": round(ratio, 4),
        "serve_shards": int(_counter_total("rsdl_queue_serve_shards")),
    }

EPOCH_FIELDNAMES = [
    "num_files", "num_row_groups_per_file", "num_reducers", "num_trainers",
    "num_epochs", "max_concurrent_epochs", "trial", "epoch", "duration",
    "row_throughput", "batch_throughput", "batch_throughput_per_trainer",
    "map_stage_duration", "reduce_stage_duration", "consume_stage_duration",
    "avg_map_task_duration", "std_map_task_duration",
    "max_map_task_duration", "min_map_task_duration",
    "avg_read_duration", "std_read_duration", "max_read_duration",
    "min_read_duration",
    "avg_reduce_task_duration", "std_reduce_task_duration",
    "max_reduce_task_duration", "min_reduce_task_duration",
    "avg_consume_task_duration", "std_consume_task_duration",
    "max_consume_task_duration", "min_consume_task_duration",
    "avg_time_to_consume", "std_time_to_consume", "max_time_to_consume",
    "min_time_to_consume",
]


def process_stats(all_stats: List[Tuple[TrialStats, List[Tuple[float, MemorySample]]]],
                  overwrite_stats: bool,
                  stats_dir: str,
                  no_epoch_stats: bool,
                  unique_stats: bool,
                  num_rows: int,
                  num_files: int,
                  num_row_groups_per_file: int,
                  batch_size: int,
                  num_reducers: int,
                  num_trainers: int,
                  num_epochs: int,
                  max_concurrent_epochs: int) -> None:
    """Write trial + epoch CSVs and print the summary
    (reference: stats.py:255-574; same signature, same columns)."""
    fileio.makedirs(stats_dir)
    stats_list = [s for s, _ in all_stats]
    store_stats_list = [ss for _, ss in all_stats]
    times = [s.duration for s in stats_list]
    mean, std = float(np.mean(times)), float(np.std(times))
    all_samples = [sample.object_store_bytes_used
                   for trial_ss in store_stats_list
                   for _, sample in trial_ss]
    num_samples = len(all_samples)
    max_util = human_readable_size(max(all_samples)) if all_samples else "0 B"
    throughput_std = float(np.std(
        [num_epochs * num_rows / t for t in times]))
    batch_tp_std = float(np.std(
        [(num_epochs * num_rows / batch_size) / t for t in times]))
    print(f"\nMean over {len(times)} trials: {mean:.3f}s +- {std}")
    print(f"Mean throughput over {len(times)} trials: "
          f"{num_epochs * num_rows / mean:.2f} rows/s +- {throughput_std:.2f}")
    print(f"Mean batch throughput over {len(times)} trials: "
          f"{(num_epochs * num_rows / batch_size) / mean:.2f} batches/s +- "
          f"{batch_tp_std:.2f}")
    print(f"Max memory utilization over {num_samples} samples: {max_util}\n")

    write_mode = "w+" if overwrite_stats else "a+"
    hr_rows = human_readable_big_num(num_rows)
    hr_batch = human_readable_big_num(batch_size)
    now = datetime.datetime.now(datetime.timezone.utc).isoformat()

    def _open_report(kind: str):
        filename = f"{kind}_stats_{hr_rows}_rows_{hr_batch}_batch_size"
        filename += f"_{now}.csv" if unique_stats else ".csv"
        path = fileio.join(stats_dir, filename)
        header = (overwrite_stats or fileio.file_size(path) == 0)
        return path, header

    static = {
        "num_files": num_files,
        "num_row_groups_per_file": num_row_groups_per_file,
        "num_reducers": num_reducers,
        "num_trainers": num_trainers,
        "num_epochs": num_epochs,
        "max_concurrent_epochs": max_concurrent_epochs,
    }

    wd = watchdog_stats().snapshot()
    fs = fault_stats().snapshot()
    recovery = process_recovery_totals()
    serve = queue_serve_totals()
    verdict = rt_telemetry.attribution().run_summary() or {}
    verdict_stages = verdict.get("stages", {})

    path, header = _open_report("trial")
    logger.info("Writing trial stats to %s", path)
    with fileio.open_text(path, write_mode) as f:
        writer = csv.DictWriter(f, fieldnames=TRIAL_FIELDNAMES)
        if header:
            writer.writeheader()
        for trial, (stats, trial_ss) in enumerate(all_stats):
            row: Dict[str, Any] = dict(static)
            row["trial"] = trial
            row["watchdog_events"] = wd["watchdog_events"]
            row["stall_escalations"] = wd["stall_escalations"]
            row["fallbacks_engaged"] = wd["fallbacks_engaged"]
            row["faults_injected"] = fs["injected"]
            row["fault_retries"] = fs["retries"]
            row["fault_recomputes"] = fs["recomputes"]
            row["fault_quarantines"] = fs["quarantines"]
            row["fault_recoveries_exhausted"] = fs["exhausted"]
            row["bottleneck_stage"] = verdict.get("bottleneck_stage", "")
            row["telemetry_stall_pct"] = verdict.get("stall_pct", 0.0)
            row["queue_frames_replayed"] = recovery["queue_frames_replayed"]
            row["queue_server_restarts"] = recovery["queue_server_restarts"]
            row["queue_lease_expiries"] = recovery["queue_lease_expiries"]
            row["queue_bytes_on_wire"] = serve["queue_bytes_on_wire"]
            row["queue_handle_hits"] = serve["queue_handle_hits"]
            row["queue_handle_misses"] = serve["queue_handle_misses"]
            row["queue_compression_ratio"] = serve[
                "queue_compression_ratio"]
            row["serve_shards"] = serve["serve_shards"]
            for stage in rt_telemetry.STAGES:
                row[f"p95_{stage}_ms"] = verdict_stages.get(
                    stage, {}).get("p95_ms", 0.0)
            row["duration"] = stats.duration
            row_tp = num_epochs * num_rows / stats.duration
            row["row_throughput"] = row_tp
            row["batch_throughput"] = row_tp / batch_size
            row["batch_throughput_per_trainer"] = (
                row_tp / batch_size / num_trainers)
            samples = [s.object_store_bytes_used for _, s in trial_ss]
            row["avg_object_store_utilization"] = (
                float(np.mean(samples)) if samples else 0.0)
            row["max_object_store_utilization"] = (
                float(np.max(samples)) if samples else 0.0)
            row.update(_spread("epoch_duration",
                               [e.duration for e in stats.epoch_stats]))
            row.update(_spread(
                "map_stage_duration",
                [e.map_stats.stage_duration for e in stats.epoch_stats]))
            row.update(_spread(
                "reduce_stage_duration",
                [e.reduce_stats.stage_duration for e in stats.epoch_stats]))
            row.update(_spread(
                "consume_stage_duration",
                [e.consume_stats.stage_duration for e in stats.epoch_stats]))
            row.update(_spread(
                "map_task_duration",
                [d for e in stats.epoch_stats
                 for d in e.map_stats.task_durations]))
            row.update(_spread(
                "read_duration",
                [d for e in stats.epoch_stats
                 for d in e.map_stats.read_durations]))
            row.update(_spread(
                "reduce_task_duration",
                [d for e in stats.epoch_stats
                 for d in e.reduce_stats.task_durations]))
            row.update(_spread(
                "consume_task_duration",
                [d for e in stats.epoch_stats
                 for d in e.consume_stats.task_durations]))
            row.update(_spread(
                "time_to_consume",
                [d for e in stats.epoch_stats
                 for d in e.consume_stats.consume_times]))
            writer.writerow(row)

    if no_epoch_stats:
        return
    path, header = _open_report("epoch")
    logger.info("Writing epoch stats to %s", path)
    with fileio.open_text(path, write_mode) as f:
        writer = csv.DictWriter(f, fieldnames=EPOCH_FIELDNAMES)
        if header:
            writer.writeheader()
        for trial, (stats, _) in enumerate(all_stats):
            for epoch, e in enumerate(stats.epoch_stats):
                row = dict(static)
                row["trial"] = trial
                row["epoch"] = epoch
                row["duration"] = e.duration
                row_tp = num_rows / e.duration if e.duration else 0.0
                row["row_throughput"] = row_tp
                row["batch_throughput"] = row_tp / batch_size
                row["batch_throughput_per_trainer"] = (
                    row_tp / batch_size / num_trainers)
                row["map_stage_duration"] = e.map_stats.stage_duration
                row["reduce_stage_duration"] = e.reduce_stats.stage_duration
                row["consume_stage_duration"] = (
                    e.consume_stats.stage_duration)
                row.update(_spread("map_task_duration",
                                   e.map_stats.task_durations))
                row.update(_spread("read_duration",
                                   e.map_stats.read_durations))
                row.update(_spread("reduce_task_duration",
                                   e.reduce_stats.task_durations))
                row.update(_spread("consume_task_duration",
                                   e.consume_stats.task_durations))
                row.update(_spread("time_to_consume",
                                   e.consume_stats.consume_times))
                writer.writerow(row)
