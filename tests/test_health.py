"""Ops-plane tests: metrics federation, history ring, SLO detectors
(hysteresis: fire exactly once, no flapping), incident capsules, and the
chaos-delay -> detector -> capsule path end to end in-process."""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from ray_shuffling_data_loader_tpu.runtime import health as rt_health
from ray_shuffling_data_loader_tpu.runtime import history as rt_history
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.runtime import watchdog as rt_watchdog

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_capture_state(monkeypatch):
    """Capsule capture keeps a process-wide cooldown; tests must not
    suppress each other's captures."""
    monkeypatch.setattr(rt_health, "CAPSULE_COOLDOWN_S", 0.0)
    monkeypatch.setattr(rt_health, "_last_capture_mono", None)
    yield
    rt_health.disarm()


def _labels(**kv):
    return tuple(sorted((k, str(v)) for k, v in kv.items()))


def _snap(t, counters=None, gauges=None):
    """Synthetic history snapshot: values are scalars (unlabeled) or
    {label_tuple: value} dicts (build label tuples with ``_labels``)."""
    samples = {}
    for src in (counters or {}), (gauges or {}):
        for name, value in src.items():
            if isinstance(value, dict):
                samples[name] = dict(value)
            else:
                samples[name] = {(): float(value)}
    return {"t": t, "t_unix": 1.7e9 + t, "samples": samples}


def _ring(interval_s=0.1, capacity=400):
    return rt_history.HistoryRing(capacity=capacity, interval_s=interval_s)


# ---------------------------------------------------------------------------
# Watchdog periodic + history ring
# ---------------------------------------------------------------------------


def test_watchdog_periodic_ticks_and_cancel():
    wd = rt_watchdog.get_watchdog()
    ticks = []
    handle = wd.every(0.03, lambda: ticks.append(1), name="test-tick")
    deadline = time.monotonic() + 5.0
    while len(ticks) < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    wd.cancel(handle)
    assert len(ticks) >= 3, "periodic never ran on the monitor thread"
    count = len(ticks)
    time.sleep(0.15)
    assert len(ticks) == count, "cancel() did not stop the periodic"


def test_history_ring_capacity_series_and_rate():
    ring = _ring(capacity=10)
    for i in range(25):
        ring.append_snapshot(_snap(float(i),
                                   counters={"rsdl_events_total": 10.0 * i}))
    snaps = ring.snapshots()
    assert len(snaps) == 10, "ring must drop oldest at capacity"
    series = ring.series("rsdl_events_total")
    assert series[0][1] == 150.0 and series[-1][1] == 240.0
    rates = ring.rate("rsdl_events_total", window_ticks=2)
    assert rates and all(abs(r - 10.0) < 1e-9 for _, r in rates)


def test_history_label_filter_sums_matching_children():
    ring = _ring()
    ring.append_snapshot(_snap(0.0, counters={"rsdl_events_total": {
        _labels(kind="map_read"): 5.0, _labels(kind="convert"): 7.0}}))
    assert ring.series("rsdl_events_total")[0][1] == 12.0
    assert ring.series("rsdl_events_total",
                       {"kind": "map_read"})[0][1] == 5.0
    assert ring.series("rsdl_events_total", {"kind": "nope"}) == []


def test_history_slice_roundtrip_and_cross_pid_merge():
    ring = _ring()
    for i in range(6):
        ring.append_snapshot(_snap(float(i),
                                   counters={"rsdl_events_total": 2.0 * i}))
    blob = json.dumps(ring.slice())
    loaded = rt_history.load_slice(json.loads(blob))
    assert (loaded.series("rsdl_events_total")
            == ring.series("rsdl_events_total"))
    merged = rt_history.merged_series(
        [json.loads(blob), json.loads(blob)], "rsdl_events_total")
    assert merged[-1][1] == 2 * ring.series("rsdl_events_total")[-1][1]


def test_live_tick_snapshots_registry_and_rss():
    counter = rt_metrics.counter("rsdl_events_total", "", kind="hist-test")
    ring = _ring()
    counter.inc(3)
    ring.tick()
    counter.inc(4)
    ring.tick()
    series = ring.series("rsdl_events_total", {"kind": "hist-test"})
    assert [v for _, v in series] == [3.0, 7.0]
    assert ring.series("rsdl_process_rss_bytes"), "rss gauge not sampled"


# ---------------------------------------------------------------------------
# Detectors: hysteresis = fire exactly once per episode, no flapping
# ---------------------------------------------------------------------------


def _monitor(ring, names, fired, **overrides):
    mon = rt_health.HealthMonitor(
        ring, detectors=rt_health.default_detectors(names=names,
                                                    **overrides),
        fire_ticks=2, clear_ticks=4, capture=False,
        on_fire=lambda v: fired.append(v))
    return mon


def test_droop_fires_exactly_once_despite_noise():
    ring, fired = _ring(), []
    mon = _monitor(ring, ["throughput_droop"], fired,
                   slo_droop_window_ticks=3, slo_droop_floor_eps=1.0)
    events, t = 0.0, 0.0
    for _ in range(12):  # healthy: 100 events/tick
        events, t = events + 100, t + 0.1
        ring.append_snapshot(_snap(t, counters={"rsdl_events_total": events}))
        mon.tick()
    for i in range(14):  # drooped, with noisy trickle (1-3 events/tick)
        events, t = events + (3 if i % 4 == 0 else 1), t + 0.1
        ring.append_snapshot(_snap(t, counters={"rsdl_events_total": events}))
        mon.tick()
    assert mon.total_fires == 1, mon.summary()
    assert len(fired) == 1
    assert fired[0]["detector"] == "throughput_droop"
    # recovery + second droop = a second episode, allowed to fire again
    for _ in range(8):
        events, t = events + 100, t + 0.1
        ring.append_snapshot(_snap(t, counters={"rsdl_events_total": events}))
        mon.tick()
    for _ in range(8):
        t += 0.1
        ring.append_snapshot(_snap(t, counters={"rsdl_events_total": events}))
        mon.tick()
    assert mon.total_fires == 2


def test_droop_needs_traffic_floor():
    """An idle pipeline (peak below the floor) is not a drooping one."""
    ring, fired = _ring(), []
    mon = _monitor(ring, ["throughput_droop"], fired,
                   slo_droop_window_ticks=3, slo_droop_floor_eps=1000.0)
    events, t = 0.0, 0.0
    for i in range(20):
        events, t = events + (50 if i < 10 else 0), t + 0.1
        ring.append_snapshot(_snap(t, counters={"rsdl_events_total": events}))
        mon.tick()
    assert mon.total_fires == 0


def test_ledger_creep_fires_once_and_respects_policy_override(monkeypatch):
    def run(threshold_env):
        if threshold_env is not None:
            monkeypatch.setenv("RSDL_SLO_CREEP_MB_PER_MIN", threshold_env)
        else:
            monkeypatch.delenv("RSDL_SLO_CREEP_MB_PER_MIN", raising=False)
        ring, fired = _ring(), []
        mon = _monitor(ring, ["ledger_creep"], fired)
        t, rss = 0.0, 100 << 20
        for _ in range(30):  # +1 MiB per 0.1s tick = 600 MiB/min
            t, rss = t + 0.1, rss + (1 << 20)
            ring.append_snapshot(_snap(t, gauges={
                "rsdl_ledger_bytes_in_use": float(rss)}))
            mon.tick()
        return mon.total_fires

    assert run(None) == 1          # default 512 MiB/min < 600 -> fires once
    assert run("10000") == 0       # raised SLO: same series stays healthy
    assert run("1") == 1           # tightened SLO still fires exactly once


def test_queue_saturation_fires_once_without_flapping(monkeypatch):
    monkeypatch.setenv("RSDL_SLO_QUEUE_DEPTH", "100")
    ring, fired = _ring(), []
    mon = _monitor(ring, ["queue_saturation"], fired)
    t = 0.0
    # Oscillates around the bound WITHIN one episode (never 4 clean
    # ticks in a row): hysteresis must hold it at one fire.
    depths = [10, 10, 150, 180, 90, 200, 160, 90, 220, 150, 90, 250]
    for depth in depths:
        t += 0.1
        ring.append_snapshot(_snap(t, gauges={"rsdl_queue_depth": {
            _labels(queue="3"): float(depth)}}))
        mon.tick()
    assert mon.total_fires == 1, mon.summary()
    assert "queue 3" in fired[0]["detail"]


def test_stall_breach_detector_on_synthetic_waits():
    ring, fired = _ring(), []
    mon = _monitor(ring, ["stall_breach"], fired,
                   slo_stall_pct=50.0, slo_droop_window_ticks=3)
    t, wait_s, batches = 0.0, 0.0, 0
    for i in range(20):
        t += 0.1
        if i >= 8:  # consumer now waits 90% of each tick
            wait_s += 0.09
            batches += 1
        ring.append_snapshot(_snap(t, counters={
            "rsdl_batch_wait_seconds_sum": wait_s,
            "rsdl_batch_wait_seconds_count": float(batches)}))
        mon.tick()
    assert mon.total_fires == 1, mon.summary()


def test_lease_churn_and_straggler_drift_detectors():
    ring, fired = _ring(), []
    mon = _monitor(ring, ["lease_churn", "straggler_drift"], fired,
                   slo_lease_churn_per_min=30.0,
                   slo_straggler_drift_x=3.0,
                   slo_droop_window_ticks=3)
    t, expiries = 0.0, 0.0
    for i in range(16):
        t += 0.1
        expiries += 1 if i >= 8 else 0   # 10/s = 600/min >> 30/min
        straggler = 2.0 if i >= 10 else 0.2
        ring.append_snapshot(_snap(
            t,
            counters={"rsdl_queue_lease_expiries_total": expiries},
            gauges={"rsdl_trace_straggler_seconds": {
                _labels(stage="map_read"): straggler}}))
        mon.tick()
    names = sorted({v["detector"] for v in fired})
    assert names == ["lease_churn", "straggler_drift"], mon.summary()
    assert mon.total_fires == 2


def test_health_verdict_exported_as_metrics_and_events():
    rt_telemetry.configure()
    ring, fired = _ring(), []
    mon = _monitor(ring, ["queue_saturation"], fired, slo_queue_depth=10.0)
    t = 0.0
    for _ in range(4):
        t += 0.1
        ring.append_snapshot(_snap(t, gauges={"rsdl_queue_depth": {
            _labels(queue="0"): 99.0}}))
        mon.tick()
    state = rt_metrics.get("rsdl_health_state",
                           {"detector": "queue_saturation"})
    assert state is not None and state.value == 1.0
    breaches = rt_metrics.get("rsdl_health_breaches_total",
                              {"detector": "queue_saturation"})
    assert breaches is not None and breaches.value >= 1
    kinds = [e["kind"] for e in rt_telemetry.recorder().events()]
    assert "health_breach" in kinds


# ---------------------------------------------------------------------------
# Federation: per-pid shards merge into the cluster-wide exposition
# ---------------------------------------------------------------------------


def test_shard_write_read_merge_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("RSDL_TELEMETRY_DIR", str(tmp_path))
    rt_metrics.counter("rsdl_events_total", "", kind="fed-test").inc(5)
    path = rt_metrics.write_shard()
    assert path and os.path.basename(path) == \
        f"rsdl-metrics-{os.getpid()}.prom"
    # a second "pid"'s shard: same content under another pid's name
    import shutil
    shutil.copy(path, rt_metrics.shard_path(str(tmp_path), pid=424242))
    shards = rt_metrics.read_shards(str(tmp_path))
    assert set(shards) == {os.getpid(), 424242}
    merged, types = rt_metrics.merge_series(shards.values())
    key = (("kind", "fed-test"),)
    assert merged["rsdl_events_total"][key] == 10.0
    assert types["rsdl_events_total"] == "counter"
    # merged text round-trips through the typed parser
    text = rt_metrics.render_merged(merged, types)
    samples, parsed_types = rt_metrics.parse_exposition_typed(text)
    assert samples == merged and parsed_types == types


def test_worker_only_counter_visible_in_merged_exposition(tmp_path,
                                                          monkeypatch):
    """The PR 7 blind spot, pinned: a counter incremented ONLY inside a
    spawn-mode pool worker must appear in the merged exposition (the
    driver-only registry cannot see it), and the pool's pids must appear
    in rsdl_top's per-process view."""
    monkeypatch.setenv("RSDL_TELEMETRY_DIR", str(tmp_path))
    from ray_shuffling_data_loader_tpu import procpool
    pool = procpool.ProcessPoolExecutor(num_workers=2)
    try:
        refs = [pool.submit_kind("ping", {"worker_index": i})
                for i in range(4)]
        worker_pids = sorted({r.result()["pid"] for r in refs})
    finally:
        pool.shutdown()
    assert worker_pids and os.getpid() not in worker_pids
    shards = rt_metrics.read_shards(str(tmp_path))
    assert set(worker_pids) <= set(shards), (worker_pids, sorted(shards))
    # rsdl_worker_tasks_total lives ONLY in worker registries...
    own = rt_metrics.parse_exposition(rt_metrics.render())
    assert "rsdl_worker_tasks_total" not in own
    # ...yet the merged/federated exposition carries all 4 increments.
    merged, _types, pids = rt_metrics.federated_series()
    assert sum(merged["rsdl_worker_tasks_total"].values()) == 4.0
    assert len(pids) >= 3  # driver + 2 workers
    # rsdl_top --dir per-process view marks the pool-worker pids.
    spec = importlib.util.spec_from_file_location(
        "_rsdl_top", os.path.join(_REPO_ROOT, "tools", "rsdl_top.py"))
    rsdl_top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rsdl_top)
    merged_dir, per_pid = rsdl_top.read_shard_dir(str(tmp_path))
    # the worker-up gauge rides the DRIVER registry; merge it in the way
    # the live exporter does (driver registry + shards)
    text = rsdl_top.render_processes(per_pid, rt_metrics.federated_series()[0])
    for pid in worker_pids:
        assert f"{pid}" in text and "worker" in text, text


def test_process_backend_shuffle_federates_two_plus_pids(tmp_path, rng,
                                                         monkeypatch):
    """Acceptance: during a process-backend shuffle the merged
    exposition carries samples from >=2 pids — the map_read events live
    in WORKER registries (the driver only feeds attribution via
    observe_stage, no ring events), so their presence in the merged
    view proves federation, not driver bookkeeping."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from ray_shuffling_data_loader_tpu import procpool
    from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle

    files = []
    for i in range(2):
        n = 64
        path = str(tmp_path / f"fed_{i}.parquet")
        pq.write_table(pa.table({
            "key": pa.array(range(i * n, (i + 1) * n), type=pa.int64()),
            "labels": pa.array(rng.random(n).astype("float32"))}), path)
        files.append(path)
    shard_dir = str(tmp_path / "shards")
    monkeypatch.setenv("RSDL_TELEMETRY_DIR", shard_dir)
    pool = procpool.ProcessPoolExecutor(num_workers=2)
    try:
        run_shuffle(files, lambda ti, e, refs: [r.result() for r in refs]
                    if refs is not None else None,
                    1, num_reducers=2, num_trainers=1,
                    max_concurrent_epochs=1, seed=11, collect_stats=False,
                    file_cache=None, pool=pool)
        worker_pids = set(pool.worker_pids())
    finally:
        pool.shutdown()
    shards = rt_metrics.read_shards(shard_dir)
    assert len(set(shards) & worker_pids) >= 2, (sorted(shards),
                                                 sorted(worker_pids))
    merged, _types = rt_metrics.merge_series(shards.values())
    map_reads = sum(v for labels, v in
                    merged.get("rsdl_events_total", {}).items()
                    if dict(labels).get("kind") == "map_read")
    assert map_reads >= 2, merged.get("rsdl_events_total")


def test_federated_exposition_file_and_history_merge(tmp_path, monkeypatch):
    monkeypatch.setenv("RSDL_TELEMETRY_DIR", str(tmp_path / "shards"))
    rt_metrics.counter("rsdl_events_total", "", kind="fed-file").inc(2)
    rt_metrics.write_shard()
    out = str(tmp_path / "rsdl.prom")
    rt_metrics.write_file(out)
    parsed = rt_metrics.parse_exposition(open(out).read())
    assert parsed["rsdl_federated_processes"][()] >= 1.0


# ---------------------------------------------------------------------------
# Capsules + the end-to-end chaos-delay -> detector -> capsule path
# ---------------------------------------------------------------------------


def test_capture_incident_layout_and_cooldown(tmp_path, monkeypatch):
    monkeypatch.setenv("RSDL_INCIDENT_DIR", str(tmp_path))
    rt_telemetry.configure()
    rt_telemetry.record("map_read", epoch=0, task=0, dur_s=0.01)
    ring = _ring()
    ring.tick()
    path = rt_health.capture_incident(reason="test", ring=ring,
                                      profile_s=0.05, wait_s=0.1)
    assert path and os.path.isdir(path)
    names = sorted(os.listdir(path))
    for required in ("capsule.json", "history.json", "metrics.prom",
                     "policy.json", "traces"):
        assert required in names, names
    manifest = json.load(open(os.path.join(path, "capsule.json")))
    assert manifest["schema"] == "rsdl-incident-v1"
    assert manifest["pids"] == [os.getpid()]
    assert manifest["traces"]
    policy_blob = json.load(open(os.path.join(path, "policy.json")))
    assert "slo_droop_pct" in policy_blob["policy"]
    # cooldown: an immediate second capture is suppressed
    monkeypatch.setattr(rt_health, "CAPSULE_COOLDOWN_S", 60.0)
    assert rt_health.capture_incident(reason="again", ring=ring,
                                      profile_s=0.0, wait_s=0.0) is None


#: The droop's run a backend: the thread backend's delay fires in this
#: process; the process backend's in the pool's workers (they take the
#: spec from their environment), and its epochs 0-1 set the baseline,
#: spawn included.
_DROOP_RUNS = {
    "thread": dict(interval_s=0.05, files=3, epochs=3, delayed=(1, 2),
                   delay_ms=1000, workers=None),
    "process": dict(interval_s=0.1, files=4, epochs=5, delayed=(2, 3, 4),
                    delay_ms=1500, workers=2),
}


@pytest.mark.parametrize("backend", sorted(_DROOP_RUNS))
def test_chaos_delay_to_detector_to_capsule_end_to_end(tmp_path, rng,
                                                       monkeypatch, backend):
    """An injected reduce_gather delay droops the activity rate mid-run,
    the armed detector fires, and the auto-captured capsule parses through
    tools/rsdl_incident.py as an operator would read it. On the process
    backend the capsule's trace dumps span the driver and the pool
    workers it SIGUSR1'd. (reduce_gather, not map_read: the process
    backend's segment cache skips the decode after epoch 0.)"""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
    from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle

    run = _DROOP_RUNS[backend]
    files = []
    for i in range(run["files"]):
        n = 64
        path = str(tmp_path / f"e2e_{i}.parquet")
        pq.write_table(pa.table({
            "key": pa.array(range(i * n, (i + 1) * n), type=pa.int64()),
            "labels": pa.array(rng.random(n).astype("float32"))}), path)
        files.append(path)
    monkeypatch.setenv("RSDL_INCIDENT_DIR", str(tmp_path / "inc"))
    monkeypatch.setenv("RSDL_TRACE_DIR", str(tmp_path / "trace"))
    os.makedirs(str(tmp_path / "trace"), exist_ok=True)
    # The detector compares rates smoothed over window_ticks x interval_s:
    # only a silence longer than that window (plus fire_ticks) can read
    # as a droop, so the injected delay has to outlast it.
    spec = ",".join(f"reduce_gather:epoch{e}:delay{run['delay_ms']}"
                    for e in run["delayed"])
    monkeypatch.setenv("RSDL_CHAOS_SPEC", spec)     # a pool's workers
    rt_telemetry.configure()
    monitor = rt_health.arm(
        interval_s=run["interval_s"], capacity=600,
        detectors=("throughput_droop",),
        fire_ticks=2, clear_ticks=50, incident_dir=str(tmp_path / "inc"),
        slo_droop_window_ticks=8, slo_droop_floor_eps=2.0)
    assert monitor is not None
    rt_faults.install(spec, seed=0)                 # this process's threads
    try:
        run_shuffle(files, lambda ti, e, refs: [r.result() for r in refs]
                    if refs is not None else None,
                    run["epochs"], num_reducers=len(files), num_trainers=1,
                    max_concurrent_epochs=1, seed=7, collect_stats=False,
                    file_cache=None, executor_backend=backend,
                    num_workers=run["workers"])
        capsules = monitor.wait_captures(timeout_s=30.0)
    finally:
        rt_faults.clear()
        rt_health.disarm()
    assert monitor.total_fires >= 1, monitor.summary()
    assert capsules
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools",
                                      "rsdl_incident.py"),
         capsules[0], "--json"], capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr
    incident = json.loads(out.stdout)
    assert incident["verdict"]["detector"] == "throughput_droop"
    assert len(incident["pids"]) >= (2 if backend == "process" else 1), (
        f"the capsule's trace dumps span only {incident['pids']}")
    assert incident["activity_rates"], "capsule history slice is empty"


def test_arm_disarm_respects_health_policy_off(monkeypatch):
    monkeypatch.setenv("RSDL_HEALTH", "0")
    assert rt_health.arm() is None
    monkeypatch.delenv("RSDL_HEALTH")
    monitor = rt_health.arm(interval_s=0.05,
                            detectors=("throughput_droop",), capture=False)
    assert monitor is not None
    assert rt_health.armed_monitor() is monitor
    assert rt_health.disarm() is monitor
    assert rt_health.armed_monitor() is None


def test_install_incident_signal_main_thread():
    previous = signal.getsignal(signal.SIGUSR2)
    try:
        assert rt_health.install_incident_signal() is True
    finally:
        signal.signal(signal.SIGUSR2, previous)


# ---------------------------------------------------------------------------
# Run report
# ---------------------------------------------------------------------------


def test_rsdl_report_check_and_html_build(tmp_path, monkeypatch):
    monkeypatch.setenv("RSDL_INCIDENT_DIR", str(tmp_path))
    rt_telemetry.configure()
    rt_telemetry.record("map_read", epoch=0, task=0, dur_s=0.02)
    ring = _ring()
    rt_metrics.counter("rsdl_events_total", "", kind="report").inc(2)
    ring.tick()
    rt_metrics.counter("rsdl_events_total", "", kind="report").inc(2)
    ring.tick()
    capsule = rt_health.capture_incident(reason="report-test", ring=ring,
                                         profile_s=0.0, wait_s=0.0)
    tool = os.path.join(_REPO_ROOT, "tools", "rsdl_report.py")
    check = subprocess.run(
        [sys.executable, tool, "--check", "--capsule", capsule],
        capture_output=True, text=True, timeout=120)
    assert check.returncode == 0, check.stderr
    assert "0 invalid" in check.stdout, check.stdout
    out_html = str(tmp_path / "report.html")
    build = subprocess.run(
        [sys.executable, tool, "--capsule", capsule, "-o", out_html],
        capture_output=True, text=True, timeout=120)
    assert build.returncode == 0, build.stderr
    text = open(out_html).read()
    assert "<svg" in text and "rsdl run report" in text
    assert "Incident" in text and "report-test" in text
