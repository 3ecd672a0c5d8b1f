"""Telemetry spine tests: flight-recorder ring semantics, chaos/telemetry
correlation through a real 2-epoch run, histogram bucket math, exposition
round-trip through the hand-rolled parser, SIGUSR1 dumps in a subprocess,
and the bottleneck-verdict regression (a delay-injected slow reduce must
be named by the verdict)."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from ray_shuffling_data_loader_tpu import data_generation as dg
from ray_shuffling_data_loader_tpu import stats as stats_mod
from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset
from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
from ray_shuffling_data_loader_tpu.runtime import metrics
from ray_shuffling_data_loader_tpu.runtime import telemetry
from ray_shuffling_data_loader_tpu.runtime import watchdog as rt_watchdog

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_telemetry():
    """Each test gets a fresh ring + attributor (the metrics registry is
    process-global by design; tests read deltas or per-instance state)."""
    telemetry.configure(enabled_flag=True)
    yield
    telemetry.configure()


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_ring_buffer_overwrites_oldest_keeps_order():
    rec = telemetry.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record((float(i), "k", None, i, None, None, None, None))
    assert rec.total_recorded == 20
    events = rec.events()
    assert len(events) == 8
    # The retained window is the LAST capacity events, oldest first.
    assert [e["task"] for e in events] == list(range(12, 20))


def test_ring_buffer_partial_fill_in_order():
    rec = telemetry.FlightRecorder(capacity=16)
    for i in range(5):
        rec.record((float(i), "k", 0, i, None, 0.1, None, {"x": i}))
    events = rec.events()
    assert [e["task"] for e in events] == [0, 1, 2, 3, 4]
    assert events[0]["x"] == 0 and events[0]["dur_s"] == 0.1


def test_record_disabled_is_free_and_records_nothing():
    telemetry.configure(enabled_flag=False)
    before = telemetry.recorder().total_recorded
    telemetry.record("map_read", epoch=0, task=0, dur_s=1.0)
    assert telemetry.recorder().total_recorded == before


def test_span_records_duration_event():
    with telemetry.span("convert", epoch=3, batch=7):
        time.sleep(0.01)
    events = telemetry.recorder().events()
    ev = [e for e in events if e["kind"] == "convert"][-1]
    assert ev["epoch"] == 3 and ev["batch"] == 7
    assert ev["dur_s"] >= 0.009


def test_measured_record_overhead_is_tiny():
    per_event = telemetry.measure_record_overhead(samples=500)
    assert per_event < 5e-5  # 50us is already 10x the observed cost


# ---------------------------------------------------------------------------
# Correlation: chaos faults join stage events by (kind, epoch, task)
# ---------------------------------------------------------------------------


def test_chaos_and_telemetry_correlate_through_two_epoch_run(
        tmp_parquet_dir):
    filenames, _ = dg.generate_data_local(300, 3, 1, 0.0, tmp_parquet_dir)
    rt_faults.install("map_read:epoch1:file0", seed=0)
    try:
        # file_cache=None: the epoch-1 read must hit the real fault
        # site, not the RAM cache.
        ds = ShufflingDataset(filenames, 2, num_trainers=1, batch_size=50,
                              rank=0, num_reducers=2, file_cache=None,
                              queue_name="telemetry-correlate")
        for epoch in range(2):
            ds.set_epoch(epoch)
            assert sum(t.num_rows for t in ds) == 300
    finally:
        rt_faults.clear()
    events = telemetry.recorder().events()
    faults = [e for e in events if e.get("fault") == "injected"]
    assert faults, "injected fault never reached the flight recorder"
    fault = faults[0]
    assert (fault["kind"], fault["epoch"], fault["task"]) == \
        ("map_read", 1, 0)
    # The recovered (lineage-recomputed) read records a stage event with
    # the SAME key — the join the chaos/telemetry contract promises.
    joined = [e for e in events
              if "fault" not in e and "dur_s" in e
              and (e["kind"], e.get("epoch"), e.get("task"))
              == ("map_read", 1, 0)]
    assert joined, "no map_read stage event joins the injected fault"
    # Both epochs are represented across the stage vocabulary.
    for epoch in (0, 1):
        kinds = {e["kind"] for e in events if e.get("epoch") == epoch}
        assert {"map_read", "reduce_gather", "queue_wait"} <= kinds, kinds


# ---------------------------------------------------------------------------
# Histogram bucket math
# ---------------------------------------------------------------------------


def test_histogram_bucket_assignment_and_percentiles():
    h = metrics.Histogram(bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.bucket_counts() == [1, 2, 1, 1]  # (<=1, <=2, <=4, +Inf]
    assert h.count == 5
    assert h.sum == pytest.approx(106.5)
    assert 0.0 < h.percentile(0.5) <= 2.0
    # Values in the +Inf bucket floor at the largest finite bound.
    assert h.percentile(1.0) == 4.0
    with pytest.raises(ValueError):
        h.percentile(1.5)


def test_histogram_merge_adds_counts_and_rejects_mismatched_bounds():
    a = metrics.Histogram(bounds=(1.0, 2.0))
    b = metrics.Histogram(bounds=(1.0, 2.0))
    a.observe(0.5)
    b.observe(1.5)
    b.observe(5.0)
    a.merge(b)
    assert a.count == 3
    assert a.bucket_counts() == [1, 1, 1]
    assert a.sum == pytest.approx(7.0)
    with pytest.raises(ValueError):
        a.merge(metrics.Histogram(bounds=(1.0, 3.0)))


def test_counter_monotonic_and_gauge_set():
    c = metrics.counter("test_tele_counter_total", "t")
    base = c.value
    c.inc()
    c.inc(2.5)
    assert c.value == pytest.approx(base + 3.5)
    with pytest.raises(ValueError):
        c.inc(-1)
    g = metrics.gauge("test_tele_gauge", "t")
    g.set(7)
    g.dec(2)
    assert g.value == 5.0
    assert metrics.get("test_tele_gauge") is g


# ---------------------------------------------------------------------------
# Exposition round-trip
# ---------------------------------------------------------------------------


def test_exposition_round_trips_through_hand_rolled_parser():
    metrics.counter("test_expo_requests_total", "requests",
                    site="map_read").inc(41)
    metrics.counter("test_expo_requests_total", "requests",
                    site='we"ird\nname').inc()
    metrics.gauge("test_expo_depth", "queue depth").set(3.25)
    h = metrics.histogram("test_expo_latency_seconds", "lat",
                          buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(10.0)
    parsed = metrics.parse_exposition(metrics.render())
    req = parsed["test_expo_requests_total"]
    assert req[(("site", "map_read"),)] == 41.0
    assert req[(("site", 'we"ird\nname'),)] == 1.0
    assert parsed["test_expo_depth"][()] == 3.25
    buckets = parsed["test_expo_latency_seconds_bucket"]
    assert buckets[(("le", "0.1"),)] == 1.0
    assert buckets[(("le", "1"),)] == 2.0
    assert buckets[(("le", "+Inf"),)] == 3.0
    assert parsed["test_expo_latency_seconds_count"][()] == 3.0
    assert parsed["test_expo_latency_seconds_sum"][()] == \
        pytest.approx(10.55)


def test_exposition_file_and_http_endpoint(tmp_path):
    import urllib.request
    metrics.counter("test_expo_file_total", "t").inc(5)
    path = metrics.write_file(str(tmp_path / "metrics.prom"))
    with open(path) as f:
        parsed = metrics.parse_exposition(f.read())
    assert parsed["test_expo_file_total"][()] >= 5.0
    server, port = metrics.start_http_server(0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            body = resp.read().decode()
        assert metrics.parse_exposition(body)["test_expo_file_total"][()] \
            >= 5.0
    finally:
        server.shutdown()


def test_rsdl_top_renders_from_exposition(tmp_path):
    """The tail CLI parses real exposition without the package import."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "rsdl_top", os.path.join(REPO_ROOT, "tools", "rsdl_top.py"))
    rsdl_top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rsdl_top)
    metrics.histogram("rsdl_stage_seconds", "s",
                      stage="reduce").observe(0.02)
    # Serving-plane shard line (multiqueue_service v3 per-shard series).
    metrics.gauge("rsdl_queue_shard_depth", "d", shard="0").set(4)
    metrics.counter("rsdl_queue_handle_hits_total", "h", shard="0").inc(9)
    metrics.counter("rsdl_queue_handle_misses_total", "m",
                    shard="0").inc(1)
    metrics.counter("rsdl_queue_bytes_on_wire_total", "w",
                    shard="0").inc(2048)
    path = metrics.write_file(str(tmp_path / "m.prom"))
    parsed = rsdl_top.read_exposition(file=path)
    table = rsdl_top.render(parsed)
    assert "reduce" in table
    # Per-shard serving-plane line: present, with the hit share computed
    # from the SAME exposition (the process registry is shared across
    # tests, so the absolute counts here are cumulative, not ours).
    hits = rsdl_top._by_label(parsed, "rsdl_queue_handle_hits_total",
                              "shard")["0"]
    misses = rsdl_top._by_label(parsed, "rsdl_queue_handle_misses_total",
                                "shard")["0"]
    expect_pct = 100.0 * hits / (hits + misses)
    assert "shard 0" in table
    assert f"handle-hit {expect_pct:5.1f}%" in table
    assert rsdl_top.main([f"--file={path}", "--once"]) == 0


# ---------------------------------------------------------------------------
# SIGUSR1 dump (subprocess: signal handlers are process-global state)
# ---------------------------------------------------------------------------


def test_sigusr1_dump_in_subprocess(tmp_path):
    dump_dir = str(tmp_path / "dumps")
    child_code = """
import os, sys, time
from ray_shuffling_data_loader_tpu.runtime import telemetry
assert telemetry.install_signal_dump()
telemetry.record("map_read", epoch=0, task=1, dur_s=0.01)
print("READY", flush=True)
time.sleep(60)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["RSDL_TELEMETRY_DUMP_DIR"] = dump_dir
    proc = subprocess.Popen([sys.executable, "-c", child_code],
                            stdout=subprocess.PIPE, text=True, env=env,
                            cwd=REPO_ROOT)
    try:
        assert proc.stdout.readline().strip() == "READY"
        os.kill(proc.pid, signal.SIGUSR1)
        deadline = time.monotonic() + 30
        dumps = []
        while time.monotonic() < deadline and not dumps:
            if os.path.isdir(dump_dir):
                dumps = sorted(os.listdir(dump_dir))
            time.sleep(0.05)
        assert dumps, "SIGUSR1 produced no dump file"
        lines = [json.loads(line) for line in
                 open(os.path.join(dump_dir, dumps[0]))]
    finally:
        proc.kill()
        proc.wait(timeout=30)
    meta = lines[0]
    assert meta["kind"] == "dump_meta" and "signal" in meta["reason"]
    kinds = {line["kind"] for line in lines}
    assert "map_read" in kinds
    stacks = [line for line in lines if line["kind"] == "thread_stack"]
    assert stacks, "dump carries no thread stacks"
    assert any(s["thread"] == "MainThread" for s in stacks)


def test_watchdog_escalation_triggers_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("RSDL_TELEMETRY_DUMP_DIR", str(tmp_path / "wd"))
    wd = rt_watchdog.Watchdog(poll_interval_s=0.01)
    with wd.watch("test.telemetry_dump", deadline_s=0.05):
        time.sleep(0.25)  # >= 2 deadline multiples -> escalation 2
    dump_dir = str(tmp_path / "wd")
    deadline = time.monotonic() + 5
    dumps = []
    while time.monotonic() < deadline and not dumps:
        if os.path.isdir(dump_dir):
            dumps = os.listdir(dump_dir)
        time.sleep(0.02)
    assert dumps, "watchdog escalation did not dump the flight recorder"
    lines = [json.loads(line)
             for line in open(os.path.join(dump_dir, sorted(dumps)[0]))]
    assert "watchdog escalation" in lines[0]["reason"]
    assert any(line["kind"] == "watchdog_stall" for line in lines)


# ---------------------------------------------------------------------------
# Chaos delay grammar (the slow-stage injection the verdict test uses)
# ---------------------------------------------------------------------------


def test_chaos_delay_rule_parses_and_sleeps():
    rules = rt_faults.parse_spec("reduce_gather:delay60")
    assert rules[0].delay_ms == 60
    rt_faults.install("reduce_gather:delay60", seed=0)
    try:
        start = time.monotonic()
        rt_faults.inject("reduce_gather", epoch=0, task=0)  # must NOT raise
        assert time.monotonic() - start >= 0.05
        # Fires once per (site, epoch, task) key, like failure rules.
        start = time.monotonic()
        rt_faults.inject("reduce_gather", epoch=0, task=0)
        assert time.monotonic() - start < 0.05
    finally:
        rt_faults.clear()


def test_bottleneck_verdict_names_delayed_reduce(tmp_parquet_dir):
    """Regression: a slow reduce (chaos delay) must be the verdict."""
    filenames, _ = dg.generate_data_local(240, 2, 1, 0.0, tmp_parquet_dir)
    rt_faults.install("reduce_gather:delay150", seed=0)
    try:
        ds = JaxShufflingDataset(
            filenames, num_epochs=2, num_trainers=1, batch_size=40, rank=0,
            feature_columns=list(dg.FEATURE_COLUMNS),
            feature_types=[np.int32] * len(dg.FEATURE_COLUMNS),
            label_column=dg.LABEL_COLUMN, num_reducers=2,
            queue_name="telemetry-verdict", device_put=False)
        for epoch in range(2):
            ds.set_epoch(epoch)
            rows = sum(label.shape[0] for _, label in ds)
            assert rows == 240
    finally:
        rt_faults.clear()
    summary = telemetry.attribution().run_summary()
    assert summary is not None
    assert summary["stall_pct"] > 10.0, summary
    assert summary["bottleneck_stage"] == "reduce", summary
    assert summary["stages"]["reduce"]["p95_ms"] >= 100.0, summary
    # Per-epoch verdicts exist for both epochs too.
    for epoch in (0, 1):
        verdict = telemetry.attribution().epoch_verdict(epoch)
        assert verdict and verdict["stages"].get("reduce"), (epoch, verdict)


def test_trial_csv_gains_bottleneck_columns(tmp_path):
    """The appended telemetry columns land in the trial CSV schema and
    carry the current run summary."""
    import csv
    telemetry.record("reduce_gather", epoch=0, task=0, dur_s=0.5)
    telemetry.record("batch_wait", epoch=0, dur_s=0.4)
    collector = stats_mod.TrialStatsCollector(1, 1, 1, 1)
    collector.trial_start()
    collector.epoch_start(0)
    collector.map_start(0)
    collector.map_done(0, 0.01, 0.005)
    collector.reduce_start(0)
    collector.reduce_done(0, 0.01)
    collector.consume_start(0)
    collector.consume_done(0, 0.01, 0.01)
    collector.trial_done()
    stats_mod.process_stats(
        [(collector.get_stats(timeout=5), [])], overwrite_stats=True,
        stats_dir=str(tmp_path), no_epoch_stats=True, unique_stats=False,
        num_rows=100, num_files=1, num_row_groups_per_file=1,
        batch_size=10, num_reducers=1, num_trainers=1, num_epochs=1,
        max_concurrent_epochs=1)
    trial_csv = list(tmp_path.glob("trial_stats_*.csv"))[0]
    with open(trial_csv) as f:
        row = list(csv.DictReader(f))[0]
    assert row["bottleneck_stage"] == "reduce"
    assert float(row["telemetry_stall_pct"]) > 10.0
    assert float(row["p95_reduce_ms"]) > 0.0


# ---------------------------------------------------------------------------
# One span vocabulary: consumer spans, CPU beside wall, the epoch turnover
# ---------------------------------------------------------------------------


def _three_epochs(tmp_parquet_dir, queue_name, **kwargs):
    filenames, _ = dg.generate_data_local(1200, 2, 1, 0.0, tmp_parquet_dir)
    ds = JaxShufflingDataset(
        filenames, num_epochs=3, num_trainers=1, batch_size=100, rank=0,
        num_reducers=2, queue_name=queue_name,
        feature_columns=list(dg.FEATURE_COLUMNS),
        feature_types=[np.int32] * len(dg.FEATURE_COLUMNS),
        label_column=dg.LABEL_COLUMN, **kwargs)
    for epoch in range(3):
        ds.set_epoch(epoch)
        assert sum(label.shape[0] for _, label in ds) == 1200
    # The last transfers' spans close on the reaper's thread.
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        events = telemetry.recorder().events()
        spans = [e for e in events
                 if e["kind"] == "device_transfer" and "dur_s" in e]
        attempts = [e for e in events if e.get("attempt")]
        if len(spans) >= len(attempts):
            break
        time.sleep(0.01)
    return telemetry.recorder().events()


def _cpu_clock_step():
    """The step ``time.thread_time`` moves by: nanoseconds where the
    kernel accounts a thread's CPU time precisely (0.0 then), a scheduler
    tick of some 10 ms where it does not."""
    step = None
    prev = time.thread_time()
    end = time.monotonic() + 0.05
    while time.monotonic() < end:
        now = time.thread_time()
        if now != prev:
            step = now - prev if step is None else min(step, now - prev)
            prev = now
    return 0.0 if step is not None and step < 1e-4 else (step or 0.05)


def test_consumer_spans_carry_cpu_within_wall(tmp_parquet_dir):
    """Every consumer-thread span of a bulk-path run carries ``cpu_s``
    (``thread_time`` at both ends, read inside the wall reads), and a
    sleeping span shows the difference."""
    tick = _cpu_clock_step()
    sp = telemetry.span_begin("carve", epoch=0, cpu=True)
    try:
        time.sleep(0.05)
    finally:
        telemetry.span_end(sp)
    assert sp.cpu_s <= sp.dur_s + tick and sp.dur_s - sp.cpu_s >= 0.03
    events = _three_epochs(tmp_parquet_dir, "telemetry-cpu",
                           device_rebatch=True)
    by_kind = {}
    for e in events:
        by_kind.setdefault(e["kind"], []).append(e)
    for kind in ("batch_wait", "carve", "set_epoch", "epoch_end"):
        assert by_kind[kind], kind
        for e in by_kind[kind]:
            assert 0.0 <= e["cpu_s"] <= e["dur_s"] + tick, e
    # Children of the epoch's end name it as their parent.
    assert {e.get("parent") for e in by_kind["trace_gauges"]} >= {"epoch_end"}
    # One carve a batch; one timed get an item, the first of each epoch
    # marked.
    assert len(by_kind["carve"]) >= 30
    assert sum(1 for e in by_kind["batch_wait"] if e.get("first")) == 3


@pytest.mark.parametrize("kwargs", [
    {"device_rebatch": True},
    {"device_rebatch": False, "persistent_prefetch": False},
], ids=["bulk-persistent", "per-batch-single-epoch"])
def test_turnover_parts_sum_to_its_total(tmp_parquet_dir, kwargs):
    hist = metrics.histogram("rsdl_epoch_turnover_seconds", "")
    before = hist.count
    events = _three_epochs(tmp_parquet_dir, "telemetry-turnover", **kwargs)
    turnovers = [e for e in events if e["kind"] == "epoch_turnover"]
    assert [e["epoch"] for e in turnovers] == [0, 1]
    assert hist.count - before == 2
    for e in turnovers:
        parts = {k: v for k, v in e.items()
                 if k.endswith("_s") and k != "dur_s"}
        assert {"end_get_s", "epoch_end_s", "set_epoch_s", "first_get_s",
                "other_s"} <= set(parts)
        assert sum(parts.values()) == pytest.approx(e["dur_s"], abs=1e-9)
        assert e["other_s"] >= 0.0
        # The parts are the spans the recorder holds for that turnover.
        end = [x for x in events if x["kind"] == "epoch_end"
               and x["epoch"] == e["epoch"]][0]
        assert e["epoch_end_s"] == end["dur_s"]


def test_transfer_span_runs_to_the_landed_copy(tmp_parquet_dir):
    """``device_transfer`` opens at the dispatch on the producer's thread
    and is closed by the reaper once the copy has landed: the event keeps
    the opening thread's id and the dispatch's share."""
    events = _three_epochs(tmp_parquet_dir, "telemetry-transfer",
                           device_rebatch=True)
    spans = [e for e in events
             if e["kind"] == "device_transfer" and "dur_s" in e]
    assert spans and all(0.0 <= e["dispatch_s"] <= e["dur_s"]
                         for e in spans)
    consumer = {e["tid"] for e in events if e["kind"] == "carve"}
    assert consumer.isdisjoint({e["tid"] for e in spans})
    # Nothing is recorded twice: one span per dispatch attempt.
    assert len(spans) == len([e for e in events if e.get("attempt")])


def test_a_batch_already_on_the_device_counts_without_a_ring_event():
    hist = metrics.histogram("rsdl_batch_wait_seconds", "")
    before = hist.count
    recorded = telemetry.recorder().total_recorded
    telemetry.observe_batch_wait(epoch=4)
    assert hist.count == before + 1
    assert telemetry.recorder().total_recorded == recorded
    verdict = telemetry.attribution().epoch_verdict(4)
    assert verdict["batches_waited"] == 1 and verdict["batch_wait_s"] == 0.0


def test_held_epoch_line_is_logged_once_with_the_turnover(monkeypatch):
    lines = []
    monkeypatch.setattr(telemetry.logger, "info",
                        lambda fmt, *args: lines.append(fmt % args))
    telemetry.record("train_step", epoch=0, dur_s=0.01)
    telemetry.epoch_complete(0, source="dataset", hold_log=True)
    telemetry.epoch_complete(0, source="jax", hold_log=True)
    assert lines == []
    telemetry.turnover_complete(0, 0.5, {"epoch_end": 0.3, "set_epoch": 0.01,
                                         "first_get": 0.1})
    assert len(lines) == 1 and lines[0].startswith("epoch 0 bottleneck=")
    assert lines[0].endswith("turnover=500.0ms (epoch_end 300.0, "
                             "set_epoch 10.0, first_get 100.0, other 90.0)")
    # The last epoch has no turnover: its second caller releases the line.
    telemetry.record("train_step", epoch=1, dur_s=0.01)
    telemetry.epoch_complete(1, source="dataset", hold_log=True)
    telemetry.epoch_complete(1, source="jax")
    assert len(lines) == 2 and lines[1].startswith("epoch 1 bottleneck=")
    # A dataset closed mid-turnover flushes what is held.
    telemetry.record("train_step", epoch=2, dur_s=0.01)
    telemetry.epoch_complete(2, source="dataset", hold_log=True)
    telemetry.flush_epoch_log()
    telemetry.flush_epoch_log()
    assert len(lines) == 3


def test_hard_off_opens_no_annotation_and_reads_no_clock(monkeypatch):
    """``RSDL_TELEMETRY=0``: the span entry points are no-ops that touch
    neither a clock nor the profiler."""
    class _NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read on the hard-off path")

    class _NoAnnotation:
        def __init__(self, *args, **kwargs):
            raise AssertionError("annotation opened on the hard-off path")

    telemetry.configure(enabled_flag=False)
    try:
        monkeypatch.setattr(telemetry, "time", _NoClock())
        monkeypatch.setattr(telemetry, "_annotation_cls", _NoAnnotation)
        with telemetry.span("carve", epoch=0, batch=1) as sp:
            assert sp is None
        # rsdl-lint: disable=span-unbalanced
        token = telemetry.span_begin("batch_wait", epoch=0, cpu=True)
        telemetry.span_end(token)
        assert token is None
        telemetry.observe_batch_wait(0)
        telemetry.epoch_complete(0)
        telemetry.turnover_complete(0, 1.0, {"epoch_end": 0.5})
        assert telemetry.stamp() == 0.0
    finally:
        monkeypatch.undo()
        telemetry.configure(enabled_flag=True)


def test_consumer_span_overhead_is_a_few_microseconds():
    """What one instrumented batch adds on the consumer's thread (a carve
    span with CPU time and a train_step span), beside the cost of one
    recorded event."""
    import jax  # noqa: F401 - with jax imported, spans open annotations
    per_event = telemetry.measure_record_overhead(samples=500)
    n = 500
    start = time.perf_counter()
    for i in range(n):
        carve = telemetry.span_begin("carve", epoch=0, batch=i, cpu=True)
        try:
            pass
        finally:
            telemetry.span_end(carve)
        step = telemetry.span_begin("train_step", epoch=0)
        try:
            pass
        finally:
            telemetry.span_end(step)
    per_batch = (time.perf_counter() - start) / n
    assert telemetry._annotation_cls is not None
    assert per_batch < 2e-4, per_batch   # 200us: 10x what was observed
    assert per_batch < 40 * per_event + 1e-4
