"""Causal tracing layer: merge, critical path, what-if, Perfetto,
profiler, manual spans, wire-frame task propagation."""

import json
import os
import subprocess
import sys
import threading
import time

import pyarrow as pa
import pytest

from ray_shuffling_data_loader_tpu import multiqueue as mq
from ray_shuffling_data_loader_tpu import multiqueue_service as svc
from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
from ray_shuffling_data_loader_tpu.runtime import profiler as rt_profiler
from ray_shuffling_data_loader_tpu.runtime import telemetry
from ray_shuffling_data_loader_tpu.runtime import trace as rt_trace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.configure(enabled_flag=True)
    yield
    telemetry.configure()


def _span(kind, t0, t1, epoch=0, task=None, pid=1, tid=None, **attrs):
    ev = {"kind": kind, "epoch": epoch, "dur_s": t1 - t0,
          "t_mono": t1, "t0": float(t0), "t1": float(t1), "pid": pid}
    if task is not None:
        ev["task"] = task
    if tid is not None:
        ev["tid"] = tid
    ev.update(attrs)
    return ev


def _synthetic_epoch(epoch=0, pid=1, base=0.0):
    """map task1 is the 2s straggler; reduce waits for it; the consumer
    chain follows. Known critical path: map_read -> reduce -> convert
    -> train_step."""
    return [
        _span("map_read", base + 0.0, base + 1.0, epoch, task=0, pid=pid),
        _span("map_read", base + 0.0, base + 3.0, epoch, task=1, pid=pid),
        _span("reduce_gather", base + 3.0, base + 4.0, epoch, task=0,
              pid=pid),
        _span("convert", base + 4.0, base + 4.5, epoch, pid=pid),
        _span("train_step", base + 4.5, base + 5.0, epoch, task=0,
              pid=pid),
    ]


# ---------------------------------------------------------------------------
# Deterministic ids
# ---------------------------------------------------------------------------


def test_trace_ids_deterministic_and_distinct():
    assert rt_trace.trace_id(0, 3) == rt_trace.trace_id(0, 3)
    assert rt_trace.trace_id(0, 3) != rt_trace.trace_id(0, 4)
    assert rt_trace.trace_id(1, 3) != rt_trace.trace_id(0, 3)
    sid = rt_trace.span_id(0, 3, "reduce_gather", 2)
    assert sid == rt_trace.span_id(0, 3, "reduce_gather", 2)
    assert sid != rt_trace.span_id(0, 3, "reduce_gather", 1)
    assert len(rt_trace.trace_id(0, 3)) == 16
    int(sid, 16)  # hex


# ---------------------------------------------------------------------------
# Critical path / self time / stragglers / what-if
# ---------------------------------------------------------------------------


def test_synthetic_epoch_critical_path_and_self_time():
    analysis = rt_trace.analyze(_synthetic_epoch())
    assert analysis["epochs"] == [0]
    cp = {e["stage"]: e["cp_ms"] for e in analysis["critical_path"]}
    # The straggler map dominates: its 3s span is on the path.
    assert cp["map_read"] == pytest.approx(3000.0, abs=1.0)
    assert cp["reduce"] == pytest.approx(1000.0, abs=1.0)
    assert analysis["critical_path"][0]["stage"] == "map_read"
    # Self time is the busy-interval UNION: the two overlapping maps
    # cover [0, 3], not 4s of summed durations.
    assert analysis["self_time_ms"]["map_read"] == pytest.approx(
        3000.0, abs=1.0)
    # Straggler ranking: (map_read, task 1) first.
    top = analysis["stragglers"][0]
    assert (top["stage"], top["task"]) == ("map_read", 1)
    assert top["self_ms"] == pytest.approx(3000.0, abs=1.0)


def test_whatif_monotone_in_speedup_and_zero_at_one():
    events = _synthetic_epoch()
    saved = [rt_trace.analyze(events, whatif_speedup=s)
             ["whatif"]["map_read"]["epoch_time_saved_pct"]
             for s in (1.0, 2.0, 4.0, 8.0)]
    assert saved[0] == 0.0
    assert saved == sorted(saved)
    # 2x faster on a 3s critical-path share of a 5s epoch: 30% saved.
    assert saved[1] == pytest.approx(30.0, abs=1.0)


def test_epochless_spans_adopt_enclosing_epoch_window():
    events = _synthetic_epoch()
    events.append(_span("device_transfer", 4.6, 4.8, epoch=None, task=9))
    analysis = rt_trace.analyze(events)
    assert "device_transfer" in analysis["self_time_ms"]
    assert analysis["epochs"] == [0]


# ---------------------------------------------------------------------------
# Multi-process dump merge
# ---------------------------------------------------------------------------


def _write_dump(path, pid, time_unix, t_mono, events, role="test",
                events_total=None, threads=()):
    with open(path, "w", encoding="utf-8") as f:
        f.write(json.dumps({
            "kind": "dump_meta", "pid": pid, "time_unix": time_unix,
            "t_mono": t_mono, "events_total": events_total or len(events),
            "trace_seed": 7, "role": role}) + "\n")
        for ident, name in threads:
            f.write(json.dumps({"kind": "thread_stack", "ident": ident,
                                "thread": name, "stack": []}) + "\n")
        for ev in events:
            f.write(json.dumps(ev) + "\n")


def test_merge_dumps_aligns_clocks_and_dedups_per_pid(tmp_path):
    # Producer process: its monotonic clock starts at 1000.
    producer = [{"kind": "map_read", "epoch": 0, "task": 1,
                 "dur_s": 2.0, "t_mono": 1002.0, "tid": 11}]
    # Consumer process: a different monotonic origin; its convert runs
    # strictly after the producer's map in WALL time.
    consumer = [{"kind": "convert", "epoch": 0, "dur_s": 0.5,
                 "t_mono": 55.5, "tid": 22}]
    _write_dump(tmp_path / "a.jsonl", 100, 5000.0, 1010.0, producer,
                threads=[(11, "rsdl-worker_0")])
    _write_dump(tmp_path / "b.jsonl", 200, 5000.0, 53.0, consumer)
    # A stale earlier dump from pid 100: must be superseded, not
    # double-counted.
    _write_dump(tmp_path / "a0.jsonl", 100, 4999.0, 1009.0, producer[:1],
                events_total=0)
    merged = rt_trace.merge_dumps([str(tmp_path / "a0.jsonl"),
                                   str(tmp_path / "a.jsonl"),
                                   str(tmp_path / "b.jsonl")])
    assert {m["pid"] for m in merged["processes"]} == {100, 200}
    events = merged["events"]
    assert len(events) == 2  # dedup kept one dump per pid
    by_kind = {e["kind"]: e for e in events}
    # Wall alignment: map [4990, 4992], convert [5002, 5002.5].
    assert by_kind["map_read"]["t1"] == pytest.approx(4992.0)
    assert by_kind["convert"]["t0"] == pytest.approx(5002.0)
    assert by_kind["map_read"]["thread"] == "rsdl-worker_0"
    analysis = rt_trace.analyze(events)
    assert analysis["critical_path"][0]["stage"] in ("map_read", "convert")


def test_load_dump_tolerates_torn_tail(tmp_path):
    path = tmp_path / "torn.jsonl"
    _write_dump(path, 1, 10.0, 1.0,
                [{"kind": "map_read", "epoch": 0, "dur_s": 1.0,
                  "t_mono": 2.0}])
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"kind": "map_read", "epo')  # process died mid-write
    dump = rt_trace.load_dump(str(path))
    assert len(dump["events"]) == 1


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------


def test_perfetto_export_valid_with_consistent_pid_tid(tmp_path):
    _write_dump(tmp_path / "a.jsonl", 100, 5000.0, 1010.0,
                [{"kind": "map_read", "epoch": 0, "task": 1, "dur_s": 2.0,
                  "t_mono": 1002.0, "tid": 11}],
                threads=[(11, "rsdl-worker_0")])
    _write_dump(tmp_path / "b.jsonl", 200, 5000.0, 53.0,
                [{"kind": "frame_recv", "epoch": 0, "task": 1,
                  "t_mono": 55.0, "tid": 22}])
    merged = rt_trace.merge_dumps([str(tmp_path / "a.jsonl"),
                                   str(tmp_path / "b.jsonl")])
    perfetto = rt_trace.to_perfetto(merged, seed=7)
    blob = json.dumps(perfetto)
    parsed = json.loads(blob)  # valid chrome-trace JSON
    events = parsed["traceEvents"]
    assert events
    for ev in events:
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert ev["ph"] in ("X", "i", "M")
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and ev["ts"] >= 0
    durations = [e for e in events if e["ph"] == "X"]
    assert durations[0]["pid"] == 100 and durations[0]["tid"] == 11
    # Both processes share the deterministic trace id for epoch 0.
    ids = {e["args"].get("trace_id") for e in events
           if e["ph"] in ("X", "i")}
    assert ids == {rt_trace.trace_id(7, 0)}
    names = [e for e in events if e["ph"] == "M"
             and e["name"] == "thread_name"]
    assert any(e["args"]["name"] == "rsdl-worker_0" for e in names)


# ---------------------------------------------------------------------------
# delayN chaos straggler through a REAL shuffle
# ---------------------------------------------------------------------------


def _three_key_files(tmp_path, rows):
    import pyarrow.parquet as pq

    files = []
    for i in range(3):
        path = str(tmp_path / f"part_{i}.parquet")
        pq.write_table(pa.table({"key": pa.array(range(i * rows,
                                                       (i + 1) * rows))}),
                       path)
        files.append(path)
    return files


def test_delay_chaos_straggler_ranked_first(tmp_path):
    from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle

    files = _three_key_files(tmp_path, 32)
    telemetry.configure(enabled_flag=True)
    rt_faults.install("map_read:file1:delay300", seed=0)
    try:
        consumed = []

        def consumer(trainer_idx, epoch, refs):
            if refs is not None:
                consumed.extend(r.result().num_rows for r in refs)

        run_shuffle(files, consumer, 1, num_reducers=2, num_trainers=1,
                    max_concurrent_epochs=1, seed=5, collect_stats=False,
                    file_cache=None)
    finally:
        rt_faults.clear()
    assert sum(consumed) == 96
    analysis = rt_trace.analyze(telemetry.recorder().events())
    top = analysis["stragglers"][0]
    assert (top["stage"], top["task"]) == ("map_read", 1), analysis[
        "stragglers"][:3]
    assert analysis["critical_path"][0]["stage"] in ("map_read", "reduce")
    assert analysis["whatif"]["map_read"]["epoch_time_saved_pct"] > 0


def test_delay_chaos_straggler_ranked_first_across_processes(tmp_path,
                                                            monkeypatch):
    """The same straggler where the producer is another process: a
    supervised queue server shuffles under the delay (the spec rides its
    environment) and this process consumes over the wire. The child dumps
    its recorder on SIGTERM -> atexit, this process dumps its own, the two
    merge into one clock, and the merged analysis names the producer's
    stage and the child's delayed task; the Perfetto export keeps both
    pids."""
    import glob

    from ray_shuffling_data_loader_tpu.runtime import supervisor as rt_sup

    files = _three_key_files(tmp_path, 64)
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    monkeypatch.setenv("RSDL_TRACE_DIR", str(trace_dir))
    # The delay has to dominate the child's first Parquet read, which
    # lands on whichever file maps first: 0.3-0.6 s of pyarrow warm-up on
    # an idle host, 1.6 s seen beside five other test workers. The child
    # maps on threads: a pool's workers would each pay that cold start
    # beside the delay.
    monkeypatch.setenv("RSDL_CHAOS_SPEC", "map_read:file1:delay4000")
    monkeypatch.setenv("RSDL_EXECUTOR_BACKEND", "thread")
    telemetry.configure(enabled_flag=True)   # a fresh ring, dumped there
    seed, epochs = 17, 1
    supervisor, address = rt_sup.launch_supervised_queue_server(dict(
        filenames=files, num_epochs=epochs, num_trainers=1, num_reducers=2,
        seed=seed, max_concurrent_epochs=1,
        journal_path=str(tmp_path / "trace.wal"), file_cache=None))
    rows = 0
    try:
        assert rt_sup.wait_for_server(address, timeout_s=60)
        with svc.RemoteQueue(address, retries=12, max_batch=2) as remote:
            for queue_idx in range(epochs):
                while (item := remote.get(queue_idx)) is not None:
                    rows += item.num_rows
    finally:
        supervisor.stop()       # SIGTERM: the child's atexit dump
    assert rows == epochs * 3 * 64
    telemetry.dump(reason="test")
    dumps = sorted(glob.glob(str(trace_dir / "*.jsonl")))
    merged = rt_trace.merge_dumps(dumps)
    pids = {m["pid"] for m in merged["processes"]}
    assert os.getpid() in pids and len(pids) >= 2, (sorted(pids), dumps)
    analysis = rt_trace.analyze(merged["events"])
    top = analysis["stragglers"][0]
    assert (top["stage"], top["task"]) == ("map_read", 1), analysis[
        "stragglers"][:3]
    assert analysis["critical_path"][0]["stage"] in ("map_read", "reduce")
    assert analysis["whatif"]
    perfetto = json.loads(json.dumps(rt_trace.to_perfetto(merged,
                                                          seed=seed)))
    events = perfetto["traceEvents"]
    assert events and all(isinstance(e.get("pid"), int)
                          and isinstance(e.get("tid"), int) for e in events)
    assert pids <= {e["pid"] for e in events}


# ---------------------------------------------------------------------------
# Manual span API + hard-off fast path
# ---------------------------------------------------------------------------


def test_span_begin_end_records_duration_and_restores_kind():
    # This test deliberately drives the manual API outside the finally
    # shape the rule enforces — the nesting itself is under test:
    # rsdl-lint: disable=span-unbalanced
    outer = telemetry.span_begin("convert", epoch=1, task=2)
    ident = threading.get_ident()
    assert telemetry.active_kinds()[ident] == "convert"
    inner = telemetry.span_begin(  # rsdl-lint: disable=span-unbalanced
        "device_transfer", epoch=1)
    assert telemetry.active_kinds()[ident] == "device_transfer"
    time.sleep(0.01)
    telemetry.span_end(inner)
    assert telemetry.active_kinds()[ident] == "convert"
    telemetry.span_end(outer, extra="x")
    assert ident not in telemetry.active_kinds()
    events = telemetry.recorder().events()
    convert = [e for e in events if e["kind"] == "convert"][-1]
    assert convert["epoch"] == 1 and convert["task"] == 2
    assert convert["dur_s"] >= 0.01 and convert["extra"] == "x"
    assert convert["tid"] == ident
    telemetry.span_end(None)  # disabled-begin token: must be a no-op


def test_rsdl_telemetry_hard_off_rebinds_to_noops():
    telemetry.configure(enabled_flag=False)
    try:
        assert telemetry.record is telemetry._noop_record
        assert telemetry.span is telemetry._noop_span
        before = telemetry.recorder().total_recorded
        telemetry.record("map_read", epoch=0, task=0, dur_s=1.0)
        with telemetry.span("convert", epoch=0):
            pass
        # Exercising the disabled no-op path, not the pairing contract:
        # rsdl-lint: disable=span-unbalanced
        token = telemetry.span_begin("queue_wait")
        telemetry.span_end(token)
        assert token is None
        assert telemetry.recorder().total_recorded == before
        # The off path costs nanoseconds, orders below the enabled path.
        assert telemetry.measure_disabled_overhead(500) < 5e-6
    finally:
        telemetry.configure(enabled_flag=True)
    assert telemetry.record is telemetry._record_impl


# ---------------------------------------------------------------------------
# Producer-task propagation through the queue wire (v2.1 frames)
# ---------------------------------------------------------------------------


def test_frame_recv_carries_producer_task_across_wire():
    table = pa.table({"x": list(range(8))}).replace_schema_metadata(
        {b"rsdl.trace": b"5:0:3"})
    queue = mq.MultiQueue(1)
    queue.put(0, table)
    queue.put(0, None)
    with svc.serve_queue(queue) as server:
        remote = svc.RemoteQueue(server.address, max_batch=2)
        try:
            got = remote.get(0)
            assert got.num_rows == 8
            # Metadata survived serialization end to end.
            assert got.schema.metadata[b"rsdl.trace"] == b"5:0:3"
            assert remote.get(0) is None
        finally:
            remote.close()
    queue.shutdown()
    frame_recvs = [e for e in telemetry.recorder().events()
                   if e["kind"] == "frame_recv"]
    assert frame_recvs and frame_recvs[-1]["task"] == 3
    assert frame_recvs[-1]["epoch"] == 0


def test_reduce_outputs_carry_lineage_metadata(tmp_path):
    import pyarrow.parquet as pq

    from ray_shuffling_data_loader_tpu.shuffle import shuffle as run_shuffle

    path = str(tmp_path / "part.parquet")
    pq.write_table(pa.table({"key": pa.array(range(64))}), path)
    outputs = []

    def consumer(trainer_idx, epoch, refs):
        if refs is not None:
            outputs.extend(r.result() for r in refs)

    run_shuffle([path], consumer, 1, num_reducers=2, num_trainers=1,
                max_concurrent_epochs=1, seed=9, collect_stats=False,
                file_cache=None)
    assert len(outputs) == 2
    tasks = sorted(int(t.schema.metadata[b"rsdl.trace"].rsplit(b":", 1)[-1])
                   for t in outputs)
    assert tasks == [0, 1]
    assert all(t.schema.metadata[b"rsdl.trace"].startswith(b"9:0:")
               for t in outputs)


# ---------------------------------------------------------------------------
# Sampling profiler
# ---------------------------------------------------------------------------


def test_profiler_folds_named_thread_stacks_and_bills_stage():
    stop = threading.Event()

    def busy_marker_fn():
        with telemetry.span("convert", epoch=0):
            while not stop.is_set():
                sum(i * i for i in range(500))

    worker = threading.Thread(target=busy_marker_fn, daemon=True,
                              name="rsdl-test-busy")
    profiler = rt_profiler.SamplingProfiler(interval_s=0.005)
    worker.start()
    with profiler:
        time.sleep(0.3)
    stop.set()
    worker.join(timeout=5)
    assert profiler.samples > 10
    folded = profiler.folded()
    marked = [k for k in folded if "busy_marker_fn" in k
              and k.startswith("rsdl-test-busy")]
    assert marked, sorted(folded)[:5]
    assert profiler.by_stage().get("convert", 0) > 0
    summary = profiler.summary()
    assert summary["samples"] == profiler.samples
    assert summary["hottest_stacks"]
    if os.path.isdir("/proc/self/task"):
        assert isinstance(profiler.cpu_by_thread(), dict)


def test_profiler_write_folded_makes_the_directory_and_the_file(tmp_path):
    folded_path = str(tmp_path / "prof" / "stacks.folded")
    prof = rt_profiler.SamplingProfiler(interval_s=0.005).start()
    deadline = time.monotonic() + 2.0
    while prof.samples < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    prof.stop()
    prof.write_folded(folded_path)
    with open(folded_path) as f:
        lines = f.read().splitlines()
    assert lines and all(line.rsplit(" ", 1)[1].isdigit() for line in lines)


# ---------------------------------------------------------------------------
# CLI smoke (subprocess, stdlib-only contract)
# ---------------------------------------------------------------------------


def test_rsdl_trace_cli_merges_and_exports(tmp_path):
    _write_dump(tmp_path / "a.jsonl", 100, 5000.0, 1010.0,
                [{"kind": "map_read", "epoch": 0, "task": 1, "dur_s": 2.0,
                  "t_mono": 1002.0, "tid": 11},
                 {"kind": "reduce_gather", "epoch": 0, "task": 0,
                  "dur_s": 0.5, "t_mono": 1002.5, "tid": 11}])
    _write_dump(tmp_path / "b.jsonl", 200, 5000.0, 53.0,
                [{"kind": "convert", "epoch": 0, "dur_s": 0.2,
                  "t_mono": 56.0, "tid": 22}])
    out = str(tmp_path / "perfetto.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "rsdl_trace.py"),
         str(tmp_path), "--perfetto", out],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "critical-path" in proc.stdout or "critical" in proc.stdout
    assert "stragglers" in proc.stdout
    with open(out) as f:
        parsed = json.load(f)
    assert parsed["traceEvents"]
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "rsdl_trace.py"),
         str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["critical_path"] and payload["whatif"]


def test_rsdl_trace_cli_prints_the_epoch_turnover_split(tmp_path):
    """The turnover's split from any run's dumps: ``epoch_turnover``
    events come out as a table (and under ``turnovers`` with ``--json``)
    whose parts, ``other`` included, sum to the total."""
    _write_dump(tmp_path / "a.jsonl", 100, 5000.0, 1010.0,
                [{"kind": "train_step", "epoch": 0, "dur_s": 0.02,
                  "t_mono": 1002.0, "tid": 11},
                 {"kind": "epoch_turnover", "epoch": 0, "dur_s": 0.35,
                  "t_mono": 1003.0, "tid": 11, "end_get_s": 0.0001,
                  "epoch_end_s": 0.34, "set_epoch_s": 0.0001,
                  "first_get_s": 0.0018, "first_carve_s": 0.003,
                  "other_s": 0.005}])
    tool = os.path.join(REPO_ROOT, "tools", "rsdl_trace.py")
    proc = subprocess.run([sys.executable, tool, str(tmp_path)],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "epoch 0 -> 1: 350.0 (end_get 0.1, epoch_end 340.0, " \
        "set_epoch 0.1, first_get 1.8, first_carve 3.0, other 5.0)" \
        in proc.stdout
    proc = subprocess.run([sys.executable, tool, str(tmp_path), "--json"],
                          capture_output=True, text=True, timeout=60)
    (turnover,) = json.loads(proc.stdout)["turnovers"]
    assert turnover["epoch"] == 0 and turnover["pid"] == 100
    assert sum(turnover["parts"].values()) == pytest.approx(
        turnover["total_ms"])
    assert list(turnover["parts"])[-1] == "other"
