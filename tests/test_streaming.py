"""Streaming plane tests (streaming/ = continuous ingestion + windowed
shuffle + online training).

The design under test: **a window is an epoch**. Sources re-yield a
deterministic event sequence (manifest journal / seeded arrivals), the
assembler seals windows at policy bounds and journals a monotone ingest
watermark, each sealed window compiles to a normal ``plan.ir.EpochSpec``
— so the PR 5 exactly-once matrix carries across window boundaries
unchanged. The chaos legs pin exactly that: a ``kill -9``'d trainer
resumed mid-window, a ``kill -9``'d queue shard at a window boundary,
and a late file during window close each end with ZERO missed and ZERO
duplicated row offsets, bit-identical to the fault-free run.
"""

import itertools
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from ray_shuffling_data_loader_tpu import checkpoint as ckpt
from ray_shuffling_data_loader_tpu import multiqueue as mq
from ray_shuffling_data_loader_tpu import multiqueue_service as svc
from ray_shuffling_data_loader_tpu import streaming as st
from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset
from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
from ray_shuffling_data_loader_tpu.runtime import health as rt_health
from ray_shuffling_data_loader_tpu.runtime import history as rt_history
from ray_shuffling_data_loader_tpu.runtime import supervisor as rt_sup
from ray_shuffling_data_loader_tpu.shuffle import shuffle_epochs
from ray_shuffling_data_loader_tpu.streaming import runner as st_runner
from ray_shuffling_data_loader_tpu.streaming import source as st_source
from ray_shuffling_data_loader_tpu.streaming import window as st_window
from ray_shuffling_data_loader_tpu.workloads import dlrm_criteo as dlrm

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_stream_files(directory, num_files, rows=32, prefix="part"):
    """Parquet files with globally-unique int64 keys (exactly-once
    accounting is key-set accounting)."""
    os.makedirs(directory, exist_ok=True)
    files = []
    for i in range(num_files):
        table = pa.table({
            "key": pa.array(range(i * rows, (i + 1) * rows),
                            type=pa.int64()),
            "labels": pa.array(
                np.zeros(rows, dtype=np.float32)),
        })
        path = os.path.join(directory, f"{prefix}_{i:03d}.parquet")
        pq.write_table(table, path)
        files.append(path)
    return files


def _ev(index, path, ts, size=10):
    return st_source.StreamEvent(index=index, path=path, timestamp=ts,
                                 size_bytes=size)


class _ScriptedSource(st_source.StreamSource):
    """A test source yielding a predefined event sequence, one per
    poll — deterministic by construction (the StreamSource contract)."""

    def __init__(self, events):
        self._events = list(events)
        self._pos = 0

    def poll(self, now=None):
        if self._pos >= len(self._events):
            return []
        event = self._events[self._pos]
        self._pos += 1
        return [event]

    @property
    def exhausted(self):
        return self._pos >= len(self._events)


# ---------------------------------------------------------------------------
# Sources: deterministic re-yield is the ingest half of exactly-once
# ---------------------------------------------------------------------------


def _drain_source(source):
    events = []
    while not source.exhausted:
        events.extend(source.poll())
    return events


def test_synthetic_source_identical_across_instances(tmp_path):
    files = _make_stream_files(str(tmp_path), 3)
    first = _drain_source(st.SyntheticEventSource(files, seed=7,
                                                  total_events=10))
    second = _drain_source(st.SyntheticEventSource(files, seed=7,
                                                   total_events=10))
    assert first == second, "same seed must re-yield the identical stream"
    assert [e.index for e in first] == list(range(10))
    times = [e.timestamp for e in first]
    assert times == sorted(times), "arrivals must be monotone"
    other = _drain_source(st.SyntheticEventSource(files, seed=8,
                                                  total_events=10))
    assert [e.timestamp for e in other] != times, "seed must matter"


def test_synthetic_source_clocked_poll_releases_by_arrival(tmp_path):
    files = _make_stream_files(str(tmp_path), 2)
    probe = st.SyntheticEventSource(files, seed=3, total_events=8)
    cutoff = probe.arrival_time(4)
    source = st.SyntheticEventSource(files, seed=3, total_events=8)
    released = source.poll(now=cutoff)
    assert [e.index for e in released] == [0, 1, 2, 3, 4]
    assert all(e.timestamp <= cutoff for e in released)
    # Nothing new until the clock passes the next arrival.
    assert source.poll(now=cutoff) == []
    rest = source.poll(now=probe.arrival_time(7))
    assert [e.index for e in rest] == [5, 6, 7]
    assert source.exhausted


def test_directory_tail_journaled_discovery_and_replay(tmp_path):
    stream_dir = str(tmp_path / "arrivals")
    journal = str(tmp_path / "manifest.wal")
    files = _make_stream_files(stream_dir, 2, prefix="a")
    tail = st.DirectoryTailSource(stream_dir, journal_path=journal)
    first = tail.poll()
    assert [e.path for e in first] == sorted(files)
    assert [e.index for e in first] == [0, 1]
    assert tail.poll() == [], "a discovered file is yielded exactly once"
    late_file = _make_stream_files(stream_dir, 1, prefix="z")[0]
    second = tail.poll()
    assert [(e.index, e.path) for e in second] == [(2, late_file)]
    tail.close()

    # Recovery: the directory now lists DIFFERENTLY (one file deleted,
    # one added), but the manifest replay re-yields the journaled
    # sequence first, bit-for-bit — discovery order survives the crash.
    os.remove(late_file)
    newcomer = _make_stream_files(stream_dir, 1, prefix="b")[0]
    recovered = st.DirectoryTailSource(stream_dir, journal_path=journal)
    replayed = recovered.poll()
    assert replayed[:3] == first + second, \
        "manifest replay must reproduce the original discovery order"
    assert [(e.index, e.path) for e in replayed[3:]] == [(3, newcomer)]
    recovered.close()


def test_directory_tail_skips_half_written_files(tmp_path):
    stream_dir = str(tmp_path / "arrivals")
    os.makedirs(stream_dir)
    empty = os.path.join(stream_dir, "pending.parquet")
    open(empty, "w").close()
    tail = st.DirectoryTailSource(stream_dir)
    assert tail.poll() == [], "an empty (still-writing) file must wait"
    with open(empty, "wb") as f:
        f.write(b"x" * 16)
    assert [e.path for e in tail.poll()] == [empty]


# ---------------------------------------------------------------------------
# Window policy + assembler
# ---------------------------------------------------------------------------


def test_window_policy_env_resolution_and_validation(monkeypatch):
    monkeypatch.setenv("RSDL_STREAM_WINDOW_MAX_FILES", "7")
    monkeypatch.setenv("RSDL_STREAM_WINDOW_LATE_POLICY", "quarantine")
    policy = st.WindowPolicy.resolve()
    assert policy.max_files == 7
    assert policy.late_policy == "quarantine"
    # Kwarg overrides beat env; every bound disabled falls back to a
    # 1-file window (a window must be closable).
    policy = st.WindowPolicy.resolve(max_files=0, max_bytes=0,
                                     max_wait_s=0.0, late_policy="admit")
    assert policy.max_files == 1
    with pytest.raises(ValueError):
        st.WindowPolicy(late_policy="drop")


def test_window_assembler_count_byte_and_wait_bounds():
    count = st_window.WindowAssembler(st.WindowPolicy(max_files=2))
    count.admit(_ev(0, "f0", 1.0))
    assert not count.should_close()
    count.admit(_ev(1, "f1", 2.0))
    assert count.should_close()

    by_bytes = st_window.WindowAssembler(
        st.WindowPolicy(max_files=0, max_bytes=100))
    by_bytes.admit(_ev(0, "f0", 1.0, size=60))
    assert not by_bytes.should_close()
    by_bytes.admit(_ev(1, "f1", 2.0, size=60))
    assert by_bytes.should_close()

    by_wait = st_window.WindowAssembler(
        st.WindowPolicy(max_files=0, max_wait_s=5.0))
    by_wait.admit(_ev(0, "f0", 1.0))
    by_wait.admit(_ev(1, "f1", 3.0))
    assert not by_wait.should_close(), "2s of stream time < 5s bound"
    by_wait.admit(_ev(2, "f2", 6.5))
    assert by_wait.should_close(), "5.5s of stream-time age seals"


def test_late_events_admit_vs_quarantine_and_monotone_watermark():
    admit = st_window.WindowAssembler(
        st.WindowPolicy(max_files=2, late_policy="admit"))
    admit.admit(_ev(0, "f0", 5.0))
    admit.admit(_ev(1, "f1", 6.0))
    sealed = admit.close_window()
    assert sealed.ingest_watermark == 6.0
    assert admit.ingest_watermark == 6.0
    # ts 4.0 < watermark: late, but ADMITTED into the open window.
    assert admit.admit(_ev(2, "f2", 4.0)) is True
    assert admit.late_events == 1
    window = admit.close_window()
    assert window.late_events == 1
    assert window.ingest_watermark == 6.0, \
        "a purely-late window must not move the watermark backwards"
    assert admit.quarantined == []

    quarantine = st_window.WindowAssembler(
        st.WindowPolicy(max_files=2, late_policy="quarantine"))
    quarantine.admit(_ev(0, "f0", 5.0))
    quarantine.admit(_ev(1, "f1", 6.0))
    quarantine.close_window()
    assert quarantine.admit(_ev(2, "f2", 4.0)) is False
    assert quarantine.pending_events == 0
    assert [e.index for e in quarantine.quarantined] == [2]
    assert quarantine.late_events == 1


def test_assembler_journal_resume_state_and_torn_tail(tmp_path):
    journal_path = str(tmp_path / "ingest.wal")
    journal = ckpt.StreamJournal(journal_path)
    assembler = st_window.WindowAssembler(st.WindowPolicy(max_files=2),
                                          journal=journal)
    for i in range(4):
        assembler.admit(_ev(i, f"f{i}", float(i)))
        assembler.maybe_close()
    journal.close()
    state = st_window.resume_state(journal_path)
    assert state == {"next_window": 2, "events_sealed": 4,
                     "ingest_watermark": 3.0}
    # A torn tail (half-written record at crash) must not poison resume.
    with open(journal_path, "ab") as f:
        f.write(b'{"kind": "waterma')
    assert st_window.resume_state(journal_path) == state

    resumed = st_window.WindowAssembler(
        st.WindowPolicy(max_files=2), first_window=state["next_window"])
    resumed.ingest_watermark = state["ingest_watermark"]
    assert resumed.window_index == 2
    assert resumed.next_epoch == 2, \
        "a resumed stream continues the epoch numbering it left off at"


def test_freeze_schedule_roundtrips_through_json(tmp_path):
    files = _make_stream_files(str(tmp_path), 4)
    source = st.SyntheticEventSource(files, seed=11, total_events=4)
    specs = st_window.freeze_schedule(source,
                                      policy=st.WindowPolicy(max_files=2))
    assert [s.epoch for s in specs] == [0, 1]
    assert list(specs[0].filenames) + list(specs[1].filenames) == files
    assert all(s.window["index"] == s.epoch for s in specs)
    wire = json.loads(json.dumps(st_window.specs_to_dicts(specs)))
    assert st_window.specs_from_dicts(wire) == specs, \
        "the frozen schedule is pure data: JSON roundtrip is identity"


def test_epoch_range_bounded_and_unbounded():
    assert list(plan_ir.epoch_range(0, 3)) == [0, 1, 2]
    assert list(plan_ir.epoch_range(2, 5)) == [2, 3, 4]
    unbounded = plan_ir.epoch_range(4, None)
    assert list(itertools.islice(unbounded, 3)) == [4, 5, 6]


def test_unbounded_dataset_requires_serving_queue():
    with pytest.raises(ValueError, match="unbounded"):
        ShufflingDataset([], None, num_trainers=1, batch_size=4, rank=0)


# ---------------------------------------------------------------------------
# Runner: pipelined windows, watermarks, journal resume
# ---------------------------------------------------------------------------


def test_runner_streams_windows_and_resumes_from_journal(tmp_path):
    files = _make_stream_files(str(tmp_path / "stream"), 8)
    journal_path = str(tmp_path / "ingest.wal")
    policy = st.WindowPolicy(max_files=2)

    def collect(into):
        def consumer(rank, epoch, refs):
            if refs is None:
                return
            for ref in refs:
                table = ref.result() if hasattr(ref, "result") else ref
                into.setdefault(epoch, []).extend(
                    table.column("key").to_pylist())
        return consumer

    first_keys = {}
    runner = st.StreamingShuffleRunner(
        st.SyntheticEventSource(files, seed=5, total_events=8),
        collect(first_keys), num_reducers=2, num_trainers=1, seed=5,
        max_concurrent_epochs=2, policy=policy, journal_path=journal_path,
        max_windows=2)
    summary = runner.run()
    runner.close()
    assert sorted(first_keys) == [0, 1]
    assert summary["windows_served"] == 2
    assert summary["events_sealed"] == 4
    assert summary["serve_watermark"] == summary["ingest_watermark"], \
        "a drained bounded run ends with serve == ingest watermark"

    # Resume over the SAME journal with a fresh (identically re-yielding)
    # source: the sealed 4-event prefix is skipped, epochs continue at 2.
    second_keys = {}
    resumed = st.StreamingShuffleRunner(
        st.SyntheticEventSource(files, seed=5, total_events=8),
        collect(second_keys), num_reducers=2, num_trainers=1, seed=5,
        max_concurrent_epochs=2, policy=policy, journal_path=journal_path)
    assert resumed.resume_skip_events == 4
    summary2 = resumed.run()
    resumed.close()
    assert sorted(second_keys) == [2, 3]
    assert summary2["windows_served"] == 2
    assert summary2["serve_watermark"] >= summary["serve_watermark"]

    # Exactly-once across the restart: every row delivered exactly once,
    # no window re-served, no event re-sealed.
    delivered = sorted(key for keys in first_keys.values() for key in keys)
    delivered += sorted(key for keys in second_keys.values()
                        for key in keys)
    assert sorted(delivered) == list(range(8 * 32))
    assert len(set(delivered)) == len(delivered)


def test_late_file_during_window_close_admit_and_quarantine(tmp_path):
    """Satellite chaos leg: a LATE file lands while windows are closing.
    ``admit`` rolls it into the open window — zero rows missed, zero
    duplicated; ``quarantine`` excludes exactly that file's rows into
    the structured report and nothing else changes."""
    files = _make_stream_files(str(tmp_path / "stream"), 5)
    # Arrival order: f0(t5) f1(t6) | seal | f2(t10) f3(t4 = LATE) f4(t11)
    timestamps = [5.0, 6.0, 10.0, 4.0, 11.0]

    def run(late_policy):
        events = [_ev(i, files[i], timestamps[i],
                      size=os.path.getsize(files[i]))
                  for i in range(5)]
        keys = []

        def consumer(rank, epoch, refs):
            if refs is None:
                return
            for ref in refs:
                table = ref.result() if hasattr(ref, "result") else ref
                keys.extend(table.column("key").to_pylist())

        runner = st.StreamingShuffleRunner(
            _ScriptedSource(events), consumer, num_reducers=2,
            num_trainers=1, seed=3, max_concurrent_epochs=1,
            policy=st.WindowPolicy(max_files=2, late_policy=late_policy))
        summary = runner.run()
        return keys, summary, runner

    admitted_keys, admitted, _ = run("admit")
    # Nothing lost, nothing duplicated: the window boundary moved past
    # the late file, the data did not.
    assert sorted(admitted_keys) == list(range(5 * 32))
    assert admitted["late_events"] == 1
    assert admitted["quarantined"] == 0
    assert admitted["windows_closed"] == 3
    assert admitted["ingest_watermark"] == 11.0

    quarantined_keys, quarantined, runner = run("quarantine")
    late_rows = set(range(3 * 32, 4 * 32))  # f3's keys, excluded
    assert sorted(quarantined_keys) == sorted(
        set(range(5 * 32)) - late_rows)
    assert len(set(quarantined_keys)) == len(quarantined_keys)
    assert quarantined["late_events"] == 1
    assert quarantined["quarantined"] == 1
    assert [e.index for e in runner.assembler.quarantined] == [3]


def test_online_training_tracks_drifting_click_stream(tmp_path):
    """The online-training property: trained per-window on the served
    stream, the model's CTR estimate follows the drift; a frozen
    estimate (predict 0.5 forever — the untrained model) accumulates
    strictly more error. Deterministic in (files, seed)."""
    files = dlrm.generate_drifting_stream(12, 64, str(tmp_path / "clicks"),
                                          seed=3)
    history = dlrm.run_online_training(files, num_windows=6,
                                       files_per_window=2, seed=3,
                                       num_reducers=2)
    assert [rec["window"] for rec in history] == list(range(6))
    # Warm-up excluded: the first window IS the first gradient signal.
    tail = history[1:]
    online_error = np.mean([abs(rec["estimate"] - rec["observed_ctr"])
                            for rec in tail])
    frozen_error = np.mean([abs(0.5 - rec["observed_ctr"])
                            for rec in tail])
    assert online_error < frozen_error, (online_error, frozen_error)
    # And it is not a constant model: the estimate actually moves.
    estimates = [rec["estimate"] for rec in history]
    assert max(estimates) - min(estimates) > 0.02
    # Bit-reproducible: the whole run is pure in (files, seed).
    again = dlrm.run_online_training(files, num_windows=6,
                                     files_per_window=2, seed=3,
                                     num_reducers=2)
    assert again == history


# ---------------------------------------------------------------------------
# Health: the watermark_lag detector (standard hysteresis contract)
# ---------------------------------------------------------------------------


def _lag_snap(t, lag):
    return {"t": t, "t_unix": 1.7e9 + t, "samples": {
        "rsdl_stream_watermark_lag_seconds": {(): float(lag)}}}


def test_watermark_lag_detector_fires_once_per_episode(monkeypatch):
    monkeypatch.setenv("RSDL_SLO_WATERMARK_LAG_S", "10")
    ring = rt_history.HistoryRing(capacity=400, interval_s=0.1)
    fired = []
    monitor = rt_health.HealthMonitor(
        ring, detectors=rt_health.default_detectors(
            names=["watermark_lag"]),
        fire_ticks=2, clear_ticks=3, capture=False,
        on_fire=lambda v: fired.append(v))
    t = 0.0
    for lag in [2.0] * 6 + [50.0] * 8:
        t += 0.1
        ring.append_snapshot(_lag_snap(t, lag))
        monitor.tick()
    assert monitor.total_fires == 1, monitor.summary()
    assert fired[0]["detector"] == "watermark_lag"
    assert "lag" in fired[0]["detail"]
    # Recovery then a second breach = a second episode, fires again.
    for lag in [0.0] * 6 + [50.0] * 6:
        t += 0.1
        ring.append_snapshot(_lag_snap(t, lag))
        monitor.tick()
    assert monitor.total_fires == 2


def test_rsdl_top_renders_streaming_line():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "rsdl_top_under_test", os.path.join(REPO_ROOT, "tools",
                                            "rsdl_top.py"))
    top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(top)
    from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
    exposition = "\n".join([
        "rsdl_stream_window 4",
        "rsdl_stream_windows_closed_total 5",
        "rsdl_stream_events_admitted_total 20",
        "rsdl_stream_watermark_lag_seconds 3.5",
        'rsdl_stream_late_events_total{policy="admit"} 2',
    ])
    lines = top.render_streaming(rt_metrics.parse_exposition(exposition))
    assert len(lines) == 1
    line = lines[0]
    assert "window 4" in line and "5 closed" in line
    assert "lag 3.5s" in line and "late 2" in line
    # No streaming traffic -> no line (static trials stay uncluttered).
    assert top.render_streaming(
        rt_metrics.parse_exposition("rsdl_stream_window 0")) == []


# ---------------------------------------------------------------------------
# Chaos legs: exactly-once across kill -9, across a window boundary
# ---------------------------------------------------------------------------


def _streaming_server_config(files, tmpdir, num_trainers, num_reducers,
                             seed, files_per_window=2):
    source = st.SyntheticEventSource(files, seed=seed,
                                     total_events=len(files))
    return st_runner.server_config(
        source, num_trainers=num_trainers, num_reducers=num_reducers,
        journal_path=os.path.join(tmpdir, "watermarks.wal"), seed=seed,
        policy=st.WindowPolicy(max_files=files_per_window),
        max_concurrent_epochs=1,
        ingest_journal_path=os.path.join(tmpdir, "ingest.wal"),
        file_cache=None)


def _expected_rank_streams(config):
    """Fault-free per-(rank, epoch) key streams for a frozen window
    schedule, straight off the deterministic shuffle lineage."""
    specs = st_window.specs_from_dicts(config["epochs"])
    streams = {}

    def consumer(rank, epoch, refs):
        if refs is not None:
            streams.setdefault((rank, epoch), []).extend(refs)

    shuffle_epochs(iter(specs), consumer, config["num_reducers"],
                   config["num_trainers"], max_concurrent_epochs=1,
                   seed=config["seed"], file_cache=None,
                   epochs_hint=len(specs))
    return {key: [tuple(r.result().column("key").to_pylist())
                  for r in refs]
            for key, refs in streams.items()}


def _kill9_mid_stream_then_rerun(first_args, rerun_args, out_path,
                                 after_lines, done):
    """Start a consumer process, kill -9 it once it has written
    ``after_lines`` lines to ``out_path``, then run a fresh process to its
    end (it must print ``done``)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    first = subprocess.Popen(first_args, cwd=REPO_ROOT, env=env,
                             stdout=subprocess.PIPE, text=True)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if os.path.exists(out_path) and \
                sum(1 for _ in open(out_path)) >= after_lines:
            break
        time.sleep(0.05)
    os.kill(first.pid, signal.SIGKILL)
    first.wait(timeout=30)
    assert first.returncode == -9
    second = subprocess.run(rerun_args, cwd=REPO_ROOT, env=env,
                            capture_output=True, text=True, timeout=240)
    assert second.returncode == 0, second.stderr[-3000:]
    assert done in second.stdout


def _merged_positions(out_path):
    """``{(epoch, position): keys}`` of the ``epoch:position:keys`` lines
    two consumer processes appended; a position written twice (the
    at-least-once replay across the crash) must be IDENTICAL."""
    merged = {}
    for line in open(out_path):
        epoch_str, position_str, keys = line.strip().split(":", 2)
        position = (int(epoch_str), int(position_str))
        table = tuple(int(k) for k in keys.split(",") if k)
        assert merged.setdefault(position, table) == table, \
            f"the replay at {position} diverged across the crash"
    return merged


_STREAM_TRAINER_CODE = """
import sys
from ray_shuffling_data_loader_tpu import checkpoint as ckpt
from ray_shuffling_data_loader_tpu import multiqueue_service as svc
from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset

host, port, ckpt_path, out_path, seed, epochs = sys.argv[1:7]
port, seed, epochs = int(port), int(seed), int(epochs)

remote = svc.RemoteQueue((host, port), ack_mode="manual", consumer_id=77)
ds = ShufflingDataset([], epochs, num_trainers=1, batch_size=30, rank=0,
                      batch_queue=remote, shuffle_result=None, seed=seed)
try:
    checkpoint = ckpt.LoaderCheckpoint.load(ckpt_path)
except FileNotFoundError:
    checkpoint = ckpt.LoaderCheckpoint(
        seed=seed, epoch=0, batches_consumed=0, num_epochs=epochs,
        num_trainers=1, rank=0, batch_size=30)
with open(out_path, "a") as out:
    for batch in ckpt.resume_iterator(ds, checkpoint, ckpt_path,
                                      checkpoint_every=1):
        keys = ",".join(str(k) for k in
                        batch.column("key").to_pylist())
        out.write(f"{checkpoint.epoch}:{checkpoint.batches_consumed}:"
                  f"{keys}\\n")
        out.flush()
print("TRAINER DONE")
"""


def test_stream_trainer_kill9_mid_window_resume_exactly_once(
        tmp_parquet_dir):
    """Tentpole proof, trainer half: an online trainer is kill -9'd
    MID-WINDOW and a fresh process resumes from its LoaderCheckpoint
    against the streaming queue server (frozen window schedule). The
    merged output misses ZERO and duplicates ZERO (epoch, offset)
    positions across the window boundary — any replayed position is
    bit-identical, the deduped stream equals the fault-free grid."""
    seed = 13
    files = _make_stream_files(tmp_parquet_dir, 6, rows=64,
                               prefix="stream")
    config = _streaming_server_config(files, tmp_parquet_dir,
                                      num_trainers=1, num_reducers=3,
                                      seed=seed)
    epochs = len(config["epochs"])
    assert epochs == 3, "6 files / 2-file windows = 3 window-epochs"

    # Fault-free expectation: the exact batch grid of each window-epoch,
    # through the same ShufflingDataset batching the trainer uses.
    specs = st_window.specs_from_dicts(config["epochs"])
    grid_queue = mq.MultiQueue(epochs)

    def feed(rank, epoch, refs):
        if refs is None:
            grid_queue.put(plan_ir.queue_index(epoch, rank, 1), None)
        else:
            grid_queue.put_batch(plan_ir.queue_index(epoch, rank, 1),
                                 list(refs))

    shuffle_epochs(iter(specs), feed, 3, 1, max_concurrent_epochs=1,
                   seed=seed, file_cache=None, epochs_hint=epochs)
    ds = ShufflingDataset([], epochs, num_trainers=1, batch_size=30,
                          rank=0, batch_queue=grid_queue,
                          shuffle_result=None, seed=seed)
    expected = {}
    for epoch in range(epochs):
        ds.set_epoch(epoch)
        expected[epoch] = [tuple(b.column("key").to_pylist()) for b in ds]
    grid_queue.shutdown()

    supervisor, address = rt_sup.launch_supervised_queue_server(config)
    ckpt_path = os.path.join(tmp_parquet_dir, "loader.ckpt")
    out_path = os.path.join(tmp_parquet_dir, "consumed.txt")
    try:
        assert rt_sup.wait_for_server(address, timeout_s=60)
        host, port = address
        args = [sys.executable, "-c", _STREAM_TRAINER_CODE, host,
                str(port), ckpt_path, out_path, str(seed), str(epochs)]
        # Kill mid-window-0: after a couple of its ~5 batches land.
        _kill9_mid_stream_then_rerun(args, args, out_path, after_lines=2,
                                     done="TRAINER DONE")
    finally:
        supervisor.stop()

    # Offset accounting: merged by (epoch, batch offset), the deduped
    # positions must cover the fault-free grid exactly — zero missed,
    # zero duplicated.
    merged = _merged_positions(out_path)
    for epoch in range(epochs):
        batches = [merged[(epoch, i + 1)]
                   for i in range(len(expected[epoch]))]
        assert batches == expected[epoch], \
            f"window-epoch {epoch} diverged from the fault-free grid"
    assert len(merged) == sum(len(v) for v in expected.values()), \
        "positions outside the fault-free grid were delivered"


_POSITIONED_CONSUMER_CODE = """
import sys
import time
from ray_shuffling_data_loader_tpu import multiqueue_service as svc
from ray_shuffling_data_loader_tpu.plan import ir as plan_ir

host, port, out_path, epochs, pace_s = sys.argv[1:6]
# manual ack, never committed: the whole stream replays to a successor
remote = svc.RemoteQueue((host, int(port)), retries=12, max_batch=2,
                         ack_mode="manual", consumer_id=88)
with open(out_path, "a") as out:
    for epoch in plan_ir.epoch_range(0, int(epochs)):
        queue_idx = plan_ir.queue_index(epoch, 0, 1)
        while True:
            item, row_offset = remote.get_positioned(queue_idx)
            if item is None:
                break
            keys = ",".join(str(k) for k in
                            item.column("key").to_pylist())
            out.write(f"{epoch}:{row_offset}:{keys}\\n")
            out.flush()
            time.sleep(float(pace_s))    # a step a table: the kill lands
remote.close()
print("CONSUMER DONE")
"""


def test_stream_consumer_kill9_uncommitted_replays_the_same_row_offsets(
        tmp_parquet_dir):
    """The frames' own positions across a consumer's death: a consumer
    that reads ``get_positioned`` under manual ack and commits nothing is
    kill -9'd in the middle of the stream, and a fresh process under its
    identity reads on. Merged by (window-epoch, row_offset), what the two
    wrote is the fault-free lineage at the rows each table starts from:
    the same offsets, the same tables at each, every row exactly once."""
    seed, rows = 29, 64
    files = _make_stream_files(tmp_parquet_dir, 6, rows=rows,
                               prefix="positioned")
    config = _streaming_server_config(files, tmp_parquet_dir,
                                      num_trainers=1, num_reducers=3,
                                      seed=seed)
    epochs = len(config["epochs"])
    expected = {}
    for (_, epoch), tables in _expected_rank_streams(config).items():
        row_offset = 0
        for keys in tables:
            expected[(epoch, row_offset)] = keys
            row_offset += len(keys)

    out_path = os.path.join(tmp_parquet_dir, "delivered.txt")
    supervisor, address = rt_sup.launch_supervised_queue_server(config)
    try:
        assert rt_sup.wait_for_server(address, timeout_s=60)
        args = [sys.executable, "-c", _POSITIONED_CONSUMER_CODE,
                address[0], str(address[1]), out_path, str(epochs)]
        # paced into window 1, then read on at full speed
        _kill9_mid_stream_then_rerun(args + ["0.2"], args + ["0"], out_path,
                                     after_lines=4, done="CONSUMER DONE")
    finally:
        supervisor.stop()

    merged = _merged_positions(out_path)
    assert merged == expected, sorted(set(merged) ^ set(expected))[:5]
    assert sorted(k for table in merged.values() for k in table) == \
        list(range(6 * rows)), "rows missed or invented across the kill"


def test_stream_shard_kill9_at_window_boundary_replays_bit_identical(
        tmp_parquet_dir):
    """Tentpole proof, serving half: a queue SHARD serving a frozen
    window schedule is kill -9'd exactly at a window boundary (window
    0 fully drained, unacked). The restarted incarnation replays window
    0 bit-identically — same tables at the same absolute row offsets —
    and serves the remaining windows to the fault-free lineage: zero
    missed, zero duplicated row_offsets."""
    seed, trainers = 9, 2
    files = _make_stream_files(tmp_parquet_dir, 6, rows=64,
                               prefix="shardstream")
    config = _streaming_server_config(files, tmp_parquet_dir,
                                      num_trainers=trainers,
                                      num_reducers=4, seed=seed)
    epochs = len(config["epochs"])
    expected = _expected_rank_streams(config)

    supervisors, shard_map = rt_sup.launch_supervised_queue_shards(
        config, num_shards=2)
    assert shard_map.shard_for_rank(0) == 0

    def drain(ack_mode, epoch_list):
        """Rank 0's stream as ``{epoch: [(row_offset, keys)]}`` — frame
        identity AND payload, the offset-accounting unit."""
        out = {}
        with svc.ShardedRemoteQueue(shard_map, retries=12, max_batch=4,
                                    ack_mode=ack_mode) as remote:
            for epoch in epoch_list:
                queue_idx = plan_ir.queue_index(epoch, 0, trainers)
                stream = []
                while True:
                    item, row_offset = remote.get_positioned(queue_idx)
                    if item is None:
                        break
                    stream.append(
                        (row_offset,
                         tuple(item.column("key").to_pylist())))
                out[epoch] = stream
        return out

    try:
        for address in shard_map.addresses:
            assert rt_sup.wait_for_server(tuple(address), timeout_s=60)
        # Window 0 drained in full, manual-ack never committed: the
        # boundary is crossed with everything still unacked.
        first = drain("manual", [0])
        assert first[0]
        # kill -9 AT the window boundary, then a full resumed drain.
        os.kill(supervisors[0].pid, signal.SIGKILL)
        time.sleep(0.5)
        assert rt_sup.wait_for_server(tuple(shard_map.addresses[0]),
                                      timeout_s=60)
        full = drain("delivered", list(range(epochs)))
    finally:
        for supervisor in supervisors:
            supervisor.stop()

    assert supervisors[0].restarts >= 1
    assert supervisors[1].restarts == 0, \
        "killing one shard must not disturb its sibling"
    # (a) The replayed window is bit-identical INCLUDING row offsets.
    assert full[0] == first[0], \
        "window 0's replay diverged across the shard kill"
    # (b) Offset accounting per window-epoch: offsets strictly increase
    # (no duplicate, no reorder) and payloads equal the fault-free
    # lineage (no loss) — zero missed / zero duplicated row_offsets.
    for epoch in range(epochs):
        offsets = [offset for offset, _ in full[epoch]]
        assert offsets == sorted(set(offsets)), \
            f"window-epoch {epoch} duplicated or reordered row offsets"
        keys = [payload for _, payload in full[epoch]]
        assert keys == expected[(0, epoch)], \
            f"window-epoch {epoch} diverged from fault-free lineage"
