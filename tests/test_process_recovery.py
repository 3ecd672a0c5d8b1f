"""Process-level crash recovery (multiqueue_service v2 + supervisor).

The v1 cross-process topology died with its processes: a reset
mid-response lost batches, a killed server lost every queued table, a
crashed trainer leaked its queue, and no byte was integrity-checked.
These tests pin the v2 contract: sequenced/acked/CRC'd frames with
server-side replay, journal-backed server restart that regenerates only
the undelivered remainder from shuffle lineage, consumer leases with
policy-driven expiry, and checkpoint resume composed with real
``kill -9`` process death — every recovery asserted **bit-identical**
to a fault-free run.
"""

import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pyarrow as pa
import pytest

from ray_shuffling_data_loader_tpu import checkpoint as ckpt
from ray_shuffling_data_loader_tpu import data_generation as dg
from ray_shuffling_data_loader_tpu import multiqueue as mq
from ray_shuffling_data_loader_tpu import multiqueue_service as svc
from ray_shuffling_data_loader_tpu import spill as spill_mod
from ray_shuffling_data_loader_tpu import stats as rsdl_stats
from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset
from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
from ray_shuffling_data_loader_tpu.runtime import supervisor as rt_sup
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_tel
from ray_shuffling_data_loader_tpu.shuffle import (recompute_reducer_output,
                                                   shuffle as run_shuffle)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clear_chaos():
    yield
    rt_faults.clear()


def _fill_queue(n=20, sentinel=True):
    queue = mq.MultiQueue(1)
    for i in range(n):
        queue.put(0, pa.table({"seq": [i, i * 10]}))
    if sentinel:
        queue.put(0, None)
    return queue


def _drain_remote(remote, queue_idx=0):
    out = []
    while True:
        item = remote.get(queue_idx)
        if item is None:
            return out
        out.append(item.column("seq")[0].as_py())


# ---------------------------------------------------------------------------
# Wire protocol v2: integrity, replay, acks
# ---------------------------------------------------------------------------


def test_conn_reset_midframe_recovers_exactly_once():
    """A connection reset in the middle of a response frame (v1's silent
    batch loss) reconnects and replays the unacked suffix — no loss, no
    duplicate."""
    queue = _fill_queue(20)
    rt_faults.install("conn_reset_midframe:task0:after1", seed=0)
    with svc.serve_queue(queue) as server:
        with svc.RemoteQueue(server.address, max_batch=3) as remote:
            assert _drain_remote(remote) == list(range(20))
    # The recovery is joinable with the injected fault by construction:
    # the client's plain conn_reset_midframe event shares the fault
    # event's (kind, task) key.
    events = rt_tel.recorder().events()
    assert any(e["kind"] == "conn_reset_midframe" and e.get("fault")
               for e in events)
    assert any(e["kind"] == "conn_reset_midframe" and not e.get("fault")
               for e in events)


def test_frame_corrupt_nacked_and_resent():
    """A corrupt payload byte is caught by the frame CRC, NACK'd, and
    re-sent from the server's replay buffer — damaged bytes never reach
    the application."""
    before = rsdl_stats.process_recovery_totals()
    queue = _fill_queue(12)
    rt_faults.install("frame_corrupt:task0:after2", seed=0)
    with svc.serve_queue(queue) as server:
        with svc.RemoteQueue(server.address, max_batch=3) as remote:
            assert _drain_remote(remote) == list(range(12))
    delta = {k: v - before[k]
             for k, v in rsdl_stats.process_recovery_totals().items()}
    assert delta["queue_frames_corrupt"] >= 1
    assert delta["queue_frames_nacked"] >= 1
    assert delta["queue_frames_replayed"] >= 1


def test_reset_on_one_queue_and_corrupt_frame_on_another_in_one_session():
    """Both wire faults against one server and one consumer: the reset
    tears queue 0's response, the corrupt byte lands in queue 1's; the
    consumer reads both queues whole, in order, through the reconnect."""
    queue = mq.MultiQueue(2)
    for queue_idx in range(2):
        for i in range(12):
            queue.put(queue_idx, pa.table({"seq": [100 * queue_idx + i]}))
        queue.put(queue_idx, None)
    rt_faults.install(
        "conn_reset_midframe:task0:after1,frame_corrupt:task1:after2",
        seed=0)
    with svc.serve_queue(queue) as server:
        with svc.RemoteQueue(server.address, retries=12,
                             max_batch=2) as remote:
            for queue_idx in range(2):
                assert _drain_remote(remote, queue_idx) == [
                    100 * queue_idx + i for i in range(12)]


def test_ack_lost_is_harmless():
    """Acks are cumulative: suppressing one GET's watermark changes
    nothing about delivery."""
    queue = _fill_queue(10)
    rt_faults.install("ack_lost:task0", seed=0)
    with svc.serve_queue(queue) as server:
        with svc.RemoteQueue(server.address, max_batch=2) as remote:
            assert _drain_remote(remote) == list(range(10))


def test_manual_ack_mode_replays_uncommitted_after_reconnect():
    """ack_mode='manual': frames delivered but not committed stay in the
    server replay buffer; a fresh consumer (same identity, no local
    state — the crashed-trainer shape) sees them again, while committed
    frames are gone."""
    queue = _fill_queue(8)
    with svc.serve_queue(queue) as server:
        remote = svc.RemoteQueue(server.address, max_batch=2,
                                 ack_mode="manual", consumer_id=7)
        first = [remote.get(0).column("seq")[0].as_py() for _ in range(4)]
        assert first == [0, 1, 2, 3]
        remote.commit()          # durable through seq of item 3
        got = remote.get(0).column("seq")[0].as_py()  # delivered, uncommitted
        assert got == 4
        remote.close()           # trainer dies without committing item 4

        resumed = svc.RemoteQueue(server.address, max_batch=2,
                                  ack_mode="manual", consumer_id=7)
        rest = _drain_remote(resumed)
        resumed.close()
    # Item 4 replays (uncommitted at the crash); items 0-3 do not.
    assert rest == [4, 5, 6, 7]


def test_replay_buffer_backpressure_bounded():
    """An unacking consumer cannot grow the replay buffer past its byte
    budget: the server stops popping (min one frame per GET) instead of
    dropping unacked data."""
    os.environ["RSDL_QUEUE_REPLAY_BYTES"] = "1"
    try:
        queue = _fill_queue(6)
        with svc.serve_queue(queue) as server:
            with svc.RemoteQueue(server.address, max_batch=4,
                                 ack_mode="manual") as remote:
                # Never committing: every GET may carry at most one new
                # frame once over budget — the stream still completes.
                assert _drain_remote(remote) == list(range(6))
    finally:
        os.environ.pop("RSDL_QUEUE_REPLAY_BYTES", None)


# ---------------------------------------------------------------------------
# Shutdown race + socket hygiene (PR-5 satellites)
# ---------------------------------------------------------------------------


def test_server_close_joins_handlers_without_logging(caplog):
    """close() with a consumer blocked in a server-side GET drains the
    handler thread instead of letting it raise into the logger after the
    listener is gone."""
    queue = mq.MultiQueue(1)  # empty: the GET blocks server-side
    server = svc.serve_queue(queue)
    raw = socket.create_connection(server.address, timeout=10)
    raw.sendall(svc._REQUEST.pack(svc.OP_GET_BATCH, 0, 0, 4, svc.ACK_NONE))
    time.sleep(0.3)  # let the handler block in the queue pop
    with caplog.at_level("WARNING",
                         logger="ray_shuffling_data_loader_tpu."
                                "multiqueue_service"):
        server.close()
        time.sleep(0.3)
    raw.close()
    assert not server._accept_thread.is_alive()
    assert not server._conn_threads
    dropped = [r for r in caplog.records if "dropped" in r.message]
    assert not dropped, dropped


def test_socket_timeout_and_nodelay_resolve_through_policy():
    os.environ["RSDL_QUEUE_TIMEOUT_S"] = "7.5"
    os.environ["RSDL_QUEUE_NODELAY"] = "0"
    try:
        queue = _fill_queue(1)
        with svc.serve_queue(queue) as server:
            assert server._timeout_s == 7.5
            with svc.RemoteQueue(server.address) as remote:
                assert remote._sock.gettimeout() == 7.5
                assert remote._sock.getsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY) == 0
    finally:
        os.environ.pop("RSDL_QUEUE_TIMEOUT_S", None)
        os.environ.pop("RSDL_QUEUE_NODELAY", None)


# ---------------------------------------------------------------------------
# Consumer leases
# ---------------------------------------------------------------------------


def _with_lease_env(timeout_s, policy):
    os.environ["RSDL_QUEUE_LEASE_TIMEOUT_S"] = str(timeout_s)
    os.environ["RSDL_QUEUE_ON_DEAD_CONSUMER"] = policy


def _clear_lease_env():
    os.environ.pop("RSDL_QUEUE_LEASE_TIMEOUT_S", None)
    os.environ.pop("RSDL_QUEUE_ON_DEAD_CONSUMER", None)


def test_lease_expiry_fail_fast_downs_the_server():
    _with_lease_env(0.5, "fail_fast")
    try:
        before = rsdl_stats.process_recovery_totals()
        queue = _fill_queue(4)
        server = svc.serve_queue(queue)
        dead = svc.RemoteQueue(server.address, max_batch=1)
        dead.get(0)
        dead.close()  # heartbeats stop; no goodbye
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and not server._closed.is_set():
            time.sleep(0.05)
        assert server._closed.is_set(), \
            "fail_fast lease expiry must down the server"
        delta = rsdl_stats.process_recovery_totals()
        assert delta["queue_lease_expiries"] - \
            before["queue_lease_expiries"] >= 1
    finally:
        _clear_lease_env()


def test_lease_expiry_drain_frees_dead_consumer_queue():
    _with_lease_env(0.5, "drain")
    try:
        queue = _fill_queue(6, sentinel=False)
        with svc.serve_queue(queue) as server:
            dead = svc.RemoteQueue(server.address, max_batch=1)
            dead.get(0)
            dead.close()
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline and queue.size(0):
                time.sleep(0.05)
            assert queue.size(0) == 0, \
                "drain policy must free the dead consumer's queue"
    finally:
        _clear_lease_env()


def test_lease_expiry_redistributes_to_survivor():
    """Two trainer ranks; rank 0 dies. Its undelivered tables reroute to
    rank 1's queue, so epoch coverage survives the death."""
    _with_lease_env(0.7, "redistribute")
    try:
        queue = mq.MultiQueue(2)  # one epoch, two ranks
        for i in range(4):
            queue.put(0, pa.table({"seq": [i]}))        # rank 0
        for i in range(4, 6):
            queue.put(1, pa.table({"seq": [i]}))        # rank 1
        with svc.serve_queue(queue, num_trainers=2) as server:
            dead = svc.RemoteQueue(server.address, max_batch=1)
            dead.get(0)  # rank 0 consumes one table, then dies
            dead.close()
            survivor = svc.RemoteQueue(server.address, max_batch=1)
            got = []
            # 2 own tables + 3 redistributed from the dead rank.
            deadline = time.monotonic() + 20
            while len(got) < 5 and time.monotonic() < deadline:
                got.append(survivor.get(1).column("seq")[0].as_py())
            survivor.close()
        assert sorted(got) == [1, 2, 3, 4, 5], got
    finally:
        _clear_lease_env()


# ---------------------------------------------------------------------------
# Watermark journal
# ---------------------------------------------------------------------------


def test_watermark_journal_roundtrip_torn_tail_and_compact(tmp_path):
    path = str(tmp_path / "wal" / "watermarks.wal")
    journal = ckpt.WatermarkJournal(path)
    journal.record(0, 0, 100)
    journal.record(0, 3, 400)
    journal.record(1, 2, 300, done=True)
    journal.close()
    with open(path, "a") as f:
        f.write('{"crc": 1, "entry": {"q": 0, "seq": 9, "rows": 1, '
                '"done": false}}\n')   # bad crc: must be ignored
        f.write('{"crc": 123, "en')    # torn tail: must be ignored
    state = ckpt.WatermarkJournal.load(path)
    assert state[0].seq == 3 and state[0].rows == 400 and not state[0].done
    assert state[1].seq == 2 and state[1].done
    journal2 = ckpt.WatermarkJournal(path)
    journal2.compact()
    assert ckpt.WatermarkJournal.load(path) == state
    with open(path) as f:
        assert len(f.read().splitlines()) == 2  # one record per queue


def test_resume_plan_math():
    state = {
        0: ckpt.WatermarkEntry(seq=4, rows=500, done=True),   # e0 r0 done
        1: ckpt.WatermarkEntry(seq=4, rows=500, done=True),   # e0 r1 done
        2: ckpt.WatermarkEntry(seq=1, rows=200, done=False),  # e1 r0 partial
    }
    start_epoch, skip = svc._resume_plan(state, num_epochs=3,
                                         num_trainers=2)
    assert start_epoch == 1
    # Only queues at/after the resume epoch need item skips.
    assert skip == {2: 2}


# ---------------------------------------------------------------------------
# Spill integrity: crc + lineage recompute
# ---------------------------------------------------------------------------


def _spilled_handle(tmp_path, table, recompute=None):
    manager = spill_mod.SpillManager(str(tmp_path), over_budget=lambda: True)
    handle = manager.maybe_spill(table, recompute=recompute, epoch=0, task=0)
    assert isinstance(handle, spill_mod.SpilledTable)
    return handle


def test_spill_crc_detects_corruption_and_recomputes(tmp_path):
    table = pa.table({"x": list(range(64))})
    fs_before = rsdl_stats.fault_stats().snapshot()
    handle = _spilled_handle(tmp_path, table,
                             recompute=lambda: pa.table(
                                 {"x": list(range(64))}))
    with open(handle._path, "r+b") as f:  # flip one byte on disk
        f.seek(-3, os.SEEK_END)
        byte = f.read(1)
        f.seek(-3, os.SEEK_END)
        f.write(bytes([byte[0] ^ 0xFF]))
    loaded = handle.load()
    assert loaded.equals(table)
    fs_after = rsdl_stats.fault_stats().snapshot()
    assert fs_after["quarantines"] - fs_before["quarantines"] == 1
    assert fs_after["recomputes"] - fs_before["recomputes"] >= 1


def test_spill_corruption_without_lineage_fails_loudly(tmp_path):
    table = pa.table({"x": list(range(16))})
    handle = _spilled_handle(tmp_path, table)
    with open(handle._path, "r+b") as f:
        f.seek(-3, os.SEEK_END)
        f.write(b"\x00")
    with pytest.raises((spill_mod.SpillCorruption, pa.ArrowInvalid,
                        OSError)):
        handle.load()


def test_recompute_reducer_output_bit_identical(tmp_parquet_dir):
    """The spill recovery closure's foundation: a reducer output rebuilt
    from scratch lineage equals the pipeline's own output."""
    filenames, _ = dg.generate_data_local(300, 2, 1, 0.0, tmp_parquet_dir)
    streams = {}

    def consumer(trainer_idx, epoch, refs):
        if refs is not None:
            streams.setdefault(epoch, []).extend(refs)

    run_shuffle(filenames, consumer, 1, num_reducers=3, num_trainers=1,
                max_concurrent_epochs=1, seed=9, collect_stats=False,
                file_cache=None)
    for reduce_index, ref in enumerate(streams[0]):
        rebuilt = recompute_reducer_output(filenames, 3, 9, 0, reduce_index)
        assert rebuilt.equals(ref.result())


# ---------------------------------------------------------------------------
# Server process death: kill -9 + journal + lineage regeneration
# ---------------------------------------------------------------------------


def _reference_streams(filenames, epochs, reducers, seed):
    streams = {}

    def consumer(trainer_idx, epoch, refs):
        if refs is not None:
            streams.setdefault(epoch, []).extend(refs)

    run_shuffle(filenames, consumer, epochs, num_reducers=reducers,
                num_trainers=1, max_concurrent_epochs=1, seed=seed,
                collect_stats=False, file_cache=None)
    return {epoch: [tuple(r.result().column("key").to_pylist())
                    for r in refs]
            for epoch, refs in streams.items()}


def _consume_with_kills(address, filenames, epochs, seed, kill_points,
                        supervisor):
    remote = svc.RemoteQueue(address, retries=12, max_batch=2)
    ds = ShufflingDataset(filenames, epochs, num_trainers=1, batch_size=50,
                          rank=0, batch_queue=remote, shuffle_result=None,
                          seed=seed)
    got = {}
    kills = list(kill_points)
    for epoch in range(epochs):
        ds.set_epoch(epoch)
        tables = []
        for table in ds.iter_tables():
            tables.append(tuple(table.column("key").to_pylist()))
            if kills and (epoch, len(tables)) == kills[0]:
                os.kill(supervisor.pid, signal.SIGKILL)
                kills.pop(0)
        got[epoch] = tables
    remote.close()
    assert not kills, f"kill points never reached: {kills}"
    return got


def _kill9_scenario(tmp_parquet_dir, rows, epochs, reducers, seed,
                    kill_points):
    filenames, _ = dg.generate_data_local(rows, 2, 1, 0.0, tmp_parquet_dir)
    expected = _reference_streams(filenames, epochs, reducers, seed)
    journal = os.path.join(tmp_parquet_dir, "watermarks.wal")
    restarts_before = rsdl_stats.process_recovery_totals()[
        "queue_server_restarts"]
    supervisor, address = rt_sup.launch_supervised_queue_server(dict(
        filenames=filenames, num_epochs=epochs, num_trainers=1,
        num_reducers=reducers, seed=seed, max_concurrent_epochs=1,
        journal_path=journal, file_cache=None))
    try:
        assert rt_sup.wait_for_server(address, timeout_s=60)
        got = _consume_with_kills(address, filenames, epochs, seed,
                                  kill_points, supervisor)
    finally:
        supervisor.stop()
    assert supervisor.restarts >= len(kill_points)
    # the deaths reach the process's own totals (the trial report's row)
    assert rsdl_stats.process_recovery_totals()["queue_server_restarts"] \
        >= restarts_before + len(kill_points)
    assert got == expected, {
        epoch: (len(got[epoch]), len(expected[epoch]))
        for epoch in expected}


def test_queue_server_kill9_midepoch_resumes_bit_identical(tmp_parquet_dir):
    """Quick tier-1 variant: one real SIGKILL of the queue-server
    subprocess mid-epoch; the supervisor restarts it, the journal +
    shuffle lineage regenerate the undelivered remainder, and the
    consumer's stream is bit-identical to the fault-free run."""
    _kill9_scenario(tmp_parquet_dir, rows=400, epochs=2, reducers=3,
                    seed=5, kill_points=[(0, 2)])


@pytest.mark.slow
def test_queue_server_kill9_soak(tmp_parquet_dir):
    """Slow soak: repeated SIGKILLs across epochs (including one during
    the later epoch, exercising multi-epoch journal resume)."""
    _kill9_scenario(tmp_parquet_dir, rows=2_000, epochs=3, reducers=4,
                    seed=6, kill_points=[(0, 2), (1, 1), (2, 3)])


# ---------------------------------------------------------------------------
# Trainer process death: kill -9 + LoaderCheckpoint resume against the
# replaying queue (the crash/resume composition satellite)
# ---------------------------------------------------------------------------


_TRAINER_CODE = """
import sys
import numpy as np
from ray_shuffling_data_loader_tpu import checkpoint as ckpt
from ray_shuffling_data_loader_tpu import multiqueue_service as svc
from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset

host, port, ckpt_path, out_path, seed, epochs = sys.argv[1:7]
port, seed, epochs = int(port), int(seed), int(epochs)

remote = svc.RemoteQueue((host, port), ack_mode="manual", consumer_id=41)
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


def test_trainer_kill9_checkpoint_resume_bit_identical(tmp_parquet_dir):
    """Kill -9 a trainer subprocess mid-epoch; a fresh process resumes
    from its LoaderCheckpoint against the REPLAYING queue (manual acks
    committed at each checkpoint save), and the merged stream is
    bit-identical to a fault-free run — at-least-once across the crash,
    never a skip, never a divergence."""
    seed, epochs = 17, 2
    filenames, _ = dg.generate_data_local(600, 2, 1, 0.0, tmp_parquet_dir)

    # Fault-free expectation: the exact-size batch grid of each epoch.
    from ray_shuffling_data_loader_tpu.dataset import (
        create_batch_queue_and_shuffle)
    queue, result = create_batch_queue_and_shuffle(
        filenames, epochs, num_trainers=1, batch_size=30,
        max_concurrent_epochs=1, num_reducers=3, seed=seed,
        queue_name="proc-recovery-expect")
    ds = ShufflingDataset(filenames, epochs, num_trainers=1, batch_size=30,
                          rank=0, batch_queue=queue, shuffle_result=result,
                          seed=seed)
    expected = {}
    for epoch in range(epochs):
        ds.set_epoch(epoch)
        expected[epoch] = [tuple(b.column("key").to_pylist()) for b in ds]

    # Live pipeline served over the wire with a watermark journal.
    queue2, result2 = create_batch_queue_and_shuffle(
        filenames, epochs, num_trainers=1, batch_size=30,
        max_concurrent_epochs=1, num_reducers=3, seed=seed,
        queue_name="proc-recovery-live")
    journal = ckpt.WatermarkJournal(
        os.path.join(tmp_parquet_dir, "trainer.wal"))
    ckpt_path = os.path.join(tmp_parquet_dir, "loader.ckpt")
    out_path = os.path.join(tmp_parquet_dir, "consumed.txt")
    with svc.serve_queue(queue2, num_trainers=1, journal=journal) as server:
        host, port = server.address
        args = [sys.executable, "-c", _TRAINER_CODE, host, str(port),
                ckpt_path, out_path, str(seed), str(epochs)]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        first = subprocess.Popen(args, cwd=REPO_ROOT, env=env,
                                 stdout=subprocess.PIPE, text=True)
        # Kill -9 mid-epoch: after a few batches hit the output file.
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if os.path.exists(out_path) and \
                    sum(1 for _ in open(out_path)) >= 4:
                break
            time.sleep(0.05)
        os.kill(first.pid, signal.SIGKILL)
        first.wait(timeout=30)
        assert first.returncode == -9

        second = subprocess.run(args, cwd=REPO_ROOT, env=env,
                                capture_output=True, text=True,
                                timeout=240)
        assert second.returncode == 0, second.stderr[-3000:]
        assert "TRAINER DONE" in second.stdout
    result2.result()
    queue2.shutdown()

    # Merge: duplicates across the crash must be IDENTICAL (at-least-
    # once), and the deduped stream must equal the fault-free run.
    merged = {}
    for line in open(out_path):
        epoch_str, index_str, keys = line.strip().split(":", 2)
        position = (int(epoch_str), int(index_str))
        batch = tuple(int(k) for k in keys.split(",") if k)
        if position in merged:
            assert merged[position] == batch, \
                f"replayed batch {position} diverged"
        merged[position] = batch
    for epoch in range(epochs):
        batches = [merged[(epoch, i + 1)]
                   for i in range(len(expected[epoch]))]
        assert batches == expected[epoch], f"epoch {epoch} diverged"


# ---------------------------------------------------------------------------
# Supervisor unit behavior
# ---------------------------------------------------------------------------


def test_supervisor_restart_budget_exhaustion():
    os.environ["RSDL_SUPERVISOR_RETRY_MAX_ATTEMPTS"] = "3"
    os.environ["RSDL_SUPERVISOR_RETRY_INITIAL_BACKOFF_S"] = "0.01"
    os.environ["RSDL_SUPERVISOR_RETRY_MAX_BACKOFF_S"] = "0.02"
    try:
        spawned = []

        def spawn(restart_index):
            proc = subprocess.Popen([sys.executable, "-c", "pass"])
            spawned.append(proc)
            return proc

        supervisor = rt_sup.ProcessSupervisor(spawn, name="t").start()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not supervisor.failed:
            time.sleep(0.02)
        assert supervisor.failed
        assert supervisor.restarts == 3
        assert len(spawned) == 3  # initial + 2 restarts
        supervisor.stop()
    finally:
        os.environ.pop("RSDL_SUPERVISOR_RETRY_MAX_ATTEMPTS", None)
        os.environ.pop("RSDL_SUPERVISOR_RETRY_INITIAL_BACKOFF_S", None)
        os.environ.pop("RSDL_SUPERVISOR_RETRY_MAX_BACKOFF_S", None)


def test_queue_server_crash_site_downs_inprocess_server():
    """The queue_server_crash fault site models the whole server dying:
    in-process servers close (subprocess mode does os._exit)."""
    queue = _fill_queue(4)
    rt_faults.install("queue_server_crash:task0", seed=0)
    server = svc.serve_queue(queue)
    with svc.RemoteQueue(server.address, retries=1,
                         initial_backoff_s=0.05) as remote:
        with pytest.raises((RuntimeError, ConnectionError, OSError)):
            _drain_remote(remote)
    assert server._closed.is_set()
