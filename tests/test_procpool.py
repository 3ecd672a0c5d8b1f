"""Process-pool data plane tests (procpool.py): Executor-contract parity,
shared-memory Arrow handoff, thread/process bit-identity, worker-death
lineage recovery, and per-worker trace dumps."""

import glob
import os
import signal
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import importlib

sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
from ray_shuffling_data_loader_tpu import executor as ex
from ray_shuffling_data_loader_tpu import procpool
from ray_shuffling_data_loader_tpu import spill
from ray_shuffling_data_loader_tpu import stats as stats_mod
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.runtime import trace as rt_trace


def _write_files(tmp_path, num_files=3, rows=400, seed=0):
    rng = np.random.default_rng(seed)
    files = []
    for i in range(num_files):
        table = pa.table({
            "a": rng.integers(0, 1000, rows).astype(np.int64),
            "b": rng.random(rows),
            "c": rng.integers(0, 7, rows).astype(np.int32),
        })
        path = str(tmp_path / f"part_{i}.parquet")
        pq.write_table(table, path)
        files.append(path)
    return files


def _run_shuffle(files, backend, num_epochs=2, num_reducers=3, seed=11,
                 num_workers=2, on_bad_file=None):
    got = {}
    lock = threading.Lock()

    def consumer(trainer, epoch, refs):
        if refs is None:
            return
        for ref in refs:
            table = spill.unwrap(ref.result())
            with lock:
                got.setdefault(epoch, []).append(table)

    sh.shuffle(files, consumer, num_epochs=num_epochs,
               num_reducers=num_reducers, num_trainers=1, seed=seed,
               num_workers=num_workers, collect_stats=False,
               executor_backend=backend, on_bad_file=on_bad_file)
    return {epoch: pa.concat_tables(tables, promote_options="permissive")
            for epoch, tables in got.items()}


# ---------------------------------------------------------------------------
# Executor contract
# ---------------------------------------------------------------------------


def test_generic_submit_and_wait_contract():
    with procpool.ProcessPoolExecutor(num_workers=2) as pool:
        assert pool.backend == "process"
        assert pool.num_workers == 2
        refs = [pool.submit(os.path.join, "a", str(i)) for i in range(4)]
        done, not_done = ex.wait(refs, num_returns=len(refs))
        assert len(done) == 4 and not not_done
        assert ex.get(refs) == [os.path.join("a", str(i)) for i in range(4)]
        once = pool.submit_once(os.path.basename, "/x/y")
        assert once.result() == "y"


def test_worker_pids_are_real_subprocesses():
    with procpool.ProcessPoolExecutor(num_workers=2) as pool:
        pids = pool.worker_pids()
        assert len(pids) == 2
        assert os.getpid() not in pids
        assert len(set(pids)) == 2
        # The ping task proves each pid is live and answering.
        reply = pool.submit_kind("ping", {}).result()
        assert reply["pid"] in pids


def test_sigusr1_during_worker_boot_does_not_kill_the_worker():
    """The incident capture SIGUSR1s every published pool pid, and a
    spawned worker imports for seconds before it can install its dump
    handler. A signal in that window must wait for the handler, not take
    the default action."""
    with procpool.ProcessPoolExecutor(num_workers=2) as pool:
        pids = pool.worker_pids()
        for pid in pids:
            os.kill(pid, signal.SIGUSR1)  # workers are still importing
        replies = [pool.submit_kind("ping", {}).result(timeout=60.0)
                   for _ in range(4)]
        assert {r["pid"] for r in replies} <= set(pids)
        assert pool.worker_pids() == pids, "a worker died and was respawned"


class _TwoArgError(Exception):
    """Pickles, but cannot be rebuilt from its ``.args`` alone."""

    def __init__(self, code, detail):
        super().__init__(f"{code}: {detail}")


def _raise(kind):
    if kind == "injected":
        from ray_shuffling_data_loader_tpu.runtime import faults
        raise faults.InjectedFault("map_read", 0, 3, "map_read:file3")
    raise _TwoArgError(7, "boom")


def test_worker_exception_that_cannot_be_rebuilt_comes_back():
    """An exception whose __init__ wants more than its .args dumps fine
    and then fails to load in the driver; that used to kill the
    dispatcher thread and leave the task's future pending forever."""
    from ray_shuffling_data_loader_tpu.runtime import faults
    with procpool.ProcessPoolExecutor(num_workers=1) as pool:
        with pytest.raises(faults.InjectedFault) as info:
            pool.submit_once(_raise, "injected").result(timeout=60.0)
        assert (info.value.site, info.value.task) == ("map_read", 3)
        with pytest.raises(procpool.RemoteTaskError, match="7: boom"):
            pool.submit_once(_raise, "custom").result(timeout=60.0)
        # The dispatcher survived both.
        assert pool.submit_kind("ping", {}).result(timeout=60.0)["pid"]


def test_submit_after_shutdown_raises():
    pool = procpool.ProcessPoolExecutor(num_workers=1)
    pool.shutdown()
    with pytest.raises(RuntimeError):
        pool.submit(os.getcwd)
    # Idempotent.
    pool.shutdown()


def test_shutdown_removes_segment_dir():
    pool = procpool.ProcessPoolExecutor(num_workers=1)
    seg_dir = pool.segment_dir
    assert os.path.isdir(seg_dir)
    pool.submit_kind("ping", {}).result()
    pool.shutdown()
    assert not os.path.exists(seg_dir)


# ---------------------------------------------------------------------------
# Shuffle data plane
# ---------------------------------------------------------------------------


def test_process_shuffle_bit_identical_to_thread(tmp_path):
    files = _write_files(tmp_path)
    thread = _run_shuffle(files, "thread")
    process = _run_shuffle(files, "process")
    assert sorted(thread) == sorted(process)
    for epoch in thread:
        assert thread[epoch].num_rows == 1200
        assert thread[epoch].equals(process[epoch]), f"epoch {epoch}"


def test_process_shuffle_trace_metadata_stamped(tmp_path):
    files = _write_files(tmp_path, num_files=2)
    got = {}

    def consumer(trainer, epoch, refs):
        if refs is None:
            return
        got.setdefault(epoch, []).extend(r.result() for r in refs)

    sh.shuffle(files, consumer, num_epochs=1, num_reducers=2,
               num_trainers=1, seed=5, num_workers=2,
               collect_stats=False, executor_backend="process")
    for table in got[0]:
        meta = table.schema.metadata or {}
        assert meta.get(b"rsdl.trace", b"").startswith(b"5:0:")


def test_process_shuffle_quarantines_corrupt_file(tmp_path):
    files = _write_files(tmp_path, num_files=3)
    with open(files[1], "wb") as f:
        f.write(b"this is not parquet")
    before = stats_mod.fault_stats().snapshot()["quarantines"]
    thread = _run_shuffle(files, "thread", num_epochs=1,
                          on_bad_file="skip")
    process = _run_shuffle(files, "process", num_epochs=1,
                           on_bad_file="skip")
    assert thread[0].num_rows == process[0].num_rows == 800
    assert thread[0].equals(process[0])
    after = stats_mod.fault_stats().snapshot()["quarantines"]
    assert after - before >= 2  # one per backend run


def test_segment_cache_reused_across_epochs(tmp_path):
    files = _write_files(tmp_path, num_files=2)
    got = {}

    def consumer(trainer, epoch, refs):
        if refs is None:
            return
        got.setdefault(epoch, []).extend(
            spill.unwrap(r.result()) for r in refs)

    pool = procpool.ProcessPoolExecutor(num_workers=2)
    try:
        sh.shuffle(files, consumer, num_epochs=3, num_reducers=2,
                   num_trainers=1, seed=3, collect_stats=False, pool=pool)
        # Decoded-table segments were published once per file and re-read
        # by later epochs (the process-backend file cache).
        assert pool.bytes_cached > 0
        assert len(glob.glob(os.path.join(pool.segment_dir,
                                          "table_f*.arrow"))) == 2
        # Epoch-scoped plan segments were unlinked as epochs drained
        # (the final epoch's may still be present until its refs drop).
        assert len(glob.glob(os.path.join(pool.segment_dir, "*.idx"))) <= 2
    finally:
        pool.shutdown()
    assert got[0][0].equals(got[0][0])
    total = {e: pa.concat_tables(ts, promote_options="permissive").num_rows for e, ts in got.items()}
    assert total == {0: 800, 1: 800, 2: 800}


def test_worker_kill9_recovers_from_lineage():
    before = stats_mod.fault_stats().snapshot()["recomputes"]
    with procpool.ProcessPoolExecutor(num_workers=1) as pool:
        victim = pool.worker_pids()[0]
        ref = pool.submit(time.sleep, 1.5)
        time.sleep(0.4)  # let the worker start the task
        os.kill(victim, signal.SIGKILL)
        # The dispatcher resubmits the (pure) task to the respawned
        # worker; the ref resolves instead of erroring.
        assert ref.result(timeout=30.0) is None
        assert pool.worker_pids()[0] != victim
    after = stats_mod.fault_stats().snapshot()["recomputes"]
    assert after - before >= 1


def test_worker_kill9_during_shuffle_bit_identical(tmp_path):
    files = _write_files(tmp_path, num_files=3, rows=2000)
    baseline = _run_shuffle(files, "process", num_epochs=2)

    got = {}
    lock = threading.Lock()

    def consumer(trainer, epoch, refs):
        if refs is None:
            return
        for ref in refs:
            table = spill.unwrap(ref.result())
            with lock:
                got.setdefault(epoch, []).append(table)

    pool = procpool.ProcessPoolExecutor(num_workers=2)
    killer_done = threading.Event()

    def killer():
        time.sleep(0.15)
        pids = pool.worker_pids()
        try:
            if pids:
                os.kill(pids[0], signal.SIGKILL)
        except OSError:
            pass  # worker already gone — the run still asserts identity
        killer_done.set()

    threading.Thread(target=killer, daemon=True).start()
    try:
        sh.shuffle(files, consumer, num_epochs=2, num_reducers=3,
                   num_trainers=1, seed=11, collect_stats=False, pool=pool)
    finally:
        killer_done.wait(timeout=5.0)
        pool.shutdown()
    for epoch, expected in baseline.items():
        assert pa.concat_tables(got[epoch], promote_options="permissive").equals(expected), f"e{epoch}"


def test_submit_once_not_resubmitted_after_worker_death():
    with procpool.ProcessPoolExecutor(num_workers=1) as pool:
        victim = pool.worker_pids()[0]
        ref = pool.submit_once(time.sleep, 5.0)
        time.sleep(0.4)
        os.kill(victim, signal.SIGKILL)
        with pytest.raises(procpool.WorkerDied):
            ref.result(timeout=30.0)


# ---------------------------------------------------------------------------
# Backend resolution
# ---------------------------------------------------------------------------


def test_resolve_backend_explicit_and_auto(monkeypatch):
    monkeypatch.setenv("RSDL_EXECUTOR_BACKEND", "thread")
    assert procpool.resolve_backend() == "thread"
    monkeypatch.setenv("RSDL_EXECUTOR_BACKEND", "process")
    assert procpool.resolve_backend() == "process"
    monkeypatch.delenv("RSDL_EXECUTOR_BACKEND")
    # kwarg rung beats env.
    assert procpool.resolve_backend(override="thread") == "thread"
    with pytest.raises(ValueError):
        procpool.resolve_backend(override="quantum")


def test_resolve_backend_auto_rejects_unpicklable_transform(monkeypatch):
    monkeypatch.setenv("RSDL_EXECUTOR_BACKEND", "auto")
    lock = threading.Lock()

    def unpicklable(table, _lock=lock):  # closure over a Lock
        return table

    assert procpool.resolve_backend(
        transforms=(unpicklable,), num_workers=4) == "thread"


def test_resolve_backend_auto_single_worker_stays_thread(monkeypatch):
    monkeypatch.setenv("RSDL_EXECUTOR_BACKEND", "auto")
    assert procpool.resolve_backend(num_workers=1) == "thread"


# ---------------------------------------------------------------------------
# Segment I/O primitives
# ---------------------------------------------------------------------------


def test_segment_roundtrip(tmp_path):
    table = pa.table({"x": np.arange(100, dtype=np.int64)})
    path = str(tmp_path / "seg.arrow")
    nbytes = procpool.write_table_segment(table, path)
    assert nbytes == os.stat(path).st_size > 0
    back = procpool.open_table_segment(path)
    assert back.equals(table)


def test_index_segment_roundtrip(tmp_path):
    offsets = np.array([0, 3, 5], dtype=np.int64)
    flat = np.array([4, 1, 0, 3, 2], dtype=np.int64)
    path = str(tmp_path / "seg.idx")
    procpool.write_index_segment(path, offsets, flat)
    got_off, got_flat = procpool.read_index_segment(path)
    assert np.array_equal(got_off, offsets)
    assert np.array_equal(got_flat, flat)


# ---------------------------------------------------------------------------
# Cross-process tracing
# ---------------------------------------------------------------------------


def test_process_shuffle_trace_spans_all_worker_pids(tmp_path, monkeypatch):
    trace_dir = tmp_path / "traces"
    trace_dir.mkdir()
    monkeypatch.setenv("RSDL_TRACE_DIR", str(trace_dir))
    rt_telemetry.configure()
    files = _write_files(tmp_path, num_files=2)
    pool = procpool.ProcessPoolExecutor(num_workers=2)
    worker_pids = set(pool.worker_pids())
    got = []

    def consumer(trainer, epoch, refs):
        if refs is None:
            return
        got.extend(spill.unwrap(r.result()) for r in refs)

    try:
        sh.shuffle(files, consumer, num_epochs=1, num_reducers=2,
                   num_trainers=1, seed=9, collect_stats=False, pool=pool)
    finally:
        pool.shutdown()  # workers exit cleanly -> atexit dumps fire
        rt_telemetry.dump(reason="test")  # the driver's own dump
        monkeypatch.delenv("RSDL_TRACE_DIR")
        rt_telemetry.configure()
    dumps = glob.glob(os.path.join(str(trace_dir), "*.jsonl"))
    assert dumps, "no per-process dumps written"
    merged = rt_trace.merge_dumps(dumps)
    pids = {proc["pid"] for proc in merged["processes"]}
    assert os.getpid() in pids
    assert worker_pids <= pids, (worker_pids, pids)
    assert len(pids) >= 3  # driver + both workers
    kinds = {ev["kind"] for ev in merged["events"]}
    assert "map_read" in kinds and "reduce_gather" in kinds
