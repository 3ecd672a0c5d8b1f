"""Deterministic fault injection + lineage recovery (runtime/faults.py,
runtime/retry.py, shuffle.EpochLineage).

The contract under test: every task is a pure function of
``(seed, epoch, task)``, so a lost task is RECOMPUTED from lineage —
and the recomputed stream is bit-identical to a fault-free run. The
seeded chaos spec makes those losses reproducible
(``RSDL_CHAOS_SPEC="map_read:epoch1:file2"`` fails the same way every
run), which is what lets these tests assert recovery exactly."""

import logging
import socket
import threading

import pyarrow as pa
import pytest

import importlib

from ray_shuffling_data_loader_tpu import checkpoint as ckpt_mod
from ray_shuffling_data_loader_tpu import data_generation as dg
from ray_shuffling_data_loader_tpu import dataset as dataset_mod
from ray_shuffling_data_loader_tpu import executor as ex
from ray_shuffling_data_loader_tpu import multiqueue as mq
from ray_shuffling_data_loader_tpu import multiqueue_service as mqs
from ray_shuffling_data_loader_tpu import spill as spill_mod
from ray_shuffling_data_loader_tpu import stats as stats_mod
from ray_shuffling_data_loader_tpu.runtime import faults, retry
from ray_shuffling_data_loader_tpu.parallel import transport as tr

# The package __init__ rebinds the ``shuffle`` attribute to the function.
sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")


@pytest.fixture(autouse=True)
def _no_chaos_leak():
    """Every test leaves the process chaos-free."""
    yield
    faults.clear()


def _delta(before, after):
    return {k: after[k] - before[k] for k in
            ("injected", "retries", "recomputes", "quarantines",
             "exhausted")}


# ---------------------------------------------------------------------------
# Chaos-spec parsing + injector semantics
# ---------------------------------------------------------------------------


def test_parse_spec_full_grammar():
    rules = faults.parse_spec(
        "map_read:epoch1:file2, reduce_gather:task0:x3,"
        "queue_get:task1:after2, transport_send@0.25")
    assert [(r.site, r.epoch, r.task, r.after, r.count, r.rate)
            for r in rules] == [
        ("map_read", 1, 2, 0, 1, None),
        ("reduce_gather", None, 0, 0, 3, None),
        ("queue_get", None, 1, 2, 1, None),
        ("transport_send", None, None, 0, 1, 0.25),
    ]


@pytest.mark.parametrize("bad", [
    "no_such_site", "map_read:bogus7", "map_read@1.5", "map_read:x0"])
def test_parse_spec_rejects_bad_rules(bad):
    with pytest.raises(ValueError):
        faults.parse_spec(bad)


def test_rule_fires_once_per_key_so_retries_succeed():
    injector = faults.FaultInjector(faults.parse_spec("map_read:file1"))
    fault = injector.check("map_read", 0, 1)
    assert isinstance(fault, faults.InjectedFault)
    assert (fault.site, fault.epoch, fault.task) == ("map_read", 0, 1)
    # The retry/recompute of the SAME key passes.
    assert injector.check("map_read", 0, 1) is None
    # A different epoch is a different key: fires again.
    assert injector.check("map_read", 1, 1) is not None
    # Non-matching task never fires.
    assert injector.check("map_read", 0, 0) is None


def test_after_and_count_qualifiers():
    injector = faults.FaultInjector(
        faults.parse_spec("queue_get:task3:after2:x2"))
    hits = [injector.check("queue_get", None, 3) is not None
            for _ in range(6)]
    assert hits == [False, False, True, True, False, False]


def test_rate_rules_are_deterministic_per_seed():
    def fired(seed):
        injector = faults.FaultInjector(
            faults.parse_spec("queue_put@0.3"), seed=seed)
        return {t for t in range(200)
                if injector.check("queue_put", None, t) is not None}

    first, second = fired(11), fired(11)
    assert first == second, "same seed must reproduce the same failures"
    assert 0 < len(first) < 200, "rate 0.3 should fire on some, not all"
    assert fired(12) != first, "different seed should differ somewhere"


def test_env_configuration_roundtrip(monkeypatch):
    monkeypatch.setenv("RSDL_CHAOS_SPEC", "spill_read")
    monkeypatch.setenv("RSDL_CHAOS_SEED", "9")
    injector = faults.configure_from_env()
    assert faults.active() and injector.seed == 9
    with pytest.raises(faults.InjectedFault):
        faults.inject("spill_read")
    monkeypatch.delenv("RSDL_CHAOS_SPEC")
    faults.configure_from_env()
    assert not faults.active()
    faults.inject("spill_read")  # inactive: free no-op


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------


class Flaky:
    def __init__(self, failures, exc_factory=RuntimeError):
        self.failures = failures
        self.calls = 0
        self.exc_factory = exc_factory

    def __call__(self, value=None):
        self.calls += 1
        if self.calls <= self.failures:
            raise self.exc_factory(f"injected #{self.calls}")
        return value


def test_retry_policy_bounded_attempts_and_jittered_backoff():
    sleeps = []
    policy = retry.RetryPolicy(max_attempts=4, initial_backoff_s=0.05,
                               max_backoff_s=10.0, seed=3,
                               sleep=sleeps.append)
    flaky = Flaky(3)
    assert policy.call(flaky, 42) == 42
    assert flaky.calls == 4
    assert len(sleeps) == 3
    assert all(s >= 0.05 for s in sleeps)
    # Decorrelated jitter with a seed is reproducible.
    sleeps2 = []
    retry.RetryPolicy(max_attempts=4, initial_backoff_s=0.05,
                      max_backoff_s=10.0, seed=3,
                      sleep=sleeps2.append).call(Flaky(3), 42)
    assert sleeps == sleeps2


def test_retry_policy_exhaustion_raises_last_error():
    policy = retry.RetryPolicy(max_attempts=2, initial_backoff_s=0,
                               sleep=lambda s: None)
    flaky = Flaky(5)
    with pytest.raises(RuntimeError, match="#2"):
        policy.call(flaky)
    assert flaky.calls == 2


def test_retry_policy_deadline_stops_early():
    policy = retry.RetryPolicy(max_attempts=50, deadline_s=0.0,
                               sleep=lambda s: None)
    flaky = Flaky(50)
    with pytest.raises(RuntimeError):
        policy.call(flaky)
    assert flaky.calls == 1, "an expired deadline must not burn attempts"


def test_retry_policy_respects_predicate_and_teardown_signals():
    policy = retry.RetryPolicy(max_attempts=5, sleep=lambda s: None,
                               retryable=lambda e: isinstance(e, OSError))
    flaky = Flaky(2, exc_factory=ValueError)
    with pytest.raises(ValueError):
        policy.call(flaky)
    assert flaky.calls == 1

    interrupts = Flaky(2, exc_factory=KeyboardInterrupt)
    with pytest.raises(KeyboardInterrupt):
        policy.call(interrupts)
    assert interrupts.calls == 1


def test_retry_policy_on_recovery_and_fault_stats():
    before = stats_mod.fault_stats().snapshot()
    recoveries = []
    policy = retry.RetryPolicy(max_attempts=3, initial_backoff_s=0,
                               sleep=lambda s: None)
    policy.call(Flaky(2), on_recovery=lambda n, s: recoveries.append(n))
    assert recoveries == [2]
    delta = _delta(before, stats_mod.fault_stats().snapshot())
    assert delta["retries"] == 2


def test_executor_retries_ride_retry_policy_and_log_final_error():
    sleeps = []
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    handler = Capture()
    retry_logger = logging.getLogger(
        "ray_shuffling_data_loader_tpu.runtime.retry")
    retry_logger.addHandler(handler)
    try:
        policy = retry.RetryPolicy(max_attempts=3, initial_backoff_s=0.01,
                                   seed=1, sleep=sleeps.append,
                                   component="executor")
        with ex.Executor(num_workers=1, retry_policy=policy) as pool:
            with pytest.raises(RuntimeError):
                pool.submit(Flaky(9)).result()
    finally:
        retry_logger.removeHandler(handler)
    assert len(sleeps) == 2 and all(s > 0 for s in sleeps), \
        "executor retries must back off, not hammer"
    final = [r for r in records if r.levelno == logging.ERROR]
    assert final, "the exhausted attempt must be logged at ERROR"


# ---------------------------------------------------------------------------
# Tentpole: the epoch survives injected task loss, bit-identically
# ---------------------------------------------------------------------------


def _consume_streams(filenames, *, num_epochs, num_trainers, seed,
                     queue_name, batch_size=16, num_reducers=4):
    """Run the full queue-routed pipeline; returns
    {(rank, epoch): [batch key-tuples...]} for every trainer stream."""
    queue, result = dataset_mod.create_batch_queue_and_shuffle(
        filenames, num_epochs, num_trainers, batch_size,
        max_concurrent_epochs=2, num_reducers=num_reducers, seed=seed,
        queue_name=queue_name, file_cache=None)
    streams = {}
    errors = []

    def run(rank):
        try:
            ds = dataset_mod.ShufflingDataset(
                filenames, num_epochs, num_trainers, batch_size, rank,
                batch_queue=queue,
                shuffle_result=result if rank == 0 else None,
                num_reducers=num_reducers, seed=seed)
            for epoch in range(num_epochs):
                ds.set_epoch(epoch)
                batches = []
                for table in ds:
                    batches.append(
                        tuple(table.column(dg.KEY_COLUMN).to_pylist()))
                streams[(rank, epoch)] = batches
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append((rank, e))

    threads = [threading.Thread(target=run, args=(r,), daemon=True)
               for r in range(num_trainers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "trainer hung"
    if errors:
        raise AssertionError(f"rank {errors[0][0]} failed") from errors[0][1]
    result.result()  # zero ShuffleFailure: the driver must have succeeded
    queue.shutdown()
    return streams


def test_chaos_epoch_survives_map_and_reduce_loss_bit_identically(
        tmp_parquet_dir):
    """THE acceptance scenario: one map-task failure and one reduce-gather
    failure injected per epoch; the 2-epoch/2-trainer shuffle completes
    with zero ShuffleFailure items, recomputes >= 2, and a batch stream
    bit-identical to the fault-free run with the same seed."""
    filenames, _ = dg.generate_data_local(240, 4, 1, 0.0, tmp_parquet_dir)
    clean = _consume_streams(filenames, num_epochs=2, num_trainers=2,
                             seed=13, queue_name="MQ-chaos-clean")

    faults.install("map_read:file1,reduce_gather:task0", seed=0)
    before = stats_mod.fault_stats().snapshot()
    try:
        chaotic = _consume_streams(filenames, num_epochs=2, num_trainers=2,
                                   seed=13, queue_name="MQ-chaos-injected")
    finally:
        faults.clear()
    delta = _delta(before, stats_mod.fault_stats().snapshot())

    # One map + one reduce failure per epoch actually happened...
    assert delta["injected"] >= 4, delta
    # ...and every loss was recovered by recompute, none exhausted.
    assert delta["recomputes"] >= 2, delta
    assert delta["exhausted"] == 0, delta
    # Bit-identical consumed streams, batch for batch, rank for rank.
    assert chaotic == clean


def test_chaos_recovery_exhaustion_reaches_poison_pill(tmp_parquet_dir):
    """x9 exceeds every retry budget: the file's map task can never be
    recomputed, recovery exhausts, and ONLY then does the failure reach
    the consumer (as the poison-pill RuntimeError chain)."""
    filenames, _ = dg.generate_data_local(80, 2, 1, 0.0, tmp_parquet_dir)
    faults.install("map_read:file0:x99", seed=0)
    before = stats_mod.fault_stats().snapshot()
    ds = dataset_mod.ShufflingDataset(
        filenames, num_epochs=1, num_trainers=1, batch_size=10, rank=0,
        num_reducers=2, file_cache=None, queue_name="MQ-chaos-exhaust")
    ds.set_epoch(0)
    with pytest.raises((faults.InjectedFault, RuntimeError)):
        for _ in ds:
            pass
    delta = _delta(before, stats_mod.fault_stats().snapshot())
    assert delta["exhausted"] >= 1, delta


def test_chaos_spec_env_var_reproduces_without_code(tmp_parquet_dir):
    """The zero-code reproduction path: a fresh process with
    RSDL_CHAOS_SPEC exported injects and recovers with no test scaffolding
    (what a multi-host PR will use to assert recovery deterministically)."""
    import os
    import subprocess
    import sys

    filenames, _ = dg.generate_data_local(80, 2, 1, 0.0, tmp_parquet_dir)
    code = """
import json, sys
from ray_shuffling_data_loader_tpu import stats
from ray_shuffling_data_loader_tpu import shuffle as sh_pkg
import importlib
sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
refs = []
def consumer(rank, epoch, batch_refs):
    if batch_refs is not None:
        refs.extend(batch_refs)
sh.shuffle(sys.argv[1:], consumer, num_epochs=1, num_reducers=2,
           num_trainers=1, collect_stats=False, file_cache=None)
rows = sum(r.result().num_rows for r in refs)
print(json.dumps({"rows": rows,
                  "stats": stats.fault_stats().snapshot()}))
"""
    # A rule's fire-once state is per process: every pool worker would
    # inherit the spec and fire it again on the recompute it is handed,
    # so whether recovery exhausts would depend on which worker that is.
    # One process, one injector: the thread backend.
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RSDL_CHAOS_SPEC="map_read:file0", RSDL_CHAOS_SEED="0",
               RSDL_EXECUTOR_BACKEND="thread")
    proc = subprocess.run([sys.executable, "-c", code] + list(filenames),
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    import json
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["rows"] == 80, "the injected loss must be fully recovered"
    assert out["stats"]["injected"] >= 1
    assert out["stats"]["recomputes"] >= 1
    assert out["stats"]["exhausted"] == 0


# ---------------------------------------------------------------------------
# Quarantine (on_bad_file)
# ---------------------------------------------------------------------------


def _collect_keys(filenames, **kwargs):
    refs = []
    lock = threading.Lock()

    def consumer(rank, epoch, batch_refs):
        if batch_refs is not None:
            with lock:
                refs.extend(batch_refs)

    sh.shuffle(filenames, consumer, num_epochs=1, num_reducers=2,
               num_trainers=1, collect_stats=False, file_cache=None,
               **kwargs)
    return sorted(k for ref in refs
                  for k in ref.result().column(dg.KEY_COLUMN).to_pylist())


def test_corrupt_file_quarantined_under_skip_policy(tmp_parquet_dir):
    filenames, _ = dg.generate_data_local(120, 3, 1, 0.0, tmp_parquet_dir)
    good_keys = _collect_keys(filenames)
    with open(filenames[1], "wb") as f:
        f.write(b"this is not a parquet file")
    before = stats_mod.fault_stats().snapshot()
    surviving = _collect_keys(filenames, on_bad_file="skip")
    delta = _delta(before, stats_mod.fault_stats().snapshot())
    assert delta["quarantines"] == 1
    report = stats_mod.fault_stats()["recent_quarantines"][-1]
    assert report["filename"] == filenames[1] and report["file_index"] == 1
    # Exactly the corrupt file's rows are missing; the rest shuffled.
    assert set(surviving) < set(good_keys)
    assert len(surviving) == 80


def test_corrupt_file_raises_under_default_policy(tmp_parquet_dir):
    filenames, _ = dg.generate_data_local(80, 2, 1, 0.0, tmp_parquet_dir)
    with open(filenames[0], "wb") as f:
        f.write(b"garbage")
    with pytest.raises(pa.ArrowInvalid):
        _collect_keys(filenames)


def test_bad_on_bad_file_value_rejected(tmp_parquet_dir):
    filenames, _ = dg.generate_data_local(40, 1, 1, 0.0, tmp_parquet_dir)
    with pytest.raises(ValueError, match="on_bad_file"):
        sh.shuffle_map(filenames[0], 2, 0, 0, 0, on_bad_file="ignore")


# ---------------------------------------------------------------------------
# Satellite: checkpoint resume after an injected mid-epoch crash
# ---------------------------------------------------------------------------


def test_checkpoint_resume_after_injected_crash_is_bit_identical(
        tmp_parquet_dir, tmp_path):
    """Kill the consumer via a chaos site mid-epoch-1, resume from the
    persisted LoaderCheckpoint, and assert prefix + resumed replay is
    bit-identical to an uninjected run."""
    filenames, _ = dg.generate_data_local(120, 3, 1, 0.0, tmp_parquet_dir)
    seed, num_epochs, batch_size = 5, 3, 10

    def make_ds(queue_name, start_epoch=0):
        return dataset_mod.ShufflingDataset(
            filenames, num_epochs, num_trainers=1, batch_size=batch_size,
            rank=0, num_reducers=2, seed=seed, file_cache=None,
            start_epoch=start_epoch, queue_name=queue_name)

    # Fault-free reference stream (all three epochs, per-batch keys).
    clean_ds = make_ds("MQ-ckpt-clean")
    clean = []
    for epoch in range(num_epochs):
        clean_ds.set_epoch(epoch)
        for table in clean_ds:
            clean.append(tuple(table.column(dg.KEY_COLUMN).to_pylist()))

    # Crash run: epoch 1's queue (queue_idx = 1*1+0 = 1) dies on its
    # SECOND get — i.e. mid-epoch, with batches already consumed.
    ckpt_path = str(tmp_path / "loader.json")
    faults.install("queue_get:task1:after1", seed=0)
    crashed = []
    checkpoint = ckpt_mod.LoaderCheckpoint(
        seed=seed, epoch=0, batches_consumed=0, num_epochs=num_epochs,
        num_trainers=1, rank=0, batch_size=batch_size)
    with pytest.raises(faults.InjectedFault):
        for table in ckpt_mod.resume_iterator(
                make_ds("MQ-ckpt-crash"), checkpoint,
                checkpoint_path=ckpt_path, checkpoint_every=1):
            crashed.append(tuple(table.column(dg.KEY_COLUMN).to_pylist()))
    faults.clear()
    assert crashed, "the crash must land mid-run, after real consumption"

    # Resume from the persisted checkpoint in a FRESH pipeline.
    restored = ckpt_mod.LoaderCheckpoint.load(ckpt_path)
    assert restored.epoch == 1
    epoch0_batches = 120 // batch_size
    assert restored.batches_consumed == len(crashed) - epoch0_batches
    assert restored.batches_consumed > 0, "crash must be MID-epoch"
    resumed = []
    for table in ckpt_mod.resume_iterator(
            make_ds("MQ-ckpt-resume", start_epoch=restored.epoch),
            restored):
        resumed.append(tuple(table.column(dg.KEY_COLUMN).to_pylist()))

    assert crashed + resumed == clean, \
        "prefix + resumed stream must replay the uninjected run exactly"


# ---------------------------------------------------------------------------
# Transport / queue / spill / remote-queue sites
# ---------------------------------------------------------------------------


def test_transport_injected_send_fault_redials_and_delivers():
    t0, t1 = tr.create_local_transports(2)
    try:
        faults.install("transport_send:epoch0:task3", seed=0)
        before = stats_mod.fault_stats().snapshot()
        t0.send(1, (0, 3, 0), b"survives-redial")
        assert t1.recv(0, (0, 3, 0), timeout_s=10) == b"survives-redial"
        delta = _delta(before, stats_mod.fault_stats().snapshot())
        assert delta["injected"] == 1
    finally:
        faults.clear()
        t0.close()
        t1.close()


def test_transport_injected_recv_fault_is_retryable():
    t0, t1 = tr.create_local_transports(2)
    try:
        t0.send(1, (0, 0, 0), b"payload")
        faults.install("transport_recv:epoch0:task0", seed=0)
        with pytest.raises(faults.InjectedFault):
            t1.recv(0, (0, 0, 0), timeout_s=10)
        # The message was NOT consumed by the failed recv: a caller-level
        # retry gets it.
        assert t1.recv(0, (0, 0, 0), timeout_s=10) == b"payload"
    finally:
        faults.clear()
        t0.close()
        t1.close()


def test_spill_write_fault_degrades_to_in_memory(tmp_path):
    manager = spill_mod.SpillManager(str(tmp_path), over_budget=lambda: True)
    table = pa.table({"x": list(range(100))})
    faults.install("spill_write", seed=0)
    kept = manager.maybe_spill(table)
    assert kept is table, "a failed spill write must keep the table"
    assert manager.spill_count == 0
    faults.clear()
    handle = manager.maybe_spill(table)
    assert isinstance(handle, spill_mod.SpilledTable)
    assert handle.load().equals(table)


def test_spill_read_fault_fails_consumer_loudly(tmp_path):
    manager = spill_mod.SpillManager(str(tmp_path), over_budget=lambda: True)
    handle = manager.maybe_spill(pa.table({"x": [1, 2, 3]}))
    assert isinstance(handle, spill_mod.SpilledTable)
    faults.install("spill_read", seed=0)
    with pytest.raises(faults.InjectedFault):
        handle.load()
    faults.clear()
    assert handle.load().num_rows == 3  # nothing was consumed by the fault


def test_remote_queue_fetch_retries_injected_fault():
    queue = mq.MultiQueue(1, name=None)
    queue.put(0, pa.table({"x": [1, 2]}))
    queue.put(0, None)
    server = mqs.serve_queue(queue)
    try:
        faults.install("queue_fetch:task0", seed=0)
        before = stats_mod.fault_stats().snapshot()
        client = mqs.RemoteQueue(server.address, prefetch=False)
        table = client.get(0)
        delta = _delta(before, stats_mod.fault_stats().snapshot())
        assert delta["injected"] == 1 and delta["retries"] >= 1
        assert table.column("x").to_pylist() == [1, 2]
        assert client.get(0) is None
        client.close()
    finally:
        faults.clear()
        server.close()
        queue.shutdown()


def test_remote_queue_fetch_survives_server_connection_reset():
    """A socket killed between round trips reconnects and refetches (the
    request had not consumed anything server-side)."""
    queue = mq.MultiQueue(1, name=None)
    queue.put(0, pa.table({"x": [7]}))
    queue.put(0, None)
    server = mqs.serve_queue(queue)
    try:
        client = mqs.RemoteQueue(server.address, prefetch=False)
        # Sever the client's socket: the next fetch hits a dead pipe
        # before any response byte, reconnects, and re-requests.
        client._sock.shutdown(socket.SHUT_RDWR)
        client._sock.close()
        table = client.get(0)
        assert table.column("x").to_pylist() == [7]
        client.close()
    finally:
        server.close()
        queue.shutdown()
