"""Tests for the map/reduce shuffle engine (shuffle.py)."""

import collections
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import importlib

from ray_shuffling_data_loader_tpu import executor as ex
from ray_shuffling_data_loader_tpu import stats as stats_mod

# The package re-exports the shuffle *function* under the module's name for
# parity with the reference (__init__.py), so fetch the module explicitly.
sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")


def write_files(tmp_path, num_files=4, rows_per_file=100):
    """Parquet files with a globally-unique monotonically increasing key."""
    filenames = []
    for i in range(num_files):
        start = i * rows_per_file
        table = pa.table({
            "key": pa.array(range(start, start + rows_per_file),
                            type=pa.int64()),
            "value": pa.array(
                np.arange(start, start + rows_per_file, dtype=np.float64)),
        })
        path = str(tmp_path / f"input_{i}.parquet")
        pq.write_table(table, path)
        filenames.append(path)
    return filenames


class CollectingConsumer:
    """batch_consumer that materializes every reducer table per (rank, epoch)."""

    def __init__(self):
        self.tables = collections.defaultdict(list)
        self.sentinels = []
        self.lock = threading.Lock()

    def __call__(self, rank, epoch, refs):
        if refs is None:
            with self.lock:
                self.sentinels.append((rank, epoch))
        else:
            # Resolve the reduce futures BEFORE taking the lock: holding
            # it across ref.result() would serialize every concurrent
            # consumer behind the slowest reducer.
            tables = [ref.result() for ref in refs]
            with self.lock:
                self.tables[(rank, epoch)].extend(tables)

    def epoch_keys(self, epoch, num_trainers):
        keys = []
        for rank in range(num_trainers):
            for table in self.tables[(rank, epoch)]:
                keys.extend(table.column("key").to_pylist())
        return keys


def test_every_key_exactly_once_per_epoch(tmp_path):
    filenames = write_files(tmp_path, num_files=4, rows_per_file=100)
    consumer = CollectingConsumer()
    result = sh.shuffle(filenames, consumer, num_epochs=3, num_reducers=5,
                        num_trainers=2, max_concurrent_epochs=2, seed=7)
    assert isinstance(result, stats_mod.TrialStats)
    for epoch in range(3):
        keys = consumer.epoch_keys(epoch, num_trainers=2)
        assert sorted(keys) == list(range(400)), f"epoch {epoch} key multiset"
    # One sentinel per (rank, epoch).
    assert sorted(consumer.sentinels) == sorted(
        (r, e) for r in range(2) for e in range(3))


def test_epochs_are_permutations_not_identical(tmp_path):
    filenames = write_files(tmp_path, num_files=2, rows_per_file=200)
    consumer = CollectingConsumer()
    sh.shuffle(filenames, consumer, num_epochs=2, num_reducers=3,
               num_trainers=1, seed=11, collect_stats=False)
    e0 = consumer.epoch_keys(0, 1)
    e1 = consumer.epoch_keys(1, 1)
    assert sorted(e0) == sorted(e1)
    assert e0 != e1  # different permutations across epochs


def test_shuffle_deterministic_replay(tmp_path):
    filenames = write_files(tmp_path, num_files=3, rows_per_file=50)
    runs = []
    for _ in range(2):
        consumer = CollectingConsumer()
        sh.shuffle(filenames, consumer, num_epochs=2, num_reducers=4,
                   num_trainers=2, seed=42, collect_stats=False)
        runs.append({k: [t.column("key").to_pylist() for t in v]
                     for k, v in consumer.tables.items()})
    assert runs[0] == runs[1]


def test_different_seeds_differ(tmp_path):
    filenames = write_files(tmp_path, num_files=2, rows_per_file=100)
    orders = []
    for seed in (1, 2):
        consumer = CollectingConsumer()
        sh.shuffle(filenames, consumer, num_epochs=1, num_reducers=2,
                   num_trainers=1, seed=seed, collect_stats=False)
        orders.append(consumer.epoch_keys(0, 1))
    assert sorted(orders[0]) == sorted(orders[1])
    assert orders[0] != orders[1]


def test_single_reducer_single_trainer(tmp_path):
    filenames = write_files(tmp_path, num_files=2, rows_per_file=30)
    consumer = CollectingConsumer()
    sh.shuffle(filenames, consumer, num_epochs=1, num_reducers=1,
               num_trainers=1, seed=0, collect_stats=False)
    assert sorted(consumer.epoch_keys(0, 1)) == list(range(60))


def test_more_reducers_than_rows(tmp_path):
    # The reference asserts len(rows) > num_reducers (shuffle.py:209); we
    # support tiny files — empty reducer outputs are legal.
    filenames = write_files(tmp_path, num_files=1, rows_per_file=3)
    consumer = CollectingConsumer()
    sh.shuffle(filenames, consumer, num_epochs=1, num_reducers=8,
               num_trainers=2, seed=0, collect_stats=False)
    assert sorted(consumer.epoch_keys(0, 2)) == [0, 1, 2]


def test_stats_collected(tmp_path):
    filenames = write_files(tmp_path, num_files=3, rows_per_file=40)
    consumer = CollectingConsumer()
    trial_stats = sh.shuffle(filenames, consumer, num_epochs=2,
                             num_reducers=2, num_trainers=2, seed=0,
                             collect_stats=True)
    assert trial_stats.duration > 0
    assert len(trial_stats.epoch_stats) == 2
    for es in trial_stats.epoch_stats:
        assert len(es.map_stats.task_durations) == 3
        assert len(es.map_stats.read_durations) == 3
        assert len(es.reduce_stats.task_durations) == 2
        assert len(es.consume_stats.task_durations) == 2
        assert es.duration > 0
        assert es.map_stats.stage_duration > 0
        assert es.reduce_stats.stage_duration > 0


def test_throttle_limits_concurrency(tmp_path):
    """With max_concurrent_epochs=1, epoch N+1's maps never overlap epoch
    N's reducers."""
    filenames = write_files(tmp_path, num_files=2, rows_per_file=50)
    active = {"reduces": 0, "max_overlap": 0}
    lock = threading.Lock()
    orig_reduce = sh.shuffle_reduce

    def tracking_reduce(reduce_index, seed, epoch, chunks,
                        stats_collector=None, reduce_transform=None,
                        gather_threads=None):
        with lock:
            active["reduces"] += 1
            active["max_overlap"] = max(active["max_overlap"],
                                        active["reduces"])
        try:
            return orig_reduce(reduce_index, seed, epoch, chunks,
                               stats_collector, reduce_transform)
        finally:
            with lock:
                active["reduces"] -= 1
    # 3 epochs, 2 reducers each, serialized epochs: overlap must be <= 2.
    import unittest.mock as mock
    with mock.patch.object(sh, "shuffle_reduce", tracking_reduce):
        consumer = CollectingConsumer()
        sh.shuffle(filenames, consumer, num_epochs=3, num_reducers=2,
                   num_trainers=1, max_concurrent_epochs=1, seed=0,
                   collect_stats=False)
    assert active["max_overlap"] <= 2


def test_shuffle_in_background_returns_joinable_ref(tmp_path):
    filenames = write_files(tmp_path, num_files=2, rows_per_file=40)
    consumer = CollectingConsumer()
    ref = sh.run_shuffle_in_background(
        filenames, consumer, num_epochs=2, num_reducers=2, num_trainers=1,
        seed=0)
    duration = ref.result(timeout=60)
    assert isinstance(duration, float)
    assert sorted(consumer.epoch_keys(0, 1)) == list(range(80))
    assert sorted(consumer.epoch_keys(1, 1)) == list(range(80))


def test_small_pool_no_deadlock(tmp_path):
    """More reducers than worker threads must not deadlock."""
    filenames = write_files(tmp_path, num_files=6, rows_per_file=20)
    consumer = CollectingConsumer()
    sh.shuffle(filenames, consumer, num_epochs=2, num_reducers=12,
               num_trainers=2, max_concurrent_epochs=2, seed=0,
               num_workers=2, collect_stats=False)
    assert sorted(consumer.epoch_keys(0, 2)) == list(range(120))


def test_reduce_preserves_one_row(tmp_path):
    """Regression guard on the reference's len==1 bug (shuffle.py:241-242)."""
    table = pa.table({"key": pa.array([7], type=pa.int64())})
    out = sh.shuffle_reduce(0, seed=0, epoch=0, chunks=[table])
    assert isinstance(out, pa.Table)
    assert out.column("key").to_pylist() == [7]


def test_map_failure_propagates_not_hangs(tmp_path):
    """A missing input file must raise promptly, not hang the driver
    (regression: task exceptions used to be swallowed by ex.wait)."""
    consumer = CollectingConsumer()
    with pytest.raises(FileNotFoundError):
        sh.shuffle([str(tmp_path / "missing.parquet")], consumer,
                   num_epochs=1, num_reducers=2, num_trainers=1, seed=0,
                   collect_stats=True)


def test_derive_gather_threads_scales_with_cores(monkeypatch):
    """Threads per reduce gather = cores / concurrent reduce tasks,
    clamped to [1, 16] (round-3 reduce-stage thread tuning)."""
    monkeypatch.setattr(sh._os, "cpu_count", lambda: 96)
    assert sh.derive_gather_threads(4, 96) == 16   # capped
    assert sh.derive_gather_threads(12, 96) == 8
    assert sh.derive_gather_threads(19, 96) == 5
    # Loopback multi-host emulation splits the machine across "hosts".
    assert sh.derive_gather_threads(4, 96, host_share=4) == 6
    monkeypatch.setattr(sh._os, "cpu_count", lambda: 8)
    assert sh.derive_gather_threads(19, 8) == 1    # no oversubscription
    monkeypatch.setattr(sh._os, "cpu_count", lambda: 1)
    assert sh.derive_gather_threads(4, 8) == 1
    monkeypatch.setattr(sh._os, "cpu_count", lambda: None)
    assert sh.derive_gather_threads(0, 0) == 1     # degenerate inputs


def test_composed_shuffle_position_uniformity(tmp_path):
    """The COMPOSED shuffle (uniform reducer assignment -> per-reducer
    permutation -> contiguous reducer routing) must place any given key
    approximately uniformly over output positions across seeds — the
    statistical contract the reference's unseeded two-stage shuffle
    provides only in expectation (reference: shuffle.py:213,240)."""
    filenames = write_files(tmp_path, num_files=2, rows_per_file=100)
    n, buckets, trials = 200, 4, 48
    counts = np.zeros(buckets, dtype=int)
    for seed in range(trials):
        consumer = CollectingConsumer()
        # On threads: the order is a function of (seed, epoch, task) alone
        # and bit-identical across backends (tests/test_procpool.py), and
        # 48 spawned pools would cost minutes to shuffle 200 rows each.
        sh.shuffle(filenames, consumer, num_epochs=1, num_reducers=3,
                   num_trainers=1, seed=seed, collect_stats=False,
                   executor_backend="thread")
        order = consumer.epoch_keys(0, 1)
        pos = order.index(0)  # tracked key
        counts[pos * buckets // n] += 1
    # Chi-square against uniform: df=3, p=0.001 critical value ~16.27.
    expected = trials / buckets
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    assert chi2 < 16.27, (counts.tolist(), chi2)
