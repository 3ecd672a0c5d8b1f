"""Tests for the JAX binding (jax_dataset.py) on a virtual 8-device CPU mesh."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_shuffling_data_loader_tpu import jax_dataset as jd
from ray_shuffling_data_loader_tpu import multiqueue as mq


@pytest.fixture(autouse=True)
def fresh_registry():
    mq._REGISTRY.clear()
    yield
    mq._REGISTRY.clear()


def write_files(tmp_path, num_files=2, rows_per_file=128):
    filenames = []
    for i in range(num_files):
        start = i * rows_per_file
        n = rows_per_file
        rng = np.random.default_rng(i)
        table = pa.table({
            "key": pa.array(range(start, start + n), type=pa.int64()),
            "emb_1": pa.array(rng.integers(0, 100, n), type=pa.int64()),
            "emb_2": pa.array(rng.integers(0, 50, n), type=pa.int64()),
            "vec": pa.array([list(map(float, row))
                             for row in rng.random((n, 4))],
                            type=pa.list_(pa.float64())),
            "labels": pa.array(rng.random(n), type=pa.float64()),
        })
        path = str(tmp_path / f"input_{i}.parquet")
        pq.write_table(table, path)
        filenames.append(path)
    return filenames


def test_spec_normalization_defaults():
    cols, shapes, types, label, lshape, ltype = jd._normalize_jax_data_spec(
        feature_columns="a", label_column="y")
    assert cols == ["a"] and shapes == [None]
    assert types == [np.dtype(np.float32)]
    assert ltype == np.dtype(np.float32)


def test_spec_normalization_mismatch_raises():
    with pytest.raises(ValueError):
        jd._normalize_jax_data_spec(feature_columns=["a", "b"],
                                    feature_shapes=[(1,)], label_column="y")
    with pytest.raises(ValueError):
        jd._normalize_jax_data_spec(feature_columns=["a"],
                                    feature_types=[np.int32, np.int64],
                                    label_column="y")


def test_convert_to_arrays_shapes_and_dtypes():
    table = pa.table({
        "a": pa.array([1, 2, 3, 4], type=pa.int64()),
        "v": pa.array([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0], [7.0, 8.0]],
                      type=pa.list_(pa.float64())),
        "y": pa.array([0.0, 1.0, 0.0, 1.0], type=pa.float64()),
    })
    spec = jd._normalize_jax_data_spec(
        feature_columns=["a", "v"], feature_shapes=[None, (2,)],
        feature_types=[np.int32, np.float32], label_column="y")
    features, label = jd.convert_to_arrays(table, *spec)
    assert features[0].shape == (4, 1) and features[0].dtype == np.int32
    assert features[1].shape == (4, 2) and features[1].dtype == np.float32
    assert label.shape == (4, 1) and label.dtype == np.float32
    np.testing.assert_array_equal(features[0].ravel(), [1, 2, 3, 4])
    np.testing.assert_array_equal(features[1][1], [3.0, 4.0])


def test_unsupported_object_column_raises():
    table = pa.table({"s": pa.array(["x", "y"]),
                      "y": pa.array([0.0, 1.0])})
    spec = jd._normalize_jax_data_spec(feature_columns=["s"],
                                       label_column="y")
    with pytest.raises(TypeError):
        jd.convert_to_arrays(table, *spec)


def test_e2e_jax_batches_on_host(tmp_path):
    filenames = write_files(tmp_path)
    ds = jd.JaxShufflingDataset(
        filenames, num_epochs=2, num_trainers=1, batch_size=32, rank=0,
        feature_columns=["emb_1", "emb_2", "vec"],
        feature_shapes=[None, None, (4,)],
        feature_types=[np.int32, np.int32, np.float32],
        label_column="labels", num_reducers=4, seed=3,
        queue_name="jax-e2e")
    for epoch in range(2):
        ds.set_epoch(epoch)
        count = 0
        for features, label in ds:
            assert isinstance(label, jax.Array)
            assert features[0].shape == (32, 1)
            assert features[2].shape == (32, 4)
            assert label.shape == (32, 1)
            count += 1
        assert count == 8  # 256 rows / 32, drop_last default
    # Stall metric was recorded.
    assert ds.batch_wait_stats.summary()["count"] >= 16


def test_e2e_sharded_over_mesh(tmp_path):
    devices = jax.devices()
    assert len(devices) == 8, "conftest must provide 8 virtual devices"
    mesh = Mesh(np.array(devices), ("data",))
    filenames = write_files(tmp_path)
    ds = jd.JaxShufflingDataset(
        filenames, num_epochs=1, num_trainers=1, batch_size=64, rank=0,
        feature_columns=["emb_1"], feature_types=[np.int32],
        label_column="labels", num_reducers=2, seed=0,
        queue_name="jax-mesh", mesh=mesh)
    ds.set_epoch(0)
    batches = list(ds)
    assert len(batches) == 4
    features, label = batches[0]
    expected = NamedSharding(mesh, P("data", None))
    assert features[0].sharding.is_equivalent_to(expected, features[0].ndim)
    assert label.sharding.is_equivalent_to(expected, label.ndim)
    # Each device holds 64/8 = 8 rows.
    shard = features[0].addressable_shards[0]
    assert shard.data.shape == (8, 1)
    # The sharded batch is usable in a jitted computation.
    total = jax.jit(lambda x: jnp.sum(x))(features[0])
    assert int(total) == int(np.sum(np.asarray(features[0])))


def test_prefetch_pipeline_error_propagates(tmp_path):
    filenames = write_files(tmp_path)
    ds = jd.JaxShufflingDataset(
        filenames, num_epochs=1, num_trainers=1, batch_size=32, rank=0,
        feature_columns=["no_such_column"], label_column="labels",
        num_reducers=2, seed=0, queue_name="jax-err")
    ds.set_epoch(0)
    with pytest.raises(KeyError):
        list(ds)


def _assert_no_prefetch_thread():
    import threading
    import time
    deadline = 100

    def extra():
        return [t.name for t in threading.enumerate()
                if t.name.startswith("rsdl-jax-prefetch")]

    while extra() and deadline:
        time.sleep(0.1)
        deadline -= 1
    assert not extra(), extra()


def test_early_abandon_releases_producer(tmp_path):
    """With persistent_prefetch=False, breaking out of iteration mid-epoch
    must not leak a blocked prefetch thread (regression)."""
    import threading
    filenames = write_files(tmp_path)
    ds = jd.JaxShufflingDataset(
        filenames, num_epochs=1, num_trainers=1, batch_size=16, rank=0,
        feature_columns=["emb_1"], feature_types=[np.int32],
        label_column="labels", num_reducers=2, seed=0,
        queue_name="jax-abandon", prefetch_size=1,
        persistent_prefetch=False)
    ds.set_epoch(0)
    it = iter(ds)
    next(it)
    it.close()  # abandon mid-epoch
    _assert_no_prefetch_thread()


def test_persistent_close_releases_producer(tmp_path):
    """With persistent prefetch (the default) the producer survives
    mid-epoch abandonment by design; close() must release it, and
    iterating after close() raises instead of replaying epochs."""
    import threading
    filenames = write_files(tmp_path)
    ds = jd.JaxShufflingDataset(
        filenames, num_epochs=2, num_trainers=1, batch_size=16, rank=0,
        feature_columns=["emb_1"], feature_types=[np.int32],
        label_column="labels", num_reducers=2, seed=0,
        queue_name="jax-abandon-p", prefetch_size=1)
    ds.set_epoch(0)
    it = iter(ds)
    next(it)
    it.close()  # abandon mid-epoch: producer keeps running
    ds.close()
    _assert_no_prefetch_thread()
    ds.close()  # idempotent
    ds.set_epoch(1)
    with pytest.raises(RuntimeError, match="closed"):
        next(iter(ds))


# -- persistent-prefetch regression tests ----------------------------------

def _make_ds(tmp_path, qname, **kw):
    filenames = write_files(tmp_path)
    kw.setdefault("num_epochs", 3)
    return jd.JaxShufflingDataset(
        filenames, num_trainers=1, batch_size=16, rank=0,
        feature_columns=["emb_1"], feature_types=[np.int32],
        label_column="labels", num_reducers=2, seed=0,
        queue_name=qname, **kw)


def test_persistent_sequential_epochs_yield_all_batches(tmp_path):
    ds = _make_ds(tmp_path, "jax-pp-seq", num_epochs=3)
    for epoch in range(3):
        ds.set_epoch(epoch)
        batches = list(ds)
        assert len(batches) == 256 // 16, epoch
    ds.close()


def test_persistent_out_of_order_epoch_raises(tmp_path):
    ds = _make_ds(tmp_path, "jax-pp-ooo")
    ds.set_epoch(0)
    list(ds)
    with pytest.raises(ValueError, match="sequential"):
        ds.set_epoch(2)
    ds.close()


def test_persistent_abandon_then_continue(tmp_path):
    """Mid-epoch abandonment counts the epoch as consumed; the next
    sequential set_epoch works and yields only the NEXT epoch's batches."""
    ds = _make_ds(tmp_path, "jax-pp-abandon", num_epochs=2)
    ds.set_epoch(0)
    it = iter(ds)
    next(it)
    next(it)
    it.close()  # early stop after 2 of 16 batches
    ds.set_epoch(1)
    batches = list(ds)
    assert len(batches) == 256 // 16
    ds.close()


def test_persistent_skip_before_producer_starts(tmp_path):
    ds = _make_ds(tmp_path, "jax-pp-skip-pre", num_epochs=1)
    ds.set_epoch(0, skip_batches=5)  # producer not started yet
    batches = list(ds)
    assert len(batches) == 256 // 16 - 5
    ds.close()


def test_persistent_skip_after_producer_started(tmp_path):
    ds = _make_ds(tmp_path, "jax-pp-skip-post", num_epochs=2)
    ds.set_epoch(0)
    list(ds)
    # By now the producer has (likely) already entered epoch 1; either way
    # the skip must drop exactly 3 batches of epoch 1.
    ds.set_epoch(1, skip_batches=3)
    batches = list(ds)
    assert len(batches) == 256 // 16 - 3
    ds.close()


def test_persistent_repeated_set_epoch_does_not_double_skip(tmp_path):
    import time
    ds = _make_ds(tmp_path, "jax-pp-skip-twice", num_epochs=1)
    ds.set_epoch(0, skip_batches=4)
    # Let the producer start epoch 0 and apply the Arrow-level skip.
    it = iter(ds)
    first = next(it)
    it.close()
    ds2 = _make_ds(tmp_path, "jax-pp-skip-twice2", num_epochs=1)
    ds2.set_epoch(0, skip_batches=4)
    time.sleep(0.1)
    ds2.set_epoch(0, skip_batches=4)  # same epoch, same skip: no double drop
    batches = list(ds2)
    assert len(batches) == 256 // 16 - 4
    ds2.close()
    ds.close()


def test_persistent_oversized_skip_does_not_eat_next_epoch(tmp_path):
    """skip_batches >= batches-in-epoch must leave the NEXT epoch intact."""
    ds = _make_ds(tmp_path, "jax-pp-skip-big", num_epochs=2)
    ds.set_epoch(0)
    it = iter(ds)
    next(it)  # ensure the producer entered epoch 0 (consumer-side skip path)
    it.close()
    # Epoch 1 may or may not have been entered yet; drive the consumer-side
    # path deterministically by iterating one batch first.
    ds.set_epoch(1, skip_batches=10_000)
    batches = list(ds)
    assert batches == []
    # A skip larger than the epoch must not leak into any later iteration.
    assert ds._consumer_skip == 0
    ds.close()


def test_persistent_epoch_rollover_prefetches_ahead(tmp_path):
    """The point of the persistent producer: while the consumer sits
    between epochs, batches of the next epoch are already buffered."""
    import time
    ds = _make_ds(tmp_path, "jax-pp-rollover", num_epochs=2,
                  prefetch_size=4)
    ds.set_epoch(0)
    list(ds)
    # Producer should roll into epoch 1 without any consumer action.
    deadline = time.monotonic() + 10
    while ds._out.qsize() == 0 and time.monotonic() < deadline:
        time.sleep(0.05)
    assert ds._out.qsize() > 0, "producer did not prefetch across the epoch boundary"
    ds.set_epoch(1)
    assert len(list(ds)) == 256 // 16
    ds.close()


def test_persistent_dropped_without_close_releases_producer(tmp_path):
    """A dataset abandoned mid-epoch and simply dropped (no close()) must
    not leak its producer: the producer holds no reference to the wrapper,
    so GC fires the finalizer that stops the thread."""
    import gc
    import threading
    filenames = write_files(tmp_path)
    ds = jd.JaxShufflingDataset(
        filenames, num_epochs=3, num_trainers=1, batch_size=16, rank=0,
        feature_columns=["emb_1"], feature_types=[np.int32],
        label_column="labels", num_reducers=2, seed=0,
        queue_name="jax-gc-abandon", prefetch_size=1)
    ds.set_epoch(0)
    it = iter(ds)
    next(it)
    del it
    del ds  # crash-style abandonment: no close() anywhere
    gc.collect()
    _assert_no_prefetch_thread()


def test_close_wakes_blocked_consumer(tmp_path):
    """close() from another thread must fail a consumer blocked waiting on
    the next batch with a clear error, not hang it."""
    import threading
    import time
    filenames = write_files(tmp_path)
    ds = jd.JaxShufflingDataset(
        filenames, num_epochs=1, num_trainers=1, batch_size=16, rank=0,
        feature_columns=["emb_1"], feature_types=[np.int32],
        label_column="labels", num_reducers=2, seed=0,
        queue_name="jax-close-wake", prefetch_size=1)
    ds.set_epoch(0)
    it = iter(ds)
    next(it)
    errors = []
    consumed = []

    def consume_rest():
        try:
            for _ in it:
                consumed.append(1)
                time.sleep(0.05)  # slow consumer: queue stays behind us
        except RuntimeError as e:
            errors.append(e)

    t = threading.Thread(target=consume_rest)
    t.start()
    time.sleep(0.15)
    ds.close()
    t.join(timeout=10)
    assert not t.is_alive(), "consumer hung after close()"
    assert errors and "closed" in str(errors[0])


# -- device_rebatch (bulk table transfer + on-device slicing) --------------

def _collect_batches(tmp_path, qname, device_rebatch, *, drop_last=True,
                     skips=None, max_table_bytes=None, num_epochs=2,
                     batch_size=48, stack=False):
    filenames = write_files(tmp_path, num_files=3, rows_per_file=128)
    kwargs = {}
    if max_table_bytes is not None:
        kwargs["max_device_table_bytes"] = max_table_bytes
    if stack:
        feature_columns = ["emb_1", "emb_2"]
        feature_shapes = None
        feature_types = [np.int32, np.int32]
    else:
        # include a shaped (list) column so bulk slicing covers ndim > 2
        feature_columns = ["emb_1", "emb_2", "vec"]
        feature_shapes = [None, None, (4,)]
        feature_types = [np.int32, np.int32, np.float32]
    ds = jd.JaxShufflingDataset(
        filenames, num_epochs=num_epochs, num_trainers=1,
        batch_size=batch_size, rank=0,
        feature_columns=feature_columns, feature_shapes=feature_shapes,
        feature_types=feature_types,
        label_column="labels", num_reducers=3, seed=7,
        queue_name=qname, drop_last=drop_last, prefetch_size=2,
        stack_features=stack, device_rebatch=device_rebatch, **kwargs)
    out = []
    for epoch in range(num_epochs):
        skip = (skips or {}).get(epoch, 0)
        ds.set_epoch(epoch, skip_batches=skip)
        for features, label in ds:
            if stack:
                out.append((np.asarray(features), np.asarray(label)))
            else:
                out.append((tuple(np.asarray(f) for f in features),
                            np.asarray(label)))
    return out


def _assert_batches_equal(a, b):
    assert len(a) == len(b)
    for (fa, la), (fb, lb) in zip(a, b):
        if isinstance(fa, tuple):
            assert len(fa) == len(fb)
            for x, y in zip(fa, fb):
                np.testing.assert_array_equal(x, y)
        else:
            np.testing.assert_array_equal(fa, fb)
        np.testing.assert_array_equal(la, lb)


def test_device_rebatch_matches_host_path(tmp_path):
    """Bulk-table mode must yield bit-identical batches in the same order
    as per-batch host re-batching (boundary batches stitched correctly)."""
    host = _collect_batches(tmp_path, "dr-host", device_rebatch=False)
    dev = _collect_batches(tmp_path, "dr-dev", device_rebatch=True)
    assert len(host) > 4  # sanity: multiple bulk tables per epoch
    _assert_batches_equal(host, dev)


def test_device_rebatch_tail_batch(tmp_path):
    """drop_last=False must yield the identical ragged tail batch."""
    host = _collect_batches(tmp_path, "drt-host", False, drop_last=False,
                            batch_size=50)
    dev = _collect_batches(tmp_path, "drt-dev", True, drop_last=False,
                           batch_size=50)
    assert host[-1][1].shape[0] != 50  # a real ragged tail exists
    _assert_batches_equal(host, dev)


def test_device_rebatch_skip_batches(tmp_path):
    """skip_batches (checkpoint resume) must drop the same batches whether
    the producer skips at the Arrow level (epoch not yet started) or the
    consumer drops client-side (mid-flight)."""
    skips = {0: 2, 1: 3}
    host = _collect_batches(tmp_path, "drs-host", False, skips=skips)
    dev = _collect_batches(tmp_path, "drs-dev", True, skips=skips)
    _assert_batches_equal(host, dev)


def test_device_rebatch_consumer_side_skip(tmp_path):
    """A skip issued after the producer already ran the epoch must drop the
    first batches of bulk tables client-side."""
    filenames = write_files(tmp_path, num_files=2, rows_per_file=128)

    def run(device_rebatch, qname):
        ds = jd.JaxShufflingDataset(
            filenames, num_epochs=2, num_trainers=1, batch_size=32, rank=0,
            feature_columns=["emb_1"], feature_types=[np.int32],
            label_column="labels", num_reducers=2, seed=3,
            queue_name=qname, prefetch_size=1,
            device_rebatch=device_rebatch)
        out = []
        ds.set_epoch(0)
        for f, lb in ds:
            out.append(np.asarray(lb))
        # epoch 1 was prefetched by now; this skip goes client-side
        import time
        time.sleep(0.3)
        ds.set_epoch(1, skip_batches=3)
        for f, lb in ds:
            out.append(np.asarray(lb))
        return out

    host = run(False, "drcs-host")
    dev = run(True, "drcs-dev")
    _assert_batches_equal([((), x) for x in host], [((), x) for x in dev])


def test_device_rebatch_fat_table_fallback(tmp_path):
    """Tables over max_device_table_bytes stream per batch — results must
    still be identical."""
    host = _collect_batches(tmp_path, "drf-host", False)
    dev = _collect_batches(tmp_path, "drf-dev", True, max_table_bytes=64)
    _assert_batches_equal(host, dev)


def test_device_rebatch_stack_features(tmp_path):
    host = _collect_batches(tmp_path, "drst-host", False, stack=True)
    dev = _collect_batches(tmp_path, "drst-dev", True, stack=True)
    _assert_batches_equal(host, dev)


def test_device_rebatch_mesh_requires_divisible_batch():
    devices = jax.devices()
    mesh = Mesh(np.array(devices[:4]), ("data",))
    with pytest.raises(ValueError, match="divisible"):
        jd.JaxShufflingDataset(
            ["f"], num_epochs=1, num_trainers=1, batch_size=9, rank=0,
            feature_columns=["a"], label_column="b", num_reducers=1,
            mesh=mesh, device_rebatch=True,
            batch_queue=object(), shuffle_result=object())


def test_device_rebatch_sharded_mesh_matches_host_path(tmp_path):
    """Bulk chunks under a mesh transfer with the batch axis sharded; the
    yielded batch stream must be value-identical to the per-batch mesh
    path, and every batch must carry the data-axis sharding."""
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))
    filenames = write_files(tmp_path, num_files=3, rows_per_file=128)

    def run(device_rebatch, qname):
        ds = jd.JaxShufflingDataset(
            filenames, num_epochs=2, num_trainers=1, batch_size=48, rank=0,
            feature_columns=["emb_1", "emb_2"],
            feature_types=[np.int32, np.int32],
            label_column="labels", num_reducers=3, seed=7,
            queue_name=qname, mesh=mesh, device_rebatch=device_rebatch)
        touch = jax.jit(lambda fs, y: sum(f.sum() for f in fs) + y.sum())
        out, shardings = [], []
        for epoch in range(2):
            ds.set_epoch(epoch)
            for features, label in ds:
                out.append((tuple(np.asarray(f) for f in features),
                            np.asarray(label)))
                shardings.append(label.sharding)
                touch(features, label)
        return out, shardings, touch._cache_size()

    host, _, _ = run(False, "drm-host")
    dev, dev_shardings, dev_programs = run(True, "drm-dev")
    _assert_batches_equal(host, dev)
    expected = NamedSharding(mesh, P("data", None))
    for s in dev_shardings:
        assert s.is_equivalent_to(expected, 2)
    # Carved and stitched batches must be ONE input type to a consumer's
    # jitted step: equal layouts under different sharding objects (the
    # carve used to come back as P("data")) make it compile twice.
    assert dev_programs == 1


def test_device_rebatch_repacking_spec_rejected(tmp_path):
    """A spec that repacks the sample dimension (flat column reshaped to
    (2,)) cannot be bulk-converted; the producer must fail loudly instead
    of silently regrouping rows differently from the host path."""
    filenames = write_files(tmp_path, num_files=1, rows_per_file=128)
    ds = jd.JaxShufflingDataset(
        filenames, num_epochs=1, num_trainers=1, batch_size=16, rank=0,
        feature_columns=["emb_1"], feature_shapes=[(2,)],
        feature_types=[np.int32],
        label_column="labels", num_reducers=1, seed=0,
        queue_name="jax-repack", device_rebatch=True)
    ds.set_epoch(0)
    with pytest.raises(ValueError, match="sample"):
        list(ds)


def test_device_rebatch_skip_with_tail(tmp_path):
    """skip_batches combined with drop_last=False: the resumed stream must
    keep the identical ragged tail."""
    skips = {0: 1, 1: 4}
    host = _collect_batches(tmp_path, "drskt-host", False, drop_last=False,
                            batch_size=50, skips=skips)
    dev = _collect_batches(tmp_path, "drskt-dev", True, drop_last=False,
                           batch_size=50, skips=skips)
    assert host[-1][1].shape[0] != 50
    _assert_batches_equal(host, dev)


def test_device_rebatch_empty_reducer_tables(tmp_path):
    """iter_tables can yield 0-row reducer outputs (more reducers than
    rows) — the bulk producer must pass through them without error and
    deliver every row exactly once."""
    filenames = write_files(tmp_path, num_files=1, rows_per_file=6)
    ds = jd.JaxShufflingDataset(
        filenames, num_epochs=1, num_trainers=1, batch_size=2, rank=0,
        feature_columns=["emb_1"], feature_types=[np.int32],
        label_column="labels", num_reducers=16, seed=0, drop_last=False,
        queue_name="jax-empty-reducers", device_rebatch=True)
    ds.set_epoch(0)
    rows = sum(int(lb.shape[0]) for _, lb in ds)
    assert rows == 6


def test_device_rebatch_auto_falls_back_on_repacking_spec(tmp_path):
    """When device_rebatch was resolved from "auto" (not explicitly
    requested), a spec that repacks the sample dimension must NOT break the
    job mid-epoch: the producer falls back to per-batch transfers and the
    batch stream matches the host path exactly (ADVICE r3, medium)."""
    filenames = write_files(tmp_path, num_files=1, rows_per_file=128)

    def run(device_rebatch, qname, mark_auto=False):
        ds = jd.JaxShufflingDataset(
            filenames, num_epochs=1, num_trainers=1, batch_size=16, rank=0,
            feature_columns=["emb_1"], feature_shapes=[(2,)],
            feature_types=[np.int32],
            label_column="labels", num_reducers=2, seed=0,
            queue_name=qname, device_rebatch=device_rebatch)
        if mark_auto:
            # Simulate "auto" resolution (the CPU test backend resolves
            # auto to False, so flag the converter directly).
            ds._converter.device_rebatch_auto = True
        ds.set_epoch(0)
        return [(tuple(np.asarray(f) for f in feats), np.asarray(lb))
                for feats, lb in ds]

    host = run(False, "jax-repack-fb-host")
    fallback = run(True, "jax-repack-fb-auto", mark_auto=True)
    assert len(host) == len(fallback) == 8  # 128 rows / 16-row batches
    for (fa, la), (fb, lb) in zip(host, fallback):
        for x, y in zip(fa, fb):
            np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(la, lb)


def test_disk_cache_mode_matches_ram_cache_stream(tmp_path):
    """file_cache="disk" at the JAX-binding level: later epochs stream
    from the mmap'd decoded-IPC tier and the device batch stream is
    bit-identical to the RAM-cache run."""
    filenames = write_files(tmp_path, num_files=2, rows_per_file=96)

    def run(cache, qname):
        ds = jd.JaxShufflingDataset(
            filenames, num_epochs=2, num_trainers=1, batch_size=32,
            rank=0, feature_columns=["emb_1", "emb_2"],
            feature_types=[np.int64, np.int64], label_column="labels",
            num_reducers=2, seed=5, drop_last=True, file_cache=cache,
            queue_name=qname)
        out = []
        for epoch in range(2):
            ds.set_epoch(epoch)
            for feats, lb in ds:
                out.append((tuple(np.asarray(f).tolist() for f in feats),
                            np.asarray(lb).tolist()))
        ds.close()
        return out

    ram = run("auto", "jaxdisk-ram")
    disk = run("disk", "jaxdisk-disk")
    assert ram == disk and len(ram) == 12  # 2 epochs x 192 rows / 32
