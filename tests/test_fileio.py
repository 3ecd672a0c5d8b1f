"""Remote-URI IO (utils/fileio.py): the reference's smart_open capability
(reference: shuffle.py:7,208) exercised against fsspec's in-process
memory:// filesystem — no network needed."""

import numpy as np
import pyarrow as pa
import pytest

from ray_shuffling_data_loader_tpu import data_generation as datagen
from ray_shuffling_data_loader_tpu import multiqueue as mq
from ray_shuffling_data_loader_tpu.shuffle import FileTableCache
from ray_shuffling_data_loader_tpu.dataset import ShufflingDataset
from ray_shuffling_data_loader_tpu.utils import fileio


@pytest.fixture(autouse=True)
def fresh_state():
    mq._REGISTRY.clear()
    import fsspec
    fsspec.filesystem("memory").store.clear()
    yield
    mq._REGISTRY.clear()


def test_parse_uri_local():
    fs, inner = fileio.parse_uri("/tmp/x.parquet")
    assert fs is None and inner == "/tmp/x.parquet"
    fs, inner = fileio.parse_uri("file:///tmp/x.parquet")
    assert fs is None and inner == "/tmp/x.parquet"


def test_join_and_roundtrip_memory_uri():
    assert fileio.join("memory://corpus", "a.parquet") == \
        "memory://corpus/a.parquet"
    table = pa.table({"x": np.arange(10, dtype=np.int64)})
    uri = "memory://roundtrip/a.parquet"
    fileio.write_parquet(table, uri)
    back = fileio.read_parquet(uri)
    assert back.equals(table)
    assert fileio.listdir("memory://roundtrip") == [uri]


def test_datagen_to_remote_uri():
    filenames, _ = datagen.generate_data(
        num_rows=64, num_files=2, num_row_groups_per_file=2,
        max_row_group_skew=0.0, data_dir="memory://gen", seed=0)
    assert all(f.startswith("memory://gen/") for f in filenames)
    total = sum(fileio.read_parquet(f).num_rows for f in filenames)
    assert total == 64


def test_shuffle_dataset_end_to_end_over_remote_uri(monkeypatch):
    """Full pipeline — datagen write, shuffle_map read, cache keyed on the
    URI — against a remote (memory://) corpus. fsspec's ``memory://`` and
    the ``FileTableCache`` both live in this process, so the map tasks
    must too: the thread backend, whatever the host's core count picks."""
    monkeypatch.setenv("RSDL_EXECUTOR_BACKEND", "thread")
    filenames, _ = datagen.generate_data(
        num_rows=128, num_files=2, num_row_groups_per_file=2,
        max_row_group_skew=0.0, data_dir="memory://e2e", seed=0)
    cache = FileTableCache(max_bytes=1 << 30)
    ds = ShufflingDataset(
        filenames, num_epochs=2, num_trainers=1, batch_size=32, rank=0,
        num_reducers=2, max_concurrent_epochs=2, seed=0,
        queue_name="fileio-e2e", file_cache=cache)
    seen = []
    for epoch in range(2):
        ds.set_epoch(epoch)
        keys = []
        for batch in ds:
            keys.extend(batch.column("key").to_pylist())
        assert sorted(keys) == list(range(128)), f"epoch {epoch}"
        seen.append(keys)
    assert seen[0] != seen[1]  # different epoch permutations
    # The cache holds both files, keyed by full URI.
    assert cache.get(filenames[0]) is not None
    assert cache.get(filenames[1]) is not None
