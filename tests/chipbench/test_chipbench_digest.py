"""The digest of delivered rows: the device's arithmetic equals the host's,
it does not depend on order or batching, it equals the files' digest after
the upstream project's shuffle done the plain way, and it changes when a
column is delivered narrower than its values need."""

import numpy as np
import pytest

from chipbench import data as bench_data
from chipbench import digest

DATA = {
    "rows": 4096, "files": 4, "row_groups_per_file": 2,
    "columns": [
        {"name": "key", "kind": "key"},
        {"name": "small", "kind": "int", "cardinality": 100,
         "role": "feature"},
        {"name": "wide", "kind": "int", "cardinality": 900000,
         "role": "feature"},
        {"name": "labels", "kind": "float", "role": "label"},
    ],
}


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    return bench_data.generate(DATA, str(tmp_path_factory.mktemp("d")), 7)


def _columns(frame):
    return [frame["small"].to_numpy(), frame["wide"].to_numpy(),
            frame["labels"].to_numpy()]


def test_same_seed_same_files_other_seed_other_files(files, tmp_path):
    again = bench_data.generate(DATA, str(tmp_path / "again"), 7)
    other = bench_data.generate(DATA, str(tmp_path / "other"), 8)
    want = digest.files_digest_reference(files, ["small", "wide"], "labels")
    assert digest.files_digest_reference(
        again, ["small", "wide"], "labels") == want
    assert digest.files_digest_reference(
        other, ["small", "wide"], "labels") != want
    assert want[0] == DATA["rows"]


def test_seed_past_32_bits_makes_files(tmp_path):
    big = bench_data.generate(DATA, str(tmp_path / "big"), 2**31 + 12345)
    assert digest.files_digest_reference(
        big, ["small", "wide"], "labels")[0] == DATA["rows"]


def test_device_digest_equals_host_digest(files):
    import jax
    import pandas as pd
    frame = pd.read_parquet(files[0])
    host = digest.rows_digest_host(_columns(frame))
    narrow = [frame["small"].to_numpy().astype(np.int8),
              frame["wide"].to_numpy().astype(np.int32),
              frame["labels"].to_numpy().astype(np.float32)]
    device = jax.jit(digest.rows_digest_device)(
        [jax.numpy.asarray(c) for c in narrow])
    assert tuple(int(x) for x in np.asarray(device)) == host
    assert digest.combine(np.asarray(device)) == digest.combine(host)


def test_token_rows_hash_the_same_on_host_and_device():
    import jax
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 30522, size=(64, 128), dtype=np.int32)
    labels = np.zeros(64, np.int64)
    host = digest.rows_digest_host([tokens, labels])
    device = jax.jit(digest.rows_digest_device)(
        [jax.numpy.asarray(tokens), jax.numpy.asarray(labels, "int32")])
    assert tuple(int(x) for x in np.asarray(device)) == host
    swapped = tokens.copy()
    swapped[:, [3, 4]] = swapped[:, [4, 3]]     # position matters
    assert digest.rows_digest_host([swapped, labels]) != host


def test_digest_of_the_plain_shuffle_equals_the_files(files):
    """The upstream shuffle done the plain way (pandas, boolean-mask
    partition, concat + sample, exact-size re-batching) delivers every row
    exactly once: its batches digest to what the files hold."""
    want_rows, want = digest.files_digest_reference(
        files, ["small", "wide"], "labels")
    rng = np.random.default_rng(3)
    batches = digest.reference_shuffle_epoch(files, num_reducers=3,
                                             batch_size=256, rng=rng)
    assert [len(b) for b in batches] == [256] * (want_rows // 256)
    lanes = (0, 0)
    for batch in batches:
        lanes = digest.add(lanes, digest.rows_digest_host(_columns(batch)))
    assert digest.combine(lanes) == want
    keys = np.concatenate([b["key"].to_numpy() for b in batches])
    assert sorted(keys) == list(range(want_rows))


def test_a_row_twice_or_a_row_missing_changes_the_digest(files):
    import pandas as pd
    frame = pd.read_parquet(files[0])
    whole = digest.rows_digest_host(_columns(frame))
    assert digest.rows_digest_host(_columns(frame.iloc[1:])) != whole
    twice = pd.concat([frame, frame.iloc[:1]])
    assert digest.rows_digest_host(_columns(twice)) != whole
    # order and batch boundaries do not matter
    parts = [frame.iloc[100:], frame.iloc[:100]]
    lanes = (0, 0)
    for part in parts:
        lanes = digest.add(lanes, digest.rows_digest_host(_columns(part)))
    assert lanes == whole


@pytest.mark.parametrize("column,dtype", [("wide", np.int16),
                                          ("small", np.int8)])
def test_a_column_narrower_than_its_values_changes_the_digest(
        files, column, dtype):
    import pandas as pd
    frame = pd.read_parquet(files[0])
    whole = digest.rows_digest_host(_columns(frame))
    narrowed = frame.copy()
    narrowed[column] = narrowed[column].to_numpy().astype(dtype)
    fits = np.array_equal(narrowed[column].to_numpy(),
                          frame[column].to_numpy())
    same = digest.rows_digest_host(_columns(narrowed)) == whole
    # int8 holds [0, 100): lossless, same digest; int16 wraps 900000
    assert same == fits
    assert fits == (column == "small")
