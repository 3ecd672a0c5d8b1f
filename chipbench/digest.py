"""Order-independent 64-bit digest of a set of rows, the same arithmetic
on the host (numpy, over what the Parquet files hold) and on the device
(``jax.numpy``, over what the loader delivered).

Each row hashes to two independent 32-bit lanes over every value of every
column, salted by the value's position in the row; the digest of a row set
is the sum of its rows' hashes modulo 2**32 in each lane. Two row sets
have the same digest exactly when (up to collisions) they hold the same
rows the same number of times, whatever the order and the batch
boundaries. A value that was narrowed past what it needs (an int16 column
that wraps, a float that lost bits it had in float32) hashes differently.

Integer columns are read as int32 and float columns as the bits of their
float32 value: that is the width the configurations deliver at most, and
the device has no 64-bit integers by default.
"""

from __future__ import annotations

from typing import Any, List, Sequence, Tuple

import numpy as np

# murmur3's 32-bit finalizer constants, and two lane seeds/salts.
_C1, _C2 = 0x85EBCA6B, 0xC2B2AE35
_LANES = ((0x9E3779B9, 0x7F4A7C15), (0x632BE5AB, 0x1B873593))


def _fmix(x, xp):
    u = xp.uint32
    x = x ^ (x >> u(16))
    x = x * u(_C1)
    x = x ^ (x >> u(13))
    x = x * u(_C2)
    return x ^ (x >> u(16))


def _as_u32(column, xp):
    """(rows, width) uint32 view of one column's values."""
    a = xp.asarray(column)
    a = a.astype(xp.float32 if xp.issubdtype(a.dtype, xp.floating)
                 else xp.int32)
    a = a.view(xp.uint32) if xp is np else _bitcast(a)
    return a.reshape(a.shape[0], -1)


def _bitcast(a):
    import jax
    import jax.numpy as jnp
    return jax.lax.bitcast_convert_type(a, jnp.uint32)


def _row_hashes(columns: Sequence[Any], xp) -> Tuple[Any, Any]:
    """Per-row hash in each lane over all ``columns`` (features, then the
    label, in the configuration's order)."""
    u = xp.uint32
    lanes = []
    for seed, salt in _LANES:
        acc = None
        position = 1
        for column in columns:
            v = _as_u32(column, xp)
            width = v.shape[1]
            salts = (xp.arange(position, position + width, dtype=xp.uint32)
                     * u(salt))
            term = _fmix(v + salts[None, :], xp).sum(axis=1, dtype=xp.uint32)
            acc = term if acc is None else acc + term
            position += width
        lanes.append(_fmix(acc + u(seed), xp))
    return lanes[0], lanes[1]


def rows_digest_host(columns: Sequence[Any]) -> Tuple[int, int]:
    """(lane A, lane B) of a row set held in numpy arrays."""
    with np.errstate(over="ignore"):
        a, b = _row_hashes(columns, np)
        return (int(a.sum(dtype=np.uint32)), int(b.sum(dtype=np.uint32)))


def rows_digest_device(columns: Sequence[Any]):
    """The same, traced: returns a ``uint32[2]`` device array. Call it
    inside a jitted function."""
    import jax.numpy as jnp
    a, b = _row_hashes(columns, jnp)
    return jnp.stack([a.sum(dtype=jnp.uint32), b.sum(dtype=jnp.uint32)])


def combine(lanes: Sequence[int]) -> int:
    """Two 32-bit lanes as one 64-bit number."""
    return (int(lanes[0]) % 2**32) << 32 | (int(lanes[1]) % 2**32)


def add(x: Tuple[int, int], y: Tuple[int, int]) -> Tuple[int, int]:
    return ((x[0] + y[0]) % 2**32, (x[1] + y[1]) % 2**32)


def files_digest_reference(filenames: Sequence[str],
                           feature_columns: Sequence[str],
                           label_column: str) -> Tuple[int, int]:
    """(row count, 64-bit digest) of what the Parquet files hold, read the
    plain way: ``pandas.read_parquet`` one file at a time, every row of
    every delivered column, at the files' own dtypes. This is the reference
    the delivered stream is held to; it shares nothing with the loader."""
    import pandas as pd
    rows, lanes = 0, (0, 0)
    for filename in filenames:
        frame = pd.read_parquet(
            filename, columns=list(feature_columns) + [label_column])
        rows += len(frame)
        lanes = add(lanes, rows_digest_host(
            [_frame_column(frame, c) for c in feature_columns]
            + [_frame_column(frame, label_column)]))
    return rows, combine(lanes)


def _frame_column(frame, name: str) -> np.ndarray:
    values = frame[name].to_numpy()
    if values.dtype == object:          # a list column: one array per row
        values = np.stack(values)
    return values


def reference_shuffle_epoch(filenames: Sequence[str], num_reducers: int,
                            batch_size: int, rng: np.random.Generator
                            ) -> List[Any]:
    """One epoch of the upstream project's shuffle, the plain way (pandas
    ``read_parquet``, boolean-mask partition, ``concat`` + ``sample``,
    exact-size re-batching with the remainder dropped): the batches
    (DataFrames) it delivers. Copied from
    ``bench._pandas_reference_baseline``; the tests hold the digest of its
    batches to the files' digest."""
    import pandas as pd
    parts: List[List[Any]] = [[] for _ in range(num_reducers)]
    for filename in filenames:
        rows = pd.read_parquet(filename)
        assignment = rng.integers(num_reducers, size=len(rows))
        for r in range(num_reducers):
            parts[r].append(rows[assignment == r])
    shuffled = [pd.concat(p).sample(frac=1, random_state=rng.integers(2**31))
                for p in parts]
    batches, buffer = [], None
    for frame in shuffled:
        buffer = frame if buffer is None else pd.concat([buffer, frame])
        while len(buffer) >= batch_size:
            batches.append(buffer[:batch_size])
            buffer = buffer[batch_size:]
    return batches
