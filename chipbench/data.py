"""Makes a cell's Parquet files from ``--seed``: one general generator
that reads the ``data`` section of a configuration file.

``data`` lists the columns: ``{"name", "kind": "int", "cardinality"}``
draws int64 values in ``[0, cardinality)``, ``{"kind": "float"}`` float64
in ``[0, 1)``, ``{"kind": "key"}`` the global row number, and
``{"kind": "tokens", "width", "vocab", "first", "last"}`` a
``FixedSizeList<int32>`` of ``width`` token ids in ``[4, vocab)`` with the
given first and last id. The same seed gives the same files, bit for bit;
the files are written in parallel threads (pyarrow's writer releases the
interpreter lock) and made anew in every run, which counts as set-up.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq


def _column(spec: Dict[str, Any], rng: np.random.Generator, start: int,
            rows: int):
    kind = spec["kind"]
    if kind == "key":
        return np.arange(start, start + rows, dtype=np.int64)
    if kind == "int":
        return rng.integers(0, spec["cardinality"], size=rows,
                            dtype=np.int64)
    if kind == "float":
        return rng.random(rows)
    if kind == "tokens":
        width = spec["width"]
        tokens = rng.integers(4, spec["vocab"], size=(rows, width),
                              dtype=np.int32)
        tokens[:, 0] = spec["first"]
        tokens[:, -1] = spec["last"]
        return pa.FixedSizeListArray.from_arrays(
            pa.array(tokens.reshape(-1)), width)
    raise ValueError(f"unknown column kind {kind!r} in {spec}")


def _write_file(data: Dict[str, Any], data_dir: str, seed: int,
                index: int) -> str:
    rows = data["rows"] // data["files"]
    rng = np.random.Generator(np.random.Philox(
        np.random.SeedSequence([seed, index])))
    table = pa.table({spec["name"]: _column(spec, rng, index * rows, rows)
                      for spec in data["columns"]})
    path = os.path.join(data_dir, f"part_{index:03d}.parquet.snappy")
    pq.write_table(table, path, compression="snappy",
                   row_group_size=max(1, rows // data["row_groups_per_file"]))
    return path


def generate(data: Dict[str, Any], data_dir: str, seed: int) -> List[str]:
    """Write the files of ``data`` into ``data_dir``; returns their paths
    in file order."""
    if data["rows"] % data["files"]:
        raise ValueError(f"{data['rows']} rows do not split evenly over "
                         f"{data['files']} files")
    os.makedirs(data_dir, exist_ok=True)
    with ThreadPoolExecutor(min(data["files"], os.cpu_count() or 1)) as pool:
        return list(pool.map(
            lambda i: _write_file(data, data_dir, seed, i),
            range(data["files"])))


def delivered(data: Dict[str, Any]):
    """(feature column names, label column name): what the loader is asked
    to deliver, in the configuration's order."""
    features = [c["name"] for c in data["columns"]
                if c.get("role") == "feature"]
    labels = [c["name"] for c in data["columns"] if c.get("role") == "label"]
    if len(labels) != 1:
        raise ValueError(f"the data names {len(labels)} label columns")
    return features, labels[0]
