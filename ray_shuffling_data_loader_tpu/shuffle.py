"""Per-epoch map/reduce shuffle engine with epoch pipelining.

Capability parity with the reference's shuffle engine (reference:
shuffle.py:21-263): per epoch, one map task per Parquet file uniformly
scatters its rows across ``num_reducers`` reducers; one reduce task per
reducer concatenates its chunks from every file and permutes them; reducer
outputs are routed round-robin-contiguously to trainers via a
``batch_consumer`` callback followed by a ``None`` sentinel; shuffles for up
to ``max_concurrent_epochs`` epochs run concurrently with consumption,
throttled so host memory stays bounded.

TPU-native design differences:

- Tasks are host threads on the TPU-VM (executor.py), not Ray tasks; data
  is pyarrow Tables, not pandas DataFrames — Arrow's C++ kernels (Parquet
  decode, take, concat) release the GIL and its buffers are the zero-copy
  data plane that plasma provided externally (SURVEY.md §2.3).
- Map->reduce dependencies resolve by submission order: per epoch all maps
  are submitted before any reduce, and the FIFO thread pool guarantees a
  blocked reduce only ever waits on maps that already hold or preceded its
  worker slot, so the pattern is deadlock-free at any pool size.
- Every random draw is keyed by (seed, epoch, task) — the reference's
  unseeded ``np.random.randint`` / ``df.sample`` (reference: shuffle.py:213,
  240) made epochs irreproducible; ours replay bit-identically, which is
  what makes loader checkpoint/resume possible.
- The reference's reduce bug that turns a 1-row batch into a column lookup
  (``if len(batch) == 1: batch = batch[0]``, reference: shuffle.py:241-242)
  is intentionally not replicated.
"""

from __future__ import annotations

import functools
import threading
import timeit
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

import numpy as np
import pyarrow as pa

from ray_shuffling_data_loader_tpu import executor as ex
from ray_shuffling_data_loader_tpu import stats as stats_mod
from ray_shuffling_data_loader_tpu import storage as rt_storage
from ray_shuffling_data_loader_tpu.ops import partition as ops
from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
from ray_shuffling_data_loader_tpu.runtime import policy as rt_policy
from ray_shuffling_data_loader_tpu.runtime import retry as rt_retry
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
# Retired into the storage package (PR 14) but re-exported here: the
# disk tier predates storage/ and callers construct it by this name.
from ray_shuffling_data_loader_tpu.storage.cache import (  # noqa: F401
    DiskTableCache, DiskTier, TieredStore)
# Not read directly anymore (dataset bytes flow through rt_storage), but
# kept as a re-export: tests and downstream callers reach fileio via the
# shuffle namespace.
from ray_shuffling_data_loader_tpu.utils import fileio  # noqa: F401
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)

# batch_consumer(rank, epoch, refs_or_None) — refs are TaskRefs resolving to
# pyarrow Tables (reference passes ObjectRefs of DataFrames,
# reference: dataset.py:213-224).
BatchConsumer = Callable[[int, int, Optional[Sequence[ex.TaskRef]]], None]

# Optional table -> table hook applied by the map task right after the
# Parquet read (e.g. cast int64 -> int32 before any shuffling, halving all
# downstream memory traffic). Must be row-order preserving.
MapTransform = Callable[[pa.Table], pa.Table]

# Optional table -> table hook applied by the reduce task to its shuffled
# output (e.g. decode encoded image bytes into fixed-shape pixel columns —
# BASELINE config 3 runs image decode inside shuffle reducers so the decode
# cost is spread over the reducer pool and overlaps training). Must be
# row-order preserving; runs once per reducer per epoch.
ReduceTransform = Callable[[pa.Table], pa.Table]

# Fallback per-call thread count for the native fused scatter-gather when
# no pool-aware value was derived (direct shuffle_reduce calls). Modest so
# that concurrently-running reduce tasks don't oversubscribe the host; on a
# 1-core host this is 1.
import os as _os
_SCATTER_GATHER_THREADS = max(1, min(4, (_os.cpu_count() or 1)))

# Shared pool for per-column gather fan-out inside _fused_reduce. Lazy and
# process-wide: reduce tasks from every concurrent epoch feed it leaf work
# (no column task ever waits on another pool task, so it cannot deadlock at
# any width).
_column_pool = None
_column_pool_lock = threading.Lock()


def _column_gather_pool():
    global _column_pool
    if _column_pool is None:
        with _column_pool_lock:
            if _column_pool is None:
                import concurrent.futures as _cf
                _column_pool = _cf.ThreadPoolExecutor(
                    max_workers=max(2, min(16, (_os.cpu_count() or 1))),
                    thread_name_prefix="rsdl-gather-col")
    return _column_pool


def derive_gather_threads(concurrent_reduces: int, pool_workers: int,
                          host_share: int = 1) -> int:
    """Threads per reduce task's fused gather, sized to the host.

    The static ``min(4, cores)`` default underuses big TPU-VM hosts (a
    100-core host running 4 reduce tasks would leave 84 cores idle in the
    shuffle's hottest loop) and oversubscribes small ones (8 cores with 19
    concurrent reducers at 4 threads each). Divide the cores across the
    reduce tasks that can actually run at once (ROADMAP round-3 item:
    reduce-stage thread tuning).

    ``concurrent_reduces`` is the caller's bound on simultaneously-running
    reduce tasks — for the epoch-pipelined driver that is
    ``num_reducers * max_concurrent_epochs``, not one epoch's worth.
    ``host_share``: how many pipeline "hosts" share this machine (the
    localhost multi-host emulation runs world transports in one process;
    a real deployment owns its cores and passes 1).
    """
    cores = (_os.cpu_count() or 1) // max(1, host_share)
    concurrent = max(1, min(concurrent_reduces, pool_workers))
    return max(1, min(16, cores // concurrent))

def _transient_read_retryable(error: BaseException) -> bool:
    """Map-read in-place retry predicate: an IO blip (NFS/GCS hiccup)
    heals on retry; corrupt content (``ArrowInvalid``) and injected
    task faults do not and must surface to quarantine/lineage."""
    return isinstance(error, OSError) and not isinstance(
        error, rt_faults.InjectedFault)


def default_fault_policies() -> Dict[str, Any]:
    """Per-stage RetryPolicy objects, resolved from the runtime policy
    registry (``RSDL_RETRY_*`` globally, ``RSDL_MAP_READ_RETRY_*`` /
    ``RSDL_REDUCE_RETRY_*`` / ``RSDL_LINEAGE_RETRY_*`` per stage).
    Built once per shuffle driver and shared by every epoch."""
    return {
        "read": rt_retry.RetryPolicy.for_component(
            "map_read", retryable=_transient_read_retryable),
        "reduce": rt_retry.RetryPolicy.for_component("reduce"),
        "lineage": rt_retry.RetryPolicy.for_component("lineage"),
    }


# How long shuffle() waits for consumers to release tables when
# max_inflight_bytes is exceeded before proceeding with a warning.
# Policy-overridable (RSDL_SHUFFLE_BUDGET_WAIT_TIMEOUT_S / kwargs); this
# constant is the library baseline. The wait itself is event-driven: the
# buffer ledger wakes it on every release (runtime/release.py), not a
# poll cadence.
_BUDGET_POLL_TIMEOUT_S = 30.0


def _table_numpy_columns(table: pa.Table) -> Optional[Dict[str, np.ndarray]]:
    """{column -> 1-D ndarray} views of a table, or None if any column is
    non-primitive / nullable (those fall back to the Arrow concat+take
    reduce path)."""
    cols: Dict[str, np.ndarray] = {}
    for name in table.column_names:
        col = table.column(name)
        if col.null_count != 0:
            return None
        t = col.type
        if not (pa.types.is_integer(t) or pa.types.is_floating(t)
                or pa.types.is_boolean(t)):
            return None
        if col.num_chunks == 0:
            cols[name] = np.empty(0, dtype=t.to_pandas_dtype())
            continue
        if col.num_chunks == 1:
            combined = col.chunk(0)
        else:
            # Blessed: runs once per shard (MapShard.numpy_columns' locked
            # cache); cached tables are single-chunk, so steady state
            # never reaches it. rsdl-lint: disable=copy-in-hot-path
            combined = col.combine_chunks()
        # Blessed: zero-copy for the single-chunk primitive columns this
        # function admits; cached per shard. rsdl-lint: disable=copy-in-hot-path
        arr = combined.to_numpy(zero_copy_only=False)
        if arr.dtype == object:
            return None
        cols[name] = arr
    return cols


class FileTableCache:
    """Bounded, thread-safe cache of decoded (and map-transformed) tables.

    The reference re-reads and re-decodes every Parquet file every epoch
    (reference: shuffle.py:208) — Ray's stateless tasks can't do better, and
    the OS page cache only skips disk IO, not decompression/decode. Our map
    tasks are host-local, so steady-state epochs can skip the whole
    read+decode+cast stage. Insertion stops at the byte budget (no
    eviction: every cached file is hit once per epoch, so LRU churn would
    only add copies).
    """

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        self._bytes = 0
        self._tables: Dict[str, pa.Table] = {}
        self._lock = threading.Lock()

    def get(self, key: str) -> Optional[pa.Table]:
        with self._lock:
            return self._tables.get(key)

    def put(self, key: str, table: pa.Table) -> bool:
        """Insert if the byte budget allows; returns True if inserted."""
        with self._lock:
            if key in self._tables:
                return True
            nbytes = table.nbytes
            if self._bytes + nbytes > self.max_bytes:
                return False
            self._tables[key] = table
            self._bytes += nbytes
        return True

    @property
    def bytes_cached(self) -> int:
        with self._lock:
            return self._bytes


def default_file_cache() -> Optional[FileTableCache]:
    """Cache budgeted at 1/3 of currently-available host RAM (None if that
    cannot be determined)."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    kb = int(line.split()[1])
                    return FileTableCache(max_bytes=kb * 1024 // 3)
    except (OSError, ValueError, IndexError):
        pass
    return None


# DiskTableCache lived here through PR 13; it is now the legacy face of
# storage.cache.DiskTier (same constructor, same no-eviction/no-ledger
# semantics, plus per-entry CRC) and is re-exported above — the explicit
# tier hierarchy, ledger charging, and promotion live in
# storage.cache.TieredStore.


def default_disk_cache_bytes(cache_dir: Optional[str] = None) -> int:
    """Disk budget for ``file_cache="disk"``: half the free space of the
    scratch filesystem (decoded tables are ~2-3x their parquet size)."""
    import shutil as _shutil
    import tempfile as _tempfile
    try:
        free = _shutil.disk_usage(cache_dir or _tempfile.gettempdir()).free
        return free // 2
    except OSError:
        return 16 << 30


def resolve_file_cache(spec, epochs_remaining: int):
    """Resolve a ``file_cache`` argument to ``(cache, owned)``.

    ``spec`` is ``"auto"`` (RAM cache when >1 epoch will map each file),
    ``"disk"`` (fresh :class:`DiskTableCache`, budgeted by
    ``default_disk_cache_bytes``), ``"tiered"`` (a full
    :class:`storage.cache.TieredStore`: hot RAM LRU over a ledger-charged
    CRC'd disk tier over the installed storage source, with the
    prefetcher seam the plan scheduler warms next-epoch files through),
    ``None``, or an instance. ``owned`` is True when this call created a
    disk-backed cache the driver must close after the run (its scratch
    files are useless to anyone else: reducer outputs are gathered
    copies, never views of cached tables)."""
    if spec == "auto":
        return (default_file_cache() if epochs_remaining > 1 else None,
                False)
    if spec == "disk":
        if epochs_remaining <= 1:
            return None, False
        return DiskTableCache(max_bytes=default_disk_cache_bytes()), True
    if spec == "tiered":
        if epochs_remaining <= 1:
            return None, False
        ram = default_file_cache()
        hot_bytes = ram.max_bytes if ram is not None else 1 << 30
        return TieredStore(
            hot_bytes,
            disk=DiskTier(max_bytes=default_disk_cache_bytes()),
            source=rt_storage.get_source()), True
    return spec, False


class MapShard:
    """Lazy map output: the source table plus per-reducer row-index arrays.

    The reference's map task materializes ``num_reducers`` DataFrame
    partitions (reference: shuffle.py:215-220). Within a host that gather
    is pure waste — the reduce permutation gathers the same rows again — so
    the map task only *plans* the partition and the reduce task performs a
    single fused gather (see :func:`shuffle_reduce`). Materialized
    partitions are still available via indexing/iteration for the
    cross-host transport path.
    """

    __slots__ = ("table", "index_parts", "_np_cols", "_np_cols_known",
                 "_np_cols_lock")

    def __init__(self, table: pa.Table, index_parts: List[np.ndarray]):
        self.table = table
        self.index_parts = index_parts
        self._np_cols: Optional[Dict[str, np.ndarray]] = None
        self._np_cols_known = False
        self._np_cols_lock = threading.Lock()

    def __len__(self) -> int:
        return len(self.index_parts)

    def __getitem__(self, reducer_index: int) -> "LazyChunk":
        return LazyChunk(self, reducer_index)

    def __iter__(self):
        return (self[r] for r in range(len(self.index_parts)))

    def numpy_columns(self) -> Optional[Dict[str, np.ndarray]]:
        """Cached numpy views of the source table (None if ineligible).

        Locked: all of this shard's reduce tasks race here at once, and an
        unsynchronized miss would make each of them combine_chunks() its own
        full copy of the source table.
        """
        if self._np_cols_known:
            return self._np_cols
        with self._np_cols_lock:
            if not self._np_cols_known:
                self._np_cols = _table_numpy_columns(self.table)
                self._np_cols_known = True
        return self._np_cols


class LazyChunk:
    """One reducer's slice of a map output, gathered only on demand."""

    __slots__ = ("shard", "reducer_index")

    def __init__(self, shard: MapShard, reducer_index: int):
        self.shard = shard
        self.reducer_index = reducer_index

    @property
    def num_rows(self) -> int:
        return len(self.shard.index_parts[self.reducer_index])

    @property
    def indices(self) -> np.ndarray:
        return self.shard.index_parts[self.reducer_index]

    def materialize(self) -> pa.Table:
        return self.shard.table.take(self.indices)


def plan_map_partition(num_rows: int, num_reducers: int, seed: int,
                       epoch: int, file_index: int) -> List[np.ndarray]:
    """The map task's row->reducer partition plan, policy-selected.

    ``partition_plan="fused"`` (default) runs the one-kernel counter-based
    plan (``ops.plan_partition``: the native kernel emits partition indices
    straight from its hash stream; the NumPy fallback is bit-identical).
    ``"philox"`` keeps the legacy two-stage draw+sort pipeline. Both
    executor backends and every recovery recompute resolve the same knob,
    so a given ``(seed, epoch, file)`` always replays the same plan.
    """
    if rt_policy.resolve("shuffle", "partition_plan") == "philox":
        rng = ops.map_rng(seed, epoch, file_index)
        assignments = ops.assign_reducers(num_rows, num_reducers, rng)
        return ops.partition_indices(assignments, num_reducers)
    return ops.plan_partition(num_rows, num_reducers, seed, epoch,
                              file_index, nthreads=_SCATTER_GATHER_THREADS)


class FusedMapShard:
    """Map output of the streaming decode->partition->gather pipeline.

    Unlike :class:`MapShard` (source table + per-reducer index arrays,
    gather deferred to the reduce), the fused pipeline has ALREADY placed
    every row in its reducer's region while the Parquet record batches
    streamed through — ``table`` holds the rows GROUPED by reducer, and
    each reducer's chunk is a zero-copy slice ``[offsets[r], offsets[r+1])``
    of it. The reduce body treats those slices as already-in-order sources
    (the ``idx=None`` arm of :func:`_fused_reduce`) — rows were scattered
    in increasing global row order, i.e. exactly the stable order the
    legacy plan's gather produces, so both paths emit bit-identical
    reducer outputs. The ``table`` / indexing / iteration /
    ``materialize()`` surface matches :class:`MapShard`'s so every
    consumer (cross-host ship, recovery, tests) works on either shape.
    """

    __slots__ = ("table", "offsets", "columns")

    def __init__(self, table: pa.Table, offsets: np.ndarray,
                 columns: Dict[str, np.ndarray]):
        self.table = table
        self.offsets = offsets
        self.columns = columns  # the grouped numpy buffers backing table

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def __getitem__(self, reducer_index: int) -> "FusedChunk":
        return FusedChunk(self, reducer_index)

    def __iter__(self):
        return (self[r] for r in range(len(self)))


class FusedChunk:
    """One reducer's zero-copy slice of a fused (grouped) map output."""

    __slots__ = ("shard", "reducer_index")

    def __init__(self, shard: FusedMapShard, reducer_index: int):
        self.shard = shard
        self.reducer_index = reducer_index

    @property
    def _bounds(self) -> "tuple[int, int]":
        offsets = self.shard.offsets
        return int(offsets[self.reducer_index]), \
            int(offsets[self.reducer_index + 1])

    @property
    def num_rows(self) -> int:
        lo, hi = self._bounds
        return hi - lo

    @property
    def indices(self) -> np.ndarray:
        # Rows are pre-grouped, so this chunk's rows of the shard table
        # are simply the contiguous run — materialized lazily for the
        # (diagnostic/test) callers that inspect the gather plan.
        lo, hi = self._bounds
        return np.arange(lo, hi, dtype=np.int64)

    def materialize(self) -> pa.Table:
        lo, hi = self._bounds
        return self.shard.table.slice(lo, hi - lo)


#: Record-batch granularity of the streaming map pipeline. Large enough
#: that the per-batch Python overhead (dest assignment, column loop)
#: amortizes; small enough that decode->scatter stays cache-resident and
#: peak memory holds ~one batch plus the grouped output.
_FUSED_STREAM_BATCH_ROWS = 1 << 16


def _fused_pipeline_enabled() -> bool:
    return rt_policy.resolve("shuffle", "shuffle_fused_pipeline") is not False


def _fused_stream_columns(filename: str, num_reducers: int, seed: int,
                          epoch: int, file_index: int,
                          map_transform: Optional[MapTransform]):
    """Stream a Parquet file's record batches straight into per-reducer
    grouped column buffers: fused decode->partition->gather, no
    intermediate decoded-table materialization.

    Returns ``(out_cols, offsets, names)`` — flat per-column arrays
    grouped by reducer plus the region offsets — or ``None`` whenever the
    input falls outside the fast path's contract (non-primitive or
    nullable columns, a transform that is not row-elementwise, >= 2**31
    rows, mid-stream schema drift): the caller falls back to the legacy
    read-then-plan path, whose output is bit-identical.

    The partition stream is the same ``(seed, epoch, file_index)``
    splitmix64 stream as :func:`plan_map_partition`'s fused plan —
    per-reducer counts come from the hash alone (no data), and each
    batch's rows scatter to ``assign_dest_batch`` slots that reproduce the
    legacy counting sort's stable layout.
    """
    from ray_shuffling_data_loader_tpu import native
    if map_transform is not None and not getattr(
            map_transform, "row_elementwise", False):
        return None
    pf = rt_storage.open_parquet(filename, epoch=epoch, task=file_index)
    try:
        num_rows = pf.metadata.num_rows
        if num_rows <= 0 or num_rows >= 2**31:
            return None
        counts = ops.partition_counts(num_rows, num_reducers, seed, epoch,
                                      file_index,
                                      nthreads=_SCATTER_GATHER_THREADS)
        offsets = np.zeros(num_reducers + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        cursors = offsets[:-1].copy()
        use_native = native.available()
        out_cols: Optional[Dict[str, np.ndarray]] = None
        names: Optional[List[str]] = None
        row0 = 0
        for batch in pf.iter_batches(batch_size=_FUSED_STREAM_BATCH_ROWS):
            tbl = pa.Table.from_batches([batch])
            if map_transform is not None:
                tbl = map_transform(tbl)
                if tbl.num_rows != batch.num_rows:
                    return None
            cols = _table_numpy_columns(tbl)
            if cols is None:
                return None
            if out_cols is None:
                names = list(cols)
                out_cols = {name: np.empty(num_rows, dtype=cols[name].dtype)
                            for name in names}
            elif (list(cols) != names
                  or any(cols[n].dtype != out_cols[n].dtype for n in names)):
                return None
            n = tbl.num_rows
            dest = ops.assign_dest_batch(n, num_reducers, seed, epoch,
                                         file_index, row0, cursors)
            for name in names:
                src = cols[name]
                out = out_cols[name]
                if (use_native and dest.dtype == np.int32
                        and src.flags.c_contiguous
                        and src.dtype.itemsize in (1, 2, 4, 8)):
                    native.scatter_gather(src, None, dest, out,
                                          nthreads=_SCATTER_GATHER_THREADS)
                else:
                    out[dest] = src
            row0 += n
        if out_cols is None or row0 != num_rows:
            return None  # torn metadata: let the legacy reader diagnose it
        return out_cols, offsets, names
    finally:
        try:
            pf.close()
        except AttributeError:  # older pyarrow: reader closes with GC
            pass


def _fused_stream_map(filename: str, num_reducers: int, seed: int,
                      epoch: int, file_index: int,
                      map_transform: Optional[MapTransform]
                      ) -> Optional[FusedMapShard]:
    """:func:`_fused_stream_columns` packaged as per-reducer zero-copy
    table chunks (the thread backend's shard shape); ``None`` when the
    file is outside the fast path's contract."""
    streamed = _fused_stream_columns(filename, num_reducers, seed, epoch,
                                     file_index, map_transform)
    if streamed is None:
        return None
    out_cols, offsets, names = streamed
    from ray_shuffling_data_loader_tpu import native
    table = pa.table({name: out_cols[name] for name in names})
    native.account_table(table)
    return FusedMapShard(table, offsets, out_cols)


def _read_map_table(filename: str, epoch: int, file_index: int,
                    read_retry: Optional[rt_retry.RetryPolicy],
                    inject: bool = True) -> pa.Table:
    """The map task's dataset read, as named fault sites plus an
    in-place retry for transient IO errors (an NFS/GCS/remote blip
    heals on retry; a corrupt file does not, so ``ArrowInvalid`` is not
    retried and surfaces to the quarantine policy in
    :func:`shuffle_map`). The bytes come from the installed
    :mod:`storage` source — local disk, HTTP, or the simulated object
    store — which is also where the ``storage_read``/``storage_stall``
    chaos sites live.

    ``faults.inject`` sits OUTSIDE the retried read on purpose: an
    injected fault simulates a *lost task*, and must surface to the
    lineage-recovery machinery under test rather than be absorbed here.
    ``inject=False`` skips the map_read fault site — used when the
    caller already fired it for this task (the streaming pipeline's
    ineligible-file fallback) so one map task never consumes two
    map_read injections (the storage sites are exactly-once per key on
    their own).
    """
    if inject:
        rt_faults.inject("map_read", epoch=epoch, task=file_index)
    return rt_storage.read_table(filename, epoch=epoch, task=file_index,
                                 retry=read_retry)


def shuffle_map(filename: str,
                num_reducers: int,
                seed: int,
                epoch: int,
                file_index: int,
                stats_collector=None,
                map_transform: Optional[MapTransform] = None,
                file_cache: Optional[FileTableCache] = None,
                on_bad_file: str = "raise",
                read_retry: Optional[rt_retry.RetryPolicy] = None):
    """Read one file and plan the scatter of its rows across reducers
    (reference: shuffle.py:199-226 — but the per-reducer gather is deferred
    to the reduce task, which fuses it with the shuffle permutation).

    Returns a :class:`MapShard`, or — when the file is corrupt/unreadable
    after ``read_retry`` and ``on_bad_file="skip"`` — a structured
    :class:`runtime.faults.QuarantinedFile` report that the reduce gather
    drops (recorded in ``stats.fault_stats()``, never silent). With the
    default ``on_bad_file="raise"`` a bad file fails the map task; lineage
    recovery retries it, and only exhausted recovery poisons the run.
    """
    if on_bad_file not in ("raise", "skip"):
        raise ValueError(
            f"on_bad_file must be 'raise' or 'skip', got {on_bad_file!r}")
    if stats_collector is not None:
        stats_collector.map_start(epoch)
    start = timeit.default_timer()
    # Flight-recorder stage span: the kind reuses the fault-site name, so
    # a chaos run's map_read faults join this event by (kind, epoch,
    # task). It covers the read (and its retries, or its quarantine), not
    # the partition plan below it.
    with rt_telemetry.span("map_read", epoch=epoch, task=file_index):
        # Streaming fast path: cache-less reads only — the decoded table is
        # never materialized, so there is nothing to publish into a
        # cross-epoch cache (cached runs keep the legacy read: their
        # steady-state epochs pay no decode at all, and the reduce's single
        # fused gather is already one pass).
        if file_cache is None and _fused_pipeline_enabled():
            rt_faults.inject("map_read", epoch=epoch, task=file_index)
            fused_fn = functools.partial(
                _fused_stream_map, filename, num_reducers, seed, epoch,
                file_index, map_transform)
            try:
                shard = (fused_fn() if read_retry is None
                         else read_retry.call(fused_fn,
                                              describe=f"stream {filename}"))
            except (OSError, pa.ArrowInvalid) as e:
                if on_bad_file != "skip":
                    raise
                report = rt_faults.QuarantinedFile(
                    filename=filename, epoch=epoch, file_index=file_index,
                    error=f"{type(e).__name__}: {e}")
                stats_mod.fault_stats().record_quarantine(report)
                logger.error(
                    "quarantined unreadable input file %s (epoch %d, "
                    "file %d): %s; shuffling the remaining files "
                    "(on_bad_file='skip')", filename, epoch, file_index, e)
                if stats_collector is not None:
                    stats_collector.map_done(
                        epoch, timeit.default_timer() - start,
                        timeit.default_timer() - start)
                return report
            if shard is not None:
                end_read = timeit.default_timer()
                if stats_collector is not None:
                    stats_collector.map_done(
                        epoch, timeit.default_timer() - start,
                        end_read - start)
                return shard
            # Ineligible for streaming: legacy read below (the map_read
            # fault site already fired once for this task, so skip the
            # legacy reader's injection).
            inject_fault = False
        else:
            inject_fault = True
        table = file_cache.get(filename) if file_cache is not None else None
        if table is None:
            # Local path or remote URI (gs://, s3://, ... — the reference
            # reads via smart_open, reference: shuffle.py:7,208); the cache
            # above keys on the full URI string either way.
            try:
                table = _read_map_table(filename, epoch, file_index,
                                        read_retry, inject=inject_fault)
            except (OSError, pa.ArrowInvalid) as e:
                if on_bad_file != "skip":
                    raise
                report = rt_faults.QuarantinedFile(
                    filename=filename, epoch=epoch, file_index=file_index,
                    error=f"{type(e).__name__}: {e}")
                stats_mod.fault_stats().record_quarantine(report)
                logger.error(
                    "quarantined unreadable input file %s (epoch %d, "
                    "file %d): %s; shuffling the remaining files "
                    "(on_bad_file='skip')", filename, epoch, file_index, e)
                if stats_collector is not None:
                    stats_collector.map_done(
                        epoch, timeit.default_timer() - start,
                        timeit.default_timer() - start)
                return report
            if map_transform is not None:
                table = map_transform(table)
            if file_cache is not None:
                # Blessed: paid once per CACHED file — single-chunk columns
                # make every later epoch's numpy views zero-copy.
                # rsdl-lint: disable=copy-in-hot-path
                table = table.combine_chunks()
                file_cache.put(filename, table)
            # Charge the decoded table to the buffer ledger for its
            # lifetime — whether it now lives in the cache or only in this
            # epoch's MapShard, 'wrapper alive' is 'bytes in flight'.
            from ray_shuffling_data_loader_tpu import native
            native.account_table(table)
        end_read = timeit.default_timer()
    index_parts = plan_map_partition(table.num_rows, num_reducers,
                                     seed, epoch, file_index)
    shard = MapShard(table, index_parts)
    if stats_collector is not None:
        stats_collector.map_done(epoch, timeit.default_timer() - start,
                                 end_read - start)
    return shard


def _fused_reduce(reduce_index: int, seed: int, epoch: int,
                  sources: Sequence[Tuple[Dict[str, np.ndarray],
                                          Optional[np.ndarray], int]],
                  column_names: Sequence[str],
                  gather_threads: Optional[int] = None) -> pa.Table:
    """Single-pass scatter-gather: out[i] = concat(chunks)[perm[i]].

    Each source is ``(columns, row_indices_or_None, num_rows)``; ``None``
    indices mean the source rows are already this reducer's chunk in order.
    Bit-identical to ``pa.concat_tables(chunks).take(perm)``.
    """
    counts = [n for _, _, n in sources]
    total = sum(counts)
    perm = ops.permutation(total, ops.reduce_rng(seed, epoch, reduce_index))
    # inverse permutation: concat-order row j lands at output position inv[j].
    # int32 indices: the scatter-gather's dominant memory traffic is the
    # index arrays themselves (2 index reads per row per column), so
    # halving index width outruns the one-time casts. idx values address the
    # SOURCE table (not this reducer's output), so the width must cover the
    # largest source row count as well as `total`.
    max_source_rows = max(
        (next(iter(cols.values())).size for cols, _, _ in sources if cols),
        default=0)
    index_dtype = (np.int32 if max(total, max_source_rows) < 2**31
                   else np.int64)
    inv = np.empty(total, dtype=index_dtype)
    inv[perm] = np.arange(total, dtype=index_dtype)
    sources = [(cols, None if idx is None
                else idx.astype(index_dtype, copy=False), n)
               for cols, idx, n in sources]
    from ray_shuffling_data_loader_tpu import native
    use_native = native.available() and index_dtype == np.int32
    threads = gather_threads or _SCATTER_GATHER_THREADS
    names = list(column_names)
    # Column fan-out: columns are independent gathers, so run them on the
    # shared column pool and split this task's thread budget across the
    # columns in flight — total concurrency stays at `threads`, but the
    # per-column Python loop (slice bookkeeping, the numpy fallback arms)
    # no longer serializes the whole reduce. Small outputs stay inline:
    # below the native kernel's own threading floor the handoff costs more
    # than it saves.
    fan_out = min(len(names), threads) if total >= (1 << 16) else 1
    col_threads = max(1, threads // fan_out)

    def _gather_column(name: str) -> np.ndarray:
        dtype = sources[0][0][name].dtype
        out = np.empty(total, dtype=dtype)
        offset = 0
        for cols, idx, n in sources:
            dest = inv[offset:offset + n]
            src = cols[name]
            if (use_native and src.flags.c_contiguous
                    and dtype.itemsize in (1, 2, 4, 8)):
                native.scatter_gather(src, idx, dest, out,
                                      nthreads=col_threads)
            elif idx is None:
                out[dest] = src
            else:
                out[dest] = src[idx]
            offset += n
        return out

    if fan_out > 1:
        pool = _column_gather_pool()
        futures = [pool.submit(_gather_column, name) for name in names[1:]]
        out_cols = {names[0]: _gather_column(names[0])}
        for name, future in zip(names[1:], futures):
            out_cols[name] = future.result()
    else:
        out_cols = {name: _gather_column(name) for name in names}
    return pa.table(out_cols)


def shuffle_reduce(reduce_index: int,
                   seed: int,
                   epoch: int,
                   chunks: Sequence[Union[pa.Table, LazyChunk]],
                   stats_collector=None,
                   reduce_transform: Optional[ReduceTransform] = None,
                   gather_threads: Optional[int] = None) -> pa.Table:
    """Concatenate one chunk per file and permute the rows
    (reference: shuffle.py:229-247).

    Chunks may be materialized ``pa.Table``s (the cross-host path) or
    :class:`LazyChunk`s (host-local map outputs). When every chunk's
    columns are primitive and null-free the concat+permute+gather collapses
    into ONE numpy scatter-gather pass per column — the output is
    bit-identical to the materialize-concat-take path, at roughly half the
    memory traffic.
    """
    if stats_collector is not None:
        stats_collector.reduce_start(epoch)
    start = timeit.default_timer()
    # No span of its own: a reduce task times its whole body, this call
    # included, under its reduce_gather span (rsdl.loader.reduce).
    shuffled = _shuffle_reduce_body(reduce_index, seed, epoch, chunks,
                                    reduce_transform, gather_threads)
    if stats_collector is not None:
        stats_collector.reduce_done(epoch, timeit.default_timer() - start)
    return shuffled


def _promote_offset_type(t: pa.DataType) -> pa.DataType:
    """64-bit-offset (``large_*``) form of ``t``, recursing into nested
    value types: ``list<string>`` becomes ``large_list<large_string>``
    (a promoted outer list with 32-bit child offsets would re-raise
    ArrowInvalid on the retried take when the CHILD data exceeds 2 GiB).
    Fixed-size lists keep their width but promote their children; struct
    fields promote independently."""
    if pa.types.is_binary(t):
        return pa.large_binary()
    if pa.types.is_string(t):
        return pa.large_string()
    if pa.types.is_list(t) or pa.types.is_large_list(t):
        return pa.large_list(_promote_offset_type(t.value_type))
    if pa.types.is_fixed_size_list(t):
        return pa.list_(_promote_offset_type(t.value_type), t.list_size)
    if pa.types.is_struct(t):
        return pa.struct([
            field.with_type(_promote_offset_type(field.type)) for field in t
        ])
    return t


def _promote_large_offsets(table: pa.Table) -> pa.Table:
    """Cast 32-bit-offset variable-width columns (binary/string/list,
    including nested children) to their 64-bit ``large_*`` forms so a
    single reducer output may exceed 2 GiB of variable-width data."""
    fields = []
    changed = False
    for field in table.schema:
        t = _promote_offset_type(field.type)
        if t != field.type:
            changed = True
        fields.append(field.with_type(t))
    if not changed:
        return table
    return table.cast(pa.schema(fields, metadata=table.schema.metadata))


def _shuffle_reduce_body(reduce_index, seed, epoch, chunks,
                         reduce_transform, gather_threads=None):
    shuffled = None
    sources = []
    schema = None
    for chunk in chunks:
        if isinstance(chunk, LazyChunk):
            cols = chunk.shard.numpy_columns()
            if cols is None:
                break
            chunk_schema = chunk.shard.table.schema
            sources.append((cols, chunk.indices, chunk.num_rows))
        elif isinstance(chunk, FusedChunk):
            # Pre-grouped rows: this reducer's run is a contiguous slice
            # of the shard's numpy buffers, already in the stable order
            # the legacy gather would produce — the idx=None arm.
            lo, hi = chunk._bounds
            cols = {name: arr[lo:hi]
                    for name, arr in chunk.shard.columns.items()}
            chunk_schema = chunk.shard.table.schema
            sources.append((cols, None, hi - lo))
        else:
            cols = _table_numpy_columns(chunk)
            if cols is None:
                break
            chunk_schema = chunk.schema
            sources.append((cols, None, chunk.num_rows))
        if schema is None:
            schema = chunk_schema
        elif schema != chunk_schema:
            break
    else:
        if schema is not None:
            shuffled = _fused_reduce(reduce_index, seed, epoch, sources,
                                     schema.names, gather_threads)
    if shuffled is None and chunks:
        # Fallback: nested / nullable / mixed-schema columns.
        tables = [
            c.materialize() if isinstance(c, (LazyChunk, FusedChunk)) else c
            for c in chunks
        ]
        # permissive promotion: a map-side transform (or a partially
        # promoted cross-host stream) may hand this reducer chunks whose
        # schemas differ only in offset width; unifying them here keeps
        # the fallback alive in exactly the regime it serves.
        table = pa.concat_tables(tables, promote_options="permissive")
        perm = ops.permutation(table.num_rows,
                               ops.reduce_rng(seed, epoch, reduce_index))
        try:
            shuffled = table.take(perm)
        except pa.ArrowInvalid:
            # >2 GiB of variable-width data in ONE reducer output (e.g.
            # 1e6-image corpora with few reducers): the gather's chunk
            # concatenation overflows 32-bit offsets. Promote to 64-bit
            # offset types and retry — Arrow IPC, the transport, and the
            # consumers all handle large_* columns.
            table = _promote_large_offsets(table)
            shuffled = table.take(perm)
    elif shuffled is None:
        shuffled = pa.table({})
    # Applied even to 0-row outputs: a schema-changing transform (e.g.
    # image decode) must keep every reducer's schema identical or the
    # iterator's carry-buffer concat breaks on the mixed schemas.
    if reduce_transform is not None and shuffled.num_columns:
        shuffled = reduce_transform(shuffled)
    return shuffled


def recompute_reducer_output(filenames: Sequence[str], num_reducers: int,
                             seed: int, epoch: int, reduce_index: int,
                             map_transform: Optional[MapTransform] = None,
                             reduce_transform: Optional[ReduceTransform]
                             = None,
                             on_bad_file: str = "raise") -> pa.Table:
    """Rebuild one reducer output from scratch lineage: re-read every
    input file, re-plan its scatter, and re-run the fused reduce — a pure
    function of ``(seed, epoch, reduce_index)`` and the files, so the
    result is bit-identical to the original. This is the spill tier's
    corruption-recovery path (spill.py): deliberately self-contained (no
    map-shard refs captured) so an armed :class:`spill.SpilledTable`
    handle pins only this closure's small arguments, never an epoch's
    decoded tables."""
    chunks = []
    for file_index, filename in enumerate(filenames):
        shard = shuffle_map(filename, num_reducers, seed, epoch,
                            file_index, None, map_transform, None,
                            on_bad_file, None)
        if isinstance(shard, rt_faults.QuarantinedFile):
            continue
        chunks.append(shard[reduce_index])
    return shuffle_reduce(reduce_index, seed, epoch, chunks, None,
                          reduce_transform)


class EpochLineage:
    """Recompute lost map outputs from their ``(seed, epoch, file)`` lineage.

    Ray reconstructs a lost object by re-running the task recorded in its
    lineage; here every map task is a pure function of
    ``(seed, epoch, file_index)`` (the determinism contract checkpoint.py
    already exploits), so the lineage IS those three integers plus the
    map configuration this object captures. When a reduce gather observes
    a failed map ref it calls :meth:`recover`: the first reducer to
    observe the failure recomputes the map task **inline on its own
    worker thread** (never re-submitted to the pool — all workers may be
    reduce tasks blocked on this very output, and a pool-queued recompute
    behind them would deadlock; the FIFO submission-order argument in the
    module docstring only covers the original submission). Every other
    reducer waits on the first one's result, so a lost map is recomputed
    exactly once per epoch no matter how many reducers need it.

    Recovery is bounded by a :class:`runtime.retry.RetryPolicy`; a
    recovery that exhausts its attempts raises (and is cached, so later
    reducers fail fast instead of re-running a known-dead recompute) —
    those are the only map failures that reach the ``ShuffleFailure``
    poison pill. Recomputed shards are bit-identical to the lost ones
    (seeded RNG, row-order-preserving transforms), so the consumed batch
    stream is unchanged by recovery.
    """

    class _Cell:
        __slots__ = ("done", "result", "error")

        def __init__(self):
            self.done = threading.Event()
            self.result = None
            self.error: Optional[BaseException] = None

    def __init__(self, filenames: Sequence[str], num_reducers: int,
                 seed: int, epoch: int, stats_collector=None,
                 map_transform: Optional[MapTransform] = None,
                 file_cache: Optional[FileTableCache] = None,
                 retry_policy: Optional[rt_retry.RetryPolicy] = None,
                 on_bad_file: str = "raise",
                 read_retry: Optional[rt_retry.RetryPolicy] = None):
        self._filenames = list(filenames)
        self._num_reducers = num_reducers
        self._seed = seed
        self._epoch = epoch
        self._stats_collector = stats_collector
        self._map_transform = map_transform
        self._file_cache = file_cache
        self._retry = (retry_policy if retry_policy is not None
                       else rt_retry.RetryPolicy.for_component("lineage"))
        self._on_bad_file = on_bad_file
        self._read_retry = read_retry
        self._lock = threading.Lock()
        self._cells: Dict[int, EpochLineage._Cell] = {}
        self.recomputes = 0

    def recover(self, file_index: int, cause: BaseException):
        """Return the recomputed output of map task ``file_index``
        (a MapShard or QuarantinedFile), recomputing it at most once."""
        with self._lock:
            cell = self._cells.get(file_index)
            claimed = cell is None
            if claimed:
                cell = self._cells[file_index] = EpochLineage._Cell()
        if claimed:
            self._recompute(file_index, cell, cause)
        else:
            cell.done.wait()
        if cell.error is not None:
            # Re-raise the recompute's own failure (same type as the
            # original — the task is deterministic), chained to the first
            # observed one: consumers keep matching on the real exception
            # class (ValueError from a bad transform, FileNotFoundError
            # from a missing file), with lineage exhaustion in the chain.
            raise cell.error from cause
        return cell.result

    def _recompute(self, file_index: int, cell: "EpochLineage._Cell",
                   cause: BaseException) -> None:
        start = timeit.default_timer()
        logger.warning(
            "map task %d (epoch %d) failed (%s); recomputing from lineage",
            file_index, self._epoch, cause)
        try:
            cell.result = self._retry.call(
                shuffle_map, self._filenames[file_index],
                self._num_reducers, self._seed, self._epoch, file_index,
                self._stats_collector, self._map_transform,
                self._file_cache, self._on_bad_file, self._read_retry,
                describe=f"map recompute e{self._epoch} f{file_index}")
        except BaseException as e:  # noqa: BLE001 - cached + re-raised
            stats_mod.fault_stats().record_exhausted("lineage")
            cell.error = e
        else:
            latency = timeit.default_timer() - start
            with self._lock:
                self.recomputes += 1
            stats_mod.fault_stats().record_recompute("lineage", latency)
            logger.info(
                "recomputed map task %d (epoch %d) from lineage in %.3fs",
                file_index, self._epoch, latency)
        finally:
            cell.done.set()


def _reduce_task(reduce_index: int, seed: int, epoch: int,
                 map_refs: Sequence[ex.TaskRef], stats_collector,
                 reduce_transform: Optional[ReduceTransform] = None,
                 spill_manager=None,
                 gather_threads: Optional[int] = None,
                 lineage: Optional[EpochLineage] = None,
                 retry_policy: Optional[rt_retry.RetryPolicy] = None,
                 spill_recompute=None) -> pa.Table:
    """Executor wrapper: resolve this reducer's chunk from every map output.

    Equivalent of Ray resolving ``shuffle_reduce.remote(*refs)`` argument
    refs (reference: shuffle.py:182-187) — but the chunks stay lazy
    (index arrays into the map tables) until the fused reduce gathers them.

    Fault handling (this is where lineage recovery hooks in): a failed
    map ref is recomputed via ``lineage.recover`` instead of propagating;
    a :class:`runtime.faults.QuarantinedFile` marker (``on_bad_file=
    "skip"``) drops that file's chunk; and the gather+shuffle itself is
    re-run under ``retry_policy`` on failure — safe because the whole
    body is a pure function of ``(seed, epoch, reduce_index)`` and the
    (lazy, repeatable) map outputs. Only exhausted recovery escapes.
    """

    def _gather_and_shuffle() -> pa.Table:
        # The telemetry span covers the WHOLE reduce task body (fault
        # site, ref gather, fused shuffle) — that is the unit the
        # bottleneck attribution bills to the "reduce" stage, and the
        # unit a reduce_gather chaos rule (fail or delayN) perturbs, so
        # the two correlate by (kind, epoch, task).
        with rt_telemetry.span("reduce_gather", epoch=epoch,
                               task=reduce_index):
            rt_faults.inject("reduce_gather", epoch=epoch,
                             task=reduce_index)
            chunks = []
            for file_index, ref in enumerate(map_refs):
                try:
                    shard = ref.result()
                except Exception as e:  # noqa: BLE001 - lineage recovers
                    if lineage is None:
                        raise
                    shard = lineage.recover(file_index, e)
                if isinstance(shard, rt_faults.QuarantinedFile):
                    continue  # dropped file: shuffle the surviving inputs
                chunks.append(shard[reduce_index])
            return shuffle_reduce(reduce_index, seed, epoch, chunks,
                                  stats_collector, reduce_transform,
                                  gather_threads)

    if retry_policy is None:
        shuffled = _gather_and_shuffle()
    else:
        def _recovered(failed_attempts: int, elapsed_s: float) -> None:
            stats_mod.fault_stats().record_recompute("reduce", elapsed_s)

        shuffled = retry_policy.call(
            _gather_and_shuffle,
            describe=f"reduce e{epoch} r{reduce_index}",
            on_recovery=_recovered)
    return account_and_maybe_spill(shuffled, spill_manager,
                                   recompute=spill_recompute,
                                   epoch=epoch, task=reduce_index,
                                   seed=seed)


def account_and_maybe_spill(shuffled: pa.Table, spill_manager,
                            recompute=None, epoch: Optional[int] = None,
                            task: Optional[int] = None,
                            seed: Optional[int] = None) -> pa.Table:
    """Post-reduce memory policy, shared by the single-host and distributed
    reduce wrappers so their semantics cannot diverge: charge the output's
    in-flight bytes to the buffer ledger (plasma's store-utilization role;
    the max_inflight_bytes throttle reads the same counter), then spill it
    if a spill manager is active and the pipeline is over budget — the
    SpilledTable handle replaces the table, so the in-memory copy is
    released as soon as the reduce task returns. ``recompute`` (single-
    host path: :func:`recompute_reducer_output` bound to this reducer's
    lineage) arms the handle's corrupt-spill recovery; the cross-host
    path passes None — its inputs crossed the wire, so a corrupt spill
    there stays a loud failure.

    The output is also stamped with its lineage as ``rsdl.trace``
    schema metadata (``"seed:epoch:task"``) — the causal trace context
    (runtime/trace.py). Schema metadata survives slicing, Arrow IPC
    (spill files, the queue wire, the transport) and concatenation, so
    whichever process ends up holding this table can name the exact
    reduce span that built it; the queue service copies the task id
    into its v2 frame headers from here."""
    if epoch is not None and task is not None:
        from ray_shuffling_data_loader_tpu.runtime import latency as rt_lat
        meta = dict(shuffled.schema.metadata or {})
        meta[b"rsdl.trace"] = f"{seed if seed is not None else 0}:" \
                              f"{epoch}:{task}".encode()
        # Birth stamp (runtime/latency.py): the delivery-latency plane's
        # t=0 for this payload. Both clocks + the producing pid ride
        # along so any downstream process (queue shard, trainer, device
        # loop) computes a skew-proof age; like rsdl.trace, the stamp
        # survives slicing, IPC, spill and the queue wire.
        meta[rt_lat.BIRTH_META_KEY] = rt_lat.encode_stamp(
            rt_lat.now_stamp())
        shuffled = shuffled.replace_schema_metadata(meta)
    from ray_shuffling_data_loader_tpu import native
    native.account_table(shuffled)
    if spill_manager is not None:
        shuffled = spill_manager.maybe_spill(shuffled, recompute=recompute,
                                             epoch=epoch, task=task)
    return shuffled


def consume(trainer_idx: int,
            batch_consumer: BatchConsumer,
            trial_start: float,
            stats_collector,
            epoch: int,
            batches: List[ex.TaskRef]) -> None:
    """Hand one trainer its epoch's reducer refs (reference: shuffle.py:250-263)."""
    if stats_collector is not None:
        stats_collector.consume_start(epoch)
    start = timeit.default_timer()
    trial_time_to_consume = start - trial_start
    batch_consumer(trainer_idx, epoch, batches)
    if stats_collector is not None:
        stats_collector.consume_done(epoch, timeit.default_timer() - start,
                                     trial_time_to_consume)


def shuffle_epoch(epoch: int,
                  filenames: Sequence[str],
                  batch_consumer: BatchConsumer,
                  num_reducers: int,
                  num_trainers: int,
                  pool: ex.Executor,
                  seed: int,
                  trial_start: float,
                  stats_collector=None,
                  map_transform: Optional[MapTransform] = None,
                  file_cache: Optional[FileTableCache] = None,
                  reduce_transform: Optional[ReduceTransform] = None,
                  spill_manager=None,
                  gather_threads: Optional[int] = None,
                  on_bad_file: str = "raise",
                  fault_policies: Optional[Dict[str, Any]] = None,
                  window: Optional[Dict[str, Any]] = None
                  ) -> List[ex.TaskRef]:
    """Launch one epoch's map/reduce and route outputs to trainers
    (reference: shuffle.py:163-196). Returns the reducer TaskRefs.

    The epoch is executed as an explicit :class:`plan.ir.EpochPlan`
    (files -> map partitions -> reduce slices -> queue routes) driven by
    the plan scheduler (plan/scheduler.py): dependency-ordered dispatch
    onto the pool, optional speculative re-execution of stragglers
    (``RSDL_PLAN_SPECULATION``) and work-stealing placement
    (``RSDL_PLAN_STEALING``) — on both executor backends.

    ``fault_policies`` carries the per-stage RetryPolicy objects built
    once by the driver (keys ``read``/``reduce``/``lineage``); when
    omitted they resolve from the runtime policy registry here — so a
    directly-driven epoch still recovers lost maps from lineage.
    """
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
    if stats_collector is not None:
        stats_collector.epoch_start(epoch)
    plan = plan_ir.build_epoch_plan(filenames, num_reducers, num_trainers,
                                    seed, epoch, window=window)
    if getattr(pool, "backend", "thread") == "process":
        reduce_refs = _shuffle_epoch_process(
            plan, pool, stats_collector, map_transform, reduce_transform,
            spill_manager, gather_threads, on_bad_file)
    else:
        reduce_refs = _shuffle_epoch_thread(
            plan, pool, stats_collector, map_transform, file_cache,
            reduce_transform, spill_manager, gather_threads, on_bad_file,
            fault_policies)
    # Queue routes come FROM the plan: each route node names its trainer
    # rank, queue index and contiguous reducer span (the arithmetic the
    # inline ops.contiguous_splits call used to re-derive).
    for route in sorted(plan.routes(), key=lambda n: n.key.task):
        rank = route.key.task
        batches = [reduce_refs[i] for i in route.meta["reducers"]]
        consume(rank, batch_consumer, trial_start, stats_collector,
                epoch, batches)
        # Epoch-end sentinel per trainer (reference: shuffle.py:195).
        batch_consumer(rank, epoch, None)
    return reduce_refs


def _shuffle_epoch_thread(plan, pool, stats_collector, map_transform,
                          file_cache, reduce_transform, spill_manager,
                          gather_threads, on_bad_file, fault_policies
                          ) -> List[ex.TaskRef]:
    """Thread-backend epoch engine: the plan's map/reduce nodes dispatch
    onto the thread pool in dependency order. Reduce tasks keep their
    :class:`EpochLineage` recovery (a failed map ref is recomputed inline
    by the first reduce that observes it — recompute counts and failure
    semantics are unchanged by the plan engine underneath). Speculative
    backup attempts run under ``telemetry.speculative()`` with no stats
    collector, so duplicated work never double-counts anywhere."""
    from ray_shuffling_data_loader_tpu.plan import scheduler as plan_sched
    epoch, seed = plan.epoch, plan.seed
    num_reducers = plan.num_reducers
    filenames_list = list(plan.filenames)
    policies = fault_policies if fault_policies is not None \
        else default_fault_policies()
    if gather_threads is None:
        gather_threads = derive_gather_threads(num_reducers,
                                               pool.num_workers)
    lineage = EpochLineage(filenames_list, num_reducers, seed, epoch,
                           stats_collector, map_transform, file_cache,
                           retry_policy=policies.get("lineage"),
                           on_bad_file=on_bad_file,
                           read_retry=policies.get("read"))

    def _spill_recompute_for(reduce_index: int):
        if spill_manager is None:
            return None
        return functools.partial(
            recompute_reducer_output, filenames_list, num_reducers, seed,
            epoch, reduce_index, map_transform, reduce_transform,
            on_bad_file)

    holder: Dict[str, Any] = {}

    def _run_map(node, attempt: int):
        file_index = node.key.task
        if attempt == 0:
            return shuffle_map(node.meta["file"], num_reducers, seed,
                               epoch, file_index, stats_collector,
                               map_transform, file_cache, on_bad_file,
                               policies.get("read"))
        with rt_telemetry.speculative(attempt):
            return shuffle_map(node.meta["file"], num_reducers, seed,
                               epoch, file_index, None, map_transform,
                               file_cache, on_bad_file,
                               policies.get("read"))

    def _run_reduce(node, attempt: int):
        reduce_index = node.key.task
        map_refs = [holder["scheduler"].ref_for(dep) for dep in node.deps]
        if attempt == 0:
            return _reduce_task(reduce_index, seed, epoch, map_refs,
                                stats_collector, reduce_transform,
                                spill_manager, gather_threads, lineage,
                                policies.get("reduce"),
                                _spill_recompute_for(reduce_index))
        with rt_telemetry.speculative(attempt):
            return _reduce_task(reduce_index, seed, epoch, map_refs,
                                None, reduce_transform, spill_manager,
                                gather_threads, lineage,
                                policies.get("reduce"),
                                _spill_recompute_for(reduce_index))

    # Plan-driven cache warming (storage/prefetch.py): when the cache is
    # a TieredStore, idle scheduler lanes warm the files the NEXT epoch
    # re-reads (the plan's file list is the same every epoch). Below
    # steal/speculation priority; canceled when real work lands.
    prefetcher = None
    maker = getattr(file_cache, "make_prefetcher", None)
    if maker is not None and rt_policy.resolve("storage",
                                               "storage_prefetch"):
        prefetcher = maker(plan)
    scheduler = plan_sched.PlanScheduler(
        plan, pool,
        dispatchers={
            "map": lambda node, attempt: pool.submit(_run_map, node,
                                                     attempt),
            "reduce": lambda node, attempt: pool.submit(_run_reduce, node,
                                                        attempt),
        },
        prefetcher=prefetcher)
    holder["scheduler"] = scheduler
    scheduler.start()
    return scheduler.refs("reduce")


def _shuffle_epoch_process(plan, pool, stats_collector, map_transform,
                           reduce_transform, spill_manager, gather_threads,
                           on_bad_file):
    """Process-backend epoch launch: delegate the PLAN to the pool's data
    plane (procpool.process_epoch) with the workload hooks pickled once.
    The spill-recompute lineage closure is driver-side (identical to the
    thread path), so a corrupt spilled segment recovers the same way on
    either backend."""
    import pickle as _pickle
    from ray_shuffling_data_loader_tpu import procpool
    epoch, seed = plan.epoch, plan.seed
    num_reducers = plan.num_reducers
    filenames_list = list(plan.filenames)
    if gather_threads is None:
        gather_threads = derive_gather_threads(num_reducers,
                                               pool.num_workers)

    def _spill_recompute_factory(reduce_index: int):
        return functools.partial(
            recompute_reducer_output, filenames_list, num_reducers, seed,
            epoch, reduce_index, map_transform, reduce_transform,
            on_bad_file)

    return procpool.process_epoch(
        plan, pool, stats_collector,
        _pickle.dumps(map_transform) if map_transform is not None else None,
        _pickle.dumps(reduce_transform)
        if reduce_transform is not None else None,
        spill_manager, gather_threads, on_bad_file,
        _spill_recompute_factory if spill_manager is not None else None)


def shuffle(filenames: Sequence[str],
            batch_consumer: BatchConsumer,
            num_epochs: int,
            num_reducers: int,
            num_trainers: int,
            max_concurrent_epochs: int = 2,
            seed: int = 0,
            num_workers: Optional[int] = None,
            collect_stats: bool = True,
            pool: Optional[ex.Executor] = None,
            start_epoch: int = 0,
            map_transform: Optional[MapTransform] = None,
            file_cache: Union[FileTableCache, None, str] = "auto",
            reduce_transform: Optional[ReduceTransform] = None,
            task_retries: int = 0,
            max_inflight_bytes: Optional[int] = None,
            spill_dir: Optional[str] = None,
            on_bad_file: Optional[str] = None,
            executor_backend: Optional[str] = None
            ) -> Union[stats_mod.TrialStats, float]:
    """Multi-epoch pipelined shuffle driver (reference: shuffle.py:79-160).

    Keeps at most ``max_concurrent_epochs`` epochs' shuffles in flight:
    before launching epoch E, blocks on the oldest incomplete epoch's
    reducers and then drops their refs so Arrow buffers already consumed
    by trainers can be freed (reference: shuffle.py:103-140).

    ``max_inflight_bytes`` bounds TRANSIENT pipeline memory (in-flight map
    and reducer tables as accounted by the buffer ledger, file-cache bytes
    excluded): before launching a new epoch, waits — first by draining
    older epochs, then blocked on ledger release events (every consumer
    table release wakes the wait, runtime/release.py) — until under
    budget. The explicit analog of the reference operators sizing the
    plasma store and disabling spill (reference: benchmarks/cluster.yaml:175),
    with plasma's release-wakes-producer semantics. The budget must exceed
    one epoch's working set; if consumers do not release within
    ``_BUDGET_POLL_TIMEOUT_S`` (policy key ``budget_wait_timeout_s``) the
    launch proceeds with a warning rather than deadlocking.

    ``spill_dir`` (with ``max_inflight_bytes``) enables plasma's spill
    role: reducer outputs produced while over budget are written to Arrow
    IPC files under a scratch subdir and lazily memory-mapped back by the
    consumer (spill.py) — budgets smaller than one epoch's working set
    then make progress instead of warning.

    ``start_epoch`` > 0 (checkpoint resume) skips shuffling the already-
    fully-consumed epochs; epoch PRNG keys depend only on (seed, epoch),
    so the produced epochs replay exactly.

    Failure semantics (runtime/faults.py, runtime/retry.py): a failed
    map task is recomputed from its ``(seed, epoch, file)`` lineage by
    the first reduce gather that observes it; a failed reduce body is
    re-run in-task; both under bounded, jittered RetryPolicies — and
    only exhausted recovery propagates to the caller (and from there to
    the ``ShuffleFailure`` poison pill). ``on_bad_file`` (default
    ``"raise"``, policy key ``RSDL_SHUFFLE_ON_BAD_FILE``) set to
    ``"skip"`` quarantines a corrupt/unreadable input file into a
    structured ``QuarantinedFile`` report and shuffles the remaining
    files instead of failing the epoch.

    Returns ``TrialStats`` when ``collect_stats`` else the wall-clock
    duration in seconds (reference: shuffle.py:155-160).
    """
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
    if not 0 <= start_epoch <= num_epochs:
        raise ValueError(
            f"start_epoch {start_epoch} out of range [0, {num_epochs}]")
    stats_collector = None
    if collect_stats:
        if start_epoch:
            raise ValueError(
                "collect_stats with start_epoch > 0 is unsupported (stats "
                "collectors assume all epochs run)")
        stats_collector = stats_mod.TrialStatsCollector(
            num_epochs, num_maps=len(filenames), num_reduces=num_reducers,
            num_consumes=num_trainers)
        stats_collector.trial_start()
    duration = shuffle_epochs(
        plan_ir.static_epoch_specs(filenames, num_epochs, start_epoch),
        batch_consumer, num_reducers, num_trainers,
        max_concurrent_epochs=max_concurrent_epochs, seed=seed,
        num_workers=num_workers, pool=pool,
        stats_collector=stats_collector, map_transform=map_transform,
        file_cache=file_cache, reduce_transform=reduce_transform,
        task_retries=task_retries, max_inflight_bytes=max_inflight_bytes,
        spill_dir=spill_dir, on_bad_file=on_bad_file,
        executor_backend=executor_backend,
        epochs_hint=num_epochs - start_epoch)
    if stats_collector is not None:
        stats_collector.trial_done()
        return stats_collector.get_stats()
    return duration


def shuffle_epochs(epoch_specs,
                   batch_consumer: BatchConsumer,
                   num_reducers: int,
                   num_trainers: int,
                   max_concurrent_epochs: int = 2,
                   seed: int = 0,
                   num_workers: Optional[int] = None,
                   pool: Optional[ex.Executor] = None,
                   stats_collector=None,
                   map_transform: Optional[MapTransform] = None,
                   file_cache: Union[FileTableCache, None, str] = "auto",
                   reduce_transform: Optional[ReduceTransform] = None,
                   task_retries: int = 0,
                   max_inflight_bytes: Optional[int] = None,
                   spill_dir: Optional[str] = None,
                   on_bad_file: Optional[str] = None,
                   executor_backend: Optional[str] = None,
                   epochs_hint: Optional[int] = None,
                   on_epoch_done: Optional[Callable[[int], None]] = None
                   ) -> float:
    """The generalized pipelined driver: shuffle every epoch an
    *iterator* of :class:`plan.ir.EpochSpec` yields, keeping at most
    ``max_concurrent_epochs`` in flight.

    This is :func:`shuffle` with the epoch schedule inverted out: the
    static trial passes :func:`plan.ir.static_epoch_specs`; a streaming
    window assembler (``streaming/window.py``) yields specs unboundedly
    as windows close, and may BLOCK in ``__next__`` waiting for input —
    the pipeline then idles with all launched epochs still draining.

    ``epochs_hint`` sizes the decoded-file cache and the gather-thread
    overlap for finite schedules; ``None`` (unbounded stream, every file
    shuffled exactly once) disables the cache and sizes overlap at the
    concurrency cap. ``on_epoch_done(epoch)`` fires after an epoch's
    reducer refs fully drain — the streaming runner's serve-watermark
    hook. Returns the wall-clock duration in seconds.
    """
    # Causal-trace context: every id this run's spans carry derives from
    # (seed, epoch, task); stamping the seed puts it into recorder dumps
    # so offline merges re-derive the same ids (runtime/trace.py).
    rt_telemetry.set_trace_seed(seed)
    start = timeit.default_timer()

    owns_pool = pool is None
    if pool is None:
        # Backend selection (kwarg > RSDL_EXECUTOR_BACKEND > auto): the
        # process pool is the multicore data plane; the thread pool stays
        # the fallback whenever shared memory / picklable hooks are not
        # available (procpool.resolve_backend).
        from ray_shuffling_data_loader_tpu import procpool
        backend = procpool.resolve_backend(
            override=executor_backend, num_workers=num_workers,
            transforms=(map_transform, reduce_transform))
        if backend == "process":
            pool = procpool.ProcessPoolExecutor(num_workers=num_workers,
                                                task_retries=task_retries)
        else:
            pool = ex.Executor(num_workers=num_workers,
                               task_retries=task_retries)
    process_backend = getattr(pool, "backend", "thread") == "process"
    if process_backend:
        # The pool's shm segment arena IS the decoded-file cache in
        # process mode (cross-epoch table segments); a driver-side table
        # cache would just duplicate the resident set. The pool exposes
        # `bytes_cached`, so the transient-byte budget discounts cache
        # growth exactly like a FileTableCache.
        file_cache, owns_file_cache = None, False
        budget_cache = pool
    else:
        # Caching only pays when a file is mapped more than once. An
        # unbounded stream (epochs_hint None) maps each window's files
        # exactly once, so it resolves as a single-pass trial.
        file_cache, owns_file_cache = resolve_file_cache(
            file_cache, epochs_hint if epochs_hint is not None else 1)
        budget_cache = file_cache
        if hasattr(file_cache, "set_transform"):
            # The cache stores TRANSFORMED tables (the map stage puts
            # them post-transform); the prefetch warmer must apply the
            # same hook or a warmed hit would change the stream.
            file_cache.set_transform(map_transform)
    from ray_shuffling_data_loader_tpu.spill import make_budget_state
    _over_budget, spill_manager = make_budget_state(
        budget_cache, max_inflight_bytes, spill_dir)
    # Epoch pipelining keeps up to max_concurrent_epochs epochs' reduce
    # tasks in flight on this one pool — size gather threads for that
    # total, not one epoch's worth (but no more epochs than actually run;
    # an unbounded stream saturates the concurrency cap).
    overlap = max(1, max_concurrent_epochs) if epochs_hint is None \
        else max(1, min(max_concurrent_epochs, epochs_hint))
    gather_threads = derive_gather_threads(
        num_reducers * overlap, pool.num_workers)
    from ray_shuffling_data_loader_tpu.runtime import policy as rt_policy
    on_bad_file = rt_policy.resolve("shuffle", "on_bad_file",
                                    override=on_bad_file)
    fault_policies = default_fault_policies()

    try:
        in_progress: Dict[int, List[ex.TaskRef]] = {}
        for spec in epoch_specs:
            epoch_idx = spec.epoch
            throttle_start = timeit.default_timer()
            while in_progress and (len(in_progress) >= max_concurrent_epochs
                                   or _over_budget()):
                oldest_epoch = min(in_progress)
                refs = in_progress.pop(oldest_epoch)
                ex.wait(refs, num_returns=len(refs))
                for ref in refs:
                    ref.result()  # propagate map/reduce failures (instant)
                # Refs dropped here -> reducer Tables release once trainers
                # finish with them (reference: shuffle.py:131-132). The
                # frame's loop variables would otherwise pin the drained
                # epoch's last reducer table through the budget wait below.
                refs = ref = None
                if on_epoch_done is not None:
                    on_epoch_done(oldest_epoch)
            if _over_budget() and spill_manager is None:
                # All prior epochs drained; wait for consumers to release
                # tables (bounded — never deadlock the pipeline on a
                # too-small budget). With a spill manager the launch
                # proceeds instead: over-budget reducer outputs go to disk.
                # Event-driven: every last-ref ledger decref (and free-list
                # trim) wakes this wait immediately (runtime/release.py) —
                # plasma's release semantics, replacing the old periodic
                # process-wide gc.collect() cadence.
                from ray_shuffling_data_loader_tpu.runtime import (
                    policy as rt_policy, release as rt_release)
                timeout_s = rt_policy.resolve(
                    "shuffle", "budget_wait_timeout_s",
                    default=_BUDGET_POLL_TIMEOUT_S)
                if not rt_release.wait_while(
                        _over_budget, timeout_s=timeout_s,
                        heartbeat_s=rt_policy.resolve(
                            "shuffle", "release_heartbeat_s")):
                    logger.warning(
                        "epoch %d launching over max_inflight_bytes=%d "
                        "(consumers did not release within %.0fs)",
                        epoch_idx, max_inflight_bytes, timeout_s)
            throttle_duration = timeit.default_timer() - throttle_start
            if stats_collector is not None and throttle_duration > 1e-4:
                stats_collector.throttle_done(epoch_idx, throttle_duration)
            if throttle_duration > 1e-4:
                logger.info("epoch %d throttled for %.3fs", epoch_idx,
                            throttle_duration)
            # An elastic world retopologizes per epoch spec: a window
            # sealed on a new membership view carries its own reducer
            # count (plan.ir.EpochSpec.num_reducers); the driver default
            # covers every fixed-world spec. Trainer count never moves,
            # so the queue-route keys stay stable across resizes.
            spec_reducers = (spec.num_reducers
                             if getattr(spec, "num_reducers", None)
                             else num_reducers)
            in_progress[epoch_idx] = shuffle_epoch(
                epoch_idx, spec.filenames, batch_consumer, spec_reducers,
                num_trainers, pool, seed, start, stats_collector,
                map_transform, file_cache, reduce_transform, spill_manager,
                gather_threads, on_bad_file, fault_policies,
                window=spec.window)
        # Final drain: wait for all remaining reducer tasks
        # (reference: shuffle.py:148-151).
        for epoch_idx in sorted(in_progress):
            refs = in_progress.pop(epoch_idx)
            ex.wait(refs, num_returns=len(refs))
            for ref in refs:
                ref.result()  # propagate map/reduce failures (instant)
            refs = ref = None
            if on_epoch_done is not None:
                on_epoch_done(epoch_idx)
    finally:
        if owns_pool:
            pool.shutdown()
        if owns_file_cache:
            # Reducer outputs are gathered COPIES, never views of cached
            # tables, and all refs were drained above — the scratch files
            # have no remaining readers.
            file_cache.close()
        if spill_manager is not None:
            # Scratch-dir deletion is reference-managed (consumers may
            # still be draining spilled batches from the queue).
            spill_manager.report()
        if owns_pool:
            # End-of-trial hygiene: give the pool's recycled buffers back
            # to the OS instead of pinning up to the freelist cap between
            # trials. Gated like pool.shutdown(): a caller-supplied pool
            # signals deliberate cross-trial reuse, where warm buffers are
            # the point.
            from ray_shuffling_data_loader_tpu import native
            native.trim_freelist()

    return timeit.default_timer() - start


def shuffle_with_stats(
        filenames: Sequence[str],
        batch_consumer: BatchConsumer,
        num_epochs: int,
        num_reducers: int,
        num_trainers: int,
        max_concurrent_epochs: int = 2,
        seed: int = 0,
        num_workers: Optional[int] = None,
        utilization_sample_period: float = 5.0,
        map_transform: Optional[MapTransform] = None,
        file_cache: Union[FileTableCache, None, str] = "auto",
        reduce_transform: Optional[ReduceTransform] = None,
        task_retries: int = 0,
        max_inflight_bytes: Optional[int] = None,
        spill_dir: Optional[str] = None,
        on_bad_file: Optional[str] = None
) -> Tuple[stats_mod.TrialStats, List]:
    """Shuffle plus a concurrent memory-utilization sampler thread
    (reference: shuffle.py:21-55). Forwards the workload hooks
    (map/reduce transforms, file cache, retries) so the stats-collecting
    benchmark path can measure e.g. the decode-in-reducer ImageNet config."""
    store_stats: List = []
    done_event = stats_mod.start_store_stats_sampler(
        store_stats, sample_period_s=utilization_sample_period)
    try:
        trial_stats = shuffle(filenames, batch_consumer, num_epochs,
                              num_reducers, num_trainers,
                              max_concurrent_epochs, seed=seed,
                              num_workers=num_workers, collect_stats=True,
                              map_transform=map_transform,
                              file_cache=file_cache,
                              reduce_transform=reduce_transform,
                              task_retries=task_retries,
                              max_inflight_bytes=max_inflight_bytes,
                              spill_dir=spill_dir,
                              on_bad_file=on_bad_file)
    finally:
        done_event.set()
    return trial_stats, store_stats


def shuffle_no_stats(filenames: Sequence[str],
                     batch_consumer: BatchConsumer,
                     num_epochs: int,
                     num_reducers: int,
                     num_trainers: int,
                     max_concurrent_epochs: int = 2,
                     seed: int = 0,
                     num_workers: Optional[int] = None,
                     map_transform: Optional[MapTransform] = None,
                     file_cache: Union[FileTableCache, None, str] = "auto",
                     reduce_transform: Optional[ReduceTransform] = None,
                     task_retries: int = 0,
                     max_inflight_bytes: Optional[int] = None,
                     spill_dir: Optional[str] = None,
                     on_bad_file: Optional[str] = None
                     ) -> Tuple[float, List]:
    """Duration-only variant (reference: shuffle.py:58-76)."""
    duration = shuffle(filenames, batch_consumer, num_epochs, num_reducers,
                       num_trainers, max_concurrent_epochs, seed=seed,
                       num_workers=num_workers, collect_stats=False,
                       map_transform=map_transform, file_cache=file_cache,
                       reduce_transform=reduce_transform,
                       task_retries=task_retries,
                       max_inflight_bytes=max_inflight_bytes,
                       spill_dir=spill_dir, on_bad_file=on_bad_file)
    return duration, []


def run_shuffle_in_background(
        filenames: Sequence[str],
        batch_consumer: BatchConsumer,
        num_epochs: int,
        num_reducers: int,
        num_trainers: int,
        max_concurrent_epochs: int = 2,
        seed: int = 0,
        num_workers: Optional[int] = None,
        collect_stats: bool = False,
        start_epoch: int = 0,
        map_transform: Optional[MapTransform] = None,
        file_cache: Union[FileTableCache, None, str] = "auto",
        reduce_transform: Optional[ReduceTransform] = None,
        task_retries: int = 0,
        max_inflight_bytes: Optional[int] = None,
        spill_dir: Optional[str] = None,
        on_bad_file: Optional[str] = None,
        on_failure: Optional[Callable[[BaseException], None]] = None
        ) -> ex.TaskRef:
    """Launch the whole multi-epoch shuffle as one background task.

    Stands in for the reference driver's ``ray.remote(shuffle).remote(...)``
    (reference: dataset.py:110-118): the returned TaskRef is the
    ``shuffle_result`` handle the dataset joins after the last epoch.

    ``on_failure`` is invoked (once, from the driver thread) if the shuffle
    dies, BEFORE the error is stored in the returned ref — the dataset layer
    uses it to poison-pill trainer queues so blocked consumers fail fast
    instead of hanging (the reference has no equivalent: a dead Ray shuffle
    task leaves trainers blocked on the queue actor forever).
    """
    # A dedicated single-worker executor hosts the driver loop so it never
    # competes with map/reduce workers for a pool slot.
    driver_pool = ex.Executor(num_workers=1, thread_name_prefix="rsdl-driver")

    def _run():
        try:
            return shuffle(filenames, batch_consumer, num_epochs,
                           num_reducers, num_trainers, max_concurrent_epochs,
                           seed=seed, num_workers=num_workers,
                           collect_stats=collect_stats,
                           start_epoch=start_epoch,
                           map_transform=map_transform,
                           file_cache=file_cache,
                           reduce_transform=reduce_transform,
                           task_retries=task_retries,
                           max_inflight_bytes=max_inflight_bytes,
                           spill_dir=spill_dir, on_bad_file=on_bad_file)
        except BaseException as e:  # noqa: BLE001 - forwarded to consumers
            if on_failure is not None:
                try:
                    on_failure(e)
                except Exception:  # noqa: BLE001
                    logger.exception("shuffle on_failure hook itself failed")
            raise
        finally:
            driver_pool.shutdown(wait_for_tasks=False)

    return driver_pool.submit(_run)


def run_shuffle_epochs_in_background(
        epoch_specs,
        batch_consumer: BatchConsumer,
        num_reducers: int,
        num_trainers: int,
        max_concurrent_epochs: int = 2,
        seed: int = 0,
        num_workers: Optional[int] = None,
        file_cache: Union[FileTableCache, None, str] = "auto",
        task_retries: int = 0,
        max_inflight_bytes: Optional[int] = None,
        spill_dir: Optional[str] = None,
        on_bad_file: Optional[str] = None,
        epochs_hint: Optional[int] = None,
        on_epoch_done: Optional[Callable[[int], None]] = None,
        on_failure: Optional[Callable[[BaseException], None]] = None
        ) -> ex.TaskRef:
    """:func:`run_shuffle_in_background` for an epoch-spec schedule: the
    driver loop consumes ``epoch_specs`` (an iterable/iterator of
    :class:`plan.ir.EpochSpec` — a streaming window schedule, possibly
    unbounded) on a dedicated single-worker executor. Same ``on_failure``
    poison-pill contract as the static launcher."""
    driver_pool = ex.Executor(num_workers=1, thread_name_prefix="rsdl-driver")

    def _run():
        try:
            return shuffle_epochs(
                epoch_specs, batch_consumer, num_reducers, num_trainers,
                max_concurrent_epochs=max_concurrent_epochs, seed=seed,
                num_workers=num_workers, file_cache=file_cache,
                task_retries=task_retries,
                max_inflight_bytes=max_inflight_bytes, spill_dir=spill_dir,
                on_bad_file=on_bad_file, epochs_hint=epochs_hint,
                on_epoch_done=on_epoch_done)
        except BaseException as e:  # noqa: BLE001 - forwarded to consumers
            if on_failure is not None:
                try:
                    on_failure(e)
                except Exception:  # noqa: BLE001
                    logger.exception("shuffle on_failure hook itself failed")
            raise
        finally:
            driver_pool.shutdown(wait_for_tasks=False)

    return driver_pool.submit(_run)
