"""Process-pool data plane: worker subprocesses + shared-memory Arrow handoff.

The thread executor (executor.py) keeps the whole map/reduce hot path in
ONE Python process — pyarrow and the native kernels release the GIL, but
everything interpreter-bound (per-task Python bookkeeping, numpy fallback
arms, Parquet metadata churn) serializes on it. This module is the
multicore plane behind the
same ``Executor`` contract (``submit`` / ``submit_once`` / ``wait`` /
``TaskRef``):

- N worker **subprocesses** (``multiprocessing`` spawn, supervisor-style
  respawn-on-death like the PR 5 queue server) each run map/reduce tasks
  end to end.
- Handoff is **zero-copy Arrow over shared memory**: a worker writes its
  output table as an Arrow IPC file into a tmpfs segment dir (``/dev/shm``
  by default) and sends back only the path; the driver, other workers, and
  the spill tier ``pa.memory_map`` the very buffers the worker wrote —
  tables never cross a pickle.
- Decoded-table segments double as the cross-epoch file cache (the
  process-backend analog of ``shuffle.FileTableCache``), budgeted by the
  ``executor_shm_bytes`` policy knob and charged to the process-wide
  buffer ledger (``native.buffer_ledger()``) like every other in-flight
  byte.
- Worker death is recovered from **lineage**: map/reduce payloads are pure
  functions of ``(seed, epoch, task)`` plus file paths, so the dispatcher
  resubmits the dead worker's task to a surviving worker (recorded as a
  lineage recompute in ``stats.fault_stats()``) and respawns the worker
  with bounded backoff.

Workers inherit the environment, so ``RSDL_CHAOS_SPEC`` chaos,
``RSDL_TELEMETRY`` and ``RSDL_TRACE_DIR`` all apply per worker: each
worker records its own ``map_read`` / ``reduce_gather`` spans and dumps
its flight recorder at exit, which is what lets ``tools/rsdl_trace.py``
merge a critical path spanning the driver plus every pool worker.
"""

from __future__ import annotations

import collections
import concurrent.futures as cf
import multiprocessing as mp
import os
import pickle
import signal
import sys
import tempfile
import threading
import timeit
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ray_shuffling_data_loader_tpu import executor as ex
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import policy as rt_policy
from ray_shuffling_data_loader_tpu.runtime import retry as rt_retry
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)

# Worker respawn budget/backoff — supervisor-grade, not call-retry-grade
# (same reasoning as runtime/supervisor.py): a preempted host may lose
# several workers in one run.
rt_policy.register_defaults("procpool", retry_max_attempts=4,
                            retry_initial_backoff_s=0.1,
                            retry_max_backoff_s=2.0)

#: Per-task resubmission budget after a worker death (the task itself is a
#: pure lineage function, so a second execution is a recompute, not a
#: replay hazard).
_TASK_RESUBMITS = 2


class WorkerDied(RuntimeError):
    """A pool worker died while running the task and the resubmission
    budget is exhausted (or the task is one-shot)."""


class RemoteTaskError(RuntimeError):
    """A task raised in a worker and its exception could not be pickled
    back verbatim; carries the remote type name and traceback text."""


def shm_base_dir(override: Optional[str] = None) -> str:
    """Segment root: ``executor_shm_dir`` policy, else ``/dev/shm`` when
    writable (true shared memory), else the system temp dir (degrades to
    page-cache-backed mmap — still correct, still no pickling)."""
    configured = rt_policy.resolve("executor", "executor_shm_dir",
                                   override=override)
    if configured:
        return configured
    if os.path.isdir("/dev/shm") and os.access("/dev/shm", os.W_OK):
        return "/dev/shm"
    return tempfile.gettempdir()


def shm_available(override: Optional[str] = None) -> bool:
    base = shm_base_dir(override)
    return os.path.isdir(base) and os.access(base, os.W_OK)


def default_shm_bytes(base_dir: str) -> int:
    """Auto segment-cache budget: half the free bytes of the segment
    filesystem (decoded tables are the dominant resident)."""
    import shutil as _shutil
    try:
        return _shutil.disk_usage(base_dir).free // 2
    except OSError:
        return 1 << 30


def picklable(obj: Any) -> bool:
    if obj is None:
        return True
    try:
        pickle.dumps(obj)
        return True
    except Exception:  # noqa: BLE001 - any failure means "can't ship it"
        return False


def resolve_backend(override: Optional[str] = None,
                    num_workers: Optional[int] = None,
                    transforms: Sequence[Any] = ()) -> str:
    """``thread`` | ``process`` for a driver that owns its pool.

    ``auto`` picks the process backend when it can actually help and
    actually work: more than one host core, a writable shared-memory dir,
    every workload hook picklable (they must cross to the workers), and no
    *programmatic* chaos injector active (an env-spec chaos reproduces in
    the workers by construction; an ``install()``-ed one lives only in the
    driver process and would silently stop firing).
    """
    backend = rt_policy.resolve("executor", "executor_backend",
                                override=override)
    if backend not in ("thread", "process", "auto"):
        raise ValueError(
            f"executor_backend must be thread|process|auto, got {backend!r}")
    if backend == "auto":
        from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
        cores = os.cpu_count() or 1
        workers = num_workers if num_workers else cores
        programmatic_chaos = (
            rt_faults.active()
            and not any(os.environ.get(name, "").strip()
                        for name in rt_faults._SPEC_ENVS))
        if (cores > 1 and workers > 1 and shm_available()
                and not programmatic_chaos):
            backend = "process"
        else:
            backend = "thread"
    if backend == "process" and not shm_available():
        logger.warning("executor_backend=process but no writable shm/temp "
                       "dir; falling back to the thread backend")
        backend = "thread"
    if backend == "process" and not all(picklable(t) for t in transforms):
        # Applies to EXPLICIT process selection too: a closure transform
        # cannot cross to the workers, and failing the whole shuffle over
        # an env var would be worse than the thread pool it replaces.
        logger.warning("executor_backend=process but a map/reduce "
                       "transform is not picklable; falling back to the "
                       "thread backend")
        backend = "thread"
    return backend


# ---------------------------------------------------------------------------
# Segment I/O (shared by driver and workers)
# ---------------------------------------------------------------------------


def write_table_segment(table, path: str) -> int:
    """Write ``table`` as an Arrow IPC file at ``path`` (tmp + atomic
    rename so a dying writer never leaves a torn segment under the final
    name). Returns the on-disk byte size."""
    import pyarrow as pa
    tmp = f"{path}.{os.getpid()}.tmp"
    with pa.OSFile(tmp, "wb") as sink:
        with pa.ipc.new_file(sink, table.schema) as writer:
            writer.write_table(table)
    os.replace(tmp, path)
    return os.stat(path).st_size


def open_table_segment(path: str):
    """Memory-map an IPC segment back as a table — zero-copy: the Arrow
    buffers ARE the shm pages the writer produced."""
    import pyarrow as pa
    with pa.memory_map(path) as source:
        return pa.ipc.open_file(source).read_all()


def write_buffer_segment(buf, path: str) -> int:
    """Write an already-serialized buffer (e.g. an Arrow IPC stream from
    ``pa.BufferOutputStream``) at ``path`` with the same tmp + atomic
    rename discipline as :func:`write_table_segment`. Returns the byte
    size. The queue serving plane uses this for shm-handle delivery: the
    one serialization the v2 wire already paid becomes the segment the
    consumer mmaps, and no byte ever rides the socket."""
    import pyarrow as pa
    tmp = f"{path}.{os.getpid()}.tmp"
    with pa.OSFile(tmp, "wb") as sink:
        sink.write(buf)
    os.replace(tmp, path)
    return os.stat(path).st_size


def read_segment_buffer(path: str):
    """Memory-map a segment back as one zero-copy ``pa.Buffer`` (the raw
    bytes, not a decoded table): CRC verification and Arrow IPC decode
    both read straight off the mapped pages."""
    import pyarrow as pa
    with pa.memory_map(path) as source:
        return source.read_buffer()


def pin_segment(nbytes: int) -> int:
    """Charge a segment's bytes to the process-wide buffer ledger
    (``native.buffer_ledger()``) on behalf of an EXTERNAL consumer — the
    queue server pins each unacked handle frame's segment so the budget
    machinery sees replay-held shm like any other in-flight byte.
    Returns the ledger id for :func:`release_segment`."""
    from ray_shuffling_data_loader_tpu import native
    return native.buffer_ledger().register(nbytes)


def release_segment(ledger_id: Optional[int], path: Optional[str] = None,
                    unlink: bool = False) -> None:
    """Release a :func:`pin_segment` lease (idempotent) and optionally
    unlink the segment file — consumers that already mmap'd it keep
    their mapping (POSIX unlink semantics), so acked frames free the
    name immediately without racing a slow reader."""
    from ray_shuffling_data_loader_tpu import native
    if ledger_id is not None:
        try:
            native.buffer_ledger().decref(ledger_id)
        except KeyError:
            pass
    if unlink and path:
        _unlink_quiet(path)


def write_index_segment(path: str, offsets: np.ndarray,
                        flat: np.ndarray) -> int:
    """Partition-plan segment: int64 header ``[num_reducers, num_rows]``
    then offsets then the flat row-index array."""
    header = np.array([len(offsets) - 1, len(flat)], dtype=np.int64)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        f.write(header.tobytes())
        f.write(np.ascontiguousarray(offsets, dtype=np.int64).tobytes())
        f.write(np.ascontiguousarray(flat, dtype=np.int64).tobytes())
    os.replace(tmp, path)
    return os.stat(path).st_size


def read_index_segment(path: str) -> "tuple[np.ndarray, np.ndarray]":
    """``(offsets, flat)`` views of an index segment (mmap-backed)."""
    raw = np.memmap(path, dtype=np.int64, mode="r")
    num_reducers, num_rows = int(raw[0]), int(raw[1])
    offsets = raw[2:3 + num_reducers]
    flat = raw[3 + num_reducers:3 + num_reducers + num_rows]
    return offsets, flat


def _unlink_quiet(path: str) -> None:
    try:
        os.unlink(path)
    except OSError:
        pass


# ---------------------------------------------------------------------------
# Worker process
# ---------------------------------------------------------------------------

#: Worker-local mmap cache of decoded-table segments: every reducer of
#: every epoch gathers from the same file segments, so re-opening per task
#: would re-pay Arrow IPC footer parsing num_reducers times per epoch.
_seg_table_cache: Dict[str, Any] = {}


def _cached_segment_table(path: str):
    table = _seg_table_cache.get(path)
    if table is None:
        table = _seg_table_cache[path] = open_table_segment(path)
    return table


def _load_blob(blob: Optional[bytes]):
    return None if blob is None else pickle.loads(blob)


def _worker_task_map(payload: dict) -> dict:
    """Map task body: decode (or mmap the cached segment), optionally
    publish the decoded table as a new cache segment, run the fused
    partition plan, and write the plan as an index segment. Fault/retry/
    quarantine semantics mirror ``shuffle.shuffle_map`` exactly."""
    import importlib
    import pyarrow as pa
    sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
    from ray_shuffling_data_loader_tpu.ops import partition as ops_p
    from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults

    filename = payload["filename"]
    epoch, file_index = payload["epoch"], payload["file_index"]
    seed = payload["seed"]
    rt_telemetry.set_trace_seed(seed)
    start = timeit.default_timer()
    # The worker's own recorder gets the real map_read span (the driver
    # only hears the duration back, through observe_stage). It covers the
    # read and its retries, or its quarantine; not the partition plan.
    read_span = rt_telemetry.span_begin("map_read", epoch=epoch,
                                        task=file_index)
    try:
        table = None
        table_seg = payload.get("table_seg")
        wrote_table_bytes = 0
        cached = False
        if table_seg is not None:
            try:
                table = _cached_segment_table(table_seg)
                cached = True
            except (OSError, pa.ArrowInvalid) as e:
                logger.warning("table segment %s unreadable (%s); re-decoding",
                               table_seg, e)
                _seg_table_cache.pop(table_seg, None)
                table = None
                table_seg = None
        grouped = False
        grouped_offsets = None
        if table is None:
            import functools
            read_retry = rt_retry.RetryPolicy.for_component(
                "map_read", retryable=sh._transient_read_retryable)
            map_transform = _load_blob(payload.get("map_transform"))
            streamed = None
            tried_fused = False
            # Streaming fast path — epoch-scoped segments only: a cross-epoch
            # cache grant must publish the DECODED table (the grouped layout
            # depends on (seed, epoch), so it cannot be reused next epoch).
            if not payload.get("cache_grant") and sh._fused_pipeline_enabled():
                tried_fused = True
                rt_faults.inject("map_read", epoch=epoch, task=file_index)
                fused_fn = functools.partial(
                    sh._fused_stream_columns, filename,
                    payload["num_reducers"], seed, epoch, file_index,
                    map_transform)
                try:
                    streamed = read_retry.call(
                        fused_fn, describe=f"stream {filename}")
                except (OSError, pa.ArrowInvalid) as e:
                    if payload.get("on_bad_file") != "skip":
                        raise
                    return {"quarantined": rt_faults.QuarantinedFile(
                        filename=filename, epoch=epoch, file_index=file_index,
                        error=f"{type(e).__name__}: {e}")}
            if streamed is not None:
                out_cols, grouped_offsets, names = streamed
                # The segment IS the grouped layout: reducer r's rows are the
                # contiguous slice [offsets[r], offsets[r+1]) in original row
                # order, so the reduce stage slices instead of gathering —
                # bit-identical rows either way (same stable order).
                table = pa.table({name: out_cols[name] for name in names})
                grouped = True
            else:
                try:
                    table = sh._read_map_table(filename, epoch, file_index,
                                               read_retry,
                                               inject=not tried_fused)
                except (OSError, pa.ArrowInvalid) as e:
                    if payload.get("on_bad_file") != "skip":
                        raise
                    return {"quarantined": rt_faults.QuarantinedFile(
                        filename=filename, epoch=epoch, file_index=file_index,
                        error=f"{type(e).__name__}: {e}")}
                if map_transform is not None:
                    table = map_transform(table)
                # Single-chunk columns => zero-copy numpy views for every
                # reducer that maps this segment (same invariant as the
                # thread-mode cache).
                table = table.combine_chunks()
            # The reducers gather from the SEGMENT, so the decoded table must
            # always be published — either into the cross-epoch cache slot the
            # driver granted, or into an epoch-scoped segment the driver
            # unlinks when the epoch's reduces finish. A write failure is a
            # task failure (there is nothing for the reduce stage to read).
            write_seg = payload.get("write_table_seg") or \
                f"{payload['idx_seg']}.table.arrow"
            wrote_table_bytes = write_table_segment(table, write_seg)
            cached = bool(payload.get("cache_grant")) and \
                write_seg == payload.get("write_table_seg")
            if cached:
                _seg_table_cache[write_seg] = table
            table_seg = write_seg
    finally:
        rt_telemetry.span_end(read_span)
    end_read = timeit.default_timer()
    if grouped:
        # The stream already placed every row; the index segment carries
        # only the region offsets (empty flat array).
        idx_bytes = write_index_segment(payload["idx_seg"], grouped_offsets,
                                        np.empty(0, dtype=np.int64))
    else:
        flat, offsets = ops_p.plan_partition_flat(
            table.num_rows, payload["num_reducers"], seed, epoch,
            file_index, nthreads=payload.get("plan_threads") or 1)
        idx_bytes = write_index_segment(payload["idx_seg"], offsets, flat)
    return {
        "num_rows": table.num_rows,
        "table_seg": table_seg,
        "cached": cached,
        "grouped": grouped,
        "wrote_table_bytes": wrote_table_bytes,
        "idx_seg": payload["idx_seg"],
        "idx_bytes": idx_bytes,
        "read_s": end_read - start,
        "dur_s": timeit.default_timer() - start,
    }


def _worker_task_reduce(payload: dict) -> dict:
    """Reduce task body: gather this reducer's rows from every map
    segment with the SAME fused kernel path as the thread backend
    (``shuffle.shuffle_reduce`` over lazy chunks), then publish the output
    as a fresh segment. Bit-identity with the thread backend is
    structural: same plan segments, same permutation RNG, same gather."""
    import importlib
    sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
    from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults

    reduce_index = payload["reduce_index"]
    epoch, seed = payload["epoch"], payload["seed"]
    rt_telemetry.set_trace_seed(seed)
    start = timeit.default_timer()
    reduce_transform = _load_blob(payload.get("reduce_transform"))

    def _gather_and_shuffle():
        with rt_telemetry.span("reduce_gather", epoch=epoch,
                               task=reduce_index):
            rt_faults.inject("reduce_gather", epoch=epoch,
                             task=reduce_index)
            chunks = []
            for source in payload["sources"]:
                table_seg, idx_seg, cacheable = source[:3]
                grouped = len(source) > 3 and bool(source[3])
                # Epoch-scoped segments are unlinked when the epoch
                # drains; caching them in the worker would pin the pages
                # past that, so only cross-epoch cache segments persist.
                table = (_cached_segment_table(table_seg) if cacheable
                         else open_table_segment(table_seg))
                offsets, flat = read_index_segment(idx_seg)
                if grouped:
                    # Streaming-pipeline segment: rows already grouped by
                    # reducer in original row order — a zero-copy slice
                    # replaces the gather, bit-identically.
                    lo = int(offsets[reduce_index])
                    hi = int(offsets[reduce_index + 1])
                    chunks.append(table.slice(lo, hi - lo))
                else:
                    idx = np.asarray(
                        flat[offsets[reduce_index]:
                             offsets[reduce_index + 1]])
                    chunks.append(sh.MapShard(table, [idx])[0])
            return sh.shuffle_reduce(reduce_index, seed, epoch, chunks,
                                     None, reduce_transform,
                                     payload.get("gather_threads"))

    retry = rt_retry.RetryPolicy.for_component("reduce")
    shuffled = retry.call(_gather_and_shuffle,
                          describe=f"reduce e{epoch} r{reduce_index}")
    out_seg = payload["out_seg"]
    nbytes = write_table_segment(shuffled, out_seg)
    return {
        "out_seg": out_seg,
        "num_rows": shuffled.num_rows,
        "nbytes": nbytes,
        "dur_s": timeit.default_timer() - start,
    }


def _worker_task_call(payload: dict) -> Any:
    fn, args, kwargs = pickle.loads(payload["blob"])
    return fn(*args, **kwargs)


def _worker_task_ping(payload: dict) -> dict:
    return {"pid": os.getpid(), "worker_index": payload.get("worker_index")}


def _run_worker_task(kind: str, payload: dict) -> Any:
    """Dispatch one task body; speculative backup attempts (plan
    scheduler first-completion-wins duplicates, ``payload["attempt"]``)
    run under ``telemetry.speculative()`` so their recorder events carry
    the ``spec`` attr and never double-count in trace merge or
    attribution."""
    handler = _TASK_HANDLERS[kind]
    attempt = payload.get("attempt", 0) if isinstance(payload, dict) else 0
    if attempt:
        with rt_telemetry.speculative(attempt):
            return handler(payload)
    return handler(payload)


_TASK_HANDLERS: Dict[str, Callable[[dict], Any]] = {
    "map": _worker_task_map,
    "reduce": _worker_task_reduce,
    "call": _worker_task_call,
    "ping": _worker_task_ping,
}


def _worker_main(conn, worker_index: int) -> None:
    """Worker loop: one task at a time off the duplex pipe.

    SIGTERM converts to SystemExit (same pattern as the supervised queue
    server's ``_serve_main``) so atexit hooks — notably the
    ``RSDL_TRACE_DIR`` flight-recorder dump — run even when the driver
    tears the pool down with terminate().
    """
    import signal as _signal

    def _on_sigterm(signum, frame):
        raise SystemExit(0)

    try:
        _signal.signal(_signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):  # non-main thread / exotic platform
        pass
    threading.current_thread().name = f"rsdl-proc-worker-{worker_index}"
    # The driver owns the chip; a worker does host CPU work only and must
    # never ask for it, whatever the inherited environment says. jax reads
    # JAX_PLATFORMS when it is imported, and the spawn re-import of the
    # driver's __main__ may already have imported it.
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax_mod = sys.modules.get("jax")
    if jax_mod is not None:
        jax_mod.config.update("jax_platforms", "cpu")
    # Ops-plane federation (inherited through the spawn env like
    # RSDL_CHAOS_SPEC): the worker writes its per-pid metrics shard
    # under RSDL_TELEMETRY_DIR so the driver's merged exposition counts
    # the processes doing the work, and answers the incident capture's
    # SIGUSR1 with a flight-recorder dump into RSDL_TRACE_DIR.
    rt_telemetry.install_signal_dump()
    # Born with SIGUSR1 blocked (see _spawn_worker); deliverable now.
    _signal.pthread_sigmask(_signal.SIG_UNBLOCK, {_signal.SIGUSR1})
    rt_metrics.maybe_start_shard_writer()
    tasks_done = rt_metrics.counter(
        "rsdl_worker_tasks_total",
        "tasks completed inside pool worker processes",
        worker=str(worker_index))
    # Service loop, not a retry: exits on pipe EOF (driver gone) or the
    # explicit shutdown sentinel. rsdl-lint: disable=unbounded-retry
    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):
            break
        if msg is None:
            break
        task_id, kind, payload = msg
        try:
            result = _run_worker_task(kind, payload)
            reply = (task_id, True, result)
            tasks_done.inc()
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:  # noqa: BLE001 - shipped to the driver
            import traceback as _tb
            try:
                # Round trip, not just dumps: an exception whose
                # __init__ takes other arguments than its .args pickles
                # fine and then raises in the driver's recv(), which
                # would kill the dispatcher and leave the task's future
                # unresolved forever.
                pickle.loads(pickle.dumps(e))
                err: Any = e
            except Exception:  # noqa: BLE001 - unpicklable exception
                err = RemoteTaskError(
                    f"{type(e).__name__}: {e}\n{_tb.format_exc()}")
            reply = (task_id, False, err)
        try:
            conn.send(reply)
        except (BrokenPipeError, OSError):
            break


# ---------------------------------------------------------------------------
# Driver side
# ---------------------------------------------------------------------------


class ProcTaskRef(ex.TaskRef):
    """TaskRef whose ``result()`` optionally applies a driver-side
    transform to the worker's raw reply (e.g. mmap a reduce-output
    segment into a table) — applied once, cached, thread-safe."""

    __slots__ = ("_transform", "_final", "_final_error", "_final_lock",
                 "_finalized")

    def __init__(self, future: cf.Future, transform=None):
        super().__init__(future)
        self._transform = transform
        self._final = None
        self._final_error: Optional[BaseException] = None
        # Runs the caller-supplied transform while held (exactly-once
        # with side effects: ledger decrefs, segment unlinks), so
        # locksan records edges into whatever locks that opaque
        # callable takes — edges the static pass cannot resolve. Safe:
        # this is a per-future leaf lock no transform can reach back
        # into, so it cannot close a cycle.
        # rsdl-lint: disable=inconsistent-lock-order
        self._final_lock = threading.Lock()
        self._finalized = False

    def result(self, timeout: Optional[float] = None) -> Any:
        raw = self._future.result(timeout)
        if self._transform is None:
            return raw
        with self._final_lock:
            if not self._finalized:
                try:
                    self._final = self._transform(raw)
                except BaseException as e:  # noqa: BLE001 - replayed below
                    self._final_error = e
                self._finalized = True
            if self._final_error is not None:
                raise self._final_error
            return self._final


class _Task:
    __slots__ = ("id", "kind", "payload", "future", "retryable", "attempts",
                 "affinity")

    def __init__(self, task_id: int, kind: str, payload: dict,
                 retryable: bool, affinity: Optional[int]):
        self.id = task_id
        self.kind = kind
        self.payload = payload
        self.future: cf.Future = cf.Future()
        self.retryable = retryable
        self.attempts = 0
        self.affinity = affinity


class _Worker:
    __slots__ = ("proc", "conn", "index", "restarts")

    def __init__(self, proc, conn, index: int):
        self.proc = proc
        self.conn = conn
        self.index = index
        self.restarts = 0


class ProcessPoolExecutor:
    """Per-host process-pool executor (the multicore data plane).

    Satisfies the ``executor.Executor`` contract — ``submit`` /
    ``submit_once`` return :class:`ex.TaskRef`-compatible refs that
    ``executor.wait`` / ``executor.get`` accept unchanged — plus the
    shuffle-specific ``submit_kind`` used by the process-mode epoch path
    (procpool.process_epoch). Generic ``submit`` pickles ``(fn, args,
    kwargs)``, so only module-level callables travel; the shuffle path
    never ships closures, only segment paths and lineage integers.
    """

    backend = "process"

    def __init__(self, num_workers: Optional[int] = None,
                 shm_dir: Optional[str] = None,
                 shm_bytes: Optional[int] = None,
                 name: str = "rsdl-procpool",
                 task_retries: int = 0):
        if num_workers is None:
            num_workers = rt_policy.resolve("executor", "executor_workers")
        if not num_workers:
            num_workers = os.cpu_count() or 1
        self._num_workers = max(1, int(num_workers))
        self._name = name
        base = shm_base_dir(shm_dir)
        os.makedirs(base, exist_ok=True)
        self.segment_dir = tempfile.mkdtemp(prefix="rsdl-pool-", dir=base)
        budget = rt_policy.resolve("executor", "executor_shm_bytes",
                                   override=shm_bytes)
        self.shm_bytes = budget if budget else default_shm_bytes(base)
        # task_retries parity with the thread executor: pure tasks may be
        # re-run after a worker death; the budget below is per task.
        self._task_resubmits = max(_TASK_RESUBMITS, task_retries)
        self._ctx = mp.get_context("spawn")
        self._lock = threading.Condition()
        self._next_task_id = 1
        self._global_q: "collections.deque[_Task]" = collections.deque()
        self._affinity_q: List["collections.deque[_Task]"] = [
            collections.deque() for _ in range(self._num_workers)]
        self._shutdown = False
        self._wait_for_tasks = True
        self._alive_dispatchers = self._num_workers
        restart_policy = rt_retry.RetryPolicy.for_component("procpool")
        self._max_restarts = restart_policy.max_attempts
        self._backoffs = restart_policy.backoffs()
        # Segment-cache registry (driver-authoritative): filename ->
        # (segment path, bytes). Charged to the buffer ledger below.
        self._table_segs: Dict[str, "tuple[str, int]"] = {}
        self._table_seg_inflight: set = set()
        self._table_seg_grants = 0
        self._table_seg_bytes = 0
        self._cache_full = False
        self._ledger_ids: List[int] = []
        rt_metrics.gauge("rsdl_executor_workers",
                         "pool width by pool name",
                         pool=name).set(self._num_workers)
        self._tasks_submitted = rt_metrics.counter(
            "rsdl_executor_tasks_total", "tasks submitted by pool name",
            pool=name)
        self._worker_restarts = rt_metrics.counter(
            "rsdl_pool_worker_restarts_total",
            "pool worker processes respawned after death", pool=name)
        self._workers: List[_Worker] = [
            self._spawn_worker(i) for i in range(self._num_workers)]
        self._dispatchers = [
            threading.Thread(target=self._dispatch_loop, args=(i,),
                             name=f"{name}-dispatch-{i}", daemon=True)
            for i in range(self._num_workers)]
        for t in self._dispatchers:
            t.start()
        ex.note_worker_pool("process", self._num_workers,
                            self.worker_pids())
        self._publish_worker_pids()

    # -- Executor contract ---------------------------------------------

    @property
    def num_workers(self) -> int:
        return self._num_workers

    def worker_pids(self) -> List[int]:
        return [w.proc.pid for w in self._workers
                if w.proc is not None and w.proc.pid is not None]

    def _publish_worker_pids(self) -> None:
        """Per-pid pool membership for the ops plane: rsdl_top's
        per-process view marks these pids as pool workers, and the
        incident capture signals them for trace dumps."""
        for pid in self.worker_pids():
            rt_metrics.gauge("rsdl_executor_worker_up",
                             "1 while the pid is a live pool worker",
                             pool=self._name, pid=str(pid)).set(1)

    def submit(self, fn: Callable, *args, **kwargs) -> ProcTaskRef:
        blob = pickle.dumps((fn, args, kwargs))
        return self.submit_kind("call", {"blob": blob}, retryable=True)

    def submit_once(self, fn: Callable, *args, **kwargs) -> ProcTaskRef:
        blob = pickle.dumps((fn, args, kwargs))
        return self.submit_kind("call", {"blob": blob}, retryable=False)

    def map(self, fn: Callable, items: Sequence) -> List[ProcTaskRef]:
        return [self.submit(fn, item) for item in items]

    def submit_kind(self, kind: str, payload: dict,
                    affinity: Optional[int] = None,
                    transform=None, retryable: bool = True) -> ProcTaskRef:
        with self._lock:
            if self._shutdown:
                raise RuntimeError("executor is shut down")
            task = _Task(self._next_task_id, kind, payload, retryable,
                         affinity)
            self._next_task_id += 1
            if affinity is not None:
                self._affinity_q[affinity % self._num_workers].append(task)
            else:
                self._global_q.append(task)
            self._lock.notify_all()
        self._tasks_submitted.inc()
        return ProcTaskRef(task.future, transform)

    def shutdown(self, wait_for_tasks: bool = True) -> None:
        with self._lock:
            if self._shutdown:
                return
            self._shutdown = True
            self._wait_for_tasks = wait_for_tasks
            self._lock.notify_all()
        if not wait_for_tasks:
            for worker in self._workers:
                if worker.proc is not None and worker.proc.is_alive():
                    worker.proc.terminate()
        for t in self._dispatchers:
            t.join(timeout=60.0)
        for worker in self._workers:
            self._stop_worker(worker)
        self._release_segments()

    def __enter__(self) -> "ProcessPoolExecutor":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- Segment cache (decoded tables, cross-epoch) -------------------

    @property
    def bytes_cached(self) -> int:
        """Resident bytes of the decoded-table segment cache — the budget
        machinery (spill.make_budget_state) discounts cache growth from
        the transient-byte ledger, same duck-typed surface as
        shuffle.FileTableCache."""
        with self._lock:
            return self._table_seg_bytes

    def segment_path(self, stem: str) -> str:
        return os.path.join(self.segment_dir, stem)

    def cached_table_seg(self, filename: str) -> Optional[str]:
        with self._lock:
            entry = self._table_segs.get(filename)
            return entry[0] if entry else None

    def plan_table_seg_write(self, filename: str) -> Optional[str]:
        """Decide (driver-authoritative, so concurrent epochs cannot race)
        whether this map task should publish the decoded table as a cache
        segment; returns the target path or None. The path is numbered by
        grant, not by the file's index in its epoch: a streaming run's
        windows hold different files under the same indices, and the
        cache outlives the epoch."""
        with self._lock:
            if (self._cache_full or filename in self._table_segs
                    or filename in self._table_seg_inflight):
                return None
            self._table_seg_inflight.add(filename)
            grant = self._table_seg_grants
            self._table_seg_grants += 1
        return self.segment_path(f"table_f{grant}.arrow")

    def note_table_seg(self, filename: str, path: Optional[str],
                       nbytes: int) -> None:
        """Record a map task's cache-segment outcome and charge the
        ledger; past the byte budget the cache stops growing (files keep
        re-decoding — same degradation as DiskTableCache)."""
        from ray_shuffling_data_loader_tpu import native
        with self._lock:
            self._table_seg_inflight.discard(filename)
            if not path or not nbytes:
                return
            if filename in self._table_segs:
                return
            self._table_segs[filename] = (path, nbytes)
            self._table_seg_bytes += nbytes
            if self._table_seg_bytes >= self.shm_bytes:
                self._cache_full = True
        # Register outside the pool condition (the ledger has its own
        # lock), but the id list is shared with _release_segments on
        # the shutdown path, so the append itself goes back under it.
        buf_id = native.buffer_ledger().register(nbytes)
        with self._lock:
            self._ledger_ids.append(buf_id)

    def _release_segments(self) -> None:
        from ray_shuffling_data_loader_tpu import native
        import shutil as _shutil
        ledger = native.buffer_ledger()
        with self._lock:
            ledger_ids, self._ledger_ids = self._ledger_ids, []
        for buf_id in ledger_ids:
            try:
                ledger.decref(buf_id)
            except KeyError:
                pass
        _shutil.rmtree(self.segment_dir, ignore_errors=True)

    # -- Worker lifecycle ----------------------------------------------

    def _spawn_worker(self, index: int) -> _Worker:
        parent_conn, child_conn = self._ctx.Pipe(duplex=True)
        proc = self._ctx.Process(
            target=_worker_main, args=(child_conn, index),
            name=f"{self._name}-worker-{index}", daemon=True)
        # A spawned worker imports for seconds before _worker_main can
        # install its SIGUSR1 dump handler, and its pid is published at
        # once — the ops plane's incident capture signals every pool pid,
        # and until the handler is in, SIGUSR1's default action kills the
        # worker. The signal mask survives fork+exec: start the child
        # with SIGUSR1 blocked, so one sent during boot stays pending
        # until the worker unblocks it behind its handler.
        old_mask = signal.pthread_sigmask(signal.SIG_BLOCK,
                                          {signal.SIGUSR1})
        try:
            proc.start()
        finally:
            signal.pthread_sigmask(signal.SIG_SETMASK, old_mask)
        child_conn.close()
        return _Worker(proc, parent_conn, index)

    def _stop_worker(self, worker: _Worker, timeout_s: float = 5.0) -> None:
        if worker.proc is None:
            return
        try:
            if worker.proc.is_alive():
                try:
                    worker.conn.send(None)
                except (BrokenPipeError, OSError):
                    pass
                worker.proc.join(timeout=timeout_s)
            if worker.proc.is_alive():
                worker.proc.terminate()
                worker.proc.join(timeout=timeout_s)
            if worker.proc.is_alive():
                worker.proc.kill()
                worker.proc.join(timeout=timeout_s)
        finally:
            try:
                worker.conn.close()
            except OSError:
                pass

    def _next_task(self, index: int) -> Optional[_Task]:
        """Own affinity queue first (segment warmth), then the global
        queue, then steal from the longest sibling queue — affinity is a
        hint, so stealing is always safe."""
        with self._lock:
            # Condition-wait loop, not a retry: exits on shutdown, and
            # every pass either pops work or blocks on the condition.
            # rsdl-lint: disable=unbounded-retry
            while True:
                queue = self._affinity_q[index]
                if not queue and self._global_q:
                    queue = self._global_q
                if not queue:
                    siblings = [q for q in self._affinity_q if q]
                    if siblings:
                        queue = max(siblings, key=len)
                if queue:
                    return queue.popleft()
                if self._shutdown:
                    return None
                self._lock.wait(timeout=0.2)

    def _complete(self, task: _Task, ok: bool, result: Any) -> None:
        try:
            if ok:
                task.future.set_result(result)
            else:
                task.future.set_exception(result)
        except cf.InvalidStateError:
            pass  # cancelled ref

    def _handle_worker_death(self, index: int, task: Optional[_Task]
                             ) -> bool:
        """Respawn the dead worker (bounded backoff) and resubmit the
        in-flight task from lineage. Returns False when the respawn
        budget is exhausted (the dispatcher slot retires)."""
        from ray_shuffling_data_loader_tpu import stats as stats_mod
        worker = self._workers[index]
        exitcode = worker.proc.exitcode if worker.proc else None
        rt_telemetry.record("pool_worker_crash", rc=exitcode, worker=index)
        self._worker_restarts.inc()
        if task is not None:
            task.attempts += 1
            if task.retryable and task.attempts <= self._task_resubmits:
                logger.warning(
                    "%s: worker %d died (rc=%s) running %s task %d; "
                    "resubmitting from lineage (attempt %d)", self._name,
                    index, exitcode, task.kind, task.id, task.attempts)
                stats_mod.fault_stats().record_recompute("lineage", 0.0)
                with self._lock:
                    self._global_q.appendleft(task)
                    self._lock.notify_all()
            else:
                self._complete(task, False, WorkerDied(
                    f"pool worker {index} died (exitcode {exitcode}) "
                    f"running {task.kind} task {task.id}"))
        worker.restarts += 1
        if worker.restarts >= self._max_restarts:
            logger.error(
                "%s: worker %d restart budget (%d) exhausted; retiring "
                "the slot", self._name, index, self._max_restarts)
            return False
        with self._lock:
            # The backoff generator is shared by every dispatcher slot;
            # generators are not re-entrant, so draw under the lock.
            pause = next(self._backoffs)
        logger.error("%s: worker %d died (rc=%s); respawning in %.2fs "
                     "(%d/%d)", self._name, index, exitcode,
                     pause, worker.restarts, self._max_restarts - 1)
        with self._lock:
            if self._shutdown:
                return False
        import time as _time
        _time.sleep(pause)
        try:
            worker.conn.close()
        except OSError:
            pass
        dead_pid = worker.proc.pid if worker.proc is not None else None
        replacement = self._spawn_worker(index)
        replacement.restarts = worker.restarts
        self._workers[index] = replacement
        ex.note_worker_pool("process", self._num_workers,
                            self.worker_pids())
        if dead_pid is not None:
            rt_metrics.gauge("rsdl_executor_worker_up",
                             "1 while the pid is a live pool worker",
                             pool=self._name, pid=str(dead_pid)).set(0)
        self._publish_worker_pids()
        return True

    def _dispatch_loop(self, index: int) -> None:
        try:
            # Service loop: exits via the shutdown sentinel from
            # _next_task or a retired respawn budget — each death path is
            # itself bounded. rsdl-lint: disable=unbounded-retry
            while True:
                task = self._next_task(index)
                if task is None:
                    return
                if task.future.cancelled():
                    continue
                worker = self._workers[index]
                try:
                    worker.conn.send((task.id, task.kind, task.payload))
                    reply = worker.conn.recv()
                except (EOFError, OSError):
                    with self._lock:
                        dying = self._shutdown and not self._wait_for_tasks
                    if dying:
                        self._complete(task, False, WorkerDied(
                            "pool shut down while the task was in flight"))
                        return
                    if not self._handle_worker_death(index, task):
                        return
                    continue
                task_id, ok, result = reply
                assert task_id == task.id, (task_id, task.id)
                self._complete(task, ok, result)
        finally:
            self._retire_dispatcher(index)

    def _retire_dispatcher(self, index: int) -> None:
        with self._lock:
            self._alive_dispatchers -= 1
            # Orphaned affinity work must not starve: spill it to the
            # global queue for surviving slots.
            while self._affinity_q[index]:
                self._global_q.append(self._affinity_q[index].popleft())
            last = self._alive_dispatchers == 0
            self._lock.notify_all()
        if last:
            # Every slot retired (crash storm, or a no-wait shutdown with
            # work still queued): fail what's queued so callers see
            # WorkerDied instead of hanging on a future nobody will
            # resolve. Bounded by the queue length (each pass pops one
            # task, submit refuses after shutdown).
            # rsdl-lint: disable=unbounded-retry
            while True:
                with self._lock:
                    if not self._global_q:
                        break
                    task = self._global_q.popleft()
                self._complete(task, False, WorkerDied(
                    "pool retired before the task ran (worker restart "
                    "budget exhausted, or no-wait shutdown)"))


# ---------------------------------------------------------------------------
# Process-mode shuffle epoch (driver side)
# ---------------------------------------------------------------------------


def process_epoch(plan,
                  pool: ProcessPoolExecutor,
                  stats_collector=None,
                  map_transform_blob: Optional[bytes] = None,
                  reduce_transform_blob: Optional[bytes] = None,
                  spill_manager=None,
                  gather_threads: Optional[int] = None,
                  on_bad_file: str = "raise",
                  spill_recompute_factory=None) -> List[ProcTaskRef]:
    """Execute one epoch's :class:`plan.ir.EpochPlan` on the process
    pool; returns reducer refs whose ``result()`` is a driver-mmap'd
    (then accounted / possibly spilled / trace-stamped) table — the same
    contract as the thread-mode ``_reduce_task`` refs.

    The plan scheduler drives dispatch: map nodes go out with file
    affinity (segment warmth — locality-aware placement), reduce nodes
    dispatch only after the ``map`` stage barrier collects every map's
    segment results on the scheduler's driver thread (never on a pool
    dispatcher thread, which a blocking collect could deadlock). A map
    task that fails even after the pool's worker-death resubmission is
    re-run once more from lineage inside that barrier; only exhausted
    recovery propagates (thread-mode ``EpochLineage`` semantics).
    Speculative backup attempts (``RSDL_PLAN_SPECULATION``) re-run the
    same lineage payload on another worker under ATTEMPT-SCOPED segment
    paths (``…a1.idx``): a cache-granted primary writes a flat
    ``(offsets, flat)`` index while an ungranted backup streams the
    grouped layout, so the two attempts' bytes are NOT identical and a
    shared path would let the loser's atomic rewrite silently mismatch
    the winner's ``grouped`` flag in ``sources`` (an empty flat array
    read as a gather index drops the whole file's rows). With per-attempt
    paths first-completion-wins is safe: the winner's ``res`` carries its
    own paths into ``sources``, and the loser's files are reaped at epoch
    drain (or by pool teardown if the loser finishes after the drain).
    """
    import importlib
    sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
    from ray_shuffling_data_loader_tpu import stats as stats_mod
    from ray_shuffling_data_loader_tpu.plan import (
        scheduler as plan_scheduler)

    epoch, seed = plan.epoch, plan.seed
    num_reducers = plan.num_reducers
    filenames = plan.filenames
    plan_threads = sh.derive_gather_threads(len(filenames),
                                            pool.num_workers)

    def _map_payload(file_index: int, filename: str,
                     allow_cache_write: bool, attempt: int = 0) -> dict:
        # Attempt-scoped paths: a backup attempt must never rewrite the
        # primary's segments (the two can legally differ in layout — see
        # the function docstring).
        suffix = f".a{attempt}" if attempt else ""
        payload = {
            "filename": filename,
            "num_reducers": num_reducers,
            "seed": seed,
            "epoch": epoch,
            "file_index": file_index,
            "on_bad_file": on_bad_file,
            "map_transform": map_transform_blob,
            "plan_threads": plan_threads,
            "idx_seg": pool.segment_path(
                f"e{epoch}_f{file_index}{suffix}.idx"),
            "table_seg": pool.cached_table_seg(filename),
        }
        if payload["table_seg"] is None:
            grant = (pool.plan_table_seg_write(filename)
                     if allow_cache_write else None)
            payload["cache_grant"] = grant is not None
            payload["write_table_seg"] = grant or pool.segment_path(
                f"e{epoch}_f{file_index}_table{suffix}.arrow")
        return payload

    holder: Dict[str, Any] = {}
    sources: List["tuple[str, str, bool, bool]"] = []
    epoch_segs: List[str] = []  # epoch-scoped: unlinked at epoch drain
    transient = {"bytes": 0, "buf_id": None}

    def _dispatch_map(node, attempt: int) -> ProcTaskRef:
        file_index = node.key.task
        payload = _map_payload(file_index, node.meta["file"],
                               allow_cache_write=attempt == 0,
                               attempt=attempt)
        if attempt:
            payload["attempt"] = attempt
            # Pre-register the backup's epoch-scoped segments so the
            # loser's files are reaped at epoch drain; if it wins,
            # _collect_maps re-appends the same paths (unlink is quiet).
            epoch_segs.append(payload["idx_seg"])
            if payload.get("write_table_seg"):
                epoch_segs.append(payload["write_table_seg"])
        elif stats_collector is not None:
            stats_collector.map_start(epoch)
        return pool.submit_kind("map", payload, affinity=file_index)

    def _collect_maps() -> None:
        """Map-stage barrier (scheduler driver thread): fold every map
        node's segment reply into the reduce inputs, exactly the
        bookkeeping the old await-then-submit loop did inline."""
        from ray_shuffling_data_loader_tpu import native
        scheduler = holder["scheduler"]
        for node in sorted(plan.maps(), key=lambda n: n.key.task):
            file_index = node.key.task
            filename = node.meta["file"]
            try:
                res = scheduler.ref_for(node.id).result()
            except Exception as e:  # noqa: BLE001 - lineage re-run below
                logger.warning(
                    "map task %d (epoch %d) failed on the pool (%s); "
                    "recomputing from lineage", file_index, epoch, e)
                start = timeit.default_timer()
                retry_ref = pool.submit_kind(
                    "map", _map_payload(file_index, filename, False),
                    affinity=file_index)
                res = retry_ref.result()  # exhausted recovery propagates
                stats_mod.fault_stats().record_recompute(
                    "lineage", timeit.default_timer() - start)
            quarantined = res.get("quarantined")
            if quarantined is not None:
                stats_mod.fault_stats().record_quarantine(quarantined)
                logger.error(
                    "quarantined unreadable input file %s (epoch %d, "
                    "file %d): %s (on_bad_file='skip')", filename, epoch,
                    file_index, quarantined.error)
                if stats_collector is not None:
                    stats_collector.map_done(epoch, 0.0, 0.0)
                continue
            cached = bool(res.get("cached"))
            if cached:
                pool.note_table_seg(filename, res.get("table_seg"),
                                    res.get("wrote_table_bytes", 0))
            else:
                # Clears any unused cache grant (e.g. the granted attempt
                # died and the lineage re-run published an epoch-scoped
                # segment).
                pool.note_table_seg(filename, None, 0)
                epoch_segs.append(res["table_seg"])
                transient["bytes"] += res.get("wrote_table_bytes", 0)
            epoch_segs.append(res["idx_seg"])
            transient["bytes"] += res.get("idx_bytes", 0)
            sources.append((res["table_seg"], res["idx_seg"], cached,
                            bool(res.get("grouped"))))
            if stats_collector is not None:
                stats_collector.map_done(epoch, res["dur_s"], res["read_s"])
            rt_telemetry.observe_stage("map_read", epoch=epoch,
                                       task=file_index,
                                       dur_s=res["read_s"])
        if transient["bytes"]:
            transient["buf_id"] = native.buffer_ledger().register(
                transient["bytes"])

    def _dispatch_reduce(node, attempt: int) -> ProcTaskRef:
        reduce_index = node.key.task
        suffix = f".a{attempt}" if attempt else ""
        payload = {
            "reduce_index": reduce_index,
            "seed": seed,
            "epoch": epoch,
            "sources": sources,
            "gather_threads": gather_threads,
            "reduce_transform": reduce_transform_blob,
            "out_seg": pool.segment_path(
                f"e{epoch}_r{reduce_index}{suffix}.arrow"),
        }
        if attempt:
            payload["attempt"] = attempt
            # Reap the loser's output at epoch drain; a winner's file is
            # unlink-while-mmapped (safe) and its finalize unlink is
            # quiet. A loser that finishes after the drain is left for
            # pool teardown.
            epoch_segs.append(payload["out_seg"])
        elif stats_collector is not None:
            stats_collector.reduce_start(epoch)
        return pool.submit_kind("reduce", payload)

    pending = {"reduces": num_reducers}
    cleanup_lock = threading.Lock()

    def _epoch_cleanup() -> None:
        # Last reduce reply consumed -> the epoch's plan segments (and any
        # uncached table segments) have no readers left.
        from ray_shuffling_data_loader_tpu import native
        for path in epoch_segs:
            _unlink_quiet(path)
        if transient["buf_id"] is not None:
            try:
                native.buffer_ledger().decref(transient["buf_id"])
            except KeyError:
                pass

    def _finalize_factory(reduce_index: int):
        recompute = (spill_recompute_factory(reduce_index)
                     if spill_recompute_factory is not None else None)

        def _finalize(res: dict):
            table = open_table_segment(res["out_seg"])
            weakref.finalize(table, _unlink_quiet, res["out_seg"])
            if stats_collector is not None:
                stats_collector.reduce_done(epoch, res["dur_s"])
            rt_telemetry.observe_stage("reduce_gather", epoch=epoch,
                                       task=reduce_index,
                                       dur_s=res["dur_s"])
            with cleanup_lock:
                pending["reduces"] -= 1
                if pending["reduces"] == 0:
                    _epoch_cleanup()
            return sh.account_and_maybe_spill(
                table, spill_manager, recompute=recompute, epoch=epoch,
                task=reduce_index, seed=seed)

        return _finalize

    scheduler = plan_scheduler.PlanScheduler(
        plan, pool,
        dispatchers={"map": _dispatch_map, "reduce": _dispatch_reduce},
        barriers={"map": _collect_maps})
    holder["scheduler"] = scheduler
    scheduler.start()
    return [ProcTaskRef(future, _finalize_factory(reduce_index))
            for reduce_index, future in enumerate(
                scheduler.futures("reduce"))]
