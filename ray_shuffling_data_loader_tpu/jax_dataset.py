"""JAX/TPU binding: shuffled batches as device-resident ``jax.Array``s.

L4 equivalent of the reference's Torch binding (reference:
torch_dataset.py:12-238): a column spec (names + shapes + dtypes for
features, plus a label column) is normalized with the same rules, and each
iterator batch is converted column-by-column into arrays shaped
``(batch, *shape)`` (default ``(batch, 1)``).

TPU-native design: instead of CPU torch tensors that the trainer later
copies to GPU (reference: torch_dataset.py:206-238 + the trainer's
``.cuda()`` at ray_torch_shuffle.py:189-192), conversion lands batches
directly in device memory as sharded ``jax.Array``s: a background prefetch
thread converts Arrow columns to NumPy (zero-copy where possible) and
``jax.device_put``s the *next* batches onto the device mesh while the
current step runs — double-buffering host->HBM copies behind compute.
Batch-axis sharding uses ``NamedSharding(mesh, P("data", ...))`` so a
multi-chip DP trainer receives its shard without any gather.

The iterator also records the north-star stall metric — time blocked
waiting for a batch (reference: ray_torch_shuffle.py:186-218) — in
``batch_wait_stats``.
"""

from __future__ import annotations

import concurrent.futures as cf
import itertools
import queue as _queue
import threading
import time
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
from ray_shuffling_data_loader_tpu.dataset import (ShufflingDataset,
                                                   slice_batches)
from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
from ray_shuffling_data_loader_tpu.runtime import latency as rt_latency
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import retry as rt_retry
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.stats import BatchWaitStats
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)


def _normalize_jax_data_spec(feature_columns=None,
                             feature_shapes=None,
                             feature_types=None,
                             label_column=None,
                             label_shape=None,
                             label_type=None):
    """Normalize the column spec with the reference's rules
    (reference: torch_dataset.py:146-204): scalars become lists, shapes
    must match feature count, dtypes default to float32.
    """
    import jax.numpy as jnp

    if not isinstance(feature_columns, list):
        feature_columns = [feature_columns]

    if feature_shapes:
        if not isinstance(feature_shapes, list):
            feature_shapes = [feature_shapes]
        if len(feature_columns) != len(feature_shapes):
            raise ValueError(
                "The feature_shapes size must match the feature_columns")
        feature_shapes = [
            tuple(s) if isinstance(s, (list, tuple))
            else (None if s is None else (s,))
            for s in feature_shapes
        ]
    else:
        feature_shapes = [None] * len(feature_columns)

    if feature_types:
        if not isinstance(feature_types, list):
            feature_types = [feature_types]
        if len(feature_columns) != len(feature_types):
            raise ValueError(
                "The feature_types size must match the feature_columns")
        feature_types = [np.dtype(t) for t in feature_types]
    else:
        feature_types = [np.dtype(jnp.float32)] * len(feature_columns)

    if label_type is None:
        label_type = np.dtype(jnp.float32)
    else:
        label_type = np.dtype(label_type)

    return (feature_columns, feature_shapes, feature_types, label_column,
            label_shape, label_type)


def _column_to_numpy(column: pa.ChunkedArray, dtype: np.dtype) -> np.ndarray:
    """Arrow column -> contiguous ndarray, zero-copy when types align.

    Handles the reference's object-column cases (ndarray / list / tuple
    cells, reference: torch_dataset.py:211-223): Arrow list columns become
    stacked 2-D arrays.
    """
    if column.num_chunks == 1:
        combined = column.chunk(0)
    else:
        # Blessed: reducer outputs arrive single-chunk (fused gather), so
        # this arm only runs for carry-buffer concatenations at batch
        # boundaries. rsdl-lint: disable=copy-in-hot-path
        combined = column.combine_chunks()
    if (pa.types.is_fixed_size_list(combined.type)
            and pa.types.is_primitive(combined.type.value_type)
            and combined.null_count == 0):
        # Fast path for image pixels / token sequences: the child values
        # buffer IS the (rows * list_size) array — flatten() respects the
        # slice offset, so the reshape is zero-copy.
        width = combined.type.list_size
        # Blessed conversion boundary: the device transfer needs a host
        # ndarray; flatten() is zero-copy here (primitive child buffer).
        # rsdl-lint: disable=copy-in-hot-path
        flat = combined.flatten().to_numpy(zero_copy_only=False)
        arr = flat.reshape(-1, width)
    elif pa.types.is_list(combined.type) \
            or pa.types.is_large_list(combined.type) \
            or pa.types.is_fixed_size_list(combined.type):
        # Blessed: ragged lists have no zero-copy ndarray form — the
        # stack IS the conversion. rsdl-lint: disable=copy-in-hot-path
        arr = np.stack(combined.to_numpy(zero_copy_only=False))
    else:
        # Blessed: primitive null-free columns come back zero-copy; the
        # permissive flag only covers the object-cell fallback below.
        # rsdl-lint: disable=copy-in-hot-path
        arr = combined.to_numpy(zero_copy_only=False)
        if arr.dtype == object:
            first = arr[0] if len(arr) else None
            if isinstance(first, np.ndarray):
                arr = np.stack(arr)
            elif isinstance(first, (list, tuple)):
                arr = np.asarray([list(x) for x in arr])
            else:
                raise TypeError(
                    f"Column cell type {type(first)} is not supported. It "
                    "must be a numeric type or an object of (ndarray, list, "
                    "tuple)")
    return np.ascontiguousarray(arr.astype(dtype, copy=False))


def make_cast_transform(feature_columns: Sequence[Any],
                        feature_types: Sequence[np.dtype],
                        label_column: Any,
                        label_type: np.dtype):
    """Map-time column-cast hook: cast spec'd numeric columns to their final
    dtypes right after the Parquet read, BEFORE any shuffling.

    The reference converts dtypes per batch on the trainer
    (reference: torch_dataset.py:206-238); casting at the map stage instead
    (e.g. int64 -> int32) halves the memory traffic of every downstream
    stage — partition, permute-gather, re-batch, host->device DMA. Columns
    that are not primitive numerics, are nullable, or are not in the spec
    pass through untouched. The cast is an unchecked ``ndarray.astype``
    (same semantics as the reference's ``torch.as_tensor(..., dtype=)``).
    """
    targets = {}
    for col, dtype in zip(feature_columns, feature_types):
        targets[col] = np.dtype(dtype)
    targets[label_column] = np.dtype(label_type)
    return CastTransform(targets)


class CastTransform:
    """The map-time cast hook as a picklable callable: the process-pool
    executor ships transforms to its workers by pickle, and a closure
    would silently force the thread backend for every cast-at-map
    workload (procpool.resolve_backend's picklability gate). State is
    one ``{column -> np.dtype}`` dict."""

    __slots__ = ("targets",)

    #: Per-row independent and row-count preserving: the fused streaming
    #: map pipeline (shuffle._fused_stream_columns) may apply this per
    #: record batch instead of per file — same bytes either way.
    row_elementwise = True

    def __init__(self, targets):
        self.targets = dict(targets)

    def __call__(self, table: pa.Table) -> pa.Table:
        columns = []
        changed = False
        for field in table.schema:
            col = table.column(field.name)
            target = self.targets.get(field.name)
            if (target is not None and col.null_count == 0
                    and (pa.types.is_integer(field.type)
                         or pa.types.is_floating(field.type))
                    and np.issubdtype(target, np.number)
                    and pa.from_numpy_dtype(target) != field.type):
                if col.num_chunks == 1:
                    combined = col.chunk(0)
                else:
                    # Blessed: fresh-from-parquet tables are chunked per
                    # row group; the cast below re-materializes anyway.
                    # rsdl-lint: disable=copy-in-hot-path
                    combined = col.combine_chunks()
                # Blessed: the dtype-changing cast IS this hook's job (the
                # guard above skips same-dtype columns), and copy=False
                # keeps the would-be-no-op arm free.
                # rsdl-lint: disable=copy-in-hot-path
                col = pa.array(combined.to_numpy(
                    zero_copy_only=False).astype(target, copy=False))
                changed = True
            columns.append(col)
        if not changed:
            return table
        return pa.table(columns, names=table.column_names)


def convert_to_arrays(table: pa.Table,
                      feature_columns: List[Any],
                      feature_shapes: List[Optional[Tuple[int, ...]]],
                      feature_types: List[np.dtype],
                      label_column: Any,
                      label_shape: Optional[int],
                      label_type: np.dtype
                      ) -> Tuple[List[np.ndarray], np.ndarray]:
    """Arrow batch -> (per-feature arrays, label array), each reshaped to
    ``(batch, *shape)`` / ``(batch, 1)`` (reference: torch_dataset.py:206-238).
    """
    features = []
    for col, shape, dtype in zip(feature_columns, feature_shapes,
                                 feature_types):
        arr = _column_to_numpy(table.column(col), dtype)
        if shape is not None:
            arr = arr.reshape(-1, *shape)
        elif arr.ndim == 1:
            arr = arr.reshape(-1, 1)
        features.append(arr)
    label = _column_to_numpy(table.column(label_column), label_type)
    if label_shape:
        label = label.reshape(-1, label_shape)
    elif label.ndim == 1:
        label = label.reshape(-1, 1)
    return features, label


class _BatchConverter:
    """Self-contained Arrow-batch -> device-batch pipeline stage.

    Holds ONLY the column spec and transfer config — deliberately no
    reference to the dataset wrapper, so the persistent producer thread
    (which runs this) never pins the wrapper and a dropped
    ``JaxShufflingDataset`` can be garbage-collected (its finalizer then
    stops the producer).
    """

    def __init__(self, feature_columns, feature_shapes, feature_types,
                 label_column, label_shape, label_type, stack_features,
                 mesh, data_axis, device_put, device_rebatch=False,
                 device_rebatch_auto=False,
                 max_table_bytes=512 * 1024 * 1024,
                 watchdog=None, bulk_transfer_deadline_s=30.0,
                 stall_action="degrade"):
        self._feature_columns = feature_columns
        self._feature_shapes = feature_shapes
        self._feature_types = feature_types
        self._label_column = label_column
        self._label_shape = label_shape
        self._label_type = label_type
        self._stack_features = stack_features
        self._mesh = mesh
        self._data_axis = data_axis
        self._device_put = device_put
        self._device_concat = None  # jitted column concat, built lazily
        # Device-rebatch mode: whole reducer tables are transferred in bulk
        # and batch slicing happens on the accelerator (see
        # JaxShufflingDataset docstring). These two fields configure the
        # producer's table path; the per-batch path ignores them.
        self.device_rebatch = device_rebatch
        # True when rebatch was resolved from "auto" rather than requested
        # explicitly: specs the bulk path can't reproduce then fall back to
        # per-batch transfers instead of failing a previously-working job.
        self.device_rebatch_auto = device_rebatch_auto
        self.max_table_bytes = max_table_bytes
        # Liveness supervision of the bulk path (runtime/watchdog.py): a
        # chunk device_put/carve that misses the deadline is reported and
        # — under the default "degrade" stall action — permanently drops
        # this converter to the per-batch path (see _on_bulk_stall).
        self.watchdog = watchdog
        self.bulk_transfer_deadline_s = bulk_transfer_deadline_s
        self.stall_action = stall_action
        self.fallback_engaged = False  # a stall degraded the bulk path
        # Double-buffered device staging (RSDL_DEVICE_DOUBLE_BUFFER,
        # default on): the per-batch producer dispatches batch N's
        # host->device transfer on a staging thread while it converts
        # batch N+1 — upload overlaps host work, FIFO order preserved.
        # The owning JaxShufflingDataset overrides this from its resolved
        # runtime_policy; the resolve here covers direct converter use.
        from ray_shuffling_data_loader_tpu.runtime import (policy as
                                                           rt_policy)
        self.double_buffer = bool(
            rt_policy.resolve("jax_dataset", "device_double_buffer"))
        self._slicer = {}  # batch_size -> jitted batch slicer, built lazily
        # Transient device-transfer failures (a PJRT I/O error, an injected
        # `device_transfer` fault) are retried in place: the source arrays
        # are host-resident numpy, so a re-put is pure. Predicate is
        # IO-shaped only — a shape/dtype error is a bug and surfaces.
        self._transfer_retry = rt_retry.RetryPolicy.for_component(
            "jax_dataset", retryable=rt_retry.transient_retryable)
        self._transfer_seq = 0  # producer-thread-only; keys chaos draws
        # Delivery-latency probe (runtime/latency.py), installed by the
        # owning JaxShufflingDataset: convert() notes the source table's
        # birth, transfer completions close the delivered->device and
        # birth->device hops. None = no probe (direct converter use).
        self.latency_probe = None

    def _device_put_retried(self, thunk):
        """One (bulk or per-batch) device_put: named fault site + bounded
        retry; a recovery after failure is recorded in fault_stats. The
        per-converter transfer sequence keys the chaos site, so a rate
        rule (``device_transfer@0.02``) draws independently per transfer
        and a targeted rule can hit exactly one."""

        def _put():
            self._transfer_seq += 1
            # Attempt marker (no duration — not a stage sample): carries
            # the same task key the device_transfer fault site draws on,
            # so an injected transfer fault joins telemetry by
            # (kind, epoch, task) like every other site. The stage's
            # latency samples come from the epoch-tagged transfer spans
            # (_timed_transfer).
            rt_telemetry.record("device_transfer", task=self._transfer_seq,
                                attempt=True)
            rt_faults.inject("device_transfer", task=self._transfer_seq)
            return thunk()

        def _recovered(failed_attempts: int, elapsed_s: float) -> None:
            from ray_shuffling_data_loader_tpu import stats as stats_mod
            stats_mod.fault_stats().record_recompute(
                "device_transfer", elapsed_s)

        return self._transfer_retry.call(_put, describe="device_put",
                                         on_recovery=_recovered)

    def _note_device_done(self) -> None:
        """One device-transfer completion on the latency plane (no-op
        without a probe). ``device_put`` is async — the hop closed here
        is dispatch-complete; the ``device_transfer`` telemetry span runs
        on to the landed copy (:func:`_timed_transfer`)."""
        if self.latency_probe is not None:
            self.latency_probe.device_done()

    def _on_bulk_stall(self, report) -> None:
        """Watchdog escalation hook — runs on the MONITOR thread (the
        producer is, by definition, stuck inside the supervised call).
        Caps in-flight bulk bytes so any future chunk is smaller, and
        under the "degrade" action flips this converter to the per-batch
        path; the producer reroutes the moment the stuck call returns.
        """
        from ray_shuffling_data_loader_tpu import stats as stats_mod
        if report.escalation == 1:
            self.max_table_bytes = max(1, self.max_table_bytes // 2)
        if self.stall_action == "degrade" and self.device_rebatch:
            self.device_rebatch = False
            self.fallback_engaged = True
            reason = (f"{report.name} stalled {report.waited_s:.2f}s "
                      f"(deadline {report.deadline_s:.2f}s"
                      f"{', ' + report.detail if report.detail else ''}); "
                      "degrading to per-batch transfers")
            stats_mod.watchdog_stats().record_fallback(
                "jax_dataset.device_rebatch", reason)
            logger.warning("%s", reason)

    def _sharding(self, ndim: int):
        from jax.sharding import NamedSharding, PartitionSpec as P
        if self._mesh is None:
            return None
        return NamedSharding(
            self._mesh, P(self._data_axis, *([None] * (ndim - 1))))

    def convert(self, table: pa.Table):
        if self.latency_probe is not None:
            self.latency_probe.table_arrived(table)
        return convert_to_arrays(
            table, self._feature_columns, self._feature_shapes,
            self._feature_types, self._label_column, self._label_shape,
            self._label_type)

    def transfer(self, arrays_label):
        """Host arrays -> device arrays (sharded if a mesh was given).

        With ``stack_features``, per-column host arrays are transferred
        individually (zero-copy views of the Arrow buffers) and stacked by
        one jitted ``jnp.concatenate`` on device — the host-side strided
        interleave this replaces was a top host cost of the ingest path.
        """
        import jax
        features, label = arrays_label
        if not self._device_put:
            if self._stack_features:
                features = (features[0] if len(features) == 1
                            else np.concatenate(features, axis=1))
            self._note_device_done()
            return features, label
        # ONE device_put for the whole batch pytree: the runtime batches
        # the per-column copies into a single transfer (through the PJRT
        # client once, not once per column).
        if self._mesh is None:
            out_features, out_label = self._device_put_retried(
                lambda: jax.device_put((features, label)))
        else:
            out_features, out_label = self._device_put_retried(
                lambda: jax.device_put(
                    (features, label),
                    ([self._sharding(a.ndim) for a in features],
                     self._sharding(label.ndim))))
        if self._stack_features:
            if len(out_features) == 1:
                out_features = out_features[0]
            else:
                if self._device_concat is None:
                    import jax.numpy as jnp
                    self._device_concat = jax.jit(
                        lambda cols: jnp.concatenate(cols, axis=1))
                out_features = self._device_concat(out_features)
        self._note_device_done()
        return out_features, out_label

    def transfer_table(self, arrays_label, n_batches: int, batch_size: int):
        """Bulk host->device transfer of a whole (multi-batch) chunk.

        One ``device_put`` moves every column's full span — a few ~MB
        transfers per reducer output instead of one dispatch per batch per
        column. Stacking/reshaping is deferred to :meth:`slice_batch`, which
        runs on device.

        Without a mesh, arrays go up as flat ``(n_batches * batch_size,
        ...)`` spans. With a mesh, each array is reshaped host-side
        (zero-copy) to ``(n_batches, batch_size, ...)`` and transferred with
        the BATCH dimension (axis 1) sharded over ``data_axis`` — every
        device receives its slice of every batch, so the per-batch carve in
        :meth:`slice_batch` is a free axis-0 index with no resharding.
        """
        import jax
        features, label = arrays_label
        if not self._device_put:
            self._note_device_done()
            return features, label
        if self._mesh is None:
            item = self._device_put_retried(
                lambda: jax.device_put((features, label)))
            self._note_device_done()
            return item
        from jax.sharding import NamedSharding, PartitionSpec as P

        def chunked(a):
            return a.reshape(n_batches, batch_size, *a.shape[1:])

        def sharding(a):
            return NamedSharding(
                self._mesh, P(None, self._data_axis,
                              *([None] * (a.ndim - 2))))

        features = [chunked(f) for f in features]
        label = chunked(label)
        item = self._device_put_retried(
            lambda: jax.device_put(
                (features, label),
                ([sharding(f) for f in features], sharding(label))))
        self._note_device_done()
        return item

    def slice_batch(self, dev_table, batch_index: int, batch_size: int):
        """Carve batch ``batch_index`` out of a bulk device chunk: one
        jitted program per chunk length (the index is a traced scalar, and
        chunk lengths are bounded at ``_MAX_CHUNK_BATCHES`` batches, so the
        compile set is small and reused across tables and epochs),
        producing the same ``(features, label)`` pytree the per-batch path
        yields. Flat (mesh-less) chunks dynamic-slice rows; mesh chunks are
        ``(n_batches, batch, ...)`` with the batch axis sharded, so the
        carve is a free axis-0 index that keeps the sharding. On TPU this
        rides HBM bandwidth; the host does no per-batch copy at all.
        """
        import jax
        chunked = self._mesh is not None
        features, label = dev_table
        slicer = self._slicer.get(batch_size)
        if slicer is None:
            from jax import lax
            import jax.numpy as jnp
            stack = self._stack_features

            def _slice(features, label, idx):
                if chunked:
                    fs = [lax.dynamic_index_in_dim(f, idx, 0, keepdims=False)
                          for f in features]
                    lb = lax.dynamic_index_in_dim(label, idx, 0,
                                                  keepdims=False)
                else:
                    fs = [lax.dynamic_slice_in_dim(
                              f, idx * batch_size, batch_size, axis=0)
                          for f in features]
                    lb = lax.dynamic_slice_in_dim(
                        label, idx * batch_size, batch_size, axis=0)
                if stack:
                    fs = fs[0] if len(fs) == 1 else jnp.concatenate(fs, axis=1)
                return fs, lb

            out_shardings = None
            if chunked:
                # The sharding objects transfer() gives the stitched
                # batches, not whatever equal layout XLA names for the
                # carve: a consumer's jitted step is keyed on them, and
                # P("data") beside P("data", None) — the same layout —
                # would be two programs.
                out_shardings = (
                    self._sharding(2) if stack else
                    [self._sharding(f.ndim - 1) for f in features],
                    self._sharding(label.ndim - 1))
            slicer = self._slicer[batch_size] = jax.jit(
                _slice, out_shardings=out_shardings)
        return slicer(features, label, np.int32(batch_index))


class _TransferReaper:
    """Closes ``device_transfer`` spans when the copy has landed.

    ``jax.device_put`` returns at dispatch, and no thread of the pipeline
    waits on the host for the copy: the consumer's carve and train step
    wait for it on the device. This thread waits in their place, out of
    the pipeline's path (the producer and the consumer gain no wait), and
    closes each span the dispatching thread handed off. One daemon thread
    a process, started with the first transfer that telemetry times."""

    def __init__(self):
        self._queue: "_queue.SimpleQueue" = _queue.SimpleQueue()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None

    def submit(self, span, arrays, **attrs) -> None:
        if self._thread is None:
            with self._lock:
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._run, daemon=True,
                        name="rsdl-transfer-reaper")
                    self._thread.start()
        self._queue.put((span, arrays, attrs))

    def _run(self) -> None:
        while True:
            span, arrays, attrs = self._queue.get()
            error = self._wait_landed(arrays)
            arrays = None
            if error is not None:
                attrs["error"] = error
            rt_telemetry.span_end(span, **attrs)

    @staticmethod
    def _wait_landed(arrays) -> Optional[str]:
        """Wait for the copy; the name of what went wrong, if anything.
        A buffer the consumer donated away, a device error the consumer
        will meet itself: the span still closes."""
        import jax
        try:
            jax.block_until_ready(arrays)
        except Exception as e:  # noqa: BLE001 - observation only
            return type(e).__name__
        return None


_TRANSFER_REAPER = _TransferReaper()


def _timed_transfer(epoch: Optional[int], transfer, *args):
    """One host->device transfer under the ``device_transfer`` span
    (``rsdl.feed.transfer``). The span opens before the dispatch and
    closes when the copy has landed, on the reaper's thread;
    ``dispatch_s`` on the event is the part this thread spent in
    ``device_put``."""
    # Closed by the reaper, or right here when the dispatch raises: the
    # handoff is the point. rsdl-lint: disable=span-unbalanced
    span = rt_telemetry.span_begin("device_transfer", epoch=epoch,
                                   handoff=True)
    if span is None:
        return transfer(*args)
    try:
        out = transfer(*args)
    except BaseException:
        rt_telemetry.span_end(span, failed=True)
        raise
    _TRANSFER_REAPER.submit(span, out,
                            dispatch_s=rt_telemetry.stamp() - span.t0)
    return out


def _persistent_producer(dataset: ShufflingDataset,
                         converter: _BatchConverter,
                         out: "_queue.Queue",
                         stop: threading.Event,
                         lock: threading.Lock,
                         pending_skips: dict,
                         started_epochs: set) -> None:
    """Producer loop for ALL epochs (persistent_prefetch).

    Module-level on purpose: it references the underlying ShufflingDataset
    and small shared state objects but NOT the JaxShufflingDataset wrapper,
    so an abandoned wrapper is collectable and its weakref finalizer (which
    sets ``stop`` and drains ``out``) releases this thread even when the
    consumer never called close().
    """

    def put(item) -> bool:
        while not stop.is_set():
            try:
                out.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue
        return False

    try:
        # plan.ir.epoch_range: a bounded range for a classic trial, an
        # unbounded count when the dataset consumes a stream
        # (num_epochs=None) — the producer keeps entering epochs as
        # windows close server-side.
        for epoch in plan_ir.epoch_range(dataset.start_epoch,
                                         dataset.num_epochs):
            with lock:
                started_epochs.add(epoch)
                skip = pending_skips.pop(epoch, 0)
            dataset.set_epoch(epoch, skip_batches=skip)
            if converter.device_rebatch:
                if not _produce_epoch_tables(dataset, converter, epoch, put,
                                             queue_depth=out.qsize):
                    return
            elif converter.double_buffer:
                if not _produce_epoch_batches_staged(dataset, converter,
                                                     epoch, put):
                    return
            else:
                for table in dataset:
                    with rt_telemetry.span("convert", epoch=epoch):
                        arrays = converter.convert(table)
                    batch = _timed_transfer(epoch, converter.transfer,
                                            arrays)
                    if not put(("batch", epoch, batch)):
                        return
            if not put(("end", epoch, None)):
                return
    except BaseException as e:  # noqa: BLE001 - forwarded to consumer
        put(e)


def _staged_transfer(converter: _BatchConverter, arrays, epoch):
    """One per-batch host->device transfer on the staging thread — the
    same retried/fault-injected ``converter.transfer`` (and the same
    telemetry span) the serial path runs inline."""
    return _timed_transfer(epoch, converter.transfer, arrays)


def _produce_epoch_batches_staged(dataset, converter: _BatchConverter,
                                  epoch: int, put) -> bool:
    """Per-batch producer loop with double-buffered device staging
    (``RSDL_DEVICE_DOUBLE_BUFFER``, default on): batch N's transfer is
    dispatched on a one-thread staging pool while this thread fetches
    and converts batch N+1, overlapping host->device upload with host
    decode/convert. Exactly one transfer is in flight and results are
    awaited FIFO, so delivery order, chaos draws and retry semantics are
    those of the serial path. Returns False when the consumer is gone."""
    with cf.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="rsdl-device-stage") as pool:
        pending = None
        for table in dataset:
            with rt_telemetry.span("convert", epoch=epoch):
                arrays = converter.convert(table)
            fut = pool.submit(_staged_transfer, converter, arrays, epoch)
            if pending is not None and not put(
                    ("batch", epoch, pending.result())):
                return False
            pending = fut
        if pending is not None and not put(
                ("batch", epoch, pending.result())):
            return False
    return True


# Upper bound on batches per bulk device chunk: caps both the jit slicer's
# compiled-shape set (chunk lengths are 1.._MAX_CHUNK_BATCHES batches) and
# per-chunk HBM bytes.
_MAX_CHUNK_BATCHES = 8


def _supervised_transfer_table(converter: _BatchConverter, arrays_label,
                               nb: int, bs: int, queue_depth):
    """One bulk chunk transfer under watchdog supervision.

    A wedged ``device_put`` (stuck PJRT client) blocks
    this thread indefinitely; the watchdog's monitor detects the missed
    deadline WHILE it is stuck, files the stall (with the prefetch-queue
    depth — 0 means the consumer is blocked waiting on this very chunk),
    and fires the converter's escalation hook. When the call finally
    returns, a "raise" stall action surfaces here; "degrade" reroutes in
    the caller's loop via ``converter.device_rebatch``.
    """
    wd = converter.watchdog
    if wd is None:
        return converter.transfer_table(arrays_label, nb, bs)
    detail_fn = None
    if queue_depth is not None:
        detail_fn = lambda: (  # noqa: E731
            f"chunk={nb} batches, prefetch_queue_depth={queue_depth()} "
            "(0 = consumer blocked)")
    with wd.watch("jax_dataset.bulk_transfer",
                  deadline_s=converter.bulk_transfer_deadline_s,
                  on_stall=converter._on_bulk_stall,
                  detail_fn=detail_fn) as handle:
        item = converter.transfer_table(arrays_label, nb, bs)
    if handle.stalled and converter.stall_action == "raise":
        raise RuntimeError(
            f"bulk device transfer stalled: ran {handle.report.waited_s:.2f}s"
            f" against a {handle.report.deadline_s:.2f}s deadline "
            "(stall_action='raise')")
    return item


def _produce_epoch_tables(dataset: ShufflingDataset,
                          converter: _BatchConverter,
                          epoch: int,
                          put,
                          queue_depth=None) -> bool:
    """Device-rebatch producer for one epoch: bulk table transfers.

    Consumes RAW reducer tables (``ShufflingDataset.iter_tables``) instead
    of host-sliced batches. Each table's batch-aligned middle is moved to
    the device in multi-batch chunks (one dispatch per column per ~8
    batches, not per batch) and carved into batches on-device by the
    consumer. Rows that don't align with the batch grid — the tail of one
    table plus the head of the next — are stitched host-side into ordinary
    per-batch items, so the batch sequence is identical to the host
    re-batching path (same carry arithmetic as ``ShufflingDataset.__iter__``,
    reference: dataset.py:170-202).

    Workloads where a single batch exceeds ``converter.max_table_bytes``
    (fat rows, e.g. decoded images) fall back to per-batch transfers.
    """
    bs = dataset.batch_size
    carry: List[Tuple[List[np.ndarray], np.ndarray]] = []
    carry_rows = 0

    def flush_carry():
        pieces_f = [np.concatenate([p[0][i] for p in carry], axis=0)
                    for i in range(len(carry[0][0]))]
        pieces_l = np.concatenate([p[1] for p in carry], axis=0)
        return _timed_transfer(epoch, converter.transfer,
                               (pieces_f, pieces_l))

    tables = dataset.iter_tables()
    emitted = False  # anything put() or carried yet this epoch
    for table in tables:
        with rt_telemetry.span("convert", epoch=epoch):
            features, label = converter.convert(table)
        n = table.num_rows
        if any(f.shape[0] != n for f in features) or label.shape[0] != n:
            # A spec whose reshape repacks the sample dimension (e.g. a flat
            # column with feature_shape=(4,)) groups rows differently per
            # converted span, so bulk conversion cannot reproduce the host
            # path's per-batch grouping. When rebatch was an "auto" default
            # (not explicitly requested), fall back to the per-batch
            # convert+transfer path — same batch grid via slice_batches, so
            # the stream is identical to the host path. Only an explicit
            # device_rebatch=True fails loudly.
            if converter.device_rebatch_auto and not emitted:
                logger.warning(
                    "device_rebatch (auto) disabled: the column spec "
                    "repacks the sample dimension; using per-batch "
                    "transfers")
                # Permanent for this dataset: the spec-to-shape ratio is
                # constant across tables and epochs, so later epochs take
                # the per-batch path directly instead of rediscovering the
                # mismatch (and re-logging) every epoch.
                converter.device_rebatch = False
                for batch_table in slice_batches(
                        itertools.chain([table], tables), bs,
                        dataset.drop_last):
                    with rt_telemetry.span("convert", epoch=epoch):
                        arrays = converter.convert(batch_table)
                    batch = _timed_transfer(epoch, converter.transfer,
                                            arrays)
                    if not put(("batch", epoch, batch)):
                        return False
                return True
            raise ValueError(
                "device_rebatch requires specs whose converted arrays keep "
                "one sample per table row; a feature_shape/label_shape "
                "repacks the sample dimension here. Construct with "
                "device_rebatch=False for this spec.")
        if n:
            emitted = True
        offset = 0
        if carry_rows:
            take = min(bs - carry_rows, n)
            carry.append(([f[:take] for f in features], label[:take]))
            carry_rows += take
            offset = take
            if carry_rows == bs:
                if not put(("batch", epoch, flush_carry())):
                    return False
                carry, carry_rows = [], 0
        full_batches = (n - offset) // bs
        if full_batches:
            row_bytes = (sum(a.nbytes for a in features) + label.nbytes) // n
            batch_bytes = max(1, row_bytes * bs)
            # Chunked bulk transfers, at most _MAX_CHUNK_BATCHES batches per
            # chunk and at most max_table_bytes per chunk. Fixed chunk sizes
            # keep the jitted slicer's shape set bounded (<= one compile per
            # chunk length, reused across tables and epochs) and bound
            # per-item HBM residency: the pipeline holds at most
            # ~(prefetch_size + 2) chunks on device at once. The per-chunk
            # cap is re-read every chunk: a watchdog stall halves it (and,
            # under the default "degrade" action, clears device_rebatch so
            # the rest of this table — and every later table — moves
            # per-batch instead of trusting the path that just wedged).
            done = 0  # full batches already emitted from this table
            while done < full_batches and converter.device_rebatch:
                k = min(_MAX_CHUNK_BATCHES, converter.max_table_bytes
                        // batch_bytes)
                if k < 1:
                    # Fat rows (a single batch exceeds the cap): per-batch
                    # transfers bound device residency.
                    break
                nb = min(k, full_batches - done)
                lo = offset + done * bs
                hi = lo + nb * bs
                item = _timed_transfer(
                    epoch, _supervised_transfer_table, converter,
                    ([f[lo:hi] for f in features], label[lo:hi]),
                    nb, bs, queue_depth)
                if not put(("table", epoch, (item, nb))):
                    return False
                done += nb
            for b in range(done, full_batches):
                lo = offset + b * bs
                batch = _timed_transfer(
                    epoch, converter.transfer,
                    ([f[lo:lo + bs] for f in features], label[lo:lo + bs]))
                if not put(("batch", epoch, batch)):
                    return False
            offset += full_batches * bs
        if offset < n:
            carry.append(([f[offset:] for f in features], label[offset:]))
            carry_rows += n - offset
    if carry_rows and not dataset.drop_last:
        if not put(("batch", epoch, flush_carry())):
            return False
    return True


class _ConsumerSpans:
    """The spans of the consumer's thread inside the feed, and what is
    summed from them: the wall and CPU seconds of the two
    ``rsdl_feed_consumer_*`` counters and the split of the epoch turnover
    under way. The counters leave the wait on the queue out: wall minus
    CPU in the spans that do no waiting of their own (the carve's
    dispatch, ``set_epoch``, the epoch's end) is time spent waiting for
    the GIL or the scheduler.

    A turnover runs from the consumer's ``next()`` that meets an epoch's
    end to the yield of the next epoch's first batch; its parts are the
    spans closed with a ``part`` meanwhile, the rest is the caller's own
    work between them."""

    def __init__(self):
        self._wall = rt_metrics.counter(
            "rsdl_feed_consumer_wall_seconds_total",
            "consumer-thread seconds inside the device feed's spans, the "
            "wait on its queue apart")
        self._cpu = rt_metrics.counter(
            "rsdl_feed_consumer_cpu_seconds_total",
            "consumer-thread CPU seconds inside those spans")
        self._turnover: Optional[dict] = None

    def span_begin(self, kind: str, epoch: Optional[int], **attrs):
        return rt_telemetry.span_begin(kind, epoch=epoch, cpu=True, **attrs)

    def span_end(self, span, part: Optional[str] = None,
                 counted: bool = True) -> None:
        rt_telemetry.span_end(span)
        if span is None:
            return
        if counted:
            self._wall.inc(span.dur_s)
            self._cpu.inc(span.cpu_s)
        if part is not None and self._turnover is not None:
            parts = self._turnover["parts"]
            parts[part] = parts.get(part, 0.0) + span.dur_s

    def turnover_begin(self, epoch: int, entered_at: float, end_get) -> None:
        """The get that ``end_get`` timed returned ``epoch``'s end; the
        consumer had entered that ``next()`` at ``entered_at``."""
        if end_get is not None:
            self._turnover = {"epoch": epoch, "t0": entered_at,
                              "parts": {"end_get": end_get.dur_s}}

    def turnover_end(self) -> None:
        """The next epoch's first batch is about to be yielded."""
        turnover, self._turnover = self._turnover, None
        if turnover is not None:
            rt_telemetry.turnover_complete(
                turnover["epoch"], rt_telemetry.stamp() - turnover["t0"],
                turnover["parts"])


def _release_producer(stop: threading.Event, out: "_queue.Queue") -> None:
    """Finalizer for a dropped JaxShufflingDataset: stop the producer and
    drop its buffered device batches."""
    stop.set()
    try:
        while True:
            out.get_nowait()
    except _queue.Empty:
        pass


class JaxShufflingDataset:
    """Shuffled batches as device-resident, optionally mesh-sharded
    ``jax.Array``s, with prefetch double-buffering.

    Constructor mirrors ``TorchShufflingDataset``
    (reference: torch_dataset.py:43-78) plus the TPU knobs:

    Args:
        mesh: optional ``jax.sharding.Mesh``; batches are laid out with the
            leading (batch) axis sharded over ``data_axis``.
        data_axis: mesh axis name for the batch dimension.
        prefetch_size: how many converted+transferred batches to keep ahead
            of the consumer (2 = classic double buffering).
        drop_last: fixed shapes are strongly recommended on TPU (a ragged
            tail batch triggers one extra XLA compile), so this defaults to
            True — unlike the reference.
        stack_features: yield features as ONE ``(batch, num_features)``
            device array instead of a list of ``(batch, 1)`` arrays.
            Requires identical feature dtypes and scalar/1-wide shapes —
            this is the layout DLRM-style models consume anyway. With
            ``device_put`` the stack happens ON DEVICE (columns are
            transferred zero-copy and concatenated by one fused XLA op), so
            the host never pays the strided ``np.concatenate`` pass — on
            TPU the concat rides HBM bandwidth instead of host memory.
        cast_at_map: cast spec'd columns to their final dtypes at the map
            stage (before shuffling) instead of per batch — see
            :func:`make_cast_transform`. Only effective when this dataset
            launches the shuffle (rank 0 without an external
            ``batch_queue``).
        reduce_transform: optional ``pa.Table -> pa.Table`` hook run by each
            reduce task on its shuffled output — e.g. image decode inside
            the reducers (``workloads.imagenet.decode_transform``). Only
            effective when this dataset launches the shuffle.
        persistent_prefetch: keep ONE producer thread alive across epochs
            (default). The producer rolls straight from epoch N's last
            batch into epoch N+1's convert+transfer, so the epoch boundary
            costs the consumer ~zero wait instead of a full
            convert+transfer pipeline refill. Requires epochs to be
            iterated sequentially from ``start_epoch`` (the universal
            pattern; ``set_epoch`` raises otherwise). Set False to restore
            a fresh producer per epoch (any epoch order, at the price of
            the boundary bubble).
        file_cache: forwarded to the shuffle driver (rank-0 launch path
            only): a ``shuffle.FileTableCache``, ``"auto"`` (budgeted from
            host RAM), or ``None`` to disable cross-epoch caching of
            decoded files.
        max_inflight_bytes: byte budget for transient shuffle memory
            (in-flight map + reducer tables); see ``shuffle.shuffle``.
        spill_dir: with ``max_inflight_bytes``, spill over-budget reducer
            outputs to Arrow IPC files here instead of throttling
            (plasma's spill role; see spill.py).
        device_rebatch: move whole reducer outputs to the device in bulk
            (one ``device_put`` per multi-batch chunk, a few MB per column)
            and carve batches ON DEVICE with one jitted program, instead of
            one host convert+transfer per batch. Cuts host->device
            dispatches per epoch by ~an order of magnitude — on a
            high-latency device link this is the dominant producer cost —
            and the per-batch carve rides HBM bandwidth. Batch contents are
            identical to the host path (grid-unaligned rows at reducer
            boundaries are stitched host-side). With a mesh, chunks are
            reshaped host-side (zero-copy) to ``(n_batches, batch, ...)``
            and transferred with the batch axis sharded over ``data_axis``,
            so the carve is a free axis-0 index with no resharding —
            requires ``batch_size`` divisible by the data-axis device
            count. ``"auto"`` (default) enables it when
            ``persistent_prefetch`` and ``device_put`` are on (and the
            divisibility holds) on non-CPU backends.
        max_device_input_bytes: combined HBM budget for the input
            pipeline in device_rebatch mode. The pipeline holds at most
            ~``(prefetch_size + 2)`` chunks on device, so the per-chunk
            cap is derived as ``max_device_input_bytes /
            (prefetch_size + 2)`` — raising ``prefetch_size`` shrinks
            chunks instead of multiplying HBM residency. Default 1 GiB.
        max_device_table_bytes: explicit per-chunk byte cap for
            device_rebatch (chunks also cap at 8 batches); overrides the
            derivation from ``max_device_input_bytes`` when set.
            Workloads where one batch alone exceeds the cap (fat rows —
            e.g. decoded images) fall back to per-batch transfers.
        runtime_policy: explicit overrides for the runtime
            health/degradation policy (``runtime/policy.py`` keys:
            ``watchdog``, ``bulk_transfer_deadline_s``, ``stall_action``,
            ``device_rebatch``, ...). Defaults resolve through
            ``RSDL_JAX_DATASET_<KEY>`` / ``RSDL_<KEY>`` env vars, so
            e.g. ``RSDL_DEVICE_REBATCH=0`` makes the per-batch path the
            library default for ``device_rebatch="auto"`` constructions.
            The bulk path runs under a progress watchdog: a chunk
            ``device_put``/carve that misses ``bulk_transfer_deadline_s``
            files a structured stall report into
            ``stats.watchdog_stats()``, halves the in-flight bulk byte
            cap, and — under the default ``stall_action="degrade"`` —
            permanently drops this dataset to per-batch transfers with a
            logged reason instead of hanging ("warn" records only,
            "raise" fails the producer).
    """

    def __init__(self,
                 filenames: Sequence[str],
                 num_epochs: Optional[int],
                 num_trainers: int,
                 batch_size: int,
                 rank: int,
                 feature_columns: List[Any] = None,
                 feature_shapes: Optional[List[Any]] = None,
                 feature_types: Optional[List[Any]] = None,
                 label_column: Any = None,
                 label_shape: Optional[int] = None,
                 label_type: Optional[Any] = None,
                 drop_last: bool = True,
                 num_reducers: Optional[int] = None,
                 max_concurrent_epochs: int = 2,
                 batch_queue=None,
                 shuffle_result=None,
                 max_batch_queue_size: int = 0,
                 seed: int = 0,
                 num_workers: Optional[int] = None,
                 queue_name: str = "MultiQueue",
                 mesh=None,
                 data_axis: str = "data",
                 prefetch_size: int = 2,
                 device_put: bool = True,
                 start_epoch: int = 0,
                 stack_features: bool = False,
                 cast_at_map: bool = True,
                 reduce_transform=None,
                 persistent_prefetch: bool = True,
                 file_cache="auto",
                 max_inflight_bytes: Optional[int] = None,
                 spill_dir: Optional[str] = None,
                 device_rebatch="auto",
                 max_device_input_bytes: int = 1 << 30,
                 max_device_table_bytes: Optional[int] = None,
                 runtime_policy: Optional[dict] = None):
        (self._feature_columns, self._feature_shapes, self._feature_types,
         self._label_column, self._label_shape, self._label_type) = (
             _normalize_jax_data_spec(feature_columns, feature_shapes,
                                      feature_types, label_column,
                                      label_shape, label_type))
        if stack_features:
            if len(set(self._feature_types)) != 1:
                raise ValueError(
                    "stack_features requires identical feature dtypes, got "
                    f"{self._feature_types}")
            for shape in self._feature_shapes:
                if shape is not None and tuple(shape) != (1,):
                    raise ValueError(
                        "stack_features requires scalar (or (1,)-shaped) "
                        f"feature columns, got shape {shape}")
        self._stack_features = stack_features
        # Resolve/validate device_rebatch BEFORE constructing the underlying
        # dataset: the rank-0 path below launches the named queue and the
        # background shuffle, which must not leak if this config is invalid.
        def _mesh_divisible():
            if mesh is None:
                return True
            n_data = int(np.prod([s for n, s in zip(mesh.axis_names,
                                                    mesh.devices.shape)
                                  if n == data_axis] or [1]))
            return batch_size % max(1, n_data) == 0

        # Runtime health/degradation policy (runtime/policy.py): explicit
        # runtime_policy kwargs > RSDL_JAX_DATASET_* env > RSDL_* env >
        # library defaults. RSDL_DEVICE_REBATCH=0 makes the per-batch
        # path the library default for every "auto" construction.
        from ray_shuffling_data_loader_tpu.runtime import (policy as
                                                           rt_policy)
        self._runtime_policy = rt_policy.resolve_all(
            "jax_dataset", **(runtime_policy or {}))
        if (device_rebatch == "auto"
                and self._runtime_policy["device_rebatch"] is False):
            device_rebatch = False
        device_rebatch_auto = device_rebatch == "auto"
        if device_rebatch == "auto":
            # Bulk transfers need the persistent producer (the table path
            # lives there), a real device_put (otherwise there is nothing to
            # gain and tests expect host arrays), and — with a mesh — a
            # batch size divisible by the data axis (chunks transfer with
            # the batch axis sharded). On a CPU backend the "transfer" is a
            # host memcpy, so bulk moves only add copies — keep the
            # per-batch path there.
            device_rebatch = (persistent_prefetch and device_put
                              and _mesh_divisible())
            if device_rebatch:
                import jax
                device_rebatch = jax.default_backend() != "cpu"
        elif device_rebatch:
            if not persistent_prefetch or not device_put:
                raise ValueError(
                    "device_rebatch requires persistent_prefetch=True and "
                    "device_put=True")
            if not _mesh_divisible():
                raise ValueError(
                    "device_rebatch with a mesh requires batch_size "
                    "divisible by the data-axis device count (bulk chunks "
                    "transfer with the batch axis sharded)")
        map_transform = None
        if cast_at_map and label_column is not None:
            map_transform = make_cast_transform(
                self._feature_columns, self._feature_types,
                self._label_column, self._label_type)
        self._dataset = ShufflingDataset(
            filenames, num_epochs, num_trainers, batch_size, rank,
            drop_last=drop_last, num_reducers=num_reducers,
            max_concurrent_epochs=max_concurrent_epochs,
            batch_queue=batch_queue, shuffle_result=shuffle_result,
            max_batch_queue_size=max_batch_queue_size, seed=seed,
            num_workers=num_workers, queue_name=queue_name,
            start_epoch=start_epoch, map_transform=map_transform,
            reduce_transform=reduce_transform, file_cache=file_cache,
            max_inflight_bytes=max_inflight_bytes, spill_dir=spill_dir)
        self._dataset.hold_epoch_log = True  # this layer logs it
        self._mesh = mesh
        self._data_axis = data_axis
        self._prefetch_size = max(1, prefetch_size)
        self._device_put = device_put
        if max_device_table_bytes is None:
            # Keep TOTAL device-resident input bytes at the documented
            # budget regardless of queue depth: the pipeline holds at most
            # ~(prefetch_size + 2) chunks at once (ADVICE r3).
            max_device_table_bytes = max(
                1, max_device_input_bytes // (self._prefetch_size + 2))
        # The watchdog supervises only the bulk path (there is nothing to
        # time out per-batch: each transfer is small and the consumer's
        # queue.get is already interruptible via close()).
        wd = None
        if self._runtime_policy["watchdog"] and bool(device_rebatch):
            from ray_shuffling_data_loader_tpu.runtime import (watchdog as
                                                               rt_watchdog)
            wd = rt_watchdog.get_watchdog()
        self._converter = _BatchConverter(
            self._feature_columns, self._feature_shapes, self._feature_types,
            self._label_column, self._label_shape, self._label_type,
            stack_features, mesh, data_axis, device_put,
            device_rebatch=bool(device_rebatch),
            device_rebatch_auto=device_rebatch_auto,
            max_table_bytes=max_device_table_bytes,
            watchdog=wd,
            bulk_transfer_deadline_s=(
                self._runtime_policy["bulk_transfer_deadline_s"]),
            stall_action=self._runtime_policy["stall_action"])
        # Close the delivery-latency loop at the device boundary: the
        # probe observes delivered->device and birth->device per source
        # table and refreshes this rank's freshness gauge (the
        # freshness_stall detector's series).
        self._converter.latency_probe = rt_latency.LatencyProbe(
            queue=str(self._dataset.rank))
        self._converter.double_buffer = bool(
            self._runtime_policy["device_double_buffer"])
        self.batch_wait_stats = BatchWaitStats()
        # Persistent-prefetch state (one producer thread for ALL epochs).
        self._persistent = persistent_prefetch
        self._lock = threading.Lock()
        self._out: Optional[_queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._pending_skips: dict = {}   # epoch -> skip_batches (pre-start)
        self._scheduled_skips: dict = {}  # epoch -> skip already producer-side
        self._started_epochs: set = set()  # epochs the producer entered
        self._consumer_skip = 0          # device batches to drop client-side
        self._next_epoch = self._dataset.start_epoch  # next to consume
        self._epoch_set = False          # set_epoch called since last iter
        self._closed = False             # close() is terminal
        self._active_gen = None          # live persistent-epoch generator
        self._spans = _ConsumerSpans()   # consumer-thread telemetry

    def set_epoch(self, epoch: int, skip_batches: int = 0) -> None:
        span = self._spans.span_begin("set_epoch", epoch)
        try:
            self._set_epoch(epoch, skip_batches)
        finally:
            self._spans.span_end(span, part="set_epoch")

    def _set_epoch(self, epoch: int, skip_batches: int) -> None:
        if not self._persistent:
            self._dataset.set_epoch(epoch, skip_batches=skip_batches)
            return
        if skip_batches < 0:
            raise ValueError(f"skip_batches must be >= 0, got {skip_batches}")
        # Validate BEFORE destroying any in-flight iterator, so an illegal
        # call leaves the current epoch resumable. A suspended (mid-epoch)
        # iterator counts its epoch as consumed once we finalize it below,
        # so the expected argument is one past it.
        import inspect
        gen_state = (inspect.getgeneratorstate(self._active_gen)
                     if self._active_gen is not None else None)
        if gen_state == inspect.GEN_RUNNING:
            raise RuntimeError(
                "set_epoch called while another thread is iterating "
                "this dataset")
        expected = (self._next_epoch + 1
                    if gen_state == inspect.GEN_SUSPENDED
                    else self._next_epoch)
        if epoch != expected:
            raise ValueError(
                f"persistent_prefetch requires sequential epochs: expected "
                f"set_epoch({expected}), got set_epoch({epoch}). "
                "Construct with persistent_prefetch=False for out-of-order "
                "epoch iteration.")
        if self._active_gen is not None:
            # Finalize a previous epoch's iterator NOW (a consumer that
            # broke out mid-epoch and moved on without close()-ing the
            # iterator must not depend on GC timing): closing it runs the
            # generator's finally, which marks that epoch consumed.
            try:
                self._active_gen.close()
            except ValueError:
                # TOCTOU with the state probe above: the other thread
                # resumed the generator in between.
                raise RuntimeError(
                    "set_epoch called while another thread is iterating "
                    "this dataset")
            self._active_gen = None
        assert epoch == self._next_epoch, (epoch, self._next_epoch)
        # The lock guards only the skip MAPS shared with the producer
        # thread; _consumer_skip is consumer-thread-owned (set_epoch and
        # the iterator run on the same thread) and is assigned outside.
        with self._lock:
            if epoch in self._started_epochs:
                # Producer already ran (or is running) this epoch's convert+
                # transfer; drop the first N finished batches client-side —
                # minus whatever a previous set_epoch call for this epoch
                # already had the producer skip at the Arrow level.
                already = self._scheduled_skips.get(epoch, 0)
                consumer_skip = max(0, skip_batches - already)
            else:
                # Cheap path: the producer will skip at the Arrow-slice
                # level, before any conversion or transfer. Keep the two
                # maps in lockstep so a repeated/reduced skip request
                # neither double-drops nor leaves a stale pending skip.
                if skip_batches:
                    self._pending_skips[epoch] = skip_batches
                else:
                    self._pending_skips.pop(epoch, None)
                self._scheduled_skips[epoch] = skip_batches
                consumer_skip = 0
        self._consumer_skip = consumer_skip
        self._epoch_set = True

    @property
    def batch_size(self) -> int:
        return self._dataset.batch_size

    @property
    def device_rebatch(self) -> bool:
        """Whether batches currently travel the bulk path (whole chunks
        transferred, batches carved on device). Resolved at construction;
        a watchdog stall under ``stall_action="degrade"`` clears it."""
        return self._converter.device_rebatch

    @property
    def fallback_engaged(self) -> bool:
        """True once a bulk-path stall dropped this dataset to per-batch
        transfers."""
        return self._converter.fallback_engaged

    @property
    def seed(self) -> int:
        return self._dataset.seed

    @property
    def num_epochs(self) -> Optional[int]:
        """Epoch count; None means unbounded streaming consumption."""
        return self._dataset.num_epochs

    def _convert(self, table: pa.Table):
        return self._converter.convert(table)

    def _timed_get(self, out: "_queue.Queue", epoch: Optional[int],
                   first: bool):
        """The consumer's get of the next item, under the ``batch_wait``
        span (``rsdl.feed.queue_get``; an epoch's first marked, and a
        part of the turnover). Returns the item and the closed span.
        Durations are on the recorder's own clock (``time.monotonic``),
        ``batch_wait_stats``' too."""
        wait_start = time.monotonic()
        span = self._spans.span_begin("batch_wait", epoch,
                                      **({"first": True} if first else {}))
        try:
            item = out.get()
        finally:
            # The wait is not the consumer's own work: it stays out of
            # the wall/CPU counters.
            self._spans.span_end(span, part="first_get" if first else None,
                                 counted=False)
        self.batch_wait_stats.record(time.monotonic() - wait_start)
        return item, span

    def _transfer(self, arrays_label):
        return self._converter.transfer(arrays_label)

    def __iter__(self) -> Iterator[Tuple[List[Any], Any]]:
        """Yield ``(features, label)`` device batches.

        A background thread runs convert+device_put ``prefetch_size`` batches
        ahead; ``jax.device_put`` is async (returns before the copy lands),
        so the host->device DMA for batch N+1 overlaps the consumer's
        compute on batch N. With ``persistent_prefetch`` (default) that
        thread lives for ALL epochs: when epoch N's tables run out it rolls
        straight into epoch N+1, so the consumer's first batch of the new
        epoch is typically already on device.
        """
        if self._persistent:
            gen = self._iter_persistent()
            self._active_gen = gen
            return gen
        return self._iter_single_epoch()

    # -- persistent (cross-epoch) producer ---------------------------------

    def _iter_persistent(self) -> Iterator[Tuple[List[Any], Any]]:
        if self._closed:
            raise RuntimeError(
                "JaxShufflingDataset was closed; the persistent producer "
                "cannot restart (it has already consumed the shuffle "
                "queue). Construct a new dataset to iterate again.")
        if not self._epoch_set:
            raise ValueError(
                "You must set the epoch on this dataset via set_epoch() at "
                "the beginning of each epoch, before iterating over this "
                "dataset (e.g. via enumerate(ds)).")
        self._epoch_set = False
        epoch = self._next_epoch
        if self._thread is None:
            import weakref
            self._out = _queue.Queue(maxsize=self._prefetch_size)
            # The producer references the underlying ShufflingDataset and
            # converter but NOT self — so a wrapper dropped without close()
            # is garbage-collected and this finalizer releases the thread
            # (and its device-resident buffered batches).
            self._thread = threading.Thread(
                target=_persistent_producer,
                args=(self._dataset, self._converter, self._out, self._stop,
                      self._lock, self._pending_skips, self._started_epochs),
                daemon=True, name="rsdl-jax-prefetch")
            weakref.finalize(self, _release_producer, self._stop, self._out)
            self._thread.start()
        spans = self._spans
        # The last epoch has no turnover to wait for: its line is logged
        # at its end. An earlier epoch's is held for the turnover's split.
        last_epoch = (self._dataset.num_epochs is not None
                      and epoch >= self._dataset.num_epochs - 1)
        entered_at = rt_telemetry.stamp()  # this next() began here
        first_get = True
        try:
            while True:
                item, get_span = self._timed_get(self._out, epoch, first_get)
                first_get = False
                if isinstance(item, BaseException):
                    raise item
                kind, item_epoch, payload = item
                if item_epoch < epoch:
                    # Remnants of an epoch abandoned mid-iteration; batches
                    # were converted in vain but correctness needs them gone.
                    continue
                assert item_epoch == epoch, (item_epoch, epoch)
                if kind == "end":
                    if not last_epoch:
                        spans.turnover_begin(epoch, entered_at, get_span)
                    end_span = spans.span_begin("epoch_end", epoch)
                    try:
                        rt_telemetry.epoch_complete(
                            epoch, source="jax", hold_log=not last_epoch)
                    finally:
                        spans.span_end(end_span, part="epoch_end")
                    break
                if kind == "table":
                    # Bulk device table: carve batches on-device. Later
                    # batches of the same item count as zero wait —
                    # accurate: they are already in HBM. The FIRST carve of
                    # each item is watchdog-supervised (it dispatches the
                    # jitted slicer — the carve half of the bulk path's
                    # liveness contract); a deadline miss files a stall
                    # and, under "degrade", stops the producer sending
                    # further bulk items.
                    dev_table, n_batches = payload
                    start = 0
                    if self._consumer_skip:
                        start = min(self._consumer_skip, n_batches)
                        self._consumer_skip -= start
                    bs = self._dataset.batch_size
                    wd = self._converter.watchdog
                    for b in range(start, n_batches):
                        if b > start:
                            self.batch_wait_stats.record(0.0)
                            rt_telemetry.observe_batch_wait(epoch)
                        carve_span = spans.span_begin("carve", epoch, batch=b)
                        try:
                            if b == start and wd is not None:
                                with wd.watch(
                                        "jax_dataset.bulk_carve",
                                        deadline_s=(
                                            self._converter
                                            .bulk_transfer_deadline_s),
                                        on_stall=(self._converter
                                                  ._on_bulk_stall)):
                                    batch = self._converter.slice_batch(
                                        dev_table, b, bs)
                            else:
                                batch = self._converter.slice_batch(
                                    dev_table, b, bs)
                        finally:
                            spans.span_end(carve_span, part="first_carve")
                        spans.turnover_end()
                        # From the yield to the consumer's next next():
                        # the consumer's own work, the train_step stage of
                        # the bottleneck decomposition.
                        step_span = rt_telemetry.span_begin("train_step",
                                                            epoch=epoch)
                        try:
                            yield batch
                        finally:
                            rt_telemetry.span_end(step_span)
                        entered_at = rt_telemetry.stamp()
                    continue
                if self._consumer_skip:
                    self._consumer_skip -= 1
                    continue
                spans.turnover_end()
                step_span = rt_telemetry.span_begin("train_step",
                                                    epoch=epoch)
                try:
                    yield payload
                finally:
                    rt_telemetry.span_end(step_span)
                entered_at = rt_telemetry.stamp()
        finally:
            # Runs on normal completion AND on mid-epoch abandonment
            # (GeneratorExit from iterator.close() / going out of scope):
            # an abandoned epoch counts as consumed — the producer has
            # already pulled its batches off the shuffle queue — so the
            # legal next call is set_epoch(epoch + 1). Leftover in-flight
            # batches of this epoch are dropped by the item_epoch < epoch
            # guard above. A leftover skip must not eat the next epoch.
            self._consumer_skip = 0
            self._next_epoch = epoch + 1
            # Break the wrapper->generator->frame->wrapper reference
            # cycle: with it intact, a finished epoch's last device batch
            # (held by this frame) is only released at a full cycle
            # collection — the delayed-free class the release-event budget
            # wait (runtime/release.py) exists to eliminate.
            self._active_gen = None

    def close(self) -> None:
        """Stop the persistent producer and drop buffered device batches.

        Only needed when abandoning the dataset before its last epoch was
        fully iterated; the producer exits on its own after the final
        epoch. Idempotent, and terminal: iterating after close() raises
        (the producer has already drained the underlying shuffle queue, so
        a restarted one would replay or block forever).
        """
        self._closed = True
        self._stop.set()
        rt_telemetry.flush_epoch_log()
        if self._thread is not None:
            # Join BEFORE draining: the producer notices the stop event
            # within one bounded-put poll (0.1s) and exits, so nothing
            # refills the queue between the drain and the poison below.
            self._thread.join(timeout=5)
            self._thread = None
        if self._out is not None:
            try:
                while True:
                    self._out.get_nowait()
            except _queue.Empty:
                pass
            try:
                # A live consumer — blocked in the iterator's get() or
                # about to call next() — gets this instead of hanging on
                # the drained queue or ending its epoch loop with silent
                # truncation. (Deliberately no generator.close() here: its
                # effect would depend on whether the consumer happened to
                # be suspended or blocked at this instant; the poison item
                # raises consistently in both timings.)
                self._out.put_nowait(
                    RuntimeError("JaxShufflingDataset was closed while the "
                                 "epoch was still being iterated"))
            except _queue.Full:
                pass  # unreachable after the join+drain above
        self._active_gen = None

    # -- per-epoch producer (persistent_prefetch=False) --------------------

    def _iter_single_epoch(self) -> Iterator[Tuple[List[Any], Any]]:
        out: _queue.Queue = _queue.Queue(maxsize=self._prefetch_size)
        SENTINEL = object()
        stop = threading.Event()

        def _put(item) -> bool:
            """Bounded put that gives up when the consumer is gone."""
            while not stop.is_set():
                try:
                    out.put(item, timeout=0.1)
                    return True
                except _queue.Full:
                    continue
            return False

        def producer():
            epoch = getattr(self._dataset, "_epoch", None)
            try:
                if self._converter.double_buffer:
                    # Double-buffered staging (same shape as the
                    # persistent producer's): transfer N overlaps
                    # convert N+1, delivered FIFO.
                    if not _produce_epoch_batches_staged(
                            self._dataset, self._converter, epoch,
                            lambda item: _put(item[2])):
                        return
                else:
                    for table in self._dataset:
                        with rt_telemetry.span("convert", epoch=epoch):
                            arrays = self._convert(table)
                        batch = _timed_transfer(epoch, self._transfer,
                                                arrays)
                        if not _put(batch):
                            return
                _put(SENTINEL)
            except BaseException as e:  # noqa: BLE001 - forwarded to consumer
                _put(e)

        thread = threading.Thread(target=producer, daemon=True,
                                  name="rsdl-jax-prefetch")
        thread.start()
        epoch = getattr(self._dataset, "_epoch", None)
        spans = self._spans
        entered_at = rt_telemetry.stamp()
        first_get = True
        try:
            while True:
                item, get_span = self._timed_get(out, epoch, first_get)
                first_get = False
                if item is SENTINEL:
                    if epoch is not None:
                        # Epochs come in any order here, so the verdict
                        # line is logged now and a turnover's split on a
                        # line of its own.
                        spans.turnover_begin(epoch, entered_at, get_span)
                        end_span = spans.span_begin("epoch_end", epoch)
                        try:
                            rt_telemetry.epoch_complete(epoch, source="jax")
                        finally:
                            spans.span_end(end_span, part="epoch_end")
                    break
                if isinstance(item, BaseException):
                    raise item
                spans.turnover_end()
                step_span = rt_telemetry.span_begin("train_step",
                                                    epoch=epoch)
                try:
                    yield item
                finally:
                    rt_telemetry.span_end(step_span)
                entered_at = rt_telemetry.stamp()
        finally:
            # Consumer done or abandoned mid-epoch: release the producer
            # (it would otherwise block forever on the bounded queue,
            # pinning device-resident batches) and drop buffered batches.
            stop.set()
            try:
                while True:
                    out.get_nowait()
            except _queue.Empty:
                pass
            thread.join(timeout=5)
