"""Framework-agnostic shuffling dataset API.

Capability parity with the reference's L3 dataset layer (reference:
dataset.py:17-230): a rank-aware iterable dataset where rank 0 creates the
batch queue and launches the multi-epoch shuffle while other ranks connect
by name; the iterator pops reducer-output refs from its per-(epoch, rank)
queue, materializes them, and re-chunks variable-size reducer outputs into
exact ``batch_size``-row batches with a leftover carry buffer, ``drop_last``
handling, a ``set_epoch`` misuse guard, and a join on the shuffle driver
after the final epoch.

TPU-native differences: batches are pyarrow Tables (zero-copy slices of
Arrow buffers) rather than pandas DataFrames; the shuffle driver is a
background thread task rather than a Ray remote task; and a ``seed``
parameter makes every epoch's order replayable. The JAX binding that turns
these tables into device-sharded ``jax.Array`` batches lives in
jax_dataset.py (L4).
"""

from __future__ import annotations

import functools
import timeit
from typing import Iterator, List, Optional, Sequence

import pyarrow as pa

import importlib

from ray_shuffling_data_loader_tpu import executor as ex
from ray_shuffling_data_loader_tpu import multiqueue as mq
from ray_shuffling_data_loader_tpu import spill

# Not ``from ray_shuffling_data_loader_tpu import shuffle``: the package
# __init__ rebinds that attribute to the shuffle() function, so attribute
# import resolves differently under ``python -m`` than under package import.
sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
from ray_shuffling_data_loader_tpu.runtime import latency as rt_latency
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.utils.config import default_num_reducers
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)

# Well-known queue name (reference: dataset.py:11 MULTIQUEUE_ACTOR_NAME).
MULTIQUEUE_NAME = "MultiQueue"


class ShuffleFailure:
    """Poison pill broadcast into every trainer queue when the shuffle
    driver dies, so consumers blocked on ``queue.get`` raise immediately
    instead of hanging forever (the reference has no equivalent; a dead
    shuffle task strands its trainers)."""

    __slots__ = ("error",)

    def __init__(self, error: BaseException):
        self.error = error


def make_failure_broadcaster(batch_queue: mq.MultiQueue,
                             num_queues: int):
    """``on_failure`` hook for ``run_shuffle_in_background``: put a
    :class:`ShuffleFailure` into every queue. A full bounded queue has
    items EVICTED to make room — the pipeline is dead, so pending batches
    are worthless, and without the marker a consumer that drains the
    buffered batches would block forever on the next ``get``."""

    def broadcast(error: BaseException) -> None:
        marker = ShuffleFailure(error)
        for queue_idx in range(num_queues):
            # Evict-and-retry loop, bounded in case a live consumer races
            # the eviction: each iteration frees one slot, so maxsize
            # iterations always suffice absent consumers.
            for _ in range(10_000):
                try:
                    batch_queue.put_nowait(queue_idx, marker)
                    break
                except mq.Full:
                    try:
                        batch_queue.get_nowait(queue_idx)
                    except mq.Empty:
                        continue  # consumer drained it; retry the put
                except RuntimeError:
                    break  # queue shut down — nobody left to notify

    return broadcast


def batch_consumer(queue: mq.MultiQueue,
                   num_trainers: int,
                   rank: int,
                   epoch: int,
                   batches: Optional[Sequence[ex.TaskRef]]) -> None:
    """Glue given to the shuffler: route reducer refs into the right queue
    (reference: dataset.py:213-224). ``None`` is the epoch-end sentinel.
    The queue index is a plan query (plan/ir.py) — the one home of the
    route-key arithmetic the ``lineage-outside-plan`` lint rule pins."""
    queue_idx = plan_ir.queue_index(epoch, rank, num_trainers)
    if batches is None:
        queue.put(queue_idx, None)
    else:
        queue.put_batch(queue_idx, list(batches))


def debug_batch_consumer(rank: int,
                         epoch: int,
                         batches: Optional[Sequence[ex.TaskRef]]) -> None:
    """Print-only consumer for eyeballing the shuffle alone
    (reference: dataset.py:227-230)."""
    num_batches = len(batches) if batches is not None else 0
    print(f"Received {num_batches} batches in consumer {rank}.")


def create_batch_queue_and_shuffle(
        filenames: Sequence[str],
        num_epochs: int,
        num_trainers: int,
        batch_size: int,
        max_concurrent_epochs: int,
        num_reducers: Optional[int] = None,
        max_batch_queue_size: int = 0,
        seed: int = 0,
        num_workers: Optional[int] = None,
        queue_name: str = MULTIQUEUE_NAME,
        start_epoch: int = 0,
        map_transform=None,
        reduce_transform=None,
        task_retries: int = 0,
        file_cache="auto",
        max_inflight_bytes: Optional[int] = None,
        spill_dir: Optional[str] = None):
    """Driver-mode helper: create the queue and start the shuffle before any
    trainer exists, so every rank can be a pure consumer
    (reference: dataset.py:17-51)."""
    if not 0 <= start_epoch <= num_epochs:
        raise ValueError(
            f"start_epoch {start_epoch} out of range [0, {num_epochs}]")
    batch_queue = mq.MultiQueue(
        num_epochs * num_trainers, max_batch_queue_size, name=queue_name)
    batch_queue.size(0)  # liveness probe kept for parity (dataset.py:106)
    if num_reducers is None:
        num_reducers = default_num_reducers(num_trainers)
    logger.info(
        "Starting shuffle: %d files, %d epochs, %d reducers, %d trainers",
        len(filenames), num_epochs, num_reducers, num_trainers)
    shuffle_result = sh.run_shuffle_in_background(
        filenames,
        functools.partial(batch_consumer, batch_queue, num_trainers),
        num_epochs,
        num_reducers,
        num_trainers,
        max_concurrent_epochs,
        seed=seed,
        num_workers=num_workers,
        collect_stats=False,
        start_epoch=start_epoch,
        map_transform=map_transform,
        reduce_transform=reduce_transform,
        task_retries=task_retries,
        file_cache=file_cache,
        max_inflight_bytes=max_inflight_bytes,
        spill_dir=spill_dir,
        on_failure=make_failure_broadcaster(batch_queue,
                                            num_epochs * num_trainers))
    return batch_queue, shuffle_result


def connect_remote_queue(target, **remote_kwargs):
    """One connector for every remote-queue topology: pass a single
    ``(host, port)`` and get a ``multiqueue_service.RemoteQueue``; pass
    a shard map (a ``plan.ir.ShardMap``, its dict, or its JSON — what
    ``runtime.supervisor.launch_supervised_queue_shards`` returns) and
    get a ``multiqueue_service.ShardedRemoteQueue`` that routes each
    per-rank stream to its serving shard. Either return value drops
    into ``ShufflingDataset(batch_queue=...)`` unchanged — consumer
    code does not know how many shards serve it."""
    from ray_shuffling_data_loader_tpu import multiqueue_service as svc
    if isinstance(target, tuple) and len(target) == 2 \
            and isinstance(target[0], str):
        return svc.RemoteQueue(target, **remote_kwargs)
    return svc.ShardedRemoteQueue(target, **remote_kwargs)


class ShufflingDataset:
    """Iterable dataset of exact-size shuffled batches
    (reference: dataset.py:53-210).

    Rank 0 creates the named queue and kicks off shuffling for up to
    ``max_concurrent_epochs`` epochs at construction; other ranks connect to
    the queue by name. Alternatively pass ``batch_queue=``/
    ``shuffle_result=`` from :func:`create_batch_queue_and_shuffle` and all
    ranks are pure consumers (the pattern the distributed trainer example
    uses, reference: dataset.py:84-85,133-135).

    Call :meth:`set_epoch` before each epoch's iteration; the iterator
    yields pyarrow Tables of exactly ``batch_size`` rows (final partial
    batch included unless ``drop_last``).
    """

    def __init__(self,
                 filenames: Sequence[str],
                 num_epochs: Optional[int],
                 num_trainers: int,
                 batch_size: int,
                 rank: int,
                 drop_last: bool = False,
                 num_reducers: Optional[int] = None,
                 max_concurrent_epochs: int = 2,
                 batch_queue: Optional[mq.MultiQueue] = None,
                 shuffle_result: Optional[ex.TaskRef] = None,
                 max_batch_queue_size: int = 0,
                 seed: int = 0,
                 num_workers: Optional[int] = None,
                 queue_name: str = MULTIQUEUE_NAME,
                 start_epoch: int = 0,
                 map_transform=None,
                 reduce_transform=None,
                 task_retries: int = 0,
                 file_cache="auto",
                 max_inflight_bytes: Optional[int] = None,
                 spill_dir: Optional[str] = None):
        if num_reducers is None:
            num_reducers = default_num_reducers(num_trainers)
        self._batch_size = batch_size

        self._owns_queue = False
        if batch_queue is None:
            if rank == 0 and num_epochs is None:
                # Unbounded (streaming) consumption is pure-consumer:
                # epochs are produced by a streaming runner or a
                # supervised queue server whose window schedule bounds
                # the queue count — this constructor cannot size a
                # queue for "forever".
                raise ValueError(
                    "num_epochs=None (unbounded streaming) requires a "
                    "batch_queue from the streaming serving plane; "
                    "rank 0 cannot launch a static shuffle without an "
                    "epoch count")
            if rank == 0:
                self._batch_queue, self._shuffle_result = (
                    create_batch_queue_and_shuffle(
                        filenames, num_epochs, num_trainers, batch_size,
                        max_concurrent_epochs, num_reducers,
                        max_batch_queue_size, seed=seed,
                        num_workers=num_workers, queue_name=queue_name,
                        start_epoch=start_epoch,
                        map_transform=map_transform,
                        reduce_transform=reduce_transform,
                        task_retries=task_retries,
                        file_cache=file_cache,
                        max_inflight_bytes=max_inflight_bytes,
                        spill_dir=spill_dir))
                self._owns_queue = True
            else:
                self._batch_queue = mq.MultiQueue(
                    0, name=queue_name, connect=True)
                self._shuffle_result = None
        else:
            self._batch_queue = batch_queue
            self._shuffle_result = shuffle_result

        if num_epochs is not None and not 0 <= start_epoch <= num_epochs:
            raise ValueError(
                f"start_epoch {start_epoch} out of range [0, {num_epochs}]")
        if num_epochs is None and start_epoch < 0:
            raise ValueError(
                f"start_epoch {start_epoch} must be >= 0")
        self._start_epoch = start_epoch
        self._num_epochs = num_epochs
        self._num_trainers = num_trainers
        self._rank = rank
        self._seed = seed
        self._skip_batches = 0
        self._epoch: Optional[int] = None
        # Set by a binding that ends the epoch itself (JaxShufflingDataset):
        # the epoch's log line then waits for that layer, which adds the
        # split of the turnover into the next epoch.
        self.hold_epoch_log = False
        # Guards against iterating without a fresh set_epoch
        # (reference: dataset.py:143-168).
        self._last_epoch: Optional[int] = None
        self._drop_last = drop_last
        # Delivery-latency plane (runtime/latency.py): the end-to-end
        # birth->delivered hop is observed HERE for in-process queues
        # (reducer output metadata -> consumer hand-off). Remote queue
        # clients see the wire stamps first and observe it themselves —
        # their `observes_delivery` marker keeps the hop single-counted.
        self._lat_observe = not getattr(self._batch_queue,
                                        "observes_delivery", False)
        self._lat_queue = str(rank)
        self._lat_anchors = rt_latency.ClockAnchors()

    @property
    def batch_size(self) -> int:
        return self._batch_size

    @property
    def seed(self) -> int:
        return self._seed

    @property
    def num_epochs(self) -> Optional[int]:
        """Epoch count of the trial; None means unbounded (streaming)."""
        return self._num_epochs

    @property
    def num_trainers(self) -> int:
        return self._num_trainers

    @property
    def rank(self) -> int:
        return self._rank

    @property
    def start_epoch(self) -> int:
        return self._start_epoch

    @property
    def drop_last(self) -> bool:
        return self._drop_last

    def set_epoch(self, epoch: int, skip_batches: int = 0) -> None:
        """Declare the epoch about to be iterated. Must be called before
        each epoch's iteration (reference: dataset.py:147-157).

        ``skip_batches`` drops the first N batches of the epoch as zero-copy
        Arrow slices — the cheap path for checkpoint resume (the rows are
        still shuffled/fetched, but never converted or transferred).
        """
        if epoch < self._start_epoch:
            raise ValueError(
                f"epoch {epoch} precedes start_epoch {self._start_epoch}; "
                "epochs before the resume point are never shuffled and "
                "iterating them would block forever")
        if skip_batches < 0:
            raise ValueError(f"skip_batches must be >= 0, got {skip_batches}")
        self._skip_batches = skip_batches
        self._epoch = epoch

    def iter_tables(self) -> Iterator[pa.Table]:
        """Yield this epoch's raw reducer tables (variable row counts).

        Handles everything the batch iterator needs below it: the set_epoch
        guard, the epoch's queue drain with sentinel/failure handling, the
        ``skip_batches`` row skip (applied here as whole-table drops and one
        zero-copy slice), and the end-of-trial shuffle join. The JAX binding
        consumes this directly in device-rebatch mode, where batch slicing
        happens on the accelerator instead of in Arrow.
        """
        if self._epoch is None or self._epoch == self._last_epoch:
            raise ValueError(
                "You must set the epoch on this dataset via set_epoch() at "
                "the beginning of each epoch, before iterating over this "
                "dataset (e.g. via enumerate(ds)).")

        skip_rows = self._skip_batches * self._batch_size  # rows, not batches
        to_skip = skip_rows
        self._skip_batches = 0
        queue_idx = plan_ir.queue_index(self._epoch, self._rank,
                                        self._num_trainers)
        # Positioned gets (multiqueue_service.RemoteQueue) return the
        # table's absolute row offset in the queue's stream. A replaying
        # queue legally restarts the stream mid-epoch (at the consumer's
        # last durable watermark), so a checkpoint-resume skip must be
        # absolute — "drop rows before position skip_rows" — not a count
        # of rows seen on THIS connection.
        get_positioned = getattr(self._batch_queue, "get_positioned", None)
        while True:
            # Epoch-tagged queue wait: this is where a consumer blocks
            # when the shuffle cannot keep up — the "queue_wait" stage
            # of the bottleneck decomposition (the queue layer's own
            # queue_get events have no epoch identity). Manual
            # begin/end span so a get() that dies still records the
            # time the consumer sat here (the span-unbalanced lint
            # rule pins the finally shape).
            wait_span = rt_telemetry.span_begin(
                "queue_wait", epoch=self._epoch, task=queue_idx)
            try:
                if get_positioned is not None:
                    ref, row_offset = get_positioned(queue_idx)
                else:
                    ref = self._batch_queue.get(queue_idx, block=True)
                    row_offset = None
            finally:
                rt_telemetry.span_end(wait_span)
            if ref is None:
                break
            if isinstance(ref, ShuffleFailure):
                raise RuntimeError(
                    "the shuffle driver died; no more batches are coming"
                ) from ref.error
            # In-process queues carry TaskRefs; remote queue clients
            # (multiqueue_service.py) deliver materialized tables. A
            # budget-spilled reducer output arrives as a lazy handle and
            # is memory-mapped back here (spill.py) — but only if any of
            # it survives the resume skip: a fully-skipped handle is
            # dropped unloaded (its finalizer unlinks the file).
            raw = ref.result() if hasattr(ref, "result") else ref
            if row_offset is not None:
                to_skip = max(0, skip_rows - row_offset)
            if to_skip and raw.num_rows <= to_skip:
                if row_offset is None:
                    to_skip -= raw.num_rows
                continue
            table: pa.Table = spill.unwrap(raw)
            if self._lat_observe:
                meta = table.schema.metadata
                birth = rt_latency.parse_stamp(
                    meta.get(rt_latency.BIRTH_META_KEY) if meta else None)
                if birth is not None:
                    age = self._lat_anchors.latency_s(birth)
                    rt_latency.observe_hop(
                        rt_latency.HOP_BIRTH_TO_DELIVERED,
                        self._lat_queue, age)
                    rt_latency.set_freshness(self._lat_queue, age)
            if to_skip:
                table = table.slice(to_skip)
                to_skip = 0
            yield table
            # Drop the consumed table before blocking on the next get:
            # this frame would otherwise pin it (delaying its ledger
            # release — the budget wait in shuffle.py wakes on that
            # release) for as long as the queue stays empty.
            ref = raw = table = None
        self._last_epoch = self._epoch
        # Epoch-complete hook: logs the one-line bottleneck verdict
        # (first completion wins — the JAX binding's consumer-side end
        # calls this too, whichever finishes first).
        rt_telemetry.epoch_complete(self._epoch, source="dataset",
                                    hold_log=self.hold_epoch_log)
        if (self._num_epochs is not None
                and self._epoch == self._num_epochs - 1
                and self._shuffle_result is not None):
            # Join the shuffle driver (reference: dataset.py:208-210), then
            # release the queue's name so a later trial in the same process
            # can reuse it.
            self._shuffle_result.result()
            self.shutdown()

    def __iter__(self) -> Iterator[pa.Table]:
        return slice_batches(self.iter_tables(), self._batch_size,
                             self._drop_last)

    def commit_consumed(self) -> None:
        """Tell a manual-ack batch queue that consumption so far is
        durable (``checkpoint.resume_iterator`` calls this after every
        checkpoint save). No-op for in-process queues and auto-ack
        remote queues."""
        commit = getattr(self._batch_queue, "commit", None)
        if commit is not None:
            commit()

    def shutdown(self) -> None:
        """Release the named queue if this dataset created it. Idempotent.

        The reference leaks its named actor until process exit; we free the
        name so back-to-back trials in one process work.
        """
        if self._owns_queue:
            self._batch_queue.shutdown()
            self._owns_queue = False


def slice_batches(tables: Iterator[pa.Table], batch_size: int,
                  drop_last: bool) -> Iterator[pa.Table]:
    """Exact-size re-batching over a stream of variable-size tables.

    The leftover carry buffer spans table boundaries (reference keeps a
    DataFrame buffer, dataset.py:170-202; we keep a list of zero-copy
    table slices and concat only when yielding). Shared by
    ``ShufflingDataset.__iter__`` and the JAX binding's per-batch fallback
    so their batch grids cannot diverge.
    """
    carry: List[pa.Table] = []
    carry_rows = 0
    for table in tables:
        offset = 0
        num_rows = table.num_rows
        # Top up the carry buffer to a full batch first.
        if carry_rows:
            need = batch_size - carry_rows
            take = min(need, num_rows)
            carry.append(table.slice(0, take))
            carry_rows += take
            offset = take
            if carry_rows == batch_size:
                # permissive promotion: the >2GiB fallback promotes
                # offsets PER REDUCER OUTPUT (shuffle.py), so one epoch
                # stream may legally mix large_* and 32-bit-offset
                # schemas and an unpromoted concat would raise
                # ArrowInvalid exactly in the huge-corpus regime.
                yield pa.concat_tables(carry, promote_options="permissive")
                carry = []
                carry_rows = 0
        # Yield full batches straight out of this table, zero-copy.
        while num_rows - offset >= batch_size:
            yield table.slice(offset, batch_size)
            offset += batch_size
        # Stash the tail.
        if offset < num_rows:
            carry.append(table.slice(offset))
            carry_rows += num_rows - offset
    if carry_rows and not drop_last:
        yield pa.concat_tables(carry, promote_options="permissive")


if __name__ == "__main__":
    # Smoke driver (reference: dataset.py:233-276): generate synthetic rows
    # locally, run a few epochs through the full pipeline, count batches.
    import argparse
    import tempfile
    import timeit

    from ray_shuffling_data_loader_tpu import data_generation as dg

    parser = argparse.ArgumentParser(description="ShufflingDataset smoke run")
    parser.add_argument("--num-rows", type=int, default=10**6)
    parser.add_argument("--num-files", type=int, default=10)
    parser.add_argument("--num-epochs", type=int, default=4)
    parser.add_argument("--num-reducers", type=int, default=8)
    parser.add_argument("--batch-size", type=int, default=50_000)
    parser.add_argument("--max-concurrent-epochs", type=int, default=2)
    args = parser.parse_args()

    with tempfile.TemporaryDirectory() as tmpdir:
        print(f"Generating {args.num_rows} rows over {args.num_files} files.")
        filenames, _ = dg.generate_data_local(args.num_rows, args.num_files,
                                              1, 0.0, tmpdir)
        print(f"Starting {args.num_epochs}-epoch consumption, "
              f"{args.num_reducers} reducers, 1 trainer.")
        start = timeit.default_timer()
        ds = ShufflingDataset(filenames,
                              args.num_epochs,
                              num_trainers=1,
                              batch_size=args.batch_size,
                              rank=0,
                              num_reducers=args.num_reducers,
                              max_concurrent_epochs=args.max_concurrent_epochs)
        for epoch in plan_ir.epoch_range(0, args.num_epochs):
            ds.set_epoch(epoch)
            rows = batches = 0
            for batch in ds:
                batches += 1
                rows += batch.num_rows
            assert rows == args.num_rows, (rows, args.num_rows)
            print(f"epoch {epoch}: {batches} batches, {rows} rows")
        duration = timeit.default_timer() - start
        total = args.num_epochs * args.num_rows
        print(f"Done: {total} rows in {duration:.2f}s "
              f"({total / duration:,.0f} rows/s)")
