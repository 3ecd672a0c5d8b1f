"""Multi-host distributed shuffle: per-host map/reduce + DCN all-to-all.

The reference scales across nodes by letting Ray place map/reduce tasks
anywhere on the cluster and shipping chunks through the plasma object store
(reference: shuffle.py:174-187, SURVEY.md §2.3). The TPU-native topology is
SPMD: one loader process per TPU-VM host (``jax.distributed``-style world),
each host mapping its contiguous shard of the global file list and owning a
contiguous shard of the global reducers. Only map->reduce chunks cross
hosts — an all-to-all over the host network / DCN carried by
``parallel.transport.TcpTransport``. Reducer ownership is aligned with the
reference's reducer->trainer routing (``np.array_split`` contiguous groups,
reference: shuffle.py:188-189), so reduce->trainer traffic is always
host-local.

Determinism contract: map and reduce PRNG streams are keyed by **global**
file and reducer indices (ops/partition.py), so for a given
``(seed, num_reducers, num_trainers)`` the batches global trainer ``t``
consumes are bit-identical whether the shuffle ran on one host or many —
the property test_distributed.py asserts, and what makes checkpoint/resume
topology-independent.
"""

from __future__ import annotations

import functools
import timeit
from typing import Dict, List, Optional, Sequence, Tuple

import pyarrow as pa

from ray_shuffling_data_loader_tpu import executor as ex
from ray_shuffling_data_loader_tpu import multiqueue as mq
# Not ``from ray_shuffling_data_loader_tpu import shuffle``: the package
# __init__ rebinds that attribute to the shuffle() function.
import importlib
sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")
from ray_shuffling_data_loader_tpu.dataset import batch_consumer as queue_batch_consumer
from ray_shuffling_data_loader_tpu.ops import partition as ops
from ray_shuffling_data_loader_tpu.parallel.transport import TcpTransport
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)


def serialize_table(table: pa.Table) -> pa.Buffer:
    """Arrow IPC stream as a ``pa.Buffer`` (C++ writer; the buffer goes to
    the socket via the buffer protocol — no to_pybytes() memcpy on the
    shuffle's hottest cross-host path)."""
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as writer:
        writer.write_table(table)
    return sink.getvalue()


def deserialize_table(payload: bytes) -> pa.Table:
    with pa.ipc.open_stream(pa.BufferReader(payload)) as reader:
        return reader.read_all()


class ShardPlan:
    """Static partition of files, reducers, and trainers across hosts.

    - Global trainer ``t = host * trainers_per_host + local_rank``.
    - Reducer groups: ``contiguous_splits(range(num_reducers), num_trainers)``
      — exactly the reference's reducer->trainer routing
      (reference: shuffle.py:188-189) — and host ``h`` owns the union of its
      trainers' groups (a contiguous reducer range).
    - File shard: ``contiguous_splits(range(num_files), world)``.
    """

    def __init__(self, num_files: int, num_reducers: int, world: int,
                 trainers_per_host: int = 1):
        if world < 1 or trainers_per_host < 1:
            raise ValueError("world and trainers_per_host must be >= 1")
        self.world = world
        self.trainers_per_host = trainers_per_host
        # The STATIC shard plan is frozen at launch by contract (the
        # trainer count never changes under elasticity — membership/
        # resizes reducer placement, not trainer topology).
        # rsdl-lint: disable=fixed-world-assumption
        self.num_trainers = world * trainers_per_host
        self.num_files = num_files
        self.num_reducers = num_reducers
        self.file_shards: List[List[int]] = ops.contiguous_splits(
            list(range(num_files)), world)
        trainer_groups = ops.contiguous_splits(
            list(range(num_reducers)), self.num_trainers)
        self.trainer_reducers: List[List[int]] = trainer_groups
        # reducer -> owning host, via owning trainer.
        self._reducer_host = {}
        for t, group in enumerate(trainer_groups):
            for r in group:
                self._reducer_host[r] = t // trainers_per_host
        # file -> owning host, O(1) (resolved once per (file, reducer) pair
        # per epoch on the reduce hot path).
        self._file_host = [0] * num_files
        for h, shard in enumerate(self.file_shards):
            for f in shard:
                self._file_host[f] = h

    def file_host(self, file_index: int) -> int:
        if not 0 <= file_index < self.num_files:
            raise ValueError(f"file index {file_index} out of range")
        return self._file_host[file_index]

    def reducer_host(self, reducer_index: int) -> int:
        return self._reducer_host[reducer_index]

    def local_files(self, host: int) -> List[int]:
        return self.file_shards[host]

    def local_trainers(self, host: int) -> List[int]:
        base = host * self.trainers_per_host
        return list(range(base, base + self.trainers_per_host))

    def local_reducers(self, host: int) -> List[int]:
        out: List[int] = []
        for t in self.local_trainers(host):
            out.extend(self.trainer_reducers[t])
        return out


def _map_task(filename: str, global_file_index: int, num_reducers: int,
              seed: int, epoch: int, plan: ShardPlan,
              transport: TcpTransport, stats_collector,
              map_transform=None,
              file_cache=None) -> Dict[int, "sh.LazyChunk"]:
    """Map one local file, ship remote reducers' chunks, keep local ones.

    Remote chunks are materialized (gathered) only to cross the wire and
    leave immediately (sendall releases the GIL); host-local chunks stay
    lazy index arrays so the local reduce can run its single fused gather —
    the distributed analog of Ray's per-slice multi-return fetch
    (reference: shuffle.py:174-176).
    """
    shard = sh.shuffle_map(filename, num_reducers, seed, epoch,
                           global_file_index, stats_collector, map_transform,
                           file_cache)
    local: Dict[int, sh.LazyChunk] = {}
    for reducer_index, chunk in enumerate(shard):
        owner = plan.reducer_host(reducer_index)
        if owner == transport.host_id:
            local[reducer_index] = chunk
        else:
            # Fused-pipeline shards yield already-materialized tables;
            # legacy shards yield lazy chunks gathered here.
            payload = (chunk if isinstance(chunk, pa.Table)
                       else chunk.materialize())
            transport.send(owner, (epoch, reducer_index, global_file_index),
                           serialize_table(payload))
    return local


def _reduce_task(reducer_index: int, seed: int, epoch: int,
                 plan: ShardPlan, transport: TcpTransport,
                 local_map_refs: Dict[int, ex.TaskRef],
                 stats_collector, reduce_transform=None,
                 spill_manager=None, gather_threads=None) -> pa.Table:
    """Collect this reducer's chunk from every global file, then
    concat + seeded permute (global-index RNG => topology-independent)."""
    # The whole reduce task body under one span, as on one host
    # (shuffle._reduce_task): the receive from the other hosts included.
    with rt_telemetry.span("reduce_gather", epoch=epoch, task=reducer_index):
        chunks: List = []  # LazyChunk (local) or pa.Table (remote)
        for file_index in range(plan.num_files):
            src = plan.file_host(file_index)
            if src == transport.host_id:
                chunks.append(local_map_refs[file_index].result()[reducer_index])
            else:
                payload = transport.recv(src, (epoch, reducer_index, file_index))
                chunks.append(deserialize_table(payload))
        shuffled = sh.shuffle_reduce(reducer_index, seed, epoch, chunks,
                                     stats_collector, reduce_transform,
                                     gather_threads)
    return sh.account_and_maybe_spill(shuffled, spill_manager,
                                      epoch=epoch, task=reducer_index,
                                      seed=seed)


def shuffle_epoch_distributed(epoch: int,
                              filenames: Sequence[str],
                              batch_consumer: sh.BatchConsumer,
                              plan: ShardPlan,
                              transport: TcpTransport,
                              pool: ex.Executor,
                              seed: int,
                              trial_start: float,
                              stats_collector=None,
                              map_transform=None,
                              file_cache=None,
                              reduce_transform=None,
                              spill_manager=None,
                              concurrent_epochs: int = 2) -> List[ex.TaskRef]:
    """One epoch on this host: map local files, reduce owned reducers,
    feed local trainers. Returns refs whose completion implies every
    cross-host send of this host's chunks has finished."""
    if stats_collector is not None:
        stats_collector.epoch_start(epoch)
    local_file_indices = plan.local_files(transport.host_id)
    map_refs: Dict[int, ex.TaskRef] = {
        fi: pool.submit(_map_task, filenames[fi], fi, plan.num_reducers,
                        seed, epoch, plan, transport, stats_collector,
                        map_transform, file_cache)
        for fi in local_file_indices
    }
    # submit_once: a reduce consumes transport messages exactly once, so a
    # retry would block on already-consumed tags until the recv timeout
    # and mask the original error. Maps MAY retry (duplicate sends are
    # dropped by the receiving transport).
    local_reducers = plan.local_reducers(transport.host_id)
    # Loopback worlds (tests, single-machine emulation)
    # run every "host" on this one machine — split the cores; a real
    # deployment owns its cores per host. The driver's epoch throttle keeps
    # up to ``concurrent_epochs`` epochs' reducers in flight.
    loopback = all(host in ("127.0.0.1", "localhost")
                   for host, _ in transport.addresses)
    gather_threads = sh.derive_gather_threads(
        max(1, concurrent_epochs) * len(local_reducers), pool.num_workers,
        host_share=transport.world if loopback else 1)
    reduce_refs: Dict[int, ex.TaskRef] = {
        r: pool.submit_once(_reduce_task, r, seed, epoch, plan, transport,
                            map_refs, stats_collector, reduce_transform,
                            spill_manager, gather_threads)
        for r in local_reducers
    }
    for local_rank, trainer in enumerate(plan.local_trainers(transport.host_id)):
        refs = [reduce_refs[r] for r in plan.trainer_reducers[trainer]]
        sh.consume(local_rank, batch_consumer, trial_start, stats_collector,
                   epoch, refs)
        batch_consumer(local_rank, epoch, None)
    # Map refs are included so the epoch drain also guarantees this host's
    # outbound chunks were sent even for reducers it does not own.
    return list(reduce_refs.values()) + list(map_refs.values())


def shuffle_distributed(filenames: Sequence[str],
                        batch_consumer: sh.BatchConsumer,
                        num_epochs: int,
                        num_reducers: int,
                        transport: TcpTransport,
                        trainers_per_host: int = 1,
                        max_concurrent_epochs: int = 2,
                        seed: int = 0,
                        num_workers: Optional[int] = None,
                        pool: Optional[ex.Executor] = None,
                        start_epoch: int = 0,
                        map_transform=None,
                        file_cache="auto",
                        reduce_transform=None,
                        task_retries: int = 0,
                        collect_stats: bool = False,
                        max_inflight_bytes=None,
                        spill_dir=None):
    """Multi-epoch pipelined distributed shuffle driver for ONE host.

    Run with the same arguments on every host of the world (SPMD); hosts
    synchronize only through the chunk exchange itself. The per-host epoch
    throttle (``max_concurrent_epochs``) mirrors the reference driver's
    (reference: shuffle.py:103-140); a host cannot run ahead unboundedly
    because its reducers block on every peer's chunks for the oldest
    in-flight epoch. Returns wall-clock duration in seconds, or — with
    ``collect_stats`` — THIS host's ``TrialStats`` (its local maps/
    reduces/consumes; aggregate across hosts by summing the per-host CSVs,
    the analog of the reference's per-node stage spans).

    ``max_inflight_bytes`` / ``spill_dir`` carry the single-host driver's
    memory-budget semantics per host (see ``shuffle.shuffle``): without a
    spill dir the budget drains older epochs before launching; with one,
    over-budget reducer outputs spill to disk. ``batch_consumer`` then
    receives refs that may resolve to ``spill.SpilledTable`` handles —
    ``ShufflingDataset`` unwraps them automatically; custom consumers
    should call ``spill.unwrap``.
    """
    from ray_shuffling_data_loader_tpu import stats as stats_mod

    if not 0 <= start_epoch <= num_epochs:
        raise ValueError(
            f"start_epoch {start_epoch} out of range [0, {num_epochs}]")
    plan = ShardPlan(len(filenames), num_reducers, transport.world,
                     trainers_per_host)
    stats_collector = None
    if collect_stats:
        if start_epoch:
            raise ValueError(
                "collect_stats with start_epoch > 0 is unsupported (stats "
                "collectors assume all epochs run)")
        stats_collector = stats_mod.TrialStatsCollector(
            num_epochs,
            num_maps=len(plan.local_files(transport.host_id)),
            num_reduces=len(plan.local_reducers(transport.host_id)),
            num_consumes=trainers_per_host)
        stats_collector.trial_start()
    file_cache, owns_file_cache = sh.resolve_file_cache(
        file_cache, num_epochs - start_epoch)

    # Same budget semantics as the single-host driver, per host.
    from ray_shuffling_data_loader_tpu.spill import make_budget_state
    _over_budget, spill_manager = make_budget_state(
        file_cache, max_inflight_bytes, spill_dir)
    start = timeit.default_timer()
    owns_pool = pool is None
    if pool is None:
        pool = ex.Executor(num_workers=num_workers,
                           task_retries=task_retries)
    try:
        from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
        in_progress: Dict[int, List[ex.TaskRef]] = {}
        # Epoch schedule comes from the plan layer (the static-epoch-
        # assumption contract): the multi-host driver iterates specs,
        # never the raw count.
        for spec in plan_ir.static_epoch_specs(filenames, num_epochs,
                                               start_epoch):
            epoch_idx = spec.epoch
            throttle_start = timeit.default_timer()
            # Budget pressure without a spill tier drains older epochs
            # before launching (single-host driver parity); with spilling
            # the launch proceeds and over-budget outputs go to disk. No
            # consumer-poll here: hosts must stay loosely in step, and a
            # long local stall would back-pressure every peer's reducers.
            while in_progress and (len(in_progress) >= max_concurrent_epochs
                                   or (spill_manager is None
                                       and _over_budget())):
                oldest = min(in_progress)
                refs = in_progress.pop(oldest)
                ex.wait(refs, num_returns=len(refs))
                for ref in refs:
                    ref.result()
            if stats_collector is not None:
                throttle_duration = timeit.default_timer() - throttle_start
                if throttle_duration > 1e-4:
                    stats_collector.throttle_done(epoch_idx,
                                                  throttle_duration)
            in_progress[epoch_idx] = shuffle_epoch_distributed(
                epoch_idx, filenames, batch_consumer, plan, transport, pool,
                seed, start, stats_collector=stats_collector,
                map_transform=map_transform,
                file_cache=file_cache, reduce_transform=reduce_transform,
                spill_manager=spill_manager,
                concurrent_epochs=min(max_concurrent_epochs,
                                      num_epochs - start_epoch))
        for epoch_idx in sorted(in_progress):
            refs = in_progress.pop(epoch_idx)
            ex.wait(refs, num_returns=len(refs))
            for ref in refs:
                ref.result()
    finally:
        if owns_pool:
            pool.shutdown()
        if owns_file_cache:
            # Same release point as the single-host driver: reducer
            # outputs are gathered copies, so drained refs mean the
            # decoded-cache scratch files have no remaining readers.
            file_cache.close()
        if spill_manager is not None:
            spill_manager.report()
        if owns_pool:
            # End-of-trial hygiene (same gating as the single-host
            # driver): release the pool's recycled recv buffers to the OS.
            from ray_shuffling_data_loader_tpu import native
            native.trim_freelist()
    if stats_collector is not None:
        stats_collector.trial_done()
        return stats_collector.get_stats()
    return timeit.default_timer() - start


def create_distributed_batch_queue_and_shuffle(
        filenames: Sequence[str],
        num_epochs: int,
        num_reducers: int,
        transport: TcpTransport,
        trainers_per_host: int = 1,
        max_concurrent_epochs: int = 2,
        max_batch_queue_size: int = 0,
        seed: int = 0,
        num_workers: Optional[int] = None,
        queue_name: Optional[str] = None,
        start_epoch: int = 0,
        map_transform=None,
        reduce_transform=None,
        task_retries: int = 0,
        file_cache="auto",
        max_inflight_bytes: Optional[int] = None,
        spill_dir: Optional[str] = None
) -> Tuple[mq.MultiQueue, ex.TaskRef]:
    """Host-local queue + background distributed shuffle driver.

    The returned ``(batch_queue, shuffle_result)`` plug straight into
    ``ShufflingDataset(batch_queue=..., shuffle_result=...)`` /
    ``JaxShufflingDataset`` with ``rank`` = local rank in
    ``[0, trainers_per_host)`` and ``num_trainers = trainers_per_host`` —
    the consumer-only pattern of the reference's distributed example
    (reference: dataset.py:17-51, ray_torch_shuffle.py:316-322).
    """
    from ray_shuffling_data_loader_tpu.dataset import make_failure_broadcaster
    batch_queue = mq.MultiQueue(num_epochs * trainers_per_host,
                                max_batch_queue_size, name=queue_name)
    consumer = functools.partial(queue_batch_consumer, batch_queue,
                                 trainers_per_host)
    on_failure = make_failure_broadcaster(batch_queue,
                                          num_epochs * trainers_per_host)
    driver_pool = ex.Executor(num_workers=1,
                              thread_name_prefix="rsdl-dist-driver")

    def _run():
        try:
            return shuffle_distributed(
                filenames, consumer, num_epochs, num_reducers, transport,
                trainers_per_host=trainers_per_host,
                max_concurrent_epochs=max_concurrent_epochs, seed=seed,
                num_workers=num_workers, start_epoch=start_epoch,
                map_transform=map_transform,
                reduce_transform=reduce_transform,
                task_retries=task_retries, file_cache=file_cache,
                max_inflight_bytes=max_inflight_bytes, spill_dir=spill_dir)
        except BaseException as e:  # noqa: BLE001 - forwarded to consumers
            on_failure(e)
            raise
        finally:
            driver_pool.shutdown(wait_for_tasks=False)

    return batch_queue, driver_pool.submit(_run)
