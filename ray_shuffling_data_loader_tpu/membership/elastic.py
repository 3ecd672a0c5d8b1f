"""Elastic shuffle execution: resize-as-plan-rewrite over a live view.

The fixed-world runner assumes every rank that started an epoch finishes
it. This runner makes world composition an *input*: each epoch opens by
reading the :class:`membership.MembershipManager`'s current view, places
the (fixed) reducer set over the live ranks with
``plan_ir.reduce_placement``, and runs one worker per live rank. Because
every reducer output is a pure function of ``(seed, epoch, reducer)``
(``shuffle.recompute_reducer_output`` — the same lineage contract the
spill tier's corruption recovery uses), moving a reducer to a different
rank moves *where* it is computed, never *what* it contains: an elastic
run's merged stream is bit-identical to the fixed-world run's.

Shrink (``member_down`` mid-epoch): the dead rank's undelivered reducers
are re-placed onto the survivors (deterministic ``route_slices``
rebalance) and recomputed from lineage. A driver-side **delivery
ledger** keyed ``(epoch, reducer)`` makes delivery exactly-once — a
reducer the dead rank already delivered is never recomputed, and a
racing duplicate is dropped, so the stream has zero missed and zero
duplicated rows. Grow (``member_join``): the joined rank takes effect at
the next epoch boundary — the current epoch's placement is immutable, so
a join never causes replay.

The ``member_crash`` chaos site fires here, through
``MembershipManager.maybe_crash``, at the moment a rank's worker picks
up its next reducer — the mid-epoch kill the dryrun's elastic scene
drives.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

from ray_shuffling_data_loader_tpu.membership import MembershipManager
from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)


class ElasticShuffleRunner:
    """Run shuffle epochs over an elastic world.

    Args:
        filenames: epoch input files (identical across epochs; per-epoch
            reshuffle comes from the seed/epoch lineage, as everywhere
            else in the repo).
        num_reducers: the FIXED reducer count — elasticity moves
            placement, not partitioning, which is what keeps the merged
            stream bit-identical across resizes.
        seed: shuffle seed (lineage root).
        manager: the membership manager whose journaled view drives
            placement. ``maybe_crash`` is consulted per pickup so a
            ``member_crash:rankN`` chaos rule kills that rank mid-epoch.
    """

    def __init__(self, filenames: Sequence[str], num_reducers: int,
                 seed: int, manager: MembershipManager,
                 map_transform: Optional[Callable] = None,
                 reduce_transform: Optional[Callable] = None,
                 on_bad_file: str = "raise"):
        if num_reducers < 1:
            raise ValueError("num_reducers must be >= 1")
        self.filenames = list(filenames)
        self.num_reducers = int(num_reducers)
        self.seed = int(seed)
        self.manager = manager
        self.map_transform = map_transform
        self.reduce_transform = reduce_transform
        self.on_bad_file = on_bad_file
        #: Stats of the most recent :meth:`run_epoch`.
        self.last_stats: Dict[str, float] = {}

    # -- one epoch -----------------------------------------------------

    def run_epoch(self, epoch: int) -> List:
        """Run one epoch; returns reducer-indexed outputs (pa.Tables).

        Degraded completion: if a rank dies mid-epoch (detected here via
        the ``member_crash`` site, or already recorded in the view by an
        external failure detector), its undelivered reducers are
        rebalanced over the survivors and recomputed from lineage; the
        epoch completes with every reducer delivered exactly once.
        """
        view = self.manager.current_view()
        live = list(view.ranks)
        placement = plan_ir.reduce_placement(self.num_reducers, live)
        queues: Dict[int, collections.deque] = {
            rank: collections.deque() for rank in live}
        for reducer in range(self.num_reducers):
            queues[placement[reducer]].append(reducer)

        lock = threading.Lock()
        ledger: Dict[int, object] = {}       # reducer -> delivered table
        orphans: collections.deque = collections.deque()
        dead: set = set()
        death_times: List[float] = []
        stats = {"epoch": epoch, "view_id": view.view_id,
                 "live_ranks": len(live), "recomputed": 0,
                 "duplicates_dropped": 0, "resize_stall_ms": 0.0}

        # Late import: the package root re-exports a `shuffle` FUNCTION,
        # so the module must be imported by its dotted name.
        from ray_shuffling_data_loader_tpu.shuffle import (
            recompute_reducer_output)

        def compute(reducer: int):
            return recompute_reducer_output(
                self.filenames, self.num_reducers, self.seed, epoch,
                reducer, self.map_transform, self.reduce_transform,
                self.on_bad_file)

        def deliver(reducer: int, table) -> None:
            with lock:
                if reducer in ledger:
                    # Exactly-once: a racing recompute of a reducer the
                    # dead rank in fact delivered is dropped here.
                    stats["duplicates_dropped"] += 1
                    return
                ledger[reducer] = table

        def worker(rank: int) -> None:
            while True:
                with lock:
                    if rank in dead:
                        return
                    if queues[rank]:
                        reducer = queues[rank].popleft()
                        recovered = False
                    elif orphans:
                        reducer = orphans.popleft()
                        recovered = True
                    else:
                        return
                if self.manager.maybe_crash(epoch, rank):
                    # The rank died holding `reducer` undelivered: it
                    # goes back to the pool with the rest of the rank's
                    # queue for the survivors to drain.
                    with lock:
                        dead.add(rank)
                        orphans.append(reducer)
                        orphans.extend(queues[rank])
                        queues[rank].clear()
                        death_times.append(time.monotonic())
                    return
                deliver(reducer, compute(reducer))
                if recovered:
                    with lock:
                        stats["recomputed"] += 1

        start = time.monotonic()
        threads = [threading.Thread(target=worker, args=(rank,),
                                    daemon=True,
                                    name=f"rsdl-elastic-r{rank}")
                   for rank in live]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        # Degraded completion backstop: every rank died (or died after
        # the survivors had already drained and exited). The driver
        # itself finishes the epoch from lineage — the epoch NEVER ends
        # with a hole.
        leftovers = list(orphans)
        for rank in live:
            leftovers.extend(queues[rank])
        missing = [r for r in range(self.num_reducers) if r not in ledger]
        for reducer in sorted(set(leftovers) | set(missing)):
            if reducer in ledger:
                continue
            deliver(reducer, compute(reducer))
            stats["recomputed"] += 1

        end = time.monotonic()
        stats["dur_s"] = end - start
        if death_times:
            # Tail latency attributable to the resize: from the first
            # death to epoch completion (the survivors' recompute tax).
            stats["resize_stall_ms"] = (end - min(death_times)) * 1000.0
        self.last_stats = stats
        if stats["recomputed"] or dead:
            rt_telemetry.record(
                "member_resize", epoch=epoch, view=view.view_id,
                recomputed=stats["recomputed"],
                dead=sorted(dead), dur_s=stats["dur_s"])
            logger.warning(
                "elastic epoch %d completed DEGRADED: ranks %s died, "
                "%d reducer(s) recomputed on survivors", epoch,
                sorted(dead), stats["recomputed"])
        assert len(ledger) == self.num_reducers
        return [ledger[r] for r in range(self.num_reducers)]

    def run(self, num_epochs: int) -> List[List]:
        """Run ``num_epochs`` epochs; view changes (shrink from chaos or
        detector verdicts, grow from ``member_join``) take effect at
        each epoch boundary."""
        return [self.run_epoch(e)
                for e in plan_ir.epoch_range(0, num_epochs)]


def trainer_streams(reducer_outputs: Sequence, num_trainers: int) -> List:
    """Slice reducer-indexed outputs into per-trainer streams with the
    same ``route_slices`` contract the queue plane uses — the trainer
    count never changes under elasticity, so queue math stays stable."""
    spans = plan_ir.route_slices(len(reducer_outputs), num_trainers)
    return [list(reducer_outputs[start:stop]) for start, stop in spans]


def total_rows(reducer_outputs: Sequence) -> int:
    """Summed row count over reducer outputs (a ``rows_lost`` check
    compares this against the fixed-world run)."""
    return sum(t.num_rows for t in reducer_outputs)


__all__ = ["ElasticShuffleRunner", "trainer_streams", "total_rows"]
