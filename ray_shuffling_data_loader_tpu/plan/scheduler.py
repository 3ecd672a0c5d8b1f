"""Plan-driven execution engine: dependency-ordered dispatch, speculative
re-execution of stragglers, and work-stealing placement.

Replaces the inline epoch loops (``shuffle.shuffle_epoch``'s submit-all
fan-out and ``procpool.process_epoch``'s await-then-submit sequence) with
one engine that executes an :class:`plan.ir.EpochPlan` on any pool
satisfying the ``executor.Executor`` contract:

- **Dependency-ordered dispatch**: a node is submitted only when every
  dependency has *resolved* (completed — successfully or not; failure
  semantics stay with the consumer, e.g. the reduce task's
  ``EpochLineage`` recovery observes a failed map ref exactly as
  before). No worker is ever parked blocking on an unfinished input.

- **Speculative re-execution** (``RSDL_PLAN_SPECULATION``, default off):
  when a running task's elapsed time exceeds a policy-gated multiple of
  the rolling per-stage median (``RSDL_PLAN_SPECULATION_MULTIPLIER``,
  floored by ``RSDL_PLAN_SPECULATION_MIN_S``) and an idle lane exists, a
  backup attempt of the SAME node is launched — the classic MapReduce
  answer to stragglers, provably safe here because every task is a pure
  function of its ``(seed, epoch, task)`` lineage key, so duplicate
  executions are bit-identical. First completion wins; the loser is
  cancelled if still queued, otherwise its result is discarded
  (``rsdl_plan_speculative_wasted_total``). Backup attempts run under
  ``telemetry.speculative()`` so their recorder events carry a ``spec``
  attr and never double-count in trace merge or bottleneck attribution.

- **Work stealing / locality-aware placement**
  (``RSDL_PLAN_STEALING``, default on): nodes are assigned to logical
  lanes (one per pool worker, ``task % lanes`` — the static round-robin
  the inline loops effectively had). An idle lane whose own queue is
  empty pulls the oldest ready node from the longest sibling queue
  (``rsdl_plan_steals_total``) instead of idling; with stealing off,
  placement is strictly static (the A/B baseline the equivalence tests
  pin — outputs are identical either way, only idle time differs).

- **Idle-lane prefetch** (``prefetcher=``, storage/prefetch.py): a lane
  with no real work, nothing to steal, and no speculation candidate
  pulls a cache-warming task from the prefetcher instead of idling —
  the lowest rung of the priority ladder (ready nodes > steals >
  speculation > prefetch). A prefetch does NOT mark its lane busy: the
  lane stays claimable, and the moment real work lands on it the
  prefetch is canceled (best effort — a transfer already in flight
  finishes and still warms the cache). Warms run on dedicated daemon
  threads, never on pool workers, so an in-flight remote fetch cannot
  occupy a worker slot a real task would queue behind. Prefetches
  still in flight when the plan resolves are left to complete: they
  are warming the files the NEXT epoch's plan reads.

The engine runs on one named driver thread per plan (no polling when
speculation is off: dispatch is woken by completion events). Stage
barrier hooks (``barriers={stage: fn}``) run on the driver thread after
a stage fully resolves and before dependents dispatch — the process
backend uses one to collect map segment results (including its
driver-side lineage re-run) without ever blocking a pool dispatcher
thread.
"""

from __future__ import annotations

import collections
import concurrent.futures as cf
import queue as queue_mod
import statistics
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ray_shuffling_data_loader_tpu import executor as ex
from ray_shuffling_data_loader_tpu.plan import ir
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import policy as rt_policy
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)

#: Dispatcher signature: submit one attempt of a node to the pool.
Dispatcher = Callable[[ir.PlanNode, int], ex.TaskRef]

#: Rolling window of completed durations per stage for the speculation
#: median (bounded memory; stragglers are judged against recent peers).
_MEDIAN_WINDOW = 64

# Process-wide speculation/steal totals (callers read deltas of these;
# the registry counters carry the same numbers per stage for the
# exposition/rsdl_top view).
_totals_lock = threading.Lock()
_totals = {"speculative_launched": 0, "speculative_won": 0,
           "speculative_wasted": 0, "steals": 0}


def speculation_totals() -> Dict[str, int]:
    """Process-wide ``{speculative_launched, speculative_won,
    speculative_wasted, steals}`` counters across all schedulers."""
    with _totals_lock:
        return dict(_totals)


def _bump(name: str, n: int = 1) -> None:
    with _totals_lock:
        _totals[name] += n


class SchedulerPolicy:
    """Resolved ``plan`` component policy knobs (kwarg > RSDL_PLAN_* env
    > default; see runtime/policy.py for the precedence contract)."""

    def __init__(self, speculation: Optional[bool] = None,
                 stealing: Optional[bool] = None,
                 multiplier: Optional[float] = None,
                 min_task_s: Optional[float] = None,
                 check_interval_s: Optional[float] = None):
        self.speculation = rt_policy.resolve("plan", "plan_speculation",
                                             override=speculation)
        self.stealing = rt_policy.resolve("plan", "plan_stealing",
                                          override=stealing)
        self.multiplier = rt_policy.resolve(
            "plan", "plan_speculation_multiplier", override=multiplier)
        self.min_task_s = rt_policy.resolve(
            "plan", "plan_speculation_min_s", override=min_task_s)
        self.check_interval_s = rt_policy.resolve(
            "plan", "plan_speculation_check_s", override=check_interval_s)


class _NodeState:
    __slots__ = ("node", "future", "lane", "indegree", "attempts",
                 "started_at", "backup_launched")

    def __init__(self, node: ir.PlanNode, lane: int, indegree: int):
        self.node = node
        self.future: cf.Future = cf.Future()
        self.lane = lane
        self.indegree = indegree
        #: attempt -> (ref, start monotonic) for in-flight attempts.
        self.attempts: Dict[int, Tuple[ex.TaskRef, float]] = {}
        self.started_at: Optional[float] = None
        self.backup_launched = False


class PlanScheduler:
    """Execute the scheduled stages of one :class:`ir.EpochPlan`.

    ``dispatchers`` maps stage name -> callable submitting one attempt
    to the pool; stages without a dispatcher (``route``) are not
    scheduled — they are the driver's consumption plan. ``barriers``
    maps stage name -> hook run once on the driver thread when that
    stage fully resolves, before dependents dispatch.

    :meth:`start` returns immediately; per-node results are exposed as
    ``executor.TaskRef``s (:meth:`ref_for` / :meth:`refs`) the existing
    drain/consume machinery accepts unchanged.
    """

    def __init__(self, plan: ir.EpochPlan, pool,
                 dispatchers: Dict[str, Dispatcher],
                 barriers: Optional[Dict[str, Callable[[], None]]] = None,
                 policy: Optional[SchedulerPolicy] = None,
                 speculative_stages: Sequence[str] = ("map", "reduce"),
                 lanes: Optional[int] = None,
                 name: Optional[str] = None,
                 prefetcher=None):
        plan.validate()
        self.plan = plan
        self.pool = pool
        self.policy = policy if policy is not None else SchedulerPolicy()
        self._dispatchers = dict(dispatchers)
        self._barriers = dict(barriers or {})
        #: storage.prefetch.PrefetchManager (duck-typed: ``next()`` ->
        #: task with ``run``/``cancel``) feeding idle lanes, or None.
        self._prefetcher = prefetcher
        self._lane_prefetch: Dict[int, object] = {}
        self._speculative_stages = frozenset(speculative_stages)
        self._lanes = max(1, lanes if lanes is not None
                          else getattr(pool, "num_workers", 1))
        self._name = name or f"rsdl-plan-e{plan.epoch}"
        self._events: "queue_mod.Queue[tuple]" = queue_mod.Queue()
        # No instance lock on purpose: every field below is owned by
        # the driver thread running the event loop (callbacks talk to
        # it through self._events); a lock here would only disguise
        # that confinement contract from the concurrency pass.
        self._lane_busy = [False] * self._lanes
        self._lane_queues: List["collections.deque[_NodeState]"] = [
            collections.deque() for _ in range(self._lanes)]
        self._durations: Dict[str, "collections.deque[float]"] = {}
        self._stage_outstanding: Dict[str, int] = {}
        self._barrier_done: set = set()
        self._states: Dict[str, _NodeState] = {}
        self._unresolved = 0
        self._started = False
        self._driver: Optional[threading.Thread] = None
        dependents = plan.dependents()
        scheduled = set(self._dispatchers)
        for node in plan.nodes.values():
            if node.stage not in scheduled:
                continue
            indegree = sum(1 for dep in node.deps
                           if plan.nodes[dep].stage in scheduled)
            state = _NodeState(node, node.key.task % self._lanes, indegree)
            self._states[node.id] = state
            self._stage_outstanding[node.stage] = \
                self._stage_outstanding.get(node.stage, 0) + 1
        self._dependents = {
            nid: [d for d in dependents.get(nid, ()) if d in self._states]
            for nid in self._states}
        self._unresolved = len(self._states)
        #: stages (in dependency order) whose nodes this run schedules.
        self._scheduled_stages = [s for s in ir.STAGES if s in scheduled
                                  and self._stage_outstanding.get(s)]

    # -- public surface -------------------------------------------------

    def start(self) -> "PlanScheduler":
        assert not self._started, "scheduler already started"
        self._started = True
        for state in self._states.values():
            if state.indegree == 0 and self._deps_barriers_done(state.node):
                self._lane_queues[state.lane].append(state)
        self._driver = threading.Thread(target=self._drive,
                                        name=self._name, daemon=True)
        self._driver.start()
        return self

    def ref_for(self, nid: str) -> ex.TaskRef:
        return ex.TaskRef(self._states[nid].future)

    def refs(self, stage: str) -> List[ex.TaskRef]:
        """Stage refs in task order (the contract the drain/consume
        loops expect: ``refs[i]`` is task ``i``)."""
        nodes = sorted((s.node for s in self._states.values()
                        if s.node.stage == stage), key=lambda n: n.key.task)
        return [self.ref_for(n.id) for n in nodes]

    def futures(self, stage: str) -> List[cf.Future]:
        nodes = sorted((s.node for s in self._states.values()
                        if s.node.stage == stage), key=lambda n: n.key.task)
        return [self._states[n.id].future for n in nodes]

    def join(self, timeout: Optional[float] = None) -> bool:
        """Wait for the driver thread (every scheduled node resolved)."""
        assert self._driver is not None
        self._driver.join(timeout)
        return not self._driver.is_alive()

    # -- driver loop -----------------------------------------------------

    def _drive(self) -> None:
        try:
            self._fill_lanes()
            while self._unresolved:
                timeout = (self.policy.check_interval_s
                           if self.policy.speculation else None)
                try:
                    event = self._events.get(timeout=timeout)
                except queue_mod.Empty:
                    self._maybe_speculate()
                    continue
                self._handle_done(*event)
                # Drain whatever else arrived without re-blocking.
                while True:
                    try:
                        event = self._events.get_nowait()
                    except queue_mod.Empty:
                        break
                    self._handle_done(*event)
                self._fill_lanes()
                if self.policy.speculation:
                    self._maybe_speculate()
        except BaseException as e:  # noqa: BLE001 - surfaced via futures
            logger.exception("%s: plan driver failed", self._name)
            for state in self._states.values():
                if not state.future.done():
                    state.future.set_exception(e)

    def _deps_barriers_done(self, node: ir.PlanNode) -> bool:
        for dep in node.deps:
            stage = self.plan.nodes[dep].stage
            if stage in self._barriers and stage not in self._barrier_done:
                return False
        return True

    def _fill_lanes(self) -> None:
        for lane in range(self._lanes):
            while not self._lane_busy[lane]:
                state = self._take_work(lane)
                if state is None:
                    break
                # Real work outranks a warming fetch: reclaim the lane.
                self._cancel_prefetch(lane)
                self._dispatch(state, attempt=0, lane=lane)
        if self._prefetcher is not None:
            self._fill_prefetch()

    def _cancel_prefetch(self, lane: int) -> None:
        task = self._lane_prefetch.pop(lane, None)
        if task is not None:
            task.cancel()

    def _fill_prefetch(self) -> None:
        """Bottom of the priority ladder: lanes with no real work, and
        nothing stealable, pull cache-warming tasks. The lane is NOT
        marked busy — and the warm runs on its own daemon thread, NOT
        the executor pool: a submitted pool task would occupy a real
        worker slot for the whole remote fetch, so the next epoch's map
        (or this epoch's reduce) would queue behind a cache warm —
        exactly the priority inversion the ladder forbids. A warm is
        mostly remote-latency sleep; a thread per in-flight warm
        (bounded by the lane count) costs nothing the pool would not."""
        for lane in range(self._lanes):
            if (self._lane_busy[lane] or lane in self._lane_prefetch
                    or self._lane_queues[lane]):
                continue
            task = self._prefetcher.next()
            if task is None:
                return
            def _warm(task=task, lane=lane):
                try:
                    task.run()
                finally:
                    self._events.put(("__prefetch__", lane))
            self._lane_prefetch[lane] = task
            threading.Thread(target=_warm, daemon=True,
                             name=f"{self._name}-prefetch-l{lane}").start()

    def _take_work(self, lane: int) -> Optional[_NodeState]:
        own = self._lane_queues[lane]
        if own:
            return own.popleft()
        if not self.policy.stealing:
            return None
        victim = max(self._lane_queues, key=len)
        if not victim:
            return None
        state = victim.popleft()
        _bump("steals")
        rt_metrics.counter(
            "rsdl_plan_steals_total",
            "ready plan nodes pulled by an idle lane instead of waiting "
            "on static placement", stage=state.node.stage).inc()
        rt_telemetry.record("plan_steal", epoch=state.node.key.epoch,
                            task=state.node.key.task,
                            stage=state.node.stage, lane=lane,
                            home=state.lane)
        return state

    def _dispatch(self, state: _NodeState, attempt: int, lane: int) -> None:
        node = state.node
        dispatcher = self._dispatchers[node.stage]
        try:
            ref = dispatcher(node, attempt)
        except BaseException as e:  # noqa: BLE001 - surfaced via future
            if attempt > 0:
                # A failed BACKUP submission must never poison a node
                # whose original attempt is still running.
                logger.warning("%s: speculative dispatch of %s failed "
                               "(%s); original attempt continues",
                               self._name, node.id, e)
            elif not state.future.done():
                state.future.set_exception(e)
                self._on_resolved(state)
            return
        now = time.monotonic()
        if attempt == 0:
            self._lane_busy[lane] = True
            state.lane = lane
            state.started_at = now
        state.attempts[attempt] = (ref, now)
        nid, aid = node.id, attempt
        ref.add_done_callback(
            lambda _f: self._events.put((nid, aid)))

    def _handle_done(self, nid: str, attempt: int) -> None:
        if nid == "__prefetch__":
            # A warming task finished (or was canceled): free its lane's
            # prefetch slot so _fill_lanes can issue the next one.
            self._lane_prefetch.pop(attempt, None)
            return
        state = self._states.get(nid)
        if state is None:
            return
        entry = state.attempts.pop(attempt, None)
        if entry is None:
            return
        ref, started = entry
        node = state.node
        if state.future.done():
            # A sibling attempt already won; this completion is waste.
            _bump("speculative_wasted")
            rt_metrics.counter(
                "rsdl_plan_speculative_wasted_total",
                "completed attempts whose result was discarded "
                "(first-completion-wins)", stage=node.stage).inc()
            return
        dur = time.monotonic() - started
        try:
            result = ref.result()
        except BaseException as e:  # noqa: BLE001 - consumer semantics
            state.future.set_exception(e)
        else:
            state.future.set_result(result)
        window = self._durations.setdefault(
            node.stage, collections.deque(maxlen=_MEDIAN_WINDOW))
        window.append(dur)
        if attempt > 0:
            _bump("speculative_won")
            rt_metrics.counter(
                "rsdl_plan_speculative_won_total",
                "speculative backup attempts that finished first",
                stage=node.stage).inc()
            rt_telemetry.record("plan_speculate_win",
                                epoch=node.key.epoch, task=node.key.task,
                                stage=node.stage, dur_s=dur)
        for other_attempt, (other_ref, _) in list(state.attempts.items()):
            other_ref.cancel()
        self._on_resolved(state)

    def _on_resolved(self, state: _NodeState) -> None:
        node = state.node
        self._unresolved -= 1
        self._lane_busy[state.lane] = False
        self._stage_outstanding[node.stage] -= 1
        if self._stage_outstanding[node.stage] == 0:
            hook = self._barriers.get(node.stage)
            if hook is not None:
                hook()
            self._barrier_done.add(node.stage)
        for child_id in self._dependents[node.id]:
            child = self._states[child_id]
            child.indegree -= 1
            if child.indegree == 0 and \
                    self._deps_barriers_done(child.node):
                self._lane_queues[child.lane].append(child)
        # A stage barrier may have unblocked nodes whose indegree hit 0
        # earlier in the stage (they were held back only by the hook).
        if node.stage in self._barrier_done:
            for child in self._states.values():
                if (child.indegree == 0 and not child.future.done()
                        and not child.attempts
                        and child not in self._lane_queues[child.lane]
                        and self._deps_barriers_done(child.node)):
                    self._lane_queues[child.lane].append(child)

    # -- speculation ----------------------------------------------------

    def _threshold(self, stage: str) -> Optional[float]:
        window = self._durations.get(stage)
        if not window:
            return None
        median = statistics.median(window)
        return max(self.policy.min_task_s,
                   self.policy.multiplier * median)

    def _maybe_speculate(self) -> None:
        idle = [lane for lane in range(self._lanes)
                if not self._lane_busy[lane]
                and not self._lane_queues[lane]]
        if not idle:
            return
        now = time.monotonic()
        for state in self._states.values():
            if not idle:
                return
            node = state.node
            if (state.backup_launched or state.future.done()
                    or 0 not in state.attempts
                    or node.stage not in self._speculative_stages):
                continue
            threshold = self._threshold(node.stage)
            if threshold is None:
                continue
            elapsed = now - state.attempts[0][1]
            if elapsed <= threshold:
                continue
            state.backup_launched = True
            # Speculation outranks prefetch for the lane's capacity.
            self._cancel_prefetch(idle.pop())
            logger.warning(
                "%s: task %s running %.3fs (> %.3fs threshold); "
                "launching speculative backup", self._name, node.id,
                elapsed, threshold)
            _bump("speculative_launched")
            rt_metrics.counter(
                "rsdl_plan_speculative_launched_total",
                "speculative backup attempts launched for straggling "
                "plan nodes", stage=node.stage).inc()
            rt_telemetry.record("plan_speculate", epoch=node.key.epoch,
                                task=node.key.task, stage=node.stage,
                                elapsed_s=elapsed, threshold_s=threshold)
            self._dispatch(state, attempt=1, lane=-1)


# ---------------------------------------------------------------------------
# Membership-aware plan rewrite (membership/)
# ---------------------------------------------------------------------------


def rewrite_for_view(plan: ir.EpochPlan,
                     live_ranks: Sequence[int]) -> int:
    """Resize-as-plan-rewrite: re-place the plan's reduce and route
    nodes over the LIVE membership rank set.

    A ``member_down`` mid-epoch does not change *what* the plan
    computes — every node keeps its ``(seed, epoch, task)`` lineage key,
    so outputs stay bit-identical — it changes *where*: the dead rank's
    reduce nodes are handed to survivors via
    :func:`plan.ir.reduce_placement` (``route_slices`` arithmetic over
    the shrunken rank set) and each route node follows the trainer-span
    rebalance the same way. The placement lands in ``node.meta["host"]``
    (advisory, like ``cost_s`` — excluded from plan equality), which is
    how the dryrun scene and ``tools/rsdl_plan.py`` show the resized
    world. Returns the number of nodes whose host changed.
    """
    placement = ir.reduce_placement(plan.num_reducers, live_ranks)
    trainer_host: Dict[int, int] = {}
    for host, (start, stop) in ir.rebalance_spans(
            plan.num_trainers, live_ranks).items():
        for trainer in range(start, stop):
            trainer_host[trainer] = host
    moved = 0
    for node in plan.reduces():
        host = placement[node.key.task]
        if node.meta.get("host") not in (None, host):
            moved += 1
        node.meta["host"] = host
    for node in plan.routes():
        host = trainer_host[int(node.meta.get("rank", node.key.task))]
        if node.meta.get("host") not in (None, host):
            moved += 1
        node.meta["host"] = host
    if moved:
        rt_telemetry.record("plan_rewrite", epoch=plan.epoch,
                            moved=moved, live=sorted(
                                int(r) for r in live_ranks))
        logger.warning("plan epoch %d: rewrote %d node placement(s) "
                       "onto live ranks %s", plan.epoch, moved,
                       sorted(int(r) for r in live_ranks))
    return moved


def rebalance_queues(shard_map: ir.ShardMap,
                     moves: Dict[int, int]) -> ir.ShardMap:
    """Rebalance-as-plan-rewrite: re-home trainer ranks' queues onto
    other shards of the serving fabric.

    The ``rewrite_for_view`` sibling for the serving plane: ``moves``
    maps trainer rank -> target shard, and the result is a NEW
    :class:`plan.ir.ShardMap` whose ``overrides`` carry the merged
    placement and whose ``generation`` is bumped by one — the fence the
    wire protocol stamps into every frame so post-move frames from the
    old home are droppable. Pure data-in/data-out (the input map is
    never mutated); no-op moves (rank already on the target) are
    dropped, and if every move is a no-op the INPUT map is returned
    unchanged so callers can cheaply detect "nothing to do" by
    identity. Raises :class:`plan.ir.PlanError` on out-of-range ranks
    or shards (``ShardMap.validate``).
    """
    overrides = dict(shard_map.overrides)
    applied: Dict[int, int] = {}
    for rank, shard in sorted(moves.items()):
        rank, shard = int(rank), int(shard)
        if shard_map.shard_for_rank(rank) == shard:
            continue
        overrides[rank] = shard
        applied[rank] = shard
    if not applied:
        return shard_map
    # An override that lands a rank back on its static home is pure
    # noise — drop it so maps stay canonical (and serialize minimally).
    overrides = {rank: shard for rank, shard in overrides.items()
                 if shard != rank % shard_map.num_shards}
    rebalanced = ir.ShardMap(
        num_trainers=shard_map.num_trainers,
        addresses=[tuple(addr) for addr in shard_map.addresses],
        version=shard_map.version,
        overrides=overrides,
        generation=shard_map.generation + 1)
    rebalanced.validate()
    rt_telemetry.record("plan_rebalance",
                        generation=rebalanced.generation,
                        moves={str(r): s for r, s in applied.items()})
    logger.warning("shard map generation %d: rebalanced %d rank(s) %s",
                   rebalanced.generation, len(applied),
                   {r: s for r, s in applied.items()})
    return rebalanced
