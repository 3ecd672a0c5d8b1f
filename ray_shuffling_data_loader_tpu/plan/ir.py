"""THE epoch-plan IR: one declarative object per shuffle epoch.

The pipeline's determinism contract — every task is a pure function of
``(seed, epoch, task)`` — used to be *implicit*, smeared across
``shuffle.EpochLineage``, the queue server's resume arithmetic
(``queue_id = epoch * num_trainers + rank``), checkpoint skip math, the
procpool's kill-recovery resubmission and the chaos harness's rule keys.
Each consumer re-derived the same keys with its own private arithmetic,
and nothing could *look at* an epoch's task graph as data.

This module reifies that knowledge as an explicit, serializable plan:

- :class:`PlanNode` — one task (``map`` / ``reduce`` / ``route``) with
  its lineage key, dependency edges, and an optional cost annotation fed
  back from telemetry.
- :class:`EpochPlan` — the per-epoch DAG ``files -> map partitions ->
  reduce slices -> queue routes``, built by :func:`build_epoch_plan`,
  validated by :meth:`EpochPlan.validate`, round-tripped by
  :meth:`EpochPlan.to_json` / :func:`from_json` (stable key order, so
  tools and the checkpoint journal can diff two serializations).
- The **plan queries** every resume/recovery path must use instead of
  re-deriving keys: :func:`queue_index` / :func:`queue_epoch` /
  :func:`queue_rank` (the route-key arithmetic, in exactly one place),
  :func:`route_slices` (the contiguous reducer->trainer split,
  remainder-first like ``np.array_split``), and
  :func:`resume_from_watermarks` (the PR 5 journal-resume math the
  restarted queue server runs).

The ``lineage-outside-plan`` rsdl-lint rule closes the loop from the
other side: fresh ``(seed, epoch, task)`` key-derivation arithmetic in
library code outside ``plan/`` is flagged — resume and recovery must
query the plan, not re-derive.

Execution of a plan lives in :mod:`plan.scheduler`. This module is
stdlib-only and import-free on purpose (the ``runtime/`` contract):
``tools/rsdl_plan.py`` loads it by file path on images without numpy or
pyarrow.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import re
from typing import (Any, Dict, Iterable, List, Mapping, Optional,
                    Sequence, Tuple)

#: Serialization format version (bumped on breaking shape changes).
PLAN_VERSION = 1

#: Mirrors tenancy/__init__.py's id shape (this module stays import-free
#: so tools can load it by file path without the package).
_TENANT_ID_RE = re.compile(r"^[a-z0-9][a-z0-9_.-]{0,63}$")

#: Shard-map serialization version (the serving-plane config, PR 10).
SHARD_MAP_VERSION = 1

#: Stage names, in dependency order.
STAGES = ("map", "reduce", "route")


class PlanError(ValueError):
    """A plan failed validation (or deserialization)."""


# ---------------------------------------------------------------------------
# Lineage / route key derivation — THE one place for this arithmetic.
# ---------------------------------------------------------------------------


def queue_index(epoch: int, rank: int, num_trainers: int) -> int:
    """The multiqueue index carrying ``rank``'s tables for ``epoch``
    (the wire contract of multiqueue.py / multiqueue_service.py)."""
    return epoch * num_trainers + rank


def queue_epoch(queue_idx: int, num_trainers: int) -> int:
    """Inverse of :func:`queue_index`: the epoch a queue belongs to."""
    return queue_idx // num_trainers


def queue_rank(queue_idx: int, num_trainers: int) -> int:
    """Inverse of :func:`queue_index`: the trainer rank a queue feeds."""
    return queue_idx % num_trainers


def queue_shard(queue_idx: int, num_trainers: int, num_shards: int) -> int:
    """The serving-plane shard responsible for ``queue_idx``.

    Placement is BY RANK (``queue_rank % num_shards``), so every epoch of
    one trainer's stream lands on the same shard — a consumer holds one
    connection per shard for its whole run, and a shard's watermark
    journal covers complete per-rank histories (the per-shard recovery
    matrix needs no cross-shard coordination)."""
    return queue_rank(queue_idx, num_trainers) % max(1, num_shards)


def shard_ranks(shard: int, num_trainers: int, num_shards: int) -> List[int]:
    """The trainer ranks (hence queues, across every epoch) shard
    ``shard`` owns under the :func:`queue_shard` placement."""
    num_shards = max(1, num_shards)
    return [r for r in range(num_trainers) if r % num_shards == shard]


def split_sizes(total: int, num_parts: int) -> List[int]:
    """Sizes of the contiguous reducer->trainer split: remainder-first,
    exactly ``np.array_split(range(total), num_parts)`` (the reference's
    routing arithmetic, reference: shuffle.py:188-189; mirrored from
    ``ops.partition.split_sizes`` so this module stays stdlib-only —
    equality is pinned by a test)."""
    base, rem = divmod(total, num_parts)
    return [base + 1 if i < rem else base for i in range(num_parts)]


def route_slices(num_reducers: int, num_trainers: int
                 ) -> List[Tuple[int, int]]:
    """Per-trainer ``(start, stop)`` reducer-index spans (contiguous,
    remainder-first)."""
    out: List[Tuple[int, int]] = []
    start = 0
    for size in split_sizes(num_reducers, num_trainers):
        out.append((start, start + size))
        start += size
    return out


def rebalance_spans(num_items: int, live_ranks: Sequence[int]
                    ) -> Dict[int, Tuple[int, int]]:
    """Contiguous ``(start, stop)`` item spans re-placed over an
    ELASTIC rank set: :func:`route_slices` arithmetic, but keyed by the
    live ranks themselves (sorted) instead of ``range(world)`` — THE
    membership-resize placement query. A shrunken world hands the dead
    rank's span to survivors (remainder-first, so the split is uneven
    but deterministic); a grown world spreads the same items thinner.
    Placement moves, content never does: the items are still the same
    global indices, so every task's ``(seed, epoch, task)`` lineage key
    — and therefore its output — is unchanged by any resize."""
    ranks = sorted(int(r) for r in live_ranks)
    if not ranks:
        raise PlanError("rebalance_spans needs at least one live rank")
    spans = route_slices(num_items, len(ranks))
    return {rank: spans[i] for i, rank in enumerate(ranks)}


def reduce_placement(num_reducers: int, live_ranks: Sequence[int]
                     ) -> Dict[int, int]:
    """``reducer_index -> owning live rank`` under the
    :func:`rebalance_spans` placement — the inverse view the elastic
    runner's per-reducer loop wants."""
    placement: Dict[int, int] = {}
    for rank, (start, stop) in rebalance_spans(num_reducers,
                                               live_ranks).items():
        for reducer in range(start, stop):
            placement[reducer] = rank
    return placement


def node_id(stage: str, epoch: int, task: int) -> str:
    """Stable node id: ``stage:eE:tT``."""
    return f"{stage}:e{epoch}:t{task}"


# ---------------------------------------------------------------------------
# IR data model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LineageKey:
    """The ``(seed, epoch, task)`` triple that makes a task pure: the
    same key always reproduces the same output, which is what makes
    recomputation, replay, checkpoint resume and speculative duplicate
    execution all provably safe."""

    seed: int
    epoch: int
    task: int

    def as_tuple(self) -> Tuple[int, int, int]:
        return (self.seed, self.epoch, self.task)

    def __str__(self) -> str:
        return f"{self.seed}:{self.epoch}:{self.task}"


@dataclasses.dataclass
class PlanNode:
    """One task of an epoch plan.

    ``meta`` carries the stage-specific payload (map: ``file`` path and
    ``file_index``; reduce: nothing extra; route: ``rank``, ``queue``
    and the contiguous ``reducers`` span it consumes). ``cost_s`` is an
    advisory duration annotation fed back from telemetry — schedulers
    may use it for placement, tools render it; it never affects
    correctness (it is excluded from plan equality on purpose)."""

    id: str
    stage: str
    key: LineageKey
    deps: Tuple[str, ...] = ()
    cost_s: Optional[float] = None
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "id": self.id,
            "stage": self.stage,
            "key": list(self.key.as_tuple()),
            "deps": list(self.deps),
        }
        if self.cost_s is not None:
            d["cost_s"] = round(float(self.cost_s), 6)
        if self.meta:
            d["meta"] = dict(sorted(self.meta.items()))
        return d

    @classmethod
    def from_dict(cls, data: Mapping[str, Any]) -> "PlanNode":
        try:
            seed, epoch, task = data["key"]
            return cls(id=str(data["id"]), stage=str(data["stage"]),
                       key=LineageKey(int(seed), int(epoch), int(task)),
                       deps=tuple(str(d) for d in data.get("deps", ())),
                       cost_s=(None if data.get("cost_s") is None
                               else float(data["cost_s"])),
                       meta=dict(data.get("meta", {})))
        except (KeyError, TypeError, ValueError) as e:
            raise PlanError(f"malformed plan node {data!r}: {e}") from e


@dataclasses.dataclass
class EpochPlan:
    """The declarative task graph of ONE shuffle epoch.

    Node order is deterministic (maps by file index, reduces by reducer
    index, routes by rank), so two plans built from the same inputs
    serialize byte-identically — the property the checkpoint journal and
    ``tools/rsdl_plan.py`` diffing rely on.

    ``window`` is the streaming provenance block (``streaming/window.py``):
    a closed window compiles to a normal epoch plan and stamps
    ``{"index", "policy", "ingest_watermark", "late_events"}`` here so
    recovery and tools can see which stream window an epoch came from.
    ``None`` (the static-file-list case) serializes to nothing — plans
    from the pre-streaming world stay byte-identical.

    ``tenant_id`` names the tenant the epoch is served FOR
    (tenancy/__init__.py): the serving plane attributes queue bytes
    and the storage plane attributes cache residency to it. Like
    ``window``, ``None`` serializes to nothing so single-tenant plans
    stay byte-identical with every pre-tenancy journal."""

    seed: int
    epoch: int
    num_reducers: int
    num_trainers: int
    filenames: List[str]
    nodes: Dict[str, PlanNode] = dataclasses.field(default_factory=dict)
    version: int = PLAN_VERSION
    window: Optional[Dict[str, Any]] = None
    tenant_id: Optional[str] = None

    # -- queries --------------------------------------------------------

    def stage_nodes(self, stage: str) -> List[PlanNode]:
        return [n for n in self.nodes.values() if n.stage == stage]

    def maps(self) -> List[PlanNode]:
        return self.stage_nodes("map")

    def reduces(self) -> List[PlanNode]:
        return self.stage_nodes("reduce")

    def routes(self) -> List[PlanNode]:
        return self.stage_nodes("route")

    def node(self, nid: str) -> PlanNode:
        try:
            return self.nodes[nid]
        except KeyError:
            raise PlanError(f"unknown plan node {nid!r}") from None

    def map_key(self, file_index: int) -> LineageKey:
        return self.node(node_id("map", self.epoch, file_index)).key

    def reduce_key(self, reduce_index: int) -> LineageKey:
        return self.node(node_id("reduce", self.epoch, reduce_index)).key

    def dependents(self) -> Dict[str, List[str]]:
        """Reverse edges: node id -> ids depending on it."""
        out: Dict[str, List[str]] = {nid: [] for nid in self.nodes}
        for node in self.nodes.values():
            for dep in node.deps:
                if dep in out:
                    out[dep].append(node.id)
        return out

    def annotate_costs(self, stage_costs: Mapping[str, float]) -> None:
        """Stamp advisory per-stage cost annotations (seconds) onto every
        node of each stage — the telemetry feedback hook (the
        scheduler passes stage p50s from ``telemetry.attribution()``)."""
        for node in self.nodes.values():
            cost = stage_costs.get(node.stage)
            if cost is not None:
                node.cost_s = float(cost)

    # -- validation -----------------------------------------------------

    def validate(self) -> None:
        """Raise :class:`PlanError` unless the plan is well-formed:
        unique stage/epoch/task-consistent ids, closed acyclic dependency
        edges, reduces depending on every map, and route nodes covering
        the reducer range contiguously exactly once."""
        if self.version != PLAN_VERSION:
            raise PlanError(
                f"plan version {self.version} != {PLAN_VERSION}")
        if self.num_reducers < 1 or self.num_trainers < 1:
            raise PlanError("num_reducers and num_trainers must be >= 1")
        if self.window is not None:
            if not isinstance(self.window, dict):
                raise PlanError("window metadata must be a dict")
            try:
                if int(self.window["index"]) < 0:
                    raise PlanError("window index must be >= 0")
            except (KeyError, TypeError, ValueError) as e:
                raise PlanError(
                    f"malformed window metadata {self.window!r}: {e}") from e
        if self.tenant_id is not None:
            if not isinstance(self.tenant_id, str) \
                    or not _TENANT_ID_RE.match(self.tenant_id):
                raise PlanError(
                    f"invalid tenant_id {self.tenant_id!r}: want "
                    "^[a-z0-9][a-z0-9_.-]{0,63}$")
        maps, reduces, routes = [], [], []
        for nid, node in self.nodes.items():
            if node.id != nid:
                raise PlanError(f"node indexed as {nid!r} carries id "
                                f"{node.id!r}")
            if node.stage not in STAGES:
                raise PlanError(f"{nid}: unknown stage {node.stage!r}")
            if node.id != node_id(node.stage, node.key.epoch, node.key.task):
                raise PlanError(f"{nid}: id does not encode its stage/"
                                f"lineage key {node.key}")
            if node.key.seed != self.seed or node.key.epoch != self.epoch:
                raise PlanError(
                    f"{nid}: lineage key {node.key} disagrees with plan "
                    f"(seed={self.seed}, epoch={self.epoch})")
            for dep in node.deps:
                if dep not in self.nodes:
                    raise PlanError(f"{nid}: unknown dep {dep!r}")
            {"map": maps, "reduce": reduces,
             "route": routes}[node.stage].append(node)
        if {n.key.task for n in maps} != set(range(len(self.filenames))):
            raise PlanError("map tasks do not cover the file list "
                            f"(files={len(self.filenames)})")
        if {n.key.task for n in reduces} != set(range(self.num_reducers)):
            raise PlanError("reduce tasks do not cover "
                            f"range({self.num_reducers})")
        if {n.key.task for n in routes} != set(range(self.num_trainers)):
            raise PlanError("route tasks do not cover "
                            f"range({self.num_trainers})")
        map_ids = {n.id for n in maps}
        for node in reduces:
            if set(node.deps) != map_ids:
                raise PlanError(
                    f"{node.id}: a reduce must depend on every map "
                    "(its permutation gathers one chunk per file)")
        covered: List[int] = []
        for node in sorted(routes, key=lambda n: n.key.task):
            span = node.meta.get("reducers")
            expect_queue = queue_index(self.epoch, node.key.task,
                                       self.num_trainers)
            if node.meta.get("queue") != expect_queue:
                raise PlanError(f"{node.id}: queue {node.meta.get('queue')}"
                                f" != queue_index() {expect_queue}")
            if span is None:
                raise PlanError(f"{node.id}: route without a reducers span")
            covered.extend(span)
            want_deps = {node_id("reduce", self.epoch, r) for r in span}
            if set(node.deps) != want_deps:
                raise PlanError(f"{node.id}: deps do not match its "
                                "reducers span")
        if covered != list(range(self.num_reducers)):
            raise PlanError("route nodes do not cover the reducer range "
                            "contiguously exactly once")
        self._check_acyclic()

    def _check_acyclic(self) -> None:
        indegree = {nid: len(n.deps) for nid, n in self.nodes.items()}
        ready = [nid for nid, d in indegree.items() if d == 0]
        dependents = self.dependents()
        seen = 0
        while ready:
            nid = ready.pop()
            seen += 1
            for child in dependents[nid]:
                indegree[child] -= 1
                if indegree[child] == 0:
                    ready.append(child)
        if seen != len(self.nodes):
            raise PlanError("dependency cycle detected")

    # -- serialization --------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "version": self.version,
            "seed": self.seed,
            "epoch": self.epoch,
            "num_reducers": self.num_reducers,
            "num_trainers": self.num_trainers,
            "filenames": list(self.filenames),
            "nodes": [n.as_dict() for n in self.nodes.values()],
        }
        if self.window is not None:
            # After "nodes" on purpose: absent for static plans, so the
            # pre-streaming serialization stays byte-identical.
            d["window"] = dict(sorted(self.window.items()))
        if self.tenant_id is not None:
            # Same back-compat contract as "window": single-tenant
            # plans serialize byte-identically to pre-tenancy ones.
            d["tenant_id"] = self.tenant_id
        return d

    def to_json(self, indent: Optional[int] = None) -> str:
        """Stable serialization: fixed top-level key order, nodes in
        build order, node dicts with sorted meta — byte-identical for
        equal plans."""
        return json.dumps(self.to_dict(), indent=indent, sort_keys=False)

    @classmethod
    def from_dict(cls, data: Mapping[str, Any]) -> "EpochPlan":
        try:
            window = data.get("window")
            plan = cls(seed=int(data["seed"]), epoch=int(data["epoch"]),
                       num_reducers=int(data["num_reducers"]),
                       num_trainers=int(data["num_trainers"]),
                       filenames=[str(f) for f in data["filenames"]],
                       version=int(data.get("version", PLAN_VERSION)),
                       window=dict(window) if window is not None else None,
                       tenant_id=data.get("tenant_id"))
        except (KeyError, TypeError, ValueError) as e:
            raise PlanError(f"malformed plan: {e}") from e
        for node_data in data.get("nodes", ()):
            node = PlanNode.from_dict(node_data)
            if node.id in plan.nodes:
                raise PlanError(f"duplicate node id {node.id!r}")
            plan.nodes[node.id] = node
        return plan


def from_json(text: str) -> EpochPlan:
    try:
        data = json.loads(text)
    except json.JSONDecodeError as e:
        raise PlanError(f"plan is not valid JSON: {e}") from e
    if not isinstance(data, dict):
        raise PlanError("plan JSON must be an object")
    return EpochPlan.from_dict(data)


def build_epoch_plan(filenames: Iterable[str], num_reducers: int,
                     num_trainers: int, seed: int, epoch: int,
                     window: Optional[Dict[str, Any]] = None,
                     tenant_id: Optional[str] = None) -> EpochPlan:
    """Build (and validate) the canonical plan of one epoch:
    one map node per file, one reduce node per reducer (depending on
    every map), one route node per trainer rank consuming its contiguous
    reducer span and naming its queue index. ``window`` stamps streaming
    provenance onto the plan (closed-window epochs); ``tenant_id``
    stamps the owning tenant (tenancy plans)."""
    plan = EpochPlan(seed=seed, epoch=epoch, num_reducers=num_reducers,
                     num_trainers=num_trainers,
                     filenames=[str(f) for f in filenames],
                     window=dict(window) if window is not None else None,
                     tenant_id=tenant_id)
    map_ids = []
    for file_index, filename in enumerate(plan.filenames):
        nid = node_id("map", epoch, file_index)
        plan.nodes[nid] = PlanNode(
            id=nid, stage="map", key=LineageKey(seed, epoch, file_index),
            meta={"file": filename, "file_index": file_index})
        map_ids.append(nid)
    reduce_ids = []
    for reduce_index in range(num_reducers):
        nid = node_id("reduce", epoch, reduce_index)
        plan.nodes[nid] = PlanNode(
            id=nid, stage="reduce",
            key=LineageKey(seed, epoch, reduce_index),
            deps=tuple(map_ids))
        reduce_ids.append(nid)
    for rank, (start, stop) in enumerate(route_slices(num_reducers,
                                                      num_trainers)):
        nid = node_id("route", epoch, rank)
        plan.nodes[nid] = PlanNode(
            id=nid, stage="route", key=LineageKey(seed, epoch, rank),
            deps=tuple(reduce_ids[start:stop]),
            meta={"rank": rank,
                  "queue": queue_index(epoch, rank, num_trainers),
                  "reducers": list(range(start, stop))})
    plan.validate()
    return plan


# ---------------------------------------------------------------------------
# Epoch specs: what the generalized shuffle driver iterates
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EpochSpec:
    """One epoch's worth of work, as the shuffle driver sees it BEFORE a
    plan is built: the epoch index, the files it shuffles, and optional
    streaming window provenance (stamped onto the built plan).

    The driver loop in ``shuffle.py`` consumes an *iterator* of these —
    the static file list compiles to :func:`static_epoch_specs`, a
    stream's window assembler yields them unboundedly as windows close.
    The ``static-epoch-assumption`` rsdl-lint rule pins the inversion:
    library code no longer counts epochs with ``range(num_epochs)``;
    epochs arrive from here.

    ``num_reducers`` overrides the driver-wide reducer count for THIS
    epoch (None = the driver default): the elastic-membership hook that
    lets a streaming run retopologize at a window seal — window N built
    on the old view's count, window N+1 on the new one — with zero
    replay, because each epoch's plan always carried its own reducer
    count."""

    epoch: int
    filenames: Tuple[str, ...]
    window: Optional[Dict[str, Any]] = None
    tenant_id: Optional[str] = None
    num_reducers: Optional[int] = None


def static_epoch_specs(filenames: Iterable[str], num_epochs: int,
                       start_epoch: int = 0,
                       tenant_id: Optional[str] = None
                       ) -> Iterable[EpochSpec]:
    """The classic epochs-over-a-fixed-file-list schedule as an epoch-spec
    iterator: every epoch reshuffles the same files, ``start_epoch``
    resumes mid-trial. THE one place the per-trial epoch range is
    enumerated (shuffle.py consumes the iterator, never the count)."""
    files = tuple(str(f) for f in filenames)
    for epoch in range(start_epoch, num_epochs):
        yield EpochSpec(epoch=epoch, filenames=files,
                        tenant_id=tenant_id)


def epoch_range(start_epoch: int, num_epochs: Optional[int]):
    """Epoch indices for a consumer: ``range`` for a bounded trial,
    ``itertools.count`` when ``num_epochs`` is None (an unbounded stream
    — epochs keep arriving as windows close). Consumers iterate this
    instead of hand-rolling ``range(num_epochs)``; the
    ``static-epoch-assumption`` lint rule enforces it."""
    if num_epochs is None:
        return itertools.count(start_epoch)
    return range(start_epoch, num_epochs)


# ---------------------------------------------------------------------------
# Serving-plane shard map (the PR 10 queue fabric config)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardMap:
    """The serving plane's one config object: which shard serves which
    (trainer, epoch) queue, and where each shard listens.

    Replaces the single ``(host, port)`` of the pre-sharded topology.
    Placement is the :func:`queue_shard` plan query (by rank), so the
    map is pure data — ``addresses[i]`` is shard ``i``'s ``(host,
    port)``. Stdlib-only and JSON round-trippable (stable key order)
    like :class:`EpochPlan`, so tools and child-process configs can
    carry it verbatim.

    ``overrides`` (rank -> shard) layers the rebalancer's live moves on
    top of the static ``rank % num_shards`` arithmetic, and
    ``generation`` counts committed placement changes — it is the fence
    stamped into every wire frame so a zombie source shard's post-move
    frames are loudly droppable. Both serialize only when non-default,
    so pre-rebalance maps round-trip byte-identically.
    """

    num_trainers: int
    addresses: List[Tuple[str, int]]
    version: int = SHARD_MAP_VERSION
    overrides: Dict[int, int] = dataclasses.field(default_factory=dict)
    generation: int = 0

    @property
    def num_shards(self) -> int:
        return len(self.addresses)

    def validate(self) -> None:
        if self.version != SHARD_MAP_VERSION:
            raise PlanError(
                f"shard map version {self.version} != {SHARD_MAP_VERSION}")
        if self.num_trainers < 1:
            raise PlanError("shard map needs num_trainers >= 1")
        if not self.addresses:
            raise PlanError("shard map needs at least one shard address")
        for addr in self.addresses:
            if len(tuple(addr)) != 2 or not isinstance(addr[0], str):
                raise PlanError(f"malformed shard address {addr!r}")
        if self.generation < 0:
            raise PlanError("shard map generation must be >= 0")
        for rank, shard in self.overrides.items():
            if not 0 <= int(rank) < self.num_trainers:
                raise PlanError(f"override for unknown rank {rank}")
            if not 0 <= int(shard) < self.num_shards:
                raise PlanError(
                    f"override routes rank {rank} to unknown shard {shard}")

    def shard_for_queue(self, queue_idx: int) -> int:
        return self.shard_for_rank(
            queue_rank(queue_idx, self.num_trainers))

    def shard_for_rank(self, rank: int) -> int:
        return self.overrides.get(rank, rank % self.num_shards)

    def ranks_for_shard(self, shard: int) -> List[int]:
        return [rank for rank in range(self.num_trainers)
                if self.shard_for_rank(rank) == shard]

    def address_for_queue(self, queue_idx: int) -> Tuple[str, int]:
        return tuple(self.addresses[self.shard_for_queue(queue_idx)])

    def to_dict(self) -> Dict[str, Any]:
        data: Dict[str, Any] = {
            "version": self.version,
            "num_trainers": self.num_trainers,
            "addresses": [[host, int(port)]
                          for host, port in self.addresses],
        }
        if self.overrides:
            data["overrides"] = {str(rank): int(shard) for rank, shard
                                 in sorted(self.overrides.items())}
        if self.generation:
            data["generation"] = self.generation
        return data

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=False)

    @classmethod
    def from_dict(cls, data: Mapping[str, Any]) -> "ShardMap":
        try:
            shard_map = cls(
                num_trainers=int(data["num_trainers"]),
                addresses=[(str(h), int(p)) for h, p in data["addresses"]],
                version=int(data.get("version", SHARD_MAP_VERSION)),
                overrides={int(rank): int(shard) for rank, shard
                           in dict(data.get("overrides", {})).items()},
                generation=int(data.get("generation", 0)))
        except (KeyError, TypeError, ValueError) as e:
            raise PlanError(f"malformed shard map: {e}") from e
        shard_map.validate()
        return shard_map

    @classmethod
    def from_json(cls, text: str) -> "ShardMap":
        try:
            data = json.loads(text)
        except json.JSONDecodeError as e:
            raise PlanError(f"shard map is not valid JSON: {e}") from e
        if not isinstance(data, dict):
            raise PlanError("shard map JSON must be an object")
        return cls.from_dict(data)


# ---------------------------------------------------------------------------
# Resume queries (the PR 5 journal math, now a plan query)
# ---------------------------------------------------------------------------


def _entry_fields(entry: Any) -> Tuple[int, bool]:
    """``(seq, done)`` from a WatermarkEntry-shaped object or dict."""
    if isinstance(entry, Mapping):
        return int(entry["seq"]), bool(entry.get("done", False))
    return int(entry.seq), bool(getattr(entry, "done", False))


def resume_from_watermarks(state: Mapping[int, Any], num_epochs: int,
                           num_trainers: int,
                           ranks: Optional[Iterable[int]] = None
                           ) -> Tuple[int, Dict[int, int]]:
    """``(start_epoch, skip_items)`` for a restarted producer: the first
    epoch any rank has not fully consumed, and — per queue at/after it —
    how many items (tables + sentinel) of the deterministic re-run are
    already journaled as delivered and must not be re-enqueued.

    ``state`` maps queue index -> a ``checkpoint.WatermarkEntry`` (or a
    dict with ``seq``/``done``). ``ranks`` restricts the scan to the
    trainer ranks the caller actually serves — a restarted queue SHARD
    (``queue_shard`` placement) passes its owned ranks so a foreign
    rank's absent journal entries cannot drag its start epoch back to
    zero. This is the one resume-math implementation;
    ``multiqueue_service._resume_plan`` and
    ``checkpoint.WatermarkJournal.resume_plan`` both delegate here.
    """
    owned = list(ranks) if ranks is not None else list(range(num_trainers))
    start_epoch = num_epochs
    for rank in owned:
        for epoch in range(num_epochs):
            entry = state.get(queue_index(epoch, rank, num_trainers))
            if entry is None or not _entry_fields(entry)[1]:
                start_epoch = min(start_epoch, epoch)
                break
    owned_set = set(owned)
    skip_items = {q: _entry_fields(entry)[0] + 1
                  for q, entry in state.items()
                  if queue_epoch(q, num_trainers) >= start_epoch
                  and queue_rank(q, num_trainers) in owned_set}
    return start_epoch, skip_items
