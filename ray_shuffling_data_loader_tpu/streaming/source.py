"""Stream sources: where unbounded input comes from.

A :class:`StreamSource` yields :class:`StreamEvent` records — arriving
Parquet objects with a monotone discovery index and a *stream-time*
timestamp — under the same determinism discipline the rest of the
pipeline runs on: the sequence of events a source yields is a pure
function of its construction arguments plus its journal, so a recovered
source re-yields the **identical** sequence and window assembly
(``streaming/window.py``) re-derives the identical epochs. That is the
ingest half of the exactly-once proof; the delivery half (watermark
journals + seq replay) is PR 5 and applies unchanged.

Two implementations:

:class:`DirectoryTailSource`
    Tails an arriving-file directory over the PR 14 storage plane.
    Directory listing order is NOT stable across filesystems (or across
    a crash), so discovery order is journaled: every newly discovered
    file appends a manifest record (``checkpoint.StreamJournal``), and
    a recovered tail replays the manifest FIRST — the file sequence a
    resumed pipeline sees is the journaled one, bit-for-bit, no matter
    what the directory says today.

:class:`SyntheticEventSource`
    A seeded, hermetic arrival process for tests:
    arrival times and event order are pure functions of
    ``(seed, event_index)`` via sha256 — the
    :class:`storage.source.SimulatedObjectStore` contract — so a fixed
    seed reproduces the byte-identical event sequence on any host.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
from typing import List, Optional, Sequence

from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)


@dataclasses.dataclass(frozen=True)
class StreamEvent:
    """One arrived object: a Parquet file entering the stream.

    ``index`` is the monotone discovery index (the event's identity in
    every journal); ``timestamp`` is STREAM time — the event's arrival
    instant in the source's own clock (file mtime for a directory tail,
    the seeded arrival process for synthetic events) — which is what
    watermarks and lateness are measured in, never wall clock."""

    index: int
    path: str
    timestamp: float
    size_bytes: int


class StreamSource:
    """The contract: :meth:`poll` returns newly arrived events, in a
    stable deterministic order, each exactly once per source instance.

    A RECOVERED instance (same construction arguments, same journal)
    re-yields the identical prefix before any new discoveries — callers
    that already sealed a prefix into windows skip it by event index
    (``WindowAssembler`` resume). ``exhausted`` turns True when the
    source knows no further events will ever arrive (a bounded synthetic
    stream); a directory tail never exhausts on its own."""

    def poll(self, now: Optional[float] = None) -> List[StreamEvent]:
        """Newly arrived events since the last poll. ``now`` advances
        sources with their own clock (synthetic stream time); sources
        paced by the outside world ignore it."""
        raise NotImplementedError

    @property
    def exhausted(self) -> bool:
        return False

    def close(self) -> None:
        """Release journal handles. Idempotent."""


class DirectoryTailSource(StreamSource):
    """Tail an arriving-file directory with journaled discovery order.

    Each :meth:`poll` lists ``directory``, admits not-yet-known files
    matching ``suffix`` in lexicographic order (stable *within* one
    poll), assigns them the next discovery indices, and appends one
    durable manifest record per file to the journal. On construction the
    manifest is replayed: journaled files are re-yielded first, in
    journal order, with their journaled timestamps/sizes — so recovery
    re-discovers the identical file sequence even if the directory now
    lists differently (or a file was compacted away).

    Files are only admitted once they are stat-able and non-empty;
    half-written files should be staged elsewhere and renamed in (the
    standard arrival discipline — rename is atomic on POSIX).
    """

    def __init__(self, directory: str,
                 journal_path: Optional[str] = None,
                 suffix: str = ".parquet"):
        from ray_shuffling_data_loader_tpu import checkpoint as ckpt
        self._directory = directory
        self._suffix = suffix
        self._known = set()  # paths already yielded (journal + live)
        self._next_index = 0
        self._replay: List[StreamEvent] = []
        self._journal = None
        if journal_path:
            for entry in ckpt.StreamJournal.load(journal_path):
                if entry.get("kind") != "file":
                    continue
                event = StreamEvent(index=int(entry["n"]),
                                    path=str(entry["path"]),
                                    timestamp=float(entry["ts"]),
                                    size_bytes=int(entry["size"]))
                self._replay.append(event)
                self._known.add(event.path)
                self._next_index = max(self._next_index, event.index + 1)
            self._journal = ckpt.StreamJournal(journal_path)
            if self._replay:
                logger.info(
                    "directory tail %s: recovered %d journaled events "
                    "(next index %d)", directory, len(self._replay),
                    self._next_index)

    def poll(self, now: Optional[float] = None) -> List[StreamEvent]:
        events, self._replay = self._replay, []
        try:
            names = sorted(os.listdir(self._directory))
        except FileNotFoundError:
            names = []
        for name in names:
            if not name.endswith(self._suffix):
                continue
            path = os.path.join(self._directory, name)
            if path in self._known:
                continue
            try:
                stat = os.stat(path)
            except OSError:
                continue  # vanished between list and stat
            if stat.st_size == 0:
                continue  # still being written; next poll
            event = StreamEvent(index=self._next_index, path=path,
                                timestamp=float(stat.st_mtime),
                                size_bytes=int(stat.st_size))
            if self._journal is not None:
                self._journal.append({"kind": "file", "n": event.index,
                                      "path": event.path,
                                      "ts": event.timestamp,
                                      "size": event.size_bytes})
            self._known.add(path)
            self._next_index += 1
            events.append(event)
        return events

    def close(self) -> None:
        if self._journal is not None:
            self._journal.close()
            self._journal = None


class SyntheticEventSource(StreamSource):
    """A deterministic seeded arrival process over a fixed file pool.

    Event ``i`` references ``files[i % len(files)]`` and arrives at a
    stream time built from seeded inter-arrival draws: each gap is
    ``mean_interarrival_s`` scaled by a jittered factor drawn as a pure
    function of ``(seed, i)`` via sha256 (the ``SimulatedObjectStore``
    idiom — no RNG state, bit-reproducible on any host). ``poll(now)``
    releases every not-yet-yielded event whose arrival time is <= ``now``;
    ``poll()`` with no clock releases exactly the next event — the
    drive-by-count mode tests use.

    ``total_events`` bounds the stream (``exhausted`` turns True after
    the last event); ``None`` streams forever.
    """

    def __init__(self, files: Sequence[str], seed: int = 0,
                 mean_interarrival_s: float = 1.0,
                 jitter_pct: float = 25.0,
                 total_events: Optional[int] = None,
                 start_time: float = 0.0):
        if not files:
            raise ValueError("SyntheticEventSource needs at least one file")
        self._files = [str(f) for f in files]
        self.seed = int(seed)
        self.mean_interarrival_s = float(mean_interarrival_s)
        self.jitter_pct = float(jitter_pct)
        self.total_events = total_events
        self.start_time = float(start_time)
        self._cursor = 0  # next event index to yield
        self._sizes = {}  # path -> cached size
        self._arrivals: List[float] = []  # memoized cumulative stream time

    def _draw(self, event_index: int) -> float:
        """Uniform [0, 1) from a stable hash — the faults.py idiom."""
        digest = hashlib.sha256(
            f"{self.seed}:arrival:{event_index}".encode()).digest()
        return int.from_bytes(digest[:8], "big") / float(1 << 64)

    def _gap(self, event_index: int) -> float:
        jitter = 1.0 + (self.jitter_pct / 100.0) * (
            2.0 * self._draw(event_index) - 1.0)
        return self.mean_interarrival_s * max(0.0, jitter)

    def arrival_time(self, event_index: int) -> float:
        """Stream time event ``event_index`` arrives — a pure function
        of ``(seed, event_index)`` (the prefix sums are memoized, not
        state: two instances at the same seed agree exactly)."""
        while len(self._arrivals) <= event_index:
            prev = self._arrivals[-1] if self._arrivals else self.start_time
            self._arrivals.append(prev + self._gap(len(self._arrivals)))
        return self._arrivals[event_index]

    def _size(self, path: str) -> int:
        size = self._sizes.get(path)
        if size is None:
            try:
                size = os.path.getsize(path)
            except OSError:
                size = 0
            self._sizes[path] = size
        return size

    def event(self, event_index: int) -> StreamEvent:
        """Event ``event_index``, pure in ``(seed, event_index)``."""
        path = self._files[event_index % len(self._files)]
        return StreamEvent(index=event_index, path=path,
                           timestamp=self.arrival_time(event_index),
                           size_bytes=self._size(path))

    def poll(self, now: Optional[float] = None) -> List[StreamEvent]:
        events: List[StreamEvent] = []
        while not self.exhausted:
            nxt = self.event(self._cursor)
            if now is not None and nxt.timestamp > now:
                break
            events.append(nxt)
            self._cursor += 1
            if now is None:
                break  # un-clocked poll: exactly the next event
        return events

    @property
    def exhausted(self) -> bool:
        return (self.total_events is not None
                and self._cursor >= self.total_events)
