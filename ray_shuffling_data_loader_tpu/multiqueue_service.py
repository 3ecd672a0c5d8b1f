"""Cross-process queue service: trainer processes attach by address.

The reference's queue is a Ray actor precisely so that trainer processes
spawned elsewhere (Horovod workers with no handle to driver state) can
rendezvous with the pipeline by name (reference: multiqueue.py:310-332,
SURVEY.md §1). Our in-process ``MultiQueue`` covers the SPMD
one-process-per-host topology; this module restores the reference's
*separate-trainer-process* topology:

- :func:`serve_queue` exports an existing ``MultiQueue`` over TCP. For
  each GET the server resolves the queued ref to its pyarrow Table and
  streams it as Arrow IPC — consumers never see executor internals, and
  data crosses the process boundary zero-copy on the Arrow buffers.
- :class:`RemoteQueue` is the consumer side: ``get(queue_idx)`` returns a
  materialized ``pa.Table`` (or ``None`` for the epoch-end sentinel), so
  it plugs straight into ``ShufflingDataset(batch_queue=...)`` /
  ``JaxShufflingDataset`` — same consumer code as in-process, matching
  the reference's connect-by-name contract (retry with doubling backoff).

Round-trip amortization (the reference's batched actor ops existed for
exactly this, reference: multiqueue.py:127-154): a GET request carries
``max_items``; the server answers with one *batch* — a blocking get for
the first item, then an opportunistic non-blocking drain of up to
``max_items - 1`` more, stopping at an epoch sentinel. The consumer
buffers the batch locally and, while the trainer chews on it, a
background prefetcher keeps one batched request in flight — so steady
state pays ~one round trip per ``max_items`` tables and overlaps the
wire time with consumption.

Wire format **v2** (process-crash recovery), little-endian. Requests are
a fixed 14-byte struct ``(u8 op, u8 flags, u32 a, u32 b, u32 c)``:

====================  =====================================================
op                    fields
====================  =====================================================
``1 OP_GET_BATCH``    a=queue_idx, b=max_items, c=ack watermark (the last
                      seq the consumer durably consumed for this queue;
                      ``0xFFFFFFFF`` = none). ``flags & FLAG_RESUME``:
                      first GET on a (re)connected socket — the server
                      rewinds its send cursor to the ack watermark and
                      replays exactly the unacked suffix.
``2 OP_HELLO``        a|b<<32 = 64-bit consumer id (lease identity; sent
                      once per connection, survives reconnects).
``3 OP_HEARTBEAT``    consumer-side lease keep-alive between GETs.
``4 OP_NACK``         a=queue_idx, b=seq of a frame whose CRC failed; the
                      server rewinds its send cursor to ``seq - 1`` and
                      re-sends from its replay buffer.
``5 OP_TENANT``       a|b<<32 = 64-bit consumer id, c = byte length of a
                      JSON ``TenantContext`` blob that follows the
                      request struct (tenancy/__init__.py canonical
                      form). Binds this consumer's lease — and the
                      ranks it subsequently GETs — to the tenant, so
                      the weighted-fair scheduler and per-tenant
                      metrics attribute its bytes. Optional: servers
                      ignore unknown-tenant blobs gracefully and
                      legacy clients never send it (v3.2, backward and
                      forward compatible).
====================  =====================================================

Responses are ``(u32 count)`` followed by ``count`` frames of
``(u8 kind, u32 epoch, u32 seq, u32 crc32, u64 row_offset, u64 length,
u32 task, payload)`` with kind 0=table IPC stream, 1=epoch-end
sentinel, 2=shuffle-failure (payload = error text). ``task`` is the
producing reduce task's lineage id (``0xFFFFFFFF`` = unknown), read
from the ``rsdl.trace`` schema metadata the reducer stamped on its
output — the cross-process causal-trace context (runtime/trace.py):
the consumer records it per frame, so a merged trace joins this
frame's fetch to the exact server-side reduce span that built it.
``seq`` is a per-queue
monotonic frame number (stable across server restarts — restored from
the delivered-watermark journal); ``crc32`` covers the payload bytes
(zlib CRC-32), so corruption anywhere on the wire or in a replayed
buffer is detected at the consumer and NACK'd; ``row_offset`` is the
cumulative row count of all preceding table frames in this queue's
stream, which lets a checkpoint-resuming consumer skip already-consumed
rows *absolutely* even when the stream replays from mid-epoch.

The **v1** format (pre-recovery, for archaeology): requests were
``(u8 op=1, u32 queue_idx, u32 max_items)`` and frames were bare
``(u8 kind, u64 length, payload)`` — no identity, no integrity, no ack:
the server popped items destructively before streaming them, so a
connection reset mid-response silently lost batches, and a killed
server process lost every queued table.

Recovery semantics built on v2 (see ``examples/fault_tolerance.md`` for
the full process-failure matrix):

- The server keeps a bounded per-queue **replay buffer** of unacked
  frames; acks piggyback on every GET and are journaled
  (``checkpoint.WatermarkJournal``), so a connection reset at ANY byte
  of a response is recovered by reconnect + FLAG_RESUME — exactly-once
  delivery, asserted bit-identical in tests.
- A killed server process is restarted by
  ``runtime.supervisor.ProcessSupervisor``; :func:`serve_pipeline`
  reloads the journal and re-runs the deterministic shuffle lineage for
  the in-flight epoch, re-enqueueing only the undelivered remainder.
- Per-consumer **leases** (heartbeats ride on every request plus an idle
  keep-alive thread) detect crashed trainers; expiry policy
  ``RSDL_QUEUE_ON_DEAD_CONSUMER`` = ``fail_fast`` | ``drain`` |
  ``redistribute`` decides whether the pipeline dies loudly, frees the
  dead rank's queues, or reroutes its undelivered tables to survivors.

Wire format **v3** (sharded zero-copy serving plane) extends v2 in
place — same request struct, same frame struct, same recovery matrix:

- The frame ``kind`` byte now carries a codec in its high nibble
  (``kind | codec << 4``; codec 0 = none, 1 = zlib, 2 = zstd, 3 = lz4).
  Streamed table payloads at/above ``RSDL_QUEUE_COMPRESSION_MIN_BYTES``
  are compressed when ``RSDL_QUEUE_COMPRESSION`` names a codec; ``crc``
  is computed over the UNCOMPRESSED payload, so corruption detection
  and NACK/replay semantics are byte-for-byte the v2 ones.
- New frame kind ``KIND_TABLE_HANDLE``: when server and consumer share
  a host (the consumer offered ``FLAG_HANDLES_OK`` on its HELLO), a
  table frame's payload is a ~100-byte shm **segment handle**
  (``{"path", "offset", "size", "crc"}``) instead of the table bytes —
  the consumer mmaps the very buffers the server serialized
  (``procpool.read_segment_buffer``), verifies the segment CRC off the
  mapped pages, and acks by seq exactly as before. The replay buffer
  retains the handle and PINS the segment via the NativeBufferPool
  ledger (``procpool.pin_segment``) until the ack lands — unacked
  bytes stay accounted, but exist exactly once, in shared memory.
  ``OP_NACK`` with ``c=1`` (``NACK_NO_HANDLE``) reports an unusable
  handle (a mis-detected host split, a vanished segment): the server
  marks that queue stream-only, rewinds, and replays the same frames
  as byte streams — delivery degrades, exactly-once does not.
- Queues are served by N **shard** processes placed by the plan query
  ``plan.ir.queue_shard`` (by trainer rank, so one rank's whole stream
  lives on one shard); a :class:`plan.ir.ShardMap` replaces the single
  ``(host, port)``. :class:`ShardedQueueServer` /
  :class:`ShardedRemoteQueue` are the in-process pair;
  ``runtime.supervisor.launch_supervised_queue_shards`` is the
  per-shard-supervised-process topology, each shard with its own
  watermark journal (``checkpoint.shard_journal_path``).

Wire format **v3.1** (delivery-latency plane, runtime/latency.py)
appends two clock stamps to every frame header: the payload's **birth**
(``(t_mono, t_unix, pid)`` taken where the reducer produced the table,
read from its ``rsdl.birth`` schema metadata) and the frame's
**queued** stamp (taken when the server built the frame). Zeroed
stamps mean "unknown" (sentinels, failure frames, tables from a
stamp-less producer). The server observes the ``birth_to_queued`` hop;
the consumer observes ``queued_to_delivered`` and the end-to-end
``birth_to_delivered`` into the ``rsdl_delivery_latency_seconds``
sketch, labeled by trainer rank. Latency honesty across failure:

- replay-buffer frames keep the stamps they were built with, so a
  reconnect/NACK replay is delivered with its ORIGINAL birth — a
  replay surfaces as the latency spike it really is;
- a frame's birth is also journaled (``WatermarkJournal.record_birth``)
  when the frame is first built, so a ``kill -9``'d server's restarted
  incarnation re-attaches the original births to the frames it
  regenerates — crash recovery cannot launder delivery latency into
  recompute-fresh stamps. Exactly-once semantics (seqs, CRCs, acks)
  are untouched by all of this: stamps are header-only evidence.
"""

from __future__ import annotations

import base64
import collections
import concurrent.futures as cf
import itertools
import json
import os
import shutil
import socket
import struct
import sys
import tempfile
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional, Tuple, Union

import pyarrow as pa

from ray_shuffling_data_loader_tpu import multiqueue as mq
from ray_shuffling_data_loader_tpu import procpool as pp
from ray_shuffling_data_loader_tpu import tenancy as rt_tenancy
from ray_shuffling_data_loader_tpu.dataset import ShuffleFailure
from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
from ray_shuffling_data_loader_tpu.tenancy import fairshare as rt_fairshare
from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
from ray_shuffling_data_loader_tpu.runtime import latency as rt_lat
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import policy as rt_policy
from ray_shuffling_data_loader_tpu.runtime import retry as rt_retry
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)

_REQUEST = struct.Struct("<BBIII")
_BATCH_HEADER = struct.Struct("<I")
#: v3.3 frame header: (kind|codec<<4, epoch, seq, crc, row_offset,
#: length, task) + the delivery-latency stamps — birth (t_mono, t_unix,
#: pid) then queued (t_mono, t_unix, pid); all-zero stamp = unknown —
#: then the placement ``generation`` (rebalance/): the fence a consumer
#: compares against its per-rank floor, so a zombie source shard's
#: post-migration frames are loudly droppable (the membership
#: incarnation-fencing idiom applied to queue placement). Pre-rebalance
#: servers stamp 0 and pre-rebalance clients never raise their floor,
#: so the fence is inert until a move commits.
_FRAME = struct.Struct("<BIIIQQIddIddII")


def _pack_stamp(stamp) -> tuple:
    """A latency Stamp (or None) as the 3 header fields."""
    if stamp is None:
        return (0.0, 0.0, 0)
    return (stamp.t_mono, stamp.t_unix, stamp.pid)


def _unpack_stamp(t_mono: float, t_unix: float, pid: int):
    if not t_mono and not t_unix:
        return None
    return rt_lat.Stamp(pid, t_mono, t_unix)

#: Frame ``task`` value for payloads with no lineage metadata
#: (sentinels, failure frames, tables from a non-reduce producer).
TASK_NONE = 0xFFFFFFFF

OP_GET_BATCH = 1
OP_HELLO = 2
OP_HEARTBEAT = 3
OP_NACK = 4
#: v3.2: bind a consumer lease to a TenantContext (a|b<<32 = consumer
#: id, c = length of the JSON blob following the request struct).
OP_TENANT = 5
#: v3.3: rebalance admin verb (rebalance/). ``flags`` is the phase
#: (REB_*), ``a`` = trainer rank, ``b`` = placement generation, ``c`` =
#: length of the JSON payload following the request. The response is a
#: u32 length + a ``checkpoint.crc_line`` JSON payload (the handoff
#: manifest for PREPARE; an ack/error blob otherwise).
OP_REBALANCE = 6

#: OP_REBALANCE phases: PREPARE seals the rank at a watermark and
#: exports the CRC'd handoff manifest; ADOPT imports it on the target
#: at the new generation (journaled — the durable half of COMMIT);
#: RELEASE drops the rank on the source and arms MOVED redirects;
#: UNSEAL is the abort path (source resumes, authoritative).
REB_PREPARE = 1
REB_ADOPT = 2
REB_RELEASE = 3
REB_UNSEAL = 4

FLAG_RESUME = 1
#: OP_HELLO flag: the consumer can mmap paths on the server's host
#: (loopback or a shared shm mount) — the server may answer table GETs
#: with segment handles instead of streamed bytes.
FLAG_HANDLES_OK = 2

KIND_TABLE = 0
KIND_SENTINEL = 1
KIND_FAILURE = 2
#: Table delivered as a shm segment handle (payload = JSON blob with
#: path/offset/size/crc); the header CRC covers the blob itself.
KIND_TABLE_HANDLE = 3
#: v3.3 redirect (rebalance/): the queue's rank migrated to another
#: shard. Payload = JSON blob with host/port/generation/rank; the
#: header CRC covers the blob and the header generation carries the
#: new placement generation (the consumer raises its fence floor
#: BEFORE redialing, so the old home's stale frames can never race in
#: after the redirect).
KIND_MOVED = 4

#: High nibble of the frame kind byte: payload codec.
_KIND_MASK = 0x0F
CODEC_NONE, CODEC_ZLIB, CODEC_ZSTD, CODEC_LZ4 = 0, 1, 2, 3
_CODEC_IDS = {"zlib": CODEC_ZLIB, "zstd": CODEC_ZSTD, "lz4": CODEC_LZ4}

#: OP_NACK ``c`` field: 0 = CRC corruption (rewind + re-send), 1 = the
#: consumer cannot use shm handles on this queue (downgrade to stream).
NACK_CRC = 0
NACK_NO_HANDLE = 1

#: "no watermark" on the wire (seq is u32; -1 internally).
ACK_NONE = 0xFFFFFFFF

DEFAULT_MAX_BATCH = 8

_LOOPBACK_HOSTS = frozenset({"127.0.0.1", "localhost", "::1"})


def _crc(payload) -> int:
    """CRC-32 (zlib-compatible) of a bytes-like payload, as an unsigned
    u32. Runs on the native hardware/slice-by-8 kernel when loaded
    (``RSDL_CRC_BACKEND`` selects; the polynomial and output match
    ``zlib.crc32`` bit for bit, so frames CRC'd by either backend verify
    under the other)."""
    from ray_shuffling_data_loader_tpu import native
    return native.crc32(memoryview(payload)) & 0xFFFFFFFF


_codec_warned: set = set()


def _resolve_compression() -> Optional[Tuple[int, Callable]]:
    """``(codec_id, compress)`` for the RSDL_QUEUE_COMPRESSION policy, or
    None when off. zstd/lz4 degrade to zlib with a one-time warning when
    the codec module is not importable (nothing is pip-installed here)."""
    name = str(rt_policy.resolve("queue", "queue_compression")).strip()
    name = name.lower()
    if name in ("", "off", "0", "none", "false"):
        return None
    if name not in _CODEC_IDS:
        raise ValueError(
            f"RSDL_QUEUE_COMPRESSION must be off, zlib, zstd or lz4; "
            f"got {name!r}")
    if name == "zstd":
        try:
            import zstandard
            return CODEC_ZSTD, zstandard.ZstdCompressor().compress
        except ImportError:
            pass
    elif name == "lz4":
        try:
            import lz4.frame
            return CODEC_LZ4, lz4.frame.compress
        except ImportError:
            pass
    if name != "zlib" and name not in _codec_warned:
        _codec_warned.add(name)
        logger.warning("queue compression codec %r is not installed; "
                       "degrading to zlib", name)
    # level 1: the wire win is latency-bound, not ratio-bound. zlib
    # accepts any buffer-protocol object, so pa.Buffer payloads compress
    # without an intermediate bytes copy.
    return CODEC_ZLIB, lambda data: zlib.compress(data, 1)


def _decompress(codec: int, payload) -> bytes:
    if codec == CODEC_ZLIB:
        return zlib.decompress(payload)
    if codec == CODEC_ZSTD:
        import zstandard
        return zstandard.ZstdDecompressor().decompress(bytes(payload))
    if codec == CODEC_LZ4:
        import lz4.frame
        return lz4.frame.decompress(bytes(payload))
    raise ValueError(f"unknown frame codec {codec}")


try:
    _IOV_MAX = os.sysconf("SC_IOV_MAX")
    if _IOV_MAX <= 0:
        _IOV_MAX = 1024
except (AttributeError, ValueError, OSError):
    _IOV_MAX = 1024


def _sendmsg_all(sock: socket.socket, buffers) -> None:
    """Write every buffer to ``sock`` with scatter-gather ``sendmsg`` —
    one syscall for a whole GET response (headers + payloads) where the
    legacy path issued ``1 + 2N`` ``sendall`` calls. Handles partial
    sends with a continuation loop and batches the iovec list under the
    kernel's IOV_MAX; the bytes on the wire are identical to the
    sequential-sendall ordering by construction."""
    views = [m for m in (memoryview(b).cast("B") for b in buffers)
             if m.nbytes]
    idx = 0
    while idx < len(views):
        sent = sock.sendmsg(views[idx:idx + _IOV_MAX])
        while sent > 0:
            view = views[idx]
            if sent >= view.nbytes:
                sent -= view.nbytes
                idx += 1
            else:
                views[idx] = view[sent:]
                sent = 0


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    remaining = n
    while remaining:
        chunk = sock.recv(min(remaining, 1 << 20))
        if not chunk:
            raise ConnectionError("peer closed connection mid-message")
        chunks.append(chunk)
        remaining -= len(chunk)
    return b"".join(chunks) if len(chunks) != 1 else chunks[0]


def _recv_payload(sock: socket.socket, n: int) -> memoryview:
    """Receive exactly ``n`` payload bytes into ONE preallocated buffer
    via ``recv_into`` — no per-chunk bytes objects, no join copy (the
    v2 path built a chunk list and re-copied it into one ``bytes``;
    large frames paid the payload twice). The returned memoryview is
    held end to end: CRC, decompression and Arrow IPC decode all read
    it in place."""
    buf = bytearray(n)
    view = memoryview(buf)
    received = 0
    while received < n:
        got = sock.recv_into(view[received:], n - received)
        if not got:
            raise ConnectionError("peer closed connection mid-message")
        received += got
    return view


def _serialize(table: pa.Table) -> pa.Buffer:
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as writer:
        writer.write_table(table)
    return sink.getvalue()


def _producer_task(table: pa.Table) -> int:
    """Producing reduce task from the ``rsdl.trace`` schema metadata the
    reducer stamped (``"seed:epoch:task"``); TASK_NONE when absent."""
    meta = table.schema.metadata
    if not meta:
        return TASK_NONE
    raw = meta.get(b"rsdl.trace")
    if not raw:
        return TASK_NONE
    try:
        return int(raw.rsplit(b":", 1)[-1])
    except ValueError:
        return TASK_NONE


def _materialize(item) -> Tuple[int, object, int, int]:
    """Resolve one queued item into ``(kind, data, num_rows, task)`` —
    ``data`` is the pa.Table for KIND_TABLE (serialization is the frame
    builder's business, because handle delivery writes a segment instead
    of wire bytes) and the payload bytes for sentinel/failure frames."""
    if item is None:
        return KIND_SENTINEL, b"", 0, TASK_NONE
    if isinstance(item, ShuffleFailure):
        return KIND_FAILURE, repr(item.error).encode(), 0, TASK_NONE
    try:
        table = item.result() if hasattr(item, "result") else item
        from ray_shuffling_data_loader_tpu import spill
        table = spill.unwrap(table)
        return KIND_TABLE, table, table.num_rows, _producer_task(table)
    except Exception as e:  # noqa: BLE001 - forwarded
        # A failed shuffle task ref: the consumer gets the real cause as
        # a failure frame, not a dead socket.
        return KIND_FAILURE, repr(e).encode(), 0, TASK_NONE


class _Frame:
    """One response frame held in the server replay buffer.

    ``wire`` is the exact on-wire payload (a pa.Buffer / memoryview /
    bytes — built once, never re-copied); ``crc`` covers the logical
    payload (pre-compression; for handle frames, the blob itself, with
    the segment CRC inside the blob); ``data_crc`` is the CRC of the
    serialized TABLE bytes, kept so a handle frame can be downgraded to
    a byte stream without re-CRCing the segment. ``payload_bytes`` is
    the logical (uncompressed) size; handle frames pin that many shm
    bytes in the buffer ledger (``ledger_id``) until acked.
    """

    __slots__ = ("seq", "kind", "epoch", "wire", "crc", "row_offset",
                 "nrows", "task", "codec", "payload_bytes", "data_crc",
                 "handle_path", "ledger_id", "birth", "queued",
                 "pending_codec", "tenant")

    def __init__(self, seq, kind, epoch, wire, crc, row_offset, nrows,
                 task=TASK_NONE, codec=CODEC_NONE, payload_bytes=None,
                 data_crc=None, handle_path=None, ledger_id=None,
                 birth=None, queued=None):
        self.seq = seq
        self.kind = kind
        self.epoch = epoch
        self.wire = wire
        self.crc = crc
        self.row_offset = row_offset
        self.nrows = nrows
        self.task = task
        self.codec = codec
        self.payload_bytes = (payload_bytes if payload_bytes is not None
                              else self.wire_len)
        self.data_crc = data_crc if data_crc is not None else crc
        self.handle_path = handle_path
        self.ledger_id = ledger_id
        # Delivery-latency stamps (runtime/latency.py). A frame in the
        # replay buffer keeps these, so replays carry the ORIGINAL
        # birth/queued times — late delivery stays visible as such.
        self.birth = birth
        self.queued = queued
        # (future, codec_id) while a codec-pool compression is in
        # flight; the frame serves the uncompressed buffer until
        # :meth:`resolve_codec` swaps the result in.
        self.pending_codec = None
        # The tenant this frame's bytes were CHARGED to at pop time
        # (set by _collect_frames). Ack/reset credit the same account,
        # so a rank->tenant rebind between pop and ack cannot strand
        # the debit on one tenant and land the credit on another.
        self.tenant = None

    def resolve_codec(self) -> int:
        """Finish a deferred codec-pool compression: swap the compressed
        payload in as the wire buffer iff it actually shrank (mirroring
        the inline path's keep-smaller rule). Returns the resident-byte
        delta (<= 0) the caller applies to its replay accounting."""
        fut, codec_id = self.pending_codec
        self.pending_codec = None
        old = self.wire_len
        compressed = fut.result()
        if len(compressed) < self.payload_bytes:
            self.wire = compressed
            self.codec = codec_id
        return self.wire_len - old

    @property
    def wire_len(self) -> int:
        wire = self.wire
        return wire.size if isinstance(wire, pa.Buffer) else len(wire)

    @property
    def size(self) -> int:
        """Bytes this unacked frame actually holds resident — the shm
        segment for handle frames, the (possibly compressed) wire
        payload otherwise. Each byte is charged exactly once: the wire
        buffer IS the replay copy, never a second materialization."""
        if self.kind == KIND_TABLE_HANDLE:
            return self.payload_bytes
        return self.wire_len


class _QueueState:
    """Per-queue-index sequencing + replay state (one consumer per queue
    by the ``queue_id = epoch * num_trainers + rank`` contract)."""

    __slots__ = ("next_seq", "sent_seq", "acked_seq", "acked_rows",
                 "rows_total", "replay", "replay_bytes", "done", "lock",
                 "no_handles", "births")

    def __init__(self, next_seq: int = 0, rows: int = 0,
                 done: bool = False, births=None):
        self.next_seq = next_seq       # seq the next popped item gets
        self.sent_seq = next_seq - 1   # last seq sent on the live conn
        self.acked_seq = next_seq - 1  # last seq the consumer acked
        self.acked_rows = rows         # rows delivered through acked_seq
        self.rows_total = rows         # rows assigned through next_seq-1
        self.replay: collections.deque = collections.deque()  # unacked
        self.replay_bytes = 0
        self.done = done               # sentinel acked: queue complete
        self.lock = threading.Lock()
        self.no_handles = False        # NACK_NO_HANDLE: stream-only
        #: seq -> original birth Stamp restored from the journal: a
        #: restarted server re-attaches these to the frames it
        #: regenerates, so crash replays keep their true birth.
        self.births: Dict[int, rt_lat.Stamp] = births or {}


class _Lease:
    __slots__ = ("consumer_id", "last_beat", "queues", "expired",
                 "tenant")

    def __init__(self, consumer_id: int):
        self.consumer_id = consumer_id
        self.last_beat = time.monotonic()
        self.queues: set = set()
        self.expired = False
        #: tenant id bound by OP_TENANT (None = unbound / legacy client;
        #: attribution then falls back to the server's config table).
        self.tenant: Optional[str] = None


class QueueMoved(Exception):
    """A GET hit a queue whose rank migrated to another shard (the
    server answered with a ``KIND_MOVED`` redirect). Carries everything
    a router needs to follow: the new ``address`` and the committed
    placement ``generation`` (the consumer's fence floor is already
    raised when this is thrown). :class:`ShardedRemoteQueue` handles it
    transparently; a bare :class:`RemoteQueue` surfaces it — a consumer
    that cached a ``(host, port)`` is exactly what the
    ``shard-affinity-assumption`` lint rule exists to catch."""

    def __init__(self, queue_index: int, rank: int,
                 address: Tuple[str, int], generation: int):
        super().__init__(
            f"queue {queue_index} (rank {rank}) moved to "
            f"{address[0]}:{address[1]} at placement generation "
            f"{generation}")
        self.queue_index = queue_index
        self.rank = rank
        self.address = (str(address[0]), int(address[1]))
        self.generation = generation


_POP_CLOSED = object()
_POP_EMPTY = object()


def _put_quiet(queue: mq.MultiQueue, queue_idx: int, item) -> bool:
    """Best-effort redistribution put: a full or shut-down target queue
    drops the item (degrading to drain) instead of wedging the lease
    drainer."""
    try:
        queue.put(queue_idx, item)
        return True
    except (mq.Full, RuntimeError):
        return False


class QueueServer:
    """Exports a ``MultiQueue`` over TCP with the v2 sequenced/acked
    protocol. One thread per consumer connection; the first item of each
    batched GET blocks server-side until the queue yields (and the ref
    materializes), so consumer backpressure is preserved; the rest of the
    batch is an opportunistic non-blocking drain.

    ``journal`` (a ``checkpoint.WatermarkJournal``) persists ack
    watermarks so a restarted server process (``serve_pipeline``) can
    regenerate exactly the undelivered remainder; ``initial_state`` is
    that journal's loaded ``{queue_idx: WatermarkEntry}`` map, which
    restores per-queue sequence numbers and row offsets so frame
    identity is stable across restarts. ``exit_on_crash_site=True``
    (the dedicated-server-process mode) turns an injected
    ``queue_server_crash`` fault into a hard ``os._exit`` — a real
    process death for the supervisor to recover, not an exception.
    """

    def __init__(self, queue: mq.MultiQueue, address: Tuple[str, int],
                 num_trainers: int = 1, journal=None,
                 initial_state: Optional[Dict[int, object]] = None,
                 exit_on_crash_site: bool = False,
                 shard_index: int = 0, num_shards: int = 1,
                 handle_dir: Optional[str] = None,
                 tenants: Optional[dict] = None,
                 placement: Optional[dict] = None):
        self._queue = queue
        self._num_trainers = max(1, num_trainers)
        self._journal = journal
        self._exit_on_crash_site = exit_on_crash_site
        self._shard_index = shard_index
        self._num_shards = max(1, num_shards)
        # -- live-migration placement plane (rebalance/). ``placement``
        # is the serialized state the controller journals:
        # ``{"generation": G, "overrides": {rank: shard},
        #    "rank_generations": {rank: gen}, "addresses": [[h, p]..]}``.
        # A rank whose override routes it *here* is adopted
        # (``_extra_ranks``); a rank that statically belongs here but is
        # overridden *away* answers GETs with a ``KIND_MOVED`` redirect
        # (``_moved``). ``_rank_gen`` is stamped into every outbound
        # frame header — the fence that makes a zombie source's
        # post-move frames loudly droppable at the consumer.
        placement = placement or {}
        self._placement_gen = int(placement.get("generation", 0))
        self._rank_gen: Dict[int, int] = {
            int(r): int(g)
            for r, g in dict(placement.get("rank_generations", {})).items()}
        self._sealed_ranks: set = set()
        self._extra_ranks: set = set()
        self._moved: Dict[int, Tuple[int, Tuple[str, int]]] = {}
        addresses = [tuple(a) for a in placement.get("addresses", ())]
        for r, s in dict(placement.get("overrides", {})).items():
            rank, shard_for_rank = int(r), int(s)
            static = rank % self._num_shards
            if shard_for_rank == static:
                continue
            if shard_for_rank == self._shard_index:
                self._extra_ranks.add(rank)
            elif static == self._shard_index:
                if shard_for_rank >= len(addresses):
                    raise ValueError(
                        f"placement override routes rank {rank} to shard "
                        f"{shard_for_rank} but only {len(addresses)} "
                        f"addresses were supplied")
                self._moved[rank] = (
                    self._rank_gen.get(rank, self._placement_gen),
                    (str(addresses[shard_for_rank][0]),
                     int(addresses[shard_for_rank][1])))
        self._timeout_s = rt_policy.resolve("queue", "queue_timeout_s")
        self._nodelay = rt_policy.resolve("queue", "queue_nodelay")
        self._replay_budget = rt_policy.resolve("queue",
                                                "queue_replay_bytes")
        # -- tenancy plane (tenancy/): weighted-fair sharing of the
        # replay-byte budget. ``tenants`` is the config table
        # ``{tenant_id: {"weight": w, "ranks": [...]}}``; with no table
        # and no OP_TENANT binding the scheduler stays None and every
        # byte of behavior is the pre-tenancy single-tenant one.
        self._tenants = rt_tenancy.tenants_from_config(tenants)
        self._tenant_lock = threading.Lock()
        self._rank_tenant: Dict[int, str] = {}
        for tenant_id, spec in self._tenants.items():
            for rank in spec.get("ranks", ()):
                self._rank_tenant[int(rank)] = tenant_id
        self._fair: Optional[rt_fairshare.FairShare] = None
        if self._tenants:
            self._fair = rt_fairshare.FairShare(
                {t: spec["weight"] for t, spec in self._tenants.items()},
                int(self._replay_budget),
                quantum_bytes=int(rt_policy.resolve(
                    "queue", "tenant_drr_quantum_bytes")),
                active_window_s=float(rt_policy.resolve(
                    "queue", "tenant_active_window_s")))
        self._floor_pace_s = float(rt_policy.resolve(
            "queue", "tenant_floor_pace_s"))
        self._tenant_replay: Dict[str, int] = {}
        self._tenant_metrics: Dict[str, tuple] = {}
        self._lease_timeout_s = rt_policy.resolve("queue",
                                                  "queue_lease_timeout_s")
        self._on_dead_consumer = rt_policy.resolve("queue",
                                                   "on_dead_consumer")
        if self._on_dead_consumer not in ("fail_fast", "drain",
                                          "redistribute"):
            raise ValueError(
                f"RSDL_QUEUE_ON_DEAD_CONSUMER must be fail_fast, drain, or "
                f"redistribute, got {self._on_dead_consumer!r}")
        self._delivery = rt_policy.resolve("queue", "queue_delivery")
        if self._delivery not in ("auto", "stream", "handle"):
            raise ValueError(
                f"RSDL_QUEUE_DELIVERY must be auto, stream or handle, "
                f"got {self._delivery!r}")
        self._compression = _resolve_compression()
        self._compression_min = rt_policy.resolve(
            "queue", "queue_compression_min_bytes")
        self._sendmsg = bool(rt_policy.resolve("queue", "queue_sendmsg"))
        codec_threads = int(rt_policy.resolve("queue",
                                              "queue_codec_threads"))
        # Bounded codec pool: frame compression runs on these threads
        # (overlapping the serving thread's next pop/serialize) and is
        # capped at codec_threads cores across every connection. 0 =
        # compress inline on the serving thread (the legacy shape).
        self._codec_pool = (
            cf.ThreadPoolExecutor(
                max_workers=codec_threads,
                thread_name_prefix=f"rsdl-codec-s{shard_index}")
            if self._compression and codec_threads > 0 else None)
        self._handle_dir = handle_dir
        self._own_handle_dir = False
        self._handle_names = itertools.count()
        shard = str(shard_index)
        self._payload_bytes = rt_metrics.counter(
            "rsdl_queue_payload_bytes_total",
            "logical (uncompressed) table-payload bytes served",
            shard=shard)
        self._wire_bytes = rt_metrics.counter(
            "rsdl_queue_bytes_on_wire_total",
            "payload bytes actually written to consumer sockets",
            shard=shard)
        self._handle_hits = rt_metrics.counter(
            "rsdl_queue_handle_hits_total",
            "table frames delivered as shm segment handles", shard=shard)
        self._handle_misses = rt_metrics.counter(
            "rsdl_queue_handle_misses_total",
            "table frames streamed as bytes (no handle possible)",
            shard=shard)
        self._compression_saved = rt_metrics.counter(
            "rsdl_queue_compression_saved_bytes_total",
            "payload bytes saved by frame compression", shard=shard)
        self._shard_depth = rt_metrics.gauge(
            "rsdl_queue_shard_depth",
            "items resident across this shard's served queues",
            shard=shard)
        self._anchors = rt_lat.ClockAnchors()
        self._states: Dict[int, _QueueState] = {}
        self._states_lock = threading.Lock()
        if initial_state:
            for q, entry in initial_state.items():
                births = {
                    seq: rt_lat.Stamp(int(pid), float(tm), float(tu))
                    for seq, (pid, tm, tu) in
                    getattr(entry, "births", {}).items()}
                self._states[q] = _QueueState(next_seq=entry.seq + 1,
                                              rows=entry.rows,
                                              done=entry.done,
                                              births=births)
        self._leases: Dict[int, _Lease] = {}
        self._lease_lock = threading.Lock()
        self._lease_thread: Optional[threading.Thread] = None
        self._drained_ranks: set = set()
        self._conn_threads: set = set()
        self._conn_lock = threading.Lock()
        self._replayed = rt_metrics.counter(
            "rsdl_queue_frames_replayed_total",
            "frames re-sent from the server replay buffer")
        self._nacked = rt_metrics.counter(
            "rsdl_queue_frames_nacked_total",
            "frames NACK'd by consumers (CRC mismatch)")
        self._lease_expiries = rt_metrics.counter(
            "rsdl_queue_lease_expiries_total",
            "consumer leases that expired without a heartbeat")
        self._consumers_alive = rt_metrics.gauge(
            "rsdl_queue_consumers_alive",
            "consumers with a live (unexpired) lease")
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind(address)
        listener.listen(16)
        # Finite accept timeout: the accept loop ticks so close() can
        # stop it deterministically on every platform (and the
        # socket-op-no-timeout invariant holds by construction).
        listener.settimeout(1.0)
        self._listener = listener
        self._closed = threading.Event()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True, name="rsdl-qserve-accept")
        self._accept_thread.start()

    @property
    def address(self) -> Tuple[str, int]:
        return self._listener.getsockname()

    # -- connection plumbing ------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._closed.is_set():
            try:
                conn, peer = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            if self._nodelay:
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # Socket hygiene (runtime/policy.py): a finite recv timeout
            # so a wedged peer cannot pin this handler past the watchdog;
            # 0 disables (deliberate infinite wait).
            conn.settimeout(self._timeout_s or None)
            thread = threading.Thread(target=self._serve_conn, args=(conn,),
                                      daemon=True, name="rsdl-qserve-conn")
            # Registered and started under one lock: close() joins what
            # it finds registered, and a thread not yet started cannot be
            # joined.
            with self._conn_lock:
                self._conn_threads.add(thread)
                thread.start()

    def _state(self, queue_idx: int) -> _QueueState:
        with self._states_lock:
            state = self._states.get(queue_idx)
            if state is None:
                state = self._states[queue_idx] = _QueueState()
            return state

    def _pop(self, queue_idx: int, blocking: bool, consumer_id):
        """One queue pop; blocking pops tick on a short timeout so close()
        (and the consumer's lease) stay live while the queue is idle.
        ``mq.ShutdownError`` (the QUEUE shut down, not this server)
        propagates so the consumer gets a loud failure frame."""
        rank = plan_ir.queue_rank(queue_idx, self._num_trainers)
        while not self._closed.is_set():
            try:
                return self._queue.get(queue_idx, block=blocking,
                                       timeout=0.25 if blocking else None)
            except mq.Empty:
                if not blocking:
                    return _POP_EMPTY
                if rank in self._sealed_ranks:
                    # The rank was PREPARE-sealed while this GET was
                    # parked on an idle live stream. The caller holds
                    # the queue's state lock, which the migration's
                    # export needs to snapshot the replay suffix — so
                    # give the lock back with an empty batch (the
                    # consumer refetches and lands on the seal path /
                    # MOVED redirect) instead of stalling PREPARE
                    # behind the next produced item.
                    return _POP_EMPTY
                # A consumer blocked in a server-side GET is alive by
                # definition — beat its lease while it waits.
                self._lease_beat(consumer_id, None)
        return _POP_CLOSED

    # -- frame building / serving -------------------------------------------

    def _epoch_of(self, queue_idx: int) -> int:
        return plan_ir.queue_epoch(queue_idx, self._num_trainers)

    def _owns_queue(self, queue_idx: int) -> bool:
        rank = plan_ir.queue_rank(queue_idx, self._num_trainers)
        if rank in self._moved:
            return False
        if rank in self._extra_ranks:
            return True
        return (self._num_shards <= 1
                or plan_ir.queue_shard(queue_idx, self._num_trainers,
                                       self._num_shards)
                == self._shard_index)

    # -- tenancy attribution ------------------------------------------------

    def _tenant_of_queue(self, queue_idx: int) -> str:
        """The tenant a queue's bytes belong to: the config table's
        rank mapping (or an OP_TENANT binding recorded against the
        rank), else the default tenant — attribution never fails, it
        degrades to the single-tenant account."""
        rank = plan_ir.queue_rank(queue_idx, self._num_trainers)
        with self._tenant_lock:
            return self._rank_tenant.get(rank,
                                         rt_tenancy.DEFAULT_TENANT_ID)

    def _tenant_counters(self, tenant_id: str) -> tuple:
        """(delivered-bytes counter, replay gauge, budget gauge) for one
        tenant, cached — label cardinality is bounded by the tenant
        table plus wire-bound tenants."""
        with self._tenant_lock:
            counters = self._tenant_metrics.get(tenant_id)
            if counters is None:
                counters = self._tenant_metrics[tenant_id] = (
                    rt_metrics.counter(
                        "rsdl_tenant_bytes_delivered_total",
                        "payload bytes delivered per tenant",
                        tenant=tenant_id),
                    rt_metrics.gauge(
                        "rsdl_tenant_replay_bytes",
                        "unacked (in-flight) bytes held per tenant",
                        tenant=tenant_id),
                    rt_metrics.gauge(
                        "rsdl_tenant_budget_bytes",
                        "weighted-fair share of the replay budget",
                        tenant=tenant_id),
                )
            return counters

    def _charge_tenant(self, queue_idx: int, delta: int,
                       tenant_id: Optional[str] = None) -> str:
        """Mirror every replay-byte mutation into the owning tenant's
        ledger (the quantity the fair scheduler partitions). Positive
        deltas also charge the DRR deficit — delivered bytes are what
        the round-robin meters.

        Returns the tenant charged. Pop-time callers pin it on the
        frame; release paths pass that pinned tenant back, so the
        credit lands on the account that was debited even when the
        rank's tenant binding changed in between (an OP_TENANT landing
        after GETs already charged the default tenant would otherwise
        drive the new tenant's ledger permanently negative while the
        old one stays inflated)."""
        if tenant_id is None:
            tenant_id = self._tenant_of_queue(queue_idx)
        with self._tenant_lock:
            self._tenant_replay[tenant_id] = \
                self._tenant_replay.get(tenant_id, 0) + delta
            replay = self._tenant_replay[tenant_id]
        self._tenant_counters(tenant_id)[1].set(replay)
        if delta > 0 and self._fair is not None:
            self._fair.charge(tenant_id, delta)
        return tenant_id

    def _tenant_may_pop(self, tenant_id: str) -> bool:
        """The weighted-fair gate in the GET pop loop (frames past the
        first only): a tenant may keep popping while its unacked bytes
        sit under its weighted share of the replay budget AND the
        deficit round robin grants it another frame."""
        fair = self._fair
        if fair is None:
            return True
        budget = fair.budget(tenant_id)
        self._tenant_counters(tenant_id)[2].set(budget)
        with self._tenant_lock:
            replay = self._tenant_replay.get(tenant_id, 0)
        if replay >= budget:
            return False
        return fair.grant(tenant_id)

    def _bind_wire_tenant(self, consumer_id: Optional[int],
                          blob: bytes) -> None:
        """OP_TENANT: bind a consumer's lease (and, as its GETs arrive,
        its ranks) to the announced TenantContext. A malformed blob is
        logged and ignored — tenancy is a policy layer, never a way to
        kill a serving connection."""
        try:
            ctx = rt_tenancy.TenantContext.from_json(blob)
        except (ValueError, KeyError, TypeError,
                UnicodeDecodeError) as e:
            logger.warning("ignoring malformed OP_TENANT blob: %s", e)
            return
        # The whole bind — known-check, table mutation, FairShare
        # creation/weight registration — is one critical section: two
        # concurrent OP_TENANT binds racing here could each observe
        # ``_fair is None`` and build rival schedulers (losing one
        # tenant's weight), or one could iterate ``_tenants`` while the
        # other mutates it. FairShare's own lock is leaf-level, so
        # taking it (set_weight) under _tenant_lock cannot invert.
        with self._tenant_lock:
            known = ctx.tenant_id in self._tenants
            if not known:
                self._tenants[ctx.tenant_id] = \
                    {"weight": ctx.effective_weight}
            if self._fair is None:
                self._fair = rt_fairshare.FairShare(
                    {t: spec["weight"]
                     for t, spec in self._tenants.items()},
                    int(self._replay_budget),
                    quantum_bytes=int(rt_policy.resolve(
                        "queue", "tenant_drr_quantum_bytes")),
                    active_window_s=float(rt_policy.resolve(
                        "queue", "tenant_active_window_s")))
            elif not known:
                # The server-side config table wins over a
                # wire-announced weight for tenants it already names.
                self._fair.set_weight(ctx.tenant_id,
                                      ctx.effective_weight)
        with self._lease_lock:
            if consumer_id is not None:
                lease = self._leases.get(consumer_id)
                if lease is not None:
                    lease.tenant = ctx.tenant_id
        logger.info("consumer %s bound to tenant %r (weight %.1f)",
                    f"{consumer_id:x}" if consumer_id is not None
                    else "?", ctx.tenant_id, ctx.effective_weight)

    def _ensure_handle_dir(self) -> Optional[str]:
        """The segment dir for handle frames (created on first use under
        the procpool shm root, or the path the supervised config pinned
        so restarts reuse it)."""
        if self._handle_dir is None:
            self._handle_dir = tempfile.mkdtemp(
                prefix=f"rsdl-qhandles-s{self._shard_index}-",
                dir=pp.shm_base_dir())
            self._own_handle_dir = True
        else:
            os.makedirs(self._handle_dir, exist_ok=True)
        return self._handle_dir

    def _release_frame(self, frame: _Frame) -> None:
        """Drop an unacked frame's resident bytes: unpin (and unlink)
        the shm segment for handle frames — consumers that already
        mmap'd it keep their mapping."""
        pp.release_segment(frame.ledger_id, frame.handle_path,
                           unlink=True)
        frame.ledger_id = None

    def _make_frame(self, queue_idx: int, seq: int, kind: int, data,
                    nrows: int, task: int, row_offset: int,
                    want_handle: bool,
                    restored_birth=None) -> _Frame:
        """Build one frame, serializing the table exactly once. Handle
        delivery publishes the serialized buffer as a shm segment and
        puts only the ~100-byte handle blob on the wire; streamed
        delivery keeps the pa.Buffer AS the wire payload (the same
        object rides the socket and the replay buffer — satellite fix:
        no fresh ``bytes`` copy), optionally compressed.

        Latency plane: ``restored_birth`` (the journal's stamp for this
        seq, when this server is a restarted incarnation regenerating
        it) wins over the table's own ``rsdl.birth`` metadata — the
        regenerated table carries a recompute-fresh stamp, and using it
        would launder the crash out of the latency record. A NEWLY
        assigned seq's birth is journaled here (flush, no fsync), and
        the ``birth_to_queued`` hop is observed server-side."""
        epoch = self._epoch_of(queue_idx)
        queued = rt_lat.now_stamp()
        if kind != KIND_TABLE:
            return _Frame(seq, kind, epoch, data, _crc(data), row_offset,
                          nrows, task, queued=queued)
        birth = restored_birth
        if birth is None:
            meta = data.schema.metadata
            birth = rt_lat.parse_stamp(
                meta.get(rt_lat.BIRTH_META_KEY) if meta else None)
            if birth is not None and self._journal is not None:
                self._journal.record_birth(queue_idx, seq, *birth)
        if birth is not None:
            rt_lat.observe_hop(
                rt_lat.HOP_BIRTH_TO_QUEUED,
                str(plan_ir.queue_rank(queue_idx, self._num_trainers)),
                self._anchors.latency_s(birth, now_mono=queued.t_mono,
                                        now_unix=queued.t_unix))
        buf = _serialize(data)
        logical = buf.size
        data_crc = _crc(buf)
        if want_handle and self._delivery != "stream":
            path = os.path.join(
                self._ensure_handle_dir(),
                f"h{os.getpid()}_{next(self._handle_names)}.arrow")
            pp.write_buffer_segment(buf, path)
            ledger_id = pp.pin_segment(logical)
            blob = json.dumps({"path": path, "offset": 0,
                               "size": logical,
                               "crc": data_crc}).encode()
            self._handle_hits.inc()
            return _Frame(seq, KIND_TABLE_HANDLE, epoch, blob, _crc(blob),
                          row_offset, nrows, task,
                          payload_bytes=logical, data_crc=data_crc,
                          handle_path=path, ledger_id=ledger_id,
                          birth=birth, queued=queued)
        self._handle_misses.inc()
        wire: object = buf
        codec = CODEC_NONE
        pending = None
        if self._compression and logical >= self._compression_min:
            codec_id, compress = self._compression
            if self._codec_pool is not None:
                # Deferred: the pool compresses while the serving thread
                # pops/serializes the next frame; _collect_frames
                # resolves every pending codec before the batch leaves
                # its queue lock. The CRC was taken pre-compression, so
                # the deferral cannot change what the consumer verifies.
                pending = (self._codec_pool.submit(compress, buf),
                           codec_id)
            else:
                compressed = compress(buf)
                if len(compressed) < logical:
                    wire, codec = compressed, codec_id
                    self._compression_saved.inc(logical - len(compressed))
        frame = _Frame(seq, KIND_TABLE, epoch, wire, data_crc, row_offset,
                      nrows, task, codec=codec, payload_bytes=logical,
                      data_crc=data_crc, birth=birth, queued=queued)
        frame.pending_codec = pending
        return frame

    def _downgrade_frame(self, frame: _Frame) -> _Frame:
        """Replay a handle frame as a byte stream (NACK_NO_HANDLE): mmap
        the segment the server itself wrote and make its buffer the wire
        payload. Seq/row accounting and the segment pin carry over, so
        ack release and exactly-once hold unchanged; the CRC is the
        stored segment CRC — the bytes are identical by construction."""
        buf = pp.read_segment_buffer(frame.handle_path)
        downgraded = _Frame(frame.seq, KIND_TABLE, frame.epoch, buf,
                            frame.data_crc, frame.row_offset,
                            frame.nrows, frame.task,
                            payload_bytes=frame.payload_bytes,
                            data_crc=frame.data_crc,
                            handle_path=frame.handle_path,
                            ledger_id=frame.ledger_id,
                            birth=frame.birth, queued=frame.queued)
        downgraded.tenant = frame.tenant
        return downgraded

    def _note_shard_depth(self) -> None:
        if rt_telemetry.stamp():
            with self._states_lock:
                queues = list(self._states)
            self._shard_depth.set(sum(self._queue.sizes(queues)))

    def _apply_ack(self, queue_idx: int, state: _QueueState,
                   ack: int) -> None:
        state.acked_seq = ack
        done = state.done
        while state.replay and state.replay[0].seq <= ack:
            frame = state.replay.popleft()
            state.replay_bytes -= frame.size
            self._charge_tenant(queue_idx, -frame.size, frame.tenant)
            self._release_frame(frame)
            state.acked_rows = frame.row_offset + frame.nrows
            if frame.kind == KIND_SENTINEL:
                done = True
        state.done = done
        if self._journal is not None:
            self._journal.record(queue_idx, ack, state.acked_rows,
                                 done=done)

    def _collect_frames(self, queue_idx: int, max_items: int,
                        ack: Optional[int], resume: bool,
                        consumer_id,
                        handles_ok: bool = False) -> Optional[List[_Frame]]:
        """Assemble one response: unacked replay suffix first, then new
        pops. Returns None when the server closed under the blocking get.
        ``handles_ok`` is the CONNECTION's capability (the consumer's
        HELLO offered shm-handle delivery); a queue NACK'd with
        NACK_NO_HANDLE stays stream-only regardless.
        """
        # Fault site: a crash HERE models the whole server process dying
        # mid-epoch (the supervisor's recovery unit). In dedicated-server
        # mode it is a real process exit; in-process it downs the server.
        try:
            rt_faults.inject("queue_server_crash",
                             epoch=self._epoch_of(queue_idx),
                             task=queue_idx)
        except rt_faults.InjectedFault:
            if self._exit_on_crash_site:
                os._exit(137)
            self.close()
            raise
        tenant_id = self._tenant_of_queue(queue_idx)
        if self._fair is not None:
            # Every GET marks its tenant active: the fair scheduler's
            # work-conserving partition is over tenants currently asking.
            self._fair.touch(tenant_id)
            if not sum(self._queue.sizes([queue_idx])):
                # Nothing queued for this tenant right now (a live
                # stream between frames): drop its claim so unspent
                # credit cannot gate tenants that DO have work — work
                # conservation without waiting out the activity window.
                # It rejoins with a fresh quantum on its next GET.
                self._fair.idle(tenant_id)
            elif self._floor_pace_s > 0 and not self._tenant_may_pop(
                    tenant_id):
                # Pace the liveness floor: a tenant the scheduler is
                # currently denying still gets its one frame per GET
                # (liveness — acks must always be able to progress),
                # but not at raw round-trip rate. On a fast loopback an
                # unpaced floor alone out-delivers the DRR grants and
                # the configured weights stop shaping anything.
                # ``_tenant_may_pop`` consumes no credit (only
                # ``charge`` does), so this probe never alters the
                # round-robin accounting.
                time.sleep(self._floor_pace_s)
        state = self._state(queue_idx)
        rank = plan_ir.queue_rank(queue_idx, self._num_trainers)
        sealed = rank in self._sealed_ranks
        with state.lock:
            want_handle = handles_ok and not state.no_handles
            if ack is not None and ack > state.acked_seq:
                self._apply_ack(queue_idx, state, ack)
            if resume:
                # Reconnect: rewind the send cursor to the watermark so
                # the unacked suffix replays — the frames a reset ate.
                state.sent_seq = state.acked_seq
            if not want_handle and any(
                    f.kind == KIND_TABLE_HANDLE and f.seq > state.sent_seq
                    for f in state.replay):
                # The consumer (or a NACK_NO_HANDLE) withdrew handle
                # capability: downgrade the unsent handle frames to byte
                # streams in place — same seqs, same bytes, same CRCs.
                state.replay = collections.deque(
                    self._downgrade_frame(f)
                    if f.kind == KIND_TABLE_HANDLE
                    and f.seq > state.sent_seq else f
                    for f in state.replay)
            frames: List[_Frame] = [f for f in state.replay
                                    if f.seq > state.sent_seq][:max_items]
            if frames:
                self._replayed.inc(len(frames))
                rt_telemetry.record("frame_replay", epoch=frames[0].epoch,
                                    task=queue_idx, count=len(frames))
            try:
                # A PREPARE-sealed rank serves ONLY its replay suffix —
                # the handoff manifest snapshotted everything past the
                # watermark, so popping anything new here would fork the
                # stream the target is about to adopt.
                while (not sealed and len(frames) < max_items
                       and (not frames
                            or frames[-1].kind in (KIND_TABLE,
                                                   KIND_TABLE_HANDLE))):
                    if frames and state.replay_bytes > self._replay_budget:
                        # Backpressure: unacked bytes are at budget — stop
                        # popping (never below one frame per GET, so the
                        # consumer's acks always make progress possible).
                        break
                    if frames and not self._tenant_may_pop(tenant_id):
                        # Weighted-fair backpressure (tenancy/fairshare):
                        # this tenant's unacked bytes reached its share
                        # of the budget, or the deficit round robin owes
                        # the next frames to a competing tenant. Same
                        # one-frame-per-GET floor as the global check.
                        break
                    item = self._pop(queue_idx, blocking=not frames,
                                     consumer_id=consumer_id)
                    if item is _POP_CLOSED:
                        return None if not frames else frames
                    if item is _POP_EMPTY:
                        break
                    kind, data, nrows, task = _materialize(item)
                    seq = state.next_seq
                    state.next_seq += 1
                    row_offset = state.rows_total
                    state.rows_total += nrows
                    if seq <= state.acked_seq:
                        # Regenerated-after-restart item the consumer
                        # already consumed (its ack outran the journal's
                        # last fsync): drop it, but keep the row
                        # accounting advancing.
                        state.acked_rows = row_offset + nrows
                        state.births.pop(seq, None)
                        continue
                    frame = self._make_frame(queue_idx, seq, kind, data,
                                             nrows, task, row_offset,
                                             want_handle,
                                             restored_birth=state.births.pop(
                                                 seq, None))
                    state.replay.append(frame)
                    state.replay_bytes += frame.size
                    frame.tenant = self._charge_tenant(queue_idx,
                                                       frame.size)
                    frames.append(frame)
            finally:
                # Land every deferred codec-pool compression before the
                # batch leaves the queue lock (runs on EVERY exit, the
                # mid-loop server-closed return included): the replay
                # buffer and the wire must serve the same bytes.
                for f in frames:
                    if f.pending_codec is not None:
                        delta = f.resolve_codec()
                        state.replay_bytes += delta
                        if delta:
                            self._charge_tenant(queue_idx, delta,
                                                f.tenant)
                        if delta < 0:
                            self._compression_saved.inc(-delta)
            if frames:
                state.sent_seq = frames[-1].seq
        if sealed and not frames:
            # Pace a consumer polling a sealed-and-drained queue: an
            # empty batch is a valid response (the client just refetches)
            # but an unpaced loop would spin the loopback until the
            # MOVED redirect or an UNSEAL lands.
            time.sleep(0.05)
        self._note_shard_depth()
        return frames

    def _send_frames(self, conn: socket.socket, queue_idx: int,
                     frames: List[_Frame]) -> None:
        """Write one GET response. With ``RSDL_QUEUE_SENDMSG`` (default
        on) the batch header plus every frame header and payload gather
        into a single scatter-gather ``sendmsg`` call — one syscall per
        response instead of the legacy ``1 + 2N`` ``sendall``s — with
        byte-for-byte identical wire content, chaos sites included: a
        torn header flushes exactly the bytes the sequential path would
        have pushed before the injected reset."""
        gather = self._sendmsg and hasattr(conn, "sendmsg")
        gen = self._rank_gen.get(
            plan_ir.queue_rank(queue_idx, self._num_trainers), 0)
        vecs: List = [_BATCH_HEADER.pack(len(frames))]
        if not gather:
            conn.sendall(vecs[0])
            vecs.clear()
        for frame in frames:
            size = frame.wire_len
            kind_byte = frame.kind | (frame.codec << 4)
            header = _FRAME.pack(kind_byte, frame.epoch, frame.seq,
                                 frame.crc, frame.row_offset, size,
                                 frame.task,
                                 *_pack_stamp(frame.birth),
                                 *_pack_stamp(frame.queued), gen)
            try:
                rt_faults.inject("conn_reset_midframe", epoch=frame.epoch,
                                 task=queue_idx)
            except rt_faults.InjectedFault as e:
                # A torn frame then a hard close: the consumer observes
                # bytes stopping mid-frame — the exact reset-mid-response
                # shape v2 recovery exists for.
                if gather:
                    vecs.append(header[:_FRAME.size // 2])
                    _sendmsg_all(conn, vecs)
                else:
                    # Sequential fallback's torn-frame chaos write — one
                    # deliberate half-header, nothing to gather.
                    # rsdl-lint: disable=sendall-in-loop
                    conn.sendall(header[:_FRAME.size // 2])
                raise ConnectionError(
                    f"injected connection reset mid-frame: {e}") from e
            corrupt = False
            if size:
                # Only payload frames are corruptible: firing the site
                # on a zero-length sentinel would record an "injected"
                # event with nothing on the wire to corrupt — the
                # consumer sees a clean CRC and the chaos<->telemetry
                # join (fault_events_joinable) loses the event.
                try:
                    rt_faults.inject("frame_corrupt", epoch=frame.epoch,
                                     task=queue_idx)
                except rt_faults.InjectedFault:
                    corrupt = True
            payload = None
            if size:
                if corrupt:
                    # Flip one payload byte ON THE WIRE only — the replay
                    # buffer keeps the good copy the NACK re-send needs.
                    damaged = bytearray(memoryview(frame.wire))
                    damaged[-1] ^= 0xFF
                    payload = damaged
                else:
                    # pa.Buffer / memoryview go straight to the socket —
                    # the serialized table is never flattened into a
                    # fresh bytes object on this path.
                    payload = frame.wire
            if gather:
                vecs.append(header)
                if payload is not None:
                    vecs.append(payload)
            else:
                # The RSDL_QUEUE_SENDMSG=0 sequential arm: kept as the
                # byte-for-byte reference the gather path is tested
                # against, so these two writes stay per-frame by design.
                # rsdl-lint: disable=sendall-in-loop
                conn.sendall(header)
                if payload is not None:
                    # rsdl-lint: disable=sendall-in-loop
                    conn.sendall(payload)
            if frame.kind in (KIND_TABLE, KIND_TABLE_HANDLE):
                self._wire_bytes.inc(size)
                self._payload_bytes.inc(frame.payload_bytes)
                self._tenant_counters(self._tenant_of_queue(queue_idx))[
                    0].inc(frame.payload_bytes)
        if gather:
            _sendmsg_all(conn, vecs)

    def _fail_frame(self, text: bytes) -> bytes:
        """A one-frame failure response (v2 shape: count + header +
        payload). Failure frames stamp placement generation 0 — they
        are exempt from the consumer's fence so an error always lands,
        even from a zombie."""
        return (_BATCH_HEADER.pack(1)
                + _FRAME.pack(KIND_FAILURE, 0, ACK_NONE, _crc(text), 0,
                              len(text), TASK_NONE, 0.0, 0.0, 0,
                              0.0, 0.0, 0, 0) + text)

    def _moved_frame(self, queue_idx: int, rank: int) -> bytes:
        """A one-frame ``KIND_MOVED`` redirect: the JSON payload carries
        the adopting shard's address and the committed placement
        generation; the header's generation field repeats it so the
        consumer raises its fence floor before it ever dials the new
        address."""
        generation, (host, port) = self._moved[rank]
        blob = json.dumps({"host": host, "port": port,
                           "generation": generation, "rank": rank},
                          sort_keys=True).encode()
        return (_BATCH_HEADER.pack(1)
                + _FRAME.pack(KIND_MOVED, 0, ACK_NONE, _crc(blob), 0,
                              len(blob), TASK_NONE, 0.0, 0.0, 0,
                              0.0, 0.0, 0, generation) + blob)

    def _serve_conn(self, conn: socket.socket) -> None:
        consumer_id: Optional[int] = None
        handles_ok = False
        try:
            while not self._closed.is_set():
                try:
                    raw = conn.recv(_REQUEST.size)
                except socket.timeout:
                    continue  # idle tick; leases expire separately
                if not raw:
                    return  # consumer done
                if len(raw) < _REQUEST.size:
                    raw += _recv_exact(conn, _REQUEST.size - len(raw))
                op, flags, a, b, c = _REQUEST.unpack(raw)
                if op == OP_HELLO:
                    consumer_id = a | (b << 32)
                    handles_ok = bool(flags & FLAG_HANDLES_OK)
                    self._lease_beat(consumer_id, None)
                    continue
                if op == OP_HEARTBEAT:
                    self._lease_beat(consumer_id, None)
                    continue
                if op == OP_TENANT:
                    blob = _recv_exact(conn, c) if c else b""
                    self._lease_beat(consumer_id, None)
                    self._bind_wire_tenant(consumer_id, blob)
                    continue
                if op == OP_NACK:
                    self._handle_nack(a, b, c)
                    self._lease_beat(consumer_id, a)
                    continue
                if op == OP_REBALANCE:
                    blob = _recv_exact(conn, c) if c else b""
                    payload = self._rebalance_admin(flags, a, b, blob)
                    conn.sendall(_BATCH_HEADER.pack(len(payload)) + payload)
                    continue
                if op != OP_GET_BATCH:
                    raise ConnectionError(f"unknown request op {op}")
                queue_idx, max_items = a, b
                moved_rank = plan_ir.queue_rank(queue_idx,
                                                self._num_trainers)
                if moved_rank in self._moved:
                    # This rank migrated away under a committed placement
                    # decision: answer with a redirect (new address +
                    # generation), never a foreign-rank stream.
                    conn.sendall(self._moved_frame(queue_idx, moved_rank))
                    continue
                if not self._owns_queue(queue_idx):
                    # Routing bug (a consumer dialing the wrong shard)
                    # must fail loudly, not serve a foreign rank's
                    # stream with divergent seq state.
                    conn.sendall(self._fail_frame(
                        f"queue {queue_idx} is not served by shard "
                        f"{self._shard_index}/{self._num_shards} "
                        f"(plan query queue_shard)".encode()))
                    continue
                ack = None if c == ACK_NONE else c
                self._lease_beat(consumer_id, queue_idx)
                try:
                    frames = self._collect_frames(
                        queue_idx, max(1, max_items), ack,
                        bool(flags & FLAG_RESUME), consumer_id,
                        handles_ok=handles_ok)
                except mq.ShutdownError as e:
                    # Queue shut down under a blocked GET: fail loudly
                    # (the reference's actor kill surfaced as
                    # RayActorError on the consumer).
                    conn.sendall(self._fail_frame(repr(e).encode()))
                    return
                if frames is None:
                    return  # server closing: drain quietly
                self._send_frames(conn, queue_idx, frames)
        except (ConnectionError, OSError) as e:
            if not self._closed.is_set():
                logger.warning("queue server connection dropped: %s", e)
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._conn_lock:
                self._conn_threads.discard(threading.current_thread())

    def _handle_nack(self, queue_idx: int, bad_seq: int,
                     mode: int = NACK_CRC) -> None:
        state = self._state(queue_idx)
        with state.lock:
            state.sent_seq = min(state.sent_seq, bad_seq - 1)
            if mode == NACK_NO_HANDLE:
                # The consumer cannot map this queue's segments (handle
                # capability was mis-detected, or the segment vanished):
                # stream-only from here on; the rewound replay suffix is
                # downgraded frame-by-frame at the next GET.
                state.no_handles = True
        self._nacked.inc()
        if mode == NACK_NO_HANDLE:
            rt_telemetry.record("handle_downgrade",
                                epoch=self._epoch_of(queue_idx),
                                task=queue_idx, seq=bad_seq)
            logger.warning(
                "queue %d: consumer cannot use shm handle for frame %d; "
                "downgrading the queue to streamed delivery", queue_idx,
                bad_seq)
            return
        rt_telemetry.record("frame_nack", epoch=self._epoch_of(queue_idx),
                            task=queue_idx, seq=bad_seq)
        logger.warning("queue %d: consumer NACK'd frame %d (CRC mismatch); "
                       "re-sending from replay", queue_idx, bad_seq)

    # -- live queue migration (rebalance/) ----------------------------------

    def _rank_queues(self, rank: int) -> List[int]:
        """Every queue index of ``rank`` that has server-side state
        (``queue_id = epoch * num_trainers + rank``)."""
        with self._states_lock:
            return sorted(q for q in self._states
                          if plan_ir.queue_rank(q, self._num_trainers)
                          == rank)

    def _crash_site(self, site: str, generation: int, rank: int) -> None:
        """One injected chaos site = the whole server process dying at
        this exact migration phase (same recovery unit as
        ``queue_server_crash``)."""
        try:
            rt_faults.inject(site, epoch=generation, task=rank)
        except rt_faults.InjectedFault:
            if self._exit_on_crash_site:
                os._exit(137)
            self.close()
            raise

    def _rebalance_admin(self, phase: int, rank: int, generation: int,
                         payload: bytes) -> bytes:
        """Dispatch one OP_REBALANCE phase. Every response is a
        ``checkpoint.crc_line`` JSON payload; errors come back as
        ``{"error": ...}`` lines so the driver can abort cleanly instead
        of eating a connection reset."""
        from ray_shuffling_data_loader_tpu import checkpoint as ckpt
        try:
            if phase == REB_PREPARE:
                self._crash_site("rebalance_prepare", generation, rank)
                line = self._export_rank(rank, generation)
                rt_telemetry.record("rebalance_prepare", epoch=generation,
                                    task=rank, shard=self._shard_index)
                return line
            if phase == REB_ADOPT:
                self._crash_site("rebalance_commit", generation, rank)
                # Verify the manifest line's CRC HERE, on the adopting
                # shard: the driver ships the source's crc_line verbatim,
                # so corruption anywhere on the path is caught before a
                # single byte of state is installed.
                manifest = ckpt.parse_crc_line(
                    payload.decode("utf-8"))["manifest"]
                self._import_rank(manifest)
                rt_telemetry.record("rebalance_commit", epoch=generation,
                                    task=rank, shard=self._shard_index)
                return ckpt.crc_line({"adopted": rank,
                                      "generation": generation}).encode()
            if phase == REB_RELEASE:
                target = json.loads(payload.decode("utf-8"))
                self._release_rank(rank, generation,
                                   (str(target["host"]),
                                    int(target["port"])))
                rt_telemetry.record("rebalance_release", epoch=generation,
                                    task=rank, shard=self._shard_index)
                return ckpt.crc_line({"released": rank,
                                      "generation": generation}).encode()
            if phase == REB_UNSEAL:
                self._sealed_ranks.discard(rank)
                rt_telemetry.record("rebalance_unseal", epoch=generation,
                                    task=rank, shard=self._shard_index)
                return ckpt.crc_line({"unsealed": rank}).encode()
            return ckpt.crc_line(
                {"error": f"unknown rebalance phase {phase}"}).encode()
        except rt_faults.InjectedFault:
            raise
        except Exception as e:  # noqa: BLE001 - reported to the driver
            logger.warning("rebalance phase %d for rank %d failed: %s",
                           phase, rank, e)
            return ckpt.crc_line({"error": repr(e)}).encode()

    def _export_rank(self, rank: int, generation: int) -> bytes:
        """PREPARE: seal ``rank`` at its watermark and export everything
        a target shard needs to continue its streams exactly-once — per
        queue the sequence cursor, row accounting, journal birth stamps,
        and the full unacked replay suffix as base64 byte frames (handle
        frames are downgraded first: a foreign shard cannot mmap this
        host's shm segments). The whole manifest rides one
        ``checkpoint.crc_line`` so it is tamper-evident end to end."""
        from ray_shuffling_data_loader_tpu import checkpoint as ckpt
        self._sealed_ranks.add(rank)
        queues: Dict[str, dict] = {}
        for q in self._rank_queues(rank):
            state = self._state(q)
            with state.lock:
                frames = []
                for frame in state.replay:
                    if frame.pending_codec is not None:
                        state.replay_bytes += frame.resolve_codec()
                    if frame.kind == KIND_TABLE_HANDLE:
                        frame = self._downgrade_frame(frame)
                    frames.append({
                        "seq": frame.seq, "kind": frame.kind,
                        "epoch": frame.epoch, "crc": frame.crc,
                        "data_crc": frame.data_crc,
                        "row_offset": frame.row_offset,
                        "nrows": frame.nrows, "task": frame.task,
                        "codec": frame.codec,
                        "payload_bytes": frame.payload_bytes,
                        "wire": base64.b64encode(
                            bytes(memoryview(frame.wire))).decode("ascii"),
                        "birth": list(frame.birth) if frame.birth else None,
                        "queued": (list(frame.queued)
                                   if frame.queued else None),
                    })
                queues[str(q)] = {
                    "next_seq": state.next_seq,
                    "acked_seq": state.acked_seq,
                    "acked_rows": state.acked_rows,
                    "rows_total": state.rows_total,
                    "done": state.done,
                    "births": {str(seq): list(stamp)
                               for seq, stamp in state.births.items()},
                    "frames": frames,
                }
        manifest = {"rank": rank, "generation": generation,
                    "num_trainers": self._num_trainers,
                    "source_shard": self._shard_index,
                    "queues": queues}
        return ckpt.crc_line({"manifest": manifest}).encode()

    def _import_rank(self, manifest: dict) -> None:
        """COMMIT: install an exported rank's queue states (idempotent —
        re-adopting the same generation is a no-op) and merge its
        watermarks into this shard's journal, so even a restart of the
        TARGET after adoption regenerates exactly the undelivered
        remainder through the normal resume machinery."""
        rank = int(manifest["rank"])
        generation = int(manifest["generation"])
        if int(manifest["num_trainers"]) != self._num_trainers:
            raise ValueError(
                f"manifest num_trainers {manifest['num_trainers']} != "
                f"server num_trainers {self._num_trainers}")
        if self._rank_gen.get(rank, 0) >= generation > 0:
            logger.warning("rank %d already adopted at generation >= %d; "
                           "treating re-adopt as a no-op", rank, generation)
            return
        for q_str, entry in manifest["queues"].items():
            q = int(q_str)
            births = {
                int(seq): rt_lat.Stamp(int(pid), float(tm), float(tu))
                for seq, (pid, tm, tu) in entry["births"].items()}
            state = _QueueState(next_seq=int(entry["next_seq"]),
                                done=bool(entry["done"]), births=births)
            state.acked_seq = int(entry["acked_seq"])
            state.sent_seq = state.acked_seq
            state.acked_rows = int(entry["acked_rows"])
            state.rows_total = int(entry["rows_total"])
            for f in entry["frames"]:
                birth = (rt_lat.Stamp(int(f["birth"][0]),
                                      float(f["birth"][1]),
                                      float(f["birth"][2]))
                         if f["birth"] else None)
                queued = (rt_lat.Stamp(int(f["queued"][0]),
                                       float(f["queued"][1]),
                                       float(f["queued"][2]))
                          if f["queued"] else None)
                frame = _Frame(int(f["seq"]), int(f["kind"]),
                               int(f["epoch"]),
                               base64.b64decode(f["wire"]),
                               int(f["crc"]), int(f["row_offset"]),
                               int(f["nrows"]), int(f["task"]),
                               codec=int(f["codec"]),
                               payload_bytes=int(f["payload_bytes"]),
                               data_crc=int(f["data_crc"]),
                               birth=birth, queued=queued)
                state.replay.append(frame)
                state.replay_bytes += frame.size
                frame.tenant = self._charge_tenant(q, frame.size)
            with self._states_lock:
                self._states[q] = state
            if self._journal is not None:
                for seq, stamp in births.items():
                    self._journal.record_birth(q, seq, stamp.pid,
                                               stamp.t_mono, stamp.t_unix)
                for frame in state.replay:
                    if frame.birth is not None:
                        self._journal.record_birth(
                            q, frame.seq, frame.birth.pid,
                            frame.birth.t_mono, frame.birth.t_unix)
                if state.acked_seq >= 0:
                    self._journal.record(q, state.acked_seq,
                                         state.acked_rows,
                                         done=state.done)
        self._rank_gen[rank] = generation
        self._extra_ranks.add(rank)
        self._moved.pop(rank, None)
        self._sealed_ranks.discard(rank)
        logger.warning("shard %d adopted rank %d at placement generation "
                       "%d (%d queue(s))", self._shard_index, rank,
                       generation, len(manifest["queues"]))

    def _release_rank(self, rank: int, generation: int,
                      target: Tuple[str, int]) -> None:
        """Post-COMMIT: drop the source's copy of a migrated rank and
        start answering its GETs with ``KIND_MOVED`` redirects. The
        shared ``MultiQueue`` is deliberately NOT drained — in the
        in-process topology the adopting server pops the same queue
        objects, so undelivered items flow to the target untouched."""
        for q in self._rank_queues(rank):
            state = self._state(q)
            with state.lock:
                while state.replay:
                    frame = state.replay.popleft()
                    state.replay_bytes -= frame.size
                    self._charge_tenant(q, -frame.size, frame.tenant)
                    self._release_frame(frame)
            with self._states_lock:
                self._states.pop(q, None)
        self._sealed_ranks.discard(rank)
        self._extra_ranks.discard(rank)
        self._moved[rank] = (generation,
                             (str(target[0]), int(target[1])))
        logger.warning("shard %d released rank %d to %s:%d at placement "
                       "generation %d", self._shard_index, rank,
                       target[0], target[1], generation)

    # -- consumer leases ----------------------------------------------------

    def _lease_beat(self, consumer_id: Optional[int],
                    queue_idx: Optional[int]) -> None:
        if consumer_id is None:
            return
        with self._lease_lock:
            lease = self._leases.get(consumer_id)
            if lease is None:
                lease = self._leases[consumer_id] = _Lease(consumer_id)
                logger.info("consumer %x: lease granted", consumer_id)
            lease.last_beat = time.monotonic()
            lease.expired = False
            if queue_idx is not None:
                lease.queues.add(queue_idx)
                if lease.tenant is not None:
                    # A wire-bound tenant claims the ranks it GETs, so
                    # attribution works without a server-side table.
                    rank = plan_ir.queue_rank(queue_idx,
                                              self._num_trainers)
                    with self._tenant_lock:
                        self._rank_tenant.setdefault(rank, lease.tenant)
            self._consumers_alive.set(
                sum(1 for le in self._leases.values() if not le.expired))
            if (self._lease_thread is None
                    or not self._lease_thread.is_alive()):
                self._lease_thread = threading.Thread(
                    target=self._lease_sweeper, daemon=True,
                    name="rsdl-qserve-lease")
                self._lease_thread.start()

    def _lease_sweeper(self) -> None:
        interval = max(0.05, self._lease_timeout_s / 4.0)
        while not self._closed.wait(interval):
            now = time.monotonic()
            newly_dead: List[_Lease] = []
            with self._lease_lock:
                for lease in self._leases.values():
                    if (not lease.expired
                            and now - lease.last_beat
                            > self._lease_timeout_s):
                        lease.expired = True
                        newly_dead.append(lease)
                alive = sum(1 for le in self._leases.values()
                            if not le.expired)
                self._consumers_alive.set(alive)
            for lease in newly_dead:
                self._on_lease_expired(lease)

    def _on_lease_expired(self, lease: _Lease) -> None:
        self._lease_expiries.inc()
        rt_telemetry.record("lease_expired", consumer=lease.consumer_id,
                            queues=sorted(lease.queues),
                            policy=self._on_dead_consumer)
        logger.error(
            "consumer %x: lease expired after %.1fs without a heartbeat "
            "(queues %s); policy=%s", lease.consumer_id,
            self._lease_timeout_s, sorted(lease.queues),
            self._on_dead_consumer)
        if self._on_dead_consumer == "fail_fast":
            # The strictest contract: a dead trainer downs the pipeline
            # loudly rather than silently shuffling for nobody.
            self.close()
            return
        ranks = {plan_ir.queue_rank(q, self._num_trainers)
                 for q in lease.queues}
        with self._lease_lock:
            ranks -= self._drained_ranks
            self._drained_ranks |= ranks
        if not ranks:
            return
        redistribute = self._on_dead_consumer == "redistribute"
        threading.Thread(
            target=self._drain_dead_ranks, args=(ranks, redistribute),
            daemon=True, name="rsdl-qserve-lease-drain").start()

    def notify_member_down(self, rank: int) -> None:
        """View-aware lease sweep (membership/): a ``member_down``
        verdict force-expires every lease holding queues that route to
        the dead rank — the failure detector's seconds-scale verdict
        beats the lease timeout, so the dead rank's queues drain (or
        redistribute, per ``RSDL_QUEUE_ON_DEAD_CONSUMER``) without
        waiting out the lease clock."""
        rank = int(rank)
        victims: List[_Lease] = []
        with self._lease_lock:
            for lease in self._leases.values():
                if lease.expired:
                    continue
                if any(plan_ir.queue_rank(q, self._num_trainers) == rank
                       for q in lease.queues):
                    lease.expired = True
                    victims.append(lease)
            self._consumers_alive.set(
                sum(1 for le in self._leases.values() if not le.expired))
        rt_telemetry.record("member_lease_sweep", task=rank,
                            leases=[le.consumer_id for le in victims])
        for lease in victims:
            logger.warning(
                "consumer %x: lease force-expired (membership declared "
                "rank %d down)", lease.consumer_id, rank)
            self._on_lease_expired(lease)

    def attach_membership(self, manager) -> None:
        """Subscribe this server to a ``MembershipManager``: each
        ``down`` transition triggers :meth:`notify_member_down` for the
        dead rank."""

        def _listener(event, view) -> None:
            if event.kind == "down":
                self.notify_member_down(event.rank)

        manager.add_listener(_listener)

    def _survivor_rank(self) -> Optional[int]:
        with self._lease_lock:
            ranks = sorted(
                plan_ir.queue_rank(q, self._num_trainers)
                for lease in self._leases.values() if not lease.expired
                for q in lease.queues)
        for rank in ranks:
            if rank not in self._drained_ranks:
                return rank
        return None

    def _drain_dead_ranks(self, ranks: set, redistribute: bool) -> None:
        """Free (or reroute) a dead consumer's queues so producers are
        unblocked and its tables don't leak until process exit."""
        num_queues = self._queue.num_queues
        dead_queues = [
            q for q in range(num_queues)
            if plan_ir.queue_rank(q, self._num_trainers) in ranks]
        for q in dead_queues:
            state = self._state(q)
            with state.lock:
                for frame in state.replay:
                    self._release_frame(frame)
                    # Credit each frame's PINNED tenant (the one charged
                    # at pop time), not whatever the rank maps to now.
                    self._charge_tenant(q, -frame.size, frame.tenant)
                state.replay.clear()
                state.replay_bytes = 0
        while not self._closed.wait(0.2):
            moved = 0
            for q in dead_queues:
                while True:
                    try:
                        item = self._queue.get_nowait(q)
                    except (mq.Empty, mq.ShutdownError, RuntimeError):
                        break
                    moved += 1
                    if not redistribute or item is None or isinstance(
                            item, ShuffleFailure):
                        continue  # drained and dropped
                    survivor = self._survivor_rank()
                    if survivor is None:
                        continue  # nobody left: degrade to drain
                    target = (self._epoch_of(q) * self._num_trainers
                              + survivor)
                    if _put_quiet(self._queue, target, item):
                        rt_telemetry.record(
                            "frame_redistributed", epoch=self._epoch_of(q),
                            task=target, source_queue=q)
            if moved:
                logger.info("dead-consumer policy %s: moved %d items off "
                            "ranks %s",
                            "redistribute" if redistribute else "drain",
                            moved, sorted(ranks))

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        """Stop accepting, drain in-flight responses, join every handler.

        Handler threads finish the frame they are writing, observe the
        closed flag at the next loop tick (blocking pops tick at 250 ms),
        and exit without logging — so no thread can raise into the logger
        after the listener is gone (the PR-5 shutdown-race fix).
        """
        if self._closed.is_set():
            return
        self._closed.set()
        try:
            self._listener.close()
        except OSError:
            pass
        with self._conn_lock:
            threads = list(self._conn_threads)
        for thread in threads:
            if thread is threading.current_thread():
                continue  # a handler downing its own server cannot join itself
            thread.join(timeout=5.0)
            if thread.is_alive():
                logger.warning(
                    "queue server handler %s did not drain within 5s",
                    thread.name)
        self._accept_thread.join(timeout=2.0)
        # Release the handle-frame segment pins the replay buffers still
        # hold (consumers that mmap'd a segment keep their mapping), and
        # the segment dir if this server created it.
        with self._states_lock:
            states = list(self._states.values())
        for state in states:
            with state.lock:
                for frame in state.replay:
                    self._release_frame(frame)
        if self._own_handle_dir and self._handle_dir:
            shutil.rmtree(self._handle_dir, ignore_errors=True)
        if self._codec_pool is not None:
            self._codec_pool.shutdown(wait=True)

    def __enter__(self) -> "QueueServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def serve_queue(queue: mq.MultiQueue,
                address: Tuple[str, int] = ("127.0.0.1", 0),
                num_trainers: int = 1,
                journal=None,
                initial_state: Optional[Dict[int, object]] = None,
                exit_on_crash_site: bool = False,
                shard_index: int = 0, num_shards: int = 1,
                handle_dir: Optional[str] = None,
                tenants: Optional[dict] = None,
                placement: Optional[dict] = None) -> QueueServer:
    """Start serving ``queue`` on ``address`` (port 0 = ephemeral)."""
    return QueueServer(queue, address, num_trainers=num_trainers,
                       journal=journal, initial_state=initial_state,
                       exit_on_crash_site=exit_on_crash_site,
                       shard_index=shard_index, num_shards=num_shards,
                       handle_dir=handle_dir, tenants=tenants,
                       placement=placement)


def _rebalance_call(address: Tuple[str, int], phase: int, rank: int,
                    generation: int, payload: bytes = b"",
                    timeout_s: float = 30.0) -> str:
    """One OP_REBALANCE round trip on a short-lived admin connection.
    Returns the raw ``checkpoint.crc_line`` response (CRC verified;
    ``{"error": ...}`` entries raise)."""
    from ray_shuffling_data_loader_tpu import checkpoint as ckpt
    with socket.create_connection(tuple(address),
                                  timeout=timeout_s) as sock:
        sock.sendall(_REQUEST.pack(OP_REBALANCE, phase, rank, generation,
                                   len(payload)) + payload)
        (length,) = _BATCH_HEADER.unpack(
            _recv_exact(sock, _BATCH_HEADER.size))
        line = _recv_exact(sock, length).decode("utf-8")
    entry = ckpt.parse_crc_line(line)
    if "error" in entry:
        raise RuntimeError(
            f"rebalance phase {phase} for rank {rank} failed on "
            f"{address[0]}:{address[1]}: {entry['error']}")
    return line


def rebalance_prepare(address: Tuple[str, int], rank: int,
                      generation: int, timeout_s: float = 30.0) -> str:
    """PREPARE on the source shard: seal ``rank`` at its watermark and
    return its CRC'd handoff manifest line — ship this string VERBATIM
    to :func:`rebalance_adopt` so the target re-verifies the same CRC
    the source computed."""
    return _rebalance_call(address, REB_PREPARE, rank, generation,
                           timeout_s=timeout_s)


def rebalance_adopt(address: Tuple[str, int], manifest_line: str,
                    timeout_s: float = 30.0) -> str:
    """COMMIT on the target shard: install the manifest's queue states
    and merge its watermarks into the target's journal."""
    from ray_shuffling_data_loader_tpu import checkpoint as ckpt
    manifest = ckpt.parse_crc_line(manifest_line)["manifest"]
    return _rebalance_call(address, REB_ADOPT, int(manifest["rank"]),
                           int(manifest["generation"]),
                           payload=manifest_line.encode("utf-8"),
                           timeout_s=timeout_s)


def rebalance_release(address: Tuple[str, int], rank: int,
                      generation: int, target: Tuple[str, int],
                      timeout_s: float = 30.0) -> str:
    """Post-COMMIT on the source shard: drop the migrated rank's state
    and start redirecting its consumers to ``target``."""
    payload = json.dumps({"host": str(target[0]),
                          "port": int(target[1])}).encode("utf-8")
    return _rebalance_call(address, REB_RELEASE, rank, generation,
                           payload=payload, timeout_s=timeout_s)


def rebalance_unseal(address: Tuple[str, int], rank: int,
                     timeout_s: float = 30.0) -> str:
    """ABORT cleanup on the source shard: lift a PREPARE seal so the
    still-authoritative source resumes serving new frames."""
    return _rebalance_call(address, REB_UNSEAL, rank, 0,
                           timeout_s=timeout_s)


class ShardedQueueServer:
    """N in-process :class:`QueueServer` shards over one ``MultiQueue``.

    The in-process face of the sharded serving plane: each shard owns
    the queues of its ranks (``plan.ir.queue_shard``), listens on its
    own port, keeps its own replay/lease/journal state, and publishes
    per-shard metrics. ``shard_map`` is the :class:`plan.ir.ShardMap`
    consumers route by (hand it to :class:`ShardedRemoteQueue`). The
    process-per-shard topology lives in
    ``runtime.supervisor.launch_supervised_queue_shards``.
    """

    def __init__(self, queue: mq.MultiQueue, num_shards: int,
                 num_trainers: int = 1, host: str = "127.0.0.1",
                 journals: Optional[List] = None,
                 initial_states: Optional[List] = None,
                 handle_dir: Optional[str] = None,
                 tenants: Optional[dict] = None):
        num_shards = max(1, num_shards)
        self.servers: List[QueueServer] = []
        try:
            for shard in range(num_shards):
                self.servers.append(QueueServer(
                    queue, (host, 0), num_trainers=num_trainers,
                    journal=journals[shard] if journals else None,
                    initial_state=(initial_states[shard]
                                   if initial_states else None),
                    shard_index=shard, num_shards=num_shards,
                    handle_dir=(os.path.join(handle_dir, f"s{shard}")
                                if handle_dir else None),
                    tenants=tenants))
        except BaseException:
            self.close()
            raise
        self.shard_map = plan_ir.ShardMap(
            num_trainers=max(1, num_trainers),
            addresses=[s.address for s in self.servers])
        rt_metrics.gauge(
            "rsdl_queue_serve_shards",
            "shard count of the live queue serving plane").set(num_shards)

    @property
    def num_shards(self) -> int:
        return len(self.servers)

    def close(self) -> None:
        for server in self.servers:
            server.close()

    def __enter__(self) -> "ShardedQueueServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def serve_queue_sharded(queue: mq.MultiQueue,
                        num_shards: Optional[int] = None,
                        num_trainers: int = 1,
                        host: str = "127.0.0.1",
                        **kwargs) -> ShardedQueueServer:
    """Shard-serve ``queue`` (``num_shards`` defaults to the
    ``RSDL_QUEUE_SHARDS`` policy; 1 reproduces the single-server
    topology exactly)."""
    if num_shards is None:
        num_shards = rt_policy.resolve("queue", "queue_shards")
    return ShardedQueueServer(queue, num_shards,
                              num_trainers=num_trainers, host=host,
                              **kwargs)


class RemoteQueue:
    """Consumer-side handle to a served queue.

    ``get`` returns a materialized ``pa.Table``, ``None`` (epoch end), or
    a :class:`ShuffleFailure` — the exact item vocabulary
    ``ShufflingDataset.__iter__`` consumes, so
    ``ShufflingDataset(batch_queue=RemoteQueue(addr), shuffle_result=None)``
    is a drop-in remote trainer. Connects with the reference's
    retry-with-doubling-backoff schedule (reference: multiqueue.py:310-332).

    ``max_batch`` tables ride each round trip, and with ``prefetch=True``
    (default) a background thread keeps the next batched request in
    flight while the consumer drains the local buffer — the wire is
    overlapped with consumption instead of serialized against it.

    v2 recovery surface:

    - every frame's CRC is verified; a corrupt frame is NACK'd and
      re-fetched from the server's replay buffer — the stream never
      carries damaged bytes forward.
    - a connection failure at ANY point (including mid-response) is
      recovered by reconnect + resume: the first GET per queue after a
      (re)connect carries ``FLAG_RESUME`` and the delivered watermark,
      the server replays the unacked suffix, and frames at-or-below the
      watermark are dropped client-side — exactly-once delivery.
    - ``ack_mode="delivered"`` (default) acks each frame as ``get``
      returns it. ``ack_mode="manual"`` holds acks until
      :meth:`commit` — the checkpoint integration: ``resume_iterator``
      commits at every checkpoint save, so a killed-and-resumed trainer
      finds everything since its last checkpoint still replayable.
    - a heartbeat thread keeps the server-side consumer lease alive
      between GETs (long train steps must not read as a dead trainer).
    """

    #: Consumer-side delivery-latency hops are observed HERE (the wire
    #: client sees the stamps first); datasets layered on top read this
    #: marker and skip their own birth_to_delivered observation.
    observes_delivery = True

    def __init__(self, address: Tuple[str, int],
                 retries: int = mq.CONNECT_RETRIES,
                 initial_backoff_s: float = mq.CONNECT_INITIAL_BACKOFF_S,
                 max_batch: int = DEFAULT_MAX_BATCH,
                 prefetch: bool = True,
                 ack_mode: str = "delivered",
                 consumer_id: Optional[int] = None,
                 delivery: Optional[str] = None,
                 num_trainers: int = 1,
                 tenant=None):
        if ack_mode not in ("delivered", "manual"):
            raise ValueError(
                f"ack_mode must be 'delivered' or 'manual', got {ack_mode!r}")
        self._address = address
        self._ack_mode = ack_mode
        # Tenancy (tenancy/): a TenantContext / id / dict announces this
        # consumer's identity via OP_TENANT right after every HELLO, so
        # reconnects re-bind it; None sends nothing (the legacy wire).
        self._tenant = (rt_tenancy.resolve(tenant)
                        if tenant is not None else None)
        # Latency-plane labeling: the queue label is the TRAINER RANK
        # (bounded cardinality), derived from the queue index by the
        # plan's route contract. Single-trainer consumers (the default)
        # resolve every queue to rank 0; sharded consumers get the real
        # width from their shard map.
        self._num_trainers = max(1, int(num_trainers))
        self._lat_anchors = rt_lat.ClockAnchors()
        # Shm-handle capability (v3): "auto" offers handles when the
        # server address is loopback (same host by construction);
        # "handle" forces the offer (shared shm mounts); "stream" never
        # offers — the v2 wire exactly. A handle that turns out to be
        # unusable is NACK'd with NACK_NO_HANDLE and the queue degrades
        # to streamed delivery, so a wrong "handle" is slow, not wrong.
        self._delivery = rt_policy.resolve("queue", "queue_delivery",
                                           override=delivery)
        if self._delivery not in ("auto", "stream", "handle"):
            raise ValueError(
                f"delivery must be auto, stream or handle, "
                f"got {self._delivery!r}")
        host = str(address[0])
        self._offer_handles = (
            self._delivery == "handle"
            or (self._delivery == "auto"
                and (host in _LOOPBACK_HOSTS or host.startswith("127."))))
        self._consumer_id = (consumer_id if consumer_id is not None
                             else int.from_bytes(os.urandom(8), "little"))
        self._timeout_s = rt_policy.resolve("queue", "queue_timeout_s")
        self._nodelay = rt_policy.resolve("queue", "queue_nodelay")
        self._lease_timeout_s = rt_policy.resolve("queue",
                                                  "queue_lease_timeout_s")
        # One RetryPolicy for connect AND mid-stream refetch: jittered
        # doubling backoff (many trainer processes dialing one server
        # de-synchronize), attempts pinned by the caller's budget.
        self._retry = rt_retry.RetryPolicy.for_component(
            "queue", retry_max_attempts=retries + 1,
            retry_initial_backoff_s=initial_backoff_s,
            retryable=rt_retry.transient_retryable)
        self._io_lock = threading.Lock()      # serializes wire round trips
        self._state_lock = threading.Lock()   # guards buffers/done/pending
        self._closed = threading.Event()
        #: queue -> deque of (seq, row_offset_or_None, item)
        self._buffers: Dict[int, collections.deque] = \
            collections.defaultdict(collections.deque)
        self._done: set = set()
        self._pending: Dict[int, cf.Future] = {}
        #: last seq handed to the application, per queue (-1 = none).
        self._delivered: Dict[int, int] = collections.defaultdict(lambda: -1)
        #: ack watermark for manual mode (advanced by commit()).
        self._committed: Dict[int, int] = collections.defaultdict(lambda: -1)
        #: queues that completed a fetch on the CURRENT connection; the
        #: first GET per queue per connection carries FLAG_RESUME (a
        #: no-op on a healthy stream, a replay after any reconnect).
        self._fetched_since_connect: set = set()
        self._reconnects = rt_metrics.counter(
            "rsdl_queue_client_reconnects_total",
            "RemoteQueue reconnect-and-resume cycles")
        self._corrupt = rt_metrics.counter(
            "rsdl_queue_frames_corrupt_total",
            "frames rejected client-side on CRC mismatch")
        #: rank -> placement-generation fence floor (rebalance/). Raised
        #: by a KIND_MOVED redirect or adopt_positions(); any data frame
        #: stamped BELOW the floor is a zombie source still serving a
        #: migrated rank — dropped loudly, never delivered. Plain int
        #: reads/writes under the GIL; 0 (the pre-rebalance stamp) means
        #: no fence and reproduces the v3.2 wire behavior exactly.
        self._gen_floor: Dict[int, int] = {}
        self._fenced = rt_metrics.counter(
            "rsdl_rebalance_fenced_frames_total",
            "frames dropped below the placement-generation fence")
        try:
            self._retry.call(self._reconnect, describe=f"connect {address}")
        except OSError as e:
            raise ConnectionError(
                f"could not reach queue server at {address} after "
                f"{retries + 1} attempts: {e}")
        self._max_batch = max(1, max_batch)
        self._prefetch = prefetch
        self._io = cf.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="rsdl-rqueue-prefetch")
        self._heartbeat_thread = threading.Thread(
            target=self._heartbeat_loop, daemon=True,
            name="rsdl-rqueue-heartbeat")
        self._heartbeat_thread.start()

    def _reconnect(self) -> None:
        """(Re-)dial the queue server; the old socket (if any) is closed
        first so a half-dead connection cannot leak. Sends the lease
        HELLO and arms per-queue resume so the next GET on every queue
        replays the unacked suffix."""
        with self._io_lock:
            old = getattr(self, "_sock", None)
            if old is not None:
                try:
                    old.close()
                except OSError:
                    pass
                self._reconnects.inc()
            sock = socket.create_connection(self._address, timeout=30)
            # Socket hygiene via runtime/policy.py: finite recv timeout
            # (0 disables). With v2 resume, a timed-out response is
            # simply reconnected-and-replayed — never lost data.
            sock.settimeout(self._timeout_s or None)
            if self._nodelay:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.sendall(_REQUEST.pack(
                OP_HELLO,
                FLAG_HANDLES_OK if self._offer_handles else 0,
                self._consumer_id & 0xFFFFFFFF,
                (self._consumer_id >> 32) & 0xFFFFFFFF, 0))
            if self._tenant is not None:
                blob = self._tenant.to_json()
                sock.sendall(_REQUEST.pack(
                    OP_TENANT, 0,
                    self._consumer_id & 0xFFFFFFFF,
                    (self._consumer_id >> 32) & 0xFFFFFFFF,
                    len(blob)) + blob)
            self._sock = sock
            self._fetched_since_connect = set()

    def _heartbeat_loop(self) -> None:
        """Keep the server-side lease alive while the trainer chews on a
        long step between GETs. Skips a beat rather than queueing behind
        an in-flight round trip (which beats the lease by itself)."""
        interval = max(0.2, self._lease_timeout_s / 3.0)
        while not self._closed.wait(interval):
            if not self._io_lock.acquire(timeout=interval / 2):
                continue  # a round trip is in flight: that IS a beat
            try:
                self._sock.sendall(_REQUEST.pack(OP_HEARTBEAT, 0, 0, 0, 0))
            except OSError:
                pass  # next fetch reconnects; lease survives the gap
            finally:
                self._io_lock.release()

    def _ack_for(self, queue_index: int) -> int:
        watermark = (self._committed[queue_index]
                     if self._ack_mode == "manual"
                     else self._delivered[queue_index])
        return ACK_NONE if watermark < 0 else watermark

    def commit(self, queue_index: Optional[int] = None) -> None:
        """Advance the manual-ack watermark to everything delivered so
        far (one queue, or all). Call after durably recording consumption
        — e.g. a checkpoint save; ``resume_iterator`` does this through
        ``ShufflingDataset.commit_consumed``."""
        with self._state_lock:
            indices = ([queue_index] if queue_index is not None
                       else list(self._delivered))
            for q in indices:
                self._committed[q] = max(self._committed[q],
                                         self._delivered[q])

    def export_positions(self, rank: int) -> Dict[int, Tuple[int, int]]:
        """Snapshot ``{queue: (delivered, committed)}`` for every queue
        of ``rank`` this client has touched, dropping its local buffers
        (a post-migration replay from the adopting shard supersedes
        them). The router hands this to the new shard's client via
        :meth:`adopt_positions` so the handoff stays exactly-once."""
        positions: Dict[int, Tuple[int, int]] = {}
        with self._state_lock:
            for q in set(self._delivered) | set(self._committed):
                if plan_ir.queue_rank(q, self._num_trainers) != rank:
                    continue
                positions[q] = (self._delivered[q], self._committed[q])
                self._buffers.pop(q, None)
                self._pending.pop(q, None)
        return positions

    def adopt_positions(self, positions: Dict[int, Tuple[int, int]],
                        generation: int = 0,
                        rank: Optional[int] = None) -> None:
        """Merge another client's delivered/committed watermarks (max
        wins — positions only ever advance) and raise ``rank``'s fence
        floor to ``generation``, so this client's first GET resumes at
        the exact frame the old shard's stream stopped at."""
        with self._state_lock:
            for q, (delivered, committed) in positions.items():
                self._delivered[q] = max(self._delivered[q], delivered)
                self._committed[q] = max(self._committed[q], committed)
            if rank is not None and generation > self._gen_floor.get(rank, 0):
                self._gen_floor[rank] = generation

    def _fetch_batch(self, queue_index: int) -> Tuple[List, bool]:
        """One wire round trip: request up to ``max_batch`` items and
        decode + CRC-verify the response frames. Runs on the caller's
        thread or the prefetcher; ``_io_lock`` keeps round trips whole.

        Failure handling rides the shared RetryPolicy: ANY round-trip
        death — before or after response bytes — reconnects and resumes.
        The v2 sequence numbers make the resume exact: the server replays
        from the ack watermark and frames the client already delivered
        are dropped by seq, so a reset can neither lose nor duplicate an
        item (the v1 protocol had to fail loudly mid-response here).
        """

        def _round_trip() -> Tuple[List[Tuple], bool]:
            response_started = False
            epoch_hint = None
            try:
                with self._io_lock:
                    rt_faults.inject("queue_fetch", task=queue_index)
                    resume = queue_index not in self._fetched_since_connect
                    ack = self._ack_for(queue_index)
                    try:
                        rt_faults.inject("ack_lost", task=queue_index)
                    except rt_faults.InjectedFault:
                        # A lost ack is harmless by design: acks are
                        # cumulative, the next GET's watermark covers it.
                        rt_telemetry.record("ack_lost", task=queue_index,
                                            suppressed_ack=ack)
                        ack = ACK_NONE
                    self._sock.sendall(_REQUEST.pack(
                        OP_GET_BATCH, FLAG_RESUME if resume else 0,
                        queue_index, self._max_batch, ack))
                    (count,) = _BATCH_HEADER.unpack(
                        _recv_exact(self._sock, _BATCH_HEADER.size))
                    response_started = True
                    frames = []
                    corrupt_seq = None
                    handle_fail_seq = None
                    rank = plan_ir.queue_rank(queue_index,
                                              self._num_trainers)
                    for _ in range(count):
                        (kind_byte, epoch, seq, crc, row_offset, length,
                         src_task, b_mono, b_unix, b_pid, q_mono, q_unix,
                         q_pid, gen) = _FRAME.unpack(
                             _recv_exact(self._sock, _FRAME.size))
                        kind = kind_byte & _KIND_MASK
                        codec = kind_byte >> 4
                        epoch_hint = epoch
                        birth = _unpack_stamp(b_mono, b_unix, b_pid)
                        queued = _unpack_stamp(q_mono, q_unix, q_pid)
                        payload = (_recv_payload(self._sock, length)
                                   if length else b"")
                        if corrupt_seq is not None \
                                or handle_fail_seq is not None:
                            continue  # drain framing past the bad frame
                        if kind == KIND_MOVED:
                            # Live-migration redirect (rebalance/): raise
                            # this rank's fence floor FIRST (so a zombie
                            # source can never out-race the redirect),
                            # then surface the new address to the router.
                            blob = bytes(payload)
                            if _crc(blob) != crc:
                                raise ConnectionError(
                                    "MOVED redirect failed CRC; refetching")
                            info = json.loads(blob.decode())
                            moved_gen = int(info["generation"])
                            if moved_gen > self._gen_floor.get(rank, 0):
                                self._gen_floor[rank] = moved_gen
                            raise QueueMoved(queue_index,
                                             int(info["rank"]),
                                             (info["host"], info["port"]),
                                             moved_gen)
                        if kind != KIND_FAILURE:
                            # Placement-generation fence: a data frame
                            # stamped below this rank's floor comes from
                            # a zombie source still serving a migrated
                            # rank — drop it loudly. Failure frames are
                            # exempt (stamped 0): errors always land.
                            floor = self._gen_floor.get(rank, 0)
                            if gen < floor:
                                self._fenced.inc()
                                rt_telemetry.record(
                                    "rebalance_fence", epoch=epoch,
                                    task=queue_index, seq=seq,
                                    generation=gen, floor=floor)
                                logger.warning(
                                    "queue %d: fenced frame %d from "
                                    "zombie source (generation %d < "
                                    "floor %d)", queue_index, seq, gen,
                                    floor)
                                continue
                            if gen > floor:
                                self._gen_floor[rank] = gen
                        try:
                            # CRC is pre-compression: decompress first,
                            # verify the logical bytes (a torn
                            # compressed stream raises and is NACK'd
                            # like any corruption).
                            raw = (_decompress(codec, payload)
                                   if codec != CODEC_NONE else payload)
                        except Exception:  # noqa: BLE001 - NACK'd below
                            raw = None
                        if raw is None or _crc(raw) != crc:
                            # End-to-end integrity: reject the frame and
                            # everything after it (in-order delivery),
                            # but keep READING so the stream framing
                            # stays aligned; NACK below so the server
                            # rewinds and re-sends the good copy from
                            # its replay buffer.
                            corrupt_seq = seq
                            self._corrupt.inc()
                            rt_telemetry.record("frame_corrupt",
                                                epoch=epoch,
                                                task=queue_index, seq=seq)
                            logger.warning(
                                "queue %d: frame %d failed CRC; NACKing",
                                queue_index, seq)
                            continue
                        if kind == KIND_TABLE_HANDLE:
                            # Shm-handle delivery: mmap the segment the
                            # server serialized and verify its CRC off
                            # the mapped pages — zero-copy, nothing but
                            # the blob crossed the socket. Any failure
                            # downgrades this queue to streamed bytes
                            # (NACK_NO_HANDLE below).
                            try:
                                handle = json.loads(bytes(raw).decode())
                                buf = pp.read_segment_buffer(
                                    handle["path"])
                                if _crc(buf) != handle["crc"]:
                                    raise ValueError(
                                        "segment CRC mismatch")
                            except (OSError, ValueError, KeyError,
                                    TypeError) as e:
                                handle_fail_seq = seq
                                rt_telemetry.record(
                                    "handle_downgrade", epoch=epoch,
                                    task=queue_index, seq=seq)
                                logger.warning(
                                    "queue %d: shm handle for frame %d "
                                    "unusable (%s); requesting streamed "
                                    "delivery", queue_index, seq, e)
                                continue
                            kind, raw = KIND_TABLE, buf
                        if kind == KIND_TABLE and src_task != TASK_NONE:
                            # Cross-process causal link: this frame's
                            # payload was built by reduce task
                            # ``src_task`` in the SERVER process — the
                            # merged trace (runtime/trace.py) joins the
                            # consumer-side fetch to that exact span by
                            # (epoch, task).
                            rt_telemetry.record("frame_recv", epoch=epoch,
                                                task=src_task, seq=seq)
                        frames.append((kind, seq, row_offset, raw,
                                       birth, queued))
                    if corrupt_seq is not None:
                        self._sock.sendall(_REQUEST.pack(
                            OP_NACK, 0, queue_index, corrupt_seq,
                            NACK_CRC))
                    elif handle_fail_seq is not None:
                        self._sock.sendall(_REQUEST.pack(
                            OP_NACK, 0, queue_index, handle_fail_seq,
                            NACK_NO_HANDLE))
                    self._fetched_since_connect.add(queue_index)
                return frames, resume
            except (ConnectionError, OSError) as e:
                if response_started:
                    # Mid-response reset: v1's unrecoverable case, now
                    # the recovery path's bread and butter. The plain
                    # event joins an injected conn_reset_midframe fault
                    # by (kind, epoch, task) — by construction.
                    rt_telemetry.record("conn_reset_midframe",
                                        epoch=epoch_hint, task=queue_index,
                                        error=str(e))
                    logger.warning(
                        "queue %d: connection died mid-response (%s); "
                        "reconnecting and replaying the unacked suffix",
                        queue_index, e)
                raise

        def _redial(error: BaseException) -> None:
            if not isinstance(error, (ConnectionError, OSError)):
                return
            try:
                self._reconnect()
            except OSError as e:
                # A restarting server may not be accepting yet; the old
                # socket is already closed, so the NEXT attempt fails
                # fast and this redial runs again after its backoff —
                # the reconnect storm spends the retry budget, it does
                # not escape it.
                logger.info("queue redial to %s not up yet (%s); will "
                            "retry", self._address, e)

        with rt_telemetry.span("queue_fetch", task=queue_index):
            frames, resumed = self._retry.call(
                _round_trip, describe=f"fetch queue {queue_index}",
                on_retry=_redial)
        items: List[Tuple] = []
        for kind, seq, row_offset, payload, birth, queued in frames:
            if kind == KIND_SENTINEL:
                items.append((seq, None, None, None, None))
                break  # epoch over; nothing valid can follow
            if kind == KIND_FAILURE:
                items.append((seq, None, ShuffleFailure(
                    RuntimeError(bytes(payload).decode())), None, None))
                break
            # ``payload`` is a pa.Buffer (mmap'd segment), a memoryview
            # of the recv buffer, or decompressed bytes — all read
            # zero-copy through py_buffer; the table's Arrow buffers
            # alias it, so no re-materialization happens here either.
            source = (payload if isinstance(payload, pa.Buffer)
                      else pa.py_buffer(payload))
            with pa.ipc.open_stream(pa.BufferReader(source)) as reader:
                items.append((seq, row_offset, reader.read_all(),
                              birth, queued))
        return items, resumed

    def _epoch_over(self, entry) -> bool:
        _, _, item = entry
        return item is None or isinstance(item, ShuffleFailure)

    def _ingest(self, queue_index: int, items: List[Tuple],
                resumed: bool) -> None:
        buf = self._buffers[queue_index]
        if resumed:
            # The server replayed from the ack watermark: locally
            # buffered-but-undelivered copies are superseded by the
            # replay (same seqs), so drop them rather than double-buffer.
            buf.clear()
        delivered = self._delivered[queue_index]
        rank = str(plan_ir.queue_rank(queue_index, self._num_trainers))
        fresh = []
        for seq, row_offset, item, birth, queued in items:
            if seq <= delivered or (buf and seq <= buf[-1][0]):
                continue  # replayed frame we already have: exactly-once
            # Delivery-latency hops, observed only for frames actually
            # entering the stream (a dup dropped by seq above was
            # already delivered once — observing it again would count
            # one payload twice). Replayed frames carry their ORIGINAL
            # stamps, so a replay records its true, crash/reset-spanning
            # latency here.
            queued_lat = self._lat_anchors.latency_s(queued)
            rt_lat.observe_hop(rt_lat.HOP_QUEUED_TO_DELIVERED, rank,
                               queued_lat)
            if self._tenant is not None and queued_lat is not None:
                rt_metrics.sketch(
                    "rsdl_tenant_delivery_latency_seconds",
                    "per-tenant delivery latency by hop",
                    hop=rt_lat.HOP_QUEUED_TO_DELIVERED,
                    tenant=self._tenant.tenant_id).observe(queued_lat)
            if birth is not None:
                age = self._lat_anchors.latency_s(birth)
                rt_lat.observe_hop(rt_lat.HOP_BIRTH_TO_DELIVERED, rank,
                                   age)
                if self._tenant is not None and age is not None:
                    rt_metrics.sketch(
                        "rsdl_tenant_delivery_latency_seconds",
                        "per-tenant delivery latency by hop",
                        hop=rt_lat.HOP_BIRTH_TO_DELIVERED,
                        tenant=self._tenant.tenant_id).observe(age)
                rt_lat.set_freshness(rank, age)
            if item is None and row_offset is None:
                fresh.append((seq, None, None))
            else:
                fresh.append((seq, row_offset, item))
        buf.extend(fresh)
        if fresh and self._epoch_over(fresh[-1]):
            self._done.add(queue_index)
        elif self._prefetch and queue_index not in self._pending:
            # Submit the NEXT batched request as soon as this one lands,
            # so the wire round trip overlaps the consumption of the
            # whole freshly-buffered batch (costs one extra batch of
            # client-side buffering); waiting until the buffer drained
            # would overlap only the last item's consumption.
            # _ingest is only ever called with _state_lock held by its
            # caller (get below), so this write IS lock-guarded:
            # rsdl-lint: disable=lock-mutation
            self._pending[queue_index] = self._io.submit(
                self._fetch_batch, queue_index)

    def get_positioned(self, queue_index: int):
        """Blocking get returning ``(item, row_offset)``: the item plus
        the absolute row position of its first row in this queue's stream
        (None for sentinels/failures). ``ShufflingDataset`` uses the
        position to make checkpoint-resume skips exact against a
        replaying stream."""
        with self._state_lock:
            buf = self._buffers[queue_index]
            while not buf:
                if queue_index in self._done:
                    raise RuntimeError(
                        f"remote queue {queue_index} already yielded its "
                        f"epoch-end sentinel")
                # At most ONE in-flight request per queue index: a second
                # concurrent getter on the same index waits on the SAME
                # future instead of issuing its own round trip, which
                # could ingest batches out of request order. The future
                # stays registered while in flight; whichever waiter
                # observes it still registered after completion unlinks
                # it and ingests — exactly once.
                fut = self._pending.get(queue_index)
                if fut is None:
                    fut = self._pending[queue_index] = self._io.submit(
                        self._fetch_batch, queue_index)
                # Do the (possibly long) wire wait without holding the
                # state lock, so a concurrent get on another queue index
                # can still drain its local buffer.
                self._state_lock.release()
                try:
                    # The wire wait runs with _state_lock RELEASED (the
                    # release/reacquire bracket above/below); the static
                    # with-block scope is wider than the dynamic hold:
                    # rsdl-lint: disable=lock-blocking-call
                    items, resumed = fut.result()
                finally:
                    self._state_lock.acquire()
                    mine = self._pending.get(queue_index) is fut
                    if mine:
                        del self._pending[queue_index]
                if mine:
                    self._ingest(queue_index, items, resumed)
            seq, row_offset, item = buf.popleft()
            if seq != ACK_NONE:  # out-of-band failure frames carry no seq
                self._delivered[queue_index] = max(
                    self._delivered[queue_index], seq)
        return item, row_offset

    def get(self, queue_index: int, block: bool = True):
        if not block:
            raise ValueError("RemoteQueue only supports blocking gets")
        item, _ = self.get_positioned(queue_index)
        return item

    def close(self) -> None:
        self._closed.set()
        self._io.shutdown(wait=False, cancel_futures=True)
        try:
            self._sock.close()
        except OSError:
            pass

    def __enter__(self) -> "RemoteQueue":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class ShardedRemoteQueue:
    """Consumer-side handle to the sharded serving plane.

    Routes every queue index to its shard by the plan query the server
    placed it with (:meth:`plan.ir.ShardMap.shard_for_queue`), holding
    one :class:`RemoteQueue` per shard it actually touches (a trainer
    rank touches exactly one, by the rank-based placement). Duck-types
    the ``RemoteQueue`` consumer surface (``get`` / ``get_positioned``
    / ``commit`` / ``close``), so
    ``ShufflingDataset(batch_queue=ShardedRemoteQueue(shard_map))`` is
    the same drop-in remote trainer — each shard connection keeps its
    own lease, resume watermarks and prefetch pipeline, so one dead
    shard never stalls a stream served by its siblings.
    """

    #: See RemoteQueue.observes_delivery (every shard client observes).
    observes_delivery = True

    def __init__(self, shard_map: Union[plan_ir.ShardMap, dict, str],
                 **remote_kwargs):
        if isinstance(shard_map, str):
            shard_map = plan_ir.ShardMap.from_json(shard_map)
        elif isinstance(shard_map, dict):
            shard_map = plan_ir.ShardMap.from_dict(shard_map)
        shard_map.validate()
        self._shard_map = shard_map
        # The shard map knows the trainer width — hand it to each shard
        # client so latency-plane queue labels resolve to real ranks.
        remote_kwargs.setdefault("num_trainers", shard_map.num_trainers)
        self._remote_kwargs = remote_kwargs
        self._clients: Dict[int, RemoteQueue] = {}
        # _client() constructs a RemoteQueue while held, and that
        # __init__ dials through RetryPolicy.call — a bound-method hop
        # the static lock pass cannot follow, so locksan reports the
        # _clients_lock -> _io_lock edge as statically missing. It
        # cannot invert: the _io_lock taken under this lock belongs to
        # a client no other thread can reach until _client publishes
        # it into self._clients and returns.
        # rsdl-lint: disable=inconsistent-lock-order
        self._clients_lock = threading.Lock()

    @property
    def shard_map(self) -> plan_ir.ShardMap:
        return self._shard_map

    def _client(self, shard: int) -> RemoteQueue:
        with self._clients_lock:
            client = self._clients.get(shard)
            if client is None:
                client = self._clients[shard] = RemoteQueue(
                    tuple(self._shard_map.addresses[shard]),
                    **self._remote_kwargs)
            return client

    def client_for_queue(self, queue_index: int) -> RemoteQueue:
        return self._client(self._shard_map.shard_for_queue(queue_index))

    def _apply_move(self, moved: QueueMoved) -> None:
        """Follow a live-migration redirect: rewrite the local shard
        map's override for the moved rank, transfer the old shard
        client's delivered/committed positions to the new shard's client
        (max-merge — exactly-once across the handoff), and raise its
        fence floor so the zombie source's stragglers are dropped."""
        target_shard = None
        for shard, addr in enumerate(self._shard_map.addresses):
            if (str(addr[0]), int(addr[1])) == moved.address:
                target_shard = shard
                break
        if target_shard is None:
            raise RuntimeError(
                f"MOVED redirect names {moved.address[0]}:"
                f"{moved.address[1]}, which is not in this consumer's "
                f"shard map — the placement decision and the map "
                f"disagree") from moved
        with self._clients_lock:
            old_shard = self._shard_map.shard_for_rank(moved.rank)
            self._shard_map.overrides[moved.rank] = target_shard
            self._shard_map.generation = max(self._shard_map.generation,
                                             moved.generation)
            old_client = self._clients.get(old_shard)
        positions = (old_client.export_positions(moved.rank)
                     if old_client is not None else {})
        self._client(target_shard).adopt_positions(
            positions, generation=moved.generation, rank=moved.rank)
        logger.warning(
            "following MOVED redirect: rank %d shard %d -> %d at "
            "placement generation %d (%d queue position(s) carried)",
            moved.rank, old_shard, target_shard, moved.generation,
            len(positions))

    def _route(self, queue_index: int, op: Callable):
        """Run one consumer op against the owning shard, transparently
        following up to a handful of MOVED redirects (a stable placement
        needs exactly one; a bound stops a routing loop from a
        misconfigured plane)."""
        for _ in range(4):
            try:
                return op(self.client_for_queue(queue_index))
            except QueueMoved as moved:
                self._apply_move(moved)
        raise RuntimeError(
            f"queue {queue_index} still redirecting after 4 MOVED "
            f"hops; placement plane is unstable or misconfigured")

    def get_positioned(self, queue_index: int):
        return self._route(
            queue_index,
            lambda client: client.get_positioned(queue_index))

    def get(self, queue_index: int, block: bool = True):
        return self._route(
            queue_index,
            lambda client: client.get(queue_index, block=block))

    def commit(self, queue_index: Optional[int] = None) -> None:
        if queue_index is not None:
            self.client_for_queue(queue_index).commit(queue_index)
            return
        with self._clients_lock:
            clients = list(self._clients.values())
        for client in clients:
            client.commit()

    def close(self) -> None:
        with self._clients_lock:
            clients = list(self._clients.values())
            self._clients.clear()
        for client in clients:
            client.close()

    def __enter__(self) -> "ShardedRemoteQueue":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# Dedicated-server-process mode: build the whole producer pipeline (queue +
# deterministic shuffle + v2 server) from a config dict, resuming from the
# delivered-watermark journal — the unit runtime.supervisor restarts.
# ---------------------------------------------------------------------------


def _resume_plan(state: Dict[int, object], num_epochs: int,
                 num_trainers: int,
                 ranks: Optional[List[int]] = None
                 ) -> Tuple[int, Dict[int, int]]:
    """``(start_epoch, skip_items)`` from a loaded journal: the first
    epoch any rank has not fully consumed, and per-queue counts of items
    (tables + sentinel) already delivered that the re-run must not
    re-enqueue. The math is a plan query
    (``plan.ir.resume_from_watermarks``) — the server no longer carries
    private resume arithmetic; this wrapper keeps the historical name.
    ``ranks`` restricts the scan to a shard's owned ranks."""
    return plan_ir.resume_from_watermarks(state, num_epochs, num_trainers,
                                          ranks=ranks)


def _resuming_batch_consumer(queue: mq.MultiQueue, num_trainers: int,
                             skip_items: Dict[int, int],
                             owned_ranks: Optional[List[int]] = None):
    """``batch_consumer`` that re-runs the lineage but enqueues only the
    undelivered remainder: the first ``skip_items[q]`` items of each
    queue's deterministic stream (tables, then the sentinel) are dropped
    — they are already journaled as delivered. A serving SHARD passes
    its ``owned_ranks`` so foreign ranks' outputs (recomputed by the
    deterministic lineage regardless) are never enqueued or held."""
    remaining = dict(skip_items)
    owned = set(owned_ranks) if owned_ranks is not None else None
    lock = threading.Lock()

    def consumer(rank, epoch, refs):
        if owned is not None and rank not in owned:
            return
        queue_idx = plan_ir.queue_index(epoch, rank, num_trainers)
        with lock:
            to_skip = remaining.get(queue_idx, 0)
            if refs is None:
                if to_skip > 0:
                    remaining[queue_idx] = to_skip - 1
                    return
            else:
                refs = list(refs)
                dropped = min(to_skip, len(refs))
                remaining[queue_idx] = to_skip - dropped
                refs = refs[dropped:]
                if not refs:
                    return
        if refs is None:
            queue.put(queue_idx, None)
        else:
            queue.put_batch(queue_idx, refs)

    return consumer


def serve_pipeline(config: dict):
    """Child-process entry: queue + shuffle + v2/v3 server from
    ``config``.

    Resumes from the journal at ``config["journal_path"]``: per-queue
    sequence numbers and row offsets restore to their journaled
    watermarks, the shuffle re-runs from the first incomplete epoch
    (``(seed, epoch, task)`` determinism makes the re-run bit-identical),
    and already-delivered items are dropped before the queue — so the
    restarted server serves exactly the undelivered remainder.

    Sharding (``config["num_shards"]`` > 1 with ``"shard_index"``): this
    process serves ONLY the ranks ``plan.ir.shard_ranks`` assigns it —
    its journal covers exactly those queues, the resume scan is
    restricted to them, and foreign ranks' regenerated outputs are
    dropped before the queue. ``config["handle_dir"]`` (optional) pins
    the shm-handle segment dir so restarts reuse one location; stale
    segments from a killed incarnation are swept at startup.

    Returns ``(server, shuffle_result, queue)``.
    """
    from ray_shuffling_data_loader_tpu import checkpoint as ckpt
    from ray_shuffling_data_loader_tpu import dataset as ds
    import importlib
    sh = importlib.import_module("ray_shuffling_data_loader_tpu.shuffle")

    # Streaming mode: ``config["epochs"]`` is a FROZEN window schedule
    # (one ``{"epoch", "filenames", "window"}`` record per closed window,
    # ``streaming/window.py``). The schedule is data in the config, so a
    # restarted incarnation re-derives the identical epoch sequence —
    # the window-boundary half of the exactly-once proof; the journal
    # half below is epoch-generic and applies unchanged.
    stream_epochs = config.get("epochs")
    if stream_epochs is not None:
        num_epochs = len(stream_epochs)
    else:
        num_epochs = int(config["num_epochs"])
    num_trainers = int(config["num_trainers"])
    num_shards = int(config.get("num_shards", 1))
    shard_index = int(config.get("shard_index", 0))
    # Placement overrides (rebalance/): a restarted incarnation launched
    # AFTER a committed migration owns the post-move rank set — the
    # journal merge the adoption performed makes the resume exact.
    placement = config.get("placement") or {}
    overrides = {int(r): int(s)
                 for r, s in dict(placement.get("overrides", {})).items()}
    if num_shards > 1:
        owned_ranks = [r for r in range(num_trainers)
                       if overrides.get(r, r % num_shards) == shard_index]
    else:
        owned_ranks = None
    journal_path = config["journal_path"]
    handle_dir = config.get("handle_dir")
    if not handle_dir:
        # A STABLE per-journal segment dir under shm: a kill -9'd
        # incarnation cannot clean its segments, so the restarted child
        # (same journal identity -> same dir) must find and sweep them
        # instead of leaking shm until reboot.
        digest = zlib.crc32(os.path.abspath(journal_path).encode())
        handle_dir = os.path.join(pp.shm_base_dir(),
                                  f"rsdl-qhandles-{digest:08x}")
    if os.path.isdir(handle_dir):
        # Sweep stale segments from the previous incarnation (safe:
        # consumers mmap segments at fetch time, so a live mapping
        # survives the unlink).
        for name in os.listdir(handle_dir):
            try:
                os.unlink(os.path.join(handle_dir, name))
            except OSError:
                pass
    state = ckpt.WatermarkJournal.load(journal_path)
    start_epoch, skip_items = _resume_plan(state, num_epochs, num_trainers,
                                           ranks=owned_ranks)
    if state:
        logger.warning(
            "queue server (shard %d/%d) resuming from journal %s: "
            "start_epoch=%d, skipping %s already-delivered items",
            shard_index, num_shards, journal_path, start_epoch,
            {q: n for q, n in skip_items.items() if n})
    journal = ckpt.WatermarkJournal(journal_path)
    journal.compact()
    queue = mq.MultiQueue(num_epochs * num_trainers)
    consumer = _resuming_batch_consumer(queue, num_trainers, skip_items,
                                        owned_ranks=owned_ranks)
    if stream_epochs is not None:
        specs = [plan_ir.EpochSpec(
                     epoch=int(e["epoch"]),
                     filenames=tuple(str(f) for f in e["filenames"]),
                     window=(dict(e["window"])
                             if e.get("window") is not None else None),
                     tenant_id=e.get("tenant_id"))
                 for e in stream_epochs]
        specs = [s for s in specs if s.epoch >= start_epoch]
        serve_gauge = rt_metrics.gauge(
            "rsdl_stream_serve_watermark",
            "stream time fully handed to the serving plane")

        def _on_epoch_done(epoch: int,
                           by_epoch={s.epoch: s for s in specs}) -> None:
            spec = by_epoch.get(epoch)
            watermark = (spec.window or {}).get("ingest_watermark") \
                if spec is not None else None
            if watermark is not None:
                serve_gauge.set(float(watermark))

        shuffle_result = sh.run_shuffle_epochs_in_background(
            specs, consumer, int(config["num_reducers"]), num_trainers,
            int(config.get("max_concurrent_epochs", 2)),
            seed=int(config.get("seed", 0)),
            num_workers=config.get("num_workers"),
            file_cache=config.get("file_cache", "auto"),
            epochs_hint=len(specs), on_epoch_done=_on_epoch_done,
            on_failure=ds.make_failure_broadcaster(
                queue, num_epochs * num_trainers))
    else:
        shuffle_result = sh.run_shuffle_in_background(
            list(config["filenames"]), consumer, num_epochs,
            int(config["num_reducers"]), num_trainers,
            int(config.get("max_concurrent_epochs", 2)),
            seed=int(config.get("seed", 0)),
            num_workers=config.get("num_workers"),
            collect_stats=False, start_epoch=start_epoch,
            file_cache=config.get("file_cache", "auto"),
            on_failure=ds.make_failure_broadcaster(
                queue, num_epochs * num_trainers))
    server = QueueServer(
        queue, (config.get("host", "127.0.0.1"), int(config["port"])),
        num_trainers=num_trainers, journal=journal, initial_state=state,
        exit_on_crash_site=True, shard_index=shard_index,
        num_shards=num_shards, handle_dir=handle_dir,
        tenants=config.get("tenants"),
        placement=config.get("placement"))
    rt_metrics.gauge(
        "rsdl_queue_serve_shards",
        "shard count of the live queue serving plane").set(num_shards)
    return server, shuffle_result, queue


def _serve_main(argv: List[str]) -> int:
    """``python -m ray_shuffling_data_loader_tpu.multiqueue_service
    <config.json>`` — the supervised queue-server child process."""
    if len(argv) != 2:
        print("usage: python -m ray_shuffling_data_loader_tpu."
              "multiqueue_service <config.json>", file=sys.stderr)
        return 2
    with open(argv[1]) as f:
        config = json.load(f)

    # The supervisor stops a child with SIGTERM; convert it into a
    # normal SystemExit unwind so the finally below (and the atexit
    # trace dump telemetry registers under RSDL_TRACE_DIR, which this
    # child inherits through the environment) actually runs — a killed
    # incarnation's flight recorder is exactly the evidence a merged
    # cross-process trace needs from it.
    import signal as _signal

    def _on_sigterm(_signum, _frame):
        raise SystemExit(0)

    try:
        _signal.signal(_signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):  # pragma: no cover - non-main thread
        pass

    # Ops-plane federation (both inherited through the child env, like
    # RSDL_TRACE_DIR): the server's registry joins the merged exposition
    # via its per-pid shard, and an incident capture's SIGUSR1 gets a
    # live flight-recorder dump instead of waiting for process exit.
    rt_telemetry.install_signal_dump()
    rt_metrics.maybe_start_shard_writer()

    server, shuffle_result, queue = serve_pipeline(config)
    print(f"READY {server.address[1]}", flush=True)
    try:
        shuffle_result.result()
        # Shuffling is done but consumers may still be draining (and
        # re-fetching replays); serve until the supervisor stops us.
        threading.Event().wait()
    finally:
        server.close()
        queue.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(_serve_main(sys.argv))
