"""The ``StorageSource`` contract: where dataset bytes actually live.

The reference reads its training corpus from object storage via
``smart_open`` (reference: shuffle.py:7,208) — ingest latency there is
remote-GET latency, not disk. This repo historically only read local
files; this module makes the byte origin an explicit, swappable
backend so the same pipeline runs against local disk, a plain HTTP
server, or a hermetic *simulated* object store with the latency and
failure shape of the real thing:

``LocalSource``
    The historical behavior: :func:`utils.fileio.read_parquet` (local
    mmap fast path, pyarrow/fsspec filesystems for URIs).
``HTTPRangeSource``
    Stdlib ``http.client`` range reads against any static file server
    — no SDK dependency. Transient failures retry through the PR 3
    :class:`runtime.retry.RetryPolicy` (component ``storage``).
``SimulatedObjectStore``
    Local files served through a policy-tunable remote-latency model:
    first-byte latency, sustained bandwidth, multiplicative jitter and
    a transient error rate, every draw a pure function of
    ``(seed, path, attempt)`` — a fixed seed reproduces the identical
    stall/error sequence on any host, which is what lets the tests
    exercise cold remote ingest hermetically.

Every fetch funnels through the module-level :func:`read_table` /
:func:`open_parquet` in ``storage/__init__.py``, which is also where
the ``storage_read`` / ``storage_stall`` chaos sites fire — the
injection sits OUTSIDE the in-place IO retry on purpose, so an
injected fault surfaces to the lineage-recovery machinery under test
instead of being absorbed as weather (the ``map_read`` precedent).
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Dict, Optional

import pyarrow as pa
import pyarrow.parquet as pq

from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.runtime import policy as rt_policy
from ray_shuffling_data_loader_tpu.runtime import retry as rt_retry
from ray_shuffling_data_loader_tpu.utils import fileio
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)


class StorageSource:
    """Where dataset bytes come from. Implementations are thread-safe
    (map tasks fetch concurrently) and deterministic: ``read_table``
    of the same path returns bit-identical tables on every call —
    the property that makes cache fall-through (a corrupt disk-tier
    entry refetched from remote) invisible to the delivered stream.
    """

    #: Tier label used in logs/metrics ("local", "http", "sim").
    name: str = "source"

    def read_table(self, path: str) -> pa.Table:
        """Fetch and decode one Parquet object."""
        raise NotImplementedError

    def open_parquet(self, path: str) -> pq.ParquetFile:
        """A :class:`pq.ParquetFile` over the object, for streaming
        record-batch readers (the fused map pipeline)."""
        raise NotImplementedError

    def read_bytes(self, path: str, offset: int = 0,
                   length: Optional[int] = None) -> bytes:
        """Raw byte range of the object (length None = to EOF)."""
        raise NotImplementedError

    def size(self, path: str) -> int:
        """Object size in bytes, 0 if it does not exist."""
        raise NotImplementedError


class LocalSource(StorageSource):
    """Direct filesystem (and pyarrow/fsspec URI) reads — the
    historical read path, byte-for-byte."""

    name = "local"

    def read_table(self, path: str) -> pa.Table:
        return fileio.read_parquet(path)

    def open_parquet(self, path: str) -> pq.ParquetFile:
        fs, inner = fileio.parse_uri(path)
        if fs is None:
            return pq.ParquetFile(inner)
        return pq.ParquetFile(fs.open_input_file(inner))

    def read_bytes(self, path: str, offset: int = 0,
                   length: Optional[int] = None) -> bytes:
        fs, inner = fileio.parse_uri(path)
        if fs is None:
            with open(inner, "rb") as f:
                f.seek(offset)
                return f.read() if length is None else f.read(length)
        with fs.open_input_file(inner) as f:
            f.seek(offset)
            return f.read() if length is None else f.read(length)

    def size(self, path: str) -> int:
        return fileio.file_size(path)


class HTTPRangeSource(StorageSource):
    """Range reads from any HTTP(S) file server via stdlib
    ``http.client`` — the minimal object-store protocol (GET +
    ``Range:``), no SDK. One pooled connection per thread; transient
    socket/5xx failures retry through the ``storage`` RetryPolicy.

    ``base_url`` is the prefix objects are resolved against, so the
    pipeline's filenames stay relative (``shard_0.parquet``) and the
    same run script points at local disk or a server by swapping the
    source.
    """

    name = "http"

    def __init__(self, base_url: str,
                 retry: Optional[rt_retry.RetryPolicy] = None):
        import urllib.parse
        parsed = urllib.parse.urlsplit(base_url)
        if parsed.scheme not in ("http", "https"):
            raise ValueError(f"HTTPRangeSource wants http(s), "
                             f"got {base_url!r}")
        self._scheme = parsed.scheme
        self._netloc = parsed.netloc
        self._prefix = parsed.path.rstrip("/")
        self._retry = retry or rt_retry.RetryPolicy.for_component(
            "storage", retryable=rt_retry.transient_retryable)
        self._local = threading.local()
        self._remote_bytes = rt_metrics.counter(
            "rsdl_storage_remote_bytes_read_total",
            "bytes fetched from the remote storage tier")

    def _conn(self):
        import http.client
        conn = getattr(self._local, "conn", None)
        if conn is None:
            cls = (http.client.HTTPSConnection
                   if self._scheme == "https"
                   else http.client.HTTPConnection)
            conn = cls(self._netloc, timeout=60)
            self._local.conn = conn
        return conn

    def _drop_conn(self) -> None:
        conn = getattr(self._local, "conn", None)
        if conn is not None:
            try:
                conn.close()
            finally:
                self._local.conn = None

    def _url_path(self, path: str) -> str:
        return f"{self._prefix}/{path.lstrip('/')}"

    def _request(self, method: str, path: str,
                 headers: Optional[Dict[str, str]] = None):
        conn = self._conn()
        try:
            conn.request(method, self._url_path(path),
                         headers=headers or {})
            resp = conn.getresponse()
        except (OSError, ConnectionError) as e:
            self._drop_conn()  # stale keep-alive: next attempt redials
            raise OSError(f"http {method} {path}: {e}") from e
        if resp.status >= 500:
            resp.read()
            raise OSError(f"http {method} {path}: server error "
                          f"{resp.status}")
        if resp.status >= 400:
            resp.read()
            raise FileNotFoundError(
                f"http {method} {path}: {resp.status}")
        return resp

    def _fetch(self, path: str, offset: int,
               length: Optional[int]) -> bytes:
        headers = {}
        if offset or length is not None:
            end = "" if length is None else str(offset + length - 1)
            headers["Range"] = f"bytes={offset}-{end}"
        resp = self._request("GET", path, headers)
        data = resp.read()
        self._remote_bytes.inc(len(data))
        return data

    def read_bytes(self, path: str, offset: int = 0,
                   length: Optional[int] = None) -> bytes:
        return self._retry.call(self._fetch, path, offset, length,
                                describe=f"http range {path}")

    def read_table(self, path: str) -> pa.Table:
        data = self.read_bytes(path)
        return pq.read_table(pa.BufferReader(data))

    def open_parquet(self, path: str) -> pq.ParquetFile:
        # Whole-object fetch: the streaming reader then iterates local
        # buffers. Per-column-chunk range reads would save bytes on
        # projected reads, but the map stage always reads every column.
        data = self.read_bytes(path)
        return pq.ParquetFile(pa.BufferReader(data))

    def size(self, path: str) -> int:
        def head() -> int:
            resp = self._request("HEAD", path)
            resp.read()
            return int(resp.headers.get("Content-Length", 0))
        try:
            return self._retry.call(head, describe=f"http head {path}")
        except FileNotFoundError:
            return 0


class SimulatedObjectStore(StorageSource):
    """Local files behind a deterministic remote-latency model.

    Every fetch pays a first-byte latency plus ``size / bandwidth``
    transfer time, both scaled by a seeded multiplicative jitter, and
    may raise a transient ``OSError`` at the configured error rate
    (absorbed by the storage RetryPolicy exactly like a real remote
    blip). All draws are pure functions of ``(seed, path, attempt)``
    via sha256 — no RNG state, so a fixed seed reproduces the byte-
    identical timing/error sequence on any host, which is what makes
    chaos runs comparable with each other.

    Knobs resolve through :mod:`runtime.policy`
    (``RSDL_STORAGE_SIM_FIRST_BYTE_MS`` / ``_MB_PER_S`` /
    ``_JITTER_PCT`` / ``_ERROR_RATE`` / ``_SEED``); constructor
    kwargs override.
    """

    name = "sim"

    def __init__(self, inner: Optional[StorageSource] = None,
                 first_byte_ms: Optional[float] = None,
                 mb_per_s: Optional[float] = None,
                 jitter_pct: Optional[float] = None,
                 error_rate: Optional[float] = None,
                 seed: Optional[int] = None,
                 sleep=time.sleep):
        def res(key, override):
            return rt_policy.resolve("storage", key, override=override)
        self._inner = inner or LocalSource()
        self.first_byte_ms = res("storage_sim_first_byte_ms",
                                 first_byte_ms)
        self.mb_per_s = res("storage_sim_mb_per_s", mb_per_s)
        self.jitter_pct = res("storage_sim_jitter_pct", jitter_pct)
        self.error_rate = res("storage_sim_error_rate", error_rate)
        self.seed = res("storage_sim_seed", seed)
        self._sleep = sleep
        self._lock = threading.Lock()
        self._attempts: Dict[str, int] = {}
        self.bytes_read = 0
        self._remote_bytes = rt_metrics.counter(
            "rsdl_storage_remote_bytes_read_total",
            "bytes fetched from the remote storage tier")

    def _draw(self, path: str, attempt: int, salt: str) -> float:
        """Uniform [0, 1) from a stable hash — the faults.py idiom."""
        digest = hashlib.sha256(
            f"{self.seed}:{salt}:{path}:{attempt}".encode()).digest()
        return int.from_bytes(digest[:8], "big") / float(1 << 64)

    def _next_attempt(self, path: str) -> int:
        with self._lock:
            attempt = self._attempts.get(path, 0)
            self._attempts[path] = attempt + 1
            return attempt

    def _simulate(self, path: str, nbytes: int) -> None:
        attempt = self._next_attempt(path)
        if (self.error_rate > 0
                and self._draw(path, attempt, "err") < self.error_rate):
            raise OSError(
                f"simulated object-store error for {path!r} "
                f"(attempt {attempt}, rate {self.error_rate:g})")
        jitter = 1.0 + (self.jitter_pct / 100.0) * (
            2.0 * self._draw(path, attempt, "lat") - 1.0)
        delay = self.first_byte_ms / 1000.0
        if self.mb_per_s > 0:
            delay += nbytes / (self.mb_per_s * 1e6)
        delay *= max(0.0, jitter)
        if delay > 0:
            self._sleep(delay)
        with self._lock:
            self.bytes_read += nbytes
        self._remote_bytes.inc(nbytes)

    def read_table(self, path: str) -> pa.Table:
        self._simulate(path, self._inner.size(path))
        return self._inner.read_table(path)

    def open_parquet(self, path: str) -> pq.ParquetFile:
        # The whole object crosses the simulated wire (HTTP source
        # parity), then the streaming reader iterates local buffers.
        data = self.read_bytes(path)
        return pq.ParquetFile(pa.BufferReader(data))

    def read_bytes(self, path: str, offset: int = 0,
                   length: Optional[int] = None) -> bytes:
        data = self._inner.read_bytes(path, offset, length)
        self._simulate(path, len(data))
        return data

    def size(self, path: str) -> int:
        return self._inner.size(path)

    def reset(self) -> None:
        """Forget attempt counters — replays the exact draw sequence
        (an A/B leg re-running the same files at the same seed)."""
        with self._lock:
            self._attempts.clear()
            self.bytes_read = 0
