"""The explicit cache hierarchy: hot RAM over disk over remote.

A cold path refetches and re-decodes bytes the host already saw.
Before this module the repo had three independent caches (the RAM
``FileTableCache``, an ad-hoc ``DiskTableCache``, the process
backend's shm segment arena) that accounted memory independently and
knew nothing about each other. This module makes the hierarchy
explicit and puts every tier on the ONE ``native.buffer_ledger()``:

``hot``     decoded tables in RAM (LRU within a byte budget; bytes are
            ledger-charged via ``native.account_table`` when decoded)
``disk``    decoded tables as uncompressed Arrow IPC files on local
            scratch (:class:`DiskTier` — the retired ``DiskTableCache``
            plus per-entry CRC32 and LRU eviction), memory-mapped back
            on hit and promoted to hot
``remote``  the :class:`storage.source.StorageSource` itself — a miss
            here is a real fetch, counted as such

Integrity: every disk entry records a ``native.crc32`` checksum at
write time (the spill-file discipline) and is re-verified on every
read; a mismatch — or any IO/decode failure — evicts the entry and
falls through to the next tier, so a flipped bit on scratch disk costs
one remote refetch and is otherwise invisible: sources are
deterministic, refetch-decode is bit-identical.

:class:`TieredStore` speaks the ``FileTableCache`` protocol
(``get``/``put``/``bytes_cached``/``close``), so it drops into
``shuffle()``'s existing ``file_cache=`` seam unchanged, and exposes
``warm()`` + ``make_prefetcher()`` for the plan scheduler's idle-lane
prefetch (:mod:`storage.prefetch`).
"""

from __future__ import annotations

import collections
import hashlib
import os
import tempfile
import threading
from typing import Dict, Optional, Tuple

import pyarrow as pa

from ray_shuffling_data_loader_tpu import native
from ray_shuffling_data_loader_tpu import tenancy as rt_tenancy
from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)

_CRC_CHUNK = 1 << 20


def _file_crc(path: str) -> int:
    """Streaming CRC32 of a file (the spill.py discipline: 1 MiB
    chunks through the pluggable ``native.crc32`` kernel)."""
    crc = 0
    with open(path, "rb", buffering=0) as f:
        while True:
            chunk = f.read(_CRC_CHUNK)
            if not chunk:
                break
            crc = native.crc32(chunk, crc)
    return crc & 0xFFFFFFFF


def _tier_counters(tier: str) -> Tuple[object, object, object, object]:
    """(hits, misses, evictions, corrupt) counters for one tier."""
    return (rt_metrics.counter("rsdl_storage_hits_total", tier=tier),
            rt_metrics.counter("rsdl_storage_misses_total", tier=tier),
            rt_metrics.counter("rsdl_storage_evictions_total", tier=tier),
            rt_metrics.counter("rsdl_storage_corrupt_total", tier=tier))


def _tenant_counters(tenant_id: str) -> Tuple[object, object, object]:
    """(hits, misses, evictions) counters for one tenant — the series
    the per-tenant thrash detector (runtime/health.py) watches."""
    return (rt_metrics.counter("rsdl_tenant_storage_hits_total",
                               tenant=tenant_id),
            rt_metrics.counter("rsdl_tenant_storage_misses_total",
                               tenant=tenant_id),
            rt_metrics.counter("rsdl_tenant_storage_evictions_total",
                               tenant=tenant_id))


class DiskTier:
    """Decoded-table cache on local disk: Arrow IPC files, memory-mapped
    back on hit, every entry CRC'd.

    The cold regime's dominant per-epoch cost is Parquet decompression +
    decode, which the reference re-pays every epoch (reference:
    shuffle.py:208) and the RAM cache can only skip while the decoded
    corpus fits in memory. This tier removes the constraint: the FIRST
    decode of a file writes the decoded table as an UNCOMPRESSED Arrow
    IPC file to local scratch; every later epoch memory-maps it — no
    decompression, no parse, zero-copy columns whose pages fault in
    lazily and remain reclaimable page cache, so RSS stays bounded no
    matter how large the corpus is.

    Integrity: ``put`` records the written file's ``native.crc32``;
    ``get`` re-verifies before trusting the mapping (sequential page-in
    of bytes the decode was about to touch anyway) — a mismatch evicts
    the entry and returns ``None`` so the caller falls through to the
    next tier (remote refetch, bit-identical by source determinism).

    Disk usage is budgeted (``max_bytes``). With ``evict=True`` (the
    tiered default) insertion past the budget evicts least-recently-hit
    entries; with ``evict=False`` (the legacy ``DiskTableCache``
    behavior) further files simply re-decode parquet each epoch. With
    ``charge_ledger=True`` every on-disk byte is registered with
    ``native.buffer_ledger()`` and reported via ``bytes_cached`` so the
    budget machinery (spill.make_budget_state) sees one consistent
    account; the legacy subclass keeps both off.
    """

    #: Metrics tier label.
    tier = "disk"

    def __init__(self, max_bytes: int, cache_dir: Optional[str] = None,
                 evict: bool = True, charge_ledger: bool = True):
        self.max_bytes = max_bytes
        if cache_dir is None:
            cache_dir = tempfile.mkdtemp(prefix="rsdl_decoded_cache_")
            self._owns_dir = True
        else:
            os.makedirs(cache_dir, exist_ok=True)
            self._owns_dir = False
        self.cache_dir = cache_dir
        self._evict = evict
        self._charge_ledger = charge_ledger
        self._bytes = 0
        # key -> (path, bytes, crc, ledger buf_id or None); ordered by
        # recency of use (LRU eviction order).
        self._paths: "collections.OrderedDict[str, Tuple[str, int, int, Optional[int]]]" = \
            collections.OrderedDict()
        self._inflight: set = set()  # keys with a write in progress
        self._lock = threading.Lock()
        self._closed = False
        (self._hits, self._misses, self._evictions,
         self._corrupt) = _tier_counters(self.tier)
        self._bytes_gauge = rt_metrics.gauge(
            "rsdl_storage_tier_bytes", tier=self.tier)

    def _path_for(self, key: str) -> str:
        digest = hashlib.sha1(key.encode()).hexdigest()[:16]
        return os.path.join(self.cache_dir, f"{digest}.arrow")

    def _uncharge(self, buf_id: Optional[int]) -> None:
        if buf_id is not None:
            native.buffer_ledger().decref(buf_id)

    def _forget(self, key: str, path: str, nbytes: int) -> None:
        """Drop a bad/stale entry: uncharge the budget, delete the file."""
        buf_id = None
        with self._lock:
            entry = self._paths.get(key)
            if entry is not None and entry[0] == path:
                buf_id = entry[3]
                del self._paths[key]
                self._bytes -= nbytes
                self._bytes_gauge.set(self._bytes)
        self._uncharge(buf_id)
        try:
            os.remove(path)
        except OSError:
            pass

    def _evict_lru(self, incoming: int) -> None:
        """Drop least-recently-used entries until ``incoming`` fits
        (lock held by caller is NOT assumed; takes its own). The sweep
        is bounded by the entry count: every iteration pops one."""
        dropped = []
        with self._lock:
            while self._bytes + incoming > self.max_bytes and self._paths:
                key, (path, nbytes, _crc, buf_id) = \
                    self._paths.popitem(last=False)
                self._bytes -= nbytes
                dropped.append((path, buf_id))
            self._bytes_gauge.set(self._bytes)
        for path, buf_id in dropped:
            self._evictions.inc()
            self._uncharge(buf_id)
            try:
                os.remove(path)
            except OSError:
                pass

    def get(self, key: str) -> Optional[pa.Table]:
        with self._lock:
            entry = self._paths.get(key)
            if entry is not None:
                self._paths.move_to_end(key)  # LRU touch
        if entry is None:
            self._misses.inc()
            return None
        path, nbytes, crc, _buf_id = entry
        try:
            actual = _file_crc(path)
            if actual != crc:
                self._corrupt.inc()
                logger.warning(
                    "decoded-cache CRC mismatch for %s (%08x != %08x); "
                    "dropping entry, falling through to refetch",
                    key, actual, crc)
                self._forget(key, path, nbytes)
                self._misses.inc()
                return None
            with pa.memory_map(path) as source:
                table = pa.ipc.open_file(source).read_all()
            self._hits.inc()
            return table
        except (OSError, pa.ArrowInvalid) as e:
            logger.warning("decoded-cache read failed for %s (%s); "
                           "re-decoding", key, e)
            self._forget(key, path, nbytes)
            self._misses.inc()
            return None

    def put(self, key: str, table: pa.Table) -> bool:
        """Write-if-budget-allows; returns True if the file was cached."""
        nbytes = table.nbytes
        if self._evict:
            self._evict_lru(nbytes)
        with self._lock:
            if self._closed:
                return False
            if key in self._paths:
                return True
            if key in self._inflight:
                # Another epoch's map task is writing this key right now
                # (concurrent epochs map the same files); it keeps its own
                # decoded table for this epoch, the writer's file serves
                # the next.
                return False
            if self._bytes + nbytes > self.max_bytes:
                return False
            # Reserve under the lock so concurrent map tasks cannot
            # overshoot the budget together; release on failure below.
            self._bytes += nbytes
            self._inflight.add(key)
        path = self._path_for(key)
        # Writer-unique tmp name: _inflight already serializes same-key
        # writers, this guards against a stale .tmp from a crashed run.
        tmp_path = f"{path}.{id(table):x}.tmp"
        try:
            with pa.OSFile(tmp_path, "wb") as sink:
                with pa.ipc.new_file(sink, table.schema) as writer:
                    writer.write_table(table)
            os.replace(tmp_path, path)
            crc = _file_crc(path)
        except OSError as e:
            logger.warning("decoded-cache write failed for %s (%s); "
                           "cold reads continue from parquet", key, e)
            with self._lock:
                self._bytes -= nbytes
                self._inflight.discard(key)
            try:
                os.remove(tmp_path)
            except OSError:
                pass
            return False
        # Charge the REAL on-disk size against the budget, not
        # table.nbytes: IPC framing, schema/footer metadata, and 8/64-byte
        # alignment padding make the file larger than the raw column bytes
        # (ADVICE r5 — the drift compounds over thousands of files and let
        # the cache overshoot its disk budget).
        try:
            disk_bytes = os.stat(path).st_size
        except OSError:
            disk_bytes = nbytes  # keep the reservation if stat fails
        buf_id = (native.buffer_ledger().register(disk_bytes)
                  if self._charge_ledger and disk_bytes > 0 else None)
        with self._lock:
            self._inflight.discard(key)
            self._bytes += disk_bytes - nbytes  # re-charge at actual size
            if self._closed:  # closed while writing: drop the orphan
                self._bytes -= disk_bytes
                try:
                    os.remove(path)
                except OSError:
                    pass
                self._uncharge(buf_id)
                return False
            self._paths[key] = (path, disk_bytes, crc, buf_id)
            self._bytes_gauge.set(self._bytes)
        return True

    def __contains__(self, key: str) -> bool:
        with self._lock:
            return key in self._paths

    @property
    def bytes_cached(self) -> int:
        """Ledger-visible bytes: what make_budget_state must discount.
        Zero unless this tier charges the ledger (the legacy subclass
        pins no accounted memory at all)."""
        if not self._charge_ledger:
            return 0
        with self._lock:
            return self._bytes

    @property
    def disk_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def close(self) -> None:
        """Delete cached files (safe even with live mmaps: POSIX keeps
        unlinked mappings valid) and, if this cache made its own scratch
        dir, the dir itself."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            entries = list(self._paths.values())
            self._paths.clear()
            self._bytes = 0
            self._bytes_gauge.set(0)
        for path, _nbytes, _crc, buf_id in entries:
            self._uncharge(buf_id)
            try:
                os.remove(path)
            except OSError:
                pass
        if self._owns_dir:
            try:
                os.rmdir(self.cache_dir)
            except OSError:
                pass


class DiskTableCache(DiskTier):
    """The pre-storage/ disk cache, now a thin legacy face over
    :class:`DiskTier`: no LRU eviction (once full, further files
    re-decode each epoch), no ledger charge, ``bytes_cached == 0``
    (it pins no accounted RAM — the budget machinery must not discount
    reclaimable page cache). New code should compose :class:`DiskTier`
    inside a :class:`TieredStore` instead."""

    def __init__(self, max_bytes: int, cache_dir: Optional[str] = None):
        super().__init__(max_bytes, cache_dir=cache_dir, evict=False,
                         charge_ledger=False)


class TieredStore:
    """hot (RAM LRU) over disk (:class:`DiskTier`) over remote (the
    installed :class:`StorageSource`), behind the ``FileTableCache``
    protocol so it plugs into ``shuffle(file_cache=...)`` unchanged.

    ``get`` promotes a disk hit into the hot tier; a hot insertion past
    the byte budget demotes by LRU — dropped from RAM but still served
    by its disk copy (``put`` writes through). A ``get`` miss on both
    tiers returns ``None`` and the caller's ordinary read path performs
    the remote fetch, which is also what a CRC-corrupt disk entry
    degrades to: sources are deterministic, so the refetched table is
    bit-identical to the lost one.

    ``warm(path)`` is the prefetch entry point: fetch + decode + (same
    transform the map stage applies) + insert, so a later ``get`` is a
    hit. ``make_prefetcher(plan)`` hands the plan scheduler a
    :class:`storage.prefetch.PrefetchManager` over the plan's map
    files — the duck-typed seam ``_shuffle_epoch_thread`` looks for.
    """

    def __init__(self, hot_bytes: int,
                 disk: Optional[DiskTier] = None,
                 source: Optional[object] = None,
                 tenant_quotas: Optional[Dict[str, int]] = None):
        self.hot_bytes = hot_bytes
        self.disk = disk
        self._source = source
        self._transform = None
        self._hot: "collections.OrderedDict[str, pa.Table]" = \
            collections.OrderedDict()
        self._hot_bytes_used = 0
        self._lock = threading.Lock()
        self._prefetched: set = set()
        # Tenancy partition of the hot tier: tenant_quotas caps each
        # tenant's RESIDENT bytes; the ambient TenantContext's
        # cache_quota_bytes fills in for tenants not in the table.
        # Over-quota insertion evicts the tenant's OWN LRU entries, so
        # one tenant's cold scan demotes its own pages, never a
        # neighbor's working set.
        self._tenant_quotas: Dict[str, int] = dict(tenant_quotas or {})
        self._key_tenant: Dict[str, str] = {}
        self._tenant_hot_bytes: Dict[str, int] = {}
        self._tenant_metrics: Dict[str, Tuple[object, object, object]] = {}
        # key -> Event for warms in flight: a reader that misses both
        # tiers JOINS the warm (waits for the fetch already running on
        # a prefetch thread) instead of racing it with a duplicate
        # remote GET. The event always fires (warm sets it in finally).
        self._warming: Dict[str, threading.Event] = {}
        (self._hot_hits, self._hot_misses, self._hot_evictions,
         _unused) = _tier_counters("hot")
        self._remote_misses = rt_metrics.counter(
            "rsdl_storage_misses_total", tier="remote")
        self._hot_gauge = rt_metrics.gauge(
            "rsdl_storage_tier_bytes", tier="hot")
        self._prefetch_hits = rt_metrics.counter(
            "rsdl_storage_prefetch_hits_total",
            "prefetched entries later hit by a real map task")

    # -- FileTableCache protocol ---------------------------------------

    def get(self, key: str) -> Optional[pa.Table]:
        # Bounded: each pass either returns or waits for ONE in-flight
        # warm of this key; when the warm resolves (success or not) the
        # re-probe either hits a tier or finds no warm and returns None.
        tenant_id = rt_tenancy.current_tenant().tenant_id
        t_hits, t_misses, _ = self._tenant_counters_for(tenant_id)
        while True:
            with self._lock:
                table = self._hot.get(key)
                if table is not None:
                    self._hot.move_to_end(key)
                    was_prefetched = key in self._prefetched
                    self._prefetched.discard(key)
                else:
                    was_prefetched = False
            if table is not None:
                self._hot_hits.inc()
                t_hits.inc()
                if was_prefetched:
                    self._prefetch_hits.inc()
                return table
            self._hot_misses.inc()
            if self.disk is not None:
                table = self.disk.get(key)  # CRC-verified; None if corrupt
                if table is not None:
                    with self._lock:
                        was_prefetched = key in self._prefetched
                        self._prefetched.discard(key)
                    if was_prefetched:
                        self._prefetch_hits.inc()
                    self._promote(key, table)
                    t_hits.inc()
                    return table
            with self._lock:
                event = self._warming.get(key)
            if event is None:
                self._remote_misses.inc()
                t_misses.inc()
                return None
            # A prefetch thread is already fetching this key: join it —
            # the wait is the REMAINDER of a transfer that started on
            # idle time, never a fresh full fetch, and never a
            # duplicate remote GET for bytes already on the wire.
            event.wait()

    def put(self, key: str, table: pa.Table) -> bool:
        """Insert into hot (LRU-evicting to fit) and write through to
        the disk tier; True if either tier holds it afterwards."""
        in_hot = self._promote(key, table)
        on_disk = self.disk.put(key, table) if self.disk is not None \
            else False
        return in_hot or on_disk

    @property
    def bytes_cached(self) -> int:
        """Every ledger-charged byte this store holds resident — the
        quantity spill.make_budget_state discounts from the transient
        ledger: hot tables (charged via native.account_table at decode
        time) plus the disk tier's ledger-charged file bytes."""
        with self._lock:
            hot = self._hot_bytes_used
        return hot + (self.disk.bytes_cached if self.disk is not None
                      else 0)

    def close(self) -> None:
        with self._lock:
            self._hot.clear()
            self._hot_bytes_used = 0
            self._hot_gauge.set(0)
            self._prefetched.clear()
            self._key_tenant.clear()
            for tenant_id in self._tenant_hot_bytes:
                rt_metrics.gauge("rsdl_tenant_cache_bytes",
                                 tenant=tenant_id).set(0)
            self._tenant_hot_bytes.clear()
        if self.disk is not None:
            self.disk.close()

    # -- internals -----------------------------------------------------

    def _tenant_counters_for(self, tenant_id: str
                             ) -> Tuple[object, object, object]:
        counters = self._tenant_metrics.get(tenant_id)
        if counters is None:
            counters = _tenant_counters(tenant_id)
            self._tenant_metrics[tenant_id] = counters
        return counters

    def _tenant_quota(self, tenant_id: str) -> Optional[int]:
        """This tenant's hot-tier byte cap: the explicit quota table
        first, the ambient context's cache_quota_bytes second, None
        (share the global budget unpartitioned) otherwise."""
        quota = self._tenant_quotas.get(tenant_id)
        if quota is None:
            ctx = rt_tenancy.current_tenant()
            if ctx.tenant_id == tenant_id:
                quota = ctx.cache_quota_bytes
        if quota is not None:
            rt_metrics.gauge("rsdl_tenant_cache_quota_bytes",
                             tenant=tenant_id).set(quota)
        return quota

    def _drop_hot_locked(self, key: str, table: pa.Table) -> str:
        """Remove ``key`` from the hot tier (caller holds ``_lock`` —
        the ``_locked`` suffix is the contract); returns the tenant the
        entry was charged to."""
        # rsdl-lint: disable=lock-mutation
        self._hot_bytes_used -= table.nbytes
        tenant_id = self._key_tenant.pop(key, rt_tenancy.DEFAULT_TENANT_ID)
        # rsdl-lint: disable=lock-mutation
        self._tenant_hot_bytes[tenant_id] = \
            self._tenant_hot_bytes.get(tenant_id, 0) - table.nbytes
        return tenant_id

    def _promote(self, key: str, table: pa.Table) -> bool:
        nbytes = table.nbytes
        tenant_id = rt_tenancy.current_tenant().tenant_id
        quota = self._tenant_quota(tenant_id)
        evicted = []  # (key, charged tenant)
        with self._lock:
            if key in self._hot:
                self._hot.move_to_end(key)
                return True
            if quota is not None and nbytes > quota:
                return False  # can never fit this tenant's partition
            # Tenant-preferential eviction: an over-quota tenant demotes
            # its OWN least-recent entries; neighbors' working sets stay
            # resident no matter how cold this tenant's scan runs.
            if quota is not None:
                while (self._tenant_hot_bytes.get(tenant_id, 0) + nbytes
                       > quota):
                    victim = next(
                        (k for k in self._hot
                         if self._key_tenant.get(k) == tenant_id), None)
                    if victim is None:
                        break
                    old = self._hot.pop(victim)
                    evicted.append((victim, self._drop_hot_locked(
                        victim, old)))
            while (self._hot_bytes_used + nbytes > self.hot_bytes
                   and self._hot):
                old_key, old = self._hot.popitem(last=False)
                evicted.append((old_key, self._drop_hot_locked(
                    old_key, old)))
            if self._hot_bytes_used + nbytes > self.hot_bytes:
                self._hot_gauge.set(self._hot_bytes_used)
                ok = False
            else:
                self._hot[key] = table
                self._hot_bytes_used += nbytes
                self._key_tenant[key] = tenant_id
                self._tenant_hot_bytes[tenant_id] = \
                    self._tenant_hot_bytes.get(tenant_id, 0) + nbytes
                self._hot_gauge.set(self._hot_bytes_used)
                ok = True
            touched = {tenant_id} | {t for _, t in evicted}
            tenant_bytes = {t: self._tenant_hot_bytes.get(t, 0)
                            for t in touched}
        for _, victim_tenant in evicted:
            # Demotion, not loss: put() wrote the entry through to disk,
            # so the evicted key keeps serving from the next tier down.
            self._hot_evictions.inc()
            self._tenant_counters_for(victim_tenant)[2].inc()
        for t, used in tenant_bytes.items():
            rt_metrics.gauge("rsdl_tenant_cache_bytes",
                             tenant=t).set(used)
        return ok

    # -- prefetch seam -------------------------------------------------

    def set_transform(self, transform) -> None:
        """The map stage caches TRANSFORMED tables; warm() must apply
        the same transform or a prefetched hit would change the
        delivered stream. shuffle() wires this before the first epoch."""
        self._transform = transform

    def resident(self, key: str) -> bool:
        with self._lock:
            if key in self._hot:
                return True
        return self.disk is not None and key in self.disk

    def resident_bytes(self, key: str) -> int:
        """Size of ``key``'s resident copy (hot table bytes, else the
        disk entry's on-disk bytes, else 0) — prefetch quota
        accounting."""
        with self._lock:
            table = self._hot.get(key)
            if table is not None:
                return table.nbytes
        if self.disk is not None:
            with self.disk._lock:
                entry = self.disk._paths.get(key)
                if entry is not None:
                    return entry[1]
        return 0

    def warm(self, key: str) -> bool:
        """Fetch + decode + transform + insert ``key`` so a later map
        task's ``get`` hits — or, if the get arrives mid-fetch, JOINS
        this warm instead of duplicating the remote GET. Returns True
        when the entry is resident afterwards (already-resident keys
        short-circuit; a concurrent warm of the same key is waited on,
        not raced)."""
        if self.resident(key):
            return True
        with self._lock:
            event = self._warming.get(key)
            if event is None:
                event = threading.Event()
                self._warming[key] = event
                owner = True
            else:
                owner = False
        if not owner:
            event.wait()
            return self.resident(key)
        ok = False
        try:
            source = self._source
            if source is None:
                from ray_shuffling_data_loader_tpu import storage \
                    as rt_storage
                source = rt_storage.get_source()
            table = source.read_table(key)
            if self._transform is not None:
                table = self._transform(table)
            # Single-chunk like the map path, so later epochs' numpy
            # views stay zero-copy. rsdl-lint: disable=copy-in-hot-path
            table = table.combine_chunks()
            native.account_table(table)
            ok = self.put(key, table)
            if ok:
                with self._lock:
                    self._prefetched.add(key)
            return ok
        finally:
            with self._lock:
                self._warming.pop(key, None)
            event.set()

    def make_prefetcher(self, plan):
        """A PrefetchManager over ``plan``'s map files — the epoch-N
        plan names exactly the files epoch N+1 re-reads, so warming
        them on idle lanes turns the next epoch's cold reads warm."""
        from ray_shuffling_data_loader_tpu.storage.prefetch import \
            PrefetchManager
        files = [node.meta["file"] for node in plan.maps()
                 if node.meta.get("file")]
        # Pin the tenant at construction: the plan's tenant_id if it
        # carries one, else whoever is building the prefetcher — pool
        # threads running the tasks later may sit in a different
        # ambient scope.
        tenant = getattr(plan, "tenant_id", None) \
            or rt_tenancy.current_tenant()
        return PrefetchManager(self, files, tenant=tenant)
