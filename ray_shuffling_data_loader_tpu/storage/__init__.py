"""storage/: remote object storage as a first-class source.

Every dataset read in the pipeline funnels through this package:

- :mod:`storage.source` — WHERE bytes live: :class:`LocalSource`,
  :class:`HTTPRangeSource`, :class:`SimulatedObjectStore` behind the
  one :class:`StorageSource` contract.
- :mod:`storage.cache` — the explicit hot (RAM) → disk (CRC'd Arrow
  IPC) → remote tier hierarchy (:class:`TieredStore`,
  :class:`DiskTier`), every tier on the one buffer ledger.
- :mod:`storage.prefetch` — plan-driven warming on idle scheduler
  lanes (:class:`PrefetchManager`).

This module owns the PROCESS-WIDE source: :func:`get_source` resolves
it lazily from the ``RSDL_STORAGE_BACKEND`` policy knob ("local" |
"sim"), :func:`set_source` installs one programmatically (tests — same
process-local caveat as programmatic chaos: process-backend workers
resolve their own from the inherited env).

:func:`read_table` / :func:`open_parquet` are the routed read calls
``shuffle._read_map_table`` and the fused streaming pipeline use; they
fire the ``storage_read`` / ``storage_stall`` chaos sites OUTSIDE the
in-place retry, so an injected fault surfaces to lineage recovery
instead of being absorbed as IO weather (the ``map_read`` discipline,
runtime/faults.py).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import pyarrow as pa
import pyarrow.parquet as pq

from ray_shuffling_data_loader_tpu.runtime import faults as rt_faults
from ray_shuffling_data_loader_tpu.runtime import policy as rt_policy
from ray_shuffling_data_loader_tpu.runtime import retry as rt_retry
from ray_shuffling_data_loader_tpu.runtime import telemetry as rt_telemetry
from ray_shuffling_data_loader_tpu.storage.cache import (DiskTableCache,
                                                         DiskTier,
                                                         TieredStore)
from ray_shuffling_data_loader_tpu.storage.prefetch import (PrefetchManager,
                                                            PrefetchTask)
from ray_shuffling_data_loader_tpu.storage.source import (HTTPRangeSource,
                                                          LocalSource,
                                                          SimulatedObjectStore,
                                                          StorageSource)

__all__ = [
    "StorageSource", "LocalSource", "HTTPRangeSource",
    "SimulatedObjectStore", "DiskTier", "DiskTableCache", "TieredStore",
    "PrefetchManager", "PrefetchTask", "get_source", "set_source",
    "read_table", "open_parquet",
]

_lock = threading.Lock()
_source: Optional[StorageSource] = None


def _resolve_default() -> StorageSource:
    backend = str(rt_policy.resolve("storage", "storage_backend")).lower()
    if backend == "sim":
        return SimulatedObjectStore()
    if backend != "local":
        raise ValueError(
            f"RSDL_STORAGE_BACKEND must be 'local' or 'sim' (install "
            f"anything else via storage.set_source), got {backend!r}")
    return LocalSource()


def get_source() -> StorageSource:
    """The process-wide source, resolved from policy on first use."""
    global _source
    with _lock:
        if _source is None:
            _source = _resolve_default()
        return _source


def set_source(source: Optional[StorageSource]) -> Optional[StorageSource]:
    """Install ``source`` process-wide (None = re-resolve from policy
    on next use); returns the previous source for save/restore."""
    global _source
    with _lock:
        previous, _source = _source, source
    return previous


def _inject(epoch: Optional[int], task: Optional[int]) -> None:
    # Two sites, one boundary: storage_read is the lost-GET failure
    # shape, storage_stall the slow-first-byte delay shape (delayN
    # sleeps instead of raising). Both free when chaos is inactive.
    rt_faults.inject("storage_read", epoch=epoch, task=task)
    t0 = time.monotonic()
    rt_faults.inject("storage_stall", epoch=epoch, task=task)
    if rt_faults.active():
        # Surface the measured stall (usually 0; the injected delay when
        # a delayN rule fired) as a plain stage event so a storage_stall
        # fault is JOINABLE by its (kind, epoch, task) key in the
        # chaos/telemetry correlation
        # — a raise-shape stall joins through the recovery re-read that
        # lands here with the rule already spent. No entry in
        # trace.STAGE_RANK, so it never enters critical-path
        # attribution, and with chaos inactive no event is recorded.
        rt_telemetry.record("storage_stall", epoch=epoch, task=task,
                            dur_s=time.monotonic() - t0)


def read_table(path: str, epoch: Optional[int] = None,
               task: Optional[int] = None,
               retry: Optional[rt_retry.RetryPolicy] = None,
               source: Optional[StorageSource] = None) -> pa.Table:
    """Fetch + decode one dataset file through the installed source.

    The chaos sites fire BEFORE the (optionally retried) fetch: an
    injected fault is a lost task for the recovery machinery, not IO
    weather for ``retry`` to absorb.
    """
    src = source if source is not None else get_source()
    _inject(epoch, task)
    t0 = time.monotonic()
    if retry is None:
        table = src.read_table(path)
    else:
        table = retry.call(src.read_table, path,
                           describe=f"storage read {path}")
    # Plain stage event for the chaos/telemetry join: a storage_read
    # fault shares this (kind, epoch, task) key — the recovery re-read
    # lands here, so even a raise-shape injection is joinable. Absent
    # from trace.STAGE_RANK => never on the critical path.
    rt_telemetry.record("storage_read", epoch=epoch, task=task,
                        dur_s=time.monotonic() - t0)
    return table


def open_parquet(path: str, epoch: Optional[int] = None,
                 task: Optional[int] = None,
                 source: Optional[StorageSource] = None) -> pq.ParquetFile:
    """A streaming-reader handle through the installed source (the
    fused map pipeline's entry); same chaos-site discipline."""
    src = source if source is not None else get_source()
    _inject(epoch, task)
    t0 = time.monotonic()
    handle = src.open_parquet(path)
    rt_telemetry.record("storage_read", epoch=epoch, task=task,
                        dur_s=time.monotonic() - t0)
    return handle
