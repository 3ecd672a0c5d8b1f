"""Native (C++) host kernels, loaded via ctypes.

The reference's native substrate is Ray's C++ core (SURVEY.md §2.3). Here the
native layer is a small shared library built from ``src/shuffle_native.cpp``
at first use (g++ -O3) and kept next to the source under a name that carries
a hash of what it was built from. Every kernel has a NumPy twin, selected
only by ``RSDL_TPU_DISABLE_NATIVE=1``: a build or load that fails without
that switch is an error, never a silent drop to the slow path.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "src", "shuffle_native.cpp")
_FLAGS = ("-O3", "-march=native", "-std=c++17", "-pthread")

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_load_lock = threading.Lock()


class NativeBuildError(RuntimeError):
    """The native library could not be built or loaded on this host."""


def _notify_release() -> None:
    """Wake budget waiters blocked on ledger releases (runtime/release.py).

    Called on every last-ref decref and free-list trim — the ledger-side
    half of the release-event channel that replaced the budget wait's
    ``gc.collect()`` polling cadence. Lazy import: the runtime package
    must stay importable without this module and vice versa.
    """
    from ray_shuffling_data_loader_tpu.runtime import release
    release.notify_release()

# Fixed so fill_random_* output is host-independent for a given seed; the
# per-thread stream layout is a function of this value, not of cpu_count.
_DEFAULT_FILL_THREADS = 8


def _host_cpu_key() -> str:
    """What ``-march=native`` resolves against: the architecture plus the
    first processor's model and feature lines of /proc/cpuinfo."""
    lines = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if not line.strip():
                    break  # end of the first processor block
                if line.split(":", 1)[0].strip() in (
                        "model name", "flags", "Features", "CPU part"):
                    lines.append(line.strip())
    except OSError:
        pass
    return "\n".join(lines)


def compiled_library(src: str, flags: Sequence[str],
                     libs: Sequence[str] = ()) -> str:
    """Path of the shared library for ``src``, built here if absent.

    The name carries a hash of the source bytes, the compiler flags, the
    ``libs`` linked after the source, and the host CPU (the build may use
    ``-march=native``), so a library left by other source or copied from
    another machine is never loaded — it is rebuilt under a new name and
    the stale one removed. The compiler writes to a private name that is
    renamed into place, so concurrently spawned workers each load a whole
    file whoever finishes first.
    """
    digest = hashlib.sha256()
    with open(src, "rb") as f:
        digest.update(f.read())
    digest.update("\0".join((*flags, *libs)).encode())
    digest.update(_host_cpu_key().encode())
    src_dir = os.path.dirname(src)
    stem = "lib" + os.path.splitext(os.path.basename(src))[0]
    lib_path = os.path.join(src_dir,
                            f"{stem}-{digest.hexdigest()[:16]}.so")
    if os.path.exists(lib_path):
        return lib_path
    tmp_path = os.path.join(src_dir, f".build-{os.getpid()}-{stem}.so")
    cmd = ["g++", *flags, "-shared", "-fPIC", src, "-o", tmp_path, *libs]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"{' '.join(cmd)} did not run: {e}") from e
    if proc.returncode != 0:
        if os.path.exists(tmp_path):
            os.unlink(tmp_path)
        raise NativeBuildError(
            f"{' '.join(cmd)} exited {proc.returncode}:\n"
            f"{proc.stderr[-2000:]}")
    os.replace(tmp_path, lib_path)
    for stale in glob.glob(os.path.join(src_dir, f"{stem}*.so")):
        if stale != lib_path:
            try:
                os.unlink(stale)
            except OSError:
                pass  # a racing worker removed it first
    return lib_path


def _bind(lib: ctypes.CDLL) -> None:
    i64, u64, u32p, i64p, f64p = (ctypes.c_int64, ctypes.c_uint64,
                                  ctypes.POINTER(ctypes.c_uint32),
                                  ctypes.POINTER(ctypes.c_int64),
                                  ctypes.POINTER(ctypes.c_double))
    lib.rsdl_partition_indices.argtypes = [u32p, i64, i64, i64p, i64p]
    lib.rsdl_partition_indices.restype = ctypes.c_int
    lib.rsdl_plan_partition.argtypes = [i64, i64, u64, i64p, i64p,
                                        ctypes.c_int]
    lib.rsdl_plan_partition.restype = ctypes.c_int
    lib.rsdl_partition_counts.argtypes = [i64, i64, u64, i64, i64p,
                                          ctypes.c_int]
    lib.rsdl_partition_counts.restype = ctypes.c_int
    lib.rsdl_assign_dest.argtypes = [i64, i64, u64, i64, i64p,
                                     ctypes.POINTER(ctypes.c_int32)]
    lib.rsdl_assign_dest.restype = ctypes.c_int
    lib.rsdl_crc32.argtypes = [ctypes.c_void_p, i64, ctypes.c_uint32]
    lib.rsdl_crc32.restype = ctypes.c_uint32
    lib.rsdl_scatter_gather.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        i64, ctypes.c_int32, ctypes.c_int
    ]
    lib.rsdl_scatter_gather.restype = ctypes.c_int
    lib.rsdl_fill_random_int64.argtypes = [i64p, i64, i64, u64, ctypes.c_int]
    lib.rsdl_fill_random_int64.restype = None
    lib.rsdl_fill_random_double.argtypes = [f64p, i64, u64, ctypes.c_int]
    lib.rsdl_fill_random_double.restype = None
    lib.rsdl_buffer_alloc.argtypes = [i64]
    lib.rsdl_buffer_alloc.restype = i64
    lib.rsdl_buffer_register.argtypes = [i64]
    lib.rsdl_buffer_register.restype = i64
    lib.rsdl_buffer_data.argtypes = [i64]
    lib.rsdl_buffer_data.restype = ctypes.c_void_p
    lib.rsdl_buffer_size.argtypes = [i64]
    lib.rsdl_buffer_size.restype = i64
    lib.rsdl_buffer_incref.argtypes = [i64]
    lib.rsdl_buffer_incref.restype = i64
    lib.rsdl_buffer_decref.argtypes = [i64]
    lib.rsdl_buffer_decref.restype = i64
    lib.rsdl_buffer_bytes_in_use.argtypes = []
    lib.rsdl_buffer_bytes_in_use.restype = i64
    lib.rsdl_buffer_count.argtypes = []
    lib.rsdl_buffer_count.restype = i64
    lib.rsdl_frame_send.argtypes = [ctypes.c_int, ctypes.c_void_p, i64,
                                    ctypes.c_void_p, i64]
    lib.rsdl_frame_send.restype = ctypes.c_int
    lib.rsdl_read_exact.argtypes = [ctypes.c_int, ctypes.c_void_p, i64]
    lib.rsdl_read_exact.restype = i64
    lib.rsdl_buffer_trim_freelist.argtypes = []
    lib.rsdl_buffer_trim_freelist.restype = None
    lib.rsdl_buffer_freelist_bytes.argtypes = []
    lib.rsdl_buffer_freelist_bytes.restype = i64


def _load() -> Optional[ctypes.CDLL]:
    """The bound library; ``None`` only under ``RSDL_TPU_DISABLE_NATIVE``.
    A failed build or load raises :class:`NativeBuildError` — on every
    call, so no caller proceeds on the NumPy twin by accident."""
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    with _load_lock:
        if _load_attempted:
            return _lib
        if not os.environ.get("RSDL_TPU_DISABLE_NATIVE"):
            lib_path = compiled_library(_SRC, _FLAGS)
            try:
                lib = ctypes.CDLL(lib_path)
                _bind(lib)
            except (OSError, AttributeError) as e:
                raise NativeBuildError(
                    f"cannot load {lib_path}: {e}") from e
            _lib = lib
        _load_attempted = True
        return _lib


def available() -> bool:
    """True if the native library is loaded; False only when
    ``RSDL_TPU_DISABLE_NATIVE`` turned it off."""
    return _load() is not None


_crc_backend_cached: Optional[str] = None


def crc_backend() -> str:
    """The resolved CRC backend: ``"native"`` or ``"zlib"``.

    Policy knob ``crc_backend`` (env ``RSDL_CRC_BACKEND``): ``auto``
    (default — native when the library is loaded), ``native``, ``zlib``.
    Resolved once per process (the wire path calls :func:`crc32` per
    frame); tests flip backends via :func:`reset_crc_backend`. An explicit
    ``native`` request without a loaded library degrades to zlib — the
    checksums are bit-identical, so integrity is never at stake, only
    speed.
    """
    global _crc_backend_cached
    if _crc_backend_cached is None:
        from ray_shuffling_data_loader_tpu.runtime import policy as rt_policy
        choice = rt_policy.resolve("native", "crc_backend")
        if choice == "zlib":
            _crc_backend_cached = "zlib"
        else:
            _crc_backend_cached = "native" if available() else "zlib"
    return _crc_backend_cached


def reset_crc_backend() -> None:
    """Drop the cached backend choice (test hook for env flips)."""
    global _crc_backend_cached
    _crc_backend_cached = None


def crc32(data, value: int = 0) -> int:
    """``zlib.crc32``-compatible checksum over any contiguous buffer.

    Same polynomial, same init/running-value semantics as ``zlib.crc32``
    (``crc = crc32(chunk, crc)`` chains), so every recorded checksum —
    wire frames, spill files, shm segments, watermark journals — stays
    valid across backend switches. The native kernel (slice-by-8 tables,
    ARMv8 ``crc32`` intrinsics where available) runs without the GIL.
    """
    import zlib
    if crc_backend() != "native":
        return zlib.crc32(data, value)
    try:
        buf = np.frombuffer(data, dtype=np.uint8)
    except ValueError:  # non-contiguous / exotic buffer: zlib handles it
        return zlib.crc32(data, value)
    if buf.nbytes == 0:
        return value & 0xFFFFFFFF
    lib = _load()
    assert lib is not None
    return int(lib.rsdl_crc32(buf.ctypes.data, buf.nbytes,
                              value & 0xFFFFFFFF))


def partition_indices(assignments: np.ndarray,
                      num_reducers: int) -> List[np.ndarray]:
    """O(n) stable counting-sort partition (see ops/partition.py docstring)."""
    if num_reducers < 1:
        raise ValueError(f"num_reducers must be >= 1, got {num_reducers}")
    lib = _load()
    assert lib is not None
    assignments = np.asarray(assignments)
    if assignments.dtype != np.uint32:
        # Guard the lossy cast: values that would wrap modulo 2**32 must
        # raise like the NumPy fallback does, not silently mis-partition.
        if assignments.size and (assignments.min() < 0
                                 or assignments.max() >= 2**32):
            raise ValueError(
                f"assignment value out of range for num_reducers={num_reducers}")
    assignments = np.ascontiguousarray(assignments, dtype=np.uint32)
    n = len(assignments)
    out = np.empty(n, dtype=np.int64)
    offsets = np.empty(num_reducers + 1, dtype=np.int64)
    rc = lib.rsdl_partition_indices(
        assignments.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), n,
        num_reducers, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    if rc != 0:
        raise ValueError(
            f"assignment value out of range for num_reducers={num_reducers}")
    return [out[offsets[r]:offsets[r + 1]] for r in range(num_reducers)]


_GOLDEN = np.uint64(0x9e3779b97f4a7c15)
_MIX_C1 = np.uint64(0xbf58476d1ce4e5b9)
_MIX_C2 = np.uint64(0x94d049bb133111eb)


def hash_assign(num_rows: int, num_reducers: int, key: int,
                row0: int = 0) -> np.ndarray:
    """Vectorized splitmix64 per-row reducer assignment.

    Bit-identical to the per-row hash inside the native
    ``rsdl_plan_partition`` kernel (same constants, same stream layout:
    ``mix64(key + (i+1) * golden) % num_reducers``), so the NumPy plan
    fallback and the fused native plan produce the same partition on any
    host. Counter-based on purpose: every row's draw is independent, which
    is what lets the native kernel recompute assignments in its placement
    pass instead of materializing them — and what lets the streaming map
    pipeline draw any batch's slice of the stream via ``row0`` (global row
    offset of the batch's first row) without touching earlier rows.
    """
    if num_reducers < 1:
        raise ValueError(f"num_reducers must be >= 1, got {num_reducers}")
    i = np.arange(row0 + 1, row0 + num_rows + 1, dtype=np.uint64)
    x = np.uint64(key & 0xFFFFFFFFFFFFFFFF) + i * _GOLDEN
    x ^= x >> np.uint64(30)
    x *= _MIX_C1
    x ^= x >> np.uint64(27)
    x *= _MIX_C2
    x ^= x >> np.uint64(31)
    return (x % np.uint64(num_reducers)).astype(np.uint32)


def partition_counts(num_rows: int, num_reducers: int, key: int,
                     row0: int = 0, nthreads: int = 1) -> np.ndarray:
    """Per-reducer row counts for ``num_rows`` rows of the ``key`` hash
    stream starting at global row ``row0`` — no data, no index array
    (native kernel). Prefix-summing the result gives the exact region
    offsets the streaming map pipeline scatters into."""
    lib = _load()
    assert lib is not None
    counts = np.empty(num_reducers, dtype=np.int64)
    rc = lib.rsdl_partition_counts(
        num_rows, num_reducers, key & 0xFFFFFFFFFFFFFFFF, row0,
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        max(1, nthreads))
    if rc != 0:
        raise ValueError(
            f"invalid partition_counts arguments (num_rows={num_rows}, "
            f"num_reducers={num_reducers})")
    return counts


def assign_dest(num_rows: int, num_reducers: int, key: int, row0: int,
                cursors: np.ndarray) -> np.ndarray:
    """Destination slots for one record batch of the streaming map
    pipeline: ``dest[i] = cursors[assign(row0 + i)]++`` (native kernel,
    cursors advanced in place). int32 output; raises when a slot exceeds
    int32 range (callers fall back to the NumPy int64 path)."""
    lib = _load()
    assert lib is not None
    assert cursors.dtype == np.int64 and cursors.flags.c_contiguous
    dest = np.empty(num_rows, dtype=np.int32)
    rc = lib.rsdl_assign_dest(
        num_rows, num_reducers, key & 0xFFFFFFFFFFFFFFFF, row0,
        cursors.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        dest.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if rc != 0:
        raise ValueError(
            "assign_dest arguments invalid or destination exceeds int32 "
            f"(num_rows={num_rows}, num_reducers={num_reducers})")
    return dest


def plan_partition_flat(num_rows: int, num_reducers: int, key: int,
                        nthreads: int = 1
                        ) -> "tuple[np.ndarray, np.ndarray]":
    """Fused RNG -> stable counting-sort partition plan (native kernel).

    Returns ``(indices, offsets)``: ``indices`` is a permutation of
    ``arange(num_rows)`` grouped by reducer (original row order within a
    group), ``offsets`` has ``num_reducers + 1`` entries delimiting the
    groups. The assignment array is never materialized — the kernel
    recomputes the per-row hash in its placement pass.
    """
    lib = _load()
    assert lib is not None
    indices = np.empty(num_rows, dtype=np.int64)
    offsets = np.empty(num_reducers + 1, dtype=np.int64)
    rc = lib.rsdl_plan_partition(
        num_rows, num_reducers, key & 0xFFFFFFFFFFFFFFFF,
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        max(1, nthreads))
    if rc != 0:
        raise ValueError(
            f"invalid plan_partition arguments (num_rows={num_rows}, "
            f"num_reducers={num_reducers})")
    return indices, offsets


def scatter_gather(src: np.ndarray, idx: Optional[np.ndarray],
                   dest: np.ndarray, out: np.ndarray,
                   nthreads: int = 1) -> None:
    """Fused ``out[dest] = src[idx]`` (``src[i]`` when ``idx`` is None) in
    one memory pass — NumPy's fancy-index form gathers into a temporary
    then scatters it. ``dest`` entries must be unique; ``idx``/``dest``
    must be int32; ``src``/``out`` must share a 1/2/4/8-byte dtype.
    """
    lib = _load()
    assert lib is not None
    n = len(dest)
    if idx is not None:
        assert idx.dtype == np.int32 and idx.flags.c_contiguous
        assert len(idx) == n
    assert dest.dtype == np.int32 and dest.flags.c_contiguous
    assert src.flags.c_contiguous and out.flags.c_contiguous
    assert src.dtype.itemsize == out.dtype.itemsize
    rc = lib.rsdl_scatter_gather(
        src.ctypes.data, 0 if idx is None else idx.ctypes.data,
        dest.ctypes.data, out.ctypes.data, n, src.dtype.itemsize,
        nthreads)
    if rc != 0:
        raise ValueError(
            f"unsupported element size {src.dtype.itemsize} for "
            "native scatter_gather")


def fill_random_int64(n: int, bound: int, seed: int,
                      nthreads: int = 0) -> np.ndarray:
    """Threaded uniform int64 fill in [0, bound).

    Output depends on (seed, nthreads) only — the default nthreads is a
    fixed constant (not cpu_count) so the same seed reproduces the same
    data on any host.
    """
    if bound < 1:
        raise ValueError(f"bound must be >= 1, got {bound}")
    lib = _load()
    assert lib is not None
    if nthreads <= 0:
        nthreads = _DEFAULT_FILL_THREADS
    out = np.empty(n, dtype=np.int64)
    lib.rsdl_fill_random_int64(
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n, bound,
        seed & 0xFFFFFFFFFFFFFFFF, nthreads)
    return out


def fill_random_double(n: int, seed: int, nthreads: int = 0) -> np.ndarray:
    """Threaded uniform double fill in [0, 1). Same (seed, nthreads)
    determinism contract as :func:`fill_random_int64`."""
    lib = _load()
    assert lib is not None
    if nthreads <= 0:
        nthreads = _DEFAULT_FILL_THREADS
    out = np.empty(n, dtype=np.float64)
    lib.rsdl_fill_random_double(
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), n,
        seed & 0xFFFFFFFFFFFFFFFF, nthreads)
    return out


class NativeBufferPool:
    """Thin Python handle over the C++ ref-counted host buffer pool.

    Plasma-equivalent role (SURVEY.md §2.3): host-RAM buffers with explicit
    refcounts so the shuffle's memory footprint is observable and bounded.
    Two kinds of entries share the ledger:

    - ``alloc``: real 64-byte-aligned allocations (transport recv buffers
      use these — see :func:`alloc_tracked_buffer`).
    - ``register``: accounting-only entries for bytes owned by an external
      allocator (Arrow tables — see :func:`account_table`).
    """

    def register(self, size: int) -> int:
        """Ledger-only entry for externally-allocated bytes."""
        if size < 0:
            raise ValueError(f"buffer size must be >= 0, got {size}")
        lib = _load()
        assert lib is not None
        buf_id = lib.rsdl_buffer_register(size)
        if buf_id == 0:
            raise MemoryError(f"native buffer register of {size} bytes failed")
        return buf_id

    def alloc(self, size: int) -> int:
        if size < 0:
            raise ValueError(f"buffer size must be >= 0, got {size}")
        lib = _load()
        assert lib is not None
        buf_id = lib.rsdl_buffer_alloc(size)
        if buf_id == 0:
            raise MemoryError(f"native buffer alloc of {size} bytes failed")
        return buf_id

    def view(self, buf_id: int) -> np.ndarray:
        """uint8 view of the buffer (no copy, no ownership transfer)."""
        lib = _load()
        assert lib is not None
        size = lib.rsdl_buffer_size(buf_id)
        if size < 0:
            raise KeyError(f"unknown buffer id {buf_id}")
        data = lib.rsdl_buffer_data(buf_id)
        if not data:
            # register()-created ledger entries carry no memory; wrapping
            # the NULL pointer would hand out a segfaulting array.
            raise KeyError(f"buffer id {buf_id} is accounting-only")
        return np.ctypeslib.as_array(
            ctypes.cast(data, ctypes.POINTER(ctypes.c_uint8)), shape=(size,))

    def incref(self, buf_id: int) -> int:
        lib = _load()
        assert lib is not None
        count = lib.rsdl_buffer_incref(buf_id)
        if count < 0:
            raise KeyError(f"unknown buffer id {buf_id}")
        return count

    def decref(self, buf_id: int) -> int:
        lib = _load()
        assert lib is not None
        count = lib.rsdl_buffer_decref(buf_id)
        if count < 0:
            raise KeyError(f"unknown buffer id {buf_id}")
        if count == 0:
            _notify_release()
        return count

    def bytes_in_use(self) -> int:
        lib = _load()
        assert lib is not None
        return lib.rsdl_buffer_bytes_in_use()

    def buffer_count(self) -> int:
        lib = _load()
        assert lib is not None
        return lib.rsdl_buffer_count()

    def freelist_bytes(self) -> int:
        """Bytes held in the exact-size reuse cache (not in use)."""
        lib = _load()
        assert lib is not None
        return lib.rsdl_buffer_freelist_bytes()

    def trim_freelist(self) -> None:
        """Release every cached free-list block back to the OS."""
        lib = _load()
        assert lib is not None
        lib.rsdl_buffer_trim_freelist()
        _notify_release()


class PythonBufferLedger:
    """Pure-Python twin with NativeBufferPool's accounting API, used
    under RSDL_TPU_DISABLE_NATIVE so pipeline memory accounting works
    without the library. ``alloc`` entries are backed by numpy arrays."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries = {}  # id -> [data_or_None, size, refcount]
        self._next_id = 1
        self._bytes = 0

    def _new_entry(self, data, size: int) -> int:
        with self._lock:
            buf_id = self._next_id
            self._next_id += 1
            self._entries[buf_id] = [data, size, 1]
            self._bytes += size
            return buf_id

    def register(self, size: int) -> int:
        if size < 0:
            raise ValueError(f"buffer size must be >= 0, got {size}")
        return self._new_entry(None, size)

    def alloc(self, size: int) -> int:
        if size < 0:
            raise ValueError(f"buffer size must be >= 0, got {size}")
        return self._new_entry(np.empty(size, dtype=np.uint8), size)

    def view(self, buf_id: int) -> np.ndarray:
        with self._lock:
            if buf_id not in self._entries:
                raise KeyError(f"unknown buffer id {buf_id}")
            data = self._entries[buf_id][0]
        if data is None:
            raise KeyError(f"buffer id {buf_id} is accounting-only")
        return data

    def incref(self, buf_id: int) -> int:
        with self._lock:
            if buf_id not in self._entries:
                raise KeyError(f"unknown buffer id {buf_id}")
            self._entries[buf_id][2] += 1
            return self._entries[buf_id][2]

    def decref(self, buf_id: int) -> int:
        with self._lock:
            if buf_id not in self._entries:
                raise KeyError(f"unknown buffer id {buf_id}")
            entry = self._entries[buf_id]
            entry[2] -= 1
            if entry[2] == 0:
                del self._entries[buf_id]
                self._bytes -= entry[1]
        if entry[2] == 0:
            _notify_release()
        return entry[2]

    def bytes_in_use(self) -> int:
        with self._lock:
            return self._bytes

    def buffer_count(self) -> int:
        with self._lock:
            return len(self._entries)

    def freelist_bytes(self) -> int:
        """Always 0: numpy's allocator does its own recycling."""
        return 0

    def trim_freelist(self) -> None:
        pass


_py_ledger: Optional[PythonBufferLedger] = None
_py_ledger_lock = threading.Lock()


def buffer_ledger():
    """THE process-wide buffer ledger: the native pool when the C++ library
    is loaded, else the Python fallback. All pipeline memory accounting
    (file cache, in-flight reducer tables, transport recv buffers) goes
    through this one object; ``stats.get_memory_stats().pool_bytes``
    reports its total."""
    global _py_ledger
    if available():
        return NativeBufferPool()
    with _py_ledger_lock:
        if _py_ledger is None:
            _py_ledger = PythonBufferLedger()
        return _py_ledger


def trim_freelist() -> None:
    """Release the pool's recycled (free) buffers back to the OS. Shared
    end-of-trial hygiene for the shuffle drivers; no-op on the Python
    ledger."""
    buffer_ledger().trim_freelist()


def account_table(table) -> None:
    """Charge an Arrow table's bytes to the ledger for the lifetime of its
    Python wrapper (released by GC — the wrapper is the handle every
    pipeline stage passes around, so 'wrapper alive' is 'bytes in flight').
    """
    import weakref
    nbytes = table.nbytes
    if nbytes <= 0:
        return
    ledger = buffer_ledger()
    buf_id = ledger.register(nbytes)
    weakref.finalize(table, ledger.decref, buf_id)


def frame_send(fd: int, header, payload) -> None:
    """Send a framed message (header then payload) as one scatter-gather
    ``writev`` stream, entirely outside the GIL. ``header``/``payload`` are
    any contiguous buffer-protocol objects. Raises OSError on socket errors
    (callers treat it like a failed ``sendall``)."""
    lib = _load()
    assert lib is not None
    h = np.frombuffer(header, dtype=np.uint8)
    p = np.frombuffer(payload, dtype=np.uint8)
    rc = lib.rsdl_frame_send(fd, h.ctypes.data, h.nbytes, p.ctypes.data,
                             p.nbytes)
    if rc != 0:
        raise OSError(-rc, os.strerror(-rc))


# Mirrors RSDL_EEOF_MID_MESSAGE in shuffle_native.cpp: a sentinel far
# outside the errno range, so real socket errnos (including a genuine
# EPIPE from read()) are reported faithfully.
_EEOF_MID_MESSAGE = 1000000


def read_exact_into(fd: int, buf: np.ndarray, n: int) -> bool:
    """Read exactly ``n`` bytes from ``fd`` into ``buf`` with one GIL-free
    call. Returns True on success, False on clean EOF before the first
    byte; raises OSError on socket errors or mid-message EOF."""
    import errno as _errno
    lib = _load()
    assert lib is not None
    assert buf.nbytes >= n and buf.flags.c_contiguous
    got = lib.rsdl_read_exact(fd, buf.ctypes.data, n)
    if got == n:
        return True
    if got == 0:
        return False
    err = -got
    if err == _EEOF_MID_MESSAGE:
        raise OSError(_errno.EPIPE, "peer closed connection mid-message")
    raise OSError(err, os.strerror(err))


def alloc_tracked_buffer(size: int) -> np.ndarray:
    """Pool-allocated uint8 buffer returned as an ndarray; the bytes are
    returned to the pool when the array (and everything referencing it —
    memoryviews, Arrow buffers made with pa.py_buffer) is collected."""
    import weakref
    ledger = buffer_ledger()
    if isinstance(ledger, NativeBufferPool):
        buf_id = ledger.alloc(size)
        arr = ledger.view(buf_id)
    else:
        # Fallback: numpy owns the bytes; the ledger only accounts them
        # (storing the array in the ledger would keep it alive forever).
        arr = np.empty(size, dtype=np.uint8)
        buf_id = ledger.register(size)
    weakref.finalize(arr, ledger.decref, buf_id)
    return arr
