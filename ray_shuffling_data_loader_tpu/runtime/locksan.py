"""Runtime lock sanitizer: the dynamic half of the concurrency pass.

The static lock-order analysis (``analysis/locksets.py``) proves what
the source *says*; this module records what a live process actually
*does*. Opt-in (``RSDL_LOCKSAN=1`` before the package allocates its
locks — tests/conftest.py wires it), :func:`install` monkeypatches the
``threading.Lock`` / ``RLock`` / ``Condition`` factories so that every
lock **allocated from package code** is wrapped in a recording proxy.
Locks allocated elsewhere (stdlib internals, third-party code, test
files) pass through untouched — the proxy tax is paid only where the
contract applies.

Each proxy knows its allocation site as ``path:line`` relative to the
repo root — the exact key ``locksets.LockDecl`` uses for the same
construction site, which is what makes the static and dynamic order
graphs directly comparable (:func:`crosscheck`). Recorded per process:

- **acquisition-order edges**: acquiring B while holding A adds
  ``A -> B`` (with a ``same_instance`` flag when one allocation site
  serves several runtime instances — orderings the static pass
  declines to judge);
- **held-while-blocking events**: a ``Condition.wait`` entered while
  holding *other* package locks, or a contended acquire that stalled
  past ``RSDL_LOCKSAN_SLOW_MS`` (default 50) while holding locks.

:func:`dump` writes the order-graph JSON artifact
(``RSDL_LOCKSAN_OUT``, default ``.rsdl-locksan-graph.json``);
``rsdl-lint --concurrency --locksan-graph <file>`` cross-checks it:
dynamic edges the static graph lacks are findings, static cycles
confirmed dynamically are hard failures.

Overhead is one dict update per acquisition under a dedicated real
lock — fine for tests and chaos runs, not meant for production runs.
Stdlib-only, like everything else in ``runtime/``.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

_REAL_LOCK = threading.Lock
_REAL_RLOCK = threading.RLock
_REAL_CONDITION = threading.Condition

#: Package whose allocation sites get wrapped (path prefix under root).
_DEFAULT_INCLUDE = ("ray_shuffling_data_loader_tpu/",)

_installed = False
_root: str = ""
_include: Tuple[str, ...] = _DEFAULT_INCLUDE
_slow_ms: float = 50.0

_guard = _REAL_LOCK()          # protects the shared tables below
_edges: Dict[Tuple[str, str], Dict[str, Any]] = {}
_events: List[Dict[str, Any]] = []
_sites: Dict[str, str] = {}    # site -> kind
_tls = threading.local()

_MODULE_FILE = os.path.abspath(__file__)


def _held_stack() -> List["_SanLock"]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _alloc_site() -> Optional[str]:
    """``path:line`` of the nearest caller frame inside the package."""
    frame = sys._getframe(2)
    while frame is not None and \
            os.path.abspath(frame.f_code.co_filename) == _MODULE_FILE:
        frame = frame.f_back
    if frame is None:
        return None
    filename = os.path.abspath(frame.f_code.co_filename)
    rel = os.path.relpath(filename, _root).replace(os.sep, "/")
    if rel.startswith("..") or not rel.startswith(_include):
        return None
    return f"{rel}:{frame.f_lineno}"


def _record_acquired(proxy: "_SanLock", waited_s: float,
                     reentered: bool) -> None:
    stack = _held_stack()
    if not reentered:
        with _guard:
            for held in stack:
                if held is proxy:
                    continue
                key = (held.site, proxy.site)
                entry = _edges.get(key)
                if entry is None:
                    entry = _edges[key] = {
                        "src": held.site, "dst": proxy.site, "count": 0,
                        "same_instance": False}
                entry["count"] += 1
                if held.site == proxy.site:
                    entry["same_instance"] = True
            if stack and waited_s * 1000.0 >= _slow_ms:
                _events.append({
                    "type": "contended-acquire-while-holding",
                    "site": proxy.site,
                    "held": [h.site for h in stack],
                    "waited_ms": round(waited_s * 1000.0, 3),
                    "thread": threading.current_thread().name,
                })
    stack.append(proxy)


def _record_released(proxy: "_SanLock") -> None:
    stack = _held_stack()
    for i in range(len(stack) - 1, -1, -1):
        if stack[i] is proxy:
            del stack[i]
            return


class _SanLock:
    """Recording proxy over a real lock/rlock primitive."""

    __slots__ = ("_real", "site", "reentrant")

    def __init__(self, real: Any, site: str, reentrant: bool):
        self._real = real
        self.site = site
        self.reentrant = reentrant

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        reentered = self.reentrant and self in _held_stack()
        start = time.monotonic()
        got = self._real.acquire(blocking, timeout)
        if got:
            _record_acquired(self, time.monotonic() - start, reentered)
        return got

    def release(self) -> None:
        self._real.release()
        _record_released(self)

    def locked(self) -> bool:
        return self._real.locked()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()

    # Condition() interrogates its lock for these; delegate so a
    # proxied RLock keeps its reentrancy bookkeeping intact.
    def _release_save(self):
        inner = getattr(self._real, "_release_save", None)
        state = inner() if inner is not None else self._real.release()
        _record_released(self)
        return state

    def _acquire_restore(self, state) -> None:
        start = time.monotonic()
        inner = getattr(self._real, "_acquire_restore", None)
        if inner is not None:
            inner(state)
        else:
            self._real.acquire()
        _record_acquired(self, time.monotonic() - start, reentered=False)

    def _is_owned(self) -> bool:
        inner = getattr(self._real, "_is_owned", None)
        if inner is not None:
            return inner()
        if self._real.acquire(False):
            self._real.release()
            return False
        return True

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<locksan {self._real!r} @ {self.site}>"


class _SanCondition:
    """Recording proxy over a real Condition bound to a _SanLock."""

    __slots__ = ("_real", "_lock", "site")

    def __init__(self, real: Any, lock: _SanLock, site: str):
        self._real = real
        self._lock = lock
        self.site = site

    def acquire(self, *args, **kwargs) -> bool:
        return self._real.acquire(*args, **kwargs)

    def release(self) -> None:
        self._real.release()

    def __enter__(self):
        return self._real.__enter__()

    def __exit__(self, *exc):
        return self._real.__exit__(*exc)

    def _note_blocking_wait(self) -> None:
        others = [h.site for h in _held_stack() if h is not self._lock]
        if not others:
            return
        frame = sys._getframe(2)
        where = "?"
        if frame is not None:
            rel = os.path.relpath(
                os.path.abspath(frame.f_code.co_filename),
                _root).replace(os.sep, "/")
            where = f"{rel}:{frame.f_lineno}"
        with _guard:
            _events.append({
                "type": "held-while-blocking",
                "site": self.site,
                "held": others,
                "where": where,
                "thread": threading.current_thread().name,
            })

    def wait(self, timeout: Optional[float] = None) -> bool:
        self._note_blocking_wait()
        return self._real.wait(timeout)

    def wait_for(self, predicate, timeout: Optional[float] = None):
        self._note_blocking_wait()
        return self._real.wait_for(predicate, timeout)

    def notify(self, n: int = 1) -> None:
        self._real.notify(n)

    def notify_all(self) -> None:
        self._real.notify_all()

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<locksan {self._real!r} @ {self.site}>"


def _lock_factory():
    site = _alloc_site()
    real = _REAL_LOCK()
    if site is None:
        return real
    with _guard:
        _sites.setdefault(site, "Lock")
    return _SanLock(real, site, reentrant=False)


def _rlock_factory():
    site = _alloc_site()
    real = _REAL_RLOCK()
    if site is None:
        return real
    with _guard:
        _sites.setdefault(site, "RLock")
    return _SanLock(real, site, reentrant=True)


def _condition_factory(lock=None):
    site = _alloc_site()
    if site is None:
        return _REAL_CONDITION(lock)
    if lock is None:
        # Same default as the real Condition, but the inner RLock must
        # be OUR proxy so acquisitions through the condition record.
        lock = _SanLock(_REAL_RLOCK(), site, reentrant=True)
    elif not isinstance(lock, _SanLock):
        lock = _SanLock(lock, site, reentrant=True)
    with _guard:
        _sites.setdefault(lock.site, "Condition")
    real = _REAL_CONDITION(lock)
    return _SanCondition(real, lock, lock.site)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def enabled_by_env() -> bool:
    return os.environ.get("RSDL_LOCKSAN", "") == "1"


def installed() -> bool:
    return _installed


def install(root: Optional[str] = None,
            include: Tuple[str, ...] = _DEFAULT_INCLUDE) -> None:
    """Patch the threading factories. Must run BEFORE the package
    modules allocate their module-level locks to see those sites;
    idempotent. ``root`` is the repo root the static analyzer runs
    from (default: the checkout containing this file)."""
    global _installed, _root, _include, _slow_ms
    _root = os.path.abspath(root) if root else os.path.dirname(
        os.path.dirname(os.path.dirname(_MODULE_FILE)))
    _include = tuple(include)
    _slow_ms = float(os.environ.get("RSDL_LOCKSAN_SLOW_MS", "50"))
    if _installed:
        return
    threading.Lock = _lock_factory
    threading.RLock = _rlock_factory
    threading.Condition = _condition_factory
    _installed = True


def uninstall() -> None:
    """Restore the real factories (existing proxies keep recording)."""
    global _installed
    threading.Lock = _REAL_LOCK
    threading.RLock = _REAL_RLOCK
    threading.Condition = _REAL_CONDITION
    _installed = False


def reset() -> None:
    """Drop recorded edges/events/sites (tests)."""
    with _guard:
        _edges.clear()
        _events.clear()
        _sites.clear()


def graph() -> Dict[str, Any]:
    """The dynamic order graph in the same JSON shape as the static
    one (``locksets.LockAnalysis.static_graph``)."""
    with _guard:
        return {
            "kind": "rsdl-lock-order-graph",
            "source": "dynamic",
            "nodes": [{"key": site, "kind": kind}
                      for site, kind in sorted(_sites.items())],
            "edges": [dict(e) for _, e in sorted(_edges.items())],
            "events": [dict(e) for e in _events],
        }


def cycles(order_graph: Optional[Dict[str, Any]] = None
           ) -> List[List[str]]:
    """Distinct-site cycles in the (dynamic) order graph — a non-empty
    result means two threads actually interleaved opposing acquisition
    orders in this process."""
    g = order_graph if order_graph is not None else graph()
    adj: Dict[str, List[str]] = {}
    for e in g.get("edges", []):
        if e["src"] != e["dst"]:
            adj.setdefault(e["src"], []).append(e["dst"])
    # Iterative DFS cycle collection over SCCs (no recursion limits).
    from ray_shuffling_data_loader_tpu.analysis.locksets import (
        _cycle_path, _tarjan)
    out: List[List[str]] = []
    for scc in _tarjan(adj):
        if len(scc) >= 2:
            out.append(_cycle_path(adj, scc))
    return out


def crosscheck(static_graph: Dict[str, Any],
               dynamic_graph: Optional[Dict[str, Any]] = None
               ) -> Dict[str, Any]:
    """Static<->dynamic comparison (see ``locksets.crosscheck``)."""
    from ray_shuffling_data_loader_tpu.analysis import locksets
    g = dynamic_graph if dynamic_graph is not None else graph()
    return locksets.crosscheck(static_graph, g)


def dump(path: Optional[str] = None) -> str:
    """Write the order-graph artifact; returns the path written."""
    path = path or os.environ.get("RSDL_LOCKSAN_OUT",
                                  ".rsdl-locksan-graph.json")
    payload = graph()
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path
