"""Capturing a JAX/XLA profiler trace, and the trainer's step marker.

The program's stage spans are ``runtime/telemetry.span`` /
``span_begin`` / ``span_end``: one call lands an interval in the flight
recorder and, under its fixed ``rsdl.*`` name
(``telemetry.SPAN_NAMES``), in the profiler's trace, so a captured trace
shows the host pipeline stages on the same timeline as the XLA device
operations, which carry the step's own scopes (``telemetry.STEP_SCOPES``:
every operation of the jitted train step under one ``rsdl.*``
``jax.named_scope``). This module captures (:func:`profile_trace`); view
with TensorBoard's profile plugin, which groups device time by those
names, or Perfetto.

**The step's own counters.** What the device computes at run time and an
operator wants to see (how many tiles the expert walk took this step)
leaves the jitted train step as an output beside the loss. Model code
calls :func:`step_stat` while the step is being traced;
:func:`with_step_stats` around the loss gathers what was recorded so
that the values leave ``jax.value_and_grad`` (and a ``jax.checkpoint``
on the way: :func:`step_stats_of`) as outputs and not as leaked tracers;
``SpmdTrainer.train_step`` hands each step's stats, still on the device,
to :func:`keep_step_stats`. They are *folded* (brought to the host, added
to the registry, written to the flight recorder:
``telemetry.step_stats_folded``) only once their arrays are ready, so
the step path never waits for the device; :func:`step_stats` returns the
folded entries by the step's number, which is the ``step_num`` the
``rsdl.trainer.step`` annotation carries into a profiler trace. A leaf's
move that is no optimizer's (:func:`leaf_move`) leaves the loss the same
way and stays on the device: the train step adds it to the leaf.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import threading
import time
from typing import (Any, Callable, Deque, Dict, Iterator, List, Optional,
                    Tuple)

from ray_shuffling_data_loader_tpu.runtime import telemetry


def step_span(step: int):
    """Train-step marker: lets the profiler group device ops per step.
    Returns a context manager. Profiler-only (the consumer's loop records
    the ``train_step`` stage)."""
    from jax.profiler import StepTraceAnnotation
    return StepTraceAnnotation(telemetry.STEP_ANNOTATION, step_num=step)


@contextlib.contextmanager
def profile_trace(log_dir: str) -> Iterator[None]:
    """Capture a JAX profiler trace (host spans + device timeline) into
    ``log_dir`` for the duration of the block."""
    import jax
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# -- the step's own counters -------------------------------------------------

#: A recorded stat's key: its name and its labels, sorted (a pytree's
#: dictionary key has to sort).
StatKey = Tuple[str, Tuple[Tuple[str, str], ...]]

#: Steps the ring holds, folded and waiting together.
STEP_STATS_KEPT = 1024

_collecting = threading.local()     # .stack: open collectors, innermost last
_ring_lock = threading.Lock()       # the two deques
_fold_lock = threading.Lock()       # one fold at a time, in the steps' order
_waiting: Deque[Tuple[int, Dict[StatKey, Any]]] = collections.deque()
_folded: Deque[Dict[str, Any]] = collections.deque()


def step_stat(name: str, value: Any, **labels: Any) -> None:
    """Record ``value``, a device scalar or a short vector of the fields
    ``telemetry.STEP_STAT_FIELDS[name]`` lists, as this step's ``name``
    under ``labels`` (``layer=3``). For model code, while the step is
    traced. Outside :func:`with_step_stats` (a forward pass alone, an
    evaluation) nobody collects and the value is dropped."""
    if name not in telemetry.STEP_STAT_FIELDS:
        raise ValueError(f"unknown step stat {name!r}; known: "
                         f"{sorted(telemetry.STEP_STAT_FIELDS)}")
    key = (name, tuple(sorted((k, str(v)) for k, v in labels.items())))
    _record_stats({key: value})


#: The name a leaf's move rides out of the loss under, beside the stats.
LEAF_MOVE = "leaf_move"


def leaf_move(path: Tuple[str, ...], delta: Any) -> None:
    """Record that this step moves the parameter leaf at ``path`` (its
    keys from the tree's root) by ``delta``, beside what the optimizer
    does to it: for a leaf that takes no gradient and follows a rule of
    its own (a router's selection bias under its balancing update). For
    model code, while the step is traced; the value leaves the loss as a
    stat does, and ``parallel/trainer.make_train_step`` adds it
    (:func:`leaf_moves`). Outside :func:`with_step_stats` it is
    dropped."""
    _record_stats({(LEAF_MOVE, (("path", "/".join(path)),)): delta})


def leaf_moves(stats: Dict[StatKey, Any]
               ) -> Tuple[Dict[Tuple[str, ...], Any], Dict[StatKey, Any]]:
    """``stats`` as :func:`with_step_stats` returned them, split into the
    recorded moves, by each leaf's path, and the step's counters."""
    moves = {tuple(dict(key[1])["path"].split("/")): value
             for key, value in stats.items() if key[0] == LEAF_MOVE}
    return moves, {key: value for key, value in stats.items()
                   if key[0] != LEAF_MOVE}


def _record_stats(stats: Dict[StatKey, Any]) -> None:
    stack = getattr(_collecting, "stack", None)
    if not stack:
        return
    for key, value in stats.items():
        if key in stack[-1]:
            raise ValueError(f"step stat {key} recorded twice in one step")
        stack[-1][key] = value


def with_step_stats(fn: Callable) -> Callable:
    """``fn`` returning ``(its result, {key: value})``: the stats ``fn``
    recorded while it was traced, as outputs. The shape
    ``jax.value_and_grad(..., has_aux=True)`` takes a loss in."""

    @functools.wraps(fn)
    def collecting(*args, **kwargs):
        stack = _collecting.__dict__.setdefault("stack", [])
        stack.append({})
        try:
            out = fn(*args, **kwargs)
        finally:
            stats = stack.pop()
        return out, stats

    return collecting


def step_stats_of(transformed: Callable) -> Callable:
    """For a function that a JAX transform traces on its own
    (``jax.checkpoint(with_step_stats(half))``): call it, record the
    stats it returns again at the caller's level, return its result."""

    def outside(*args, **kwargs):
        out, stats = transformed(*args, **kwargs)
        _record_stats(stats)
        return out

    return outside


def _ready(value: Any) -> bool:
    is_ready = getattr(value, "is_ready", None)
    return True if is_ready is None else bool(is_ready())


def keep_step_stats(step: int, stats: Dict[StatKey, Any]) -> None:
    """Step ``step``'s stats as the jitted step returned them, into the
    ring; then fold whatever has arrived. Never waits for the device: the
    copies to the host are asked for here and read by a later call's
    fold, once they have landed."""
    for value in stats.values():
        start_copy = getattr(value, "copy_to_host_async", None)
        if start_copy is not None:
            start_copy()
    with _ring_lock:
        _waiting.append((step, stats))
        _trim_locked()
    fold_step_stats()


def _trim_locked() -> None:
    # The oldest go first: folded entries, then (a device 1,024 steps
    # behind its host) entries nobody ever read.
    while len(_waiting) + len(_folded) > STEP_STATS_KEPT:
        (_folded if _folded else _waiting).popleft()


def fold_step_stats(wait: bool = False) -> int:
    """Fold the oldest waiting entries whose arrays are ready, in the
    steps' order; with ``wait`` every entry, blocking on the device (a
    reader's call after the run). Returns how many it folded. The only
    place a stat reaches the host."""
    import numpy as np
    if not _fold_lock.acquire(blocking=wait):
        return 0            # another thread is folding; it will get there
    count = 0
    try:
        while True:
            with _ring_lock:
                if not _waiting:
                    break
                step, stats = _waiting[0]
                if not wait and not all(map(_ready, stats.values())):
                    break
                _waiting.popleft()
            t0 = time.perf_counter()
            rows: Dict[str, List[Dict[str, Any]]] = {}
            for (name, labels), value in sorted(stats.items()):
                values = np.asarray(value).reshape(-1).tolist()
                rows.setdefault(name, []).append({
                    **dict(labels),
                    **dict(zip(telemetry.STEP_STAT_FIELDS[name], values))})
            telemetry.step_stats_folded(step, rows)
            entry = {"step": step, "stats": rows,
                     "fold_s": time.perf_counter() - t0}
            with _ring_lock:
                _folded.append(entry)
                _trim_locked()
            count += 1
    finally:
        _fold_lock.release()
    return count


def step_stats(first: Optional[int] = None, last: Optional[int] = None
               ) -> List[Dict[str, Any]]:
    """The folded entries of steps ``first`` to ``last`` (both included;
    ``None``: no bound), oldest first: ``{"step": n, "stats": {name:
    [{label: value, ..., field: value, ...}, ...]}, "fold_s": host seconds
    the fold took}``. Entries still waiting for the device are not
    there: :func:`fold_step_stats` with ``wait`` brings them."""
    with _ring_lock:
        return [e for e in _folded
                if (first is None or e["step"] >= first)
                and (last is None or e["step"] <= last)]


def reset_step_stats() -> None:
    """Empty the ring (a new trainer's steps count from 0 again)."""
    with _ring_lock:
        _waiting.clear()
        _folded.clear()
