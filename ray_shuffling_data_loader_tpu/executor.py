"""Futures-based task executor with ``wait(num_returns)`` semantics.

The reference schedules its map/reduce fan-out on Ray's raylet (C++ external
dependency) and throttles with ``ray.wait`` (reference: shuffle.py:126-131,
148-151). On a TPU-VM there is no cluster scheduler between the loader and
the host: map/reduce tasks are CPU work on the local host (pyarrow releases
the GIL for Parquet decode and take), so the idiomatic equivalent is a
thread-pool executor per host plus an explicit ``wait`` that reproduces
``ray.wait``'s contract — return when ``num_returns`` of the given futures
have completed, preserving submission order in the done list.

Multi-host scaling composes above this: each host of a TPU slice runs its own
executor over its shard of the files (SPMD, see parallel/), so no cross-host
task scheduler is needed. Plasma's role — a shared, accounted buffer plane —
is played by Arrow C++ buffers, with pipeline-wide byte accounting in
``native.NativeBufferPool`` (see native/ and stats.py's pool_bytes column).
"""

from __future__ import annotations

import concurrent.futures as cf
import os
import threading
from typing import Any, Callable, List, Optional, Sequence, Tuple

from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)


class TaskRef:
    """A handle to an in-flight task's result.

    Plays the role of a Ray ObjectRef for loader code: created by
    :meth:`Executor.submit`, resolved by :func:`get`, waited on by
    :func:`wait`. Holds a strong reference to the result until dropped,
    which is what gives the shuffle's throttle loop its memory-release
    semantics (dropping refs frees buffers, reference: shuffle.py:131-132).
    """

    __slots__ = ("_future",)

    def __init__(self, future: cf.Future):
        self._future = future

    def result(self, timeout: Optional[float] = None) -> Any:
        return self._future.result(timeout)

    def done(self) -> bool:
        return self._future.done()

    def cancel(self) -> bool:
        return self._future.cancel()

    def add_done_callback(self, fn) -> None:
        """Invoke ``fn(future)`` when the task completes (immediately if
        it already has) — the completion-event hook the plan scheduler's
        dependency-ordered dispatch rides on (plan/scheduler.py)."""
        self._future.add_done_callback(fn)


def get(refs, timeout: Optional[float] = None):
    """Resolve a TaskRef or list of TaskRefs to values (ray.get parity)."""
    if isinstance(refs, TaskRef):
        return refs.result(timeout)
    return [r.result(timeout) for r in refs]


def wait(refs: Sequence[TaskRef],
         num_returns: int = 1,
         timeout: Optional[float] = None) -> Tuple[List[TaskRef], List[TaskRef]]:
    """Block until ``num_returns`` of ``refs`` are done (ray.wait parity).

    Returns ``(done, not_done)`` with ``done`` ordered by completion
    readiness scan order (stable w.r.t. input order, like ray.wait).
    If fewer than ``num_returns`` complete before ``timeout``, returns
    whatever is done — the caller must not assume ``len(done) ==
    num_returns`` (the reference's throttle miscounts exactly this way,
    SURVEY.md §7 "known bugs"; we return the true count).
    """
    if num_returns > len(refs):
        raise ValueError(
            f"num_returns={num_returns} exceeds number of refs={len(refs)}")
    if len({id(r) for r in refs}) != len(refs):
        raise ValueError("wait() does not accept duplicate refs")
    import time
    deadline = None if timeout is None else time.monotonic() + timeout
    pending = {r._future: r for r in refs}
    done_refs: List[TaskRef] = []
    satisfied: set = set()
    while num_returns > 0 and len(satisfied) < num_returns:
        # Satisfied futures were dropped from `pending` below, so every
        # wake scans only the still-live futures — a large fan-out's
        # worst case is one pass per completion over the survivors, not
        # O(n^2) rebuilds of the full list.
        budget = (None if deadline is None
                  else max(0.0, deadline - time.monotonic()))
        finished, _ = cf.wait(
            pending.keys(), timeout=budget,
            return_when=cf.ALL_COMPLETED
            if num_returns - len(satisfied) == len(pending)
            else cf.FIRST_COMPLETED)
        satisfied.update(finished)
        for future in finished:
            pending.pop(future, None)
        if deadline is not None and time.monotonic() >= deadline:
            break
    for ref in refs:  # stable order
        if ref._future in satisfied and len(done_refs) < max(num_returns, 0):
            done_refs.append(ref)
    done_set = set(id(r) for r in done_refs)
    not_done = [r for r in refs if id(r) not in done_set]
    return done_refs, not_done


# Last shuffle-worker pool created in this process: a benchmark record
# reports the EFFECTIVE data-plane width and backend, not os.cpu_count()
# — a 1-wide pool on a 96-core host must not claim 96-way normalization.
# Only real worker pools register; single-thread driver/utility pools do
# not.
_pool_info_lock = threading.Lock()
_last_pool_info = {"backend": None, "workers": None, "pids": []}


def note_worker_pool(backend: str, workers: int, pids: Sequence[int]) -> None:
    """Record the most recent worker pool's effective shape."""
    with _pool_info_lock:
        _last_pool_info.update(backend=backend, workers=workers,
                               pids=list(pids))


def last_worker_pool() -> dict:
    """``{backend, workers, pids}`` of the most recent worker pool;
    ``backend`` None if none yet."""
    with _pool_info_lock:
        return dict(_last_pool_info)


class Executor:
    """Per-host thread-pool task executor.

    Threads (not processes) because the hot work — pyarrow Parquet decode,
    take/concat, NumPy RNG — releases the GIL; threads share the host RAM
    arrow buffers zero-copy, which is the plasma-equivalent data plane.
    """

    def __init__(self, num_workers: Optional[int] = None,
                 thread_name_prefix: str = "rsdl-worker",
                 task_retries: int = 0,
                 retry_policy=None):
        """``task_retries``: re-run a task that raises up to N extra times
        before surfacing the failure — the stand-in for Ray's implicit task
        retry the reference leans on (SURVEY.md §5). Safe for local shuffle
        tasks (every random draw is keyed by (seed, epoch, task), so a
        retried task reproduces its output exactly) and for distributed MAP
        tasks (re-sent chunks are deduplicated by the receiver). NOT safe
        for tasks that consume one-shot inputs — distributed REDUCE tasks
        consume transport messages exactly once, so they are submitted via
        :meth:`submit_once`.

        Retries run under the shared ``runtime.retry.RetryPolicy``
        (exponential backoff with decorrelated jitter — a zero-sleep loop
        hammers exactly the resource that just failed), resolved for the
        ``executor`` component (``RSDL_EXECUTOR_RETRY_*`` env overrides
        the backoff bounds; ``task_retries`` pins the attempt budget).
        Pass ``retry_policy`` to override wholesale."""
        if num_workers is None:
            num_workers = os.cpu_count() or 4
        if task_retries < 0:
            raise ValueError(f"task_retries must be >= 0, got {task_retries}")
        self._num_workers = num_workers
        self._task_retries = task_retries
        # Registry evidence for rsdl_top / exposition: pool width and a
        # per-pool submission counter (labelled by thread-name prefix —
        # the same name SIGUSR1 stack dumps show, so the two join).
        from ray_shuffling_data_loader_tpu.runtime import metrics as rt_metrics
        rt_metrics.gauge("rsdl_executor_workers",
                         "thread-pool width by pool name",
                         pool=thread_name_prefix).set(num_workers)
        self._tasks_submitted = rt_metrics.counter(
            "rsdl_executor_tasks_total", "tasks submitted by pool name",
            pool=thread_name_prefix)
        # The thread backend's "worker process" is this process: publish
        # it under the same per-pid gauge the process pool uses so
        # rsdl_top's per-process view reads identically across backends.
        rt_metrics.gauge("rsdl_executor_worker_up",
                         "1 while the pid is a live pool worker",
                         pool=thread_name_prefix,
                         pid=str(os.getpid())).set(1)
        if retry_policy is None and task_retries:
            from ray_shuffling_data_loader_tpu.runtime import retry as rt
            retry_policy = rt.RetryPolicy.for_component(
                "executor", retry_max_attempts=task_retries + 1)
        self._retry_policy = retry_policy
        self._pool = cf.ThreadPoolExecutor(
            max_workers=num_workers, thread_name_prefix=thread_name_prefix)
        self._shutdown = False
        if thread_name_prefix == "rsdl-worker":
            note_worker_pool("thread", num_workers, [os.getpid()])

    #: Data-plane discriminator (procpool.ProcessPoolExecutor says
    #: "process"); shuffle_epoch keys off it.
    backend = "thread"

    @property
    def num_workers(self) -> int:
        return self._num_workers

    def worker_pids(self) -> List[int]:
        """PIDs actually executing tasks — for the thread backend that is
        this process alone."""
        return [os.getpid()]

    def submit(self, fn: Callable, *args, **kwargs) -> TaskRef:
        if self._shutdown:
            raise RuntimeError("executor is shut down")
        self._tasks_submitted.inc()
        if self._retry_policy is not None:
            return TaskRef(self._pool.submit(self._run_with_retries, fn,
                                             args, kwargs))
        return TaskRef(self._pool.submit(fn, *args, **kwargs))

    def submit_once(self, fn: Callable, *args, **kwargs) -> TaskRef:
        """Submit WITHOUT the executor's retry policy — for tasks whose
        inputs are consumed on first use (e.g. one-shot transport
        messages), where a retry could only block and then fail with a
        misleading timeout."""
        if self._shutdown:
            raise RuntimeError("executor is shut down")
        self._tasks_submitted.inc()
        return TaskRef(self._pool.submit(fn, *args, **kwargs))

    def _run_with_retries(self, fn: Callable, args, kwargs) -> Any:
        # RetryPolicy owns the loop: jittered backoff between attempts
        # (never a zero-sleep hammer), the teardown-signal exclusions
        # (KeyboardInterrupt/SystemExit are never swallowed and never
        # retried), WARNING per intermediate failure, and the FINAL
        # failure logged at ERROR with the exhausted budget.
        return self._retry_policy.call(
            fn, *args, describe=getattr(fn, "__name__", repr(fn)), **kwargs)

    def map(self, fn: Callable, items: Sequence) -> List[TaskRef]:
        return [self.submit(fn, item) for item in items]

    def shutdown(self, wait_for_tasks: bool = True) -> None:
        self._shutdown = True
        self._pool.shutdown(wait=wait_for_tasks)

    def __enter__(self) -> "Executor":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
