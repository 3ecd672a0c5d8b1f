"""Generic progress/deadline watchdog for pipeline stages.

The flagship bulk device-rebatch path had no liveness guarantee: a
wedged ``jax.device_put`` (a stuck PJRT client) blocked
the producer thread forever while the consumer sat in ``queue.get`` —
an indefinite, silent stall at exactly the scale the library exists
for. Threads can't be interrupted mid-C-call, so the
cure is supervision: a stage registers a *watch* around its blocking
step; a single daemon monitor thread detects a missed deadline WHILE
the step is still stuck, files a structured :class:`StallReport` into
``stats.watchdog_stats()``, logs the reason, and runs the stage's
``on_stall`` escalation hook (which for the bulk path flips the
converter to the per-batch fallback — see jax_dataset.py). When the
stuck call finally returns, the stage sees ``handle.stalled`` and
finishes degraded instead of trusting the path that just wedged.

One process-wide instance (:func:`get_watchdog`) supervises every
stage; the monitor thread parks on a condition when no watches are
active, so an idle watchdog costs nothing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Callable, Iterator, Optional

from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)


@dataclasses.dataclass
class StallReport:
    """One deadline miss, as recorded into ``stats.watchdog_stats()``."""

    name: str            # watch name, e.g. "jax_dataset.bulk_transfer"
    waited_s: float      # time since the watch's last heartbeat
    deadline_s: float    # the deadline that was missed
    escalation: int      # 1 on the first miss, 2 at 2x the deadline, ...
    detail: str          # stage-supplied context (queue depth, bytes, ...)
    timestamp: float     # time.time() at detection


class WatchHandle:
    """Live handle for one supervised step.

    The supervised thread calls :meth:`beat` to reset the deadline (for
    multi-part steps); the monitor sets :attr:`stalled` /
    :attr:`report` when a deadline is missed, which the supervised
    thread inspects after its blocking call returns.
    """

    __slots__ = ("name", "deadline_s", "on_stall", "detail_fn",
                 "_last_beat", "stalled", "escalations", "report")

    def __init__(self, name: str, deadline_s: float,
                 on_stall: Optional[Callable[[StallReport], None]],
                 detail_fn: Optional[Callable[[], str]]):
        self.name = name
        self.deadline_s = deadline_s
        self.on_stall = on_stall
        self.detail_fn = detail_fn
        self._last_beat = time.monotonic()
        self.stalled = False
        self.escalations = 0
        self.report: Optional[StallReport] = None

    def beat(self) -> None:
        """Report progress: the deadline clock restarts from now."""
        self._last_beat = time.monotonic()

    def _detail(self) -> str:
        if self.detail_fn is None:
            return ""
        try:
            return str(self.detail_fn())
        except Exception as e:  # noqa: BLE001 - detail must never kill it
            return f"<detail failed: {e}>"


class PeriodicHandle:
    """One registered periodic callback run by the monitor thread."""

    __slots__ = ("name", "interval_s", "fn", "next_due")

    def __init__(self, name: str, interval_s: float, fn: Callable[[], None]):
        self.name = name
        # Floor guards a zero/negative interval from busy-looping the
        # one monitor thread every subsystem shares.
        self.interval_s = max(0.01, float(interval_s))
        self.fn = fn
        self.next_due = time.monotonic() + self.interval_s


class Watchdog:
    """Deadline monitor: one daemon thread supervising all active watches.

    The same thread services registered *periodic* callbacks
    (:meth:`every`) — the history ring tick and the health detectors ride
    the existing supervision thread instead of each spawning their own.
    """

    def __init__(self, poll_interval_s: float = 0.05):
        self.poll_interval_s = poll_interval_s
        self._cond = threading.Condition()
        self._watches: "set[WatchHandle]" = set()
        self._periodics: "set[PeriodicHandle]" = set()
        self._thread: Optional[threading.Thread] = None

    def _ensure_thread_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._monitor, daemon=True, name="rsdl-watchdog")
            self._thread.start()
        self._cond.notify_all()

    @contextlib.contextmanager
    def watch(self, name: str, deadline_s: float,
              on_stall: Optional[Callable[[StallReport], None]] = None,
              detail_fn: Optional[Callable[[], str]] = None
              ) -> Iterator[WatchHandle]:
        """Supervise the enclosed block: if it runs longer than
        ``deadline_s`` without a :meth:`WatchHandle.beat`, a stall is
        reported (and re-escalated at every further deadline multiple).
        ``on_stall`` runs on the MONITOR thread — the supervised thread
        is, by definition, stuck."""
        handle = WatchHandle(name, deadline_s, on_stall, detail_fn)
        with self._cond:
            self._watches.add(handle)
            self._ensure_thread_locked()
        try:
            yield handle
        finally:
            with self._cond:
                self._watches.discard(handle)

    def every(self, interval_s: float, fn: Callable[[], None],
              name: str = "periodic") -> PeriodicHandle:
        """Run ``fn`` on the monitor thread every ``interval_s`` seconds
        until :meth:`cancel` — even while no watches are active (the
        monitor parks only when it has neither watches nor periodics).
        ``fn`` must be brief and must never raise for long-term health;
        raising is survived and logged."""
        handle = PeriodicHandle(name, interval_s, fn)
        with self._cond:
            self._periodics.add(handle)
            self._ensure_thread_locked()
        return handle

    def cancel(self, handle: PeriodicHandle) -> None:
        with self._cond:
            self._periodics.discard(handle)

    def _monitor(self) -> None:
        from ray_shuffling_data_loader_tpu import stats as stats_mod
        while True:
            with self._cond:
                if not self._watches and not self._periodics:
                    # Idle park; a new watch()/every() notifies. Bounded
                    # wait only so a torn-down interpreter lets the
                    # daemon cycle out.
                    self._cond.wait(timeout=5.0)
                    continue
                now = time.monotonic()
                due = []
                for w in self._watches:
                    waited = now - w._last_beat
                    if waited >= w.deadline_s * (w.escalations + 1):
                        w.escalations += 1
                        w.stalled = True
                        due.append((w, waited, w.escalations))
                due_periodics = []
                for p in self._periodics:
                    if now >= p.next_due:
                        p.next_due = now + p.interval_s
                        due_periodics.append(p)
                if not due and not due_periodics:
                    # Nothing to fire this pass: sleep to the earlier of
                    # the watch poll tick and the next periodic due time.
                    if self._watches:
                        timeout = self.poll_interval_s
                    else:
                        timeout = min(5.0, max(
                            0.005,
                            min(p.next_due for p in self._periodics) - now))
                    self._cond.wait(timeout=timeout)
            # Reports, logs, escalation hooks and periodic callbacks run
            # OUTSIDE the lock: a callback that takes its subsystem's
            # locks (the degrade path does) must not be able to deadlock
            # new watch()ers.
            for p in due_periodics:
                try:
                    p.fn()
                except Exception:  # noqa: BLE001 - supervision survives
                    logger.exception("watchdog periodic %s failed", p.name)
            for w, waited, escalation in due:
                report = StallReport(
                    name=w.name, waited_s=waited, deadline_s=w.deadline_s,
                    escalation=escalation, detail=w._detail(),
                    timestamp=time.time())
                w.report = report
                stats_mod.watchdog_stats().record_stall(report)
                if escalation == 2:
                    # The stall persisted past a second deadline: dump
                    # the flight recorder + thread stacks ONCE per watch
                    # while the stuck call is still stuck — the forensic
                    # record a post-mortem cannot reconstruct.
                    from ray_shuffling_data_loader_tpu.runtime import (
                        telemetry)
                    try:
                        telemetry.dump(
                            reason=f"watchdog escalation: {w.name}")
                    except Exception:  # noqa: BLE001 - supervision survives
                        logger.exception(
                            "watchdog telemetry dump failed for %s", w.name)
                log = logger.warning if escalation == 1 else logger.error
                log("watchdog: %s has run %.2fs (deadline %.2fs, "
                    "escalation %d)%s", report.name, report.waited_s,
                    report.deadline_s, report.escalation,
                    f": {report.detail}" if report.detail else "")
                if w.on_stall is not None:
                    try:
                        w.on_stall(report)
                    except Exception:  # noqa: BLE001 - supervision survives
                        logger.exception(
                            "watchdog on_stall hook for %s failed", w.name)


_global_lock = threading.Lock()
_global: Optional[Watchdog] = None


def get_watchdog() -> Watchdog:
    """THE process-wide watchdog (poll interval from the policy registry
    at first use)."""
    global _global
    with _global_lock:
        if _global is None:
            from ray_shuffling_data_loader_tpu.runtime import policy
            _global = Watchdog(poll_interval_s=policy.resolve(
                "watchdog", "watchdog_poll_interval_s"))
        return _global
