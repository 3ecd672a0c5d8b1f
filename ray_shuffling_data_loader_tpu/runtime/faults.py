"""Deterministic fault injection for the shuffle pipeline.

Ray users test failure handling by killing raylets; this repo's tasks
are host threads, so failure testing needs its own plane. This module
is a seeded, policy-configured registry of **named fault sites**
threaded through the pipeline's hot paths:

===================  ======================================================
site                 where it fires
===================  ======================================================
``map_read``         the Parquet read in ``shuffle.shuffle_map``
``reduce_gather``    the map-output gather in ``shuffle._reduce_task``
``queue_put``        ``multiqueue.MultiQueue.put``
``queue_get``        ``multiqueue.MultiQueue.get``
``queue_fetch``      ``multiqueue_service.RemoteQueue._fetch_batch``
``transport_send``   ``parallel.transport.TcpTransport.send`` (per frame)
``transport_recv``   ``parallel.transport.TcpTransport.recv``
``spill_write``      ``spill.SpillManager.maybe_spill``
``spill_read``       ``spill.SpilledTable.load``
``device_transfer``  the ``jax.device_put`` in ``jax_dataset``
``queue_server_crash``  ``QueueServer`` GET handling — the whole server
                     process dies (``os._exit`` in dedicated-server
                     mode; in-process servers close) and the supervisor
                     must restart it from the watermark journal
``conn_reset_midframe``  ``QueueServer`` response writing — a torn frame
                     then a hard close, the reset-mid-response shape the
                     v2 replay protocol recovers
``frame_corrupt``    ``QueueServer`` response writing — one payload byte
                     flipped ON THE WIRE (replay buffer keeps the good
                     copy); the consumer CRC-rejects and NACKs
``ack_lost``         ``RemoteQueue`` request sending — one GET's ack
                     watermark suppressed; harmless by design (acks are
                     cumulative)
``storage_read``     the ``storage`` source fetch (``storage.read_table``
                     / ``storage.open_parquet``) — the remote-object-GET
                     failure shape, surfaced before the in-place IO retry
``storage_stall``    same boundary, but with ``:delayN`` — a slow remote
                     first byte (latency, not loss); without a delay it
                     behaves like ``storage_read``
``member_crash``     ``membership.MembershipManager.maybe_crash`` — the
                     named rank dies at the epoch/window boundary check
                     (``member_crash:rank2`` — ``rankN`` is sugar for
                     ``taskN``) and the world shrinks around it
``member_partition`` ``TcpTransport.send``/``send_heartbeat`` — frames
                     to the matched dest rank vanish silently (a
                     blackholing link, not an error), starving the
                     failure detector
``member_flap``      ``membership.detector.HeartbeatProber`` — one probe
                     round to the matched rank is dropped, driving the
                     detector's flap hysteresis
===================  ======================================================

A chaos spec (``RSDL_CHAOS_SPEC`` env var, or :func:`install`) is a
comma-separated list of rules::

    rule := site[@rate][:epochN][:taskN|fileN][:afterN][:xN][:delayN]

    map_read:epoch1:file2      fail epoch 1's read of file 2, once
    reduce_gather:task0        fail reducer 0's gather once per epoch
    queue_get:task1:after2     fail queue 1's third get
    map_read:file0:x5          fail file 0's read 5 times per epoch
                               (exhausts a <5-attempt recovery budget)
    transport_send@0.01        fail ~1% of (epoch, reducer) send keys
    reduce_gather:delay50      SLOW epoch's reduce gathers by 50 ms
                               (once per (site, epoch, task) key; no
                               fault raised — a latency, not a loss)

Rules fire **per distinct (site, epoch, task) key**: the first matching
call for a key raises :class:`InjectedFault`; the retry/recompute of
the same key passes — which is exactly what makes recovery machinery
provable (the recomputed task succeeds and its output can be asserted
bit-identical). ``afterN`` skips the key's first N calls; ``xN`` fails
N consecutive calls per key (to force recovery exhaustion). Rate rules
draw from a hash of ``(seed, site, epoch, task)`` — the same seed
reproduces the same failures every run, on any host.

:class:`InjectedFault` deliberately does NOT subclass ``OSError``: it
represents a *task-level* fault and must surface through the recovery
machinery under test, not be absorbed by an in-place IO retry.

Stdlib-only (importable before jax/pyarrow and from the native layer).
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)

#: The registered site names; a spec naming anything else is rejected at
#: parse time (a typo'd site must fail loudly, not silently never fire).
SITES = frozenset({
    "map_read", "reduce_gather", "queue_put", "queue_get", "queue_fetch",
    "transport_send", "transport_recv", "spill_write", "spill_read",
    "device_transfer",
    # Process-level sites (PR 5): the cross-process queue topology.
    "queue_server_crash", "conn_reset_midframe", "frame_corrupt",
    "ack_lost",
    # Storage plane (storage/): the remote-object fetch boundary.
    "storage_read", "storage_stall",
    # Membership plane (membership/): elastic-world failure shapes.
    # member_crash kills a rank (``:rankN`` — sugar for taskN) at an
    # epoch/window boundary check; member_partition blackholes transport
    # frames to a dest rank; member_flap starves one probe round.
    "member_crash", "member_partition", "member_flap",
    # Rebalance plane (rebalance/): live queue migration phases. Each
    # site models the whole process dying at that exact phase — source
    # mid-PREPARE, target mid-COMMIT, driver mid-decision — keyed by
    # (epoch = the move's target placement generation, task = rank).
    "rebalance_prepare", "rebalance_commit", "rebalance_abort",
})

_SPEC_ENVS = ("RSDL_CHAOS_SPEC", "RSDL_FAULTS_SPEC")
_SEED_ENVS = ("RSDL_CHAOS_SEED", "RSDL_FAULTS_SEED")


class InjectedFault(RuntimeError):
    """Raised by a fault site matched by the active chaos spec."""

    def __init__(self, site: str, epoch: Optional[int],
                 task: Optional[int], rule: str):
        super().__init__(
            f"injected fault at site {site!r} "
            f"(epoch={epoch}, task={task}, rule={rule!r})")
        self.site = site
        self.epoch = epoch
        self.task = task
        self.rule = rule

    def __reduce__(self):
        # Crosses the process-pool pipe: the default reduce would rebuild
        # from the formatted message alone and fail __init__.
        return (type(self), (self.site, self.epoch, self.task, self.rule))


@dataclasses.dataclass
class QuarantinedFile:
    """Structured report for an input file dropped by ``on_bad_file="skip"``.

    Returned by ``shuffle_map`` in place of a ``MapShard``; the reduce
    gather skips it, and the report is recorded in
    ``stats.fault_stats()`` so the drop is observable, not silent.
    """

    filename: str
    epoch: int
    file_index: int
    error: str
    timestamp: float = dataclasses.field(default_factory=time.time)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ChaosRule:
    """One parsed spec rule (see module docstring for the grammar)."""

    site: str
    epoch: Optional[int] = None   # None = any epoch
    task: Optional[int] = None    # None = any task
    after: int = 0                # skip the key's first N matching calls
    count: int = 1                # then fail N consecutive calls per key
    rate: Optional[float] = None  # probabilistic gate per key (None = 1.0)
    delay_ms: Optional[int] = None  # slow the call instead of failing it
    text: str = ""                # original rule text, for error messages

    def matches(self, site: str, epoch: Optional[int],
                task: Optional[int]) -> bool:
        if site != self.site:
            return False
        if self.epoch is not None and epoch != self.epoch:
            return False
        if self.task is not None and task != self.task:
            return False
        return True


def _parse_rule(text: str) -> ChaosRule:
    tokens = [t.strip() for t in text.split(":") if t.strip()]
    if not tokens:
        raise ValueError(f"empty chaos rule in spec: {text!r}")
    site_token = tokens[0]
    rate = None
    if "@" in site_token:
        site_token, _, rate_token = site_token.partition("@")
        rate = float(rate_token)
        if not 0.0 <= rate <= 1.0:
            raise ValueError(f"chaos rate must be in [0, 1]: {text!r}")
    if site_token not in SITES:
        raise ValueError(
            f"unknown chaos site {site_token!r} in rule {text!r} "
            f"(known: {sorted(SITES)})")
    rule = ChaosRule(site=site_token, rate=rate, text=text)
    for token in tokens[1:]:
        for prefix, field in (("epoch", "epoch"), ("file", "task"),
                              ("task", "task"), ("rank", "task"),
                              ("after", "after"),
                              ("delay", "delay_ms"), ("x", "count")):
            if token.startswith(prefix) and token[len(prefix):].isdigit():
                setattr(rule, field, int(token[len(prefix):]))
                break
        else:
            raise ValueError(
                f"bad chaos qualifier {token!r} in rule {text!r} "
                "(expected epochN, taskN/fileN, afterN, xN, or delayN)")
    if rule.count < 1:
        raise ValueError(f"xN count must be >= 1: {text!r}")
    return rule


def parse_spec(spec: str) -> List[ChaosRule]:
    """Parse a full chaos spec string; raises ValueError on any bad rule."""
    return [_parse_rule(part) for part in spec.split(",") if part.strip()]


def spec_for_node(site: str, node, delay_ms: Optional[int] = None,
                  count: int = 1) -> str:
    """Chaos-rule text targeting one epoch-plan node (plan/ir.py).

    The harness used to hand-write ``site:epochE:taskT`` rules from
    privately re-derived key arithmetic; deriving the rule FROM the plan
    node keeps the chaos key and the task's lineage key equal by
    construction (they join in telemetry by ``(kind, epoch, task)``).
    ``delay_ms`` builds a ``delayN`` straggler rule (the speculation
    tests' injector) instead of a failure rule.
    """
    if site not in SITES:
        raise ValueError(f"unknown chaos site {site!r} "
                         f"(known: {sorted(SITES)})")
    rule = f"{site}:epoch{node.key.epoch}:task{node.key.task}"
    if delay_ms is not None:
        rule += f":delay{int(delay_ms)}"
    if count != 1:
        rule += f":x{int(count)}"
    _parse_rule(rule)  # validate the composed text loudly
    return rule


def _stable_draw(seed: int, site: str, epoch, task) -> float:
    """Deterministic uniform [0, 1) draw keyed by (seed, site, epoch,
    task) — the same seed reproduces the same failure set on any host."""
    digest = hashlib.sha256(
        f"{seed}:{site}:{epoch}:{task}".encode()).digest()
    return int.from_bytes(digest[:8], "little") / 2.0**64


class FaultInjector:
    """Active chaos configuration: parsed rules + per-key call counters."""

    def __init__(self, rules: List[ChaosRule], seed: int = 0):
        self.rules = rules
        self.seed = seed
        self._lock = threading.Lock()
        # (rule_index, site, epoch, task) -> matching calls seen so far.
        self._calls: Dict[Tuple, int] = {}
        self._fired: List[dict] = []

    def check(self, site: str, epoch: Optional[int],
              task: Optional[int]) -> Optional[InjectedFault]:
        for index, rule in enumerate(self.rules):
            if not rule.matches(site, epoch, task):
                continue
            key = (index, site, epoch, task)
            with self._lock:
                seen = self._calls.get(key, 0)
                self._calls[key] = seen + 1
            if not rule.after <= seen < rule.after + rule.count:
                continue
            if rule.rate is not None and _stable_draw(
                    self.seed, site, epoch, task) >= rule.rate:
                continue
            with self._lock:
                self._fired.append({
                    "site": site, "epoch": epoch, "task": task,
                    "rule": rule.text, "call": seen,
                })
            if rule.delay_ms is not None:
                # A latency fault: slow the matched call instead of
                # failing it (bottleneck-attribution regressions inject
                # a slow stage this way). Later rules may still fail
                # this same call.
                from ray_shuffling_data_loader_tpu.runtime import telemetry
                telemetry.record(site, epoch=epoch, task=task,
                                 fault="delay", delay_ms=rule.delay_ms)
                time.sleep(rule.delay_ms / 1e3)
                continue
            return InjectedFault(site, epoch, task, rule.text)
        return None

    def fired(self) -> List[dict]:
        with self._lock:
            return list(self._fired)


# Fast path: `inject()` sits on per-item hot paths (queue get/put), so
# the inactive case must be one attribute load, not an env lookup.
_ACTIVE = False
_injector: Optional[FaultInjector] = None
_install_lock = threading.Lock()


def install(spec: str, seed: int = 0) -> FaultInjector:
    """Programmatically activate a chaos spec (tests, the dryrun)."""
    global _ACTIVE, _injector
    injector = FaultInjector(parse_spec(spec), seed=seed)
    with _install_lock:
        _injector = injector
        _ACTIVE = bool(injector.rules)
    if injector.rules:
        logger.warning("fault injection ACTIVE: %d rule(s), seed=%d: %s",
                       len(injector.rules), seed, spec)
    return injector


def clear() -> None:
    """Deactivate fault injection (does NOT re-read the environment)."""
    global _ACTIVE, _injector
    with _install_lock:
        _injector = None
        _ACTIVE = False


def configure_from_env() -> Optional[FaultInjector]:
    """(Re-)read ``RSDL_CHAOS_SPEC``/``RSDL_CHAOS_SEED`` (aliases:
    ``RSDL_FAULTS_*``); clears the injector when no spec is set."""
    spec = next((os.environ[name] for name in _SPEC_ENVS
                 if os.environ.get(name, "").strip()), None)
    if spec is None:
        clear()
        return None
    seed = int(next((os.environ[name] for name in _SEED_ENVS
                     if os.environ.get(name, "").strip()), "0"))
    return install(spec, seed=seed)


def active() -> bool:
    return _ACTIVE


def get_injector() -> Optional[FaultInjector]:
    return _injector


def inject(site: str, epoch: Optional[int] = None,
           task: Optional[int] = None) -> None:
    """Fault-site hook: raises :class:`InjectedFault` when the active
    chaos spec matches this call; free (one global load) when inactive."""
    if not _ACTIVE:
        return
    injector = _injector
    if injector is None:
        return
    fault = injector.check(site, epoch, task)
    if fault is not None:
        from ray_shuffling_data_loader_tpu import stats as stats_mod
        from ray_shuffling_data_loader_tpu.runtime import telemetry
        stats_mod.fault_stats().record_injected(site, epoch, task)
        # kind = the fault-site name: the chaos event and the stage's
        # own telemetry events join on (kind, epoch, task).
        telemetry.record(site, epoch=epoch, task=task, fault="injected",
                         rule=fault.rule)
        logger.warning("%s", fault)
        raise fault


# Honor a spec present in the environment at import time, so a driver
# exporting RSDL_CHAOS_SPEC reproduces its failures with zero code.
configure_from_env()
