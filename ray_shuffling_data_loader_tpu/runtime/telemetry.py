"""Structured-event flight recorder + per-batch bottleneck attribution.

The pipeline's observability used to be three disconnected islands —
``stats.py`` wall-clock collectors, ``utils/tracing.py`` profiler spans,
and the watchdog/fault snapshot dicts — with no shared identity for an
event: "epoch 3 stalled" could not be joined against "reducer 2 retried
a fetch" without log scraping. This module is the spine they all report
through:

**Flight recorder** — a lock-cheap, fixed-size ring of structured
events ``(t_mono, kind, epoch, task, batch, dur_s, attrs)`` emitted
from every pipeline stage (shuffle map read, reduce gather, queue
put/get/fetch, transport send/recv, spill write/read, device transfer,
convert, batch wait, train step) plus watchdog stalls and fault
injections/retries/recomputes. Event ``kind`` reuses the 10 fault-site
names from :mod:`runtime.faults` wherever a stage has a fault site, so
a chaos run's fault events and its telemetry events correlate by
``(kind, epoch, task)`` BY CONSTRUCTION. The ring is dumpable as JSONL
on demand (:func:`dump`), on watchdog escalation (runtime/watchdog.py),
and on ``SIGUSR1`` (:func:`install_signal_dump`) together with
named-thread stack traces.

**Bottleneck attribution** — the one question a production loader must
answer online, the way tf.data's analysis framework and Plumber answer
it for TensorFlow input pipelines: *is the device waiting on the
loader, and on which stage?* Stage-kind events feed per-epoch
fixed-bucket histograms (mergeable — :mod:`runtime.metrics`), and
:meth:`StageAttribution.epoch_verdict` decomposes each epoch into
``{bottleneck_stage, stall_pct, p50/p95/p99 per stage}``: when the
consumer's batch-wait share of wall clock exceeds the policy threshold
the verdict names the busiest producer stage; otherwise the pipeline
keeps up and the verdict is ``train_step`` (compute-bound — the goal
state). The verdict lands in the trial CSV and a human one-liner
logged at each epoch's completion.

Every event also feeds the metrics registry (``rsdl_events_total`` by
kind, ``rsdl_stage_seconds`` by stage), so the exposition endpoint and
``tools/rsdl_top.py`` see the same truth as the recorder.

Overhead: disabled, ``record()`` is one global load (the
:mod:`runtime.faults` fast-path pattern). Enabled, it is one
``monotonic()`` read, one tuple, and two lock round-trips — measured
by :func:`measure_record_overhead` (tests/test_telemetry.py holds it
under a per-event ceiling).

**One span vocabulary** — :func:`span` / :func:`span_begin` /
:func:`span_end` are the only way the program times a stage. One call
lands the interval in the recorder and, for the kinds of
:data:`SPAN_NAMES`, in the JAX profiler's trace under the kind's fixed
``rsdl.<layer>.<what>`` name (the annotation class is resolved lazily,
once ``jax`` is in the process), so the host's stages sit on the device
trace's clock without a second recording.

Stdlib-only at import (importable before jax/pyarrow and from the native
layer).
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import signal
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from ray_shuffling_data_loader_tpu.runtime import metrics
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)

#: Event kind -> attribution stage. Kinds reuse the fault-site
#: vocabulary (runtime/faults.py) wherever the stage has a fault site,
#: so chaos and telemetry events join on (kind, epoch, task). Kinds not
#: in this table (queue_put, queue_get, transport_send/recv,
#: spill_write/read, watchdog_stall, fault bookkeeping) are recorded and
#: exported but are not latency-decomposition stages: queue_get's wait
#: is owned by the dataset layer's epoch-tagged ``queue_wait`` event
#: (counting both would double-bill the same blocked time).
STAGE_BY_KIND: Dict[str, str] = {
    "map_read": "map_read",
    "reduce_gather": "reduce",
    "queue_wait": "queue_wait",
    "queue_fetch": "fetch",
    "convert": "convert",
    "device_transfer": "device_transfer",
    "train_step": "train_step",
}

#: THE span vocabulary: recorder kind -> the span's fixed name in the
#: profiler's trace (``jax.profiler.TraceAnnotation``). A span opened
#: through :func:`span` / :func:`span_begin` whose kind is listed here
#: also holds that annotation while it is open (once ``jax`` is imported
#: in the process), with ``epoch`` / ``task`` / ``batch`` as the
#: annotation's arguments and never in its name, so one interval lands
#: in both sinks from one call. Kinds not listed are recorder-only.
#: PERF.md section 3 tabulates this list with each span's reader.
SPAN_NAMES: Dict[str, str] = {
    "map_read": "rsdl.loader.map_read",
    "reduce_gather": "rsdl.loader.reduce",
    "spill_write": "rsdl.loader.spill_write",
    "spill_read": "rsdl.loader.spill_read",
    "queue_wait": "rsdl.loader.queue_wait",
    "queue_fetch": "rsdl.loader.queue_fetch",
    "convert": "rsdl.feed.convert",
    "device_transfer": "rsdl.feed.transfer",
    "batch_wait": "rsdl.feed.queue_get",
    "carve": "rsdl.feed.carve",
    "set_epoch": "rsdl.feed.set_epoch",
    "epoch_end": "rsdl.feed.epoch_end",
    "epoch_verdict": "rsdl.feed.epoch_verdict",
    "trace_gauges": "rsdl.feed.trace_gauges",
}

#: The one annotation the program opens outside :func:`span`: the
#: trainer's per-step marker (``utils/tracing.step_span``), which lets
#: the profiler group device operations by step. Profiler-only.
STEP_ANNOTATION = "rsdl.trainer.step"

#: THE device-scope vocabulary: every ``jax.named_scope`` the jitted train
#: step runs under -> (PERF.md's layer, the module that enters it). A
#: module names the scope it enters with :func:`step_scope`, which knows
#: no other name. The scopes partition the step: a device trace bills each
#: operation to the innermost scope on its ``op_name`` (TensorBoard's
#: profile plugin over ``utils/tracing.profile_trace`` groups by these
#: names; ``chipbench/readers/step_scopes.py`` tabulates them and reads
#: what is left as ``step_unscoped_pct``). Three hold what a model runs
#: outside its named parts and no product, kernel or loop of their own
#: (``tests/test_step_scopes.py``): ``rsdl.lm.layer``, ``rsdl.lm.loss``
#: (``models/mellum.py``) and ``rsdl.lm.moe_loops`` (``ops/moe.py``).
#: What stays outside by nature: the containers (``while``,
#: ``conditional``, ``call``: their bodies' operations are in the trace
#: beside them, and a reader that sums a scope would count both) and the
#: instructions XLA emits with no ``op_name`` (``copy-start`` / ``-done``,
#: ``async-done``, ``slice-start`` / ``-done``). A scope has no off
#: switch: it is a name on the compiled step's instructions and costs
#: nothing at run time. PERF.md section 3 tabulates this list with what
#: is under each scope and the metric that reads it.
STEP_SCOPES: Dict[str, Tuple[str, str]] = {
    "rsdl.train.optimizer": ("trainer", "parallel/trainer.py"),
    "rsdl.embedding.grad_exchange": ("collectives", "ops/embedding.py"),
    "rsdl.dlrm.lookup": ("kernels", "models/dlrm.py"),
    "rsdl.dlrm.interaction": ("model", "models/dlrm.py"),
    "rsdl.dlrm.mlp": ("model", "models/dlrm.py"),
    "rsdl.bert.mask": ("model", "workloads/bert_mlm.py"),
    "rsdl.bert.embed": ("model", "models/bert.py"),
    "rsdl.bert.proj": ("model", "models/bert.py"),
    "rsdl.bert.attention": ("kernels", "models/bert.py"),
    "rsdl.bert.mlp": ("model", "models/bert.py"),
    "rsdl.bert.mlm_head": ("model", "models/bert.py"),
    "rsdl.lm.embed": ("model", "models/mellum.py"),
    "rsdl.lm.layer": ("model", "models/mellum.py"),
    "rsdl.lm.norm": ("model", "models/mellum.py"),
    "rsdl.lm.proj": ("model", "models/mellum.py"),
    "rsdl.lm.rope": ("model", "ops/rope.py"),
    "rsdl.lm.attention": ("kernels", "models/mellum.py"),
    "rsdl.lm.ssm": ("kernels", "ops/ssd.py"),
    "rsdl.lm.sscan": ("kernels", "ops/selective_scan.py"),
    "rsdl.lm.gmu": ("model", "models/mellum.py"),
    "rsdl.lm.sconv": ("kernels", "ops/sconv.py"),
    "rsdl.lm.mlp": ("model", "models/mellum.py"),
    "rsdl.lm.moe": ("kernels", "ops/moe.py"),
    "rsdl.lm.moe_loops": ("kernels", "ops/moe.py"),
    "rsdl.lm.noise": ("model", "models/mellum.py"),
    "rsdl.lm.head": ("model", "models/mellum.py"),
    "rsdl.lm.loss": ("model", "models/mellum.py"),
}


def step_scope(name: str) -> str:
    """``name``, a key of :data:`STEP_SCOPES`: how a module names a scope
    it enters. A name the vocabulary lacks is a ``KeyError`` when the
    module is imported."""
    if name not in STEP_SCOPES:
        raise KeyError(name)
    return name


#: The step's own counters (``utils/tracing.step_stat``): stat name ->
#: the fields of the short vector the device computes for it each step.
#: ``moe_walk`` is one sparse-expert layer's walk (``ops/moe.py``): all
#: (token, pick) pairs, those whose expert this chip holds, the tiles and
#: rounds the walk took for them and the fullest held expert's pairs: what
#: ``_dispatch`` already holds, and no pass over the tokens (a count of
#: each token's held picks moved the compiled step's schedule, and 2.4 ms
#: of a 680 ms step with it: PERF.md section 6, PR 36). ``ssm_scan`` is
#: one state-space layer's chunked scan (``ops/ssd.py``), float32: the mean
#: over rows, chunks and heads of a chunk's whole decay ``exp(cum_end)``
#: (the share of the state a chunk starts from that reaches its end) and
#: the largest ``|carry|`` handed from one chunk to the next; Mamba-1's
#: selective scan (``ops/selective_scan.py``) records the same two, the
#: mean over channels and states. ``diff_attention`` is one differential
#: attention layer's ``lambda``, the scalar its second softmax map is
#: subtracted under (``models/mellum.py``).
#: :func:`step_stats_folded` has what each becomes in the registry.
STEP_STAT_FIELDS: Dict[str, Tuple[str, ...]] = {
    "moe_walk": ("pairs", "pairs_held", "tiles", "rounds",
                 "fullest_expert_rows"),
    "ssm_scan": ("end_decay_mean", "carry_abs_max"),
    "diff_attention": ("lambda",),
    "lm_noise": ("masked", "weight_sum"),
}

#: Upper bounds of ``rsdl_moe_tiles_per_step``: an even routing walks 128
#: and 256 tiles a step in the two decoder cells, a collapsed one 700.
_TILES_PER_STEP_BUCKETS = (16, 32, 64, 96, 128, 160, 192, 256, 320, 384,
                           512, 768, 1024, 2048, 4096)


def annotation_names() -> frozenset:
    """Every ``TraceAnnotation`` name the program may emit."""
    return frozenset(SPAN_NAMES.values()) | {STEP_ANNOTATION}


#: The decomposition's stage order (CSV columns, rsdl_top).
STAGES: Tuple[str, ...] = ("map_read", "reduce", "queue_wait", "fetch",
                           "convert", "device_transfer", "train_step")

#: Stages that do WORK (bottleneck candidates). Wait stages are
#: symptoms: a consumer blocked in queue_wait means an upstream work
#: stage is slow, and the verdict should name that stage.
_WORK_STAGES: Tuple[str, ...] = ("map_read", "reduce", "fetch", "convert",
                                 "device_transfer")

Event = Tuple[float, str, Optional[int], Optional[int], Optional[int],
              Optional[float], Optional[int], Optional[dict]]


class FlightRecorder:
    """Fixed-size ring buffer of structured events.

    Overwrite semantics: the ring holds the most recent ``capacity``
    events; ``total_recorded`` keeps counting past the wrap so readers
    can tell how much history was shed.
    """

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._buf: List[Optional[Event]] = [None] * capacity
        self._idx = 0
        self._lock = threading.Lock()

    def record(self, event: Event) -> None:
        with self._lock:
            self._buf[self._idx % self.capacity] = event
            self._idx += 1

    @property
    def total_recorded(self) -> int:
        with self._lock:
            return self._idx

    def events(self) -> List[Dict[str, Any]]:
        """Retained events, oldest first, as dicts (None fields elided)."""
        with self._lock:
            idx = self._idx
            if idx <= self.capacity:
                raw = self._buf[:idx]
            else:
                pivot = idx % self.capacity
                raw = self._buf[pivot:] + self._buf[:pivot]
        out = []
        for ev in raw:
            if ev is None:
                continue
            t_mono, kind, epoch, task, batch, dur_s, tid, attrs = ev
            d: Dict[str, Any] = {"t_mono": t_mono, "kind": kind}
            if epoch is not None:
                d["epoch"] = epoch
            if task is not None:
                d["task"] = task
            if batch is not None:
                d["batch"] = batch
            if dur_s is not None:
                d["dur_s"] = dur_s
            if tid is not None:
                d["tid"] = tid
            if attrs:
                d.update(attrs)
            out.append(d)
        return out


class StageAttribution:
    """Online per-epoch latency decomposition over stage events.

    Bounded state: per (epoch, stage) one fixed-bucket histogram plus
    totals, pruned to the most recent ``max_epochs`` epochs. Epoch-less
    stage events (e.g. a bare queue drained outside any dataset epoch)
    land in the run aggregate only.
    """

    _MAX_EPOCHS = 64

    def __init__(self, stall_threshold_pct: float = 10.0):
        self._lock = threading.Lock()
        self.stall_threshold_pct = stall_threshold_pct
        # epoch -> stage -> Histogram (epoch None = unattributed)
        self._hists: Dict[Optional[int], Dict[str, metrics.Histogram]] = {}
        # epoch -> (batch_wait_total_s, batch_count)
        self._waits: Dict[Optional[int], List[float]] = {}
        # epoch -> [first_t, last_t] monotonic bounds (wall clock of epoch)
        self._bounds: Dict[Optional[int], List[float]] = {}
        self._verdict_logged: set = set()
        # epoch -> its verdict line, held back until the turnover into the
        # next epoch is known (release_line appends the turnover's split)
        self._held_lines: Dict[int, str] = {}

    def observe(self, stage: str, epoch: Optional[int], dur_s: float,
                t: float) -> None:
        with self._lock:
            per_epoch = self._hists.setdefault(epoch, {})
            hist = per_epoch.get(stage)
            if hist is None:
                hist = per_epoch[stage] = metrics.Histogram()
            bounds = self._bounds.setdefault(epoch, [t - dur_s, t])
            bounds[0] = min(bounds[0], t - dur_s)
            bounds[1] = max(bounds[1], t)
            if epoch is not None and len(self._hists) > self._MAX_EPOCHS:
                self._prune_locked()
        hist.observe(dur_s)

    def observe_wait(self, epoch: Optional[int], dur_s: float,
                     t: float) -> None:
        with self._lock:
            wait = self._waits.setdefault(epoch, [0.0, 0])
            wait[0] += dur_s
            wait[1] += 1
            bounds = self._bounds.setdefault(epoch, [t - dur_s, t])
            bounds[0] = min(bounds[0], t - dur_s)
            bounds[1] = max(bounds[1], t)

    def _prune_locked(self) -> None:
        real = sorted(e for e in self._hists if e is not None)
        for stale in real[:len(real) - self._MAX_EPOCHS]:
            self._hists.pop(stale, None)
            self._waits.pop(stale, None)
            self._bounds.pop(stale, None)
            self._held_lines.pop(stale, None)

    def _verdict_locked(self, epochs: List[Optional[int]]
                        ) -> Optional[Dict[str, Any]]:
        merged: Dict[str, metrics.Histogram] = {}
        wait_total = 0.0
        wait_count = 0
        wall = 0.0
        seen = False
        for epoch in epochs:
            for stage, hist in self._hists.get(epoch, {}).items():
                seen = True
                agg = merged.get(stage)
                if agg is None:
                    agg = merged[stage] = metrics.Histogram(hist.bounds)
                agg.merge(hist)
            if epoch in self._waits:
                seen = True
                wait_total += self._waits[epoch][0]
                wait_count += int(self._waits[epoch][1])
            if epoch in self._bounds:
                lo, hi = self._bounds[epoch]
                wall += max(0.0, hi - lo)
        if not seen:
            return None
        stall_pct = 100.0 * wait_total / wall if wall > 0 else 0.0
        stages = {}
        for stage in STAGES:
            hist = merged.get(stage)
            if hist is None or hist.count == 0:
                continue
            stages[stage] = {
                "count": hist.count,
                "total_s": round(hist.sum, 6),
                "p50_ms": round(hist.percentile(0.50) * 1e3, 3),
                "p95_ms": round(hist.percentile(0.95) * 1e3, 3),
                "p99_ms": round(hist.percentile(0.99) * 1e3, 3),
            }
        work = {s: d["total_s"] for s, d in stages.items()
                if s in _WORK_STAGES}
        if stall_pct <= self.stall_threshold_pct:
            # The consumer rarely waited: the pipeline keeps up and the
            # trainer's own step is the bottleneck — the goal state.
            bottleneck = "train_step"
        elif work:
            bottleneck = max(work, key=work.get)
        else:
            bottleneck = "queue_wait" if "queue_wait" in stages else "unknown"
        return {
            "bottleneck_stage": bottleneck,
            "stall_pct": round(stall_pct, 3),
            "batch_wait_s": round(wait_total, 6),
            "batches_waited": wait_count,
            "wall_s": round(wall, 6),
            "stages": stages,
        }

    def epoch_verdict(self, epoch: int) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._verdict_locked([epoch])

    def run_summary(self) -> Optional[Dict[str, Any]]:
        """Verdict over every retained epoch (plus unattributed events)."""
        with self._lock:
            return self._verdict_locked(list(self._hists)
                                        + [e for e in self._waits
                                           if e not in self._hists])

    def epoch_complete(self, epoch: int, source: str = "",
                       hold: bool = False) -> None:
        """Log the epoch's one-line verdict (once per epoch per process;
        the dataset layer and the JAX binding both call this and the
        first completion wins). With ``hold`` the line is kept until
        :meth:`release_line` logs it, so that it can carry the split of
        the turnover into the next epoch; a later caller that does not
        hold releases a line an earlier one held."""
        with self._lock:
            if epoch in self._verdict_logged:
                held = None if hold else self._held_lines.pop(epoch, None)
                verdict = None
            else:
                held = None
                self._verdict_logged.add(epoch)
                verdict = self._verdict_locked([epoch])
        if held is not None:
            logger.info("%s", held)
        if verdict is None:
            return
        busiest = verdict["stages"].get(verdict["bottleneck_stage"], {})
        line = (
            "epoch %d bottleneck=%s stall=%.1f%% (wait %.2fs over %.2fs"
            "%s); %s p95=%.1fms over %d events" % (
                epoch, verdict["bottleneck_stage"], verdict["stall_pct"],
                verdict["batch_wait_s"], verdict["wall_s"],
                f", {source}" if source else "",
                verdict["bottleneck_stage"], busiest.get("p95_ms", 0.0),
                busiest.get("count", 0)))
        if hold:
            with self._lock:
                self._held_lines[epoch] = line
        else:
            logger.info("%s", line)

    def release_line(self, epoch: Optional[int] = None,
                     suffix: str = "") -> None:
        """Log the held verdict line of ``epoch`` (of every epoch when
        ``None``) with ``suffix`` appended. An epoch whose line another
        caller already logged still gets its suffix, on a line of its
        own."""
        with self._lock:
            if epoch is None:
                lines = list(self._held_lines.values())
                self._held_lines.clear()
            else:
                line = self._held_lines.pop(epoch, None)
                if line is None and not suffix:
                    return
                lines = [line or f"epoch {epoch}"]
        for line in lines:
            logger.info("%s%s", line, suffix)


# ---------------------------------------------------------------------------
# Process-wide wiring (the runtime/faults.py fast-path pattern: the
# disabled case is one global load, no env lookup, no lock)
# ---------------------------------------------------------------------------

_ENABLED = True
_lock = threading.Lock()
_recorder: Optional[FlightRecorder] = None
_attribution: Optional[StageAttribution] = None
_events_counter_cache: Dict[str, metrics.Counter] = {}
_stage_hist_cache: Dict[str, metrics.Histogram] = {}
#: thread ident -> the spans open on that thread, innermost last (the
#: sampling profiler bills stack samples to the innermost one's kind; a
#: span's ``parent`` is the one below it). A stack, not a saved
#: "previous kind": a span held across a generator's ``yield`` can close
#: out of order. Plain dict and list operations are GIL-atomic; no lock
#: on the hot path.
_open_spans: Dict[int, List["_Span"]] = {}
#: ``jax.profiler.TraceAnnotation``, resolved on the first span opened
#: after ``jax`` was imported in this process (this module stays
#: stdlib-only at import, and a process that never imports jax never
#: pays for it).
_annotation_cls: Any = None
#: Lineage seed of the run this process participates in (trace.py's
#: deterministic trace/span ids derive from it). Stamped into dumps.
_trace_seed: Optional[int] = None
_exit_dump_registered = False


def _apply_enabled_locked() -> None:
    """Swap the public entry points between the real implementations and
    no-ops: the RSDL_TELEMETRY=0 hard-off fast path. Every caller uses
    module-attribute access (``rt_telemetry.record(...)``), so the swap
    takes effect process-wide; the disabled cost is one no-op call
    (:func:`measure_disabled_overhead`)."""
    g = globals()
    if _ENABLED:
        g["record"] = _record_impl
        g["span"] = _span_impl
        g["span_begin"] = _span_begin_impl
        g["span_end"] = _span_end_impl
        g["stamp"] = _stamp_impl
    else:
        g["record"] = _noop_record
        g["span"] = _noop_span
        g["span_begin"] = _noop_span_begin
        g["span_end"] = _noop_span_end
        g["stamp"] = _noop_stamp


def _register_exit_dump_locked() -> None:
    """With a trace dir configured (RSDL_TRACE_DIR), every process dumps
    its recorder there at interpreter exit — the per-process half of the
    multi-process merge contract (tools/rsdl_trace.py). The dir is
    re-resolved at fire time so a scene that unsets the env after its
    run leaves no stray dump."""
    global _exit_dump_registered
    if _exit_dump_registered:
        return
    _exit_dump_registered = True
    import atexit

    def _exit_dump() -> None:
        from ray_shuffling_data_loader_tpu.runtime import policy
        if not policy.resolve("telemetry", "trace_dir"):
            return
        try:
            dump(reason="atexit")
        except OSError:
            logger.exception("telemetry exit dump failed")

    atexit.register(_exit_dump)


def _init_locked() -> None:
    global _recorder, _attribution, _ENABLED
    if _recorder is not None:
        return
    from ray_shuffling_data_loader_tpu.runtime import policy
    _ENABLED = policy.resolve("telemetry", "telemetry")
    _recorder = FlightRecorder(
        capacity=int(policy.resolve("telemetry", "telemetry_capacity")))
    _attribution = StageAttribution(stall_threshold_pct=policy.resolve(
        "telemetry", "bottleneck_stall_threshold_pct"))
    _apply_enabled_locked()
    if policy.resolve("telemetry", "trace_dir"):
        _register_exit_dump_locked()


def recorder() -> FlightRecorder:
    """THE process-wide flight recorder."""
    with _lock:
        _init_locked()
        return _recorder


def attribution() -> StageAttribution:
    """THE process-wide bottleneck attributor."""
    with _lock:
        _init_locked()
        return _attribution


def enabled() -> bool:
    with _lock:
        _init_locked()
    return _ENABLED


def configure(enabled_flag: Optional[bool] = None,
              capacity: Optional[int] = None) -> None:
    """Reconfigure in place (tests): a fresh ring / attributor,
    resolving unset arguments from the policy registry."""
    global _ENABLED, _recorder, _attribution
    from ray_shuffling_data_loader_tpu.runtime import policy
    with _lock:
        _ENABLED = (policy.resolve("telemetry", "telemetry")
                    if enabled_flag is None else bool(enabled_flag))
        _recorder = FlightRecorder(capacity=int(
            policy.resolve("telemetry", "telemetry_capacity",
                           override=capacity)))
        _attribution = StageAttribution(stall_threshold_pct=policy.resolve(
            "telemetry", "bottleneck_stall_threshold_pct"))
        _apply_enabled_locked()
        if policy.resolve("telemetry", "trace_dir"):
            _register_exit_dump_locked()


def set_trace_seed(seed: int) -> None:
    """Declare the lineage seed this process's run derives from. The
    deterministic trace/span ids (runtime/trace.py) are functions of
    ``(seed, epoch, task)``; stamping the seed here puts it into every
    dump's meta so offline merges can re-derive the same ids the other
    processes used. Recorded once per distinct seed."""
    global _trace_seed
    if _trace_seed == seed:
        return
    _trace_seed = seed
    record("trace_meta", seed=seed)


def trace_seed() -> Optional[int]:
    return _trace_seed


#: Thread-local marker set while a SPECULATIVE backup attempt runs
#: (plan/scheduler.py first-completion-wins duplicates). Events recorded
#: under it carry a ``spec`` attr and skip the attribution/histogram
#: observation, so a duplicated attempt can never double-count a stage
#: in trace merge or bottleneck attribution — the original attempt owns
#: the canonical span for its lineage key.
_speculative = threading.local()


@contextlib.contextmanager
def speculative(attempt: int = 1) -> Iterator[None]:
    """Mark the enclosed work as a speculative duplicate attempt."""
    prev = getattr(_speculative, "attempt", 0)
    _speculative.attempt = attempt
    try:
        yield
    finally:
        _speculative.attempt = prev


def speculative_attempt() -> int:
    """The calling thread's active speculative attempt (0 = original)."""
    return getattr(_speculative, "attempt", 0)


def _record_impl(kind: str, epoch: Optional[int] = None,
                 task: Optional[int] = None, batch: Optional[int] = None,
                 dur_s: Optional[float] = None, t: Optional[float] = None,
                 tid: Optional[int] = None, **attrs: Any) -> None:
    """Record one structured event (free when telemetry is disabled).

    ``t`` is the event's END in ``time.monotonic()`` terms (defaults to
    now); events with ``dur_s`` therefore span ``[t - dur_s, t]``. The
    recording thread's ident (or ``tid``: the thread that opened a span
    another thread closed) rides along so multi-thread traces export
    with real tids (Perfetto pid/tid mapping).
    """
    if not _ENABLED:
        return
    rec = _recorder
    if rec is None:
        rec = recorder()
        if not _ENABLED:
            return
    spec = getattr(_speculative, "attempt", 0)
    if spec:
        attrs = {**attrs, "spec": spec}
    now = time.monotonic() if t is None else t
    rec.record((now, kind, epoch, task, batch, dur_s,
                threading.get_ident() if tid is None else tid,
                attrs or None))
    if spec:
        # Ring-only: the duplicate attempt is visible evidence (joined to
        # the original by its lineage key) but must not double-count the
        # stage in counters, histograms or bottleneck attribution.
        return
    events_counter = _events_counter_cache.get(kind)
    if events_counter is None:
        events_counter = _events_counter_cache[kind] = metrics.counter(
            "rsdl_events_total", "flight-recorder events by kind",
            kind=kind)
    events_counter.inc()
    if dur_s is None:
        return
    if kind == "batch_wait":
        attribution().observe_wait(epoch, dur_s, now)
        _batch_wait_hist().observe(dur_s)
        return
    stage = STAGE_BY_KIND.get(kind)
    if stage is None:
        return
    attribution().observe(stage, epoch, dur_s, now)
    hist = _stage_hist_cache.get(stage)
    if hist is None:
        hist = _stage_hist_cache[stage] = metrics.histogram(
            "rsdl_stage_seconds", "per-event stage latency", stage=stage)
    hist.observe(dur_s)


class _Span:
    """An open span: what :func:`span_begin` hands out and
    :func:`span_end` closes. Once closed it carries what was measured
    (``t1``, ``dur_s`` and, when asked for, ``cpu_s``) for a caller that
    sums spans into something larger (the feed's turnover split)."""

    __slots__ = ("kind", "epoch", "task", "batch", "attrs", "ident",
                 "bound", "annotation", "t0", "c0", "t1", "dur_s", "cpu_s")


def _open_annotation(kind: str, epoch: Optional[int], task: Optional[int],
                     batch: Optional[int]) -> Any:
    """The entered ``TraceAnnotation`` of a span of ``kind``, or ``None``
    where the kind has no fixed name or jax is not in the process."""
    global _annotation_cls
    name = SPAN_NAMES.get(kind)
    if name is None:
        return None
    cls = _annotation_cls
    if cls is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation as cls
        except ImportError:  # jax is still being imported on another thread
            return None
        _annotation_cls = cls
    args = {}
    if epoch is not None:
        args["epoch"] = epoch
    if task is not None:
        args["task"] = task
    if batch is not None:
        args["batch"] = batch
    annotation = cls(name, **args)
    annotation.__enter__()
    return annotation


def _span_begin_impl(kind: str, epoch: Optional[int] = None,
                     task: Optional[int] = None,
                     batch: Optional[int] = None, cpu: bool = False,
                     handoff: bool = False,
                     **attrs: Any) -> Optional[_Span]:
    """Open a span: THE way the program times a stage. One call lands the
    interval in both sinks on one clock: the flight recorder (an event of
    ``kind`` at the close) and, for the kinds of :data:`SPAN_NAMES`, the
    profiler's trace (an annotation under the kind's fixed name, held
    while the span is open). Returns the token for :func:`span_end`,
    which MUST run on all exit paths (``finally``); the
    ``span-unbalanced`` rsdl-lint rule enforces the shape.

    ``cpu`` also reads ``time.thread_time()`` at both ends (``cpu_s`` on
    the event): wall minus CPU inside a span that does no I/O is time
    spent waiting for the GIL or the scheduler. Where the kernel accounts
    a thread's CPU time by the tick (10 ms on the TPU v5e host's, PERF.md
    PR 24) one span's ``cpu_s`` is zero or a tick, and only a sum over
    many spans says anything. ``handoff`` opens a span
    that ANOTHER thread will close (a transfer that ends when the copy
    has landed): it is not published as this thread's open span and
    cannot carry ``cpu_s``."""
    if not _ENABLED:
        return None
    sp = _Span()
    sp.kind, sp.epoch, sp.task, sp.batch = kind, epoch, task, batch
    sp.ident = threading.get_ident()
    sp.bound = not handoff
    if sp.bound:
        stack = _open_spans.get(sp.ident)
        if stack is None:
            stack = _open_spans[sp.ident] = []
        elif stack:
            attrs["parent"] = stack[-1].kind
        stack.append(sp)
    sp.attrs = attrs
    sp.annotation = _open_annotation(kind, epoch, task, batch)
    sp.t0 = time.monotonic()
    # CPU reads nest inside the wall reads: cpu_s <= dur_s, up to the
    # CPU clock's own step.
    sp.c0 = time.thread_time() if (cpu and sp.bound) else None
    return sp


def _span_end_impl(sp: Optional[_Span], **late_attrs: Any) -> None:
    """Close a :func:`span_begin` token, recording the duration event.
    ``None`` tokens (telemetry disabled at begin time) are a no-op, so
    callers never need to guard."""
    if sp is None:
        return
    attrs = sp.attrs
    if sp.c0 is not None:
        sp.cpu_s = time.thread_time() - sp.c0
        attrs["cpu_s"] = sp.cpu_s
    else:
        sp.cpu_s = None
    sp.t1 = time.monotonic()
    sp.dur_s = sp.t1 - sp.t0
    if sp.annotation is not None:
        sp.annotation.__exit__(None, None, None)
    if sp.bound:
        stack = _open_spans.get(sp.ident)
        if stack:
            if stack[-1] is sp:
                stack.pop()
            elif sp in stack:
                stack.remove(sp)
            if not stack:
                _open_spans.pop(sp.ident, None)
    if late_attrs:
        attrs.update(late_attrs)
    record(sp.kind, epoch=sp.epoch, task=sp.task, batch=sp.batch,
           dur_s=sp.dur_s, t=sp.t1, tid=sp.ident, **attrs)


@contextlib.contextmanager
def _span_impl(kind: str, epoch: Optional[int] = None,
               task: Optional[int] = None, batch: Optional[int] = None,
               **attrs: Any) -> Iterator[Optional[_Span]]:
    """:func:`span_begin` / :func:`span_end` around the enclosed block
    (disabled: the overhead is the generator frame alone)."""
    sp = _span_begin_impl(kind, epoch, task, batch, **attrs)
    try:
        yield sp
    finally:
        _span_end_impl(sp)


def active_kinds() -> Dict[int, str]:
    """Snapshot of thread ident -> innermost open span kind."""
    out = {}
    for ident, stack in list(_open_spans.items()):
        try:
            out[ident] = stack[-1].kind
        except IndexError:  # closed between the two reads
            continue
    return out


def observe_batch_wait(epoch: Optional[int] = None,
                       dur_s: float = 0.0) -> None:
    """Count a batch the consumer did not have to wait for (a later batch
    of a bulk chunk that is already on the device) in the wait histogram
    and the epoch's verdict. No ring event: nothing was timed."""
    if not _ENABLED:
        return
    attribution().observe_wait(epoch, dur_s, time.monotonic())
    _batch_wait_hist().observe(dur_s)


def _batch_wait_hist() -> metrics.Histogram:
    hist = _stage_hist_cache.get("batch_wait")
    if hist is None:
        hist = _stage_hist_cache["batch_wait"] = metrics.histogram(
            "rsdl_batch_wait_seconds",
            "consumer time blocked waiting on the next batch")
    return hist


def observe_stage(kind: str, epoch: Optional[int] = None,
                  task: Optional[int] = None, dur_s: float = 0.0) -> None:
    """Feed the bottleneck attribution + stage histograms with a duration
    measured in ANOTHER process.

    The process-pool workers (procpool.py) record the real ``map_read`` /
    ``reduce_gather`` events in their own flight recorders (dumped via
    ``RSDL_TRACE_DIR``); re-recording them in the driver's ring would
    double-count the spans when the per-process dumps are merged
    (tools/rsdl_trace.py). This entry point updates only the driver-side
    attribution state and latency histograms — no ring event.
    """
    if not _ENABLED:
        return
    stage = STAGE_BY_KIND.get(kind)
    if stage is None:
        return
    attribution().observe(stage, epoch, dur_s, time.monotonic())
    hist = _stage_hist_cache.get(stage)
    if hist is None:
        hist = _stage_hist_cache[stage] = metrics.histogram(
            "rsdl_stage_seconds", "per-event stage latency", stage=stage)
    hist.observe(dur_s)


# -- RSDL_TELEMETRY=0 hard-off fast path: the public names rebind to
# these no-ops (one call frame, no env lookup, no branch chain).

def _noop_record(kind: str, *args: Any, **kwargs: Any) -> None:
    return None


_NULL_SPAN = contextlib.nullcontext()


def _noop_span(kind: str, *args: Any, **kwargs: Any):
    return _NULL_SPAN


def _noop_span_begin(*args: Any, **kwargs: Any) -> None:
    return None


def _noop_span_end(token: Any = None, **kwargs: Any) -> None:
    return None


def _stamp_impl() -> float:
    """Clock read for hot-path duration measurement (``time.monotonic``).

    PRs 4-6 put two clock reads on every queue put/get and wire frame —
    true per-item fast paths. Under the hard-off rebind this name becomes
    a constant-return no-op, so RSDL_TELEMETRY=0 strips the clock reads
    along with the record calls (the r03->r05 hot-path audit, ISSUE 7):
    ``start = stamp(); ...; record(kind, dur_s=stamp() - start)`` costs
    two no-op calls when telemetry is off.
    """
    return time.monotonic()


def _noop_stamp() -> float:
    return 0.0


# Public entry points (swapped by _apply_enabled_locked when policy
# resolves telemetry off).
record = _record_impl
span = _span_impl
span_begin = _span_begin_impl
span_end = _span_end_impl
stamp = _stamp_impl


def _update_trace_gauges(epoch: int) -> None:
    """Per-epoch critical-path exposition (tools/rsdl_top.py's
    critical-path line): run the trace analyzer over the recorder's
    retained events for this epoch and publish per-stage critical-path
    seconds plus the top straggler. Best-effort — exposition must never
    take down the pipeline."""
    try:
        from ray_shuffling_data_loader_tpu.runtime import trace as rt_trace
        analysis = rt_trace.analyze(recorder().events(), epoch=epoch)
        for entry in analysis["critical_path"]:
            metrics.gauge(
                "rsdl_trace_cp_seconds",
                "critical-path seconds attributed to the stage "
                "(latest analyzed epoch)",
                stage=entry["stage"]).set(entry["cp_ms"] / 1e3)
        stragglers = [s for s in analysis["stragglers"]
                      if s["cp_ms"] > 0 and s["task"] is not None]
        if stragglers:
            top = stragglers[0]
            metrics.gauge(
                "rsdl_trace_straggler_task",
                "task id of the current critical-path straggler",
                stage=top["stage"]).set(float(top["task"]))
            metrics.gauge(
                "rsdl_trace_straggler_seconds",
                "critical-path seconds of the current straggler task",
                stage=top["stage"]).set(top["cp_ms"] / 1e3)
    except Exception:  # noqa: BLE001 - observability stays best-effort
        logger.exception("trace gauge update failed (epoch %d)", epoch)


def epoch_complete(epoch: int, source: str = "",
                   hold_log: bool = False) -> None:
    """Epoch-end hook for dataset layers: logs the one-line verdict and
    refreshes the critical-path exposition gauges, each under a span of
    its own (both run on the caller's thread, the consumer's in the JAX
    binding). ``hold_log`` keeps the line for :func:`turnover_complete`
    to log with the turnover's split."""
    if not _ENABLED:
        return
    with span("epoch_verdict", epoch=epoch):
        attribution().epoch_complete(epoch, source=source, hold=hold_log)
    with span("trace_gauges", epoch=epoch):
        _update_trace_gauges(epoch)


def turnover_complete(epoch: int, total_s: float,
                      parts: Dict[str, float]) -> None:
    """The turnover out of ``epoch`` is over: the consumer asked for a
    batch, got the epoch's end instead, and now holds the next epoch's
    first batch. ``parts`` are the seconds of ``total_s`` spent in the
    feed's own spans (``epoch_end``, ``set_epoch``, ``first_get``, ...);
    the rest is the caller's own work between them (``other``). One
    ``epoch_turnover`` event, one histogram sample, and the epoch's held
    log line with the split appended."""
    if not _ENABLED:
        return
    other_s = total_s - sum(parts.values())
    record("epoch_turnover", epoch=epoch, dur_s=total_s, other_s=other_s,
           **{f"{name}_s": value for name, value in parts.items()})
    hist = _stage_hist_cache.get("epoch_turnover")
    if hist is None:
        hist = _stage_hist_cache["epoch_turnover"] = metrics.histogram(
            "rsdl_epoch_turnover_seconds",
            "consumer stall from one epoch's end to the next epoch's "
            "first batch")
    hist.observe(total_s)
    split = ", ".join(f"{name} {value * 1e3:.1f}"
                      for name, value in parts.items())
    attribution().release_line(
        epoch, suffix=f"; turnover={total_s * 1e3:.1f}ms ({split}, "
                      f"other {other_s * 1e3:.1f})")


def step_stats_folded(step: int,
                      stats: Dict[str, List[Dict[str, Any]]]) -> None:
    """Train step ``step``'s own counters have reached the host
    (``utils/tracing.fold_step_stats``): ``stats`` maps a stat's name
    (:data:`STEP_STAT_FIELDS`) to one row a label set, labels and fields
    together. One ``step_stats`` event with the step's number, and the
    registry: ``moe_walk`` adds each layer's pairs, held pairs, tiles and
    rounds to their counters, sets the layer's fullest-expert gauge, and
    samples the step's tiles, all layers summed, into
    ``rsdl_moe_tiles_per_step`` (and the gauge of the last step's);
    ``ssm_scan`` sets each layer's two gauges, ``diff_attention`` each
    layer's one, ``lm_noise`` the step's two."""
    if not _ENABLED:
        return
    record("step_stats", step=step, stats=stats)
    metrics.counter("rsdl_step_stats_folded_total",
                    "train steps whose own counters reached the host").inc()
    for row in stats.get("lm_noise", ()):
        metrics.gauge("rsdl_lm_noise_masked_positions",
                      "positions block diffusion's noise masked, the "
                      "batch's rows summed, last folded step"
                      ).set(row["masked"])
        metrics.gauge("rsdl_lm_noise_weight_sum",
                      "sum over the masked positions of their loss "
                      "weights, one over a block's masking probability "
                      "(its expectation is the batch's tokens), last "
                      "folded step").set(row["weight_sum"])
    for row in stats.get("diff_attention", ()):
        metrics.gauge("rsdl_lm_diff_lambda",
                      "the scalar a differential attention layer subtracts "
                      "its second softmax map under, last folded step",
                      layer=row.get("layer", "")).set(row["lambda"])
    for row in stats.get("ssm_scan", ()):
        layer = row.get("layer", "")
        metrics.gauge("rsdl_ssm_end_decay_mean",
                      "share of the state a chunk of the state-space scan "
                      "starts from that reaches its end, mean over rows, "
                      "chunks and heads, last folded step",
                      layer=layer).set(row["end_decay_mean"])
        metrics.gauge("rsdl_ssm_carry_abs_max",
                      "largest value of the state handed from one chunk of "
                      "the state-space scan to the next, last folded step",
                      layer=layer).set(row["carry_abs_max"])
    walks = stats.get("moe_walk")
    if not walks:
        return
    for row in walks:
        layer = row.get("layer", "")
        metrics.counter("rsdl_moe_pairs_total",
                        "(token, pick) pairs routed by the sparse-expert "
                        "layers, every folded step").inc(row["pairs"])
        metrics.counter("rsdl_moe_pairs_held_total",
                        "pairs whose expert this chip holds",
                        layer=layer).inc(row["pairs_held"])
        metrics.counter("rsdl_moe_tiles_total",
                        "tiles the expert walk took", layer=layer
                        ).inc(row["tiles"])
        metrics.counter("rsdl_moe_rounds_total",
                        "rounds (buffers of tiles) the expert walk took",
                        layer=layer).inc(row["rounds"])
        metrics.gauge("rsdl_moe_fullest_expert_rows",
                      "pairs of the fullest held expert, last folded step",
                      layer=layer).set(row["fullest_expert_rows"])
    tiles = sum(row["tiles"] for row in walks)
    metrics.histogram("rsdl_moe_tiles_per_step",
                      "tiles the expert walk took in one step, all sparse "
                      "layers summed", buckets=_TILES_PER_STEP_BUCKETS
                      ).observe(tiles)
    metrics.gauge("rsdl_moe_tiles_last_step",
                  "tiles the expert walk took, all sparse layers summed, "
                  "last folded step").set(tiles)


def flush_epoch_log() -> None:
    """Log every held epoch line as it stands (a dataset that is closed
    between an epoch's end and the next epoch's first batch)."""
    if not _ENABLED:
        return
    attribution().release_line()


# ---------------------------------------------------------------------------
# Dumps: JSONL events + named-thread stacks (on demand / watchdog / SIGUSR1)
# ---------------------------------------------------------------------------


def _thread_stacks() -> List[Dict[str, Any]]:
    frames = sys._current_frames()
    by_ident = {t.ident: t for t in threading.enumerate()}
    out = []
    for ident, frame in frames.items():
        thread = by_ident.get(ident)
        buf = io.StringIO()
        traceback.print_stack(frame, file=buf)
        out.append({
            "kind": "thread_stack",
            "thread": thread.name if thread else f"ident-{ident}",
            "ident": ident,
            "daemon": bool(thread.daemon) if thread else None,
            "stack": buf.getvalue().rstrip().splitlines(),
        })
    return out


_dump_seq = 0


def dump(path: Optional[str] = None, reason: str = "on-demand") -> str:
    """Write the flight recorder + thread stacks as JSONL; returns the
    path. Default location: ``telemetry_dump_dir`` policy key
    (``RSDL_TELEMETRY_DUMP_DIR``), else the system temp dir."""
    global _dump_seq
    if path is None:
        from ray_shuffling_data_loader_tpu.runtime import policy
        import tempfile
        directory = (policy.resolve("telemetry", "trace_dir")
                     or policy.resolve("telemetry", "telemetry_dump_dir")
                     or tempfile.gettempdir())
        os.makedirs(directory, exist_ok=True)
        with _lock:
            _dump_seq += 1
            seq = _dump_seq
        path = os.path.join(
            directory, f"rsdl-telemetry-{os.getpid()}-{seq}.jsonl")
    rec = recorder()
    with open(path, "w", encoding="utf-8") as f:
        # time.time() here is a SERIALIZED timestamp (never used in
        # interval math): it anchors t_mono offsets to wall clock for
        # whoever reads the dump — the cross-process clock alignment
        # runtime/trace.py merges on.
        f.write(json.dumps({
            "kind": "dump_meta", "reason": reason, "pid": os.getpid(),
            "time_unix": time.time(), "t_mono": time.monotonic(),
            "events_total": rec.total_recorded,
            "events_retained": min(rec.total_recorded, rec.capacity),
            "trace_seed": _trace_seed,
            "role": os.path.basename(sys.argv[0]) or "python",
        }) + "\n")
        for event in rec.events():
            f.write(json.dumps(event) + "\n")
        for stack in _thread_stacks():
            f.write(json.dumps(stack) + "\n")
    logger.warning("telemetry dump (%s): %s", reason, path)
    return path


def install_signal_dump(signum: int = signal.SIGUSR1) -> bool:
    """Install a SIGUSR1 (by default) handler that writes a flight
    recorder dump. Returns False (no-op) off the main thread or on
    platforms without the signal — callers need not guard."""
    if threading.current_thread() is not threading.main_thread():
        return False

    def _handler(_signum, _frame):
        try:
            dump(reason=f"signal {_signum}")
        except OSError:
            logger.exception("telemetry signal dump failed")

    try:
        signal.signal(signum, _handler)
    except (ValueError, OSError, AttributeError):
        return False
    return True


# ---------------------------------------------------------------------------
# Overhead self-measurement (the recorder's own self-test)
# ---------------------------------------------------------------------------


def measure_record_overhead(samples: int = 2000) -> float:
    """Seconds per ENABLED ``record()`` call, measured against throwaway
    doubles of everything the real path touches — ring, events counter,
    stage histogram, attribution observe — so the number is the full
    per-event cost, not just the ring append (the live recorder is not
    polluted). Times the events recorded in a window it gives that
    window's telemetry overhead."""
    probe = FlightRecorder(capacity=256)
    probe_counter = metrics.Counter()
    probe_attr = StageAttribution()
    probe_hist = metrics.Histogram()
    start = time.perf_counter()
    for i in range(samples):
        now = time.monotonic()
        probe.record((now, "probe", 0, i, None, 1e-6,
                      threading.get_ident(), None))
        probe_counter.inc()
        probe_attr.observe("map_read", 0, 1e-6, now)
        probe_hist.observe(1e-6)
    elapsed = time.perf_counter() - start
    return elapsed / samples


def measure_disabled_overhead(samples: int = 2000) -> float:
    """Seconds per call of the RSDL_TELEMETRY=0 hard-off fast path (the
    no-op ``record`` the public name rebinds to): the proof the off
    switch is ~free."""
    start = time.perf_counter()
    for i in range(samples):
        _noop_record("probe", epoch=0, task=i, dur_s=1e-6)
    elapsed = time.perf_counter() - start
    return elapsed / samples


# Honor env-driven SIGUSR1 installation at import: RSDL_TELEMETRY_SIGUSR1=1
# makes any driver dumpable with `kill -USR1 <pid>`, zero code.
if os.environ.get("RSDL_TELEMETRY_SIGUSR1", "").strip().lower() in (
        "1", "true", "yes", "on"):
    install_signal_dump()
