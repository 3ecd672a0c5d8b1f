"""Per-layer metrics read from the program's own instrumentation: its
spans in the profiler's trace (``rsdl.<layer>.<what>``, the fixed list of
``runtime/telemetry.SPAN_NAMES``) and its metrics registry.

The harness keeps only its own ``chipbench.*`` spans when it loads the
trace, so this module loads the program's itself, once a run. A program
that lacks a span or a counter (a commit from before they were added)
gives a reader nothing to read: it returns ``None`` and the harness leaves
the metric out. A share over 100 % is an error here, before anything is
printed.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from chipbench import xplane

Span = Tuple[str, float, float]

PROGRAM_PREFIX = "rsdl."
FEED_PREFIX = "rsdl.feed."
_CACHE_KEY = "_program_trace"


def _share(name: str, value: float) -> float:
    if value > 100.0:
        raise ValueError(f"{name} reads {value:.3f} %: the spans cover more "
                         "than the window they were clipped to")
    return value


def program_trace(facts: Dict[str, Any]) -> Optional[xplane.Trace]:
    """The run's trace with the program's spans beside the harness's
    (loaded once and kept in ``facts``), or ``None`` in a run that traced
    nothing."""
    if facts.get(_CACHE_KEY) is None:
        path = facts.get("trace_path")
        if not path or facts.get("trace_window") is None:
            return None
        facts[_CACHE_KEY] = xplane.load(
            path, span_prefixes=(PROGRAM_PREFIX, "chipbench."))
    return facts[_CACHE_KEY]


def span_share_pct(spans: Sequence[Span], name: str,
                   window: xplane.Interval) -> Optional[float]:
    """Share of ``window`` covered by the spans called ``name`` (their
    union, so spans of two threads are not counted twice); ``None`` where
    the trace holds no such span at all."""
    named = [(start, end) for n, start, end in spans if n == name]
    if not named:
        return None
    inside = xplane.total(xplane.union(xplane.clip(named, window)))
    return _share(name, 100.0 * inside / (window[1] - window[0]))


def span_median_ms(spans: Sequence[Span], name: str,
                   window: xplane.Interval) -> Optional[float]:
    """Median length of the spans called ``name`` that lie wholly inside
    ``window``; ``None`` where none does."""
    inside = [end - start for n, start, end in spans
              if n == name and start >= window[0] and end <= window[1]]
    return 1e3 * statistics.median(inside) if inside else None


def idle_by_span(trace: xplane.Trace, window: xplane.Interval
                 ) -> Dict[str, float]:
    """The first chip's idle seconds in ``window`` by the innermost host
    span, the harness's and the program's together."""
    if not trace.ops:
        return {}
    first = min(trace.ops)
    idle = xplane.gaps(((op.start, op.end) for op in trace.ops[first]),
                       window)
    return xplane.attribute_gaps(idle, trace.spans)


def idle_under_pct(by_span: Dict[str, float], prefix: str,
                   window: xplane.Interval) -> float:
    """Idle seconds whose innermost span starts with ``prefix``, as a
    share of ``window``."""
    under = sum(s for name, s in by_span.items() if name.startswith(prefix))
    return _share("idle under " + prefix,
                  100.0 * under / (window[1] - window[0]))


def histogram_totals(since: Optional[Dict[str, Tuple[int, float]]] = None
                     ) -> Dict[str, Tuple[int, float]]:
    """(count, sum) of each unlabelled histogram the program's catalog
    (``runtime/metric_names``) names and its registry holds by now, less
    what an earlier reading ``since`` held (the registry is the process's:
    the tests rehearse several runs in one). The loop takes it when the
    consumer has met an epoch's end, so that a reader can tell one epoch's
    sample from the next's; empty for a program without the catalog."""
    try:
        from ray_shuffling_data_loader_tpu.runtime import metric_names, metrics
    except ImportError:
        return {}
    totals: Dict[str, Tuple[int, float]] = {}
    for name, (kind, labels) in metric_names.METRIC_NAMES.items():
        if kind != "histogram" or labels:
            continue
        held = metrics.get(name)
        if held is not None:
            count, total = (since or {}).get(name, (0, 0.0))
            totals[name] = (int(held.count) - count, float(held.sum) - total)
    return totals


def counter_offcpu_pct(wall_s: Optional[float], cpu_s: Optional[float]
                       ) -> Optional[float]:
    """100 x (1 - cpu / wall); ``None`` where nothing was counted."""
    if not wall_s or cpu_s is None:
        return None
    return _share("off-CPU share", 100.0 * max(0.0, 1.0 - cpu_s / wall_s))


# -- the readers ---------------------------------------------------------------

def span_pct(facts: Dict[str, Any], span: str) -> Optional[float]:
    """Share of the traced window inside the program's span ``span``
    (``rsdl.feed.carve``, ``rsdl.feed.queue_get``: both are the consumer's
    thread alone)."""
    trace = program_trace(facts)
    if trace is None:
        return None
    return span_share_pct(trace.spans, span, facts["trace_window"])


def span_ms(facts: Dict[str, Any], span: str) -> Optional[float]:
    """Median of the program's span ``span`` in the traced window
    (``rsdl.feed.transfer``: from the dispatch to the landed copy)."""
    trace = program_trace(facts)
    if trace is None:
        return None
    return span_median_ms(trace.spans, span, facts["trace_window"])


def feed_offcpu_pct(facts: Dict[str, Any], wall: str, cpu: str
                    ) -> Optional[float]:
    """Share of the consumer thread's time inside the feed's own spans
    (carve, ``set_epoch``, the epoch's end; not the wait on the queue) in
    which it was off the CPU: waiting for the GIL or the scheduler. From
    the program's two registry counters, since the process started."""
    try:
        from ray_shuffling_data_loader_tpu.runtime import metrics
    except ImportError:
        return None
    values: List[Optional[float]] = []
    for name in (wall, cpu):
        counter = metrics.get(name)
        values.append(None if counter is None else float(counter.value))
    return counter_offcpu_pct(*values)


def first_turnover_ms(facts: Dict[str, Any], histogram: str
                      ) -> Optional[float]:
    """What the consumer's first epoch end cost (the end met in
    ``next()`` to the next epoch's first batch: the program's
    ``epoch_turnover`` event, sampled into ``histogram``): the histogram's
    first sample, from the totals the loop took at each epoch end
    (``histogram_totals``). The first end at which it holds a sample has
    to hold exactly one; ``None`` where no end has been met or the program
    never sampled it. A process pays it once; where the traffic opens the
    window after it, it is part of ``setup_s``."""
    for end in facts.get("epoch_ends", ()):
        count, total = end["program"].get(histogram, (0, 0.0))
        if count:
            return 1e3 * total if count == 1 else None
    return None


def idle_under_feed_pct(facts: Dict[str, Any]) -> Optional[float]:
    """Device-idle seconds of the traced window whose innermost host span
    is one of the device feed's (``rsdl.feed.*``), over the window.
    Earlier lines give the whole table of idle gaps by span."""
    trace = program_trace(facts)
    if trace is None or not trace.ops:
        return None
    window = facts["trace_window"]
    by_span = idle_by_span(trace, window)
    for name, seconds in sorted(by_span.items(), key=lambda kv: -kv[1]):
        print(f"# idle gap under {name}: {seconds * 1e3:.4f} ms", flush=True)
    if not any(n.startswith(PROGRAM_PREFIX) for n, _, _ in trace.spans):
        return None
    return idle_under_pct(by_span, FEED_PREFIX, window)
