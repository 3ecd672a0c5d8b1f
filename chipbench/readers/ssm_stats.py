"""Per-layer metrics read from the state-space scan's own statistics: what
the device computed in each Mamba layer's chunked scan at run time and the
program carried out of its jitted step (``utils/tracing.step_stat``'s
``ssm_scan``, from ``ops/ssd.py``): the mean share of a chunk's starting
state that reaches the chunk's end, and the largest value of the state
handed from one chunk to the next.

The join with the traced window is ``readers/step_stats.py``'s: the
``step_num`` of the ``rsdl.trainer.step`` annotations that start inside
the window, asked of the program's ring. A program without the channel or
without the statistic (a commit from before either, a model with no such
layer) gives the reader nothing to read: it returns ``None`` and the
harness leaves the metric out.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Sequence

from chipbench.readers import step_stats

SCAN = "ssm_scan"
_CACHE_KEY = "_ssm_stats_window"

Entry = Dict[str, Any]


def scans_of(ring: Optional[Any], steps: Sequence[int]
             ) -> Optional[List[Entry]]:
    """The ring's folded entries of ``steps`` that hold a scan's
    statistics, oldest first, after folding what still waits for the
    device; ``None`` without a ring, without steps, or where no such step
    recorded a scan."""
    if ring is None or not steps:
        return None
    ring.fold_step_stats(wait=True)
    wanted = set(steps)
    entries = [e for e in ring.step_stats(min(steps), max(steps))
               if e["step"] in wanted and e["stats"].get(SCAN)]
    return entries or None


def carry_pct(entries: Sequence[Entry]) -> float:
    """100 x the mean over ``entries``' steps and their Mamba layers of a
    chunk's whole decay: of the state a chunk starts from, the share that
    crosses to the next."""
    return 100.0 * statistics.fmean(
        row["end_decay_mean"] for e in entries for row in e["stats"][SCAN])


def series_lines(entries: Sequence[Entry], window_steps: Sequence[int]
                 ) -> List[str]:
    """One ``# step stats`` line a step: each Mamba layer's share of state
    crossing a chunk's end and the largest carried value over the layers;
    a ``*`` marks a step of the traced window."""
    inside = set(window_steps)
    lines = []
    for e in entries:
        rows = e["stats"][SCAN]
        lines.append(
            f"# step stats {e['step']}{'*' if e['step'] in inside else ''}: "
            "scan crossing "
            + "/".join(f"{100 * row['end_decay_mean']:.3f}" for row in rows)
            + " % (layers " + "/".join(str(row["layer"]) for row in rows)
            + f"), largest carry "
            f"{max(row['carry_abs_max'] for row in rows):.6g}; fold "
            f"{e['fold_s'] * 1e3:.3f} ms")
    return lines


def _window_scans(facts: Dict[str, Any]) -> Optional[List[Entry]]:
    """The traced window's steps' entries (found once a run and kept in
    ``facts``); the first call prints the series of every step the ring
    still holds."""
    if _CACHE_KEY not in facts:
        ring, scans = step_stats.channel(), None
        path, window = facts.get("trace_path"), facts.get("trace_window")
        if ring is not None and path and window is not None:
            steps = step_stats.annotated_steps(path, window)
            scans = scans_of(ring, steps)
            if scans:
                kept = [e for e in ring.step_stats() if e["stats"].get(SCAN)]
                for line in series_lines(kept, steps):
                    print(line, flush=True)
                print(f"# step stats: {len(scans)} steps of the traced "
                      f"window ({scans[0]['step']}-{scans[-1]['step']}) of "
                      f"{len(kept)} in the ring hold a scan's statistics",
                      flush=True)
        facts[_CACHE_KEY] = scans
    return facts[_CACHE_KEY]


def ssm_carry_pct(facts: Dict[str, Any]) -> Optional[float]:
    """Of the state a chunk of the scan starts from, the share that
    reaches the chunk's end, in per cent: the mean over the traced
    window's steps, the Mamba layers, rows, chunks and heads. What a
    kernel that dropped the carry would lose; 0 where every head forgets
    within a chunk."""
    scans = _window_scans(facts)
    return None if scans is None else carry_pct(scans)
