"""Per-layer metrics read from the train step's own counters: what the
device computed at run time and the program carried out of its jitted step
(``utils/tracing.step_stat``; the expert walk's held pairs, tiles and
rounds a sparse layer, ``ops/moe.py``).

The join with the traced window is the step's number. The program's
``rsdl.trainer.step`` annotation carries ``step_num`` into the profiler's
trace, and its ring of folded counters (``tracing.step_stats``) is keyed by
the same number: this module takes the numbers of the annotation's
instances that start inside the traced window (``chipbench/xplane.py``
keeps no event arguments, so it reads the file itself) and asks the ring
for those steps. An annotation is around the step's dispatch, which runs
``run_ahead_steps`` ahead of the completions the window is cut at, so the
steps read are the window's own shifted by that many: the same count of
steps, of the same routing regime.

A program without the channel (a commit from before it) gives a reader
nothing to read: it returns ``None`` and the harness leaves the metric
out.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Sequence

from chipbench import xplane

STEP_ANNOTATION = "rsdl.trainer.step"
WALK = "moe_walk"
_CACHE_KEY = "_step_stats_window"

Entry = Dict[str, Any]


def channel() -> Optional[Any]:
    """The program's module that holds the ring, or ``None`` for a program
    that has none."""
    try:
        from ray_shuffling_data_loader_tpu.utils import tracing
    except ImportError:
        return None
    return tracing if hasattr(tracing, "step_stats") else None


def annotated_steps(path: str, window: xplane.Interval) -> List[int]:
    """``step_num`` of each ``rsdl.trainer.step`` annotation of the trace
    at ``path`` that starts inside ``window``, in order."""
    from jax.profiler import ProfileData
    steps = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != STEP_ANNOTATION:
                    continue
                if window[0] <= ev.start_ns * 1e-9 <= window[1]:
                    number = dict(ev.stats).get("step_num")
                    if number is not None:
                        steps.append(int(number))
    return sorted(steps)


def walks_of(ring: Optional[Any], steps: Sequence[int]
             ) -> Optional[List[Entry]]:
    """The ring's folded entries of ``steps`` that hold an expert walk's
    counters, oldest first, after folding what still waits for the
    device; ``None`` without a ring, without steps, or where no such step
    recorded a walk (a model with no sparse layer)."""
    if ring is None or not steps:
        return None
    ring.fold_step_stats(wait=True)
    wanted = set(steps)
    entries = [e for e in ring.step_stats(min(steps), max(steps))
               if e["step"] in wanted and e["stats"].get(WALK)]
    return entries or None


def _step_tiles(entry: Entry) -> int:
    return sum(row["tiles"] for row in entry["stats"][WALK])


def held_pairs_pct(entries: Sequence[Entry]) -> float:
    """Pairs whose expert the chip holds over all (token, pick) pairs of
    every sparse layer, over ``entries``' steps."""
    rows = [row for e in entries for row in e["stats"][WALK]]
    return (100.0 * sum(row["pairs_held"] for row in rows)
            / sum(row["pairs"] for row in rows))


def tiles_per_step(entries: Sequence[Entry]) -> float:
    """Mean tiles walked a step, all sparse layers summed."""
    return statistics.fmean(_step_tiles(e) for e in entries)


def tiles_drift_pct(entries: Sequence[Entry]) -> float:
    """100 x (mean tiles a step of the last quarter of ``entries`` over
    the first quarter's, less one): whether the steps mixed regimes."""
    quarter = max(1, len(entries) // 4)
    first = tiles_per_step(entries[:quarter])
    return 100.0 * (tiles_per_step(entries[-quarter:]) / first - 1.0)


def series_lines(entries: Sequence[Entry], window_steps: Sequence[int]
                 ) -> List[str]:
    """One ``# step stats`` line a step: held pairs and tiles by layer
    (then the step's tiles summed), rounds, the fullest expert's rows over
    the layers, and what the fold cost the host; a ``*`` marks a step of
    the traced window."""
    inside = set(window_steps)
    lines = []
    for e in entries:
        rows = e["stats"][WALK]

        def by_layer(field: str) -> str:
            return "/".join(str(row[field]) for row in rows)

        lines.append(
            f"# step stats {e['step']}{'*' if e['step'] in inside else ''}: "
            f"held {by_layer('pairs_held')} of {rows[0]['pairs']} pairs a "
            f"layer (layers {by_layer('layer')}), tiles {by_layer('tiles')} "
            f"({_step_tiles(e)}), rounds {by_layer('rounds')}, fullest "
            f"expert {max(row['fullest_expert_rows'] for row in rows)} "
            f"rows; fold {e['fold_s'] * 1e3:.3f} ms")
    return lines


def _window_walks(facts: Dict[str, Any]) -> Optional[List[Entry]]:
    """The traced window's steps' entries (found once a run and kept in
    ``facts``); the first call prints the series of every step the ring
    still holds."""
    if _CACHE_KEY not in facts:
        ring, walks = channel(), None
        path, window = facts.get("trace_path"), facts.get("trace_window")
        if ring is not None and path and window is not None:
            steps = annotated_steps(path, window)
            walks = walks_of(ring, steps)
            if walks:
                kept = [e for e in ring.step_stats()
                        if e["stats"].get(WALK)]
                for line in series_lines(kept, steps):
                    print(line, flush=True)
                fold_ms = [1e3 * e["fold_s"] for e in walks]
                print(f"# step stats: {len(walks)} steps of the traced "
                      f"window ({walks[0]['step']}-{walks[-1]['step']}) of "
                      f"{len(kept)} in the ring; the fold took "
                      f"{statistics.fmean(fold_ms):.3f} ms a step on the "
                      f"host (max {max(fold_ms):.3f})", flush=True)
        facts[_CACHE_KEY] = walks
    return facts[_CACHE_KEY]


# -- the readers -------------------------------------------------------------

def moe_held_pairs_pct(facts: Dict[str, Any]) -> Optional[float]:
    """Share of the sparse layers' (token, pick) pairs whose expert this
    chip holds, over the traced window's steps: the held share of the
    router's experts under an even routing, more once the router has
    moved the picks onto the held ones."""
    walks = _window_walks(facts)
    return None if walks is None else held_pairs_pct(walks)


def moe_tiles_per_step(facts: Dict[str, Any]) -> Optional[float]:
    """Mean tiles the expert walk took a step, all sparse layers summed,
    over the traced window's steps."""
    walks = _window_walks(facts)
    return None if walks is None else tiles_per_step(walks)


def moe_tiles_drift_pct(facts: Dict[str, Any]) -> Optional[float]:
    """How far the walk grew inside the traced window: the last quarter
    of its steps against the first, in per cent of tiles a step."""
    walks = _window_walks(facts)
    return None if walks is None else tiles_drift_pct(walks)
