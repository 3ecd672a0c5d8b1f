"""What block diffusion's mask lets through against what the attention
kernels walk to cover it, with the noise's own statistics printed beside
it.

The number is the program's own count, set in its registry when a step is
traced (``models/mellum.py:_count_diffusion_tiles``: a head of a layer,
forward and backward): the query-key pairs the mask lets through over the
pairs in the tiles the kernels visit. It depends on the tiles the program
chose and on nothing a run does. What each step's noise did (how many
positions it masked, and what their loss weights summed to) rides out of
the jitted step as its ``lm_noise`` (``utils/tracing.step_stat``) and is
printed on ``# step stats`` lines and summed on a ``# noise:`` line; the
join with the traced window is ``readers/step_stats.py``'s. A program
without the gauges (a commit from before them, a model that does not train
by diffusion, a run whose attention XLA computed inline) gives the reader
nothing to read: it returns ``None`` and the harness leaves the metric
out.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Sequence

from chipbench.readers import step_stats

NOISE = "lm_noise"
LIVE_PAIRS = "rsdl_lm_attention_live_pairs"
TILE_PAIRS = "rsdl_lm_attention_tile_pairs"
TILES_VISITED = "rsdl_lm_attention_tiles_visited"
TILES_COMPARED = "rsdl_lm_attention_tiles_compared"
DIRECTIONS = ("forward", "backward")
_PRINTED_KEY = "_diffusion_noise_printed"

Entry = Dict[str, Any]


def _gauges(name: str) -> Optional[List[float]]:
    """The program's gauge ``name`` by direction, or ``None`` where it
    has none."""
    try:
        from ray_shuffling_data_loader_tpu.runtime import metrics
    except ImportError:
        return None
    found = [metrics.get(name, {"direction": d}) for d in DIRECTIONS]
    if any(gauge is None for gauge in found):
        return None
    return [float(gauge.value) for gauge in found]


def live_pct(live: Sequence[float], walked: Sequence[float]) -> float:
    """100 x the pairs the mask lets through over the pairs in the tiles
    visited, forward and backward together."""
    return 100.0 * sum(live) / sum(walked)


def series_lines(entries: Sequence[Entry], window_steps: Sequence[int],
                 positions: int) -> List[str]:
    """One ``# step stats`` line a step that recorded its noise: the
    masked positions, their share of the batch's ``positions`` tokens, and
    the sum of their weights over those tokens (1 in expectation); a ``*``
    marks a step of the traced window."""
    inside = set(window_steps)
    lines = []
    for e in entries:
        for row in e["stats"].get(NOISE, ()):
            lines.append(
                f"# step stats {e['step']}"
                f"{'*' if e['step'] in inside else ''}: noise masked "
                f"{row['masked']:.0f} of {positions} tokens "
                f"({100.0 * row['masked'] / positions:.3f} %), weights "
                f"sum to {row['weight_sum'] / positions:.4f} a token")
    return lines


def _print_noise(facts: Dict[str, Any]) -> None:
    """Once a run: the series of every step the ring still holds, and the
    traced window's mean masked share."""
    if facts.get(_PRINTED_KEY):
        return
    facts[_PRINTED_KEY] = True
    ring = step_stats.channel()
    path, window = facts.get("trace_path"), facts.get("trace_window")
    if ring is None or not path or window is None:
        return
    steps = step_stats.annotated_steps(path, window)
    ring.fold_step_stats(wait=True)
    positions = facts["rows_per_step"] * facts["sizes"]["seq_len"]
    kept = [e for e in ring.step_stats() if e["stats"].get(NOISE)]
    for line in series_lines(kept, steps, positions):
        print(line, flush=True)
    inside = [row["masked"] / positions for e in kept
              if e["step"] in set(steps) for row in e["stats"][NOISE]]
    if inside:
        print(f"# noise: masked share a step {100 * min(inside):.3f} / "
              f"{100 * statistics.fmean(inside):.3f} / "
              f"{100 * max(inside):.3f} % (least / mean / most) over the "
              f"{len(inside)} steps of the traced window", flush=True)


def lm_attention_live_pct(facts: Dict[str, Any]) -> Optional[float]:
    """The query-key pairs block diffusion's mask lets through as a share
    of the pairs in the tiles the attention kernels visit, forward and
    backward, in per cent, after the noise's lines."""
    live, walked = _gauges(LIVE_PAIRS), _gauges(TILE_PAIRS)
    if live is None or walked is None or sum(walked) <= 0:
        return None
    _print_noise(facts)
    visited, compared = _gauges(TILES_VISITED), _gauges(TILES_COMPARED)
    if visited is not None and compared is not None:
        print("# attention tiles a head a layer, forward / backward: "
              f"visited {visited[0]:.0f} / {visited[1]:.0f}, of them "
              f"comparing positions {compared[0]:.0f} / {compared[1]:.0f}; "
              f"live pairs {live[0]:.0f} of {walked[0]:.0f} / "
              f"{live[1]:.0f} of {walked[1]:.0f}", flush=True)
    value = live_pct(live, walked)
    if value > 100.0:
        raise ValueError(f"lm_attention_live_pct reads {value:.3f} %: more "
                         "pairs live than the visited tiles hold")
    return value
