"""The arithmetic of a measured window: whole completions over the time
between two completions.

A completion is a host clock reading taken when a step's loss (or an
epoch's last digest) has arrived on the host. The window opens AT a
completion and closes AT the first completion at or after ``seconds``
later, so nothing is counted by a wall-clock edge: a run that happens to
start 5 ms later counts the same whole steps over the same kind of
interval.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence


@dataclasses.dataclass(frozen=True)
class Window:
    opened_at: float        # completion time of the step before the first counted
    closed_at: float        # completion time of the last counted step
    counted: int            # completions after the opening one, up to the closing one
    gaps: List[float]       # seconds between consecutive counted completions

    @property
    def elapsed(self) -> float:
        return self.closed_at - self.opened_at

    def rate(self, units_per_completion: float) -> float:
        """Units (rows) per second over the whole window."""
        if self.counted < 1 or self.elapsed <= 0:
            raise ValueError(f"an empty window has no rate: {self}")
        return self.counted * units_per_completion / self.elapsed


def closes(completions: Sequence[float], seconds: float) -> bool:
    """True once the newest completion is ``seconds`` or more after the
    first: the loop calls this after every completion and stops on True."""
    return (len(completions) >= 2
            and completions[-1] - completions[0] >= seconds)


def cut(completions: Sequence[float], seconds: float) -> Window:
    """The window over ``completions`` (ascending host times; the first
    one opens it). Closes at the first completion at or after ``seconds``;
    if none is that late the last one closes it."""
    if len(completions) < 2:
        raise ValueError("a window needs an opening completion and at "
                         f"least one more, got {len(completions)}")
    if any(b < a for a, b in zip(completions, completions[1:])):
        raise ValueError("completions are not in time order")
    opened = completions[0]
    last = len(completions) - 1
    for i in range(1, len(completions)):
        if completions[i] - opened >= seconds:
            last = i
            break
    kept = list(completions[:last + 1])
    return Window(opened_at=opened, closed_at=kept[-1], counted=last,
                  gaps=[b - a for a, b in zip(kept, kept[1:])])


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics, as numpy's default."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    pos = (len(ordered) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def samples_beyond(values: Sequence[float], q: float) -> int:
    """How many samples lie beyond the ``q``-th percentile's position: a
    tail wants ten or more."""
    return int(len(values) * (100.0 - q) / 100.0)
