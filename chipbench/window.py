"""The arithmetic of a measured window: whole completions over the time
between two completions.

A completion is a host clock reading taken when a step's loss (or an
epoch's last digest) has arrived on the host. The window opens AT a
completion and closes AT the first completion at or after ``seconds``
later, so nothing is counted by a wall-clock edge: a run that happens to
start 5 ms later counts the same whole steps over the same kind of
interval.

A completion is SEEN on the host, after the step has ended on the device:
the thread that reads the loss has to take the interpreter back from the
loader's threads first. Seen late, it makes its own gap long and the next
one short by as much, and the device notices nothing. So the tail of the
gaps is taken over groups of consecutive gaps (``group_tail``): inside a
group the two cancel, and what stalls the device does not.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Sequence

#: A tail is read only where this many samples lie beyond it.
LEAST_BEYOND = 10


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


def group_means(gaps: Sequence[float], size: int) -> List[float]:
    """Mean gap of each run of ``size`` consecutive gaps, the runs not
    overlapping; gaps left over at the end are dropped."""
    if size < 1:
        raise ValueError(f"a group holds one gap or more, not {size}")
    whole = len(gaps) - len(gaps) % size
    return [sum(gaps[i:i + size]) / size for i in range(0, whole, size)]


def group_tail(gaps: Sequence[float], size: int, q: float = 95.0,
               least_beyond: int = LEAST_BEYOND) -> float:
    """The ``q``-th percentile of the groups' mean gaps: the tail of the
    time a step takes as ``size`` steps in a row show it. An error where
    fewer than ``least_beyond`` groups lie beyond the percentile."""
    means = group_means(gaps, size)
    beyond = samples_beyond(means, q)
    if beyond < least_beyond:
        raise ValueError(
            f"{len(gaps)} gaps make {len(means)} groups of {size}, "
            f"{beyond} beyond the {q:g}th percentile: a tail wants "
            f"{least_beyond} or more")
    return percentile(means, q)


def lag1_autocorrelation(values: Sequence[float]) -> float:
    """Correlation of each value with the one after it: near -0.5 where
    the values are a steady period plus the noise of the stamps at their
    ends, near 0 where each varies on its own. 0.0 where nothing varies."""
    if len(values) < 3:
        raise ValueError("an autocorrelation wants three values or more")
    mean = statistics.fmean(values)
    centred = [v - mean for v in values]
    var = sum(c * c for c in centred)
    if var <= 0.0:
        return 0.0
    return sum(a * b for a, b in zip(centred, centred[1:])) / var

