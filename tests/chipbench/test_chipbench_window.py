"""The rate and the 95th percentile from a list of completion times."""

import pytest

from chipbench import window


def _steps(n, gap, start=100.0):
    return [start + i * gap for i in range(n)]


def test_rate_is_whole_steps_over_time_between_two_completions():
    times = _steps(11, 0.5)            # opens at 100.0; 10 steps in 5 s
    win = window.cut(times, seconds=5.0)
    assert (win.counted, win.opened_at, win.closed_at) == (10, 100.0, 105.0)
    assert win.rate(2048) == pytest.approx(10 * 2048 / 5.0)
    assert win.gaps == pytest.approx([0.5] * 10)


def test_window_that_ends_between_two_steps_closes_at_the_later_one():
    times = _steps(20, 0.3)            # 4.0 s falls between 3.9 and 4.2
    win = window.cut(times, seconds=4.0)
    assert win.closed_at == pytest.approx(100.0 + 14 * 0.3)
    assert win.counted == 14
    # whole steps over the time they took, not over the 4.0 s asked for
    assert win.rate(1) == pytest.approx(1 / 0.3)


@pytest.mark.parametrize("shift", [0.0, 0.004, 0.0199, 0.1])
def test_rate_does_not_depend_on_where_the_wall_clock_edge_falls(shift):
    """What refused PR 22: steps counted inside a fixed wall window gain or
    lose one with the phase of the first step. Completions-to-completions
    does not."""
    win = window.cut(_steps(600, 0.02, start=50.0 + shift), seconds=10.0)
    assert win.rate(2048) == pytest.approx(2048 / 0.02, rel=1e-9)


def test_closes_tells_the_loop_when_to_stop():
    times = []
    for t in _steps(100, 0.25):
        times.append(t)
        if window.closes(times, 2.0):
            break
    assert len(times) == 9 and times[-1] - times[0] == pytest.approx(2.0)
    assert window.cut(times, 2.0).counted == 8


def test_one_slow_step_shows_in_the_tail_before_the_mean():
    gaps = [0.02] * 95 + [0.2] * 5
    times = [0.0]
    for g in gaps:
        times.append(times[-1] + g)
    win = window.cut(times, seconds=1e9)
    assert window.percentile(win.gaps, 50) == pytest.approx(0.02)
    assert window.percentile(win.gaps, 95) > 0.02
    assert window.samples_beyond(win.gaps, 95) == 5


@pytest.mark.parametrize("values,q,want", [
    ([1.0, 2.0, 3.0, 4.0, 5.0], 50, 3.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    ([1.0, 2.0, 3.0, 4.0, 5.0], 95, 4.8),
    ([7.0], 95, 7.0),
    ([1.0, 2.0], 0, 1.0),
    ([1.0, 2.0], 100, 2.0),
])
def test_percentile_interpolates_like_numpy(values, q, want):
    import numpy as np
    assert window.percentile(values, q) == pytest.approx(want)
    assert window.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


@pytest.mark.parametrize("times", [[], [1.0], [2.0, 1.0]])
def test_a_window_needs_two_ordered_completions(times):
    with pytest.raises(ValueError):
        window.cut(times, 1.0)
