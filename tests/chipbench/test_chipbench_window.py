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


# -- the tail over groups of consecutive gaps ---------------------------------

_STEP = 0.02            # the device's step, seconds
_GROUP = 4              # the run-ahead's depth
_BOUND = 0.01           # step_gap_p95_ms's bound in BENCHMARK.json


def _gaps(times):
    return [b - a for a, b in zip(times, times[1:])]


def _seen(n=1200, late=(), stalls=(), slow=1.0):
    """Host times at which ``n`` completions are seen. The device ends a
    step every ``_STEP * slow`` seconds; ``late[i]`` seconds pass before
    completion ``i`` is seen (the device does not wait for that);
    ``stalls[i]`` seconds of a starved device come before step ``i`` (every
    later step is that much later)."""
    late, stalls = dict(late), dict(stalls)
    times, at = [], 100.0
    for i in range(n):
        at += _STEP * slow + stalls.get(i, 0.0)
        times.append(at + late.get(i, 0.0))
    return times


def _alternating(n=1200, by=0.0008):
    """Every other completion seen ``by`` late, the one after on time."""
    return {i: by for i in range(0, n, 2)}


def _every(n, every, seconds):
    return {i: seconds for i in range(every, n, every)}


@pytest.mark.parametrize("case,times,single_rises,group_rises", [
    # A completion seen late and the short gap after it fall into one group
    # and cancel: the single-gap tail reads the observation, the group's
    # the device.
    ("seen late, device on time", _seen(late=_alternating()), True, False),
    ("sound", _seen(), False, False),
    # What the metric is for stalls the device and does not cancel.
    ("120 ms stall every 50 steps", _seen(stalls=_every(1200, 50, 0.12)),
     False, True),
    ("uniform 5 % slowdown", _seen(slow=1.05), True, True),
    ("a compile-sized gap at every chunk length, 1.4 s each 70 steps",
     _seen(stalls=_every(1200, 70, 1.4)), False, True),
    # Rarer than one group in twenty, a stall stays beyond the percentile
    # (one gap in a hundred is beyond the single-gap one too): the rate
    # carries it, 0.12 s in 100 steps of 0.02 s is 5.7 %.
    ("120 ms stall every 100 steps", _seen(stalls=_every(1200, 100, 0.12)),
     False, False),
])
def test_group_tail_reads_the_device_not_the_observation(
        case, times, single_rises, group_rises):
    gaps = _gaps(times)
    single = window.percentile(gaps, 95)
    grouped = window.group_tail(gaps, _GROUP)
    assert (single > _STEP * (1 + _BOUND)) is single_rises, case
    assert (grouped > _STEP * (1 + _BOUND)) is group_rises, case
    if not group_rises:
        assert grouped == pytest.approx(_STEP, rel=_BOUND / 4), case


def test_a_rare_stall_shows_in_the_rate():
    times = _seen(stalls=_every(1200, 100, 0.12))
    win = window.cut(times, seconds=1e9)
    assert win.rate(1) < (1 / _STEP) * (1 - 5 * _BOUND)


def test_group_means_are_consecutive_and_do_not_overlap():
    assert window.group_means([1, 3, 5, 7, 9, 11, 100], 2) == [2, 6, 10]
    assert window.group_means([1, 2, 3], 4) == []
    with pytest.raises(ValueError):
        window.group_means([1.0], 0)


@pytest.mark.parametrize("n_gaps,ok", [(799, False), (800, True)])
def test_a_tail_wants_ten_groups_beyond_it(n_gaps, ok):
    gaps = [_STEP] * n_gaps        # 800 gaps: 200 groups, 10 beyond
    if ok:
        assert window.group_tail(gaps, _GROUP) == pytest.approx(_STEP)
    else:
        with pytest.raises(ValueError, match="a tail wants 10 or more"):
            window.group_tail(gaps, _GROUP)
    # a run that reports no tail may still print one
    assert window.group_tail(gaps, _GROUP, least_beyond=0) == pytest.approx(
        _STEP)


def test_lag1_autocorrelation_tells_observation_noise_from_own_variation():
    import random
    rng = random.Random(5)
    # a steady period seen with noisy stamps: each gap takes what the last
    # one gave
    stamps = [i * _STEP + rng.gauss(0, 0.0004) for i in range(4000)]
    assert window.lag1_autocorrelation(_gaps(stamps)) == pytest.approx(
        -0.5, abs=0.05)
    # each step varying on its own
    own = [_STEP + rng.gauss(0, 0.0004) for _ in range(4000)]
    assert abs(window.lag1_autocorrelation(own)) < 0.05
    assert window.lag1_autocorrelation([_STEP] * 10) == 0.0
    with pytest.raises(ValueError):
        window.lag1_autocorrelation([1.0, 2.0])
