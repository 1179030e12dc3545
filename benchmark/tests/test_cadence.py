"""Seconds a step from the feed's own timeline (``harness.paced_stretches``,
``weighted_median``, ``Schedule.step_seconds``) on hand-made timelines: a loop
that runs ahead of the device in bursts, one that every batch paces alike,
a neighbour that slows the feed for a while, and windows too short to cut."""

import random

import pytest

from benchmark import harness

STEP = 0.107


def bursty(steps, burst=8, step=STEP, ask=0.004, slow=None):
    """When each batch is asked for by a loop that dispatches ``burst`` steps
    ``ask`` seconds apart and then waits for the device to finish them.
    ``slow`` maps a burst's number to extra seconds the device took over it."""
    t, out = 0.0, []
    for k in range(steps):
        if k and k % burst == 0:
            t = done
        if k % burst == 0:
            done = t + burst * step + (slow or {}).get(k // burst, 0.0)
        out.append(t + (k % burst) * ask)
    return out


def test_a_loop_that_runs_ahead_in_bursts_is_cut_at_the_waits():
    stretches = harness.paced_stretches(bursty(280))
    assert len(stretches) == 280 // 8 - 2    # the first burst waited for nothing
    assert all(n == 8 and s == pytest.approx(8 * STEP) for n, s in stretches)
    assert harness.weighted_median([(s / n, n) for n, s in stretches]) == pytest.approx(STEP)


def test_a_neighbour_that_slows_the_feed_for_a_while_does_not_move_the_median():
    # one stall of 1.7 s and six bursts that each took 10% longer: 2.2 s of a
    # 30 s window, which the mean pays in full and the median not at all
    slow = {12: 1.7, **{j: 0.8 * STEP for j in range(20, 26)}}
    times = bursty(280, slow=slow)
    stretches = harness.paced_stretches(times)
    median = harness.weighted_median([(s / n, n) for n, s in stretches])
    assert median == pytest.approx(STEP)
    assert sum(s for _, s in stretches) / sum(n for n, _ in stretches) > 1.07 * STEP
    # a step that really got slower moves it in full
    slower = harness.paced_stretches(bursty(280, step=1.05 * STEP))
    assert harness.weighted_median([(s / n, n) for n, s in slower]) == pytest.approx(1.05 * STEP)


def test_a_host_hiccup_inside_a_burst_splits_one_stretch_and_no_more():
    times = bursty(280)
    for k in range(83, 88):      # the fourth batch of a burst came 150 ms late,
        times[k] += 0.150        # and the rest of the burst behind it
    stretches = harness.paced_stretches(times)
    assert sorted(n for n, _ in stretches if n != 8) == [3, 5]
    assert harness.weighted_median([(s / n, n) for n, s in stretches]) == pytest.approx(STEP)


def test_batches_that_all_wait_alike_still_tile_the_timeline():
    rs = random.Random(5)
    t, times = 0.0, []
    for _ in range(400):
        times.append(t)
        t += STEP * (1 + rs.uniform(-0.1, 0.1))
    stretches = harness.paced_stretches(times)
    assert len(stretches) > 100
    assert sum(n for n, _ in stretches) <= 399
    # every stretch ends on a long interval and starts after one
    assert sum(s for _, s in stretches) / sum(n for n, _ in stretches) == pytest.approx(STEP, rel=0.01)
    assert harness.weighted_median([(s / n, n) for n, s in stretches]) == pytest.approx(STEP, rel=0.02)


@pytest.mark.parametrize("pairs, want", [
    ([(1.0, 1)], 1.0),
    ([(1.0, 1), (3.0, 1)], 2.0),
    ([(1.0, 1), (2.0, 1), (9.0, 1)], 2.0),
    ([(1.0, 8), (2.0, 3), (3.0, 5)], 1.5),   # half the weight lies under 1.0 exactly
    ([(1.0, 3), (2.0, 8), (3.0, 5)], 2.0),
])
def test_weighted_median(pairs, want):
    assert harness.weighted_median(pairs) == want


def schedule(mix_steps, epochs, t_open=0.0, t_close=10.0):
    s = harness.Schedule({"steps_per_epoch": mix_steps}, pool=[], seconds=10)
    s.epochs, s.t_open, s.t_close = epochs, t_open, t_close
    return s


def test_one_epoch_to_the_deadline_takes_the_median_of_its_stretches():
    times = bursty(96, slow={5: 2.0})
    s = schedule(None, [
        {"kind": "warmup", "steps": 10, "t_yield": bursty(10)},
        {"kind": "window", "steps": 96, "t_yield": times},
    ], t_close=times[-1] + 1.0)
    step_s, how = s.step_seconds()
    assert step_s == pytest.approx(STEP) and how == "median of 10 paced stretches"


def test_too_short_a_window_and_a_window_of_epochs_take_the_mean():
    s = schedule(None, [{"kind": "window", "steps": 20, "t_yield": bursty(20)}], t_close=2.5)
    assert s.step_seconds() == (2.5 / 20, "window mean")     # one stretch only
    epochs = [{"kind": "window", "steps": 30, "t_yield": bursty(30)} for _ in range(3)]
    s = schedule(30, epochs, t_close=12.0)
    assert s.step_seconds() == (12.0 / 90, "window mean")    # boundaries and saves count
    assert schedule(None, [], t_close=1.0).step_seconds() == (None, "no whole step")
