"""The rate arithmetic, held to hand-computed answers."""
import pytest

from chipbench import timing


def test_window_rate_is_whole_steps_over_measured_time():
    # 10 steps of 256 samples between fences 4.0 s apart, whatever --seconds
    assert timing.window_rate(256, 10, 100.0, 104.0) == 640.0


@pytest.mark.parametrize("steps,t0,t1", [(0, 0.0, 1.0), (5, 2.0, 2.0),
                                         (5, 3.0, 2.0)])
def test_window_rate_refuses_an_empty_window(steps, t0, t1):
    with pytest.raises(ValueError):
        timing.window_rate(1, steps, t0, t1)


def test_segment_rates_whole_segments_only():
    # a step every 0.5 s for 4.75 s, one unit each: four a 2-second slice;
    # the last 0.75 s is a partial slice and is left out
    stamps = [10.25 + 0.5 * i for i in range(10)]
    assert timing.segment_rates(stamps, 10.0, [1] * 10) == [2.0, 2.0]


def test_segment_rates_show_a_stall():
    # steps every 0.5 s, but nothing between 2.4 and 3.9
    stamps = [0.4, 0.9, 1.4, 1.9, 2.4, 3.9, 4.4, 4.9, 5.4, 5.9, 6.1]
    rates = timing.segment_rates(stamps, 0.0, [4] * 11)
    assert rates == [8.0, 4.0, 8.0]
    assert timing.longest_step(stamps, 0.0) == (pytest.approx(1.5), 5)


def test_segment_rates_empty():
    assert timing.segment_rates([], 0.0, []) == []


@pytest.mark.parametrize("marks,want", [
    ((1, 2), {1: 4.0, 2: 4.0}),          # steady: same rate in any prefix
    ((2, 100), {2: 4.0}),                # a mark beyond the window is left out
])
def test_prefix_rates(marks, want):
    stamps = [0.25 * (i + 1) for i in range(12)]      # 3 s of 4 steps/s
    got = timing.prefix_rates(stamps, 0.0, [1] * 12, marks, skip=0)
    assert got == pytest.approx(want)


def test_prefix_rates_skip_the_dispatches_that_ran_ahead():
    # two steps in flight: the first two stamps come at once, then one a
    # step; the steady rate is 10 units a second whatever the prefix
    stamps = [0.0, 0.001] + [0.1 * i for i in range(1, 40)]
    got = timing.prefix_rates(stamps, 0.0, [1] * 41, (1, 2, 3), skip=2)
    assert got == pytest.approx({1: 10.0, 2: 10.0, 3: 10.0})
    assert timing.prefix_rates(stamps[:3], 0.0, [1] * 3, (1,), skip=3) == {}


def test_longest_step_counts_from_the_opening_fence():
    assert timing.longest_step([3.0, 3.5], 1.0) == (2.0, 0)


@pytest.mark.parametrize("q,want", [(0.5, 2.0), (0.95, 9.0), (1.0, 9.0),
                                    (0.01, 1.0)])
def test_weighted_percentile(q, want):
    # 10 requests saw 1 ms, 80 saw 2 ms, 10 saw 9 ms
    assert timing.weighted_percentile([9.0, 1.0, 2.0], [10, 10, 80], q) == want


def test_weighted_percentile_ignores_zero_weights_and_needs_samples():
    assert timing.weighted_percentile([5.0, 1.0], [0, 3], 0.95) == 1.0
    with pytest.raises(ValueError):
        timing.weighted_percentile([1.0], [0], 0.5)


def test_gaps_one_per_slot_active_before_the_tick():
    values, weights = timing.gaps([1.0, 1.5, 2.5], 0.5, [2, 3, 3])
    assert values == [0.5, 0.5, 1.0] and weights == [2, 3, 3]
    # 8 gaps: 5 of 0.5 s and 3 of 1.0 s
    assert timing.weighted_percentile(values, weights, 0.5) == 0.5
    assert timing.weighted_percentile(values, weights, 0.95) == 1.0


def test_histogram_open_last_bin():
    assert timing.histogram([0.5, 1.5, 99.0], [1, 2, 3], [0, 1, 2]) == \
        [1.0, 2.0, 3.0]


def test_spread_is_the_contracts():
    import statistics

    v = [100.0, 101.0, 99.0, 100.5, 99.5, 100.2]
    q = statistics.quantiles(v, n=4)
    assert timing.spread(v) == pytest.approx(
        (q[2] - q[0]) / statistics.median(v))
