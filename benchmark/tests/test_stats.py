import pytest

from benchmark.lib import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 100) == 50.0
    # position (5 - 1) * 0.9 = 3.6 -> 40 + 0.6 * (50 - 40)
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile([3.0, 1.0, 2.0], 25) == pytest.approx(1.5)


def test_percentile_of_one_value_and_of_none():
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_percentile_matches_numpy():
    np = pytest.importorskip("numpy")
    xs = list(np.random.default_rng(0).normal(size=101))
    for q in (10, 25, 50, 75, 90, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_gaps_are_milliseconds_between_stamps():
    assert stats.gaps_ms([1.0, 1.1, 1.25]) == pytest.approx([100.0, 150.0])


def test_throughput_is_read_at_the_median_gap():
    # 5 stamps bound 4 whole steps in 2 s; 1000 tokens a step on 4 chips
    r = stats.throughput([10.0, 10.5, 11.0, 11.5, 12.0], 1000, 4)
    assert r["steps"] == 4
    assert r["seconds"] == pytest.approx(2.0)
    assert r["tokens_per_s_per_chip"] == pytest.approx(1000 / 0.5 / 4)
    assert r["whole_window_tokens_per_s_per_chip"] == pytest.approx(
        4 * 1000 / 2.0 / 4
    )


def test_one_pause_of_the_machine_does_not_move_the_throughput():
    steady = [0.05 * i for i in range(401)]
    paused = steady[:200] + [t + 14.0 for t in steady[200:]]
    a = stats.throughput(steady, 12288, 1)
    b = stats.throughput(paused, 12288, 1)
    assert b["tokens_per_s_per_chip"] == pytest.approx(
        a["tokens_per_s_per_chip"]
    )
    assert b["whole_window_tokens_per_s_per_chip"] < (
        0.6 * a["whole_window_tokens_per_s_per_chip"]
    )
    # a slowdown of more than a tenth of the steps moves the 90th percentile
    slow, t = [0.0], 0.0
    for i in range(400):
        t += 0.06 if i % 8 == 0 else 0.05
        slow.append(t)
    assert stats.percentile(stats.gaps_ms(slow), 90) == pytest.approx(60.0)


def test_throughput_needs_two_stamps():
    with pytest.raises(ValueError):
        stats.throughput([1.0], 10, 1)
