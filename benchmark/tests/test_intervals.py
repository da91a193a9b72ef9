"""The interval arithmetic on synthetic stamps."""

import pytest

from benchlib import intervals as iv


def test_window_closes_at_the_first_stamp_at_or_after_seconds():
    stamps = [0.0, 0.25, 0.5, 0.75, 1.0, 1.25, 1.5]
    # opened at stamp 1 (0.25): 0.25 + 1.0 = 1.25 is itself a stamp
    assert iv.close_index(stamps, 1, 1.0) == 5
    # 0.25 + 0.9 = 1.15: the first stamp after it is 1.25
    assert iv.close_index(stamps, 1, 0.9) == 5
    assert iv.close_index(stamps, 1, 1.01) == 6
    assert iv.close_index(stamps, 1, 5.0) is None


def test_nothing_is_divided_by_the_nominal_seconds():
    # 39.9 steps of 250.4 ms fit into 10 s: a count over the nominal
    # window reads 39 or 40 steps (2.5% apart); the stamps do not care.
    step = 0.2504
    stamps = [i * step for i in range(60)]
    close = iv.close_index(stamps, 3, 10.0)
    got = iv.summarize(stamps[3:close + 1], 1, 32768, 1)
    assert got["rate_per_chip"] == pytest.approx(32768 / step, rel=1e-9)
    assert got["window_s"] >= 10.0
    assert got["stall_pct"] == pytest.approx(0.0, abs=1e-9)


def test_a_fused_dispatch_counts_as_k_steps_of_interval_over_k():
    stamps = [0.0, 0.47, 0.94, 1.41]
    assert iv.step_intervals(stamps, 10) == pytest.approx([0.047] * 3)
    got = iv.summarize(stamps, 10, 128, 1)
    assert got["steps"] == 30 and got["dispatches"] == 3
    assert got["step_ms_p50"] == pytest.approx(47.0)
    assert got["rate_per_chip"] == pytest.approx(128 / 0.047)
    with pytest.raises(ValueError):
        iv.step_intervals(stamps, 0)


def test_median_and_p90():
    xs = [float(i) for i in range(1, 12)]          # 1..11
    assert iv.median(xs) == 6.0
    assert iv.p90(xs) == 10.0
    assert iv.median([1.0, 2.0, 3.0, 4.0]) == 2.5
    assert iv.p90([1.0, 2.0]) == pytest.approx(1.9)
    with pytest.raises(ValueError):
        iv.median([])


def test_a_stall_lowers_the_end_to_end_rate_and_not_the_median():
    # 99 steps of 100 ms and one of 600 ms: the window is 10.5 s
    stamps, t = [0.0], 0.0
    for i in range(100):
        t += 0.6 if i == 50 else 0.1
        stamps.append(t)
    got = iv.summarize(stamps, 1, 1000, 1)
    assert got["step_ms_p50"] == pytest.approx(100.0)
    assert got["step_ms_p90"] == pytest.approx(100.0)
    # the end-to-end rate is all the work over all the time
    assert got["rate_per_chip"] == pytest.approx(100 * 1000 / 10.5)
    assert got["median_rate_per_chip"] == pytest.approx(10000.0)
    # the stall costs 0.5 s of 10.5 s
    assert got["stall_pct"] == pytest.approx(0.5 / 10.5 * 100)
    assert got["step_ms_max"] == pytest.approx(600.0)


def test_one_slow_step_in_ten_shows_in_the_p90():
    stamps, t = [0.0], 0.0
    for i in range(100):
        t += 0.13 if i % 8 == 0 else 0.1        # 13 of 100 slow
        stamps.append(t)
    got = iv.summarize(stamps, 1, 1, 1)
    assert got["step_ms_p50"] == pytest.approx(100.0)
    assert got["step_ms_p90"] == pytest.approx(130.0)


def test_rate_is_per_chip():
    assert iv.rate_per_chip(131072, 0.663, 4) == pytest.approx(
        131072 / 0.663 / 4)
