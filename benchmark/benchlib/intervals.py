"""From completion stamps to a cell's numbers.

A stamp is the host clock read in the worker right after a lagged block
on a dispatch's loss (``loop.py``). A dispatch may fuse K optimizer
steps; it is one stamp and counts as K steps of interval / K. Nothing
here divides by the nominal ``--seconds``: the window opens at a stamp
and closes at the first stamp at or after ``open + seconds``.
"""

from __future__ import annotations

import math


def close_index(stamps: list[float], open_index: int,
                seconds: float) -> int | None:
    """Index of the stamp that closes a window opened at
    ``stamps[open_index]``: the first at or after ``open + seconds``.
    None while no stamp has got there."""
    limit = stamps[open_index] + seconds
    for i in range(open_index + 1, len(stamps)):
        if stamps[i] >= limit:
            return i
    return None


def step_intervals(stamps: list[float], k: int = 1) -> list[float]:
    """Seconds per optimizer step, one value per dispatch: the
    difference of consecutive stamps over the K steps it fused."""
    if k < 1:
        raise ValueError(f"steps per dispatch must be >= 1, got {k}")
    return [(b - a) / k for a, b in zip(stamps, stamps[1:])]


def quantile(values: list[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default
    rule), so the median of an even count is the mean of the middle
    two."""
    if not values:
        raise ValueError("no values")
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: list[float]) -> float:
    return quantile(values, 0.5)


def p90(values: list[float]) -> float:
    return quantile(values, 0.9)


def rate_per_chip(samples_per_step: int, step_s: float,
                  chips: int) -> float:
    """Samples per second per chip at ``step_s`` seconds a step."""
    return samples_per_step / step_s / chips


def whole_window_step_s(stamps: list[float], k: int = 1) -> float:
    """Mean seconds per step between the first and last stamp: all the
    work over all the time of the window, stalls included."""
    n = (len(stamps) - 1) * k
    if n < 1:
        raise ValueError("a window needs two stamps")
    return (stamps[-1] - stamps[0]) / n


def stall_pct(stamps: list[float], k: int = 1) -> float:
    """Shortfall of the whole-window rate against the rate at the
    median step, in percent: 0 when every step takes the median, 5
    when stalls cost 5% of the window. Negative where the mean step is
    under the median."""
    mean_s = whole_window_step_s(stamps, k)
    return (1.0 - median(step_intervals(stamps, k)) / mean_s) * 100.0


def summarize(stamps: list[float], k: int, samples_per_step: int,
              chips: int) -> dict:
    """Every number a window of stamps gives. ``rate_per_chip`` is the
    end-to-end rate: every sample between the first and the last stamp
    over the time between them, so a stall in the window lowers it.
    ``median_rate_per_chip`` is what the chip does at its median step;
    the two differ by ``stall_pct``."""
    steps = step_intervals(stamps, k)
    med = median(steps)
    return {
        "dispatches": len(steps), "steps": len(steps) * k,
        "window_s": stamps[-1] - stamps[0],
        "step_ms_p50": med * 1e3, "step_ms_p90": p90(steps) * 1e3,
        "step_ms_min": min(steps) * 1e3, "step_ms_max": max(steps) * 1e3,
        "rate_per_chip": rate_per_chip(
            samples_per_step, whole_window_step_s(stamps, k), chips),
        "median_rate_per_chip": rate_per_chip(samples_per_step, med, chips),
        "stall_pct": stall_pct(stamps, k),
    }
