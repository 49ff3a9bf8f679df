"""Arithmetic on samples: percentiles, rates, spreads. Plain Python."""

from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0-100) by the nearest-rank rule: the smallest
    value with at least q% of the sample at or below it. A tail is a value
    some request really saw, never an interpolation."""
    xs = sorted(values)
    if not xs:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def with_failures_as_largest(values, failed_floor):
    """Latencies with failed requests set to the largest value: each entry
    of `failed_floor` is the least the failed request had already waited;
    it counts as max(that, every observed value)."""
    top = max(list(values) + list(failed_floor)) if (values or failed_floor) \
        else None
    return list(values) + [top] * len(failed_floor)


def completion_rate(units_and_times, t_start):
    """(units per second, units, seconds): the units of everything completed
    divided by the measured time from `t_start` to the LAST completion.
    `units_and_times`: (units, t_completed) pairs. None when nothing
    completed or no time passed."""
    done = [(u, t) for u, t in units_and_times if t is not None]
    if not done:
        return None, 0, 0.0
    units = sum(u for u, _ in done)
    seconds = max(t for _, t in done) - t_start
    if seconds <= 0:
        return None, units, seconds
    return units / seconds, units, seconds


def iqr_share(values):
    """The spread the contract uses: (Q3 - Q1) / median, with Python's
    statistics.quantiles(values, n=4)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
