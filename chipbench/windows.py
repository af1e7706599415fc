"""Arithmetic on the readings one run takes: window rates and step tails.

Pure Python, no jax: the tests hold it to hand-worked numbers on CPU.
"""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def window_rates(closes: Sequence[float], samples_per_window: float, chips: int) -> list[float]:
    """Samples/s/chip of each window between consecutive close times.

    ``closes`` are host-clock times at which the Trainer's ``log_interval``
    drain returned; window *i* is ``closes[i] .. closes[i + 1]`` and holds
    ``samples_per_window`` samples over ``chips`` chips.
    """
    return [
        samples_per_window / (b - a) / chips
        for a, b in zip(closes, closes[1:])
    ]


def overall_rate(closes: Sequence[float], samples_per_window: float, chips: int) -> float:
    """All samples of the measured span over all of its time: the
    end-to-end rate (no window is left out)."""
    if len(closes) < 2:
        raise ValueError("a measured span needs at least one whole window")
    n = len(closes) - 1
    return n * samples_per_window / (closes[-1] - closes[0]) / chips


def median(values: Sequence[float]) -> float:
    return statistics.median(values)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest reading with at least ``q``
    percent of the readings at or below it.  No interpolation, so the
    result is always a reading that was taken."""
    if not values:
        raise ValueError("no readings")
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    ordered = sorted(values)
    rank = math.ceil(q / 100.0 * len(ordered))
    return ordered[rank - 1]


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, as the driver reads it
    (``statistics.quantiles(n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
