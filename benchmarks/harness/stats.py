"""Sample statistics of the benchmark: the percentile rule and the spread.

The percentile rule is the `choosing-metrics` guide's: report the median and
the highest percentile that still has ten samples beyond it. Percentiles are
nearest-rank on the sorted samples (no interpolation), so a tail is a latency
some request really had.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Sequence

TAILS = (99.9, 99.0, 95.0, 90.0, 75.0)
MIN_BEYOND = 10


def _rank(n: int, pct: float) -> int:
    """Nearest rank of `pct` among n sorted samples, 1-based (the small
    subtraction keeps 99.9% of 10,000 at 9,990 in floating point)."""
    return max(1, math.ceil(pct * n / 100.0 - 1e-9))


def percentile(samples: Sequence[float], pct: float) -> float:
    """Nearest-rank percentile of `samples` (any order); raises on none."""
    if not samples:
        raise ValueError("percentile of no samples")
    xs = sorted(samples)
    return float(xs[_rank(len(xs), pct) - 1])


def samples_beyond(n: int, pct: float) -> int:
    """How many of `n` samples lie strictly above the nearest-rank `pct`."""
    return n - _rank(n, pct)


def highest_supported_tail(n: int) -> float:
    """The highest of TAILS that leaves at least MIN_BEYOND samples beyond
    it; 50.0 when the sample supports none."""
    for pct in TAILS:
        if samples_beyond(n, pct) >= MIN_BEYOND:
            return pct
    return 50.0


def latency_summary(seconds: Sequence[float]) -> Dict[str, float]:
    """{n, p50_ms, p95_ms, highest_tail, beyond_p95} of request latencies."""
    ms = [s * 1e3 for s in seconds]
    return {
        "n": len(ms),
        "p50_ms": percentile(ms, 50.0),
        "p95_ms": percentile(ms, 95.0),
        "highest_tail": highest_supported_tail(len(ms)),
        "beyond_p95": samples_beyond(len(ms), 95.0),
    }


def median(xs: Sequence[float]) -> float:
    return float(statistics.median(xs))


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile (Python's
    `statistics.quantiles(values, n=4)`) as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def union_seconds(intervals: List[tuple]) -> float:
    """Total length covered by [start, end) intervals (any order, any unit)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total
