"""Percentile and spread arithmetic of the benchmark (pure Python)."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks (the rule numpy calls "linear").  Raises on no data: a
    metric with nothing to read is left out by its reader, never made 0."""
    vals = sorted(float(v) for v in values)
    if not vals:
        raise ValueError("percentile of no values")
    if len(vals) == 1:
        return vals[0]
    pos = (len(vals) - 1) * (q / 100.0)
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of `statistics.quantiles(values, n=4)` —
    the spread the contract sets bounds from."""
    q1, _, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / abs(statistics.median(values))


def lateness_ms(due, sent) -> list[float]:
    """How late each request left against its schedule, in ms (never
    negative: a request is not sent before it is due)."""
    return [max(0.0, (s - d) * 1000.0) for d, s in zip(due, sent)]


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals, overlaps and
    nesting counted once."""
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


def gaps(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    """The (start, end) stretches of [t0, t1] no interval covers."""
    out, cur = [], t0
    for s, e in sorted(intervals):
        if e <= t0 or s >= t1:
            continue
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out
