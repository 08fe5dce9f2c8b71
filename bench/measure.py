"""Statistics the benchmark computes itself, never read from the program."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-quantile (0 < q <= 1).  A missing value (a request
    that failed or never came) is passed as ``math.inf``, so it counts as
    slower than every answered one."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("no values")
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


def merge(intervals: Sequence[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals: Sequence[tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    return sum(e - s for s, e in merge(intervals))


def subtract(a: Sequence[tuple[float, float]], b: Sequence[tuple[float, float]]) -> float:
    """Length of the union of ``a`` not covered by the union of ``b``."""
    a, b = merge(a), merge(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total
