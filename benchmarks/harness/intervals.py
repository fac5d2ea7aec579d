"""Arithmetic on half-open [start, end) intervals (any one time unit).

The device's busy time is the UNION of its op intervals — ops of several
lines overlap, and summing durations would count that time twice.  The
exposed part of a collective is what is left of it after the time in
which a compute op ran on the same device is taken out.
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover.  Touching intervals merge; empty or
    reversed ones are dropped."""
    out: List[Interval] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def subtract(a: Iterable[Interval], b: Iterable[Interval]
             ) -> List[Interval]:
    """The part of union(a) that no interval of b covers."""
    cover = union(b)
    out: List[Interval] = []
    j = 0
    for s, e in union(a):
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi): what union(busy) leaves open."""
    return subtract([(lo, hi)], busy)
