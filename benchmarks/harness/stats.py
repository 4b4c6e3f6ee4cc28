"""The benchmark's own arithmetic on samples and intervals."""
from __future__ import annotations

import math


def percentile(values, q):
    """The q-th percentile (0..100), linear between closest ranks, of a
    non-empty list."""
    if not values:
        raise ValueError("percentile of no sample")
    v = sorted(values)
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(intervals):
    """Total length of the union of `intervals`."""
    return sum(e - s for s, e in union(intervals))


def gaps(intervals, lo, hi):
    """The idle (start, end) stretches of [lo, hi] that no interval
    covers, longest first."""
    out, at = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def idle_share(intervals, lo, hi):
    """100 x (1 - busy / span) over [lo, hi]."""
    if hi <= lo:
        raise ValueError("empty span")
    return 100.0 * (1.0 - covered(clip(intervals, lo, hi)) / (hi - lo))
