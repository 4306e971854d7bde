"""Percentiles and measured-window arithmetic, shared by every driver.

Times are seconds on the run's own clock.  The window is [t0, t1).
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Sequence, Tuple


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank q-th percentile (0 < q <= 100): the smallest value
    with at least q% of the sample at or below it.  `math.inf` entries
    (requests that missed) sort last, so a tail with misses in it is
    infinite."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def in_window(times: Sequence[float], t0: float, t1: float) -> List[float]:
    return [t for t in times if t0 <= t < t1]


def tokens_in_window(token_times: Dict[int, Sequence[float]], t0: float,
                     t1: float) -> int:
    """Output tokens emitted inside the window; a request that straddles
    an edge counts only its tokens inside."""
    return sum(len(in_window(ts, t0, t1)) for ts in token_times.values())


def tpot_samples(token_times: Dict[int, Sequence[float]], t0: float,
                 t1: float, min_span: float = 0.0) -> List[float]:
    """Per request with at least 2 tokens inside the window, whose first
    and last token there lie at least `min_span` apart: (last token time
    - first token time) / (tokens - 1), all times inside."""
    out = []
    for ts in token_times.values():
        inside = in_window(ts, t0, t1)
        if len(inside) >= 2 and inside[-1] - inside[0] >= min_span:
            out.append((inside[-1] - inside[0]) / (len(inside) - 1))
    return out


def group_times(calls: Sequence[Tuple[float, float]],
                min_span: float) -> List[float]:
    """Consecutive (start, end) calls in groups, each closed by its first
    call that ends `min_span` or more after the group's first start: the
    time per call of each group, host time between its calls included.
    A last group that spans less is left out."""
    out, first, n = [], None, 0
    for s, e in calls:
        if first is None:
            first = s
        n += 1
        if e - first >= min_span:
            out.append((e - first) / n)
            first, n = None, 0
    return out


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by a set of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(intervals: Iterable[Tuple[float, float]], t0: float,
         t1: float) -> List[Tuple[float, float]]:
    """Intervals cut to [t0, t1); those outside are dropped."""
    out = []
    for s, e in intervals:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((s, e))
    return out


def gaps(intervals: Iterable[Tuple[float, float]], t0: float,
         t1: float) -> List[Tuple[float, float]]:
    """The idle stretches of [t0, t1) that no interval covers."""
    out, cur = [], t0
    for s, e in sorted(clip(intervals, t0, t1)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out
