"""The arithmetic between stamps and metrics: percentiles, gaps between
tokens, first-token times counted from the instant a request was due."""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default).
    ``q`` in [0, 100].  None for no samples."""
    data = sorted(values)
    if not data:
        return None
    if len(data) == 1:
        return float(data[0])
    rank = (len(data) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(data) - 1)
    return float(data[lo] + (data[hi] - data[lo]) * (rank - lo))


def ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else seconds * 1e3


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def iqr_share(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles (``statistics.quantiles(n=4)``)
    as a share of the median: the spread the bounds are set from."""
    import statistics

    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    mid = statistics.median(values)
    return (q3 - q1) / abs(mid) if mid else None


def token_gaps(stamps: Dict[int, List[float]],
               window: Tuple[float, float]) -> List[float]:
    """Gaps between a request's consecutive tokens, for every token after
    its first that was emitted inside ``window`` (open, closed].  The gap
    reaches back to the previous token even where that one came before
    the window: the stall is what the user saw."""
    start, end = window
    gaps = []
    for times in stamps.values():
        for prev, now in zip(times, times[1:]):
            if start < now <= end:
                gaps.append(now - prev)
    return gaps


def tokens_in_window(stamps: Dict[int, List[float]],
                     window: Tuple[float, float]) -> int:
    start, end = window
    return sum(
        1 for times in stamps.values() for t in times if start < t <= end
    )


def first_token_times(due: Dict[int, float],
                      stamps: Dict[int, List[float]],
                      window: Tuple[float, float],
                      lost: Iterable[int] = ()) -> List[float]:
    """Time to the first token from the instant each request was DUE,
    over requests due inside ``window``.  A request that was refused or
    failed (``lost``), or has no token by the window's end, counts as the
    window's length: it missed any limit."""
    start, end = window
    lost = set(lost)
    out = []
    for rid, t_due in due.items():
        if not (start <= t_due < end):
            continue
        times = stamps.get(rid) or []
        if rid in lost or not times or times[0] > end:
            out.append(end - start)
        else:
            out.append(times[0] - t_due)
    return out
