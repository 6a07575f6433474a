"""Reductions of the client's records to end-to-end numbers.

All of them run over every sample of the window, never over medians of
parts: a percentile is taken over all samples at once, and a rate is a
count over the whole window's length.
"""
from __future__ import annotations

from typing import Iterable, List, Optional

import numpy as np


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation) of all values; None
    when there are none."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def chunk_times(records, t0: float, t1: float) -> List[float]:
    """Arrival times of every streamed token inside [t0, t1)."""
    return [t for r in records for t in r.times if t0 <= t < t1]


def rate(records, t0: float, t1: float) -> float:
    """Tokens streamed to clients in [t0, t1) per second of it."""
    return len(chunk_times(records, t0, t1)) / (t1 - t0)


def inter_token_gaps(records, t0: float, t1: float) -> List[float]:
    """Every gap between consecutive tokens of one request whose later
    token arrived inside [t0, t1)."""
    return [b - a for r in records for a, b in zip(r.times, r.times[1:])
            if t0 <= b < t1]


def ttfts(records, t0: float, t1: float) -> List[float]:
    """Time from when each request due inside [t0, t1) was due to be
    sent to its first token, for those that got one."""
    return [r.times[0] - r.due for r in records
            if t0 <= r.due < t1 and r.times]
