"""The reduction from a run's stamps to its end-to-end numbers.

Percentiles are numpy's default (linear between closest ranks). Every
number is taken over all the requests or tokens of the window, never over a
filtered subset.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_samples(records: Iterable, w0: float, w1: float,
                 t_close: float) -> List[float]:
    """Time to first token of every request due in [w0, w1), from its due
    time. One that has no token by ``t_close`` counts at t_close - due."""
    out = []
    for r in records:
        if w0 <= r.due < w1:
            first = r.token_times[0] if r.token_times else None
            if first is None or first > t_close:
                first = t_close
            out.append(first - r.due)
    return out


def itl_samples(records: Iterable, w0: float, w1: float) -> List[float]:
    """Every gap between consecutive output tokens of a request whose later
    token is stamped in [w0, w1)."""
    out = []
    for r in records:
        t = r.token_times
        out.extend(b - a for a, b in zip(t, t[1:]) if w0 <= b < w1)
    return out


def tokens_in(records: Iterable, w0: float, w1: float) -> int:
    return sum(sum(1 for t in r.token_times if w0 <= t < w1)
               for r in records)
