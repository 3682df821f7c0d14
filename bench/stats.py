"""Latency and rate arithmetic of the benchmark, on the harness's own clock.

Every statistic is taken over all requests (or all tokens) of a window,
never as a median of chunks.  Percentiles are nearest rank: the smallest
sample whose rank is at least ``ceil(p/100 * n)``.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(samples: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank ``p``-th percentile of ``samples``; None when empty."""
    if not samples:
        return None
    ordered = sorted(samples)
    rank = max(math.ceil(p / 100.0 * len(ordered)), 1)
    return ordered[min(rank, len(ordered)) - 1]


def tpot(token_times: Sequence[float]) -> Optional[float]:
    """Time per output token over ``token_times``: (last - first) / (n - 1).

    None for fewer than two tokens (a single token has no gap).
    """
    if len(token_times) < 2:
        return None
    return (token_times[-1] - token_times[0]) / (len(token_times) - 1)


def rate(count: float, seconds: float) -> Optional[float]:
    """``count`` per second over ``seconds``; None for an empty window."""
    if seconds <= 0:
        return None
    return count / seconds
