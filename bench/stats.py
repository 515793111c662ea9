"""Metric arithmetic shared by the readers: tails over every request, rates
over the whole window, failures counted as missing."""

from __future__ import annotations

import math


def tail_ms(latencies_s: list, q: float, missing_s: float) -> float:
    """The ``q`` quantile (nearest rank) of every request's latency in ms.
    ``None`` marks a request that failed or never answered: it counts as
    ``missing_s``, which the caller sets above every success (the time the
    harness stopped waiting for it)."""
    if not latencies_s:
        raise ValueError("no requests in the window")
    vals = sorted(missing_s if v is None else v for v in latencies_s)
    rank = max(1, math.ceil(q * len(vals)))
    return vals[rank - 1] * 1e3


def median(values: list) -> float | None:
    """Median of ``values`` (mean of the middle pair), or None if empty."""
    if not values:
        return None
    vals = sorted(values)
    mid = len(vals) // 2
    return vals[mid] if len(vals) % 2 else (vals[mid - 1] + vals[mid]) / 2


def rate(total: float, window_s: float) -> float:
    """``total`` per second of the whole window."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return total / window_s
