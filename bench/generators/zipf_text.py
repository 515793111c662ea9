"""The enwik stand-in: Zipf-distributed bytes with a text-like
rank-frequency curve, ``min(Z - 1, 255)`` for ``Z ~ Zipf(a)``, as
``benchmarks/datasets.py`` draws it.  Drawn here by inverting the capped
distribution's CDF through a 2^16-bucket table, which gives the same
distribution several times faster than ``Generator.zipf``."""

import numpy as np

_BUCKETS = 1 << 16


def make(rng: np.random.Generator, size: int, a: float) -> np.ndarray:
    pmf = np.arange(1, 256, dtype=np.float64) ** -a
    # P(Z = k) = k^-a / zeta(a); the mass of every Z >= 256 lands on 255.
    cdf = np.cumsum(pmf / (pmf.sum() + _zeta_tail(a, 256)))
    u = rng.random(size)
    bucket = (u * _BUCKETS).astype(np.int64)
    edges = np.arange(_BUCKETS + 1) / _BUCKETS
    lo = np.searchsorted(cdf, edges[:-1], side="right")
    hi = np.searchsorted(cdf, edges[1:], side="right")
    out = lo[bucket]
    split = np.nonzero(hi[bucket] != out)[0]   # buckets a CDF step crosses
    out[split] = np.searchsorted(cdf, u[split], side="right")
    return out.astype(np.int32)


def _zeta_tail(a: float, n: int) -> float:
    """sum_{k >= n} k^-a, by Euler-Maclaurin (error far below 1e-12)."""
    return n ** (1 - a) / (a - 1) + n ** -a / 2 + a * n ** (-a - 1) / 12
