"""The paper's ``rand_<lambda>`` (arXiv 2306.12141 §5.1 Table 4):
exponentially distributed bytes; higher lambda, more skew, more
compressible.  Copied from ``benchmarks/datasets.py``."""

import numpy as np


def make(rng: np.random.Generator, size: int, lam: float) -> np.ndarray:
    vals = rng.exponential(scale=2550.0 / lam, size=size)
    return np.minimum(vals, 255).astype(np.int32)
