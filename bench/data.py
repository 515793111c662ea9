"""Seeded content for a configuration's ``objects`` entry.

A generator is ``bench/generators/<name>.py`` with ``make(rng, size,
**params)`` returning int32 symbols; the ones here are copied from
``benchmarks/datasets.py`` with the run's ``--seed`` in place of their
fixed seeds, so every run draws new content of the same distribution.
"""

from __future__ import annotations

import numpy as np

from bench.files import function


def make_objects(spec: dict, seed: int) -> dict[str, np.ndarray]:
    """``{name: symbols}``: ``count`` objects of ``size`` symbols from the
    ``generator`` with ``params``, object ``i`` drawn from the random
    stream ``(seed, i)``."""
    make = function("generators", spec["generator"], "make")
    return {f"{spec['prefix']}{i}": make(
                np.random.default_rng([int(seed) % 2 ** 63, i]),
                int(spec["size"]), **spec["params"])
            for i in range(int(spec["count"]))}
