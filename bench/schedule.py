"""The one traffic generator: a mix file's parameters -> request schedules.

A mix (``bench/traffic/<mix>.json``) fixes the *shape* of the load from its
own ``shape_seed``: how many requests, their arrival times, their (object
rank, capability) pairs.  The run's ``--seed`` only deals that fixed
multiset of requests onto the fixed arrival times in another order and maps
popularity ranks onto the objects, so every seed offers the same work at
the same moments.  (Permuting the gaps as well moved an open-loop cell's
95th percentile by +-13% from seed to seed on a TPU v5e, against 3%
between two runs of one seed: the seed changed where the bursts fell.)

Keys of a mix file:

  loop          ``"closed"`` (each client waits for its answer before it
                sends the next request) or ``"open"`` (requests are sent
                on a schedule, whatever the service does);
  capabilities  client decode parallelisms (thread counts) the mix uses;
  popularity    ``{"kind": "uniform"}`` or ``{"kind": "zipf", "constant":
                c}`` over the configuration's objects (rank ``r`` has weight
                ``r ** -c``, YCSB's zipfian generator), or, closed loop
                only, ``{"kind": "own"}``: client ``k`` always fetches the
                object of rank ``k`` (modulo the object count), so clients
                that are served together fetch different objects;
  clients       closed loop: clients per capability;
  rate_hz       open loop: the offered rate (Poisson arrivals);
  deadline      the broker deadline class every request carries;
  check_sample  how many answers a run keeps to compare with the source.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: Draws per closed-loop client; a client cycles through its list.
CLOSED_DRAWS = 4096


def _run_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 63, *stream])


def _rank_weights(popularity: dict, n_objects: int) -> np.ndarray:
    kind = popularity["kind"]
    if kind == "uniform":
        w = np.ones(n_objects)
    elif kind == "zipf":
        w = np.arange(1, n_objects + 1, dtype=np.float64) ** -float(
            popularity["constant"])
    else:
        raise ValueError(f"unknown popularity kind {kind!r}")
    return w / w.sum()


def _rank_to_name(names: list[str], seed: int) -> list[str]:
    """Which object holds which popularity rank: a permutation drawn from
    the run seed (rank 0 is the hottest)."""
    order = _run_rng(seed, 1).permutation(len(names))
    return [names[i] for i in order]


@dataclasses.dataclass(frozen=True)
class OpenSchedule:
    offsets_s: np.ndarray          # due time of each request, from the open
    requests: list[tuple[str, int]]  # (object name, capability) per request


def open_schedule(mix: dict, names: list[str], seed: int,
                  seconds: float) -> OpenSchedule:
    """``round(rate * seconds)`` requests with exponential gaps that sum to
    the window's expected span; the requests are dealt onto those arrival
    times in an order drawn from ``seed``."""
    shape = np.random.default_rng(int(mix["shape_seed"]))
    n = max(1, int(round(float(mix["rate_hz"]) * seconds)))
    gaps = shape.exponential(1.0, size=n)
    gaps *= seconds * n / (n + 1) / gaps.sum()
    caps = list(mix["capabilities"])
    ranks = shape.choice(len(names), size=n,
                         p=_rank_weights(mix["popularity"], len(names)))
    cap_of = shape.integers(len(caps), size=n)
    order = _run_rng(seed, 2).permutation(n)
    by_rank = _rank_to_name(names, seed)
    requests = [(by_rank[ranks[i]], int(caps[cap_of[i]])) for i in order]
    return OpenSchedule(offsets_s=np.cumsum(gaps), requests=requests)


def closed_clients(mix: dict, names: list[str],
                   seed: int) -> list[tuple[int, list[str]]]:
    """One ``(capability, object sequence)`` per closed-loop client; each
    client cycles through its sequence."""
    shape = np.random.default_rng(int(mix["shape_seed"]))
    by_rank = _rank_to_name(names, seed)
    out = []
    if mix["popularity"]["kind"] == "own":
        caps = [int(cap) for cap in mix["capabilities"]
                for _ in range(int(mix["clients"]))]
        return [(cap, [by_rank[k % len(names)]])
                for k, cap in enumerate(caps)]
    weights = _rank_weights(mix["popularity"], len(names))
    for c, cap in enumerate(mix["capabilities"]):
        for k in range(int(mix["clients"])):
            ranks = shape.choice(len(names), size=CLOSED_DRAWS, p=weights)
            ranks = ranks[_run_rng(seed, 3, c, k).permutation(CLOSED_DRAWS)]
            out.append((int(cap), [by_rank[r] for r in ranks]))
    return out
