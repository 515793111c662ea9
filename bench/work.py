"""The least memory traffic one decode request needs, from its sizes alone.

A request reads its client's wire payload once (the compressed stream, the
thinned split metadata, the table and final states: the container a client
at that capability receives) and writes its decoded symbols once, one byte
each.  The walk is integer VPU work for which the published peaks give no
rate, so the request's bound time is these bytes at the chip's HBM peak.
The count depends only on sizes, so it reads the same whatever implements
the decode.
"""

from __future__ import annotations


def request_bytes(wire_bytes: int, n_symbols: int) -> int:
    return int(wire_bytes) + int(n_symbols)


def bound_seconds(total_bytes: float, peaks: dict) -> float:
    return float(total_bytes) / float(peaks["hbm_bytes_per_s"])
