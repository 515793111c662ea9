"""Median ``dispatch`` span of a request's ticket trace (plan resolution
for its group: memo lookup, or fused plan and slab build), in ms."""

from bench.stats import median


def read(run):
    return median([run.span_ms(t, "dispatch") for t in run.tickets])
