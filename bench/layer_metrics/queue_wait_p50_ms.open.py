"""Median time a request waits in the broker (lane ``queue`` plus group
``coalesce`` spans of its ticket trace), in ms."""

from bench.stats import median


def read(run):
    return median([run.span_ms(t, "queue", "coalesce") for t in run.tickets])
