"""95th percentile of every request's latency in the window, in ms.  A
refused, failed or unanswered request counts as slower than every
success."""

from bench.stats import tail_ms


def read(run):
    return tail_ms([r.latency_s for r in run.requests], 0.95, run.missing_s)
