"""Source bytes (one per symbol) of every request answered inside the
window, per second of the whole window, in MB/s."""

from bench.stats import rate


def read(run):
    done = run.answered_in_window()
    return rate(sum(run.sizes[r.name] for r in done), run.window_s) / 1e6
