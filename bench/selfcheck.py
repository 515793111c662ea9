"""Self-check of the benchmark's yardstick, on the CPU.

    python3 bench/selfcheck.py

1. ``trace_reduce`` on a small trace recorded on a TPU v5e
   (``bench/selfcheck/catalog_open.xplane.pb.xz``: a 2 s
   ``catalog.zipf.open`` window, xz-compressed) against a plain recomputation: busy time as the union of the
   device op intervals on a 1 us grid, op time as the sum of durations,
   and idle gaps that fit in the window's idle time.
2. The metric arithmetic: a tail over every request with failures counted
   as missing, a rate over the whole window that counts only answers
   inside it.

Prints ``selfcheck ok`` and exits 0, or names the first failed check and
exits 1.
"""

from __future__ import annotations

import lzma
import os
import sys
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"),
                os.path.dirname(HERE)]

from bench import load, run, stats, trace_reduce  # noqa: E402

TRACE = os.path.join(HERE, "selfcheck", "catalog_open.xplane.pb.xz")
KERNEL = "walk_decode_symbol_pallas"


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"selfcheck failed: {what}")


def check_trace() -> None:
    from jax.profiler import ProfileData
    with lzma.open(TRACE) as f:
        planes = list(ProfileData.from_serialized_xspace(f.read()).planes)
    red = trace_reduce.reduce_planes(planes)
    lo, hi = trace_reduce._window(planes)
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    summed, n_events = 0.0, 0
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for e in line.events:
                a = max(e.start_ns, lo)
                b = min(e.start_ns + e.duration_ns, hi)
                if b > a:
                    grid[int((a - lo) // 1000):int(-(-(b - lo) // 1000))] = 1
                    summed += b - a
                    n_events += 1
    busy_grid = grid.sum() * 1e-6
    check(n_events > 0, "the recorded trace has device ops in its window")
    check(abs(red.window_s - (hi - lo) * 1e-9) < 1e-9, "window length")
    check(0 < red.busy_s <= red.window_s, "busy time inside the window")
    check(abs(red.busy_s - busy_grid) <= 2e-6 * n_events + 1e-6,
          f"busy {red.busy_s} vs grid union {busy_grid}")
    check(abs(sum(red.op_seconds.values()) - summed * 1e-9) < 1e-9,
          "op seconds sum the clipped durations")
    idle = red.window_s - red.busy_s
    check(sum(s for _, s in red.gaps) <= idle + 1e-9,
          "named gaps fit in the idle time")
    check(len(red.gaps) <= trace_reduce.TOP_GAPS, "at most TOP_GAPS gaps")
    check(red.seconds_matching(KERNEL) > 0, "the walk kernel is found")
    check(trace_reduce.union_ns([(0, 2), (1, 3), (5, 6)]) == 4, "union")
    check(trace_reduce.idle_intervals([(1, 2), (4, 5)], 0, 6)
          == [(0, 1), (2, 4), (5, 6)], "idle intervals")


def check_arithmetic() -> None:
    lat = [0.010, 0.020, None, 0.030]
    check(stats.tail_ms(lat, 0.5, 99.0) == 20.0, "median by nearest rank")
    check(stats.tail_ms(lat, 0.95, 99.0) == 99000.0,
          "a failed request counts as missing, slower than any success")
    check(abs(stats.tail_ms([0.001 * i for i in range(1, 101)], 0.95, 9.0)
              - 95.0) < 1e-9, "p95 over every sample")
    check(stats.rate(50.0, 10.0) == 5.0, "rate over the whole window")
    check(stats.median([3, 1, 2, 4]) == 2.5, "median of an even count")

    reqs = [load.Request("a", 1, 0.0, done=1.0, status="ok"),
            load.Request("a", 1, 1.0, done=2.5, status="ok"),
            load.Request("a", 1, 9.0, done=10.5, status="ok"),
            load.Request("a", 1, 9.5, status="rejected")]
    fake = types.SimpleNamespace(
        requests=reqs, window_s=10.0, t_open=0.0, t_close=10.0,
        missing_s=70.0, sizes={"a": 1_000_000}, setup_s=1.0)
    fake.answered_in_window = lambda: run.Run.answered_in_window(fake)
    mbps = run.reader("end_to_end", "decoded_MBps")(fake)
    check(abs(mbps - 0.2) < 1e-12,
          f"decoded_MBps counts only answers inside the window: {mbps}")
    p95 = run.reader("end_to_end", "fetch_p95_ms")(fake)
    check(p95 == 70_000.0, f"fetch_p95_ms counts the refused one: {p95}")


def main() -> None:
    check_trace()
    check_arithmetic()
    print("selfcheck ok")


if __name__ == "__main__":
    main()
