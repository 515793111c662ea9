"""Find an open-loop cell's knee: the highest offered rate the service
sustains without a growing backlog.

    python3 bench/sweep.py --workload catalog.zipf.open --seed 7 \\
        --seconds 10 --rates 5 10 15 20 30

One process builds the cell once, then offers each rate for ``--seconds``
(lowest first) with the cell's mix at that rate.  For each rate it prints
one JSON line: the p50 and p95 latency, the share answered inside the
window, and the backlog growth, the median latency of the window's last
quarter of requests over that of its first quarter (about 1 while the
service keeps up, growing with the window while it does not).  Used once
to choose the rate written into the mix file; the cell's runs never sweep.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"),
                os.path.dirname(HERE)]

from bench import load, run, schedule, stats  # noqa: E402


def quarter_growth(window: load.Window) -> float | None:
    reqs = sorted(window.requests, key=lambda r: r.t0)
    q = len(reqs) // 4
    first = stats.median([r.latency_s for r in reqs[:q] if r.done])
    last = stats.median([r.latency_s for r in reqs[-q:] if r.done])
    return None if not first or last is None else last / first


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    _bench, cell, config, mix = run.load_cell(args.workload)
    if mix["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: needs a TPU")
    dep, _setup = run.build(config, mix, args.seed)
    names = list(dep.objects)
    for rate in sorted(args.rates):
        plan = schedule.open_schedule(dict(mix, rate_hz=rate), names,
                                      args.seed, args.seconds)
        sample = load.Reservoir(1, args.seed)
        t = time.perf_counter()
        window = load.run_open(dep.svc, plan.offsets_s, plan.requests,
                               mix["deadline"], args.seconds, sample,
                               contextlib.nullcontext())
        lat = [r.latency_s for r in window.requests]
        inside = sum(r.done is not None and r.done <= window.t_close
                     for r in window.requests)
        print(json.dumps({
            "rate_hz": rate, "requests": len(lat),
            "p50_ms": stats.tail_ms(lat, 0.5, 1e9),
            "p95_ms": stats.tail_ms(lat, 0.95, 1e9),
            "answered_in_window": inside / len(lat),
            "failed": sum(r.done is None for r in window.requests),
            "backlog_growth": quarter_growth(window),
            "drain_s": time.perf_counter() - t - args.seconds,
        }), flush=True)
    dep.close()


if __name__ == "__main__":
    main()
