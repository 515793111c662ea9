"""Readings behind the correctness limits: the program and its control.

    python3 bench/control.py --workload rand50.c2048.bulk --seconds 5 \\
        --seeds 11 12 13

For each seed, one process builds the cell once and drives two short
windows of its mix: first the program as it is, then its control.  The
control is the program with one stated guarantee broken where the answer
is produced: every configuration states lossless bytes (symbols 0..255 as
int32), and the control emits them one precision lower, as signed int8,
the step an 8-bit output path would tempt, so bytes of 128 and above wrap.
Each window prints its checks (``wrong_symbols`` and the rest) as one JSON
line.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"),
                os.path.dirname(HERE)]

from bench import run  # noqa: E402


def int8_control(dep: run.Deployment) -> None:
    """Make the service's decode emit its symbols through int8."""
    import jax.numpy as jnp
    session = dep.svc.session
    execute = session.execute
    session.execute = lambda plan: execute(plan).astype(jnp.int8).astype(
        jnp.int32)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    _bench, _cell, config, mix = run.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("control: needs a TPU")
    for seed in args.seeds:
        dep, setup = run.build(config, mix, seed)
        for which in ("program", "control"):
            if which == "control":
                int8_control(dep)
            res = run.measure(args.workload, dep, setup, mix, [], seed,
                              args.seconds, False, time.perf_counter(),
                              close=which == "control")
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "run": which, "correct": res["correct"],
                              "attempted": res["attempted"],
                              "checks": res["checks"]}), flush=True)


if __name__ == "__main__":
    main()
