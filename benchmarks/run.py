"""Benchmark driver — one suite per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Emits CSV to stdout and benchmarks/results/*.csv.  Suites:

    compression       Tables 4-6   variations (a)-(e) per dataset x n
    partition_sweep   Figure 3     size vs #partitions, Conventional vs Recoil
    throughput        Figure 7     CPU decode MB/s at matched parallelism
    combine           §3.3         server-side metadata thinning latency
    engine            DESIGN §4    cache-warm DecoderSession vs one-shot path
    encode            DESIGN §5    cache-warm ingest engine vs host encode+plan
    pipeline          DESIGN §8    async broker vs synchronous serving loop
    streaming         DESIGN §10   incremental re-ingest + chunked first-chunk latency
    roofline          §Roofline    aggregates dry-run JSONs (if present)
    tuning            DESIGN §11   autotuned vs legacy bucket ladder + DB reuse
    predictive        DESIGN §12   speculative pre-thinning vs reactive cold path
    observability     DESIGN §13   tracing/metrics overhead + span decomposition
    reliability       DESIGN §14   fault-injection plumbing cost + fault-storm survival

Also writes ``benchmarks/results/BENCH_summary.json`` — one consolidated
machine-readable record per run (suite rows + per-suite wall time + the
standalone suite summaries such as tuning_bench.json) for cross-run
comparison in CI.
"""

from __future__ import annotations

import argparse
import csv
import json
import os
import sys
import time

from repro.launch.cache import use_compile_cache

from . import (bench_combine, bench_compression, bench_encode, bench_engine,
               bench_observability, bench_partition_sweep, bench_pipeline,
               bench_predictive, bench_reliability, bench_roofline,
               bench_streaming, bench_throughput, bench_tuning)

SUITES = {
    "compression": bench_compression.run,
    "partition_sweep": bench_partition_sweep.run,
    "throughput": bench_throughput.run,
    "combine": bench_combine.run,
    "engine": bench_engine.run,
    "encode": bench_encode.run,
    "pipeline": bench_pipeline.run,
    "streaming": bench_streaming.run,
    "roofline": bench_roofline.run,
    "tuning": bench_tuning.run,
    "predictive": bench_predictive.run,
    "observability": bench_observability.run,
    "reliability": bench_reliability.run,
}

# Suites that write their own guarded JSON summary; BENCH_summary.json
# inlines these so CI reads ONE artifact.
SUITE_SUMMARIES = {
    "tuning": "benchmarks/results/tuning_bench.json",
    "predictive": "benchmarks/results/predictive.json",
    "observability": "benchmarks/results/observability.json",
    "reliability": "benchmarks/results/reliability.json",
}


def write_summary(results: dict) -> None:
    path = "benchmarks/results/BENCH_summary.json"
    payload = {"quick": results.pop("_quick", False), "suites": {}}
    for name, entry in results.items():
        payload["suites"][name] = entry
        extra = SUITE_SUMMARIES.get(name)
        if extra and os.path.exists(extra):
            with open(extra) as f:
                payload["suites"][name]["summary"] = json.load(f)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"\nwrote {path}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small datasets / fewer variants (CI mode)")
    ap.add_argument("--only", default="", choices=["", *SUITES])
    args = ap.parse_args()
    use_compile_cache()
    os.makedirs("benchmarks/results", exist_ok=True)
    names = [args.only] if args.only else list(SUITES)
    summary = {"_quick": args.quick}
    for name in names:
        t0 = time.time()
        try:
            rows = SUITES[name](quick=args.quick)
        except TypeError:
            rows = SUITES[name]()
        dt = time.time() - t0
        print(f"\n## {name} ({dt:.1f}s)", flush=True)
        summary[name] = {"seconds": round(dt, 1), "rows": rows or []}
        if not rows:
            continue
        keys = sorted({k for r in rows for k in r})
        writer = csv.DictWriter(sys.stdout, fieldnames=keys)
        writer.writeheader()
        for r in rows:
            writer.writerow(r)
        with open(f"benchmarks/results/{name}.csv", "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=keys)
            w.writeheader()
            for r in rows:
                w.writerow(r)
    write_summary(summary)
    print("\nbenchmarks complete", flush=True)


if __name__ == "__main__":
    main()
