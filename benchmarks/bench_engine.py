"""Decode-engine steady state: cache-warm DecoderSession vs the one-shot path.

The one-shot flow (``walk_decode_batch`` per request) re-traces and
re-compiles for every distinct input size because the walk's scan length and
output size are static under jit — a server sweeping request sizes pays a
compile per size.  The engine pads every shape knob to power-of-two buckets
(DESIGN.md §4), so the whole sweep runs one AOT-compiled executable.

Measured here (jnp impl; the Pallas kernel's mode follows the platform —
interpreted on CPU, which times Python, not hardware; EXPERIMENTS.md §Perf):

  * cold:  one pass over ``len(SIZES)`` distinct request sizes through
           ``walk_decode_batch`` — each size jit-compiles, as in production
           today;
  * warm:  the same requests through one ``DecoderSession`` after a single
           warm-up pass — plus the recompile count across the measured
           sweep, which must be 0 (all sizes share one bucket).

Two serving-tier rows ride along (this PR's plan/executor split):

  * microbatch: 8 concurrent small requests through ``DecodeService`` —
    sequential dispatch (one executable call per request) vs coalesced
    (``submit``/``flush``: ONE fused executable call, per-request slices
    out).  Small requests are overhead-dominated, which is exactly the
    traffic microbatching exists for; the coalesced row must show >= 1.5x
    request throughput.
  * sharded: the warm size sweep through the multi-device executor
    (``impl="sharded"`` over a 1-D mesh of every visible device), with the
    same 0-recompiles regression.  Skipped (and marked so in the JSON) on
    single-device containers; CI runs it under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

Writes ``benchmarks/results/engine.json`` — ``engine_multidev.json`` when
more than one device is visible, so the CI multi-device run doesn't
clobber the single-device artifact — and returns CSV rows for the run.py
driver.
"""

from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.core import recoil
from repro.core.engine import DecoderSession
from repro.core.rans import RansParams, StaticModel
from repro.core.recoil import build_split_states
from repro.core.vectorized import (WalkBatch, encode_interleaved_fast,
                                   walk_decode_batch)
from repro.runtime.serve import DecodeService

from . import datasets

# Request-size sweeps chosen so stream words (~0.44 words/symbol on the
# lam=50 exponential dataset), output symbols, and walk steps all land in
# ONE shape bucket — the steady state the engine is built for.
QUICK_SIZES = (1_600_000, 1_750_000, 1_900_000, 2_000_000)   # 2 MB dataset
FULL_SIZES = (6_500_000, 7_200_000, 7_800_000, 8_300_000)    # 10 MB dataset
N_SPLITS = 64

# Microbatch tier: 8 concurrent small requests (the overhead-dominated
# regime; ~2 KB payloads at 16-way client parallelism).
MICRO_REQS = 8
MICRO_SIZE = 2_000
MICRO_SPLITS = 16


def run(quick: bool = False, repeats: int = 3) -> list:
    sizes = QUICK_SIZES if quick else FULL_SIZES
    syms = datasets.rand_exponential(50, max(sizes))
    params = RansParams(n_bits=11, ways=32)
    model = StaticModel.from_symbols(syms, 256, params)

    reqs = []
    for n in sizes:
        enc = encode_interleaved_fast(syms[:n], model)
        plan = recoil.plan_splits(enc, N_SPLITS)
        batch = WalkBatch.from_splits(
            build_split_states(plan, enc.final_states), plan.ways)
        reqs.append({"n": n, "enc": enc, "plan": plan, "batch": batch,
                     "syms": syms[:n]})
    sweep_mb = sum(n for n in sizes) / 1e6

    # ---- correctness, untimed: both paths verified once up front (the
    # timed regions below measure decode only, symmetrically)
    sess = DecoderSession(model, impl="jnp")
    for r in reqs:
        r["ds"] = sess.upload_stream(r["enc"].stream)
        out = np.asarray(
            sess.decode(r["plan"], r["ds"], r["enc"].final_states))
        assert (out == syms[:r["n"]]).all()
        assert (walk_decode_batch(r["batch"], r["enc"].stream, model,
                                  r["n"]) == syms[:r["n"]]).all()

    # ---- cold: per-request one-shot flow; each distinct size re-compiles
    # (clear jit caches so the verification pass above doesn't pre-warm it;
    # the session's AOT executables are unaffected)
    jax.clear_caches()
    t0 = time.perf_counter()
    for r in reqs:
        walk_decode_batch(r["batch"], r["enc"].stream, model, r["n"])
    cold_s = time.perf_counter() - t0

    # ---- warm: same requests through the resident session
    compiles_before = sess.stats.compiles
    warm_ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for r in reqs:
            jax.block_until_ready(
                sess.decode(r["plan"], r["ds"], r["enc"].final_states))
        warm_ts.append(time.perf_counter() - t0)
    warm_s = float(np.median(warm_ts))
    recompiles = sess.stats.compiles - compiles_before

    summary = {
        "sizes": list(sizes),
        "n_splits": N_SPLITS,
        "sweep_mb": sweep_mb,
        "cold_mb_per_s": round(sweep_mb / cold_s, 2),
        "warm_mb_per_s": round(sweep_mb / warm_s, 2),
        "speedup": round(cold_s / warm_s, 2),
        "recompiles_warm_sweep": recompiles,
        "engine_executables": len(sess._exec),
        "engine_stats": sess.stats.snapshot(),
    }
    rows = [{"bench": "engine", "path": "cold_per_call", "sizes": len(sizes),
             "mb_per_s": summary["cold_mb_per_s"],
             "recompiles": len(sizes)},
            {"bench": "engine", "path": "session_warm", "sizes": len(sizes),
             "mb_per_s": summary["warm_mb_per_s"],
             "recompiles": recompiles}]

    summary["layout_symbol"] = _bench_symbol_layout(model, reqs, sweep_mb,
                                                    warm_s, repeats)
    rows.append({"bench": "engine", "path": "layout_symbol_warm",
                 "sizes": len(sizes),
                 "mb_per_s": summary["layout_symbol"]["warm_mb_per_s"],
                 "recompiles":
                     summary["layout_symbol"]["recompiles_warm_sweep"]})

    summary["microbatch"] = _bench_microbatch(model, repeats)
    rows += [
        {"bench": "engine", "path": "microbatch_sequential",
         "sizes": MICRO_REQS,
         "req_per_s": summary["microbatch"]["sequential_req_per_s"],
         "recompiles": 0},
        {"bench": "engine", "path": "microbatch_coalesced",
         "sizes": MICRO_REQS,
         "req_per_s": summary["microbatch"]["coalesced_req_per_s"],
         "recompiles": summary["microbatch"]["recompiles_warm"]},
    ]

    summary["sharded"] = _bench_sharded(model, reqs, sweep_mb, repeats)
    if not summary["sharded"].get("skipped"):
        rows.append({"bench": "engine", "path": "sharded_warm",
                     "sizes": len(sizes),
                     "mb_per_s": summary["sharded"]["warm_mb_per_s"],
                     "recompiles": summary["sharded"]["recompiles_warm_sweep"]})

    os.makedirs("benchmarks/results", exist_ok=True)
    name = "engine.json" if len(jax.devices()) == 1 else "engine_multidev.json"
    with open(f"benchmarks/results/{name}", "w") as f:
        json.dump(summary, f, indent=2)
    return rows


def _bench_symbol_layout(model: StaticModel, reqs: list, sweep_mb: float,
                         warm_pointer_s: float, repeats: int) -> dict:
    """The pointer-free symbol-indexed layout (DESIGN.md §9) on the same
    warm size sweep: content registered WITH its emission log, decode walk
    gathers ``words_by_symbol`` rows as pre-hoisted scan inputs — no stream
    pointer, no per-step renorm cumsum in the carry.  Reported against the
    pointer walk's warm sweep (identical requests, identical buckets); the
    CI floor is >= 1.15x with 0 warm recompiles."""
    from repro.core.engine import with_symbol_layout

    sess = DecoderSession(model, impl="jnp", layout="symbol")
    handles = [
        with_symbol_layout(sess.upload_stream(r["enc"].stream),
                           r["enc"].k_of_word, r["n"]) for r in reqs]
    for r, ds in zip(reqs, handles):   # warm + verify, untimed
        out = np.asarray(sess.decode(r["plan"], ds, r["enc"].final_states))
        assert (out == r["syms"]).all()
    compiles_before = sess.stats.compiles
    warm_ts = []
    for _ in range(max(repeats, 5)):
        t0 = time.perf_counter()
        for r, ds in zip(reqs, handles):
            jax.block_until_ready(
                sess.decode(r["plan"], ds, r["enc"].final_states))
        warm_ts.append(time.perf_counter() - t0)
    warm_s = float(np.median(warm_ts))
    return {
        "layout": "symbol",
        "warm_mb_per_s": round(sweep_mb / warm_s, 2),
        "pointer_warm_mb_per_s": round(sweep_mb / warm_pointer_s, 2),
        "speedup_vs_pointer": round(warm_pointer_s / warm_s, 2),
        "recompiles_warm_sweep": sess.stats.compiles - compiles_before,
        "layout_plans": dict(sess.executor.layout_plans),
        "engine_stats": sess.stats.snapshot(),
    }


def _bench_microbatch(model: StaticModel, repeats: int) -> dict:
    """8 concurrent small requests: sequential dispatch vs one fused call.

    Both paths are plan-warm and executable-warm before timing (the service
    memoizes thinned plans per (name, threads) and fused plans per request
    group), so the comparison is pure dispatch: 8 executable calls vs 1.
    """
    rng = np.random.default_rng(11)
    payloads = {
        f"r{i}": np.minimum(
            rng.exponential(50.0, size=MICRO_SIZE).astype(np.int64), 255)
        for i in range(MICRO_REQS)}
    svc = DecodeService(model, impl="jnp", microbatch=MICRO_REQS)
    for name, syms in payloads.items():
        enc = encode_interleaved_fast(syms, model)
        svc.register(name, recoil.plan_splits(enc, MICRO_SPLITS),
                     enc.stream, enc.final_states)
    names = list(payloads)

    # warm + verify both paths once, untimed
    for name in names:
        assert (np.asarray(svc.decode(name, MICRO_SPLITS))
                == payloads[name]).all()
    tickets = [svc.submit(n, MICRO_SPLITS) for n in names]
    svc.flush()
    for name, t in zip(names, tickets):
        assert (np.asarray(t.result()) == payloads[name]).all()

    compiles_before = svc.stats.compiles
    seq_ts, coal_ts = [], []
    for _ in range(max(repeats, 5)):
        t0 = time.perf_counter()
        for name in names:
            jax.block_until_ready(svc.decode(name, MICRO_SPLITS))
        seq_ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        tickets = [svc.submit(n, MICRO_SPLITS) for n in names]
        svc.flush()
        for t in tickets:
            jax.block_until_ready(t.result())
        coal_ts.append(time.perf_counter() - t0)
    seq_s, coal_s = float(np.median(seq_ts)), float(np.median(coal_ts))
    return {
        "n_requests": MICRO_REQS,
        "request_symbols": MICRO_SIZE,
        "request_splits": MICRO_SPLITS,
        "sequential_req_per_s": round(MICRO_REQS / seq_s, 1),
        "coalesced_req_per_s": round(MICRO_REQS / coal_s, 1),
        "speedup": round(seq_s / coal_s, 2),
        "recompiles_warm": svc.stats.compiles - compiles_before,
        "service_stats": svc.stats.snapshot(),
        # Per-plan-key compile times (DESIGN.md §13): which shapes paid
        # compilation.
        "profiler": svc.obs.profiler.snapshot(top=4),
    }


def _bench_sharded(model: StaticModel, reqs: list, sweep_mb: float,
                   repeats: int) -> dict:
    """Warm size sweep through the multi-device sharded executor."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"skipped": True, "n_devices": n_dev}
    sess = DecoderSession(model, impl="sharded")
    handles = [sess.upload_stream(r["enc"].stream) for r in reqs]
    for r, ds in zip(reqs, handles):   # warm + verify, untimed
        out = np.asarray(sess.decode(r["plan"], ds, r["enc"].final_states))
        assert (out == r["syms"]).all()
    compiles_before = sess.stats.compiles
    warm_ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for r, ds in zip(reqs, handles):
            jax.block_until_ready(
                sess.decode(r["plan"], ds, r["enc"].final_states))
        warm_ts.append(time.perf_counter() - t0)
    warm_s = float(np.median(warm_ts))
    return {
        "n_devices": n_dev,
        "warm_mb_per_s": round(sweep_mb / warm_s, 2),
        "recompiles_warm_sweep": sess.stats.compiles - compiles_before,
        "engine_stats": sess.stats.snapshot(),
    }
