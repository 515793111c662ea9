"""Paper Figure 7 (CPU side): decode throughput of Single-Thread vs
Conventional vs Recoil at matched split counts.

On CPU the measured numbers are for the XLA:CPU lowering of the SAME
group-stepped walk the Pallas TPU kernel implements; the kernel's mode
follows the platform, and on CPU it runs interpreted (not timed —
interpret mode measures Python, not TPUs; see EXPERIMENTS.md §Perf for the
kernel's roofline-based analysis).  The paper's claims reproduced here:

  * Recoil decode throughput ~= Conventional at the same parallelism;
  * both scale with split count while Single-Thread does not;
  * combining metadata does not change Recoil's per-split throughput.

Rows: variant, splits, n_bits, MB/s (median of `repeats` runs).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import conventional, recoil
from repro.core.engine import DecoderSession
from repro.core.rans import RansParams, StaticModel
from repro.core.recoil import build_split_states
from repro.core.vectorized import (WalkBatch, encode_interleaved_fast,
                                   walk_decode_batch)
from repro.core.conventional import to_split_states

from . import datasets


def _time(fn, repeats: int):
    ts = []
    fn()  # warm (jit)
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run(size: int = 0, quick: bool = False, repeats: int = 3) -> list:
    size = size or (2 * datasets.MB if quick else 10 * datasets.MB)
    syms = datasets.rand_exponential(50, size)
    mb = len(syms) / 1e6
    rows = []
    for n_bits in ((11,) if quick else (11, 16)):
        params = RansParams(n_bits=n_bits, ways=32)
        model = StaticModel.from_symbols(syms, 256, params)
        enc = encode_interleaved_fast(syms, model)
        configs = [("single_thread", 1), ("recoil", 16), ("recoil", 256),
                   ("recoil", 2176), ("recoil_engine", 256),
                   ("conventional", 16), ("conventional", 2176)]
        plan_max = recoil.plan_splits(enc, 2176)
        sess = DecoderSession(model, impl="jnp")
        stream_dev = sess.upload_stream(enc.stream)
        for variant, m in configs:
            if variant == "conventional":
                conv = conventional.encode_conventional(syms, model, m)
                states, words, bases = to_split_states(conv)
                batch = WalkBatch.from_splits(states, 32, bases)
                fn = lambda: walk_decode_batch(batch, words, model, len(syms))
            elif variant == "recoil_engine":
                # warm DecoderSession at matched parallelism: same walk and
                # same prebuilt batch as the `recoil` rows, but stream
                # resident and executable cached (DESIGN.md §4)
                plan = recoil.combine_plan(plan_max, m)
                states = build_split_states(plan, enc.final_states)
                batch = WalkBatch.from_splits(states, 32)
                fn = lambda: np.asarray(sess.decode_batch(
                    batch, stream_dev, len(syms)))
            else:
                plan = recoil.combine_plan(plan_max, m)
                states = build_split_states(plan, enc.final_states)
                batch = WalkBatch.from_splits(states, 32)
                fn = lambda: walk_decode_batch(batch, enc.stream, model,
                                               len(syms))
            out = fn()
            assert (out == syms).all()
            dt = _time(fn, repeats)
            rows.append({"bench": "throughput", "variant": variant,
                         "splits": m, "n_bits": n_bits,
                         "mb_per_s": round(mb / dt, 2),
                         "ms_per_decode": round(dt * 1e3, 2)})
    return rows
