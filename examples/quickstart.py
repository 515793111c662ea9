"""Quickstart: encode once, scale the metadata to any decoder, decode in
parallel — the paper's pipeline in ~30 lines.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import (RansParams, StaticModel, combine_plan, plan_splits,
                        serialize_plan)
from repro.core.vectorized import decode_recoil_fast, encode_interleaved_fast
from repro.kernels.rans_decode import decode_recoil_kernel

# --- data + model: 2 MB of skewed bytes, 11-bit quantized distribution ----
rng = np.random.default_rng(0)
symbols = np.minimum(rng.exponential(30, size=2_000_000).astype(np.int64), 255)
params = RansParams(n_bits=11, ways=32)          # paper Table 3
model = StaticModel.from_symbols(symbols, 256, params)

# --- encode ONCE at the server's max supported parallelism ---------------
encoded = encode_interleaved_fast(symbols, model)
plan = plan_splits(encoded, 2176)                # split metadata, no re-encode
print(f"stream: {encoded.stream_bytes():,} B   "
      f"metadata@2176: {len(serialize_plan(plan)):,} B")

# --- serve a 16-core client: combine splits by DELETING metadata ---------
small = combine_plan(plan, 16)
print(f"metadata@16:   {len(serialize_plan(small)):,} B "
      f"(same bitstream, no re-encode)")

# --- decode with both plans, on the jnp fast path and the Pallas kernel --
for name, p in [("client@2176", plan), ("client@16", small)]:
    out = decode_recoil_fast(p, encoded.stream, encoded.final_states, model)
    assert (out == symbols).all()
    print(f"{name}: jnp walk decode OK ({p.n_threads} threads)")

out = decode_recoil_kernel(combine_plan(plan, 64), encoded.stream,
                           encoded.final_states, model)
assert (out == symbols).all()
# This is the pointer-layout kernel: the platform picks its mode, and it
# runs interpreted on CPU (Mosaic does not compile it for TPU).
print("client@64: Pallas kernel OK")
