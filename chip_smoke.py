"""Chip smoke test: the served decode path, end to end, on a TPU.

    python chip_smoke.py               # one chip: Pallas kernel + XLA walk
    python chip_smoke.py --four-chips  # sharded decode over a 4-chip mesh

One process owns the chip(s).  The run builds the paper's 10 MB ``rand_50``
asset (``benchmarks.datasets.rand_exponential(50)``, seeded), ingests it on
the device at 2048 splits (W = 32, n = 11, packed LUT) and decodes it through
``DecodeService`` and its ``PipelineBroker`` at several client capabilities:

  * one chip: capabilities 2048, 256 and 16 on ``impl="pallas"`` (the
    Mosaic-compiled walk kernel; at 16 splits it walks 24,576 steps in T
    tiles) and 2048 and 64 on ``impl="jnp"`` (the XLA walk);
  * ``--four-chips``: capabilities 2048 and 64 on ``impl="sharded"`` over a
    four-device mesh, against the one-chip XLA walk on device 0.

Every output must equal the input symbols, the backends must agree, and the
broker must report no dispatch error and no worker restart.  Earlier lines
print set-up facts (device kind, compile counts, wall time per phase, peak
device memory); they are not benchmark metrics.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script exits nonzero, and prints no such line, when JAX finds no TPU or
any phase fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402

ASSET = "rand_50"
N_SPLITS = 2048
PALLAS_CAPS = (2048, 256, 16)
JNP_CAPS = (2048, 64)
SHARDED_CAPS = (2048, 64)
REPEATS = 2            # broker submits per capability
RESULT_TIMEOUT_S = 600.0


def log(msg: str) -> None:
    print(msg, flush=True)


def make_asset(size: int | None = None):
    """The seeded ``rand_50`` symbols and their static model (n = 11,
    W = 32; 8-bit symbols, so the session picks the packed LUT)."""
    from benchmarks.datasets import rand_exponential
    from repro.core.rans import RansParams, StaticModel
    syms = (rand_exponential(50) if size is None
            else rand_exponential(50, size=size))
    model = StaticModel.from_symbols(syms, 256,
                                     RansParams(n_bits=11, ways=32))
    return syms, model


def serve(svc, caps, syms: np.ndarray) -> dict:
    """Submit ``REPEATS`` decodes per capability through the broker, check
    each against ``syms`` and return ``{capability: output}``."""
    broker = svc.start_pipeline()
    try:
        tickets = [(cap, svc.submit(ASSET, cap))
                   for cap in caps for _ in range(REPEATS)]
        outs = {}
        for cap, ticket in tickets:
            out = np.asarray(ticket.result(timeout=RESULT_TIMEOUT_S))
            if not np.array_equal(out, syms):
                raise AssertionError(
                    f"impl={svc.session.impl} at capability {cap}: output "
                    f"differs from the input symbols")
            outs[cap] = out
        snap = broker.snapshot()
    finally:
        svc.stop_pipeline()
    if snap["dispatch_errors"] or snap["worker_restarts"]:
        raise AssertionError(
            f"impl={svc.session.impl}: broker reported dispatch_errors="
            f"{snap['dispatch_errors']} worker_restarts="
            f"{snap['worker_restarts']}")
    st = svc.stats
    log(f"  impl={svc.session.impl} caps={list(caps)}: "
        f"{snap['completed']} decodes bit-exact, "
        f"{snap['dispatch_groups']} dispatch groups, "
        f"{st.compiles} decode compiles, layout={svc.layout_for(ASSET)}")
    return outs


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def one_chip(syms: np.ndarray, model, n_splits: int = N_SPLITS,
             pallas_caps=PALLAS_CAPS, jnp_caps=JNP_CAPS) -> None:
    """Ingest once on the Pallas service, serve it there, re-register the
    same device-resident content with an XLA-walk service, serve it there
    and compare the two backends."""
    from repro.runtime.serve import DecodeService

    t0 = time.perf_counter()
    pallas = DecodeService(model, impl="pallas")
    plan = pallas.ingest(ASSET, syms, n_splits)
    content = pallas.content(ASSET)
    jax.block_until_ready(content.stream.by_symbol)
    log(f"ingest: {len(syms)} symbols -> {plan.n_threads} splits, "
        f"{content.stream.n_words} words, "
        f"{pallas.stats.encode_compiles} encode compiles, "
        f"{time.perf_counter() - t0:.3f} s")

    t0 = time.perf_counter()
    mode = "interpreted" if pallas.session.executor.interpret else "compiled"
    log(f"pallas ({mode}, packed LUT={pallas.session.packed_lut}):")
    out_p = serve(pallas, pallas_caps, syms)
    log(f"  {time.perf_counter() - t0:.3f} s")

    t0 = time.perf_counter()
    xla = DecodeService(model, impl="jnp")
    xla.register(ASSET, content.plan, content.stream, content.final_states)
    log("jnp (XLA walk):")
    out_j = serve(xla, jnp_caps, syms)
    log(f"  {time.perf_counter() - t0:.3f} s")

    for cap in sorted(set(out_p) & set(out_j)):
        if not np.array_equal(out_p[cap], out_j[cap]):
            raise AssertionError(f"pallas and jnp disagree at capability "
                                 f"{cap}")
    log(f"pallas == jnp at capabilities {sorted(set(out_p) & set(out_j))}")


def four_chips(syms: np.ndarray, model, n_splits: int = N_SPLITS,
               caps=SHARDED_CAPS) -> None:
    """Sharded decode over a four-device mesh against the one-chip XLA
    walk on device 0, and evidence that data and work span the mesh."""
    from repro.launch.mesh import make_decode_mesh
    from repro.runtime.serve import DecodeService

    devices = jax.devices()
    if len(devices) != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, JAX sees "
                           f"{len(devices)}")
    t0 = time.perf_counter()
    sharded = DecodeService(model, impl="sharded", mesh=make_decode_mesh(4))
    plan = sharded.ingest(ASSET, syms, n_splits)
    content = sharded.content(ASSET)
    log(f"ingest: {len(syms)} symbols -> {plan.n_threads} splits, "
        f"{time.perf_counter() - t0:.3f} s")

    t0 = time.perf_counter()
    log(f"sharded over {sharded.session.executor.n_shards} devices:")
    out_s = serve(sharded, caps, syms)
    log(f"  {time.perf_counter() - t0:.3f} s")

    # The plan's split rows and stream slabs are row-sharded: each device
    # must hold its own distinct shard.
    dplan = sharded.prepare_request(ASSET, max(caps))
    slabs = dplan.args[0]
    shards = slabs.addressable_shards
    holders = {s.device.id for s in shards}
    log(f"slabs {slabs.shape} sharded as {slabs.sharding.spec}: "
        + ", ".join(f"device {s.device.id} rows {s.index[0]}"
                    for s in shards))
    if len(holders) != 4 or slabs.sharding.is_fully_replicated:
        raise AssertionError(f"stream slabs are not split over 4 devices: "
                             f"{slabs.sharding}")
    for arr in dplan.args[4:]:
        if len(arr.sharding.device_set) != 4:
            raise AssertionError(f"split array on {arr.sharding}")
    shard_bytes = min(s.data.nbytes for s in shards)
    peaks = {d.id: peak_bytes(d) for d in devices}
    log(f"peak_bytes_in_use per device: {peaks}")
    if any(p is not None and p < shard_bytes for p in peaks.values()):
        raise AssertionError("a device never held its slab shard")

    t0 = time.perf_counter()
    xla = DecodeService(model, impl="jnp")
    xla.register(ASSET, content.plan, content.stream, content.final_states)
    log(f"jnp (XLA walk) on device {devices[0].id}:")
    out_j = serve(xla, caps, syms)
    log(f"  {time.perf_counter() - t0:.3f} s")
    for cap in caps:
        if not np.array_equal(out_s[cap], out_j[cap]):
            raise AssertionError(f"sharded and one-chip outputs differ at "
                                 f"capability {cap}")
    log(f"sharded == one-chip jnp at capabilities {list(caps)}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded decode over four chips")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX's default device is "
                 f"{dev.platform!r}")
    from repro.launch.cache import use_compile_cache
    log(f"device: {dev.device_kind} x{len(devices)}; compile cache: "
        f"{use_compile_cache()}")

    t0 = time.perf_counter()
    syms, model = make_asset()
    log(f"asset: {ASSET}, {len(syms)} symbols, "
        f"{time.perf_counter() - t0:.3f} s")
    if args.four_chips:
        four_chips(syms, model)
    else:
        one_chip(syms, model)
        log(f"peak_bytes_in_use: {peak_bytes(dev)}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
