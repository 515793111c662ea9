"""The paper's content-delivery scenario end-to-end (§3.3, §5).

A server encodes content ONCE at max parallelism (2176 splits, GPU-grade).
Clients attach their parallel capacity to the request; the server thins the
split metadata in real time (no re-encode, no second stored variant) and
ships bitstream + right-sized metadata.  Every client decodes with its own
thread count and verifies the content.

Clients decode through a persistent :class:`repro.core.engine.DecoderSession`
— device-resident LUTs and a bucketed executable cache — so only a client's
FIRST fetch pays a compile; repeat fetches (even of different-sized payloads
within a shape bucket) run the cached executable (DESIGN.md §4).

    PYTHONPATH=src python examples/content_delivery.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import container, recoil
from repro.core.engine import DecoderSession
from repro.core.rans import RansParams, StaticModel


class ContentServer:
    """Encode once; serve any client parallelism by deleting metadata.

    Encoding runs through the ingest engine (``core.encode.EncoderSession``
    — bucketed executables, so re-encoding a refreshed payload of similar
    size never recompiles); this wire-format server materializes the
    stream for ``container`` packing, while the pure-serving path
    (``DecodeService.ingest``, see ``microbatch_demo``) keeps it on
    device end to end."""

    def __init__(self, payload: np.ndarray, max_splits: int = 2176):
        from repro.core.encode import EncoderSession
        self.params = RansParams(n_bits=11, ways=32)
        self.model = StaticModel.from_symbols(payload, 256, self.params)
        self.encoder = EncoderSession(self.model)
        t0 = time.perf_counter()
        self.enc = self.encoder.encode(payload)
        self.plan = recoil.plan_splits(self.enc, max_splits)
        self.encode_s = time.perf_counter() - t0

    def serve(self, client_threads: int) -> bytes:
        t0 = time.perf_counter()
        plan = recoil.combine_plan(self.plan, client_threads)
        buf = container.pack_recoil(self.enc, self.model, plan)
        self.last_serve_ms = (time.perf_counter() - t0) * 1e3
        return buf


class Client:
    """Holds a decode session across fetches — tables and compiled
    executables persist, so steady-state fetches never recompile."""

    def __init__(self, name: str, threads: int):
        self.name, self.threads = name, threads
        self.session = None

    def fetch_and_decode(self, server: ContentServer) -> np.ndarray:
        buf = server.serve(self.threads)
        self.received_bytes = len(buf)
        pc = container.parse(buf, server.params)
        if self.session is None:
            self.session = DecoderSession(pc.model, impl="jnp")
        t0 = time.perf_counter()
        out = self.session.decode(pc.plan, pc.stream, pc.final_states)
        out = np.asarray(out)  # sync for honest timing
        self.decode_s = time.perf_counter() - t0
        return out


def main():
    rng = np.random.default_rng(7)
    payload = np.minimum(rng.exponential(35, size=4_000_000).astype(np.int64),
                         255)
    server = ContentServer(payload)
    print(f"server: encoded {len(payload)/1e6:.0f} MB once in "
          f"{server.encode_s:.2f}s at {server.plan.n_threads} splits\n")
    clients = [Client("phone (2 cores)", 2),
               Client("laptop (16 cores)", 16),
               Client("workstation (256)", 256),
               Client("gpu-box (2176)", 2176)]
    for c in clients:
        out = c.fetch_and_decode(server)
        assert (out == payload).all(), f"{c.name}: decode mismatch!"
        print(f"{c.name:20s} fetched {c.received_bytes:>9,} B "
              f"(server thinning {server.last_serve_ms:6.1f} ms)  "
              f"decoded+verified in {c.decode_s:5.2f}s with "
              f"{c.threads} threads")
    big = clients[-1].received_bytes
    small = clients[0].received_bytes
    print(f"\nbandwidth saved for the phone vs shipping the GPU variation: "
          f"{big - small:,} B ({100 * (big - small) / big:.2f}%) — "
          f"the paper's decoder-adaptive scalability claim")

    # Steady state: the same clients fetch again — sessions are warm, the
    # second decode reuses the bucketed executable (0 new compiles).
    print("\nsecond fetch (warm sessions):")
    for c in clients:
        before = c.session.stats.compiles
        out = c.fetch_and_decode(server)
        assert (out == payload).all()
        print(f"{c.name:20s} decoded in {c.decode_s:5.2f}s  "
              f"(new compiles: {c.session.stats.compiles - before}, "
              f"cache hits: {c.session.stats.cache_hits})")

    microbatch_demo()


def microbatch_demo():
    """Server-side decode: assets arrive as raw symbols and are ingested by
    the encode engine (``DecodeService.ingest`` — encode + Def-4.1 split
    planning on device, stream never visits the host), then many small
    concurrent requests coalesce into one fused dispatch
    (runtime.serve.DecodeService.submit/flush)."""
    from repro.runtime.serve import DecodeService

    rng = np.random.default_rng(11)
    params = RansParams(n_bits=11, ways=32)
    payloads = {f"asset{i}": np.minimum(
        rng.exponential(35, size=2_000).astype(np.int64), 255)
        for i in range(8)}
    model = StaticModel.from_symbols(
        np.concatenate(list(payloads.values())), 256, params)
    svc = DecodeService(model, microbatch=8)
    t0 = time.perf_counter()
    svc.ingest_batch(payloads, 16)   # ONE vmapped encode+plan dispatch
    cold_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()         # refreshed assets: executable is warm
    svc.ingest_batch(payloads, 16)
    warm_ms = (time.perf_counter() - t0) * 1e3
    print(f"\ningested {len(payloads)} assets: {cold_ms:.0f} ms cold "
          f"(incl. {svc.stats.encode_compiles} compile), "
          f"{warm_ms:.1f} ms warm re-ingest (0 new compiles)")
    print("microbatched decode (8 concurrent small asset requests):")
    # warm: first round compiles the fused bucket executable
    tickets = {n: svc.submit(n, 16) for n in payloads}
    svc.flush()
    for name, t in tickets.items():
        assert (np.asarray(t.result()) == payloads[name]).all()
    # steady state: one fused executable call for all 8 requests
    t0 = time.perf_counter()
    tickets = {n: svc.submit(n, 16) for n in payloads}
    svc.flush()
    for name, t in tickets.items():
        assert (np.asarray(t.result()) == payloads[name]).all()
    dt = (time.perf_counter() - t0) * 1e3
    s = svc.stats
    print(f"8 requests decoded+verified in {dt:.1f} ms via "
          f"{s.fused_dispatches} fused dispatches "
          f"({s.coalesced_requests} requests coalesced, "
          f"plan cache hits: {s.plan_hits})")

    capability_demo()


def capability_demo():
    """Capability negotiation through the async pipeline (DESIGN.md §8):
    clients DECLARE their parallelism once (``CapabilityRegistry``); the
    server ships each one the same bitstream with metadata thinned to its
    declaration — the transfer-size vs decode-parallelism tradeoff of the
    paper's §3.3, served per client instead of per call.  Decode requests
    ride the broker's capability lanes (uniform-capability fused groups,
    adaptive flush, ingest overlapped on its own worker)."""
    from repro.core.recoil import decode_recoil
    from repro.runtime.serve import DecodeService

    rng = np.random.default_rng(23)
    params = RansParams(n_bits=11, ways=32)
    asset = np.minimum(rng.exponential(35, size=500_000).astype(np.int64),
                       255)
    model = StaticModel.from_symbols(asset, 256, params)
    svc = DecodeService(model)
    svc.ingest("asset", asset, 128)   # planned once at server parallelism
    print("\ncapability negotiation (same asset, three declared clients):")
    with svc.start_pipeline() as broker:
        reg = broker.registry
        clients = [("iot-sensor", 1), ("phone", 8), ("edge-box", 64)]
        for cid, threads in clients:
            reg.declare(cid, threads)
        full = np.asarray(svc.decode("asset", 128))
        base = None
        for cid, threads in clients:
            buf = reg.container_for("asset", cid)   # thinned wire payload
            t0 = time.perf_counter()
            out = np.asarray(reg.submit_for("asset", cid).result())
            dt = (time.perf_counter() - t0) * 1e3
            assert (out == full).all() and (out == asset).all()
            pc = container.parse(buf, params)
            assert (decode_recoil(pc.plan, pc.stream, pc.final_states,
                                  pc.model) == asset).all()
            base = base or len(buf)
            print(f"  {cid:11s} declares {threads:3d} threads -> "
                  f"{len(buf):>9,} B on wire "
                  f"(+{len(buf) - base:>6,} B metadata vs 1-thread), "
                  f"decoded+verified in {dt:6.1f} ms")
        snap = broker.snapshot()
        print(f"  broker: {snap['completed']} requests, "
              f"wait p50 {snap['wait']['p50_ms']:.1f} ms, "
              f"overlap ratio {snap['overlap']['overlap_ratio']:.2f}")

    predictive_demo()


def predictive_demo():
    """Predictive hot-set serving (DESIGN.md §12): a skewed client
    population hammers a few (content, capability) pairs; the broker's
    heat tracker ranks them and its pre-thinner derives thinned plans,
    downscaled containers and pre-compiled dispatch shapes in idle gaps —
    so the hot set's FIRST real fetch is served entirely from caches.
    Compare the same cold first fetches on a reactive broker."""
    from repro.runtime.serve import DecodeService

    rng = np.random.default_rng(31)
    params = RansParams(n_bits=11, ways=32)
    # Distinct sizes -> distinct executable shape buckets: every pair's
    # cold first request faces a real compile on the reactive path.
    sizes = {"news": 8_000, "map-tile": 18_000, "video-seg": 42_000}
    caps = {"news": 8, "map-tile": 1, "video-seg": 64}
    assets = {n: np.minimum(
        rng.exponential(35, size=s).astype(np.int64), 255)
        for n, s in sizes.items()}
    model = StaticModel.from_symbols(
        np.concatenate(list(assets.values())), 256, params)

    def first_fetches(svc, broker):
        rows = []
        for name, syms in assets.items():
            cap = caps[name]
            t0 = time.perf_counter()
            wire = broker.registry.container_for_threads(name, cap)
            out = np.asarray(
                svc.submit(name, cap, deadline="interactive").result())
            dt = (time.perf_counter() - t0) * 1e3
            assert (out == syms).all(), name
            rows.append((name, cap, len(wire), dt))
        return rows

    def build(predictive):
        svc = DecodeService(model, max_delay_ms=1e9)
        svc.ingest_batch(assets, 64)
        return svc, svc.start_pipeline(predictive=predictive)

    print("\npredictive hot-set serving (skewed population, cold first "
          "fetches):")
    svc, broker = build(predictive=False)
    with broker:
        reactive = first_fetches(svc, broker)

    svc, broker = build(predictive=True)
    with broker:
        # A Zipf-skewed request log declares the hot set — in production
        # this is live traffic; anticipate() stands in for the history.
        for name in rng.choice(list(assets), p=(0.6, 0.3, 0.1), size=64):
            broker.anticipate(str(name), caps[str(name)])
        units = broker.speculate()   # idle-gap work, off the request path
        compiles_before = svc.stats.compiles
        predictive = first_fetches(svc, broker)
        new_compiles = svc.stats.compiles - compiles_before
        heat = broker.snapshot()["heat"]["top"]

    print(f"  heat ranking: " + ", ".join(
        f"{h['name']}@{h['n_threads']} ({h['heat']:.0f})" for h in heat))
    print(f"  {units} speculative units ran in idle gaps "
          f"(prethin + container pack + shape warm)")
    for (name, cap, wire_r, dt_r), (_, _, wire_p, dt_p) in zip(
            reactive, predictive):
        assert wire_r == wire_p   # same downscaled container either way
        print(f"  {name:10s} @{cap:3d} threads  {wire_r:>8,} B on wire   "
              f"first fetch {dt_r:7.1f} ms reactive -> {dt_p:6.1f} ms "
              f"predictive ({dt_r / dt_p:5.1f}x)")
    total_r = sum(r[3] for r in reactive)
    total_p = sum(p[3] for p in predictive)
    print(f"  hot set total: {total_r:.0f} ms -> {total_p:.0f} ms "
          f"({total_r / total_p:.1f}x), {new_compiles} compiles in the "
          f"predictive window")

    observability_demo()


def observability_demo():
    """End-to-end ticket tracing + the unified metrics surface
    (DESIGN.md §13): every ticket carries a span tree — admission, lane
    queue wait, coalesce, dispatch, executor run, delivery — whose spans
    tile its lifetime exactly, so "where did this request's latency go"
    is answerable per ticket, not just in aggregate.  The same service
    exposes one ``metrics()`` snapshot unifying service/engine/broker/
    registry/predictor counters with per-class deadline-miss accounting."""
    from repro.runtime.observability import waterfall
    from repro.runtime.pipeline import ControllerConfig
    from repro.runtime.serve import DecodeService

    rng = np.random.default_rng(29)
    params = RansParams(n_bits=11, ways=32)
    assets = {f"asset{i}": np.minimum(
        rng.exponential(35, size=6_000).astype(np.int64), 255)
        for i in range(4)}
    model = StaticModel.from_symbols(
        np.concatenate(list(assets.values())), 256, params)
    svc = DecodeService(model, max_delay_ms=1e9)
    svc.ingest_batch(assets, 64)

    print("\nobservability (per-ticket span waterfall + unified metrics):")
    with svc.start_pipeline(config=ControllerConfig(
            max_batch=4, batch_sizes=(4,), target_delay_ms=5.0)) as broker:
        names = list(assets)
        for _ in range(2):                 # warm the fused group shape
            for t in [svc.submit(n, 8) for n in names]:
                np.asarray(t.result(timeout=120))
        tickets = [broker.submit(n, 8, deadline="interactive")
                   for n in names]
        for name, t in zip(names, tickets):
            assert (np.asarray(t.result(timeout=120)) == assets[name]).all()
        print()
        print(waterfall(tickets[0].trace))
        snap = svc.metrics()
        deadline = broker.snapshot()["deadline"]
    lat = snap["recoil_request_latency_ms"]["values"]
    ok = lat.get("decode|ok", {"count": 0, "sum": 0.0})
    print(f"\n  unified snapshot: {len(snap)} metric families")
    print(f"  decode ok latency: {ok['count']} requests, "
          f"mean {ok['sum'] / max(ok['count'], 1):.2f} ms")
    for cls, d in sorted(deadline.items()):
        print(f"  deadline class {cls!r}: {d['fulfilled']} fulfilled, "
              f"{d['missed']} missed")
    prof = svc.obs.profiler.snapshot(top=1)["decode"]
    print(f"  decode executor: {prof['compiles']} compiles "
          f"({prof['compile_s'] * 1e3:.0f} ms)")


if __name__ == "__main__":
    main()
