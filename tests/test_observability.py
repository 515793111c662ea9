"""Observability tier (DESIGN.md §13): ticket tracing, the unified metrics
registry, executor profiling hooks, and deadline-miss accounting.

The acceptance invariants asserted here:

  * a single warm ``submit()`` -> ``result()`` round-trip yields a span
    tree covering admission, queue wait, coalesce, dispatch, execute, and
    delivery whose span-sum is within 10% of the measured end-to-end
    latency (the phase-boundary model makes spans tile by construction);
  * the unified ``snapshot()`` exposes deadline-miss counts per class;
  * every unhappy path — cancelled-before-dispatch, in-flight cancel,
    ``result(timeout)`` expiry, admission rejection — terminates its span
    tree exactly once with the right status;
  * the metrics surface is schema-stable: every emitted name appears in
    ``observability.SCHEMA`` with matching type and label keys.
"""

import json
import threading
import time

import numpy as np
import pytest

from repro.core.rans import RansParams, StaticModel
from repro.runtime.metrics import LatencyWindow
from repro.runtime.observability import (NULL_TRACE, ExecProfiler,
                                         MetricsRegistry, SCHEMA, SCOPES,
                                         SPANS, TicketTracer, waterfall)
from repro.runtime.pipeline import (BrokerSaturated, ControllerConfig,
                                    TicketCancelled)
from repro.runtime.serve import DecodeService, DecodeTicket


def _payloads(n_contents=2, size=2048, seed=3):
    rng = np.random.default_rng(seed)
    return {f"c{i}": np.minimum(
        rng.exponential(35.0, size=size).astype(np.int64), 255)
        for i in range(n_contents)}


def _service(payloads, n_splits=16, **kw):
    model = StaticModel.from_symbols(
        np.concatenate(list(payloads.values())), 256,
        RansParams(n_bits=11, ways=32))
    svc = DecodeService(model, **kw)
    svc.ingest_batch(payloads, n_splits)
    return svc


def _frozen_broker(svc, **kw):
    """A broker whose worker never dispatches on its own (see
    test_pipeline) — tests control exactly when tickets leave the lanes."""
    return svc.start_pipeline(
        config=ControllerConfig(max_batch=64, batch_sizes=(64,),
                                target_delay_ms=3_600_000.0), **kw)


# ----------------------------------------------------------------------
# Trace primitives
# ----------------------------------------------------------------------

def test_trace_spans_tile_and_sum_exactly():
    tr = TicketTracer().start("decode", name="x", t0=10.0)
    tr.phase("admission", 10.5)
    tr.phase("queue", 12.0)
    tr.phase("execute", 15.0)
    tr.finish("ok", 15.25)
    assert tr.status == "ok"
    assert tr.span_names() == ["admission", "queue", "execute", "ok"]
    # Phase boundaries tile [t0, t1]: span-sum == duration EXACTLY.
    assert tr.span_sum_s() == pytest.approx(tr.duration_s)
    assert tr.duration_s == pytest.approx(5.25)
    d = tr.to_dict()
    assert d["duration_ms"] == pytest.approx(5250.0)
    assert [s["span"] for s in d["spans"]] == tr.span_names()
    assert sum(s["dur_ms"] for s in d["spans"]) == \
        pytest.approx(d["duration_ms"], rel=1e-6)


def test_trace_finish_is_idempotent_and_drops_late_phases():
    tr = TicketTracer().start("decode", t0=0.0)
    tr.phase("queue", 1.0)
    tr.finish("cancelled", 2.0)
    # A racing dispatch marks phases after the cancel won: dropped.
    tr.phase("execute", 3.0)
    tr.finish("ok", 4.0)
    assert tr.status == "cancelled"
    assert tr.span_names() == ["queue", "cancelled"]
    assert tr.duration_s == pytest.approx(2.0)
    # Zero-width events DO record after finish (e.g. result_timeout).
    tr.event("result_timeout", 5.0, timeout_s=1.0)
    assert tr.span_names()[-1] == "result_timeout"
    assert tr.span_sum_s() == pytest.approx(2.0)   # events are zero-width


def test_null_trace_is_inert():
    assert NULL_TRACE.live is False
    assert NULL_TRACE.phase("x") is None
    assert NULL_TRACE.finish("ok") is None
    assert NULL_TRACE.to_dict() == {}


def test_tracer_ring_bound_and_jsonl_export(tmp_path):
    tracer = TicketTracer(capacity=4)
    for i in range(10):
        t = tracer.start("decode", name=f"n{i}", t0=float(i))
        t.finish("ok", float(i) + 0.5)
    snap = tracer.snapshot()
    assert snap["started"] == 10
    assert snap["retained"] == 4                  # oldest evicted
    assert snap["finished"] == {"ok": 10}
    assert [t.name for t in tracer.recent()] == ["n6", "n7", "n8", "n9"]
    path = tmp_path / "traces.jsonl"
    assert tracer.export_jsonl(str(path)) == 4
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["name"] for r in rows] == ["n6", "n7", "n8", "n9"]
    assert all(r["status"] == "ok" for r in rows)


def test_tracer_disabled_hands_out_null_trace():
    tracer = TicketTracer(enabled=False)
    assert tracer.start("decode") is NULL_TRACE
    assert tracer.snapshot()["started"] == 0


# ----------------------------------------------------------------------
# Metrics registry
# ----------------------------------------------------------------------

def test_registry_instruments_and_exposition():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", labelnames=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    g = reg.gauge("depth")
    g.set(7)
    h = reg.histogram("lat_ms", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    snap = reg.snapshot()
    assert snap["req_total"]["values"] == {"a": 3.0, "b": 1.0}
    assert snap["depth"]["values"][""] == 7.0
    hval = snap["lat_ms"]["values"][""]
    assert hval["count"] == 3 and hval["sum"] == pytest.approx(55.5)
    assert hval["buckets"] == {1.0: 1, 10.0: 2}   # cumulative (Prometheus)
    text = reg.exposition()
    assert '# TYPE req_total counter' in text
    assert 'req_total{kind="a"} 3' in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert 'lat_ms_count 3' in text
    with pytest.raises(ValueError):
        reg.counter("req_total", labelnames=())   # re-declared differently
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)                # counters only go up
    with pytest.raises(TypeError):
        g.observe(1.0)


def test_registry_collectors_merge_and_collide_loudly():
    reg = MetricsRegistry()
    reg.register_collector(lambda: [
        {"name": "ext_total", "type": "counter", "value": 5},
        {"name": "ext_depth", "type": "gauge", "value": 2,
         "labels": {"lane": "8"}}])
    snap = reg.snapshot()
    assert snap["ext_total"]["values"][""] == 5
    assert snap["ext_depth"]["values"]["8"] == 2
    reg.counter("ext_total").inc()
    with pytest.raises(ValueError):
        reg.snapshot()                            # native/collector collision


def test_profiler_records_and_bounds_keys():
    prof = ExecProfiler(max_keys=2)
    prof.record_compile("decode", ("k1",), 0.5)
    prof.record_compile("decode", ("k2",), 0.2)
    prof.record_compile("decode", ("k3",), 0.3)   # beyond max_keys
    prof.record_compile("decode", ("k4",), 0.1)   # beyond max_keys
    t = prof.totals("decode")
    # 2 real keys + the bounded "<overflow>" aggregation row.
    assert t == {"keys": 3, "compiles": 4,
                 "compile_s": pytest.approx(1.1)}
    snap = prof.snapshot()
    rows = snap["decode"]["top"]
    assert [r["key"] for r in rows] == [
        str(("k1",)), ExecProfiler.OVERFLOW, str(("k2",))]   # by compile time
    assert rows[1]["compiles"] == 2 and rows[1]["compile_ms"] == 400.0
    assert set(rows[0]) == {"key", "compiles", "compile_ms"}
    off = ExecProfiler(enabled=False)
    off.record_compile("decode", ("k1",), 0.5)
    assert off.totals("decode")["compiles"] == 0


# ----------------------------------------------------------------------
# LatencyWindow (satellite: explicit thread-safety + reset)
# ----------------------------------------------------------------------

def test_latency_window_reset_isolates_phases():
    w = LatencyWindow(size=16)
    for _ in range(8):
        w.record(1.0)                             # cold phase
    w.reset()
    assert w.count == 0
    assert w.summary_ms()["count"] == 0
    w.record(0.002)                               # warm phase only
    s = w.summary_ms()
    assert s["count"] == 1
    assert s["p99_ms"] == pytest.approx(2.0)      # no cold-tail leakage


def test_latency_window_concurrent_recorders():
    w = LatencyWindow(size=64)
    stop = threading.Event()

    def pound():
        while not stop.is_set():
            w.record(0.001)
            w.summary_ms()

    threads = [threading.Thread(target=pound) for _ in range(4)]
    for t in threads:
        t.start()
    for _ in range(50):
        w.reset()
        w.percentile(99)
    stop.set()
    for t in threads:
        t.join()
    assert w.summary_ms()["p50_ms"] in (0.0, pytest.approx(1.0))


# ----------------------------------------------------------------------
# End-to-end span trees (acceptance)
# ----------------------------------------------------------------------

REQUIRED_SPANS = {"admission", "queue", "coalesce", "dispatch", "execute",
                  "delivery"}


def test_warm_roundtrip_span_tree_matches_e2e_latency():
    payloads = _payloads(n_contents=1)
    svc = _service(payloads)
    with svc.start_pipeline(config=ControllerConfig(
            max_batch=4, batch_sizes=(4,), target_delay_ms=5.0)) as b:
        for _ in range(2):                        # warm the group shape
            tks = [svc.submit("c0", 8) for _ in range(4)]
            for t in tks:
                np.asarray(t.result(timeout=60))
        tks = [svc.submit("c0", 8) for _ in range(4)]
        outs = [t.result(timeout=60) for t in tks]
    for t, out in zip(tks, outs):
        assert (np.asarray(out) == payloads["c0"]).all()
        tr = t.trace
        assert tr.status == "ok"
        assert REQUIRED_SPANS <= set(tr.span_names())
        e2e = t.completed_at - t.submitted_at
        # Span-sum within 10% of the measured end-to-end latency.
        assert tr.span_sum_s() == pytest.approx(e2e, rel=0.10)
        # And internally exact: phases tile the trace lifetime.
        assert tr.span_sum_s() == pytest.approx(tr.duration_s, rel=1e-9)
    # The finished traces landed in the ring and the waterfall renders.
    recent = svc.obs.tracer.recent(kind="decode", status="ok")
    assert len(recent) >= 4
    art = waterfall(recent[-1])
    assert "execute" in art and "[ok]" in art


def test_sync_path_span_tree():
    payloads = _payloads(n_contents=1)
    svc = _service(payloads, microbatch=2, max_delay_ms=10_000.0)
    t1 = svc.submit("c0", 8)
    t2 = svc.submit("c0", 8)                      # completes the microbatch
    assert (np.asarray(t1.result()) == payloads["c0"]).all()
    for t in (t1, t2):
        assert t.trace.status == "ok"
        assert REQUIRED_SPANS <= set(t.trace.span_names())
        assert t.trace.span_sum_s() == pytest.approx(t.trace.duration_s)
    assert t1.trace.meta["path"] == "sync"


def test_ingest_and_stream_span_trees():
    payloads = _payloads(n_contents=1)
    svc = _service(payloads)
    with svc.start_pipeline() as b:
        it = b.submit_ingest("new", payloads["c0"], 8)
        it.result(timeout=60)
        st = b.submit_stream("new", 8, n_chunks=4)
        np.asarray(st.result())
        b.drain()
        assert it.trace.status == "ok"
        assert {"admission", "queue", "execute"} <= set(it.trace.span_names())
        assert st.trace.status == "ok"
        assert {"admission", "queue", "dispatch",
                "execute"} <= set(st.trace.span_names())


# ----------------------------------------------------------------------
# Unhappy-path span trees (satellite)
# ----------------------------------------------------------------------

def test_cancel_before_dispatch_terminates_span_tree():
    payloads = _payloads(n_contents=1)
    svc = _service(payloads)
    _frozen_broker(svc)
    try:
        t = svc.submit("c0", 4)
        assert t.cancel() is True
        with pytest.raises(TicketCancelled):
            t.result(timeout=1)
    finally:
        svc.stop_pipeline()
    tr = t.trace
    assert tr.status == "cancelled"
    # Complete tree: admission, then the queue wait accounted as the
    # terminal "cancelled" span (it never reached coalesce/dispatch).
    assert tr.span_names() == ["admission", "cancelled"]
    assert tr.span_sum_s() == pytest.approx(tr.duration_s)
    assert tr.duration_s == pytest.approx(
        t.completed_at - t.submitted_at, rel=0.10)
    assert svc.obs.tracer.snapshot()["finished"].get("cancelled", 0) >= 1


def test_cancel_in_flight_keeps_cancelled_status():
    payloads = _payloads(n_contents=1)
    svc = _service(payloads)
    with svc.start_pipeline(config=ControllerConfig(
            max_batch=2, batch_sizes=(2,), target_delay_ms=5.0)):
        gate = threading.Event()
        orig = svc.dispatch_group

        def slow_dispatch(requests, tickets):
            gate.set()
            time.sleep(0.15)
            return orig(requests, tickets)

        svc.dispatch_group = slow_dispatch
        try:
            t1 = svc.submit("c0", 4)
            t2 = svc.submit("c0", 4)
            assert gate.wait(timeout=30)
            assert t1.cancel() is True            # races the dispatch
            with pytest.raises(TicketCancelled):
                t1.result(timeout=30)
            np.asarray(t2.result(timeout=30))
        finally:
            svc.dispatch_group = orig
    # The cancel won: terminal status stays "cancelled"; the dispatch's
    # late execute/delivery/ok marks were dropped after termination.
    assert t1.trace.status == "cancelled"
    assert t1.trace.span_names()[-1] == "cancelled"
    assert "delivery" not in t1.trace.span_names()
    assert t2.trace.status == "ok"


def test_result_timeout_records_event_then_cancel_terminates():
    payloads = _payloads(n_contents=1)
    svc = _service(payloads)
    _frozen_broker(svc)
    try:
        t = svc.submit("c0", 4)
        with pytest.raises(TimeoutError):
            t.result(timeout=0.05)
        assert t.trace.live                       # not terminated by expiry
        names = t.trace.span_names()
        assert "result_timeout" in names
        assert t.cancel() is True
    finally:
        svc.stop_pipeline()
    assert t.trace.status == "cancelled"
    assert t.trace.span_names()[-1] == "cancelled"


def test_admission_rejection_trace_carries_retry_hint():
    payloads = _payloads(n_contents=1)
    svc = _service(payloads)
    _frozen_broker(svc, max_queue=2)
    try:
        for _ in range(2):
            svc.submit("c0", 4)
        with pytest.raises(BrokerSaturated) as exc:
            svc.submit("c0", 4)
    finally:
        svc.stop_pipeline()
    rejected = svc.obs.tracer.recent(status="rejected")
    assert len(rejected) == 1
    tr = rejected[0]
    assert tr.status == "rejected"
    assert tr.span_names()[0] == "admission"
    assert set(tr.span_names()) <= {"admission", "rejected"}
    admission_meta = tr.to_dict()["spans"][0]["meta"]
    assert admission_meta["rejected"] is True
    assert admission_meta["retry_after_s"] == exc.value.retry_after_s
    assert svc.obs.tracer.snapshot()["finished"]["rejected"] == 1


# ----------------------------------------------------------------------
# Deadline-miss accounting (satellite, acceptance)
# ----------------------------------------------------------------------

def test_deadline_miss_accounting_per_class():
    payloads = _payloads(n_contents=1)
    svc = _service(payloads)
    with svc.start_pipeline(config=ControllerConfig(
            max_batch=2, batch_sizes=(2,), target_delay_ms=5.0,
            deadline_classes=(("rush", 0.001), ("lax", 600_000.0)),
            default_class="lax")) as b:
        # Warm, then one group with an impossible budget (must miss) and
        # one with an enormous budget (must not).
        for _ in range(2):
            tks = [svc.submit("c0", 8) for _ in range(2)]
            for t in tks:
                np.asarray(t.result(timeout=60))
        miss = [b.submit("c0", 8, deadline="rush") for _ in range(2)]
        for t in miss:
            np.asarray(t.result(timeout=60))
        hit = [b.submit("c0", 8, deadline="lax") for _ in range(2)]
        for t in hit:
            np.asarray(t.result(timeout=60))
        snap = b.snapshot()["deadline"]
        m = svc.metrics()
    miss_cls, hit_cls = miss[0].deadline_class, hit[0].deadline_class
    assert snap[miss_cls]["missed"] == 2
    assert snap[miss_cls]["fulfilled"] >= 2
    assert snap[hit_cls]["missed"] == 0
    assert snap[hit_cls]["fulfilled"] >= 2
    # The unified snapshot exposes the per-class counts (acceptance).
    assert m["recoil_deadline_missed_total"]["values"][miss_cls] == 2
    assert m["recoil_deadline_missed_total"]["values"][hit_cls] == 0
    assert m["recoil_deadline_fulfilled_total"]["values"][hit_cls] >= 2


# ----------------------------------------------------------------------
# Unified snapshot schema (satellite: schema-tested layout)
# ----------------------------------------------------------------------

def test_metrics_snapshot_is_schema_stable():
    payloads = _payloads()
    svc = _service(payloads)
    with svc.start_pipeline() as b:
        tks = [svc.submit("c0", 8) for _ in range(3)]
        for t in tks:
            np.asarray(t.result(timeout=60))
        b.submit_ingest("n2", payloads["c1"], 8).result(timeout=60)
        b.drain()
        snap = svc.metrics()
        text = svc.metrics_text()
    # Every emitted name is catalogued, with exact type/label agreement.
    for name, entry in snap.items():
        assert name in SCHEMA, f"uncatalogued metric {name}"
        mtype, labels = SCHEMA[name]
        assert entry["type"] == mtype, name
        assert tuple(entry["labelnames"]) == tuple(sorted(labels)) or \
            tuple(entry["labelnames"]) == tuple(labels), name
    # The load-bearing surfaces are present with real values.
    for required in (
            "recoil_service_decodes_total", "recoil_service_ingests_total",
            "recoil_engine_executables", "recoil_engine_stream_uploads_total",
            "recoil_profiler_compiles_total",
            "recoil_engine_walk_slots_total",
            "recoil_engine_walk_symbols_total",
            "recoil_traces_started_total",
            "recoil_request_latency_ms", "recoil_broker_submitted_total",
            "recoil_broker_queue_depth", "recoil_registry_memo_hits_total",
            "recoil_heat_pairs", "recoil_controller_lane_rate_hz",
            "recoil_deadline_fulfilled_total"):
        assert required in snap, required
    assert snap["recoil_service_decodes_total"]["values"][""] > 0
    lat = snap["recoil_request_latency_ms"]
    assert sum(v["count"] for v in lat["values"].values()) >= 3
    # Exposition parses: TYPE lines + 'name{labels} value' samples.
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            continue
        head, value = line.rsplit(" ", 1)
        float(value)
        assert head[0].isalpha()
    assert "# TYPE recoil_request_latency_ms histogram" in text
    assert 'recoil_request_latency_ms_bucket{kind="decode",status="ok",' \
        in text


# ----------------------------------------------------------------------
# Profiling hooks (tentpole part 3)
# ----------------------------------------------------------------------

def test_profiler_wired_through_sessions_and_executors():
    payloads = _payloads(n_contents=1)
    svc = _service(payloads)                      # ingest -> encode session
    svc.decode("c0", 8)
    prof = svc.obs.profiler.snapshot()
    svc.decode("c0", 8)                           # warm: run without compile
    warm = svc.obs.profiler.snapshot()
    assert prof["decode"]["compiles"] >= 1
    assert warm["decode"]["compiles"] == prof["decode"]["compiles"]
    assert warm["decode"]["compiles"] == svc.session.stats.compiles
    assert prof["decode"]["compile_s"] > 0
    assert prof["encode"]["compiles"] >= 1        # the ingest dispatch
    top = prof["decode"]["top"]
    assert top and top[0]["compile_ms"] > 0
    assert "runs" not in warm["decode"] and "run_s" not in warm["decode"]
    # Byte accounting: ingested streams are device-resident (no upload);
    # a host registration pays the padded upload exactly once.
    ex = svc.session.executor
    before = ex.stream_upload_bytes
    svc.register("hosted", svc.content("c0").plan,
                 np.asarray(svc.content("c0").stream.words
                            [:svc.content("c0").stream.n_words]),
                 svc.content("c0").final_states)
    assert ex.stream_upload_bytes - before == \
        svc.content("hosted").stream.bucket * 4
    assert ex.stream_upload_bytes % 4 == 0


def test_observe_false_disables_instrumentation():
    payloads = _payloads(n_contents=1)
    svc = _service(payloads, observe=False)
    assert svc.obs.profiler is None
    assert svc.session.profiler is None
    t = svc.submit("c0", 8)
    np.asarray(t.result())
    assert t.trace is NULL_TRACE
    assert svc.obs.tracer.snapshot() == {
        "enabled": False, "capacity": 1024, "started": 0, "retained": 0,
        "finished": {}}
    # The pull surface still works (collectors don't need the tracer).
    snap = svc.metrics()
    assert snap["recoil_service_decodes_total"]["values"][""] > 0


# ----------------------------------------------------------------------
# Layer names on the profiler's clock, and the padded-slot counters
# ----------------------------------------------------------------------

def test_scope_and_span_names_are_pinned():
    # The benchmark's per-layer readers key on these names: renaming one
    # silences a metric, so it must fail here first.
    assert SCOPES == ("recoil.walk_gather", "recoil.walk_kernel",
                      "recoil.scatter")
    assert SPANS == ("recoil.thin", "recoil.plan", "recoil.deliver",
                     "recoil.ingest")


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_walk_slot_counters_advance_exactly(impl):
    payloads = _payloads(n_contents=1)
    svc = _service(payloads, impl=impl)
    plan = svc.prepare_request("c0", 8)
    # args: (stream or slabs, sym_lut, f_lut, F_lut, k, ...); k is the
    # executor's lane tile: (rows, 128) on Pallas, (splits, W) on jnp.
    k = plan.args[4]
    assert plan.walk_slots == plan.statics["n_steps"] * k.shape[0] \
        * k.shape[1]
    if impl == "pallas":
        assert k.shape[1] == 128
    assert plan.walk_slots > plan.n_symbols == payloads["c0"].size
    before = svc.session.walk_totals()
    np.asarray(svc.session.execute(plan))
    np.asarray(svc.session.execute(plan))
    after = svc.session.walk_totals()
    assert tuple(a - b for a, b in zip(after, before)) == (
        2 * plan.walk_slots, 2 * plan.n_symbols, 2 * plan.walk_tiles,
        2 * plan.scatter_runs)
    # Pallas walks one T tile per row block here; the XLA walk has no grid.
    assert plan.walk_tiles == (k.shape[0] // 8 if impl == "pallas" else 0)
    snap = svc.metrics()
    assert snap["recoil_engine_walk_slots_total"]["values"][""] == after[0]
    assert snap["recoil_engine_walk_symbols_total"]["values"][""] == after[1]
    assert snap["recoil_engine_walk_tiles_total"]["values"][""] == after[2]
    with svc.start_pipeline() as b:
        bs = b.snapshot()
    assert (bs["walk_slots"], bs["walk_symbols"], bs["walk_tiles"],
            bs["scatter_runs"]) == after


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_scatter_run_counter_counts_kept_runs(impl):
    """``recoil_engine_scatter_runs_total`` advances by the non-empty kept
    runs of every executed plan: one a split on the Pallas path, whose
    scatter copies them in order, none on the XLA walk."""
    payloads = _payloads(n_contents=2)
    svc = _service(payloads, impl=impl)
    plans = [svc.prepare_request("c0", 8), svc.prepare_request("c1", 16),
             svc.prepare_group([("c0", 16), ("c1", 4)])]
    runs = [int((batch.keep_hi > batch.keep_lo).sum())
            for batch in (svc._thinned_batch("c0", 8)[0],
                          svc._thinned_batch("c1", 16)[0])]
    runs.append(sum(int((b.keep_hi > b.keep_lo).sum()) for b in (
        svc._thinned_batch("c0", 16)[0], svc._thinned_batch("c1", 4)[0])))
    assert runs == [8, 16, 20]
    assert [p.scatter_runs for p in plans] == (
        runs if impl == "pallas" else [0, 0, 0])
    total = 0
    for plan in plans:
        before = svc.session.walk_totals()[3]
        np.asarray(svc.session.execute(plan))
        assert svc.session.walk_totals()[3] - before == plan.scatter_runs
        total += plan.scatter_runs
    snap = svc.metrics()
    assert snap["recoil_engine_scatter_runs_total"]["values"][""] == total


def test_host_spans_reach_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData
    payloads = _payloads()
    svc = _service(payloads)
    tickets = [DecodeTicket(svc), DecodeTicket(svc)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.dispatch_group([("c0", 8), ("c1", 8)], tickets)
        for t in tickets:
            np.asarray(t.result())
    finally:
        jax.profiler.stop_trace()
    (path,) = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for e in line.events}
    # A fresh group at a new capability thins, plans and delivers.
    assert {"recoil.thin", "recoil.plan", "recoil.deliver"} <= names
    for t, name in zip(tickets, ("c0", "c1")):
        assert (np.asarray(t.result()) == payloads[name]).all()


def test_scopes_name_the_fused_decode_stages_in_hlo():
    import re
    from repro.kernels.rans_decode import ops
    payloads = _payloads(n_contents=1)
    svc = _service(payloads, impl="pallas")
    plan = svc.prepare_request("c0", 8)
    hlo = ops.decode_tiles_fused_symbol.lower(
        *plan.args, **plan.statics).as_text(debug_info=True)
    names = set(re.findall(r'"([^"]*recoil\.[^"]*)"', hlo))
    for scope in SCOPES:
        assert any(scope in n for n in names), scope
