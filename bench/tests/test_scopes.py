"""Device seconds by program scope, on the CPU.

    python3 -m pytest -q bench/tests

``bench.xspace`` against ``jax.profiler.ProfileData`` on the trace recorded
on a TPU v5e that the self-check reads (``bench/selfcheck/``; recorded
before the program had scopes, so its ops fall to the innermost-jit
attribution), and the compiled-HLO attribution the per-layer readers use
against a fused decode compiled here.
"""

from __future__ import annotations

import lzma
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "..", "..", "src"),
                os.path.join(HERE, "..", "..")]

from bench import run, scopes, selfcheck, trace_reduce, xspace  # noqa: E402


@pytest.fixture(scope="module")
def recorded():
    """``(raw bytes, ProfileData planes)`` of the committed chip trace."""
    from jax.profiler import ProfileData
    with lzma.open(selfcheck.TRACE) as f:
        raw = f.read()
    return raw, list(ProfileData.from_serialized_xspace(raw).planes)


def _profile_ops(planes):
    return [(p.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in planes if p.name.startswith("/device:")
            for line in p.lines if line.name == trace_reduce.OPS_LINE
            for e in line.events]


def test_xspace_reads_the_events_profiledata_reads(recorded):
    raw, planes = recorded
    ref = sorted(_profile_ops(planes))
    mine = sorted((o.plane, o.name, o.start_ns, o.end_ns)
                  for o in xspace.device_ops(xspace.parse(raw)))
    assert len(mine) == len(ref) > 0
    for a, b in zip(ref, mine):
        assert a[:2] == b[:2]
        # ProfileData rounds start and duration to whole ns each.
        assert abs(a[2] - b[2]) <= 1.0 and abs(a[3] - b[3]) <= 2.0


def test_xspace_finds_every_tf_op(recorded):
    raw, _ = recorded
    space = xspace.parse(raw)
    expected = 0
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        stat_names = {k: m.name for k, m in plane.stat_metadata.items()}
        tagged = {mid for mid, meta in plane.event_metadata.items()
                  if any(stat_names.get(s.metadata_id) == xspace.TF_OP
                         for s in meta.stats)}
        expected += sum(e.metadata_id in tagged for line in plane.lines
                        if line.name == xspace.OPS_LINE for e in line.events)
    found = [o.tf_op for o in xspace.device_ops(space) if o.tf_op]
    assert expected > 0 and len(found) == expected
    assert all(t.startswith("jit(") for t in found)


def test_scope_seconds_sum_to_op_seconds(recorded):
    raw, planes = recorded
    red = trace_reduce.reduce_planes(planes)
    lo, hi = trace_reduce._window(planes)
    by_scope = scopes.trace_seconds(xspace.device_ops(xspace.parse(raw)),
                                    lo, hi)
    assert abs(sum(by_scope.values()) - sum(red.op_seconds.values())) < 1e-6


def test_innermost_jit_attribution_on_the_recorded_trace(recorded):
    raw, _ = recorded
    ops = [o for o in xspace.device_ops(xspace.parse(raw))
           if o.plane == "/device:TPU:0"]
    total = scopes.trace_seconds(ops, float("-inf"), float("inf"))
    assert total["walk_decode_symbol_pallas"] > total["scatter_outputs"] \
        > total[scopes.UNSCOPED]
    # Unclipped sums over the first chip, read once with another decoder.
    assert total["walk_decode_symbol_pallas"] == pytest.approx(6.036,
                                                               abs=2e-3)
    assert total["scatter_outputs"] == pytest.approx(2.229, abs=2e-3)
    assert total[scopes.UNSCOPED] == pytest.approx(0.035, abs=1e-3)


def test_selfcheck_readings_unchanged():
    selfcheck.check_trace()
    selfcheck.check_arithmetic()


@pytest.mark.parametrize("op_name, scope", [
    (None, "unscoped"),
    ("", "unscoped"),
    ("jit(f)/jit(walk_decode_symbol_pallas)/gather:",
     "walk_decode_symbol_pallas"),
    ("jit(f)/jit(g)/recoil.walk_gather/jit(clip)/min", "recoil.walk_gather"),
    ("jit(f)/jit(g)/recoil.walk_kernel/walk_decode_symbol_pallas/"
     "pallas_call:", "recoil.walk_kernel"),
    ("jit(f)/recoil.walk_kernel/jit(g)/recoil.walk_gather/gather",
     "recoil.walk_gather"),
    ("jit(f)/jit(s)/recoil.scatter/reshape;recoil.scatter/reshape",
     "recoil.scatter"),
    ("y", "unscoped"),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


@pytest.fixture(scope="module")
def fused_hlo():
    """The optimized HLO of one fused symbol-layout decode compiled on the
    CPU (the Pallas walk interpreted), through the service."""
    from repro.core.rans import RansParams, StaticModel
    from repro.runtime.serve import DecodeService
    rng = np.random.default_rng(5)
    syms = np.minimum(rng.exponential(35.0, 4096).astype(np.int64), 255)
    model = StaticModel.from_symbols(syms, 256, RansParams(n_bits=11,
                                                           ways=32))
    svc = DecodeService(model, impl="pallas")
    svc.ingest("a", syms, 16)
    assert (np.asarray(svc.decode("a", 8)) == syms).all()
    return svc.session.compiled_hlo()


def test_compiled_hlo_names_each_program_scope(fused_hlo):
    from repro.runtime.observability import SCOPES
    assert (scopes.WALK_GATHER, scopes.WALK_KERNEL, scopes.SCATTER) == SCOPES
    found = {scopes.scope_of(n)
             for n in scopes.hlo_op_names(fused_hlo).values()}
    assert set(SCOPES) <= found


def test_readers_sum_the_ops_of_each_scope(fused_hlo):
    keys = list(scopes.hlo_op_names(fused_hlo))
    op_seconds = {k: 0.001 for k in keys}
    op_seconds["fusion.9 fusion s32[7]{0}"] = 1.0     # not in any program
    by_scope = scopes.op_seconds_by_scope(op_seconds, fused_hlo)
    assert sum(by_scope.values()) == pytest.approx(sum(op_seconds.values()))
    assert by_scope[scopes.UNSCOPED] >= 1.0

    done = [object()] * 4
    fake = types.SimpleNamespace(
        device=types.SimpleNamespace(op_seconds=op_seconds),
        answered_in_window=lambda: done,
        _dep=types.SimpleNamespace(svc=types.SimpleNamespace(
            session=types.SimpleNamespace(compiled_hlo=lambda: fused_hlo))))
    for metric, scope in (("walk_gather_ms.bulk", scopes.WALK_GATHER),
                          ("scatter_ms.bulk", scopes.SCATTER)):
        got = run.reader("layer_metrics", metric)(fake)
        assert got == pytest.approx(by_scope[scope] / 4 * 1e3)

    # A program whose session shows no HLO gives no reading.
    fake._dep = types.SimpleNamespace(svc=types.SimpleNamespace(
        session=types.SimpleNamespace()))
    assert run.reader("layer_metrics", "walk_gather_ms.bulk")(fake) is None


def test_padded_slot_reader():
    read = run.reader("layer_metrics", "padded_slot_pct.bulk")
    before = {"walk_slots": 0, "walk_symbols": 0}
    after = {"walk_slots": 100_663_296, "walk_symbols": 80_000_000}
    fake = types.SimpleNamespace(
        broker_delta=lambda k: after[k] - before[k])
    assert read(fake) == pytest.approx(100 * (1 - 80_000_000 / 100_663_296))

    def missing(key):
        raise KeyError(key)
    assert read(types.SimpleNamespace(broker_delta=missing)) is None
