"""Pallas rANS decode kernel: shape/dtype sweeps vs the pure-jnp oracle.

The algorithm is integer-exact, so comparisons are equality (assert_allclose
with zero tolerance).  On CPU the kernels run in interpret mode, as the
platform decides (TPU is the compile target — see DESIGN.md §2;
tests/test_tpu_compile.py compiles them for a described v5e).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core.engine import DecoderSession, with_symbol_layout
from repro.core.rans import RansParams, StaticModel
from repro.core import conventional, recoil
from repro.core.recoil import build_split_states
from repro.core.vectorized import WalkBatch, encode_interleaved_fast
from repro.kernels.rans_decode import decode, decode_recoil_kernel
from repro.kernels.rans_decode.rans_decode import LANES, interpret_mode
from repro.kernels.rans_decode.ref import decode_reference, walk_reference


def _make(seed=0, n=40_000, ways=32, n_bits=11, alphabet=256, lam=40.0):
    rng = np.random.default_rng(seed)
    syms = np.minimum(rng.exponential(lam, size=n).astype(np.int64),
                      alphabet - 1)
    params = RansParams(n_bits=n_bits, ways=ways)
    model = StaticModel.from_symbols(syms, alphabet, params)
    return syms, model, encode_interleaved_fast(syms, model)


@pytest.mark.parametrize("ways", [8, 16, 32, 64, 128])
def test_kernel_way_sweep(ways):
    syms, model, enc = _make(ways=ways, n=30_000)
    plan = recoil.plan_splits(enc, 24)
    out = decode_recoil_kernel(plan, enc.stream, enc.final_states, model)
    assert_allclose(out, syms, rtol=0, atol=0)


@pytest.mark.parametrize("n_bits", [8, 11, 14, 16])
def test_kernel_quantization_sweep(n_bits):
    syms, model, enc = _make(n_bits=n_bits, n=25_000)
    plan = recoil.plan_splits(enc, 16)
    out = decode_recoil_kernel(plan, enc.stream, enc.final_states, model)
    assert_allclose(out, syms, rtol=0, atol=0)


def test_kernel_16bit_symbols():
    """16-bit symbol alphabet (paper Table 3 sizeof(s) = 16)."""
    rng = np.random.default_rng(5)
    syms = rng.integers(0, 4096, size=20_000)
    params = RansParams(n_bits=14, ways=32)
    model = StaticModel.from_symbols(syms, 4096, params)
    enc = encode_interleaved_fast(syms, model)
    plan = recoil.plan_splits(enc, 12)
    out = decode_recoil_kernel(plan, enc.stream, enc.final_states, model)
    assert_allclose(out, syms, rtol=0, atol=0)


@pytest.mark.parametrize("n", [999, 4096, 17_331])
@pytest.mark.parametrize("splits", [3, 17])
def test_kernel_shape_sweep(n, splits):
    syms, model, enc = _make(n=n, seed=n)
    plan = recoil.plan_splits(enc, splits)
    out = decode_recoil_kernel(plan, enc.stream, enc.final_states, model)
    assert_allclose(out, syms, rtol=0, atol=0)


def test_kernel_tiles_match_reference_exactly():
    """Tile-level contract: kernel output == ref.py oracle elementwise."""
    syms, model, enc = _make(n=20_000)
    plan = recoil.plan_splits(enc, 10)
    splits = build_split_states(plan, enc.final_states)
    batch = WalkBatch.from_splits(splits, plan.ways)
    ref_tiles, ref_qf = walk_reference(batch, enc.stream, model)
    ref_out = decode_reference(batch, enc.stream, model, plan.n_symbols)
    kern_out = decode(batch, enc.stream, model, plan.n_symbols, impl="pallas")
    assert_allclose(kern_out, ref_out, rtol=0, atol=0)
    assert_allclose(kern_out, syms, rtol=0, atol=0)


def test_kernel_rows_per_block_padding():
    """Split counts that don't fill a (rows_per_block x PACK) grid block."""
    syms, model, enc = _make(n=60_000)
    for m in (2, 5, 33, 41):
        plan = recoil.plan_splits(enc, m)
        out = decode_recoil_kernel(plan, enc.stream, enc.final_states, model,
                                   rows_per_block=4)
        assert_allclose(out, syms, rtol=0, atol=0)


def test_kernel_conventional_adapter():
    """The Conventional baseline decodes through the same kernel."""
    syms, model, enc = _make(n=30_000)
    conv = conventional.encode_conventional(syms, model, 9)
    states, words, out_bases = conventional.to_split_states(conv)
    batch = WalkBatch.from_splits(states, 32, out_bases)
    out = decode(batch, words, model, conv.n_symbols, impl="pallas")
    assert_allclose(out, syms, rtol=0, atol=0)


def test_jnp_impl_matches_pallas():
    syms, model, enc = _make(n=15_000)
    plan = recoil.plan_splits(enc, 8)
    splits = build_split_states(plan, enc.final_states)
    batch = WalkBatch.from_splits(splits, plan.ways)
    a = decode(batch, enc.stream, model, plan.n_symbols, impl="jnp")
    b = decode(batch, enc.stream, model, plan.n_symbols, impl="pallas")
    assert_allclose(a, b, rtol=0, atol=0)


@pytest.mark.parametrize("ways", [32, 128])
@pytest.mark.parametrize("n_bits", [11, 12])
def test_packed_lut_agrees_with_oracle(n_bits, ways):
    """§4.4 packed-LUT tripartite equality: python oracle == packed jnp walk
    == packed Pallas kernel (interpret), bit-exact."""
    syms, model, enc = _make(n=20_000, ways=ways, n_bits=n_bits)
    plan = recoil.plan_splits(enc, 12)
    oracle = recoil.decode_recoil(plan, enc.stream, enc.final_states, model)
    assert_allclose(oracle, syms, rtol=0, atol=0)
    splits = build_split_states(plan, enc.final_states)
    batch = WalkBatch.from_splits(splits, plan.ways)
    from repro.core.vectorized import walk_decode_batch
    jnp_out = walk_decode_batch(batch, enc.stream, model, plan.n_symbols,
                                packed_lut=True)
    pallas_out = decode(batch, enc.stream, model, plan.n_symbols,
                        impl="pallas", packed_lut=True)
    assert_allclose(jnp_out, oracle, rtol=0, atol=0)
    assert_allclose(np.asarray(pallas_out), oracle, rtol=0, atol=0)


def test_packed_lut_rejected_when_it_cannot_fit():
    syms, model, enc = _make(n=5_000, n_bits=14)
    plan = recoil.plan_splits(enc, 4)
    splits = build_split_states(plan, enc.final_states)
    batch = WalkBatch.from_splits(splits, plan.ways)
    with pytest.raises(ValueError, match="packed LUT"):
        decode(batch, enc.stream, model, plan.n_symbols, impl="pallas",
               packed_lut=True)


# ----------------------------------------------------------------------
# Platform-derived mode and plan-time refusals (no option selects them:
# the tests steer the platform the code observes)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("backend, interpret", [("cpu", True),
                                                ("tpu", False)])
def test_interpret_mode_follows_platform(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert interpret_mode() is interpret


def test_interpret_mode_refuses_other_platforms(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        interpret_mode()


def test_pallas_plan_refuses_pointer_content_on_tpu(monkeypatch):
    """On a TPU the pointer walk has no kernel Mosaic compiles: the plan
    raises with the compiler's reason instead of switching backends."""
    syms, model, enc = _make(n=5_000)
    plan = recoil.plan_splits(enc, 4)
    batch = WalkBatch.from_splits(
        build_split_states(plan, enc.final_states), plan.ways)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sess = DecoderSession(model, impl="pallas")
    assert sess.executor.interpret is False
    with pytest.raises(NotImplementedError, match="cumsum"):
        sess.prepare(batch, enc.stream, plan.n_symbols)


def test_pallas_plan_tiles_walk_over_vmem_limit():
    """A one-split walk of ~6.6k steps would need a whole-walk block over
    the kernel's VMEM limit: the plan tiles the walk along its steps, each
    tile's block fits, and the tiled walk decodes the content."""
    from repro.kernels.rans_decode.rans_decode import (VMEM_LIMIT_BYTES,
                                                       block_vmem_bytes)
    syms, model, enc = _make(n=210_000)
    plan = recoil.plan_splits(enc, 1)
    batch = WalkBatch.from_splits(
        build_split_states(plan, enc.final_states), plan.ways)
    sess = DecoderSession(model, impl="pallas")
    ds = with_symbol_layout(sess.upload_stream(enc.stream), enc.k_of_word,
                            plan.n_symbols)
    dplan = sess.prepare(batch, ds, plan.n_symbols)
    T, tile = dplan.statics["n_steps"], dplan.statics["tile_steps"]
    rows = dplan.args[4].shape[0]
    lut_words = dplan.args[1].size
    assert block_vmem_bytes(T, 8, lut_words) > VMEM_LIMIT_BYTES
    assert T // tile >= 2 and T % tile == 0
    assert block_vmem_bytes(tile, 8, lut_words) <= VMEM_LIMIT_BYTES
    assert tile in dplan.key
    assert dplan.walk_tiles == T // tile * rows // 8
    assert_allclose(np.asarray(sess.execute(dplan)), syms, rtol=0, atol=0)


@pytest.mark.parametrize("ways, contents, tile", [
    (32, {"a": (40_000, 8)}, 64),                  # three tiles
    (128, {"a": (40_000, 8)}, 16),                 # three tiles, W = 128
    (32, {"a": (20_000, 2), "b": (3_000, 8)}, 64),  # b's splits < one tile
    (32, {"a": (40_000, 8)}, 32),                  # last tile all padding
], ids=["w32", "w128", "short_splits", "padding_tile"])
def test_tiled_walk_matches_oracle_and_one_tile(ways, contents, tile):
    """The walk tiled along its steps, the lane states carried from tile
    to tile, equals the one-tile walk and the scalar oracle symbol for
    symbol.  The tile is forced through the kernel's static argument."""
    from repro.core.recoil import combine_plan
    from repro.kernels.rans_decode import ops
    from repro.runtime.serve import DecodeService
    rng = np.random.default_rng(ways + tile)
    payloads = {name: np.minimum(rng.exponential(40.0, size=n), 255)
                .astype(np.int64) for name, (n, _) in contents.items()}
    model = StaticModel.from_symbols(np.concatenate(list(payloads.values())),
                                     256, RansParams(n_bits=11, ways=ways))
    svc = DecodeService(model, impl="pallas")
    svc.ingest_batch(payloads, 32)
    group = [(name, cap) for name, (_, cap) in contents.items()]
    plan = svc.prepare_group(group)
    T = plan.statics["n_steps"]
    assert plan.statics["tile_steps"] == T     # the whole walk fits VMEM
    assert T // tile >= 3 and T % tile == 0
    one = np.asarray(svc.session.execute(plan))
    tiled = np.asarray(ops.decode_tiles_fused_symbol(
        *plan.args, **{**plan.statics, "tile_steps": tile})
        [:plan.n_symbols])
    assert_allclose(tiled, one, rtol=0, atol=0)
    oracle = []
    for name, cap in group:
        c = svc.content(name)
        words = np.asarray(c.stream.words[:c.stream.n_words])
        oracle.append(recoil.decode_recoil(combine_plan(c.plan, cap), words,
                                           c.final_states, model))
        assert_allclose(oracle[-1], payloads[name], rtol=0, atol=0)
    assert_allclose(tiled, np.concatenate(oracle), rtol=0, atol=0)
    steps = [-(-n // cap // ways) + 1 for n, cap in contents.values()]
    if len(contents) > 1:
        assert min(steps) < tile
    if tile == 32:
        assert max(steps) <= T - tile          # the last tile walks padding


# ----------------------------------------------------------------------
# Walk-order words: one window read per split
# ----------------------------------------------------------------------

def _elementwise_walk_order_words(slabs, sym_rel, g_hi, *, ways, n_steps,
                                  rows_per_block):
    """The reference: one clamped slab element per walk slot.  Returns the
    (T, rows, 128) words and the mask of slots whose index was in range."""
    n_rows, L = g_hi.shape
    t = np.arange(n_steps)[:, None, None]
    lane = (np.arange(L) % ways)[None, None, :]
    raw = (g_hi[None] - t) * ways + lane + sym_rel[None]
    block = np.arange(n_rows)[None, :, None] // rows_per_block
    words = slabs[block, np.clip(raw, 0, slabs.shape[1] - 1)]
    return words, (raw >= 0) & (raw < slabs.shape[1])


@pytest.mark.parametrize("ways", [32, 128])
@pytest.mark.parametrize("layout", ["from_zero", "deep", "inert_padding"])
def test_walk_order_windows_match_elementwise_gather(ways, layout):
    """The window read gives the element-wise gather's word at every slot
    whose index is in the unguarded slab, every active slot among them.

    Slabs are built as ``PallasExecutor.plan`` builds them.  ``from_zero``
    starts the walk at index 0 (splits shorter than the walk, windows
    reaching below the slab's start); ``deep`` starts far into a
    permutation, so ``sym_rel`` is large, negative and not a multiple of
    W; ``inert_padding`` leaves a partial row and a partial block of inert
    splits.  Every block's last split ends at its slab's last word."""
    from repro.kernels.rans_decode.ops import (build_slabs, pack_batch,
                                               pad_to_rows)
    from repro.kernels.rans_decode.rans_decode import (LANES,
                                                       _walk_order_words,
                                                       window_guard)
    rng = np.random.default_rng([ways, len(layout)])
    T, R = 12, 8
    pack = LANES // ways
    S = 3 * R * pack - (R * pack // 2 + 1 if layout == "inert_padding" else 0)
    lengths = rng.integers(1, (T - 2) * ways, S)
    lengths[: S // 4] = rng.integers(1, ways, S // 4)     # one-group splits
    begin = 0 if layout == "from_zero" else int(rng.integers(10**5, 10**6))
    stop = begin + np.concatenate([[0], np.cumsum(lengths)[:-1]])
    start = stop + lengths - 1
    sym_base = ways * int(rng.integers(0, 100))
    stream = rng.integers(-2**31, 2**31, int(start[-1]) + sym_base + 1,
                          dtype=np.int64).astype(np.int32)
    z = np.zeros((S, ways), np.int32)
    zs = np.zeros(S, np.int32)
    batch = WalkBatch(k=z, y=z.view(np.uint32), x0=z.view(np.uint32), q0=zs,
                      g_hi=(start // ways).astype(np.int32),
                      start=start.astype(np.int32),
                      stop=stop.astype(np.int32), keep_lo=zs, keep_hi=zs,
                      out_base=zs, n_steps=T, ways=ways,
                      sym_base=np.full(S, sym_base, np.int32))
    packed, per_split, rows, _, _ = pack_batch(batch)
    rows = pad_to_rows(packed, per_split, rows, pack, -(-rows // R) * R)
    win = dict(q0=per_split["start"] + per_split["sym_base"],
               span=per_split["span"])

    def rel_lanes(origin):
        rel = per_split["sym_base"] - np.repeat(origin, R * pack)
        return np.repeat(rel.reshape(rows, pack), ways, axis=1)

    plain, lo = build_slabs(stream, win, rows, pack, R)
    guarded, origin = build_slabs(stream, win, rows, pack, R,
                                  guard=window_guard(T, ways))
    g_hi = packed["g_hi"]
    ref, in_range = _elementwise_walk_order_words(
        plain, rel_lanes(lo), g_hi, ways=ways, n_steps=T, rows_per_block=R)
    words = np.asarray(_walk_order_words(
        jnp.asarray(guarded), jnp.asarray(rel_lanes(origin)),
        jnp.asarray(g_hi), ways=ways, n_steps=T, rows_per_block=R))

    t = np.arange(T)[:, None, None]
    i = (g_hi[None] - t) * ways + (np.arange(LANES) % ways)[None, None, :]
    active = (i >= packed["stop"][None]) & (i <= packed["start"][None])
    assert active.sum() == lengths.sum()
    assert in_range[active].all()
    assert_allclose(words[in_range], ref[in_range], rtol=0, atol=0)
    if layout == "from_zero":
        assert not in_range.all()     # some windows start below the slab
    if layout == "deep":
        assert (rel_lanes(lo) % ways).any()


# ----------------------------------------------------------------------
# Output: one ordered contiguous copy per split
# ----------------------------------------------------------------------

def _closed_form_scatter(tiles, per_split, *, ways, pack, n_out):
    """The reference: every kept slot written at its closed-form position
    ``out_base + (g_hi - t) * W + lane``; ``-1`` wherever none is kept."""
    T, rows, L = tiles.shape
    s = np.arange(rows)[:, None] * pack + np.arange(L)[None, :] // ways
    t = np.arange(T)[:, None, None]
    i = ((per_split["g_hi"][s][None] - t) * ways
         + (np.arange(L) % ways)[None, None, :] + per_split["out_base"][s][None])
    out = np.full(n_out, -1, np.int32)
    kept = tiles >= 0
    assert len(np.unique(i[kept])) == kept.sum()
    out[i[kept]] = tiles[kept]
    return out


def _asset_splits(rng, lengths, n_steps, ways):
    """Completion-ordered splits of one asset with runs of ``lengths``, each
    with a top group ``g_hi`` drawn anywhere its T-step walk covers its run
    (so the run's offset in its tile varies)."""
    hi = np.cumsum(lengths)
    lo = hi - lengths
    g_min = -(-hi // ways) - 1
    g_max = lo // ways + n_steps - 1
    assert np.all(g_min <= g_max)
    g_hi = rng.integers(g_min, g_max + 1)
    return g_hi, lo, hi


@pytest.mark.parametrize("case", [
    "short_runs", "long_walk", "inert_padding", "out_of_order",
    "eight_assets", "bucket_edge", "short_asset"])
def test_scatter_run_copy_matches_closed_form_scatter(case):
    """The ordered run copy gives the closed-form index scatter's output
    bit for bit, ``-1`` beyond the symbols included.

    ``short_runs``: 64 splits of one-group runs and up (c2048-like);
    ``long_walk``: 3 splits walking 240 steps (c16-like);
    ``inert_padding``: rows of inert splits (``start = -1``, empty runs);
    ``out_of_order``: the splits' rows in no output order;
    ``eight_assets``: 8 assets placed one after another by ``out_base``;
    ``bucket_edge``: the last run ends at the bucket's edge, so the last
    copies reach into the output's guard;
    ``short_asset``: fewer symbols than one split's ``T * W`` words."""
    from repro.kernels.rans_decode.ops import ordered_runs, scatter_outputs
    rng = np.random.default_rng(sum(map(ord, case)))
    ways, T = (32, 240) if case == "long_walk" else (32, 12)
    if case == "short_asset":
        ways = 16
    pack = LANES // ways
    n_assets = {"eight_assets": 8, "out_of_order": 3}.get(case, 1)
    n_splits = {"long_walk": 3, "short_asset": 4,
                "inert_padding": 61}.get(case, 64)
    cols = {k: [] for k in ("g_hi", "out_base", "keep_lo", "keep_hi")}
    base = 0
    for _ in range(n_assets):
        if case == "long_walk":
            lengths = rng.integers((T - 20) * ways, (T - 2) * ways, n_splits)
        elif case == "short_asset":
            lengths = rng.integers(1, 2 * ways, n_splits)
        else:
            lengths = rng.integers(1, (T - 2) * ways, n_splits)
            lengths[rng.random(n_splits) < 0.25] = 1
        g_hi, lo, hi = _asset_splits(rng, lengths, T, ways)
        for k, v in (("g_hi", g_hi), ("keep_lo", lo), ("keep_hi", hi),
                     ("out_base", np.full(n_splits, base))):
            cols[k].append(v)
        base += int(hi[-1])
    n_symbols = base
    cols = {k: np.concatenate(v).astype(np.int32) for k, v in cols.items()}
    S = len(cols["g_hi"])
    order = (rng.permutation(S) if case in ("out_of_order", "eight_assets")
             else np.arange(S))
    n_inert = -S % pack + (2 * pack if case == "inert_padding" else 0)
    per_split = {k: np.concatenate([v[order], np.zeros(n_inert, np.int32)])
                 for k, v in cols.items()}
    S_pad = S + n_inert
    rows = S_pad // pack
    assert S_pad % pack == 0
    n_out = n_symbols if case == "bucket_edge" else n_symbols + 3 * T * ways
    if case == "short_asset":
        assert n_symbols < T * ways

    # Tiles as the kernel leaves them: symbols at kept slots, -1 elsewhere.
    s = np.arange(rows)[:, None] * pack + np.arange(LANES)[None, :] // ways
    i = ((per_split["g_hi"][s][None] - np.arange(T)[:, None, None]) * ways
         + (np.arange(LANES) % ways)[None, None, :])
    kept = (i >= per_split["keep_lo"][s][None]) & \
        (i < per_split["keep_hi"][s][None])
    tiles = np.where(kept, rng.integers(0, 2**31 - 1, kept.shape),
                     -1).astype(np.int32)
    assert kept.sum() == n_symbols

    src, dst, n_runs = ordered_runs(per_split, n_steps=T, ways=ways,
                                    n_symbols=n_symbols)
    assert n_runs == S and len(src) == len(dst) == S_pad + 1
    assert np.all(np.diff(dst) >= 0) and dst[-1] == n_symbols
    if case == "bucket_edge":
        assert dst[-2] + T * ways > n_out     # the copy needs the guard
    ref = _closed_form_scatter(tiles, per_split, ways=ways, pack=pack,
                               n_out=n_out)
    got = np.asarray(scatter_outputs(
        jnp.asarray(tiles), jnp.asarray(src), jnp.asarray(dst), ways=ways,
        pack=pack, n_symbols=n_out))
    assert got.dtype == np.int32 and got.shape == (n_out,)
    assert np.array_equal(got, ref)
    assert (got[:n_symbols] >= 0).all() and (got[n_symbols:] == -1).all()


@pytest.mark.parametrize("fault", ["gap", "overlap", "short"])
def test_kept_runs_that_do_not_tile_are_refused(fault):
    """A plan whose kept runs leave a gap, overlap, or stop short of the
    symbols is refused on the host, before any device work, by the engine's
    Pallas plan and by ``ops.decode(check=True)``; the plan as built
    decodes."""
    syms, model, enc = _make(n=6_000)
    plan = recoil.plan_splits(enc, 6)
    batch = WalkBatch.from_splits(
        build_split_states(plan, enc.final_states), plan.ways)
    sess = DecoderSession(model, impl="pallas")
    ds = with_symbol_layout(sess.upload_stream(enc.stream), enc.k_of_word,
                            plan.n_symbols)
    good = sess.prepare(batch, ds, plan.n_symbols)
    assert good.scatter_runs == len(batch.keep_lo)
    assert_allclose(np.asarray(sess.execute(good)), syms, rtol=0, atol=0)
    n = plan.n_symbols
    if fault == "gap":
        batch.keep_hi[2] -= 1
    elif fault == "overlap":
        batch.keep_lo[3] -= 1
    else:
        n += 1
    with pytest.raises(ValueError, match="do not tile"):
        sess.prepare(batch, ds, n)
    with pytest.raises(ValueError, match="do not tile"):
        decode(batch, enc.stream, model, n, check=True)
