"""jit'd public wrapper for the Pallas rANS walk-decode kernel.

Handles the host-side data plumbing around the kernel:

  * lane packing     — PACK = 128 // W splits per (sublane) row, padding with
                       inert splits (``start = -1`` never activates);
  * slab building    — per-grid-block contiguous stream windows sized to the
                       block's worst-case word consumption (kernel VMEM bound;
                       see rans_decode.py header), built with one vectorized
                       strided gather, with slab-relative ``q0``;
  * scatter          — kernel emits (T, rows, 128) symbols (-1 = not kept);
                       positions are reconstructed closed-form from
                       ``g_hi - t`` and scattered into the flat output ON
                       DEVICE (the tile never round-trips to host numpy).

``decode(...)`` is the user entry point; ``impl='jnp'`` routes to the pure
jnp batched walk (same math, no Pallas) for CPU-fast paths and A/B tests.
For steady-state serving use :class:`repro.core.engine.DecoderSession`,
which reuses this module's packing/slab/scatter plumbing behind a bucketed
executable cache.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.rans import StaticModel, pack_decode_lut
from repro.core.vectorized import WalkBatch, walk_decode_batch
from repro.runtime.observability import SCATTER
from .rans_decode import LANES, interpret_mode, walk_decode_pallas


def pack_batch(batch: WalkBatch):
    """Lane-pack a WalkBatch: (S, W) split arrays -> (rows, 128) tiles."""
    W = batch.ways
    if LANES % W != 0:
        raise ValueError(f"ways={W} must divide {LANES} for the Pallas path")
    pack = LANES // W
    S = batch.k.shape[0]
    rows = -(-S // pack)
    S_pad = rows * pack

    def pad_splits(a, fill):
        out = np.full((S_pad,) + a.shape[1:], fill, a.dtype)
        out[:S] = a
        return out

    # Inert padding: start=-1 & stop=0 makes `active` always false.
    k = pad_splits(batch.k, np.int32(2 ** 30))
    y = pad_splits(batch.y, np.uint32(0))
    x0 = pad_splits(batch.x0, np.uint32(0))
    q0 = pad_splits(batch.q0, np.int32(0))
    g_hi = pad_splits(batch.g_hi, np.int32(0))
    start = pad_splits(batch.start, np.int32(-1))
    stop = pad_splits(batch.stop, np.int32(0))
    keep_lo = pad_splits(batch.keep_lo, np.int32(0))
    keep_hi = pad_splits(batch.keep_hi, np.int32(0))
    out_base = pad_splits(batch.out_base.astype(np.int32), np.int32(0))
    sym_base = pad_splits(batch.sym_bases(), np.int32(0))

    def lanes(a):   # (S_pad, W) -> (rows, 128)
        return np.ascontiguousarray(a.reshape(rows, pack * W))

    def scalars(a):  # (S_pad,) -> (rows, 128), broadcast per segment
        return np.ascontiguousarray(
            np.repeat(a.reshape(rows, pack), W, axis=1))

    packed = dict(
        k=lanes(k.astype(np.int32)), y=lanes(y.view(np.int32)),
        x0=lanes(x0.view(np.int32)), q0=scalars(q0), g_hi=scalars(g_hi),
        start=scalars(start), stop=scalars(stop), keep_lo=scalars(keep_lo),
        keep_hi=scalars(keep_hi))
    per_split = dict(q0=q0, g_hi=g_hi, out_base=out_base,
                     span=start - stop + 1, start=start, sym_base=sym_base)
    return packed, per_split, rows, pack, S_pad


def pad_to_rows(packed: dict, per_split: dict, rows: int, pack: int,
                target_rows: int) -> int:
    """Grow the lane-packed tiles to ``target_rows`` with inert splits
    (``start = -1`` never activates), in place.  Returns the new row count."""
    pad_rows = target_rows - rows
    if pad_rows < 0:
        raise ValueError(f"target_rows {target_rows} < packed rows {rows}")
    if pad_rows:
        for name, arr in packed.items():
            fill = -1 if name == "start" else 0
            if name == "k":
                fill = 2 ** 30
            packed[name] = np.concatenate(
                [arr, np.full((pad_rows, LANES), fill, arr.dtype)], axis=0)
        for name in ("q0", "g_hi", "out_base", "span", "start", "sym_base"):
            a = per_split[name]
            per_split[name] = np.concatenate(
                [a, np.zeros(pad_rows * pack, a.dtype)])
    return target_rows


def build_slabs(stream: np.ndarray, per_split: dict, rows: int, pack: int,
                rows_per_block: int, guard: tuple[int, int] = (0, 0)):
    """Per-block stream slabs.  A split consumes at most one word per walked
    symbol index, so its reads live in ``[q0 - span, q0]``; the block slab is
    the union over its splits, padded to the max block width (multiple of 8
    words for sublane alignment).  ``guard = (front, back)`` puts that many
    zero words before and after every block's window.

    Returns ``(slabs, origin)``: column ``j`` of block ``b`` holds
    ``stream[origin[b] + j]``."""
    n_blocks = rows // rows_per_block
    per_block = rows_per_block * pack
    q0 = per_split["q0"].reshape(n_blocks, per_block)
    span = per_split["span"].reshape(n_blocks, per_block)
    lo = np.maximum(0, (q0 - span).min(axis=1))
    hi = q0.max(axis=1)
    front, back = guard
    width = front + int((hi - lo + 1).max()) + back
    width = -(-width // 8) * 8
    origin = lo - front
    stream32 = np.ascontiguousarray(stream).astype(np.uint32).astype(np.int32)
    n = len(stream32)
    if n == 0:
        return np.zeros((n_blocks, width), dtype=np.int32), origin
    # One strided gather builds every slab: block b's row reads
    # stream[origin[b] + j] where that lies in [lo[b], hi[b]], zero
    # elsewhere.
    idx = origin[:, None] + np.arange(width, dtype=np.int64)[None, :]
    valid = (idx >= lo[:, None]) & (idx <= hi[:, None])
    slabs = np.where(valid, stream32[np.clip(idx, 0, n - 1)], 0)
    return np.ascontiguousarray(slabs.astype(np.int32)), origin


def packed_lut_ok(model: StaticModel) -> bool:
    """True iff the §4.4 packed single-int32 LUT layout fits this model."""
    return model.alphabet_size <= 256 and model.params.n_bits <= 12


def _luts(model: StaticModel, packed: bool):
    if packed:
        return (jnp.asarray(pack_decode_lut(model.f, model.F)), None, None)
    lut = model.slot_lut()
    slot_f = model.f.astype(np.int32)[lut]
    slot_F = model.F[:-1].astype(np.int32)[lut]
    return (jnp.asarray(lut.astype(np.int32)), jnp.asarray(slot_f),
            jnp.asarray(slot_F))


def decode(batch: WalkBatch, stream: np.ndarray, model: StaticModel,
           n_symbols: int, *, impl: str = "pallas", rows_per_block: int = 8,
           packed_lut: bool | None = None, check: bool = True) -> jax.Array:
    """Decode a planned WalkBatch into the flat symbol device array.

    ``packed_lut=None`` (auto) uses the §4.4 packed LUT whenever the model
    fits it (8-bit symbols, n <= 12); the result is bit-identical either way.
    The kernel runs as the platform allows (:func:`interpret_mode`): this
    is the pointer-layout walk, so it runs on CPU only.
    ``check`` asserts full output coverage (one device reduction + a host
    sync; matches the jnp path's behavior — the engine's fused path skips
    it to stay sync-free).
    """
    if packed_lut is None:
        packed_lut = packed_lut_ok(model)
    elif packed_lut and not packed_lut_ok(model):
        raise ValueError("packed LUT requires 8-bit symbols and n <= 12")
    if impl == "jnp":
        return walk_decode_batch(batch, stream, model, n_symbols,
                                 packed_lut=packed_lut)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    packed, per_split, rows, pack, S_pad = pack_batch(batch)
    rows = pad_to_rows(packed, per_split, rows, pack,
                       -(-rows // rows_per_block) * rows_per_block)
    S_pad = rows * pack
    slabs, slab_lo = build_slabs(stream, per_split, rows, pack, rows_per_block)
    # q0 relative to the block slab
    n_blocks = rows // rows_per_block
    lo_rows = np.repeat(slab_lo, rows_per_block).astype(np.int32)
    q0_rel = packed["q0"] - lo_rows[:, None]
    sym_lut, f_lut, F_lut = _luts(model, packed_lut)
    out, qf = walk_decode_pallas(
        jnp.asarray(slabs), sym_lut, f_lut, F_lut,
        jnp.asarray(packed["k"]), jnp.asarray(packed["y"]),
        jnp.asarray(packed["x0"]), jnp.asarray(q0_rel),
        jnp.asarray(packed["g_hi"]), jnp.asarray(packed["start"]),
        jnp.asarray(packed["stop"]), jnp.asarray(packed["keep_lo"]),
        jnp.asarray(packed["keep_hi"]),
        n_bits=model.params.n_bits, ways=batch.ways, n_steps=batch.n_steps,
        rows_per_block=rows_per_block, interpret=interpret_mode())
    flat = scatter_outputs(out, jnp.asarray(per_split["g_hi"]),
                           jnp.asarray(per_split["out_base"]),
                           ways=batch.ways, pack=pack, n_symbols=n_symbols)
    if check:
        assert bool(jnp.all(flat >= 0)), \
            "kernel outputs did not cover all symbols"
    return flat


@functools.partial(jax.jit, static_argnames=("ways", "pack", "n_symbols"))
def scatter_outputs(out_tiles: jax.Array, g_hi: jax.Array, out_base: jax.Array,
                    *, ways: int, pack: int, n_symbols: int) -> jax.Array:
    """(T, rows, 128) kernel tiles -> flat decoded symbols, on device.

    The closed-form position reconstruction of ``_walk_batch_jit``: kept
    positions are unique by construction, non-kept lanes scatter out of
    bounds and are removed by ``mode="drop"`` — the (T, rows, 128) tile is
    never materialized on host.
    """
    with jax.named_scope(SCATTER):
        T, rows, L = out_tiles.shape
        S_pad = rows * pack
        # (T, rows, pack, W) -> (S_pad, T, W)
        tiles = out_tiles.reshape(T, rows, pack, ways).transpose(1, 2, 0, 3)
        tiles = tiles.reshape(S_pad, T, ways)
        t = jnp.arange(T, dtype=jnp.int32)
        lane = jnp.arange(ways, dtype=jnp.int32)
        i = ((g_hi[:, None, None].astype(jnp.int32) - t[None, :, None])
             * ways + lane[None, None, :]
             + out_base[:, None, None].astype(jnp.int32))
        i = jnp.where(tiles >= 0, i, n_symbols)
        outv = jnp.full((n_symbols,), -1, dtype=jnp.int32)
        return outv.at[i.reshape(-1)].set(tiles.reshape(-1), mode="drop",
                                          unique_indices=True)


@functools.partial(jax.jit, static_argnames=(
    "n_bits", "ways", "n_steps", "rows_per_block", "interpret", "pack",
    "n_symbols"))
def decode_tiles_fused(slabs, sym_lut, f_lut, F_lut, k, y, x0, q0, g_hi,
                       start, stop, keep_lo, keep_hi, g_hi_split,
                       out_base_split, *, n_bits: int, ways: int,
                       n_steps: int, rows_per_block: int, interpret: bool,
                       pack: int, n_symbols: int) -> jax.Array:
    """Pallas walk + on-device scatter as ONE executable — the unit the
    decode engine AOT-compiles and caches per shape bucket (DESIGN.md §4):
    the (T, rows, 128) tile lives only between the two fused stages."""
    out, _qf = walk_decode_pallas(
        slabs, sym_lut, f_lut, F_lut, k, y, x0, q0, g_hi, start, stop,
        keep_lo, keep_hi, n_bits=n_bits, ways=ways, n_steps=n_steps,
        rows_per_block=rows_per_block, interpret=interpret)
    return scatter_outputs(out, g_hi_split, out_base_split, ways=ways,
                           pack=pack, n_symbols=n_symbols)


@functools.partial(jax.jit, static_argnames=(
    "n_bits", "ways", "n_steps", "rows_per_block", "interpret", "pack",
    "n_symbols", "tile_steps"))
def decode_tiles_fused_symbol(slabs, sym_lut, f_lut, F_lut, k, y, x0, sym_rel,
                              g_hi, start, stop, keep_lo, keep_hi, g_hi_split,
                              out_base_split, *, n_bits: int, ways: int,
                              n_steps: int, rows_per_block: int,
                              interpret: bool, pack: int, n_symbols: int,
                              tile_steps: int) -> jax.Array:
    """Symbol-layout twin of :func:`decode_tiles_fused`: the pointer-free
    Pallas walk (``slabs`` hold per-block ``words_by_symbol`` windows,
    ``sym_rel`` the slab-relative permutation bases, ``tile_steps`` its T
    tile) + the same on-device scatter, fused into ONE cacheable
    executable.  The scatter reads the whole ``(T, rows, 128)`` tile."""
    from .rans_decode import walk_decode_symbol_pallas
    out = walk_decode_symbol_pallas(
        slabs, sym_lut, f_lut, F_lut, k, y, x0, sym_rel, g_hi, start, stop,
        keep_lo, keep_hi, n_bits=n_bits, ways=ways, n_steps=n_steps,
        rows_per_block=rows_per_block, interpret=interpret,
        tile_steps=tile_steps)
    return scatter_outputs(out, g_hi_split, out_base_split, ways=ways,
                           pack=pack, n_symbols=n_symbols)


def decode_recoil_kernel(plan, stream, final_states, model: StaticModel,
                         **kw) -> np.ndarray:
    """Convenience: RecoilPlan -> kernel decode."""
    from repro.core.recoil import build_split_states
    splits = build_split_states(plan, final_states)
    batch = WalkBatch.from_splits(splits, plan.ways)
    return decode(batch, stream, model, plan.n_symbols, **kw)
