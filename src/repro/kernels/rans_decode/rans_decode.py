"""Pallas TPU kernel for the Recoil parallel rANS walk decode (paper §4.1).

Hardware adaptation (DESIGN.md §2).  The paper's CUDA kernel maps one split
to one 32-thread warp; the AVX512 variant packs 16 u32 lanes per register.
On TPU the natural unit is the (8, 128) VPU vector tile, so we:

  * pack ``PACK = 128 // W`` splits side by side along the lane axis (for the
    paper-faithful W = 32 that is 4 splits/row; a W = 128 "TPU-native" codec
    fills the row with one split) — the per-lane decode math is identical,
    only the renorm read-offset assignment is per *segment* of W lanes;
  * put ``ROWS`` packed rows in the sublane axis, so one grid step decodes
    ``ROWS * PACK`` splits on a (ROWS, 128) tile;
  * replace the warp ballot + prefix used by CUDA for read offsets with a
    segmented reversed cumsum over the lane axis (VPU-friendly);
  * keep the slot->(symbol, f, F) tables resident in VMEM as lane-major
    ``(rows, 128)`` tiles.  Mosaic lowers a gather only within one vector
    tile, so a lookup gathers each table row along the lanes with
    ``slot % 128`` and selects row ``slot // 128`` (16 lane gathers for the
    packed n = 11 table).

Step layout: the output block is ``(T, ROWS, 128)`` with the walk step on
the leading axis, so step ``t`` writes ``out_ref[t]`` — a whole-tile store.

Stream residency: each grid block's stream window is a per-block *slab*
(host re-layout, ``ops.build_slabs``) sized to the worst-case consumption of
its splits, so the kernel never needs the full bitstream.

  * Symbol layout (:func:`_walk_kernel_symbol`): a lane's word is a
    closed-form function of its own walk index, and over the walk a split's
    W lanes read one contiguous window of its block's slab.  So the wrapper
    reads each split's window as one slice (:func:`_walk_order_words`; the
    slab carries :func:`window_guard` zeros so that no slice start is ever
    clamped), lays the windows out in walk order, and the kernel reads
    ``words_ref[t]`` — no gather from the stream in the kernel.  This is
    the layout every ingested content serves under.
  * Pointer layout (:func:`_walk_kernel`): the word index depends on the
    walk state (``q`` minus the read offset), so the kernel gathers it from
    the block's slab every step.  Mosaic refuses that 1-D gather and the
    lane ``cumsum`` of the read offsets (:data:`POINTER_KERNEL_REFUSAL`),
    so this kernel runs only interpreted, on CPU; ``PallasExecutor.plan``
    refuses pointer content on a TPU.

VMEM: every block is double-buffered and the output and word blocks span
the whole walk, so a symbol-layout block needs about ``4 * T * ROWS * 128 *
4`` bytes.  ``PallasExecutor.plan`` refuses a walk over
:data:`VMEM_LIMIT_BYTES` (:func:`check_vmem`, ROADMAP S5/R3).

Walk-step recurrences are exactly :func:`repro.core.vectorized._walk_one_split`
(the jnp oracle these kernels are tested against, see ref.py):

    reconstruct (i == k_j):  x_j = (y_j << 16) | word
    decode      (i <  k_j):  slot = x & mask; s = lut[slot]
                             x = f_s * (x >> n) + slot - F_s
                             if x < L: x = (x << 16) | word

Integer notes: states are uint32 (top bit is live — comparisons and shifts
must be unsigned); the decode transform never overflows (DESIGN.md §2 /
rans.py header derivation); no integer division anywhere in decode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime.observability import WALK_GATHER, WALK_KERNEL

LANES = 128  # TPU VPU lane width

#: Scoped VMEM the walk kernels ask Mosaic for.  A TPU v5e core has 128 MiB
#: of VMEM; the rest is left to the compiler's internal scratch.
VMEM_LIMIT_BYTES = 96 * 2 ** 20

#: What Mosaic says when it compiles :func:`_walk_kernel` for a TPU v5e.
POINTER_KERNEL_REFUSAL = (
    "the pointer-layout Pallas walk does not compile for TPU: Mosaic "
    "refuses its read-offset prefix sum ('Unimplemented primitive in Pallas "
    "TPU lowering for KernelType.TC: cumsum'), and its per-lane stream-word "
    "gather from a 1-D slab is not a gather Mosaic lowers ('Only 2D gather "
    "is supported'); register the content with an emission log (symbol "
    "layout) or decode it with impl='jnp'")


def interpret_mode() -> bool:
    """How the walk kernels run on the current backend: interpreted on CPU,
    compiled by Mosaic on TPU.  No other platform has a walk kernel."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas walk kernel runs on 'tpu' (compiled) or 'cpu' "
        f"(interpreted); the default backend is {backend!r}")


def check_vmem(n_steps: int, rows_per_block: int, lut_words: int) -> None:
    """Raise when a symbol-layout walk's grid block does not fit
    :data:`VMEM_LIMIT_BYTES`.  Every block is double-buffered: the
    (T, ROWS, 128) words and output, the slot tables and the nine
    (ROWS, 128) per-lane tiles."""
    tile = rows_per_block * LANES * 4
    need = 2 * (2 * n_steps * tile + 4 * lut_words + 9 * tile)
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"a Pallas walk of {n_steps} steps x {rows_per_block} rows needs "
            f"{need} B of VMEM per block, over the kernel's "
            f"VMEM_LIMIT_BYTES = {VMEM_LIMIT_BYTES} B (the output block "
            f"spans the whole walk); decode this capability with impl='jnp'")


def lut_tiles(lut: jax.Array) -> jax.Array:
    """1-D slot table -> zero-padded lane-major ``(rows, 128)`` tile."""
    n = lut.shape[0]
    rows = -(-n // LANES)
    return jnp.pad(lut.astype(jnp.int32),
                   (0, rows * LANES - n)).reshape(rows, LANES)


def _lut_lookup(lut_refs, slot):
    """``tab[slot]`` for each ``(rows, 128)`` table ref: every table row is
    gathered along the lanes with ``slot % 128`` (a gather Mosaic lowers)
    and the lane keeps the row ``slot // 128``."""
    lo = slot & (LANES - 1)
    hi = slot >> 7                       # slot // LANES
    n_rows = lut_refs[0].shape[0]

    def body(j, accs):
        hit = hi == j
        return tuple(
            jnp.where(hit, jnp.take_along_axis(
                jnp.broadcast_to(ref[pl.ds(j, 1), :], slot.shape), lo,
                axis=1, mode="promise_in_bounds"), acc)
            for ref, acc in zip(lut_refs, accs))

    init = tuple(jnp.zeros(slot.shape, jnp.int32) for _ in lut_refs)
    return jax.lax.fori_loop(0, n_rows, body, init, unroll=n_rows <= 32)


def _kernel_slot_decode(lut_refs, slot):
    """slot -> (symbol, f, F) from the VMEM-resident tables — the §4.4
    packed single-int32 unpack (sym[0:8] | f[8:20] | F[20:32]) when one
    table is given, else three split tables.  Shared by the pointer and
    symbol-layout kernels; the jnp walks' array-based twin is
    ``vectorized._slot_decode``."""
    vals = _lut_lookup(lut_refs, slot)
    if len(vals) == 1:
        pw = vals[0].astype(jnp.uint32)
        s = (pw & jnp.uint32(0xFF)).astype(jnp.int32)
        fs = (pw >> jnp.uint32(8)) & jnp.uint32(0xFFF)
        Fs = (pw >> jnp.uint32(20)) & jnp.uint32(0xFFF)
    else:
        s, fs, Fs = vals
        fs = fs.astype(jnp.uint32)
        Fs = Fs.astype(jnp.uint32)
    return s, fs, Fs


def _segment_read_offsets(reads: jax.Array, ways: int):
    """Per-lane read slots within each W-lane segment, descending-lane-first.

    Returns (suffix_excl, seg_total): lane l's word index is
    ``q - suffix_excl[l]`` and its segment consumed ``seg_total`` words.
    Implemented as a full-row reversed cumsum + segment-boundary correction
    (static-index gathers only), the VPU analogue of a warp ballot+prefix.
    """
    rows, L = reads.shape
    rd = reads.astype(jnp.int32)
    # exclusive prefix (no lane reversals — see EXPERIMENTS §Perf H3):
    # P[j] = reads in lanes < j;  suffix_excl = seg_total - in-seg prefix - rd
    prefix = jnp.cumsum(rd, axis=1)
    padded = jnp.concatenate([jnp.zeros((rows, 1), jnp.int32), prefix], axis=1)
    lanes = jax.lax.iota(jnp.int32, L)
    seg_start = (lanes // ways) * ways
    seg_next = jnp.minimum(seg_start + ways, L)
    p_excl = padded[:, :-1]                       # P[j], exclusive of lane j
    p_start = jnp.take(padded, seg_start, axis=1)
    p_next = jnp.take(padded, seg_next, axis=1)
    seg_total = p_next - p_start
    suffix_excl = seg_total - (p_excl - p_start) - rd
    return suffix_excl, seg_total


def _walk_kernel(stream_ref, *refs, n_bits: int, ways: int, n_steps: int,
                 n_luts: int):
    """One grid step: walk ``n_steps`` symbol groups for a (ROWS, 128) tile.

    ``n_luts == 1`` selects the §4.4 single-table LUT: the table then holds
    the packed int32 slot words (symbol | f << 8 | F << 20).
    """
    lut_refs = refs[:n_luts]
    (k_ref, y_ref, x0_ref, q0_ref, ghi_ref, start_ref, stop_ref, klo_ref,
     khi_ref, out_ref, qf_ref) = refs[n_luts:]
    L_bound = jnp.uint32(1 << 16)
    b_bits = jnp.uint32(16)
    slot_mask = jnp.uint32((1 << n_bits) - 1)
    rows, L = k_ref.shape
    lane_in_seg = jax.lax.broadcasted_iota(jnp.int32, (rows, L), 1) & (ways - 1)

    k = k_ref[...]
    y = y_ref[...].astype(jnp.uint32)
    start = start_ref[...]
    stop = stop_ref[...]
    keep_lo = klo_ref[...]
    keep_hi = khi_ref[...]
    g_hi = ghi_ref[...]
    stream = stream_ref[0, 0]  # block spec delivers (1, 1, slab_words)

    def step(t, carry):
        x, q = carry
        g = g_hi - t
        i = g * ways + lane_in_seg
        active = (i <= start) & (i >= stop)
        recon = active & (i == k)
        dec = active & (i < k)
        slot = (x & slot_mask).astype(jnp.int32)
        s, fs, Fs = _kernel_slot_decode(lut_refs, slot)
        x_dec = fs * (x >> jnp.uint32(n_bits)) + (slot.astype(jnp.uint32) - Fs)
        under = x_dec < L_bound
        reads = recon | (dec & under)
        suffix_excl, seg_total = _segment_read_offsets(reads, ways)
        idx = jnp.clip(q - suffix_excl, 0, stream.shape[0] - 1)
        word = jnp.take(stream, idx).astype(jnp.uint32)
        x_recon = (y << b_bits) | word
        x_dec2 = jnp.where(under, (x_dec << b_bits) | word, x_dec)
        x_new = jnp.where(recon, x_recon, jnp.where(dec, x_dec2, x))
        q_new = q - seg_total
        keep = dec & (i >= keep_lo) & (i < keep_hi)
        out_ref[t] = jnp.where(keep, s, -1)
        return (x_new, q_new)

    x0 = x0_ref[...].astype(jnp.uint32)
    q0 = q0_ref[...]
    _xf, qf = jax.lax.fori_loop(0, n_steps, step, (x0, q0))
    qf_ref[...] = qf


def _walk_kernel_symbol(words_ref, *refs, n_bits: int, ways: int,
                        n_steps: int, n_luts: int):
    """Pointer-free grid step (symbol-indexed layout, DESIGN.md §9).

    ``words_ref[t]`` holds the (ROWS, 128) words the lanes read at step
    ``t`` (gathered into walk order by :func:`_walk_order_words`), so the
    warp-ballot/cumsum read-offset machinery of :func:`_walk_kernel` and
    every stream gather disappear and the carry is just the lane states.
    """
    lut_refs = refs[:n_luts]
    (k_ref, y_ref, x0_ref, ghi_ref, start_ref, stop_ref, klo_ref, khi_ref,
     out_ref) = refs[n_luts:]
    L_bound = jnp.uint32(1 << 16)
    b_bits = jnp.uint32(16)
    slot_mask = jnp.uint32((1 << n_bits) - 1)
    rows, L = k_ref.shape
    lane_in_seg = jax.lax.broadcasted_iota(jnp.int32, (rows, L), 1) & (ways - 1)

    k = k_ref[...]
    y = y_ref[...].astype(jnp.uint32)
    start = start_ref[...]
    stop = stop_ref[...]
    keep_lo = klo_ref[...]
    keep_hi = khi_ref[...]
    g_hi = ghi_ref[...]

    def step(t, x):
        g = g_hi - t
        i = g * ways + lane_in_seg
        active = (i <= start) & (i >= stop)
        recon = active & (i == k)
        dec = active & (i < k)
        slot = (x & slot_mask).astype(jnp.int32)
        s, fs, Fs = _kernel_slot_decode(lut_refs, slot)
        x_dec = fs * (x >> jnp.uint32(n_bits)) + (slot.astype(jnp.uint32) - Fs)
        under = x_dec < L_bound
        word = words_ref[t].astype(jnp.uint32)
        x_recon = (y << b_bits) | word
        x_dec2 = jnp.where(under, (x_dec << b_bits) | word, x_dec)
        x_new = jnp.where(recon, x_recon, jnp.where(dec, x_dec2, x))
        keep = dec & (i >= keep_lo) & (i < keep_hi)
        out_ref[t] = jnp.where(keep, s, -1)
        return x_new

    jax.lax.fori_loop(0, n_steps, step, x0_ref[...].astype(jnp.uint32))


def window_guard(n_steps: int, ways: int) -> tuple[int, int]:
    """Zero words a symbol-layout slab carries before and after its window
    (``ops.build_slabs``'s ``guard``), so that every split's walk-order
    window (:func:`_walk_order_words`) lies inside the slab."""
    return n_steps * ways, ways


def _walk_order_words(slabs: jax.Array, sym_rel: jax.Array, g_hi: jax.Array,
                      *, ways: int, n_steps: int,
                      rows_per_block: int) -> jax.Array:
    """(T, rows, 128) symbol-layout words in walk order: at step ``t`` lane
    ``l`` of row ``r`` reads ``slab[i + sym_rel]`` of its block, where
    ``i = (g_hi - t) * ways + l % ways`` is its walk index.

    ``g_hi`` and ``sym_rel`` are per split (broadcast over its W lanes), so
    at step ``t`` a split's lanes read W consecutive words, and each step
    moves them down by W: over the walk a split reads the one window
    ``slab[sym_rel + (g_hi - T + 1) * W : sym_rel + (g_hi + 1) * W]``, its
    last W-word row first.  So the words are one contiguous ``T * W``-word
    slice per split, relaid to the kernel's tiles by one transpose and a
    reversal of the steps.

    A slice must never move: a gather clamps an out-of-range start, which
    would shift every word of the split.  Every window lies inside a slab
    built with :func:`window_guard`'s ``T * W`` zero words in front and W
    behind: ``ops.build_slabs`` puts the words of a split's indices
    ``[stop, start]`` between the guards, so ``stop + sym_rel >= T * W`` and
    ``start + sym_rel < width - W``; with ``(g_hi + 1) * W >= stop``
    (``start >= stop - 1``) and ``g_hi * W <= start`` the window starts at
    or after 0 and ends at or before ``width``.  Inert padding splits have
    ``g_hi = 0`` and ``sym_rel = T * W``.
    """
    with jax.named_scope(WALK_GATHER):
        n_rows, L = g_hi.shape
        T, W = n_steps, ways
        pack = L // W
        # One value per split: the first lane of its W-lane segment.
        top = g_hi.reshape(n_rows * pack, W)[:, 0]
        rel = sym_rel.reshape(n_rows * pack, W)[:, 0]
        block = jnp.arange(n_rows * pack, dtype=jnp.int32) // (
            rows_per_block * pack)
        starts = jnp.stack([block, rel + (top - T + 1) * W], axis=1)
        windows = jax.lax.gather(
            slabs, starts,
            jax.lax.GatherDimensionNumbers(offset_dims=(1,),
                                           collapsed_slice_dims=(0,),
                                           start_index_map=(0, 1)),
            slice_sizes=(1, T * W), mode="clip")
        words = windows.reshape(n_rows, pack, T, W).transpose(2, 0, 1, 3)
        return jax.lax.rev(words.reshape(T, n_rows, L), (0,))


def _lut_args(sym_lut, f_lut, F_lut) -> tuple:
    packed = f_lut is None
    assert (F_lut is None) == packed, "pass both f_lut and F_lut or neither"
    return tuple(lut_tiles(a) for a in
                 ((sym_lut,) if packed else (sym_lut, f_lut, F_lut)))


def _full_spec(arr) -> pl.BlockSpec:
    return pl.BlockSpec(arr.shape, lambda b: (0,) * arr.ndim)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "ways", "n_steps", "rows_per_block", "interpret"))
def walk_decode_symbol_pallas(slabs: jax.Array, sym_lut: jax.Array,
                              f_lut: jax.Array | None,
                              F_lut: jax.Array | None, k: jax.Array,
                              y: jax.Array, x0: jax.Array, sym_rel: jax.Array,
                              g_hi: jax.Array, start: jax.Array,
                              stop: jax.Array, keep_lo: jax.Array,
                              keep_hi: jax.Array, *, n_bits: int, ways: int,
                              n_steps: int, rows_per_block: int,
                              interpret: bool):
    """pallas_call wrapper for the symbol-indexed walk.  ``slabs`` is the
    per-block window of ``words_by_symbol`` between :func:`window_guard`
    zeros, with ``sym_rel`` already slab-relative; everything else matches
    :func:`walk_decode_pallas` minus the stream pointer (no ``q0``, no
    ``qf`` output).

    Returns int32 (n_steps, n_rows, 128), -1 where not kept.
    """
    luts = _lut_args(sym_lut, f_lut, F_lut)
    n_rows, L = k.shape
    R = rows_per_block
    assert L == LANES and n_rows % R == 0
    assert slabs.shape[0] == n_rows // R
    words = _walk_order_words(slabs, sym_rel, g_hi, ways=ways,
                              n_steps=n_steps, rows_per_block=R)
    row_spec = pl.BlockSpec((R, L), lambda b: (b, 0))
    step_spec = pl.BlockSpec((n_steps, R, L), lambda b: (0, b, 0))
    kernel = functools.partial(_walk_kernel_symbol, n_bits=n_bits, ways=ways,
                               n_steps=n_steps, n_luts=len(luts))
    # The device op takes its HLO name from the innermost name-stack
    # component: ``name`` keeps it the kernel's, under the layer scope.
    with jax.named_scope(WALK_KERNEL):
        return pl.pallas_call(
            kernel,
            grid=(n_rows // R,),
            in_specs=[step_spec, *[_full_spec(a) for a in luts],
                      *[row_spec] * 8],
            out_specs=step_spec,
            out_shape=jax.ShapeDtypeStruct((n_steps, n_rows, L), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
            name="walk_decode_symbol_pallas",
        )(words, *luts, k, y, x0, g_hi, start, stop, keep_lo, keep_hi)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "ways", "n_steps", "rows_per_block", "interpret"))
def walk_decode_pallas(slabs: jax.Array, sym_lut: jax.Array,
                       f_lut: jax.Array | None, F_lut: jax.Array | None,
                       k: jax.Array, y: jax.Array,
                       x0: jax.Array, q0: jax.Array, g_hi: jax.Array,
                       start: jax.Array, stop: jax.Array, keep_lo: jax.Array,
                       keep_hi: jax.Array, *, n_bits: int, ways: int,
                       n_steps: int, rows_per_block: int, interpret: bool):
    """pallas_call wrapper.  All per-split arrays are lane-packed to
    (n_rows, 128) by :mod:`.ops`; ``slabs`` is (n_blocks, slab_words) — the
    per-block stream slab with ``q0`` already slab-relative.

    ``f_lut=F_lut=None`` selects the packed-LUT kernel: ``sym_lut`` must then
    be the :func:`repro.core.rans.pack_decode_lut` int32 table.

    Returns (out, qf): out is int32 (n_steps, n_rows, 128), -1 where not
    kept.
    """
    luts = _lut_args(sym_lut, f_lut, F_lut)
    n_rows, L = k.shape
    R = rows_per_block
    assert L == LANES and n_rows % R == 0
    n_blocks, slab_words = slabs.shape
    assert n_blocks == n_rows // R
    row_spec = pl.BlockSpec((R, L), lambda b: (b, 0))
    step_spec = pl.BlockSpec((n_steps, R, L), lambda b: (0, b, 0))
    kernel = functools.partial(_walk_kernel, n_bits=n_bits, ways=ways,
                               n_steps=n_steps, n_luts=len(luts))
    with jax.named_scope(WALK_KERNEL):
        return pl.pallas_call(
            kernel,
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec((1, 1, slab_words), lambda b: (b, 0, 0)),
                *[_full_spec(a) for a in luts],
                *[row_spec] * 9,
            ],
            out_specs=[step_spec, row_spec],
            out_shape=[
                jax.ShapeDtypeStruct((n_steps, n_rows, L), jnp.int32),
                jax.ShapeDtypeStruct((n_rows, L), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
            name="walk_decode_pallas",
        )(slabs.reshape(n_blocks, 1, slab_words), *luts, k, y, x0, q0, g_hi,
          start, stop, keep_lo, keep_hi)
