"""Sharded multi-device decode: the paper's §3.3 scalability, mesh edition.

Recoil's pitch is that one bitstream scales to whatever parallelism the
decoder has; on a device mesh that parallelism is the mesh itself.  The
:class:`ShardedExecutor` shards the padded split rows of a ``WalkBatch``
across every device of a mesh with ``shard_map``:

  * split arrays (``k``/``y``/``x0``/... — leading dim = bucketed split
    count) arrive row-sharded over the product of the mesh axes; the slot
    tables arrive replicated;
  * the stream arrives **slab-thinned**: shard ``s`` receives only the
    window ``[lo_s, hi_s]`` of the stream its rows can read.  A row's walk
    consumes at most one word per walked index, descending from its ``q0``,
    so its reads live in ``[q0 - (start - stop), q0]``; the shard window is
    the union over the shard's non-inert rows, padded to a common pow2 slab
    bucket, gathered ON DEVICE from the resident stream (works for fused
    microbatch streams that never had host words), and each row's ``q0`` is
    rebased to its shard's slab.  This replaces the full-stream replication
    the first sharded tier shipped with: per-device stream bytes drop from
    ``stream_bucket`` to ``slab_bucket`` (~``1/n_shards`` for evenly
    planned splits, plus pow2 rounding);
  * each device runs the SAME vmapped walk the single-device jnp executor
    runs (``_walk_batch_impl``) over its local rows, scattering its kept
    symbols into a full-size local output initialized to -1;
  * kept output positions are disjoint across splits by construction
    (disjoint ``[keep_lo, keep_hi)`` windows), so a ``lax.pmax`` over the
    mesh axes merges the per-shard outputs exactly — every position is
    written by one shard and -1 everywhere else;
  * the merged output is replicated (``out_specs=P()``; the pmax makes the
    shards identical, ``check_vma=False`` because shard_map cannot prove
    that statically through the walk's scatter).

Bucketing: the split-row bucket is ``n_shards * work_bucket(ceil(S /
n_shards))`` so every shard gets the same inert-padded row count and any
split count within the per-shard bucket reuses the executable; the slab
bucket (pow2, floor 1024) joins the cache key.  One bucketed AOT
executable per (mesh, bucket) — the session's ``EngineStats`` counts
compiles exactly as for the single-device backends.

Inputs are ``device_put`` with explicit NamedShardings at plan time, so the
AOT executable's expected shardings always match and repeat traffic moves
no split bytes through implicit reshards.
"""

from __future__ import annotations

import math
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.engine.executors import JnpExecutor, _check_sym_alignment
from repro.core.engine.plan import (BucketPolicy, DecodePlan, SPLIT_FIELDS,
                                    SYMBOL_SPLIT_FIELDS, pad_split_arrays)
from repro.core.vectorized import _walk_batch_impl, _walk_batch_symbol_impl


class ShardedExecutor(JnpExecutor):
    """Multi-device decode over a mesh (see module docstring).

    ``mesh=None`` builds a 1-D mesh over every visible device
    (:func:`repro.launch.mesh.make_decode_mesh`); any mesh works — split
    rows shard over the *product* of its axes, so the smoke meshes from
    ``repro.launch.mesh.make_smoke_mesh`` are valid too.
    """

    impl = "sharded"

    def __init__(self, model, packed_lut: bool, luts: tuple, *, mesh=None,
                 layout: str = "auto", policy: BucketPolicy | None = None):
        super().__init__(model, packed_lut, luts, layout, policy)
        if mesh is None:
            from repro.launch.mesh import make_decode_mesh
            mesh = make_decode_mesh()
        self.mesh = mesh
        self.axes = tuple(mesh.axis_names)
        self.n_shards = int(math.prod(mesh.shape[a] for a in self.axes))
        self._repl = NamedSharding(mesh, P())
        self._rows = NamedSharding(mesh, P(self.axes))
        self._slab_rows = NamedSharding(mesh, P(self.axes, None))
        # Slot tables replicate across the mesh once, at construction.
        self.luts = tuple(None if l is None else jax.device_put(l, self._repl)
                          for l in luts)
        # Replicated re-pin cache: plan() must read the slab gather source
        # under a mesh-consistent sharding, but re-pinning the SAME resident
        # handle on every plan would move stream bytes per request under
        # broker traffic (the pipeline plans on every fused-group miss).
        # Weakref-identity keyed, like the jnp executor's upgrade cache;
        # lock-guarded like it too (plan() may run from any thread).  Keys
        # carry the field name — the symbol layout re-pins ``by_symbol``
        # through the same cache.
        self._repl_cache: dict[tuple, tuple[weakref.ref, jax.Array]] = {}
        self._repl_lock = threading.Lock()

    def _replicated(self, ds, field: str = "words") -> jax.Array:
        with self._repl_lock:
            hit = self._repl_cache.get((id(ds), field))
            if hit is not None and hit[0]() is ds:
                return hit[1]
            repl = jax.device_put(getattr(ds, field), self._repl)
            if len(self._repl_cache) > 512:   # prune dead handles
                for key in [k for k, (ref, _) in self._repl_cache.items()
                            if ref() is None]:
                    del self._repl_cache[key]
            self._repl_cache[(id(ds), field)] = (weakref.ref(ds), repl)
            return repl

    # Streams upload replicated over the mesh; plan() thins them into
    # per-shard slabs with an on-device gather, so the replicated copy is
    # only the gather source, and repeat traffic (memoized plans) holds
    # just the row-sharded slabs.
    def _put(self, padded: np.ndarray) -> jax.Array:
        return jax.device_put(padded, self._repl)

    def _split_bucket(self, S: int) -> int:
        """Equal inert-padded rows per shard: shard count x per-shard work
        bucket, so ragged split counts still divide the mesh evenly."""
        return self.n_shards * self.policy.work(-(-S // self.n_shards))

    def plan(self, batch, ds, n_symbols: int) -> DecodePlan:
        layout = self.select_layout(ds)
        self._count_layout(layout)
        p = self.model.params
        W = batch.ways
        S = batch.k.shape[0]
        s_b = self._split_bucket(S)
        steps_b = self.policy.work(batch.n_steps)
        out_b = self.policy.mem(n_symbols)
        arrs = pad_split_arrays(batch, s_b)
        rows_per = s_b // self.n_shards
        statics = dict(n_bits=p.n_bits, ways=W, n_steps=steps_b,
                       n_symbols=out_b)

        start = np.full(s_b, -1, np.int64)
        stop = np.zeros(s_b, np.int64)
        start[:S] = batch.start
        stop[:S] = batch.stop
        act = (start >= 0).reshape(self.n_shards, rows_per)

        if layout == "symbol":
            _check_sym_alignment(batch, ds, W)
            # Per-shard slab thinning, permutation edition: row m's walk
            # gathers symbol indices [stop + sym_base, start + sym_base],
            # so the shard slab is that union sliced from words_by_symbol
            # (rounded down to a whole W-group so group rows stay aligned).
            # Replaces the pointer path's q0-read-window union.  Chunked
            # decode (DESIGN.md §10) rides this for free: a ChunkSpec's
            # rows keep absolute start/stop windows, so each chunk's slabs
            # cover only that chunk's permutation slice.
            by_sym = self._replicated(ds, "by_symbol")
            sym_base = np.zeros(s_b, np.int64)
            sym_base[:S] = batch.sym_bases()
            row_lo = (stop + sym_base).reshape(self.n_shards, rows_per)
            row_hi = (start + sym_base).reshape(self.n_shards, rows_per)
            lo_s = np.where(act, row_lo, np.int64(1) << 60).min(axis=1)
            hi_s = np.where(act, row_hi, np.int64(-1)).max(axis=1)
            lo_s = np.clip(np.minimum(lo_s, hi_s + 1), 0, None)
            lo_s = (lo_s // W) * W                       # whole-group origin
            slab_len = int(np.maximum(hi_s - lo_s + 1, 0).max()) if S else 1
            slab_b = self.policy.mem(max(slab_len, W), 1024)
            gidx = jnp.asarray(lo_s.astype(np.int32))[:, None] \
                + jnp.arange(slab_b, dtype=jnp.int32)
            slabs = jax.device_put(
                by_sym[jnp.clip(gidx, 0, ds.sym_bucket - 1)],
                self._slab_rows)
            arrs["sym_base"] = jnp.asarray(
                (sym_base - np.repeat(lo_s, rows_per)).astype(np.int32))
            # Permutation dtype joins the key (u16 small-asset variant):
            # slabs inherit it, so u16/u32 must not alias one executable.
            key = (self.impl, layout, self.policy.tag, self.n_shards,
                   self.axes, self.packed_lut, p.n_bits, W, s_b, steps_b,
                   slab_b, ds.by_symbol.dtype.name, out_b)
            args = (slabs, *self.luts,
                    *(jax.device_put(arrs[f], self._rows)
                      for f in SYMBOL_SPLIT_FIELDS))
            return DecodePlan(key=key, args=args, statics=statics,
                              n_symbols=n_symbols, out_bucket=out_b,
                              walk_slots=s_b * W * steps_b, layout=layout)

        ds = self.resident(ds)
        # Fused streams built by the microbatcher (device-side concatenate)
        # may come back without an explicit sharding; re-pin replicated so
        # the slab gather below reads a mesh-consistent source (memoized
        # per live handle — warm broker traffic moves no stream bytes).
        stream = self._replicated(ds)

        # --- per-shard read windows (host arithmetic on the padded layout;
        # inert padding rows carry start = -1 and are excluded) ---
        q0 = np.zeros(s_b, np.int64)
        q0[:S] = batch.q0
        row_lo = (q0 - (start - stop)).reshape(self.n_shards, rows_per)
        row_hi = q0.reshape(self.n_shards, rows_per)
        lo_s = np.where(act, row_lo, np.int64(1) << 60).min(axis=1)
        hi_s = np.where(act, row_hi, np.int64(-1)).max(axis=1)
        lo_s = np.clip(np.minimum(lo_s, hi_s + 1), 0, None)  # empty -> len 0
        slab_len = int(np.maximum(hi_s - lo_s + 1, 0).max()) if S else 1
        slab_b = self.policy.mem(max(slab_len, 1), 1024)
        gidx = jnp.asarray(lo_s.astype(np.int32))[:, None] \
            + jnp.arange(slab_b, dtype=jnp.int32)
        slabs = jax.device_put(
            stream[jnp.clip(gidx, 0, ds.bucket - 1)], self._slab_rows)
        arrs["q0"] = jnp.asarray(
            (q0 - np.repeat(lo_s, rows_per)).astype(np.int32))

        key = (self.impl, layout, self.policy.tag, self.n_shards, self.axes,
               self.packed_lut, p.n_bits, W, s_b, steps_b, slab_b, out_b)
        args = (slabs, *self.luts,
                *(jax.device_put(arrs[f], self._rows) for f in SPLIT_FIELDS))
        return DecodePlan(key=key, args=args, statics=statics,
                          n_symbols=n_symbols, out_bucket=out_b,
                          walk_slots=s_b * W * steps_b, layout=layout)

    def lower(self, plan: DecodePlan):
        st = plan.statics
        axes = self.axes

        if plan.layout == "symbol":
            def local(slab, sym_lut, f_lut, F_lut, *splits):
                out = _walk_batch_symbol_impl(
                    slab[0], sym_lut, f_lut, F_lut, *splits,
                    n_bits=st["n_bits"], ways=st["ways"],
                    n_steps=st["n_steps"], n_symbols=st["n_symbols"],
                    ctx_of_index=None)
                return jax.lax.pmax(out, axes)
        else:
            def local(slab, sym_lut, f_lut, F_lut, *splits):
                out, _qf = _walk_batch_impl(
                    slab[0], sym_lut, f_lut, F_lut, *splits,
                    n_bits=st["n_bits"], ways=st["ways"],
                    n_steps=st["n_steps"], n_symbols=st["n_symbols"],
                    ctx_of_index=None)
                return jax.lax.pmax(out, axes)

        sharded = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P(axes, None), P(), P(), P()) + (P(axes),) * 10,
            out_specs=P(), check_vma=False)
        return jax.jit(sharded).lower(*plan.args).compile()

    def run(self, exe, plan: DecodePlan) -> jax.Array:
        return exe(*plan.args)
