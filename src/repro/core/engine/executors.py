"""Pluggable decode executors behind one interface.

An :class:`Executor` owns one backend's request preparation and lowering:

    upload_stream(words)     -> DeviceStream   (backend decides residency)
    plan(batch, ds, n)       -> DecodePlan     (host prep; pure, cacheable)
    lower(plan)              -> executable     (AOT jit(...).lower().compile())
    run(exe, plan)           -> device syms    (bucketed; caller slices)

:class:`~repro.core.engine.session.DecoderSession` composes an executor with
the executable cache and stats; it never branches on the backend.  Backends:

  * ``jnp``     — XLA walk over the full device-resident stream (fast CPU
                  path; also the oracle for the others);
  * ``pallas``  — the TPU kernel (per-block stream slabs, fused scatter;
                  compiled on TPU, interpreted on CPU);
  * ``sharded`` — multi-device shard_map over the split rows, one bucketed
                  executable per (mesh, bucket); lives in
                  ``repro.parallel.decode_shard`` (imported lazily so the
                  core engine never touches mesh state).
"""

from __future__ import annotations

import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..rans import StaticModel
from ..vectorized import WalkBatch, _walk_batch_jit, _walk_batch_symbol_jit
from .plan import (BucketPolicy, DecodePlan, DeviceStream, LEGACY_POLICY,
                   SPLIT_FIELDS, SYMBOL_SPLIT_FIELDS, pad_split_arrays,
                   pow2_bucket)


class Executor:
    """Backend contract (see module docstring).  ``luts`` is the session's
    device-resident slot-table tuple ``(sym_lut, f_lut, F_lut)`` — the last
    two are None under the §4.4 packed layout.

    ``layout`` is the stream-layout policy (DESIGN.md §9): ``"auto"`` plans
    the pointer-free symbol-indexed walk whenever the handle carries a
    ``words_by_symbol`` permutation and falls back to the pointer walk
    otherwise; ``"pointer"``/``"symbol"`` force one layout (``"symbol"``
    raises on content registered without an emission log).  The selected
    layout joins the plan key, so the two walks never share executables.

    ``policy`` is the bucket ladder (DESIGN.md §11): every compute-shaped
    dimension (split rows, scan steps, output slots) is padded through
    ``policy.work``/``policy.mem`` and ``policy.tag`` joins every plan key,
    so two ladders can never alias one executable.  Stream *residency*
    buckets (``upload_stream``) stay on the fixed pow2 ladder — a handle is
    shared across executors and must not depend on any one policy.
    """

    impl: str = "?"

    def __init__(self, model: StaticModel, packed_lut: bool, luts: tuple,
                 layout: str = "auto", policy: BucketPolicy | None = None):
        if layout not in ("auto", "pointer", "symbol"):
            raise ValueError(f"unknown layout policy {layout!r}")
        self.model = model
        self.packed_lut = packed_lut
        self.luts = luts
        self.layout = layout
        self.policy = policy if policy is not None else LEGACY_POLICY
        # Per-layout plan counts (observability; picked up by ServiceStats).
        # plan() may run from any thread (the broker's workers and direct
        # session users), so bumps go through _count_layout's lock.
        self.layout_plans = {"pointer": 0, "symbol": 0}
        self._layout_lock = threading.Lock()
        # Transfer byte accounting (DESIGN.md §13): padded host->device
        # upload bytes (JnpExecutor bumps) and lazy device->host
        # materialization bytes (PallasExecutor bumps).  Declared on the
        # base so the metrics collector reads one surface per executor.
        self.stream_upload_bytes = 0
        self.host_materialized_bytes = 0

    def _count_layout(self, layout: str) -> None:
        with self._layout_lock:
            self.layout_plans[layout] += 1

    def select_layout(self, ds: DeviceStream) -> str:
        """The layout this request will run under (policy x availability)."""
        if self.layout == "pointer":
            return "pointer"
        if ds.by_symbol is None:
            if self.layout == "symbol":
                raise ValueError(
                    "layout='symbol' requires content registered with an "
                    "emission log (DeviceStream.by_symbol is None)")
            return "pointer"
        return "symbol"

    def upload_stream(self, stream: np.ndarray) -> DeviceStream:
        """Default: host-side registration only (backends that never read
        the whole stream on device, e.g. Pallas per-block slabs)."""
        host = np.ascontiguousarray(np.asarray(stream))
        return DeviceStream(words=None, host=host, n_words=len(host),
                            bucket=pow2_bucket(len(host), 1024))

    def plan(self, batch: WalkBatch, ds: DeviceStream,
             n_symbols: int) -> DecodePlan:
        raise NotImplementedError

    def lower(self, plan: DecodePlan):
        raise NotImplementedError

    def run(self, exe, plan: DecodePlan) -> jax.Array:
        raise NotImplementedError


def _check_sym_alignment(batch: WalkBatch, ds: DeviceStream, W: int) -> None:
    """Loud host-side guards for the symbol layout: the walk gathers whole
    W-wide groups, so every permutation base must be group-aligned, and the
    permutation bucket must hold whole groups."""
    bases = batch.sym_bases()
    if bases.size and np.any(bases % W):
        raise ValueError("sym_base entries must be multiples of ways for "
                         "the symbol-indexed layout")
    if ds.sym_bucket % W:
        raise ValueError(
            f"sym_bucket={ds.sym_bucket} is not a multiple of ways={W}")


class JnpExecutor(Executor):
    """XLA walk over the full device-resident stream."""

    impl = "jnp"

    def __init__(self, model: StaticModel, packed_lut: bool, luts: tuple,
                 layout: str = "auto", policy: BucketPolicy | None = None):
        super().__init__(model, packed_lut, luts, layout, policy)
        # Cross-impl handle fix: a DeviceStream registered by a backend that
        # skips the full-stream upload (words=None) used to be re-uploaded
        # on EVERY decode.  The upgrade is cached here keyed by handle id,
        # with a weakref identity check (not a strong ref — a strong ref
        # would pin every one-off handle's device buffer for the session's
        # lifetime) so a recycled id can never serve a stale upload.
        self._stream_cache: dict[int, tuple[weakref.ref, DeviceStream]] = {}
        self._cache_lock = threading.Lock()   # guards cache + prune + count
        self.stream_uploads = 0

    def _put(self, padded: np.ndarray) -> jax.Array:
        return jnp.asarray(padded)

    def upload_stream(self, stream: np.ndarray) -> DeviceStream:
        host = np.ascontiguousarray(np.asarray(stream))
        bucket = pow2_bucket(len(host), 1024)
        padded = np.zeros(bucket, np.uint32)
        padded[:len(host)] = host.astype(np.uint32)
        self.stream_uploads += 1
        self.stream_upload_bytes += int(padded.nbytes)
        return DeviceStream(words=self._put(padded), host=host,
                            n_words=len(host), bucket=bucket)

    def resident(self, ds: DeviceStream) -> DeviceStream:
        """Ensure the handle has device words, uploading at most once per
        live handle.  Lock-guarded: ``plan()`` may run from any thread
        using the session directly (the pipeline's workers go through the
        service lock, but the session's prepare/execute is public API)."""
        if ds.words is not None:
            return ds
        with self._cache_lock:
            hit = self._stream_cache.get(id(ds))
            if hit is not None and hit[0]() is ds:
                return hit[1]
            up = self.upload_stream(ds.host)
            if len(self._stream_cache) > 512:   # prune dead handles
                for key in [k for k, (ref, _) in self._stream_cache.items()
                            if ref() is None]:
                    del self._stream_cache[key]
            self._stream_cache[id(ds)] = (weakref.ref(ds), up)
            return up

    def _split_bucket(self, S: int) -> int:
        return self.policy.work(S)

    def plan(self, batch: WalkBatch, ds: DeviceStream,
             n_symbols: int) -> DecodePlan:
        layout = self.select_layout(ds)
        self._count_layout(layout)
        p = self.model.params
        W = batch.ways
        s_b = self._split_bucket(batch.k.shape[0])
        steps_b = self.policy.work(batch.n_steps)
        out_b = self.policy.mem(n_symbols)
        arrs = pad_split_arrays(batch, s_b)
        statics = dict(n_bits=p.n_bits, ways=W, n_steps=steps_b,
                       n_symbols=out_b)
        if layout == "symbol":
            _check_sym_alignment(batch, ds, W)
            # The permutation dtype (u16 for small assets, u32 otherwise)
            # joins the key: same sym_bucket, different dtype must not
            # alias one executable.
            key = (self.impl, layout, self.policy.tag, self.packed_lut,
                   p.n_bits, W, s_b, steps_b, ds.sym_bucket,
                   ds.by_symbol.dtype.name, out_b)
            args = (ds.by_symbol, *self.luts,
                    *(arrs[f] for f in SYMBOL_SPLIT_FIELDS))
        else:
            ds = self.resident(ds)
            key = (self.impl, layout, self.policy.tag, self.packed_lut,
                   p.n_bits, W, s_b, steps_b, ds.bucket, out_b)
            args = (ds.words, *self.luts,
                    *(arrs[f] for f in SPLIT_FIELDS))
        return DecodePlan(key=key, args=args, statics=statics,
                          n_symbols=n_symbols, out_bucket=out_b,
                          walk_slots=s_b * W * steps_b, layout=layout)

    def lower(self, plan: DecodePlan):
        jitted = (_walk_batch_symbol_jit if plan.layout == "symbol"
                  else _walk_batch_jit)
        return jitted.lower(
            *plan.args, **plan.statics, ctx_of_index=None).compile()

    def run(self, exe, plan: DecodePlan) -> jax.Array:
        res = exe(*plan.args, ctx_of_index=None)
        if plan.layout == "symbol":
            return res
        out, _qf = res
        return out


class PallasExecutor(Executor):
    """TPU kernel: lane-packed tiles, per-block stream slabs, fused scatter.

    The kernel mode follows the platform (``rans_decode.interpret_mode``):
    compiled by Mosaic on TPU, interpreted on CPU, refused elsewhere.  On
    TPU only the symbol layout has a kernel Mosaic compiles, so pointer
    content raises at plan time there."""

    impl = "pallas"

    def __init__(self, model: StaticModel, packed_lut: bool, luts: tuple, *,
                 rows_per_block: int = 8, layout: str = "auto",
                 policy: BucketPolicy | None = None):
        from repro.kernels.rans_decode.rans_decode import interpret_mode
        super().__init__(model, packed_lut, luts, layout, policy)
        self.interpret = interpret_mode()
        self.rows_per_block = rows_per_block
        # Lazy host materialization for device-resident (ingested / fused)
        # streams: the slab build reads host words, but the copy is deferred
        # to the FIRST plan against the handle — ingest latency never pays
        # it, and jnp/sharded decodes of the same handle never trigger it.
        # Same weakref-identity cache discipline as JnpExecutor's upgrade
        # cache (a recycled id can never serve stale words).  Keys carry the
        # field name: the symbol layout lazily materializes ``by_symbol``
        # through the same cache.
        self._host_cache: dict[tuple, tuple[weakref.ref, np.ndarray]] = {}
        self._cache_lock = threading.Lock()   # guards cache + prune + count
        self.host_materializations = 0

    def _host_arr(self, ds: DeviceStream, field: str,
                  device_arr, n: int) -> np.ndarray:
        with self._cache_lock:
            hit = self._host_cache.get((id(ds), field))
            if hit is not None and hit[0]() is ds:
                return hit[1]
            host = np.ascontiguousarray(np.asarray(device_arr[:n]))
            self.host_materializations += 1
            self.host_materialized_bytes += int(host.nbytes)
            if len(self._host_cache) > 512:   # prune dead handles
                for key in [k for k, (ref, _) in self._host_cache.items()
                            if ref() is None]:
                    del self._host_cache[key]
            self._host_cache[(id(ds), field)] = (weakref.ref(ds), host)
            return host

    def _host_words(self, ds: DeviceStream) -> np.ndarray:
        if ds.host is not None:
            return ds.host
        if ds.words is None:
            raise ValueError("DeviceStream has neither host nor device words")
        return self._host_arr(ds, "words", ds.words, ds.n_words)

    def _host_by_symbol(self, ds: DeviceStream) -> np.ndarray:
        return self._host_arr(ds, "by_symbol", ds.by_symbol, ds.sym_bucket)

    def plan(self, batch: WalkBatch, ds: DeviceStream,
             n_symbols: int) -> DecodePlan:
        from repro.kernels.rans_decode.ops import (build_slabs, pack_batch,
                                                   pad_to_rows)
        from repro.kernels.rans_decode.rans_decode import (
            LANES, POINTER_KERNEL_REFUSAL, check_vmem, window_guard)
        layout = self.select_layout(ds)
        if layout == "pointer" and not self.interpret:
            raise NotImplementedError(POINTER_KERNEL_REFUSAL)
        self._count_layout(layout)
        p = self.model.params
        W = batch.ways
        rpb = self.rows_per_block
        steps_b = self.policy.work(batch.n_steps)
        packed, per_split, rows, pack, _ = pack_batch(batch)
        rows = pad_to_rows(packed, per_split, rows, pack,
                           self.policy.work(-(-rows // rpb)) * rpb)
        out_b = self.policy.mem(n_symbols)
        statics = dict(n_bits=p.n_bits, ways=W, n_steps=steps_b,
                       rows_per_block=rpb, interpret=self.interpret,
                       pack=pack, n_symbols=out_b)
        if layout == "symbol":
            # The walk's T tile, from the shapes and the VMEM limit.
            tile = check_vmem(steps_b, rpb, sum(
                -(-l.shape[0] // LANES) * LANES
                for l in self.luts if l is not None))
            statics["tile_steps"] = tile
            _check_sym_alignment(batch, ds, W)
            # Per-block slab of the PERMUTATION: rows gather symbol indices
            # in [stop + sym_base, start + sym_base], so reuse the q0-window
            # slab builder with hi = start + sym_base, span = start - stop
            # (+1 slack below; build_slabs already clamps at 0), guarded so
            # that each split's walk-order window is one in-range slice.
            win = dict(q0=per_split["start"] + per_split["sym_base"],
                       span=per_split["span"])
            slabs, origin = build_slabs(self._host_by_symbol(ds), win,
                                        rows, pack, rpb,
                                        guard=window_guard(steps_b, W))
            slab_b = self.policy.mem(slabs.shape[1], 8)
            if slab_b > slabs.shape[1]:
                slabs = np.pad(slabs, ((0, 0), (0, slab_b - slabs.shape[1])))
            sym_rel = per_split["sym_base"] - np.repeat(
                origin, rpb * pack).astype(np.int32)
            sym_rel_packed = np.ascontiguousarray(
                np.repeat(sym_rel.reshape(-1, pack), W, axis=1))
            key = (self.impl, layout, self.policy.tag, self.packed_lut,
                   p.n_bits, W, rows, steps_b, slab_b, out_b, rpb, tile)
            args = (jnp.asarray(slabs), *self.luts,
                    jnp.asarray(packed["k"]), jnp.asarray(packed["y"]),
                    jnp.asarray(packed["x0"]), jnp.asarray(sym_rel_packed),
                    jnp.asarray(packed["g_hi"]), jnp.asarray(packed["start"]),
                    jnp.asarray(packed["stop"]),
                    jnp.asarray(packed["keep_lo"]),
                    jnp.asarray(packed["keep_hi"]),
                    jnp.asarray(per_split["g_hi"]),
                    jnp.asarray(per_split["out_base"]))
            return DecodePlan(key=key, args=args, statics=statics,
                              n_symbols=n_symbols, out_bucket=out_b,
                              walk_slots=steps_b * rows * LANES,
                              walk_tiles=steps_b // tile * rows // rpb,
                              layout=layout)
        host_words = self._host_words(ds)
        slabs, slab_lo = build_slabs(host_words, per_split, rows, pack, rpb)
        slab_b = self.policy.mem(slabs.shape[1], 8)
        if slab_b > slabs.shape[1]:
            slabs = np.pad(slabs, ((0, 0), (0, slab_b - slabs.shape[1])))
        lo_rows = np.repeat(slab_lo, rpb).astype(np.int32)
        q0_rel = packed["q0"] - lo_rows[:, None]
        key = (self.impl, layout, self.policy.tag, self.packed_lut,
               p.n_bits, W, rows, steps_b, slab_b, out_b, rpb)
        args = (jnp.asarray(slabs), *self.luts,
                jnp.asarray(packed["k"]), jnp.asarray(packed["y"]),
                jnp.asarray(packed["x0"]), jnp.asarray(q0_rel),
                jnp.asarray(packed["g_hi"]), jnp.asarray(packed["start"]),
                jnp.asarray(packed["stop"]), jnp.asarray(packed["keep_lo"]),
                jnp.asarray(packed["keep_hi"]),
                jnp.asarray(per_split["g_hi"]),
                jnp.asarray(per_split["out_base"]))
        return DecodePlan(key=key, args=args, statics=statics,
                          n_symbols=n_symbols, out_bucket=out_b,
                          walk_slots=steps_b * rows * LANES,
                          walk_tiles=rows // rpb, layout=layout)

    def lower(self, plan: DecodePlan):
        from repro.kernels.rans_decode.ops import (decode_tiles_fused,
                                                   decode_tiles_fused_symbol)
        fn = (decode_tiles_fused_symbol if plan.layout == "symbol"
              else decode_tiles_fused)
        return fn.lower(*plan.args, **plan.statics).compile()

    def run(self, exe, plan: DecodePlan) -> jax.Array:
        return exe(*plan.args)


def make_executor(impl: str, model: StaticModel, packed_lut: bool,
                  luts: tuple, *, rows_per_block: int = 8, mesh=None,
                  layout: str = "auto",
                  policy: BucketPolicy | None = None) -> Executor:
    if impl == "jnp":
        return JnpExecutor(model, packed_lut, luts, layout, policy)
    if impl == "pallas":
        return PallasExecutor(model, packed_lut, luts,
                              rows_per_block=rows_per_block, layout=layout,
                              policy=policy)
    if impl == "sharded":
        from repro.parallel.decode_shard import ShardedExecutor
        return ShardedExecutor(model, packed_lut, luts, mesh=mesh,
                               layout=layout, policy=policy)
    raise ValueError(f"unknown impl {impl!r}")
