"""DecoderSession: a thin plan -> executable cache over a pluggable executor.

The session owns exactly three things (DESIGN.md §4, §4b):

  * device-resident slot tables, uploaded once at construction;
  * the executable cache — ``plan.key -> compiled`` — so a bucket hit
    physically cannot re-trace, and ``stats.compiles`` counts builds exactly;
  * request accounting (:class:`EngineStats`), including the walk's padded
    slots against the symbols it answers.

All backend knowledge lives in the executor (``jnp`` / ``pallas`` /
``sharded`` — see ``engine.executors``).  The prepare/execute split is
public API: callers that re-issue the same request shape (e.g.
``runtime.serve.DecodeService``) cache the :class:`DecodePlan` and skip the
host-side preparation entirely.

Thread model (DESIGN.md §8): the async serving pipeline dispatches decode
and ingest from separate worker threads, so the executable cache and stats
are guarded by ``_lock`` — a cache miss compiles under the lock (a racing
thread waits instead of double-compiling, keeping ``stats.compiles``
exact), while the compiled executable RUNS outside it (XLA executions are
thread-safe; holding the lock there would serialize decode against any
concurrent session user).  Executor ``plan()`` needs no *session* lock —
its only cross-request state is the per-handle identity caches (stream
upgrades, lazy host words, replicated re-pins), each guarded by its own
executor-level lock.
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import numpy as np

from ..rans import StaticModel
from ..vectorized import WalkBatch
from .executors import make_executor
from .plan import DecodePlan, DeviceStream


@dataclasses.dataclass
class EngineStats:
    compiles: int = 0      # executables built (bucket misses)
    cache_hits: int = 0    # decodes served by an existing executable
    decodes: int = 0
    walk_slots: int = 0    # walk positions executed, padding included
    walk_symbols: int = 0  # symbols those walks answered
    walk_tiles: int = 0    # walk-kernel grid steps: T tiles x row blocks

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


class DecoderSession:
    """Device-resident Recoil decoder with a bucketed executable cache.

    ``impl`` is ``"jnp"`` (XLA walk — the fast CPU path), ``"pallas"`` (the
    TPU kernel; compiled on TPU and interpreted on CPU, as the platform
    decides), or ``"sharded"``
    (multi-device shard_map over split rows; pass ``mesh=`` or the executor
    builds a 1-D mesh over every visible device).  ``packed_lut`` defaults
    to auto: the §4.4 packed table whenever the model fits it.

    ``layout`` is the stream-layout policy (DESIGN.md §9): ``"auto"``
    (default) runs the pointer-free symbol-indexed walk for handles that
    carry a ``words_by_symbol`` permutation and the classic pointer walk
    otherwise; ``"pointer"``/``"symbol"`` force one layout.  The layout
    joins the executable-cache key, so the walks never share executables.

    ``policy`` is the bucket-ladder policy (DESIGN.md §11): ``None``
    (default) keeps the legacy pow2/midpoint ladder unless the
    ``REPRO_TUNING_DB`` environment variable points at a tuning database;
    ``"tuned"`` resolves the best persisted profile for this backend (env
    var, then user cache, then the committed CPU defaults); ``"legacy"``
    forces the hand-picked ladder; a :class:`~repro.core.engine.plan
    .BucketPolicy` instance is used directly.  ``policy.tag`` joins every
    executable-cache key, so ladders never alias.
    """

    def __init__(self, model: StaticModel, *, impl: str = "jnp",
                 packed_lut: bool | None = None, rows_per_block: int = 8,
                 mesh=None, layout: str = "auto", policy=None,
                 profiler=None):
        if impl not in ("jnp", "pallas", "sharded"):
            raise ValueError(f"unknown impl {impl!r}")
        # Injected per-plan-key compile timer (duck-typed — see
        # repro.runtime.observability.ExecProfiler; core never imports
        # runtime).  None keeps compiles free of timing branches.
        self.profiler = profiler
        from repro.kernels.rans_decode.ops import _luts, packed_lut_ok
        self.model = model
        self.impl = impl
        if packed_lut is None:
            packed_lut = packed_lut_ok(model)
        elif packed_lut and not packed_lut_ok(model):
            raise ValueError("packed LUT requires 8-bit symbols and n <= 12")
        self.packed_lut = packed_lut
        # Lazy import: tuning sits above plan/executors in the layer order,
        # so the session resolves policies at construction time only.
        from ..tuning import resolve_policy
        self.policy, self.tuning_profile = resolve_policy(
            policy, impl=impl, layout=layout)
        # Device-resident slot tables, uploaded once.
        self._luts = _luts(model, packed_lut)
        self.executor = make_executor(
            impl, model, packed_lut, self._luts,
            rows_per_block=rows_per_block, mesh=mesh, layout=layout,
            policy=self.policy)
        self._exec: dict[tuple, object] = {}
        self._lock = threading.Lock()   # guards _exec + stats (see header)
        self.stats = EngineStats()

    # ------------------------------------------------------------------
    # Streams
    # ------------------------------------------------------------------

    def upload_stream(self, stream: np.ndarray) -> DeviceStream:
        """Register a bitstream once; reuse the handle across decodes.
        Residency is the executor's call (jnp/sharded upload the padded
        words; Pallas registers host-side and DMAs per-block slabs)."""
        return self.executor.upload_stream(stream)

    # ------------------------------------------------------------------
    # Decode
    # ------------------------------------------------------------------

    def decode(self, plan, stream, final_states) -> jax.Array:
        """RecoilPlan + stream (+ transmitted final states) -> device int32
        symbol array.  ``stream`` may be a raw word array or a resident
        :class:`DeviceStream` from :meth:`upload_stream`."""
        from ..recoil import build_split_states
        splits = build_split_states(plan, final_states)
        batch = WalkBatch.from_splits(splits, plan.ways)
        return self.decode_batch(batch, stream, plan.n_symbols)

    def decode_conventional(self, conv) -> jax.Array:
        """Conventional-partitioning adapter through the same engine."""
        from ..conventional import to_split_states
        splits, words, out_bases = to_split_states(conv)
        batch = WalkBatch.from_splits(splits, self.model.params.ways,
                                      out_bases)
        return self.decode_batch(batch, words, conv.n_symbols)

    def prepare(self, batch: WalkBatch, stream, n_symbols: int) -> DecodePlan:
        """Host-side request preparation only (no dispatch): bucket, pad,
        assemble args.  The returned plan may be cached and re-executed."""
        if n_symbols >= 2 ** 31:
            raise ValueError(
                f"n_symbols={n_symbols} exceeds int32 device-scatter indices")
        if not isinstance(stream, DeviceStream):
            stream = self.upload_stream(stream)
        return self.executor.plan(batch, stream, n_symbols)

    def is_compiled(self, plan: DecodePlan) -> bool:
        """Whether :meth:`execute` would dispatch a cached executable for
        this plan (no compile).  Plan-memo surface for speculative warmers
        (DESIGN.md §12): the predictive pre-thinner probes hot-set group
        shapes with this and compiles only the missing ones — already-warm
        shapes cost a dict lookup instead of a redundant dispatch."""
        with self._lock:
            return plan.key in self._exec

    @property
    def executables(self) -> int:
        """Number of distinct compiled executables resident in the cache."""
        with self._lock:
            return len(self._exec)

    def compiled_hlo(self) -> list[str]:
        """The optimized HLO text of every cached executable.  Each
        instruction's ``op_name`` metadata carries the program scopes
        (``repro.runtime.observability.SCOPES``) a profiler trace reports
        as the op's ``tf_op``."""
        with self._lock:
            exes = list(self._exec.values())
        return [exe.as_text() for exe in exes]

    def walk_totals(self) -> tuple[int, int, int]:
        """``(walk_slots, walk_symbols, walk_tiles)``, read together: the
        walk positions every executed plan ran (bucketed steps over all
        its lanes, padding included), the symbols they answered, and the
        walk kernel's grid steps (T tiles x row blocks)."""
        with self._lock:
            return (self.stats.walk_slots, self.stats.walk_symbols,
                    self.stats.walk_tiles)

    def execute(self, plan: DecodePlan) -> jax.Array:
        """Run a prepared plan: compile on bucket miss, else reuse.

        With a profiler injected, the compile (under the lock, counted
        once per bucket miss) is timed per plan key."""
        prof = self.profiler
        with self._lock:
            self.stats.decodes += 1
            self.stats.walk_slots += plan.walk_slots
            self.stats.walk_symbols += plan.n_symbols
            self.stats.walk_tiles += plan.walk_tiles
            exe = self._exec.get(plan.key)
            if exe is None:
                if prof is None:
                    exe = self.executor.lower(plan)
                else:
                    t0 = prof.now()
                    exe = self.executor.lower(plan)
                    prof.record_compile("decode", plan.key, prof.now() - t0)
                self._exec[plan.key] = exe
                self.stats.compiles += 1
            else:
                self.stats.cache_hits += 1
        return self.executor.run(exe, plan)[:plan.n_symbols]

    def decode_batch(self, batch: WalkBatch, stream,
                     n_symbols: int) -> jax.Array:
        return self.execute(self.prepare(batch, stream, n_symbols))
