"""EncoderSession: a thin plan -> executable cache over an ingest executor.

Mirror of :class:`~repro.core.engine.session.DecoderSession` (DESIGN.md
§5).  The session owns exactly three things:

  * device-resident frequency tables, uploaded once at construction
    (static ``[A]`` or adaptive ``[C, A]``);
  * the executable cache — ``(plan.key, tier) -> compiled`` — so a bucket
    hit physically cannot re-trace and ``stats.compiles`` counts builds
    exactly.  Each plan key owns up to TWO executables: the fast tier
    (round-0 heuristic, ~N/2-word stream capacity) and the full tier
    (all retry rounds, N-word capacity), compiled lazily only when a
    content trips a fast-tier flag — heuristic window expansion or
    capacity overflow (``stats.fallbacks``);
  * request accounting (:class:`EncodeStats`).

``ingest`` is the device-resident path: symbols -> (DeviceStream,
RecoilPlan, final states) with only split metadata and scalars visiting the
host — the stream feeds :meth:`repro.runtime.serve.DecodeService.register`
directly.  ``encode`` materializes a host :class:`EncodedStream` (the
oracle-compatible object, used by the parity tests and host tooling).
``ingest_batch`` runs B contents through one vmapped executable.

Thread model (DESIGN.md §8): the async pipeline's ingest worker encodes
while the decode worker serves traffic, so the executable cache and stats
are guarded by ``_lock`` — same contract as
:class:`~repro.core.engine.session.DecoderSession`: a miss compiles under
the lock (no double-compiles, exact ``stats.compiles``), the executable
runs outside it.  ``prepare``/``_materialize`` are pure host work on
request-local data and need no lock.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.plan import DeviceStream, pow2_bucket
from ..interleaved import EncodedStream
from ..recoil import RecoilPlan, SplitPoint
from .executors import make_encode_executor
from .ops import ROUNDS
from .plan import EncodePlan

# Device-side H and index arithmetic is int32; 2*N must not wrap.
MAX_SYMBOLS = 1 << 30


@dataclasses.dataclass
class EncodeStats:
    compiles: int = 0      # executables built (bucket misses)
    cache_hits: int = 0    # ingests served by an existing executable
    encodes: int = 0       # pipeline dispatches (batch counts as one)
    fallbacks: int = 0     # full-tier re-runs (round-0 miss / overflow)
    extends: int = 0       # incremental re-ingests (suffix-only encodes)
    resume_evictions: int = 0   # LRU-evicted resumable tails

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Splice kernels (incremental re-ingest, DESIGN.md §10).  Gather + select
# only; shapes are the static residency buckets and every size-dependent
# quantity is a traced scalar, so warm extends with stable buckets re-run
# existing traces — jax.jit's cache keys on (shapes, out_len) alone.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("out_len",))
def _splice_words(old, suffix, old_n, total_n, out_len: int):
    """Concatenate the suffix stream after the registered words: emission
    (g, j) pairs of the suffix are lexicographically after every old pair,
    so suffix offsets rebase by plain ``+ old_n`` (no interleaving)."""
    q = jnp.arange(out_len, dtype=jnp.int32)
    o = old[jnp.clip(q, 0, old.shape[0] - 1)].astype(jnp.uint32)
    s = suffix[jnp.clip(q - old_n, 0, suffix.shape[0] - 1)]
    return jnp.where(q >= total_n, jnp.uint32(0),
                     jnp.where(q < old_n, o, s))


@functools.partial(jax.jit, static_argnames=("out_len",))
def _splice_by_symbol(old, suffix, n_old, n_total, origin, out_len: int):
    """Splice the suffix grid's permutation entries after the registered
    ones: suffix-local flat index ``l`` is absolute symbol ``origin + l``
    (``origin = (N_old // W) * W``, the suffix grid's origin)."""
    i = jnp.arange(out_len, dtype=jnp.int32)
    o = old[jnp.clip(i, 0, old.shape[0] - 1)].astype(jnp.uint32)
    s = suffix[jnp.clip(i - origin, 0, suffix.shape[0] - 1)]
    return jnp.where(i >= n_total, jnp.uint32(0),
                     jnp.where(i < n_old, o, s))


def _permutation_dtype(n_words: int):
    """u16 permutation variant: with fewer than 2**16 stream words every
    entry fits a u16, halving symbol-layout residency for small assets.
    The dtype joins the decode plan keys (`engine.executors`) so u16 and
    u32 buckets never alias one executable."""
    return jnp.uint16 if n_words < (1 << 16) else jnp.uint32


@dataclasses.dataclass
class _ResumeState:
    """Per-name tail of the last ingest: everything ``extend`` resumes
    from.  ``final_states`` seed the suffix encode; the device handles are
    the registered content the splice appends to."""

    n_symbols: int
    final_states: np.ndarray     # uint32[W]
    stream: DeviceStream
    plan: RecoilPlan


@dataclasses.dataclass(frozen=True)
class IngestResult:
    """One ingested content: everything ``DecodeService.register`` needs.

    ``stream.words`` is the device-resident padded word array (``host`` is
    None — the bitstream never visited the host); ``plan`` carries the
    Definition-4.1 split metadata, already validated.
    """

    stream: DeviceStream
    plan: RecoilPlan
    final_states: np.ndarray   # uint32[W]
    n_words: int


class EncoderSession:
    """Device-resident Recoil ingest engine with a bucketed executable cache.

    ``model`` is a :class:`~repro.core.rans.StaticModel` or a
    :class:`~repro.core.adaptive.ContextModel` (adaptive, index-keyed
    distributions; pass the per-symbol ``ctx`` map to each request, or rely
    on ``model.ctx`` when the lengths match).  ``window`` is the Def-4.1
    candidate half-window (must match the oracle's to stay bit-exact).
    ``fast_rounds=False`` disables the round-0 fast path and always runs
    the full-rounds executable (mainly for tests).

    ``policy`` selects the bucket ladder for the group-count compute dim
    (same contract as :class:`DecoderSession`: ``None`` = legacy unless
    ``REPRO_TUNING_DB`` is set, ``"tuned"``/``"legacy"``, or a
    :class:`~repro.core.engine.plan.BucketPolicy` instance).

    ``resume_capacity`` bounds the per-name resumable-tail map that
    :meth:`extend` reads: least-recently-used tails beyond it are evicted
    (``stats.resume_evictions``) and later extends of those names fall back
    to a full re-ingest — without the bound a long-lived service pins one
    device-resident stream per content name forever.
    """

    def __init__(self, model, *, impl: str = "jnp", window: int = 96,
                 fast_rounds: bool = True, policy=None,
                 resume_capacity: int = 64, profiler=None):
        # Injected per-plan-key compile timer (duck-typed, shared with the
        # decode session under session="encode"; core never imports
        # runtime).  None keeps compiles free of timing branches.
        self.profiler = profiler
        self.model = model
        self.adaptive = np.asarray(model.f).ndim == 2
        self.params = model.params
        f = np.asarray(model.f).astype(np.int32)
        F = np.asarray(model.F).astype(np.int32)
        self.alphabet = f.shape[-1]
        from ..tuning import resolve_policy
        self.policy, self.tuning_profile = resolve_policy(
            policy, impl=impl, layout="encode")
        self.executor = make_encode_executor(
            impl, jnp.asarray(f), jnp.asarray(F), n_bits=self.params.n_bits,
            ways=self.params.ways, adaptive=self.adaptive, window=window,
            policy=self.policy)
        self.fast_rounds = fast_rounds
        if resume_capacity < 1:
            raise ValueError("resume_capacity must be >= 1")
        self.resume_capacity = resume_capacity
        self._exec: dict[tuple, object] = {}
        self._lock = threading.Lock()   # guards _exec + stats (see header)
        # LRU of resumable tails, most-recent last; guarded by _lock.
        self._resume: collections.OrderedDict[str, _ResumeState] = \
            collections.OrderedDict()
        self.stats = EncodeStats()

    # ------------------------------------------------------------------
    # Prepare / execute (public, mirrors DecoderSession)
    # ------------------------------------------------------------------

    def prepare(self, symbols, n_splits: int = 1, ctx=None) -> EncodePlan:
        """Host-side request preparation only (no dispatch): bucket, pad,
        assemble args.  The returned plan may be cached and re-executed."""
        self._check_symbols(symbols)
        if n_splits < 1:
            raise ValueError("need at least one decoder thread")
        return self.executor.plan(symbols, n_splits, self._ctx_for(symbols,
                                                                   ctx))

    def prepare_batch(self, contents, n_splits, ctxs=None) -> EncodePlan:
        for c in contents:
            self._check_symbols(c)
        if ctxs is None and self.adaptive:
            ctxs = [self._ctx_for(c, None) for c in contents]
        return self.executor.plan_batch(contents, n_splits, ctxs)

    def execute(self, plan: EncodePlan) -> tuple[dict, int]:
        """Run a prepared plan: compile on bucket miss, else reuse.  Returns
        ``(outputs, words_bucket)`` — the capacity tier that produced the
        outputs.  When the fast tier flags a split slot it could not settle
        (round-0 heuristic miss) or a stream-capacity overflow, the plan
        re-runs under the lazily compiled full tier (bit-exactness over
        speed; correctness never depends on the flags)."""
        with self._lock:
            self.stats.encodes += 1
        fast = self.fast_rounds and plan.words_bucket < plan.words_bucket_full
        rounds = 1 if self.fast_rounds else ROUNDS
        cap = plan.words_bucket if fast else plan.words_bucket_full
        out = self._run(plan, rounds, cap)
        flagged = bool(np.any(np.asarray(out["overflow"]))) or (
            rounds < ROUNDS
            and bool(np.any(np.asarray(out["needs_expansion"]))))
        if flagged:
            with self._lock:
                self.stats.fallbacks += 1
            cap = plan.words_bucket_full
            out = self._run(plan, ROUNDS, cap)
        return out, cap

    def _run(self, plan: EncodePlan, rounds: int, cap: int):
        """One tier dispatch (compiling the tier's executable on a miss)."""
        return self.executor.run(self._executable(plan, rounds, cap), plan)

    def _executable(self, plan: EncodePlan, rounds: int, words_bucket: int):
        key = plan.key + (rounds, words_bucket)
        prof = self.profiler
        with self._lock:
            exe = self._exec.get(key)
            if exe is None:
                if prof is None:
                    exe = self.executor.lower(plan, expand_rounds=rounds,
                                              words_bucket=words_bucket)
                else:
                    t0 = prof.now()
                    exe = self.executor.lower(plan, expand_rounds=rounds,
                                              words_bucket=words_bucket)
                    prof.record_compile("encode", key, prof.now() - t0)
                self._exec[key] = exe
                self.stats.compiles += 1
            else:
                self.stats.cache_hits += 1
        return exe

    # ------------------------------------------------------------------
    # Ingest (device-resident) / encode (host materialization)
    # ------------------------------------------------------------------

    def ingest(self, symbols, n_splits: int, ctx=None,
               name: str | None = None) -> IngestResult:
        """symbols -> (device stream, validated RecoilPlan, final states).

        The stream never visits the host; the returned handle plugs into
        ``DecodeService.register`` / any jnp-family decode executor.
        Passing ``name`` records the resumable tail (final states + device
        handles) so later :meth:`extend` calls can re-ingest only a delta.
        """
        plan = self.prepare(symbols, n_splits, ctx)
        out, cap = self.execute(plan)
        res = self._materialize(out, plan, plan.n_symbols, cap,
                                symbols=symbols)
        if name is not None:
            self._remember(name, res)
        return res

    def _remember(self, name: str, res: IngestResult) -> None:
        with self._lock:
            self._resume[name] = _ResumeState(
                n_symbols=res.plan.n_symbols,
                final_states=np.asarray(res.final_states),
                stream=res.stream, plan=res.plan)
            self._resume.move_to_end(name)
            while len(self._resume) > self.resume_capacity:
                self._resume.popitem(last=False)
                self.stats.resume_evictions += 1

    def can_extend(self, name: str) -> bool:
        with self._lock:
            return name in self._resume

    def forget(self, name: str) -> None:
        """Drop the resumable tail (callers fall back to full re-ingest)."""
        with self._lock:
            self._resume.pop(name, None)

    def extend(self, name: str, delta, ctx=None) -> IngestResult:
        """Incremental re-ingest: append ``delta`` to the content last
        ingested (or extended) under ``name``, encoding ONLY the suffix.

        Resumes the per-lane rANS chains from the cached ``final_states``
        (each lane's chain depends only on its own symbols, so the suffix
        emissions are bit-exact vs a full re-encode of the grown content),
        then splices stream words, split points, and permutation entries
        onto the registered device arrays — cost proportional to the
        delta, not the asset.  Raises ``KeyError`` when ``name`` has no
        resumable tail; the caller's fallback is a full re-ingest
        (DESIGN.md §10).
        """
        with self._lock:
            state = self._resume.get(name)
            if state is not None:
                self._resume.move_to_end(name)   # touch: extend = recent use
        if state is None:
            raise KeyError(
                f"no resumable ingest state for {name!r}; fall back to a "
                "full ingest (pass name= to ingest to record the tail)")
        d = int(np.asarray(delta).size)
        if d == 0:
            raise ValueError("extend needs a non-empty delta")
        self._check_symbols(delta)
        N0 = state.n_symbols
        if N0 + d >= MAX_SYMBOLS:
            raise ValueError(
                f"extended content ({N0} + {d} symbols) exceeds the int32 "
                f"device planning range (< {MAX_SYMBOLS})")
        W = self.params.ways
        head = N0 % W
        # Keep split density: the registered plan placed M0 points over N0
        # symbols, so the suffix gets ~M0 * d / N0 new ones (>= 0).
        m0 = state.plan.n_threads - 1
        n_splits = 1 + (-(-m0 * d // N0) if N0 else m0)
        plan = self.executor.plan_extend(
            delta, n_splits, head, state.final_states,
            self._ctx_for_extend(d, N0, ctx))
        out, cap = self.execute(plan)
        with self._lock:
            self.stats.extends += 1
        res = self._materialize_extend(out, state, delta)
        self._remember(name, res)
        return res

    def _ctx_for_extend(self, d: int, n0: int, ctx):
        if not self.adaptive:
            if ctx is not None:
                raise ValueError("ctx map given but the model is static")
            return None
        if ctx is not None:
            return ctx
        model_ctx = getattr(self.model, "ctx", None)
        if model_ctx is not None and len(model_ctx) >= n0 + d:
            return np.asarray(model_ctx)[n0:n0 + d]
        raise ValueError(
            f"adaptive extend of {d} symbols at offset {n0} needs a ctx "
            f"map (model.ctx covers "
            f"{0 if model_ctx is None else len(model_ctx)})")

    def _materialize_extend(self, out, state: _ResumeState,
                            delta) -> IngestResult:
        """Splice the suffix pipeline's outputs onto the registered
        content (DESIGN.md §10 invariants: suffix emissions strictly
        follow old ones in (g, j) order; suffix split coordinates rebase
        by the grid origin / old word count; old split points stay valid
        because every new completion exceeds N_old)."""
        self._check_flags(out, delta)
        W = self.params.ways
        N0 = state.n_symbols
        d = int(np.asarray(delta).size)
        n_total = N0 + d
        origin = (N0 // W) * W            # suffix grid's absolute origin
        old_n = state.stream.n_words
        suffix_n = int(out["n_words"])
        n_words = old_n + suffix_n

        found = np.asarray(out["split_found"])
        q = np.asarray(out["split_q"])
        k = np.asarray(out["split_k"]).astype(np.int64)
        y = np.asarray(out["split_y"]).astype(np.uint32)
        new_points = tuple(
            SplitPoint(offset=int(q[m]) + old_n, k=k[m] + origin, y=y[m])
            for m in np.flatnonzero(found))
        rplan = RecoilPlan(points=state.plan.points + new_points,
                           n_symbols=n_total, n_words=n_words, ways=W)
        rplan.validate(self.params.lower_bound)

        bucket = pow2_bucket(n_words, 1024)
        words = _splice_words(state.stream.words, out["stream"],
                              jnp.int32(old_n), jnp.int32(n_words),
                              out_len=bucket)
        sym_bucket = pow2_bucket(n_total, 1024)
        by = _splice_by_symbol(state.stream.by_symbol, out["by_symbol"],
                               jnp.int32(N0), jnp.int32(n_total),
                               jnp.int32(origin), out_len=sym_bucket)
        by = by.astype(_permutation_dtype(n_words))
        ds = DeviceStream(words=words, host=None, n_words=n_words,
                          bucket=bucket, by_symbol=by, sym_bucket=sym_bucket)
        return IngestResult(stream=ds, plan=rplan,
                            final_states=np.asarray(out["final_states"]),
                            n_words=n_words)

    def ingest_batch(self, contents, n_splits, ctxs=None) -> list[IngestResult]:
        """B contents through ONE vmapped dispatch; per-content results are
        device slices of the stacked outputs."""
        plan = self.prepare_batch(contents, n_splits, ctxs)
        out, cap = self.execute(plan)
        return [
            self._materialize({k: v[i] for k, v in out.items()}, plan,
                              int(np.asarray(contents[i]).size), cap,
                              symbols=contents[i])
            for i in range(plan.batch)]

    def encode(self, symbols, ctx=None) -> EncodedStream:
        """Host :class:`EncodedStream` (stream + emission log), bit-exact vs
        ``interleaved.encode_interleaved`` — the parity surface."""
        plan = self.prepare(symbols, 1, ctx)
        out, _cap = self.execute(plan)
        self._check_flags(out, symbols)
        n_words = int(out["n_words"])
        return EncodedStream(
            stream=np.asarray(out["stream"][:n_words]).astype(np.uint16),
            final_states=np.asarray(out["final_states"]),
            n_symbols=plan.n_symbols, params=self.params,
            k_of_word=np.asarray(out["k_of_word"][:n_words]).astype(np.int64),
            y_of_word=np.asarray(out["y_of_word"][:n_words]))

    # ------------------------------------------------------------------
    # Internals
    # ------------------------------------------------------------------

    def _ctx_for(self, symbols, ctx):
        if not self.adaptive:
            if ctx is not None:
                raise ValueError("ctx map given but the model is static")
            return None
        if ctx is not None:
            return ctx
        n = int(np.asarray(symbols).size)
        model_ctx = getattr(self.model, "ctx", None)
        if model_ctx is not None and len(model_ctx) >= n:
            return np.asarray(model_ctx)[:n]
        raise ValueError(
            f"adaptive ingest of {n} symbols needs a ctx map (model.ctx "
            f"covers {0 if model_ctx is None else len(model_ctx)})")

    def _check_symbols(self, symbols) -> None:
        syms = np.asarray(symbols)
        if syms.size >= MAX_SYMBOLS:
            raise ValueError(
                f"n_symbols={syms.size} exceeds the int32 device planning "
                f"range (< {MAX_SYMBOLS})")
        if syms.size and (int(syms.min()) < 0
                          or int(syms.max()) >= self.alphabet):
            raise ValueError(
                f"symbols outside the model alphabet [0, {self.alphabet}): "
                f"min {int(syms.min())}, max {int(syms.max())}")

    def _check_flags(self, out, symbols) -> None:
        if bool(np.asarray(out["zero_freq"]).any()):
            detail = ""
            if symbols is not None:
                syms = np.unique(np.asarray(symbols, np.int64))
                f = np.asarray(self.model.f)
                bad = (syms[np.asarray(f[..., syms].min(axis=0) == 0).ravel()]
                       if f.ndim == 2 else syms[f[syms] == 0])
                detail = f" (symbols {bad[:8].tolist()})"
            raise ValueError(
                "content uses symbols with zero quantized frequency in the "
                f"model{detail} — it cannot be encoded; rebuild the model "
                "from counts covering these symbols")

    def _materialize(self, out, plan: EncodePlan, n_symbols: int,
                     words_bucket: int, symbols=None) -> IngestResult:
        self._check_flags(out, symbols)
        W = self.params.ways
        n_words = int(out["n_words"])
        found = np.asarray(out["split_found"])
        q = np.asarray(out["split_q"])
        k = np.asarray(out["split_k"]).astype(np.int64)
        y = np.asarray(out["split_y"]).astype(np.uint32)
        points = tuple(
            SplitPoint(offset=int(q[m]), k=k[m], y=y[m])
            for m in np.flatnonzero(found))
        rplan = RecoilPlan(points=points, n_symbols=n_symbols,
                           n_words=n_words, ways=W)
        rplan.validate(self.params.lower_bound)
        # Slice the capacity tier down to the residency bucket uploaded
        # streams get (pow2 of the real word count, floor 1024), so
        # ingested and registered copies of like-sized contents share
        # decode executables and the padding tail stays bounded.
        bucket = min(words_bucket, pow2_bucket(n_words, 1024))
        # The symbol-indexed permutation rides along (same residency-bucket
        # discipline, floor 1024 so fused offsets stay group-aligned); the
        # pipeline emits it at the padded group-grid length, sliced/padded
        # here once per ingest.
        sym_bucket = pow2_bucket(n_symbols, 1024)
        by = out["by_symbol"]
        if by.shape[0] >= sym_bucket:
            by = by[:sym_bucket]
        else:
            by = jnp.concatenate(
                [by, jnp.zeros(sym_bucket - by.shape[0], jnp.uint32)])
        by = by.astype(_permutation_dtype(n_words))
        ds = DeviceStream(words=out["stream"][:bucket], host=None,
                          n_words=n_words, bucket=bucket,
                          by_symbol=by, sym_bucket=sym_bucket)
        return IngestResult(stream=ds, plan=rplan,
                            final_states=np.asarray(out["final_states"]),
                            n_words=n_words)
