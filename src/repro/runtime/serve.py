"""Serving runtime: batched prefill + decode with KV/SSM caches, plus the
content-delivery decode service.

``ServeEngine`` is the host-side loop the content-delivery and dry-run paths
share: jit-compiled prefill and decode_step (shapes static per bucket),
greedy or temperature sampling, straggler-safe timing hooks.

``DecodeService`` is the rANS side of serving: encoded payloads registered
once (stream device-resident), split metadata thinned per request to the
client's parallelism, and every decode dispatched through a persistent
:class:`repro.core.engine.DecoderSession` so steady-state traffic never
recompiles (DESIGN.md §4).  Content enters either pre-encoded
(``register``, validated against the service model before it can serve)
or as raw symbols (``ingest``/``ingest_batch`` — the
:class:`repro.core.encode.EncoderSession` ingest engine encodes and
split-plans on device and the stream feeds registration without ever
visiting the host, DESIGN.md §5).  Two request paths:

  * ``decode(name, n_threads)`` — immediate single dispatch.  The prepared
    :class:`~repro.core.engine.DecodePlan` is memoized per
    ``(name, n_threads)``, so repeat traffic skips the host-side thinning
    (``combine_plan`` + ``build_split_states`` + ``WalkBatch.from_splits``)
    AND the engine's padding/arg assembly — the steady state is one cached
    executable call on cached device args.
  * ``submit(name, n_threads) -> DecodeTicket`` — microbatched.  Pending
    requests coalesce into ONE fused dispatch (``concat_walk_batches``:
    per-request ``out_base`` offsets write disjoint output windows; across
    different contents the resident streams are fused with per-stream word
    offsets applied to ``q0``).  Results come back as per-request device
    slices of the fused output.  Flush policy: an explicit ``flush()``, a
    full microbatch (``microbatch`` requests pending), a submit arriving
    after the oldest pending request has waited ``max_delay_ms``, or a
    ``DecodeTicket.result()`` on a still-pending ticket.  ``max_delay_ms``
    is a latency bound checked at submit time — size is the primary
    trigger; keep it comfortably above per-request COLD prep time or a
    first burst fragments into partial groups.

``start_pipeline()`` upgrades the service to the async serving pipeline
(``runtime.pipeline``, DESIGN.md §8): a broker with capability lanes,
adaptive microbatching, admission control, and an ingest worker that
overlaps encode traffic with decode dispatch.  With a broker attached the
service is a thin façade — ``submit``/``flush`` route to the broker's
queues and worker threads; ``decode``/``ingest``/``register`` remain
callable from any thread (the service lock + session locks make the
shared caches safe, see §8's lock model).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encode import EncoderSession
from repro.core.engine import (ChunkSpec, DecodePlan, DecoderSession,
                               DeviceStream, chunk_walk_batch,
                               concat_walk_batches, pow2_bucket,
                               with_symbol_layout)
from repro.core.rans import StaticModel
from repro.core.recoil import RecoilPlan, build_split_states, combine_plan
from repro.core.vectorized import WalkBatch
from repro.models.model import LM
from repro.runtime.faultinject import NULL_INJECTOR
from repro.runtime.observability import (DELIVER, INGEST, NULL_TRACE, PLAN,
                                         THIN, Observability)


@dataclasses.dataclass
class ServeStats:
    prefill_ms: float
    decode_ms_per_token: float
    tokens_generated: int


class ServeEngine:
    def __init__(self, lm: LM, params, cache_len: int = 0):
        self.lm = lm
        self.params = params
        self.cache_len = cache_len or lm.cfg.max_cache
        self._prefill = jax.jit(
            lambda p, t, f: lm.prefill(p, t, f, cache_len=self.cache_len))
        self._step = jax.jit(lm.decode_step)

    def generate(self, tokens: np.ndarray, n_tokens: int,
                 frames: Optional[np.ndarray] = None,
                 temperature: float = 0.0, seed: int = 0):
        """tokens: (B, S) prompt -> (B, n_tokens) continuations."""
        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, jnp.asarray(tokens),
                                      None if frames is None
                                      else jnp.asarray(frames))
        jax.block_until_ready(logits)
        t1 = time.perf_counter()
        rng = jax.random.PRNGKey(seed)
        out = []
        cur = self._sample(logits, temperature, rng)
        for i in range(n_tokens):
            out.append(np.asarray(cur))
            logits, cache = self._step(self.params, cache, cur[:, None])
            rng, sub = jax.random.split(rng)
            cur = self._sample(logits, temperature, sub)
        jax.block_until_ready(logits)
        t2 = time.perf_counter()
        stats = ServeStats(
            prefill_ms=(t1 - t0) * 1e3,
            decode_ms_per_token=(t2 - t1) * 1e3 / max(n_tokens, 1),
            tokens_generated=n_tokens * tokens.shape[0])
        return np.stack(out, axis=1), stats

    @staticmethod
    def _sample(logits, temperature, rng):
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / temperature, axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class _Content:
    stream: DeviceStream
    plan: RecoilPlan
    final_states: np.ndarray


@dataclasses.dataclass
class ServiceStats:
    """Engine counters + the service's own plan/microbatch accounting."""

    compiles: int
    cache_hits: int
    decodes: int
    plan_hits: int
    plan_misses: int
    coalesced_requests: int
    fused_dispatches: int
    flushes: int
    ingests: int = 0           # contents registered through the encode engine
    extends: int = 0           # incremental re-ingests (suffix-only encodes)
    stream_requests: int = 0   # chunked streaming decodes (submit_stream)
    encode_compiles: int = 0   # ingest-engine executable builds
    encode_fallbacks: int = 0  # full-rounds heuristic re-runs
    host_materializations: int = 0  # lazy device->host stream copies (pallas)
    symbol_plans: int = 0      # requests planned on the symbol-indexed layout
    pointer_plans: int = 0     # requests planned on the pointer-walk fallback

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


class DecodeTicket:
    """Handle for a submitted (possibly coalesced) decode request.

    ``trace`` is the ticket's span context (DESIGN.md §13) — a live
    :class:`~repro.runtime.observability.Trace` on traced paths,
    :data:`NULL_TRACE` for ticketless fillers and disabled tracing, so
    dispatch instrumentation never branches on ticket provenance.
    """

    __slots__ = ("_svc", "out", "err", "trace")

    def __init__(self, svc: "DecodeService"):
        self._svc = svc
        self.out = None
        self.err = None
        self.trace = NULL_TRACE

    def _fulfill(self, out=None, err=None) -> None:
        """Dispatch completion hook — the broker's ticket subclass overrides
        this to also release cross-thread waiters and timestamp the
        completion; keep all result delivery going through it."""
        self.out = out
        self.err = err

    def result(self) -> jax.Array:
        """The request's device symbol array; forces a flush if the fused
        dispatch holding this request has not run yet.  Re-raises the
        dispatch error if the flush holding this request failed."""
        if self.out is None and self.err is None:
            self._svc.flush()
        if self.err is not None:
            raise self.err
        if self.out is None:
            raise RuntimeError("request was never dispatched")
        return self.out


class StreamTicket:
    """Handle for a chunked streaming decode (DESIGN.md §10).

    The asset's thinned split rows are partitioned into ``n_chunks``
    completion-ordered chunks (``engine.plan.chunk_walk_batch``); each chunk
    is its own (bucketed, cached) executable dispatch, so the first symbols
    are ready after ~1/n_chunks of the asset's decode work instead of all of
    it.  ``chunk(i)`` blocks until chunk ``i`` has been dispatched and
    returns its device symbol array (symbols ``base..base+length`` of the
    asset); iterating the ticket yields the chunks in order.  ``result()``
    concatenates them back into the whole asset.  Timing hooks
    (``submitted_at``/``first_chunk_at``/``completed_at``) feed the
    streaming benchmark's time-to-first-chunk measurement.
    """

    __slots__ = ("n_chunks", "specs", "err", "submitted_at",
                 "first_chunk_at", "completed_at", "_chunks", "_events",
                 "trace")

    def __init__(self, n_chunks: int):
        self.n_chunks = n_chunks
        self.specs: list[ChunkSpec] | None = None   # set at dispatch time
        self.err: Exception | None = None
        self.trace = NULL_TRACE
        self.submitted_at = time.perf_counter()
        self.first_chunk_at: float | None = None
        self.completed_at: float | None = None
        self._chunks = [None] * n_chunks
        self._events = [threading.Event() for _ in range(n_chunks)]

    def _fulfill_chunk(self, i: int, out) -> None:
        self._chunks[i] = out
        now = time.perf_counter()
        if i == 0:
            self.first_chunk_at = now
        if i == self.n_chunks - 1:
            self.completed_at = now
        self._events[i].set()

    def _fail(self, err: Exception) -> None:
        self.err = err
        for ev in self._events:
            ev.set()

    def chunk(self, i: int, timeout: float | None = None) -> jax.Array:
        """Device int32 symbols of chunk ``i`` (dispatched, possibly still
        executing — ``jax.block_until_ready`` to pin arrival time)."""
        if not self._events[i].wait(timeout):
            raise TimeoutError(f"chunk {i} not dispatched within {timeout}s")
        if self.err is not None:
            raise self.err
        return self._chunks[i]

    def __iter__(self):
        for i in range(self.n_chunks):
            yield self.chunk(i)

    def result(self) -> jax.Array:
        parts = list(self)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


class DecodeService:
    """Serve Recoil-encoded content to clients of any parallel capacity.

    One :class:`DecoderSession` per service (one model, one executable
    cache).  ``register`` uploads a payload's bitstream to the device once;
    ``decode``/``submit`` thin the split metadata to the request's thread
    count (a pure metadata deletion, paper §3.3) and run the cached
    bucketed executable — zero recompiles for request sizes within a
    bucket.  See the module docstring for the two request paths.
    """

    # Fused-plan memo bound (FIFO eviction): each entry pins fused device
    # split arrays, so distinct request groups must not accumulate forever.
    MAX_FUSED_PLANS = 256

    def __init__(self, model: StaticModel, *, impl: str = "jnp",
                 microbatch: int = 8, max_delay_ms: float = 50.0,
                 observe: bool = True, trace_capacity: int = 1024,
                 faults=None, **session_kw):
        # Observability first: the decode/encode sessions take its shared
        # profiler at construction.  ``observe=False`` is the zero-overhead
        # configuration the CI guard benchmarks against (NULL_TRACE
        # everywhere, no profiler timing branches).
        self.obs = Observability(enabled=observe,
                                 trace_capacity=trace_capacity)
        # Fault injection (DESIGN.md §14): named fault points in dispatch /
        # ingest / executor boundaries consult this injector.  Production
        # default is the shared no-op singleton; the reliability suite and
        # bench pass a ``runtime.faultinject.FaultInjector`` to drive the
        # unhappy paths deterministically.
        self.faults = faults if faults is not None else NULL_INJECTOR
        self.session = DecoderSession(model, impl=impl,
                                      profiler=self.obs.profiler,
                                      **session_kw)
        self.obs.attach_service(self)
        self.microbatch = int(microbatch)
        self.max_delay_ms = float(max_delay_ms)
        self._encoder: EncoderSession | None = None   # built on first ingest
        self._contents: dict[str, _Content] = {}
        # Content generation counters: bumped on every (re-)registration so
        # downstream memos keyed on content identity (the pipeline's
        # capability registry) can invalidate without a callback channel.
        self._generations: dict[str, int] = {}
        # (name, n_threads) -> prepared request, two granularities: the
        # thinned WalkBatch (fusable) and the full DecodePlan (single path).
        self._batches: dict[tuple, tuple[WalkBatch, int]] = {}
        self._plans: dict[tuple, DecodePlan] = {}
        # (name, n_threads, n_chunks) -> [(DecodePlan, ChunkSpec), ...]:
        # the chunk axis of the streaming path.  Each chunk's plan hits the
        # same bucketed executable cache as whole-asset requests, so a warm
        # stream is n_chunks cached dispatches with zero host prep.
        self._chunk_plans: dict[tuple, list] = {}
        # Fused-dispatch memo: a request GROUP that recurs (hot working set
        # under steady traffic) reuses its fused DecodePlan + slice offsets,
        # so a warm flush is one cached executable call, zero host prep.
        self._fused_plans: dict[tuple, tuple[DecodePlan, list[int], int]] = {}
        self._pending: list[tuple[DecodeTicket, tuple, WalkBatch, int]] = []
        self._pending_t0 = 0.0
        self._plan_hits = 0
        self._plan_misses = 0
        self._coalesced = 0
        self._fused = 0
        self._flushes = 0
        self._ingests = 0
        self._extends = 0
        self._streams = 0
        # Service lock (DESIGN.md §8): guards content/memos/pending/counters.
        # Reentrant because register() flushes stale pending requests while
        # already holding it.  Heavy work never runs under it — encode and
        # decode executables run outside, so the broker's ingest worker and
        # decode worker only contend for the short host-prep sections.
        self._lock = threading.RLock()
        self._broker = None   # attached by start_pipeline()

    def register(self, name: str, plan: RecoilPlan, stream, final_states,
                 *, model=None, emission_log=None) -> None:
        """Register encoded content.  ``stream`` is a raw word array or an
        already-resident :class:`DeviceStream` (e.g. from :meth:`ingest` —
        never re-uploaded).  The content is validated against the service's
        model before it can serve: a mismatched payload raises here instead
        of silently mis-decoding for every client.  Pass ``model`` (the
        model the content was encoded with) to also check the distribution
        tables themselves.

        ``emission_log`` is the encoder's ``k_of_word`` array (one flat
        symbol index per stream word).  When present, the symbol-indexed
        decode layout (DESIGN.md §9) is derived on device at registration —
        the wire bytes are untouched; decode just drops the stream pointer.
        Host-registered content without a log serves via the pointer-walk
        fallback."""
        # Corruption fault point BEFORE validation: an armed corruptor
        # mutates the payload here, and the validation below must reject it
        # loudly — the reliability suite's proof that a poisoned container
        # cannot reach serving state.
        stream = self.faults.corrupt("service.register", stream, name=name)
        _validate_content(self.session.model, plan, stream, final_states,
                          enc_model=model)
        with self._lock:
            # Pending requests hold thinned batches of the CURRENT content;
            # dispatch them against it before it is replaced (a re-registered
            # name with stale pending metadata would otherwise decode the new
            # stream with the old split windows — silently wrong symbols).
            # (Broker-mode groups are immune: they are built at dispatch
            # time under this lock, so every group sees one consistent
            # content version.)
            if any(key[0] == name for _, key, _, _ in self._pending):
                self._flush_pending()
            if not isinstance(stream, DeviceStream):
                stream = self.session.upload_stream(stream)
            if emission_log is not None and stream.by_symbol is None:
                stream = with_symbol_layout(stream, emission_log,
                                            plan.n_symbols)
            self._contents[name] = _Content(
                stream=stream, plan=plan,
                final_states=np.asarray(final_states, np.uint32))
            self._generations[name] = self._generations.get(name, 0) + 1
            for cache in (self._batches, self._plans,    # re-registration
                          self._chunk_plans):
                for key in [k for k in cache if k[0] == name]:
                    del cache[key]
            self._fused_plans.clear()

    def generation(self, name: str) -> int:
        """Monotonic per-content registration counter (0 = never seen)."""
        with self._lock:
            return self._generations.get(name, 0)

    def layout_for(self, name: str) -> str:
        """The decode layout this content serves under: ``"symbol"`` when
        its registration carried an emission log (pointer-free walk),
        ``"pointer"`` otherwise — modulated by the session's layout policy
        (a ``layout="pointer"`` service never uses the permutation)."""
        with self._lock:
            ds = self._contents[name].stream
        return self.session.executor.select_layout(ds)

    def content(self, name: str) -> _Content:
        """The current registered content record (snapshot — the record is
        immutable; re-registration swaps the whole object)."""
        with self._lock:
            return self._contents[name]

    def content_snapshot(self, name: str) -> tuple[int, _Content]:
        """``(generation, content)`` read atomically under the service lock.

        The capability registry's original two-step read — ``generation()``
        then ``content()`` — could interleave with a concurrent ``extend()``
        re-registration and pair the OLD generation tag with the NEW bytes
        (or vice versa), poisoning a memo entry until the next bump.  One
        lock hold makes the pair consistent by construction; derivations
        tagged with this generation are guaranteed to be of these bytes.
        Raises ``KeyError`` for unregistered names."""
        with self._lock:
            gen = self._generations.get(name, 0)
            if gen == 0:
                raise KeyError(f"content {name!r} is not registered")
            return gen, self._contents[name]

    # ------------------------------------------------------------------
    # Ingest (encode engine -> registration, stream stays on device)
    # ------------------------------------------------------------------

    def ingest(self, name: str, symbols: np.ndarray, n_splits: int) -> RecoilPlan:
        """Encode + split-plan ``symbols`` on device (``core.encode``
        ingest engine) and register the result under ``name``.  On the
        jnp/sharded backends the bitstream never visits the host; only the
        split metadata does.  (The Pallas backend slabs from host words,
        but the device->host copy is LAZY — deferred to the first pallas
        decode of the handle, so ingest latency never pays it and the
        executor's ``host_materializations`` counts the copies exactly.)
        Returns the registered :class:`RecoilPlan` (e.g. for clients that
        want to know the supported parallelism)."""
        self.faults.fire("service.ingest", name=name)
        with jax.profiler.TraceAnnotation(INGEST):
            res = self._encode_session().ingest(symbols, n_splits, name=name)
        self.register(name, res.plan, res.stream, res.final_states)
        with self._lock:
            self._ingests += 1
        return res.plan

    def extend(self, name: str, delta: np.ndarray) -> RecoilPlan:
        """Incrementally re-ingest: append ``delta`` symbols to an ingested
        content and re-register the grown asset.  The encoder resumes the
        rANS state chain from the cached final states, so only the suffix is
        encoded (cost proportional to ``len(delta)``, not the asset) and the
        spliced stream is bit-exact with a full re-encode (DESIGN.md §10).
        Re-registration bumps the content generation, so capability-registry
        memos and this service's plan memos invalidate exactly as they would
        for any other content swap.  Raises ``KeyError`` when ``name`` was
        never ingested through this service (host-registered content has no
        resumable encoder state — fall back to a full :meth:`ingest`)."""
        self.faults.fire("service.extend", name=name)
        with jax.profiler.TraceAnnotation(INGEST):
            res = self._encode_session().extend(name, delta)
        self.register(name, res.plan, res.stream, res.final_states)
        with self._lock:
            self._extends += 1
        return res.plan

    def can_extend(self, name: str) -> bool:
        """Whether :meth:`extend` would succeed for ``name`` (i.e. the
        encoder holds resumable state from a prior :meth:`ingest`)."""
        with self._lock:
            enc = self._encoder
        return enc is not None and enc.can_extend(name)

    def ingest_batch(self, contents: dict, n_splits: int) -> dict:
        """Ingest many contents through ONE vmapped encode dispatch:
        ``{name: symbols}`` -> ``{name: RecoilPlan}``."""
        names = list(contents)
        with jax.profiler.TraceAnnotation(INGEST):
            results = self._encode_session().ingest_batch(
                [contents[n] for n in names], n_splits)
        for n, r in zip(names, results):
            self.register(n, r.plan, r.stream, r.final_states)
            with self._lock:
                self._ingests += 1
        return {n: r.plan for n, r in zip(names, results)}

    def _encode_session(self) -> EncoderSession:
        with self._lock:
            if self._encoder is None:
                # A service opted into tuning opts its ingest engine in too
                # (the encoder resolves its OWN profile key — decode
                # ladders never apply to encode group counts).
                self._encoder = EncoderSession(
                    self.session.model,
                    policy="tuned" if self.session.tuning_profile is not None
                    else None,
                    profiler=self.obs.profiler)
            return self._encoder

    # ------------------------------------------------------------------
    # Request preparation (memoized per (name, n_threads))
    # ------------------------------------------------------------------

    def _thinned_batch(self, name: str, n_threads: int) -> tuple[WalkBatch, int]:
        """Memoized host prep (caller holds ``_lock``).  ``plan_hits``/
        ``plan_misses`` count here (and on the deeper ``_plans`` memo in
        :meth:`decode`): every request increments exactly one of the two
        counters exactly once — a hit means the per-request host preparation
        was skipped at some layer."""
        key = (name, n_threads)
        hit = self._batches.get(key)
        if hit is not None:
            self._plan_hits += 1
            return hit
        self._plan_misses += 1
        c = self._contents[name]
        with jax.profiler.TraceAnnotation(THIN):
            plan = combine_plan(c.plan, n_threads)
            batch = WalkBatch.from_splits(
                build_split_states(plan, c.final_states), plan.ways)
        self._batches[key] = (batch, plan.n_symbols)
        return self._batches[key]

    # ------------------------------------------------------------------
    # Immediate path
    # ------------------------------------------------------------------

    def prepare_request(self, name: str, n_threads: int):
        """Build (and memoize) the single-request :class:`DecodePlan` for
        ``(name, n_threads)`` WITHOUT dispatching it — thinned batch, split
        states, and the symbol-layout permutation slice all derived and
        device-staged.  This is the speculative pre-thinner's unit of work
        (DESIGN.md §12): after it runs, the first real request for the pair
        is a pure memo hit + cached-executable dispatch.  Identical to the
        host-prep half of :meth:`decode`; both paths share the memo and the
        plan hit/miss counters."""
        key = (name, n_threads)
        with self._lock:
            plan = self._plans.get(key)
            if plan is None:
                batch, n = self._thinned_batch(name, n_threads)
                plan = self.session.prepare(
                    batch, self._contents[name].stream, n)
                self._plans[key] = plan
            else:
                self._plan_hits += 1
            return plan

    def evict_prepared(self, name: str, n_threads: int) -> bool:
        """Drop the memoized plan + thinned batch for one (name, capability)
        pair (predictive-cache eviction under an entry budget — the pair
        re-derives bit-exactly on its next request).  Returns whether
        anything was dropped."""
        key = (name, int(n_threads))
        with self._lock:
            dropped = self._plans.pop(key, None) is not None
            dropped = (self._batches.pop(key, None) is not None) or dropped
            return dropped

    def decode(self, name: str, n_threads: int) -> jax.Array:
        """Decode registered content at the client's parallelism; returns a
        device int32 symbol array (no host round-trip)."""
        return self.session.execute(self.prepare_request(name, n_threads))

    # ------------------------------------------------------------------
    # Chunked streaming path (DESIGN.md §10)
    # ------------------------------------------------------------------

    def _chunked_plans(self, name: str, n_threads: int,
                       n_chunks: int) -> list:
        """Memoized per-chunk plans (caller holds ``_lock``): the request's
        thinned rows partitioned completion-ordered into chunks
        (``chunk_walk_batch``), each prepared as its own bucketed
        :class:`DecodePlan` against the SAME resident stream — chunk ``k``
        only reads the stream-word prefix ``specs[k].words_end``, which is
        what makes decode-while-arriving sound."""
        key = (name, n_threads, int(n_chunks))
        hit = self._chunk_plans.get(key)
        if hit is not None:
            self._plan_hits += 1
            return hit
        batch, n = self._thinned_batch(name, n_threads)
        stream = self._contents[name].stream
        specs = chunk_walk_batch(batch, n, n_chunks)
        plans = [(self.session.prepare(s.batch, stream, s.length), s)
                 for s in specs]
        self._chunk_plans[key] = plans
        return plans

    def stream_chunk_count(self, name: str, n_threads: int,
                           n_chunks: int) -> int:
        """The chunk count a stream request will actually yield
        (``n_chunks`` clamped to the request's split-row count — a chunk
        must hold at least one split row)."""
        with self._lock:
            rows = min(int(n_threads), self._contents[name].plan.n_threads)
        return max(1, min(int(n_chunks), rows))

    def decode_chunks(self, name: str, n_threads: int,
                      n_chunks: int) -> list[jax.Array]:
        """Decode registered content as ``n_chunks`` pipelined dispatches;
        returns the per-chunk device symbol arrays in asset order.  Each
        dispatch is asynchronous (XLA enqueues), so chunk 0 is ready after
        ~1/n_chunks of the asset's decode work while later chunks are still
        executing — concatenating the parts equals :meth:`decode` exactly."""
        with self._lock:
            self._streams += 1
            plans = self._chunked_plans(name, n_threads, n_chunks)
        return [self.session.execute(p) for p, _ in plans]

    def submit_stream(self, name: str, n_threads: int,
                      n_chunks: int = 8) -> StreamTicket:
        """Chunked streaming decode returning a :class:`StreamTicket` that
        yields per-chunk results as they complete.  With a pipeline broker
        attached the dispatch runs on the broker's worker thread (overlapped
        with ingest traffic); otherwise the chunks are dispatched inline —
        still pipelined, because each chunk's executable is enqueued
        asynchronously."""
        broker = self._broker
        if broker is not None:
            submit = getattr(broker, "submit_stream", None)
            if submit is not None:
                return submit(name, n_threads, n_chunks)
        ticket = StreamTicket(self.stream_chunk_count(name, n_threads,
                                                      n_chunks))
        ticket.trace = self.obs.tracer.start(
            "stream", name=name, t0=ticket.submitted_at,
            n_threads=n_threads, path="sync")
        ticket.trace.phase("admission")
        return self.dispatch_stream(name, n_threads, n_chunks, ticket)

    def dispatch_stream(self, name: str, n_threads: int, n_chunks: int,
                        ticket: StreamTicket) -> StreamTicket:
        """Plan under the service lock, dispatch each chunk OUTSIDE it
        (broker backend + sync path share this).  ``ticket.n_chunks`` must
        equal :meth:`stream_chunk_count` for the request."""
        try:
            self.faults.fire("service.dispatch_stream", name=name)
            with self._lock:
                self._streams += 1
                plans = self._chunked_plans(name, n_threads, n_chunks)
            if len(plans) != ticket.n_chunks:
                raise ValueError(
                    f"ticket expects {ticket.n_chunks} chunks but the plan "
                    f"yields {len(plans)} — content re-registered with "
                    f"fewer splits between submit and dispatch")
            ticket.trace.phase("dispatch", chunks=len(plans))
            ticket.specs = [spec for _, spec in plans]
            for i, (plan, _) in enumerate(plans):
                ticket._fulfill_chunk(i, self.session.execute(plan))
            ticket.trace.phase("execute")
            ticket.trace.finish("ok")
        except Exception as e:
            ticket._fail(e)
            ticket.trace.finish("error", error=repr(e))
            raise
        return ticket

    # ------------------------------------------------------------------
    # Microbatched path
    # ------------------------------------------------------------------

    def submit(self, name: str, n_threads: int,
               deadline=None, retries: int = 0) -> DecodeTicket:
        """Queue a request for coalescing (see module docstring for the
        flush policy).  With a pipeline broker attached
        (:meth:`start_pipeline`) the request is queued on the broker's
        capability lanes instead and dispatched by its worker thread;
        ``deadline`` (a class name or explicit ms budget, DESIGN.md §12)
        then bounds its queue wait and ``retries`` opts the ticket into
        bounded transient-fault retry (DESIGN.md §14).  The sync path has
        no lane scheduler or retry queue, so its flat ``max_delay_ms``
        bound already caps the wait and both are accepted but unused."""
        broker = self._broker
        if broker is None:
            with self._lock:
                # Re-check under the lock: a raced start_pipeline() flushed
                # _pending while attaching, so queueing here now would
                # strand the ticket — route to the broker instead.
                broker = self._broker
                if broker is None:
                    now = time.perf_counter()
                    if (self._pending and (now - self._pending_t0) * 1e3
                            > self.max_delay_ms):
                        self._flush_pending()
                    key = (name, n_threads)
                    batch, n = self._thinned_batch(name, n_threads)
                    ticket = DecodeTicket(self)
                    # Sync path spans: admission = host prep at submit time
                    # (the thinning above); the wait until flush is "queue".
                    ticket.trace = self.obs.tracer.start(
                        "decode", name=name, t0=now,
                        n_threads=n_threads, path="sync")
                    ticket.trace.phase("admission")
                    if not self._pending:
                        self._pending_t0 = now
                    self._pending.append((ticket, key, batch, n))
                    if len(self._pending) >= self.microbatch:
                        self._flush_pending()
                    return ticket
        return broker.submit(name, n_threads, deadline=deadline,
                             retries=retries)

    def _flush_pending(self) -> None:
        """Dispatch the sync-path pending queue (no broker interaction —
        safe to call while holding the service lock, e.g. from
        :meth:`register`'s stale-pending guard; a broker ``drain`` here
        could deadlock against workers waiting on that lock)."""
        with self._lock:
            reqs, self._pending = self._pending, []
        if not reqs:
            return
        tq = time.perf_counter()
        for ticket, _, _, _ in reqs:
            ticket.trace.phase("queue", tq)
            ticket.trace.phase("coalesce", tq)   # sync path coalesced at submit
        try:
            self._dispatch(reqs)
        except Exception as e:
            for ticket, _, _, _ in reqs:
                ticket._fulfill(err=e)
                ticket.trace.finish("error", error=repr(e))
            raise

    def flush(self) -> None:
        """Dispatch all pending requests as one fused executable call.  On a
        dispatch error the group's tickets carry the exception (re-raised by
        ``result()``) rather than stranding as forever-pending.  With a
        broker attached this also drains the broker's queues."""
        self._flush_pending()
        broker = self._broker   # local read: a concurrent stop_pipeline()
        if broker is not None:  # may null the attribute between check/use
            broker.drain()

    def dispatch_group(self, requests, tickets) -> None:
        """Broker backend: dispatch ``requests = [(name, n_threads), ...]``
        as one fused executable call, fulfilling ``tickets`` positionally.

        Unlike :meth:`submit`, the thinned batches are built HERE — at
        dispatch time, under the service lock — so a group formed while an
        ingest worker re-registers content can never mix one request's old
        split metadata with another's new stream: every request in the
        group is prepared against one consistent content snapshot.
        Registration is validated ONCE per distinct name at group build
        (under the same RLock hold that builds the batches) rather than
        per-entry — per-entry generation reads taken under separate lock
        acquisitions are exactly the interleaving a concurrent ``extend()``
        re-registration can split (see :meth:`content_snapshot`)."""
        try:
            if len(requests) != len(tickets):
                # Tickets fulfill positionally: a silent zip over mismatched
                # lengths would strand the surplus tickets forever (their
                # callers block until timeout) — fail the WHOLE group loudly
                # so every ticket carries the error (ISSUE 10).
                raise ValueError(
                    f"dispatch_group got {len(requests)} requests but "
                    f"{len(tickets)} tickets — they must align positionally")
            self.faults.fire("service.dispatch_group",
                             names=[name for name, _ in requests])
            with self._lock:
                missing = sorted({
                    name for name, _ in requests
                    if self._generations.get(name, 0) == 0})
                if missing:
                    raise KeyError(
                        f"content not registered: {', '.join(missing)}")
                reqs = []
                for ticket, (name, n_threads) in zip(tickets, requests):
                    batch, n = self._thinned_batch(name, n_threads)
                    reqs.append((ticket, (name, n_threads), batch, n))
        except Exception as e:
            for ticket in tickets:
                ticket._fulfill(err=e)
                if not getattr(ticket, "_retry_pending", False):
                    ticket.trace.finish("error", error=repr(e))
            raise
        tc = time.perf_counter()
        for ticket in tickets:
            ticket.trace.phase("coalesce", tc)
        try:
            self._dispatch(reqs)
        except Exception as e:
            for ticket, _, _, _ in reqs:
                ticket._fulfill(err=e)
                # A broker ticket with retries left parks as retry-pending
                # instead of completing; its trace must stay open for the
                # retry attempt (the broker records a "retry" event and the
                # terminal pass finishes it).
                if not getattr(ticket, "_retry_pending", False):
                    ticket.trace.finish("error", error=repr(e))
            raise

    def prepare_group(self, requests):
        """Build (and memoize) the fused :class:`DecodePlan` a request group
        ``[(name, n_threads), ...]`` would dispatch, WITHOUT executing it.

        The predictive warmer's probe (DESIGN.md §12): pairing this with
        ``session.is_compiled(plan)`` lets the idle-gap speculation compile
        exactly the hot-set group shapes that are missing from the
        executable cache and skip the ones warm traffic already minted.
        Returns the plan only — tickets and output slicing stay with
        :meth:`dispatch_group`."""
        reqs = []
        with self._lock:
            for name, n_threads in requests:
                if self._generations.get(name, 0) == 0:
                    raise KeyError(f"content {name!r} is not registered")
                batch, n = self._thinned_batch(name, n_threads)
                reqs.append((None, (name, n_threads), batch, n))
            plan, _sym_off = self._group_plan(reqs, record=False)
        return plan

    def _group_plan(self, reqs, record: bool = True):
        """Resolve the (memoized) plan for a built request group.  Caller
        holds ``_lock``.  MUTATES ``reqs`` into canonical order (the fused
        layout is arrival-order independent, so any permutation of the same
        group shares one memo entry; tickets travel with their request, so
        slices still land).  ``record=False`` skips the dispatch counters
        (speculative probes must not inflate ``fused_dispatches``)."""
        if len(reqs) == 1:
            _, key, batch, n = reqs[0]
            plan = self._plans.get(key)
            if plan is None:
                with jax.profiler.TraceAnnotation(PLAN):
                    plan = self.session.prepare(
                        batch, self._contents[key[0]].stream, n)
                self._plans[key] = plan
            return plan, None
        if record:
            self._fused += 1
            self._coalesced += len(reqs)
        reqs.sort(key=lambda r: r[1])
        group = tuple(key for _, key, _, _ in reqs)
        hit = self._fused_plans.get(group)
        if hit is None:
            if len(self._fused_plans) >= self.MAX_FUSED_PLANS:
                self._fused_plans.pop(next(iter(self._fused_plans)))
            with jax.profiler.TraceAnnotation(PLAN):
                plan, sym_off, total = self._prepare_fused(reqs)
            self._fused_plans[group] = (plan, sym_off, total)
        else:
            plan, sym_off, total = hit
        return plan, sym_off

    def _dispatch(self, reqs) -> None:
        """Plan under the service lock; EXECUTE outside it (the executable
        run is the slow part — holding the lock there would serialize the
        broker's ingest registration against in-flight decode).

        Span marks (DESIGN.md §13): plan resolution closes "dispatch",
        executable completion closes "execute", fulfillment closes
        "delivery"; on the profiler's clock, a plan miss is the
        ``recoil.plan`` span and fulfillment ``recoil.deliver``. On the
        broker path, honest execute spans come for free: the broker
        worker ``block_until_ready``s right after dispatch anyway, so
        syncing here for traced groups only moves that wait inside the
        span. The sync path stays fully asynchronous — there the execute
        span is the host-side dispatch cost and the caller's
        ``result()`` owns the device wait (blocking a traced sync flush
        would CHARGE instrumentation for a sync the uninstrumented path
        never does, which is exactly what the CI overhead guard prices)."""
        with self._lock:
            self._flushes += 1
            plan, sym_off = self._group_plan(reqs)
        traces = [t.trace for t, _, _, _ in reqs]
        tp = time.perf_counter()
        for tr in traces:
            tr.phase("dispatch", tp)
        self.faults.fire("service.execute", group=len(reqs))
        out = self.session.execute(plan)
        if self._broker is not None and any(tr.live for tr in traces):
            jax.block_until_ready(out)
        tx = time.perf_counter()
        for tr in traces:
            tr.phase("execute", tx, group=len(reqs))
        # Per-ticket finish, right after the ticket's own fulfillment,
        # stamped at the ticket's own completion time when it records one
        # (PipelineTicket) — each trace's span-sum then equals ITS
        # measured end-to-end latency exactly.  One shared mark after the
        # loop would charge every ticket the whole group's delivery tail.
        with jax.profiler.TraceAnnotation(DELIVER):
            if sym_off is None:
                ticket = reqs[0][0]
                ticket._fulfill(out=out)
                td = getattr(ticket, "completed_at", None) \
                    or time.perf_counter()
                ticket.trace.phase("delivery", td)
                ticket.trace.finish("ok", td)
            else:
                for (ticket, _, _, n), off in zip(reqs, sym_off):
                    ticket._fulfill(out=out[off:off + n])
                    if ticket.trace.live:
                        td = getattr(ticket, "completed_at", None) \
                            or time.perf_counter()
                        ticket.trace.phase("delivery", td)
                        ticket.trace.finish("ok", td)

    def _prepare_fused(self, reqs) -> tuple[DecodePlan, list[int], int]:
        streams: dict[int, DeviceStream] = {}
        for _, key, _, _ in reqs:
            ds = self._contents[key[0]].stream
            streams.setdefault(id(ds), ds)
        if len(streams) == 1:
            fused_ds = next(iter(streams.values()))
            word_off = {id(fused_ds): 0}
            perm_off = {id(fused_ds): 0}
        else:
            fused_ds, word_off, perm_off = _fuse_streams(
                list(streams.values()), self.session.executor)
        sym_off, total = [], 0
        for _, _, _, n in reqs:
            sym_off.append(total)
            total += n
        fused = concat_walk_batches(
            [b for _, _, b, _ in reqs], sym_off,
            [word_off[id(self._contents[key[0]].stream)]
             for _, key, _, _ in reqs],
            [perm_off[id(self._contents[key[0]].stream)]
             for _, key, _, _ in reqs])
        return self.session.prepare(fused, fused_ds, total), sym_off, total

    # ------------------------------------------------------------------
    # Async serving pipeline (runtime.pipeline)
    # ------------------------------------------------------------------

    def start_pipeline(self, **broker_kw):
        """Attach a :class:`~repro.runtime.pipeline.PipelineBroker` and
        become its thin façade: ``submit``/``flush`` route through the
        broker's capability lanes and worker threads, overlapping ingest
        with decode traffic (DESIGN.md §8).  Returns the broker (also a
        context manager)."""
        from repro.runtime.pipeline import PipelineBroker
        with self._lock:
            if self._broker is not None:
                raise RuntimeError("pipeline already running; stop it first")
            # Requests queued through the sync path before the upgrade must
            # dispatch NOW: once the broker is attached, flush() routes to
            # broker.drain() and would never touch them (their tickets
            # would strand as "never dispatched").
            self._flush_pending()
            self._broker = PipelineBroker(self, **broker_kw)
        return self._broker

    def stop_pipeline(self) -> None:
        """Drain and detach the broker (no-op when none is attached)."""
        with self._lock:
            broker, self._broker = self._broker, None
        if broker is not None:
            broker.close()

    @property
    def broker(self):
        return self._broker

    @property
    def tuning_profile(self):
        """The tuned :class:`~repro.core.tuning.Profile` the decode session
        resolved (None = legacy ladder).  The pipeline broker reads the
        profile's microbatch quantization sizes so the pre-compiled shape
        set matches what dispatch actually requests."""
        return self.session.tuning_profile

    def metrics(self) -> dict:
        """The unified metrics snapshot (native instruments + every tier's
        collectors) — see ``repro.runtime.observability.SCHEMA``."""
        return self.obs.snapshot()

    def metrics_text(self) -> str:
        """Prometheus text exposition of :meth:`metrics`."""
        return self.obs.exposition()

    @property
    def stats(self) -> ServiceStats:
        e = self.session.stats
        enc = self._encoder.stats if self._encoder is not None else None
        with self._lock:
            return ServiceStats(
                compiles=e.compiles, cache_hits=e.cache_hits,
                decodes=e.decodes,
                plan_hits=self._plan_hits, plan_misses=self._plan_misses,
                coalesced_requests=self._coalesced,
                fused_dispatches=self._fused,
                flushes=self._flushes, ingests=self._ingests,
                extends=self._extends, stream_requests=self._streams,
                encode_compiles=enc.compiles if enc else 0,
                encode_fallbacks=enc.fallbacks if enc else 0,
                host_materializations=getattr(
                    self.session.executor, "host_materializations", 0),
                symbol_plans=self.session.executor.layout_plans["symbol"],
                pointer_plans=self.session.executor.layout_plans["pointer"])


def _validate_content(model: StaticModel, plan: RecoilPlan, stream,
                      final_states, enc_model=None) -> None:
    """Loud registration-time validation (a mismatched payload would decode
    to silent garbage for every client — fail here instead).

    Checks everything derivable from the metadata: way count, stream/plan
    word-count agreement, final-state shape and the rANS state invariant
    (``L <= x < 2^32``), and the plan's own split invariants.  When the
    caller supplies the model the content was *encoded* with, the
    distribution tables and params are compared against the service model
    too (the one mismatch pure metadata cannot reveal)."""
    p = model.params
    if plan.ways != p.ways:
        raise ValueError(
            f"content was planned for {plan.ways}-way interleaving but the "
            f"service model uses ways={p.ways}")
    n_words = (stream.n_words if isinstance(stream, DeviceStream)
               else len(stream))
    if n_words != plan.n_words:
        raise ValueError(
            f"stream has {n_words} words but the plan says "
            f"{plan.n_words} — truncated or mismatched payload")
    fs = np.asarray(final_states)
    if fs.shape != (p.ways,):
        raise ValueError(
            f"final_states shape {fs.shape} != (ways,) = ({p.ways},)")
    if fs.size and (int(fs.min()) < p.lower_bound
                    or int(fs.max()) >= 2 ** 32):
        raise ValueError(
            "final states violate the rANS invariant L <= x < 2^32 — "
            "content was not produced by a compatible encoder")
    plan.validate(p.lower_bound)
    if enc_model is not None:
        q = enc_model.params
        if (q.n_bits, q.ways) != (p.n_bits, p.ways):
            raise ValueError(
                f"content encoded with n_bits={q.n_bits}, ways={q.ways}; "
                f"service model has n_bits={p.n_bits}, ways={p.ways}")
        if (np.asarray(enc_model.f).shape != np.asarray(model.f).shape
                or not np.array_equal(enc_model.f, model.f)):
            raise ValueError(
                "content was encoded with a different distribution table "
                "than the service model — it would mis-decode")


def _fuse_permutations(streams: list[DeviceStream]) -> tuple:
    """Concatenate ``words_by_symbol`` permutations for a fused dispatch.

    Sym-bucket-aligned (like the word fusion), so per-request ``sym_base``
    shifts are exact AND stay multiples of ``ways`` (buckets are pow2 >=
    1024).  Any stream without a permutation downgrades the whole fused
    group to the pointer walk — layouts never mix inside one executable.
    Returns ``(by_symbol | None, sym_bucket, perm_off)``.
    """
    perm_off: dict[int, int] = {}
    total = 0
    for ds in streams:
        perm_off[id(ds)] = total
        total += ds.sym_bucket
    if any(ds.by_symbol is None for ds in streams):
        return None, 0, {id(ds): 0 for ds in streams}
    bucket = pow2_bucket(total, 1024)
    # Small streams store the permutation as uint16 (DESIGN.md §10); the
    # fused group's q0 offsets can exceed 2^16, so fusion upcasts every
    # part to the common uint32 width.
    parts = [ds.by_symbol.astype(jnp.uint32) for ds in streams]
    if bucket > total:
        parts.append(jnp.zeros(bucket - total, jnp.uint32))
    return jnp.concatenate(parts), bucket, perm_off


def _fuse_streams(streams: list[DeviceStream],
                  executor=None) -> tuple[DeviceStream, dict, dict]:
    """Concatenate resident streams for a cross-content fused dispatch.

    Layout preserves each stream's padded bucket window, so word offsets are
    bucket-aligned and the per-request ``q0`` shift is exact.  Device words
    fuse on device (no host round-trip) when every stream is device-resident
    (jnp/sharded backends); otherwise the fused stream is host-side
    (Pallas, which slabs from host anyway).  Symbol-layout permutations fuse
    alongside (:func:`_fuse_permutations`); returns ``(fused, word_off,
    perm_off)``.
    """
    word_off: dict[int, int] = {}
    total = 0
    for ds in streams:
        word_off[id(ds)] = total
        total += ds.bucket
    bucket = pow2_bucket(total, 1024)
    by_symbol, sym_bucket, perm_off = _fuse_permutations(streams)
    if all(ds.words is not None for ds in streams):
        parts = [ds.words for ds in streams]
        if bucket > total:
            parts.append(jnp.zeros(bucket - total, jnp.uint32))
        fused = DeviceStream(words=jnp.concatenate(parts), host=None,
                             n_words=total, bucket=bucket,
                             by_symbol=by_symbol, sym_bucket=sym_bucket)
        return fused, word_off, perm_off
    # Mixed residency (pallas: uploaded streams are host-side, ingested
    # ones device-only until lazily materialized) — pull device words down
    # through the executor's per-handle materialization cache when it has
    # one, so repeat fusions of the same handle don't re-copy and the
    # ``host_materializations`` counter stays exact.
    materialize = getattr(executor, "_host_words",
                          lambda ds: (ds.host if ds.host is not None
                                      else np.asarray(ds.words[:ds.n_words])))
    host = np.zeros(bucket, np.uint32)
    for ds in streams:
        host[word_off[id(ds)]:word_off[id(ds)] + ds.n_words] = \
            np.asarray(materialize(ds)).astype(np.uint32)
    fused = DeviceStream(words=None, host=host, n_words=total, bucket=bucket,
                         by_symbol=by_symbol, sym_bucket=sym_bucket)
    return fused, word_off, perm_off
