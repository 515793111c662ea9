"""Executor profiling: per-plan-key compile timing (DESIGN §13).

The decode and encode sessions already count compiles/hits exactly; what
they could not answer is *where compile time went* — which plan keys paid
compilation, and how the mix splits between layouts and policies.
:class:`ExecProfiler` is that one instrument: sessions call
``record_compile`` around ``executor.lower`` (a perf_counter pair and one
locked dict update per compile), and the bench suites/tuner read
``snapshot()`` instead of re-deriving ad-hoc timers.  A compile is
synchronous, so its host time is true.  Execution is not timed here: a
host clock around an asynchronous dispatch measures the dispatch call,
not the device work, which the JAX profiler's device trace gives by the
program scopes of :data:`repro.runtime.observability.SCOPES`.

The profiler is injected, not imported, by ``core`` sessions (they take a
``profiler=`` duck — keeping the core -> runtime layering clean); the
:class:`~repro.runtime.observability.Observability` owner shares one
instance between the decode and encode sessions of a service, with the
``session`` dimension ("decode"/"encode") separating them.

Key population is bounded (``max_keys`` per session kind): a pathological
plan-key churn aggregates into the ``"<overflow>"`` row instead of growing
the dict forever.
"""

from __future__ import annotations

import threading
import time


class _KeyStats:
    __slots__ = ("compiles", "compile_s")

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0


class ExecProfiler:
    """Per-(session, plan-key) compile accounting (module docstring)."""

    OVERFLOW = "<overflow>"

    def __init__(self, enabled: bool = True, max_keys: int = 512):
        self.enabled = bool(enabled)
        self.max_keys = int(max_keys)
        self._lock = threading.Lock()
        # session kind ("decode"/"encode") -> {key_str: _KeyStats}
        self._keys: dict[str, dict[str, _KeyStats]] = {}

    # ------------------------------------------------------------------
    # Hot-path recording (sessions call these)
    # ------------------------------------------------------------------

    def now(self) -> float:
        return time.perf_counter()

    def _stats(self, session: str, key) -> _KeyStats:
        """Caller holds ``_lock``.  Keys are stored natively (plan keys
        are hashable tuples) — stringifying on the hot path would cost
        more than the rest of the record combined; ``snapshot()`` renders
        them for JSON."""
        table = self._keys.setdefault(session, {})
        st = table.get(key)
        if st is None:
            if len(table) >= self.max_keys:
                key = self.OVERFLOW
                st = table.get(key)
                if st is None:
                    st = table[key] = _KeyStats()
            else:
                st = table[key] = _KeyStats()
        return st

    def record_compile(self, session: str, key, seconds: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            st = self._stats(session, key)
            st.compiles += 1
            st.compile_s += seconds

    # ------------------------------------------------------------------
    # Read surfaces
    # ------------------------------------------------------------------

    def totals(self, session: str) -> dict:
        with self._lock:
            table = self._keys.get(session, {})
            return {
                "keys": len(table),
                "compiles": sum(s.compiles for s in table.values()),
                "compile_s": sum(s.compile_s for s in table.values()),
            }

    def snapshot(self, top: int = 8) -> dict:
        """Per-session totals + the ``top`` keys by compile time, each
        with its compile count and milliseconds."""
        out = {"enabled": self.enabled}
        with self._lock:
            sessions = {k: dict(v) for k, v in self._keys.items()}
        for session, table in sessions.items():
            rows = sorted(
                table.items(),
                key=lambda kv: -kv[1].compile_s)[:top]
            out[session] = {
                "keys": len(table),
                "compiles": sum(s.compiles for s in table.values()),
                "compile_s": round(
                    sum(s.compile_s for s in table.values()), 6),
                "top": [{
                    "key": str(k),
                    "compiles": s.compiles,
                    "compile_ms": round(s.compile_s * 1e3, 3),
                } for k, s in rows],
            }
        return out
