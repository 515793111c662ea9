"""Device seconds by program scope.

The program names the stages of its fused decode executable with
``jax.named_scope`` (``repro.runtime.observability.SCOPES``); each device
op's HLO ``op_name`` metadata then holds the scope in its JAX name stack,
and a profiler trace reports that string as the op's ``tf_op``.  An op is
attributed to the innermost ``recoil.*`` component of its op name; without
one, to the innermost ``jit(<name>)`` component (``<name>``); without an op
name, to ``"unscoped"``.

Two ways to the op names, which give the same attribution:

  * from the trace itself (:func:`trace_seconds`, ``bench.xspace``), for a
    kept ``.xplane.pb``;
  * from the session's compiled executables (:func:`run_seconds`): their
    optimized HLO names each instruction as the trace names its op
    (``trace_reduce.op_name``), so the harness's clipped per-op device
    seconds (``Reduction.op_seconds``) sum by scope with no trace file.
    A program whose session cannot show its HLO gives nothing.
"""

from __future__ import annotations

import re

from bench import trace_reduce

#: The program's scopes (``repro.runtime.observability.SCOPES``), spelled
#: here too so a reader runs against a program that lacks them.
WALK_GATHER, WALK_KERNEL, SCATTER = "recoil.walk_gather", \
    "recoil.walk_kernel", "recoil.scatter"
UNSCOPED = "unscoped"
_PREFIX = "recoil."
_JIT = re.compile(r"^jit\((.+)\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str | None) -> str:
    """The scope an op with HLO op name (or ``tf_op``) ``op_name`` is
    counted under.  A ``tf_op`` ends in ``:<type>``; several fused op names
    are joined by ``;``, and the first is taken."""
    if not op_name:
        return UNSCOPED
    parts = op_name.split(";")[0].split("/")
    last = parts[-1]
    if ":" in last:
        parts[-1] = last[:last.rindex(":")]
    jit = None
    for part in reversed(parts):
        if part.startswith(_PREFIX):
            return part
        if jit is None and _JIT.match(part):
            jit = _JIT.match(part)[1]
    return jit or UNSCOPED


def _add(out: dict, scope: str, seconds: float) -> None:
    out[scope] = out.get(scope, 0.0) + seconds


def trace_seconds(ops, lo_ns: float, hi_ns: float) -> dict[str, float]:
    """Device seconds by scope of ``bench.xspace.device_ops``, clipped to
    ``[lo_ns, hi_ns]`` as the harness clips op seconds."""
    out: dict[str, float] = {}
    for op in ops:
        a, b = max(op.start_ns, lo_ns), min(op.end_ns, hi_ns)
        if b > a:
            _add(out, scope_of(op.tf_op), (b - a) * 1e-9)
    return out


def hlo_op_names(texts) -> dict[str, str | None]:
    """``trace_reduce.op_name`` key -> HLO op name, for every instruction
    of the optimized HLO modules ``texts``.  One program's executables
    share instruction names and carry the same scopes."""
    names: dict[str, str | None] = {}
    for text in texts:
        for line in text.splitlines():
            line = line.strip().removeprefix("ROOT ")
            if not line.startswith("%"):
                continue
            m = _OP_NAME.search(line)
            names.setdefault(trace_reduce.op_name(line), m and m[1])
    return names


def op_seconds_by_scope(op_seconds: dict[str, float],
                        texts) -> dict[str, float]:
    """Per-op device seconds (``Reduction.op_seconds``) summed by scope."""
    names = hlo_op_names(texts)
    out: dict[str, float] = {}
    for key, s in op_seconds.items():
        _add(out, scope_of(names.get(key)), s)
    return out


def run_seconds(run) -> dict[str, float]:
    """Device seconds by scope in a traced run's window, or ``{}`` when the
    run has no device reduction or its session cannot show its HLO."""
    session = getattr(getattr(getattr(run, "_dep", None), "svc", None),
                      "session", None)
    compiled_hlo = getattr(session, "compiled_hlo", None)
    if run.device is None or compiled_hlo is None:
        return {}
    texts = [t for t in compiled_hlo() if t]
    return op_seconds_by_scope(run.device.op_seconds, texts) if texts else {}


def ms_per_answer(run, scope: str) -> float | None:
    """Device time of ``scope`` per request answered in the window, ms."""
    done = run.answered_in_window()
    seconds = run_seconds(run).get(scope) if done else None
    return seconds / len(done) * 1e3 if seconds else None
