"""Profiler trace -> device busy time, per-op device time and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<stamp>/<host>.xplane.pb``.
Device planes are named ``/device:<KIND>:<n>``; their op line (``XLA Ops``)
holds one event per device operation.  Host planes hold the threads'
``TraceMe`` events, among them the harness's own
``jax.profiler.TraceAnnotation`` spans on the same clock.

The window is the harness's ``bench.window`` annotation.  Per device:

  busy      union of the op intervals clipped to the window;
  ops       device time per op name (summed over the device's events);
  gaps      the longest intervals of the window in which no op ran, each
            named after the host event that overlaps it most (what the host
            was doing).

``busy_s`` is averaged over the devices that ran anything; op times and
gaps are summed over them.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
TOP_GAPS = 10
_HLO_NAME = re.compile(r"%?([\w.\-]+) = ")
_HLO_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
#: Host events that mark the profiler's own bookkeeping, never host work.
_HOST_NOISE = ("ThreadpoolListener", "$profiler.py", WINDOW_SPAN)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                     # mean over active devices
    n_devices: int
    op_seconds: dict[str, float]      # op name -> device seconds (all devices)
    gaps: list[tuple[str, float]]     # the longest idle gaps, named

    def seconds_matching(self, needle: str) -> float:
        """Device seconds of every op whose name contains ``needle``."""
        return sum(s for name, s in self.op_seconds.items() if needle in name)

    def top_ops(self, n: int = 10) -> list[list]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[name, s] for name, s in ops[:n]]

    def top_gaps(self, n: int = 10) -> list[list]:
        return [[name, s] for name, s in self.gaps[:n]]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union_ns(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def idle_intervals(busy: list[tuple[float, float]], lo: float,
                   hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval of ``busy`` covers."""
    out, cur = [], lo
    for a, b in sorted(busy):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def op_name(hlo: str) -> str:
    """A device op's short name from its event name, the HLO instruction
    ``%name = shape opcode(...)``: ``"name opcode shape"``."""
    m = _HLO_NAME.match(hlo)
    op = m and _HLO_OPCODE.search(hlo, m.end() - 1)
    if not op:
        return hlo
    return f"{m[1]} {op[1]} {hlo[m.end():op.start()]}"


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def _window(planes) -> tuple[float, float]:
    for plane in planes:
        for line in plane.lines:
            for name, a, b in _events(line):
                if name == WINDOW_SPAN:
                    return a, b
    raise ValueError(f"trace has no {WINDOW_SPAN!r} annotation")


def _host_activity(planes, lo: float, hi: float) -> list[tuple]:
    out = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for name, a, b in _events(line):
                if b > lo and a < hi and b > a and not any(
                        name.startswith(p) for p in _HOST_NOISE):
                    out.append((a, b, name))
    return out


def _name_gap(a: float, b: float, host: list[tuple]) -> str:
    best, best_ov = "no host event", 0.0
    for ha, hb, name in host:
        ov = min(b, hb) - max(a, ha)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def reduce_planes(planes) -> Reduction:
    planes = list(planes)
    lo, hi = _window(planes)
    busy_total, n_dev = 0.0, 0
    op_ns: dict[str, float] = {}
    idle: list[tuple[float, float]] = []
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = [l for l in plane.lines if l.name == OPS_LINE]
        ivs = []
        for line in lines:
            for name, a, b in _events(line):
                a, b = max(a, lo), min(b, hi)
                if b <= a:
                    continue
                ivs.append((a, b))
                key = op_name(name)
                op_ns[key] = op_ns.get(key, 0.0) + (b - a)
        if not ivs:
            continue
        n_dev += 1
        busy_total += union_ns(ivs)
        idle.extend(idle_intervals(ivs, lo, hi))
    if n_dev == 0:
        raise ValueError("no device operation ran inside the window")
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:TOP_GAPS]
    host = _host_activity(planes, lo, hi)
    gaps = [(_name_gap(a, b, host), (b - a) * 1e-9) for a, b in longest]
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=busy_total / n_dev * 1e-9, n_devices=n_dev,
                     op_seconds={k: v * 1e-9 for k, v in op_ns.items()},
                     gaps=gaps)


def reduce_file(path: str) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)
