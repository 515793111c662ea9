"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix.  The run:

  1. refuses to run without a TPU, or with fewer chips than the cell asks;
  2. turns on JAX's persistent compilation cache (``.jax_cache/`` in the
     checkout, or ``$JAX_COMPILATION_CACHE_DIR``);
  3. generates the configuration's content from ``--seed``, builds the
     ``DecodeService`` and ingests every object on the device;
  4. starts the broker with the configuration's options and warms exactly
     the cell's group shapes (``PipelineBroker.warm``);
  5. drives the mix for ``--seconds``, timing every request (with
     ``--trace 1`` under the JAX profiler);
  6. compares a seeded sample of the answers with the source symbols,
     reads the cell's metrics with one reader per metric, and prints one
     JSON line as the last line of standard output.

Earlier lines give the set-up phases, the compiles inside the window
(expected 0), how late the open-loop generator ran, and peak device
memory.  The numbers that decide ``correct`` are printed beside their
limits as the last lines of standard error and under ``checks``.
"""

from __future__ import annotations

import time

#: Set-up is timed from here, the harness's first statement.
T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import numpy as np  # noqa: E402

from bench import data, files, load, schedule, trace_reduce  # noqa: E402

#: Where traces and other run outputs go (git-ignored).
OUT_DIR = os.path.join(ROOT, "chiprun_out", "bench")
#: JAX compile events: executables built, and loaded from the disk cache.
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def log(msg: str) -> None:
    print(msg, flush=True)


def read_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def load_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """``(benchmark, cell, configuration, mix)`` for a cell name."""
    bench = read_json("BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return (bench, cell, read_json(conf["file"]),
            read_json(f"bench/traffic/{cell['traffic']}.json"))


def cell_metrics(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``traced`` its per-layer
    metrics: those that list the cell, or that list no cells and move an
    end-to-end metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(kind: str, name: str):
    """The ``read(run)`` function of ``bench/<kind>/<name>.py``."""
    return files.function(kind, name, "read")


class CompileCounter:
    """Counts JAX compile events process-wide (any thread)."""

    def __init__(self):
        import jax
        self.events: list[tuple[str, str]] = []   # (event, function)
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, *args, **kwargs) -> None:
        if event in COMPILE_EVENTS:
            with self._lock:
                self.events.append((event, str(kwargs.get("fun_name", ""))))

    def total(self) -> int:
        with self._lock:
            return len(self.events)

    def tally(self, start: int, stop: int) -> dict:
        """Events ``start:stop`` by kind: programs compiled, and programs
        loaded from the persistent cache."""
        with self._lock:
            kinds = collections.Counter(e for e, _ in self.events[start:stop])
        return {"compiled": kinds[COMPILE_EVENTS[0]],
                "cache_hits": kinds[COMPILE_EVENTS[1]]}


class Deployment:
    """One configuration, built from a seed: its objects, the service with
    every object ingested, and the started, warmed broker."""

    def __init__(self, config: dict, seed: int, caps: list[int],
                 phases: dict):
        import jax
        from repro.core.rans import RansParams, StaticModel
        from repro.runtime.pipeline import ControllerConfig
        from repro.runtime.serve import DecodeService

        t = time.perf_counter()
        self.objects = data.make_objects(config["objects"], seed)
        self.sizes = {n: int(s.size) for n, s in self.objects.items()}
        m = config["model"]
        counts = sum(np.bincount(s, minlength=m["alphabet"])
                     for s in self.objects.values())
        self.model = StaticModel.from_counts(
            counts, RansParams(n_bits=m["n_bits"], ways=m["ways"]))
        phases["content_s"] = time.perf_counter() - t

        t = time.perf_counter()
        self.svc = DecodeService(self.model, impl=config["impl"],
                                 **config.get("service", {}))
        names = list(self.objects)
        batch = int(config.get("ingest_batch", 1))
        for i in range(0, len(names), batch):
            part = {n: self.objects[n] for n in names[i:i + batch]}
            if len(part) == 1:
                (name, syms), = part.items()
                self.svc.ingest(name, syms, config["encode_splits"])
            else:
                self.svc.ingest_batch(part, config["encode_splits"])
        jax.block_until_ready([self.svc.content(n).stream.by_symbol
                               for n in self.objects])
        phases["ingest_s"] = time.perf_counter() - t

        t = time.perf_counter()
        opts = dict(config.get("broker", {}))
        if "controller" in opts:
            opts["config"] = ControllerConfig(**{
                k: tuple(v) if isinstance(v, list) else v
                for k, v in opts.pop("controller").items()})
        self.broker = self.svc.start_pipeline(**opts)
        self.broker.warm(list(self.objects), caps)
        # A predictive broker's speculative pre-thinner builds each hot
        # object's wire container in the window; building one per object
        # here compiles the stream read-back it needs.  Without it the
        # containers are built when a reader asks, after the window.
        self.wire: dict[tuple, int] = {}
        if opts.get("predictive", True):
            for name in self.objects:
                self.container_bytes(name, caps[0])
        phases["warm_s"] = time.perf_counter() - t

    def container_bytes(self, name: str, cap: int) -> int:
        """Bytes of the container a client at ``cap`` receives for
        ``name``: the stream and its metadata thinned to ``cap``."""
        from repro.runtime.pipeline import CapabilityRegistry
        key = (name, cap)
        if key not in self.wire:
            self.wire[key] = len(CapabilityRegistry(
                self.svc).container_for_threads(name, cap))
        return self.wire[key]

    def close(self) -> None:
        self.svc.stop_pipeline()


class Run:
    """What the metric readers see of one run."""

    def __init__(self, window: load.Window, dep: Deployment, setup_s: float,
                 tickets: list, broker_before: dict, broker_after: dict,
                 device, peaks: dict | None):
        self.requests = window.requests
        self.window_s = window.seconds
        self.t_open, self.t_close = window.t_open, window.t_close
        self.missing_s = window.seconds + load.ANSWER_WAIT_S
        self.sizes = dep.sizes
        self.setup_s = setup_s
        self.tickets = [t for t in tickets
                        if t.status == "ok" and self.t_open <= t.t0
                        < self.t_close]
        self._before, self._after = broker_before, broker_after
        self.device = device
        self.peaks = peaks
        self._dep = dep

    def answered_in_window(self) -> list:
        return [r for r in self.requests
                if r.done is not None and r.done <= self.t_close]

    def broker_delta(self, key: str) -> int:
        return self._after[key] - self._before[key]

    @staticmethod
    def span_ms(trace, *names: str) -> float:
        return sum(b - a for n, a, b, _ in trace.spans if n in names) * 1e3

    def wire_bytes(self, name: str, cap: int) -> int:
        return self._dep.container_bytes(name, cap)


def compare(sample: load.Reservoir, objects: dict) -> int:
    """Symbols of the sampled answers that differ from the source."""
    wrong = 0
    for name, _cap, out in sample.kept:
        got = np.asarray(out)
        src = objects[name]
        wrong += (int(np.count_nonzero(got != src)) if got.shape == src.shape
                  else int(src.size))
    return wrong


def checks_of(window: load.Window, sample: load.Reservoir, wrong: int,
              min_checked: int) -> dict:
    status = collections.Counter(r.status for r in window.requests)
    return {
        "wrong_symbols": {"value": wrong, "limit": 0, "is": "<="},
        "unanswered": {"value": status["unanswered"], "limit": 0, "is": "<="},
        "errors": {"value": status["error"], "limit": 0, "is": "<="},
        "answers_checked": {"value": len(sample.kept), "limit": min_checked,
                            "is": ">="},
    }


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] if c["is"] == "<="
               else c["value"] >= c["limit"] for c in checks.values())


def device_facts(devices) -> dict:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices if d.memory_stats()]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


@dataclasses.dataclass
class Setup:
    cache_dir: str
    phases: dict
    compiles: CompileCounter


def build(config: dict, mix: dict, seed: int) -> tuple[Deployment, Setup]:
    """Turn on the compile cache and build the cell's deployment."""
    import jax
    from repro.launch.cache import use_compile_cache

    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    setup = Setup(cache_dir, {}, CompileCounter())
    caps = sorted({int(c) for c in mix["capabilities"]})
    dep = Deployment(config, seed, caps, setup.phases)
    return dep, setup


def measure(cell_name: str, dep: Deployment, setup: Setup, mix: dict,
            metrics: list[dict], seed: int, seconds: float, traced: bool,
            t_start: float, keep_trace: bool = False,
            close: bool = True) -> dict:
    """Drive one window of ``mix`` on a built deployment and check it;
    returns the result object (the JSON line's keys) plus ``facts`` for
    the earlier lines.  ``close`` stops the broker before the answers are
    compared."""
    import jax
    names = list(dep.objects)
    if mix["loop"] == "closed":
        clients = schedule.closed_clients(mix, names, seed)
    else:
        plan = schedule.open_schedule(mix, names, seed, seconds)
    tickets: list = []
    dep.svc.obs.tracer.on_finish(
        lambda t: tickets.append(t) if t.kind == "decode" else None)
    sample = load.Reservoir(mix["check_sample"], seed)
    trace_dir = os.path.join(OUT_DIR, cell_name, "trace")
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = dep.broker.snapshot()
    compiles_before = setup.compiles.total()
    decode_compiles_before = dep.svc.stats.compiles
    setup_s = time.perf_counter() - t_start
    span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
    if mix["loop"] == "closed":
        window = load.run_closed(dep.svc, clients, mix["deadline"], seconds,
                                 sample, span)
    else:
        window = load.run_open(dep.svc, plan.offsets_s, plan.requests,
                               mix["deadline"], seconds, sample, span)
    window_compiles = [f"{fun or event}" for event, fun
                       in setup.compiles.events[compiles_before:]]
    decode_compiles = dep.svc.stats.compiles - decode_compiles_before
    after = dep.broker.snapshot()
    if traced:
        jax.profiler.stop_trace()
    device = device_facts(jax.devices())

    if close:
        dep.close()
    wrong = compare(sample, dep.objects)
    answered = sum(r.done is not None for r in window.requests)
    checks = checks_of(window, sample, wrong,
                       max(min(int(mix["check_sample"]), answered), 1))

    reduction, peaks = None, None
    if traced:
        reduction = trace_reduce.reduce_file(
            trace_reduce.find_xplane(trace_dir))
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        peaks = read_peaks(device["kind"])
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
    run = Run(window, dep, setup_s, tickets, before, after, reduction, peaks)
    values = {}
    for m in metrics:
        kind = "layer_metrics" if traced else "end_to_end"
        v = reader(kind, m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    status = collections.Counter(r.status for r in window.requests)
    result = {
        "correct": passes(checks),
        "attempted": len(window.requests),
        "failed": len(window.requests) - status["ok"],
        "metrics": values,
        "device": device,
    }
    if reduction is not None:
        result["breakdown"] = {"device_ops": reduction.top_ops(),
                               "idle_gaps": reduction.top_gaps()}
    result["checks"] = checks
    late = sorted(window.late_s)
    result["facts"] = {
        "cache_dir": setup.cache_dir,
        "setup_phases_s": setup.phases,
        "setup_s": setup_s,
        "setup_compiles": setup.compiles.tally(0, compiles_before),
        "compiles_in_window": len(window_compiles),
        "compiled_in_window": window_compiles[:8],
        "decode_compiles_in_window": decode_compiles,
        "requests": dict(status),
        "answered_in_window": len(run.answered_in_window()),
        "generator_late_ms": ({"p50": late[len(late) // 2] * 1e3,
                               "max": late[-1] * 1e3} if late else None),
        "broker_groups": after["dispatch_groups"] - before["dispatch_groups"],
        "errors": window.errors[:3],
    }
    return result


def run_cell(cell_name: str, config: dict, mix: dict, metrics: list[dict],
             seed: int, seconds: float, traced: bool,
             t_start: float = T_START, keep_trace: bool = False) -> dict:
    """Build, warm, drive and check one cell (see :func:`measure`)."""
    dep, setup = build(config, mix, seed)
    return measure(cell_name, dep, setup, mix, metrics, seed, seconds,
                   traced, t_start, keep_trace)


def read_peaks(kind: str) -> dict:
    peaks = read_json("bench/peaks.json")
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return peaks[kind]


def report(result: dict) -> None:
    """Facts and checks on earlier lines, the JSON object last."""
    facts = result.pop("facts")
    for key, val in facts.items():
        log(f"{key}: {json.dumps(val)}")
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['is']} {c['limit']})",
              file=sys.stderr, flush=True)
    log(json.dumps(result))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the profiler trace under chiprun_out/bench/")
    args = ap.parse_args(argv)

    bench, cell, config, mix = load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX's default device is "
                         f"{devices[0].platform!r}")
    if len(devices) < int(cell["chips"]):
        raise SystemExit(f"bench: {args.workload} needs {cell['chips']} "
                         f"chips, JAX sees {len(devices)}")
    result = run_cell(args.workload, config, mix,
                      cell_metrics(bench, args.workload, bool(args.trace)),
                      args.seed, args.seconds, bool(args.trace),
                      keep_trace=args.keep_trace)
    report(result)


if __name__ == "__main__":
    main()
