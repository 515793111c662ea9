"""Drive a started decode service for one window and time every request.

Requests go through ``DecodeService.submit(name, capability)`` on the
started ``PipelineBroker`` and end with ``PipelineTicket.result()`` plus
``block_until_ready``: a request's latency runs from its submit (closed
loop) or its due time (open loop) to the moment its symbols are ready on
the device.  Every request sent in the window is recorded; one that is
refused, fails or never answers keeps ``done = None``.

A seeded reservoir keeps a uniform sample of the answers for the
correctness check after the window, so device memory for retained answers
stays bounded whatever the window's length.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time

import jax
import numpy as np

#: How long after the window's close the harness waits for an answer.
ANSWER_WAIT_S = 60.0


@dataclasses.dataclass
class Request:
    name: str
    cap: int
    t0: float                    # submit (closed) or due time (open)
    done: float | None = None    # symbols ready on the device
    status: str = "pending"      # ok | rejected | error | unanswered

    @property
    def latency_s(self) -> float | None:
        return None if self.done is None else self.done - self.t0


class Reservoir:
    """Uniform sample of at most ``size`` answers (Algorithm R, seeded)."""

    def __init__(self, size: int, seed: int):
        self.size = int(size)
        self._rng = np.random.default_rng([int(seed) % 2 ** 63, 7])
        self._seen = 0
        self._lock = threading.Lock()
        self.kept: list[tuple[str, int, jax.Array]] = []

    def offer(self, name: str, cap: int, out) -> None:
        with self._lock:
            self._seen += 1
            if len(self.kept) < self.size:
                self.kept.append((name, cap, out))
                return
            j = int(self._rng.integers(self._seen))
            if j < self.size:
                self.kept[j] = (name, cap, out)


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    requests: list[Request]
    late_s: list[float]          # open loop: submit time minus due time
    errors: list[str]

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def _answer(req: Request, ticket, deadline_at: float, sample: Reservoir,
            errors: list) -> None:
    try:
        out = ticket.result(timeout=max(deadline_at - time.perf_counter(),
                                        0.001))
        jax.block_until_ready(out)
    except TimeoutError:
        req.status = "unanswered"
        return
    except Exception as e:  # noqa: BLE001 - any failed answer is recorded
        req.status = "error"
        errors.append(repr(e))
        return
    req.done = time.perf_counter()
    req.status = "ok"
    sample.offer(req.name, req.cap, out)


def _submit(svc, req: Request, deadline: str, errors: list):
    from repro.runtime.pipeline import BrokerSaturated
    with jax.profiler.TraceAnnotation("bench.submit"):
        try:
            ticket = svc.submit(req.name, req.cap, deadline=deadline)
        except BrokerSaturated:
            req.status = "rejected"
            return None
        except Exception as e:  # noqa: BLE001 - a refused submit is a failure
            req.status = "error"
            errors.append(repr(e))
            return None
    return ticket


def run_closed(svc, clients: list[tuple[int, list[str]]], deadline: str,
               seconds: float, sample: Reservoir, span) -> Window:
    """Each client sends its next request when its last one answered.
    ``span`` is a context manager held over exactly the window."""
    requests: list[Request] = []
    errors: list[str] = []
    lock = threading.Lock()
    start = threading.Barrier(len(clients) + 1)
    bounds = {}

    def client(cap: int, seq: list[str]) -> None:
        start.wait()
        t_close = bounds["close"]
        i = 0
        while True:
            now = time.perf_counter()
            if now >= t_close:
                return
            req = Request(seq[i % len(seq)], cap, now)
            i += 1
            with lock:
                requests.append(req)
            ticket = _submit(svc, req, deadline, errors)
            if ticket is not None:
                _answer(req, ticket, t_close + ANSWER_WAIT_S, sample, errors)

    threads = [threading.Thread(target=client, args=c, daemon=True)
               for c in clients]
    for t in threads:
        t.start()
    with span:
        bounds["open"] = time.perf_counter()
        bounds["close"] = bounds["open"] + seconds
        start.wait()
        time.sleep(max(bounds["close"] - time.perf_counter(), 0.0))
    for t in threads:
        t.join()
    return Window(bounds["open"], bounds["close"], requests, [], errors)


def run_open(svc, offsets_s: np.ndarray, reqs: list[tuple[str, int]],
             deadline: str, seconds: float, sample: Reservoir,
             span) -> Window:
    """Send each request at its due time; one waiter per capability lane
    collects answers in order (a lane's groups run first in, first out).
    ``span`` is a context manager held over exactly the window."""
    errors: list[str] = []
    lanes = sorted({cap for _, cap in reqs})
    queues = {cap: queue.Queue() for cap in lanes}
    requests: list[Request] = []
    late: list[float] = []
    bounds = {}

    def waiter(q: queue.Queue) -> None:
        while (item := q.get()) is not None:
            _answer(*item, bounds["close"] + ANSWER_WAIT_S, sample, errors)

    threads = [threading.Thread(target=waiter, args=(queues[c],),
                                daemon=True) for c in lanes]
    for t in threads:
        t.start()
    with span:
        t_open = bounds["open"] = time.perf_counter()
        t_close = bounds["close"] = t_open + seconds
        _send(svc, offsets_s, reqs, deadline, t_open, queues, requests,
              late, errors)
        time.sleep(max(t_close - time.perf_counter(), 0.0))
    for c in lanes:
        queues[c].put(None)
    for t in threads:
        t.join()
    return Window(t_open, t_close, requests, late, errors)


def _send(svc, offsets_s, reqs, deadline, t_open, queues, requests, late,
          errors) -> None:
    for off, (name, cap) in zip(offsets_s, reqs):
        due = t_open + float(off)
        pause = due - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
        req = Request(name, cap, due)
        requests.append(req)
        late.append(time.perf_counter() - due)
        ticket = _submit(svc, req, deadline, errors)
        if ticket is not None:
            queues[cap].put((req, ticket))
