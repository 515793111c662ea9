"""Share of its HBM roofline the walk kernel reaches, in %: the least time
the chip needs for the bytes of every request answered in the traced
window (``bench.work``: the thinned container a client receives, read
once, and its symbols, written once) over the device time of program
scope ``recoil.walk_kernel``."""

from bench.scopes import WALK_KERNEL, run_seconds
from bench.work import bound_seconds, request_bytes


def read(run):
    done = run.answered_in_window()
    seconds = run_seconds(run).get(WALK_KERNEL) if done else None
    if not seconds:
        return None
    total = sum(request_bytes(run.wire_bytes(r.name, r.cap), run.sizes[r.name])
                for r in done)
    return 100.0 * bound_seconds(total, run.peaks) / seconds
