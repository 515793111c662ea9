"""Share of the HBM roofline reached by the decode dispatches, in %: the
least time the chip needs for the bytes of every request answered in the
traced window (``bench.work``) over the device's busy time in it, all of
which is decode work (walk-order gather, walk kernel, scatter, slices)."""

from bench.work import bound_seconds, request_bytes


def read(run):
    done = run.answered_in_window()
    if run.device is None or not done or not run.device.busy_s:
        return None
    total = sum(request_bytes(run.wire_bytes(r.name, r.cap), run.sizes[r.name])
                for r in done)
    return 100.0 * bound_seconds(total, run.peaks) / run.device.busy_s
