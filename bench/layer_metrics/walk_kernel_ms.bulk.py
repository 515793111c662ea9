"""Device time of the Pallas walk kernel's events per request answered in
the traced window, in ms."""

KERNEL = "walk_decode_symbol_pallas"


def read(run):
    done = run.answered_in_window()
    if run.device is None or not done:
        return None
    seconds = run.device.seconds_matching(KERNEL)
    return seconds / len(done) * 1e3 if seconds else None
