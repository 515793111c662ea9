"""Requests the broker answered per dispatch group over the window (its
``completed`` and ``dispatch_groups`` counters)."""


def read(run):
    groups = run.broker_delta("dispatch_groups")
    return run.broker_delta("completed") / groups if groups else None
