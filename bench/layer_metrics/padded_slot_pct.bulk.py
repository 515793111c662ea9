"""Share of the walk positions the window's dispatches executed that
answered no symbol, in %: 100 x (1 - symbols / slots) from the decode
session's ``walk_symbols`` and ``walk_slots`` totals in the broker's
snapshot (bucketed steps over every lane of the walk, padding included)."""


def read(run):
    try:
        slots = run.broker_delta("walk_slots")
        symbols = run.broker_delta("walk_symbols")
    except KeyError:      # a program that does not count them
        return None
    return 100.0 * (1.0 - symbols / slots) if slots else None
