"""Bits a client downloads per decoded symbol, as the service counts them:
8 x the bytes of the thinned containers of the requests the window's
dispatches answered (the service's ``wire_bytes`` total, sized when each
(object, capability) pair is thinned) over the symbols those walks
answered (``walk_symbols``), both from the broker's snapshot."""


def read(run):
    try:
        wire = run.broker_delta("wire_bytes")
        symbols = run.broker_delta("walk_symbols")
    except KeyError:      # a program that does not count them
        return None
    return 8.0 * wire / symbols if symbols else None
