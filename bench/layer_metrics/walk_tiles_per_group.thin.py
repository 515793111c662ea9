"""Walk-kernel grid steps per dispatch group over the window: the decode
session's ``walk_tiles`` total (T tiles x row blocks of every executed
walk) over the broker's ``dispatch_groups``, both from the broker's
snapshot."""


def read(run):
    try:
        tiles = run.broker_delta("walk_tiles")
    except KeyError:      # a program that does not count them
        return None
    groups = run.broker_delta("dispatch_groups")
    return tiles / groups if groups else None
