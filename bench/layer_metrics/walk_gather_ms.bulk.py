"""Device time of the walk-order gather (program scope
``recoil.walk_gather``: the stream words laid out in walk order before the
kernel) per request answered in the traced window, in ms."""

from bench.scopes import WALK_GATHER, ms_per_answer


def read(run):
    return ms_per_answer(run, WALK_GATHER)
