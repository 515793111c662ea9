"""Device time of the output scatter (program scope ``recoil.scatter``:
the kernel's tiles transposed, their positions sorted and scattered into
the flat output) per request answered in the traced window, in ms."""

from bench.scopes import SCATTER, ms_per_answer


def read(run):
    return ms_per_answer(run, SCATTER)
