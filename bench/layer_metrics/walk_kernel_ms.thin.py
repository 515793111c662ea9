"""Device time of the walk kernel (program scope ``recoil.walk_kernel``:
the Pallas walk, tiled along its steps where the whole walk's block would
not fit the kernel's VMEM) per request answered in the traced window, in
ms."""

from bench.scopes import WALK_KERNEL, ms_per_answer


def read(run):
    return ms_per_answer(run, WALK_KERNEL)
