"""Device self time of the resident chunk programs' operations whose
innermost scope is ``trees.commit`` (fork offsets through the
``fork_compact`` kernels; the child, join, emit and dead scatters; heap
writes), per epoch that ``RunStats`` counted in the traced window, as
``epoch_device_ms.batch`` divides.  Device trace (``bench/opscopes.py``)."""
from bench import opscopes


def read(run):
    return opscopes.phase_ms_per_epoch(run, "trees.commit")
