"""Device self time of the resident chunk programs' operations whose
innermost scope is ``trees.tasks`` (the task bodies and the rung's
``lax.switch``), per epoch that ``RunStats`` counted in the traced
window, as ``epoch_device_ms.batch`` divides.  Device trace
(``bench/opscopes.py``)."""
from bench import opscopes


def read(run):
    return opscopes.phase_ms_per_epoch(run, "trees.tasks")
