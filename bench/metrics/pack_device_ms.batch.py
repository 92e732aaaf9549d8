"""Device self time of the resident chunk programs' operations whose
innermost scope is ``trees.pack`` (the gather frontier pack, or the
masked frontier predicate, and the span-ladder rung selection), per
epoch that ``RunStats`` counted in the traced window, as
``epoch_device_ms.batch`` divides.  Device trace (``bench/opscopes.py``)."""
from bench import opscopes


def read(run):
    return opscopes.phase_ms_per_epoch(run, "trees.pack")
