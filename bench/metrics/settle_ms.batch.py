"""Mean host seconds, in ms, of the ``trees:settle`` spans in the traced
window: a chunk boundary's accounting and the surfacing of its finished
regions (``DeviceMultiplexer._finish_chunk``).  Profiler trace."""
from bench import opscopes


def read(run):
    return opscopes.span_mean_ms(run, "trees:settle")
