"""Mean host seconds, in ms, of the ``trees:reseed`` spans in the traced
window: a queued job seated into a freed region of the live wave (TV
slots, heap, arena cursor, stack row).  Profiler trace; None when no
region was reseeded."""
from bench import opscopes


def read(run):
    return opscopes.span_mean_ms(run, "trees:reseed")
