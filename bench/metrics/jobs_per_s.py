"""Jobs answered correctly inside the window, per second of the window.

Host clock.  A job counts once its answer is on the client's host, at or
before the window's close, and equals the plain reference."""
from bench.stats import rate


def read(run):
    return rate([r.done_at for r in run.records if r.ok], run.t_window,
                run.seconds)
