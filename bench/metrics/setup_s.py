"""Process start to window start: JAX start-up, data, compile or cache
load, warm-up.  Host clock."""


def read(run):
    return run.t_window - run.t_process
