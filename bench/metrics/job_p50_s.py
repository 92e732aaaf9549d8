"""Median seconds from a job's due time to its answer on the client's
host, over the jobs answered correctly inside the window.  Host clock.

In a closed loop a job is due the moment its predecessor's answer is back,
so this is the service time a client waits for one answer.  Unlike
``jobs_per_s`` it does not move in whole answers: a chunk of epochs that
runs faster shortens it at once."""
from bench.stats import percentile


def read(run):
    end = run.t_window + run.seconds
    return percentile([r.done_at - r.due for r in run.records
                       if r.ok and r.done_at <= end], 50)
