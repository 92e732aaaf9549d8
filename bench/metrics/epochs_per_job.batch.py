"""Epochs per job finished in the window, each job's own (``RunStats``
counters ``job_epochs_finished / jobs_finished``): the critical path of
one answer, in epochs."""


def read(run):
    jobs = run.stats.get("jobs_finished")
    if not jobs:
        return None
    return run.stats["job_epochs_finished"] / jobs
