"""Tasks per job finished in the window, each job's own (``RunStats``
counters ``job_tasks_finished / jobs_finished``): the work of one answer,
in tasks."""


def read(run):
    jobs = run.stats.get("jobs_finished")
    if not jobs:
        return None
    return run.stats["job_tasks_finished"] / jobs
