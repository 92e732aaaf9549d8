"""Task lanes launched per task executed in the window (``RunStats``
counters ``lanes_launched / tasks_executed``): 1 is a dense launch."""


def read(run):
    tasks = run.stats.get("tasks_executed")
    if not tasks:
        return None
    return run.stats["lanes_launched"] / tasks
