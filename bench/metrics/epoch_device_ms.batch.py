"""Device time of the resident chunk programs in the traced window, per
epoch that the runtime's ``RunStats`` counted in the same window."""


def read(run):
    if run.trace is None or not run.stats.get("epochs"):
        return None
    runs = run.trace.chunk_runs()
    if not runs:
        return None
    return 1e3 * sum(e - s for s, e in runs) / run.stats["epochs"]
