"""Mean gap between consecutive resident chunk programs on the device:
the chunk boundary (readback, settle, reseed, next launch).  Device
trace; None with fewer than two chunks in the window."""


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.chunk_runs()
    gaps = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
