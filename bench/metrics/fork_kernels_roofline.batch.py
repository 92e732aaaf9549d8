"""Share of the HBM roofline reached by the ``fork_compact`` kernels: the
least time their calls' bytes take at the chip's HBM peak, over their
summed device time.  Bytes from each call's shape (``bench/costs.py``)."""
from bench import costs


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    calls = run.trace.kernel_calls()
    t = sum(d for *_, d in calls)
    if not calls or t <= 0:
        return None
    b = sum(costs.kernel_bytes(k, rows, n_out) for k, rows, n_out, _ in calls)
    return 100.0 * b / run.peaks["hbm_bytes_per_s"] / t
