"""Gather dispatch on fused fleets through the host multiplexer.

Every registry fleet with ``dispatch="gather"`` is bit-identical per job
to the solo runs and to ``compacted``, with lane utilization >= masked:
the fused span's cross-region holes are never launched, and the skipped
holes are accounted in ``RunStats.hole_lanes_skipped``.  The rest of the
gather contract is in ``tests/test_gather_dispatch.py``.
"""
import functools

import numpy as np
import pytest
from conftest import solo_run

from repro.apps import get_fleet
from repro.service import EpochMultiplexer, Job, JobHandle, JobStatus


def _handles(fleet):
    return [
        JobHandle(i, Job(c.program, c.initial, heap_init=dict(c.heap_init),
                         quota=q, name=c.name))
        for i, (c, q) in enumerate(fleet)
    ]


@functools.lru_cache(maxsize=None)
def _fleet_run(fleet_name, dispatch):
    """A registry fleet through the host multiplexer, once per process:
    ``(handles, stats)``, shared read-only by the tests below."""
    handles = _handles(get_fleet(fleet_name))
    mux = EpochMultiplexer(handles, dispatch=dispatch)
    mux.run()
    return handles, mux.stats()


# ------------------------------------------------------------ fused fleets
@pytest.mark.parametrize("fleet_name", ["mixed3", "mixed4", "fib_fleet"])
def test_fused_gather_bit_identical_to_solo(fleet_name):
    """Acceptance: every registry fleet through the host multiplexer with
    dispatch='gather' is bit-identical per job to the solo runs, with lane
    utilization >= masked (the fused span's cross-region holes are never
    launched) and the skipped holes accounted."""
    fleet = get_fleet(fleet_name)
    solo = {case.name: solo_run(case.name, quota) for case, quota in fleet}

    stats = {}
    for dispatch in ("masked", "gather"):
        handles, stats[dispatch] = _fleet_run(fleet_name, dispatch)
        for h in handles:
            sh, sv, ss = solo[h.job.name]
            assert h.status is JobStatus.DONE
            np.testing.assert_array_equal(
                np.asarray(h.result.value), np.asarray(sv),
                err_msg=f"{h.job.name}:value:{dispatch}",
            )
            for k in sh:
                np.testing.assert_array_equal(
                    np.asarray(h.result.heap[k]), np.asarray(sh[k]),
                    err_msg=f"{h.job.name}:{k}:{dispatch}",
                )
            assert h.result.stats.epochs == ss.epochs
            assert h.result.stats.tasks_executed == ss.tasks_executed

    sm, sg = stats["masked"], stats["gather"]
    assert sg.tasks_executed == sm.tasks_executed
    assert sg.utilization >= sm.utilization
    assert sg.lanes_launched + sg.hole_lanes_skipped == sm.lanes_launched
    if len(fleet) >= 2:
        # cross-region holes exist whenever >= 2 regions fuse: the dense
        # frontier must skip some of them
        assert sg.hole_lanes_skipped > 0
        assert sg.utilization > sm.utilization


def test_gather_matches_compacted_on_fused_fleet():
    results = {}
    for dispatch in ("compacted", "gather"):
        handles, _ = _fleet_run("mixed3", dispatch)
        results[dispatch] = {
            h.job.name: np.asarray(h.result.value) for h in handles
        }
    for name in results["gather"]:
        np.testing.assert_array_equal(
            results["gather"][name], results["compacted"][name], err_msg=name
        )
