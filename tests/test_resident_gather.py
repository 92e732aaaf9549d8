"""Gather (dense-frontier) dispatch on the resident drivers.

The resident (device) drivers run gather as a fixed-shape segmented
in-loop pack (DESIGN.md §12) — bit-identical to resident masked, with
utilization >= masked and strictly fewer launched lanes on >= 2-region
fleets; only compacted stays refused (it sizes launches from runtime
populations).  The host-loop gather tests are in
``tests/test_gather_dispatch.py``.
"""
import numpy as np
import pytest
from conftest import solo_run

from repro.apps import get_case, get_fleet
from repro.core import DeviceEngine
from repro.service import (
    DeviceMultiplexer,
    Job,
    JobHandle,
    JobService,
    JobStatus,
)


def _handles(fleet):
    return [
        JobHandle(i, Job(c.program, c.initial, heap_init=dict(c.heap_init),
                         quota=q, name=c.name))
        for i, (c, q) in enumerate(fleet)
    ]


# ------------------------------------------------------- resident gather
def test_resident_drivers_still_reject_compacted():
    """Gather is now traceable on the resident drivers; compacted is not
    (it sizes per-type launches from runtime populations)."""
    case = get_case("fib")
    with pytest.raises(ValueError, match="masked"):
        DeviceEngine(case.program, dispatch="compacted")
    with pytest.raises(ValueError, match="masked"):
        DeviceMultiplexer(_handles(get_fleet("fib_fleet")),
                          dispatch="compacted")
    with pytest.raises(ValueError, match="masked"):
        JobService(engine="device", dispatch="compacted")


@pytest.mark.parametrize("name", ["fib", "mergesort"])
def test_solo_resident_gather_bit_identical(name):
    """DeviceEngine(dispatch='gather') matches the masked resident run
    exactly — values, heap, and ChunkSummary-derived stats — and the
    rung + hole accounting still tiles the full TV every epoch."""
    case = get_case(name)
    em = DeviceEngine(case.program, capacity=case.capacity)
    hm, vm, sm = em.run(case.initial, heap_init=dict(case.heap_init) or None)
    eg = DeviceEngine(case.program, capacity=case.capacity,
                      dispatch="gather")
    hg, vg, sg = eg.run(case.initial, heap_init=dict(case.heap_init) or None)
    np.testing.assert_array_equal(np.asarray(vg), np.asarray(vm))
    for k in hm:
        np.testing.assert_array_equal(np.asarray(hg[k]), np.asarray(hm[k]),
                                      err_msg=k)
    assert sg.epochs == sm.epochs
    assert sg.tasks_executed == sm.tasks_executed
    assert sg.total_forks == sm.total_forks
    # the dense rung never exceeds the span rung, and both tile the TV
    assert sg.lanes_launched <= sm.lanes_launched
    assert sg.utilization >= sm.utilization
    assert (sg.lanes_launched + sg.hole_lanes_skipped
            == sm.lanes_launched + sm.hole_lanes_skipped
            == case.capacity * sm.epochs)
    # map payloads launch over the same scattered full-TV domain
    assert sg.map_elements == sm.map_elements
    assert sg.map_lanes_launched == sm.map_lanes_launched
    assert sg.map_utilization >= sm.map_utilization


@pytest.mark.parametrize("fleet_name", ["mixed3", "mixed4", "fib_fleet"])
def test_resident_fleet_gather_bit_identical(fleet_name):
    """DeviceMultiplexer(dispatch='gather') on every registry fleet is
    bit-identical per job to solo runs, with strictly fewer launched lanes
    than resident masked (the fused span's cross-region holes are packed
    away) and the skipped holes accounted."""
    fleet = get_fleet(fleet_name)
    solo = {case.name: solo_run(case.name, quota) for case, quota in fleet}

    stats = {}
    for dispatch in ("masked", "gather"):
        handles = _handles(fleet)
        mux = DeviceMultiplexer(handles, dispatch=dispatch)
        mux.run()
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
        stats[dispatch] = mux.stats()

    sm, sg = stats["masked"], stats["gather"]
    capacity = sum(q for _, q in fleet)
    assert sg.epochs == sm.epochs
    assert sg.tasks_executed == sm.tasks_executed
    assert sg.utilization >= sm.utilization
    assert (sg.lanes_launched + sg.hole_lanes_skipped
            == sm.lanes_launched + sm.hole_lanes_skipped
            == capacity * sm.epochs)
    assert sg.map_utilization >= sm.map_utilization
    if len(fleet) >= 2:
        # >= 2 regions fuse: the union span holds cross-region holes the
        # dense pack must skip, so gather strictly wins on lane volume
        assert sg.lanes_launched < sm.lanes_launched
        assert sg.hole_lanes_skipped > sm.hole_lanes_skipped
        assert sg.utilization > sm.utilization


@pytest.mark.parametrize("chunk", [1, 4, None])
def test_service_device_gather_chunked(chunk):
    """JobService(engine='device', dispatch='gather') across the K-ladder:
    values match the masked device service bit-for-bit and the gather rows
    never launch more lanes."""
    from repro.apps import fib

    ns = (8, 10, 9)

    def run(dispatch):
        svc = JobService(capacity=1024, max_jobs=4, engine="device",
                         dispatch=dispatch, chunk=chunk)
        handles = [
            svc.submit(fib.PROGRAM, fib.initial(n), quota=256) for n in ns
        ]
        svc.drain()
        return handles, svc.stats()

    hm, sm = run("masked")
    hg, sg = run("gather")
    for h, g, n in zip(hm, hg, ns):
        assert h.status is JobStatus.DONE and g.status is JobStatus.DONE
        assert int(np.asarray(g.result.value)[0, 0]) == fib.fib_reference(n)
        np.testing.assert_array_equal(
            np.asarray(g.result.value), np.asarray(h.result.value)
        )
    assert sg.epochs == sm.epochs
    assert sg.lanes_launched < sm.lanes_launched
    assert sg.hole_lanes_skipped > sm.hole_lanes_skipped
