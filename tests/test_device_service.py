"""Device-resident fleet execution tests (DESIGN.md §9).

The load-bearing property, extending the host-mux equivalence harness of
``test_service.py`` to the resident path: running an entire admitted wave to
completion inside one ``lax.while_loop`` (``DeviceMultiplexer``) must be
*observationally invisible* to each tenant — per-job heaps, TV-value blocks,
and solo-comparable work stats bit-identical to a solo ``HostEngine.run``
with ``capacity=quota`` — while the whole wave pays O(1) critical-path
overhead: exactly one dispatch and one scalar readback.
"""
import numpy as np
import pytest
from conftest import solo_run

from repro.apps import fib, get_fleet
from repro.core import HostEngine
from repro.service import (
    DeviceMultiplexer,
    Job,
    JobFailure,
    JobHandle,
    JobService,
    JobStatus,
)


def _solo(case, quota):
    return solo_run(case.name, quota)


def _handles(fleet):
    return [
        JobHandle(i, Job(c.program, c.initial, heap_init=dict(c.heap_init),
                         quota=q, name=c.name))
        for i, (c, q) in enumerate(fleet)
    ]


# ---------------------------------------------- the acceptance equivalence
@pytest.mark.parametrize("fleet_name", ["mixed3", "mixed4", "fib_fleet"])
def test_device_wave_bit_identical_with_o1_vinf(fleet_name):
    """Acceptance: every registry fleet through the resident wave driver is
    bit-identical per job to solo runs (heaps, TV value blocks, and the
    solo-comparable stats), with fleet dispatches + scalar_transfers == 2 —
    O(1) for the whole wave, independent of epoch count."""
    fleet = get_fleet(fleet_name)
    solo = {c.name: _solo(c, q) for c, q in fleet}

    handles = _handles(fleet)
    mux = DeviceMultiplexer(handles)
    done = mux.step()
    assert {h.job_id for h in done} == {h.job_id for h in handles}

    for h in handles:
        sh, sv, ss = solo[h.job.name]
        assert h.status is JobStatus.DONE
        np.testing.assert_array_equal(
            np.asarray(h.result.value), np.asarray(sv),
            err_msg=f"{h.job.name}:value",
        )
        assert set(h.result.heap) == set(sh)
        for k in sh:
            np.testing.assert_array_equal(
                np.asarray(h.result.heap[k]), np.asarray(sh[k]),
                err_msg=f"{h.job.name}:{k}",
            )
        # per-job work accounting matches the solo run exactly
        assert h.result.stats.epochs == ss.epochs
        assert h.result.stats.tasks_executed == ss.tasks_executed
        assert h.result.stats.total_forks == ss.total_forks
        assert h.result.stats.peak_tv_slots == ss.peak_tv_slots
        # the whole wave rode exactly one dispatch + one readback
        assert h.result.stats.shared_dispatches == 1
        assert h.result.stats.shared_transfers == 1

    fs = mux.stats()
    assert fs.dispatches == 1 and fs.scalar_transfers == 1
    # resident global epochs = max over members (every live region pops
    # every iteration, the fuse_all schedule); sum over *members*, not
    # names — homogeneous fleets repeat the same case
    member_epochs = [solo[c.name][2].epochs for c, _ in fleet]
    assert fs.epochs == max(member_epochs)
    assert fs.ranges_coalesced == sum(member_epochs) - fs.epochs


def test_device_wave_map_waste_is_measurable():
    """Resident map payloads launch at MapType.max_domain; the divergence
    from the live domains must surface in RunStats, not stay silent."""
    fleet = get_fleet("mixed4")  # mergesort schedules bulk map payloads
    mux = DeviceMultiplexer(_handles(fleet))
    mux.step()
    fs = mux.stats()
    assert fs.map_launches > 0
    assert fs.map_elements > 0
    assert fs.map_lanes_launched > fs.map_elements
    assert fs.map_lanes_wasted == fs.map_lanes_launched - fs.map_elements
    assert 0.0 < fs.map_utilization < 1.0
    # the host-loop driver sizes payloads to live-domain buckets: strictly
    # fewer wasted lanes for the same work
    case = [c for c, _ in fleet if c.name == "mergesort"][0]
    _, _, hs = HostEngine(case.program, capacity=512).run(
        case.initial, heap_init=dict(case.heap_init) or None
    )
    assert hs.map_elements > 0
    assert hs.map_lanes_launched >= hs.map_elements
    assert hs.map_lanes_wasted < fs.map_lanes_wasted


# --------------------------------------------------- failure isolation
def test_device_wave_overflow_fails_only_that_job():
    """A region overflowing inside the resident loop zeroes its own stack
    pointer and fails alone; its neighbour's result is untouched."""
    bad = JobHandle(0, Job(fib.PROGRAM, fib.initial(12), quota=8, name="bad"))
    good = JobHandle(
        1, Job(fib.PROGRAM, fib.initial(10), quota=512, name="good")
    )
    mux = DeviceMultiplexer([bad, good])
    mux.step()
    assert bad.status is JobStatus.FAILED
    assert isinstance(bad.error, JobFailure)
    assert good.status is JobStatus.DONE
    _, sv, ss = HostEngine(fib.PROGRAM, capacity=512).run(fib.initial(10))
    np.testing.assert_array_equal(
        np.asarray(good.result.value), np.asarray(sv)
    )
    assert good.result.stats.epochs == ss.epochs


def test_device_wave_is_closed_to_midflight_admission():
    """The O(1)-readback trade: the host never sees a freed region until
    the wave drains, so admit() must refuse mid-flight reuse."""
    mux = DeviceMultiplexer(
        [JobHandle(0, Job(fib.PROGRAM, fib.initial(8), quota=128))]
    )
    late = JobHandle(1, Job(fib.PROGRAM, fib.initial(8), quota=128))
    assert mux.admit(late) is False
    mux.step()
    assert mux.admit(late) is False  # still closed after completion
    assert mux.step() == []  # the wave runs once


def test_device_multiplexer_rejects_compacted():
    with pytest.raises(ValueError, match="masked"):
        DeviceMultiplexer(
            [JobHandle(0, Job(fib.PROGRAM, fib.initial(8), quota=64))],
            dispatch="compacted",
        )


# --------------------------------------------------- service integration
def test_service_device_engine_runs_waves():
    """JobService(engine='device'): each wave is one resident loop; fleet
    dispatches count the number of waves, not the number of epochs."""
    svc = JobService(capacity=1024, max_jobs=2, engine="device")
    ns = (8, 9, 10, 11, 12)
    handles = [
        svc.submit(fib.PROGRAM, fib.initial(n), quota=512, name=f"fib{n}")
        for n in ns
    ]
    done = svc.drain()
    assert {h.job_id for h in done} == {h.job_id for h in handles}
    for h, n in zip(handles, ns):
        assert h.status is JobStatus.DONE
        assert int(np.asarray(h.result.value)[0, 0]) == fib.fib_reference(n)
    fs = svc.stats()
    # 5 jobs, 2 regions per wave -> 3 waves -> 3 dispatches + 3 readbacks
    assert fs.dispatches == 3
    assert fs.scalar_transfers == 3


def test_service_device_engine_rejects_host_only_options():
    with pytest.raises(ValueError, match="masked"):
        JobService(engine="device", dispatch="compacted")
    with pytest.raises(ValueError, match="fuse_all"):
        JobService(engine="device", pop_policy="round_robin")
    with pytest.raises(ValueError, match="fuse_all"):
        JobService(engine="device", gang=2)
    with pytest.raises(ValueError, match="host"):
        JobService(engine="tpu")


def test_service_device_engine_result_single_job():
    svc = JobService(capacity=512, engine="device")
    h = svc.submit(fib.PROGRAM, fib.initial(9), quota=256)
    res = svc.result(h)
    assert int(np.asarray(res.value)[0, 0]) == fib.fib_reference(9)
    assert res.stats.shared_dispatches == 1
