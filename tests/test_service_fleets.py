"""Registry fleets through the epoch-multiplexing job service (DESIGN.md §8).

A mixed fleet co-scheduled in one shared TVM is bit-identical per job to
solo ``HostEngine`` runs under every dispatch mode, while the fleet pays
strictly fewer fused dispatches + scalar readbacks than the solo runs
together; map-bearing tenants fuse their payload launches the same way.
"""
import numpy as np
import pytest
from conftest import assert_matches_solo, solo_run

from repro.apps import get_fleet
from repro.service import JobService, JobStatus


def _solo(case, quota, dispatch="masked"):
    return solo_run(case.name, quota, dispatch)


# ------------------------------------------- the multi-tenant equivalence
@pytest.mark.parametrize("dispatch", ["masked", "compacted", "gather"])
def test_mixed_fleet_bit_identical_and_cheaper(dispatch):
    """Acceptance: a mixed fleet of 3 registered apps through the service is
    bit-identical per job to solo runs, with fleet V_inf (dispatches +
    scalar transfers) strictly below the sum of the solo runs'."""
    fleet = get_fleet("mixed3")
    assert len(fleet) >= 3
    solo = {}
    solo_vinf = 0
    for case, quota in fleet:
        heap, value, stats = _solo(case, quota, dispatch)
        solo[case.name] = (heap, value, stats)
        solo_vinf += stats.dispatches + stats.scalar_transfers

    svc = JobService(
        capacity=sum(q for _, q in fleet), dispatch=dispatch
    )
    handles = [svc.submit_case(case, quota=q) for case, q in fleet]
    done = svc.drain()
    assert {h.job_id for h in done} == {h.job_id for h in handles}

    for h in handles:
        sh, sv, ss = solo[h.job.name]
        assert h.status is JobStatus.DONE
        assert_matches_solo(h, sh, sv, h.job.name)
        # per-job work accounting matches the solo run exactly
        assert h.result.stats.epochs == ss.epochs
        assert h.result.stats.tasks_executed == ss.tasks_executed
        assert h.result.stats.total_forks == ss.total_forks
        assert h.result.stats.peak_tv_slots == ss.peak_tv_slots

    fs = svc.stats()
    assert fs.dispatches + fs.scalar_transfers < solo_vinf
    # fused global epochs = max over members, not the sum
    assert fs.epochs == max(s[2].epochs for s in solo.values())
    # cross-job frontier fusion is recorded as coalesced ranges
    assert fs.ranges_coalesced > 0


def test_fused_maps_match_solo():
    """A map-bearing tenant (mergesort bulk payloads) stays bit-identical
    when its map launches run against the fused, namespaced heap."""
    fleet = [(c, q) for c, q in get_fleet("mixed4")
             if c.name in ("mergesort", "fib")]
    solo = {c.name: _solo(c, q) for c, q in fleet}
    svc = JobService(capacity=sum(q for _, q in fleet))
    handles = [svc.submit_case(c, quota=q) for c, q in fleet]
    svc.drain()
    for h in handles:
        sh, sv, ss = solo[h.job.name]
        assert_matches_solo(h, sh, sv, h.job.name)
        assert h.result.stats.epochs == ss.epochs
    # the sorted output really is sorted (guard against trivially-equal
    # garbage comparisons)
    ms = [h for h in handles if h.job.name == "mergesort"][0]
    n = ms.result.heap["inp"].shape[0]
    out = np.asarray(ms.result.heap["src"])[:n]
    np.testing.assert_array_equal(out, np.sort(np.asarray(ms.result.heap["inp"])))
