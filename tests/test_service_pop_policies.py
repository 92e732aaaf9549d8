"""Mux pop policies of the job service (DESIGN.md §8).

A gang-limited pop policy (``round_robin`` / ``deepest_first`` with
``gang`` regions per epoch) changes only the fusion schedule of a fleet,
never any job's results; ``gang=1`` degenerates to interleaved solo
execution.  The rest of the service's contract is in
``tests/test_service.py``.
"""
import pytest
from conftest import assert_matches_solo, solo_run

from repro.apps import get_fleet
from repro.service import JobService


def _solo(case, quota):
    return solo_run(case.name, quota)


@pytest.mark.parametrize(
    "policy,gang", [("round_robin", 1), ("round_robin", 2),
                    ("deepest_first", 2)]
)
def test_pop_policies_preserve_results(policy, gang):
    """Gang-limited pop policies change only the fusion schedule, never any
    job's results."""
    fleet = get_fleet("mixed3")
    solo = {c.name: _solo(c, q) for c, q in fleet}
    svc = JobService(
        capacity=sum(q for _, q in fleet), pop_policy=policy, gang=gang
    )
    handles = [svc.submit_case(c, quota=q) for c, q in fleet]
    svc.drain()
    for h in handles:
        sh, sv, _ = solo[h.job.name]
        assert_matches_solo(h, sh, sv, f"{policy}:{h.job.name}")


def test_gang1_round_robin_is_fair_serialization():
    """gang=1 degenerates to interleaved solo execution: fleet dispatches
    equal the sum of per-job epochs (no fusion), and rotation gives every
    job progress (completion order follows job size)."""
    fleet = get_fleet("mixed3")
    solo_epochs = {c.name: _solo(c, q)[2].epochs for c, q in fleet}
    svc = JobService(
        capacity=sum(q for _, q in fleet), pop_policy="round_robin", gang=1
    )
    for c, q in fleet:
        svc.submit_case(c, quota=q)
    svc.drain()
    assert svc.stats().epochs == sum(solo_epochs.values())
