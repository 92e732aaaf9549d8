"""Gather (dense-frontier) dispatch tests.

The third dispatch mode: a masked fused epoch's *scheduled* lanes are
packed into one contiguous frontier (``kernels.ops.lane_pack``), phase 2
runs over that dense frontier only, and the effects commit through the
shared :func:`~repro.core.tvm.commit_epoch` in packed lane order — which
equals masked lane order restricted to the lanes that matter, so results
are bit-identical while the cross-region hole lanes of a fused fleet are
never launched.  Load-bearing properties:

  * ``lane_pack`` (ref and the type_rank-kernel composition) produces the
    stable pack permutation;
  * solo runs are bit-identical to ``masked`` and ``compacted`` (fused
    fleets: ``tests/test_fused_gather.py``);
  * lane utilization is >= masked whenever the fused span has holes
    (fleets with >= 2 active regions), and the skipped holes are accounted
    in ``RunStats.hole_lanes_skipped``;
  * the resident (device) drivers' gather is tested in
    ``tests/test_resident_gather.py``.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import get_case
from repro.core import HostEngine
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.service import JobService, JobStatus


# ------------------------------------------------------------- lane_pack
def test_lane_pack_ref_semantics():
    act = jnp.asarray([False, True, True, False, True, False])
    perm, count = kref.lane_pack_ref(act)
    assert int(count) == 3
    np.testing.assert_array_equal(np.asarray(perm), [1, 2, 4, -1, -1, -1])


def test_lane_pack_empty_and_full():
    perm, count = kref.lane_pack_ref(jnp.zeros((4,), bool))
    assert int(count) == 0
    np.testing.assert_array_equal(np.asarray(perm), [-1] * 4)
    perm, count = kref.lane_pack_ref(jnp.ones((4,), bool))
    assert int(count) == 4
    np.testing.assert_array_equal(np.asarray(perm), [0, 1, 2, 3])


def test_lane_pack_kernel_matches_ref():
    rng = np.random.RandomState(0)
    act = jnp.asarray(rng.rand(257) < 0.3)
    perm_r, count_r = kref.lane_pack_ref(act)
    perm_k, count_k = kops.lane_pack(act, impl="interpret")
    assert int(count_r) == int(count_k)
    np.testing.assert_array_equal(np.asarray(perm_r), np.asarray(perm_k))


# ------------------------------------------------------------- solo engine
@pytest.mark.parametrize("name", ["fib", "nqueens", "mergesort"])
def test_solo_gather_bit_identical(name):
    """Gather on a solo HostEngine matches masked exactly (holes inside a
    coalesced span: lanes whose epoch number moved on)."""
    case = get_case(name)
    hm, vm, sm = case.run(dispatch="masked")
    hg, vg, sg = case.run(dispatch="gather")
    np.testing.assert_array_equal(np.asarray(vm), np.asarray(vg))
    assert set(hm) == set(hg)
    for k in hm:
        np.testing.assert_array_equal(np.asarray(hm[k]), np.asarray(hg[k]),
                                      err_msg=k)
    assert sg.epochs == sm.epochs
    assert sg.tasks_executed == sm.tasks_executed
    assert sg.total_forks == sm.total_forks
    # dense frontier: never launches more lanes than masked, and the
    # skipped lanes are exactly the accounting delta
    assert sg.lanes_launched <= sm.lanes_launched
    assert sg.utilization >= sm.utilization
    assert sg.lanes_launched + sg.hole_lanes_skipped == sm.lanes_launched
    # the pack pass costs one extra dispatch + one count transfer per epoch
    # (map payload launches ride on top, exactly as under masked)
    assert sg.dispatches == 2 * sg.epochs + sg.map_launches
    assert sg.scalar_transfers == 2 * sg.epochs
    assert sm.hole_lanes_skipped == 0


def test_solo_gather_matches_compacted():
    case = get_case("fib")
    _, vc, _ = case.run(dispatch="compacted")
    _, vg, _ = case.run(dispatch="gather")
    np.testing.assert_array_equal(np.asarray(vc), np.asarray(vg))


def test_gather_pack_kernel_plug_point():
    """The pack_fn hook accepts the Pallas composition (interpret mode on
    CPU) and yields the identical schedule."""
    case = get_case("fib")

    def pack_interpret(active):
        return kops.lane_pack(active, impl="interpret")

    _, v_ref, s_ref = case.run(dispatch="gather")
    _, v_pal, s_pal = HostEngine(
        case.program, capacity=case.capacity, dispatch="gather",
        pack_fn=pack_interpret,
    ).run(case.initial, heap_init=dict(case.heap_init) or None)
    np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_pal))
    assert s_ref.lanes_launched == s_pal.lanes_launched


def test_service_gather_dispatch_end_to_end():
    """JobService(dispatch='gather') drives waves + streaming admission on
    the gather path (queue deeper than max_jobs)."""
    from repro.apps import fib

    svc = JobService(capacity=512, max_jobs=2, dispatch="gather")
    ns = (8, 10, 9)
    handles = [
        svc.submit(fib.PROGRAM, fib.initial(n), quota=256) for n in ns
    ]
    svc.drain()
    for h, n in zip(handles, ns):
        assert h.status is JobStatus.DONE
        assert int(np.asarray(h.result.value)[0, 0]) == fib.fib_reference(n)
    assert svc.stats().hole_lanes_skipped > 0
