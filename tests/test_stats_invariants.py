"""Property sweep over the stats invariants (ISSUE 7 satellite).

Across every driver × dispatch mode × chunk size K the runtime offers,
three accounting invariants must hold:

* ``tasks_executed`` is a property of the *program*, not the driver —
  identical everywhere (the work term T_1 in the paper's accounting);
* on resident paths, the span-ladder tiling is exact:
  ``lanes_launched + hole_lanes_skipped == epochs × capacity`` (every
  full-span lane is either launched or accounted as skipped — DESIGN.md
  §11's dense-frontier claim as an equation);
* the derived ratios ``utilization`` / ``map_utilization`` stay in
  [0, 1] (they feed the RATIO_BUCKETS histograms in ``obs/metrics.py``,
  whose top bucket is 1.0).
"""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.apps import fib, get_case
from repro.core import DeviceEngine, HostEngine
from repro.service import DeviceMultiplexer, EpochMultiplexer, Job, \
    JobHandle, WaveTemplate

_POOL = ("fib", "treewalk")
_QUOTAS = (512, 1024)


def _handles(fleet):
    return [
        JobHandle(i, Job(c.program, c.initial, heap_init=dict(c.heap_init),
                         quota=q, name=f"{c.name}#{i}"))
        for i, (c, q) in enumerate(fleet)
    ]


def _check_ratios(s, label):
    assert 0.0 <= s.utilization <= 1.0, f"{label}: util={s.utilization}"
    assert 0.0 <= s.map_utilization <= 1.0, (
        f"{label}: map_util={s.map_utilization}"
    )


def _check_resident_tiling(s, capacity, label):
    assert s.lanes_launched + s.hole_lanes_skipped == s.epochs * capacity, (
        f"{label}: launched {s.lanes_launched} + skipped "
        f"{s.hole_lanes_skipped} != {s.epochs} epochs x {capacity} lanes"
    )


@settings(max_examples=3, deadline=None)
@given(members=st.lists(
    st.tuples(st.sampled_from(_POOL), st.sampled_from(_QUOTAS)),
    min_size=2, max_size=3,
))
def test_stats_invariants_across_drivers_dispatch_and_k(members):
    fleet = [(get_case(name), q) for name, q in members]
    tasks_ref = None

    # host multiplexer under every dispatch policy
    for dispatch in ("masked", "compacted", "gather"):
        handles = _handles(fleet)
        mux = EpochMultiplexer(handles, dispatch=dispatch)
        mux.run()
        s = mux.stats()
        if tasks_ref is None:
            tasks_ref = s.tasks_executed
        assert s.tasks_executed == tasks_ref, f"host:{dispatch}"
        _check_ratios(s, f"host:{dispatch}")

    # resident driver across dispatch x K (template reused across K — the
    # chunk bound is a dynamic argument of one compiled loop)
    for dispatch in ("masked", "gather"):
        template = None
        for chunk in (1, 4, None):
            handles = _handles(fleet)
            mux = DeviceMultiplexer(
                handles, dispatch=dispatch, chunk=chunk, template=template,
            )
            if template is None:
                template = WaveTemplate(
                    key=None, program=mux.program, slots=mux.slots,
                    loop=mux.loop,
                )
            mux.run()
            s = mux.stats()
            label = f"device:{dispatch}:K={chunk}"
            assert s.tasks_executed == tasks_ref, label
            _check_ratios(s, label)
            _check_resident_tiling(s, mux.capacity, label)


def test_solo_driver_invariants():
    """The solo engines obey the same equations (deterministic twin of
    the sweep, pinned so a failure names the exact configuration)."""
    cap = 256
    tasks_ref = None
    host_stats = {}
    for dispatch in ("masked", "compacted", "gather"):
        _, _, s = HostEngine(
            fib.PROGRAM, capacity=cap, dispatch=dispatch
        ).run(fib.initial(9))
        host_stats[dispatch] = s
        if tasks_ref is None:
            tasks_ref = s.tasks_executed
        assert s.tasks_executed == tasks_ref, f"host:{dispatch}"
        _check_ratios(s, f"host:{dispatch}")
    # host gather: launched + skipped tiles exactly the lane volume the
    # masked driver paid (its full-span baseline is masked's launches,
    # which are themselves span-bucketed — not epochs x capacity)
    sg, sm = host_stats["gather"], host_stats["masked"]
    assert sg.lanes_launched + sg.hole_lanes_skipped == sm.lanes_launched

    _, _, ds = DeviceEngine(
        fib.PROGRAM, capacity=cap, stack_depth=256
    ).run(fib.initial(9))
    assert ds.tasks_executed == tasks_ref
    _check_ratios(ds, "device:solo")
    _check_resident_tiling(ds, cap, "device:solo")
    np.testing.assert_allclose(
        ds.utilization, ds.tasks_executed / max(1, ds.lanes_launched)
    )
