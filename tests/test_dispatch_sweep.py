"""Property-style bit-identity sweep across every dispatch mode and K.

One random mixed fleet per example, run every way the runtime offers: the
host multiplexer under ``masked`` / ``compacted`` / ``gather`` dispatch,
then the chunked resident driver over the full configuration lattice
``megakernel ∈ {False, True} × dispatch ∈ {masked, gather} × K ∈ {1, 4,
∞}`` (one wave template per (megakernel, dispatch) cell — the chunk bound
is a dynamic argument, so all three K choices re-enter one compiled
loop; the megakernel cells run the chunk inside one persistent Pallas
kernel, interpret mode on CPU).  Every run must be bit-identical per
job: same TV value block, same heap, same solo-comparable epoch count.
A separate zero-retrace guard drives
identical consecutive megakernel waves through ``JobService`` and pins
``trace_count`` flat on the second wave.
"""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.apps import get_case
from repro.service import (
    DeviceMultiplexer,
    EpochMultiplexer,
    Job,
    JobHandle,
    JobService,
    WaveTemplate,
)

_POOL = ("fib", "treewalk")
_QUOTAS = (512, 1024)  # >= every pool member's peak TV residency


def _handles(fleet):
    return [
        JobHandle(i, Job(c.program, c.initial, heap_init=dict(c.heap_init),
                         quota=q, name=f"{c.name}#{i}"))
        for i, (c, q) in enumerate(fleet)
    ]


def _snapshot(handles):
    out = []
    for h in handles:
        assert h.status.value == "done", (h.job.name, h.error)
        out.append((
            np.asarray(h.result.value),
            {k: np.asarray(v) for k, v in sorted(h.result.heap.items())},
            h.result.stats.epochs,
            h.result.stats.tasks_executed,
        ))
    return out


def _assert_same(ref, got, label):
    assert len(ref) == len(got)
    for i, (rv, rh, re, rt) in enumerate(ref):
        gv, gh, ge, gt = got[i]
        np.testing.assert_array_equal(gv, rv, err_msg=f"{label}:job{i}:value")
        assert set(gh) == set(rh)
        for k in rh:
            np.testing.assert_array_equal(
                gh[k], rh[k], err_msg=f"{label}:job{i}:{k}"
            )
        assert ge == re, f"{label}:job{i}:epochs"
        assert gt == rt, f"{label}:job{i}:tasks"


@settings(max_examples=3, deadline=None)
@given(members=st.lists(
    st.tuples(st.sampled_from(_POOL), st.sampled_from(_QUOTAS)),
    min_size=2, max_size=3,
))
def test_all_dispatch_modes_and_chunks_bit_identical(members):
    fleet = [(get_case(name), q) for name, q in members]

    handles = _handles(fleet)
    EpochMultiplexer(handles, dispatch="masked").run()
    ref = _snapshot(handles)

    for dispatch in ("compacted", "gather", "auto"):
        handles = _handles(fleet)
        EpochMultiplexer(handles, dispatch=dispatch).run()
        _assert_same(ref, _snapshot(handles), f"host:{dispatch}")

    for megakernel in (False, True):
        for dispatch in ("masked", "gather"):
            template = None
            for chunk in (1, 4, None):
                handles = _handles(fleet)
                mux = DeviceMultiplexer(
                    handles, dispatch=dispatch, chunk=chunk,
                    template=template, megakernel=megakernel,
                    megakernel_impl="interpret" if megakernel else "auto",
                )
                if template is None:
                    template = WaveTemplate(
                        key=None, program=mux.program, slots=mux.slots,
                        loop=mux.loop,
                    )
                mux.run()
                _assert_same(
                    ref, _snapshot(handles),
                    f"device:mega={megakernel}:{dispatch}:K={chunk}",
                )

    # the scale-out axis: the same fleet through P TVM shards (vmap
    # fallback on one device — bit-identical to the mesh path by
    # construction) must land on the same bits as every solo cell
    from repro.distributed import ShardedFleet

    for shards in (1, 2):
        handles = _handles(fleet)
        ShardedFleet(handles, shards=shards, chunk=4, mesh=None).run()
        _assert_same(ref, _snapshot(handles), f"sharded:P={shards}")

    # the self-tuning axis: dispatch="auto" + chunk="auto" through the
    # service front door must land on the same bits as every static cell
    svc = JobService(
        capacity=sum(q for _, q in fleet), max_jobs=len(fleet),
        engine="device", dispatch="auto", chunk="auto",
    )
    handles = [
        svc.submit(c.program, c.initial, heap_init=dict(c.heap_init),
                   quota=q, name=f"auto#{i}")
        for i, (c, q) in enumerate(fleet)
    ]
    svc.drain()
    _assert_same(ref, _snapshot(handles), "device:auto:K=auto")


def test_megakernel_waves_zero_retrace():
    """Identical consecutive megakernel waves reuse one compiled template:
    the second wave leaves ``JobService.trace_count`` unchanged (and the
    template cache reports the hit)."""
    from repro.apps import fib

    svc = JobService(capacity=512, max_jobs=2, engine="device", chunk=2,
                     megakernel=True, megakernel_impl="interpret")
    first = [svc.submit(fib.PROGRAM, fib.initial(n), quota=256)
             for n in (8, 9)]
    svc.drain()
    traced = svc.trace_count
    assert traced > 0
    assert svc.template_cache.misses == 1
    second = [svc.submit(fib.PROGRAM, fib.initial(n), quota=256)
              for n in (8, 9)]
    svc.drain()
    assert svc.trace_count == traced, (
        "identical consecutive megakernel waves must not retrace"
    )
    assert svc.template_cache.hits >= 1
    for h, n in zip(first + second, (8, 9, 8, 9)):
        assert int(np.asarray(h.result.value)[0, 0]) == fib.fib_reference(n)


def test_chunk_auto_zero_retrace_under_k_adaptation():
    """chunk="auto" adapts K between boundaries (the controller widens
    while completions don't surface), yet every K re-enters the same
    compiled chunk template: ``trace_count`` stays flat after the first
    wave — K only ever feeds the loop's *dynamic* epoch bound — and an
    identical consecutive auto wave stays flat too."""
    from repro.apps import fib

    svc = JobService(capacity=512, max_jobs=2, engine="device",
                     dispatch="auto", chunk="auto")
    first = [svc.submit(fib.PROGRAM, fib.initial(n), quota=256)
             for n in (8, 9)]
    svc.drain()
    assert svc.chunk_controller.widened > 0, (
        "a wave with no early completions must widen K"
    )
    traced = svc.trace_count
    assert traced > 0
    second = [svc.submit(fib.PROGRAM, fib.initial(n), quota=256)
              for n in (8, 9)]
    svc.drain()
    assert svc.trace_count == traced, (
        "K adaptation and an identical consecutive auto wave must not "
        "retrace"
    )
    assert svc.template_cache.hits >= 1
    for h, n in zip(first + second, (8, 9, 8, 9)):
        assert int(np.asarray(h.result.value)[0, 0]) == fib.fib_reference(n)


def test_megakernel_template_mismatch_rejected():
    """A cached chunk template bakes its dispatch + chunk driver into the
    traced loop: reusing it under a different configuration is refused."""
    import pytest

    fleet = [(get_case("fib"), 512), (get_case("treewalk"), 512)]
    mux = DeviceMultiplexer(_handles(fleet))
    template = WaveTemplate(
        key=None, program=mux.program, slots=mux.slots, loop=mux.loop
    )
    with pytest.raises(ValueError, match="dispatch"):
        DeviceMultiplexer(_handles(fleet), dispatch="gather",
                          template=template)
    with pytest.raises(ValueError, match="megakernel"):
        DeviceMultiplexer(_handles(fleet), megakernel=True,
                          template=template)
