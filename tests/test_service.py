"""Epoch-multiplexing job service tests (DESIGN.md §8).

The load-bearing property: co-scheduling N independent programs in one
shared TVM must be *observationally invisible* to each tenant — per-job
heaps, TV-value blocks, and work stats bit-identical to a solo
``HostEngine.run`` with ``capacity=quota`` — while the fleet pays strictly
fewer fused dispatches + scalar readbacks than the sum of the solo runs.
The registry-fleet equivalences are in ``tests/test_service_fleets.py``.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import fib, get_fleet
from repro.core import HostEngine, Program, TaskType, InitialTask
from repro.service import (
    AdmissionError,
    EpochMultiplexer,
    Job,
    JobFailure,
    JobHandle,
    JobService,
    JobStatus,
    fuse_programs,
)


# --------------------------------------------------- streaming / reuse
def test_streaming_admission_reuses_regions():
    """More jobs than regions: completed regions are reclaimed and queued
    jobs of the same program template are seeded mid-flight."""
    ns = (8, 9, 10, 11, 12)
    svc = JobService(capacity=1024, max_jobs=2)
    handles = [
        svc.submit(fib.PROGRAM, fib.initial(n), quota=512, name=f"fib{n}")
        for n in ns
    ]
    seen = []
    for h in svc.completions():  # streaming completion order
        seen.append(h.job.name)
    assert sorted(seen) == sorted(f"fib{n}" for n in ns)
    for h, n in zip(handles, ns):
        assert h.status is JobStatus.DONE
        assert int(np.asarray(h.result.value)[0, 0]) == fib.fib_reference(n)
    # 5 jobs through 2 regions: at least one region was reseeded in place
    # (fib8/fib9 finish first; fib10+ ride the same multiplexer)
    assert len(seen) == len(ns)


def test_result_drives_single_job():
    svc = JobService(capacity=512)
    h = svc.submit(fib.PROGRAM, fib.initial(9), quota=256)
    assert svc.poll(h) is JobStatus.QUEUED
    res = svc.result(h)
    assert svc.poll(h) is JobStatus.DONE
    assert int(np.asarray(res.value)[0, 0]) == fib.fib_reference(9)


# -------------------------------------------------- admission / failure
def test_quota_overflow_fails_only_that_job():
    """A job outgrowing its own region fails alone; its neighbour's result
    is untouched (bounded scatters: no cross-region corruption)."""
    svc = JobService(capacity=1024)
    bad = svc.submit(fib.PROGRAM, fib.initial(12), quota=8, name="bad")
    good = svc.submit(fib.PROGRAM, fib.initial(10), quota=512, name="good")
    svc.drain()
    assert bad.status is JobStatus.FAILED
    assert isinstance(bad.error, JobFailure)
    assert good.status is JobStatus.DONE
    assert int(np.asarray(good.result.value)[0, 0]) == fib.fib_reference(10)
    with pytest.raises(JobFailure):
        svc.result(bad)


def test_admission_rejects_bad_jobs():
    svc = JobService(capacity=1024)
    with pytest.raises(AdmissionError):  # quota above service capacity
        svc.submit(fib.PROGRAM, fib.initial(8), quota=4096)
    with pytest.raises(AdmissionError):  # quota below the minimum
        svc.submit(fib.PROGRAM, fib.initial(8), quota=1)
    with pytest.raises(AdmissionError):  # unknown seed task
        svc.submit(fib.PROGRAM, InitialTask(task="nope", argi=(1,)), quota=64)


def _f32_program():
    def _emit(ctx):
        ctx.emit(jnp.float32(1.5))

    return Program(
        name="f32emit", tasks=(TaskType("emit", _emit),),
        value_dtype=jnp.float32,
    )


def test_mixed_value_dtypes_split_into_waves():
    """Fleets must share one TV value dtype; incompatible jobs are not
    rejected — the service runs them in a later wave."""
    with pytest.raises(AdmissionError):
        fuse_programs([fib.PROGRAM, _f32_program()], [64, 64])
    svc = JobService(capacity=1024, max_jobs=4)
    a = svc.submit(fib.PROGRAM, fib.initial(8), quota=256, name="i32")
    b = svc.submit(_f32_program(), InitialTask(task="emit"), quota=64,
                   name="f32")
    svc.drain()
    assert a.status is JobStatus.DONE and b.status is JobStatus.DONE
    assert int(np.asarray(a.result.value)[0, 0]) == fib.fib_reference(8)
    assert float(np.asarray(b.result.value)[0, 0]) == 1.5
    # two waves ran: one per dtype
    assert svc.stats().epochs > 0


def _w1_shape_sensitive_program():
    """value_width=1 program whose result depends on the *row shape* of
    child_values — catches fused-width leakage into a tenant's view."""

    def _root(ctx):
        leaf = ctx.argi(0) < 0
        ctx.emit(ctx.argi(0), where=leaf)
        ctx.fork("root", argi=(-1,), where=~leaf)
        ctx.fork("root", argi=(-2,), where=~leaf)
        ctx.join("gather", where=~leaf)

    def _gather(ctx):
        cv = ctx.child_values(2)  # solo shape (2, 1)
        # flat index 1 is the *second child* only at width 1
        ctx.emit(cv.reshape(-1)[1])

    return Program(
        name="w1shape",
        tasks=(TaskType("root", _root), TaskType("gather", _gather)),
        n_arg_i=1,
    )


def _w2_program():
    def _emit2(ctx):
        ctx.emit(jnp.asarray([3, 4], jnp.int32))

    return Program(
        name="w2", tasks=(TaskType("emit2", _emit2),), value_width=2
    )


def test_mixed_value_width_tenant_sees_own_shape():
    """A width-1 tenant co-scheduled with a width-2 tenant must see its own
    (n, 1) child_values rows, not the fused (n, 2)."""
    w1, w2 = _w1_shape_sensitive_program(), _w2_program()
    solo = HostEngine(w1, capacity=16).run(InitialTask(task="root", argi=(0,)))
    svc = JobService(capacity=64, max_jobs=2)
    a = svc.submit(w1, InitialTask(task="root", argi=(0,)), quota=16)
    b = svc.submit(w2, InitialTask(task="emit2"), quota=8)
    svc.drain()
    np.testing.assert_array_equal(
        np.asarray(a.result.value), np.asarray(solo[1])
    )
    assert int(np.asarray(a.result.value)[0, 0]) == -2  # the second child
    np.testing.assert_array_equal(
        np.asarray(b.result.value)[0], np.asarray([3, 4], np.int32)
    )


def test_tenant_emit_wider_than_own_width_rejected():
    """A tenant emitting wider than its own value_width must fail exactly
    as it would solo, even when the fused width could hold it."""

    def _bad(ctx):
        ctx.emit(jnp.asarray([1, 2], jnp.int32))  # width 2 in a width-1 prog

    bad = Program(name="bad", tasks=(TaskType("bad", _bad),))
    svc = JobService(capacity=64, max_jobs=2)
    svc.submit(bad, InitialTask(task="bad"), quota=8)
    svc.submit(_w2_program(), InitialTask(task="emit2"), quota=8)
    with pytest.raises(ValueError, match="wider than"):
        svc.drain()


# ----------------------------------------------------------- fusion unit
def test_fuse_programs_namespacing():
    fleet = get_fleet("mixed3")
    programs = [c.program for c, _ in fleet]
    fused, slots = fuse_programs(programs, [q for _, q in fleet])
    assert len(fused.tasks) == sum(len(p.tasks) for p in programs)
    assert fused.n_arg_i == max(p.n_arg_i for p in programs)
    # tenant namespaces are disjoint and offsets index the fused table
    for slot, p in zip(slots, programs):
        for t in p.tasks:
            fid = fused.task_id(slot.prefix + t.name)
            assert fid == slot.task_offset + p.task_id(t.name)
        for hv in p.heap:
            assert any(f.name == slot.prefix + hv.name for f in fused.heap)
    # regions tile the capacity contiguously
    assert slots[0].base == 0
    for a, b in zip(slots, slots[1:]):
        assert b.base == a.end


def test_multiplexer_direct_single_job_matches_engine():
    """The multiplexer with J=1 is exactly a solo HostEngine."""
    heap, value, stats = _solo_fib9 = (
        HostEngine(fib.PROGRAM, capacity=256).run(fib.initial(9))
    )
    h = JobHandle(0, Job(fib.PROGRAM, fib.initial(9), quota=256))
    mux = EpochMultiplexer([h])
    mux.run()
    np.testing.assert_array_equal(
        np.asarray(h.result.value), np.asarray(value)
    )
    fs = mux.stats()
    assert fs.epochs == stats.epochs
    assert fs.dispatches == stats.dispatches
    assert fs.scalar_transfers == stats.scalar_transfers


# ------------------------------------------- structural program hashing
def _make_tree_prog(fanout=2):
    """Build a fresh Program object each call: same construction path =>
    same structure, but distinct function objects and closures.  The walk
    depth is an *initial arg* (argi(1)), so structurally equal jobs can
    still run for different lengths."""

    def _node(ctx):
        d, maxd = ctx.argi(0), ctx.argi(1)
        leaf = d >= maxd
        ctx.emit(d, where=leaf)
        for _ in range(fanout):
            ctx.fork("node", argi=(d + 1, maxd), where=~leaf)
        ctx.join("sum", where=~leaf)

    def _sum(ctx):
        cv = ctx.child_values(fanout)
        ctx.emit(cv[:, 0].sum())

    return Program(
        name=f"tree{fanout}",
        tasks=(TaskType("node", _node), TaskType("sum", _sum)),
        n_arg_i=2,
    )


def test_structural_hash_equality_and_sensitivity():
    import dataclasses

    a, b, c = _make_tree_prog(2), _make_tree_prog(2), _make_tree_prog(3)
    assert a.structural_hash() == b.structural_hash()
    # captured constants (the closure's fanout) are part of the structure
    assert a.structural_hash() != c.structural_hash()
    # the display name is cosmetic, not structural
    assert (
        a.structural_hash()
        == dataclasses.replace(a, name="renamed").structural_hash()
    )
    # the fused program namespaces tasks/heaps: structurally different
    fused, _ = fuse_programs([a, b], [32, 32])
    assert fused.structural_hash() != a.structural_hash()


def test_structurally_equal_tenant_reuses_region_without_new_wave():
    """ROADMAP item: a freed region is reseeded by any same-shape tenant —
    an independently built (structurally equal) program streams into the
    region freed by a shorter job while the wave is still in flight,
    instead of forcing a second wave/retrace."""
    p1, p2 = _make_tree_prog(), _make_tree_prog()
    assert p1 is not p2
    svc = JobService(capacity=512, max_jobs=2)
    a = svc.submit(p1, InitialTask(task="node", argi=(0, 2)), quota=256,
                   name="short")
    b = svc.submit(p1, InitialTask(task="node", argi=(0, 6)), quota=256,
                   name="long")
    c = svc.submit(p2, InitialTask(task="node", argi=(0, 3)), quota=256,
                   name="late")
    muxes = set()  # hold strong refs: a freed mux's id() could be reused
    for _ in svc.completions():
        muxes.add(svc._mux)
    for h in (a, b, c):
        assert h.status is JobStatus.DONE
    # one EpochMultiplexer served all three: c streamed into a's freed
    # region (p2 is a different object but structurally equal to p1)
    assert len(muxes) == 1
    solo = HostEngine(p2, capacity=256).run(InitialTask(task="node",
                                                        argi=(0, 3)))
    np.testing.assert_array_equal(
        np.asarray(c.result.value), np.asarray(solo[1])
    )


def test_structurally_different_tenant_waits_for_next_wave():
    p1, p2 = _make_tree_prog(2), _make_tree_prog(3)
    svc = JobService(capacity=512, max_jobs=2)
    svc.submit(p1, InitialTask(task="node", argi=(0, 2)), quota=256)
    svc.submit(p1, InitialTask(task="node", argi=(0, 6)), quota=256)
    svc.submit(p2, InitialTask(task="node", argi=(0, 2)), quota=256)
    muxes = set()  # hold strong refs: a freed mux's id() could be reused
    for _ in svc.completions():
        muxes.add(svc._mux)
    assert len(muxes) == 2  # incompatible template: a second wave ran


# ------------------------------------- segmented fork-scan integration
def test_mux_with_pallas_segmented_fork_offsets():
    """The arena allocator's plug point accepts the Pallas segmented scan
    (interpret mode on CPU) and produces bit-identical fleet results."""
    from repro.kernels import ops as kops

    def seg_offsets(counts, seg, n_segs):
        return kops.segmented_fork_offsets(counts, seg, n_segs,
                                           impl="interpret")

    ns = (8, 9)
    solo = {
        n: HostEngine(fib.PROGRAM, capacity=128).run(fib.initial(n))
        for n in ns
    }
    handles = [
        JobHandle(i, Job(fib.PROGRAM, fib.initial(n), quota=128,
                         name=f"fib{n}"))
        for i, n in enumerate(ns)
    ]
    mux = EpochMultiplexer(handles, seg_offsets_fn=seg_offsets)
    mux.run()
    for h, n in zip(handles, ns):
        np.testing.assert_array_equal(
            np.asarray(h.result.value), np.asarray(solo[n][1])
        )
        assert h.result.stats.epochs == solo[n][2].epochs
