"""Persistent epoch megakernel tests (DESIGN.md §12).

The megakernel (``kernels/epoch_megakernel.py``) fuses an entire resident
chunk — scheduler pop → pack → task step → fork commit, for up to K
epochs — into one ``pl.pallas_call``, replacing the XLA ``while_loop``
sandwich in ``EpochLoop.run_chunk``.  CPU CI exercises it through Pallas
interpret mode; the jnp oracle is ``kernels/ref.py::epoch_chunk_ref``.
Load-bearing properties:

  * ``epoch_chunk`` (interpret) matches the oracle on a synthetic carry
    with scalar, array, and zero-size leaves, honouring the dynamic limit;
  * ``DeviceEngine(megakernel=True)``/``DeviceMultiplexer(megakernel=
    True)`` are bit-identical to the PR-5 ``while_loop`` resident path —
    values, heap, and the ChunkSummary-derived stats match exactly — on
    every registry fleet, for K ∈ {1, 4, ∞}, masked and gather;
  * chunked megakernel waves still pay exactly ⌈E/K⌉ readbacks;
  * the span/map width ladders clamp their minimum rung for tiny
    capacities (single-region tiny fleets stop padding to 8 lanes).
"""
import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import fib, get_case, get_fleet
from repro.core import DeviceEngine, HostEngine
from repro.core.engine import _map_width_ladder, _span_width_ladder
from repro.kernels import epoch_chunk
from repro.kernels import ref as kref
from repro.service import (
    DeviceMultiplexer,
    Job,
    JobHandle,
    JobStatus,
    WaveTemplate,
)


def _handles(fleet):
    return [
        JobHandle(i, Job(c.program, c.initial, heap_init=dict(c.heap_init),
                         quota=q, name=f"{c.name}#{i}"))
        for i, (c, q) in enumerate(fleet)
    ]


# --------------------------------------------------------- kernel plumbing
def _toy_carry():
    return {
        "n": jnp.asarray(0, jnp.int32),                 # scalar leaf
        "acc": jnp.arange(5, dtype=jnp.float32),        # array leaf
        "empty": jnp.zeros((3, 0), jnp.float32),        # zero-size leaf
    }


def _toy_cond(c, lim):
    return c["n"] < lim


def _toy_body(c):
    return {
        "n": c["n"] + 1,
        "acc": c["acc"] * 2.0 + c["empty"].sum(),
        "empty": c["empty"],
    }


@pytest.mark.parametrize("limit", [0, 1, 7])
def test_epoch_chunk_interpret_matches_ref(limit):
    ref = epoch_chunk(_toy_cond, _toy_body, _toy_carry(), limit, impl="ref")
    got = epoch_chunk(_toy_cond, _toy_body, _toy_carry(), limit,
                      impl="interpret")
    assert int(got["n"]) == int(ref["n"]) == limit
    np.testing.assert_array_equal(np.asarray(got["acc"]),
                                  np.asarray(ref["acc"]))
    assert got["empty"].shape == (3, 0)


def test_epoch_chunk_dynamic_limit_no_retrace():
    """The chunk bound is a dynamic operand: different limits re-enter one
    compiled kernel (jit cache keyed on shapes only)."""
    import jax

    calls = []

    @jax.jit
    def run(carry, lim):
        calls.append(1)
        return epoch_chunk(_toy_cond, _toy_body, carry, lim,
                           impl="interpret")
    for lim in (1, 4, 6):
        out = run(_toy_carry(), jnp.asarray(lim, jnp.int32))
        assert int(out["n"]) == lim
    assert len(calls) == 1


def test_epoch_chunk_rejects_unknown_impl():
    with pytest.raises(ValueError, match="impl"):
        epoch_chunk(_toy_cond, _toy_body, _toy_carry(), 1, impl="vulkan")


def test_epoch_chunk_ref_is_while_loop():
    out = kref.epoch_chunk_ref(_toy_cond, _toy_body, _toy_carry(),
                               jnp.asarray(3, jnp.int32))
    assert int(out["n"]) == 3


# -------------------------------------------------------------- solo engine
@pytest.mark.parametrize("dispatch", ["masked", "gather"])
def test_solo_megakernel_bit_identical(dispatch):
    """DeviceEngine(megakernel=True) under interpret mode matches the
    while_loop resident engine exactly, stats included."""
    case = get_case("fib")
    base = DeviceEngine(case.program, capacity=case.capacity,
                        dispatch=dispatch)
    hb, vb, sb = base.run(case.initial,
                          heap_init=dict(case.heap_init) or None)
    mega = DeviceEngine(case.program, capacity=case.capacity,
                        dispatch=dispatch, megakernel=True,
                        megakernel_impl="interpret")
    hm, vm, sm = mega.run(case.initial,
                          heap_init=dict(case.heap_init) or None)
    np.testing.assert_array_equal(np.asarray(vm), np.asarray(vb))
    for k in hb:
        np.testing.assert_array_equal(np.asarray(hm[k]), np.asarray(hb[k]),
                                      err_msg=k)
    assert _stats_dict(sm) == _stats_dict(sb)


def _stats_dict(s):
    d = dataclasses.asdict(s)
    d["tasks_by_type"] = dict(d["tasks_by_type"])
    d["lanes_by_type"] = dict(d["lanes_by_type"])
    return d


# ------------------------------------------------------------ fleet waves
@pytest.mark.parametrize("fleet_name", ["mixed3", "mixed4", "fib_fleet"])
@pytest.mark.parametrize("dispatch", ["masked", "gather"])
def test_fleet_megakernel_bit_identical(fleet_name, dispatch):
    """Acceptance: the megakernel chunk is bit-identical to the PR-5
    while_loop resident path on every registry fleet for K ∈ {1, 4, ∞}
    (masked and gather), with the ChunkSummary-derived fleet stats
    matching exactly."""
    fleet = get_fleet(fleet_name)
    # one wave template per driver, shared across K: the chunk bound is a
    # dynamic operand, so every K re-enters the one compiled chunk loop
    templates = {}
    for chunk in (1, 4, None):
        runs = {}
        for mega in (False, True):
            handles = _handles(fleet)
            mux = DeviceMultiplexer(
                handles, dispatch=dispatch, chunk=chunk, megakernel=mega,
                megakernel_impl="interpret" if mega else "auto",
                template=templates.get(mega),
            )
            templates.setdefault(mega, WaveTemplate(
                key=None, program=mux.program, slots=mux.slots,
                loop=mux.loop,
            ))
            mux.run()
            runs[mega] = (handles, mux.stats())
        (hb, sb), (hm, sm) = runs[False], runs[True]
        for b, m in zip(hb, hm):
            assert b.status is JobStatus.DONE and m.status is JobStatus.DONE
            np.testing.assert_array_equal(
                np.asarray(m.result.value), np.asarray(b.result.value),
                err_msg=f"{b.job.name}:K={chunk}",
            )
            for k in b.result.heap:
                np.testing.assert_array_equal(
                    np.asarray(m.result.heap[k]),
                    np.asarray(b.result.heap[k]),
                    err_msg=f"{b.job.name}:{k}:K={chunk}",
                )
            assert m.result.stats.epochs == b.result.stats.epochs
            assert (m.result.stats.tasks_executed
                    == b.result.stats.tasks_executed)
        assert _stats_dict(sm) == _stats_dict(sb), f"K={chunk}"


def test_megakernel_chunk_readback_cadence():
    """A megakernel wave of E epochs at chunk K pays exactly ⌈E/K⌉
    dispatches + readbacks, same as the while_loop driver."""
    fleet = [(get_case("fib"), 512), (get_case("treewalk"), 512)]
    template = None
    for chunk in (1, 4, None):
        handles = _handles(fleet)
        mux = DeviceMultiplexer(
            handles, chunk=chunk, megakernel=True,
            megakernel_impl="interpret", template=template,
        )
        template = template or WaveTemplate(
            key=None, program=mux.program, slots=mux.slots, loop=mux.loop,
        )
        mux.run()
        s = mux.stats()
        expect = 1 if chunk is None else math.ceil(s.epochs / chunk)
        assert s.dispatches == expect
        assert s.scalar_transfers == expect


# --------------------------------------------------------- ladder edge case
def test_width_ladders_clamp_tiny_capacities():
    """Minimum-width rungs must stay live below the default minimum: a
    capacity at/below 8 halves the floor instead of degenerating to one
    full-width rung."""
    assert _span_width_ladder(4096) == (512, 1024, 2048, 4096)
    assert _span_width_ladder(8) == (4, 8)
    assert _span_width_ladder(4) == (2, 4)
    assert _span_width_ladder(1) == (1,)
    assert _map_width_ladder(16) == (8, 16)
    assert _map_width_ladder(8) == (4, 8)
    assert _map_width_ladder(4) == (2, 4)
    assert _map_width_ladder(1) == (1,)


def test_tiny_fleet_does_not_pad_to_minimum():
    """Single-region tiny fleet: with the clamped ladder the resident
    engine launches narrow rungs (holes accrue), instead of padding every
    epoch to the old 8-lane minimum."""
    eng = DeviceEngine(fib.PROGRAM, capacity=8)
    h, v, s = eng.run(fib.initial(3))
    assert int(np.asarray(v)[0, 0]) == fib.fib_reference(3)
    # rungs are (4, 8): epochs with span <= 4 launch 4 lanes, not 8
    assert s.hole_lanes_skipped > 0
    assert s.lanes_launched < 8 * s.epochs
    assert s.lanes_launched + s.hole_lanes_skipped == 8 * s.epochs
    # bit-identical to the host run regardless of rung choice
    hh, hv, _ = HostEngine(fib.PROGRAM, capacity=8).run(fib.initial(3))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(hv))
