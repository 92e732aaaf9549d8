"""Commit-phase edge paths in ``core/tvm.py``: trailing-invalid reclamation,
TV-capacity overflow, and the fork_scan / type_rank kernels against jnp
prefix-sum references on non-block-multiple lengths (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DeviceEngine,
    EngineError,
    HeapVar,
    HostEngine,
    InitialTask,
    Program,
    TaskType,
)
from repro.core import tvm

RNG = np.random.RandomState(7)


def _burst_program(n_kids: int):
    """Root forks ``n_kids`` leaves and joins; leaves emit and die."""

    def _root(ctx):
        for _ in range(n_kids):
            ctx.fork("leaf")
        ctx.join("gather")

    def _leaf(ctx):
        ctx.emit(1)

    def _gather(ctx):
        cv = ctx.child_values(n_kids)
        ctx.emit(cv[:, 0].sum())

    return Program(
        name="burst",
        tasks=(
            TaskType("root", _root),
            TaskType("leaf", _leaf),
            TaskType("gather", _gather),
        ),
        n_arg_i=1,
        value_width=1,
        value_dtype=jnp.int32,
    )


def _run_epoch(program, state, heap, start, count, cen):
    P = 16
    idx = start + jnp.arange(P, dtype=jnp.int32)
    in_range = jnp.arange(P, dtype=jnp.int32) < count
    cidx = jnp.clip(idx, 0, state.capacity - 1)
    active = in_range & (state.epoch[cidx] == cen)
    per_type, _ = tvm.trace_tasks(program, state, heap, idx, active)
    return tvm.commit_epoch(program, state, heap, idx, active, per_type,
                            jnp.asarray(cen, jnp.int32))


def test_trailing_invalid_reclamation_shrinks_next_free():
    """Paper §5.3: when the lanes at the top of the TV die, nextFreeCore
    must decrease so the slots are reused by later epochs."""
    prog = _burst_program(3)
    state = tvm.init_state(prog, 64, InitialTask(task="root"))
    heap = {}
    # epoch 1: root forks 3 leaves into slots 1..3, joins (stays valid)
    state, heap, summary, _ = _run_epoch(prog, state, heap, 0, 1, 1)
    assert int(summary.total_forks) == 3
    assert int(state.next_free) == 4
    # epoch 2: the 3 leaves emit and die -> only slot 0 stays valid, so the
    # trailing-invalid scan must pull next_free back from 4 to 1
    state, heap, summary, _ = _run_epoch(prog, state, heap, 1, 3, 2)
    assert int(summary.total_forks) == 0
    assert int(state.next_free) == 1
    assert int(state.epoch[0]) == 1  # joined root still eligible
    # epoch 1 again: gather sums the children (their values survive death)
    state, heap, summary, _ = _run_epoch(prog, state, heap, 0, 1, 1)
    assert int(state.value[0, 0]) == 3
    assert int(state.next_free) == 0  # everything dead: full reclamation


def test_reclamation_bounds_peak_tv_through_engine():
    """End to end: repeated fork bursts reuse reclaimed slots instead of
    accumulating, so peak TV stays near one burst's width."""

    def _driver(ctx):
        step = ctx.argi(0)
        for _ in range(4):
            ctx.fork("leaf", where=step < 8)
        ctx.join("next", argi=(step,), where=step < 8)

    def _leaf(ctx):
        ctx.emit(1)

    def _next(ctx):
        ctx.fork("driver", argi=(ctx.argi(0) + 1,))
        ctx.join("done", where=False)

    def _done(ctx):
        ctx.emit(0)

    prog = Program(
        name="bursts",
        tasks=(
            TaskType("driver", _driver), TaskType("leaf", _leaf),
            TaskType("next", _next), TaskType("done", _done),
        ),
        n_arg_i=1,
    )
    _, _, stats = HostEngine(prog, capacity=1 << 10).run(
        InitialTask(task="driver", argi=(0,))
    )
    # Without reclamation the 8 bursts' 40 forks would need 41 slots (every
    # child a fresh slot).  Reclamation is trailing-only (§5.3), so the dead
    # prefix drifts by one driver slot per generation — peak stays ~burst
    # width + generation count, far below the cumulative fork total.
    assert stats.total_forks == 40
    assert stats.peak_tv_slots < stats.total_forks
    assert stats.peak_tv_slots <= 13


def test_tv_overflow_raises_host():
    prog = _burst_program(8)
    with pytest.raises(EngineError, match="overflow"):
        HostEngine(prog, capacity=4).run(InitialTask(task="root"))


def test_tv_overflow_sets_summary_flag():
    prog = _burst_program(8)
    state = tvm.init_state(prog, 4, InitialTask(task="root"))
    _, _, summary, _ = _run_epoch(prog, state, {}, 0, 1, 1)
    assert bool(summary.overflow)


def test_tv_overflow_raises_device():
    from repro.apps import fib

    with pytest.raises(EngineError, match="exhausted"):
        DeviceEngine(fib.PROGRAM, capacity=16, stack_depth=64).run(
            fib.initial(12)
        )


# ---------------------------------------------------------------- kernels
@pytest.mark.parametrize("n", [1, 5, 127, 255, 1000, 1025])
def test_fork_scan_non_block_multiple_vs_cumsum(n):
    """Pallas fork_scan (interpret mode) vs the jnp.cumsum reference on
    lengths that do not divide the kernel block."""
    from repro.kernels.fork_compact import fork_scan

    x = RNG.randint(0, 5, n).astype(np.int32)
    offs, total = fork_scan(jnp.asarray(x), block_rows=8, interpret=True)
    want = np.cumsum(x) - x
    np.testing.assert_array_equal(np.asarray(offs), want)
    assert int(total) == int(x.sum())


@pytest.mark.parametrize("n", [1, 9, 250, 257, 1023])
def test_type_rank_non_block_multiple_vs_cumsum(n):
    """Pallas type_rank (interpret mode) vs a per-type jnp.cumsum reference
    on non-block-multiple lengths."""
    from repro.kernels.fork_compact import type_rank

    T = 3
    t = RNG.randint(0, T, n).astype(np.int32)
    a = RNG.rand(n) < 0.6
    rank, counts = type_rank(
        jnp.asarray(t), jnp.asarray(a), T, block_rows=8, interpret=True
    )
    rank, counts = np.asarray(rank), np.asarray(counts)
    for tt in range(T):
        m = (t == tt) & a
        excl = np.cumsum(m.astype(np.int64)) - m
        np.testing.assert_array_equal(rank[m], excl[m])
        assert counts[tt] == m.sum()
    assert (rank[~a] == -1).all()


def test_compact_types_is_a_bijection_onto_actives():
    """compact_types' permutation must cover exactly the active lanes."""
    from repro.apps import fib

    prog = fib.PROGRAM
    state = tvm.init_state(prog, 64, fib.initial(5))
    # manufacture a mixed-type population
    state = tvm.TVMState(
        task=state.task.at[1:5].set(jnp.asarray([1, 0, 1, 0])),
        argi=state.argi, argf=state.argf,
        epoch=state.epoch.at[1:5].set(1),
        value=state.value, child_base=state.child_base,
        child_count=state.child_count, next_free=jnp.asarray(5, jnp.int32),
    )
    idx = jnp.arange(8, dtype=jnp.int32)
    active = (idx < 5) & (state.epoch[idx] == 1)
    perm, counts = tvm.compact_types(prog, state, idx, active)
    perm, counts = np.asarray(perm), np.asarray(counts)
    n_active = int(np.asarray(active).sum())
    assert counts.sum() == n_active
    # the first n_active perm entries are a permutation of the active lanes
    got = sorted(perm[:n_active].tolist())
    want = sorted(np.nonzero(np.asarray(active))[0].tolist())
    assert got == want
    assert (perm[n_active:] == -1).all()
    # and same-type lanes are contiguous: counts[0] fib lanes first
    types = np.asarray(state.task)[perm[:n_active]]
    assert (np.diff(types) >= 0).all()
