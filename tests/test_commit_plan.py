"""The commit's write plan, merged across task types (``tvm.commit_epoch``).

* bit-identity: the merged plan writes exactly what the per-type plan it
  replaced wrote (kept below as ``per_type_commit``, the reference): every
  ``TVMState`` field, the heap and every summary field, on the fused
  ``fib+fib+nqueens(6)+nqueens(6)`` and ``bfs+bfs`` programs, solo and
  arena, with an idle type, a region forced past its lane cap, and fib's
  join firing beside its forks;
* the pass count: ``commit_scatter_passes`` equals the scatters into TV
  columns in the commit's jaxpr, falls from 158 to 61 and from 98 to 49 on
  the two fused programs of the benchmark's waves, and stays at the
  per-type count on a one-type program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import bfs, fib, nqueens
from repro.core import tvm
from repro.core.tvm import (
    EpochSummary,
    JobArena,
    MapLaunch,
    MuxEpochSummary,
    TVMState,
)
from repro.kernels import ops as kops
from repro.service.multiplexer import fuse_programs

C = 512        # task-vector capacity of the synthetic states
LIVE = 0.4     # share of each region's slots below its cursor
BFS_NODES = 48


def per_type_commit(program, state, heap, idx, active, per_type, cen,
                    fork_offsets_fn=None, seg_offsets_fn=None, arena=None):
    """Reference: the commit as one scatter pass per task type, per fork
    site and per TV column (the plan before the merge)."""
    C = state.capacity
    P = idx.shape[0]
    cidx = jnp.clip(idx, 0, C - 1)

    # ---- per-lane fork counts (disjoint across types) -------------------
    lane_count = jnp.zeros((P,), jnp.int32)
    for mask_t, eff in per_type:
        cnt = jnp.zeros((P,), jnp.int32)
        for f in eff["forks"]:
            cnt = cnt + f["where"].astype(jnp.int32)
        lane_count = lane_count + jnp.where(mask_t, cnt, 0)

    lane_cap = None  # per-lane scatter bound (arena mode only)
    if arena is None:
        lane_excl, total_forks = (fork_offsets_fn or kops.fork_offsets)(
            lane_count
        )
        lane_base = state.next_free + lane_excl
        overflow = (state.next_free + total_forks) > C
    else:
        J = arena.n_jobs
        jl = jnp.clip(arena.slot_job[cidx], 0, J - 1)  # region per lane
        # segmented exclusive scan: each lane's offset among *its own job's*
        # forks — identical to the solo cumsum restricted to that region
        lane_excl, job_forks = (
            seg_offsets_fn or kops.segmented_fork_offsets
        )(lane_count, jl, J)
        job_forks = job_forks.astype(jnp.int32)
        lane_base = arena.next[jl] + lane_excl
        lane_cap = arena.end[jl]
        job_overflow = (arena.next + job_forks) > arena.end
        total_forks = job_forks.sum().astype(jnp.int32)
        overflow = job_overflow.any()

    new_task = state.task
    new_argi = state.argi
    new_argf = state.argf
    new_epoch = state.epoch
    new_value = state.value
    new_cb = state.child_base
    new_cc = state.child_count

    join_any = jnp.asarray(False)
    lane_join = jnp.zeros((P,), bool)
    map_any = jnp.asarray(False)
    map_launches: List[MapLaunch] = []
    drop = C  # out-of-range slot => dropped scatter

    for mask_t, eff in per_type:
        # -------- forks: scatter children at contiguous prefix-sum slots
        within = jnp.zeros((P,), jnp.int32)
        for f in eff["forks"]:
            fire = mask_t & f["where"]
            raw = lane_base + within
            if lane_cap is not None:
                fire = fire & (raw < lane_cap)
            slots = jnp.where(fire, raw, drop)
            new_task = new_task.at[slots].set(f["task"], mode="drop")
            new_argi = new_argi.at[slots].set(f["argi"], mode="drop")
            new_argf = new_argf.at[slots].set(f["argf"], mode="drop")
            new_epoch = new_epoch.at[slots].set(cen + 1, mode="drop")
            new_cb = new_cb.at[slots].set(0, mode="drop")
            new_cc = new_cc.at[slots].set(0, mode="drop")
            within = within + fire.astype(jnp.int32)

        # -------- join: replace own entry; epoch number stays CEN
        jw = jnp.zeros((P,), bool)
        if eff["join"] is not None:
            j = eff["join"]
            jw = mask_t & j["where"]
            jslots = jnp.where(jw, cidx, drop)
            new_task = new_task.at[jslots].set(j["task"], mode="drop")
            new_argi = new_argi.at[jslots].set(j["argi"], mode="drop")
            new_argf = new_argf.at[jslots].set(j["argf"], mode="drop")
            join_any = jnp.logical_or(join_any, jw.any())
            lane_join = lane_join | jw

        # -------- record children pointers on the (possibly joined) parent
        pslots = jnp.where(mask_t, cidx, drop)
        new_cb = new_cb.at[pslots].set(lane_base, mode="drop")
        new_cc = new_cc.at[pslots].set(lane_count, mode="drop")

        # -------- emit: store value; entry becomes invalid unless joined
        ew = mask_t & eff["emit_where"]
        eslots = jnp.where(ew, cidx, drop)
        new_value = new_value.at[eslots].set(eff["emit_value"], mode="drop")
        done = mask_t & jnp.logical_not(jw)
        dslots = jnp.where(done, cidx, drop)
        new_epoch = new_epoch.at[dslots].set(0, mode="drop")

        # -------- heap writes (reads saw the pre-epoch snapshot)
        meta = eff["meta"].value
        for w, name, op in zip(
            eff["writes"], meta["write_names"], meta["write_ops"]
        ):
            fire = mask_t & w["where"]
            arr = heap[name]
            n = arr.shape[0]
            widx = jnp.where(fire, jnp.clip(w["index"], 0, n - 1), n)
            if op == "set":
                arr = arr.at[widx].set(w["value"], mode="drop")
            elif op == "add":
                arr = arr.at[widx].add(w["value"], mode="drop")
            elif op == "min":
                arr = arr.at[widx].min(w["value"], mode="drop")
            elif op == "max":
                arr = arr.at[widx].max(w["value"], mode="drop")
            heap = dict(heap, **{name: arr})

        # -------- map scheduling
        for m, mid in zip(eff["maps"], meta["map_ids"]):
            fire = mask_t & m["where"]
            map_any = jnp.logical_or(map_any, fire.any())
            map_launches.append(
                MapLaunch(map_id=mid, where=fire, argi=m["argi"], argf=m["argf"])
            )

    # ---- trailing-invalid reclamation (paper §5.3, nextFreeCore decrease)
    iota = jnp.arange(C, dtype=jnp.int32)
    valid = new_epoch > 0
    if arena is None:
        next_free = state.next_free + total_forks
        last_valid = jnp.max(jnp.where(valid, iota, -1))
        next_free = jnp.minimum(next_free, last_valid + 1).astype(jnp.int32)
        summary = EpochSummary(
            total_forks=total_forks,
            join_scheduled=join_any,
            map_scheduled=map_any,
            n_active=active.sum().astype(jnp.int32),
            overflow=overflow,
        )
    else:
        # per-region reclamation: each cursor shrinks to just past its own
        # region's last valid slot, exactly the solo rule shifted by base
        last_valid = jax.ops.segment_max(
            jnp.where(valid, iota, -1), arena.slot_job, num_segments=J + 1
        )[:J]
        job_next = jnp.minimum(
            arena.next + job_forks, jnp.maximum(last_valid + 1, arena.base)
        ).astype(jnp.int32)
        next_free = jnp.max(job_next).astype(jnp.int32)  # fleet high-water
        summary = MuxEpochSummary(
            total_forks=total_forks,
            join_scheduled=join_any,
            map_scheduled=map_any,
            n_active=active.sum().astype(jnp.int32),
            overflow=overflow,
            job_forks=job_forks,
            job_join=jax.ops.segment_max(
                lane_join.astype(jnp.int32), jl, num_segments=J
            ) > 0,
            job_active=jax.ops.segment_sum(
                active.astype(jnp.int32), jl, num_segments=J
            ).astype(jnp.int32),
            job_overflow=job_overflow,
            job_next=job_next,
        )

    new_state = TVMState(
        task=new_task,
        argi=new_argi,
        argf=new_argf,
        epoch=new_epoch,
        value=new_value,
        child_base=new_cb,
        child_count=new_cc,
        next_free=next_free,
    )
    return new_state, heap, summary, map_launches


# ------------------------------------------------------------ the waves
@functools.lru_cache(maxsize=None)
def _wave(name: str):
    """(fused program, tenant slots) of a wave shaped like the benchmark's:
    ``bots`` fuses fib, fib, nqueens, nqueens; ``bfs`` two BFS tenants on
    one small random graph; ``bfs1`` is BFS alone (one task type)."""
    if name.startswith("bots"):
        nq = nqueens.make_program(11 if name == "bots11" else 6)
        progs = [fib.PROGRAM, fib.PROGRAM, nq, nq]
    else:
        adj_off, adj = bfs.random_graph(BFS_NODES, avg_degree=6, seed=3)
        p = bfs.make_program(BFS_NODES, len(adj))
        progs = [p] if name == "bfs1" else [p, p]
    return fuse_programs(progs, [C // len(progs)] * len(progs))


def _args(program, rng, n: int, width: int) -> np.ndarray:
    """Integer arguments a tenant's tasks can meet: fib's n (so most fib
    lanes fork twice and join), nqueens' row and attack masks, BFS's
    vertex, depth and edge chunk."""
    out = np.zeros((n, width), np.int32)
    if program.name == "fib":
        out[:, 0] = rng.integers(0, 10, n)
    elif program.name == "nqueens":
        out[:, 0] = rng.integers(0, 7, n)
        out[:, 1:4] = rng.integers(0, 1 << 11, (n, 3))
    else:
        out[:, 0] = rng.integers(0, BFS_NODES, n)
        out[:, 1] = rng.integers(0, 6, n)
        out[:, 2] = rng.integers(0, 2, n)
    return out


def _heap(name: str, rng):
    fused, slots = _wave(name)
    heap = fused.init_heap()
    if name.startswith("bfs"):
        adj_off, adj = bfs.random_graph(BFS_NODES, avg_degree=6, seed=3)
        for s in slots:
            dist = np.where(rng.random(BFS_NODES) < 0.5, bfs.INF,
                            rng.integers(0, 8, BFS_NODES)).astype(np.int32)
            heap.update({s.prefix + "adj_off": jnp.asarray(adj_off),
                         s.prefix + "adj": jnp.asarray(adj),
                         s.prefix + "dist": jnp.asarray(dist)})
    return heap


def _inputs(name: str, mode: str, case: str, seed: int):
    """A valid pre-commit task vector and one random active mask.

    Each tenant's region holds live slots below its cursor, some at the
    region's epoch number (the candidates), some waiting, some dead; every
    child slot is therefore past every live one.  ``solo`` launches the
    masked full width with one epoch number; ``arena`` packs the active
    lanes densely (the resident gather frontier) with per-region epoch
    numbers.  ``idle`` leaves one task type with no active lane;
    ``overflow`` puts the cursor (solo) or region 0's end (arena) a few
    slots past the live ones."""
    fused, slots = _wave(name)
    rng = np.random.default_rng(seed)
    J = len(slots)
    A, W = fused.n_arg_i, fused.value_width
    task = np.zeros(C, np.int32)
    argi = np.zeros((C, A), np.int32)
    epoch = np.zeros(C, np.int32)
    slot_job = np.zeros(C, np.int32)
    cens = rng.integers(2, 6, J).astype(np.int32)
    if mode == "solo":
        cens[:] = cens[0]
    nxt = np.zeros(J, np.int32)
    for j, s in enumerate(slots):
        n_live = int(s.quota * LIVE)
        slot_job[s.base:s.end] = j
        if mode == "solo":
            # one cursor: the tenants' live slots interleave at the bottom
            live = np.flatnonzero(rng.integers(0, J, C // 4) == j)
        else:
            live = np.arange(s.base, s.base + n_live)
        task[live] = s.task_offset + rng.integers(
            0, len(s.program.tasks), live.size)
        argi[live] = _args(s.program, rng, live.size, A)
        epoch[live] = rng.choice(
            np.asarray([cens[j], cens[j], cens[j] - 1, 0], np.int32),
            live.size)
        nxt[j] = s.base + n_live
    state = TVMState(
        task=jnp.asarray(task), argi=jnp.asarray(argi),
        argf=jnp.zeros((C, fused.n_arg_f), jnp.float32),
        epoch=jnp.asarray(epoch),
        value=jnp.asarray(rng.integers(-50, 50, (C, W)), fused.value_dtype),
        child_base=jnp.asarray(rng.integers(0, C, C), jnp.int32),
        child_count=jnp.asarray(rng.integers(0, 3, C), jnp.int32),
        next_free=jnp.asarray(
            nxt.max() if mode == "arena"
            else C - 3 if case == "overflow" else C // 4, jnp.int32),
    )
    cand = (epoch == cens[slot_job]) & (rng.random(C) < 0.7)
    if case == "idle":
        idle = slots[-1].task_offset  # the last tenant's first type
        cand &= task != idle
    if mode == "solo":
        idx = np.arange(C, dtype=np.int32)
        return fused, state, idx, cand, np.int32(cens[0]), None
    end = np.asarray([s.end for s in slots], np.int32)
    if case == "overflow":
        end[0] = nxt[0] + 3
    arena = JobArena(
        slot_job=jnp.asarray(slot_job),
        base=jnp.asarray([s.base for s in slots], jnp.int32),
        end=jnp.asarray(end), next=jnp.asarray(nxt),
    )
    lanes = np.flatnonzero(cand).astype(np.int32)
    idx = np.full(C, C, np.int32)
    idx[:lanes.size] = lanes
    valid = idx < C
    cen = np.where(valid, epoch[np.clip(idx, 0, C - 1)], 0).astype(np.int32)
    return fused, state, idx, valid, cen, arena


@functools.lru_cache(maxsize=None)
def _bodies(name: str):
    """The wave's task bodies over a frontier, jitted (the commits under
    test run eagerly on their effects)."""
    fused, _ = _wave(name)
    return jax.jit(lambda *a: tvm.trace_tasks(fused, *a)[0])


@pytest.mark.parametrize("case", ["mixed", "idle", "overflow"])
@pytest.mark.parametrize("mode", ["solo", "arena"])
@pytest.mark.parametrize("name", ["bots", "bfs"])
def test_merged_commit_is_bit_identical_to_per_type(name, mode, case):
    seed = 14_000 + 100 * ["bots", "bfs"].index(name) \
        + 10 * ["solo", "arena"].index(mode) \
        + ["mixed", "idle", "overflow"].index(case)
    rng = np.random.default_rng(seed + 1)
    fused, state, idx, active, cen, arena = _inputs(name, mode, case, seed)
    heap = _heap(name, rng)
    args = (state, heap, jnp.asarray(idx), jnp.asarray(active),
            jnp.asarray(cen))
    per_type = _bodies(name)(*args[:4])
    new = tvm.commit_epoch(fused, *args[:4], per_type, args[4], arena=arena)
    ref = per_type_commit(fused, *args[:4], per_type, args[4], arena=arena)
    new_leaves, new_tree = jax.tree_util.tree_flatten_with_path(new)
    ref_leaves, ref_tree = jax.tree_util.tree_flatten_with_path(ref)
    assert new_tree == ref_tree
    for (path, a), (_, b) in zip(new_leaves, ref_leaves):
        a, b = np.asarray(a), np.asarray(b)
        assert (a.dtype, a.shape) == (b.dtype, b.shape), path
        assert a.tobytes() == b.tobytes(), jax.tree_util.keystr(path)

    # the case exercises what it is named for
    new_state, _, summary, _ = new
    assert int(summary.total_forks) > 0
    assert bool(summary.overflow) == (case == "overflow")
    if name == "bots":
        assert bool(summary.join_scheduled)  # fib joins beside its forks
    if case == "idle":
        live = np.asarray(state.task)[np.clip(idx, 0, C - 1)][active]
        assert _wave(name)[1][-1].task_offset not in live
    # children landed: some slot past a cursor now holds epoch cen + 1
    grew = np.asarray(new_state.epoch) != np.asarray(state.epoch)
    assert grew.any()


# ---------------------------------------------------------- pass count
def _tv_scatters(closed) -> int:
    """Scatters into a TV column (leading dimension C, nonzero size) in a
    jaxpr, sub-jaxprs included."""
    n = 0
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "scatter":
            aval = eqn.invars[0].aval
            n += aval.shape[:1] == (C,) and aval.size > 0
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(sub, "jaxpr") and hasattr(sub, "consts"):
                    n += _tv_scatters(sub)
    return n


@pytest.mark.parametrize("name,merged,before", [
    ("bots", 36, 108),     # fib+fib+nqueens(6)+nqueens(6)
    ("bots11", 61, 158),   # the bots_dc wave: nqueens(11)
    ("bfs", 49, 98),       # the gap_urand_bfs wave: bfs+bfs
    ("bfs1", 49, 49),      # one task type: the plan is unchanged
])
def test_commit_scatter_passes(name, merged, before):
    fused, slots = _wave(name)
    assert all(hv.shape[:1] != (C,) for hv in fused.heap)
    P = 64  # a rung narrower than C, so only TV columns have C rows
    sds = jax.ShapeDtypeStruct
    state = TVMState(
        task=sds((C,), jnp.int32),
        argi=sds((C, fused.n_arg_i), jnp.int32),
        argf=sds((C, fused.n_arg_f), jnp.float32),
        epoch=sds((C,), jnp.int32),
        value=sds((C, fused.value_width), fused.value_dtype),
        child_base=sds((C,), jnp.int32),
        child_count=sds((C,), jnp.int32),
        next_free=sds((), jnp.int32),
    )
    heap = {hv.name: sds(hv.shape, hv.dtype) for hv in fused.heap}
    J = len(slots)
    arena = JobArena(sds((C,), jnp.int32), *(sds((J,), jnp.int32),) * 3)
    idx, active, cen = sds((P,), jnp.int32), sds((P,), bool), \
        sds((), jnp.int32)
    # the bodies traced once; each commit is then traced on their effects
    per_type = jax.eval_shape(
        lambda s, h, i, a: tvm.trace_tasks(fused, s, h, i, a)[0],
        state, heap, idx, active)
    recorded = []

    def count(commit, a, **kw):
        return _tv_scatters(jax.make_jaxpr(
            lambda *xs, arena: commit(fused, *xs, arena=arena, **kw)
        )(state, heap, idx, active, per_type, cen, arena=a))

    assert count(per_type_commit, None) == before
    # the merged plan, solo and arena: the jaxpr, the traced record and
    # the abstract probe agree
    assert [count(tvm.commit_epoch, a, on_passes=recorded.append)
            for a in (None, arena)] == [merged, merged]
    assert recorded == [merged, merged]
    assert tvm.commit_scatter_passes(fused) == merged
