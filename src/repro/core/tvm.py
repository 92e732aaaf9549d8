"""Task Vector Machine state + the bulk epoch step (paper §4, §5.1–5.2).

The TVM's Task Vector is stored struct-of-arrays so that every runtime access
is a unit-stride vector load/store — the TPU analogue of the paper's memory
coalescing (§5.1.2).  The Task Mask Stack is replaced, exactly as in the
paper, by per-slot Epoch Numbers (0 = invalid sentinel) plus host- or
device-side join/NDRange stacks.

The epoch step implements the paper's three phases:
  phase 1 (setup)    — pop stacks, reset fork/join/map flags  (engine)
  phase 2 (execute)  — every task type runs as one masked dense vector op
  phase 3 (commit)   — prefix-sum fork allocation, TMS update  (this module)

Each phase runs under a ``jax.named_scope`` (``trees.pack`` for the
compaction stage, ``trees.tasks``, ``trees.commit``, ``trees.maps`` for the
map payloads), so a profiler trace names the phase of every device
operation it lowers to.

The fork allocation replaces the paper's ``atomicInc(nextFreeCore)`` with an
exclusive prefix sum over per-lane fork counts (TPU has no global atomics;
the scan is deterministic and keeps children contiguous).  The scan itself is
the compute hot spot the paper optimizes with wavefront-level cooperation; we
optimize it with the ``fork_compact`` Pallas kernel (``repro.kernels``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from .primitives import EpochCtx, MapCtx
from .program import Program


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TVMState:
    """Struct-of-arrays Task Vector (+ bookkeeping scalars)."""

    task: jnp.ndarray        # i32[C]  task type id
    argi: jnp.ndarray        # i32[C, A]
    argf: jnp.ndarray        # f32[C, Af]
    epoch: jnp.ndarray       # i32[C]  epoch number; 0 = invalid
    value: jnp.ndarray       # value_dtype[C, W]  emitted values
    child_base: jnp.ndarray  # i32[C]  first child slot (contiguity invariant)
    child_count: jnp.ndarray  # i32[C]
    next_free: jnp.ndarray   # i32[]   paper's nextFreeCore

    @property
    def capacity(self) -> int:
        return self.task.shape[0]


def init_state(program: Program, capacity: int, initial) -> TVMState:
    """Paper §4.3: seed task in slot 0, eligible in the first epoch (CEN=1)."""
    from .program import pack_args

    ai, af = pack_args(program, initial.argi, initial.argf)
    tid = program.task_id(initial.task)
    state = TVMState(
        task=jnp.zeros((capacity,), jnp.int32).at[0].set(tid),
        argi=jnp.zeros((capacity, program.n_arg_i), jnp.int32).at[0].set(ai),
        argf=jnp.zeros((capacity, program.n_arg_f), jnp.float32).at[0].set(af),
        epoch=jnp.zeros((capacity,), jnp.int32).at[0].set(1),
        value=jnp.zeros((capacity, program.value_width), program.value_dtype),
        child_base=jnp.zeros((capacity,), jnp.int32),
        child_count=jnp.zeros((capacity,), jnp.int32),
        next_free=jnp.asarray(1, jnp.int32),
    )
    return state


def _exclusive_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.cumsum(x) - x


@dataclasses.dataclass(frozen=True)
class EpochSummary:
    """Scalars the CPU reads back at the end of each epoch (paper §5.2.4)."""

    total_forks: jnp.ndarray     # i32[]
    join_scheduled: jnp.ndarray  # bool[]
    map_scheduled: jnp.ndarray   # bool[]
    n_active: jnp.ndarray        # i32[]  (stats: work in tasks, T1)
    overflow: jnp.ndarray        # bool[]  TV capacity exhausted


jax.tree_util.register_dataclass(
    EpochSummary,
    data_fields=[
        "total_forks", "join_scheduled", "map_scheduled", "n_active",
        "overflow",
    ],
    meta_fields=[],
)


@dataclasses.dataclass
class JobArena:
    """Per-job slot regions inside one shared Task Vector (service layer).

    The epoch-multiplexing job service (``repro.service``) co-schedules many
    independent programs in one :class:`TVMState`.  Each job ``j`` owns the
    contiguous slot region ``[base[j], end[j])`` — its private Task Vector,
    laid out exactly as a solo run of capacity ``end[j]-base[j]`` shifted by
    ``base[j]`` — and ``slot_job`` tags every TV slot with its region index
    (``J`` for slots outside every region).  ``next`` is the per-region
    ``nextFreeCore`` cursor; :func:`commit_epoch` allocates each job's forks
    from its own cursor with a segmented prefix sum, so no job's children
    ever land in another job's region and per-job layout stays bit-identical
    to the solo run.
    """

    slot_job: jnp.ndarray  # i32[C] region index per TV slot (J = unowned)
    base: jnp.ndarray      # i32[J] region start (inclusive)
    end: jnp.ndarray       # i32[J] region end (exclusive)
    next: jnp.ndarray      # i32[J] per-region nextFreeCore (absolute slots)

    @property
    def n_jobs(self) -> int:
        return self.base.shape[0]


jax.tree_util.register_dataclass(
    JobArena,
    data_fields=["slot_job", "base", "end", "next"],
    meta_fields=[],
)


def arena_reset_region(arena: JobArena, j: int, base: int,
                       quota: int) -> JobArena:
    """Re-point region ``j``'s cursors at a freshly reseeded tenant.

    The region's ``end`` shrinks (or grows back) to the new tenant's quota
    and its ``nextFreeCore`` cursor returns to ``base + 1`` (root slot
    occupied), exactly the solo ``init_state`` layout shifted by ``base``.
    Shared by the host multiplexer's mid-flight reuse and the chunked
    resident driver's between-chunk admission, so the two paths can never
    drift.
    """
    return dataclasses.replace(
        arena,
        end=arena.end.at[j].set(base + quota),
        next=arena.next.at[j].set(base + 1),
    )


@dataclasses.dataclass(frozen=True)
class MuxEpochSummary:
    """Per-job end-of-epoch scalars for the fused multi-tenant readback.

    One ``device_get`` of this struct replaces J separate solo readbacks —
    the work-together win extended across tenants: the whole fleet pays the
    V_inf transfer once per global epoch.  The first five fields aggregate
    exactly like :class:`EpochSummary`; the ``job_*`` arrays carry each
    region's own ``nextFreeCore``/``joinScheduled``/fork totals so every
    job's scheduler can push its continuations exactly as a solo engine
    would.
    """

    total_forks: jnp.ndarray     # i32[]
    join_scheduled: jnp.ndarray  # bool[]
    map_scheduled: jnp.ndarray   # bool[]
    n_active: jnp.ndarray        # i32[]
    overflow: jnp.ndarray        # bool[]  any region exhausted
    job_forks: jnp.ndarray       # i32[J]  forks allocated per region
    job_join: jnp.ndarray        # bool[J] join scheduled per region
    job_active: jnp.ndarray      # i32[J]  active lanes per region
    job_overflow: jnp.ndarray    # bool[J] region capacity exhausted
    job_next: jnp.ndarray        # i32[J]  post-commit region cursors


jax.tree_util.register_dataclass(
    MuxEpochSummary,
    data_fields=[
        "total_forks", "join_scheduled", "map_scheduled", "n_active",
        "overflow", "job_forks", "job_join", "job_active", "job_overflow",
        "job_next",
    ],
    meta_fields=[],
)


@dataclasses.dataclass
class MapLaunch:
    """One map site's scheduled lanes, for the payload launch."""

    map_id: int
    where: jnp.ndarray  # bool[P]
    argi: jnp.ndarray   # i32[P, A]
    argf: jnp.ndarray   # f32[P, Af]


jax.tree_util.register_dataclass(
    MapLaunch,
    data_fields=["where", "argi", "argf"],
    meta_fields=["map_id"],
)


def _make_lane_fn(program: Program, ttype, heap, values):
    """Per-lane task body -> fixed effects pytree (shared by both dispatches)."""

    def lane_fn(ai, af, cb, cc, slot, _fn=ttype.fn):
        ctx = EpochCtx(program, ai, af, cb, cc, slot, heap, values)
        _fn(ctx)
        return _effects_pytree(program, ctx)

    return lane_fn


@jax.named_scope("trees.tasks")
def trace_tasks(
    program: Program,
    state: TVMState,
    heap: Dict[str, jnp.ndarray],
    idx: jnp.ndarray,
    active: jnp.ndarray,
    skip_idle_types: bool = False,
):
    """Phase 2: run every task type as one masked dense vector op.

    Baseline "work-together" dispatch: each type executes across all P
    lanes, masked — lane utilization is the divergence term of §4.4.1.

    ``skip_idle_types`` (beyond-paper engine optimization): epochs are very
    often type-homogeneous (fork epochs run forked tasks, join epochs run
    continuations — a direct consequence of the LIFO TMS), so each type's
    body is wrapped in ``lax.cond(any(mask_t))`` and skipped entirely when
    no lane of that type is active.  Effect pytrees are fixed-shape, so the
    skipped branch returns structurally identical no-op effects.
    """
    cidx = jnp.clip(idx, 0, state.capacity - 1)
    g_task = state.task[cidx]
    g_argi = state.argi[cidx]
    g_argf = state.argf[cidx]
    g_cb = state.child_base[cidx]
    g_cc = state.child_count[cidx]

    per_type = []
    for tid, ttype in enumerate(program.tasks):
        lane_fn = _make_lane_fn(program, ttype, heap, state.value)
        mask_t = active & (g_task == tid)

        def run_type(_):
            return jax.vmap(lane_fn)(g_argi, g_argf, g_cb, g_cc, cidx)

        if skip_idle_types and len(program.tasks) > 1:
            zero_eff = jax.tree.map(
                jnp.zeros_like,
                jax.eval_shape(run_type, 0),
            )
            eff = jax.lax.cond(
                mask_t.any(), run_type, lambda _: zero_eff, 0
            )
        else:
            eff = run_type(0)
        per_type.append((mask_t, eff))
    return per_type, cidx


@jax.named_scope("trees.pack")
def compact_types(
    program: Program,
    state: TVMState,
    idx: jnp.ndarray,
    active: jnp.ndarray,
    rank_fn: Optional[Callable] = None,
    offsets_fn: Optional[Callable] = None,
):
    """Compaction stage: scatter active lanes into contiguous per-type ranges.

    The §5.4 contiguity principle as a pipeline stage: each active lane gets
    a destination ``dest = type_start[type] + rank`` where ``rank`` is its
    stable within-type rank (``kernels.fork_compact.type_rank``) and
    ``type_start`` is the exclusive prefix sum of the per-type populations
    (``fork_scan`` — the same primitive that allocates fork slots).  The
    resulting permutation groups same-type tasks into dense ranges, so phase
    2 can execute each type as one coherent lane-exact launch instead of a
    full-width masked vmap.

    Returns ``(perm, counts)``:
      * ``perm`` i32[P] — ``perm[d]`` is the *lane position* (offset within
        the epoch's NDRange) of the d-th compacted lane; -1 beyond the
        active population.
      * ``counts`` i32[n_types] — per-type active populations; the host
        reads these back to size the per-type launch buckets (one extra
        V_inf transfer, the §5.4 trade).
    """
    P = idx.shape[0]
    n_types = len(program.tasks)
    cidx = jnp.clip(idx, 0, state.capacity - 1)
    types = state.task[cidx]
    if rank_fn is None:
        from ..kernels import ref as _kref

        rank, counts = _kref.type_rank_ref(types, active, n_types)
    else:
        rank, counts = rank_fn(types, active, n_types)
    if offsets_fn is None:
        type_start = _exclusive_cumsum(counts)
    else:
        type_start, _ = offsets_fn(counts)
    dest = type_start[jnp.clip(types, 0, n_types - 1)] + rank
    drop = jnp.asarray(P, jnp.int32)
    perm = (
        jnp.full((P,), -1, jnp.int32)
        .at[jnp.where(active, dest, drop)]
        .set(jnp.arange(P, dtype=jnp.int32), mode="drop")
    )
    return perm, counts.astype(jnp.int32)


@jax.named_scope("trees.tasks")
def trace_tasks_compacted(
    program: Program,
    state: TVMState,
    heap: Dict[str, jnp.ndarray],
    start: jnp.ndarray,
    count: jnp.ndarray,
    cen: jnp.ndarray,
    perm: jnp.ndarray,
    type_offsets: jnp.ndarray,
    type_counts: jnp.ndarray,
    buckets: Tuple[int, ...],
):
    """Phase 2 under the compacted dispatch: dense per-type slices.

    Each task type with a nonzero launch bucket runs over a
    ``lax.dynamic_slice`` of the compaction permutation — a contiguous range
    holding only its own lanes — instead of the full padded NDRange.  Lane
    utilization approaches 1 on heterogeneous epochs; types with zero active
    lanes launch nothing at all.

    The per-lane effects (computed at bucket width ``buckets[tid]``) are
    scattered back to full NDRange lane positions so that
    :func:`commit_epoch` observes exactly the same per-lane layout as the
    masked dispatch — fork allocation order, and therefore every result, is
    bit-identical between the two dispatches.

    Returns ``(per_type, idx, active)`` compatible with :func:`commit_epoch`.
    """
    P = perm.shape[0]
    C = state.capacity
    idx = start + jnp.arange(P, dtype=jnp.int32)
    in_range = jnp.arange(P, dtype=jnp.int32) < count
    cidx = jnp.clip(idx, 0, C - 1)
    # ``cen`` may be per-lane (service multiplexer: each lane carries its own
    # job's epoch number, 0 = lane not in any popped range); the cen>0 guard
    # keeps 0-tagged lanes from matching invalid (epoch 0) slots.
    cen_l = jnp.asarray(cen, jnp.int32)
    active = in_range & (cen_l > 0) & (state.epoch[cidx] == cen_l)
    g_task = state.task[cidx]

    pad = max(buckets) if buckets else 1
    perm_p = jnp.pad(perm, (0, max(pad, 1)), constant_values=-1)

    per_type = []
    for tid, ttype in enumerate(program.tasks):
        B = buckets[tid] if tid < len(buckets) else 0
        if B <= 0:
            continue  # no active lanes of this type: no launch at all
        mask_t = active & (g_task == tid)
        ts = type_offsets[tid]
        lanepos = jax.lax.dynamic_slice(perm_p, (ts,), (B,))
        within = jnp.arange(B, dtype=jnp.int32) < type_counts[tid]
        valid = within & (lanepos >= 0)
        src = jnp.clip(start + lanepos, 0, C - 1)
        lane_fn = _make_lane_fn(program, ttype, heap, state.value)
        eff_small = jax.vmap(lane_fn)(
            state.argi[src], state.argf[src],
            state.child_base[src], state.child_count[src], src,
        )
        # scatter effects back to NDRange lane positions for the shared commit
        pos = jnp.where(valid, lanepos, P)

        def scatter(leaf, _pos=pos):
            out = jnp.zeros((P,) + leaf.shape[1:], leaf.dtype)
            return out.at[_pos].set(leaf, mode="drop")

        eff = jax.tree.map(scatter, eff_small)
        per_type.append((mask_t, eff))
    return per_type, idx, active


def _effects_pytree(program: Program, ctx: EpochCtx):
    """Flatten recorded effects into a fixed pytree (static per task type)."""
    forks = [
        dict(where=f.where, task=f.task, argi=f.argi, argf=f.argf)
        for f in ctx.forks
    ]
    join = None
    if ctx.join_site is not None:
        j = ctx.join_site
        join = dict(where=j.where, task=j.task, argi=j.argi, argf=j.argf)
    writes = [
        dict(index=w.index, value=w.value, where=w.where) for w in ctx.writes
    ]
    maps = [
        dict(where=m.where, argi=m.argi, argf=m.argf) for m in ctx.map_sites
    ]
    meta = dict(
        write_names=tuple(w.name for w in ctx.writes),
        write_ops=tuple(w.op for w in ctx.writes),
        map_ids=tuple(m.map_id for m in ctx.map_sites),
    )
    return dict(
        forks=forks,
        join=join,
        emit_where=ctx.emit_where,
        emit_value=ctx.emit_value,
        writes=writes,
        maps=maps,
        meta=_Static(meta),
    )


class _Static:
    """Wrap static metadata so vmap treats it as an aux leaf."""

    def __init__(self, value):
        self.value = value

    def __eq__(self, other):
        return isinstance(other, _Static) and self.value == other.value

    def __hash__(self):
        return hash(repr(self.value))


jax.tree_util.register_pytree_node(
    _Static, lambda s: ((), s.value), lambda aux, _: _Static(aux)
)


def _any(masks: Sequence[jnp.ndarray], P: int) -> jnp.ndarray:
    """Lane-wise OR of per-type masks (all false when there are none)."""
    if not masks:
        return jnp.zeros((P,), bool)
    return functools.reduce(jnp.logical_or, masks)


def _by_type(pairs: Sequence[Tuple[jnp.ndarray, jnp.ndarray]]) -> jnp.ndarray:
    """Merge per-type lane values under disjoint type masks: each lane takes
    the value of the type whose mask holds it (lanes no mask holds take the
    last type's, and are dropped by the scatter that reads them)."""
    out = pairs[-1][1]
    for m, v in reversed(pairs[:-1]):
        out = jnp.where(m.reshape(m.shape + (1,) * (v.ndim - 1)), v, out)
    return out


@jax.named_scope("trees.commit")
def commit_epoch(
    program: Program,
    state: TVMState,
    heap: Dict[str, jnp.ndarray],
    idx: jnp.ndarray,
    active: jnp.ndarray,
    per_type,
    cen: jnp.ndarray,
    fork_offsets_fn: Optional[Callable] = None,
    seg_offsets_fn: Optional[Callable] = None,
    arena: Optional[JobArena] = None,
    on_passes: Optional[Callable[[int], None]] = None,
) -> Tuple[TVMState, Dict[str, jnp.ndarray], EpochSummary, List[MapLaunch]]:
    """Phase 3: prefix-sum fork allocation + TMS (epoch-number) update.

    Fork allocation defaults to ``kernels.ops.fork_offsets`` (the
    ``fork_compact.fork_scan`` Pallas kernel on TPU, its jnp reference
    elsewhere): XLA's own cumsum lowers on TPU to a reduce_window that takes
    tens of seconds to compile at 2^20 lanes, once per launch width.
    ``fork_offsets_fn(counts) -> (excl_offsets, total)`` overrides it.

    With ``arena`` (the service's multi-tenant mode) the single global
    ``nextFreeCore`` becomes one cursor per job region: every lane is tagged
    with its region index (``arena.slot_job``), fork allocation is a
    *segmented* prefix sum so each job's children stay contiguous inside its
    own region, child scatters are bounded by the region end (an overflowing
    job can never scribble into a neighbour), trailing-invalid reclamation
    (paper §5.3) runs per region, ``cen`` may be a per-lane vector (each
    lane's own job epoch number), and the summary is a
    :class:`MuxEpochSummary` carrying the per-job readback scalars.
    ``seg_offsets_fn(counts, seg, n_segs) -> (excl_offsets, seg_totals)`` is
    the arena counterpart of ``fork_offsets_fn``: it overrides
    ``kernels.ops.segmented_fork_offsets``.  This whole
    function is ``lax.while_loop``-traceable in both modes — the resident
    drivers carry the arena (cursors included) through the loop.

    The TV writes are one plan merged across task types: one scatter pass
    per column for each fork-site index (the types' ``i``-th sites share
    it), one for the joins, one each for the parent pointers, the emitted
    values and the finished entries.  A pass costs its full rung width
    however few of its rows fire, so the commit's cost follows the pass
    count.  ``on_passes(n)`` is called at trace time with the number of
    scatter passes into TV columns this commit traced.
    """
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

    cols = dict(
        task=state.task, argi=state.argi, argf=state.argf,
        epoch=state.epoch, value=state.value,
        child_base=state.child_base, child_count=state.child_count,
    )
    passes = 0
    drop = C  # out-of-range slot => dropped scatter

    def scatter(name, slots, vals):
        # one full-rung scatter pass into a TV column; a zero-width column
        # (no float args) holds nothing to write
        nonlocal passes
        if cols[name].size:
            cols[name] = cols[name].at[slots].set(vals, mode="drop")
            passes += 1

    # The write plan is merged across task types: the type masks are
    # disjoint, so one pass per column serves every type's lanes, each lane
    # carrying its own type's values.  Child slots are fresh (at or past
    # the region cursor), parent slots are the live lanes' own, and a lane
    # has one type, so no two rows of the plan hit one slot and the merge
    # writes exactly what a pass per type would.
    owned = _any([m for m, _ in per_type], P)

    # -------- forks, by site index: children at contiguous prefix-sum slots
    within = jnp.zeros((P,), jnp.int32)
    n_sites = max((len(e["forks"]) for _, e in per_type), default=0)
    for i in range(n_sites):
        sites = [(m, e["forks"][i]) for m, e in per_type
                 if len(e["forks"]) > i]
        fire = _any([m & f["where"] for m, f in sites], P)
        raw = lane_base + within
        if lane_cap is not None:
            fire = fire & (raw < lane_cap)
        slots = jnp.where(fire, raw, drop)
        for name in ("task", "argi", "argf"):
            scatter(name, slots, _by_type([(m, f[name]) for m, f in sites]))
        scatter("epoch", slots, cen + 1)
        scatter("child_base", slots, 0)
        scatter("child_count", slots, 0)
        within = within + fire.astype(jnp.int32)

    # -------- join: replace own entry; epoch number stays CEN
    joins = [(m, e["join"]) for m, e in per_type if e["join"] is not None]
    lane_join = _any([m & j["where"] for m, j in joins], P)
    join_any = lane_join.any()
    if joins:
        jslots = jnp.where(lane_join, cidx, drop)
        for name in ("task", "argi", "argf"):
            scatter(name, jslots, _by_type([(m, j[name]) for m, j in joins]))

    if per_type:
        # -------- record children pointers on the (possibly joined) parent
        pslots = jnp.where(owned, cidx, drop)
        scatter("child_base", pslots, lane_base)
        scatter("child_count", pslots, lane_count)

        # -------- emit: store value; entry becomes invalid unless joined
        ew = _any([m & e["emit_where"] for m, e in per_type], P)
        scatter("value", jnp.where(ew, cidx, drop),
                _by_type([(m, e["emit_value"]) for m, e in per_type]))
        done = owned & jnp.logical_not(lane_join)
        scatter("epoch", jnp.where(done, cidx, drop), 0)
    if on_passes is not None:
        on_passes(passes)

    map_any = jnp.asarray(False)
    map_launches: List[MapLaunch] = []
    for mask_t, eff in per_type:
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
    valid = cols["epoch"] > 0
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

    new_state = TVMState(next_free=next_free, **cols)
    return new_state, heap, summary, map_launches


def commit_scatter_passes(program: Program) -> int:
    """Scatter passes into TV columns that :func:`commit_epoch` traces per
    epoch of ``program`` when every task type runs (the masked and gather
    dispatches, solo or arena): read from an abstract trace of one lane,
    which compiles and runs nothing."""
    counted: List[int] = []
    sds = jax.ShapeDtypeStruct
    C = 2
    state = TVMState(
        task=sds((C,), jnp.int32),
        argi=sds((C, program.n_arg_i), jnp.int32),
        argf=sds((C, program.n_arg_f), jnp.float32),
        epoch=sds((C,), jnp.int32),
        value=sds((C, program.value_width), program.value_dtype),
        child_base=sds((C,), jnp.int32),
        child_count=sds((C,), jnp.int32),
        next_free=sds((), jnp.int32),
    )
    heap = {hv.name: sds(hv.shape, hv.dtype) for hv in program.heap}

    def probe(state, heap):
        idx = jnp.zeros((1,), jnp.int32)
        active = jnp.ones((1,), bool)
        per_type, _ = trace_tasks(program, state, heap, idx, active)
        return commit_epoch(
            program, state, heap, idx, active, per_type,
            jnp.asarray(1, jnp.int32), on_passes=counted.append,
        )[0]

    jax.eval_shape(probe, state, heap)
    return counted[-1]


@jax.named_scope("trees.maps")
def run_map_payload(
    program: Program,
    heap: Dict[str, jnp.ndarray],
    map_id: int,
    where: jnp.ndarray,
    argi: jnp.ndarray,
    argf: jnp.ndarray,
    domain_size: int,
) -> Dict[str, jnp.ndarray]:
    """Execute one map site's payload over lanes x dense element domain.

    The paper launches these as a separate data-parallel kernel between
    epochs (§5.2.4); here it is one vectorized masked op.
    """
    mt = program.maps[map_id]
    dom = mt.domain(argi).astype(jnp.int32)  # i32[P]

    def elem_fn(ai, af, lane_on, lane_dom, eid):
        ctx = MapCtx(program, ai, af, eid, heap)
        mt.fn(ctx)
        fire = lane_on & (eid < lane_dom)
        return [
            dict(index=w.index, value=w.value, where=fire & w.where,
                 name=_Static(w.name), op=_Static(w.op))
            for w in ctx.writes
        ]

    eids = jnp.arange(domain_size, dtype=jnp.int32)
    writes = jax.vmap(
        jax.vmap(elem_fn, in_axes=(None, None, None, None, 0)),
        in_axes=(0, 0, 0, 0, None),
    )(argi, argf, where, dom, eids)

    for w in writes:
        name = w["name"].value
        op = w["op"].value
        arr = heap[name]
        n = arr.shape[0]
        widx = jnp.where(w["where"], jnp.clip(w["index"], 0, n - 1), n)
        flat_idx = widx.reshape(-1)
        flat_val = w["value"].reshape((-1,) + arr.shape[1:])
        if op == "set":
            arr = arr.at[flat_idx].set(flat_val, mode="drop")
        elif op == "add":
            arr = arr.at[flat_idx].add(flat_val, mode="drop")
        elif op == "min":
            arr = arr.at[flat_idx].min(flat_val, mode="drop")
        elif op == "max":
            arr = arr.at[flat_idx].max(flat_val, mode="drop")
        heap = dict(heap, **{name: arr})
    return heap
