"""TREES epoch engines: one ``EpochLoop`` driver core, many configurations.

Every engine in this codebase is the same machine driven three ways.  The
scheduling layer in ``scheduler.py`` owns phase-1 policy (join/NDRange
stacks, same-CEN coalescing, launch-bucket sizing) and the V1/V_inf
accounting; the TVM in ``tvm.py`` owns phase 2/3 execution.  This module
owns the *driver*: :class:`EpochLoop` is the shared core — step builders
(masked full-width, or the §5.4 compaction pass + dense per-type step), a
readback policy (which end-of-epoch scalars the host fetches), and a
termination predicate — and each engine is one configuration of it:

  * :class:`HostEngine` — the paper-faithful CPU/GPU split: the Python host
    performs phases 1 and 3 (stack bookkeeping, flag readback — the paper's
    ``joinScheduled``/``mapScheduled``/``nextFreeCore`` transfers) and
    dispatches one jitted XLA program per epoch.  Readback policy: the
    :class:`~repro.core.tvm.EpochSummary` scalars, once per epoch.
    Termination: the host scheduler drains.  Supports the ``masked``
    (seed), ``compacted`` (§5.4 contiguity), and ``gather`` (§11
    dense-frontier pack) dispatch policies.

  * :class:`DeviceEngine` — the beyond-paper resident variant ("future
    chips with tighter CPU/GPU coupling"): the entire epoch loop runs
    on-device inside one ``lax.while_loop``, with the stacks as
    fixed-capacity device arrays (``scheduler.batched_device_stacks`` with
    ``n_regions=1``).  Readback policy: nothing per epoch — every scalar a
    host loop would fetch accumulates in the :class:`ResidentCarry` and is
    read once at the end (dispatches = transfers = 1).  Termination: the
    traced all-stacks-empty ``while_loop`` cond.  ``masked`` dispatch
    buckets each epoch's step to the live span of the popped ranges via a
    small ``lax.switch`` ladder of compiled widths (DESIGN.md §11);
    ``gather`` packs the active lanes into a dense in-loop frontier and
    buckets to the pack *count* instead (§12; ``compacted`` stays
    host-only — its per-type launch shapes come from runtime populations).
    Optionally the whole chunk runs as one persistent Pallas megakernel
    (``megakernel=True``, ``kernels/epoch_megakernel.py``).

  * the service-layer drivers (``repro.service.multiplexer``) — the host
    ``EpochMultiplexer`` and the resident ``DeviceMultiplexer`` reuse the
    same two configurations with a :class:`~repro.core.tvm.JobArena` and a
    per-lane epoch-number vector, fusing many tenant regions into each
    epoch.

The resident loop is *chunked* (DESIGN.md §10): :meth:`EpochLoop.run_chunk`
runs the resident body until every stack drains **or** a traced epoch bound
``limit`` is reached, and the bound is a dynamic argument of one compiled
loop — so host-mux cadence (K=1), chunked residency (K epochs per
re-entry), and the fully-resident wave (limit = the epoch guard) are the
same compiled template re-entered with different bounds.  Between chunks
the host fetches one compact :class:`ChunkSummary` (per-region stack
pointers, failure flags, solo-comparable accumulators, arena cursors) —
total V_inf for a wave of E epochs is ⌈E/K⌉ dispatches + readbacks.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import tvm
from ..obs.log import get_logger, kv
from ..obs.trace import NULL_TRACER
from .program import InitialTask, Program
from .scheduler import (  # noqa: F401  (re-exports kept for back-compat)
    COMPACTED,
    MASKED,
    DispatchPolicy,
    EpochScheduler,
    NullStats,
    RunStats,
    RunStatsCollector,
    StatsCollector,
    batched_device_pop,
    batched_device_push,
    batched_device_stacks,
    device_push,
    device_stacks,
    launch_bucket,
    resolve_policy,
    size_type_buckets,
)

log = get_logger("engine")


class EngineError(RuntimeError):
    pass


# every leaf of a fleet-stacked ResidentCarry shards its leading axis over
# the 1-D "fleet" mesh (launch/mesh.py make_fleet_mesh)
_FLEET_SPEC = jax.sharding.PartitionSpec("fleet")


_COMPACTED_RESIDENT_MSG = (
    "resident (device) execution supports the 'masked' and 'gather' "
    "dispatches: the on-device loop needs launch shapes fixed at trace "
    "time — gather packs into a fixed-shape in-loop frontier, but "
    "'compacted' sizes per-type launches from runtime populations (use a "
    "host-loop driver for compacted dispatch)"
)


def resolve_resident_dispatch(dispatch, controller, capacity: int,
                              peek: Optional[Callable[[str], Any]] = None):
    """Resolve ``dispatch="auto"`` for a resident (traced) loop.

    A resident template bakes its mode in at trace time, so the decision
    is made once per template, masked-vs-gather only (§5.4 compacted
    stays host-side).  With no controller (or a cold observation window)
    the answer is masked — the cheapest critical path when nothing is
    known.

    ``peek`` is the stickiness hook (optional): called with each
    candidate mode name, it returns a truthy value when a compiled
    template for this wave shape already exists under that mode.  A hit
    wins before the controller is ever consulted — identical consecutive
    waves can never retrace on a flipped decision — while a *new* wave
    shape appearing mid-service falls through to the controller, whose
    rolling window has been accumulating fill observations across every
    prior wave's chunks.  New shapes are therefore re-evaluated against
    everything the service has learned so far, not against the cold-start
    default (DESIGN.md §14-§15; the service passes a wave-template cache
    peek here, the sharded fleet the same per-shard-layout peek).
    """
    if resolve_policy(dispatch).name != "auto":
        return dispatch
    if peek is not None:
        for cand in ("masked", "gather"):
            if peek(cand):
                return cand
    if controller is None:
        return "masked"
    return controller.choose_resident(capacity).mode


def _default_rank_fn(types, active, n_types):
    from ..kernels import ops as kops

    return kops.type_rank(types, active, n_types)


def _default_pack_fn(active):
    from ..kernels import ops as kops

    return kops.lane_pack(active)


@jax.named_scope("trees.pack")
def _frontier_mask(state, start, count, cen, P: int):
    """Per-lane active predicate of a popped NDRange frontier.

    A lane is active when it is inside the popped range, carries a nonzero
    epoch number (0 tags lanes outside every popped range on fused
    frontiers), and TMS-matches (``epoch[slot] == cen``).  This predicate
    *defines* which lanes every dispatch mode executes — masked, the
    compaction pass, and the gather pack all share it, so the three modes
    can never diverge on what counts as scheduled work.  Returns
    ``(idx, active, cen_l)``.
    """
    idx = start + jnp.arange(P, dtype=jnp.int32)
    in_range = jnp.arange(P, dtype=jnp.int32) < count
    cidx = jnp.clip(idx, 0, state.capacity - 1)
    cen_l = jnp.asarray(cen, jnp.int32)
    active = in_range & (cen_l > 0) & (state.epoch[cidx] == cen_l)
    return idx, active, cen_l


class MapLauncher:
    """Host-side launcher for scheduled ``map`` payloads (paper §5.2.4).

    Sizes each payload launch to the *live* element domain of its scheduled
    lanes, skips payloads whose lanes all have empty domains, and caches the
    jitted step per (map, lane-count, domain-bucket).  Shared by every
    host-loop driver (``HostEngine`` and the service epoch multiplexer);
    resident drivers launch payloads in-loop at ``MapType.max_domain``
    instead (see :meth:`EpochLoop.resident_body`).
    """

    def __init__(self, program: Program, donate: bool = False,
                 on_trace: Optional[Callable[[], None]] = None,
                 tracer=None):
        self.program = program
        self._donate = donate
        self._on_trace = on_trace or (lambda: None)
        self.tracer = tracer or NULL_TRACER
        self._cache: Dict[Tuple[int, int, int], Any] = {}

    def _get_step(self, mid: int, P: int, D: int):
        key = (mid, P, D)
        if key not in self._cache:
            def mfn(heap, where, argi, argf):
                self._on_trace()
                return tvm.run_map_payload(
                    self.program, heap, mid, where, argi, argf, D
                )

            self._cache[key] = jax.jit(
                mfn, donate_argnums=(0,) if self._donate else ()
            )
        return self._cache[key]

    def run(self, map_launches, heap, col: StatsCollector):
        """Launch each scheduled map payload, sized to its live domain."""
        for ml in map_launches:
            where = np.asarray(jax.device_get(ml.where))
            if not where.any():
                continue
            argi = np.asarray(jax.device_get(ml.argi))
            dom = np.asarray(self.program.maps[ml.map_id].domain(argi))
            dmax = int(dom[where].max()) if dom[where].size else 0
            if dmax <= 0:
                # every scheduled lane has an empty element domain: a launch
                # would dispatch a wasted payload (launch_bucket(0) lanes)
                continue
            D = launch_bucket(dmax, minimum=8)
            P = int(where.shape[0])
            mstep = self._get_step(ml.map_id, P, D)
            with self.tracer.span(
                "map", "host", map_id=ml.map_id, lanes=P, width=D,
            ):
                heap = mstep(heap, ml.where, ml.argi, ml.argf)
            col.dispatch()
            # what to record is the collector's decision (NullStats ignores
            # the element count), not an engine-level flag's
            col.map_launch(int(dom[where].sum()), P * D)
        return heap


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ResidentCarry:
    """``lax.while_loop`` carry of the resident drivers.

    The TVM + heap + (optional) :class:`~repro.core.tvm.JobArena`, the
    ``[n_regions, depth]`` scheduler stacks with per-region stack pointers,
    and on-device accumulators for every scalar a host loop would have read
    back per epoch — the resident "readback policy" is to fetch them once,
    after the loop.
    """

    state: Any         # TVMState
    heap: Any          # Dict[str, jnp.ndarray]
    arena: Any         # JobArena (fleet) or None (solo)
    jstack: Any        # i32[J, depth]
    rstack: Any        # i32[J, depth, 2]
    sp: Any            # i32[J]   per-region stack pointers
    failed: Any        # bool[J]  region failed (TV or stack overflow)
    failed_stack: Any  # bool[J]  the failure was scheduler stack depth
    n_epochs: Any      # i32[]    global epochs (loop iterations)
    job_epochs: Any    # i32[J]   per-region epochs (== solo epochs)
    job_tasks: Any     # i32[J,2] per-region tasks executed (T1; hi/lo)
    job_forks: Any     # i32[J,2] per-region total forks (hi/lo)
    job_peak: Any      # i32[J]   per-region peak TV cursor (region-relative)
    map_launches: Any  # i32[]    map payload launches
    map_elements: Any  # i32[2]   live map element-lanes (hi/lo, base 2^20)
    map_lanes: Any     # i32[2]   launched element-lanes (hi/lo, base 2^20)
    hole_lanes: Any    # i32[2]   full-TV lanes the span buckets skipped


_HILO_BASE = 1 << 20  # split radix: i32 hi/lo pairs count exactly to ~2^51


def _hilo_add(acc, n):
    """Add ``n`` (i32, < 2^31 - 2^20) into exact i32 (hi, lo) pairs.

    x64 is typically disabled under JAX, so there is no int64 on device;
    long resident waves would wrap a plain i32 accumulator (capacity — or
    capacity x max_domain — per epoch, times up to 2^20 epochs).  Each pair
    holds hi * 2^20 + lo exactly.  ``acc`` is ``[..., 2]`` with ``n``
    broadcast over the leading axes, so the per-region task/fork
    accumulators ([J, 2]) get the same treatment as the scalar lane
    counters ([2])."""
    lo = acc[..., 1] + n
    return jnp.stack([acc[..., 0] + lo // _HILO_BASE, lo % _HILO_BASE],
                     axis=-1)


def _hilo_value(acc):
    """Decode hi/lo pairs to exact int64 (numpy scalar for a [2] pair,
    int64 array for [J, 2] per-region pairs)."""
    a = np.asarray(acc).astype(np.int64)
    return a[..., 0] * _HILO_BASE + a[..., 1]


@dataclasses.dataclass(frozen=True)
class ChunkSummary:
    """Host-side snapshot fetched once per chunk boundary (DESIGN.md §10).

    The chunked driver's readback policy: per-region stack pointers
    (``sp[j] == 0`` means region ``j`` drained — a completion to surface),
    failure flags, the solo-comparable per-region accumulators, map-launch
    volumes, and the :class:`~repro.core.tvm.JobArena` region cursors.
    Everything the host needs to stream completions, reseed freed regions,
    and account stats between chunks — without touching the bulk TV/heap
    state, which stays on device in the :class:`ResidentCarry`.
    """

    n_epochs: int             # global epochs run so far (all chunks)
    sp: np.ndarray            # i32[J] remaining stack entries per region
    failed: np.ndarray        # bool[J] region failed (TV or stack overflow)
    failed_stack: np.ndarray  # bool[J] the failure was scheduler stack depth
    job_epochs: np.ndarray    # i32[J] per-region epochs (== solo epochs)
    job_tasks: np.ndarray     # i64[J] per-region tasks executed (T1)
    job_forks: np.ndarray     # i64[J] per-region total forks
    job_peak: np.ndarray      # i32[J] per-region peak TV cursor (relative)
    map_launches: int
    map_elements: int
    map_lanes: int
    hole_lanes: int           # full-TV lanes the live-span buckets skipped
    arena_next: Optional[np.ndarray]  # i32[J] region cursors (fleet only)


def _map_width_ladder(max_domain: int, minimum: int = 8) -> Tuple[int, ...]:
    """Power-of-2 payload widths, capped at ``max_domain``.

    The resident map launcher picks one of these at runtime from the traced
    max of the scheduled lanes' live domains (a segmented max over the
    ``where`` mask), so short-domain epochs stop paying ``max_domain``-wide
    launches.  The cap keeps the worst case exactly the old fixed-width
    behaviour, never worse.  ``minimum`` is clamped when it reaches
    ``max_domain``: without the clamp any ``max_domain <= minimum``
    degenerates to a single full-width rung (the minimum-width rung is
    dead) and every launch pads to the full domain even when the live
    domains are tiny.
    """
    if max_domain <= minimum:
        minimum = max(1, max_domain // 2)
    widths: List[int] = []
    w = minimum
    while w < max_domain:
        widths.append(w)
        w *= 2
    widths.append(max_domain)
    return tuple(widths)


def _span_width_ladder(capacity: int, levels: int = 4,
                       minimum: int = 8) -> Tuple[int, ...]:
    """Live-span launch widths for the resident epoch step.

    A halving ladder from the full TV down ``levels`` rungs: the resident
    body picks the smallest width covering the union span of this epoch's
    popped ranges (a traced min/max over the per-region stack tops) and
    ``lax.switch``es into that width's compiled step — the §10 map-payload
    bucketing one level up, applied to the task launch itself.  Each width
    traces one branch of the full phase-2/3 body, so the ladder is kept
    short (``levels``) rather than lane-exact; the top rung is always the
    full TV, so the worst case is exactly the old full-width behaviour.

    ``minimum`` is clamped when it reaches ``capacity``: without the clamp
    a TV at or below the minimum width gets a single full-capacity rung
    (the minimum-width rungs are dead), so a single-region tiny fleet pads
    every epoch to the full minimum-sized launch no matter how narrow its
    live span is.
    """
    if capacity <= minimum:
        minimum = max(1, capacity // 2)
    widths = [int(capacity)]
    w = capacity // 2
    while len(widths) < levels and w >= max(1, minimum):
        widths.append(int(w))
        w //= 2
    return tuple(sorted(widths))


def _fresh_resident_carry(
    state, heap, arena, jstack, rstack, sp, n_regions: int
) -> ResidentCarry:
    z = jnp.zeros((n_regions,), jnp.int32)
    zs = jnp.asarray(0, jnp.int32)
    z2 = jnp.zeros((2,), jnp.int32)
    zj2 = jnp.zeros((n_regions, 2), jnp.int32)
    return ResidentCarry(
        state=state, heap=heap, arena=arena,
        jstack=jstack, rstack=rstack, sp=sp,
        failed=jnp.zeros((n_regions,), bool),
        failed_stack=jnp.zeros((n_regions,), bool),
        n_epochs=zs, job_epochs=z, job_tasks=zj2, job_forks=zj2, job_peak=z,
        map_launches=zs, map_elements=z2, map_lanes=z2, hole_lanes=z2,
    )


class EpochLoop:
    """The shared epoch-driver core (step builder x readback policy x
    termination predicate).  See the module docstring for the three
    configurations; no engine owns jit caches or phase-2/3 plumbing of its
    own — they all borrow this class's.
    """

    _MAX_STEP_CACHE = 256  # distinct (P, buckets) jit specializations kept

    def __init__(
        self,
        program: Program,
        dispatch: Any = MASKED,
        *,
        rank_fn: Optional[Callable] = None,
        pack_fn: Optional[Callable] = None,
        fork_offsets_fn: Optional[Callable] = None,
        seg_offsets_fn: Optional[Callable] = None,
        donate: bool = False,
        skip_idle_types: bool = False,
        megakernel: bool = False,
        megakernel_impl: str = "auto",
        tracer=None,
        controller=None,
    ):
        self.program = program
        self.policy: DispatchPolicy = resolve_policy(dispatch)
        self.task_names = [t.name for t in program.tasks]
        # dispatch="auto": a DispatchController picks the mode per fused
        # epoch (DESIGN.md §14).  Safe because all three modes are
        # bit-identical; the hook below only moves critical-path overhead.
        if self.policy.name == "auto" and controller is None:
            from ..control.controller import DispatchController

            controller = DispatchController(n_types=len(program.tasks))
        self.controller = controller
        self.last_decision = None
        self.last_span_bucket = 0
        self._rank_fn = rank_fn or _default_rank_fn
        self._pack_fn = pack_fn or _default_pack_fn
        self._fork_offsets_fn = fork_offsets_fn
        self._seg_offsets_fn = seg_offsets_fn
        self._donate = donate
        self._skip_idle_types = skip_idle_types
        # resident chunks run through the persistent Pallas megakernel
        # (kernels/epoch_megakernel.py) instead of a lax.while_loop; same
        # traced body, same bits, one fused kernel per chunk (DESIGN.md §12)
        self.megakernel = bool(megakernel)
        self.megakernel_impl = megakernel_impl
        if self.megakernel:
            from ..kernels import epoch_megakernel as mk

            mk.resolve_impl(megakernel_impl)  # refuse on TPU up front
        # trace-counter hook: every traced builder body bumps this at trace
        # time (tracing executes the Python body; cached executions do not),
        # so "two identical consecutive waves retraced nothing" is a
        # testable invariant of the wave-template cache, not a hope
        self.trace_count = 0
        # span tracing is opt-in: NULL_TRACER's hooks are constant-time
        # no-ops, so the disabled path stays off the critical budget
        self.tracer = tracer or NULL_TRACER
        self.maps = MapLauncher(program, donate=donate,
                                on_trace=self._mark_trace,
                                tracer=self.tracer)
        self._step_cache: Dict[Any, Any] = {}
        self._compact_cache: Dict[int, Any] = {}
        self._gather_cache: Dict[int, Any] = {}
        self._resident_cache: Dict[Any, Any] = {}

    def _mark_trace(self) -> None:
        self.trace_count += 1

    @functools.cached_property
    def commit_scatter_passes(self) -> int:
        """Scatter passes into TV columns per epoch of this loop's commit
        (:func:`~repro.core.tvm.commit_scatter_passes`: a static count of
        the program, the same at every rung; read from an abstract trace
        that compiles nothing and leaves ``trace_count`` alone)."""
        return tvm.commit_scatter_passes(self.program)

    # ---------------------------------------------------- traced step bodies
    def _masked_step_fn(self, P: int):
        """Phase 2+3 masked step; pure traced fn, usable both under jit
        (host loop) and inside a resident ``lax.while_loop``.

        ``cen`` may be a scalar (solo NDRange frontier) or a per-lane i32
        vector (fused multi-region frontier; 0 = lane in no popped range —
        the ``cen > 0`` guard keeps 0-tagged lanes from matching invalid
        TV slots).  ``arena`` is ``None`` (solo: one global ``nextFreeCore``)
        or a :class:`~repro.core.tvm.JobArena` (per-region cursors).
        """
        program = self.program
        skip = self._skip_idle_types

        def step(state, heap, arena, start, count, cen):
            self._mark_trace()
            idx, active, cen_l = _frontier_mask(state, start, count, cen, P)
            per_type, _ = tvm.trace_tasks(
                program, state, heap, idx, active, skip_idle_types=skip
            )
            return tvm.commit_epoch(
                program, state, heap, idx, active, per_type, cen_l,
                fork_offsets_fn=self._fork_offsets_fn,
                seg_offsets_fn=self._seg_offsets_fn,
                arena=arena,
            )

        return step

    def _evict(self):
        # Bucket combinations on k-type programs can be numerous; bound the
        # cache (FIFO eviction — evicted shapes just recompile) so a
        # long-running driver cannot grow it without limit.
        while len(self._step_cache) >= self._MAX_STEP_CACHE:
            self._step_cache.pop(next(iter(self._step_cache)))

    def masked_step(self, P: int):
        key = ("m", P)
        if key not in self._step_cache:
            self._evict()
            self._step_cache[key] = jax.jit(
                self._masked_step_fn(P),
                donate_argnums=(0, 1) if self._donate else (),
            )
        return self._step_cache[key]

    def compact_pass(self, P: int):
        """Compaction pass: types -> (perm, per-type counts), one dispatch
        (§5.4's extra V_inf dispatch + transfer, paid to make phase 2
        lane-exact)."""
        if P not in self._compact_cache:
            program, rank_fn = self.program, self._rank_fn
            offsets_fn = self._fork_offsets_fn

            def cfn(state, start, count, cen):
                self._mark_trace()
                idx, active, _ = _frontier_mask(state, start, count, cen, P)
                return tvm.compact_types(
                    program, state, idx, active,
                    rank_fn=rank_fn, offsets_fn=offsets_fn,
                )

            self._compact_cache[P] = jax.jit(cfn)
        return self._compact_cache[P]

    def compacted_step(self, P: int, buckets: Tuple[int, ...]):
        key = ("c", P, buckets)
        if key not in self._step_cache:
            self._evict()
            program = self.program

            def step(state, heap, arena, start, count, cen, perm, toffs,
                     tcounts):
                self._mark_trace()
                per_type, idx, active = tvm.trace_tasks_compacted(
                    program, state, heap, start, count, cen,
                    perm, toffs, tcounts, buckets,
                )
                return tvm.commit_epoch(
                    program, state, heap, idx, active, per_type, cen,
                    fork_offsets_fn=self._fork_offsets_fn,
                    seg_offsets_fn=self._seg_offsets_fn,
                    arena=arena,
                )

            self._step_cache[key] = jax.jit(
                step, donate_argnums=(0, 1) if self._donate else ()
            )
        return self._step_cache[key]

    def gather_pass(self, P: int):
        """Frontier pack pass: active mask -> (perm, count), one dispatch.

        The gather dispatch's sibling of :meth:`compact_pass` — one extra
        V_inf dispatch + one count transfer, paid to make the task step
        launch only the epoch's dense active frontier instead of the whole
        (hole-ridden) fused span.
        """
        if P not in self._gather_cache:
            pack_fn = self._pack_fn

            def gfn(state, start, count, cen):
                self._mark_trace()
                _, active, _ = _frontier_mask(state, start, count, cen, P)
                with jax.named_scope("trees.pack"):
                    return pack_fn(active)

            self._gather_cache[P] = jax.jit(gfn)
        return self._gather_cache[P]

    def gather_step(self, P: int, G: int):
        """Phase 2+3 over the packed dense frontier (gather dispatch).

        The frontier holds *every* active lane of the epoch in increasing
        lane order (the pack is stable), so the fork prefix sum inside
        :func:`~repro.core.tvm.commit_epoch` sees exactly the masked
        dispatch's allocation order restricted to the lanes that matter —
        results are bit-identical, hole lanes between active regions are
        simply never launched.  Each gathered lane's epoch number is read
        from the TV itself (``active`` implies ``epoch[slot] == cen``), so
        the dense step needs no per-lane CEN transfer.
        """
        key = ("g", P, G)
        if key not in self._step_cache:
            self._evict()
            program = self.program
            skip = self._skip_idle_types

            def step(state, heap, arena, start, perm):
                self._mark_trace()
                with jax.named_scope("trees.pack"):
                    lanepos = perm[:G]
                    valid = lanepos >= 0
                    idx = jnp.where(valid, start + lanepos, state.capacity)
                    cidx = jnp.clip(idx, 0, state.capacity - 1)
                    cen_g = jnp.where(valid, state.epoch[cidx], 0)
                per_type, _ = tvm.trace_tasks(
                    program, state, heap, idx, valid, skip_idle_types=skip
                )
                return tvm.commit_epoch(
                    program, state, heap, idx, valid, per_type, cen_g,
                    fork_offsets_fn=self._fork_offsets_fn,
                    seg_offsets_fn=self._seg_offsets_fn,
                    arena=arena,
                )

            self._step_cache[key] = jax.jit(
                step, donate_argnums=(0, 1) if self._donate else ()
            )
        return self._step_cache[key]

    def _resident_gather_step_fn(self, W: int):
        """Phase 2+3 over the resident *in-loop* packed frontier.

        The resident sibling of :meth:`gather_step`: ``perm`` is the
        stable full-TV pack permutation computed inside the loop body
        (fixed shape, so it traces), and ``W`` is the ladder rung covering
        the pack count — ``perm[:W]`` holds every active lane of the epoch
        in increasing lane order.  Epoch numbers are read from the TV
        itself (``active`` implies ``epoch[slot] == cen``), the commit's
        segmented fork scan sees masked allocation order restricted to the
        active lanes, and the union span's hole lanes are never stepped —
        the §11 gather frontier without leaving the resident loop.
        """
        program = self.program
        skip = self._skip_idle_types

        def step(state, heap, arena, perm):
            self._mark_trace()
            with jax.named_scope("trees.pack"):
                lanepos = perm[:W]
                valid = lanepos >= 0
                idx = jnp.where(valid, lanepos, state.capacity)
                cidx = jnp.clip(idx, 0, state.capacity - 1)
                cen_g = jnp.where(valid, state.epoch[cidx], 0)
            per_type, _ = tvm.trace_tasks(
                program, state, heap, idx, valid, skip_idle_types=skip
            )
            return tvm.commit_epoch(
                program, state, heap, idx, valid, per_type, cen_g,
                fork_offsets_fn=self._fork_offsets_fn,
                seg_offsets_fn=self._seg_offsets_fn,
                arena=arena,
            )

        return step

    # ------------------------------------------------- one host-driven epoch
    def run_epoch(self, state, heap, arena, start, span, cen, col, readback):
        """One fused host-driven epoch: optional compaction or gather-pack
        pass (+ count readback), the phase-2/3 step, then the end-of-epoch
        readback.

        ``cen`` is an int (solo frontier) or an i32 vector of length
        ``span`` (fused multi-region frontier; padded to the launch bucket
        with inert zeros).  ``readback`` is the readback policy:
        ``(summary, state) -> pytree`` of device scalars; its single
        ``device_get`` is the epoch's scalar transfer — the paper's
        ``nextFreeCore``/``joinScheduled``/``mapScheduled`` fetch.

        Returns ``(state, heap, summary, fetched, map_launches, launched,
        by_type, n_dispatches)`` where ``summary`` stays on device (drivers
        that thread device state — the multiplexer's arena — use it) and
        ``fetched`` is the host-side readback.
        """
        P = self.policy.epoch_bucket(span)
        start_j = jnp.asarray(start, jnp.int32)
        count_j = jnp.asarray(span, jnp.int32)
        if np.ndim(cen) == 0:
            cen_j = jnp.asarray(cen, jnp.int32)
        else:
            cen_np = np.zeros(P, np.int32)
            cen_np[: np.shape(cen)[0]] = np.asarray(cen)
            cen_j = jnp.asarray(cen_np)
        dispatches = 1
        by_type = None
        tr = self.tracer
        # decision hook: under dispatch="auto" the controller prices this
        # epoch's modes at the rolling observed fill and picks one; static
        # policies pass through.  The decision (and its evidence) rides the
        # dispatch span args so adaptivity is auditable in perfetto.
        mode = self.policy.name
        decision = None
        if mode == "auto":
            decision = self.controller.choose(P)
            mode = decision.mode
        self.last_decision = decision
        self.last_span_bucket = P
        dargs = {}
        if decision is not None:
            dargs["auto_reason"] = decision.reason
            if decision.hole_fraction is not None:
                dargs["auto_hole_fraction"] = round(decision.hole_fraction, 4)
            if decision.costs:
                dargs["auto_cost_us"] = {
                    m: round(c * 1e6, 2) for m, c in decision.costs.items()
                }
        if mode == "compacted":
            # the pack span includes its count readback (the §5.4 extra
            # V_inf dispatch + transfer), so its duration is that term's
            # real critical-path cost
            with tr.span("pack", "host", mode="compacted", width=P):
                perm, counts_dev = self.compact_pass(P)(
                    state, start_j, count_j, cen_j
                )
                counts = np.asarray(jax.device_get(counts_dev), np.int64)
            col.dispatch()
            col.transfer()
            dispatches += 1
            buckets, toffs, launched, by_type = size_type_buckets(
                self.policy, counts, self.task_names
            )
            with tr.span(
                "dispatch", "host", mode="compacted", launched=launched,
                **dargs,
            ):
                state, heap, summary, map_launches = self.compacted_step(
                    P, buckets
                )(
                    state, heap, arena, start_j, count_j, cen_j, perm,
                    jnp.asarray(toffs, jnp.int32),
                    jnp.asarray(counts, jnp.int32),
                )
        elif mode == "gather":
            with tr.span("pack", "host", mode="gather", width=P):
                perm, count_dev = self.gather_pass(P)(
                    state, start_j, count_j, cen_j
                )
                n_sched = int(jax.device_get(count_dev))
            col.dispatch()
            col.transfer()
            dispatches += 1
            G = self.policy.epoch_bucket(n_sched)
            with tr.span(
                "dispatch", "host", mode="gather", launched=G, holes=P - G,
                **dargs,
            ):
                state, heap, summary, map_launches = self.gather_step(P, G)(
                    state, heap, arena, start_j, perm
                )
            launched = G
            col.holes_skipped(P - G)
        else:
            with tr.span(
                "dispatch", "host", mode="masked", launched=P, **dargs,
            ):
                state, heap, summary, map_launches = self.masked_step(P)(
                    state, heap, arena, start_j, count_j, cen_j
                )
            launched = P
        # dispatch spans measure enqueue time (XLA launches are async); the
        # readback span absorbs the wait — exactly the paper's per-epoch
        # scalar-transfer stall
        with tr.span("readback", "host"):
            fetched = jax.device_get(readback(summary, state))
        col.dispatch()
        col.transfer()
        return (
            state, heap, summary, fetched, map_launches, launched, by_type,
            dispatches,
        )

    # --------------------------------------------------- resident while_loop
    def resident_body(self, capacity: int, stack_depth: int):
        """Body of the resident epoch loop.

        The device "readback policy" is *nothing per epoch*: every scalar a
        host loop fetches accrues in the :class:`ResidentCarry` instead.
        Handles both configurations:

          * solo (``carry.arena is None``): one region; its popped NDRange
            ``[start, start+count)`` is processed masked, exactly the seed
            ``DeviceEngine`` body.
          * fleet (``JobArena``): every live region's pop is fused into one
            per-lane epoch-number vector over the whole TV and committed
            with the segmented per-region allocator; the arena's region
            cursors ride the carry, so the whole wave runs without the host.

        Either way the task step itself launches at the smallest ladder
        width (`_span_width_ladder`) covering the union span of this
        epoch's popped ranges — full-TV (or full-capacity) launches only
        happen when the live span actually demands them; the skipped lanes
        accrue in the carry's ``hole_lanes`` pair (DESIGN.md §11).

        Under ``dispatch="gather"`` the same ladder sizes a *dense*
        frontier instead: the epoch's active lanes are packed in-loop by
        the stable ``lane_pack`` permutation (a fixed-shape traced pass —
        the resident analogue of :meth:`gather_pass`), and the step
        launches at the smallest rung covering the pack *count* rather
        than the union span, so cross-region holes inside the span are
        never stepped either (DESIGN.md §12).

        Region failure (TV-region or stack overflow) zeroes that region's
        stack pointer: the job stops, its neighbours keep running — the same
        isolation the host multiplexer provides.
        """
        if self.policy.name not in ("masked", "gather"):
            raise ValueError(_COMPACTED_RESIDENT_MSG)
        gather = self.policy.name == "gather"
        program = self.program
        pack_fn = self._pack_fn
        span_widths = _span_width_ladder(capacity)
        if gather:
            step_fns = {
                W: self._resident_gather_step_fn(W) for W in span_widths
            }
        else:
            step_fns = {W: self._masked_step_fn(W) for W in span_widths}

        def make_branch(W: int, fleet: bool):
            """One span-bucket branch: the masked step at width ``W`` over
            the window ``[st, st+W)`` covering the live span, with the
            map-launch tensors padded back to full-TV width so every
            ``lax.switch`` branch returns one pytree shape."""
            step_fn = step_fns[W]

            def branch(state, heap, arena_, scen, lo, ct):
                if fleet:
                    # clamp so the window stays inside the TV; W covers the
                    # span, so the clamped window still contains every
                    # popped range (st <= lo and st + W >= span end)
                    with jax.named_scope("trees.pack"):
                        st = jnp.clip(lo, 0, capacity - W)
                        cen_w = jax.lax.dynamic_slice(scen, (st,), (W,))
                    s2, h2, summ, mls = step_fn(
                        state, heap, arena_, st,
                        jnp.asarray(W, jnp.int32), cen_w,
                    )
                else:
                    st = lo
                    s2, h2, summ, mls = step_fn(
                        state, heap, arena_, st, ct, scen
                    )
                full = []
                with jax.named_scope("trees.maps"):
                    for ml in mls:
                        zw = jnp.zeros((capacity,), bool)
                        zi = jnp.zeros(
                            (capacity,) + ml.argi.shape[1:], ml.argi.dtype
                        )
                        zf = jnp.zeros(
                            (capacity,) + ml.argf.shape[1:], ml.argf.dtype
                        )
                        full.append(tvm.MapLaunch(
                            map_id=ml.map_id,
                            where=jax.lax.dynamic_update_slice(
                                zw, ml.where, (st,)
                            ),
                            argi=jax.lax.dynamic_update_slice(
                                zi, ml.argi,
                                (st,) + (0,) * (ml.argi.ndim - 1),
                            ),
                            argf=jax.lax.dynamic_update_slice(
                                zf, ml.argf,
                                (st,) + (0,) * (ml.argf.ndim - 1),
                            ),
                        ))
                return s2, h2, summ, full

            return branch

        def make_gather_branch(W: int):
            """One pack-count bucket branch: the dense gather step at rung
            ``W``, with the map-launch tensors scattered back to full-TV
            width through the pack permutation so every ``lax.switch``
            branch returns one pytree shape (the gather twin of
            ``make_branch``'s window padding)."""
            step_fn = step_fns[W]

            def branch(state, heap, arena_, perm):
                s2, h2, summ, mls = step_fn(state, heap, arena_, perm)
                full = []
                with jax.named_scope("trees.maps"):
                    lanepos = perm[:W]
                    # invalid pack slots scatter to the drop index
                    # (capacity)
                    scat = jnp.where(lanepos >= 0, lanepos, capacity)
                    for ml in mls:
                        zw = jnp.zeros((capacity,), bool)
                        zi = jnp.zeros(
                            (capacity,) + ml.argi.shape[1:], ml.argi.dtype
                        )
                        zf = jnp.zeros(
                            (capacity,) + ml.argf.shape[1:], ml.argf.dtype
                        )
                        full.append(tvm.MapLaunch(
                            map_id=ml.map_id,
                            where=zw.at[scat].set(ml.where, mode="drop"),
                            argi=zi.at[scat].set(ml.argi, mode="drop"),
                            argf=zf.at[scat].set(ml.argf, mode="drop"),
                        ))
                return s2, h2, summ, full

            return branch

        def body(carry: ResidentCarry):
            self._mark_trace()
            # the phases of one epoch, named for the profiler (op-name
            # metadata only: a scope adds no operation and no trace)
            with jax.named_scope("trees.pop"):
                cen, start, count, live, sp = batched_device_pop(
                    carry.jstack, carry.rstack, carry.sp
                )
                arena = carry.arena
                if arena is None:
                    lo, ct = start[0], count[0]
                    span_w = jnp.where(live[0], count[0], 0)
                    if gather:
                        # gather packs over the full TV, so the solo popped
                        # range becomes a per-lane CEN vector like the
                        # fleet's
                        lanes = jnp.arange(capacity, dtype=jnp.int32)
                        in_pop = live[0] & (lanes >= lo) & (lanes < lo + ct)
                        step_cen = jnp.where(in_pop, cen[0], 0)
                    else:
                        step_cen = jnp.where(live[0], cen[0], 0)
                else:
                    # fuse every live region's pop into a per-lane CEN
                    # vector over the full TV (work-together across
                    # regions); the task launch itself is then bucketed to
                    # the union span of the popped ranges — a wave with one
                    # hot region stops paying full-TV launches every epoch
                    J = arena.n_jobs
                    lanes = jnp.arange(capacity, dtype=jnp.int32)
                    jl = jnp.clip(arena.slot_job, 0, J - 1)
                    owned = arena.slot_job < J
                    in_pop = (
                        owned & live[jl]
                        & (lanes >= start[jl])
                        & (lanes < start[jl] + count[jl])
                    )
                    step_cen = jnp.where(in_pop, cen[jl], 0)
                    big = jnp.asarray(capacity, jnp.int32)
                    span_lo = jnp.min(jnp.where(live, start, big))
                    span_hi = jnp.max(jnp.where(live, start + count, 0))
                    lo = jnp.clip(span_lo, 0, capacity)
                    ct = jnp.asarray(capacity, jnp.int32)
                    span_w = jnp.clip(span_hi - lo, 0, capacity)

            with jax.named_scope("trees.pack"):
                swarr = jnp.asarray(span_widths, jnp.int32)
                if gather:
                    # the shared frontier predicate over the full TV:
                    # scheduled lanes are exactly those whose TV epoch
                    # TMS-matches the per-lane CEN of this epoch's popped
                    # ranges
                    act = (step_cen > 0) & (carry.state.epoch == step_cen)
                    perm, n_sched = pack_fn(act)
                    width_key = n_sched
                    branches = [make_gather_branch(W) for W in span_widths]
                    operands = (carry.state, carry.heap, arena, perm)
                else:
                    width_key = span_w
                    branches = [
                        make_branch(W, arena is not None)
                        for W in span_widths
                    ]
                    operands = (
                        carry.state, carry.heap, arena, step_cen, lo, ct
                    )
                sidx = jnp.clip(
                    jnp.searchsorted(swarr, width_key, side="left"),
                    0, len(span_widths) - 1,
                )
                hole_lanes = _hilo_add(
                    carry.hole_lanes,
                    jnp.asarray(capacity, jnp.int32) - swarr[sidx],
                )
            # the rung's step: each branch scopes its own pack, commit and
            # map padding; the rest of it is the tasks phase
            with jax.named_scope("trees.tasks"):
                if len(branches) == 1:
                    state, heap, summary, map_launches = branches[0](
                        *operands
                    )
                else:
                    state, heap, summary, map_launches = jax.lax.switch(
                        sidx, branches, *operands
                    )
            with jax.named_scope("trees.push"):
                if arena is None:
                    job_join = summary.join_scheduled[None]
                    job_forks = summary.total_forks[None]
                    job_next = state.next_free[None]
                    job_over = summary.overflow[None]
                    job_active = summary.n_active[None]
                    job_peak = jnp.maximum(carry.job_peak, job_next)
                else:
                    job_join = summary.job_join
                    job_forks = summary.job_forks
                    job_next = summary.job_next
                    job_over = summary.job_overflow
                    job_active = summary.job_active
                    job_peak = jnp.maximum(
                        carry.job_peak, summary.job_next - arena.base
                    )
                    # the region cursors ride the carry — the device-side
                    # equivalent of the host multiplexer's arena.next
                    # update
                    arena = dataclasses.replace(arena, next=summary.job_next)
                failed = carry.failed | (live & job_over)
                ok = live & ~failed
                # LIFO push order exactly as the host scheduler (§4.3.3):
                # join continuation below, this epoch's forked range on top
                jstack, rstack, sp, of1 = batched_device_push(
                    carry.jstack, carry.rstack, sp,
                    cen, start, count, ok & job_join, stack_depth,
                )
                jstack, rstack, sp, of2 = batched_device_push(
                    jstack, rstack, sp,
                    cen + 1, job_next - job_forks, job_forks,
                    ok & (job_forks > 0), stack_depth,
                )
                failed_stack = carry.failed_stack | of1 | of2
                failed = failed | of1 | of2
                sp = jnp.where(failed, 0, sp)
                n_epochs = carry.n_epochs + 1
                job_epochs = carry.job_epochs + live.astype(jnp.int32)
                job_tasks = _hilo_add(carry.job_tasks, job_active)
                job_forks_acc = _hilo_add(carry.job_forks, job_forks)

            # map payloads sized to a power-of-2 width bucket picked by a
            # traced max over the scheduled lanes' live domains: each bucket
            # width traces its own lax.switch branch (shapes stay static),
            # runtime pays only the selected one — instead of always
            # MapType.max_domain.  The *lane* axis is bucketed the same way
            # (DESIGN.md §14): the stable gather pack's permutation gathers
            # the scheduled lanes into `rung(count)` payload rows, so a
            # 4096-lane TV with 3 scheduled map lanes launches an 8-row
            # payload, not 4096 rows.  Heap writes land through the same
            # per-element indices in the same stable lane order, so packing
            # the rows is bit-identical.  Residual padding waste (lane rung
            # x domain rung) stays accounted in ``map_lanes``.
            with jax.named_scope("trees.maps"):
                map_ct = carry.map_launches
                map_el = carry.map_elements
                map_ln = carry.map_lanes
                lane_widths = _span_width_ladder(capacity)
                larr = jnp.asarray(lane_widths, jnp.int32)
                for ml in map_launches:
                    mt = program.maps[ml.map_id]
                    if mt.max_domain <= 0:
                        raise EngineError(
                            f"map '{mt.name}' needs max_domain>0 for resident "
                            "(device) execution"
                        )
                    dom = jnp.clip(
                        jnp.asarray(mt.domain(ml.argi), jnp.int32),
                        0, mt.max_domain,
                    )
                    live_dom = jnp.where(ml.where, dom, 0)
                    dmax = live_dom.max().astype(jnp.int32)
                    # all-empty domains skip the launch (and its counters),
                    # exactly as the host MapLauncher does
                    fired = dmax > 0
                    widths = _map_width_ladder(mt.max_domain)
                    warr = jnp.asarray(widths, jnp.int32)
                    bidx = jnp.clip(
                        jnp.searchsorted(warr, dmax, side="left"),
                        0, len(widths) - 1,
                    )
                    lperm, lcount = pack_fn(ml.where)
                    lidx = jnp.clip(
                        jnp.searchsorted(larr, lcount, side="left"),
                        0, len(lane_widths) - 1,
                    )

                    def make_lane_branch(L: int, _ml=ml):
                        def lane_branch(h):
                            rows = lperm[:L]
                            valid = rows >= 0
                            crows = jnp.clip(rows, 0, capacity - 1)
                            w_p = valid & _ml.where[crows]
                            argi_p = _ml.argi[crows]
                            argf_p = _ml.argf[crows]
                            inner = [
                                lambda hh, _D=D: tvm.run_map_payload(
                                    program, hh, _ml.map_id, w_p, argi_p,
                                    argf_p, _D,
                                )
                                for D in widths
                            ]
                            if len(inner) == 1:
                                return inner[0](h)
                            return jax.lax.switch(bidx, inner, h)

                        return lane_branch

                    branches = [lambda h: h] + [
                        make_lane_branch(L) for L in lane_widths
                    ]
                    heap = jax.lax.switch(
                        jnp.where(fired, lidx + 1, 0), branches, heap
                    )
                    fire_i = fired.astype(jnp.int32)
                    map_ct = map_ct + fire_i
                    map_el = _hilo_add(
                        map_el, live_dom.sum().astype(jnp.int32)
                    )
                    map_ln = _hilo_add(
                        map_ln, fire_i * larr[lidx] * warr[bidx]
                    )

            return ResidentCarry(
                state=state, heap=heap, arena=arena,
                jstack=jstack, rstack=rstack, sp=sp, failed=failed,
                failed_stack=failed_stack,
                n_epochs=n_epochs,
                job_epochs=job_epochs,
                job_tasks=job_tasks,
                job_forks=job_forks_acc,
                job_peak=job_peak,
                map_launches=map_ct, map_elements=map_el, map_lanes=map_ln,
                hole_lanes=hole_lanes,
            )

        return body

    def run_chunk(self, carry: ResidentCarry, limit,
                  n_regions: int) -> ResidentCarry:
        """Run the resident loop until every stack drains or the traced
        global-epoch counter reaches ``limit`` — one *chunk* (DESIGN.md
        §10).

        ``limit`` is a **dynamic** argument of one compiled loop, cached per
        (n_regions, capacity, stack_depth) — so host-mux cadence
        (``limit = n_epochs + 1``), chunked residency (``+ K``), and the
        fully-resident wave (``limit`` = the epoch guard) all re-enter the
        same compiled template; nothing retraces between chunks or between
        K choices.  A call whose carry is already drained (or already at
        ``limit``) is a clean no-op: the cond fails on entry and the carry
        comes back unchanged.

        With ``megakernel=True`` the chunk runs through the persistent
        Pallas megakernel (``kernels/epoch_megakernel.py``) instead of a
        ``lax.while_loop``: same traced body and cond, one fused kernel
        holding the carry resident for the whole chunk — bit-identical by
        construction (the while_loop path *is* the kernel's jnp oracle).
        """
        capacity = carry.state.capacity
        depth = carry.jstack.shape[1]
        key = (n_regions, capacity, depth)
        if key not in self._resident_cache:
            body = self.resident_body(capacity, depth)

            def cond(cc: ResidentCarry, lim):
                return (cc.sp > 0).any() & (cc.n_epochs < lim)

            if self.megakernel:
                from ..kernels import epoch_megakernel as mk

                impl = self.megakernel_impl

                @jax.jit
                def loop(c, lim):
                    return mk.epoch_chunk(cond, body, c, lim, impl=impl)

            else:

                @jax.jit
                def loop(c, lim):
                    return jax.lax.while_loop(
                        lambda cc: cond(cc, lim), body, c
                    )

            self._resident_cache[key] = loop
        return self._resident_cache[key](carry, jnp.asarray(limit, jnp.int32))

    def run_resident(self, carry: ResidentCarry, max_epochs: int,
                     n_regions: int) -> ResidentCarry:
        """Run the resident loop to completion: one chunk bounded only by
        the epoch guard — one dispatch for the whole program (or wave)."""
        return self.run_chunk(carry, max_epochs, n_regions)

    def run_chunk_fleet(self, carry: ResidentCarry, limits,
                        n_regions: int, n_shards: int,
                        mesh=None) -> ResidentCarry:
        """Run P independent shard chunks as ONE fused launch (DESIGN.md
        §15).

        ``carry`` is a :class:`ResidentCarry` whose every leaf carries a
        leading fleet axis of size ``n_shards`` — P full TVM + arena +
        stack blocks stacked together; ``limits`` is ``i32[P]``, each
        shard's own dynamic epoch bound (a drained or boundless shard
        passes 0 / its guard and no-ops — the per-shard cond fails on
        entry, bit-identically to never launching it).

        With ``mesh`` (a 1-D ``"fleet"`` device mesh,
        :func:`repro.launch.mesh.make_fleet_mesh`) the chunk runs under
        ``shard_map``: each device owns one shard's block and drives its
        own resident ``while_loop`` — shards advance *independently* to
        their bounds inside the one launch, no cross-shard lockstep.
        ``mesh=None`` is the single-device simulation, asked for by
        name: ``vmap`` over the shard axis, which jax batches as "while
        any shard's cond holds" with finished shards' carries frozen by
        ``select`` — bit-identical per shard, just not device-parallel.
        Under ``vmap`` every ``lax.switch`` of the span ladder becomes a
        select over all its rungs, so one shard runs without it.

        ``megakernel=True`` needs the mesh path (each device runs its
        chunk through the persistent Pallas kernel); with ``mesh=None``
        it raises rather than quietly running the ``while_loop`` oracle.

        Compiled once per (shards, regions, capacity, depth, driver) and
        cached next to the solo chunk templates; ``limits`` stays dynamic
        so K adaptation and per-shard staggering never retrace.
        """
        if self.megakernel and mesh is None:
            raise ValueError(
                "megakernel=True runs each shard's chunk as a Pallas "
                "kernel on its own device; the mesh=None vmap simulation "
                "has no such device.  Use a real 'fleet' mesh or "
                "megakernel=False."
            )
        capacity = int(carry.state.task.shape[-1])
        depth = int(carry.jstack.shape[-1])
        key = ("fleet", n_shards, n_regions, capacity, depth,
               mesh is not None)
        if key not in self._resident_cache:
            body = self.resident_body(capacity, depth)

            def cond(cc: ResidentCarry, lim):
                return (cc.sp > 0).any() & (cc.n_epochs < lim)

            if self.megakernel:
                from ..kernels import epoch_megakernel as mk

                impl = self.megakernel_impl

                def one_shard(c, lim):
                    return mk.epoch_chunk(cond, body, c, lim, impl=impl)

            else:

                def one_shard(c, lim):
                    return jax.lax.while_loop(
                        lambda cc: cond(cc, lim), body, c
                    )

            def shard_fn(c, lim):
                # one shard's block with the fleet axis still present
                # (size 1): squeeze, run the solo chunk, re-expand
                c1 = jax.tree.map(lambda x: x[0], c)
                out = one_shard(c1, lim[0])
                return jax.tree.map(lambda x: x[None], out)

            if mesh is None and n_shards == 1:
                # vmap would batch the span ladder's lax.switch into a
                # select over every rung, so each epoch would step every
                # launch width, the full-TV one included
                loop = jax.jit(shard_fn)
            elif mesh is None:
                loop = jax.jit(jax.vmap(one_shard))
            else:
                spec = jax.tree.map(lambda _: _FLEET_SPEC, carry)

                # the per-shard chunk bodies are closed computations with
                # no cross-shard collectives, so there is no varying-axes
                # bookkeeping to check (and the Pallas fork kernels inside
                # declare none)
                loop = jax.jit(jax.shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=(spec, _FLEET_SPEC),
                    out_specs=spec, check_vma=False,
                ))
            self._resident_cache[key] = loop
        return self._resident_cache[key](
            carry, jnp.asarray(limits, jnp.int32)
        )

    def fleet_chunk_summaries(self, carry: ResidentCarry,
                              n_shards: int) -> List[ChunkSummary]:
        """The fleet boundary readback: ONE ``device_get`` of the stacked
        control scalars, split host-side into per-shard
        :class:`ChunkSummary` views — P shards pay the V_inf transfer
        once per collective chunk, not once each."""
        arena_next = None if carry.arena is None else carry.arena.next
        (sp, failed, failed_stack, n_epochs, job_epochs, job_tasks,
         job_forks, job_peak, m_ct, m_el, m_ln, holes, a_next) = (
            jax.device_get((
                carry.sp, carry.failed, carry.failed_stack, carry.n_epochs,
                carry.job_epochs, carry.job_tasks, carry.job_forks,
                carry.job_peak, carry.map_launches, carry.map_elements,
                carry.map_lanes, carry.hole_lanes, arena_next,
            ))
        )
        return [
            ChunkSummary(
                n_epochs=int(n_epochs[p]),
                sp=np.asarray(sp[p]),
                failed=np.asarray(failed[p]),
                failed_stack=np.asarray(failed_stack[p]),
                job_epochs=np.asarray(job_epochs[p]),
                job_tasks=_hilo_value(job_tasks[p]),
                job_forks=_hilo_value(job_forks[p]),
                job_peak=np.asarray(job_peak[p]),
                map_launches=int(m_ct[p]),
                map_elements=int(_hilo_value(m_el[p])),
                map_lanes=int(_hilo_value(m_ln[p])),
                hole_lanes=int(_hilo_value(holes[p])),
                arena_next=None if a_next is None else np.asarray(a_next[p]),
            )
            for p in range(n_shards)
        ]

    def chunk_summary(self, carry: ResidentCarry) -> ChunkSummary:
        """The chunk-boundary readback: one ``device_get`` of the compact
        control/accounting scalars.  The arena's region cursors ride along
        so a host multiplexer can reseed freed regions between chunks
        without ever fetching the bulk TV/heap state."""
        arena_next = None if carry.arena is None else carry.arena.next
        (sp, failed, failed_stack, n_epochs, job_epochs, job_tasks,
         job_forks, job_peak, m_ct, m_el, m_ln, holes, a_next) = (
            jax.device_get((
                carry.sp, carry.failed, carry.failed_stack, carry.n_epochs,
                carry.job_epochs, carry.job_tasks, carry.job_forks,
                carry.job_peak, carry.map_launches, carry.map_elements,
                carry.map_lanes, carry.hole_lanes, arena_next,
            ))
        )
        return ChunkSummary(
            n_epochs=int(n_epochs),
            sp=np.asarray(sp),
            failed=np.asarray(failed),
            failed_stack=np.asarray(failed_stack),
            job_epochs=np.asarray(job_epochs),
            job_tasks=_hilo_value(job_tasks),
            job_forks=_hilo_value(job_forks),
            job_peak=np.asarray(job_peak),
            map_launches=int(m_ct),
            map_elements=int(_hilo_value(m_el)),
            map_lanes=int(_hilo_value(m_ln)),
            hole_lanes=int(_hilo_value(holes)),
            arena_next=None if a_next is None else np.asarray(a_next),
        )


def log_run_stats(loop: EpochLoop, stats: RunStats, **fields) -> None:
    """Log a finished run's (or wave's) counters with its commit plan."""
    if log.isEnabledFor(logging.INFO):
        flat = {k: v for k, v in stats.as_dict(derived=False).items()
                if not isinstance(v, dict)}
        log.info("run %s", kv(
            **fields, **flat,
            commit_scatter_passes=loop.commit_scatter_passes,
        ))


class HostEngine:
    """Paper-faithful engine: host drives stacks, device runs bulk epochs."""

    def __init__(
        self,
        program: Program,
        capacity: int = 1 << 14,
        collect_stats: bool = True,
        fork_offsets_fn: Optional[Callable] = None,
        donate: bool = False,
        dispatch: Any = MASKED,
        coalesce: bool = True,
        rank_fn: Optional[Callable] = None,
        pack_fn: Optional[Callable] = None,
        stats_factory: Optional[Callable[[], StatsCollector]] = None,
        tracer=None,
        controller=None,
    ):
        self.program = program
        self.capacity = capacity
        self.collect_stats = collect_stats
        self.coalesce = coalesce
        self._stats_factory = stats_factory
        self.loop = EpochLoop(
            program, dispatch,
            rank_fn=rank_fn, pack_fn=pack_fn,
            fork_offsets_fn=fork_offsets_fn, donate=donate,
            tracer=tracer, controller=controller,
        )
        self.tracer = self.loop.tracer
        self.policy = self.loop.policy
        self.controller = self.loop.controller

    def _collector(self) -> StatsCollector:
        if self._stats_factory is not None:
            return self._stats_factory()
        return RunStatsCollector() if self.collect_stats else NullStats()

    @staticmethod
    def _readback(summary, state):
        # the paper's end-of-epoch readback: nextFreeCore, joinScheduled,
        # mapScheduled (§5.2.4) (+ stats counters when enabled)
        return (
            summary.total_forks, summary.join_scheduled,
            summary.map_scheduled, summary.n_active, summary.overflow,
            state.next_free,
        )

    # --------------------------------------------------------------- run
    def run(
        self,
        initial: InitialTask,
        heap_init: Optional[Dict[str, Any]] = None,
        max_epochs: int = 1 << 20,
    ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray, RunStats]:
        """Execute the program to completion.

        Returns (final heap, final TV value array, stats).  The TVM halts
        when the join/NDRange stacks empty (paper §4.3.3).
        """
        program = self.program
        state = tvm.init_state(program, self.capacity, initial)
        heap = program.init_heap(**(heap_init or {}))
        # phase-1 state owned by the CPU, exactly as in the paper (§5.2.2)
        sched = EpochScheduler(coalesce=self.coalesce)
        sched.reset()
        col = self._collector()
        n_epochs = 0  # loop guard lives here, not in the pluggable collector
        tr = self.tracer
        if tr.enabled:
            tr.thread(1, "host-epochs")

        while sched:  # termination predicate: host stacks drained
            if n_epochs >= max_epochs:
                raise EngineError(f"exceeded max_epochs={max_epochs}")
            n_epochs += 1
            d = sched.pop()
            with tr.span(
                "epoch", "host", tid=1,
                cen=d.cen, ranges=d.n_ranges, mode=self.policy.name,
            ) as sargs:
                (state, heap, _summary, fetched, map_launches, launched,
                 by_type, _disp) = self.loop.run_epoch(
                    state, heap, None, d.start, d.count, d.cen, col,
                    self._readback,
                )
                total_forks, join_sched, map_sched, n_active, overflow, nf = (
                    fetched
                )
                if overflow:
                    raise EngineError(
                        f"task vector overflow: capacity={self.capacity}"
                    )
                if join_sched:
                    sched.push_join(d.cen, d.start, d.count)
                sched.push_forked(
                    d.cen + 1, int(nf) - int(total_forks), int(total_forks)
                )

                if map_sched:
                    heap = self.loop.maps.run(map_launches, heap, col)
                # close the feedback loop: the readback's active count vs
                # the *full* frontier width seeds the next epoch's decision
                if self.loop.controller is not None:
                    self.loop.controller.observe(
                        int(n_active), self.loop.last_span_bucket
                    )
                if tr.enabled:
                    dec = self.loop.last_decision
                    sargs.update(
                        launched=launched, active=int(n_active),
                        util=int(n_active) / max(1, launched),
                        **({"mode": dec.mode, "auto_reason": dec.reason}
                           if dec is not None else {}),
                    )

            col.epoch(d.cen, d.n_ranges)
            col.lanes(int(n_active), launched, by_type)
            col.forks(int(total_forks))
            col.tv_peak(int(nf))

        stats = col.result()
        log_run_stats(self.loop, stats, driver="host",
                      program=program.name)
        return heap, state.value, stats


class DeviceEngine:
    """Whole-program engine: stacks + epoch loop inside one XLA program.

    Beyond-paper optimization (the paper's "tighter coupling" prediction):
    zero per-epoch dispatches/transfers on the critical path — the
    :class:`EpochLoop` resident configuration with ``n_regions=1``.
    Dispatch: ``masked`` (span-ladder launches, §11) or ``gather`` (the
    in-loop dense frontier pack, §12 — the skipped lanes of either mode
    land in ``RunStats.hole_lanes_skipped``); ``compacted`` stays
    host-only (per-type launch shapes come from runtime populations).
    Map payloads are sized by the §10 ``max_domain``-capped width ladder
    (residual padding surfaced in ``RunStats.map_lanes_wasted``).
    ``megakernel=True`` routes each resident chunk through the persistent
    Pallas megakernel instead of the ``lax.while_loop`` (§12).
    """

    def __init__(
        self,
        program: Program,
        capacity: int = 1 << 12,
        stack_depth: int = 1 << 10,
        fork_offsets_fn: Optional[Callable] = None,
        dispatch: Any = MASKED,
        megakernel: bool = False,
        megakernel_impl: str = "auto",
        tracer=None,
        controller=None,
    ):
        self.program = program
        self.capacity = capacity
        self.stack_depth = stack_depth
        # a resident loop bakes its dispatch mode into the traced template,
        # so "auto" resolves *here*, once, via the controller (masked on a
        # cold window) — never per epoch inside the while_loop
        dispatch = resolve_resident_dispatch(dispatch, controller, capacity)
        if resolve_policy(dispatch).name not in ("masked", "gather"):
            raise ValueError(_COMPACTED_RESIDENT_MSG)
        self.loop = EpochLoop(program, dispatch,
                              fork_offsets_fn=fork_offsets_fn,
                              megakernel=megakernel,
                              megakernel_impl=megakernel_impl,
                              tracer=tracer)
        self.tracer = self.loop.tracer
        self.policy = self.loop.policy

    def run(
        self,
        initial: InitialTask,
        heap_init: Optional[Dict[str, Any]] = None,
        max_epochs: int = 1 << 16,
    ):
        program = self.program
        state = tvm.init_state(program, self.capacity, initial)
        heap = program.init_heap(**(heap_init or {}))
        jstack, rstack, sp = batched_device_stacks(1, self.stack_depth)
        carry = _fresh_resident_carry(
            state, heap, None, jstack, rstack, sp, n_regions=1
        )
        tr = self.tracer
        if tr.enabled:
            tr.thread(2, "resident")
        # the resident loop is unobservable per epoch by design (no per-epoch
        # readbacks to hang spans on): one "wave" span covers the whole
        # dispatch, and the per-epoch story is reconstructed from the
        # ChunkSummary deltas attached to it after the single readback
        with tr.span(
            "wave", "resident", tid=2,
            driver="device", mode=self.policy.name,
            megakernel=self.loop.megakernel,
        ) as sargs:
            with tr.span("resident_wave", "resident", tid=2):
                out = self.loop.run_resident(carry, max_epochs, n_regions=1)
            # the one scalar transfer of the whole run
            with tr.span("readback", "resident", tid=2):
                s = self.loop.chunk_summary(out)
            if tr.enabled:
                sargs.update(
                    epochs=s.n_epochs, tasks=int(s.job_tasks[0]),
                    holes=s.hole_lanes,
                )
        if s.failed.any():
            raise EngineError("TV capacity or stack depth exhausted")
        if (s.sp > 0).any():
            raise EngineError(f"exceeded max_epochs={max_epochs}")
        stats = RunStats(
            epochs=s.n_epochs, dispatches=1, scalar_transfers=1,
            tasks_executed=int(s.job_tasks[0]),
            lanes_launched=s.n_epochs * self.capacity - s.hole_lanes,
            total_forks=int(s.job_forks[0]),
            map_launches=s.map_launches, map_elements=s.map_elements,
            map_lanes_launched=s.map_lanes,
            hole_lanes_skipped=s.hole_lanes,
        )
        stats.peak_tv_slots = int(s.job_peak[0])
        log_run_stats(self.loop, stats, driver="device",
                      program=program.name)
        return out.heap, out.state.value, stats
