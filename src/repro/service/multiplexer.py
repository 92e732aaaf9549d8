"""Epoch multiplexers: fused multi-tenant driving over one shared TVM.

The paper's "work-together" principle (§3) says critical-path overhead
should be paid by the entire system at once.  A solo ``HostEngine.run``
already pays phase 1 (stack pop + launch) and phase 3 (scalar readback)
once per epoch *for one program*; N concurrent tenants would pay N× that
V_inf cost.  This module extends work-together **across tenants**, at two
levels of residency:

* :func:`fuse_programs` builds one fused :class:`Program` from N tenant
  programs — task tables and map tables concatenate (task ids shifted by a
  per-tenant offset), heap variables are namespaced ``j<k>/name``, and every
  tenant task function runs behind a context shim that translates task ids,
  map ids, and heap names back into the tenant's own vocabulary.  Phase 2
  therefore needs *no new machinery*: the fused program is an ordinary
  ``Program`` and the masked, §5.4-compacted, and §11-gather dispatches
  all apply.

* :class:`EpochMultiplexer` is the *host-loop* driver (an
  :class:`~repro.core.engine.EpochLoop` configuration): each global epoch it
  pops every ready job's frontier (``MuxPopPolicy`` selects the gang), fuses
  the popped ranges into one launch with a per-lane epoch-number vector, and
  reads back one :class:`~repro.core.tvm.MuxEpochSummary` for the whole
  fleet — V_inf paid once per *global epoch*.  Because the host sees every
  epoch, it supports streaming completion, mid-flight region reuse
  (including structurally-equal program templates, see
  ``Program.structural_hash``), gang policies, and the compacted and
  gather dispatches (the latter packs the fused span's scheduled lanes
  into one dense frontier, so cross-region hole lanes are never launched
  — DESIGN.md §11).

* :class:`DeviceMultiplexer` is the *chunked resident* driver (DESIGN.md
  §9–10): the admitted wave runs inside a ``lax.while_loop`` with
  per-region scheduler stacks (``batched_device_stacks``) and the
  :class:`~repro.core.tvm.JobArena` region cursors carried on device, for
  at most ``chunk`` (K) epochs per loop invocation.  At each chunk
  boundary the host fetches one compact
  :class:`~repro.core.engine.ChunkSummary` — so a wave of E epochs costs
  ⌈E/K⌉ dispatches + readbacks, and between chunks the host streams
  completions of drained regions and reseeds freed regions with queued
  jobs (``Program.structural_hash`` reuse, no retrace).  ``chunk=None``
  is the fully-resident endpoint (K=∞, the PR-3 behaviour: O(1) V_inf,
  host blind until the wave drains); ``chunk=1`` is host-mux cadence.
  The masked and gather dispatches are traceable on this driver (gather
  packs the scheduled lanes into a fixed-shape in-loop frontier —
  DESIGN.md §12); ``megakernel=True`` swaps the chunk's ``while_loop``
  for the persistent Pallas epoch megakernel.

Per-job results are bit-identical to the solo runs under both drivers, at
every K.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..core import tvm
from ..core.engine import (
    ChunkSummary,
    EpochLoop,
    _COMPACTED_RESIDENT_MSG,
    _HILO_BASE,
    _fresh_resident_carry,
    _hilo_value,
    resolve_resident_dispatch,
)
from ..control.controller import ChunkController
from ..core.program import HeapVar, MapType, Program, TaskType, pack_args
from ..obs.trace import NULL_TRACER
from ..core.scheduler import (
    EpochScheduler,
    NullStats,
    RunStats,
    RunStatsCollector,
    StatsCollector,
    batched_device_stacks,
    load_region_stacks,
    reseed_region_stacks,
    resolve_mux_policy,
    resolve_policy,
)
from .jobs import (
    Job,
    JobFailure,
    JobHandle,
    JobResult,
    JobStats,
    JobStatus,
    RegionCheckpoint,
    check_fleet_dtype,
    validate_job,
)


# --------------------------------------------------------------------------
# Tenant context shims: run a tenant task body against the fused program
# --------------------------------------------------------------------------
class _TenantEpochCtx:
    """EpochCtx view in the tenant's own vocabulary.

    Delegates every read/effect to the fused :class:`EpochCtx`, translating
    task names/ids by the tenant's task-table offset, map names/ids by its
    map-table offset, and heap names by its ``j<k>/`` namespace prefix.
    """

    __slots__ = ("_ctx", "_sub", "_task_off", "_map_off", "_prefix")

    def __init__(self, ctx, sub: Program, task_off: int, map_off: int,
                 prefix: str):
        self._ctx = ctx
        self._sub = sub
        self._task_off = task_off
        self._map_off = map_off
        self._prefix = prefix

    # reads -----------------------------------------------------------------
    def argi(self, k: int):
        return self._ctx.argi(k)

    def argf(self, k: int):
        return self._ctx.argf(k)

    @property
    def slot(self):
        return self._ctx.slot

    @property
    def child_count(self):
        return self._ctx.child_count

    def child_values(self, n: int):
        # slice the fused value rows down to the tenant's own width so a
        # width-w program sees exactly the (n, w) a solo run returns
        return self._ctx.child_values(n)[:, : self._sub.value_width]

    def read(self, name: str, index):
        return self._ctx.read(self._prefix + name, index)

    # effects ---------------------------------------------------------------
    def _code(self, task):
        if isinstance(task, str):
            return self._task_off + self._sub.task_id(task)
        return self._task_off + task

    def fork(self, task, argi=(), argf=(), where=True):
        self._ctx.fork(self._code(task), argi=argi, argf=argf, where=where)

    def join(self, task, argi=(), argf=(), where=True):
        self._ctx.join(self._code(task), argi=argi, argf=argf, where=where)

    def emit(self, value, where=True):
        # enforce the tenant's own value width (the fused width may be
        # larger; a solo run would reject the overflow, so must we)
        v = jnp.asarray(value).reshape(-1)
        if v.shape[0] > self._sub.value_width:
            raise ValueError("emit value wider than program.value_width")
        self._ctx.emit(value, where=where)

    def write(self, name: str, index, value, op: str = "set", where=True):
        self._ctx.write(self._prefix + name, index, value, op=op, where=where)

    def map(self, map_fn, argi=(), argf=(), where=True):
        mid = (
            self._sub.map_id(map_fn)
            if isinstance(map_fn, str)
            else int(map_fn)
        )
        self._ctx.map(self._map_off + mid, argi=argi, argf=argf, where=where)


class _TenantMapCtx:
    """MapCtx view with the tenant's heap namespace."""

    __slots__ = ("_ctx", "_prefix")

    def __init__(self, ctx, prefix: str):
        self._ctx = ctx
        self._prefix = prefix

    def argi(self, k: int):
        return self._ctx.argi(k)

    def argf(self, k: int):
        return self._ctx.argf(k)

    @property
    def eid(self):
        return self._ctx.eid

    def read(self, name: str, index):
        return self._ctx.read(self._prefix + name, index)

    def write(self, name: str, index, value, op: str = "set", where=True):
        self._ctx.write(self._prefix + name, index, value, op=op, where=where)


def _wrap_task(fn, sub: Program, task_off: int, map_off: int, prefix: str):
    def wrapped(ctx, _fn=fn):
        _fn(_TenantEpochCtx(ctx, sub, task_off, map_off, prefix))

    return wrapped


def _wrap_map(fn, prefix: str):
    def wrapped(mctx, _fn=fn):
        _fn(_TenantMapCtx(mctx, prefix))

    return wrapped


# --------------------------------------------------------------------------
# Program fusion
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TenantSlot:
    """One tenant's compile-time contribution to the fused program, plus its
    slot region in the shared TV.  The region is sized by the job's quota at
    fuse time; a later tenant re-admitted into this region may use less."""

    index: int
    program: Program
    task_offset: int
    map_offset: int
    prefix: str
    base: int
    quota: int

    @property
    def end(self) -> int:
        return self.base + self.quota


def fuse_programs(
    programs: Sequence[Program], quotas: Sequence[int]
) -> Tuple[Program, List[TenantSlot]]:
    """Concatenate N tenant programs into one fused :class:`Program`.

    Argument-register widths and the value width are the fleet maxima (a
    tenant's own args/emits occupy a prefix; the padding columns stay zero,
    so the tenant-visible slice is bit-identical to solo).  The value dtype
    must be uniform across the fleet (:func:`check_fleet_dtype`).
    """
    value_dtype = check_fleet_dtype(programs)
    tasks: List[TaskType] = []
    maps: List[MapType] = []
    heap: List[HeapVar] = []
    slots: List[TenantSlot] = []
    base = 0
    for j, (p, q) in enumerate(zip(programs, quotas)):
        prefix = f"j{j}/"
        slot = TenantSlot(
            index=j, program=p, task_offset=len(tasks),
            map_offset=len(maps), prefix=prefix, base=base, quota=int(q),
        )
        for t in p.tasks:
            tasks.append(
                TaskType(
                    prefix + t.name,
                    _wrap_task(t.fn, p, slot.task_offset, slot.map_offset,
                               prefix),
                )
            )
        for m in p.maps:
            maps.append(
                MapType(
                    prefix + m.name,
                    _wrap_map(m.fn, prefix),
                    domain=m.domain,
                    max_domain=m.max_domain,
                )
            )
        for hv in p.heap:
            heap.append(HeapVar(prefix + hv.name, hv.shape, hv.dtype))
        slots.append(slot)
        base += int(q)

    fused = Program(
        name="mux[" + "+".join(p.name for p in programs) + "]",
        tasks=tuple(tasks),
        n_arg_i=max(p.n_arg_i for p in programs),
        n_arg_f=max(p.n_arg_f for p in programs),
        value_width=max(p.value_width for p in programs),
        value_dtype=value_dtype,
        maps=tuple(maps),
        heap=tuple(heap),
    )
    return fused, slots


# --------------------------------------------------------------------------
# Shared fleet plumbing
# --------------------------------------------------------------------------
@dataclasses.dataclass
class _Region:
    """Runtime state of one slot region: the tenant currently in it (if
    any), its scheduler stacks (host driver only), and its solo-comparable
    stats."""

    slot: TenantSlot
    handle: Optional[JobHandle] = None
    sched: Optional[EpochScheduler] = None
    stats: Optional[JobStats] = None
    active_quota: int = 0

    @property
    def running(self) -> bool:
        return (
            self.handle is not None
            and self.handle.status is JobStatus.RUNNING
        )


class _FleetBase:
    """Shared multi-tenant plumbing: program fusion, the shared TVM state +
    :class:`~repro.core.tvm.JobArena`, per-region bookkeeping, and result
    extraction.  The host and resident drivers differ only in *how* they
    drive epochs; everything either one reads or writes lives here."""

    tracer = NULL_TRACER  # each driver sets its own in ``__init__``

    def __init__(
        self,
        handles: Sequence[JobHandle],
        capacity: Optional[int] = None,
        coalesce: bool = True,
        collect_stats: bool = True,
        stats_factory=None,
        template=None,
    ):
        if not handles:
            raise ValueError(f"{type(self).__name__} needs at least one job")
        # a ``None`` entry is a *vacant* slot region: the sharded fleet
        # (distributed/fleet.py) builds every shard with the same slot
        # layout and seats tenants through the admit/reseed path, so a
        # shard may start with some (or all) regions empty.  Vacancy
        # requires a template — the fused program cannot be built from
        # absent jobs.
        jobs = [h.job for h in handles if h is not None]
        if len(jobs) != len(handles) and template is None:
            raise ValueError(
                "vacant wave slots (handle=None) require a wave template: "
                "the fused program cannot be derived from absent jobs"
            )
        quota_total = (
            sum(s.quota for s in template.slots) if template is not None
            else sum(j.quota for j in jobs)
        )
        self.capacity = int(capacity) if capacity else quota_total
        if quota_total > self.capacity:
            raise ValueError(
                f"sum of job quotas ({quota_total}) exceeds TV capacity "
                f"({self.capacity})"
            )
        for j in jobs:
            validate_job(j, self.capacity)
        self.coalesce = coalesce
        self._stats_factory = stats_factory
        self._collect_stats = collect_stats

        if template is not None:
            # wave-template reuse (service/jobs.py WaveTemplateCache): this
            # wave's members are structurally equal to the template's
            # fuse-time members, so the fused program — and every compiled
            # step/loop traced against it — applies verbatim; only runtime
            # state (TV, heap, stacks) is rebuilt below
            if len(handles) != len(template.slots) or any(
                h is not None and h.job.quota != s.quota
                for h, s in zip(handles, template.slots)
            ):
                raise ValueError(
                    "wave template quota layout does not match the wave"
                )
            self.program = template.program
            self._slots = list(template.slots)
        else:
            self.program, self._slots = fuse_programs(
                [j.program for j in jobs], [j.quota for j in jobs]
            )
        self._col = self._collector()
        # (region index, handle) pairs whose TV image must be restored from
        # a RegionCheckpoint once the driver's runtime state exists — the
        # host driver restores at construction, the resident driver at its
        # first chunk (the carry is built lazily)
        self._restore_pending: List[Tuple[int, JobHandle]] = []
        self._init_fleet(handles)

    def _collector(self) -> StatsCollector:
        if self._stats_factory is not None:
            return self._stats_factory()
        return RunStatsCollector() if self._collect_stats else NullStats()

    def _init_fleet(self, handles: Sequence[JobHandle]) -> None:
        """Build the shared TVM state, arena, heap, and per-job schedulers."""
        fused, C = self.program, self.capacity
        J = len(self._slots)
        npdtype = jnp.dtype(fused.value_dtype)
        task = np.zeros(C, np.int32)
        argi = np.zeros((C, fused.n_arg_i), np.int32)
        argf = np.zeros((C, fused.n_arg_f), np.float32)
        epoch = np.zeros(C, np.int32)
        value = np.zeros((C, fused.value_width), npdtype)
        slot_job = np.full(C, J, np.int32)

        self._regions: List[_Region] = []
        self._heap: Dict[str, jnp.ndarray] = {}
        for slot, h in zip(self._slots, handles):
            slot_job[slot.base : slot.end] = slot.index
            if h is None or h.checkpoint is not None:
                # vacant region: TV slots stay zeroed (epoch 0 matches no
                # frontier), the tenant heap gets its declared-default
                # arrays so the fused program's traced steps see every
                # key; a tenant seats later via the admit/reseed path.
                # A *checkpointed* handle (preempted elsewhere, resuming
                # in this wave) is seated the same lazy way — its region
                # image restores through ``_restore_region`` once the
                # driver's runtime state exists, never by reseeding.
                for k, v in slot.program.init_heap().items():
                    self._heap[slot.prefix + k] = v
                self._regions.append(_Region(slot=slot))
                if h is not None:
                    self._restore_pending.append((slot.index, h))
                continue
            job = h.job
            tid = slot.task_offset + slot.program.task_id(job.initial.task)
            ai, af = pack_args(fused, job.initial.argi, job.initial.argf)
            task[slot.base] = tid
            argi[slot.base] = ai
            argf[slot.base] = af
            epoch[slot.base] = 1
            for k, v in slot.program.init_heap(**dict(job.heap_init)).items():
                self._heap[slot.prefix + k] = v
            sched = EpochScheduler(coalesce=self.coalesce)
            sched.reset(cen=1, start=slot.base, count=1)
            h.mark_running()
            self._regions.append(
                _Region(
                    slot=slot, handle=h, sched=sched, stats=JobStats(),
                    active_quota=job.quota,
                )
            )

        self._state = tvm.TVMState(
            task=jnp.asarray(task),
            argi=jnp.asarray(argi),
            argf=jnp.asarray(argf),
            epoch=jnp.asarray(epoch),
            value=jnp.asarray(value),
            child_base=jnp.zeros((C,), jnp.int32),
            child_count=jnp.zeros((C,), jnp.int32),
            next_free=jnp.asarray(max(s.base for s in self._slots) + 1,
                                  jnp.int32),
        )
        self._arena = tvm.JobArena(
            slot_job=jnp.asarray(slot_job),
            base=jnp.asarray([s.base for s in self._slots], jnp.int32),
            end=jnp.asarray([s.end for s in self._slots], jnp.int32),
            next=jnp.asarray([s.base + 1 for s in self._slots], jnp.int32),
        )

    @property
    def loop(self) -> EpochLoop:
        """The driver core (its steps and, resident, the chunk template)."""
        return self._loop

    @property
    def live(self) -> bool:
        return any(r.running for r in self._regions)

    def stats(self) -> RunStats:
        """Fleet-level stats: V_inf terms counted per fused dispatch."""
        return self._col.result()

    # ------------------------------------------------- streaming admission
    def admit(self, handle: JobHandle) -> bool:
        """Seed a queued job into a freed region, mid-flight.

        A region can be reused by any job whose program is *structurally
        equal* to the region's fused-in template (``Program.structural_hash``
        — same task/map/heap tables and task bytecode; the phase-2 trace is
        identical, so nothing retraces).  The new job may carry its own
        initial task, heap init, and a quota up to the region size.  Returns
        False when the driver is not currently admitting (see
        ``_admits_midflight``) or no compatible free region exists.

        The scan is shared by both drivers; only *how* a region is reseeded
        (``_seed_region``) differs — host scheduler stacks vs the resident
        carry's device stacks.
        """
        if not self._admits_midflight():
            return False
        job = handle.job
        for r in self._regions:
            if r.handle is not None:
                continue
            s = r.slot
            if job.quota > s.quota:
                continue
            if s.program is not job.program and (
                s.program.structural_hash() != job.program.structural_hash()
            ):
                continue
            self._seat(r, handle)
            return True
        return False

    def _seat(self, r: _Region, handle: JobHandle) -> None:
        """Seat a job in a free region: restore its checkpoint, or seed it
        fresh — one ``reseed`` span either way."""
        with self.tracer.span(
            "reseed", job_id=handle.job_id, quota=handle.job.quota,
            region=r.slot.index, restore=handle.checkpoint is not None,
        ):
            if handle.checkpoint is not None:
                self._restore_region(r, handle)
            else:
                self._seed_region(r, handle)

    def _admits_midflight(self) -> bool:
        return True

    def _seed_region(self, r: _Region, handle: JobHandle) -> None:
        raise NotImplementedError

    # --------------------------------------------------------- preemption
    def preempt(self, handle: JobHandle) -> bool:
        """Evict a RUNNING job at the current boundary (DESIGN.md §16).

        The job's region — TV columns, tenant heap, arena cursor, stack
        entries, accumulators — lifts into an engine-agnostic
        :class:`~repro.service.jobs.RegionCheckpoint` on the handle, the
        region is vacated (free for admission), and the handle moves to
        PREEMPTED.  Re-admitting the handle (same wave later, or any other
        wave whose layout fits) restores the image through
        ``_restore_region`` and the job continues bit-identically to an
        uninterrupted run.  Returns False when the driver is not at a
        yield point (``_admits_midflight`` — e.g. a fully resident wave)
        or the handle is not running here.
        """
        if not self._admits_midflight():
            return False
        for j, r in enumerate(self._regions):
            if r.handle is handle and r.running:
                cp = self._capture_region(j)
                self._release(j)
                self._vacate(j)
                handle.mark_preempted(cp)
                return True
        return False

    def running_handles(self) -> List[JobHandle]:
        """The handles currently seated in this wave's regions."""
        return [r.handle for r in self._regions if r.running]

    def _capture_region(self, j: int) -> RegionCheckpoint:
        raise NotImplementedError

    def _restore_region(self, r: _Region, handle: JobHandle) -> None:
        raise NotImplementedError

    def _vacate(self, j: int) -> None:
        """Driver-specific cleanup after a region's tenant was captured
        (the host driver needs none: with the scheduler gone, the stale
        TV content is unreachable — no pop ever targets the region)."""

    def _capture_tv(self, r: _Region):
        """The TVM half of a capture, shared by both drivers: the job's
        TV columns (position-dependent ones region-relative), its tenant
        heap (namespace stripped), and the arena cursor offset.

        Task codes are stored relative to the slot's fuse-time task-table
        offset and ``child_base`` relative to the region base — the
        restore target may be a different slot of a different fused
        program.  Lanes never written (epoch 0 / no children) are stored
        as zeros rather than translated: they are inert either way (the
        TMS epoch check skips them) and zeros keep the image independent
        of the source wave's offsets.
        """
        s = r.slot
        sub = s.program
        q = r.active_quota
        tgt = slice(s.base, s.base + q)
        epoch = np.asarray(self._state.epoch[tgt], np.int32)
        task = np.asarray(self._state.task[tgt], np.int32)
        child_count = np.asarray(self._state.child_count[tgt], np.int32)
        child_base = np.asarray(self._state.child_base[tgt], np.int32)
        tv = {
            "epoch": epoch,
            "task_rel": np.where(
                epoch > 0, task - s.task_offset, 0
            ).astype(np.int32),
            "argi": np.asarray(
                self._state.argi[tgt, : sub.n_arg_i], np.int32
            ),
            "argf": np.asarray(
                self._state.argf[tgt, : sub.n_arg_f], np.float32
            ),
            "value": np.asarray(
                self._state.value[tgt, : sub.value_width]
            ),
            "child_count": child_count,
            "child_base_rel": np.where(
                child_count > 0, child_base - s.base, 0
            ).astype(np.int32),
        }
        heap = {hv.name: self._heap[s.prefix + hv.name] for hv in sub.heap}
        next_off = int(np.asarray(self._arena.next)[s.index]) - s.base
        return tv, heap, next_off

    def _restore_state(self, state: tvm.TVMState, slot: TenantSlot,
                       cp: RegionCheckpoint) -> tvm.TVMState:
        """The TVM half of a restore: clear the slot region (as
        ``_seed_state`` does) and write the checkpoint image shifted to
        this slot's base and task-table offset, padded to this fused
        program's argument/value widths (the tenant's own columns are a
        prefix; padding stays zero, exactly the fuse-time layout)."""
        fused = self.program
        sl = slice(slot.base, slot.end)
        q = cp.quota
        tgt = slice(slot.base, slot.base + q)
        epoch = cp.tv["epoch"]
        task = np.where(
            epoch > 0, cp.tv["task_rel"] + slot.task_offset, 0
        ).astype(np.int32)
        cb = np.where(
            cp.tv["child_count"] > 0,
            cp.tv["child_base_rel"] + slot.base, 0,
        ).astype(np.int32)
        argi = np.zeros((q, fused.n_arg_i), np.int32)
        argi[:, : cp.tv["argi"].shape[1]] = cp.tv["argi"]
        argf = np.zeros((q, fused.n_arg_f), np.float32)
        argf[:, : cp.tv["argf"].shape[1]] = cp.tv["argf"]
        value = np.zeros((q, fused.value_width), jnp.dtype(fused.value_dtype))
        value[:, : cp.tv["value"].shape[1]] = cp.tv["value"]
        return tvm.TVMState(
            task=state.task.at[sl].set(0).at[tgt].set(jnp.asarray(task)),
            argi=state.argi.at[sl].set(0).at[tgt].set(jnp.asarray(argi)),
            argf=state.argf.at[sl].set(0.0).at[tgt].set(jnp.asarray(argf)),
            epoch=state.epoch.at[sl].set(0).at[tgt].set(jnp.asarray(epoch)),
            value=state.value.at[sl].set(0).at[tgt].set(jnp.asarray(value)),
            child_base=state.child_base.at[sl].set(0).at[tgt].set(
                jnp.asarray(cb)),
            child_count=state.child_count.at[sl].set(0).at[tgt].set(
                jnp.asarray(cp.tv["child_count"])),
            next_free=state.next_free,
        )

    def _seed_state(self, state: tvm.TVMState, slot: TenantSlot,
                    job: Job) -> tvm.TVMState:
        """Clear a freed slot region and seed the new tenant's root task —
        the TVM half of region reuse, shared by both drivers."""
        sub = slot.program
        sl = slice(slot.base, slot.end)
        tid = slot.task_offset + sub.task_id(job.initial.task)
        ai, af = pack_args(self.program, job.initial.argi, job.initial.argf)
        return tvm.TVMState(
            task=state.task.at[sl].set(0).at[slot.base].set(tid),
            argi=state.argi.at[sl].set(0).at[slot.base].set(jnp.asarray(ai)),
            argf=state.argf.at[sl].set(0.0).at[slot.base].set(
                jnp.asarray(af)),
            epoch=state.epoch.at[sl].set(0).at[slot.base].set(1),
            value=state.value.at[sl].set(0),
            child_base=state.child_base.at[sl].set(0),
            child_count=state.child_count.at[sl].set(0),
            next_free=state.next_free,
        )

    # ------------------------------------------------- completion / release
    def _finalize(self, j: int) -> JobHandle:
        """Extract the region's solo-equivalent result; free the region."""
        r = self._regions[j]
        s = r.slot
        sub = s.program
        st = r.stats
        self._col.job_finished(st.epochs, st.tasks_executed)
        with self.tracer.span("finalize", job_id=r.handle.job_id, region=j,
                              epochs=st.epochs, tasks=st.tasks_executed,
                              peak_tv_slots=st.peak_tv_slots):
            value = self._state.value[
                s.base : s.base + r.active_quota, : sub.value_width
            ]
            heap = {
                hv.name: self._heap[s.prefix + hv.name] for hv in sub.heap
            }
            r.handle.result = JobResult(heap=heap, value=value, stats=st)
            r.handle.status = JobStatus.DONE
            r.handle.mark_finished()
            return self._release(j)

    def _fail(self, j: int, reason: Optional[str] = None) -> JobHandle:
        r = self._regions[j]
        r.handle.error = JobFailure(
            reason
            or f"job {r.handle.job.name!r} overflowed its region: "
               f"quota={r.active_quota}"
        )
        r.handle.status = JobStatus.FAILED
        r.handle.mark_finished()
        return self._release(j)

    def _release(self, j: int) -> JobHandle:
        r = self._regions[j]
        h = r.handle
        r.handle = None
        r.sched = None
        r.stats = None
        r.active_quota = 0
        return h


# --------------------------------------------------------------------------
# Host-loop driver
# --------------------------------------------------------------------------
class EpochMultiplexer(_FleetBase):
    """Co-schedule a fleet of jobs inside one shared TVM (host loop).

    Each global epoch: select a gang of ready jobs (``pop_policy``), pop one
    dispatch from each job's own scheduler, fuse the ranges into a single
    launch over their covering span with a per-lane epoch-number vector
    (lanes outside every popped range carry 0 and stay inactive), commit
    with the :class:`~repro.core.tvm.JobArena` segmented allocator, and read
    back one fused :class:`~repro.core.tvm.MuxEpochSummary`.  Dispatch +
    readback are counted once per global epoch — the fleet's V_inf — while
    each job's scheduler sees exactly the solo sequence of pops and pushes.
    """

    def __init__(
        self,
        handles: Sequence[JobHandle],
        capacity: Optional[int] = None,
        dispatch: Any = "masked",
        coalesce: bool = True,
        pop_policy: Any = "fuse_all",
        gang: int = 0,
        collect_stats: bool = True,
        stats_factory=None,
        rank_fn=None,
        pack_fn=None,
        seg_offsets_fn=None,
        tracer=None,
        controller=None,
    ):
        super().__init__(
            handles, capacity=capacity, coalesce=coalesce,
            collect_stats=collect_stats, stats_factory=stats_factory,
        )
        self.pop_policy = resolve_mux_policy(pop_policy, gang)
        self._loop = EpochLoop(
            self.program, dispatch,
            rank_fn=rank_fn, pack_fn=pack_fn, seg_offsets_fn=seg_offsets_fn,
            # fused fleets have many task types but type-homogeneous epochs
            # stay common, so idle types skip via lax.cond
            skip_idle_types=True,
            tracer=tracer, controller=controller,
        )
        self.tracer = self._loop.tracer
        self.policy = self._loop.policy
        self.controller = self._loop.controller
        self._rotor = 0
        self._global_epochs = 0
        # resume preempted members: the host driver's runtime state is
        # fully built by now, so checkpointed wave members restore here
        for j, h in self._restore_pending:
            self._seat(self._regions[j], h)
        self._restore_pending = []

    @staticmethod
    def _readback(summary, state):
        # one fused readback for the whole fleet (the cross-tenant V_inf win)
        return (
            summary.job_forks, summary.job_join, summary.job_active,
            summary.job_overflow, summary.job_next, summary.map_scheduled,
        )

    # ------------------------------------------------------------ stepping
    def step(self) -> List[JobHandle]:
        """Run one fused global epoch; return handles that completed."""
        ready = [
            j for j, r in enumerate(self._regions) if r.running and r.sched
        ]
        if not ready:
            return []
        depths = [len(self._regions[j].sched) for j in ready]
        chosen = self.pop_policy.select(ready, depths, self._rotor)
        self._rotor += 1
        self._global_epochs += 1
        col = self._col

        pops = {j: self._regions[j].sched.pop() for j in chosen}
        lo = min(d.start for d in pops.values())
        hi = max(d.start + d.count for d in pops.values())
        cen_np = np.zeros(hi - lo, np.int32)
        for d in pops.values():
            cen_np[d.start - lo : d.start - lo + d.count] = d.cen

        tr = self.tracer
        if tr.enabled:
            tr.thread(1, "host-epochs")
        with tr.span(
            "epoch", "host", tid=1,
            epoch=self._global_epochs, jobs=len(chosen), span=hi - lo,
            mode=self.policy.name,
        ) as sargs:
            (self._state, self._heap, summary, fetched, map_launches,
             launched, by_type, shared_dispatches) = self._loop.run_epoch(
                self._state, self._heap, self._arena, lo, hi - lo, cen_np,
                col, self._readback,
            )
            job_forks, job_join, job_active, job_overflow, job_next, \
                map_sched = fetched
            # dispatch="auto" feedback: the fused readback's active count
            # vs the full frontier width seeds the next epoch's decision
            if self._loop.controller is not None:
                self._loop.controller.observe(
                    int(job_active.sum()), self._loop.last_span_bucket
                )
            if tr.enabled:
                n_act = int(job_active.sum())
                dec = self._loop.last_decision
                sargs.update(
                    launched=launched, active=n_act,
                    util=n_act / max(1, launched),
                    **({"mode": dec.mode, "auto_reason": dec.reason}
                       if dec is not None else {}),
                )
        # the region cursors advance on device; only the readback copy above
        # crosses to the host
        self._arena = dataclasses.replace(self._arena, next=summary.job_next)

        done: List[JobHandle] = []
        for j in chosen:
            r = self._regions[j]
            d = pops[j]
            if bool(job_overflow[j]):
                done.append(self._fail(j))
                continue
            if bool(job_join[j]):
                r.sched.push_join(d.cen, d.start, d.count)
            forks = int(job_forks[j])
            r.sched.push_forked(d.cen + 1, int(job_next[j]) - forks, forks)
            st = r.stats
            st.epochs += 1
            st.tasks_executed += int(job_active[j])
            st.total_forks += forks
            st.peak_tv_slots = max(
                st.peak_tv_slots, int(job_next[j]) - r.slot.base
            )
            st.shared_dispatches += shared_dispatches
            st.shared_transfers += shared_dispatches

        if bool(map_sched):
            self._heap = self._loop.maps.run(map_launches, self._heap, col)

        col.epoch(self._global_epochs,
                  sum(d.n_ranges for d in pops.values()))
        col.lanes(int(job_active.sum()), launched, by_type)
        col.forks(int(job_forks.sum()))
        col.tv_peak(int(job_next.max()))

        for j in chosen:
            r = self._regions[j]
            if r.running and not r.sched:
                done.append(self._finalize(j))
        return done

    def run(self, max_epochs: int = 1 << 20) -> List[JobHandle]:
        """Drive every admitted job to completion; return finished handles."""
        out: List[JobHandle] = []
        while self.live:
            if self._global_epochs >= max_epochs:
                raise RuntimeError(f"exceeded max_epochs={max_epochs}")
            out.extend(self.step())
        return out

    # ------------------------------------------------- streaming admission
    def _seed_region(self, r: _Region, handle: JobHandle) -> None:
        """Clear a freed region and seed the new tenant's root task."""
        job = handle.job
        s = r.slot
        self._state = self._seed_state(self._state, s, job)
        self._arena = tvm.arena_reset_region(
            self._arena, s.index, s.base, job.quota
        )
        for k, v in s.program.init_heap(**dict(job.heap_init)).items():
            self._heap[s.prefix + k] = v
        sched = EpochScheduler(coalesce=self.coalesce)
        sched.reset(cen=1, start=s.base, count=1)
        r.handle = handle
        r.sched = sched
        r.stats = JobStats()
        r.active_quota = job.quota
        handle.mark_running()

    # --------------------------------------------------------- preemption
    def _capture_region(self, j: int) -> RegionCheckpoint:
        r = self._regions[j]
        tv, heap, next_off = self._capture_tv(r)
        cens, ranges = r.sched.export_stack()
        ranges = ranges.copy()
        if ranges.size:
            ranges[:, 0] -= r.slot.base
        st = dataclasses.replace(r.stats)
        return RegionCheckpoint(
            structural_hash=r.slot.program.structural_hash(),
            quota=r.active_quota,
            tv=tv, heap=heap, arena_next_off=next_off,
            sp=len(cens), jstack=cens, rstack=ranges,
            job_epochs=st.epochs, job_tasks=st.tasks_executed,
            job_forks=st.total_forks, job_peak=st.peak_tv_slots,
            stats=st,
        )

    def _restore_region(self, r: _Region, handle: JobHandle) -> None:
        """Seat a preempted job's checkpoint into a freed region: the TV
        image shifts to this region's base/offsets, the arena cursor
        resumes where it left off, and the scheduler stacks reload — the
        dual of ``_seed_region`` with the checkpoint as the seed."""
        cp = handle.checkpoint
        s = r.slot
        self._state = self._restore_state(self._state, s, cp)
        arena = tvm.arena_reset_region(self._arena, s.index, s.base, cp.quota)
        self._arena = dataclasses.replace(
            arena, next=arena.next.at[s.index].set(s.base + cp.arena_next_off)
        )
        for k, v in cp.heap.items():
            self._heap[s.prefix + k] = v
        sched = EpochScheduler(coalesce=self.coalesce)
        ranges = np.asarray(cp.rstack, np.int32).reshape(-1, 2).copy()
        if ranges.size:
            ranges[:, 0] += s.base
        sched.load_stack(cp.jstack, ranges)
        r.handle = handle
        r.sched = sched
        r.stats = (
            cp.stats if cp.stats is not None
            else JobStats(
                epochs=cp.job_epochs, tasks_executed=cp.job_tasks,
                total_forks=cp.job_forks, peak_tv_slots=cp.job_peak,
            )
        )
        r.active_quota = cp.quota
        handle.checkpoint = None
        handle.mark_running()


# --------------------------------------------------------------------------
# Chunked resident driver
# --------------------------------------------------------------------------
class _ChunkLedger:
    """Fleet totals already credited to the stats collector.

    Each chunk boundary accounts only its *delta* against these, so
    re-reading the carry's monotone accumulators can never double-count and
    an empty trailing chunk credits nothing.  Per-region entries zero when
    a region is reseeded with a new tenant (the carry's accumulators zero
    at the same moment).
    """

    def __init__(self, n_regions: int):
        self.epochs = 0
        self.job_epochs = np.zeros(n_regions, np.int64)
        self.job_tasks = np.zeros(n_regions, np.int64)
        self.job_forks = np.zeros(n_regions, np.int64)
        self.map_launches = 0
        self.map_elements = 0
        self.map_lanes = 0
        self.hole_lanes = 0


class DeviceMultiplexer(_FleetBase):
    """Chunked device-resident wave execution (DESIGN.md §9–10).

    The admitted fleet runs inside a ``lax.while_loop`` — per-region
    scheduler stacks on device (``batched_device_stacks``), the
    :class:`~repro.core.tvm.JobArena` region cursors and per-region
    trailing reclamation riding the loop carry, every region's pop fused
    into one per-lane epoch-number vector per iteration — for at most
    ``chunk`` (K) epochs per invocation.  At each chunk boundary the host
    fetches one compact :class:`~repro.core.engine.ChunkSummary`; a wave of
    E epochs therefore pays ⌈E/K⌉ dispatches + readbacks, and between
    chunks the host:

      * **streams completions** — regions whose stack drained surface
        immediately, not when the whole wave ends;
      * **reseeds freed regions** — ``admit`` seats a structurally-equal
        queued job into the live carry (TV slots, heap, arena cursors,
        stack row, accumulators), and the re-entered loop simply sees one
        more live region — no retrace, the compiled chunk template is
        reused verbatim.

    ``chunk=None`` is the fully-resident endpoint (K=∞): one chunk for the
    whole wave, O(1) V_inf, the host blind until it drains — and ``admit``
    refuses, because there are no boundaries to admit at.  ``chunk=1`` is
    host-mux readback cadence.  Masked and gather dispatches only
    (resident launch shapes are fixed at trace time — gather packs into a
    fixed-shape segmented frontier, DESIGN.md §12; compacted sizes
    launches from runtime populations and stays host-only); every live
    region pops each global epoch (``fuse_all``).  ``megakernel=True``
    runs each chunk as one persistent Pallas kernel
    (``kernels/epoch_megakernel.py``) instead of the XLA ``while_loop`` —
    bit-identical, same ⌈E/K⌉ readback cadence.  A job overflowing its
    region (TV quota or stack depth) fails alone, mid-chunk: its stack
    pointer zeroes and its neighbours keep running.  Per-job results are
    bit-identical to solo ``HostEngine.run`` at every K.
    """

    def __init__(
        self,
        handles: Sequence[JobHandle],
        capacity: Optional[int] = None,
        dispatch: Any = "masked",
        stack_depth: int = 1 << 10,
        chunk: Any = None,
        collect_stats: bool = True,
        stats_factory=None,
        seg_offsets_fn=None,
        template=None,
        megakernel: bool = False,
        megakernel_impl: str = "auto",
        tracer=None,
        controller=None,
        chunk_controller=None,
        queue_probe=None,
    ):
        super().__init__(
            handles, capacity=capacity,
            collect_stats=collect_stats, stats_factory=stats_factory,
            template=template,
        )
        # dispatch="auto" resolves once, against the controller's rolling
        # window, before anything is traced: a resident loop bakes its mode
        # in (DESIGN.md §14).  The service layer makes the outcome sticky
        # per wave shape through the template cache.
        self._dispatch_controller = controller
        dispatch = resolve_resident_dispatch(
            dispatch, controller, self.capacity
        )
        policy = resolve_policy(dispatch)
        if policy.name not in ("masked", "gather"):
            raise ValueError(_COMPACTED_RESIDENT_MSG)
        # chunk="auto": a ChunkController owns K, re-decided at every chunk
        # boundary from completions + queue heat.  K only ever feeds the
        # dynamic `limit` argument of the one compiled chunk template, so
        # adaptation is retrace-free by construction.
        self._kctl = None
        self._queue_probe = queue_probe
        if chunk == "auto":
            self._kctl = chunk_controller or ChunkController()
        elif isinstance(chunk, str):
            raise ValueError(
                f"chunk must be an int >= 1, None, or 'auto'; got {chunk!r}"
            )
        elif chunk is not None and chunk < 1:
            raise ValueError(
                "chunk must be >= 1 epoch, or None for a fully resident "
                f"wave; got {chunk}"
            )
        self.stack_depth = stack_depth
        self.chunk = chunk
        if template is not None:
            if seg_offsets_fn is not None:
                raise ValueError(
                    "seg_offsets_fn cannot be overridden on a template "
                    "wave: the template's loop was already traced with its "
                    "own fork-scan kernel (build the template with the "
                    "desired seg_offsets_fn instead)"
                )
            if template.loop.policy.name != policy.name:
                raise ValueError(
                    "wave template was traced with dispatch "
                    f"{template.loop.policy.name!r} but this wave asks for "
                    f"{policy.name!r}: a cached chunk template bakes its "
                    "dispatch into the traced loop (key on dispatch when "
                    "caching templates)"
                )
            if template.loop.megakernel != bool(megakernel):
                raise ValueError(
                    "wave template was traced with megakernel="
                    f"{template.loop.megakernel} but this wave asks for "
                    f"megakernel={bool(megakernel)}: the chunk driver is "
                    "baked into the template (key on megakernel when "
                    "caching templates)"
                )
            self._loop: EpochLoop = template.loop
        else:
            self._loop = EpochLoop(
                self.program, dispatch,
                seg_offsets_fn=seg_offsets_fn, skip_idle_types=True,
                megakernel=megakernel, megakernel_impl=megakernel_impl,
            )
        self.policy = self._loop.policy
        # the mux owns its tracer rather than the (possibly template-shared)
        # loop: resident spans are emitted at chunk boundaries on the host
        # side, so a cached template can serve waves traced and untraced
        self.tracer = tracer or NULL_TRACER
        self._carry = None
        self._chunk_seq = 0
        self._ledger = _ChunkLedger(len(self._slots))
        self.last_deltas: Dict[str, int] = {}

    @property
    def slots(self):
        """Fuse-time slot layout (for wave-template capture)."""
        return self._slots

    # ------------------------------------------------------------ driving
    def _ensure_carry(self) -> None:
        """Build the resident carry on first use: a seated region's device
        stack gets its seed entry (sp=1), a *vacant* region (handle=None,
        sharded-fleet shards) starts empty (sp=0) — its tenant seats later
        through the admit/reseed path, so a shard's initial seating and
        its mid-flight reseeds are one code path."""
        if self._carry is not None:
            return
        J = len(self._slots)
        jstack, rstack, sp = batched_device_stacks(
            J, self.stack_depth,
            cens=np.ones(J, np.int32),
            starts=np.asarray([s.base for s in self._slots], np.int32),
            counts=np.ones(J, np.int32),
        )
        seated = np.asarray(
            [r.handle is not None for r in self._regions], np.int32
        )
        sp = sp * jnp.asarray(seated)
        self._carry = _fresh_resident_carry(
            self._state, self._heap, self._arena, jstack, rstack, sp,
            n_regions=J,
        )

    def _chunk_limit(self, max_epochs: int) -> int:
        """This chunk's dynamic epoch bound: the guard for a fully
        resident wave, else the ledger's epoch watermark plus K (the
        controller's K under ``chunk="auto"``)."""
        if self.chunk is None:
            return max_epochs
        k = self._kctl.current() if self._kctl is not None else self.chunk
        return min(max_epochs, self._ledger.epochs + k)

    def _attach_carry(self, carry) -> None:
        """Adopt a post-chunk carry: the bulk state stays on device; these
        references keep ``_finalize`` / ``_seed_region`` working on the
        current wave state."""
        self._carry = carry
        self._state, self._heap, self._arena = (
            carry.state, carry.heap, carry.arena
        )

    def _finish_chunk(self, s: ChunkSummary, riders: List[int],
                      max_epochs: int) -> List[JobHandle]:
        """Account one chunk's readback and settle its riders — shared by
        :meth:`step` and the sharded fleet's collective step (which runs
        the chunk itself, P shards fused, then finishes each shard here).
        Leaves the delta terms in ``last_deltas`` for span args."""
        deltas = self._account(s, riders)
        self.last_deltas = deltas
        # dispatch-controller feedback: the chunk is the finest observable
        # grain on this driver — one fill observation per boundary, against
        # the full-TV width (tasks / (lanes + holes))
        if self._dispatch_controller is not None and deltas["epochs"] > 0:
            self._dispatch_controller.observe(
                deltas["tasks"], deltas["lanes"] + deltas["holes"]
            )
        return self._settle(s, riders, max_epochs)

    def step(self, max_epochs: int = 1 << 20) -> List[JobHandle]:
        """Run one chunk — at most ``chunk`` epochs in one resident loop
        invocation (the whole wave when ``chunk`` is None) — then surface
        every region that drained or failed.

        Further calls continue the wave from the carried device state; once
        nothing is live, calls are clean no-ops that touch neither the
        device nor the stats ledger.
        """
        if self._restore_pending:
            # wave members resuming from preemption: build the carry, then
            # write each checkpoint image into its region
            self._ensure_carry()
            for j, h in self._restore_pending:
                self._seat(self._regions[j], h)
            self._restore_pending = []
        riders = [j for j, r in enumerate(self._regions) if r.running]
        if not riders:
            return []
        J = len(self._slots)
        tr = self.tracer
        if tr.enabled:
            tr.thread(2, "resident")
        self._chunk_seq += 1
        seq = self._chunk_seq
        # one "chunk" span per resident loop invocation, with the chunk's
        # launch, readback and settle as children — a wave of E epochs
        # renders as exactly ⌈E/K⌉ readback spans, the V_inf cadence made
        # countable.  Per-epoch detail inside the chunk is unobservable by
        # design (no readbacks to hang spans on); the deltas the readback
        # reveals are attached to the span's args instead, and the
        # profiler names the epoch body's phases on the device.
        with tr.span(
            "chunk", "resident", tid=2,
            seq=seq, jobs=len(riders),
            k=(self._kctl.current() if self._kctl is not None
               else self.chunk),
            mode=self.policy.name, megakernel=self._loop.megakernel,
        ) as sargs:
            self._ensure_carry()
            limit = self._chunk_limit(max_epochs)
            with tr.span("resident_chunk", "resident", seq=seq):
                carry = self._loop.run_chunk(self._carry, limit, n_regions=J)
            self._attach_carry(carry)
            # the chunk's one readback (XLA launches are async: the launch
            # span above is enqueue time, this wait is the real chunk)
            with tr.span("readback", "resident", seq=seq):
                s = self._loop.chunk_summary(carry)
            with tr.span("settle", "resident", seq=seq):
                done = self._finish_chunk(s, riders, max_epochs)
            if tr.enabled:
                sargs.update(self.last_deltas)
        # chunk-controller feedback: widen K while boundaries surface no
        # completions, shrink while the job queue runs hot or the nearest
        # deadline tightens (the probe's optional third element)
        if self._kctl is not None:
            queued, oldest, slack = (0, 0.0, None)
            if self._queue_probe is not None:
                probe = self._queue_probe()
                queued, oldest = probe[0], probe[1]
                if len(probe) > 2:
                    slack = probe[2]
            if slack is None:
                self._kctl.observe(len(done), queued, oldest)
            else:
                self._kctl.observe(
                    len(done), queued, oldest, deadline_slack=slack
                )
        return done

    def run(self, max_epochs: int = 1 << 20) -> List[JobHandle]:
        """Drive the wave to completion, chunk by chunk; API parity with
        :class:`EpochMultiplexer`."""
        out: List[JobHandle] = []
        while self.live:
            out.extend(self.step(max_epochs=max_epochs))
        return out

    # --------------------------------------------------------- accounting
    def _account(self, s: ChunkSummary, riders: List[int]) -> Dict[str, int]:
        """Credit this chunk's delta to the fleet collector and to every
        region that rode the chunk's fused launch; returns the delta terms
        (the chunk span's trace args)."""
        col = self._col
        col.dispatch()
        col.transfer()
        for j in riders:
            self._regions[j].stats.shared_dispatches += 1
            self._regions[j].stats.shared_transfers += 1
        led = self._ledger
        d_epochs = s.n_epochs - led.epochs
        d_holes = s.hole_lanes - led.hole_lanes
        d_tasks = int((s.job_tasks - led.job_tasks).sum())
        d_lanes = d_epochs * self.capacity - d_holes
        if d_epochs > 0:
            # every global epoch fused all regions live then; bulk O(1)
            # accounting from the readback, same ledger semantics as the
            # host driver's per-epoch calls.  The task launches were
            # span-bucketed on device, so launched lanes are the full-TV
            # total minus the hole lanes the ladder skipped.  Holes are
            # reported *before* the matching lanes() call (the pairing the
            # metrics adapter's hole-fraction fold relies on — the host
            # gather path keeps the same order).
            col.epoch(
                s.n_epochs,
                n_ranges=int((s.job_epochs - led.job_epochs).sum()),
                n=d_epochs,
            )
            col.holes_skipped(d_holes)
            col.lanes(d_tasks, d_lanes, None)
            col.forks(int((s.job_forks - led.job_forks).sum()))
        bases = np.asarray([sl.base for sl in self._slots])
        col.tv_peak(int((s.job_peak + bases).max()))
        d_maps = s.map_launches - led.map_launches
        if d_maps > 0:
            col.map_launch(
                s.map_elements - led.map_elements,
                s.map_lanes - led.map_lanes, n=d_maps,
            )
        led.epochs = s.n_epochs
        led.job_epochs = s.job_epochs.astype(np.int64)
        led.job_tasks = s.job_tasks.astype(np.int64)
        led.job_forks = s.job_forks.astype(np.int64)
        led.map_launches = s.map_launches
        led.map_elements = s.map_elements
        led.map_lanes = s.map_lanes
        led.hole_lanes = s.hole_lanes
        return {
            "epochs": d_epochs, "tasks": d_tasks, "lanes": d_lanes,
            "holes": d_holes, "maps": d_maps,
        }

    def _settle(self, s: ChunkSummary, riders: List[int],
                max_epochs: int) -> List[JobHandle]:
        """Surface every rider whose region drained, failed, or hit the
        epoch guard; regions still mid-flight stay RUNNING for the next
        chunk."""
        done: List[JobHandle] = []
        for j in riders:
            r = self._regions[j]
            # a region still holding stack entries at the guard has an
            # unfinished schedule: fail it (like an overflow) so the wave
            # always resolves every handle, never wedged RUNNING
            timed_out = bool(s.sp[j] > 0) and s.n_epochs >= max_epochs
            if s.sp[j] > 0 and not timed_out:
                continue
            st = r.stats
            st.epochs = int(s.job_epochs[j])
            st.tasks_executed = int(s.job_tasks[j])
            st.total_forks = int(s.job_forks[j])
            st.peak_tv_slots = int(s.job_peak[j])
            if bool(s.failed[j]) or timed_out:
                if timed_out:
                    reason = f"exceeded max_epochs={max_epochs}"
                elif bool(s.failed_stack[j]):
                    reason = (
                        f"job {r.handle.job.name!r} exhausted the resident "
                        f"scheduler stack: stack_depth={self.stack_depth}"
                    )
                else:
                    reason = None  # TV region overflow: the default message
                done.append(self._fail(j, reason=reason))
            else:
                done.append(self._finalize(j))
        return done

    # ------------------------------------------------- streaming admission
    def _admits_midflight(self) -> bool:
        # a fully resident wave (chunk=None) is closed: the host never sees
        # a freed region until the whole wave drains.  With a finite chunk
        # the host holds the carry between chunks, so freed regions reseed.
        return self.chunk is not None and self._carry is not None and self.live

    def _seed_region(self, r: _Region, handle: JobHandle) -> None:
        """Reseed a freed region *into the live carry* between chunks: TV
        slots, tenant heap, arena cursors, the region's device stack row,
        and its accumulators — the next chunk's ``while_loop`` simply sees
        one more live region."""
        job = handle.job
        s = r.slot
        j = s.index
        carry = self._carry
        state = self._seed_state(carry.state, s, job)
        heap = dict(carry.heap)
        for k, v in s.program.init_heap(**dict(job.heap_init)).items():
            heap[s.prefix + k] = v
        arena = tvm.arena_reset_region(carry.arena, j, s.base, job.quota)
        jstack, rstack, sp = reseed_region_stacks(
            carry.jstack, carry.rstack, carry.sp, j,
            cen=1, start=s.base, count=1,
        )
        self._carry = dataclasses.replace(
            carry, state=state, heap=heap, arena=arena,
            jstack=jstack, rstack=rstack, sp=sp,
            failed=carry.failed.at[j].set(False),
            failed_stack=carry.failed_stack.at[j].set(False),
            job_epochs=carry.job_epochs.at[j].set(0),
            job_tasks=carry.job_tasks.at[j].set(0),
            job_forks=carry.job_forks.at[j].set(0),
            job_peak=carry.job_peak.at[j].set(0),
        )
        self._state, self._heap, self._arena = state, heap, arena
        led = self._ledger
        led.job_epochs[j] = led.job_tasks[j] = led.job_forks[j] = 0
        r.handle = handle
        r.sched = None
        r.stats = JobStats()
        r.active_quota = job.quota
        handle.mark_running()

    # --------------------------------------------------------- preemption
    def _capture_region(self, j: int) -> RegionCheckpoint:
        """Lift region ``j`` off the live carry at a chunk boundary: TV
        image + heap + arena cursor (shared helper), this region's device
        stack row (starts made region-relative), and the carry's
        solo-comparable accumulators (hi/lo pairs decoded to ints)."""
        r = self._regions[j]
        tv, heap, next_off = self._capture_tv(r)
        carry = self._carry
        sp = int(np.asarray(carry.sp)[j])
        jst = np.asarray(carry.jstack)[j, :sp].astype(np.int32)
        rst = np.asarray(carry.rstack)[j, :sp].astype(np.int32).copy()
        if rst.size:
            rst[:, 0] -= r.slot.base
        epochs = int(np.asarray(carry.job_epochs)[j])
        tasks = int(_hilo_value(np.asarray(carry.job_tasks)[j]))
        forks = int(_hilo_value(np.asarray(carry.job_forks)[j]))
        peak = int(np.asarray(carry.job_peak)[j])
        st = dataclasses.replace(
            r.stats, epochs=epochs, tasks_executed=tasks,
            total_forks=forks, peak_tv_slots=peak,
        )
        return RegionCheckpoint(
            structural_hash=r.slot.program.structural_hash(),
            quota=r.active_quota,
            tv=tv, heap=heap, arena_next_off=next_off,
            sp=sp, jstack=jst, rstack=rst,
            job_epochs=epochs, job_tasks=tasks,
            job_forks=forks, job_peak=peak,
            stats=st,
        )

    def _vacate(self, j: int) -> None:
        # parking a vacated region is one scalar: sp=0 makes it inert (no
        # pops, so the stale TV content is unreachable — lanes only run
        # when a popped range's CEN matches their epoch).  Accumulator
        # rows are left as-is: they still match the ledger rows, so chunk
        # deltas stay zero until a reseed/restore rewrites both sides.
        carry = self._carry
        self._carry = dataclasses.replace(
            carry, sp=carry.sp.at[j].set(0)
        )

    def _restore_region(self, r: _Region, handle: JobHandle) -> None:
        """Write a checkpoint image into a freed region of the live carry:
        the between-chunks dual of ``_seed_region``, restoring TV, heap,
        arena cursor, the whole stack row, and the accumulator rows (hi/lo
        re-encoded) — with the ledger rows set to match, so the next
        chunk's delta accounting credits only new work (the pre-preemption
        work was already credited when it happened)."""
        cp = handle.checkpoint
        s = r.slot
        j = s.index
        carry = self._carry
        state = self._restore_state(carry.state, s, cp)
        heap = dict(carry.heap)
        for k, v in cp.heap.items():
            heap[s.prefix + k] = v
        arena = tvm.arena_reset_region(carry.arena, j, s.base, cp.quota)
        arena = dataclasses.replace(
            arena, next=arena.next.at[j].set(s.base + cp.arena_next_off)
        )
        ranges = np.asarray(cp.rstack, np.int32).reshape(-1, 2).copy()
        if ranges.size:
            ranges[:, 0] += s.base
        jstack, rstack, sp = load_region_stacks(
            carry.jstack, carry.rstack, carry.sp, j, cp.jstack, ranges
        )
        t_hi, t_lo = divmod(int(cp.job_tasks), _HILO_BASE)
        f_hi, f_lo = divmod(int(cp.job_forks), _HILO_BASE)
        self._carry = dataclasses.replace(
            carry, state=state, heap=heap, arena=arena,
            jstack=jstack, rstack=rstack, sp=sp,
            failed=carry.failed.at[j].set(False),
            failed_stack=carry.failed_stack.at[j].set(False),
            job_epochs=carry.job_epochs.at[j].set(cp.job_epochs),
            job_tasks=carry.job_tasks.at[j].set(
                jnp.asarray([t_hi, t_lo], jnp.int32)),
            job_forks=carry.job_forks.at[j].set(
                jnp.asarray([f_hi, f_lo], jnp.int32)),
            job_peak=carry.job_peak.at[j].set(cp.job_peak),
        )
        self._state, self._heap, self._arena = state, heap, arena
        led = self._ledger
        led.job_epochs[j] = cp.job_epochs
        led.job_tasks[j] = cp.job_tasks
        led.job_forks[j] = cp.job_forks
        r.handle = handle
        r.sched = None
        r.stats = (
            cp.stats if cp.stats is not None
            else JobStats(
                epochs=cp.job_epochs, tasks_executed=cp.job_tasks,
                total_forks=cp.job_forks, peak_tv_slots=cp.job_peak,
            )
        )
        r.active_quota = cp.quota
        handle.checkpoint = None
        handle.mark_running()
