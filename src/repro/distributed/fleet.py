"""Sharded fleet execution across a device mesh (DESIGN.md §15).

Everything below §15 runs one TVM on one device; this module is the step
to a *fleet*: P independent TVM shards — each a full scheduler-stack +
arena + :class:`~repro.core.engine.ResidentCarry` block, i.e. exactly one
:class:`~repro.service.multiplexer.DeviceMultiplexer` wave — stacked on a
leading "fleet" axis and advanced together:

* **One fused launch per collective chunk.**
  :meth:`~repro.core.engine.EpochLoop.run_chunk_fleet` runs every shard's
  resident chunk inside one compiled program — ``shard_map`` over the 1-D
  ``"fleet"`` mesh (:func:`repro.launch.mesh.make_fleet_mesh`), or a
  bit-identical single-device ``vmap`` simulation when the caller passes
  ``mesh=None`` — with each shard bounded by its *own* dynamic epoch
  limit.

* **One readback per collective chunk.**  The per-shard
  :class:`~repro.core.engine.ChunkSummary` scalars come back stacked in a
  single ``device_get`` (:meth:`EpochLoop.fleet_chunk_summaries`), so a
  fleet advancing K epochs pays ⌈E/K⌉ launches + readbacks *total*, not
  per shard.

* **Chunk-boundary work rebalancing.**  Jobs are placed on shards by a
  policy (``round_robin`` / ``least_loaded`` / ``sticky``); at each
  boundary, queued jobs stuck on a *hot* shard (no free compatible
  region) migrate to an *idle* shard (free region, least load measured
  from the stacked summaries: live regions, queue depth, sp-derived
  remaining stack work) and seat through the existing
  ``_seed_region`` / ``arena_reset_region`` reseed path — the same path
  mid-flight admission has always used, so migration cannot introduce a
  second seeding semantics.

Every shard shares ONE wave template (same fused program, slot layout,
and compiled loop): shards are *structurally* identical and differ only
in runtime state, which is what lets the collective step be a single
compiled program.  A shard region left without a tenant is *vacant*
(``handle=None``, sp=0 — inert by the TMS epoch-number guard) until a
job seats into it.  Per-job execution inside a shard region is exactly
the solo region execution, so per-job results stay bit-identical to a
solo run at every P, every placement, and every migration history.
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from ..core.engine import _FLEET_SPEC, resolve_resident_dispatch
from ..core.scheduler import RunStats
from ..obs.trace import NULL_TRACER
from ..service.jobs import (
    Job,
    JobHandle,
    WaveTemplate,
    canonical_wave_order,
)
from ..service.multiplexer import DeviceMultiplexer, fuse_programs

PLACEMENTS = ("round_robin", "least_loaded", "sticky")


def _type_key(job: Job) -> int:
    """Stable integer from a job's structural hash (sticky placement)."""
    h = job.program.structural_hash()
    try:
        return int(h, 16)
    except ValueError:
        return abs(hash(h))


class ShardWave(DeviceMultiplexer):
    """One shard: a DeviceMultiplexer wave whose regions all start vacant.

    Construction seats nobody — the fleet seats every tenant (initial and
    migrated alike) through :meth:`~repro.service.multiplexer._FleetBase.
    admit`'s reseed path against the eagerly-built all-vacant carry.  The
    chunk itself is *not* driven here: the fleet stacks the shard carries
    and runs them through one collective ``run_chunk_fleet`` launch, then
    hands each shard its own summary via ``_finish_chunk``.
    """

    def __init__(self, template: WaveTemplate, **kw):
        super().__init__(
            handles=[None] * len(template.slots), template=template, **kw
        )
        self._ensure_carry()
        # admission gating is the fleet's job: shards only ever seat
        # tenants at collective boundaries, where every region is either
        # mid-flight-with-finite-chunk or fully drained — both safe
        self._admit_ok = True

    def _admits_midflight(self) -> bool:
        return self._carry is not None and self._admit_ok

    @property
    def live_regions(self) -> int:
        return sum(1 for r in self._regions if r.running)


class ShardedFleet:
    """P TVM shards advancing together: one launch, one readback, per
    collective chunk (DESIGN.md §15).

    ``handles`` is the *anchor wave*: its jobs (in canonical order)
    define the per-shard slot layout replicated across all P shards, and
    are then placed like any later admission.  ``admit`` accepts any job
    structurally compatible with that layout — placement queues it on a
    shard, seating happens at collective boundaries through the reseed
    path.  Drive with :meth:`step` / :meth:`run`; completions stream per
    boundary exactly like a single ``DeviceMultiplexer`` wave.

    ``mesh="auto"`` builds a real ``"fleet"`` device mesh (each shard's
    resident loop runs on its own device) and raises when fewer than P
    devices are attached; ``mesh=None`` asks for the single-device
    ``vmap`` simulation by name — same bits either way.
    ``rebalance=False`` pins every job to its placed shard (sticky
    affinity); the default migrates queued jobs off hot shards at
    boundaries and counts each move in ``migrations``.
    """

    def __init__(
        self,
        handles: Sequence[JobHandle],
        shards: int,
        *,
        dispatch: Any = "masked",
        stack_depth: int = 1 << 10,
        chunk: Any = None,
        placement: str = "round_robin",
        placement_controller=None,
        rebalance: bool = True,
        collect_stats: bool = True,
        stats_factory: Optional[Callable[[int], Any]] = None,
        template: Optional[WaveTemplate] = None,
        megakernel: bool = False,
        megakernel_impl: str = "auto",
        tracer=None,
        controller=None,
        chunk_controller=None,
        queue_probe=None,
        mesh: Any = "auto",
    ):
        if shards < 1:
            raise ValueError(f"a fleet needs >= 1 shard, got {shards}")
        if placement not in PLACEMENTS + ("auto",):
            raise ValueError(
                f"placement must be one of {PLACEMENTS + ('auto',)}, "
                f"got {placement!r}"
            )
        if not handles:
            raise ValueError("ShardedFleet needs at least one anchor job")
        self.shards = int(shards)
        self.placement = placement
        self._pctl = None
        if placement == "auto":
            from ..control.controller import PlacementController

            self._pctl = (
                placement_controller or PlacementController()
            )
        self.rebalance = bool(rebalance)
        self.tracer = tracer or NULL_TRACER
        self.migrations = 0
        self.collective_steps = 0

        order = canonical_wave_order([h.job for h in handles])
        anchors = [handles[i] for i in order]
        jobs = [h.job for h in anchors]
        self.capacity = sum(j.quota for j in jobs)  # per shard

        dispatch = resolve_resident_dispatch(
            dispatch, controller, self.capacity
        )
        if template is None:
            from ..core.engine import EpochLoop

            program, slots = fuse_programs(
                [j.program for j in jobs], [j.quota for j in jobs]
            )
            template = WaveTemplate(
                key=("fleet-anon",),
                program=program,
                slots=slots,
                loop=EpochLoop(
                    program, dispatch, skip_idle_types=True,
                    megakernel=megakernel,
                    megakernel_impl=megakernel_impl,
                ),
            )
        self.template = template
        self._loop = template.loop
        self.chunk = chunk
        self._kctl = None
        if chunk == "auto":
            from ..control.controller import ChunkController

            self._kctl = chunk_controller or ChunkController()
        self._queue_probe = queue_probe
        self._shards: List[ShardWave] = [
            ShardWave(
                template,
                dispatch=dispatch,
                stack_depth=stack_depth,
                chunk=chunk,
                collect_stats=collect_stats,
                stats_factory=(
                    None if stats_factory is None
                    else (lambda _p=p: stats_factory(_p))
                ),
                megakernel=megakernel,
                megakernel_impl=megakernel_impl,
                tracer=self.tracer,
                controller=controller,
                chunk_controller=self._kctl,
            )
            for p in range(self.shards)
        ]
        self.policy = self._shards[0].policy
        self._slot_types = [
            (s.program.structural_hash(), s.quota) for s in template.slots
        ]
        if mesh == "auto":
            from ..launch.mesh import make_fleet_mesh

            mesh = make_fleet_mesh(self.shards)
        self.mesh = mesh
        self._pending: List[List[JobHandle]] = [
            [] for _ in range(self.shards)
        ]
        self._rr = 0
        # fleet-carry bookkeeping (see _view/_stacked): the stacked carry
        # is the single source of truth between boundaries; shards get
        # host-side views of it ONLY when the host actually needs to
        # touch their state (a completion to finalize, a job to seat) —
        # never as a per-step eager slice of device-sharded arrays, which
        # on a real mesh would be a cross-device gather per leaf per
        # shard per chunk
        self._fcarry = None
        self._host = None  # lazy device_get snapshot of _fcarry
        self._fresh = [True] * self.shards
        self._attached: List[Any] = [
            sh._carry for sh in self._shards
        ]
        self._last_sp: List[Optional[np.ndarray]] = [None] * self.shards
        # fleet-level V_inf: ONE fused launch + ONE stacked readback per
        # collective chunk, however many shards rode it
        self._dispatches = 0
        self._transfers = 0

        for h in anchors:
            if not self.admit(h):
                raise ValueError(
                    f"anchor job {h.job.name!r} does not fit the fleet "
                    "layout it defined"
                )

    # ----------------------------------------------------------- placement
    def compatible(self, job: Job) -> bool:
        """Whether this layout can ever run the job (structural equality
        with some slot template, quota within the slot)."""
        h = job.program.structural_hash()
        return any(h == sh and job.quota <= q for sh, q in self._slot_types)

    def _load(self, p: int):
        """Shard load, least-first comparable: queued jobs, live regions,
        and the last summary's sp-derived remaining stack work."""
        sp = self._last_sp[p]
        return (
            len(self._pending[p]),
            self._shards[p].live_regions,
            0 if sp is None else int(sp.sum()),
        )

    def _place(self, job: Job) -> int:
        policy = self.placement
        if self._pctl is not None:
            # placement="auto": the controller re-picks the concrete
            # policy per job from the observed workload mix
            self._pctl.observe_job(_type_key(job))
            policy = self._pctl.choose()
        if policy == "sticky":
            return _type_key(job) % self.shards
        if policy == "least_loaded":
            return min(range(self.shards), key=self._load)
        p = self._rr
        self._rr = (self._rr + 1) % self.shards
        return p

    def admit(self, handle: JobHandle) -> bool:
        """Queue a job on its placed shard (False if the layout can never
        run it).  Seating — including any rebalancing migration — happens
        at the next collective boundary."""
        if not self.compatible(handle.job):
            return False
        self._pending[self._place(handle.job)].append(handle)
        return True

    def _free_region(self, p: int, job: Job) -> bool:
        h = job.program.structural_hash()
        return any(
            r.handle is None
            and job.quota <= r.slot.quota
            and (
                r.slot.program is job.program
                or r.slot.program.structural_hash() == h
            )
            for r in self._shards[p]._regions
        )

    def _seat_pending(self) -> int:
        """Seat queued jobs on their shards; then (rebalance) migrate jobs
        stuck on hot shards to idle shards with free compatible regions.
        Every seat goes through the shard's admit → ``_seed_region``
        reseed path."""
        seated = 0
        for p, sh in enumerate(self._shards):
            if not self._pending[p]:
                continue
            self._view(p)  # reseed mutates the carry: need the real one
            rest: List[JobHandle] = []
            for h in self._pending[p]:
                if sh.admit(h):
                    seated += 1
                else:
                    rest.append(h)
            self._pending[p] = rest
        if self.rebalance:
            for p in range(self.shards):
                if not self._pending[p]:
                    continue
                rest = []
                for h in self._pending[p]:
                    cands = [
                        q for q in range(self.shards)
                        if q != p and self._free_region(q, h.job)
                    ]
                    tgt = min(cands, key=self._load) if cands else None
                    if tgt is not None:
                        self._view(tgt)
                    if tgt is not None and self._shards[tgt].admit(h):
                        self.migrations += 1
                        seated += 1
                    else:
                        rest.append(h)
                self._pending[p] = rest
        return seated

    # ------------------------------------------------------------- driving
    @property
    def live(self) -> bool:
        return (
            any(sh.live for sh in self._shards)
            or any(self._pending)
        )

    @property
    def loop(self):
        return self._loop

    @property
    def slots(self):
        return list(self.template.slots)

    def _ensure_host(self):
        """The host snapshot of the fleet carry — ONE bulk ``device_get``
        per boundary that needs any host interaction, shared by every
        shard viewed at that boundary."""
        if self._host is None:
            self._host = jax.device_get(self._fcarry)
        return self._host

    def _view(self, p: int) -> None:
        """Attach shard ``p``'s carry as a host-side slice of the fleet
        carry.  Deliberately NOT an eager ``x[p]`` on the collective
        output: on a real mesh that is a cross-device gather per leaf
        per shard (and can wedge XLA CPU's collective rendezvous); a
        ``device_get`` of the addressable shards costs no collective."""
        if self._fresh[p] or self._fcarry is None:
            return
        host = self._ensure_host()
        view = jax.tree.map(lambda x, _p=p: jnp.asarray(x[_p]), host)
        self._shards[p]._attach_carry(view)
        self._attached[p] = view
        self._fresh[p] = True

    def _stacked(self):
        """The fleet carry: per-shard carries stacked on the leading axis.
        Steady-state chunks reuse the previous collective output directly
        (its leaves ARE the stacked arrays); only a boundary that reseeded
        some shard's carry pays a restack, and only the reseeded shards'
        host-attached carries feed it — untouched shards come from the
        host snapshot, never from a stale attachment."""
        if self._fcarry is None:
            # first collective step: every shard's carry is authoritative
            # (built vacant, anchors seated through admit)
            parts = [sh._carry for sh in self._shards]
        elif any(
            self._fresh[p] and self._shards[p]._carry is not self._attached[p]
            for p in range(self.shards)
        ):
            host = self._ensure_host()
            parts = [
                sh._carry if self._fresh[p]
                else jax.tree.map(lambda x, _p=p: jnp.asarray(x[_p]), host)
                for p, sh in enumerate(self._shards)
            ]
        else:
            return self._fcarry
        if self.mesh is None:
            self._fcarry = jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
        else:
            # from the host straight onto the mesh, one block per device:
            # a restack never stages the whole fleet on the default device
            parts = jax.device_get(parts)
            self._fcarry = jax.device_put(
                jax.tree.map(lambda *xs: np.stack(xs), *parts),
                NamedSharding(self.mesh, _FLEET_SPEC),
            )
        self._host = None
        for p, sh in enumerate(self._shards):
            if self._fresh[p]:
                self._attached[p] = sh._carry
        return self._fcarry

    def step(self, max_epochs: int = 1 << 20) -> List[JobHandle]:
        """One collective chunk: seat/rebalance queued jobs, advance every
        live shard by (at most) K epochs in ONE fused launch, read the
        stacked summaries back ONCE, then settle each shard's riders."""
        tr = self.tracer
        if tr.enabled:
            tr.thread(3, "fleet")
            for p in range(self.shards):
                tr.thread(10 + p, f"shard{p}")
        with tr.span("admit", "fleet", tid=3):
            self._seat_pending()
        riders = [
            [j for j, r in enumerate(sh._regions) if r.running]
            for sh in self._shards
        ]
        if not any(riders):
            return []
        limits = np.asarray(
            [
                sh._chunk_limit(max_epochs) if riders[p] else 0
                for p, sh in enumerate(self._shards)
            ],
            np.int32,
        )
        self.collective_steps += 1
        seq = self.collective_steps
        with tr.span(
            "collective_chunk", "fleet", tid=3,
            seq=seq, shards=self.shards,
            jobs=sum(len(r) for r in riders),
            mode=self.policy.name,
            mesh=self.mesh is not None,
        ):
            fc = self._stacked()
            J = len(self.template.slots)
            with tr.span("resident_chunk", "fleet", seq=seq):
                out = self._loop.run_chunk_fleet(
                    fc, limits, n_regions=J, n_shards=self.shards,
                    mesh=self.mesh,
                )
            self._fcarry = out
            self._host = None
            self._fresh = [False] * self.shards
            with tr.span("readback", "fleet", seq=seq):
                summaries = self._loop.fleet_chunk_summaries(
                    out, self.shards
                )
        self._dispatches += 1
        self._transfers += 1
        done: List[JobHandle] = []
        with tr.span("settle", "fleet", tid=3, seq=seq):
            for p, sh in enumerate(self._shards):
                s = summaries[p]
                self._last_sp[p] = s.sp
                if not riders[p]:
                    continue
                # a shard's carry is only pulled to the host when settling
                # will actually touch it (a rider drained, failed, or hit
                # the guard); quiet shards ride the next chunk without any
                # host traffic on their state
                if any(
                    bool(s.failed[j]) or int(s.sp[j]) == 0
                    or s.n_epochs >= max_epochs
                    for j in riders[p]
                ):
                    self._view(p)
                shard_done = sh._finish_chunk(s, riders[p], max_epochs)
                done.extend(shard_done)
                if tr.enabled:
                    with tr.span(
                        "chunk", "fleet", tid=10 + p, shard=p,
                        jobs=len(riders[p]), **sh.last_deltas,
                    ):
                        pass
        # controller feedback, ONCE per collective boundary: the fleet
        # queue is its internal shard queues plus whatever external queue
        # the service reports (the probe's optional third element is the
        # admission layer's nearest-deadline slack)
        if self._pctl is not None:
            loads = [len(q) for q in self._pending]
            self._pctl.observe_imbalance(
                self.utilization_spread(), max(loads) - min(loads)
            )
        if self._kctl is not None:
            queued = sum(len(q) for q in self._pending)
            oldest, slack = 0.0, None
            if self._queue_probe is not None:
                probe = self._queue_probe()
                queued += probe[0]
                oldest = probe[1]
                if len(probe) > 2:
                    slack = probe[2]
            if slack is None:
                self._kctl.observe(len(done), queued, oldest)
            else:
                self._kctl.observe(
                    len(done), queued, oldest, deadline_slack=slack
                )
        return done

    # ---------------------------------------------------------- preemption
    def running_handles(self) -> List[JobHandle]:
        out: List[JobHandle] = []
        for sh in self._shards:
            out.extend(sh.running_handles())
        return out

    def preempt(self, handle: JobHandle) -> bool:
        """Lift a running job off whichever shard holds it into its
        engine-agnostic checkpoint (the region goes vacant).  Works only
        at collective boundaries — exactly when the service calls it —
        because the shard's carry must be host-attached to capture."""
        for p, sh in enumerate(self._shards):
            if any(
                r.handle is handle and r.running for r in sh._regions
            ):
                self._view(p)  # capture/vacate mutate the carry
                return sh.preempt(handle)
        return False

    def run(self, max_epochs: int = 1 << 20) -> List[JobHandle]:
        out: List[JobHandle] = []
        while self.live:
            got = self.step(max_epochs=max_epochs)
            out.extend(got)
            if not got and not any(sh.live for sh in self._shards):
                # queued jobs nobody can seat — impossible by construction
                # (compatible() gates admit), but never spin silently
                raise RuntimeError(
                    "sharded fleet wedged: queued jobs but no live or "
                    "seatable region"
                )
        return out

    # ----------------------------------------------------------- reporting
    def stats(self) -> RunStats:
        """Fleet totals: per-shard work counters summed, V_inf terms
        counted per *collective* step — P shards ride ONE launch and ONE
        readback per chunk, which is the entire point."""
        total = RunStats()
        for sh in self._shards:
            total.merge(sh.stats())
        total.dispatches = self._dispatches
        total.scalar_transfers = self._transfers
        return total

    def shard_stats(self) -> List[RunStats]:
        """Per-shard solo-comparable stats (each shard accounted as if it
        were its own DeviceMultiplexer wave)."""
        return [sh.stats() for sh in self._shards]

    def utilization_spread(self) -> float:
        """Max-min per-shard lane utilization — the load-imbalance signal
        the benchmark rows carry."""
        utils = [s.utilization for s in self.shard_stats()
                 if s.lanes_launched > 0]
        if not utils:
            return 0.0
        return max(utils) - min(utils)

    @property
    def pending_jobs(self) -> int:
        return sum(len(q) for q in self._pending)

    @property
    def trace_count(self) -> int:
        return self._loop.trace_count
