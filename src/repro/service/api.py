"""Execution surface of the layered serving front door (DESIGN.md §16).

:class:`JobService` is the multi-tenant front door: ``submit`` enqueues a
program (any app, any arguments) with a TV-region quota — optionally under
a :class:`~repro.service.admission.QuotaClass` with a priority and a
deadline — ``poll`` reports its lifecycle state, ``result`` drives the
fleet until that job finishes, and ``completions`` streams handles the
moment each job's scheduler drains.  ``submit_async`` /
:meth:`JobService.stream_results` are the non-blocking face of the same
queue: a :class:`JobFuture` awaits one job while the service keeps pumping
waves cooperatively, so callers never block on a whole wave.

The service stack is three layers (``admission.py`` module docstring):
the :class:`~repro.service.admission.AdmissionController` decides *which*
queued jobs form the next wave and *who* yields a region (EDF within
priority, class shares, token buckets, preemption plans); the wave
drivers in ``multiplexer.py``/``distributed/fleet.py`` execute those
plans at chunk boundaries through the one reseed path; this module is the
surface that wires them together.

The service runs jobs in *waves*: a wave is one fused
:class:`~repro.service.multiplexer.EpochMultiplexer` fleet (up to
``max_jobs`` jobs whose quotas fit the capacity budget and whose value
dtypes agree).  While a wave is in flight, queued jobs whose program
template matches a freed region are admitted mid-flight (streaming
multi-tenancy, no retrace); everything else waits for the next wave.  At
each chunk boundary the admission layer may also *preempt*: a running
job lifts into an engine-agnostic
:class:`~repro.service.jobs.RegionCheckpoint` and re-queues, its region
goes to a strictly-higher-priority waiter, and the resumed run stays
bit-identical to an uninterrupted one.
"""
from __future__ import annotations

import asyncio
import itertools
import time
from typing import (
    Any, AsyncIterator, Callable, Dict, Iterator, List, Mapping, Optional,
)

from ..core.engine import log_run_stats
from ..core.program import InitialTask, Program
from ..core.scheduler import RunStats
from ..obs.trace import NULL_TRACER
from .admission import AdmissionController, QuotaClass
from .jobs import (
    AdmissionError,
    Job,
    JobHandle,
    JobResult,
    JobStatus,
    WaveTemplate,
    WaveTemplateCache,
    canonical_wave_order,
    check_fleet_dtype,
    validate_job,
    wave_template_key,
)
from .multiplexer import DeviceMultiplexer, EpochMultiplexer


def merge_stats(into: RunStats, s: RunStats) -> RunStats:
    """Accumulate one wave's fleet stats into a running total.

    Kept as an exported alias; the merge itself lives on
    :meth:`~repro.core.scheduler.RunStats.merge` (one source of truth,
    next to ``as_dict`` — the shared metric vocabulary).
    """
    return into.merge(s)


class JobFuture:
    """Awaitable face of one submitted job.

    Awaiting it drives the service cooperatively — one :meth:`JobService.
    _pump` per event-loop turn, yielding control between pumps — until
    *this* job reaches a terminal state.  Any number of futures may be
    awaited concurrently (``asyncio.gather``): they share the service's
    single-threaded pump, so progress interleaves without locks and
    whichever future's job finishes first resolves first.
    """

    def __init__(self, service: "JobService", handle: JobHandle):
        self.service = service
        self.handle = handle

    @property
    def job_id(self) -> int:
        return self.handle.job_id

    @property
    def status(self) -> JobStatus:
        return self.handle.status

    def done(self) -> bool:
        return self.handle.done

    async def result(self) -> JobResult:
        h = self.handle
        while not h.done:
            if not self.service._pending():
                raise RuntimeError(
                    f"job {h.job.name!r} cannot make progress"
                )
            self.service._pump()
            await asyncio.sleep(0)
        if h.status is JobStatus.FAILED:
            raise h.error
        return h.result

    def __await__(self):
        return self.result().__await__()


class JobService:
    """Multi-tenant job service over one shared TVM.

    ``capacity`` is the slot budget a wave's quotas must fit in;
    ``max_jobs`` bounds a wave's fan-in; ``dispatch``/``coalesce`` select
    the phase-2 policy for the fused fleet exactly as on ``HostEngine``;
    ``pop_policy``/``gang`` pick the multi-stack pop policy
    (:class:`~repro.core.scheduler.MuxPopPolicy`).

    ``engine`` picks the wave driver: ``"host"`` (default) runs each wave
    on the host-loop :class:`~repro.service.multiplexer.EpochMultiplexer` —
    per-global-epoch V_inf, with streaming completion and mid-flight region
    reuse; ``"device"`` runs each wave resident inside a ``lax.while_loop``
    (:class:`~repro.service.multiplexer.DeviceMultiplexer`, DESIGN.md
    §9–10).  ``chunk`` (device engine only) is the K-knob: the resident
    loop re-enters every K epochs, paying ⌈epochs/K⌉ readbacks per wave in
    exchange for streaming completions and mid-flight region reuse at the
    chunk boundaries; ``chunk=None`` (default) is the fully-resident
    endpoint — O(1) V_inf per wave, completions surface per wave, queued
    jobs wait for the next wave.

    Device waves compile through a :class:`~repro.service.jobs.
    WaveTemplateCache`: structurally identical consecutive waves (same
    member ``structural_hash``es, quotas, capacity, stack depth, K,
    dispatch, and chunk driver) reuse one compiled chunk loop instead of
    retracing; ``trace_count`` exposes the compile-count guard.

    ``megakernel`` (device engine only) runs each resident chunk as one
    persistent Pallas kernel (``kernels/epoch_megakernel.py``) instead of
    the XLA ``while_loop`` — bit-identical results and stats, same ⌈E/K⌉
    readback cadence; ``dispatch="gather"`` on the device engine packs
    each epoch's scheduled lanes into a fixed-shape segmented frontier so
    union-span hole lanes are never stepped (DESIGN.md §12).

    ``engine="sharded"`` scales the device engine out: ``shards`` full
    device waves — same slot layout, one shared compiled template — run
    together on a 1-D ``"fleet"`` device mesh (DESIGN.md §15), one fused
    launch and one stacked readback per collective chunk.  ``placement``
    (``round_robin`` / ``least_loaded`` / ``sticky``) assigns queued jobs
    to shards; ``rebalance`` migrates jobs off hot shards at chunk
    boundaries.  Per-job results stay bit-identical to solo at every P.
    ``mesh`` is the fleet's device mesh: ``"auto"`` builds one over P
    attached devices (and raises with fewer), ``None`` runs the P shards
    as the single-device ``vmap`` simulation.

    ``calibrate`` (default on) seeds ``dispatch="auto"``'s controller
    with a :meth:`~repro.control.controller.CostModel.calibrated` micro
    -probe of this host at service start — cached per process, so only
    the first service constructed ever pays it (DESIGN.md §14).
    """

    def __init__(
        self,
        capacity: int = 1 << 14,
        max_jobs: int = 8,
        dispatch: Any = "masked",
        coalesce: bool = True,
        pop_policy: Any = "fuse_all",
        gang: int = 0,
        default_quota: int = 1 << 10,
        collect_stats: bool = True,
        rank_fn=None,
        engine: str = "host",
        stack_depth: int = 1 << 10,
        chunk: Optional[int] = None,
        template_cache: Optional[WaveTemplateCache] = None,
        megakernel: bool = False,
        megakernel_impl: str = "auto",
        metrics=None,
        tracer=None,
        shards: int = 1,
        mesh: Any = "auto",
        placement: str = "round_robin",
        rebalance: bool = True,
        calibrate: bool = True,
        classes: Optional[List[QuotaClass]] = None,
        admission: Optional[AdmissionController] = None,
        preemption: bool = True,
        evict_over_deadline: bool = False,
        clock: Callable[[], float] = time.monotonic,
    ):
        if engine not in ("host", "device", "sharded"):
            raise ValueError(
                "engine must be 'host', 'device' or 'sharded', "
                f"got {engine!r}"
            )
        if engine == "sharded":
            from ..distributed.fleet import PLACEMENTS

            if shards < 1:
                raise ValueError(f"shards must be >= 1, got {shards}")
            if placement not in PLACEMENTS + ("auto",):
                raise ValueError(
                    f"placement must be one of {PLACEMENTS + ('auto',)}, "
                    f"got {placement!r}"
                )
            if mesh == "auto":
                from ..launch.mesh import make_fleet_mesh

                mesh = make_fleet_mesh(shards)  # refuses P > devices
        elif shards != 1:
            raise ValueError(
                "shards requires engine='sharded' (host/device waves run "
                f"one TVM); got shards={shards}"
            )
        if engine in ("device", "sharded"):
            from ..core.scheduler import resolve_policy

            if resolve_policy(dispatch).name not in (
                "masked", "gather", "auto"
            ):
                raise ValueError(
                    f"engine={engine!r} supports dispatch='masked', "
                    "'gather' or 'auto' (resident launch shapes are fixed "
                    "at trace time; compacted sizes launches from runtime "
                    "populations and is host-only)"
                )
            if gang or pop_policy != "fuse_all":
                raise ValueError(
                    f"engine={engine!r} runs every live region each epoch "
                    "(fuse_all); gang/pop_policy are host-engine options"
                )
            if chunk == "auto":
                pass  # adaptive K: a ChunkController owns the cadence
            elif isinstance(chunk, str):
                raise ValueError(
                    f"chunk must be >= 1, None, or 'auto'; got {chunk!r}"
                )
            elif chunk is not None and chunk < 1:
                raise ValueError(f"chunk must be >= 1 or None, got {chunk}")
        elif chunk is not None:
            raise ValueError(
                "chunk sets the resident readback cadence; it requires "
                "engine='device' (the host engine reads back every epoch)"
            )
        elif megakernel:
            raise ValueError(
                "megakernel fuses the resident chunk loop; it requires "
                "engine='device' (the host engine has no resident loop)"
            )
        self.engine = engine
        self.shards = int(shards)
        self.mesh = mesh
        self.placement = placement
        self.rebalance = bool(rebalance)
        self.stack_depth = stack_depth
        self.chunk = chunk
        self.megakernel = bool(megakernel)
        self.megakernel_impl = megakernel_impl
        self.template_cache = (
            template_cache if template_cache is not None
            else WaveTemplateCache()
        )
        self.capacity = capacity
        self.max_jobs = max_jobs
        self.dispatch = dispatch
        self.coalesce = coalesce
        self.pop_policy = pop_policy
        self.gang = gang
        self.default_quota = default_quota
        self.collect_stats = collect_stats
        self._rank_fn = rank_fn
        # observability (DESIGN.md §13), both opt-in: ``metrics`` is a
        # MetricsRegistry fed with per-wave run series (via the collector
        # adapter) and the per-tenant job lifecycle series below; ``tracer``
        # receives epoch/chunk span timelines from the wave drivers
        self.metrics = metrics
        self.tracer = tracer or NULL_TRACER
        # self-tuning (DESIGN.md §14): the controllers live on the service
        # so what they learn carries across waves.  The dispatch controller
        # is shared by every wave's loop (host: per-epoch decisions;
        # device: one resolution per new wave shape, sticky via the
        # template cache); the chunk controller owns K across waves.
        from ..core.scheduler import resolve_policy as _rp

        self.controller = None
        if _rp(dispatch).name == "auto":
            from ..control.controller import CostModel, DispatchController

            # calibrate by default: the controller's priors come from a
            # one-shot micro-probe of *this* host (process-cached, so only
            # the first service pays it) instead of the static roofline
            # constants — DESIGN.md §14's "calibrate once, decide often"
            cost = CostModel.calibrated() if calibrate else None
            self.controller = DispatchController(cost=cost)
            if metrics is not None:
                self.controller.bind_registry(
                    metrics, driver=engine, app="service"
                )
        self.chunk_controller = None
        if chunk == "auto":
            from ..control.controller import ChunkController

            self.chunk_controller = ChunkController()
            if metrics is not None:
                self.chunk_controller.bind_registry(metrics, app="service")
        # placement="auto" (sharded): the controller lives here so its
        # workload-mix window carries across waves, like the K controller
        self.placement_controller = None
        if engine == "sharded" and placement == "auto":
            from ..control.controller import PlacementController

            self.placement_controller = PlacementController()
            if metrics is not None:
                self.placement_controller.bind_registry(
                    metrics, app="service"
                )
        # admission layer (DESIGN.md §16): the policy brain this surface
        # delegates wave assembly and preemption planning to.  An explicit
        # controller wins (its clock becomes the service clock so handle
        # stamps and deadline arithmetic share one timebase).
        if admission is not None:
            self.admission = admission
            self._clock = admission.clock
        else:
            self.admission = AdmissionController(
                classes=classes, clock=clock,
                evict_over_deadline=evict_over_deadline,
            )
            self._clock = clock
        self.preemption = bool(preemption)
        self._ids = itertools.count()
        self._queue: List[JobHandle] = []
        self._handles: Dict[int, JobHandle] = {}
        self._mux: Optional[EpochMultiplexer] = None
        self._stats = RunStats()
        self._admit_ready = False  # a region was freed since the last scan

    # ------------------------------------------------------- observability
    def _stats_factory(self):
        """Per-wave collector factory: the plain collector when metrics are
        off (the disabled path allocates nothing extra), the registry
        adapter around it when on."""
        if self.metrics is None:
            return None
        from ..core.scheduler import NullStats, RunStatsCollector, \
            resolve_policy
        from ..obs.metrics import MetricsCollector

        registry = self.metrics
        driver = self.engine
        dispatch = resolve_policy(self.dispatch).name
        collect = self.collect_stats

        def factory():
            inner = RunStatsCollector() if collect else NullStats()
            return MetricsCollector(
                inner, registry, driver=driver, dispatch=dispatch,
                app="service",
            )

        return factory

    def _sharded_stats_factory(self):
        """Per-shard collector factory for the sharded engine: same series
        as :meth:`_stats_factory` with a ``shard`` label on every one, so
        per-shard utilization and work splits are scrapeable directly.
        (A registry pins labelnames per metric name, so keep one registry
        per engine flavor — sharded services label ``shard`` on every
        run-series metric, solo services label none.)"""
        if self.metrics is None:
            return None
        from ..core.scheduler import NullStats, RunStatsCollector, \
            resolve_policy
        from ..obs.metrics import MetricsCollector

        registry = self.metrics
        dispatch = resolve_policy(self.dispatch).name
        collect = self.collect_stats

        def factory(p: int):
            inner = RunStatsCollector() if collect else NullStats()
            return MetricsCollector(
                inner, registry, driver="sharded", dispatch=dispatch,
                app="service", shard=str(p),
            )

        return factory

    def _observe_completions(self, done: List[JobHandle]) -> None:
        """Record deadline outcomes with the admission layer and feed the
        per-tenant/per-class lifecycle series for newly finished jobs:
        queue-wait and run-time latency histograms, completion counters by
        terminal status, and the per-class deadline scoreboard."""
        # admission accounting happens with or without a registry
        outcomes = {
            h.job_id: self.admission.note_finished(h) for h in done
        }
        if self.metrics is None or not done:
            return
        r = self.metrics
        lab = ("tenant",)
        qw = r.histogram(
            "trees_job_queue_wait_seconds",
            "seconds from submit to first co-scheduled epoch", lab,
        )
        rt = r.histogram(
            "trees_job_run_seconds",
            "seconds from first co-scheduled epoch to completion", lab,
        )
        fin = r.counter(
            "trees_jobs_finished_total",
            "jobs reaching a terminal status", ("tenant", "status"),
        )
        # per-class series (new names: the registry pins labelnames per
        # metric, so class-labeled series cannot share the tenant ones)
        cqw = r.histogram(
            "trees_class_queue_wait_seconds",
            "queue wait by quota class", ("klass",),
        )
        dmiss = r.counter(
            "trees_deadline_misses_total",
            "deadlined jobs finishing past their deadline", ("klass",),
        )
        dmet = r.counter(
            "trees_deadlines_met_total",
            "deadlined jobs finishing in time", ("klass",),
        )
        ratio = r.gauge(
            "trees_deadline_miss_ratio",
            "misses / (misses + met) per quota class", ("klass",),
        )
        for h in done:
            tenant = h.job.name or h.job.program.name
            if h.queue_wait is not None:
                qw.labels(tenant=tenant).observe(h.queue_wait)
                cqw.labels(klass=h.klass).observe(h.queue_wait)
            if h.run_time is not None:
                rt.labels(tenant=tenant).observe(h.run_time)
            fin.labels(tenant=tenant, status=h.status.value).inc()
            met = outcomes[h.job_id]
            if met is True:
                dmet.labels(klass=h.klass).inc()
            elif met is False:
                dmiss.labels(klass=h.klass).inc()
            if met is not None:
                ratio.labels(klass=h.klass).set(
                    self.admission.miss_ratio(h.klass)
                )
        # completions follow the wave's compiled steps, so the trace-count
        # gauge set at lookup time (pre-compile) is refreshed here with
        # whatever the wave actually traced
        r.gauge(
            "trees_wave_template_traces",
            "traced builder bodies across all wave templates",
        ).labels().set(self.template_cache.trace_count)

    def _observe_preemption(self, h: JobHandle) -> None:
        """Count one executed preemption, labeled by quota class."""
        if self.metrics is None:
            return
        self.metrics.counter(
            "trees_job_preemptions_total",
            "running jobs checkpointed and re-queued at a chunk boundary",
            ("klass",),
        ).labels(klass=h.klass).inc()

    def _observe_template_cache(self, hit: bool) -> None:
        """Mirror the wave-template cache's reuse counters into the
        registry (hit/miss per wave build, LRU evictions, plus the
        monotone trace-count gauge the compile-regression guard
        watches)."""
        if self.metrics is None:
            return
        r = self.metrics
        r.counter(
            "trees_wave_template_lookups_total",
            "wave-template cache lookups", ("outcome",),
        ).labels(outcome="hit" if hit else "miss").inc()
        r.gauge(
            "trees_wave_template_evictions",
            "wave templates LRU-evicted from the cache so far",
        ).labels().set(self.template_cache.evictions)
        r.gauge(
            "trees_wave_template_traces",
            "traced builder bodies across all wave templates",
        ).labels().set(self.template_cache.trace_count)

    # ------------------------------------------------------------- submit
    def submit(
        self,
        program: Program,
        initial: InitialTask,
        heap_init: Optional[Mapping[str, Any]] = None,
        quota: Optional[int] = None,
        name: str = "",
        priority: int = 0,
        deadline: Optional[float] = None,
        klass: str = "default",
    ) -> JobHandle:
        """Admit a job into the queue; raises AdmissionError if it can
        never run on this service.

        ``priority`` orders admission (higher first; overrides the class
        priority when nonzero) and gates preemption — a queued job evicts
        running work only when strictly higher-priority.  ``deadline`` is
        *relative* seconds from now on the service clock; the admission
        layer schedules EDF within each priority band, tightens the chunk
        cadence as it approaches, and scores met/missed per class.
        ``klass`` names a :class:`~repro.service.admission.QuotaClass`
        configured at service construction."""
        job = Job(
            program=program,
            initial=initial,
            heap_init=dict(heap_init or {}),
            quota=int(quota or self.default_quota),
            name=name or program.name,
        )
        validate_job(job, self.capacity)
        if klass not in self.admission.classes:
            raise AdmissionError(
                f"job {job.name!r}: unknown quota class {klass!r} "
                f"(known: {sorted(self.admission.classes)})"
            )
        handle = JobHandle(
            job_id=next(self._ids), job=job, clock=self._clock,
            priority=int(priority),
            deadline=(
                None if deadline is None else self._clock() + deadline
            ),
            klass=klass,
        )
        self._handles[handle.job_id] = handle
        self._queue.append(handle)
        return handle

    def submit_case(self, case, quota: Optional[int] = None,
                    name: str = "", **kw) -> JobHandle:
        """Submit a registered :class:`~repro.apps.registry.AppCase`."""
        return self.submit(
            case.program,
            case.initial,
            heap_init=dict(case.heap_init),
            quota=quota or case.capacity,
            name=name or case.name,
            **kw,
        )

    def submit_async(self, *args, **kw) -> JobFuture:
        """:meth:`submit`, wrapped in an awaitable :class:`JobFuture`."""
        return JobFuture(self, self.submit(*args, **kw))

    # -------------------------------------------------------------- query
    def poll(self, handle: JobHandle) -> JobStatus:
        return handle.status

    def result(self, handle: JobHandle) -> JobResult:
        """Drive the service until this job finishes; raise on failure."""
        while not handle.done:
            if not self._pending():
                raise RuntimeError(
                    f"job {handle.job.name!r} cannot make progress"
                )
            self._pump()
        if handle.status is JobStatus.FAILED:
            raise handle.error
        return handle.result

    # ------------------------------------------------------------- driving
    def completions(self) -> Iterator[JobHandle]:
        """Stream handles as they complete (DONE or FAILED)."""
        while self._pending():
            for h in self._pump():
                yield h

    def drain(self) -> List[JobHandle]:
        """Run every submitted job to completion; return all handles in
        completion order."""
        return list(self.completions())

    async def stream_results(self) -> AsyncIterator[JobHandle]:
        """Async face of :meth:`completions`: yield handles as they
        finish, ceding the event loop between pumps so concurrent
        coroutines (more submits, per-job awaits) interleave."""
        while self._pending():
            for h in self._pump():
                yield h
            await asyncio.sleep(0)

    def preempt(self, handle: JobHandle) -> bool:
        """Preempt one running job at the next opportunity *now*: lift it
        into its checkpoint, re-queue it, free its region.  Returns False
        if the job is not currently seated (queued, finished, or the wave
        driver cannot checkpoint mid-flight — e.g. an unchunked resident
        wave has no boundary to capture at)."""
        if self._mux is None or not self._mux.preempt(handle):
            return False
        self.admission.note_preempted(handle)
        self._observe_preemption(handle)
        self._queue.append(handle)
        self._admit_ready = True
        return True

    def stats(self) -> RunStats:
        """Fleet-level stats accumulated across every wave so far."""
        total = merge_stats(RunStats(), self._stats)
        if self._mux is not None:
            merge_stats(total, self._mux.stats())
        return total

    @property
    def trace_count(self) -> int:
        """Traced builder bodies across every device wave template — the
        compile-count regression guard: after a wave, an identical
        consecutive wave must leave this unchanged (its chunks run entirely
        on the cached compiled loop)."""
        return self.template_cache.trace_count

    # ------------------------------------------------------------ internal
    def _queue_probe(self):
        """Queue-heat signal for the chunk controller: (queued jobs, the
        oldest queued job's wait in seconds, seconds of slack to the
        nearest outstanding deadline).  The first two are the same
        quantities exported as ``trees_job_queue_wait_seconds``; the third
        lets the controller tighten K *before* a deadline, not after."""
        running = (
            self._mux.running_handles() if self._mux is not None else ()
        )
        slack = self.admission.deadline_slack(self._queue, running)
        if not self._queue:
            return (0, 0.0, slack)
        now = self._clock()
        return (
            len(self._queue),
            max(now - h.submitted_at for h in self._queue),
            slack,
        )

    def _pending(self) -> bool:
        return bool(self._queue) or (self._mux is not None and self._mux.live)

    def _pump(self) -> List[JobHandle]:
        """Make one unit of progress: (re)build or refill the fleet, then
        run one fused global epoch.  Returns newly completed handles.

        Each piece of host work is a span of its own (``wave_build``,
        ``admit``, the driver's chunk spans, ``observe``, ``preempt``), so a
        profiler trace names whatever the device waits on."""
        tr = self.tracer
        if self._mux is None or not self._mux.live:
            with tr.span("wave_build", "service") as sargs:
                if self._mux is not None:
                    wave_stats = self._mux.stats()
                    log_run_stats(self._mux.loop, wave_stats,
                                  driver=self.engine)
                    merge_stats(self._stats, wave_stats)
                    self._mux = None
                wave = self._take_wave()
                if wave:
                    hit = self._build_wave(wave)
                    if tr.enabled:
                        sargs.update(
                            members=len(wave), template_hit=hit,
                            commit_scatter_passes=(
                                self._mux.loop.commit_scatter_passes
                            ),
                        )
            if self._mux is None:
                return []
            self._admit_ready = False
        elif self._admit_ready and self._queue:
            # streaming admission: seed queued jobs into regions freed by
            # the completions (or preemptions) of the previous step — a
            # region can only free at those events, so skip the scan on
            # every other epoch
            with tr.span("admit", "service", queued=len(self._queue)):
                self._admit_queued()
            self._admit_ready = False
        done = self._mux.step()
        if done:
            self._admit_ready = True
            with tr.span("observe", "service", jobs=len(done)):
                self._observe_completions(done)
        # preemption (DESIGN.md §16): the step just crossed a chunk
        # boundary, the only place a region can yield.  Seat what free
        # regions absorb first — a free region always beats evicting work
        # — then ask admission who must yield for whoever is still stuck.
        if self.preemption and self._queue and self._mux.live:
            with tr.span("admit", "service", queued=len(self._queue)):
                self._admit_queued()
            if self._queue:
                with tr.span("preempt", "service", queued=len(self._queue)):
                    victims = self.admission.plan_preemptions(
                        self._mux.running_handles(), self._queue
                    )
                    for v in victims:
                        if self._mux.preempt(v):
                            self.admission.note_preempted(v)
                            self._observe_preemption(v)
                            self._queue.append(v)
                            self._admit_ready = True
        return done

    def _build_wave(self, wave: List[JobHandle]) -> Optional[bool]:
        """Build the wave's driver into ``self._mux``; returns whether a
        cached wave template served it (None for the host engine, which
        keeps no templates)."""
        hit = None
        if self.engine in ("device", "sharded"):
            # seat members in canonical order so a permutation of an
            # earlier wave lands on the same slot layout as its cached
            # template (the key is canonical too); each job's results
            # attach to its own handle, so no un-permuting is needed
            order = canonical_wave_order([h.job for h in wave])
            wave = [wave[i] for i in order]
            from ..core.engine import resolve_resident_dispatch

            jobs = [h.job for h in wave]
            cap = sum(h.job.quota for h in wave)

            def _peek(cand: str):
                # sticky per wave shape: a cached template's baked
                # mode wins before the controller is ever consulted,
                # so an identical consecutive wave can never retrace
                # on a flipped decision; a *new* shape falls through
                # to the controller's accumulated cross-wave window
                return self.template_cache.peek(wave_template_key(
                    jobs, cap, self.stack_depth, self.chunk,
                    dispatch=cand, megakernel=self.megakernel,
                ))

            dispatch_name = resolve_resident_dispatch(
                self.dispatch, self.controller, cap, peek=_peek
            )
            # the key is deliberately NOT a function of `shards`: a
            # sharded fleet replicates ONE per-shard wave, so the same
            # compiled template serves the solo wave and every P
            key = wave_template_key(
                jobs, cap,
                self.stack_depth, self.chunk,
                dispatch=dispatch_name,
                megakernel=self.megakernel,
            )
            tpl = self.template_cache.lookup(key)
            hit = tpl is not None
            self._observe_template_cache(hit=hit)
            if self.engine == "sharded":
                from ..distributed.fleet import ShardedFleet

                self._mux = ShardedFleet(
                    wave,
                    shards=self.shards,
                    dispatch=dispatch_name,
                    stack_depth=self.stack_depth,
                    chunk=self.chunk,
                    placement=self.placement,
                    placement_controller=self.placement_controller,
                    rebalance=self.rebalance,
                    collect_stats=self.collect_stats,
                    stats_factory=self._sharded_stats_factory(),
                    template=tpl,
                    megakernel=self.megakernel,
                    megakernel_impl=self.megakernel_impl,
                    tracer=self.tracer,
                    controller=self.controller,
                    chunk_controller=self.chunk_controller,
                    queue_probe=self._queue_probe,
                    mesh=self.mesh,
                )
                tpl_built = self._mux.template
                # the whole queue streams into the fleet's placement
                # queues up front: the anchor wave sized ONE shard's
                # layout, the other P-1 shards start vacant and fill
                # from here (and from later submits via streaming
                # admission)
                still = [
                    h for h in self._queue if not self._mux.admit(h)
                ]
                self._queue = still
            else:
                self._mux = DeviceMultiplexer(
                    wave,
                    dispatch=dispatch_name,
                    stack_depth=self.stack_depth,
                    chunk=self.chunk,
                    collect_stats=self.collect_stats,
                    stats_factory=self._stats_factory(),
                    template=tpl,
                    megakernel=self.megakernel,
                    megakernel_impl=self.megakernel_impl,
                    tracer=self.tracer,
                    controller=self.controller,
                    chunk_controller=self.chunk_controller,
                    queue_probe=self._queue_probe,
                )
                tpl_built = WaveTemplate(
                    key=key,
                    program=self._mux.program,
                    slots=self._mux.slots,
                    loop=self._mux.loop,
                )
            if tpl is None:
                self.template_cache.store(
                    WaveTemplate(
                        key=key,
                        program=tpl_built.program,
                        slots=tpl_built.slots,
                        loop=tpl_built.loop,
                    )
                )
        else:
            self._mux = EpochMultiplexer(
                wave,
                dispatch=self.dispatch,
                coalesce=self.coalesce,
                pop_policy=self.pop_policy,
                gang=self.gang,
                collect_stats=self.collect_stats,
                stats_factory=self._stats_factory(),
                rank_fn=self._rank_fn,
                tracer=self.tracer,
                controller=self.controller,
            )
        return hit

    def _admit_queued(self) -> int:
        """Try to seat queued jobs into free regions of the live wave, in
        admission order, consuming class rate tokens per seat."""
        seated = 0
        still: List[JobHandle] = []
        for h in self.admission.order(self._queue):
            if (
                self.admission.has_token(h)
                and self._mux.admit(h)
                and self.admission.allow(h)
            ):
                seated += 1
            else:
                still.append(h)
        still.sort(key=lambda h: h.job_id)
        self._queue = still
        return seated

    def _take_wave(self) -> List[JobHandle]:
        """Assemble the next wave — delegated to the admission layer.

        :meth:`~repro.service.admission.AdmissionController.take_wave`
        packs first-fit in admission order (priority desc, EDF, FIFO)
        under the capacity / max_jobs / dtype / class-share budgets.
        With no priorities, deadlines, or class limits configured this is
        exactly the greedy FIFO first-fit this method used to inline.
        """
        wave, self._queue = self.admission.take_wave(
            self._queue, self.capacity, self.max_jobs
        )
        return wave
