"""Layered serving front door tests (DESIGN.md §16).

The load-bearing property: chunk-boundary preemption is *observationally
invisible* to the preempted job.  A job lifted into a
:class:`RegionCheckpoint` at a boundary and re-admitted later — into the
same wave, a different wave, a different engine — must finish with the
exact solo result and solo-comparable stats of an uninterrupted run.
Around that: admission ordering/rate/share policy, preemption planning
strictness, the async submit/stream surface, lifecycle-clock injection,
template-cache LRU accounting, and the virtual-clock loadgen gate.
"""
import asyncio
import functools

import numpy as np
import pytest

from repro.apps import fib
from repro.core import HostEngine
from repro.service import (
    AdmissionController,
    AdmissionError,
    DeviceMultiplexer,
    EpochMultiplexer,
    Job,
    JobHandle,
    JobService,
    JobStatus,
    QuotaClass,
    WaveTemplate,
    WaveTemplateCache,
)
from repro.distributed.fleet import ShardedFleet

QUOTA = 256
FIB_N = 9


@functools.lru_cache(maxsize=None)
def _solo():
    heap, value, stats = HostEngine(fib.PROGRAM, capacity=QUOTA).run(
        fib.initial(FIB_N)
    )
    return np.asarray(value), stats


def _handle(i, n=FIB_N, **kw):
    return JobHandle(i, Job(fib.PROGRAM, fib.initial(n), quota=QUOTA), **kw)


def _mux(engine, handles, dispatch="masked"):
    if engine == "host":
        return EpochMultiplexer(handles, dispatch=dispatch)
    if engine == "device":
        return DeviceMultiplexer(handles, dispatch=dispatch, chunk=2)
    return ShardedFleet(handles, shards=2, dispatch=dispatch, chunk=2,
                        mesh=None)


# ------------------------------------------- preempt/resume bit-identity
@pytest.mark.parametrize("engine", ["host", "device", "sharded"])
@pytest.mark.parametrize("dispatch", ["masked", "gather"])
def test_preempt_resume_bit_identical(engine, dispatch):
    """Preempt mid-flight, re-admit into a *fresh* wave, compare the
    result and every solo-comparable stat against an uninterrupted solo
    HostEngine run."""
    if engine == "host" and dispatch == "gather":
        pytest.skip("gather is a resident-dispatch mode")
    solo_value, solo_stats = _solo()

    h = _handle(0)
    m1 = _mux(engine, [h], dispatch)
    for _ in range(3):
        m1.step()
    assert m1.preempt(h)
    assert h.status is JobStatus.PREEMPTED
    assert h.preemptions == 1
    assert h.checkpoint is not None
    # the checkpointed job re-queues into a *different* wave and resumes
    h2 = _handle(1)
    m2 = _mux(engine, [h2, h], dispatch)
    m2.run()
    assert h.status is JobStatus.DONE
    got = h.result.stats
    assert got.epochs == solo_stats.epochs
    assert got.tasks_executed == solo_stats.tasks_executed
    assert got.total_forks == solo_stats.total_forks
    assert got.peak_tv_slots == solo_stats.peak_tv_slots
    np.testing.assert_array_equal(np.asarray(h.result.value), solo_value)
    # the rider was untouched
    assert h2.status is JobStatus.DONE
    np.testing.assert_array_equal(np.asarray(h2.result.value), solo_value)


def test_preempt_resume_cross_engine():
    """Checkpoints are engine-agnostic: capture on the device driver,
    resume on the host driver (and vice versa), same solo bits."""
    solo_value, solo_stats = _solo()
    for first, second in (("device", "host"), ("host", "device")):
        h = _handle(0)
        m1 = _mux(first, [h])
        for _ in range(3):
            m1.step()
        assert m1.preempt(h)
        m2 = _mux(second, [_handle(1), h])
        m2.run()
        assert h.result.stats.solo_dict() == solo_stats_dict(solo_stats)
        np.testing.assert_array_equal(
            np.asarray(h.result.value), solo_value
        )


def solo_stats_dict(stats):
    return {
        "epochs": stats.epochs,
        "tasks_executed": stats.tasks_executed,
        "total_forks": stats.total_forks,
        "peak_tv_slots": stats.peak_tv_slots,
    }


def test_preempt_not_running_is_false():
    h = _handle(0)
    m = _mux(device := "device", [h])
    other = _handle(1)
    assert not m.preempt(other)  # never seated here
    m.run()
    assert not m.preempt(h)  # already finished


# ------------------------------------- service-level priority preemption
def test_service_priority_preempts_and_resumes():
    """A strictly-higher-priority submit evicts the running batch job at
    a chunk boundary; both finish, the victim with solo-identical bits,
    and the interactive job finishes first."""
    solo_value, solo_stats = _solo()
    svc = JobService(
        capacity=QUOTA, max_jobs=1, engine="device", chunk=2,
        classes=[QuotaClass("batch"),
                 QuotaClass("interactive", priority=10)],
    )
    lo = svc.submit(fib.PROGRAM, fib.initial(FIB_N), quota=QUOTA,
                    klass="batch")
    svc._pump()
    svc._pump()
    hi = svc.submit(fib.PROGRAM, fib.initial(7), quota=QUOTA,
                    klass="interactive", deadline=60.0)
    done = svc.drain()
    assert done[0] is hi
    assert lo.preemptions >= 1
    assert lo.status is JobStatus.DONE
    assert lo.result.stats.solo_dict() == solo_stats_dict(solo_stats)
    np.testing.assert_array_equal(np.asarray(lo.result.value), solo_value)
    assert svc.admission.preempted == {"batch": lo.preemptions}


def test_service_preempt_readmit_zero_retrace():
    """A preempt + re-admit cycle of known wave shapes reuses the cached
    compiled templates — trace_count must not move."""
    svc = JobService(
        capacity=QUOTA, max_jobs=1, engine="device", chunk=2,
        classes=[QuotaClass("batch"),
                 QuotaClass("interactive", priority=10)],
    )
    lo = svc.submit(fib.PROGRAM, fib.initial(FIB_N), quota=QUOTA,
                    klass="batch")
    svc._pump()
    svc._pump()
    before = svc.trace_count
    hi = svc.submit(fib.PROGRAM, fib.initial(7), quota=QUOTA,
                    klass="interactive")
    svc.drain()
    assert lo.preemptions >= 1
    assert svc.trace_count == before
    assert hi.status is JobStatus.DONE and lo.status is JobStatus.DONE


def test_equal_priority_never_preempts():
    """Strict-priority rule: equal priority can never evict (prevents
    requeue ping-pong)."""
    svc = JobService(
        capacity=QUOTA, max_jobs=1, engine="device", chunk=2,
    )
    a = svc.submit(fib.PROGRAM, fib.initial(FIB_N), quota=QUOTA)
    svc._pump()
    b = svc.submit(fib.PROGRAM, fib.initial(FIB_N), quota=QUOTA)
    done = svc.drain()
    assert a.preemptions == 0 and b.preemptions == 0
    assert done[0] is a  # FIFO preserved


# ------------------------------------------------------ admission policy
def _jh(i, quota=64, **kw):
    return JobHandle(
        i, Job(fib.PROGRAM, fib.initial(5), quota=quota), **kw
    )


def test_admission_order_priority_then_edf_then_fifo():
    adm = AdmissionController(
        classes=[QuotaClass("hi", priority=5)], clock=lambda: 0.0
    )
    a = _jh(0)                                  # default, no deadline
    b = _jh(1, deadline=10.0)                   # default, EDF first
    c = _jh(2, klass="hi")                      # class priority wins
    d = _jh(3, priority=9)                      # explicit beats class
    assert adm.order([a, b, c, d]) == [d, c, b, a]


def test_admission_default_degenerates_to_fifo():
    """No priorities/deadlines/limits: take_wave == the old greedy FIFO
    first-fit."""
    adm = AdmissionController(clock=lambda: 0.0)
    hs = [_jh(i, quota=64) for i in range(5)]
    wave, left = adm.take_wave(hs, capacity=128, max_jobs=8)
    assert [h.job_id for h in wave] == [0, 1]
    assert [h.job_id for h in left] == [2, 3, 4]


def test_admission_class_share_caps_wave_fraction():
    adm = AdmissionController(
        classes=[QuotaClass("greedy", share=0.5)], clock=lambda: 0.0
    )
    hs = [_jh(i, quota=64, klass="greedy") for i in range(4)]
    hs.append(_jh(4, quota=64))
    wave, left = adm.take_wave(hs, capacity=256, max_jobs=8)
    # greedy may hold at most 128 of 256 slots: two jobs
    assert [h.job_id for h in wave] == [0, 1, 4]
    assert [h.job_id for h in left] == [2, 3]


def test_admission_rate_limit_token_bucket():
    t = [0.0]
    adm = AdmissionController(
        classes=[QuotaClass("limited", rate=1.0, burst=1.0)],
        clock=lambda: t[0],
    )
    a, b = _jh(0, klass="limited"), _jh(1, klass="limited")
    assert adm.allow(a)
    assert not adm.allow(b)       # bucket drained
    assert adm.has_token(b) is False
    t[0] = 1.5                    # refill at 1 token/s
    assert adm.has_token(b)
    assert adm.allow(b)


def test_admission_unknown_class_raises():
    svc = JobService(capacity=256)
    with pytest.raises(AdmissionError):
        svc.submit(fib.PROGRAM, fib.initial(5), quota=64, klass="nope")


def test_plan_preemptions_strictly_lower_priority_only():
    adm = AdmissionController(
        classes=[QuotaClass("hi", priority=5),
                 QuotaClass("pinned", priority=0, preemptible=False)],
        clock=lambda: 0.0,
    )
    run_lo = _jh(0)
    run_pinned = _jh(1, klass="pinned")
    run_hi = _jh(2, klass="hi")
    for h in (run_lo, run_pinned, run_hi):
        h.mark_running()
    want = _jh(3, klass="hi")
    victims = adm.plan_preemptions([run_lo, run_pinned, run_hi], [want])
    # only the preemptible strictly-lower-priority job yields
    assert victims == [run_lo]
    # an equal-priority waiter gets nothing
    assert adm.plan_preemptions([run_hi], [_jh(4, klass="hi")]) == []


def test_deadline_scoreboard_and_slack():
    t = [0.0]
    adm = AdmissionController(clock=lambda: t[0])
    h = _jh(0, deadline=5.0, clock=lambda: t[0])
    assert adm.deadline_slack([h]) == 5.0
    t[0] = 2.0
    assert adm.deadline_slack([h]) == 3.0
    h.mark_running()
    t[0] = 4.0
    h.mark_finished()
    assert adm.note_finished(h) is True
    assert adm.miss_ratio() == 0.0
    h2 = _jh(1, deadline=1.0, clock=lambda: t[0])
    h2.mark_running()
    t[0] = 9.0
    h2.mark_finished()
    assert adm.note_finished(h2) is False
    assert adm.miss_ratio() == 0.5


# ------------------------------------------------------- lifecycle clock
def test_handle_clock_injectable_and_monotonic():
    """Lifecycle stamps come from the handle's injected clock — virtual
    time in tests/loadgen, time.monotonic by default — and are monotone
    through the full lifecycle including preemption."""
    t = [10.0]
    h = _jh(0, clock=lambda: t[0])
    assert h.submitted_at == 10.0
    t[0] = 11.0
    h.mark_running()
    assert h.started_at == 11.0
    t[0] = 9.0  # a broken clock would violate monotonicity
    t[0] = 12.0
    h.mark_finished()
    assert h.finished_at == 12.0
    assert h.submitted_at <= h.started_at <= h.finished_at
    assert h.queue_wait == 1.0
    assert h.run_time == 1.0


def test_service_clock_threads_to_handles():
    t = [0.0]
    svc = JobService(capacity=256, clock=lambda: t[0])
    t[0] = 3.0
    h = svc.submit(fib.PROGRAM, fib.initial(5), quota=64, deadline=2.0)
    assert h.submitted_at == 3.0
    assert h.deadline == 5.0  # relative deadline, absolute stamp
    assert svc.admission.clock() == 3.0


# ------------------------------------------------- template cache LRU
class _FakeLoop:
    def __init__(self, traces):
        self.trace_count = traces


def _tpl(key, traces=1):
    return WaveTemplate(
        key=(key,), program=None, slots=(), loop=_FakeLoop(traces)
    )


def test_wave_template_cache_lru_evicts_oldest_first():
    cache = WaveTemplateCache(max_entries=16)
    for i in range(17):
        cache.store(_tpl(i))
    assert cache.evictions == 1
    assert cache.peek((0,)) is None          # oldest evicted
    assert cache.peek((1,)) is not None
    # touching an entry protects it from the next eviction
    cache.lookup((1,))
    cache.store(_tpl(99))
    assert cache.evictions == 2
    assert cache.peek((1,)) is not None      # recently used: survives
    assert cache.peek((2,)) is None          # next-oldest went instead


def test_wave_template_cache_eviction_keeps_trace_count_monotone():
    cache = WaveTemplateCache(max_entries=16)
    seen = []
    for i in range(40):
        cache.store(_tpl(i, traces=2))
        seen.append(cache.trace_count)
    assert cache.evictions == 40 - 16
    assert seen == sorted(seen)
    assert cache.trace_count == 40 * 2       # evicted traces still count
    assert len(cache) == 16


def test_service_exports_eviction_metric():
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    svc = JobService(capacity=256, engine="device", chunk=2, metrics=reg,
                     template_cache=WaveTemplateCache(max_entries=16))
    svc.submit(fib.PROGRAM, fib.initial(5), quota=64)
    svc.drain()
    assert reg.value("trees_wave_template_evictions") == 0


# ----------------------------------------------------------- async API
def test_submit_async_gather_and_stream():
    solo_value, _ = _solo()

    async def main():
        svc = JobService(capacity=2 * QUOTA, max_jobs=2)
        f1 = svc.submit_async(fib.PROGRAM, fib.initial(FIB_N), quota=QUOTA)
        f2 = svc.submit_async(fib.PROGRAM, fib.initial(FIB_N), quota=QUOTA)
        r1, r2 = await asyncio.gather(f1.result(), f2.result())
        np.testing.assert_array_equal(np.asarray(r1.value), solo_value)
        np.testing.assert_array_equal(np.asarray(r2.value), solo_value)
        assert f1.done() and f2.done()
        # stream_results drains later submissions as they finish
        svc.submit(fib.PROGRAM, fib.initial(6), quota=64)
        svc.submit(fib.PROGRAM, fib.initial(5), quota=64)
        seen = [h async for h in svc.stream_results()]
        assert len(seen) == 2
        assert all(h.status is JobStatus.DONE for h in seen)

    asyncio.run(main())


def test_async_failure_raises_through_future():
    from repro.service import JobFailure

    async def main():
        svc = JobService(capacity=64, max_jobs=1)
        # fib(12) needs ~465 slots: overflows a 64-slot region
        fut = svc.submit_async(fib.PROGRAM, fib.initial(12), quota=64)
        with pytest.raises(JobFailure):
            await fut

    asyncio.run(main())


# ------------------------------------------------ controllers (§16 knobs)
def test_chunk_controller_deadline_slack_shrinks_k():
    from repro.control.controller import ChunkController

    ctl = ChunkController(k_init=8, tight_slack_s=0.1)
    assert ctl.observe(completions=1, queued=0) == 8       # hold
    assert ctl.observe(1, 0, deadline_slack=0.05) == 4     # tight: shrink
    assert ctl.observe(1, 0, deadline_slack=10.0) == 4     # loose: hold
    ctl2 = ChunkController(k_init=1)
    assert ctl2.observe(0, 0, deadline_slack=0.01) == 1    # floor holds


def test_placement_controller_policy_mix():
    from repro.control.controller import PlacementController

    ctl = PlacementController(window=8)
    # homogeneous, balanced -> round_robin
    for _ in range(4):
        ctl.observe_job(1)
    assert ctl.choose() == "round_robin"
    # diverse types, balanced -> sticky (affinity wins)
    for k in range(8):
        ctl.observe_job(k)
    assert ctl.choose() == "sticky"
    # imbalanced -> least_loaded overrides everything
    ctl.observe_imbalance(util_spread=0.5, queue_spread=0)
    assert ctl.choose() == "least_loaded"
    assert set(ctl.decisions) == {"round_robin", "sticky", "least_loaded"}


def test_sharded_fleet_auto_placement_runs():
    h = _handle(0)
    fl = ShardedFleet([h], shards=2, chunk=2, placement="auto", mesh=None)
    fl.admit(_handle(1))
    done = fl.run()
    assert len(done) == 2
    assert all(x.status is JobStatus.DONE for x in done)
    assert sum(fl._pctl.decisions.values()) == 2


# -------------------------------------------------------------- loadgen
def test_loadgen_priority_beats_fifo_and_is_deterministic(tmp_path):
    import json
    import subprocess
    import sys
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = tmp_path / "lg.json"
    cmd = [
        sys.executable, os.path.join(repo, "benchmarks", "loadgen.py"),
        "--jobs", "24", "--json", str(out),
    ]
    subprocess.run(cmd, check=True, env=env, cwd=str(tmp_path))
    doc = json.loads(out.read_text())
    rows = {r["name"]: r for r in doc["rows"]}
    sys.path.insert(0, os.path.join(repo, "benchmarks"))
    try:
        from check import parse_derived, run_latency_check
    finally:
        sys.path.pop(0)
    fifo = parse_derived(rows["loadgen_fifo"]["derived"])
    prio = parse_derived(rows["loadgen_priority"]["derived"])
    assert int(fifo["misses_interactive"]) > 0
    assert (
        int(prio["misses_interactive"]) < int(fifo["misses_interactive"])
    )
    # the gate agrees, self-contained and vs itself as baseline
    assert run_latency_check(str(out)) == 0
    assert run_latency_check(str(out), str(out)) == 0
