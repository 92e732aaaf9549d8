"""Sharded fleet execution across a device mesh (DESIGN.md §15).

The contract under test: a :class:`~repro.distributed.ShardedFleet` is P
independent device waves advancing together — ONE fused launch + ONE
stacked readback per collective chunk — and per-job results stay
bit-identical to a solo ``HostEngine.run`` at every P, every placement
policy, and every migration history.  Work counters are *conserved*:
sharding (and chunk-boundary rebalancing) moves jobs between shards but
the summed per-shard ``tasks_executed``/``total_forks`` equal the solo
totals exactly.  The shard_map mesh path (real devices, exercised in a
subprocess with 8 forced host devices) and the single-device vmap
simulation (``mesh=None``) produce the same bits.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import solo_run

from repro.apps import fib, get_fleet
from repro.core import HostEngine
from repro.distributed import ShardedFleet
from repro.service import Job, JobHandle, JobService

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


def _solo(case, quota):
    return solo_run(case.name, quota)


def _handles(fleet, tag=""):
    return [
        JobHandle(i, Job(c.program, c.initial, heap_init=dict(c.heap_init),
                         quota=q, name=c.name + tag))
        for i, (c, q) in enumerate(fleet)
    ]


def _assert_solo_identical(handle, solo):
    sh, sv, ss = solo
    r = handle.result
    assert r is not None, (handle.job.name, handle.error)
    np.testing.assert_array_equal(np.asarray(r.value), np.asarray(sv))
    assert set(r.heap) == set(sh)
    for k in sh:
        np.testing.assert_array_equal(
            np.asarray(r.heap[k]), np.asarray(sh[k]), err_msg=k
        )
    assert r.stats.epochs == ss.epochs
    assert r.stats.tasks_executed == ss.tasks_executed
    assert r.stats.total_forks == ss.total_forks
    assert r.stats.peak_tv_slots == ss.peak_tv_slots


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_fleet_bit_identical_to_solo_across_p(shards):
    """Every job's value block, heap, and solo-comparable stats match the
    solo run exactly, whatever P (the mesh=None vmap simulation)."""
    fleet = get_fleet("mixed3")
    solo = {c.name: _solo(c, q) for c, q in fleet}

    anchors = _handles(fleet)
    fl = ShardedFleet(anchors, shards=shards, chunk=4, mesh=None)
    extra = _handles(fleet, "_b") + _handles(fleet, "_c")
    for h in extra:
        assert fl.admit(h)
    done = fl.run()
    assert len(done) == len(anchors) + len(extra)
    for h in done:
        base = h.job.name.replace("_b", "").replace("_c", "")
        _assert_solo_identical(h, solo[base])


@pytest.mark.parametrize("shards", [2, 4])
def test_work_conservation_across_shards(shards):
    """Summed per-shard tasks/forks equal the solo totals exactly, and the
    fleet's collective V_inf is one dispatch + one readback per step —
    not per shard."""
    fleet = get_fleet("mixed3")
    reps = 3
    solo_tasks = solo_forks = 0
    for c, q in fleet:
        _, _, s = _solo(c, q)
        solo_tasks += reps * s.tasks_executed
        solo_forks += reps * s.total_forks

    anchors = _handles(fleet)
    fl = ShardedFleet(anchors, shards=shards, chunk=4,
                      placement="least_loaded", mesh=None)
    for tag in ("_b", "_c")[: reps - 1]:
        for h in _handles(fleet, tag):
            assert fl.admit(h)
    fl.run()

    per_shard = fl.shard_stats()
    assert len(per_shard) == shards
    assert sum(s.tasks_executed for s in per_shard) == solo_tasks
    assert sum(s.total_forks for s in per_shard) == solo_forks
    total = fl.stats()
    assert total.tasks_executed == solo_tasks
    assert total.total_forks == solo_forks
    # collective accounting: the whole point of the fleet step
    assert total.dispatches == fl.collective_steps
    assert total.scalar_transfers == fl.collective_steps


def test_rebalance_migrates_queued_jobs_off_hot_shards():
    """Sticky placement pins every fib job to one shard; with rebalancing
    on, boundary migration drains the hot shard's queue through other
    shards' free regions — and the results stay solo-identical."""
    quota = 256
    case_jobs = [
        Job(fib.PROGRAM, fib.initial(9), quota=quota, name=f"fib#{i}")
        for i in range(8)
    ]
    _, sv, ss = HostEngine(fib.PROGRAM, capacity=quota).run(fib.initial(9))

    def build(rebalance):
        handles = [JobHandle(i, j) for i, j in enumerate(case_jobs[:1])]
        fl = ShardedFleet(handles, shards=4, chunk=2, placement="sticky",
                          rebalance=rebalance, mesh=None)
        for i, j in enumerate(case_jobs[1:], start=1):
            assert fl.admit(JobHandle(i, j))
        return fl

    fl = build(rebalance=True)
    done = fl.run()
    assert len(done) == 8
    assert fl.migrations > 0, (
        "sticky placement queued every job on one shard; rebalancing "
        "must have moved some to idle shards"
    )
    for h in done:
        np.testing.assert_array_equal(
            np.asarray(h.result.value), np.asarray(sv)
        )
        assert h.result.stats.tasks_executed == ss.tasks_executed

    pinned = build(rebalance=False)
    pinned.run()
    assert pinned.migrations == 0
    # affinity respected: only the sticky shard ever executed anything
    worked = [p for p, s in enumerate(pinned.shard_stats())
              if s.tasks_executed > 0]
    assert len(worked) == 1


def test_placement_policies():
    """round_robin cycles shards; sticky maps equal-structure jobs to one
    shard; least_loaded prefers empty shards; incompatible jobs are
    refused (left for the service queue)."""
    fleet = get_fleet("mixed3")
    anchors = _handles(fleet)
    fl = ShardedFleet(anchors, shards=3, chunk=4, placement="round_robin",
                      mesh=None)
    # anchors placed round-robin: one wave's worth spread over 3 shards
    assert sum(len(q) for q in fl._pending) == len(anchors)
    assert [len(q) for q in fl._pending] == [1, 1, 1]

    sticky = ShardedFleet(_handles(fleet), shards=3, chunk=4,
                          placement="sticky", mesh=None)
    a = _handles(fleet, "_a")
    b = _handles(fleet, "_b")
    for h in a + b:
        assert sticky.admit(h)
    # same structure + quota -> same shard, always
    for ha, hb in zip(a, b):
        pa = [p for p, q in enumerate(sticky._pending) if ha in q]
        pb = [p for p, q in enumerate(sticky._pending) if hb in q]
        assert pa == pb

    # a job whose program structure matches no slot is refused
    alien = Job(
        get_fleet("fib_fleet")[0][0].program,
        get_fleet("fib_fleet")[0][0].initial,
        quota=1 << 20, name="too-big",
    )
    assert not fl.admit(JobHandle(99, alien))


def test_zero_retrace_under_migration_and_p_switch():
    """A sharded service reuses ONE compiled chunk template across waves,
    across migrations, and across shard counts: trace_count is flat after
    the first wave — the template key is deliberately not a function of
    P, and migration reseeds through the existing reseed path."""
    fleet = get_fleet("mixed3")

    def submit_all(svc, reps):
        for r in range(reps):
            for c, q in fleet:
                svc.submit_case(c, quota=q, name=f"{c.name}#{r}")

    svc = JobService(
        capacity=sum(q for _, q in fleet), engine="sharded", shards=2,
        chunk=4, max_jobs=len(fleet), placement="sticky", mesh=None,
    )
    submit_all(svc, 3)  # sticky + heterogenous -> migrations happen
    svc.drain()
    traced = svc.trace_count
    assert traced > 0
    assert svc._mux.migrations >= 0  # fleet drove to completion

    submit_all(svc, 2)  # identical consecutive wave shape
    svc.drain()
    assert svc.trace_count == traced, (
        "an identical consecutive sharded wave must not retrace"
    )

    # same template cache serves a different P: the chunk template is
    # NOT rebuilt (cache hit — same fused program, slots, and loop), the
    # only new tracing is the fleet wrapper for the new batch shape
    # (vmap/shard_map re-enters the cached body once per P), and
    # consecutive waves at the new P are again zero-retrace
    svc4 = JobService(
        capacity=sum(q for _, q in fleet), engine="sharded", shards=4,
        chunk=4, max_jobs=len(fleet),
        template_cache=svc.template_cache, mesh=None,
    )
    submit_all(svc4, 2)
    svc4.drain()
    assert svc4.template_cache.hits >= 1, (
        "switching shard counts must reuse the cached chunk template"
    )
    assert svc4.template_cache.misses == 1  # only the very first wave built
    traced4 = svc4.trace_count
    submit_all(svc4, 2)
    svc4.drain()
    assert svc4.trace_count == traced4, (
        "an identical consecutive wave at the new P must not retrace"
    )


def test_sharded_service_streams_and_matches_solo():
    """The service front door: engine='sharded' drains a many-rep queue
    through placement + streaming admission, results solo-identical."""
    fleet = get_fleet("mixed3")
    solo = {c.name: _solo(c, q) for c, q in fleet}
    svc = JobService(
        capacity=sum(q for _, q in fleet), engine="sharded", shards=4,
        chunk=4, max_jobs=len(fleet), placement="least_loaded", mesh=None,
    )
    hs = []
    for r in range(4):
        for c, q in fleet:
            hs.append(svc.submit_case(c, quota=q, name=f"{c.name}#{r}"))
    done = svc.drain()
    assert len(done) == len(hs)
    for h in hs:
        _assert_solo_identical(h, solo[h.job.name.split("#")[0]])


def test_sharded_engine_validation():
    with pytest.raises(ValueError, match="shards"):
        JobService(engine="device", shards=2)
    with pytest.raises(ValueError, match="placement"):
        JobService(engine="sharded", shards=2, placement="random")
    with pytest.raises(ValueError, match="shards"):
        JobService(engine="sharded", shards=0)


def test_fleet_mesh_fallback_and_shard_map_path():
    """make_fleet_mesh refuses more shards than devices (the vmap
    simulation is asked for by name, ``mesh=None``, and the megakernel
    refuses it); the real shard_map path runs in a subprocess with 8
    forced host devices, with every restacked carry spread over the 8
    devices, and must be bit-identical to solo."""
    from repro.launch.mesh import make_fleet_mesh

    assert make_fleet_mesh(1) is None  # P=1: never worth a mesh
    import jax

    if len(jax.devices()) < 64:
        with pytest.raises(ValueError, match="devices"):
            make_fleet_mesh(64)
        with pytest.raises(ValueError, match="devices"):
            ShardedFleet(_handles(get_fleet("mixed3")), shards=64)
        with pytest.raises(ValueError, match="devices"):
            JobService(engine="sharded", shards=64)
    with pytest.raises(ValueError):
        make_fleet_mesh(0)
    mega = ShardedFleet(_handles(get_fleet("mixed3")), shards=2, chunk=4,
                        mesh=None, megakernel=True,
                        megakernel_impl="interpret")
    with pytest.raises(ValueError, match="megakernel"):
        mega.step()

    script = """
import numpy as np
import jax
assert jax.device_count() == 8, jax.device_count()
from repro.apps import get_fleet
from repro.core import HostEngine
from repro.distributed import ShardedFleet
from repro.service import Job, JobHandle

fleet = get_fleet("mixed3")
solo = {}
for c, q in fleet:
    eng = HostEngine(c.program, capacity=q)
    solo[c.name] = eng.run(c.initial, heap_init=dict(c.heap_init) or None)

handles = [
    JobHandle(i, Job(c.program, c.initial, heap_init=dict(c.heap_init),
                     quota=q, name=c.name))
    for i, (c, q) in enumerate(fleet)
]
fl = ShardedFleet(handles, shards=8, chunk=4)
assert fl.mesh is not None, "8 devices must yield a real fleet mesh"
launches = []
run_chunk_fleet = fl._loop.run_chunk_fleet
def spy(carry, limits, **kw):
    launches.append(min(
        len(x.sharding.device_set) for x in jax.tree.leaves(carry)
    ))
    return run_chunk_fleet(carry, limits, **kw)
fl._loop.run_chunk_fleet = spy
for tag in ("_b", "_c"):
    for i, (c, q) in enumerate(fleet):
        assert fl.admit(JobHandle(100 + i, Job(
            c.program, c.initial, heap_init=dict(c.heap_init),
            quota=q, name=c.name + tag)))
done = fl.run()
assert len(done) == 9, len(done)
for h in done:
    base = h.job.name.replace("_b", "").replace("_c", "")
    sh, sv, ss = solo[base]
    np.testing.assert_array_equal(np.asarray(h.result.value),
                                  np.asarray(sv))
    for k in sh:
        np.testing.assert_array_equal(np.asarray(h.result.heap[k]),
                                      np.asarray(sh[k]))
    assert h.result.stats.tasks_executed == ss.tasks_executed
    assert h.result.stats.epochs == ss.epochs
st = fl.stats()
assert st.dispatches == fl.collective_steps
assert launches and min(launches) == 8, launches
print("SHARD_MAP_OK", fl.collective_steps)
"""
    env = {
        "PYTHONPATH": SRC,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",
        "PATH": "/usr/bin:/bin",
        "HOME": "/tmp",
    }
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SHARD_MAP_OK" in proc.stdout
