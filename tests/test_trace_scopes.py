"""Runtime spans and epoch-phase scopes in the profiler trace (DESIGN.md §13).

* the compiled resident loop names each phase of the epoch body in its
  op-name metadata (``trees.pop`` ... ``trees.push``), and the scopes add
  no trace: an identical second wave reuses the template;
* a chunked device service traced under ``jax.profiler`` with a
  ``SpanTracer`` leaves bare-named ``trees:`` host events for the chunk
  launch, its readback and settle, each finished region and each reseed;
  with ``NULL_TRACER`` it leaves none;
* ``bench/opscopes.py`` reads each device op's op-name path from a trace
  recorded on a v5e chip, and the five new per-layer readers compute what
  they should from a synthetic digest and op list.
"""
from __future__ import annotations

import logging
import lzma
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.apps import fib
from repro.obs import NULL_TRACER, SpanTracer
from repro.service import JobService
from repro.service.jobs import Job, JobHandle
from repro.service.multiplexer import DeviceMultiplexer

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, opscopes, tracecut  # noqa: E402

DATA = ROOT / "bench" / "tests" / "data"
PHASES = ("trees.pop", "trees.pack", "trees.tasks", "trees.commit",
          "trees.push")
# traced builder bodies of the two-member fib wave template below, as the
# loop traced them before the phases were scoped (the scopes add none)
TRACES_PER_TEMPLATE = 5


def _fib_handles(ns, quota=256):
    return [
        JobHandle(job_id=i, job=Job(program=fib.PROGRAM,
                                    initial=fib.initial(n), quota=quota))
        for i, n in enumerate(ns)
    ]


# ------------------------------------------------------ (a) scopes in HLO
@pytest.mark.parametrize("dispatch", ["masked", "gather"])
def test_resident_loop_hlo_names_every_phase(dispatch):
    mux = DeviceMultiplexer(_fib_handles([6, 7]), dispatch=dispatch,
                            chunk=2)
    mux.step()
    (loop,) = mux.loop._resident_cache.values()
    hlo = loop.lower(mux._carry, jnp.asarray(0, jnp.int32)).compile() \
        .as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    found = {opscopes.phase(p) for p in paths} - {None}
    want = set(PHASES) - ({"trees.pack"} if dispatch == "masked" else set())
    assert want <= found, sorted(found)


@pytest.mark.parametrize("dispatch", ["masked", "gather"])
def test_scopes_add_no_trace(dispatch):
    svc = JobService(capacity=512, max_jobs=2, engine="device", chunk=2,
                     dispatch=dispatch)
    for _ in range(2):
        svc.submit(fib.PROGRAM, fib.initial(8), quota=256)
        svc.submit(fib.PROGRAM, fib.initial(9), quota=256)
        assert all(h.status.value == "done" for h in svc.drain())
        assert svc.template_cache.trace_count == TRACES_PER_TEMPLATE
    assert svc.template_cache.hits == 1


def test_wave_build_span_carries_the_commit_plan():
    """Each wave's build span names the commit plan it runs (a new
    template's read from an abstract trace, a cached one's from its traced
    loop) and a retired wave's stats line logs it; neither adds a trace."""
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    logger = logging.getLogger("repro.engine")
    keep, level = Keep(), logger.level
    logger.addHandler(keep)
    logger.setLevel(logging.INFO)
    tr = SpanTracer()
    try:
        svc = JobService(capacity=512, max_jobs=2, engine="device", chunk=2,
                         dispatch="gather", tracer=tr)
        for _ in range(2):
            svc.submit(fib.PROGRAM, fib.initial(8), quota=256)
            svc.submit(fib.PROGRAM, fib.initial(9), quota=256)
            assert all(h.status.value == "done" for h in svc.drain())
    finally:
        logger.removeHandler(keep)
        logger.setLevel(level)
    # fib+fib: 2 fork-site indices x 5 columns, the join's task and args,
    # the parent pointers, the emits and the finished entries
    passes = [e["args"].get("commit_scatter_passes")
              for e in tr.events_named("wave_build")]
    assert passes == [16, 16]
    assert svc._mux.loop.commit_scatter_passes == 16
    assert svc.template_cache.trace_count == TRACES_PER_TEMPLATE
    (line,) = [ln for ln in lines if ln.startswith("run ")]
    assert "driver=device" in line and "commit_scatter_passes=16" in line


# --------------------------------------------- (b) spans in the profiler
def _profiled_stream(tmp_path, tracer):
    """fib(8) and fib(10) share a wave of two regions; fib(7) waits and
    is reseeded into fib(8)'s region when that frees mid-wave."""
    svc = JobService(capacity=512, max_jobs=2, engine="device", chunk=2,
                     dispatch="gather", tracer=tracer)
    for n in (8, 10, 7):
        svc.submit(fib.PROGRAM, fib.initial(n), quota=256)
    with jax.profiler.trace(str(tmp_path)):
        handles = svc.drain()
    assert all(h.status.value == "done" for h in handles)
    from jax.profiler import ProfileData

    path = tracecut.latest_trace(tmp_path)
    # the reduction's host side: a CPU trace has no device plane, so
    # ``tracecut.reduce_file`` (which needs device operations) is not
    # used here; the chip's traced runs read the same events through it
    names = [e.name for p in ProfileData.from_file(path).planes
             if not tracecut.DEVICE_PLANE.match(p.name)
             for ln in p.lines for e in ln.events
             if e.name.startswith(tracecut.HOST_PREFIXES)]
    return svc, names


def test_runtime_spans_reach_the_profiler_with_bare_names(tmp_path):
    tr = SpanTracer()
    svc, names = _profiled_stream(tmp_path / "on", tr)
    for n in ("trees:resident_chunk", "trees:readback", "trees:settle",
              "trees:finalize", "trees:reseed", "trees:wave_build",
              "trees:admit", "trees:observe", "trees:chunk"):
        assert n in names, (n, sorted(set(names)))
    assert all(re.fullmatch(r"trees:[a-z_]+", n) for n in names), names
    assert names.count("trees:finalize") == 3
    assert names.count("trees:reseed") == 1
    assert names.count("trees:readback") == svc.stats().dispatches
    # the Chrome sink records the same spans, each under its parent
    (reseed,) = tr.events_named("reseed")
    assert reseed["args"]["parent"] == "admit"
    assert reseed["args"]["quota"] == 256
    fin = tr.events_named("finalize")
    assert {e["args"]["parent"] for e in fin} == {"settle"}
    assert len({e["args"]["job_id"] for e in fin}) == 3
    # each finished job's own epochs, tasks and peak slots ride along
    jobs = {h.job_id: h.result.stats for h in svc._handles.values()}
    assert {e["args"]["job_id"]: (e["args"]["epochs"], e["args"]["tasks"],
                                  e["args"]["peak_tv_slots"])
            for e in fin} == {i: (st.epochs, st.tasks_executed,
                                  st.peak_tv_slots)
                              for i, st in jobs.items()}


def test_null_tracer_emits_no_runtime_spans(tmp_path):
    _, names = _profiled_stream(tmp_path / "off", NULL_TRACER)
    assert not [n for n in names if n.startswith("trees:")]


# -------------------------------------- (c) op-name paths of a chip trace
@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("chip") / "bots_dc.closed.xplane.pb"
    path.write_bytes(lzma.decompress(
        (DATA / "bots_dc.closed.xplane.pb.xz").read_bytes()))
    return str(path)


def test_opscopes_reads_the_ops_as_profile_data_does(chip_trace):
    from jax.profiler import ProfileData

    plane = next(p for p in ProfileData.from_file(chip_trace).planes
                 if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == tracecut.OPS_LINE)
    want = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]
    got = opscopes.read_ops(chip_trace)
    assert [op[:3] for op in got] == want


def test_opscopes_paths_cover_the_chunk_programs(chip_trace):
    digest = tracecut.reduce_file(chip_trace)
    lo, hi = digest.window
    ops = opscopes.in_chunks(
        [op for op in opscopes.read_ops(chip_trace) if lo <= op[1] < hi],
        digest.chunk_runs())
    # a leaf: no op nested in it, so its self time is its duration
    leaf = [(op, t) for op, t in zip(ops, opscopes.self_times(ops))
            if round(t * 1e9) == round(op[2] * 1e9)]
    total = sum(t for _, t in leaf)
    named = sum(t for op, t in leaf if op[3])
    assert total > 0.9 * sum(e - s for s, e in digest.chunk_runs())
    assert named >= 0.99 * total
    assert all(op[3].startswith("jit(loop)/") for op, _ in leaf if op[3])


# ----------------------------------- (d) the readers on synthetic inputs
def _synthetic_run(host=()):
    """Two chunk programs on device 0, [1, 3) and [4, 5), in a window
    [0, 10); 4 epochs."""
    modules = {0: [("jit_loop(x)", 1.0, 2.0), ("jit_loop(x)", 4.0, 1.0),
                   ("jit_other", 6.0, 1.0)]}
    digest = tracecut.Digest(window=(0.0, 10.0), ops={0: []},
                             modules=modules, host=list(host))
    return harness.Run(seconds=10.0, t_process=0.0, t_window=0.0,
                       records=[], stats={"epochs": 4}, trace=digest)


SYNTHETIC_OPS = [
    # name, start, dur, tf_op: a while op over the first chunk holding a
    # commit scatter (0.5 s), a pack (0.25 s) and a tasks op whose own
    # commit child takes 0.125 of its 0.5 s
    ("%while", 1.0, 2.0, "jit(loop)/while"),
    ("%scatter", 1.0, 0.5, "jit(loop)/while/body/trees.tasks/cond/"
     "branch_1_fun/trees.commit/scatter"),
    ("%pack", 1.5, 0.25, "jit(loop)/while/body/trees.pack/gather"),
    ("%cond", 2.0, 0.5, "jit(loop)/while/body/trees.tasks/cond"),
    ("%inner", 2.1, 0.125, "jit(loop)/while/body/trees.tasks/cond/"
     "branch_0_fun/trees.commit/add"),
    # second chunk: one commit op; then an op outside every chunk
    ("%scatter", 4.0, 0.75, "jit(loop)/while/body/trees.commit/scatter"),
    ("%other", 6.0, 1.0, "jit(other)/trees.commit/mul"),
]


def test_scope_readers_on_a_synthetic_op_list(monkeypatch):
    monkeypatch.setattr(opscopes, "window_ops", lambda run: SYNTHETIC_OPS)
    run = _synthetic_run()
    read = {m: harness.load_reader(m) for m in (
        "commit_device_ms.batch", "pack_device_ms.batch",
        "tasks_device_ms.batch")}
    # commit: 0.5 + 0.125 + 0.75 s, tasks: 0.5 - 0.125 s, pack 0.25 s;
    # per 4 epochs, in ms
    assert read["commit_device_ms.batch"](run) == pytest.approx(343.75)
    assert read["tasks_device_ms.batch"](run) == pytest.approx(93.75)
    assert read["pack_device_ms.batch"](run) == pytest.approx(62.5)
    secs = opscopes.phase_seconds(SYNTHETIC_OPS,
                                  run.trace.chunk_runs())
    assert secs[None] == pytest.approx(2.0 - 0.5 - 0.25 - 0.5)
    # no op in scope, no epochs, or no trace: no reading
    monkeypatch.setattr(opscopes, "window_ops", lambda run: [
        op for op in SYNTHETIC_OPS if "trees.pack" not in op[3]])
    assert read["pack_device_ms.batch"](run) is None
    run.stats = {"epochs": 0}
    assert read["commit_device_ms.batch"](run) is None
    run.trace = None
    assert read["tasks_device_ms.batch"](run) is None


def test_self_times_count_shared_time_once():
    """Ops as the chip's trace has them: one that starts where the last
    one ends (in float seconds, 0.078750813 is not exactly 0.07875027 +
    5.43e-07), one nested, and one that outlives the op it started in."""
    ops = [("%a", 0.07875027, 5.43e-07, ""), ("%b", 0.078750813, 4e-07, ""),
           ("%p", 1.0, 0.5, ""), ("%c", 1.1, 0.1, ""),
           ("%q", 2.0, 0.3, ""), ("%r", 2.2, 0.3, "")]
    got = opscopes.self_times(ops)
    want = [5.43e-07, 4e-07, 0.4, 0.1, 0.2, 0.3]
    assert got == pytest.approx(want, abs=1e-12)
    assert min(got) >= 0


def test_span_readers_on_a_synthetic_digest():
    run = _synthetic_run(host=[
        ("bench.pump", 1.0, 3.0), ("trees:settle", 2.0, 0.002),
        ("trees:settle", 5.0, 0.004), ("trees:reseed", 2.5, 0.010),
        ("trees:resident_chunk", 1.0, 0.001)])
    settle = harness.load_reader("settle_ms.batch")
    reseed = harness.load_reader("reseed_ms.batch")
    assert settle(run) == pytest.approx(3.0)
    assert reseed(run) == pytest.approx(10.0)
    run = _synthetic_run(host=[("bench.pump", 1.0, 3.0)])
    assert settle(run) is None and reseed(run) is None
    run.trace = None
    assert settle(run) is None


def test_window_ops_reads_the_latest_trace(chip_trace, tmp_path,
                                           monkeypatch):
    """``window_ops`` finds the run's trace where the harness leaves it
    and keeps the ops that start inside the window."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    os.symlink(chip_trace, d / "host.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    digest = tracecut.reduce_file(chip_trace)
    run = harness.Run(seconds=2.0, t_process=0.0, t_window=0.0, records=[],
                      stats={"epochs": 24}, trace=digest)
    ops = opscopes.window_ops(run)
    lo, hi = digest.window
    assert ops and all(lo <= op[1] < hi for op in ops)
