"""Benchmark harness — one function per paper table/figure (DESIGN.md §7).

Prints ``name,us_per_call,compile_us,derived`` CSV rows.  All sizes are
scaled to run
on this CPU container in minutes; the *shape* of each comparison mirrors the
paper's (Fig. 5 Fibonacci overhead, Fig. 6 FFT, Fig. 7/8 BFS/SSSP vs
hand-coded worklists, Fig. 9 sort, plus the V1/V-inf overhead decomposition
of §4.4 and the TVM serving engine).  Roofline rows (§Roofline) are derived
from the dry-run artifacts, not timed here.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Callable, List, Tuple

import numpy as np

ROWS: List[tuple] = []

# set by main() from --dispatch; every HostEngine below follows it so the
# whole harness can be A/B'd masked vs compacted (§5.4 contiguity) or run
# under the self-tuning controller ("auto", DESIGN.md §14)
DISPATCH = "masked"

# set by main() from --chunk: "auto" runs the device_service rows with the
# adaptive chunk-size controller and emits the *_kauto rows (DESIGN.md §14)
CHUNK = None

# set by main() from --smoke: shrink every group to a CI-sized subset so the
# workflow's benchmarks step can guard the rows against bit-rot in minutes
SMOKE = False

# set by main() from --megakernel: emit device_service_*_mega rows (the
# persistent Pallas epoch megakernel next to the while_loop K-ladder rows)
MEGAKERNEL = False

# set by main() from --shards: emit sharded_service_*_p{1..P} rows (the
# device-mesh fleet scale-out ladder, DESIGN.md §15); 0 skips the group
SHARDS = 0

# set by main() from --trace / --metrics: the obs tracer + metrics registry
# every service/engine below feeds when enabled (None = disabled, free)
TRACER = None
METRICS = None


def jax_backend() -> str:
    import jax

    return jax.default_backend()


class Timing(float):
    """Steady-state seconds per call, with the warmup's one-time cost kept
    on the side.  The value *is* the steady-state mean (so existing
    arithmetic on ``_time`` results is unchanged); ``compile_s`` carries
    the first call — tracing + XLA compilation — as its own number instead
    of letting it pollute the mean or vanish."""

    compile_s: float = 0.0


def _time(fn: Callable, repeats: int = 3) -> Timing:
    # first call pays tracing + compilation; time it separately so the
    # repeats measure steady-state and the compile cost stays visible.
    # NOTE: closures must reuse one engine/service across calls — a fresh
    # engine per call owns fresh jit caches and recompiles every "repeat",
    # which is exactly the bug this split makes diffable (compile_us ~ 0
    # on a row means its repeats really were steady-state).
    t0 = time.perf_counter()
    fn()  # warmup / compile
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    t = Timing((time.perf_counter() - t0) / repeats)
    t.compile_s = compile_s
    return t


def row(name: str, us: float, derived: str = "", stats=None):
    """Record one benchmark row.

    ``us`` may be a plain float (microseconds) or carry a ``Timing`` via
    the caller multiplying one by 1e6 — compile time is passed explicitly
    by callers that have it.  ``stats`` is an optional RunStats whose
    ``as_dict()`` lands structured in the JSON artifact (one metric
    vocabulary with obs/export.py)."""
    compile_us = 0.0
    base = us
    if isinstance(us, Timing):
        base = float(us) * 1e6
        compile_us = us.compile_s * 1e6
    ROWS.append((name, base, compile_us, derived, stats))
    print(f"{name},{base:.1f},{compile_us:.1f},{derived}", flush=True)


# ------------------------------------------------------------ Fig 5: fib
def bench_fib():
    from repro.apps import fib
    from repro.core import HostEngine, DeviceEngine, run_oracle, compare

    for n in (10,) if SMOKE else (12, 14, 16):
        _, _, ostats = run_oracle(fib.PROGRAM, fib.initial(n), capacity=1 << 14)

        # one engine across warmup + repeats: its jit caches persist, so
        # the repeats measure steady-state dispatch (a fresh engine per
        # call would retrace each "repeat" — the compile_us column guards
        # against that regressing)
        host_eng = HostEngine(
            fib.PROGRAM, capacity=1 << 14, collect_stats=False,
            dispatch=DISPATCH, tracer=TRACER,
        )

        def run_host():
            host_eng.run(fib.initial(n))

        eng = HostEngine(fib.PROGRAM, capacity=1 << 14, dispatch=DISPATCH)
        _, vals, hstats = eng.run(fib.initial(n))
        t_host = _time(run_host)
        rep = compare(ostats, hstats)
        row(
            f"fib{n}_trees_host", t_host,
            f"tasks={ostats.tasks_executed};epochs={ostats.epochs};"
            f"us_per_task={t_host*1e6/ostats.tasks_executed:.1f};"
            f"util={rep.utilization:.2f}",
            stats=hstats,
        )

        dev_eng = DeviceEngine(
            fib.PROGRAM, capacity=1 << 14, stack_depth=512, tracer=TRACER
        )

        def run_dev():
            dev_eng.run(fib.initial(n))

        t_dev = _time(run_dev)
        row(
            f"fib{n}_trees_device", t_dev,
            f"speedup_vs_host={t_host/t_dev:.2f}",
        )

        def run_seq():
            def f(k):
                return k if k < 2 else f(k - 1) + f(k - 2)
            return f(n)

        t_seq = _time(run_seq)
        row(
            f"fib{n}_sequential", t_seq,
            f"trees_overhead_x={t_host/max(t_seq,1e-9):.1f}",
        )


# ------------------------------------------------------------ Fig 6: fft
def bench_fft():
    from repro.apps import fft
    from repro.core import HostEngine
    import jax.numpy as jnp
    import jax

    for n in (64, 256):
        xr, xi = fft.random_input(n)
        prog = fft.make_program(n)
        eng = HostEngine(
            prog, capacity=1 << 13, collect_stats=False, dispatch=DISPATCH
        )

        def run_trees():
            eng.run(fft.initial(n), heap_init=dict(xr=xr, xi=xi))

        t_trees = _time(run_trees)

        xc = xr + 1j * xi

        @jax.jit
        def native(v):
            return jnp.fft.fft(v)

        t_native = _time(lambda: np.asarray(native(xc)))
        row(
            f"fft{n}_trees", t_trees,
            f"native_fft_us={t_native*1e6:.1f};"
            f"generality_cost_x={t_trees/max(t_native,1e-9):.1f}",
        )


# ------------------------------------------------- Fig 7/8: bfs and sssp
def bench_graph():
    from repro.apps import bfs, sssp
    from repro.apps.baselines import worklist
    from repro.core import HostEngine

    n = 256
    adj_off, adj = bfs.random_graph(n, avg_degree=4, seed=0)
    bfs_eng = HostEngine(
        bfs.make_program(n, len(adj)), capacity=1 << 15,
        collect_stats=False, dispatch=DISPATCH,
    )

    def run_trees_bfs():
        bfs_eng.run(bfs.initial(0), heap_init=bfs.heap_init(adj_off, adj, n))

    t_trees = _time(run_trees_bfs)

    def run_wl_bfs():
        worklist.bfs_worklist(adj_off, adj, 0, n)

    t_wl = _time(run_wl_bfs)
    row(
        f"bfs_n{n}_trees", t_trees,
        f"worklist_us={t_wl*1e6:.1f};overhead_vs_native_x={t_trees/t_wl:.2f}",
    )

    wgt = sssp.random_weights(len(adj), seed=1)
    sssp_eng = HostEngine(
        sssp.make_program(n, len(adj)), capacity=1 << 16,
        collect_stats=False, dispatch=DISPATCH,
    )

    def run_trees_sssp():
        sssp_eng.run(
            sssp.initial(0), heap_init=sssp.heap_init(adj_off, adj, wgt, n)
        )

    t_trees = _time(run_trees_sssp)

    def run_wl_sssp():
        worklist.sssp_worklist(adj_off, adj, wgt, 0, n)

    t_wl = _time(run_wl_sssp)
    row(
        f"sssp_n{n}_trees", t_trees,
        f"worklist_us={t_wl*1e6:.1f};overhead_vs_native_x={t_trees/t_wl:.2f}",
    )


# ------------------------------------------------------------ Fig 9: sort
def bench_sort():
    from repro.apps import mergesort
    from repro.apps.baselines import bitonic
    from repro.core import HostEngine
    import jax.numpy as jnp

    n = 64
    x = mergesort.random_input(n)
    engs = {
        use_map: HostEngine(
            mergesort.make_program(n, use_map=use_map), capacity=1 << 13,
            collect_stats=False, dispatch=DISPATCH,
        )
        for use_map in (False, True)
    }

    def run(use_map):
        engs[use_map].run(mergesort.initial(n), heap_init=dict(inp=x))

    t_naive = _time(lambda: run(False), repeats=1)
    t_map = _time(lambda: run(True), repeats=1)
    xj = jnp.asarray(x)
    t_bitonic = _time(lambda: np.asarray(bitonic.bitonic_sort(xj)))
    row(f"sort{n}_trees_naive", t_naive,
        f"vs_bitonic_x={t_naive/max(t_bitonic,1e-9):.1f}")
    row(f"sort{n}_trees_map", t_map,
        f"map_speedup_vs_naive_x={t_naive/t_map:.2f};"
        f"vs_bitonic_x={t_map/max(t_bitonic,1e-9):.1f}")
    row(f"sort{n}_bitonic_native", t_bitonic, "")


# --------------------------------------- §4.4: V1 / V_inf decomposition
def bench_overhead():
    from repro.apps import nqueens
    from repro.core import HostEngine, run_oracle, compare

    prog = nqueens.make_program(7)
    _, _, ostats = run_oracle(prog, nqueens.initial(), capacity=1 << 14)
    eng = HostEngine(prog, capacity=1 << 14, dispatch=DISPATCH)
    timed_eng = HostEngine(
        prog, capacity=1 << 14, collect_stats=False, dispatch=DISPATCH
    )
    t = _time(lambda: timed_eng.run(nqueens.initial()), repeats=1)
    _, _, st = eng.run(nqueens.initial())
    rep = compare(ostats, st)
    row(
        "nqueens7_overhead", t,
        f"T1={rep.t1_tasks};Tinf={rep.t_inf_epochs};"
        f"parallelism={rep.parallelism:.1f};"
        f"V1_lanes={rep.v1_lane_factor:.2f};"
        f"Vinf_dispatches={rep.v_inf_dispatches};"
        f"greedy_bound_P256={rep.greedy_bound(256):.0f}",
    )


# ------------------- §5.4 / §11: masked vs compacted vs gather dispatch
def bench_dispatch():
    """Lane utilization + time per app across all three dispatch modes.

    The compacted rows realize §5.4's contiguity principle (dense per-type
    launches); the gather rows realize §11's dense-frontier pack (one
    lane-exact launch, no per-type splitting, hole lanes skipped).  The
    derived column carries the utilization of *all* policies so the wins
    are visible in one row, plus the V_inf critical-path estimate from the
    roofline dispatch model and the gather path's skipped hole lanes.
    """
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from roofline import vinf_seconds

    from repro.apps import get_case
    from repro.core import HostEngine

    statics = ("masked", "compacted", "gather")
    for name in ("fib", "nqueens", "bfs"):
        case = get_case(name)
        stats = {}
        times = {}
        decisions = {}
        policies = statics + ("auto",) if DISPATCH == "auto" else statics
        for policy in policies:
            eng = HostEngine(
                case.program, capacity=case.capacity, dispatch=policy
            )
            _, _, stats[policy] = eng.run(
                case.initial, heap_init=dict(case.heap_init) or None
            )
            times[policy] = _time(
                lambda e=eng: e.run(
                    case.initial, heap_init=dict(case.heap_init) or None
                ),
                repeats=1,
            )
            if policy == "auto":
                decisions = dict(eng.controller.decisions)
        sm, sc, sg = stats["masked"], stats["compacted"], stats["gather"]
        occ = ";".join(
            f"occ_{t}={o:.2f}" for t, o in sorted(sc.occupancy_by_type.items())
        )
        # under --dispatch auto: the controller's per-epoch decision
        # counts, the auto leg's own clock, and the static envelope it is
        # gated against (check.py --auto: us_per_call <= worst static)
        auto = ""
        if DISPATCH == "auto":
            static_us = {p: times[p] * 1e6 for p in statics}
            dec = ";".join(
                f"auto_{m}={c}" for m, c in sorted(decisions.items())
            )
            auto = (
                f";util_auto={stats['auto'].utilization:.2f};"
                f"us_best_static={min(static_us.values()):.1f};"
                f"us_worst_static={max(static_us.values()):.1f};{dec}"
            )
        row(
            f"dispatch_{name}_{DISPATCH}", times[DISPATCH],
            f"util_masked={sm.utilization:.2f};"
            f"util_compacted={sc.utilization:.2f};"
            f"util_gather={sg.utilization:.2f};"
            f"us_masked={times['masked']*1e6:.1f};"
            f"us_compacted={times['compacted']*1e6:.1f};"
            f"us_gather={times['gather']*1e6:.1f};"
            f"lanes_masked={sm.lanes_launched};"
            f"lanes_compacted={sc.lanes_launched};"
            f"lanes_gather={sg.lanes_launched};"
            f"hole_lanes_skipped={sg.hole_lanes_skipped};"
            f"vinf_masked_us={vinf_seconds(sm)*1e6:.0f};"
            f"vinf_compacted_us={vinf_seconds(sc)*1e6:.0f};"
            f"vinf_gather_us={vinf_seconds(sg)*1e6:.0f};{occ}{auto}",
            stats=stats[DISPATCH],
        )


# ------------------------------ epoch-multiplexing job service (DESIGN §8)
def bench_service():
    """Multi-tenant co-scheduling: fleet V_inf vs the sum of solo runs.

    ``service_mixed3`` runs the registered mixed fleet (fib + treewalk +
    bfs) through one shared TVM and reports the fused dispatch/readback
    totals against the sum of the three solo runs — the work-together
    principle extended across tenants.  ``service_fibxN`` scales a
    homogeneous fleet to show throughput vs concurrency: fused dispatches
    grow like the *max* of the members, not the sum.
    """
    from repro.apps import get_fleet
    from repro.core import HostEngine
    from repro.service import JobService

    def run_service(fleet, n_jobs=None):
        svc = JobService(
            capacity=sum(q for _, q in fleet), dispatch=DISPATCH,
            max_jobs=n_jobs or len(fleet),
            metrics=METRICS, tracer=TRACER,
        )
        for case, quota in fleet:
            svc.submit_case(case, quota=quota)
        svc.drain()
        return svc

    if SMOKE:
        # smoke: only the x2 homogeneous point (compile-light, still
        # exercises fusion + the V_inf accounting)
        base = get_fleet("fib_fleet")[0]
        svc = run_service([base] * 2)
        fs = svc.stats()
        t = _time(lambda: run_service([base] * 2), repeats=1)
        row(
            f"service_fibx2_{DISPATCH}", t,
            f"jobs=2;fleet_dispatches={fs.dispatches};"
            f"dispatches_per_job={fs.dispatches / 2:.1f}",
            stats=fs,
        )
        return

    # mixed fleet vs sum-of-solo
    fleet = get_fleet("mixed3")
    solo_disp = solo_xfer = 0
    for case, quota in fleet:
        eng = HostEngine(case.program, capacity=quota, dispatch=DISPATCH)
        _, _, s = eng.run(case.initial, heap_init=dict(case.heap_init) or None)
        solo_disp += s.dispatches
        solo_xfer += s.scalar_transfers
    svc = run_service(fleet)
    fs = svc.stats()
    t = _time(lambda: run_service(get_fleet("mixed3")), repeats=1)
    row(
        f"service_mixed3_{DISPATCH}", t,
        f"jobs={len(fleet)};fleet_dispatches={fs.dispatches};"
        f"solo_dispatches={solo_disp};"
        f"fleet_transfers={fs.scalar_transfers};solo_transfers={solo_xfer};"
        f"vinf_saving_x={(solo_disp + solo_xfer) / max(1, fs.dispatches + fs.scalar_transfers):.2f};"
        f"util={fs.utilization:.2f};"
        f"hole_lanes_skipped={fs.hole_lanes_skipped}",
        stats=fs,
    )

    # throughput vs number of concurrent jobs (homogeneous fib fleet)
    base = get_fleet("fib_fleet")[0]
    for n in (1, 2, 4, 8):
        fleet_n = [base] * n
        svc = run_service(fleet_n)
        fs = svc.stats()
        t = _time(lambda f=fleet_n: run_service(f), repeats=1)
        row(
            f"service_fibx{n}_{DISPATCH}", t,
            f"jobs={n};fleet_dispatches={fs.dispatches};"
            f"us_per_job={t * 1e6 / n:.1f};"
            f"dispatches_per_job={fs.dispatches / n:.1f}",
        )


# ------------- chunked device-resident fleet execution (DESIGN.md §9–10)
def bench_device_service():
    """Resident fleet vs host-mux vs sum-of-solo, plus the K-epoch ladder.

    Each ``device_service_*`` row runs the same fleet three ways — N solo
    ``HostEngine`` runs (V_inf paid per job per epoch), the host-loop
    multiplexer (paid once per fused global epoch), and the resident
    ``lax.while_loop`` wave (paid once per *wave*: one dispatch + one
    readback, O(1)) — and reports all three dispatch+transfer totals plus
    the resident path's map-lane waste (its measurable work overhead).

    The ``device_service_*_k{K}`` rows sweep the chunk knob between the
    two endpoints: the wave re-enters the compiled loop every K epochs, so
    measured readbacks per wave must equal ⌈epochs/K⌉ (both numbers are
    emitted so the invariant is diffable); the timed re-run reuses the
    wave-template cache, so ``template_hits`` also guards compiled-loop
    reuse across identical consecutive waves.

    With ``--megakernel`` the ``device_service_*_mega_*`` rows run the
    same waves through the persistent Pallas epoch megakernel
    (``kernels/epoch_megakernel.py``) under masked and gather dispatch,
    next to their while_loop twins: same ⌈E/K⌉ readback invariant, same
    ``template_hits`` guard, plus ``lanes_launched``/
    ``hole_lanes_skipped`` so the gather rows' lane-volume win over the
    span ladder is diffable.  On CPU the kernel executes through the
    Pallas interpreter under ``--smoke`` (the bit-rot guard) and falls
    back to the jnp oracle otherwise (interpret mode is a simulator, not
    a perf number); on TPU it is the native kernel either way.
    """
    import math

    from repro.apps import get_fleet
    from repro.core import HostEngine
    from repro.service import JobService, WaveTemplateCache

    def run_svc(fleet, engine, chunk=None, cache=None, dispatch=None,
                megakernel=False, megakernel_impl="auto"):
        svc = JobService(
            capacity=sum(q for _, q in fleet), engine=engine,
            dispatch=(dispatch or "masked") if engine == "device"
            else DISPATCH,
            chunk=chunk if engine == "device" else None,
            template_cache=cache,
            megakernel=megakernel, megakernel_impl=megakernel_impl,
            metrics=METRICS, tracer=TRACER,
        )
        for case, quota in fleet:
            svc.submit_case(case, quota=quota)
        svc.drain()
        return svc

    if SMOKE:
        fleets = [("fibx2", [get_fleet("fib_fleet")[0]] * 2)]
        ladder = (4, None)  # one finite-K smoke row + the resident endpoint
    else:
        fleets = [
            ("mixed3", get_fleet("mixed3")),
            ("fibx4", get_fleet("fib_fleet")),
        ]
        ladder = (1, 4, 16, None)
    for fname, fleet in fleets:
        solo_vinf = 0
        for case, quota in fleet:
            eng = HostEngine(case.program, capacity=quota, dispatch=DISPATCH)
            _, _, s = eng.run(
                case.initial, heap_init=dict(case.heap_init) or None
            )
            solo_vinf += s.dispatches + s.scalar_transfers
        # one template cache across the stats pass + warmup + repeats: a
        # fresh service per call is the measurement (queue + wave build),
        # but re-tracing the chunk loop per call is not — without the
        # shared cache the "steady-state" repeats each paid a full
        # retrace, double-counting compile into us_per_call on top of the
        # compile_us column
        cache_d = WaveTemplateCache()
        hs = run_svc(fleet, "host").stats()
        ds = run_svc(fleet, "device", cache=cache_d).stats()
        t_host = _time(lambda f=fleet: run_svc(f, "host"), repeats=1)
        t_dev = _time(
            lambda f=fleet: run_svc(f, "device", cache=cache_d), repeats=1
        )
        host_vinf = hs.dispatches + hs.scalar_transfers
        dev_vinf = ds.dispatches + ds.scalar_transfers
        row(
            f"device_service_{fname}", t_dev,
            f"jobs={len(fleet)};resident_vinf={dev_vinf};"
            f"hostmux_vinf={host_vinf};solo_vinf={solo_vinf};"
            f"vinf_vs_hostmux_x={host_vinf / max(1, dev_vinf):.1f};"
            f"vinf_vs_solo_x={solo_vinf / max(1, dev_vinf):.1f};"
            f"host_mux_us={t_host * 1e6:.1f};"
            f"map_lanes_wasted={ds.map_lanes_wasted};"
            f"map_util={ds.map_utilization:.3f};"
            f"util={ds.utilization:.3f};"
            f"hole_lanes_skipped={ds.hole_lanes_skipped}",
            stats=ds,
        )

        # the K-ladder: readback cadence between host-mux and resident
        k_times = {}
        for K in ladder:
            cache = WaveTemplateCache()
            ks = run_svc(fleet, "device", chunk=K, cache=cache).stats()
            t_k = _time(
                lambda f=fleet, K=K, c=cache: run_svc(
                    f, "device", chunk=K, cache=c
                ),
                repeats=1,
            )
            k_times[K] = float(t_k)
            expected = 1 if K is None else math.ceil(ks.epochs / K)
            row(
                f"device_service_{fname}_k{'inf' if K is None else K}",
                t_k,
                f"jobs={len(fleet)};chunk={'inf' if K is None else K};"
                f"epochs={ks.epochs};readbacks={ks.scalar_transfers};"
                f"expected_readbacks={expected};dispatches={ks.dispatches};"
                f"template_hits={cache.hits};"
                f"map_lanes_wasted={ks.map_lanes_wasted};"
                f"hole_lanes_skipped={ks.hole_lanes_skipped}",
                stats=ks,
            )

        if CHUNK == "auto":
            # self-tuning endpoint: dispatch="auto" + chunk="auto" through
            # the service front door, timed against the static K-ladder's
            # envelope (check.py --auto gates us_per_call <= worst static;
            # the acceptance target is within 10% of the best)
            cache = WaveTemplateCache()
            holder = {}

            def run_auto(f=fleet, c=cache):
                holder["svc"] = run_svc(
                    f, "device", chunk="auto", cache=c, dispatch="auto"
                )

            run_auto()
            as_ = holder["svc"].stats()
            t_a = _time(run_auto, repeats=1)
            svc_a = holder["svc"]
            kctl, dctl = svc_a.chunk_controller, svc_a.controller
            dec = ";".join(
                f"auto_{m}={c}"
                for m, c in sorted(dctl.decisions.items())
            ) if dctl is not None else ""
            row(
                f"device_service_{fname}_kauto", t_a,
                f"jobs={len(fleet)};chunk=auto;epochs={as_.epochs};"
                f"readbacks={as_.scalar_transfers};"
                f"dispatches={as_.dispatches};"
                f"k_final={kctl.current()};k_widened={kctl.widened};"
                f"k_shrunk={kctl.shrunk};{dec};"
                f"template_hits={cache.hits};"
                f"us_best_static={min(k_times.values())*1e6:.1f};"
                f"us_worst_static={max(k_times.values())*1e6:.1f};"
                f"hole_lanes_skipped={as_.hole_lanes_skipped}",
                stats=as_,
            )

        if not MEGAKERNEL:
            continue
        # megakernel rows next to their while_loop twins: same fleet, same
        # K, masked + gather, with the while_loop baseline wall-clock in
        # the derived column so the comparison is one row wide
        impl = "interpret" if (SMOKE and jax_backend() != "tpu") else "auto"
        mega_ladder = (4,) if SMOKE else (4, None)
        for dispatch in ("masked", "gather"):
            for K in mega_ladder:
                cache = WaveTemplateCache()
                ms = run_svc(
                    fleet, "device", chunk=K, cache=cache,
                    dispatch=dispatch, megakernel=True,
                    megakernel_impl=impl,
                ).stats()
                t_m = _time(
                    lambda f=fleet, K=K, c=cache, d=dispatch: run_svc(
                        f, "device", chunk=K, cache=c, dispatch=d,
                        megakernel=True, megakernel_impl=impl,
                    ),
                    repeats=1,
                )
                cache_b = WaveTemplateCache()
                t_b = _time(
                    lambda f=fleet, K=K, c=cache_b, d=dispatch: run_svc(
                        f, "device", chunk=K, cache=c, dispatch=d,
                    ),
                    repeats=1,
                )
                expected = 1 if K is None else math.ceil(ms.epochs / K)
                row(
                    f"device_service_{fname}_mega_{dispatch}"
                    f"_k{'inf' if K is None else K}",
                    t_m,
                    f"jobs={len(fleet)};chunk={'inf' if K is None else K};"
                    f"impl={impl};epochs={ms.epochs};"
                    f"readbacks={ms.scalar_transfers};"
                    f"expected_readbacks={expected};"
                    f"while_loop_us={t_b * 1e6:.1f};"
                    f"lanes_launched={ms.lanes_launched};"
                    f"hole_lanes_skipped={ms.hole_lanes_skipped};"
                    f"template_hits={cache.hits};"
                    f"map_lanes_wasted={ms.map_lanes_wasted};"
                    f"util={ms.utilization:.3f}",
                    stats=ms,
                )


# ------------- sharded fleet execution across a device mesh (DESIGN §15)
def bench_sharded_service():
    """Scale-out ladder: the same job stream through P TVM shards.

    Each ``sharded_service_<fleet>_pP`` row drains R copies of the fleet
    through ``JobService(engine="sharded", shards=P)`` — P full device
    waves on a 1-D ``"fleet"`` mesh, ONE fused launch + ONE stacked
    readback per collective chunk — and reports jobs/sec against the
    ``p1`` baseline, the collective V_inf totals, rebalance-migration
    counts, and the per-shard work split (``shard_tasks``/``shard_forks``
    pipe-joined, which ``check.py --shards`` gates: their sums must equal
    the ``p1`` row's totals exactly — sharding moves work, never changes
    it).  ``mesh=1`` marks rows that ran on a real device mesh; ``mesh=0``
    is the one-shard ``p1`` row, which needs none (CI forces 8 host devices
    so the smoke row exercises the real path).

    One :class:`~repro.service.jobs.WaveTemplateCache` is shared across
    the whole ladder: the template is deliberately not keyed on P, so
    ``p1`` compiles the chunk body once and every later P reuses it
    (``template_hits`` makes that diffable per row).
    """
    import jax

    from repro.apps import get_fleet
    from repro.service import JobService, WaveTemplateCache

    fleet = get_fleet("mixed3")
    reps = 6 if SMOKE else 8  # 18 / 24 queued jobs (acceptance: >= 16)
    n_jobs = reps * len(fleet)
    chunk = 4  # finite K: rebalancing needs chunk boundaries
    ladder = [p for p in (1, 2, 4, 8) if p <= SHARDS] or [1]
    if SMOKE and SHARDS > 1:
        ladder = [1, SHARDS]  # the smoke row: baseline + full width

    def run_sharded(shards, cache):
        svc = JobService(
            capacity=sum(q for _, q in fleet), engine="sharded",
            shards=shards, chunk=chunk, dispatch="masked",
            max_jobs=len(fleet), template_cache=cache,
            metrics=METRICS, tracer=TRACER,
        )
        for r in range(reps):
            for case, quota in fleet:
                svc.submit_case(case, quota=quota, name=f"{case.name}#{r}")
        svc.drain()
        return svc

    cache = WaveTemplateCache()
    t_p1 = None
    for P in ladder:
        svc = run_sharded(P, cache)
        fs = svc.stats()
        fl = svc._mux  # the last (only) wave's fleet, post-drain
        t = _time(lambda P=P: run_sharded(P, cache), repeats=1)
        if t_p1 is None:
            t_p1 = float(t)
        shard_stats = fl.shard_stats() if fl is not None else []
        shard_tasks = "|".join(
            str(s.tasks_executed) for s in shard_stats
        )
        shard_forks = "|".join(str(s.total_forks) for s in shard_stats)
        row(
            f"sharded_service_mixed3_p{P}", t,
            f"jobs={n_jobs};shards={P};chunk={chunk};"
            f"jobs_per_sec={n_jobs / max(float(t), 1e-9):.1f};"
            f"speedup_vs_p1={t_p1 / max(float(t), 1e-9):.2f};"
            f"vinf={fs.dispatches + fs.scalar_transfers};"
            f"collective_steps={getattr(fl, 'collective_steps', 0)};"
            f"migrations={getattr(fl, 'migrations', 0)};"
            f"util_spread="
            f"{fl.utilization_spread() if fl is not None else 0:.3f};"
            f"mesh={1 if getattr(fl, 'mesh', None) is not None else 0};"
            f"devices={jax.device_count()};"
            f"template_hits={cache.hits};"
            f"shard_tasks={shard_tasks};shard_forks={shard_forks}",
            stats=fs,
        )


# --------------------------------------------------- TVM serving engine
def bench_serving():
    import jax
    import numpy as np
    from repro import configs
    from repro.models.model import init_model
    from repro.serving import EpochServer, Request

    cfg = configs.get_reduced("granite_3_8b")
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)

    def serve(slots):
        srv = EpochServer(cfg, params, n_slots=slots, max_len=64)
        for _ in range(8):
            srv.submit(
                Request(
                    prompt=rng.randint(3, cfg.vocab, 8).astype(np.int32),
                    max_new_tokens=8,
                )
            )
        done = srv.run_to_completion()
        return sum(len(r.output) for r in done), srv.epochs

    # warm (each serve() builds its own server, so the warm call pays the
    # jit tracing shared by the later calls; record it as compile time)
    t0 = time.perf_counter()
    serve(4)
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    n_tok, epochs = serve(4)
    dt = Timing(time.perf_counter() - t0)
    dt.compile_s = warm_s
    t0 = time.perf_counter()
    n1, e1 = serve(1)
    dt1 = time.perf_counter() - t0
    row(
        "serve_8req_slots4", dt,
        f"tokens={n_tok};epochs={epochs};"
        f"batch_speedup_vs_slots1={dt1/dt:.2f}",
    )


# ----------------------------------------------------- roofline summary
def bench_roofline():
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from roofline import load_all

    pts = load_all()
    if not pts:
        row("roofline", 0.0, "no dry-run artifacts; run repro.launch.dryrun")
        return
    for p in pts:
        if p.mesh != "16x16":
            continue
        row(
            f"roofline_{p.arch}_{p.shape}",
            p.bound_time * 1e6,
            f"dominant={p.dominant};useful={p.useful_ratio:.2f};"
            f"frac={p.roofline_fraction:.2f}",
        )


BENCHES = {
    "fib": bench_fib,
    "fft": bench_fft,
    "graph": bench_graph,
    "sort": bench_sort,
    "overhead": bench_overhead,
    "dispatch": bench_dispatch,
    "service": bench_service,
    "device_service": bench_device_service,
    "sharded_service": bench_sharded_service,
    "serving": bench_serving,
    "roofline": bench_roofline,
}

# the CI-sized subset --smoke restricts to (each group also shrinks its own
# sizes when SMOKE is set)
SMOKE_GROUPS = ("fib", "service", "device_service")


def write_json(path: str, dispatch: str, smoke: bool, groups) -> None:
    """Machine-readable artifact alongside the CSV stdout, so the perf
    trajectory (V_inf ladders, utilization, map waste) is diffable across
    PRs instead of living only in scrollback.  ``groups`` records which
    benchmark groups actually ran — two artifacts are only comparable row
    set to row set, never across different group selections.  Rows that
    carried a RunStats serialize it via ``RunStats.as_dict()`` — the same
    metric vocabulary ``obs/export.py`` exports — so ``check.py`` gates on
    structured counters, not just the derived string."""
    rows = []
    for n, us, cus, d, s in ROWS:
        r = {
            "name": n,
            "us_per_call": round(us, 1),
            "compile_us": round(cus, 1),
            "derived": d,
        }
        if s is not None:
            r["stats"] = s.as_dict()
        rows.append(r)
    payload = {
        "schema": "trees-bench-v2",
        "dispatch": dispatch,
        "chunk": CHUNK,
        "smoke": smoke,
        "megakernel": MEGAKERNEL,
        "shards": SHARDS,
        "groups": sorted(groups),
        "rows": rows,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")


def main(argv=None) -> None:
    global DISPATCH, CHUNK, SMOKE, MEGAKERNEL, SHARDS, TRACER, METRICS
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--dispatch", choices=("masked", "compacted", "gather", "auto"),
        default="masked",
        help="HostEngine dispatch policy for every benchmark "
        "(masked = seed full-width vmap; compacted = §5.4 dense "
        "per-type launches; gather = §11 dense-frontier pack, hole "
        "lanes skipped; auto = §14 telemetry-driven per-epoch choice)",
    )
    ap.add_argument(
        "--chunk", choices=("auto",), default=None,
        help="device_service chunk policy: 'auto' adds the *_kauto rows "
        "(adaptive-K controller, DESIGN.md §14) next to the static "
        "K-ladder",
    )
    ap.add_argument(
        "--only", nargs="+", choices=sorted(BENCHES), default=None,
        help="run only these benchmark groups",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI-sized subset: tiny problem sizes, groups "
        f"{SMOKE_GROUPS} only (unless --only overrides)",
    )
    ap.add_argument(
        "--megakernel", action="store_true",
        help="emit device_service_*_mega rows: the persistent Pallas "
        "epoch megakernel (masked + gather) next to the while_loop "
        "K-ladder rows (interpret mode on CPU under --smoke, native "
        "kernel on TPU)",
    )
    ap.add_argument(
        "--shards", type=int, default=0, metavar="P",
        help="emit the sharded_service_*_p{1..P} scale-out ladder "
        "(DESIGN.md §15) on a real 'fleet' device mesh; needs >= P "
        "devices (CI forces 8 via --xla_force_host_platform_device_count)",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="write the rows as a machine-readable JSON artifact; defaults "
        "to BENCH_9.json for full runs, off for --only subset or --smoke "
        "runs (pass a path to force, '' to disable)",
    )
    ap.add_argument(
        "--trace", default=None, metavar="PATH",
        help="run the service benchmarks with the obs span tracer on and "
        "write the Chrome-trace-event JSON (perfetto-loadable) here",
    )
    ap.add_argument(
        "--metrics", default=None, metavar="PATH",
        help="run with the obs metrics registry on and write its samples "
        "as JSONL here (plus Prometheus text exposition at PATH.prom)",
    )
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    DISPATCH = args.dispatch
    CHUNK = args.chunk
    SMOKE = args.smoke
    MEGAKERNEL = args.megakernel
    SHARDS = args.shards
    if args.trace:
        from repro.obs import SpanTracer

        TRACER = SpanTracer()
    if args.metrics:
        from repro.obs import MetricsRegistry

        METRICS = MetricsRegistry()
    only = args.only or (list(SMOKE_GROUPS) if args.smoke else None)
    if args.shards:
        # --shards opts the scale-out ladder in, whatever the selection
        if only is not None and "sharded_service" not in only:
            only = list(only) + ["sharded_service"]
    elif only is None:
        # the ladder only means something with a shard count: skip the
        # group on plain full runs rather than emitting a p1-only row set
        only = [n for n in BENCHES if n != "sharded_service"]
    ran = []
    print("name,us_per_call,compile_us,derived")
    for name, fn in BENCHES.items():
        if only and name not in only:
            continue
        ran.append(name)
        fn()
    json_path = args.json
    if json_path is None:
        # don't silently clobber the cross-PR artifact with a subset or
        # smoke run (CI's smoke job passes --json explicitly)
        json_path = "" if (args.only or args.smoke) else "BENCH_9.json"
    if json_path:
        write_json(json_path, args.dispatch, args.smoke, ran)
    if args.trace:
        TRACER.write(args.trace)
    if args.metrics:
        from repro.obs import write_jsonl, write_prometheus

        write_jsonl(METRICS, args.metrics)
        write_prometheus(METRICS, args.metrics + ".prom")


if __name__ == "__main__":
    main()
