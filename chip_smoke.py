#!/usr/bin/env python3
"""Chip smoke test: drive the TREES runtime's main path once on a TPU.

    python chip_smoke.py             # one chip: kernel, runtime, model phases
    python chip_smoke.py --chips 4   # four chips: the sharded-fleet phase only

Everything runs in this one process; it starts no other.  Each phase
prints its sizes, its compile seconds and the device's
``peak_bytes_in_use``, and checks its outputs against a plain reference.
A failing check raises, so the script exits non-zero and never prints a
result line.  Where JAX finds no TPU the script exits non-zero at start-up
and runs nothing on the CPU.  On success the last line of standard output
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Phases:

* kernels -- the four ``fork_compact`` entry points of ``kernels/ops.py``
  with ``impl="auto"`` (native Pallas on TPU) at 1<<22 lanes, equal bit
  for bit to their ``impl="ref"`` oracles;
* runtime -- a mixed fleet (fib, N-Queens, BFS, map-carrying mergesort)
  in one wave of 6,432 TV lanes through ``JobService`` on the host engine
  and the chunked device engine, each with masked and gather dispatch;
  then BOTS-class inputs (fib(27), nqueens(12), BFS on 2^16 nodes of
  average degree 16) in one wave of 2^22 TV lanes through the chunked
  device engine, masked and gather; every job checked against its plain
  reference;
* model -- ``granite_moe_1b_a400m`` at its published widths, random weights
  from the seed, served by ``EpochServer`` through ``launch/serve.py``;
  its attention kernels and first-step logits checked against references;
* fleet (``--chips 4``) -- five copies of the BOTS-class fleet cut to
  2^20 lanes per shard (fib(25), nqueens(11), BFS on 2^14 nodes) through
  ``JobService(engine="sharded", shards=4, dispatch="gather")`` on a real
  4-device mesh, bit-identical to ``shards=1`` in the same process.  (At
  2^22 lanes per shard the one-shard comparison alone would run about 13
  minutes: the wave takes about 1.5 s per gather epoch on a v5e.)
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

CAPACITY = 1 << 22   # lanes of the kernel vectors and of the BOTS wave
CHUNK = 16           # epochs per resident chunk on the device engine
# Two fleets.  MIXED holds the map-carrying mergesort and runs on all four
# runtime configurations; compile time, not the chip, sets its size.
# Compiled for a v5e on an 8-core CPU host, its fused 4-program resident
# template takes about 1.5 minutes, and over 14 at 2^22 lanes; the host
# engine's fused step takes about a minute per launch width at 2^20 lanes,
# and a wave passes through up to 20 widths (ROADMAP S8).  BOTS holds
# BOTS-class inputs without a map: their quotas fill one 2^22-lane wave,
# whose resident template compiles in about 1.5 minutes there.
MIXED = dict(fib_n=14, queens_n=6, bfs_nodes=1 << 7, sort_n=16)
BOTS = dict(fib_n=27, queens_n=12, bfs_nodes=1 << 16, sort_n=None)
# FLEET is BOTS cut to 2^20 lanes per shard (quotas 2^18/2^18/2^19), so
# that the one-shard comparison, which runs all five copies in turn on one
# chip, ends inside the four-chip run's time
FLEET = dict(fib_n=25, queens_n=11, bfs_nodes=1 << 14, sort_n=None)
FLEET_COPIES = 5     # 15 jobs: each shard queues a second job of some type
BFS_DEGREE = 16
ATTN_TOL = 2e-2      # bf16 inputs: |pallas - ref| <= tol * (1 + |ref|)
# first-step logits, as max |server - plain| over max |plain|: against a
# plain prefill of the same batch at the same bucket (the same f32 program,
# so only fusion order can differ), and against each request alone (bf16
# activations at other shapes; both dropless, so routing is the same)
BATCH_TOL = 1e-6
LOGIT_TOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not ok:
        raise AssertionError(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from
    ``jax.monitoring``; read per phase with :meth:`take`."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in self.EVENTS:
            self.seconds += secs

    def take(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def device_report(devices) -> str:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", "not reported"))
    return f"peak_bytes_in_use={peaks if len(peaks) > 1 else peaks[0]}"


def startup(chips: int):
    """Enable the compile cache, then insist on ``chips`` TPU devices."""
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX finds no TPU (platform "
            f"{devices[0].platform!r}); nothing runs on the CPU"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
            f"JAX finds {len(devices)}"
        )
    log(f"startup: {len(devices)} x {devices[0].device_kind}, "
        f"compile cache {cache}")
    return devices[:chips]


# ------------------------------------------------------------------ kernels
def phase_kernels(clock, devices, capacity: int = CAPACITY, seed: int = 0,
                  impl: str = "auto") -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.RandomState(seed)
    counts = jnp.asarray(rng.randint(0, 9, capacity), jnp.int32)
    seg = jnp.asarray(rng.randint(0, 5, capacity), jnp.int32)  # 4: unowned
    types = jnp.asarray(rng.randint(0, 6, capacity), jnp.int32)
    active = jnp.asarray(rng.rand(capacity) < 0.5)
    cases = {
        "fork_offsets": ((counts,), {}),
        "segmented_fork_offsets": ((counts, seg), {"n_segs": 4}),
        "type_rank": ((types, active), {"n_types": 6}),
        "lane_pack": ((active,), {}),
    }
    for name, (args, kw) in cases.items():
        fn = getattr(ops, name)
        t0 = time.perf_counter()
        compiled = jax.jit(
            lambda *a, fn=fn, kw=kw: fn(*a, impl=impl, **kw)
        ).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        if impl == "auto":
            check("tpu_custom_call" in compiled.as_text(),
                  f"{name}: impl='auto' compiled no Pallas kernel")
        got = jax.device_get(compiled(*args))
        want = jax.device_get(
            jax.jit(lambda *a, fn=fn, kw=kw: fn(*a, impl="ref", **kw))(*args)
        )
        for g, w in zip(got, want):
            check(np.array_equal(np.asarray(g), np.asarray(w)),
                  f"{name}: impl={impl!r} differs from the ref oracle")
        clock.take()
        log(f"kernels: {name} lanes={capacity} impl={impl} "
            f"compile_s={compile_s:.2f} equal_to_ref=True "
            f"{device_report(devices)}")


# ------------------------------------------------------------------ runtime
@dataclasses.dataclass
class Tenant:
    """One job of the fleet with its plain reference check."""

    name: str
    program: Any
    initial: Any
    heap_init: Dict[str, Any]
    peak_tv: Optional[int]          # TV high-water mark (None: measure)
    verify: Callable[[Any], None]   # raises unless the result is right

    @property
    def quota(self) -> int:
        return 1 << (self.peak_tv - 1).bit_length()


def make_tenants(seed: int, fib_n: int, queens_n: int, bfs_nodes: int,
                 sort_n: Optional[int],
                 bfs_degree: int = BFS_DEGREE) -> List[Tenant]:
    """The fleet's jobs, their data made from ``seed`` (no mergesort
    where ``sort_n`` is None).

    ``peak_tv`` is each job's TV high-water mark.  The TVM's allocation is
    deterministic, so for fib, N-Queens and mergesort it is a count of the
    task tree: one slot per fib call, one per N-Queens placement, one per
    mergesort node.  BFS depends on the graph: :data:`BFS_PEAK_TV` holds
    the measured peak of the default graph, and :func:`measure_peaks`
    measures any other.  Every wave re-measures every job's peak and
    checks it against these."""
    from repro.apps import bfs, fib, mergesort, nqueens

    fib_ref = fib.fib_reference(fib_n)

    def fib_ok(r):
        got = int(np.asarray(r.value)[0, 0])
        check(got == fib_ref, f"fib({fib_n}) = {got}, want {fib_ref}")

    queens_ref = nqueens.SOLUTIONS[queens_n]

    def queens_ok(r):
        got = int(np.asarray(r.heap["count"])[0])
        check(got == queens_ref,
              f"nqueens({queens_n}) = {got}, want {queens_ref}")

    adj_off, adj = bfs.random_graph(bfs_nodes, bfs_degree, seed=seed)
    dist_ref = bfs.bfs_reference(adj_off, adj, 0, bfs_nodes)

    def bfs_ok(r):
        check(np.array_equal(np.asarray(r.heap["dist"]), dist_ref),
              "bfs distances differ from the sequential reference")

    bfs_heap = bfs.heap_init(adj_off, adj, bfs_nodes)
    tenants = [
        Tenant(f"fib{fib_n}", fib.PROGRAM, fib.initial(fib_n), {},
               2 * fib.fib_reference(fib_n + 1) - 1, fib_ok),
        Tenant(f"nqueens{queens_n}", nqueens.make_program(queens_n),
               nqueens.initial(), {}, queens_nodes(queens_n), queens_ok),
        Tenant(f"bfs{bfs_nodes}", bfs.make_program(bfs_nodes, len(adj)),
               bfs.initial(0), bfs_heap,
               BFS_PEAK_TV.get((bfs_nodes, bfs_degree, seed)), bfs_ok),
    ]
    if sort_n is None:
        return tenants
    keys = mergesort.random_input(sort_n, seed=seed)
    sorted_ref = np.sort(keys)

    def sort_ok(r):
        got = np.asarray(r.heap["src"])[mergesort.result_buffer(sort_n)]
        check(np.array_equal(got, sorted_ref),
              "mergesort output differs from np.sort")

    tenants.append(
        Tenant(f"mergesort{sort_n}", mergesort.make_program(sort_n, True),
               mergesort.initial(sort_n), dict(inp=keys), 2 * sort_n - 1,
               sort_ok))
    return tenants


# TV high-water mark of BFS from node 0 of bfs.random_graph(nodes, degree,
# seed): one slot per visit task, measured with HostEngine on a CPU
BFS_PEAK_TV = {(1 << 7, 16, 0): 3672, (1 << 14, 16, 0): 387971,
               (1 << 16, 16, 0): 1690214}


def queens_nodes(n: int) -> int:
    """Nodes of the N-Queens backtracking tree, root included."""

    def count(row, cols, d1, d2):
        if row == n:
            return 1
        total = 1
        for c in range(n):
            if not (cols >> c & 1 or d1 >> (row + c) & 1
                    or d2 >> (row - c + n - 1) & 1):
                total += count(row + 1, cols | 1 << c, d1 | 1 << (row + c),
                               d2 | 1 << (row - c + n - 1))
        return total

    return count(0, 0, 0, 0)


def measure_peaks(clock, devices, tenants: List[Tenant],
                  capacity: int = CAPACITY) -> None:
    """Run each job whose peak is unknown alone on the host engine,
    check its result, and record the TV high-water mark it reached."""
    from repro.core import HostEngine
    from repro.service.jobs import JobResult, JobStats

    for t in tenants:
        if t.peak_tv is not None:
            continue
        t0 = time.perf_counter()
        heap, value, s = HostEngine(t.program, capacity=capacity).run(
            t.initial, heap_init=t.heap_init or None)
        t.verify(JobResult(heap=heap, value=value, stats=JobStats()))
        t.peak_tv = s.peak_tv_slots
        log(f"runtime: {t.name} alone on the host engine, tv_lanes="
            f"{capacity} peak_tv={t.peak_tv} epochs={s.epochs} "
            f"wall_s={time.perf_counter() - t0:.2f} "
            f"compile_s={clock.take():.2f} {device_report(devices)}")


def submit_all(svc, tenants: List[Tenant], copies: int = 1) -> List[Any]:
    return [
        svc.submit(t.program, t.initial, heap_init=t.heap_init,
                   quota=t.quota, name=f"{t.name}#{c}")
        for c in range(copies) for t in tenants
    ]


def verify_jobs(handles, tenants: List[Tenant], copies: int = 1) -> None:
    for h, t in zip(handles, [t for _ in range(copies) for t in tenants]):
        check(h.status.value == "done", f"{h.job.name}: {h.status.value} "
              f"({h.error})")
        t.verify(h.result)
        check(h.result.stats.peak_tv_slots == t.peak_tv,
              f"{h.job.name}: peak TV {h.result.stats.peak_tv_slots}, "
              f"measured {t.peak_tv} before")


def run_wave(clock, devices, tenants: List[Tenant], engine: str,
             dispatch: str, chunk: int = CHUNK) -> None:
    """One wave of ``tenants`` through ``JobService``, every job checked
    against its reference; on the device engine, the lane tiling too."""
    from repro.service import JobService

    wave = sum(t.quota for t in tenants)
    kw = dict(chunk=chunk) if engine == "device" else {}
    t0 = time.perf_counter()
    svc = JobService(capacity=wave, max_jobs=len(tenants), engine=engine,
                     dispatch=dispatch, **kw)
    handles = submit_all(svc, tenants)
    svc.drain()
    wall = time.perf_counter() - t0
    verify_jobs(handles, tenants)
    s = svc.stats()
    if engine == "device":
        check(s.lanes_launched + s.hole_lanes_skipped == wave * s.epochs,
              f"device/{dispatch}: launched {s.lanes_launched} + skipped "
              f"{s.hole_lanes_skipped} != {wave} x {s.epochs}")
    sizes = " ".join(f"{t.name}:quota={t.quota}" for t in tenants)
    log(f"runtime: engine={engine} dispatch={dispatch} "
        f"{'chunk=%d ' % chunk if engine == 'device' else ''}"
        f"tv_lanes={wave} {sizes} epochs={s.epochs} "
        f"tasks={s.tasks_executed} dispatches={s.dispatches} "
        f"lanes_launched={s.lanes_launched} "
        f"hole_lanes_skipped={s.hole_lanes_skipped} wall_s={wall:.2f} "
        f"compile_s={clock.take():.2f} all_jobs_match_reference=True "
        f"{device_report(devices)}")


def phase_runtime(clock, devices, mixed: List[Tenant], bots: List[Tenant],
                  chunk: int = CHUNK) -> None:
    """The mixed fleet through the host engine and the chunked device
    engine, the BOTS-class fleet through the chunked device engine, each
    under masked and gather dispatch."""
    for engine in ("host", "device"):
        for dispatch in ("masked", "gather"):
            run_wave(clock, devices, mixed, engine, dispatch, chunk)
    for dispatch in ("masked", "gather"):
        run_wave(clock, devices, bots, "device", dispatch, chunk)


# -------------------------------------------------------------------- model
def phase_model(clock, devices, cfg=None, requests: int = 8,
                max_new: int = 16, max_len: int = 128, seed: int = 0,
                impl: str = "auto") -> None:
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.kernels import ops
    from repro.launch.serve import serve
    from repro.models.model import decode_step, prefill

    cfg = cfg or configs.get_config("granite_moe_1b_a400m")
    if cfg.moe is not None:
        # dropless, as the published Granite MoE: an expert's capacity
        # covers every token, so a batch routes each token as it would
        # alone and the one-request reference computes the same function
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=cfg.moe.n_experts / cfg.moe.top_k))
    hd = cfg.resolved_head_dim
    rng = np.random.RandomState(seed)

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape), cfg.compute_dtype)

    def close(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b) / (1.0 + np.abs(b))))

    q, k, v = (rand(requests, cfg.n_heads, 32, hd),
               rand(requests, cfg.n_kv_heads, 32, hd),
               rand(requests, cfg.n_kv_heads, 32, hd))
    err = close(ops.attention(q, k, v, impl=impl),
                ops.attention(q, k, v, impl="ref"))
    check(err <= ATTN_TOL, f"flash attention vs ref: {err} > {ATTN_TOL}")
    qd = rand(requests, cfg.n_heads, hd)
    kc, vc = (rand(requests, cfg.n_kv_heads, max_len, hd),
              rand(requests, cfg.n_kv_heads, max_len, hd))
    lens = jnp.asarray(rng.randint(1, max_len + 1, requests), jnp.int32)
    derr = close(ops.gqa_decode(qd, kc, vc, lens, impl=impl),
                 ops.gqa_decode(qd, kc, vc, lens, impl="ref"))
    check(derr <= ATTN_TOL, f"decode attention vs ref: {derr} > {ATTN_TOL}")
    log(f"model: kernels at {cfg.name} widths (heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads}, head_dim {hd}, bf16) flash_err={err:.2e} "
        f"decode_err={derr:.2e} tol={ATTN_TOL} compile_s={clock.take():.2f}")

    t0 = time.perf_counter()
    server, params, done = serve(
        cfg, requests=requests, slots=requests, max_new=max_new,
        max_len=max_len, seed=seed,
    )
    wall = time.perf_counter() - t0
    check(len(done) == requests, f"served {len(done)} of {requests}")
    for r in done:
        check(len(r.output) == max_new,
              f"request {r.rid}: {len(r.output)} tokens, want {max_new}")
    rids, logits = server.last_prefill
    check(sorted(rids) == sorted(r.rid for r in done),
          "all requests should be admitted in one prefill")
    n_params = sum(int(np.prod(p.shape)) for p in params.values())
    log(f"model: served {requests} requests x {max_new} tokens, "
        f"{cfg.n_layers} layers d_model {cfg.d_model}, "
        f"{n_params} params, wall_s={wall:.2f} "
        f"compile_s={clock.take():.2f} {device_report(devices)}")

    # the plain references: the same batch through one prefill, and each
    # request alone, right-padded to the bucket the server prefilled at,
    # then greedy decode_step one token at a time
    by_rid = {r.rid: r for r in done}
    bucket = 8
    while bucket < max(len(r.prompt) for r in done):
        bucket *= 2
    one_prefill = jax.jit(lambda p, t, last: prefill(
        p, cfg, t, max_len=max_len, last_positions=last))
    one_decode = jax.jit(lambda p, t, c: decode_step(p, cfg, t, c))

    def padded(rs):
        toks = np.zeros((len(rs), bucket), np.int32)
        for i, r in enumerate(rs):
            toks[i, :len(r.prompt)] = r.prompt
        last = np.asarray([len(r.prompt) - 1 for r in rs], np.int32)
        return jnp.asarray(toks), jnp.asarray(last)

    def rel_err(got, ref):
        return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))

    # the padded vocabulary columns hold a -1e30 mask, not logits
    logits = np.asarray(logits, np.float32)[:, :cfg.vocab]
    batch, _ = one_prefill(params, *padded([by_rid[rid] for rid in rids]))
    berr = rel_err(logits, np.asarray(batch, np.float32)[:, :cfg.vocab])
    check(berr <= BATCH_TOL,
          f"first-step logits vs a plain batched prefill: {berr} > "
          f"{BATCH_TOL}")
    worst, same_tokens = 0.0, 0
    for i, rid in enumerate(rids):
        r = by_rid[rid]
        lg, cache = one_prefill(params, *padded([r]))
        ref = np.asarray(lg, np.float32)[0, :cfg.vocab]
        worst = max(worst, rel_err(logits[i], ref))
        out = [int(np.argmax(ref))]
        for _ in range(max_new - 1):
            lg, cache = one_decode(
                params, jnp.asarray([[out[-1]]], jnp.int32), cache)
            out.append(int(np.argmax(np.asarray(lg)[0])))
        same_tokens += sum(a == b for a, b in zip(out, r.output))
    check(worst <= LOGIT_TOL,
          f"first-step logits vs one-request prefill: {worst} > {LOGIT_TOL}")
    log(f"model: first-step logits vs the batched prefill max_rel_err="
        f"{berr:.2e} tol={BATCH_TOL}; vs one-request prefills max_rel_err="
        f"{worst:.2e} tol={LOGIT_TOL}; greedy tokens equal to the "
        f"one-request loop: {same_tokens}/{requests * max_new} "
        f"compile_s={clock.take():.2f} {device_report(devices)}")


# -------------------------------------------------------------------- fleet
def run_fleet(tenants: List[Tenant], shards: int, copies: int,
              chunk: int, cache=None):
    """The fleet through ``JobService(engine="sharded")``.

    Returns the service, its handles, and what every collective launch
    was handed: the fewest devices any stacked carry leaf spanned, how
    many launches took a restacked carry (one rebuilt after a boundary
    seated or migrated a job) rather than the last output, and the host
    seconds spent building the stacked carries."""
    import jax

    from repro.core.engine import EpochLoop
    from repro.distributed.fleet import ShardedFleet
    from repro.service import JobService

    svc = JobService(
        capacity=sum(t.quota for t in tenants), max_jobs=len(tenants),
        engine="sharded", shards=shards, chunk=chunk, dispatch="gather",
        template_cache=cache,
    )
    check((svc.mesh is not None) == (shards > 1),
          f"shards={shards}: mesh {svc.mesh}")
    seen = {"min_devices": None, "restacks": 0, "last": None,
            "stack_s": 0.0, "launches": 0, "t0": time.perf_counter()}
    launch, stacked = EpochLoop.run_chunk_fleet, ShardedFleet._stacked

    def spy(loop, carry, limits, **kw):
        n = min(len(x.sharding.device_set) for x in jax.tree.leaves(carry))
        seen["min_devices"] = min(n, seen["min_devices"] or n)
        if seen["last"] is not None and carry is not seen["last"]:
            seen["restacks"] += 1
        seen["last"] = jax.block_until_ready(
            launch(loop, carry, limits, **kw))
        seen["launches"] += 1
        # one line per launch, so a run cut by its time limit shows how
        # far it got
        log(f"fleet: shards={shards} launch {seen['launches']} done at "
            f"{time.perf_counter() - seen['t0']:.2f} s")
        return seen["last"]

    def timed_stacked(fleet):
        t0, before = time.perf_counter(), fleet._fcarry
        carry = stacked(fleet)
        if carry is not before:
            jax.block_until_ready(carry)
            seen["stack_s"] += time.perf_counter() - t0
        return carry

    handles = submit_all(svc, tenants, copies)
    EpochLoop.run_chunk_fleet = spy
    ShardedFleet._stacked = timed_stacked
    try:
        svc.drain()
    finally:
        EpochLoop.run_chunk_fleet = launch
        ShardedFleet._stacked = stacked
    return svc, handles, seen


def phase_fleet(clock, devices, tenants: List[Tenant], shards: int = 4,
                copies: int = FLEET_COPIES, chunk: int = CHUNK) -> None:
    from repro.service import WaveTemplateCache

    results = {}
    for p in (1, shards):
        t0 = time.perf_counter()
        svc, handles, spread = run_fleet(
            tenants, p, copies, chunk, cache=WaveTemplateCache())
        wall = time.perf_counter() - t0
        verify_jobs(handles, tenants, copies)
        fl = svc._mux
        results[p] = (svc, handles, fl)
        shard_stats = fl.shard_stats()
        if p > 1:
            check(spread["min_devices"] == p,
                  f"a launch carried leaves on {spread['min_devices']} "
                  f"of {p} devices")
            check(spread["restacks"] > 0,
                  "no launch followed a seat or migration")
        log(f"fleet: shards={p} jobs={len(handles)} chunk={chunk} "
            f"tv_lanes_per_shard={sum(t.quota for t in tenants)} "
            f"collective_steps={fl.collective_steps} "
            f"migrations={fl.migrations} restacked_launches="
            f"{spread['restacks']} stack_s={spread['stack_s']:.3f} "
            f"carry_devices={spread['min_devices']} "
            f"shard_tasks={[s.tasks_executed for s in shard_stats]} "
            f"wall_s={wall:.2f} compile_s={clock.take():.2f} "
            f"all_jobs_match_reference=True {device_report(devices)}")
    (_, h1, f1), (_, hp, fp) = results[1], results[shards]
    for a, b in zip(h1, hp):
        check(np.array_equal(np.asarray(a.result.value),
                             np.asarray(b.result.value)),
              f"{a.job.name}: value block differs between shard counts")
        for key in a.result.heap:
            check(np.array_equal(np.asarray(a.result.heap[key]),
                                 np.asarray(b.result.heap[key])),
                  f"{a.job.name}: heap {key} differs between shard counts")
        check(a.result.stats.epochs == b.result.stats.epochs,
              f"{a.job.name}: epochs differ between shard counts")
    one = f1.stats()
    per = fp.shard_stats()
    check(sum(s.tasks_executed for s in per) == one.tasks_executed,
          "per-shard task sums differ from shards=1")
    check(sum(s.total_forks for s in per) == one.total_forks,
          "per-shard fork sums differ from shards=1")
    log(f"fleet: shards={shards} bit-identical to shards=1 for "
        f"{len(h1)} jobs; tasks={one.tasks_executed} "
        f"forks={one.total_forks} conserved")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-fleet phase on 4 chips")
    ap.add_argument(
        "--seed", type=int, default=0,
        help="seed of the data and weights; a seed other than 0 first "
        "measures each BFS graph's peak TV alone on the host engine, "
        "which compiles many launch widths",
    )
    args = ap.parse_args(argv)

    devices = startup(args.chips)
    clock = CompileClock()
    if args.chips == 4:
        fleet = make_tenants(args.seed, **FLEET)
        measure_peaks(clock, devices, fleet)
        phase_fleet(clock, devices, fleet, shards=4)
    else:
        mixed = make_tenants(args.seed, **MIXED)
        bots = make_tenants(args.seed, **BOTS)
        measure_peaks(clock, devices, mixed + bots)
        phase_kernels(clock, devices, seed=args.seed)
        phase_model(clock, devices, seed=args.seed)
        phase_runtime(clock, devices, mixed, bots)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
