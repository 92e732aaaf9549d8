"""One run of one benchmark cell: set-up, a measured window, the check.

The cell is found by its name in ``BENCHMARK.json``.  Everything that
belongs to one configuration, traffic mix, job kind or metric lives in a
file of its own, which this module loads by name:

* ``BENCHMARK.json`` ``configs[].file``: the deployment (sizes, service
  settings, the guarantees its answers keep);
* ``bench/traffic/<config>/<traffic>.json``: the regions, their quotas and
  job kinds, the chunk size and the warm-up;
* ``bench/kinds/<kind>.py``: a job generator with its plain reference;
* ``bench/metrics/<metric>.py``: a reader, ``read(run) -> float | None``.

The program is driven only through ``JobService``: ``submit`` and the
one step of ``completions`` (``_pump``), which the closed loop calls
itself so that it can stop at the window's close and bound the drain.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# fixed paths inside the checkout: the compile cache's key includes its
# directory, so a path made from a temporary name would never hit
CACHE_DIR = BENCH / ".jax_cache"
TRACE_DIR = BENCH / ".trace"
DRAIN_GRACE_S = 60.0  # how long past the close the loop waits for answers
# a trace or a compile inside the window is a fault of the warm-up
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
)
SETUP_EVENTS = COMPILE_EVENTS + (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ loading
@dataclasses.dataclass
class Cell:
    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic files."""
    bm = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bm["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / w["config"] / f"{w['traffic']}.json")
        .read_text())
    return Cell(
        name=name, workload=w, config=config, traffic=traffic,
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if _applies(m, name)],
    )


def load_kind(name: str):
    return importlib.import_module(f"bench.kinds.{name}")


def load_reader(metric: str) -> Callable[[Any], Optional[float]]:
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ JAX start-up
def configure_jax() -> None:
    """Persistent compile cache in the checkout; call before JAX starts."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def tpu_devices(chips: int):
    """The TPU devices, or ``SystemExit`` when there are fewer than
    ``chips``: no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"bench: needs {chips} TPU chip(s), JAX found "
            f"{len(devices)} {devices[0].platform} device(s); no result")
        raise SystemExit(3)
    return devices


class GcClock:
    """Pauses of the garbage collector while ``on`` holds: one
    ``(generation, seconds)`` per collection (``gc.callbacks``)."""

    def __init__(self):
        self.on = False
        self.pauses: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self.on:
            self.pauses.append((info["generation"], time.monotonic() - self._t))

    def close(self):
        gc.callbacks.remove(self._cb)


class CompileCounter:
    """Counts traces and compiles (``jax.monitoring``), and the seconds of
    each set-up event: tracing, lowering, compiling, reading the cache."""

    def __init__(self):
        import jax

        self.events = 0
        self.seconds = {e.rsplit("/", 1)[1]: 0.0 for e in SETUP_EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            self.events += 1
        if name in SETUP_EVENTS:
            self.seconds[name.rsplit("/", 1)[1]] += secs


# ------------------------------------------------------------- the clients
@dataclasses.dataclass
class JobRecord:
    """One job as the client saw it."""

    kind: str
    due: float
    expect: Callable[[], Any]
    control: Callable[[], Any]
    done_at: Optional[float] = None
    status: str = "queued"
    answer: Any = None
    ok: Optional[bool] = None


class Client:
    """One closed-loop client: holds one region's worth of jobs in flight,
    and sends the next the moment the last one's answer is back."""

    def __init__(self, region: int, spec: Dict[str, Any],
                 config: Dict[str, Any], seed: int, shared: Dict[str, Any],
                 params: Optional[Dict[str, Any]] = None):
        self.region = region
        self.kind_name = spec["kind"]
        self.kind = load_kind(self.kind_name)
        self.quota = int(spec["quota"])
        self.params = {**config["sizes"], **config.get("control", {}),
                       **(params or {})}
        self.rng = np.random.default_rng([seed, 1 + region])
        self.own: Dict[str, Any] = {}
        self.shared = shared.setdefault(self.kind_name, {})

    def job(self) -> Dict[str, Any]:
        return self.kind.make(self.params, self.rng, self.own, self.shared)


def make_clients(traffic, config, seed, shared) -> List[Client]:
    if traffic["arrivals"] != "closed":
        raise ValueError(f"arrivals {traffic['arrivals']!r}: only closed "
                         "loops are generated")
    return [Client(i, spec, config, seed, shared)
            for i, spec in enumerate(traffic["regions"])]


def make_service(cell: Cell, template_cache=None, tracer=None):
    from repro.service import JobService

    svc_cfg = dict(cell.config["service"])
    cap = sum(int(r["quota"]) for r in cell.traffic["regions"])
    return JobService(
        capacity=cap, max_jobs=len(cell.traffic["regions"]),
        chunk=int(cell.traffic["chunk"]), template_cache=template_cache,
        tracer=tracer, **svc_cfg,
    )


def closed_loop(svc, clients: List[Client], keep_sending, records: list,
                span=None, grace: float = DRAIN_GRACE_S,
                on_close: Optional[Callable[[], None]] = None,
                control: bool = False,
                pumps: Optional[list] = None) -> float:
    """Run the closed loop while ``keep_sending(elapsed, answered)`` holds
    (``answered``: answers back per region), then drain what is in flight
    for at most ``grace`` seconds more.  Returns the loop's start.

    Each client's first job is due at the start; each later one is due
    the moment its predecessor's answer is back, and is sent then.  An
    answer is back once it is on the host.  Every region holds a job
    until sending stops for all of them at once, so every wave that the
    service forms has one member per region.  With ``control`` each
    answer the service returns is replaced by the kind's control answer
    (``bench/control.py``).  ``pumps`` collects the host seconds of each
    step of the service while sending."""
    from repro.service.jobs import JobStatus

    span = span or _no_span
    inflight: Dict[int, tuple] = {}
    answered = [0] * len(clients)

    def send(c: Client, due: float) -> None:
        j = c.job()
        with span("bench.submit"):
            h = svc.submit(j["program"], j["initial"], heap_init=j["heap"],
                           quota=c.quota, name=j["name"])
        rec = JobRecord(kind=c.kind_name, due=due, expect=j["expect"],
                        control=j["control"])
        records.append(rec)
        inflight[h.job_id] = (rec, c)

    t0 = time.monotonic()
    for c in clients:
        send(c, t0)
    sending, until = True, float("inf")
    while inflight:
        now = time.monotonic()
        if sending and not keep_sending(now - t0, answered):
            sending, until = False, now + grace
            if on_close is not None:
                on_close()
        if now >= until:
            break
        t_pump = time.monotonic()
        with span("bench.pump"):
            done = svc._pump()
        if pumps is not None and sending:
            pumps.append(time.monotonic() - t_pump)
        back = []
        for h in done:
            rec, c = inflight.pop(h.job_id)
            with span("bench.result_read"):
                if h.status is JobStatus.DONE:
                    rec.answer = (rec.control() if control
                                  else c.kind.answer(h.result))
                    rec.status = "done"
                else:
                    rec.status = "failed"
            rec.done_at = time.monotonic()
            answered[c.region] += 1
            h.result = None  # the client keeps the answer, not the arrays
            back.append((rec, c))
        if sending and keep_sending(time.monotonic() - t0, answered):
            for rec, c in back:
                send(c, rec.done_at)
    if sending and on_close is not None:
        on_close()
    return t0


def _no_span(name: str):
    return contextlib.nullcontext()


def warm_up(svc, cell: Cell, seed: int) -> None:
    """Run every wave shape and region reseed that the window will use.

    Each round of ``traffic["warmup"]`` (per-region parameter overrides)
    runs the closed loop until every region has answered twice, then
    drains; the next round starts a new wave from the cached template, as
    the window does.
    The overrides make some regions' jobs shorter than others', so that a
    region frees while the wave is live and is reseeded in flight."""
    for rnd, overrides in enumerate(cell.traffic["warmup"]):
        shared: Dict[str, Any] = {}
        clients = [
            Client(i, spec, cell.config, seed + rnd, shared, over)
            for i, (spec, over) in enumerate(
                zip(cell.traffic["regions"], overrides))
        ]
        closed_loop(svc, clients, lambda _t, answered: min(answered) < 2,
                    [], grace=float("inf"))


# --------------------------------------------------------------- the check
def judge(records: List[JobRecord]) -> Dict[str, int]:
    """Compare every answer with the kind's plain reference.

    ``wrong``: answered but differs; ``failed``: the service failed the
    job; ``missing``: no answer within the drain."""
    wrong = failed = missing = 0
    for r in records:
        if r.status == "failed":
            failed += 1
        elif r.status != "done":
            missing += 1
        else:
            r.ok = load_kind(r.kind).same(r.answer, r.expect())
            wrong += not r.ok
    return {"wrong": wrong, "failed": failed, "missing": missing}


LIMITS = {"wrong": 0, "failed": 0, "missing": 0}


# ------------------------------------------------------------------ a run
@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take it."""

    seconds: float
    t_process: float
    t_window: float
    records: List[JobRecord]
    stats: Dict[str, int] = dataclasses.field(default_factory=dict)
    trace: Any = None
    peaks: Optional[Dict[str, Any]] = None


def stats_delta(a, b) -> Dict[str, int]:
    da, db = a.as_dict(), b.as_dict()
    return {k: db[k] - da[k] for k in db
            if isinstance(db[k], int) and k != "peak_tv_slots"}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, devices=None, template_cache=None,
             grace: float = DRAIN_GRACE_S,
             control: bool = False) -> Dict[str, Any]:
    """Set up, warm up, measure the window, check; return the result line
    (without ``device``).  ``template_cache`` lets tests share compiled
    wave templates between runs; ``control`` answers every job with the
    kind's control (``bench/control.py``)."""
    import jax

    from bench import stats as bstats

    counter = CompileCounter()
    shared: Dict[str, Any] = {}
    tracer = None
    if trace:
        from repro.obs.trace import SpanTracer

        tracer = SpanTracer()
    svc = make_service(cell, template_cache=template_cache, tracer=tracer)
    warm_up(svc, cell, seed ^ 0x5EED)
    # what set-up made lives to the end: keep it out of the window's full
    # collections
    gc.collect()
    gc.freeze()
    gc_clock = GcClock()
    gc_clock.on = True
    pumps: List[float] = []
    clients = make_clients(cell.traffic, cell.config, seed, shared)
    records: List[JobRecord] = []
    compiles_before = counter.events
    stats0 = svc.stats()
    box: Dict[str, Any] = {}
    span = _no_span
    if trace:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        span = jax.profiler.TraceAnnotation
        window_ann = jax.profiler.TraceAnnotation("bench.window")
        window_ann.__enter__()

    def on_close():
        box["stats"] = stats_delta(stats0, svc.stats())
        box["compiles"] = counter.events - compiles_before
        gc_clock.on = False
        if trace:
            window_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()

    t0 = closed_loop(svc, clients, lambda t, _a: t < seconds, records,
                     span=span, grace=grace, on_close=on_close,
                     control=control, pumps=pumps)
    gc_clock.close()
    gc.unfreeze()
    gen2 = [t for g, t in gc_clock.pauses if g == 2]
    log(f"bench: host in the window: {len(pumps)} steps, longest "
        + " ".join(f"{t:.3f}" for t in sorted(pumps)[-4:])
        + f" s, median {bstats.percentile(pumps, 50)} s; gc "
        f"{len(gc_clock.pauses)} collections, {sum(t for _, t in gc_clock.pauses):.4f}"
        f" s, longest {max((t for _, t in gc_clock.pauses), default=0):.4f}"
        f" s; full {len(gen2)}, {sum(gen2):.4f} s")
    log(f"bench: window compiles={box['compiles']} (traces and backend "
        f"compiles inside the window; want 0); set-up seconds by event "
        + " ".join(f"{k}={v:.3f}" for k, v in counter.seconds.items()))
    memory = None
    if devices is not None:  # read before the references run
        memory = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devices[:cell.chips])
    del svc, clients
    run = Run(seconds=seconds, t_process=t_process, t_window=t0,
              records=records, stats=box["stats"])
    if trace:
        from bench import tracecut

        run.trace = tracecut.reduce_dir(TRACE_DIR)
        if devices is not None:
            run.peaks = tracecut.peaks_for(devices[0].device_kind)
    checks = judge(records)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    window = [r for r in records if r.done_at is not None
              and r.done_at <= t0 + seconds]
    lat = [r.done_at - r.due for r in window]
    log(f"bench: {len(records)} jobs sent, {len(window)} answered in the "
        f"window; latency from due p50={bstats.percentile(lat, 50)} "
        f"p95={bstats.percentile(lat, 95)} s; run stats {run.stats}")
    log("bench: answers at (s after the window's start) " + " ".join(
        f"{t - t0:.3f}" for t in sorted(
            r.done_at for r in records if r.done_at is not None)))
    out = {
        "correct": all(checks[k] <= LIMITS[k] for k in LIMITS),
        "attempted": len(records),
        "failed": checks["wrong"] + checks["failed"] + checks["missing"],
        "metrics": metrics,
        "memory_peak_bytes": memory,
    }
    if trace:
        out["busy_s"] = run.trace.busy_s
        out["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = {k: {"value": checks[k], "limit": LIMITS[k]}
                     for k in LIMITS}
    return out


def result_line(out: Dict[str, Any], devices) -> Dict[str, Any]:
    """The run's last line: ``device`` as JAX reports it, ``checks`` last."""
    d = devices[0]
    device = {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices),
        "memory_peak_bytes": out.pop("memory_peak_bytes"),
    }
    if "busy_s" in out:
        device["busy_s"] = out.pop("busy_s")
        device["window_s"] = out.pop("window_s")
    checks = out.pop("checks")
    return {**out, "device": device, "checks": checks}
