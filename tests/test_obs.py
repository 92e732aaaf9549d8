"""Telemetry subsystem tests (DESIGN.md §13).

Covers the three obs layers plus the bench regression gate:

* ``obs/trace.py`` — spans land as valid Chrome-trace-event JSON
  (perfetto-loadable), host drivers emit per-epoch phases, resident
  drivers emit per-chunk spans whose readback count is the ⌈E/K⌉ cadence
  the design promises, and the disabled path changes nothing;
* ``obs/metrics.py`` / ``obs/export.py`` — labeled registry semantics,
  the StatsCollector adapter's per-epoch utilization/hole-fraction
  pairing, per-tenant latency histograms from ``JobService`` lifecycle
  events, JSONL + Prometheus text round-trips;
* ``obs/log.py`` — the shared ``repro`` logger hierarchy and key=value
  formatting;
* ``benchmarks/check.py`` — exact on deterministic counters, fuzzy on
  wall-clock, error on incomparable artifacts.
"""
import importlib.util
import json
import logging
import math
import pathlib

import numpy as np
import pytest

from repro.apps import fib
from repro.core import HostEngine, RunStats, RunStatsCollector
from repro.obs import (
    NULL_TRACER,
    MetricsCollector,
    MetricsError,
    MetricsRegistry,
    SpanTracer,
    export_run_stats,
    get_logger,
    iter_samples,
    iter_spans,
    kv,
    load_trace,
    read_jsonl,
    to_prometheus,
    validate_chrome_trace,
    write_jsonl,
)
from repro.service import JobService


# ---------------------------------------------------------------- trace.py
def test_span_tracer_writes_valid_chrome_trace(tmp_path):
    tr = SpanTracer()
    tr.thread(1, "host-epochs")
    with tr.span("epoch", "host", tid=1, cen=3) as args:
        with tr.span("dispatch", "host", tid=1, launched=8):
            pass
        args.update(util=0.5)
    path = tmp_path / "trace.json"
    tr.write(str(path))

    events = load_trace(str(path))
    spans = list(iter_spans(events, "epoch"))
    assert len(spans) == 1
    assert spans[0]["args"] == {"cen": 3, "util": 0.5}
    inner = list(iter_spans(events, "dispatch", "host"))
    assert len(inner) == 1
    assert inner[0]["dur"] >= 0
    # the late-arg update pattern: values attached after child spans ran
    assert spans[0]["ts"] <= inner[0]["ts"]


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"events": []})
    with pytest.raises(ValueError, match="phase"):
        validate_chrome_trace([{"name": "x"}])
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(
            [{"ph": "X", "name": "x", "ts": 0, "dur": "?", "pid": 1,
              "tid": 0}]
        )


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("epoch", foo=1) as args:
        args.update(bar=2)  # throwaway dict, must not raise
    assert NULL_TRACER.events_named("epoch") == []


def test_host_engine_emits_per_epoch_spans():
    tr = SpanTracer()
    eng = HostEngine(fib.PROGRAM, capacity=256, dispatch="gather", tracer=tr)
    _, _, stats = eng.run(fib.initial(8))

    epochs = list(iter_spans(tr.events, "epoch", "host"))
    assert len(epochs) == stats.epochs
    # gather dispatch: one pack + one dispatch + one readback per epoch
    assert len(list(iter_spans(tr.events, "pack", "host"))) == stats.epochs
    assert (
        len(list(iter_spans(tr.events, "dispatch", "host"))) == stats.epochs
    )
    assert (
        len(list(iter_spans(tr.events, "readback", "host"))) == stats.epochs
    )
    for e in epochs:
        assert e["args"]["mode"] == "gather"
        assert 0.0 <= e["args"]["util"] <= 1.0
    validate_chrome_trace(tr.to_dict())


def test_tracing_off_is_bit_identical():
    ref_eng = HostEngine(fib.PROGRAM, capacity=256)
    _, ref_vals, ref_stats = ref_eng.run(fib.initial(8))
    tr = SpanTracer()
    traced_eng = HostEngine(fib.PROGRAM, capacity=256, tracer=tr)
    _, vals, stats = traced_eng.run(fib.initial(8))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ref_vals))
    assert stats == ref_stats
    assert len(list(iter_spans(tr.events, "epoch"))) == stats.epochs


# --------------------------------------- acceptance: resident chunk cadence
def test_device_service_trace_readbacks_and_tenant_latency():
    """The ISSUE's acceptance criterion: a ``JobService(engine="device",
    chunk=K)`` run with tracing on yields a perfetto-loadable trace whose
    readback-span count is ⌈E/K⌉, plus per-tenant queue-wait and run-time
    histograms for every completed job."""
    K = 3
    reg = MetricsRegistry()
    tr = SpanTracer()
    svc = JobService(
        capacity=512, max_jobs=2, engine="device", chunk=K,
        metrics=reg, tracer=tr,
    )
    svc.submit(fib.PROGRAM, fib.initial(8), quota=256, name="tenant-a")
    svc.submit(fib.PROGRAM, fib.initial(9), quota=256, name="tenant-b")
    handles = svc.drain()
    assert all(h.status.value == "done" for h in handles)

    E = svc.stats().epochs
    assert E > K  # the cadence claim is vacuous on a single chunk
    readbacks = list(iter_spans(tr.events, "readback", "resident"))
    assert len(readbacks) == math.ceil(E / K)
    chunks = list(iter_spans(tr.events, "chunk", "resident"))
    assert len(chunks) == math.ceil(E / K)
    # chunk spans reconstruct per-chunk deltas from the ChunkSummary
    assert sum(c["args"]["epochs"] for c in chunks) == E
    assert all(c["args"]["k"] == K for c in chunks)
    assert (
        sum(c["args"]["tasks"] for c in chunks)
        == svc.stats().tasks_executed
    )
    validate_chrome_trace(tr.to_dict())

    # per-tenant latency split: one queue-wait + one run-time observation
    # per completed job, and a terminal-status counter
    qw = reg.get("trees_job_queue_wait_seconds")
    rt = reg.get("trees_job_run_seconds")
    for tenant in ("tenant-a", "tenant-b"):
        assert qw.labels(tenant=tenant).count == 1
        assert rt.labels(tenant=tenant).count == 1
        assert qw.labels(tenant=tenant).sum >= 0.0
        assert rt.labels(tenant=tenant).sum > 0.0
        assert reg.value(
            "trees_jobs_finished_total", tenant=tenant, status="done"
        ) == 1

    # the template cache counters mirrored into the registry
    assert reg.value(
        "trees_wave_template_lookups_total", outcome="miss"
    ) == 1
    assert reg.value("trees_wave_template_traces") == svc.trace_count

    # driver-labeled run counters fed through the StatsCollector adapter
    assert reg.value(
        "trees_epochs_total", driver="device", dispatch="masked",
        app="service",
    ) == E


# -------------------------------------------------------------- metrics.py
def test_registry_declaration_semantics():
    r = MetricsRegistry()
    c1 = r.counter("x_total", "a counter", ("driver",))
    c2 = r.counter("x_total", "a counter", ("driver",))
    assert c1 is c2  # idempotent re-declare shares the family
    with pytest.raises(MetricsError, match="already registered"):
        r.gauge("x_total", "now a gauge", ("driver",))
    with pytest.raises(MetricsError, match="do not match"):
        c1.labels(nope="x")
    c1.labels(driver="host").inc(2)
    assert r.value("x_total", driver="host") == 2
    with pytest.raises(MetricsError, match=">= 0"):
        c1.labels(driver="host").inc(-1)


def test_histogram_buckets_and_quantile():
    r = MetricsRegistry()
    h = r.histogram("lat_seconds", "", (), buckets=(0.1, 1.0)).labels()
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 3
    assert h.counts == [1, 1, 1]
    assert h.quantile(0.5) == 1.0
    assert h.quantile(1.0) == math.inf
    with pytest.raises(MetricsError, match="histogram"):
        r.value("lat_seconds")


def test_metrics_collector_pairs_holes_with_lanes():
    """The hole-fraction fold: drivers report ``holes_skipped`` just
    before the matching ``lanes`` call, so the adapter emits exactly one
    utilization + one hole-fraction observation per epoch."""
    r = MetricsRegistry()
    eng = HostEngine(
        fib.PROGRAM, capacity=256, dispatch="gather",
        stats_factory=lambda: MetricsCollector(
            RunStatsCollector(), r, driver="host", dispatch="gather",
            app="fib",
        ),
    )
    _, _, stats = eng.run(fib.initial(8))
    lab = dict(driver="host", dispatch="gather", app="fib")
    util = r.get("trees_lane_utilization").labels(**lab)
    frac = r.get("trees_hole_fraction").labels(**lab)
    assert util.count == stats.epochs
    assert frac.count == stats.epochs
    assert r.value("trees_tasks_total", **lab) == stats.tasks_executed
    assert r.value("trees_lanes_total", **lab) == stats.lanes_launched
    assert (
        r.value("trees_hole_lanes_total", **lab) == stats.hole_lanes_skipped
    )
    assert r.value("trees_peak_tv_slots", **lab) == stats.peak_tv_slots


# --------------------------------------------------------------- export.py
def test_export_jsonl_and_prometheus(tmp_path):
    r = MetricsRegistry()
    r.counter("trees_epochs_total", "epochs", ("driver",)).labels(
        driver="host"
    ).inc(23)
    r.histogram("trees_lat_seconds", "lat", (), buckets=(1.0,)).labels(
    ).observe(0.5)

    path = tmp_path / "metrics.jsonl"
    n = write_jsonl(r, str(path))
    samples = read_jsonl(str(path))
    assert len(samples) == n == len(list(iter_samples(r)))
    by_name = {s["name"]: s for s in samples}
    assert by_name["trees_epochs_total"]["value"] == 23
    assert by_name["trees_epochs_total"]["labels"] == {"driver": "host"}
    assert by_name["trees_lat_seconds"]["count"] == 1

    text = to_prometheus(r)
    assert "# TYPE trees_epochs_total counter" in text
    assert 'trees_epochs_total{driver="host"} 23' in text
    assert 'trees_lat_seconds_bucket{le="1"} 1' in text
    assert 'trees_lat_seconds_bucket{le="+Inf"} 1' in text
    assert "trees_lat_seconds_count 1" in text
    assert text.endswith("\n")


def test_export_run_stats_shares_vocabulary():
    r = MetricsRegistry()
    stats = RunStats(epochs=3, tasks_executed=7, lanes_launched=10)
    export_run_stats(r, stats, driver="host", app="fib")
    assert r.value("trees_run_epochs", driver="host", app="fib") == 3
    assert r.value("trees_run_tasks_executed", driver="host", app="fib") == 7
    # derived fields ride along under the same keys as RunStats.as_dict()
    assert r.value(
        "trees_run_utilization", driver="host", app="fib"
    ) == stats.utilization


# ------------------------------------------------------------------ log.py
def test_logger_hierarchy_and_kv(capsys):
    log = get_logger("runtime")
    assert log.name == "repro.runtime"
    assert get_logger("runtime") is log
    line = kv(step=3, elapsed_s=0.25, name="a b")
    assert "step=3" in line and "elapsed_s=0.25" in line
    assert "name='a b'" in line  # values with spaces are quoted

    import repro.obs.log as obslog

    rec = logging.LogRecord(
        "repro.runtime", logging.WARNING, __file__, 1,
        "straggler %s", (kv(step=3),), None,
    )
    out = obslog.KeyValueFormatter().format(rec)
    assert "WARNING" in out
    assert "repro.runtime" in out
    assert "straggler step=3" in out


# ----------------------------------------------------- benchmarks/check.py
def _load_check():
    path = (
        pathlib.Path(__file__).resolve().parents[1]
        / "benchmarks" / "check.py"
    )
    spec = importlib.util.spec_from_file_location("bench_check", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _artifact(tmp_path, name, rows):
    doc = {
        "schema": "trees-bench-v2", "dispatch": "masked", "smoke": True,
        "megakernel": False, "groups": ["fib"], "rows": rows,
    }
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_check_exact_counters_fuzzy_time(tmp_path):
    check = _load_check()
    base_rows = [{
        "name": "fib8", "us_per_call": 100.0, "compile_us": 5.0,
        "derived": "tasks=55;epochs=9;us_per_task=1.8;util=0.62",
        "stats": {"epochs": 9, "tasks_executed": 55},
    }]
    base = _artifact(tmp_path, "base.json", base_rows)

    # big speedup + identical counters: passes (fuzzy one-sided on time)
    fresh_rows = json.loads(json.dumps(base_rows))
    fresh_rows[0]["us_per_call"] = 1.0
    fresh_rows[0]["derived"] = "tasks=55;epochs=9;us_per_task=0.1;util=0.99"
    fresh = _artifact(tmp_path, "fresh.json", fresh_rows)
    assert check.run_check(fresh, base) == 0
    # ... unless --strict, which flags implausible speedups too
    assert check.run_check(fresh, base, strict=True) == 1

    # slowdown beyond the factor fails
    slow_rows = json.loads(json.dumps(base_rows))
    slow_rows[0]["us_per_call"] = 100.0 * 25 * 2
    slow = _artifact(tmp_path, "slow.json", slow_rows)
    assert check.run_check(slow, base) == 1
    assert check.run_check(slow, base, ignore_time=True) == 0

    # a drifted deterministic counter fails exactly, however fast the row
    drift_rows = json.loads(json.dumps(base_rows))
    drift_rows[0]["derived"] = "tasks=56;epochs=9;us_per_task=1.8;util=0.62"
    drift = _artifact(tmp_path, "drift.json", drift_rows)
    assert check.run_check(drift, base) == 1
    # structured stats drift fails too
    sdrift_rows = json.loads(json.dumps(base_rows))
    sdrift_rows[0]["stats"]["tasks_executed"] = 56
    sdrift = _artifact(tmp_path, "sdrift.json", sdrift_rows)
    assert check.run_check(sdrift, base) == 1


def test_check_rejects_incomparable_artifacts(tmp_path):
    check = _load_check()
    a = _artifact(tmp_path, "a.json", [
        {"name": "x", "us_per_call": 1.0, "derived": ""}
    ])
    b = _artifact(tmp_path, "b.json", [
        {"name": "y", "us_per_call": 1.0, "derived": ""}
    ])
    assert check.run_check(a, b) == 2  # empty intersection
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema": "other", "rows": []}))
    assert check.run_check(a, str(bad)) == 2
