"""The reduction from a profiler trace to the per-layer metrics, checked on
a short trace recorded on a v5e chip (``bench/tests/data``)."""
from __future__ import annotations

import collections
import json
import lzma
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import costs, harness, tracecut  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    """The recorded trace of 2 s of ``bots_dc.closed`` (seed 2101)."""
    path = tmp_path_factory.mktemp("trace") / "bots_dc.closed.xplane.pb"
    path.write_bytes(lzma.decompress(
        (DATA / "bots_dc.closed.xplane.pb.xz").read_bytes()))
    return str(path)


@pytest.fixture(scope="module")
def digest(trace):
    return tracecut.reduce_file(trace)


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "bots_dc.closed.json").read_text())


def test_digest_counts_match_the_raw_trace(trace, digest):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace)
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    lo, hi = digest.window
    loops = [e for e in lines["XLA Modules"]
             if e.name.startswith("jit_loop(")
             and e.start_ns * 1e-9 >= lo and e.end_ns * 1e-9 <= hi]
    assert len(digest.chunk_runs()) == len(loops) > 1
    kernels = collections.Counter(
        e.name.split(".")[0][1:] for e in lines["XLA Ops"]
        if "tpu_custom_call" in e.name and lo <= e.start_ns * 1e-9 < hi)
    got = collections.Counter(k for k, *_ in digest.kernel_calls())
    assert got == kernels and set(got) <= set(costs.FORK_KERNELS)


def test_busy_and_gaps_partition_the_window(digest):
    busy = digest.busy_s
    idle = sum(e - s for s, e in digest.idle_gaps(0))
    assert 0 < busy <= digest.window_s
    assert abs(busy + idle - digest.window_s) < 1e-6
    # self times add up to the busy time of one device (no double count)
    assert sum(digest.self_times().values()) == pytest.approx(busy, rel=1e-2)


def test_metrics_from_the_recorded_trace(digest, recorded):
    run = harness.Run(seconds=recorded["seconds"], t_process=0.0,
                      t_window=0.0, records=[], stats=recorded["stats"],
                      trace=digest, peaks=tracecut.peaks_for("TPU v5 lite"))
    for name, want in recorded["metrics"].items():
        got = harness.load_reader(name)(run)
        assert got == pytest.approx(want, rel=1e-9), name
    roof = recorded["metrics"]["fork_kernels_roofline.batch"]
    assert 0 < roof <= 100


def test_breakdown_shape(digest):
    b = digest.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= 10
        assert all(isinstance(n, str) and v >= 0 for n, v in b[key])
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        tracecut.peaks_for("TPU v9 imaginary")
