"""The check that decides ``correct``, driven end to end on the CPU.

Each cell runs at a size a test can hold, with the chip look skipped:
a sound run must come out correct; the control (every answer replaced by
the kind's control) and each fault planted in the timed path underneath
must come out not correct.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

SMALL_QUOTA = {"fib": 1 << 11, "nqueens": 1 << 14, "bfs_urand": 1 << 13}
SMALL_SIZES = {"fib_n": [12, 13], "nqueens_n": 9, "scale": 6}
# every answer at the small sizes fits the cell's int16; the control keeps
# them in int8 instead, past which they all lie (fib(12) = 144, nqueens(9)
# = 352), so that it is wrong on every job as at the cell's own size
SMALL_CONTROL_BITS = 8
CELLS = ("bots_dc.closed", "gap_urand_bfs.closed")


def small_cell(name):
    cell = harness.load_cell(name)
    for r in cell.traffic["regions"]:
        r["quota"] = SMALL_QUOTA[r["kind"]]
    cell.config["sizes"].update(
        {k: v for k, v in SMALL_SIZES.items() if k in cell.config["sizes"]})
    if "control" in cell.config:
        cell.config["control"]["control_bits"] = SMALL_CONTROL_BITS
    return cell


@pytest.fixture(scope="module")
def caches():
    from repro.service.jobs import WaveTemplateCache

    return {name: WaveTemplateCache() for name in CELLS}


def run(name, caches, seed, **kw):
    return harness.run_cell(small_cell(name), seed, 1.0, False,
                            time.monotonic(), template_cache=caches[name],
                            grace=1.0, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, caches):
    out = run(name, caches, 2**31 + 11)
    assert out["correct"], out["checks"]
    assert out["attempted"] > len(small_cell(name).traffic["regions"])
    for m in small_cell(name).end_to_end:
        assert out["metrics"][m["name"]]["value"] > 0, m["name"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, caches):
    out = run(name, caches, 5, control=True)
    assert not out["correct"]
    assert out["checks"]["wrong"]["value"] > 0


def _plant(fault, monkeypatch):
    from repro.core.engine import EpochLoop
    from repro.service import JobService
    from repro.service.multiplexer import _FleetBase

    if fault == "state_unchanged":
        monkeypatch.setattr(EpochLoop, "run_chunk",
                            lambda self, carry, limit, n_regions: carry)
    elif fault == "half_left_out":
        pump, seen = JobService._pump, [0]

        def half(self):
            kept = []
            for h in pump(self):
                seen[0] += 1
                if seen[0] % 2:
                    kept.append(h)
            return kept

        monkeypatch.setattr(JobService, "_pump", half)
    elif fault == "answer_altered":
        fin = _FleetBase._finalize

        def altered(self, j):
            h = fin(self, j)
            r = h.result
            h.result = dataclasses.replace(
                r, value=r.value + 1,
                heap={k: v + 1 for k, v in r.heap.items()})
            return h

        monkeypatch.setattr(_FleetBase, "_finalize", altered)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, caches, monkeypatch):
    warm = harness.warm_up

    def warm_then_break(*a, **kw):
        warm(*a, **kw)
        _plant(fault, monkeypatch)

    monkeypatch.setattr(harness, "warm_up", warm_then_break)
    out = run(name, caches, 9)
    assert not out["correct"]
    key = "wrong" if fault == "answer_altered" else "missing"
    assert out["checks"][key]["value"] > 0
