"""The GAP kron cell on the CPU: its generator, its roots, its check, and
the per-job readers it reports.

The check runs the cell at a size a test can hold (a scale-8 graph, whose
largest hubs still take the fan-out), with the chip look skipped: a sound
run must come out correct, and the control and a fault planted under the
timed path must not.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.kinds import bfs_kron, bfs_urand  # noqa: E402

CELL = "gap_kron_bfs.closed"


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_kron_csr_deterministic_symmetric_simple(seed):
    a = bfs_kron.kron_csr(7, 16, np.random.default_rng(seed))
    b = bfs_kron.kron_csr(7, 16, np.random.default_rng(seed))
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    adj_off, adj = a
    n = 1 << 7
    assert adj_off[0] == 0 and adj_off[-1] == len(adj)
    src = np.repeat(np.arange(n), np.diff(adj_off))
    fwd = set(zip(src.tolist(), adj.tolist()))
    # symmetrized, with no self-loop and no duplicate arc
    assert len(fwd) == len(adj)
    assert fwd == {(v, u) for u, v in fwd}
    assert not np.any(src == adj)
    # every arc comes from one of the generator's degree * 2^scale edges
    u, v = bfs_kron.kron_edges(7, 16, np.random.default_rng(seed))
    assert len(u) == 16 << 7
    pairs = {(x, y) for x, y in zip(u.tolist(), v.tolist()) if x != y}
    assert fwd == pairs | {(y, x) for x, y in pairs}


def test_kron_differs_across_seeds():
    a = bfs_kron.kron_csr(7, 16, np.random.default_rng(1))[1]
    b = bfs_kron.kron_csr(7, 16, np.random.default_rng(2))[1]
    assert not np.array_equal(a, b)


@pytest.mark.parametrize("seed", [0, 7])
def test_kron_degrees_are_skewed(seed):
    deg = np.diff(bfs_kron.Graph(10, 16, seed, 8).adj_off)
    flat = np.diff(bfs_urand.Graph(10, 16, seed, 8).adj_off)
    # hubs: the largest degree is many times the mean and the uniform
    # graph's largest, and the top 1% of vertices hold over a tenth of
    # the arcs; the generator also leaves some vertices without a
    # neighbour, as Graph500's does
    assert deg.max() > 15 * deg.mean()
    assert deg.max() > 5 * flat.max()
    top = np.sort(deg)[::-1][:len(deg) // 100]
    assert top.sum() > 0.1 * deg.sum()
    assert np.any(deg == 0)


def test_roots_cycle_in_a_fixed_order_per_region():
    params = {"scale": 6, "degree": 16, "graph_seed": 3, "queries": 8}
    seen = []
    for seed in (1, 2**31 + 5):
        shared, owns = {}, [{}, {}]
        rngs = [np.random.default_rng([seed, 1 + i]) for i in range(2)]
        names = [[], []]
        for _ in range(9):
            for i in range(2):
                job = bfs_kron.make(params, rngs[i], owns[i], shared)
                names[i].append(int(job["name"][4:]))
        seen.append(names)
    roots = bfs_kron.Graph(6, 16, 3, 8).roots.tolist()
    assert len(set(roots)) == 8
    # region r starts at root 4r and walks the cycle; the run's seed
    # changes nothing
    assert seen[0] == seen[1]
    assert seen[0][0] == (roots * 2)[:9]
    assert seen[0][1] == (roots * 2)[4:13]


def test_reference_against_queue_bfs_and_control():
    g = bfs_kron.Graph(9, 16, seed=3, queries=4)
    for root in g.roots.tolist():
        want = np.full(g.n, bfs_kron.INF, np.int64)
        want[root] = 0
        q = collections.deque([root])
        while q:
            v = q.popleft()
            for u in g.adj[g.adj_off[v]:g.adj_off[v + 1]]:
                if want[u] == bfs_kron.INF:
                    want[u] = want[v] + 1
                    q.append(u)
        assert np.array_equal(bfs_kron.bfs_levels(g.adj_off, g.adj, root),
                              want)
        ctl = bfs_kron.first_claim_depths(g.adj_off, g.adj, root)
        assert not bfs_kron.same(ctl, want)


def test_self_loop_variant_keeps_the_shape():
    a = bfs_kron.Graph(7, 16, 2, 8)
    b = bfs_kron.Graph(7, 16, 2, 8, self_loops=True)
    assert np.array_equal(a.adj_off, b.adj_off)
    assert np.array_equal(a.roots, b.roots)
    assert np.all(b.adj == np.repeat(np.arange(a.n), np.diff(a.adj_off)))


# ------------------------------------------------------------ the readers
@pytest.mark.parametrize("name,key", [
    ("epochs_per_job.batch", "job_epochs_finished"),
    ("tasks_per_job.batch", "job_tasks_finished"),
])
def test_per_job_readers(name, key):
    from repro.core.scheduler import RunStats

    read = harness.load_reader(name)
    before = RunStats(jobs_finished=2, job_epochs_finished=30,
                      job_tasks_finished=400_000)
    after = RunStats(jobs_finished=6, job_epochs_finished=96,
                     job_tasks_finished=1_200_000)
    stats = harness.stats_delta(before, after)
    run = harness.Run(seconds=1.0, t_process=0.0, t_window=0.0, records=[],
                      stats=stats)
    assert read(run) == stats[key] / 4
    # a window in which no job finished, and a program without the
    # counters, read nothing
    for empty in ({"jobs_finished": 0, key: 0}, {"tasks_executed": 9}):
        assert read(dataclasses.replace(run, stats=empty)) is None


# --------------------------------------------------------------- the check
def small_cell():
    cell = harness.load_cell(CELL)
    for r in cell.traffic["regions"]:
        r["quota"] = 1 << 14
    cell.config["sizes"]["scale"] = 8
    return cell


@pytest.fixture(scope="module")
def cache():
    from repro.service.jobs import WaveTemplateCache

    return WaveTemplateCache()


def _alter_answers(monkeypatch):
    from repro.service.multiplexer import _FleetBase

    fin = _FleetBase._finalize

    def altered(self, j):
        h = fin(self, j)
        h.result = dataclasses.replace(
            h.result, heap={k: v + 1 for k, v in h.result.heap.items()})
        return h

    monkeypatch.setattr(_FleetBase, "_finalize", altered)


@pytest.mark.parametrize("case", ["sound", "control", "answer_altered"])
def test_kron_check(case, cache, monkeypatch):
    cell = small_cell()
    assert np.diff(bfs_kron.Graph(8, 16, 2, 8).adj_off).max() > 2 * 64
    if case == "answer_altered":
        warm = harness.warm_up

        def warm_then_break(*a, **kw):
            warm(*a, **kw)
            _alter_answers(monkeypatch)

        monkeypatch.setattr(harness, "warm_up", warm_then_break)
    out = harness.run_cell(cell, 2**31 + 11, 1.0, False, time.monotonic(),
                           template_cache=cache, grace=5.0,
                           control=case == "control")
    if case == "sound":
        assert out["correct"], out["checks"]
        assert out["attempted"] > len(cell.traffic["regions"])
        for m in cell.end_to_end:
            assert out["metrics"][m["name"]]["value"] > 0, m["name"]
    else:
        assert not out["correct"]
        assert out["checks"]["wrong"]["value"] > 0
