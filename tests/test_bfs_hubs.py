"""BFS on graphs with hubs: the range fan-out of long adjacency lists.

* the range tree of a hub forks each of its edges exactly once, in
  ``2 + height`` levels;
* BFS through ``JobService`` (device engine, gather, chunk 4) equals the
  plain reference on a Kronecker graph with hubs and on a star of degree
  4,096, whose BFS takes at most 8 epochs;
* on graphs whose degrees are all at most 64 the per-job stats are the
  chain's, as the program had them before the fan-out;
* the BFS wave's commit still makes 49 scatter passes.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

from bench.kinds import bfs_kron, bfs_urand  # noqa: E402
from repro.apps import bfs  # noqa: E402
from repro.core import tvm  # noqa: E402
from repro.core.primitives import EpochCtx  # noqa: E402
from repro.service import JobService  # noqa: E402
from repro.service.multiplexer import fuse_programs  # noqa: E402


def star(k: int):
    """CSR of a star: vertex 0 joined to vertices 1..k."""
    n = k + 1
    adj_off = np.zeros(n + 1, np.int32)
    adj_off[1] = k
    adj_off[2:] = k + np.arange(1, n)
    adj = np.concatenate([np.arange(1, n), np.zeros(k)]).astype(np.int32)
    return adj_off, adj


def _levels(chunks: int) -> int:
    """Height of the smallest CHUNK-ary tree with ``chunks`` leaves."""
    h = 0
    while bfs.CHUNK ** h < chunks:
        h += 1
    return h


def _hub_forks(k: int):
    """Run the hub's first visit and every range task below it, level by
    level, as the epochs would: the edge visits forked, and the levels."""
    adj_off, adj = star(k)
    prog = bfs.make_program(k + 1, len(adj))
    heap = {"adj_off": jnp.asarray(adj_off), "adj": jnp.asarray(adj),
            "dist": jnp.full((k + 1,), bfs.INF, jnp.int32)}
    body = prog.tasks[0].fn

    def lane(ai):
        ctx = EpochCtx(prog, ai, jnp.zeros((prog.n_arg_f,), jnp.float32),
                       0, 0, 0, heap,
                       jnp.zeros((1, prog.value_width), prog.value_dtype))
        body(ctx)
        return (jnp.stack([f.where for f in ctx.forks]),
                jnp.stack([f.argi for f in ctx.forks]))

    run = jax.jit(jax.vmap(lane))
    tasks, edges, levels = np.zeros((1, 3), np.int32), [], 0
    while len(tasks):
        where, argi = map(np.asarray, run(jnp.asarray(tasks)))
        kids = argi[where]
        assert not np.any(kids[:, 2] > 0)  # a hub never walks a chain
        visits = kids[kids[:, 2] == 0]
        assert np.all(visits[:, 1] == 1)
        edges += visits[:, 0].tolist()
        ranges = kids[kids[:, 2] < 0]
        assert np.all(ranges[:, :2] == 0)
        tasks, levels = ranges, levels + 1
    return edges, levels


@pytest.mark.parametrize("k", [65, 72, 73, 100, 520, 4096, 4105])
def test_range_split_covers_each_edge_once(k):
    edges, levels = _hub_forks(k)
    assert sorted(edges) == list(range(1, k + 1))
    chunks = -(-k // bfs.CHUNK)
    assert levels == 2 + _levels(chunks - 1)


def _service_runs(jobs, quota):
    """Run ``(name, adj_off, adj, root)`` jobs through the benchmark's
    service path, two regions at a time; ``{name: handle}``."""
    svc = JobService(capacity=2 * quota, max_jobs=2, engine="device",
                     dispatch="gather", chunk=4)
    for name, adj_off, adj, root in jobs:
        n = len(adj_off) - 1
        svc.submit(bfs.make_program(n, len(adj)), bfs.initial(root),
                   heap_init=bfs.heap_init(adj_off, adj, n), quota=quota,
                   name=name)
    out = {h.job.name: h for h in svc.completions()}
    assert len(out) == len(jobs)
    return out


def test_star_and_kron_bfs_match_the_reference():
    s_off, s_adj = star(4096)
    g = bfs_kron.Graph(9, 16, seed=1, queries=4)
    assert np.diff(g.adj_off).max() > 4 * bfs.HUB
    jobs = [("star@0", s_off, s_adj, 0), ("star@9", s_off, s_adj, 9)]
    jobs += [(f"kron@{r}", g.adj_off, g.adj, int(r)) for r in g.roots]
    done = _service_runs(jobs, 1 << 14)
    for name, adj_off, adj, root in jobs:
        h = done[name]
        assert h.status.value == "done", name
        np.testing.assert_array_equal(
            np.asarray(h.result.heap["dist"]),
            bfs_urand.bfs_levels(adj_off, adj, root), err_msg=name)
    # the chain took 512 epochs for the star's centre; the fan-out 6
    assert done["star@0"].result.stats.epochs <= 8
    assert done["star@9"].result.stats.epochs <= 8


# per-job stats of the chain, as the program had them before the hub
# fan-out (device engine, gather, chunk 4, quota 2^13 / 2^10)
CHAIN_STATS = {
    "urand8": [(12, 6403), (13, 6336), (12, 6881), (12, 6553)],
    "rand96": [(6, 687), (6, 612)],
    "star64": [(9, 72), (10, 72)],
}


def _low_degree_graph(name):
    if name == "urand8":
        g = bfs_urand.Graph(8, 16, seed=2, queries=4)
        return g.adj_off, g.adj, [int(r) for r in g.roots], 1 << 13
    if name == "rand96":
        return (*bfs.random_graph(96, avg_degree=4, seed=3), [0, 5], 1 << 13)
    return (*star(64), [0, 7], 1 << 10)


@pytest.mark.parametrize("name", sorted(CHAIN_STATS))
def test_low_degree_job_stats_unchanged(name):
    adj_off, adj, roots, quota = _low_degree_graph(name)
    assert np.diff(adj_off).max() <= bfs.HUB
    done = _service_runs(
        [(f"{name}@{r}", adj_off, adj, r) for r in roots], quota)
    for r, (epochs, tasks) in zip(roots, CHAIN_STATS[name]):
        h = done[f"{name}@{r}"]
        np.testing.assert_array_equal(
            np.asarray(h.result.heap["dist"]),
            bfs_urand.bfs_levels(adj_off, adj, r))
        # a BFS forks every task but its root and bump-allocates each
        assert h.result.stats.solo_dict() == {
            "epochs": epochs, "tasks_executed": tasks,
            "total_forks": tasks - 1, "peak_tv_slots": tasks}


@pytest.mark.parametrize("graph", ["urand", "kron"])
def test_bfs_wave_commit_passes(graph):
    make = bfs_urand.Graph if graph == "urand" else bfs_kron.Graph
    g = make(10, 16, seed=2, queries=1)
    p = bfs.make_program(g.n, len(g.adj))
    fused, _ = fuse_programs([p, p], [1 << 12, 1 << 12])
    assert tvm.commit_scatter_passes(fused) == 49
