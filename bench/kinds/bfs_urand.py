"""GAP ``urand`` BFS jobs: a uniform random graph, BFS from random roots.

The graph follows the GAP Benchmark Suite's uniform generator (Beamer et
al., arXiv:1508.03619, §4): ``degree * 2**scale`` edges whose endpoints
are drawn uniformly from ``2**scale`` vertices, symmetrized into both
directions, with self-loops and duplicate arcs removed as GAP's builder
removes them.  The arc count then depends on the graph's seed, but every
job of a run uses the one graph, so every job has one program shape.

As in GAP, one graph serves every query: it is made from the
configuration's ``graph_seed``, with ``queries`` roots drawn uniformly
among vertices that have a neighbour, as GAP's trials draw theirs.  Each
region cycles through those roots in an order drawn from the run's seed,
so every seed runs the same work in another order.
"""
from __future__ import annotations

import numpy as np

INF = 2**30


def urand_csr(scale: int, degree: int, rng: np.random.Generator):
    """``(adj_off, adj)`` of the symmetrized uniform graph, CSR by source."""
    n = 1 << scale
    m = degree * n
    u = rng.integers(0, n, size=m, dtype=np.int64)
    v = rng.integers(0, n, size=m, dtype=np.int64)
    src = np.concatenate([u, v])
    dst = np.concatenate([v, u])
    keep = src != dst
    # one arc per (source, destination) pair, sorted by source then
    # destination, as GAP's builder squishes its neighbour lists
    arcs = np.unique(src[keep] * n + dst[keep])
    src, adj = arcs // n, (arcs % n).astype(np.int32)
    counts = np.bincount(src, minlength=n)
    adj_off = np.zeros(n + 1, np.int32)
    adj_off[1:] = np.cumsum(counts)
    return adj_off, adj


def bfs_levels(adj_off, adj, root: int) -> np.ndarray:
    """Plain level-synchronous BFS: hop distance from ``root``, ``INF``
    where unreachable."""
    n = adj_off.shape[0] - 1
    dist = np.full(n, INF, np.int64)
    dist[root] = 0
    frontier = np.asarray([root])
    level = 0
    while frontier.size:
        level += 1
        starts, ends = adj_off[frontier], adj_off[frontier + 1]
        nbrs = np.concatenate(
            [adj[s:e] for s, e in zip(starts, ends)]
        ) if frontier.size else np.zeros(0, np.int64)
        nbrs = np.unique(nbrs)
        nbrs = nbrs[dist[nbrs] == INF]
        dist[nbrs] = level
        frontier = nbrs
    return dist.astype(np.int32)


def first_claim_depths(adj_off, adj, root: int) -> np.ndarray:
    """The control: a depth-first search in which each vertex keeps the
    depth of the first visit that reaches it, not the least; the answer a
    traversal gives when it drops the min of the distance write."""
    n = adj_off.shape[0] - 1
    dist = np.full(n, INF, np.int64)
    dist[root] = 0
    stack = [root]
    while stack:
        v = stack.pop()
        for u in adj[adj_off[v]:adj_off[v + 1]]:
            if dist[u] == INF:
                dist[u] = dist[v] + 1
                stack.append(u)
    return dist.astype(np.int32)


class Graph:
    """The graph and its query roots, shared by every job of a run.

    ``self_loops`` turns every arc into a self-loop: the same shape, so
    the same compiled program, but a BFS of a few epochs (the warm-up
    uses it to free a region while the others still run)."""

    def __init__(self, scale: int, degree: int, seed: int, queries: int,
                 self_loops: bool = False):
        rng = np.random.default_rng(seed)
        self.n = 1 << scale
        self.adj_off, self.adj = urand_csr(scale, degree, rng)
        if self_loops:
            self.adj = np.repeat(np.arange(self.n, dtype=np.int32),
                                 np.diff(self.adj_off))
        deg = np.diff(self.adj_off)
        self.roots = rng.choice(np.flatnonzero(deg > 0), queries,
                                replace=False)


def make(params, rng, own, shared):
    """One BFS job from the next root of this region's cycle."""
    from repro.apps import bfs

    variant = params.get("graph", "urand")
    g = shared.get(variant)
    if g is None:
        g = shared[variant] = Graph(params["scale"], params["degree"],
                                    params["graph_seed"], params["queries"],
                                    variant == "self_loops")
    if "program" not in shared:
        shared["program"] = bfs.make_program(g.n, len(g.adj))
    cycle = own.get("cycle")
    if not cycle:
        cycle = own["cycle"] = list(rng.permutation(g.roots))
    root = int(cycle.pop())
    heap = dict(adj_off=g.adj_off, adj=g.adj,
                dist=np.full(g.n, INF, np.int32))
    return dict(
        program=shared["program"], initial=bfs.initial(root), heap=heap,
        name=f"bfs@{root}",
        expect=lambda: bfs_levels(g.adj_off, g.adj, root),
        control=lambda: first_claim_depths(g.adj_off, g.adj, root),
    )


def answer(result):
    return np.asarray(result.heap["dist"])


def same(got, want) -> bool:
    return bool(np.array_equal(got, want))
