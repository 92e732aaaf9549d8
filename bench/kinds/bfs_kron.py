"""GAP ``kron`` BFS jobs: a Kronecker power-law graph, BFS from fixed roots.

The graph follows the Graph500 specification's Kronecker generator, which
the GAP Benchmark Suite's ``kron`` input uses (Beamer et al.,
arXiv:1508.03619, §4): ``degree * 2**scale`` edges, each placed by
``scale`` independent choices of a quadrant of the adjacency matrix with
probabilities A = 0.57, B = C = 0.19, D = 0.05; the vertex labels are
then randomly permuted.  As GAP's builder does, the edges are symmetrized
and self-loops and duplicate arcs removed.  The degrees follow a power
law: a few hubs hold thousands of arcs, most vertices a handful.

One graph serves every query, made from the configuration's
``graph_seed``, with ``queries`` roots drawn uniformly among vertices that
have a neighbour, as GAP's trials draw theirs.  Region ``r`` cycles
through the roots starting at root ``4 r``, in the same order on every
run: kron roots differ by several epochs, so an order drawn from the run's
seed would move the window's count of answers with the seed.

The reference is the plain level-synchronous BFS of :mod:`bfs_urand`, and
the control its first-claim depth-first search.
"""
from __future__ import annotations

import numpy as np

from bench.kinds.bfs_urand import INF, bfs_levels, first_claim_depths

INITIATOR = (0.57, 0.19, 0.19)  # A, B, C; D = 1 - A - B - C = 0.05
ROOT_STRIDE = 4  # region r starts its cycle at root ROOT_STRIDE * r


def kron_edges(scale: int, degree: int, rng: np.random.Generator):
    """``(u, v)`` of the Graph500 Kronecker generator, labels permuted."""
    a, b, c = INITIATOR
    n, m = 1 << scale, degree << scale
    ab, c_norm, a_norm = a + b, c / (1 - (a + b)), a / (a + b)
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for bit in range(scale):
        u_bit = rng.random(m) > ab
        v_bit = rng.random(m) > np.where(u_bit, c_norm, a_norm)
        u += u_bit.astype(np.int64) << bit
        v += v_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[u], perm[v]


def kron_csr(scale: int, degree: int, rng: np.random.Generator):
    """``(adj_off, adj)`` of the symmetrized Kronecker graph, CSR by
    source, without self-loops or duplicate arcs."""
    n = 1 << scale
    u, v = kron_edges(scale, degree, rng)
    src = np.concatenate([u, v])
    dst = np.concatenate([v, u])
    keep = src != dst
    arcs = np.unique(src[keep] * n + dst[keep])
    src, adj = arcs // n, (arcs % n).astype(np.int32)
    adj_off = np.zeros(n + 1, np.int32)
    adj_off[1:] = np.cumsum(np.bincount(src, minlength=n))
    return adj_off, adj


class Graph:
    """The graph and its query roots, shared by every job of a run.

    ``self_loops`` turns every arc into a self-loop: the same shape, so
    the same compiled program, but a BFS of a few epochs (the warm-up
    uses it to free a region while the others still run)."""

    def __init__(self, scale: int, degree: int, seed: int, queries: int,
                 self_loops: bool = False):
        rng = np.random.default_rng(seed)
        self.n = 1 << scale
        self.adj_off, self.adj = kron_csr(scale, degree, rng)
        deg = np.diff(self.adj_off)
        if self_loops:
            self.adj = np.repeat(np.arange(self.n, dtype=np.int32), deg)
        self.roots = rng.choice(np.flatnonzero(deg > 0), queries,
                                replace=False)


def make(params, rng, own, shared):
    """One BFS job from the next root of this region's fixed cycle."""
    from repro.apps import bfs

    variant = params.get("graph", "kron")
    g = shared.get(variant)
    if g is None:
        g = shared[variant] = Graph(params["scale"], params["degree"],
                                    params["graph_seed"], params["queries"],
                                    variant == "self_loops")
    if "program" not in shared:
        shared["program"] = bfs.make_program(g.n, len(g.adj))
    if "next" not in own:
        # clients make their first job in region order
        region = shared["clients"] = shared.get("clients", -1) + 1
        own["next"] = ROOT_STRIDE * region
    k = own["next"]
    own["next"] = k + 1
    root = int(g.roots[k % len(g.roots)])
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
