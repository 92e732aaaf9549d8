"""Task-parallel BFS (paper §6.3, Fig. 7 — Lonestar comparison).

Graph is CSR in the heap (``adj_off``, ``adj``).  A ``visit(v, d, chunk)``
task claims vertex ``v`` at depth ``d`` by a scatter-min on ``dist`` and
expands its out-edges in chunks of ``CHUNK`` static fork sites (variable
out-degree -> static site count, the TVM requirement).  Duplicate visits are
filtered against the pre-epoch ``dist`` snapshot — the same duplicated-
worklist-entry behaviour the Lonestar push worklist has; the min-write makes
them harmless.

A vertex of degree at most ``HUB`` walks its chunks as a chain: each chunk
forks its ``CHUNK`` edge visits and ``visit(v, d, chunk + 1)``, one epoch
per chunk.  A hub (degree above ``HUB``) would make that chain the BFS's
critical path, so its first visit forks chunk 0's edges and one *range*
task over chunks ``[1, ceil(deg / CHUNK))``, which fans out as an implicit
``CHUNK``-ary tree: a range at level ``L`` starts at chunk ``lo`` and
covers chunks ``[lo, lo + CHUNK**L)`` (cut at the last chunk); one that
covers more than one chunk forks its nonempty level-``L - 1`` sub-ranges,
and one that covers a single chunk forks that chunk's edge visits.  A hub
thus costs ``log_CHUNK(deg / CHUNK)`` epochs, not ``deg / CHUNK``.

A range task is a ``visit`` whose third argument is negative,
``-1 - (lo * 16 + L)``, so the program keeps one task type, three integer
arguments and ``CHUNK + 1`` fork sites: the ``CHUNK`` sites fork either
edge visits or sub-ranges, the last either the chain's next chunk or a
hub's top range.  Each fork site index and argument column is one scatter
pass of the epoch's commit at the full rung width, so a wider task would
tax every epoch of every BFS.  Below the threshold the forks are the
chain's, site for site.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core.program import HeapVar, InitialTask, Program, TaskType
from .registry import AppCase, register_case

INF = np.int32(2**30)
CHUNK = 8
HUB = 8 * CHUNK  # degrees above this fan out as a range tree
_CHUNK_BITS = CHUNK.bit_length() - 1
_LEVEL_BITS = 4  # a range task's level, in the low bits of its code


def _range_code(lo, level):
    """The third argument of a range task over chunks from ``lo``."""
    return -1 - (lo * (1 << _LEVEL_BITS) + level)


def make_program(n_nodes: int, n_edges: int) -> Program:
    # the tree's height for the longest chunk list this graph can hold
    max_chunks = -(-max(n_edges, 1) // CHUNK)
    heights = 1
    while CHUNK ** heights < max_chunks:
        heights += 1
    if (max_chunks + 1) << _LEVEL_BITS >= 2**31:
        raise ValueError(f"n_edges={n_edges}: range codes overflow int32")

    def _levels(count):
        """Height of the smallest range tree that covers ``count`` chunks."""
        return sum((count > CHUNK ** k).astype(jnp.int32)
                   for k in range(heights))

    def _visit(ctx):
        v, d, arg = ctx.argi(0), ctx.argi(1), ctx.argi(2)
        off = ctx.read("adj_off", v)
        deg = ctx.read("adj_off", v + 1) - off
        first = arg == 0
        improve = d < ctx.read("dist", v)
        live = jnp.where(first, improve, True)
        ctx.write("dist", v, d, op="min", where=first & improve)
        n_chunks = (deg + CHUNK - 1) // CHUNK
        hub = deg > HUB
        ranged = arg < 0
        code = -1 - arg
        lo, level = code >> _LEVEL_BITS, code & ((1 << _LEVEL_BITS) - 1)
        # a range over more than one chunk forks sub-ranges, not edges
        split = ranged & (level > 0) & (lo + 1 < n_chunks)
        step = jnp.left_shift(1, _CHUNK_BITS * jnp.maximum(level - 1, 0))
        base = jnp.where(ranged, lo, arg) * CHUNK
        for i in range(CHUNK):
            e = base + i
            u = ctx.read("adj", off + e)
            stale = ctx.read("dist", u) <= d + 1
            sub = lo + i * step
            ctx.fork(
                "visit",
                argi=(jnp.where(split, v, u), jnp.where(split, d, d + 1),
                      jnp.where(split, _range_code(sub, level - 1), 0)),
                where=live & jnp.where(split, sub < n_chunks,
                                       (e < deg) & ~stale),
            )
        ctx.fork(
            "visit",
            argi=(v, d, jnp.where(hub, _range_code(1, _levels(n_chunks - 1)),
                                  arg + 1)),
            where=live & ~ranged & jnp.where(hub, first,
                                             base + CHUNK < deg),
        )

    return Program(
        name="bfs",
        tasks=(TaskType("visit", _visit),),
        n_arg_i=3,
        heap=(
            HeapVar("adj_off", (n_nodes + 1,), jnp.int32),
            HeapVar("adj", (max(n_edges, 1),), jnp.int32),
            HeapVar("dist", (n_nodes,), jnp.int32),
        ),
    )


def initial(src: int = 0) -> InitialTask:
    return InitialTask(task="visit", argi=(src, 0, 0))


def random_graph(n: int, avg_degree: int = 4, seed: int = 0):
    """Random directed graph in CSR, guaranteed weakly reachable-ish."""
    rng = np.random.RandomState(seed)
    dst = [rng.randint(0, n, size=rng.poisson(avg_degree)) for _ in range(n)]
    # add a random spanning path so most nodes are reachable from 0
    perm = rng.permutation(n)
    for i in range(n - 1):
        dst[perm[i]] = np.append(dst[perm[i]], perm[i + 1])
    dst[0] = np.append(dst[0], perm[0])
    deg = np.array([len(d) for d in dst])
    adj_off = np.zeros(n + 1, np.int32)
    adj_off[1:] = np.cumsum(deg)
    adj = np.concatenate(dst).astype(np.int32) if deg.sum() else np.zeros(1, np.int32)
    return adj_off, adj


def heap_init(adj_off, adj, n: int):
    dist = np.full(n, INF, np.int32)
    return dict(adj_off=adj_off, adj=adj, dist=dist)


def bfs_reference(adj_off, adj, src: int, n: int) -> np.ndarray:
    """Sequential CPU BFS (the paper's CPU comparison point)."""
    dist = np.full(n, INF, np.int64)
    dist[src] = 0
    q = [src]
    while q:
        nxt = []
        for v in q:
            for e in range(adj_off[v], adj_off[v + 1]):
                u = adj[e]
                if dist[u] > dist[v] + 1:
                    dist[u] = dist[v] + 1
                    nxt.append(u)
        q = nxt
    return dist.astype(np.int32)


@register_case("bfs")
def case() -> AppCase:
    n = 64
    adj_off, adj = random_graph(n, avg_degree=4, seed=0)
    return AppCase(
        name="bfs",
        program=make_program(n, len(adj)),
        initial=initial(0),
        heap_init=heap_init(adj_off, adj, n),
        capacity=1 << 14,
    )
