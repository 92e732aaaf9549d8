"""Quick CPU checks of the benchmark's own arithmetic, data and contract."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import costs, stats  # noqa: E402
from bench.kinds import bfs_urand, fib, nqueens  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_urand_edge_count_fixed_and_deterministic(seed):
    scale, degree = 6, 16
    rng = np.random.default_rng(seed)
    a = bfs_urand.urand_csr(scale, degree, rng)
    b = bfs_urand.urand_csr(scale, degree, np.random.default_rng(seed))
    n = 1 << scale
    # the arc count is fixed by the seed: GAP's graph less its self-loops
    # and duplicate arcs
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, size=degree * n)
    v = rng.integers(0, n, size=degree * n)
    pairs = {(x, y) for x, y in zip(u.tolist(), v.tolist()) if x != y}
    pairs |= {(y, x) for x, y in pairs}
    assert a[1].shape == (len(pairs),)
    assert a[0][0] == 0 and a[0][-1] == len(pairs)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    # symmetrized, with no self-loop and no duplicate arc
    src = np.repeat(np.arange(n), np.diff(a[0]))
    fwd = list(zip(src.tolist(), a[1].tolist()))
    assert set(fwd) == pairs and len(fwd) == len(set(fwd))
    assert not np.any(src == a[1])


def test_urand_differs_across_seeds():
    a = bfs_urand.urand_csr(6, 16, np.random.default_rng(1))[1]
    b = bfs_urand.urand_csr(6, 16, np.random.default_rng(2))[1]
    assert not np.array_equal(a, b)


def test_bfs_reference_against_queue_bfs():
    import collections

    g = bfs_urand.Graph(7, 16, seed=3, queries=4)
    assert len(set(g.roots.tolist())) == 4
    root = int(g.roots[0])
    want = np.full(g.n, bfs_urand.INF, np.int64)
    want[root] = 0
    q = collections.deque([root])
    while q:
        v = q.popleft()
        for u in g.adj[g.adj_off[v]:g.adj_off[v + 1]]:
            if want[u] == bfs_urand.INF:
                want[u] = want[v] + 1
                q.append(u)
    assert np.array_equal(bfs_urand.bfs_levels(g.adj_off, g.adj, root), want)
    ctl = bfs_urand.first_claim_depths(g.adj_off, g.adj, root)
    assert not bfs_urand.same(ctl, want)


def test_references_known_values():
    from repro.apps.nqueens import SOLUTIONS

    for n, count in SOLUTIONS.items():
        assert nqueens.solutions(n) == count
    assert [fib.fib_iter(n) for n in range(10)] == [
        0, 1, 1, 2, 3, 5, 8, 13, 21, 34]
    assert fib.fib_iter(24) == 46368
    # the int16 control is exact to fib(23) and breaks at fib(24), the
    # largest n of the cell's cycle; in int8, as the CPU tests run it at
    # their small sizes, it breaks from fib(12) = 144 on
    for n in range(24):
        assert fib.fib_wrapped(n, 16) == fib.fib_iter(n)
    assert fib.fib_wrapped(24, 16) != 46368
    for n in range(12, 25):
        assert fib.fib_wrapped(n, 8) != fib.fib_iter(n)
    assert nqueens.solutions_wrapped(11, 16) == SOLUTIONS[11]
    for n in (9, 10, 11):
        assert nqueens.solutions_wrapped(n, 8) != SOLUTIONS[n]


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_fib_regions_pair_their_cycles(seed):
    params = {"fib_n": [20, 21, 22, 23, 24], "control_bits": 16}
    shared, owns = {}, [{}, {}]
    rngs = [np.random.default_rng([seed, 1 + i]) for i in range(2)]
    seen = [[], []]
    for _ in range(3):
        for i in range(2):
            job = fib.make(params, rngs[i], owns[i], shared)
            seen[i].append(int(job["name"][4:-1]))
    # the second region runs the first's cycle in reverse, so the first
    # three jobs of the two cover every n, fib(24) among them
    perm = shared["perms"][0]
    assert seen[0] == perm[:3] and seen[1] == perm[::-1][:3]
    assert set(seen[0] + seen[1]) == set(params["fib_n"])


def test_rate_and_percentile_from_due_times():
    due = [0.0, 0.0, 1.0, 2.0]
    done = [0.5, 1.0, 2.5, 5.0]
    lat = [b - a for a, b in zip(due, done)]
    assert stats.percentile(lat, 50) == 1.0
    assert stats.percentile(lat, 95) == 3.0
    assert stats.percentile(lat, 100) == 3.0
    assert stats.percentile([], 95) is None
    # answers at or before the close count; later ones do not
    assert stats.rate(done, 0.0, 2.5) == 3 / 2.5
    assert stats.rate(done, 0.0, 10.0) == 0.4


def test_fork_kernel_bytes():
    assert costs.kernel_bytes("fork_scan", 8) == 2 * 8 * 128 * 4 + 4
    assert costs.kernel_bytes("segmented_fork_scan", 8192, n_out=4) == (
        3 * 8192 * 128 * 4 + 16)


def test_benchmark_json_names_and_files():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bm[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    layers = {}
    for m in bm["per_layer"]:
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        layers.setdefault(m["layer"], m["name"])
    cells = {w["name"] for w in bm["workloads"]}
    for w in bm["workloads"]:
        for k in ("config", "traffic"):
            assert NAME.match(w[k]), w[k]
        assert (ROOT / "bench" / "traffic" / w["config"]
                / f"{w['traffic']}.json").is_file()
    for c in bm["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert NAME.match(k), k
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "bots_dc.closed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr
