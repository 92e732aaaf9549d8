"""Per-kernel validation: Pallas interpret-mode vs pure-jnp oracle, swept
across shapes and dtypes, plus hypothesis property tests for the scan."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import mha_flash
from repro.kernels.fork_compact import fork_scan
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.RandomState(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5
    )


# ------------------------------------------------------------- fork_scan
@pytest.mark.parametrize("n", [1, 8, 127, 1024, 4097])
@pytest.mark.parametrize("block_rows", [8, 16])
def test_fork_scan_shapes(n, block_rows):
    x = RNG.randint(0, 7, n).astype(np.int32)
    offs, tot = fork_scan(jnp.asarray(x), block_rows=block_rows,
                          interpret=True)
    ro, rt = ref.fork_scan_ref(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(offs), np.asarray(ro))
    assert int(tot) == int(rt)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 9), min_size=1, max_size=300))
def test_fork_scan_property(xs):
    x = jnp.asarray(np.asarray(xs, np.int32))
    offs, tot = fork_scan(x, block_rows=8, interpret=True)
    # offsets are the exclusive prefix sum: contiguous child allocation
    np.testing.assert_array_equal(
        np.asarray(offs), np.cumsum([0] + xs[:-1])
    )
    assert int(tot) == sum(xs)


# ------------------------------------------------------- flash attention
@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Skv,D",
    [
        (1, 2, 2, 32, 32, 32),    # MHA square
        (2, 8, 2, 64, 64, 64),    # GQA 4:1
        (1, 4, 1, 40, 72, 32),    # MQA, ragged lengths (padding paths)
        (1, 2, 2, 160, 160, 128), # multi-block q and kv
    ],
)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D, causal, dtype):
    q = jnp.asarray(RNG.normal(size=(B, Hq, Sq, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)), dtype)
    qo = Skv - Sq if causal else 0
    got = mha_flash(
        q, k, v, causal=causal, q_offset=qo, block_q=32, block_k=32,
        interpret=True,
    )
    want = ref.mha_ref(q, k, v, causal=causal, q_offset=qo)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )


# ------------------------------------------------------ decode attention
@pytest.mark.parametrize(
    "B,Hq,Hkv,S,D", [(2, 8, 2, 96, 32), (4, 4, 4, 300, 64), (1, 16, 2, 33, 128)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, Hq, Hkv, S, D, dtype):
    q = jnp.asarray(RNG.normal(size=(B, Hq, D)), dtype)
    kc = jnp.asarray(RNG.normal(size=(B, Hkv, S, D)), dtype)
    vc = jnp.asarray(RNG.normal(size=(B, Hkv, S, D)), dtype)
    lens = jnp.asarray(RNG.randint(1, S + 1, B), jnp.int32)
    got = decode_attention(q, kc, vc, lens, block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )


def test_decode_ragged_lengths_ignore_tail():
    """Garbage beyond `lengths` must not affect the output."""
    B, Hq, Hkv, S, D = 2, 4, 2, 64, 32
    q = jnp.asarray(RNG.normal(size=(B, Hq, D)), jnp.float32)
    kc = RNG.normal(size=(B, Hkv, S, D)).astype(np.float32)
    vc = RNG.normal(size=(B, Hkv, S, D)).astype(np.float32)
    lens = jnp.asarray([17, 40], jnp.int32)
    out1 = decode_attention(q, jnp.asarray(kc), jnp.asarray(vc), lens, interpret=True)
    kc[0, :, 17:] = 1e6  # poison the invalid tail
    vc[0, :, 17:] = -1e6
    out2 = decode_attention(q, jnp.asarray(kc), jnp.asarray(vc), lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


# --------------------------------------------------------------- SSD scan
@pytest.mark.parametrize(
    "S,H,P,N,chunk", [(32, 2, 8, 8, 8), (96, 3, 16, 16, 32), (65, 1, 32, 8, 16)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(S, H, P, N, chunk, dtype):
    x = jnp.asarray(RNG.normal(size=(S, H, P)), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (S, H)), dtype)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, H), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(S, N)), dtype)
    C = jnp.asarray(RNG.normal(size=(S, N)), dtype)
    y, hf = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, hr = ref.ssd_scan_ref(x, dt, A, B, C)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(
        rtol=5e-5, atol=5e-5
    )
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), **tol
    )
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr), **tol)


def test_ssd_scan_chunk_invariance():
    """Chunk size is an implementation detail: results must not change."""
    S, H, P, N = 64, 2, 16, 8
    x = jnp.asarray(RNG.normal(size=(S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, H), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(S, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(S, N)), jnp.float32)
    y8, h8 = ssd_scan(x, dt, A, B, C, chunk=8, interpret=True)
    y32, h32 = ssd_scan(x, dt, A, B, C, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y32), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h8), np.asarray(h32), rtol=1e-4, atol=1e-5)


def test_ssd_carries_initial_state():
    """Splitting a sequence and carrying h must equal one long scan."""
    S, H, P, N = 48, 2, 8, 8
    x = jnp.asarray(RNG.normal(size=(S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, H), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(S, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(S, N)), jnp.float32)
    y_full, h_full = ssd_scan(x, dt, A, B, C, chunk=16, interpret=True)
    y1, h1 = ssd_scan(x[:24], dt[:24], A, B[:24], C[:24], chunk=16, interpret=True)
    y2, h2 = ssd_scan(
        x[24:], dt[24:], A, B[24:], C[24:], h0=h1, chunk=16, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2])), np.asarray(y_full),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------ ops dispatch
def test_ops_ref_dispatch_on_cpu():
    q = jnp.asarray(RNG.normal(size=(1, 2, 16, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 16, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 16, 32)), jnp.float32)
    a = ops.attention(q, k, v, impl="auto")  # ref on CPU
    b = ops.attention(q, k, v, impl="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- type_rank
@pytest.mark.parametrize("n,T,blk", [(50, 3, 8), (1024, 2, 8), (3000, 5, 16)])
def test_type_rank_matches_oracle(n, T, blk):
    from repro.kernels.fork_compact import type_rank
    from repro.kernels.ref import type_rank_ref

    t = jnp.asarray(RNG.randint(0, T, n), jnp.int32)
    a = jnp.asarray(RNG.rand(n) < 0.7)
    r, c = type_rank(t, a, T, block_rows=blk, interpret=True)
    rr, cc = type_rank_ref(t, a, T)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(rr))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cc))


@settings(max_examples=15, deadline=None)
@given(
    st.lists(st.tuples(st.integers(0, 3), st.booleans()), min_size=1,
             max_size=200)
)
def test_type_rank_compaction_property(lanes):
    """dest = starts[type] + rank must be a bijection onto [0, n_active):
    the paper's same-type-contiguity invariant (§5.4)."""
    from repro.kernels.fork_compact import type_rank

    t = jnp.asarray([x[0] for x in lanes], jnp.int32)
    a = jnp.asarray([x[1] for x in lanes])
    r, c = type_rank(t, a, 4, block_rows=8, interpret=True)
    cnp, rnp, anp, tnp = map(np.asarray, (c, r, a, t))
    starts = np.concatenate([[0], np.cumsum(cnp)[:-1]])
    if anp.any():
        dest = starts[tnp[anp]] + rnp[anp]
        assert sorted(dest.tolist()) == list(range(int(anp.sum())))
    assert (rnp[~anp] == -1).all()


# -------------------------------------------------- segmented_fork_scan
@pytest.mark.parametrize(
    "n,n_segs,blk", [(64, 3, 8), (300, 5, 8), (1024, 2, 512), (4097, 4, 8)]
)
def test_segmented_fork_scan_matches_oracle(n, n_segs, blk):
    """A/B: the Pallas segmented scan (interpret mode) vs the jnp reference
    the JobArena commit uses by default — including out-of-range segment
    ids (unowned TV lanes), which must contribute nothing."""
    from repro.kernels.fork_compact import segmented_fork_scan

    counts = RNG.randint(0, 4, n).astype(np.int32)
    seg = RNG.randint(0, n_segs + 1, n).astype(np.int32)  # n_segs = unowned
    oi, ti = segmented_fork_scan(
        jnp.asarray(counts), jnp.asarray(seg), n_segs, block_rows=blk,
        interpret=True,
    )
    orf, trf = ref.segmented_fork_scan_ref(
        jnp.asarray(counts), jnp.asarray(seg), n_segs
    )
    np.testing.assert_array_equal(np.asarray(oi), np.asarray(orf))
    np.testing.assert_array_equal(np.asarray(ti), np.asarray(trf))


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 3)),
                min_size=1, max_size=200))
def test_segmented_fork_scan_property(lanes):
    """Within every segment, offsets are that segment's exclusive cumsum —
    the per-region contiguous child allocation invariant."""
    counts = np.asarray([c for c, _ in lanes], np.int32)
    seg = np.asarray([s for _, s in lanes], np.int32)
    offs, totals = ref.segmented_fork_scan_ref(
        jnp.asarray(counts), jnp.asarray(seg), 4
    )
    offs, totals = np.asarray(offs), np.asarray(totals)
    for s in range(4):
        m = seg == s
        expect = np.cumsum(counts[m]) - counts[m]
        np.testing.assert_array_equal(offs[m], expect)
        assert totals[s] == counts[m].sum()


def test_segmented_fork_offsets_ops_dispatch():
    """ops wrapper: ref on CPU, interpret mode explicitly."""
    counts = jnp.asarray([1, 2, 0, 3], jnp.int32)
    seg = jnp.asarray([0, 1, 0, 1], jnp.int32)
    for impl in ("ref", "interpret"):
        offs, totals = ops.segmented_fork_offsets(counts, seg, 2, impl=impl)
        np.testing.assert_array_equal(np.asarray(offs), [0, 0, 1, 2])
        np.testing.assert_array_equal(np.asarray(totals), [1, 5])


# ------------------------------------- fork_compact parity, ragged lengths
@pytest.mark.parametrize("n", [3, 129, 1000, 3 * 1024 + 5])
@pytest.mark.parametrize(
    "kernel", ["fork_offsets", "segmented_fork_offsets", "type_rank",
               "lane_pack"]
)
def test_fork_compact_parity_non_lane_multiple(kernel, n):
    """The (rows, 128)-tiled kernels (interpret mode, through ``ops``)
    equal their ``ref`` oracles bit for bit at lengths that are not a
    multiple of the 128-lane row, with several 8-row grid steps for the
    SMEM carry to cross."""
    from repro.kernels import fork_compact as fc

    counts = jnp.asarray(RNG.randint(0, 9, n), jnp.int32)
    seg = jnp.asarray(RNG.randint(0, 4, n), jnp.int32)  # 3 = unowned
    types = jnp.asarray(RNG.randint(0, 5, n), jnp.int32)
    active = jnp.asarray(RNG.rand(n) < 0.6)
    args = {
        "fork_offsets": (counts,),
        "segmented_fork_offsets": (counts, seg, 3),
        "type_rank": (types, active, 5),
        "lane_pack": (active,),
    }[kernel]
    small_blocks = {
        "fork_offsets": fc.fork_scan,
        "segmented_fork_offsets": fc.segmented_fork_scan,
        "type_rank": fc.type_rank,
        "lane_pack": fc.type_rank,
    }[kernel]
    want = getattr(ops, kernel)(*args, impl="ref")
    got = getattr(ops, kernel)(*args, impl="interpret")
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if kernel != "lane_pack":  # lane_pack rides type_rank's kernel
        got8 = small_blocks(*args, block_rows=8, interpret=True)
        for w, g in zip(want, got8):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize(
    "kernel", ["fork_offsets", "segmented_fork_offsets", "type_rank",
               "lane_pack"]
)
def test_fork_compact_parity_at_chip_capacity(kernel):
    """At the 2^22 lanes ``chip_smoke.py`` runs natively, the kernels in
    interpret mode (64 grid steps of the default 512-row block) equal
    their ``ref`` oracles bit for bit."""
    n = 1 << 22
    rng = np.random.RandomState(22)
    counts = jnp.asarray(rng.randint(0, 9, n), jnp.int32)
    seg = jnp.asarray(rng.randint(0, 5, n), jnp.int32)  # 4 = unowned
    types = jnp.asarray(rng.randint(0, 6, n), jnp.int32)
    active = jnp.asarray(rng.rand(n) < 0.5)
    args = {
        "fork_offsets": (counts,),
        "segmented_fork_offsets": (counts, seg, 4),
        "type_rank": (types, active, 6),
        "lane_pack": (active,),
    }[kernel]
    want = getattr(ops, kernel)(*args, impl="ref")
    got = getattr(ops, kernel)(*args, impl="interpret")
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
