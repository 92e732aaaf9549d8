"""Compile the main path's Pallas kernels for a TPU v5e with no chip attached.

The TPU compiler is installed even where no chip is: it compiles for a
*described* v5e topology and raises whatever the chip's compiler would
raise (unaligned blocks, primitives Mosaic cannot lower, too much fast
memory).  Interpret mode cannot see any of that.  Each test here compiles
one kernel at the size the chip runs it and checks that the compiled
program holds a ``tpu_custom_call`` (the kernel itself, not an XLA
fallback).  The epoch megakernel is the exception: its resident body does
not lower, so the test pins that it is refused on TPU.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports every test file.  The persistent compilation cache is off for
these compiles, since a cache entry written for a described chip cannot
be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import epoch_megakernel as mk
from repro.kernels import fork_compact as fc
from repro.kernels import ops

CAPACITY = 1 << 22  # TV lanes of the chip run


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "kernel", ["fork_scan", "segmented_fork_scan", "type_rank", "lane_pack"]
)
def test_fork_compact_compiles_at_capacity(one_chip, kernel):
    lanes = _sds((CAPACITY,), jnp.int32, one_chip)
    mask = _sds((CAPACITY,), jnp.bool_, one_chip)
    fn, args = {
        "fork_scan": (fc.fork_scan, (lanes,)),
        "segmented_fork_scan": (
            lambda c, s: fc.segmented_fork_scan(c, s, 4), (lanes, lanes)
        ),
        "type_rank": (lambda t, a: fc.type_rank(t, a, 6), (lanes, mask)),
        "lane_pack": (lambda a: ops.lane_pack(a, impl="pallas"), (mask,)),
    }[kernel]
    assert "tpu_custom_call" in _compile_text(fn, *args)


@pytest.fixture(scope="module")
def granite():
    return get_config("granite_moe_1b_a400m")


@pytest.mark.parametrize("seq", [13, 32, 128])
def test_flash_attention_compiles_at_granite_widths(one_chip, granite, seq):
    """Prefill attention of 8 prompts at the model's head layout (16 query
    heads over 8 KV heads, head_dim 64, bf16); 13 is a ragged one-request
    prompt, 32 a bucketed batch, 128 one full block."""
    cfg = granite
    hd = cfg.resolved_head_dim
    q = _sds((8, cfg.n_heads, seq, hd), cfg.compute_dtype, one_chip)
    kv = _sds((8, cfg.n_kv_heads, seq, hd), cfg.compute_dtype, one_chip)
    text = _compile_text(
        lambda q, k, v: ops.attention(q, k, v, impl="pallas"), q, kv, kv
    )
    assert "tpu_custom_call" in text


def test_decode_attention_compiles_at_granite_widths(one_chip, granite):
    """One decode step of 8 slots over a 128-token ragged KV cache."""
    cfg = granite
    hd = cfg.resolved_head_dim
    q = _sds((8, cfg.n_heads, hd), cfg.compute_dtype, one_chip)
    kv = _sds((8, cfg.n_kv_heads, 128, hd), cfg.compute_dtype, one_chip)
    lens = _sds((8,), jnp.int32, one_chip)
    text = _compile_text(
        lambda q, k, v, n: ops.gqa_decode(q, k, v, n, impl="pallas"),
        q, kv, kv, lens,
    )
    assert "tpu_custom_call" in text


def test_megakernel_refused_on_tpu(one_chip, monkeypatch):
    """The resident epoch body does not lower through Mosaic (its
    zero-width arena planes first, then its gathers), so on TPU
    ``megakernel_impl`` "auto" and "pallas" raise instead of quietly
    running the ``while_loop`` oracle.  If a later Mosaic lowers the body,
    the compile below stops raising and this test says so."""
    from repro.apps import fib
    from repro.core import tvm
    from repro.core.engine import EpochLoop, _fresh_resident_carry
    from repro.core.scheduler import batched_device_stacks

    cap, depth = 1 << 10, 1 << 6
    loop = EpochLoop(fib.PROGRAM, "masked")
    state = tvm.init_state(fib.PROGRAM, cap, fib.initial(5))
    jstack, rstack, sp = batched_device_stacks(1, depth)
    carry = _fresh_resident_carry(
        state, fib.PROGRAM.init_heap(), None, jstack, rstack, sp,
        n_regions=1,
    )
    shapes = jax.tree.map(
        lambda x: _sds(jnp.shape(x), jnp.asarray(x).dtype, one_chip), carry
    )
    body = loop.resident_body(cap, depth)

    def cond(c, lim):
        return (c.sp > 0).any() & (c.n_epochs < lim)

    chunk = jax.jit(
        lambda c, lim: mk.epoch_chunk(cond, body, c, lim, impl="pallas")
    )
    with pytest.raises(Exception, match="positive constant sizes"):
        chunk.lower(shapes, _sds((), jnp.int32, one_chip)).compile()

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for impl in ("auto", "pallas"):
        with pytest.raises(NotImplementedError, match="does not lower"):
            mk.resolve_impl(impl)
        with pytest.raises(NotImplementedError, match="does not lower"):
            EpochLoop(fib.PROGRAM, "masked", megakernel=True,
                      megakernel_impl=impl)
    assert mk.resolve_impl("interpret") == "interpret"
