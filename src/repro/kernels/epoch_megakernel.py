"""Persistent Pallas megakernel for the resident epoch step (DESIGN.md §12).

The chunked resident drivers (DESIGN.md §9–10) already run pop → pack →
step → commit inside one compiled ``lax.while_loop``, but each phase is
still a separate XLA op sandwich inside the loop body: every epoch pays
op-level launch overhead between the scheduler pop, the task step, and the
fork commit.  This module fuses the whole K-epoch chunk into **one**
``pl.pallas_call``: the carry pytree — TVM state, heap, JobArena cursors,
the batched ``[n_regions, depth]`` scheduler stacks with their per-region
stack pointers, and the hi/lo accumulator pairs — is loaded into
kernel-resident memory once, the epoch ``while_loop`` runs entirely inside
the kernel, and the carry is stored back when the chunk bound ``limit`` is
reached or every stack drains.  The chunk bound rides in as a dynamic
scalar operand, so K=1, K=4, and the fully-resident wave re-enter one
compiled kernel exactly like the while_loop template they replace.

The kernel is *generic over the carry pytree*: the driver passes the same
traced ``body_fn`` / ``cond_fn`` it would hand to ``lax.while_loop``
(built by :meth:`~repro.core.engine.EpochLoop.resident_body`), so the
megakernel and the while_loop baseline are bit-identical by construction —
``kernels/ref.py::epoch_chunk_ref`` is that baseline, packaged as this
kernel's oracle.

Backend dispatch: the jnp oracle on CPU, "interpret" to execute the kernel
body through the Pallas interpreter (the CPU parity path of the tests).  On
TPU the kernel is refused (:func:`resolve_impl`): Mosaic cannot lower the
real resident body.  Compiled for a v5e, its zero-width arena planes fail
first ("vector types must have positive constant sizes"), and a carry
without them fails in the gather lowering rule, which takes only
same-shape ``take_along_axis`` gathers, not the body's lane-pack and heap
gathers.  ``tests/test_tpu_compile.py`` keeps that compile as a test, so
a Mosaic that learns to lower it shows up there.

Grid strategy: one program instance owning the full TV — the epoch body
is already lane-vectorized (VPU-shaped masked/gather steps), and lanes
interact every epoch through the fork prefix sum and the stack push, so a
lane-partitioned grid would need cross-program reductions per epoch; see
DESIGN.md §12 for the single-block rationale and the TPU scaling notes.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


IMPLS = ("auto", "pallas", "interpret", "ref")


def resolve_impl(impl: str) -> str:
    """The concrete implementation for ``impl`` on this backend.

    "auto" is the ``lax.while_loop`` oracle ("ref") off TPU.  On TPU,
    "auto" and "pallas" raise: the resident epoch body does not lower
    through Mosaic (see the module docstring), and running the oracle
    instead would pass a ``while_loop`` off as the megakernel.  Callers
    that want the resident loop on TPU use ``megakernel=False``.
    """
    if impl not in IMPLS:
        raise ValueError(
            f"epoch_chunk impl must be one of {IMPLS}, got {impl!r}"
        )
    if jax.default_backend() == "tpu" and impl in ("auto", "pallas"):
        raise NotImplementedError(
            "the epoch megakernel does not lower on TPU: Mosaic refuses "
            "the resident epoch body's gathers (lane pack, heap reads) and "
            "its zero-width arena planes.  Run the resident loop with "
            "megakernel=False (the lax.while_loop chunk), or "
            "megakernel_impl='interpret' off TPU."
        )
    if impl == "auto":
        return "ref"
    return impl


def epoch_chunk(
    cond_fn: Callable,
    body_fn: Callable,
    carry: Any,
    limit,
    impl: str = "auto",
) -> Any:
    """Run one resident chunk: ``while cond_fn(carry, limit): body_fn``.

    ``carry`` is any pytree (the drivers pass a
    :class:`~repro.core.engine.ResidentCarry`); ``limit`` is the dynamic
    chunk bound (i32 scalar).  Returns the carry after the chunk, with the
    same pytree structure.  ``impl``: "pallas" (native), "interpret"
    (Pallas interpreter), "ref" (the ``lax.while_loop`` oracle), "auto"
    (platform default; see :func:`resolve_impl`).
    """
    impl = resolve_impl(impl)
    limit = jnp.asarray(limit, jnp.int32)
    if impl == "ref":
        from . import ref

        return ref.epoch_chunk_ref(cond_fn, body_fn, carry, limit)
    return _epoch_chunk_pallas(
        cond_fn, body_fn, carry, limit, interpret=(impl == "interpret")
    )


def _epoch_chunk_pallas(cond_fn, body_fn, carry, limit, *, interpret: bool):
    """One ``pallas_call`` for the whole chunk.

    The carry pytree is flattened to kernel refs (scalar leaves ride as
    length-1 vectors — TPU refs are arrays), every input aliases its
    output so the chunk updates in place, and the chunk bound is read from
    a scalar operand inside the kernel.  The kernel body is exactly the
    oracle's ``while_loop`` — evaluated in kernel-resident values instead
    of between XLA ops.
    """
    leaves, treedef = jax.tree_util.tree_flatten(carry)

    # A pallas kernel body may not close over array-valued constants, and
    # the traced epoch body mints several (the span/map width-ladder
    # tables, lane iotas).  Trace it to a jaxpr up front: the minted
    # constants surface as ``closed.consts``, which ride in as explicit
    # kernel operands and feed ``eval_jaxpr`` inside the kernel.
    def _flat_body(*ls):
        out = body_fn(jax.tree_util.tree_unflatten(treedef, ls))
        return jax.tree_util.tree_leaves(out)

    closed = jax.make_jaxpr(_flat_body)(*leaves)
    consts = [jnp.asarray(c) for c in closed.consts]

    # Zero-size leaves (e.g. a zero-width arena payload plane when the
    # program has no float args) carry no data and pallas refuses them as
    # operands — mint them inside the kernel and pass the originals
    # through unchanged on return.
    keep = [leaf.size > 0 for leaf in leaves]
    ckeep = [c.size > 0 for c in consts]

    scalar = [jnp.ndim(leaf) == 0 for leaf in leaves]
    shaped = [
        leaf[None] if s else leaf
        for leaf, s, k in zip(leaves, scalar, keep)
        if k
    ]
    cscalar = [jnp.ndim(c) == 0 for c in consts]
    cshaped = [
        c[None] if s else c
        for c, s, k in zip(consts, cscalar, ckeep)
        if k
    ]
    n, m = len(shaped), len(cshaped)

    def _unpack(refs, all_vals, kept, scal):
        """Read kept leaves from refs, mint zero-size ones in place."""
        out, it = [], iter(refs)
        for v, k, s in zip(all_vals, kept, scal):
            if k:
                r = next(it)
                out.append(r[...][0] if s else r[...])
            else:
                out.append(jnp.zeros(v.shape, v.dtype))
        return out

    def kernel(lim_ref, *refs):
        ins, cins, outs = refs[:n], refs[n:n + m], refs[n + m:]
        vals = _unpack(ins, leaves, keep, scalar)
        cvals = _unpack(cins, consts, ckeep, cscalar)
        lim = lim_ref[0]

        def loop_body(ls):
            return tuple(jax.core.eval_jaxpr(closed.jaxpr, cvals, *ls))

        def loop_cond(ls):
            cc = jax.tree_util.tree_unflatten(treedef, ls)
            return cond_fn(cc, lim)

        out_leaves = jax.lax.while_loop(loop_cond, loop_body, tuple(vals))
        kept_out = [
            (v, s)
            for v, s, k in zip(out_leaves, scalar, keep)
            if k
        ]
        for r, (v, s) in zip(outs, kept_out):
            r[...] = v[None] if s else v

    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in shaped]
    flat = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        # operand 0 is the chunk bound; carry operand i+1 aliases output i
        # (the hoisted constants after the carry alias nothing)
        input_output_aliases={i + 1: i for i in range(n)},
        interpret=interpret,
    )(limit[None], *shaped, *cshaped)
    it = iter(flat)
    outs = []
    for leaf, s, k in zip(leaves, scalar, keep):
        if k:
            v = next(it)
            outs.append(v[0] if s else v)
        else:
            outs.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, outs)
