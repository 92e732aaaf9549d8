"""Pallas TPU kernel: cooperative fork-slot allocation (exclusive prefix sum).

This is the TPU-native replacement for the paper's ``atomicInc(nextFreeCore)``
(§5.2.3).  On the GPU, TREES reduces per-wavefront then issues one atomic per
wavefront; on TPU there are no global atomics, so the whole Task Vector's
fork counts are scanned cooperatively:

  * the counts are laid out as ``(rows, 128)`` i32 (row-major: lane ``i``
    sits at row ``i // 128``, column ``i % 128``), and each grid step loads
    one ``(block_rows, 128)`` block — a whole number of (8, 128) tiles —
    into VMEM,
  * computes the block-local scan on the VPU with log-step shift-and-add
    (:func:`_block_scan`: ``pltpu.roll`` across lanes, then across rows),
    since Mosaic has no cumsum,
  * adds the running carry held in SMEM scratch — TPU grid steps execute
    *sequentially* on a core, so the carry needs no synchronization at all
    (the "wavefront -> block, atomic -> sequential-grid carry" adaptation
    from DESIGN.md §2),
  * the final step emits the grand total (the new ``nextFreeCore`` delta).

All arithmetic is i32 adds, so the outputs equal the ``kernels/ref.py``
oracles bit for bit at every length.  Used by the engine via
``ops.fork_offsets`` and by the MoE work-together dispatch (expert bincount
offsets share the same primitive).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
BLOCK_ROWS = 512  # rows per grid step (256 KiB of i32); a multiple of 8


def _block_scan(x):
    """Inclusive prefix sum of an ``(R, 128)`` i32 tile in row-major order,
    and the tile's total broadcast to a ``(1, 128)`` row.

    Hillis-Steele on both axes: ``log2(128)`` shift-and-add steps across
    the lanes of each row, then ``log2(R)`` across the row totals.  The
    shifts are ``pltpu.roll`` (rotate) with the wrapped-around part masked
    off, so every step is one rotate, one compare and one add on the VPU.
    """
    r, lanes = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < lanes:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
        s *= 2
    row_tot = jnp.broadcast_to(x[:, lanes - 1:lanes], x.shape)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    t = row_tot
    s = 1
    while s < r:
        t = t + jnp.where(row >= s, pltpu.roll(t, s, 0), 0)
        s *= 2
    return x + (t - row_tot), t[r - 1:r, :]


def _layout(n: int, block_rows: int):
    """Rows per grid step and padded row count for ``n`` lanes.

    A short vector runs as one block of ``ceil(n / 128)`` rows rounded up
    to whole (8, 128) tiles; a long one as ``block_rows``-row steps."""
    if block_rows % SUBLANES or block_rows <= 0:
        raise ValueError(
            f"block_rows must be a positive multiple of {SUBLANES}, "
            f"got {block_rows}"
        )
    rows = -(-max(n, 1) // LANES)
    rows = -(-rows // SUBLANES) * SUBLANES
    br = min(block_rows, rows)
    return br, -(-rows // br) * br


def _to_tiles(v, rows: int, fill: int = 0):
    """Pad an i32 vector to ``rows * 128`` lanes and lay it out as rows."""
    pad = rows * LANES - v.shape[0]
    return jnp.pad(
        v.astype(jnp.int32), (0, pad), constant_values=fill
    ).reshape(rows, LANES)


def _scan_call(kernel, inputs, n_out: int, rows: int, br: int,
               interpret: bool):
    """The shared ``pallas_call``: a sequential grid over ``br``-row blocks
    of every input, a per-lane ``(rows, 128)`` output with the same
    blocking, and an ``(n_out,)`` SMEM output of totals plus the SMEM
    carry of the same width."""
    spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[spec] * len(inputs),
        out_specs=[spec, smem],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((n_out,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((n_out,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(*inputs)


def _masked_scans(masked, totals_ref, carry_ref):
    """Body shared by every kernel: for each ``(mask, values)`` bucket,
    scan the masked values, offset the bucket's lanes by its SMEM carry and
    advance the carry.  Returns the block's offsets (0 on lanes in no
    bucket); the last grid step copies the carries out as the totals."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for b in range(len(masked)):
            carry_ref[b] = jnp.int32(0)

    offs = None
    for b, (m, x) in enumerate(masked):
        incl, tot = _block_scan(x)
        excl = incl - x + carry_ref[b]
        offs = (
            excl if m is None
            else jnp.where(m, excl, 0 if offs is None else offs)
        )
        carry_ref[b] = carry_ref[b] + tot[0, 0]

    @pl.when(i == pl.num_programs(0) - 1)
    def _fini():
        for b in range(len(masked)):
            totals_ref[b] = carry_ref[b]

    return offs


def _fork_scan_kernel(counts_ref, offs_ref, total_ref, carry_ref):
    offs_ref[...] = _masked_scans(
        [(None, counts_ref[...])], total_ref, carry_ref
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fork_scan(
    counts: jnp.ndarray, block_rows: int = BLOCK_ROWS, interpret: bool = False
):
    """Exclusive prefix sum + total of an i32 vector (any length).

    Returns (offsets i32[C], total i32[]).
    """
    (c,) = counts.shape
    br, rows = _layout(c, block_rows)
    offs, total = _scan_call(
        _fork_scan_kernel, [_to_tiles(counts, rows)], 1, rows, br, interpret
    )
    return offs.reshape(-1)[:c], total[0]


def _seg_scan_kernel(counts_ref, seg_ref, offs_ref, totals_ref, carry_ref,
                     *, n_segs):
    """Segmented exclusive scan: each lane's offset among *its own segment's*
    counts.  One (n_segs,)-wide running total in SMEM replaces n_segs atomic
    cursors (the ``JobArena`` per-region ``nextFreeCore``); TPU's sequential
    grid makes the carry race-free, exactly as in ``_fork_scan_kernel``."""
    cnt = counts_ref[...]
    seg = seg_ref[...]
    masked = []
    for s in range(n_segs):  # n_segs = fleet size: small and static
        m = seg == s
        masked.append((m, jnp.where(m, cnt, 0)))
    offs_ref[...] = _masked_scans(masked, totals_ref, carry_ref)


@functools.partial(
    jax.jit, static_argnames=("n_segs", "block_rows", "interpret")
)
def segmented_fork_scan(
    counts: jnp.ndarray,
    seg: jnp.ndarray,
    n_segs: int,
    block_rows: int = BLOCK_ROWS,
    interpret: bool = False,
):
    """Per-segment exclusive prefix sum + per-segment totals.

    The multi-tenant fork allocator (``JobArena`` in ``core.tvm``): lane
    ``i``'s fork slots start at ``region_cursor[seg[i]] + offsets[i]``, and
    each region's cursor advances by ``totals[seg]``.  Lanes of one segment
    need not be contiguous.  ``seg`` ids outside ``[0, n_segs)`` contribute
    to no segment and read offset 0.

    Returns (offsets i32[C], totals i32[n_segs]).
    """
    (c,) = counts.shape
    br, rows = _layout(c, block_rows)
    kernel = functools.partial(_seg_scan_kernel, n_segs=n_segs)
    offs, totals = _scan_call(
        kernel,
        # pad with segment id n_segs: matches no segment, adds nothing
        [_to_tiles(counts, rows), _to_tiles(seg, rows, fill=n_segs)],
        n_segs, rows, br, interpret,
    )
    return offs.reshape(-1)[:c], totals


def _type_rank_kernel(types_ref, active_ref, rank_ref, counts_ref, carry_ref,
                      *, n_types):
    """Per-type stable ranks: rank[i] = #active lanes of the same type before
    lane i.  One (n_types,)-wide running count in SMEM replaces n_types
    atomic counters; TPU's sequential grid makes the carry race-free."""
    types = types_ref[...]
    act = active_ref[...] != 0
    masked = []
    for t in range(n_types):  # n_types is small and static
        m = (types == t) & act
        masked.append((m, m.astype(jnp.int32)))
    rank = _masked_scans(masked, counts_ref, carry_ref)
    rank_ref[...] = jnp.where(act, rank, -1)


@functools.partial(
    jax.jit, static_argnames=("n_types", "block_rows", "interpret")
)
def type_rank(
    types: jnp.ndarray,
    active: jnp.ndarray,
    n_types: int,
    block_rows: int = BLOCK_ROWS,
    interpret: bool = False,
):
    """Stable rank of each active lane within its task type + per-type counts.

    This is the paper's §5.4 contiguity principle as a kernel: with
    ``dest = type_start[type] + rank`` (type_start = exclusive cumsum of the
    returned counts), scattering lanes to ``dest`` groups same-type tasks
    contiguously so each type executes as one dense range.  Also the core of
    the MoE work-together dispatch (type = expert id).

    Returns (rank i32[C] — -1 for inactive lanes, counts i32[n_types]).
    """
    (c,) = types.shape
    br, rows = _layout(c, block_rows)
    kernel = functools.partial(_type_rank_kernel, n_types=n_types)
    rank, counts = _scan_call(
        kernel,
        [_to_tiles(types, rows), _to_tiles(active, rows)],
        n_types, rows, br, interpret,
    )
    return rank.reshape(-1)[:c], counts
