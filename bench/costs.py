"""Bytes that the ``fork_compact`` kernels must move, from their shapes.

Each kernel (``kernels/fork_compact.py``) reads its ``(rows, 128)`` i32
inputs once and writes one ``(rows, 128)`` i32 output, plus a few words
of SMEM totals: it is bound by HBM bandwidth, and its least time is these
bytes over the chip's HBM peak.
"""
from __future__ import annotations

LANES = 128
WORD = 4

# the kernels' jitted entry points, as the trace names their calls ->
# i32 (rows, 128) inputs (``ops.lane_pack`` calls ``type_rank``)
FORK_KERNELS = {
    "fork_scan": 1,
    "segmented_fork_scan": 2,
    "type_rank": 2,
}


def kernel_bytes(kernel: str, rows: int, n_out: int = 1) -> int:
    """HBM bytes of one call over ``rows`` rows of 128 lanes with ``n_out``
    SMEM totals."""
    n_in = FORK_KERNELS[kernel]
    return (n_in + 1) * rows * LANES * WORD + n_out * WORD
