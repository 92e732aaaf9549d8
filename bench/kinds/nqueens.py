"""BOTS ``nqueens`` jobs: count the placements of ``n`` queens, one task per
partial placement.  The reference is plain backtracking over bitmasks.
"""
from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def solutions(n: int) -> int:
    full = (1 << n) - 1

    def place(cols: int, d1: int, d2: int) -> int:
        if cols == full:
            return 1
        total = 0
        free = full & ~(cols | d1 | d2)
        while free:
            bit = free & -free
            free ^= bit
            total += place(cols | bit, ((d1 | bit) << 1) & full,
                           (d2 | bit) >> 1)
        return total

    return place(0, 0, 0)


def solutions_wrapped(n: int, bits: int) -> int:
    """The control: the count kept in ``bits``-bit integers.  In int16 it
    is still exact up to nqueens(11) = 2680; in int8 it breaks from
    nqueens(9) = 352 on."""
    from bench.kinds.fib import wrap

    return wrap(solutions(n), bits)


def make(params, rng, own, shared):
    from repro.apps import nqueens

    n = int(params["nqueens_n"])
    prog = shared.get("program")
    if prog is None:
        prog = shared["program"] = nqueens.make_program(n)
    bits = int(params["control_bits"])
    return dict(program=prog, initial=nqueens.initial(), heap={},
                name=f"nqueens({n})", expect=lambda: solutions(n),
                control=lambda: solutions_wrapped(n, bits))


def answer(result):
    return int(np.asarray(result.heap["count"][0]))


def same(got, want) -> bool:
    return got == want
