"""BOTS ``fib`` jobs: naive recursive Fibonacci, one task per call.

``n`` comes from the configuration's fixed multiset, cycled in an order
drawn from the seed, so every seed runs the same mix of work.  The fib
regions of a run pair up: the first takes each cycle in the drawn order,
the second in reverse, so that the first three jobs of the two together
cover every ``n`` of a cycle of five.  The reference is plain iteration.
"""
from __future__ import annotations

import numpy as np


def fib_iter(n: int) -> int:
    a, b = 0, 1
    for _ in range(n):
        a, b = b, a + b
    return a


def wrap(x: int, bits: int) -> int:
    """``x`` in two's complement of ``bits`` bits."""
    half = 1 << (bits - 1)
    return (x + half) % (2 * half) - half


def fib_wrapped(n: int, bits: int) -> int:
    """The control: the same iteration in ``bits``-bit integers.  In int16,
    the step below the program's int32, it breaks the guarantee of exact
    answers from fib(24) = 46368 on."""
    a, b = 0, 1
    for _ in range(n):
        a, b = b, wrap(a + b, bits)
    return a


def make(params, rng, own, shared):
    from repro.apps import fib

    if "slot" not in own:
        own["slot"] = shared.get("regions", 0)
        shared["regions"] = own["slot"] + 1
        own["cycles"] = 0
    if not own.get("cycle"):
        perms = shared.setdefault("perms", [])
        while len(perms) <= own["cycles"]:
            perms.append([int(x) for x in rng.permutation(params["fib_n"])])
        perm = perms[own["cycles"]]
        own["cycles"] += 1
        # pop() takes from the end: the first region runs perm in order
        own["cycle"] = list(perm if own["slot"] % 2 else reversed(perm))
    n = int(own["cycle"].pop())
    bits = int(params["control_bits"])
    return dict(program=fib.PROGRAM, initial=fib.initial(n), heap={},
                name=f"fib({n})", expect=lambda: fib_iter(n),
                control=lambda: fib_wrapped(n, bits))


def answer(result):
    return int(np.asarray(result.value[0, 0]))


def same(got, want) -> bool:
    return got == want
