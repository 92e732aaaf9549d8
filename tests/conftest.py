"""Tier-1 test-suite bootstrap.

``multidevice_skip`` gates the distributed tests, which need >= 4 devices.
On CPU they force host devices in a subprocess
(``XLA_FLAGS=--xla_force_host_platform_device_count``, the SNIPPETS.md
idiom); on an accelerator with fewer devices they skip rather than fail.

``solo_run`` is the solo reference run many fleet tests compare against,
computed once per test process; ``assert_matches_solo`` compares a job's
result with it.
"""
from __future__ import annotations

import functools


def multidevice_skip(required: int = 4):
    """(skip?, reason) for tests that need ``required`` devices."""
    import jax

    if jax.device_count() < required and jax.default_backend() != "cpu":
        return True, f"needs >= {required} devices (have {jax.device_count()})"
    return False, ""


@functools.lru_cache(maxsize=None)
def solo_run(case_name: str, quota: int, dispatch: str = "masked"):
    """``(heap, value, stats)`` of a registered case run alone on a
    ``HostEngine`` with ``capacity=quota``.  Cached: the result is shared
    by every caller, which must treat it as read-only."""
    from repro.apps import get_case
    from repro.core import HostEngine

    case = get_case(case_name)
    return HostEngine(case.program, capacity=quota, dispatch=dispatch).run(
        case.initial, heap_init=dict(case.heap_init) or None
    )


def assert_matches_solo(handle, solo_heap, solo_value, name):
    """A finished job's value block and heap equal the solo run's."""
    import numpy as np

    r = handle.result
    np.testing.assert_array_equal(
        np.asarray(r.value), np.asarray(solo_value), err_msg=f"{name}:value"
    )
    assert set(r.heap) == set(solo_heap)
    for k in solo_heap:
        np.testing.assert_array_equal(
            np.asarray(r.heap[k]), np.asarray(solo_heap[k]),
            err_msg=f"{name}:{k}",
        )
