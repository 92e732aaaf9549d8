"""Each device operation's JAX op-name path, read from the profiler trace.

The program wraps the phases of its resident epoch body in
``jax.named_scope("trees.<phase>")`` (pop, pack, tasks, commit, push,
maps).  JAX writes the scope path into every HLO instruction's op-name
metadata, and the profiler keeps it on each ``XLA Ops`` event's metadata
as the ``tf_op`` stat (``jit(loop)/while/body/trees.tasks/cond/
branch_3_fun/trees.commit/scatter``).  ``jax.profiler.ProfileData`` does
not expose event-metadata stats, so this module reads the ``.xplane.pb``
protobuf wire format itself, with the standard library only: planes,
lines, events, and event and stat metadata.

:func:`read_ops` gives ``(name, start_s, dur_s, tf_op)`` for every event
on one device's ``XLA Ops`` line, on the clock ``tracecut`` uses;
:func:`phase_ms_per_epoch` is what the ``*_device_ms.batch`` readers
return: the self time (:func:`self_times`) of the chunk programs'
operations whose innermost ``trees.`` scope is the phase, per epoch that
``RunStats`` counted in the window.
"""
from __future__ import annotations

import heapq
import os
from typing import Dict, List, Optional, Tuple

from bench import harness, tracecut

SCOPE_PREFIX = "trees."
TF_OP = "tf_op"

Op = Tuple[str, float, float, str]  # name, start_s, dur_s, tf_op


# ------------------------------------------------------------ wire format
def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = s = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << s
        if c < 0x80:
            return x, i
        s += 7


def _fields(b: bytes, i: int, end: int):
    """``(field, value)`` of one message: an int for a varint, the
    ``(start, end)`` of the bytes for a length-delimited field."""
    while i < end:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield f, v


def _str(b: bytes, r: Tuple[int, int]) -> str:
    return b[r[0]:r[1]].decode("utf-8", "replace")


def _map_entries(b: bytes, ranges) -> Dict[int, Tuple[int, int]]:
    """A ``map<int64, Message>``: key -> the value's byte range."""
    out = {}
    for lo, hi in ranges:
        key, val = 0, (hi, hi)
        for f, v in _fields(b, lo, hi):
            if f == 1:
                key = v
            elif f == 2:
                val = v
        out[key] = val
    return out


# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4,
# stat_metadata = 5; XLine: name = 2, timestamp_ns = 3, events = 4;
# XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3;
# XEventMetadata: name = 2, stats = 5; XStat: metadata_id = 1,
# str_value = 5, ref_value = 7; XStatMetadata: name = 2.
def _plane_ops(b: bytes, lo: int, hi: int, line_name: str) -> List[Op]:
    lines, ev_meta, st_meta = [], [], []
    for f, v in _fields(b, lo, hi):
        if f == 3:
            lines.append(v)
        elif f == 4:
            ev_meta.append(v)
        elif f == 5:
            st_meta.append(v)
    stat_names = {}
    for k, (a, z) in _map_entries(b, st_meta).items():
        stat_names[k] = next((_str(b, v) for f, v in _fields(b, a, z)
                              if f == 2), "")
    tf_ids = {k for k, n in stat_names.items() if n == TF_OP}
    metas = _map_entries(b, ev_meta)
    names: Dict[int, Tuple[str, str]] = {}

    def meta(mid: int) -> Tuple[str, str]:
        if mid not in names:
            name, path = "", ""
            a, z = metas.get(mid, (0, 0))
            for f, v in _fields(b, a, z):
                if f == 2:
                    name = _str(b, v)
                elif f == 5:
                    sid, val = None, None
                    for g, w in _fields(b, *v):
                        if g == 1:
                            sid = w
                        elif g == 5:
                            val = _str(b, w)
                        elif g == 7:
                            val = stat_names.get(w, "")
                    if sid in tf_ids and val is not None:
                        path = val
            names[mid] = (name, path)
        return names[mid]

    out: List[Op] = []
    for a, z in lines:
        fields = dict((f, v) for f, v in _fields(b, a, z) if f in (2, 3))
        if _str(b, fields.get(2, (0, 0))) != line_name:
            continue
        t0 = fields.get(3, 0)
        for f, v in _fields(b, a, z):
            if f != 4:
                continue
            mid = off = dur = 0
            i, ez = v
            while i < ez:  # the event's fields, inline: this loop is hot
                key, i = _varint(b, i)
                fn, wt = key >> 3, key & 7
                if wt == 0:
                    x, i = _varint(b, i)
                    if fn == 1:
                        mid = x
                    elif fn == 2:
                        off = x
                    elif fn == 3:
                        dur = x
                elif wt == 2:
                    n, i = _varint(b, i)
                    i += n
                elif wt == 1:
                    i += 8
                else:
                    i += 4
            name, path = meta(mid)
            # whole nanoseconds, as ``ProfileData`` (and so ``tracecut``)
            # reads them
            out.append((name, (t0 + off // 1000) * 1e-9,
                        (dur // 1000) * 1e-9, path))
    return out


def read_ops(path: str, dev: int = 0,
             line: str = tracecut.OPS_LINE) -> List[Op]:
    """Every event of device ``dev``'s ``line`` in the ``.xplane.pb`` at
    ``path``: ``(name, start_s, dur_s, tf_op)``, ``tf_op`` empty where
    the event's metadata carries none."""
    with open(path, "rb") as fh:
        b = fh.read()
    want = f"/device:TPU:{dev}"
    for f, v in _fields(b, 0, len(b)):
        if f != 1:
            continue
        lo, hi = v
        name = next((_str(b, v) for g, v in _fields(b, lo, hi) if g == 2),
                    "")
        if name == want:
            return _plane_ops(b, lo, hi, line)
    return []


# ---------------------------------------------------------------- phases
def phase(tf_op: str) -> Optional[str]:
    """The innermost ``trees.`` scope of an op-name path, or None."""
    for part in reversed(tf_op.split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return None


def self_times(ops: List[Op]) -> List[float]:
    """Each op's seconds as the innermost op running (the one started
    last), in the order of ``ops``.  On nested ops this is
    ``tracecut.Digest.self_times`` per event: an op's duration less the
    ops nested in it.  It counts on whole nanoseconds, so an op that
    starts where another ends is not nested in it, and time that two
    partly overlapping ops share is counted once, never below zero."""
    ns = [(round(s * 1e9), round(s * 1e9) + round(d * 1e9))
          for _, s, d, _ in ops]
    order = sorted(range(len(ops)),
                   key=lambda k: (ns[k][0], ns[k][0] - ns[k][1]))
    depth = {k: r for r, k in enumerate(order)}  # later started: inner
    edges = sorted([(a, 1, k) for k, (a, _) in enumerate(ns)]
                   + [(z, 0, k) for k, (_, z) in enumerate(ns)])
    out = [0] * len(ops)
    running: List[Tuple[int, int]] = []  # heap of (-depth, op)
    ended = set()
    t = None
    for when, opens, k in edges:
        while running and running[0][1] in ended:
            heapq.heappop(running)
        if running and t is not None:
            out[running[0][1]] += when - t
        t = when
        if opens:
            heapq.heappush(running, (-depth[k], k))
        else:
            ended.add(k)
    return [x * 1e-9 for x in out]


def in_chunks(ops: List[Op], runs: List[Tuple[float, float]]) -> List[Op]:
    """The ops that start inside one of the chunk programs ``runs``."""
    runs = sorted(runs)
    out, j = [], 0
    for op in sorted(ops, key=lambda o: o[1]):
        while j < len(runs) and runs[j][1] <= op[1]:
            j += 1
        if j < len(runs) and runs[j][0] <= op[1]:
            out.append(op)
    return out


def phase_seconds(ops: List[Op], runs) -> Dict[Optional[str], float]:
    """Self seconds of the chunk programs' ops by phase (None: no
    ``trees.`` scope)."""
    ops = in_chunks(ops, runs)
    out: Dict[Optional[str], float] = {}
    for op, t in zip(ops, self_times(ops)):
        p = phase(op[3])
        out[p] = out.get(p, 0.0) + t
    return out


_CACHE: Dict[Tuple[str, int, int], List[Op]] = {}


def window_ops(run) -> List[Op]:
    """The ops of device 0 that start inside the run's traced window, read
    from the trace the run left in ``harness.TRACE_DIR``."""
    path = tracecut.latest_trace(harness.TRACE_DIR)
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = read_ops(path)
    lo, hi = run.trace.window
    return [op for op in _CACHE[key] if lo <= op[1] < hi]


def phase_ms_per_epoch(run, scope: str) -> Optional[float]:
    """Device self time of the chunk programs' ops in ``scope`` per epoch
    of the window; None without a trace, epochs, or any op in scope."""
    if run.trace is None or not run.stats.get("epochs"):
        return None
    try:
        ops = window_ops(run)
    except FileNotFoundError:
        return None
    secs = phase_seconds(ops, run.trace.chunk_runs())
    if scope not in secs:
        return None
    return 1e3 * secs[scope] / run.stats["epochs"]


def span_mean_ms(run, name: str) -> Optional[float]:
    """Mean duration of the host spans ``name`` in the traced window."""
    if run.trace is None:
        return None
    durs = [d for n, _, d in run.trace.host if n == name]
    return 1e3 * sum(durs) / len(durs) if durs else None
