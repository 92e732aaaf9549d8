"""From a profiler trace to the numbers the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; :func:`reduce_file` reads it
with ``jax.profiler.ProfileData`` and keeps a :class:`Digest`: the traced
window (the benchmark's ``bench.window`` annotation), every device
operation and program (XLA module) execution inside it, and the host
spans (the benchmark's ``bench.*`` annotations and the runtime's
``trees:*`` ones).  The metric readers work on the digest alone.

On a TPU each operation's event is named by its HLO text
(``%fusion.65 = s32[1048576,4]{...} fusion(...)``), nested operations
(a ``while`` and the ops of its body) included; a Pallas kernel is a
``tpu_custom_call`` named after its jitted entry point
(``%segmented_fork_scan.8 = (s32[1024,128]..., s32[4]...) custom-call``).
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from pathlib import Path
from typing import Dict, List, Tuple

from bench import costs

PEAKS = Path(__file__).resolve().parent / "peaks.json"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("bench.", "trees:")
# the resident chunk loop (``EpochLoop.run_chunk``'s jitted ``loop``)
CHUNK_MODULE = re.compile(r"^jit_loop\b")
KERNEL_CALL = re.compile(
    r"^%(" + "|".join(costs.FORK_KERNELS) + r")\.\d+ = "
    r"\(s32\[(\d+),128\]\{[^}]*\}, s32\[(\d+)\]")
NAME_CHARS = 200


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a chip not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (have {sorted(table)})")
    return table[device_kind]


Event = Tuple[str, float, float]  # name, start_s, dur_s


@dataclasses.dataclass
class Digest:
    window: Tuple[float, float]
    ops: Dict[int, List[Event]]       # device id -> operations
    modules: Dict[int, List[Event]]   # device id -> program executions
    host: List[Event]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    # ----------------------------------------------------------- busy time
    def busy_intervals(self, dev: int) -> List[Tuple[float, float]]:
        """Union of the operations' intervals on one device, clipped to
        the window."""
        lo, hi = self.window
        iv = sorted((max(s, lo), min(s + d, hi))
                    for _, s, d in self.ops.get(dev, ()))
        out: List[List[float]] = []
        for s, e in iv:
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    @property
    def busy_s(self) -> float:
        devs = sorted(self.ops) or [0]
        return sum(sum(e - s for s, e in self.busy_intervals(d))
                   for d in devs) / len(devs)

    def idle_gaps(self, dev: int = 0) -> List[Tuple[float, float]]:
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy_intervals(dev):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    # ------------------------------------------------------------ programs
    def chunk_runs(self, dev: int = 0) -> List[Tuple[float, float]]:
        """(start, end) of each resident chunk program inside the window."""
        lo, hi = self.window
        return sorted((s, s + d) for n, s, d in self.modules.get(dev, ())
                      if CHUNK_MODULE.match(n) and s >= lo and s + d <= hi)

    # ------------------------------------------------------------ kernels
    def kernel_calls(self, dev: int = 0) -> List[Tuple[str, int, int, float]]:
        """(kernel, rows, totals, seconds) of every ``fork_compact`` kernel
        call inside the window, from the shapes in the call's HLO text."""
        lo, hi = self.window
        out = []
        for n, s, d in self.ops.get(dev, ()):
            m = KERNEL_CALL.match(n)
            if m and "tpu_custom_call" in n and lo <= s < hi:
                out.append((m.group(1), int(m.group(2)), int(m.group(3)), d))
        return out

    # ----------------------------------------------------------- breakdown
    def self_times(self, dev: int = 0) -> Dict[str, float]:
        """Seconds per operation name, each event less the events nested
        in it (a loop's body ops are not counted again in the loop)."""
        lo, hi = self.window
        evs = sorted(((s, d, n) for n, s, d in self.ops.get(dev, ())
                      if lo <= s < hi), key=lambda e: (e[0], -e[1]))
        out: Dict[str, float] = {}
        stack: List[Tuple[float, str]] = []
        for s, d, n in evs:
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack:
                parent = stack[-1][1]
                out[parent] = out.get(parent, 0.0) - d
            out[n] = out.get(n, 0.0) + d
            stack.append((s + d, n))
        return out

    def host_label(self, t: float) -> str:
        """The innermost host span covering time ``t``."""
        best = None
        for n, s, d in self.host:
            if s <= t <= s + d and (best is None or d < best[1]):
                best = (n, d)
        return best[0] if best else "no host span"

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The operations with the most self time, and the longest idle
        gaps, each labelled with the host span it fell in."""
        ops = sorted(self.self_times().items(), key=lambda x: -x[1])[:top]
        gaps = sorted(self.idle_gaps(0), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[n[:NAME_CHARS], v] for n, v in ops],
            "idle_gaps": [[self.host_label((s + e) / 2), e - s]
                          for s, e in gaps],
        }


def reduce_file(path: str) -> Digest:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    window = None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dst = (ops if line.name == OPS_LINE else modules)
                dst.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events)
            elif not m:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append((e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
                        if e.name == "bench.window":
                            window = (e.start_ns * 1e-9, e.end_ns * 1e-9)
    if window is None:
        raise ValueError(f"{path}: no bench.window annotation")
    if not ops:
        raise ValueError(f"{path}: no device operations")
    lo, hi = window
    ops = {k: [e for e in v if e[1] + e[2] > lo and e[1] < hi]
           for k, v in ops.items()}
    modules = {k: [e for e in v if e[1] + e[2] > lo and e[1] < hi]
               for k, v in modules.items()}
    host = [e for e in host if e[1] + e[2] > lo and e[1] < hi]
    return Digest(window=window, ops=ops, modules=modules, host=host)


def latest_trace(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir) -> Digest:
    return reduce_file(latest_trace(trace_dir))
