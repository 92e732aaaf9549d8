"""Span tracing: one span model for the epoch runtime, on two sinks.

The paper's whole argument is an *accounting* one — V_inf critical-path
overhead (dispatches + readbacks) should be paid once by the whole system —
and the runtime already counts those terms in ``RunStats``/``ChunkSummary``.
This module turns the counters into an observable timeline.  A span is one
phase of host work, recorded twice:

* as a ``jax.profiler.TraceAnnotation`` named ``trees:<name>``, so a
  profiler session (``jax.profiler.trace``) holds the runtime's phases on
  the same clock as the device operations.  The annotation carries the
  span's numeric arguments (job id, chunk sequence number, quota) as
  keyword arguments, so the event name itself stays bare;
* as a Chrome trace event (the ``traceEvents`` JSON that chrome://tracing
  and Perfetto load directly), collected by :class:`SpanTracer` and
  written with :meth:`SpanTracer.write`.  Spans nest by thread: each
  event records its enclosing span as ``args["parent"]``, and a span
  given no ``tid`` lane takes its parent's.

Every driver emits spans against it:

* **host drivers** (``HostEngine``, ``EpochMultiplexer``) emit one
  ``epoch`` span per epoch with ``pack`` / ``dispatch`` / ``readback`` /
  ``map`` child phases — the V_inf terms as visible time, annotated with
  the CEN, dispatch mode, launch width, and lane utilization;
* **resident drivers** (``DeviceEngine``, ``DeviceMultiplexer``,
  ``ShardedFleet``) cannot observe individual epochs without paying the
  readbacks the design exists to avoid, so they emit one ``chunk`` span per
  chunk boundary, reconstructed from the :class:`~repro.core.engine.
  ChunkSummary` deltas, with the chunk's launch (``resident_chunk``), its
  single ``readback`` and its ``settle`` (``finalize`` per finished
  region) as children — the trace makes the ⌈E/K⌉ readback cadence
  literally countable; ``reseed`` marks each region seeded in flight;
* the service (``JobService``) adds ``wave_build``, ``admit``, ``observe``
  and ``preempt`` around the host work of each step.

Inside the compiled epoch body the phases are ``jax.named_scope`` blocks
(``trees.pop`` / ``pack`` / ``tasks`` / ``commit`` / ``push`` / ``maps``),
which reach the profiler as each device operation's op-name path.

Tracing is strictly opt-in: the module-level :data:`NULL_TRACER` is the
default everywhere, its hooks are constant-time no-ops, and driver code
guards argument construction behind ``tracer.enabled`` — the disabled path
adds nothing to the critical path (the zero-retrace and stats-equality
guards run with it in place).
"""
from __future__ import annotations

import json
import numbers
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

PROFILER_PREFIX = "trees:"


class NullTracer:
    """Disabled tracer: every hook is a constant-time no-op.

    ``span`` returns a shared no-op context manager whose ``__enter__``
    yields a throwaway dict, so call sites can unconditionally
    ``with tracer.span(...) as args: args.update(...)`` — though hot paths
    should still guard on ``tracer.enabled`` to skip building the args.
    """

    enabled = False

    class _NullSpan:
        def __enter__(self) -> Dict[str, Any]:
            return {}

        def __exit__(self, *exc) -> None:
            return None

    _NULL_SPAN = _NullSpan()

    def span(self, name: str, cat: str = "runtime",
             tid: Optional[int] = None, **args: Any):
        return self._NULL_SPAN

    def events_named(self, name: str) -> List[dict]:
        return []


NULL_TRACER = NullTracer()


class SpanTracer(NullTracer):
    """Collects spans as Chrome trace events and profiler annotations;
    write the events with :meth:`write`.

    Timestamps are microseconds since tracer construction
    (``perf_counter_ns`` based, so spans nest consistently within one
    process).  ``pid`` groups all events into one process track;
    each driver picks a ``tid`` lane via :meth:`thread` so e.g. the host
    epoch loop and the map launcher render as separate rows.
    """

    enabled = True

    def __init__(self, process_name: str = "trees-runtime", pid: int = 1):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.pid = pid
        self.events: List[dict] = []
        self._t0 = time.perf_counter_ns()
        self._threads: Dict[int, str] = {}
        self._open = threading.local()  # per-thread stack of open spans
        self.events.append({
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "ts": 0, "args": {"name": process_name},
        })

    # ------------------------------------------------------------- clock
    def now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    # ------------------------------------------------------------ tracks
    def thread(self, tid: int, name: str) -> int:
        """Name a tid lane (idempotent); returns the tid for chaining."""
        if self._threads.get(tid) != name:
            self._threads[tid] = name
            self.events.append({
                "ph": "M", "name": "thread_name", "pid": self.pid,
                "tid": tid, "ts": 0, "args": {"name": name},
            })
        return tid

    def _stack(self) -> List[tuple]:
        """This thread's open spans, innermost last: ``(name, tid)``."""
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    # ------------------------------------------------------------- spans
    class _Span:
        """Complete-event ("ph": "X") recorder around a profiler
        annotation.

        Yields its mutable ``args`` dict on ``__enter__`` so the caller can
        attach values only known at the end of the phase (lane utilization
        after the readback, chunk deltas after the summary fetch); those
        reach the Chrome event only — the annotation takes the numeric
        arguments known at entry.
        """

        __slots__ = ("_tr", "_name", "_cat", "_tid", "args", "_t0", "_ann")

        def __init__(self, tr: "SpanTracer", name: str, cat: str,
                     tid: Optional[int], args: Dict[str, Any]):
            self._tr = tr
            self._name = name
            self._cat = cat
            self._tid = tid
            self.args = args

        def __enter__(self) -> Dict[str, Any]:
            tr = self._tr
            stack = tr._stack()
            if stack:
                self.args["parent"] = stack[-1][0]
            if self._tid is None:
                self._tid = stack[-1][1] if stack else 0
            stack.append((self._name, self._tid))
            self._ann = tr._annotation(
                PROFILER_PREFIX + self._name,
                **{k: v for k, v in self.args.items()
                   if isinstance(v, numbers.Number)},
            )
            self._ann.__enter__()
            self._t0 = tr.now_us()
            return self.args

        def __exit__(self, *exc) -> None:
            tr = self._tr
            t1 = tr.now_us()
            self._ann.__exit__(*exc)
            tr._stack().pop()
            tr.events.append({
                "ph": "X", "name": self._name, "cat": self._cat,
                "pid": tr.pid, "tid": self._tid,
                "ts": self._t0, "dur": t1 - self._t0,
                "args": self.args,
            })
            return None

    def span(self, name: str, cat: str = "runtime",
             tid: Optional[int] = None, **args: Any) -> "SpanTracer._Span":
        """Context manager recording one span over its body: a
        ``trees:<name>`` profiler annotation and a Chrome complete
        event."""
        return SpanTracer._Span(self, name, cat, tid, args)

    # ----------------------------------------------------------- queries
    def events_named(self, name: str, cat: Optional[str] = None
                     ) -> List[dict]:
        """All non-metadata events with this name (tests count readbacks)."""
        return [
            e for e in self.events
            if e.get("name") == name and e["ph"] != "M"
            and (cat is None or e.get("cat") == cat)
        ]

    # ------------------------------------------------------------ output
    def to_dict(self) -> dict:
        return {
            "traceEvents": list(self.events),
            "displayTimeUnit": "ms",
            "otherData": {"producer": "repro.obs.trace"},
        }

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
            f.write("\n")


# --------------------------------------------------------------------------
# Validation (the tier-1 guard that emitted traces stay loadable)
# --------------------------------------------------------------------------
_REQUIRED_BY_PHASE = {
    "X": ("name", "ts", "dur", "pid", "tid"),
    "i": ("name", "ts", "pid", "tid"),
    "C": ("name", "ts", "pid", "args"),
    "M": ("name", "pid"),
    "B": ("name", "ts", "pid", "tid"),
    "E": ("ts", "pid", "tid"),
}


def validate_chrome_trace(doc: Any) -> List[dict]:
    """Check a parsed trace document is Chrome-trace-event JSON that
    chrome://tracing / Perfetto will load; returns the event list.

    Accepts both container layouts the format allows (a bare event array,
    or an object with ``traceEvents``).  Raises ``ValueError`` on the first
    structural problem — this is the tier-1 test's oracle, so the message
    names the offending event.
    """
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
        if not isinstance(events, list):
            raise ValueError("trace object has no traceEvents list")
    elif isinstance(doc, list):
        events = doc
    else:
        raise ValueError(f"trace document must be dict or list, got "
                         f"{type(doc).__name__}")
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            raise ValueError(f"event {i} is not an object: {e!r}")
        ph = e.get("ph")
        if not isinstance(ph, str) or not ph:
            raise ValueError(f"event {i} has no phase ('ph'): {e!r}")
        for field in _REQUIRED_BY_PHASE.get(ph, ("name",)):
            if field not in e:
                raise ValueError(
                    f"event {i} (ph={ph!r}, name={e.get('name')!r}) "
                    f"missing required field {field!r}"
                )
        if ph == "X" and not isinstance(e["dur"], (int, float)):
            raise ValueError(f"event {i} has non-numeric dur: {e!r}")
    return events


def load_trace(path: str) -> List[dict]:
    """Load + validate a trace file; returns its event list."""
    with open(path) as f:
        return validate_chrome_trace(json.load(f))


def iter_spans(events: List[dict], name: Optional[str] = None,
               cat: Optional[str] = None) -> Iterator[dict]:
    """Complete-event spans, optionally filtered by name/category."""
    for e in events:
        if e.get("ph") != "X":
            continue
        if name is not None and e.get("name") != name:
            continue
        if cat is not None and e.get("cat") != cat:
            continue
        yield e
