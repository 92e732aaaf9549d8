"""Epoch scheduling layer: phase-1 policy above the TVM execution substrate.

The paper fuses three concerns into each engine: (a) the join/NDRange stacks
that decide *which* epoch number runs next (§4.3.3), (b) how many lanes the
epoch's kernel launch covers (§5.2.2's NDRange sizing), and (c) how tasks are
laid out inside that launch (§5.4's contiguity principle).  Atos-style
designs show these are a *policy* layer that should be pluggable above the
execution substrate, so this module owns all three:

  * :class:`EpochScheduler` — the host-side join/NDRange stacks with
    same-CEN range coalescing: every range sitting at the current epoch
    number is merged into one dispatch, so the critical-path overhead
    (launch + readback, the V_inf terms) is paid once for the whole system —
    the paper's "work-together" point (a) of §3.
  * :class:`DispatchPolicy` — launch-bucket sizing.  ``masked`` reproduces
    the seed engine: the popped NDRange padded to a power-of-two bucket,
    every task type executed full-width and masked.  ``compacted`` is the
    §5.4 contiguity principle: active lanes are scattered into dense
    per-type ranges (``kernels.fork_compact.type_rank`` + ``fork_scan``) and
    each type launches as one dense slice sized to its own population.
    ``gather`` packs every scheduled lane (all types) into one dense
    frontier (``kernels.ops.lane_pack``) sized to the active population —
    the cross-region hole lanes of a fused fleet are never launched.
  * ``batched_device_stacks`` / ``batched_device_pop`` /
    ``batched_device_push`` — the same stack discipline as fixed-capacity
    ``[n_regions, depth]`` device arrays with per-region stack pointers, for
    the resident engines' ``lax.while_loop`` (GTaP-style fully resident
    dispatch; ``n_regions=1`` is the solo ``DeviceEngine``, ``n_regions=J``
    is the device-resident fleet of the service layer).
  * :class:`StatsCollector` — pluggable work/critical-path accounting
    (:class:`RunStats`), including per-type occupancy for the compacted
    dispatch, consumed by ``benchmarks/run.py`` and ``benchmarks/roofline.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# Launch-bucket sizing (dispatch policy)
# --------------------------------------------------------------------------
def launch_bucket(n: int, minimum: int = 8) -> int:
    """Round a launch size up to a power-of-two bucket (jit-cache friendly)."""
    p = max(1, minimum)
    while p < n:
        p *= 2
    return p


@dataclasses.dataclass(frozen=True)
class DispatchPolicy:
    """How phase 2 lays tasks into lanes and sizes the launch.

    ``epoch_min_bucket`` sizes the full-NDRange launch (and the compaction
    pass itself); ``type_min_bucket`` sizes each dense per-type slice under
    the compacted dispatch.  Compacted slices use minimum 1 because their
    whole point is lane-exact launches.
    """

    name: str
    epoch_min_bucket: int = 8
    type_min_bucket: int = 1

    def epoch_bucket(self, count: int) -> int:
        return launch_bucket(count, self.epoch_min_bucket)

    def type_bucket(self, count: int) -> int:
        if count <= 0:
            return 0
        return launch_bucket(count, self.type_min_bucket)


def size_type_buckets(policy: "DispatchPolicy", counts, task_names):
    """Per-type launch plan from the compaction counts readback (§5.4).

    Shared by the solo ``HostEngine`` and the service multiplexer so bucket
    sizing, slice offsets, and the per-type occupancy ledger can never
    diverge between the two drivers.  Returns ``(buckets, toffs, launched,
    by_type)``: the jit-key bucket tuple, the exclusive per-type offsets
    into the compaction permutation, total lanes launched, and the
    ``{name: (active, lanes)}`` dict fed to ``StatsCollector.lanes``.
    """
    counts = np.asarray(counts)
    buckets = tuple(policy.type_bucket(int(c)) for c in counts)
    toffs = np.zeros_like(counts)
    toffs[1:] = np.cumsum(counts)[:-1]
    by_type = {
        task_names[t]: (int(counts[t]), buckets[t])
        for t in range(len(buckets))
        if buckets[t] > 0
    }
    return buckets, toffs, int(sum(buckets)), by_type


MASKED = DispatchPolicy("masked")
COMPACTED = DispatchPolicy("compacted")
# gather: pack the epoch's scheduled lanes into one dense frontier
# (kernels.ops.lane_pack) and run phase 2 over that frontier only — the
# single-launch sibling of ``compacted`` (no per-type splitting), aimed at
# the cross-region hole lanes of masked *fused* epochs.  Pays the same
# extra V_inf dispatch + count transfer as the compaction pass.
GATHER = DispatchPolicy("gather")
# auto: not a traced strategy of its own — a per-epoch *selection* among
# the three above, made by control.DispatchController from the observed
# hole fraction priced against the pack-dispatch cost (DESIGN.md §14).
# Safe because every mode is bit-identical by construction; only the
# critical-path overhead moves.  Bucket params mirror the static modes so
# the full-frontier width P is the same whichever mode the epoch lands on.
AUTO = DispatchPolicy("auto")
_POLICIES = {p.name: p for p in (MASKED, COMPACTED, GATHER, AUTO)}


def resolve_policy(dispatch) -> DispatchPolicy:
    if isinstance(dispatch, DispatchPolicy):
        return dispatch
    try:
        return _POLICIES[dispatch]
    except KeyError:
        raise ValueError(
            f"unknown dispatch policy {dispatch!r}; "
            f"expected one of {sorted(_POLICIES)}"
        ) from None


# --------------------------------------------------------------------------
# Host-side epoch scheduler (paper phase 1, §5.2.2)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EpochDispatch:
    """One popped unit of work: every range at epoch number ``cen``."""

    cen: int
    start: int
    count: int
    n_ranges: int = 1  # how many stack ranges were coalesced into this span


class EpochScheduler:
    """Owns the join/NDRange stacks the paper keeps on the CPU (§5.2.2).

    LIFO pop order gives the paper's depth-first epoch order.  With
    ``coalesce=True`` a pop also drains every other stack entry carrying the
    same epoch number and merges the ranges into one covering span — holes
    between ranges hold lanes with different epoch numbers and are filtered
    by the epoch-number (TMS) check, so the merged dispatch is always
    semantically identical, it just pays phase 1+3 once for the whole system.
    """

    def __init__(self, coalesce: bool = True):
        self.coalesce = coalesce
        self._join: List[int] = []
        self._range: List[Tuple[int, int]] = []

    def reset(self, cen: int = 1, start: int = 0, count: int = 1) -> None:
        """Seed task in slot 0, eligible in the first epoch (paper §4.3)."""
        self._join = [cen]
        self._range = [(start, count)]

    def __bool__(self) -> bool:
        return bool(self._join)

    def __len__(self) -> int:
        return len(self._join)

    def pop(self) -> EpochDispatch:
        if not self._join:
            raise RuntimeError("scheduler empty — program already drained")
        cen = self._join.pop()
        start, count = self._range.pop()
        lo, hi, n = start, start + count, 1
        if self.coalesce:
            while self._join and self._join[-1] == cen:
                self._join.pop()
                s, c = self._range.pop()
                lo, hi, n = min(lo, s), max(hi, s + c), n + 1
        return EpochDispatch(cen=cen, start=lo, count=hi - lo, n_ranges=n)

    def push_join(self, cen: int, start: int, count: int) -> None:
        """Re-arm the current range: a join continuation runs at the same CEN."""
        self._join.append(cen)
        self._range.append((start, count))

    def push_forked(self, cen: int, base: int, count: int) -> None:
        """Schedule this epoch's forked children (eligible at CEN+1)."""
        if count > 0:
            self._join.append(cen)
            self._range.append((base, count))

    # -------------------------------------------------- checkpoint support
    def export_stack(self) -> Tuple[np.ndarray, np.ndarray]:
        """Snapshot the stacks bottom-to-top as ``(cens i32[sp],
        ranges i32[sp, 2])`` — the same layout as one row of the device
        stacks (``jstack[j, :sp]`` / ``rstack[j, :sp]``), so a host
        scheduler and a device stack row round-trip through one
        engine-agnostic :class:`~repro.service.jobs.RegionCheckpoint`."""
        cens = np.asarray(self._join, np.int32)
        ranges = (
            np.asarray(self._range, np.int32).reshape(-1, 2)
            if self._range else np.zeros((0, 2), np.int32)
        )
        return cens, ranges

    def load_stack(self, cens, ranges) -> None:
        """Restore a snapshot taken by :meth:`export_stack` (or sliced off
        a device stack row): entries are bottom-to-top, replacing any
        current content."""
        self._join = [int(c) for c in np.asarray(cens).reshape(-1)]
        self._range = [
            (int(s), int(c))
            for s, c in np.asarray(ranges).reshape(-1, 2)
        ]


# --------------------------------------------------------------------------
# Multi-stack pop policy (service layer: which jobs fuse into one epoch)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MuxPopPolicy:
    """Which per-job scheduler stacks pop into one fused global epoch.

    The epoch multiplexer (``repro.service``) keeps one
    :class:`EpochScheduler` per admitted job; each global epoch it selects a
    *gang* of ready jobs, pops one dispatch from each, and fuses them into a
    single launch + readback.  ``gang`` bounds the fan-in (0 = unlimited);
    the name picks the selection order when the gang is full:

      * ``fuse_all``      — every ready job, maximal work-together fusion.
      * ``round_robin``   — rotate the starting job each global epoch, so a
        bounded gang shares the fused dispatches fairly.
      * ``deepest_first`` — prefer jobs with the deepest stacks (most
        pending frontiers), draining divergent jobs to bound their TV/stack
        residency.
    """

    name: str
    gang: int = 0  # max jobs fused per global epoch; 0 = no limit

    def select(self, ready: List[int], depths: List[int], rotor: int) -> List[int]:
        """Pick which of the ready job indices pop this global epoch."""
        if self.gang <= 0 or len(ready) <= self.gang:
            return list(ready)
        if self.name == "round_robin":
            k = rotor % len(ready)
            rotated = ready[k:] + ready[:k]
            return rotated[: self.gang]
        if self.name == "deepest_first":
            order = sorted(
                range(len(ready)), key=lambda i: -depths[i]
            )
            return [ready[i] for i in order[: self.gang]]
        return list(ready)[: self.gang]


FUSE_ALL = MuxPopPolicy("fuse_all")
_MUX_POLICIES = ("fuse_all", "round_robin", "deepest_first")


def resolve_mux_policy(policy, gang: int = 0) -> MuxPopPolicy:
    if isinstance(policy, MuxPopPolicy):
        # an explicitly requested gang bound overrides the instance's
        if gang and gang != policy.gang:
            return dataclasses.replace(policy, gang=gang)
        return policy
    if policy in _MUX_POLICIES:
        return MuxPopPolicy(policy, gang)
    raise ValueError(
        f"unknown mux pop policy {policy!r}; expected one of {_MUX_POLICIES}"
    )


# --------------------------------------------------------------------------
# Device-side stacks (the same discipline inside one lax.while_loop)
# --------------------------------------------------------------------------
def batched_device_stacks(
    n_regions: int,
    depth: int,
    cens=None,
    starts=None,
    counts=None,
):
    """``[n_regions, depth]`` join/NDRange stacks as device arrays.

    Every region's stack is seeded like :meth:`EpochScheduler.reset` — one
    entry ``(cen, start, count)`` with its stack pointer at 1.  Defaults seed
    region ``j`` with ``(1, 0, 1)``; the resident fleet drivers pass each
    region's base slot as its start.  Returns ``(jstack i32[J, depth],
    rstack i32[J, depth, 2], sp i32[J])``.
    """
    J = n_regions
    cens = jnp.ones((J,), jnp.int32) if cens is None else jnp.asarray(
        cens, jnp.int32)
    starts = jnp.zeros((J,), jnp.int32) if starts is None else jnp.asarray(
        starts, jnp.int32)
    counts = jnp.ones((J,), jnp.int32) if counts is None else jnp.asarray(
        counts, jnp.int32)
    jstack = jnp.zeros((J, depth), jnp.int32).at[:, 0].set(cens)
    rstack = (
        jnp.zeros((J, depth, 2), jnp.int32)
        .at[:, 0, 0].set(starts)
        .at[:, 0, 1].set(counts)
    )
    return jstack, rstack, jnp.ones((J,), jnp.int32)


def batched_device_pop(jstack, rstack, sp):
    """Pop the top entry of every non-empty region stack at once; traced.

    Returns ``(cen, start, count, live, sp')``, all ``[n_regions]``; regions
    with an empty stack report ``live=False`` and zeroed pop values (an
    all-zero range is inert: epoch number 0 matches no valid TV slot).
    """
    J, depth = jstack.shape
    live = sp > 0
    top = jnp.clip(sp - 1, 0, depth - 1)
    rows = jnp.arange(J)
    cen = jnp.where(live, jstack[rows, top], 0)
    start = jnp.where(live, rstack[rows, top, 0], 0)
    count = jnp.where(live, rstack[rows, top, 1], 0)
    return cen, start, count, live, sp - live.astype(jnp.int32)


def batched_device_push(jstack, rstack, sp, cen, start, count, pred, depth: int):
    """Conditionally push one (cen, range) entry per region; traced.

    ``cen``/``start``/``count``/``pred`` are ``[n_regions]``.  Returns
    ``(jstack, rstack, sp', overflow)`` where ``overflow[j]`` flags a push
    attempted on a full stack (the write is clipped; the caller must fail
    that region — its schedule is no longer trustworthy).
    """
    J = jstack.shape[0]
    rows = jnp.arange(J)
    overflow = pred & (sp >= depth)
    ssp = jnp.clip(sp, 0, depth - 1)
    jstack = jstack.at[rows, ssp].set(
        jnp.where(pred, cen, jstack[rows, ssp])
    )
    entry = jnp.stack([start, count], axis=-1)
    rstack = rstack.at[rows, ssp].set(
        jnp.where(pred[:, None], entry, rstack[rows, ssp])
    )
    return jstack, rstack, sp + pred.astype(jnp.int32), overflow


def reseed_region_stacks(jstack, rstack, sp, j: int, cen: int = 1,
                         start: int = 0, count: int = 1):
    """Reset region ``j``'s stack row to a fresh seed, leaving every other
    region untouched.

    The chunked resident driver (DESIGN.md §10) uses this between chunks to
    re-admit a queued tenant into a freed region: the row is cleared and
    reseeded exactly like :meth:`EpochScheduler.reset` / one row of
    :func:`batched_device_stacks`, and the region's stack pointer returns
    to 1 — so the re-entered ``lax.while_loop`` simply sees one more live
    region, mid-wave.  Returns ``(jstack, rstack, sp)``.
    """
    jstack = jnp.asarray(jstack).at[j].set(0).at[j, 0].set(cen)
    rstack = (
        jnp.asarray(rstack)
        .at[j].set(0)
        .at[j, 0, 0].set(start)
        .at[j, 0, 1].set(count)
    )
    sp = jnp.asarray(sp).at[j].set(1)
    return jstack, rstack, sp


def load_region_stacks(jstack, rstack, sp, j: int, cens, ranges):
    """Replace region ``j``'s stack row with a checkpointed stack image.

    The multi-entry sibling of :func:`reseed_region_stacks`, used by the
    preemption path (DESIGN.md §16): a preempted job's
    :class:`~repro.service.jobs.RegionCheckpoint` carries its whole stack
    (``sp`` entries, bottom-to-top, the layout
    :meth:`EpochScheduler.export_stack` emits), and restore writes it back
    into whichever region of whichever wave the job resumes in.  Returns
    ``(jstack, rstack, sp)``.
    """
    cens = np.asarray(cens, np.int32).reshape(-1)
    ranges = np.asarray(ranges, np.int32).reshape(-1, 2)
    n = cens.shape[0]
    depth = int(np.asarray(jstack).shape[1])
    if n > depth:
        raise ValueError(
            f"checkpointed stack depth {n} exceeds this wave's "
            f"stack_depth {depth}"
        )
    jrow = jnp.zeros((depth,), jnp.int32)
    rrow = jnp.zeros((depth, 2), jnp.int32)
    if n:
        jrow = jrow.at[:n].set(jnp.asarray(cens))
        rrow = rrow.at[:n].set(jnp.asarray(ranges))
    jstack = jnp.asarray(jstack).at[j].set(jrow)
    rstack = jnp.asarray(rstack).at[j].set(rrow)
    sp = jnp.asarray(sp).at[j].set(n)
    return jstack, rstack, sp


def device_stacks(depth: int, cen: int = 1, start: int = 0, count: int = 1):
    """Single-region stacks (legacy layout: no leading region axis), seeded
    like :meth:`EpochScheduler.reset`; the stack pointer starts at 1."""
    jstack, rstack, _ = batched_device_stacks(
        1, depth, cens=[cen], starts=[start], counts=[count]
    )
    return jstack[0], rstack[0]


def device_push(jstack, rstack, sp, cen, start, count, pred, depth: int):
    """Conditionally push one (cen, range) entry; traced, race-free.

    Single-region wrapper over :func:`batched_device_push` (overflow is the
    caller's ``sp >= depth`` check, as in the seed engine)."""
    j, r, sp_out, _ = batched_device_push(
        jstack[None],
        rstack[None],
        jnp.reshape(jnp.asarray(sp, jnp.int32), (1,)),
        jnp.reshape(jnp.asarray(cen, jnp.int32), (1,)),
        jnp.reshape(jnp.asarray(start, jnp.int32), (1,)),
        jnp.reshape(jnp.asarray(count, jnp.int32), (1,)),
        jnp.reshape(jnp.asarray(pred), (1,)),
        depth,
    )
    return j[0], r[0], sp_out[0]


# --------------------------------------------------------------------------
# Stats: work / critical-path accounting (paper §4.4.1)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class RunStats:
    """Work/critical-path accounting in the paper's terms (§4.4.1)."""

    epochs: int = 0                 # critical path length T_inf (in epochs)
    tasks_executed: int = 0         # work T_1 (in tasks)
    lanes_launched: int = 0         # includes padding/invalid lanes
    total_forks: int = 0
    map_launches: int = 0
    map_elements: int = 0           # live map element-lanes (useful work)
    map_lanes_launched: int = 0     # incl. padding to the launch domain
    peak_tv_slots: int = 0          # space (paper §4.4.2)
    dispatches: int = 0             # host->device program launches (V_inf)
    scalar_transfers: int = 0       # device->host readbacks (V_inf)
    ranges_coalesced: int = 0       # extra same-CEN ranges merged into pops
    hole_lanes_skipped: int = 0     # lanes a full-span launch would have paid
    # a service's finished jobs, with their own epochs and tasks summed
    # (per-job critical path and work; a solo run leaves them 0)
    jobs_finished: int = 0
    job_epochs_finished: int = 0
    job_tasks_finished: int = 0
    tasks_by_type: Dict[str, int] = dataclasses.field(default_factory=dict)
    lanes_by_type: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def utilization(self) -> float:
        """Active lanes / launched lanes — the SIMT-divergence analogue."""
        return self.tasks_executed / max(1, self.lanes_launched)

    @property
    def map_lanes_wasted(self) -> int:
        """Map element-lanes launched beyond the live domains.

        Host launchers size payloads to the live-domain bucket, so waste is
        just padding; resident drivers size them to ``MapType.max_domain``,
        so this surfaces the max-domain vs live-domain divergence — the
        resident path's silent work overhead, made measurable."""
        return max(0, self.map_lanes_launched - self.map_elements)

    @property
    def map_utilization(self) -> float:
        """Live map elements / launched map lanes (1.0 when no maps ran)."""
        if self.map_lanes_launched <= 0:
            return 1.0
        return self.map_elements / self.map_lanes_launched

    @property
    def occupancy_by_type(self) -> Dict[str, float]:
        """Per-type active/launched lanes (known under compacted dispatch)."""
        return {
            t: self.tasks_by_type.get(t, 0) / max(1, lanes)
            for t, lanes in self.lanes_by_type.items()
        }

    def as_dict(self, derived: bool = True) -> Dict[str, object]:
        """Canonical ``metric name -> value`` view of this run.

        The single source of truth for stats metric names: the benchmark
        JSON artifact (``benchmarks/run.py::write_json``) and the metrics
        exporter (``obs/export.py::export_run_stats``) both spell their
        keys from here, so a renamed or added field propagates everywhere
        at once.  ``derived=True`` appends the ratio properties
        (utilization, map waste) next to the raw counters.
        """
        out: Dict[str, object] = {
            f.name: getattr(self, f.name) for f in dataclasses.fields(self)
        }
        out["tasks_by_type"] = dict(self.tasks_by_type)
        out["lanes_by_type"] = dict(self.lanes_by_type)
        if derived:
            out["utilization"] = self.utilization
            out["map_lanes_wasted"] = self.map_lanes_wasted
            out["map_utilization"] = self.map_utilization
        return out

    def merge(self, s: "RunStats") -> "RunStats":
        """Accumulate another run/wave's stats into this one, in place.

        Counters add; ``peak_tv_slots`` is a high-water mark and takes the
        max; the per-type dicts merge per key.  Returns ``self`` so
        ``total = RunStats().merge(a).merge(b)`` chains.
        """
        self.epochs += s.epochs
        self.tasks_executed += s.tasks_executed
        self.lanes_launched += s.lanes_launched
        self.total_forks += s.total_forks
        self.map_launches += s.map_launches
        self.map_elements += s.map_elements
        self.map_lanes_launched += s.map_lanes_launched
        self.peak_tv_slots = max(self.peak_tv_slots, s.peak_tv_slots)
        self.dispatches += s.dispatches
        self.scalar_transfers += s.scalar_transfers
        self.ranges_coalesced += s.ranges_coalesced
        self.hole_lanes_skipped += s.hole_lanes_skipped
        self.jobs_finished += s.jobs_finished
        self.job_epochs_finished += s.job_epochs_finished
        self.job_tasks_finished += s.job_tasks_finished
        for k, v in s.tasks_by_type.items():
            self.tasks_by_type[k] = self.tasks_by_type.get(k, 0) + v
        for k, v in s.lanes_by_type.items():
            self.lanes_by_type[k] = self.lanes_by_type.get(k, 0) + v
        return self


class StatsCollector:
    """No-op base; engines call these hooks, collectors interpret them.

    ``epoch``/``map_launch`` take bulk counts (``n``) so resident drivers —
    which learn a whole wave's totals from one readback — can record them in
    O(1) host work instead of replaying the loop.
    """

    def epoch(self, cen: int, n_ranges: int = 1, n: int = 1) -> None:
        pass

    def lanes(self, n_active: int, launched: int,
              by_type: Optional[Dict[str, Tuple[int, int]]] = None) -> None:
        pass

    def dispatch(self, n: int = 1) -> None:
        pass

    def transfer(self, n: int = 1) -> None:
        pass

    def forks(self, n: int) -> None:
        pass

    def map_launch(self, elements: int = 0, lanes: int = 0,
                   n: int = 1) -> None:
        pass

    def holes_skipped(self, n: int) -> None:
        """Lanes a full-span launch would have paid that a dense dispatch
        (gather frontier, resident live-span bucket) did not launch."""
        pass

    def tv_peak(self, slots: int) -> None:
        pass

    def job_finished(self, epochs: int, tasks: int) -> None:
        """A service job finished, after ``epochs`` epochs of its own in
        which it ran ``tasks`` tasks."""
        pass

    def result(self) -> RunStats:
        return RunStats()


class NullStats(StatsCollector):
    """Counts only what the driver needs for control plus the V_inf terms
    (epochs, dispatches, transfers, map launches) — no per-lane accounting."""

    def __init__(self):
        self._stats = RunStats()

    def epoch(self, cen: int, n_ranges: int = 1, n: int = 1) -> None:
        self._stats.epochs += n

    def dispatch(self, n: int = 1) -> None:
        self._stats.dispatches += n

    def transfer(self, n: int = 1) -> None:
        self._stats.scalar_transfers += n

    def map_launch(self, elements: int = 0, lanes: int = 0,
                   n: int = 1) -> None:
        self._stats.map_launches += n

    def job_finished(self, epochs: int, tasks: int) -> None:
        s = self._stats
        s.jobs_finished += 1
        s.job_epochs_finished += epochs
        s.job_tasks_finished += tasks

    def result(self) -> RunStats:
        return self._stats


class RunStatsCollector(NullStats):
    """Full accounting, including per-type occupancy when the dispatch
    policy knows per-type populations (compacted)."""

    def lanes(self, n_active: int, launched: int,
              by_type: Optional[Dict[str, Tuple[int, int]]] = None) -> None:
        s = self._stats
        s.tasks_executed += n_active
        s.lanes_launched += launched
        if by_type:
            for name, (active, lanes) in by_type.items():
                s.tasks_by_type[name] = s.tasks_by_type.get(name, 0) + active
                s.lanes_by_type[name] = s.lanes_by_type.get(name, 0) + lanes

    def epoch(self, cen: int, n_ranges: int = 1, n: int = 1) -> None:
        super().epoch(cen, n_ranges, n)
        self._stats.ranges_coalesced += n_ranges - n

    def forks(self, n: int) -> None:
        self._stats.total_forks += n

    def map_launch(self, elements: int = 0, lanes: int = 0,
                   n: int = 1) -> None:
        super().map_launch(elements, lanes, n)
        self._stats.map_elements += elements
        self._stats.map_lanes_launched += lanes

    def holes_skipped(self, n: int) -> None:
        self._stats.hole_lanes_skipped += n

    def tv_peak(self, slots: int) -> None:
        self._stats.peak_tv_slots = max(self._stats.peak_tv_slots, slots)
