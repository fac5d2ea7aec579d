"""PushPull speed telemetry + process-wide event metrics.

Reference: a rolling MB/s gauge updated every 10s, surfaced as
``bps.get_pushpull_speed()`` (reference global.cc:697-752,
common/__init__.py:130-139); off switch BYTEPS_TELEMETRY_ON.

The event sinks — :class:`Counters` / :class:`Gauges` /
:class:`Histograms` and their process singletons ``counters`` /
``gauges`` / ``histograms`` — now live in ``common/metrics.py`` as
views over one :class:`~byteps_tpu.common.metrics.MetricsRegistry`
(labels, one consistent snapshot, Prometheus exposition for the
``common/obs_server.py`` endpoint); this module re-exports them so
every established call site and metric name keeps working unchanged.
The established names: injected faults (``fault.kill`` /
``fault.delay`` / ``fault.bitflip`` / ``fault.straggler`` /
``fault.drop``), retry attempts (``retry.attempt`` /
``retry.gave_up``), recovery stages (``recovery.attempt`` /
``recovery.completed`` / ``recovery.failed``), elastic-membership
transitions (``membership.*`` plus the epoch guards
``membership.stale_chunks_dropped`` /
``membership.stale_pushes_dropped``), the data-integrity layer
(``integrity.crc_reject`` / ``retransmit`` / ``dup_dropped`` /
``nonfinite_*`` / ``quarantine_dropped``), and the engine dispatch
path (``engine.*`` counters/gauges/histograms) — the full table with
types and meanings is ``docs/observability.md``.

This module keeps the wall-clock-shaped pieces: :class:`SpeedMonitor`
(the rolling-window rate) and :class:`StepStatsTracker` (per-step
bytes/stall/retransmit/overlap accounting the engine feeds).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import sys
import threading
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax

from .config import get_config
from .metrics import (Counters, Gauges, Histograms,  # noqa: F401
                      counters, gauges, histograms, registry)
from . import tracing as _tracing


# The full gauge name of every attribution component — one literal per
# name, NOT an f-string at the emit site, so the docs/observability.md
# established-names table stays machine-checkable against the code
# (tools/bpslint metric-name rule) and every name is greppable.
ATTRIB_GAUGE_NAMES = {
    "enqueue": "step.attrib_enqueue_ms",
    "submit": "step.attrib_submit_ms",
    "wait": "step.attrib_wait_ms",
    "queue": "step.attrib_queue_ms",
    "credit": "step.attrib_credit_ms",
    "wire": "step.attrib_wire_ms",
    "merge": "step.attrib_merge_ms",
    "sync": "step.attrib_sync_ms",
    "compile": "step.attrib_compile_ms",
    "plan": "step.attrib_plan_ms",
    "dispatch": "step.attrib_dispatch_ms",
    "assemble": "step.attrib_assemble_ms",
    "tx_update": "step.attrib_tx_update_ms",
    "other": "step.attrib_other_ms",
}

# ISSUE 33: the CPU milliseconds inside each component a
# ``tracing.phase`` feeds its second clock to (``StepStats.attrib_cpu``),
# and each thread's whole CPU over the step (``StepStats.thread_cpu``) —
# same pattern, one literal a name.
ATTRIB_CPU_GAUGE_NAMES = {
    "enqueue": "step.attrib_cpu_enqueue_ms",
    "submit": "step.attrib_cpu_submit_ms",
    "wait": "step.attrib_cpu_wait_ms",
    "plan": "step.attrib_cpu_plan_ms",
    "dispatch": "step.attrib_cpu_dispatch_ms",
    "compile": "step.attrib_cpu_compile_ms",
    "sync": "step.attrib_cpu_sync_ms",
    "assemble": "step.attrib_cpu_assemble_ms",
    "tx_update": "step.attrib_cpu_tx_update_ms",
}
THREAD_CPU_GAUGE_NAMES = {
    "caller": "step.thread_cpu_caller_ms",
    "dispatcher": "step.thread_cpu_dispatcher_ms",
    "syncer": "step.thread_cpu_syncer_ms",
}


class AttributionSink:
    """Process-wide wall-time accumulators for step attribution
    (ISSUE 12 tentpole part 3).

    Components that happen OFF the engine's own threads — the sealed
    envelope wire hops (``wire``, incl. retransmit rounds), the server
    engine's merge work (``merge``), scheduler credit-gated waits
    (``credit``) — land here as they occur; the active
    :class:`StepStatsTracker` snapshots the totals at each step boundary
    and publishes the per-step deltas as ``step.attrib_*`` gauges.  One
    lock + one dict add per event: cheap enough to stay unconditional
    (every feed site already does comparable work per call)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._ms: Dict[str, float] = {}

    def add(self, component: str, ms: float) -> None:
        with self._lock:
            self._ms[component] = self._ms.get(component, 0.0) + ms

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._ms)

    def reset(self) -> None:
        with self._lock:
            self._ms.clear()


attribution = AttributionSink()


class SpeedMonitor:
    """Rolling-window byte-rate monitor (MB/s over ``window_sec``).

    ``clock`` is injectable for deterministic tests.  :meth:`speed`
    rolls a stale window on read (a paused ``record()`` stream cannot
    freeze the figure) and never answers with a near-zero partial rate
    from a *just-rolled* window: a partial younger than 10% of the
    period defers to the last closed window's figure — the previous
    implementation could report ~0 MB/s the instant after a window
    closed on full-rate traffic."""

    # partial windows younger than this fraction of the period are too
    # noisy to report when a closed window exists
    _MIN_PARTIAL_FRACTION = 0.1

    def __init__(self, window_sec: float = 10.0, history: int = 60,
                 clock: Callable[[], float] = time.monotonic):
        self._window = window_sec
        self._clock = clock
        self._lock = threading.Lock()
        self._bytes = 0
        self._t0 = clock()
        self._records: Deque[Tuple[float, float]] = collections.deque(
            maxlen=history)

    def _roll_locked(self, now: float) -> None:
        dt = now - self._t0
        # wall-clock timestamp for cross-host correlation (the
        # reference reports real timestamps for the same reason)
        self._records.append((time.time(), self._bytes / dt / 2**20))
        self._bytes = 0
        self._t0 = now

    def record(self, nbytes: int) -> None:
        now = self._clock()
        with self._lock:
            self._bytes += nbytes
            if now - self._t0 >= self._window:
                self._roll_locked(now)

    def speed(self) -> Tuple[float, float]:
        """(wall-clock timestamp, MB/s) of the freshest meaningful
        window: the live partial once it has matured past 10% of the
        period, otherwise the latest closed window (rolled on read when
        the partial has outlived the period — an idle monitor honestly
        reports 0, not its last busy figure)."""
        with self._lock:
            now = self._clock()
            dt = now - self._t0
            if dt >= self._window:
                self._roll_locked(now)
                return self._records[-1]
            if self._records and (
                    self._bytes == 0
                    or dt < self._window * self._MIN_PARTIAL_FRACTION):
                # just-rolled (or byte-less) partial: the closed window
                # is the honest figure
                return self._records[-1]
            if self._bytes and dt > 0:
                return (time.time(), self._bytes / dt / 2**20)
            if self._records:
                return self._records[-1]
            return (time.time(), 0.0)

    def total_windows(self) -> int:
        with self._lock:
            return len(self._records)


# -- per-step stats ---------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StepStats:
    """One completed training step as the engine saw it.

    ``overlap_fraction`` is the share of the step's wall time the
    syncer did NOT spend blocked on device completion — communication
    that finished under compute instead of stalling it (1.0 = fully
    hidden; the per-model bench figure in ``tools/overlap_bench.py`` is
    the end-to-end counterpart)."""

    step: int
    bytes_pushed: int
    pushes: int
    sync_stall_ms: float
    retransmits: int
    wall_ms: float
    overlap_fraction: float
    # ISSUE 12: per-step critical-path breakdown (ms) — queue wait,
    # credit stall, wire (incl. retransmits), server merge, sync block,
    # compile, plus an "other" residual so the components always account
    # for the full wall time.  Empty dict on pre-attribution records.
    attrib: Dict[str, float] = dataclasses.field(default_factory=dict)
    # the tensor whose unit retired LAST in this step — the chain the
    # step's completion actually waited on
    lagging_tensor: Optional[str] = None
    # ISSUE 20: push+pull wire bytes this step actually shipped (per-leg
    # accounting from the syncer: compressed chunks at payload size,
    # sharded-update pulls at the owner-slice/codec-payload size) — the
    # figure the sharded-vs-unsharded bench ratio is computed from
    wire_bytes_per_step: int = 0
    # ISSUE 23: the engine's own share of the step — wall inside
    # ``byteps_tpu.jax.push_pull`` (first leaf enqueued -> last handle
    # returned), summed over the step's calls; ``wall_ms`` runs push to
    # push (from where the call that made the step's first push began:
    # ``open_call``) and so holds the user's gradient program and apply too
    push_pull_ms: float = 0.0
    # device programs launched / chunk tasks they consumed this step /
    # the launches that carried a whole tensor (the step's deltas of
    # ``PushPullEngine.stats``)
    dispatches: int = 0
    chunks: int = 0
    whole_units: int = 0
    # ISSUE 24: of this step's ``pushes``, the bucket tensors (a run of
    # leaves pushed as one), and the leaves that rode them; the other
    # ``pushes - buckets`` tensors were leaves that went alone
    buckets: int = 0
    bucketed_leaves: int = 0
    # ISSUE 33: the second clock.  ``attrib_cpu[c]``: of ``attrib[c]``,
    # the milliseconds the phase's thread was RUNNING (its CPU clock,
    # from the same enter/exit pair) — a key for every component whose
    # phases READ that clock this step: ``wait`` and ``tx_update``
    # always (once a step), the per-tensor and per-unit ones
    # (``enqueue``, ``submit``, ``plan``, ``dispatch``, ``compile``,
    # ``sync``, ``assemble``) only in a step under a profiler session
    # (``tracing.phase`` has why); never ``queue``, ``credit``, ``wire``,
    # ``merge``, ``other``.  ``attrib[c] - attrib_cpu[c]`` of a working
    # phase is time spent waiting: for the interpreter lock, or inside
    # a runtime call that released it.
    attrib_cpu: Dict[str, float] = dataclasses.field(default_factory=dict)
    # of ``push_pull_ms``, the caller's CPU milliseconds (the
    # ``bps.push_pull`` phase's second clock): its enqueue + submit work
    # and what its wait burns
    push_pull_cpu_ms: float = 0.0
    # each thread's WHOLE CPU milliseconds over the step, in or out of a
    # span, read at the step's two boundaries: ``caller`` (the thread
    # that finalizes the step — where several threads push, whichever
    # made the next step's first push; left out where that was another
    # thread than the one that began this step), ``dispatcher``,
    # ``syncer``.  A key is left out, never 0, where the platform has no
    # per-thread CPU clock or the thread had not registered / has gone.
    thread_cpu: Dict[str, float] = dataclasses.field(default_factory=dict)
    # wall inside ``DistributedOptimizer.update()`` (span
    # ``bps.adapter.update``), summed over the step's calls: holds
    # ``push_pull_ms`` and ``attrib["tx_update"]``; the rest is the
    # adapter's own (flatten, leaf names, unflatten, its lock)
    update_ms: float = 0.0

    def as_dict(self) -> Dict[str, float]:
        return dataclasses.asdict(self)


class StepStatsTracker:
    """Accumulates per-step engine stats (ISSUE 6 tentpole part 4).

    A "step" is defined exactly as the tracer defines it: per-tensor
    push counts, the max of which is the global step — when any
    tensor's count advances past the current step, the previous step is
    finalized.  The dispatcher/enqueue side feeds :meth:`on_push`
    (bytes), the syncer feeds :meth:`add_stall` (ms spent blocked in
    ``block_until_ready``); retransmits are deltas of the established
    ``integrity.retransmit`` counter.  Finalized steps land in three
    places at once: the gauge set (``step.*`` — the ``/metrics``
    surface), the flight recorder (``step_stats`` events), and a
    bounded in-process history for bench summaries."""

    def __init__(self, history: int = 64, recorder=None,
                 engine_stats: Optional[Dict[str, int]] = None):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._step = 0
        self._t0 = time.monotonic()
        # when the caller's open tree-level push_pull began (open_call);
        # taken by the next push
        self._call_t0: Optional[float] = None
        self._bytes = 0
        self._pushes = 0
        self._buckets = 0
        self._bucketed_leaves = 0
        self._stall_ms = 0.0
        self._push_pull_ms = 0.0
        self._wire = 0
        # the engine's live {"dispatches", "chunks", "whole_units"}
        # totals, read at each step boundary (None: a tracker with no
        # engine behind it)
        self._engine_stats = engine_stats
        self._units0 = (0, 0, 0)
        self._retx0 = counters.get("integrity.retransmit")
        self._history: Deque[StepStats] = collections.deque(maxlen=history)
        # step-attribution state (ISSUE 12): baseline of the process-wide
        # sink at the step boundary, locally fed components (queue wait),
        # and the last-retired tensor (the lagging chain)
        self._attrib0: Dict[str, float] = attribution.totals()
        self._comp: Dict[str, float] = {}
        self._last_retired: Optional[str] = None
        self._pub_attrib: set = set()   # gauge keys published last step
        # the second clock (ISSUE 33): CPU ms inside each fed component;
        # wall of the adapter's update(); the CPU clocks of the engine's
        # threads by role (register_thread) and every thread's reading
        # at the step's start, ``role -> (whose clock, seconds)``; the
        # caller's reading where its open tree-level call began; the
        # step's unit latencies, for the histogram
        self._comp_cpu: Dict[str, float] = {}
        self._push_pull_cpu_ms = 0.0
        self._update_ms = 0.0
        self._thread_clocks: Dict[str, int] = {}
        self._cpu0: Dict[str, Tuple[int, float]] = {}
        self._call_c0: Optional[Tuple[int, float]] = None
        self._pub_attrib_cpu: set = set()
        self._unit_sync_ms: List[float] = []
        if recorder is None:
            from . import flight_recorder as _flight
            recorder = _flight.recorder
        self._recorder = recorder

    # -- feeding -----------------------------------------------------------

    def on_push(self, name: str, nbytes: int, leaves: int = 0) -> int:
        """Returns the tensor's push count — the step this push belongs
        to, which the engine's phase spans carry.  ``leaves``: how many
        leaves a bucket tensor packs (0: a tensor pushed by itself)."""
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + 1
            step = self._counts[name]
            call_t0, self._call_t0 = self._call_t0, None
            call_c0, self._call_c0 = self._call_c0, None
            if step > self._step:
                # the step's wall starts where the call that makes its
                # first push began, so the call's own span
                # (``push_pull_ms``) lies inside the wall; a push outside
                # any tree-level call starts it now
                t0 = time.monotonic()
                if call_t0 is not None and call_t0 >= self._t0:
                    t0 = call_t0
                # the threads' CPU clocks, read once a boundary: the
                # step's CPU runs as its wall does, from where the call
                # began
                cpu = self._cpu_now(call_c0)
                if self._step > 0 and self._pushes:
                    # published under the lock: two concurrent pushers
                    # finalizing steps N and N+1 must land their gauge
                    # writes and flight events in step order (the gauge
                    # and recorder locks never take this one, so there
                    # is no ordering cycle to invert)
                    self._publish(self._finalize_locked(end=t0, cpu=cpu))
                self._cpu0 = cpu
                self._step = step
                self._t0 = t0
                # flight-recorder stamp: every recorded event from here
                # on carries this step even with tracing off
                _tracing.note_step(step)
            self._bytes += int(nbytes)
            self._pushes += 1
            if leaves:
                self._buckets += 1
                self._bucketed_leaves += leaves
            return step

    def open_call(self) -> None:
        """Caller feed: a tree-level push_pull begins now, before its
        ``bps.push_pull`` span opens.  If its first push starts a step,
        the step's wall starts here."""
        c0 = (threading.get_ident(), time.thread_time())
        with self._lock:
            self._call_t0 = time.monotonic()
            self._call_c0 = c0

    def register_thread(self, role: str) -> None:
        """An engine thread (``dispatcher``, ``syncer``) names itself as
        it starts: its CPU clock is read at every step boundary from
        then on (``StepStats.thread_cpu``).  Nothing where the platform
        cannot name a thread's clock."""
        try:
            clock = time.pthread_getcpuclockid(threading.get_ident())
        except (AttributeError, OSError):
            return
        with self._lock:
            self._thread_clocks[role] = clock

    def _cpu_now(self, caller: Optional[Tuple[int, float]] = None
                 ) -> Dict[str, Tuple[int, float]]:
        """``role -> (whose clock, CPU seconds)`` now; the caller is the
        calling thread unless a reading of it is handed in."""
        now = {"caller": caller or (threading.get_ident(),
                                   time.thread_time())}
        for role, clock in self._thread_clocks.items():
            try:
                now[role] = (clock, time.clock_gettime(clock))
            except OSError:   # the thread has gone (shutdown's flush)
                pass
        return now

    def add_stall(self, ms: float, cpu_ms: Optional[float] = None) -> None:
        with self._lock:
            self._stall_ms += ms
            if cpu_ms is not None:
                self._comp_cpu["sync"] = (
                    self._comp_cpu.get("sync", 0.0) + cpu_ms)

    def add_push_pull(self, ms: float,
                      cpu_ms: Optional[float] = None) -> None:
        """Caller feed: wall (and the caller's CPU) of one whole
        tree-level push_pull."""
        with self._lock:
            self._push_pull_ms += ms
            self._push_pull_cpu_ms += cpu_ms or 0.0

    def add_update(self, ms: float, cpu_ms: Optional[float] = None) -> None:
        """Caller feed: wall of one whole ``DistributedOptimizer.update``."""
        with self._lock:
            self._update_ms += ms

    def add_wire(self, nbytes: int) -> None:
        """Syncer feed: wire bytes (push + pull legs) of each retired
        chunk, at what the legs actually shipped."""
        with self._lock:
            self._wire += int(nbytes)

    def add_component(self, component: str, ms: float,
                      cpu_ms: Optional[float] = None) -> None:
        """A phase's feed: the component's wall milliseconds and, where
        the phase read the thread's clock, the CPU milliseconds inside
        them."""
        with self._lock:
            self._comp[component] = self._comp.get(component, 0.0) + ms
            if cpu_ms is not None:
                self._comp_cpu[component] = (
                    self._comp_cpu.get(component, 0.0) + cpu_ms)

    def feed(self, component: str
             ) -> Callable[[float, Optional[float]], None]:
        """Where a ``tracing.phase`` of this name sends its wall and CPU
        milliseconds: ``add_component`` bound to the component, but
        :meth:`add_stall` for ``sync`` (which is ``sync_stall_ms`` too),
        and :meth:`add_push_pull` / :meth:`add_update` for ``push_pull``
        / ``update`` (fields of their own, not components)."""
        if component == "sync":
            return self.add_stall
        if component == "push_pull":
            return self.add_push_pull
        if component == "update":
            return self.add_update
        return functools.partial(self.add_component, component)

    def retire_unit(self, name: str, queue_ms: Optional[float] = None,
                    sync_ms: Optional[float] = None) -> None:
        """The syncer's one call a retired unit, under one lock: the
        unit's tensor (the last one standing when the step finalizes is
        the lagging tensor), its head chunk's wait in the priority queue
        (the ``queue`` component) and its dispatch -> retire latency
        (the ``engine.unit_sync_ms`` histogram, observed at the step's
        boundary with the step's other units)."""
        with self._lock:
            self._last_retired = name
            if queue_ms is not None:
                self._comp["queue"] = self._comp.get("queue", 0.0) + queue_ms
            if sync_ms is not None:
                self._unit_sync_ms.append(sync_ms)

    # -- finalization ------------------------------------------------------

    def _finalize_locked(self, end: Optional[float] = None,
                         cpu: Optional[Dict[str, Tuple[int, float]]] = None
                         ) -> StepStats:
        if end is None:
            end = time.monotonic()
        if cpu is None:
            cpu = self._cpu_now()
        # a thread's CPU over the step: its clock now minus the same
        # clock at the step's start (another clock under the role — the
        # caller changed, a thread registered late: no reading)
        thread_cpu = {
            role: round((secs - self._cpu0[role][1]) * 1e3, 3)
            for role, (who, secs) in cpu.items()
            if self._cpu0.get(role, (None,))[0] == who}
        wall_ms = max((end - self._t0) * 1e3, 1e-6)
        retx = counters.get("integrity.retransmit")
        # Per-step attribution (ISSUE 12): deltas of the process-wide
        # sink (wire / merge / credit) + the engine's phases (enqueue /
        # submit / wait / plan / dispatch / compile / assemble and the
        # adapter's tx_update, fed by tracing.phase) + queue + the
        # syncer's block time (sync).
        # "other" is max(0, wall - sum) over everything but ``wait`` —
        # a blocked caller is the other threads' work seen from outside,
        # and counting it twice would zero the residual: components
        # are wall-time integrals of each activity, so on a serialized
        # profile they partition the step, while pipelined units or
        # parallel merge/wire threads can overlap and push the sum PAST
        # the wall (other clamps at 0) — documented in
        # docs/observability.md.
        now_tot = attribution.totals()
        es = self._engine_stats
        units = ((es["dispatches"], es["chunks"], es["whole_units"])
                 if es else (0, 0, 0))
        attrib: Dict[str, float] = {}
        for k in set(now_tot) | set(self._attrib0):
            d = now_tot.get(k, 0.0) - self._attrib0.get(k, 0.0)
            if d > 0.0005:
                attrib[k] = d
        for k, v in self._comp.items():
            attrib[k] = attrib.get(k, 0.0) + v
        attrib["sync"] = attrib.get("sync", 0.0) + self._stall_ms
        known = sum(v for k, v in attrib.items() if k != "wait")
        attrib["other"] = max(0.0, wall_ms - known)
        attrib = {k: round(v, 3) for k, v in attrib.items()}
        stats = StepStats(
            step=self._step,
            bytes_pushed=self._bytes,
            pushes=self._pushes,
            sync_stall_ms=round(self._stall_ms, 3),
            retransmits=retx - self._retx0,
            wall_ms=round(wall_ms, 3),
            overlap_fraction=round(
                1.0 - min(1.0, self._stall_ms / wall_ms), 4),
            attrib=attrib,
            lagging_tensor=self._last_retired,
            wire_bytes_per_step=self._wire,
            push_pull_ms=round(self._push_pull_ms, 3),
            dispatches=units[0] - self._units0[0],
            chunks=units[1] - self._units0[1],
            whole_units=units[2] - self._units0[2],
            buckets=self._buckets,
            bucketed_leaves=self._bucketed_leaves,
            attrib_cpu={k: round(v, 3) for k, v in self._comp_cpu.items()},
            push_pull_cpu_ms=round(self._push_pull_cpu_ms, 3),
            thread_cpu=thread_cpu,
            update_ms=round(self._update_ms, 3),
        )
        if self._unit_sync_ms:
            histograms.observe_many("engine.unit_sync_ms",
                                    self._unit_sync_ms)
            self._unit_sync_ms = []
        self._comp_cpu = {}
        self._push_pull_cpu_ms = 0.0
        self._update_ms = 0.0
        self._units0 = units
        self._push_pull_ms = 0.0
        self._bytes = 0
        self._pushes = 0
        self._buckets = 0
        self._bucketed_leaves = 0
        self._stall_ms = 0.0
        self._wire = 0
        self._retx0 = retx
        self._attrib0 = now_tot
        self._comp = {}
        self._last_retired = None
        self._history.append(stats)
        return stats

    def _publish(self, stats: StepStats) -> None:
        gauges.set("step.bytes_pushed", stats.bytes_pushed)
        gauges.set("step.pushes", stats.pushes)
        gauges.set("step.sync_stall_ms", stats.sync_stall_ms)
        gauges.set("step.retransmits", stats.retransmits)
        gauges.set("step.wall_ms", stats.wall_ms)
        gauges.set("step.overlap_fraction", stats.overlap_fraction)
        gauges.set("step.wire_bytes_per_step", stats.wire_bytes_per_step)
        gauges.set("step.push_pull_ms", stats.push_pull_ms)
        gauges.set("step.dispatches", stats.dispatches)
        gauges.set("step.chunks", stats.chunks)
        gauges.set("step.whole_units", stats.whole_units)
        gauges.set("step.buckets", stats.buckets)
        gauges.set("step.bucketed_leaves", stats.bucketed_leaves)
        self._pub_attrib = self._set_components(
            ATTRIB_GAUGE_NAMES, stats.attrib, self._pub_attrib)
        gauges.set("step.update_ms", stats.update_ms)
        gauges.set("step.push_pull_cpu_ms", stats.push_pull_cpu_ms)
        self._pub_attrib_cpu = self._set_components(
            ATTRIB_CPU_GAUGE_NAMES, stats.attrib_cpu, self._pub_attrib_cpu)
        for role, ms in stats.thread_cpu.items():
            gauges.set(THREAD_CPU_GAUGE_NAMES[role], ms)
        counters.inc("step.completed")
        # the flight event names the lagging tensor and this rank — a
        # crash black box says WHO the dying step was waiting on
        try:
            rank = get_config().host_id
        except Exception:  # noqa: BLE001 — publishing must never raise
            rank = 0
        self._recorder.record("step_stats", rank=rank, **stats.as_dict())

    @staticmethod
    def _set_components(names: Dict[str, str], values: Dict[str, float],
                        published: set) -> set:
        """One gauge a component of ``values``; returns the components
        set, for the next step's call."""
        for comp, ms in values.items():
            # KeyError here is deliberate: a new attribution component
            # must be added to the name table (and the doc table) — an
            # f-string fallback would silently bypass the bpslint
            # metric-name check the tables exist for
            gauges.set(names[comp], ms)
        # zero components absent THIS step (a step-5 compile stall must
        # not haunt every later scrape — the gauge set always describes
        # ONE step, summing to its wall_ms)
        for comp in published - set(values):
            gauges.set(names[comp], 0.0)
        return set(values)

    def flush(self) -> Optional[StepStats]:
        """Finalize the in-progress step (engine shutdown: the tail step
        must not be silently lost)."""
        with self._lock:
            if self._step > 0 and self._pushes:
                done = self._finalize_locked()
                self._publish(done)
                return done
        return None

    # -- reading -----------------------------------------------------------

    @property
    def current_step(self) -> int:
        with self._lock:
            return self._step

    def last(self) -> Optional[StepStats]:
        with self._lock:
            return self._history[-1] if self._history else None

    def history(self) -> List[StepStats]:
        with self._lock:
            return list(self._history)

    def summary(self) -> Dict[str, float]:
        """Median-of-history digest for bench artifacts."""
        hist = self.history()
        if not hist:
            return {"steps": 0}

        def med(xs):
            xs = sorted(xs)
            return xs[len(xs) // 2]

        return {
            "steps": hist[-1].step,
            "bytes_pushed_med": med([s.bytes_pushed for s in hist]),
            "sync_stall_ms_med": round(
                med([s.sync_stall_ms for s in hist]), 3),
            "wall_ms_med": round(med([s.wall_ms for s in hist]), 3),
            "overlap_fraction_med": round(
                med([s.overlap_fraction for s in hist]), 4),
            "retransmits_total": sum(s.retransmits for s in hist),
        }


# -- start-up: where the time before the first step went (ISSUE 48) ----------
#
# One clock (``time.monotonic``, seconds) from the package's first
# statement to the first ``bps.init()``, and JAX's own compile durations
# as registry counters: ``metrics_snapshot()["startup"]`` plus the seven
# ``compile.*`` counters answer "why did my job take 40 s (or 190 s) to
# take its first step, and was the cache cold?".

# JAX's duration events (``jax/_src/dispatch.py``, ``compiler.py``) and the
# counter of milliseconds each feeds — one literal a name (bpslint).
COMPILE_DURATION_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_ms",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_ms",
    "/jax/core/compile/backend_compile_duration": "compile.backend_ms",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "compile.cache_retrieval_ms",
}
COMPILE_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}


class CompileSpans:
    """What each thread has already recorded of its compile work, so that
    the ``compile.*_ms`` counters are a UNION of intervals and not a sum.

    JAX reports a duration at its END, with its length, and durations
    nest: an inner ``jax.jit`` traced while an outer one is being traced
    reports first and lies inside the outer's interval
    (``pjit._create_pjit_jaxpr`` emits ``jaxpr_trace_duration`` for inner
    and outer alike); a lowering rule may trace; and in JAX 0.9.0
    ``backend_compile_duration`` wraps ``compile_or_get_cached``
    (``jax/_src/interpreters/pxla.py``), so on a cache hit it CONTAINS
    ``cache_retrieval_time_sec``.  :meth:`claim` therefore gives an event
    ``[now - seconds, now]`` minus what the same thread has recorded
    inside that interval: the inner event keeps its time under its own
    name, the outer gets the rest, the four ``*_ms`` counters are
    disjoint (``compile.backend_ms`` is the part of the backend call
    OUTSIDE the cache's retrieval: near 0 on a warm run, the XLA compile
    on a cold one) and ``trace + lower + backend + cache_retrieval``
    never exceeds the wall a thread spent in them.

    A thread's record is a list of disjoint ``[start, end, covered]``
    spans in order of ``end``; events end in that order, so an event only
    ever meets the list's tail, and one that encloses its children
    replaces them.  Past ``_CAP`` spans (a process that compiles for
    ever, never under one enclosing event) the older half becomes ONE
    span that remembers how much of it was covered; an event that cuts a
    span takes the share of its cover that lies inside."""

    _CAP = 1024

    def __init__(self):
        self._local = threading.local()

    def claim(self, seconds: float, now: float) -> float:
        """Seconds of ``[now - seconds, now]`` new to this thread."""
        spans = getattr(self._local, "spans", None)
        if spans is None:
            spans = self._local.spans = []
        start = now - max(0.0, seconds)
        fresh = now - start
        first, kept = start, 0.0
        while spans and spans[-1][1] > start:
            a, b, cover = spans.pop()
            inside = cover if a >= start else cover * (b - start) / (b - a)
            fresh -= inside
            kept += cover - inside
            first = min(first, a)
        spans.append([first, now, kept + now - start])
        if len(spans) > self._CAP:
            old = spans[:self._CAP // 2]
            spans[:self._CAP // 2] = [[old[0][0], old[-1][1],
                                       sum(s[2] for s in old)]]
        return max(0.0, fresh)


_compile_spans = CompileSpans()
_listen_lock = threading.Lock()
_listening = False


def _on_compile_duration(event: str, seconds: float, **_kw) -> None:
    name = COMPILE_DURATION_COUNTERS.get(event)
    if name is None or not get_config().telemetry_on:
        return
    counters.inc(name, _compile_spans.claim(seconds, time.monotonic()) * 1e3)
    if name == "compile.backend_ms":      # one event a program
        counters.inc("compile.programs")


def _on_compile_event(event: str, **_kw) -> None:
    name = COMPILE_EVENT_COUNTERS.get(event)
    if name is not None and get_config().telemetry_on:
        counters.inc(name)


def listen_to_compiles() -> None:
    """Register the two ``jax.monitoring`` listeners that feed the
    ``compile.*`` counters, once a process (``bps.init()`` and
    ``enable_compile_cache()`` both call this; whichever runs first
    registers).  The listeners run on whatever thread JAX compiles on —
    the engine's dispatcher compiles lazily — and cost nothing where
    nothing compiles.  Counters are cumulative over the process: their
    value when set-up ends is set-up's, their delta over a window of
    steps should be 0."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        jax.monitoring.register_event_listener(_on_compile_event)
        _listening = True


_init_record: Dict[str, object] = {}


def record_init(begin: float, end: float, parts_ms: Dict[str, float]) -> None:
    """``bps.init()``'s stamps; the FIRST init that built an engine keeps
    them (``resume()`` runs ``init`` again and must not move the job's
    start)."""
    if not _init_record:
        _init_record.update(init_begin=begin, init_end=end,
                            init_parts_ms=dict(parts_ms))


def startup_record() -> Dict[str, object]:
    """``metrics_snapshot()["startup"]``: ``import_begin`` / ``import_end``
    (first and last statement of ``byteps_tpu/__init__.py``),
    ``init_begin`` / ``init_end`` and ``init_parts_ms`` (the first
    ``bps.init()`` and its ``bps.init.mesh | engine | services`` phases),
    and ``now`` — all ``time.monotonic`` seconds, so a reader places the
    stamps against its own wall through ``now`` without sharing an epoch.
    A stamp not taken yet is None."""
    pkg = sys.modules.get("byteps_tpu")
    record: Dict[str, object] = {
        "import_begin": getattr(pkg, "_IMPORT_BEGIN", None),
        "import_end": getattr(pkg, "_IMPORT_END", None),
        "init_begin": None, "init_end": None, "init_parts_ms": {},
    }
    record.update(_init_record)
    record["now"] = time.monotonic()
    return record
