"""Priority + credit-based chunk scheduler.

Reference behavior (scheduled_queue.cc): one priority queue per pipeline
stage; ``addTask`` keeps tasks sorted by (priority desc, key asc)
(scheduled_queue.cc:82-102), ``getTask`` enforces a credit window — a
byte-budget of in-flight work (BYTEPS_SCHEDULING_CREDIT,
scheduled_queue.cc:33-45,136-150) — and ``reportFinish`` returns credits
(scheduled_queue.cc:197-203).

TPU adaptation: XLA executes collectives in dispatch order on a chip, so the
only reliable priority knob is the order in which chunk programs are
dispatched from the host (SURVEY.md §7 "hard parts").  This scheduler is that
knob: the engine feeds every chunk task in, and pulls them back out in
priority order, bounded by the credit window so a giant low-priority tensor
cannot monopolize the dispatch queue ahead of later high-priority gradients.
A single queue suffices (stages inside one chunk run inside one fused XLA
program); the reference needed one queue per stage because its stages were
separate hardware domains.
"""

from __future__ import annotations

import heapq
import threading
import time
from typing import List, Optional

from .config import ALIGN_BYTES
from .lock_witness import named_lock
from .telemetry import attribution as _attribution
from .types import ChunkTask


class ChunkScheduler:
    """Thread-safe priority queue with a bytes-in-flight credit window."""

    def __init__(self, credit_bytes: int = 0):
        # credit_bytes == 0 means unlimited (reference: credit disabled
        # unless BYTEPS_SCHEDULING_CREDIT is set).
        self._credit_limit = credit_bytes
        self._in_flight = 0
        self._heap: List[tuple] = []
        self._seq = 0
        self._cv = threading.Condition(
            named_lock("scheduler.cv", reentrant=True))
        self._interrupts = 0   # one-shot wakeups (pause handshake)
        self._shutdown = False  # latched wake (engine teardown)

    # -- producer side -----------------------------------------------------
    def add_task(self, task: ChunkTask) -> None:
        self.add_tasks((task,))

    def add_tasks(self, tasks) -> None:
        """Hand over a list of tasks in ONE step: one hold of the lock,
        one wake-up.  The queue then holds all of them or none, so a
        consumer never sees a prefix of a tensor's chunks -- which is
        what makes the runs the dispatcher forms from them
        (:meth:`pop_while`) a property of the tensor and not of timing.
        Order and credit accounting are those of adding them one by
        one."""
        with self._cv:
            for task in tasks:
                heapq.heappush(self._heap,
                               (task.sort_tuple(), self._seq, task))
                self._seq += 1
            self._cv.notify()

    # -- consumer side -----------------------------------------------------
    def _eligible_locked(self) -> bool:
        if not self._heap:
            return False
        if self._credit_limit <= 0:
            return True
        task = self._heap[0][2]
        # Always allow at least one task in flight even if it alone exceeds
        # the window, matching the reference's clamp of oversized partitions.
        return self._in_flight == 0 or \
            self._in_flight + task.nbytes <= self._credit_limit

    def get_task(self, block: bool = False,
                 timeout: Optional[float] = None) -> Optional[ChunkTask]:
        """Pop the highest-priority task if the credit window allows it.

        ``block=True`` with no timeout parks on the condition variable
        until a task becomes eligible or :meth:`interrupt`/:meth:`wake`
        fires — the dispatcher's idle wait costs zero CPU (no polling
        quantum).  An interrupted call returns ``None``."""
        with self._cv:
            if block:
                # credit-stall attribution (ISSUE 12): tasks are queued
                # but the byte window is full — the wait about to happen
                # is a CREDIT stall, not idleness; charge it to the
                # step's attrib_credit_ms component
                credit_gated = bool(self._heap) and not self._eligible_locked()
                t0 = time.monotonic() if credit_gated else 0.0
                self._cv.wait_for(
                    lambda: (self._eligible_locked() or self._shutdown
                             or self._interrupts > 0),
                    timeout=timeout)
                if credit_gated:
                    _attribution.add(
                        "credit", (time.monotonic() - t0) * 1e3)
            if block and self._interrupts > 0:
                self._interrupts -= 1
            if not self._eligible_locked():
                return None
            _, _, task = heapq.heappop(self._heap)
            self._in_flight += task.nbytes
            return task

    def pop_while(self, more, limit: Optional[int] = None
                  ) -> List[ChunkTask]:
        """Pop the tasks that follow a popped one, in priority order and
        in one hold of the lock: each for as long as the credit window
        admits it (checked per task, as :meth:`get_task` checks it) and
        ``more(task)`` says it belongs; at most ``limit`` of them.
        Never blocks.  ``more`` sees only the head of the queue, so only
        neighbours in priority order are ever taken together."""
        out: List[ChunkTask] = []
        with self._cv:
            while ((limit is None or len(out) < limit)
                   and self._eligible_locked()
                   and more(self._heap[0][2])):
                _, _, task = heapq.heappop(self._heap)
                self._in_flight += task.nbytes
                out.append(task)
        return out

    def interrupt(self) -> None:
        """One-shot wakeup: the next (or currently blocked) get_task
        returns promptly even with nothing eligible.  The pause-dispatch
        handshake's half of the no-busy-wait design."""
        with self._cv:
            self._interrupts += 1
            self._cv.notify_all()

    def wake(self) -> None:
        """Latched wakeup: every blocked and future get_task returns
        without waiting (engine shutdown).  Queue contents survive for
        :meth:`drain`."""
        with self._cv:
            self._shutdown = True
            self._cv.notify_all()

    def set_credit_bytes(self, credit_bytes: int) -> None:
        """Retarget the credit window (the planner's tuned value); a wider
        window may make queued tasks eligible, so waiters are notified."""
        with self._cv:
            self._credit_limit = int(credit_bytes)
            self._cv.notify_all()

    @property
    def credit_bytes(self) -> int:
        with self._cv:
            return self._credit_limit

    def report_finish(self, nbytes: int) -> None:
        """Return credits; a batched syncer passes one summed total per
        retire sweep (one lock round-trip for the whole dispatch unit
        batch instead of one per chunk)."""
        with self._cv:
            self._in_flight = max(0, self._in_flight - nbytes)
            self._cv.notify()

    # -- introspection ------------------------------------------------------
    @property
    def pending(self) -> int:
        with self._cv:
            return len(self._heap)

    @property
    def bytes_in_flight(self) -> int:
        with self._cv:
            return self._in_flight

    def drain(self) -> List[ChunkTask]:
        """Pop everything regardless of credit (shutdown path)."""
        with self._cv:
            tasks = [t for _, _, t in sorted(self._heap)]
            self._heap.clear()
            return tasks


# --------------------------------------------------------------------------
# Auto-tuned chunk/credit planner
# --------------------------------------------------------------------------

# Per-(size-bucket, candidate) samples required before the planner moves
# on; min-of-samples scoring rejects one-off outliers (a GC pause, a
# first-touch compile) without needing a long exploration phase.
_PLAN_SAMPLES = 2
# Chunk sizes stay on the partitioner's alignment so tuned bounds keep
# the vreg-tile guarantees; the ONE canonical constant lives in config
# (a drifted copy here would let the planner emit bounds that violate
# the tiling the partitioner rounds to).
_PLAN_ALIGN = ALIGN_BYTES

# The compressor candidate ladder (ISSUE 11): per size bucket the planner
# races these codecs on measured push wall time, gated by the codec-golden
# gradient-error ceiling.  Every quantized candidate carries error
# feedback — it is what makes a lossy codec's LONG-RUN delivered gradient
# track the true one, and the golden-error figure is EF-aware to match.
# k=0.25 for the sparsifiers: the densest rung whose EF-corrected golden
# error clears the default ceiling (k=0.01 never delivers enough mass in
# a bounded window — "Compressed Communication for Distributed Training"
# (PAPERS.md) reaches the same per-bucket-adaptive conclusion).
COMPRESS_LADDER = (
    ("none", None),
    ("onebit", {"compressor": "onebit", "ef": "vanilla"}),
    ("randomk", {"compressor": "randomk", "k": "0.25", "ef": "vanilla"}),
    ("topk", {"compressor": "topk", "k": "0.25", "ef": "vanilla"}),
)


class ChunkPlanner:
    """Online (chunk-size, credit-window) tuner for the push_pull hot path.

    The reference ships BYTEPS_PARTITION_BYTES and BYTEPS_SCHEDULING_CREDIT
    as hand-tuned deployment knobs (global.cc:134-144,
    scheduled_queue.cc:33-45); the right values depend on the host's
    dispatch overhead and the mesh's per-program cost, which this planner
    measures instead of assuming.  Per tensor-size bucket (power of two of
    nbytes) it explores a small candidate ladder — the configured bound,
    the whole tensor, and halves down to a floor — scoring each candidate
    by the best observed wall seconds of a completed push_pull, then locks
    the winner.  Locking matters twice over: steady state stops paying
    exploration dispatch patterns, and the compiled-program set stops
    growing (the zero-new-compiles-after-warmup contract the regression
    test enforces).

    Reproducibility: a pinned knob (env var present, or a non-default
    Config value) is never tuned; multi-process meshes never tune at all —
    SPMD processes must dispatch identical programs in identical order,
    and per-host timing would diverge their choices.

    Known blind spot: the compile-pollution discard keys off the engine's
    program-cache miss counter, which cannot see a RETRACE inside a
    shape-generic jit wrapper (the single-chunk collectives serve many
    shapes under one cache key) — a concurrent first-push of another
    tensor can smuggle such a compile into a kept sample.  Min-of-samples
    scoring bounds the damage (a polluted sample only mis-locks a bucket
    if EVERY sample of the true winner was also polluted), and the
    round-robin candidate order keeps one bad wall-clock window from
    landing entirely on one candidate.
    """

    def __init__(self, cfg, num_procs: int = 1):
        self._base = cfg.partition_bytes
        self._tune_partition = (cfg.autotune and not cfg.partition_pinned
                                and num_procs == 1)
        self._tune_credit = (cfg.autotune and not cfg.credit_pinned
                             and num_procs == 1)
        # Compressor-ladder dimension (ISSUE 11): opt-in (a tuned codec
        # changes gradient values, unlike a tuned chunk size), and never
        # multi-process — SPMD processes must dispatch identical
        # programs, and a per-host codec choice would diverge them.
        # Per-tensor pins (explicit compression= kwargs) live in the
        # engine: a pinned tensor never calls plan_compression at all.
        self._tune_compress = cfg.compress_autotune and num_procs == 1
        self._error_ceiling = cfg.compress_error_ceiling
        self._min_compress = cfg.min_compress_bytes
        self._cbuckets = {}         # bucket -> compressor-ladder state
        self._buckets = {}          # bucket -> state dict
        self._lock = named_lock("planner")
        self._credit = 0            # 0 = leave the scheduler unlimited

    @property
    def active(self) -> bool:
        return self._tune_partition

    # -- plan --------------------------------------------------------------
    def _candidates(self, nbytes: int) -> List[int]:
        def align(b):
            b = max(_PLAN_ALIGN, int(b))
            r = b % _PLAN_ALIGN
            return b + (_PLAN_ALIGN - r) if r else b

        ladder = [self._base, align(nbytes), align(nbytes // 2),
                  align(nbytes // 4)]
        out = []
        for c in ladder:
            if c >= _PLAN_ALIGN and c not in out:
                out.append(c)
        return out

    def plan_partition(self, nbytes: int) -> int:
        """Partition bound to use right now for a tensor of ``nbytes``.
        Tensors at or under the configured bound are single-chunk either
        way — nothing to tune.

        Exploration is ROUND-ROBIN (fewest-samples candidate first, ladder
        order on ties), not sequential blocks: a shared host's speed is
        often bimodal on a seconds timescale, and a candidate whose whole
        sample block landed in the slow regime would lose to one sampled
        in the fast regime on host luck, not merit — interleaving spreads
        every candidate across the regimes (the same reasoning as the
        overlap bench's round interleaving)."""
        if not self._tune_partition or nbytes <= self._base:
            return self._base
        bucket = nbytes.bit_length()
        with self._lock:
            st = self._buckets.get(bucket)
            if st is None:
                st = {"cands": self._candidates(nbytes),
                      "samples": {}, "locked": None}
                self._buckets[bucket] = st
            if st["locked"] is not None:
                return st["locked"]
            return min(st["cands"],
                       key=lambda c: len(st["samples"].get(c, ())))

    # -- observe -----------------------------------------------------------
    def observe(self, nbytes: int, partition_bytes: int, seconds: float,
                compiled: bool = False) -> None:
        """Record one completed push_pull.  ``compiled=True`` (a program
        compile landed inside this push's window) discards the sample —
        compile time must not be charged to the candidate."""
        if (not self._tune_partition or nbytes <= self._base
                or seconds <= 0 or compiled):
            return
        bucket = nbytes.bit_length()
        with self._lock:
            st = self._buckets.get(bucket)
            if st is None or st["locked"] is not None:
                return
            if partition_bytes not in st["cands"]:
                return  # carved under an earlier plan / repartition race
            st["samples"].setdefault(partition_bytes, []).append(seconds)
            if any(len(st["samples"].get(c, ())) < _PLAN_SAMPLES
                   for c in st["cands"]):
                return
            # every candidate sampled: lock the winner (min-of-samples)
            best = min(st["cands"],
                       key=lambda c: min(st["samples"].get(c, [float("inf")]))
                       )
            st["locked"] = best
            self._update_credit_locked()

    def _update_credit_locked(self) -> None:
        """Tuned credit window: enough for a handful of the largest locked
        chunk so the dispatcher pipelines without letting one giant
        low-priority tensor monopolize the queue (the reference's credit
        rationale, scheduled_queue.cc:33-45)."""
        if not self._tune_credit:
            return
        largest = max((st["locked"] for st in self._buckets.values()
                       if st["locked"] is not None), default=0)
        if largest:
            self._credit = 4 * largest

    def credit_bytes(self) -> int:
        """The planner's current credit-window suggestion (0 = leave the
        scheduler's window as configured)."""
        with self._lock:
            return self._credit

    def locked(self, nbytes: int) -> bool:
        if not self._tune_partition or nbytes <= self._base:
            return True             # nothing left to explore
        with self._lock:
            st = self._buckets.get(nbytes.bit_length())
            return st is not None and st["locked"] is not None

    # -- compressor ladder (ISSUE 11) --------------------------------------

    @property
    def compress_active(self) -> bool:
        return self._tune_compress

    def _compress_candidates(self) -> List[tuple]:
        """Ladder candidates for one bucket as ``(key, kwargs, golden)``
        triples.  A quantized candidate whose codec-golden gradient
        error exceeds the ceiling is excluded UP FRONT — there is no
        point paying exploration dispatches for a codec the quality
        gate would refuse to lock.  Computing the goldens runs JAX work
        (compress/decompress compiles on first use), so callers invoke
        this OUTSIDE the planner lock."""
        from ..compression import registry as _creg
        out = [("none", None, 0.0)]
        for key, kw in COMPRESS_LADDER[1:]:
            try:
                err = _creg.golden_error(kw)
            except Exception:  # noqa: BLE001 — a codec whose golden
                continue       # cannot even run must never be chosen
            if err <= self._error_ceiling:
                out.append((key, kw, err))
        return out

    def plan_compression(self, nbytes: int):
        """Compression kwargs to use right now for an unpinned tensor of
        ``nbytes`` (``None`` = uncompressed).  Exploration is the same
        fewest-samples-first round-robin as the chunk ladder; the CHUNK
        dimension must lock first — racing both dimensions at once would
        attribute a chunk candidate's wall time to a codec (and the
        compressed path carves its own bounds anyway).  The compression
        cutoff is checked against the TENSOR's nbytes, not the bucket's
        state: a bucket can straddle ``min_compress_bytes``, and a
        below-cutoff tensor planned a codec the engine then strips
        would re-carve its bounds on every push and charge its samples
        to the wrong candidate."""
        if not self._tune_compress:
            return None
        if nbytes < max(1, self._min_compress):
            return None
        if not self.locked(nbytes):
            return None
        bucket = nbytes.bit_length()
        with self._lock:
            st = self._cbuckets.get(bucket)
        if st is None:
            # golden-error computation compiles codec programs — do it
            # outside the lock (memoized module-level, so a racing
            # second thread pays nothing; setdefault dedups the bucket)
            cands = self._compress_candidates()
            with self._lock:
                st = self._cbuckets.setdefault(
                    bucket, {"cands": cands, "samples": {},
                             "locked": None})
        with self._lock:
            if st["locked"] is not None:
                return next(kw for k, kw, _ in st["cands"]
                            if k == st["locked"])
            key = min((k for k, _, _ in st["cands"]),
                      key=lambda k: len(st["samples"].get(k, ())))
            return next(kw for k, kw, _ in st["cands"] if k == key)

    def plan_param_codec(self, nbytes: int):
        """Pull-leg codec kwargs for a sharded-update tensor of
        ``nbytes`` under ``BYTEPS_SHARDED_PARAM_CODEC=auto`` (ISSUE 20),
        or ``None`` for full precision.

        Unlike :meth:`plan_compression` this is DETERMINISTIC — no
        wall-time race.  The parameter leg's codec changes the values
        every replica integrates, so the choice must be a pure function
        of tensor size and the quality gate, reproducible across runs
        and across an elastic restart (a timing-raced choice could hand
        the same tensor different codecs on two boots of the same job).
        Per size bucket: candidates are the ceiling-filtered ladder
        (:meth:`_compress_candidates`); tensors under 4 MiB take the
        LOWEST-golden-error quantized rung (quality-first — small
        tensors' wire is cheap), larger ones take onebit when it clears
        the gate (the 32x rung: wire dominates) and otherwise fall back
        to the lowest-error rung."""
        if nbytes < max(1, self._min_compress):
            return None
        cands = [(k, kw, err) for k, kw, err in self._compress_candidates()
                 if kw is not None]
        if not cands:
            return None
        if nbytes >= (4 << 20):
            for k, kw, _ in cands:
                if k == "onebit":
                    return kw
        return min(cands, key=lambda c: c[2])[1]

    def observe_compression(self, nbytes: int, codec: str, seconds: float,
                            compiled: bool = False) -> None:
        """Record one completed push of a ladder-tuned tensor under
        ``codec`` (the candidate key, e.g. "onebit").  Compile-polluted
        samples are discarded exactly like the chunk ladder's."""
        if (not self._tune_compress or seconds <= 0 or compiled
                or nbytes < max(1, self._min_compress)):
            return
        bucket = nbytes.bit_length()
        locked_now = None
        with self._lock:
            st = self._cbuckets.get(bucket)
            if st is None or st["locked"] is not None:
                return
            if codec not in {k for k, _, _ in st["cands"]}:
                return  # pushed under an earlier ladder / retune race
            st["samples"].setdefault(codec, []).append(seconds)
            if any(len(st["samples"].get(k, ())) < _PLAN_SAMPLES
                   for k, _, _ in st["cands"]):
                return
            best = min((k for k, _, _ in st["cands"]),
                       key=lambda k: min(st["samples"].get(k,
                                                           [float("inf")])))
            st["locked"] = best
            locked_now = best
        if locked_now is not None:
            # telemetry outside the planner lock: the codec-lock event is
            # an operator-visible decision (bps_top CODEC column,
            # /metrics, flight recorder)
            from . import flight_recorder as _flight
            from .telemetry import counters as _counters
            from .telemetry import gauges as _gauges
            _counters.inc("compression.planner_locked")
            _gauges.set("compression.codec_locked", 1.0,
                        bucket=bucket, codec=locked_now)
            _flight.record("compression.codec_locked", bucket=bucket,
                           codec=locked_now)

    def compress_locked(self, nbytes: int) -> bool:
        """True once the bucket's codec stopped moving (or the ladder is
        off, or the tensor is under the compression cutoff — nothing to
        explore) — the engine's cue to stop stamping measurement
        windows."""
        if (not self._tune_compress
                or nbytes < max(1, self._min_compress)):
            return True
        with self._lock:
            st = self._cbuckets.get(nbytes.bit_length())
            return st is not None and st["locked"] is not None

    def snapshot(self) -> dict:
        """Chosen knobs for the bench JSON / telemetry: per-bucket locked
        chunk size (or exploration progress) and the credit suggestion."""
        with self._lock:
            buckets = {}
            for b, st in self._buckets.items():
                buckets[str(b)] = {
                    "locked_partition_bytes": st["locked"],
                    "explored": {str(k): round(min(v), 6)
                                 for k, v in st["samples"].items() if v},
                }
            cbuckets = {}
            for b, st in self._cbuckets.items():
                cbuckets[str(b)] = {
                    "locked_codec": st["locked"],
                    "explored": {k: round(min(v), 6)
                                 for k, v in st["samples"].items() if v},
                    "golden_error": {k: round(e, 4)
                                     for k, _, e in st["cands"]},
                }
            return {"tuning_partition": self._tune_partition,
                    "tuning_credit": self._tune_credit,
                    "base_partition_bytes": self._base,
                    "credit_bytes": self._credit,
                    "buckets": buckets,
                    "compression": {"tuning": self._tune_compress,
                                    "error_ceiling": self._error_ceiling,
                                    "buckets": cbuckets}}
