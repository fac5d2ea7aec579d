"""The push_pull engine: partition -> schedule -> chunked collective -> callback.

This is the TPU-native collapse of the reference's core runtime
(operations.cc EnqueueTensor + scheduled_queue.cc + core_loops.cc).  The
reference runs ~15 dedicated stage threads because its pipeline crosses five
hardware domains (GPU, PCIe, host memory, NIC, remote server).  On TPU one
chunk's whole reduction is a single fused XLA program over the mesh, so two
threads suffice:

- the **dispatcher** pops chunk tasks from the priority scheduler (credit
  window permitting) and launches the chunk collective — JAX async dispatch
  returns immediately, so dispatch order from this thread IS the priority
  mechanism (SURVEY.md §7 "priority scheduling under XLA");
- the **syncer** blocks on issued chunks in order, returns scheduling
  credits, and fires the tensor callback when its last partition lands —
  the role the reference's SyncNcclLoop + FinishOrProceed play
  (core_loops.cc:31-137,362-376).
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..comm.collectives import (_as_stacked, aot_warm_bucket_programs,
                                aot_warm_buffer_programs,
                                aot_warm_single_program, assemble_scatter,
                                assemble_shardable, pack_bucket, pad_stacked,
                                push_pull_array, push_pull_array_scaled,
                                push_pull_arrays_batched,
                                push_pull_chunk_scatter, scatter_layout,
                                stage_local_replicated, stage_local_sharded,
                                unpack_bucket)
from ..comm.compressed import (aot_warm_compressed_programs,
                               fused_compressed_push_pull)
from ..comm.mesh import CommContext
from ..compression import registry as compression_registry
from ..common.config import Config
from ..common.handles import Handle, HandleManager, TreeHandle
from ..common.logging import get_logger
from ..common.partitioner import bucket_bounds, chunk_bounds, unit_bounds
from ..common.registry import TensorRegistry
from ..common.scheduler import ChunkPlanner, ChunkScheduler
from ..common import flight_recorder as _flight
from ..common import tracing as _tracing
from ..common.telemetry import (SpeedMonitor, StepStatsTracker,
                                counters, gauges, histograms)
from ..common.types import ChunkTask, Status, StatusCode, TensorContext
from ..fault import injector as _fault
from ..fault import membership as _membership
from .sharded_update import ShardedUpdateSlot


_SHUTDOWN = object()  # sync-queue sentinel

# A bucket of leaves (push_pull_tree_async) holds at most this many
# partitions' worth of bytes: 16 x the configured partition_bytes, 65.5 MB
# at the default 4,096,000 B.  Measured on the v5e (PERF.md section 6,
# PR 24).  A multiple of the CONFIGURED base, never of the planner's tuned
# value, which moves with timing while it explores.
BUCKET_CAP_PARTITIONS = 16


class StaleEpochError(RuntimeError):
    """A chunk from a dead membership epoch was dropped (not delivered):
    the world changed between enqueue and completion."""


def _stale_epoch_error(task, epoch: int) -> StaleEpochError:
    return StaleEpochError(
        f"stale membership epoch: chunk {task.name!r} key={task.key} was "
        f"enqueued at epoch {task.pending.mepoch}, the world is now at "
        f"epoch {epoch}; chunk dropped, re-push under the new epoch")


def _unit_stop(task) -> int:
    """The column at which the dispatch unit that starts at this chunk
    ends (0: the chunk starts none, it lies inside one)."""
    return task.pending.unit_stops.get(task.offset_elems, 0)


def _unit_followers(head):
    """Predicate for ``ChunkScheduler.pop_while``: the tasks that, one
    after the other, continue the dispatch unit a buffer-mode ``head``
    starts -- the next columns of the same push, short of the unit's
    end."""
    stop, pending = _unit_stop(head), head.pending
    end = head.offset_elems + head.num_elems

    def more(task):
        nonlocal end
        if (end >= stop or task.pending is not pending
                or task.offset_elems != end):
            return False
        end += task.num_elems
        return True
    return more


def _buffered(task) -> bool:
    return task.pending is not None and task.pending.use_buffer


def _pop_batch(scheduler, task, group_size: int):
    """The popped ``task`` and what goes with it, taken in one hold of
    the queue's lock.  Popping preserves priority order, and only
    neighbours in that order are ever taken together.

    A chunk of a buffer-mode tensor brings the rest of its dispatch
    unit: the queued chunks that continue it, of any widths, up to the
    unit's end (``PushPullEngine._unit_layout``), each gated by the
    credit window as the first was.  Anything else brings up to
    ``group_size`` tasks in all that are not buffer-mode chunks
    (reference BYTEPS_NCCL_GROUP_SIZE, nccl_manager.cc:130-134):
    parts-mode chunks, which ``_plan_batch`` merges across tensors where
    their shapes agree, and compressed ones."""
    if _buffered(task):
        return [task] + scheduler.pop_while(_unit_followers(task))
    if group_size <= 1:
        return [task]
    return [task] + scheduler.pop_while(lambda t: not _buffered(t),
                                        group_size - 1)


def _plan_batch(batch):
    """Group a popped priority-ordered task batch into dispatch units:

    - ``("run", tasks)``: a contiguous column range of ONE buffer-mode
      tensor, chunks of any widths, the tail included -- one
      chunk-scatter program.  The range is one of the tensor's dispatch
      units (``PushPullEngine._unit_layout``) or, where the batch holds
      only part of one, each of its chunks by itself.
    - ``("group", tasks)``: consecutive uncompressed equal-shape chunks of
      DISTINCT tensors — one batched-collective program (the cross-tensor
      half of the reference's NCCL group batching).
    - ``("single", [task])``: everything else (compressed chunks, odd
      shapes).

    Only ADJACENT tasks ever merge, so dispatch order — the priority
    mechanism — is preserved across units; within a unit all chunks
    execute as one program, which collapses their relative order the same
    way the reference's ncclGroupStart/End does."""
    units = []
    i = 0
    while i < len(batch):
        t = batch[i]
        if _buffered(t):
            more, j = _unit_followers(t), i + 1
            while j < len(batch) and more(batch[j]):
                j += 1
            run = batch[i:j]
            if run[-1].offset_elems + run[-1].num_elems == _unit_stop(t):
                units.append(("run", run))
            else:
                units.extend(("run", [c]) for c in run)
            i = j
            continue
        if t.compression is None:
            group = [t]
            j = i + 1
            while (j < len(batch)
                   and batch[j].compression is None
                   and not _buffered(batch[j])
                   and batch[j].data.shape == t.data.shape
                   and batch[j].data.dtype == t.data.dtype
                   and batch[j].scale == t.scale):
                group.append(batch[j])
                j += 1
            # a width-1 "group" would compile a fresh batched_ar program
            # for a computation the single-task all_reduce cache already
            # holds — route it through _dispatch_single instead
            units.append(("group" if len(group) > 1 else "single", group))
            i = j
            continue
        units.append(("single", [t]))
        i += 1
    return units


class _CompressionSlot:
    """Per-chunk compressor pair + functional state, engine-owned.

    TPU stand-in for the reference's per-partition compressor objects with
    hidden buffers (compressor_list, common.h:201): state is explicit JAX
    arrays, committed by the dispatcher at issue time (so pipelined steps
    of the same chunk chain correctly) and rolled back by the syncer if the
    async execution fails."""

    __slots__ = ("worker", "server", "wstates", "sstate")

    def __init__(self, worker, server, wstates, sstate):
        self.worker = worker
        self.server = server
        self.wstates = wstates      # rank-stacked pytree
        self.sstate = sstate        # replicated pytree


class _Bucket:
    """A run of consecutive leaves of one tree pushed as ONE engine tensor
    (ISSUE 24): packed by one program, partitioned / scheduled / reduced
    like any flat tensor of ``num_elems`` elements under ``name``, and
    split back into the leaves by one program at assembly.  Made once
    per tree signature (``PushPullEngine._plan_tree``); immutable but for
    ``warmed``."""

    __slots__ = ("name", "shapes", "dtype", "num_elems", "n_pad",
                 "shard_out", "shardings", "warmed")

    def __init__(self, name, shapes, dtype, n_ici, shard_out, shardings):
        self.name = name
        self.shapes = shapes        # the leaves' shapes, rank axis dropped
        self.dtype = dtype          # np.dtype, one per bucket
        self.num_elems = sum(int(np.prod(s)) for s in shapes)
        # rounded up to the ICI axis: the scatter layout's C * n_ici,
        # whatever the chunk bounds
        self.n_pad = -(-self.num_elems // n_ici) * n_ici
        self.shard_out = shard_out  # per leaf: block-sharded output?
        self.shardings = shardings  # per leaf: the stacked input sharding
        self.warmed = False

    def pack(self, comm, leaves):
        """The leaves -> the flat, padded [R, n_pad] array in the stacked
        sharding.  A leaf that is not already rank-major on the mesh
        (host data, a default-device array) is staged there first, as
        the per-tensor path stages it."""
        leaves = [leaf if (isinstance(leaf, jax.Array)
                           and leaf.sharding == sh)
                  else _as_stacked(comm, leaf)
                  for leaf, sh in zip(leaves, self.shardings)]
        return pack_bucket(comm, leaves, self.shapes, self.dtype.name,
                           self.n_pad)

    def unpack(self, comm, x, scale=None):
        """The reduced bucket (accumulator or flat row) -> its leaves."""
        return unpack_bucket(comm, x, self.shapes, self.dtype.name,
                             self.shard_out, scale=scale)


class _PendingTensor:
    """Accumulates finished chunks of one push_pull until all arrive.

    Two assembly modes:

    - **parts** (single-chunk, compressed, or debug-sample tensors): each
      finished chunk is kept and concatenated at the end — the round-2
      design.
    - **buffer** (uncompressed multi-chunk, the hot path): each dispatch
      unit's compiled program reduce-scatters its column range into a
      sharded accumulator in place (donated between dispatches; a unit
      that is the whole tensor makes it outright); one assemble program
      all-gathers, re-orders, scales and reshapes.  ``buf`` is only ever
      touched by the single dispatcher thread until the final callback
      fires, after which it is immutable.  ``unit_stops`` maps the
      column each dispatch unit starts at to the column it ends at
      (``PushPullEngine._unit_layout``).
    """

    def __init__(self, handle: Handle, ctx: TensorContext, out_shape, op: str,
                 denom: int, use_buffer: bool = False, comm=None,
                 scale=None, shard_out: bool = False, slot=None,
                 bucket: Optional[_Bucket] = None, unit_stops=None):
        self.handle = handle
        self.ctx = ctx
        self.out_shape = out_shape
        self.op = op
        self.denom = denom  # divisor applied at assembly (1 = plain sum)
        self.parts: Dict[int, Any] = {}
        self.total = len(ctx.chunk_bounds)
        self.use_buffer = use_buffer
        self.unit_stops: Dict[int, int] = unit_stops or {}
        self.buf = None          # dispatcher-owned until completion
        self.comm = comm
        self.scale = scale       # fused scale, applied by assemble
        self.shard_out = shard_out  # deferred-gather assembly
        # sharded-update slot (ISSUE 20): assembly routes through the
        # owner-resident optimizer instead of emitting the merged
        # gradient — the handle resolves to the optax UPDATES tensor
        self.slot = slot
        # bucket tensor (ISSUE 24): assembly splits the reduced bucket
        # into its leaves -- the handle resolves to their tuple
        self.bucket = bucket
        self.local_mode = False  # staging mode (False | True | "sharded")
        # chunk bounds snapshot: the planner can repartition the ctx for a
        # LATER push while this one is in flight-free... bounds are only
        # re-carved at inflight == 0, but the snapshot keeps assemble and
        # the bounds this push was carved with in one place regardless
        self.scatter_layout_snap = ctx.scatter_layout
        # membership epoch at enqueue: a world change (fault/membership)
        # advances the global epoch and every chunk still carrying the
        # old one is dropped, not delivered — the whole-world analog of
        # ServerEngine.reset_key's per-key epoch
        self.mepoch = _membership.current_epoch()
        # causal tracing (ISSUE 12): one TraceContext per captured push;
        # the flow arc is emitted once per push (s at the first chunk's
        # retirement record, f at the last's) — both touched only on the
        # single syncer thread
        self.trace = None
        self.trace_started = False
        self.trace_left = self.total
        self._done = 0
        self.lock = threading.Lock()

    def complete_part(self, part_idx: int, data) -> bool:
        with self.lock:
            self.parts[part_idx] = data
            return len(self.parts) == self.total

    def complete_run(self, chunks: int) -> bool:
        """Buffer mode: a dispatch unit of ``chunks`` chunks landed in
        the accumulator."""
        with self.lock:
            self._done += chunks
            return self._done == self.total

    def assemble(self):
        if self.use_buffer:
            _, C = self.scatter_layout_snap
            if self.slot is not None:
                # the accumulator IS the owner-resident gradient shard:
                # commit the fused optimizer update in place of the
                # gradient assembly (runs on the same syncer thread, so
                # retirement order == dispatch order)
                return self.slot.apply_buffer(
                    self.buf, scale=self.scale, denom=self.denom,
                    shard_out=self.shard_out)
            if self.bucket is not None:
                # assembly and unpack in one program
                return self.bucket.unpack(self.comm, self.buf, self.scale)
            return assemble_scatter(
                self.comm, self.buf, self.ctx.num_elems, C, self.out_shape,
                self.ctx.dtype_name, scale=self.scale, denom=self.denom,
                shard_out=self.shard_out)
        if self.total == 1:
            flat = self.parts[0]
        else:
            flat = jnp.concatenate([self.parts[i] for i in range(self.total)])
        if self.bucket is not None:
            # parts-mode bucket (under buffer_min_bytes, or a mesh the
            # column layout cannot express): float and uncompressed, so
            # the collective already applied the scale; a single chunk
            # still carries the pack's pad, which the split ignores
            return self.bucket.unpack(self.comm, flat)
        out = flat.reshape(self.out_shape)
        if self.denom != 1:
            # The reference divides by size in the done-callback
            # (torch/ops.cc StartTask callback; torch/__init__.py).
            if jnp.issubdtype(out.dtype, jnp.inexact):
                out = out / self.denom
            else:
                out = out // self.denom
        # f16/bf16 chunks come back as f32 sums (collectives keep the
        # accumulation dtype so the over-count division above happens
        # before any downcast); restore the declared dtype here
        if out.dtype != np.dtype(self.ctx.dtype_name):
            out = out.astype(self.ctx.dtype_name)
        if self.slot is not None:
            # parts fallback under sharded update: the merged gradient
            # was materialized anyway, so only the numerics route
            # through the slot (wire accounting stays at full size)
            return self.slot.apply_full(out)
        return out


class PushPullEngine:
    """Process-wide engine; one per bps.init() (reference BytePSGlobal)."""

    def __init__(self, comm: CommContext, cfg: Config):
        self.comm = comm
        self.cfg = cfg
        self.registry = TensorRegistry()
        self.handles = HandleManager()
        # per-tensor owner-resident optimizer slots (ISSUE 20 sharded
        # weight update); populated by declare_update
        self.update_slots: Dict[str, ShardedUpdateSlot] = {}
        # bucket plans by tree signature (push_pull_tree_async); dropped
        # with the engine on an elastic transition
        self._tree_plans: Dict[tuple, tuple] = {}
        self.scheduler = ChunkScheduler(credit_bytes=cfg.scheduling_credit)
        self.speed = SpeedMonitor()
        # ONE tracer per process (common/tracing.py): the engine, the
        # membership bus, the wire hops and the serving plane all emit
        # into the same per-rank trace file, so a push's flow arc can
        # cross component boundaries
        self.tracer = _tracing.tracer()
        # Per-step stats (bytes pushed, sync stall, retransmits, overlap
        # fraction) — surfaced through /metrics (step.* gauges), the
        # flight recorder, and the bench tools (ISSUE 6).
        # dispatch amortization accounting: programs launched vs chunk
        # tasks consumed (the bench's engine_grouped_* evidence; the
        # tracker publishes each step's deltas)
        # whole_units: the dispatches that carried ONE whole tensor
        self.stats = {"dispatches": 0, "chunks": 0, "whole_units": 0}
        self.step_stats = StepStatsTracker(engine_stats=self.stats)
        # Where each phase of the step (common/tracing.py ``phase``)
        # sends its milliseconds: the step's attribution component of
        # the same name; nowhere with telemetry off.
        self.phase_feeds = {
            c: self.step_stats.feed(c) if cfg.telemetry_on else None
            for c in ("update", "push_pull", "enqueue", "submit", "wait",
                      "tx_update", "plan", "dispatch", "compile", "sync",
                      "assemble")}
        self._sync_q: "queue.Queue" = queue.Queue()
        # Tasks popped per dispatch iteration where they are not a
        # buffer-mode tensor's chunks (_pop_batch).  Multi-host stays at
        # 1, and at one chunk a buffer-mode unit: SPMD processes must
        # dispatch identical programs in identical order (the reference
        # pins followers to the root's order via DO_* socket signals,
        # communicator.h:43), and merging what the queue held was
        # timing-dependent.  Buffer-mode units no longer are, since a
        # tensor's chunks enter the queue in one step
        # (ChunkScheduler.add_tasks): the multi-process mesh could follow.
        self._one_chunk_units = jax.process_count() > 1
        self._group_size = (1 if self._one_chunk_units
                            else max(1, cfg.group_size))
        # Auto-tuned chunk/credit planner: measures completed push_pulls
        # and re-carves partition bounds per tensor-size bucket; inert
        # when pinned (env/explicit config) or multi-process (SPMD
        # processes must dispatch identical programs).
        self.planner = ChunkPlanner(cfg, num_procs=jax.process_count())
        self._dispatch_enabled = threading.Event()
        self._dispatch_enabled.set()
        self._parked = threading.Event()  # dispatcher pause handshake
        self._running = True
        # Data-path sync deadline (BYTEPS_SYNC_DEADLINE_S, off by
        # default): a unit the syncer stays blocked on past the deadline
        # — the wedged-collective TPU failure mode, where a dead peer
        # blocks survivors inside block_until_ready without erroring
        # them — is converted into failure evidence for the installed
        # failure action (failure_detector.data_path_stalled) instead of
        # wedging silently until the step watchdog's last-resort exit.
        # The watchdog must be a SEPARATE thread: the captive syncer
        # cannot observe its own wedge.
        self._block = jax.block_until_ready  # patch point: tests wedge it
        # last compression.active codec published per tensor (scrape-time
        # gauge hygiene — see refresh_compression_gauges)
        self._comp_gauge_codecs: Dict[str, str] = {}
        self._deadline_on = cfg.sync_deadline_s > 0
        self._sync_block_lock = threading.Lock()
        self._sync_block: Optional[tuple] = None  # (t0, [tensor names])
        self._deadline_stop = threading.Event()
        self._deadline_thread: Optional[threading.Thread] = None
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="bps-dispatch", daemon=True)
        self._syncer = threading.Thread(
            target=self._sync_loop, name="bps-sync", daemon=True)
        self._dispatcher.start()
        self._syncer.start()
        if cfg.sync_deadline_s > 0:
            self._deadline_thread = threading.Thread(
                target=self._deadline_loop, name="bps-sync-deadline",
                daemon=True)
            self._deadline_thread.start()
        _flight.record("engine.init", ranks=comm.num_ranks,
                       epoch=_membership.current_epoch())

    # ------------------------------------------------------------------ API
    def push_pull_async(self, stacked, name: str,
                        priority: Optional[int] = None,
                        op: str = "average",
                        compression: Optional[Dict[str, str]] = None,
                        denom: Optional[int] = None,
                        out_shape: Optional[tuple] = None,
                        local: bool = False,
                        replicate_out: bool = False,
                        update_slot=None,
                        bucket: Optional[_Bucket] = None,
                        ) -> Handle:
        """Enqueue a rank-stacked tensor [R, ...] for reduction.

        Equivalent of common::EnqueueTensor (reference operations.cc:182-281):
        splits into partitions, each an independently scheduled ChunkTask;
        the returned handle completes when every partition's collective has
        executed and the result is reassembled.

        ``local=True``: ``stacked`` is this process's bare contribution
        (no rank axis); it is staged ONCE to one device and replicated
        on-device (collectives.stage_local_replicated) instead of R
        host->device row copies — the host-staging fast path for the
        single-process adapter case (round-3 VERDICT task 4).  Callers
        guarantee no compression and no debug sampling on this path.

        ``bucket`` (push_pull_tree_async only): ``stacked`` is the
        bucket's list of leaves; they are packed into one flat tensor
        here and the handle resolves to the tuple of reduced leaves.
        """
        if not self._running:
            raise RuntimeError("engine is shut down")
        # Caller-side prep — validation, planning, staging — until the
        # tasks enter the queue is the step's "enqueue" phase.  Opened
        # and closed by hand: it ends mid-function, inside the claim's
        # try (a raise abandons it unfed).
        ph_enq = _tracing.phase("bps.engine.enqueue",
                                self.phase_feeds["enqueue"])
        ph_enq.__enter__()
        if _membership.is_parked():
            # minority side of a partition: no epoch can be agreed from
            # here, so fail the enqueue loudly instead of queueing work
            # a suspended engine will never complete
            raise RuntimeError(
                "membership is parked on the minority side of a "
                "partition (membership.partition_minority): wait for "
                "the partition to heal, then rejoin()")
        if _fault.ENABLED:
            # one "step" per enqueued tensor: kill:step=N counts these
            _fault.on_step()
        if bucket is not None:
            # geometry is the plan's: the leaves' rank axis was checked
            # when it was made
            dtype, out_shape = bucket.dtype, (bucket.num_elems,)
        elif local:
            dtype = stacked.dtype
            if compression:
                raise ValueError(
                    "compression= is not supported on the local "
                    "(single-contribution) fast path: compressed chunks "
                    "need materialized per-rank rows.  Pass the "
                    "rank-stacked [R, ...] layout to push_pull_async, "
                    "or call push_pull_local/push_pull_local_async, "
                    "which routes compressed tensors through the "
                    "stacked layout automatically")
            if out_shape is None:
                out_shape = stacked.shape
        else:
            dtype = stacked.dtype
            r = stacked.shape[0]
            if r != self.comm.num_ranks:
                raise ValueError(
                    f"stacked rank axis {r} != mesh ranks "
                    f"{self.comm.num_ranks}")
            if out_shape is None:
                out_shape = stacked.shape[1:]
        if update_slot is not None and compression:
            raise ValueError(
                "sharded update does not take gradient compression "
                "kwargs: the gradient never leaves its owner, so there "
                "is nothing to compress on the pull leg except the "
                "parameter all-gather — use BYTEPS_SHARDED_PARAM_CODEC")
        if compression:
            # Declare/enqueue-time validation (ISSUE 11 satellite): a
            # typo'd codec name or decorator value fails HERE in the
            # caller's stack with the accepted spellings named — not as
            # a KeyError deep in the server engine on first use.
            compression_registry.validate_kwargs(compression)
            # a codec declared for a name takes its leaf out of a bucket
            self._tree_plans.clear()
        # Planner-chosen chunk size: for uncompressed tensors over the
        # base bound the auto-tuner explores, then locks, a partition
        # bytes per size bucket; an initialized tensor re-carves its
        # bounds only between pushes (inflight == 0).
        est_nbytes = self._est_nbytes(out_shape, dtype)
        plan_bytes = (self.cfg.partition_bytes if compression
                      else self.planner.plan_partition(est_nbytes))
        ctx = self.registry.init_tensor(
            name, out_shape, dtype, compression_kwargs=compression,
            partition_bytes=plan_bytes)
        # Claim the push (inflight++) ATOMICALLY with the repartition
        # decision: bounds may only move when no push holds a claim, and
        # every geometry read below (chunk_bounds, key_list,
        # scatter_layout) is stable only because this push already holds
        # one — a late claim would let a concurrent push re-carve the
        # bounds mid-read.
        # Compressor-ladder plan, computed BEFORE taking ctx.lock: the
        # first touch of a size bucket evaluates codec goldens (JAX
        # compiles), and the sync thread's _on_done takes ctx.lock —
        # holding it through a compile would stall every tensor's
        # retirement.  The benign race (another push applying a newer
        # plan first) is resolved under the lock below.
        want_tuned = None
        if (compression is None and self.planner.compress_active
                and ctx.compression_tuned is not False):
            want_tuned = self.planner.plan_compression(est_nbytes)
        with ctx.lock:
            if ctx.compression_tuned is None:
                # codec ownership decided once: explicit kwargs (this
                # push's, or an earlier declare's) pin the tensor; bare
                # tensors belong to the compressor ladder when it is on
                ctx.compression_tuned = (not compression
                                         and not ctx.compression_kwargs
                                         and self.planner.compress_active)
            elif compression and ctx.compression_tuned:
                # explicit kwargs RE-PIN a ladder-owned tensor: the
                # caller's codec wins over the planner's from now on
                # (silently keeping the planner's choice would ship a
                # different codec than the caller just named).  The pin
                # takes ownership NOW; the codec itself applies at
                # inflight == 0 — recorded on the ctx so a pin arriving
                # with pushes in flight lands at the next idle push
                # instead of being lost.
                ctx.compression_tuned = False
                ctx.compression_pin = dict(compression)
            if ctx.compression_pin is not None and ctx.inflight == 0:
                self.registry.retune_compression_locked(
                    ctx, ctx.compression_pin, self.cfg.partition_bytes)
                ctx.compression_pin = None
            if ctx.compression_tuned and ctx.inflight == 0:
                # compressor-ladder retune (ISSUE 11): the planner's
                # current codec for this size bucket, applied only
                # between pushes — the codec analog of repartitioning
                self.registry.retune_compression_locked(
                    ctx, want_tuned,
                    self.cfg.partition_bytes if want_tuned else plan_bytes)
            if (not ctx.compression_kwargs and ctx.inflight == 0
                    and ctx.partition_bytes != plan_bytes):
                self.registry.repartition_locked(ctx, plan_bytes)
            ctx.inflight += 1
            ctx.version += 1
            version = ctx.version
        try:
            if priority is None:
                prio = -ctx.declared_key if self.cfg.enable_priority else 0
            else:
                prio = priority
            handle = self.handles.allocate(name)
            if denom is None:
                denom = self.comm.num_ranks if op == "average" else 1
            self._ensure_compression(ctx, dtype)
            # Per-push planner sample: wall seconds enqueue -> completion,
            # discarded when a program compile landed inside the window.
            # Two dimensions share the window: chunk size (uncompressed
            # pushes, until the size bucket locks) and then — for
            # ladder-owned tensors — the compressor candidate.  Evaluated
            # AFTER _ensure_compression so the below-cutoff kwargs strip
            # is visible.  Zero overhead once both lock.
            eff_compressed = bool(ctx.compression_kwargs)
            track_plan = (not eff_compressed
                          and not self.planner.locked(est_nbytes))
            track_comp = (bool(ctx.compression_tuned)
                          and self.planner.locked(est_nbytes)
                          and not self.planner.compress_locked(est_nbytes))
            if track_plan or track_comp:
                t_plan0 = time.monotonic()
                miss0 = counters.get("engine.compile_cache_miss")
                part_used = ctx.partition_bytes
                codec_used = (ctx.compression_kwargs.get("compressor")
                              or "none") if eff_compressed else "none"
            if local and ctx.compressor is not None:
                # The tensor was declared WITH compression under this name by
                # an earlier push: compressed chunks need materialized per-rank
                # rows, so fall back to the broadcast-view stacked layout (the
                # caller's gate only sees its own kwargs, not registry state).
                stacked = np.broadcast_to(
                    np.asarray(stacked).reshape(-1)[None],
                    (self.comm.num_ranks, int(np.asarray(stacked).size)))
                local = False
            # Fused-scale fast path (float, uncompressed): the collective
            # applies 1/denom in-graph, so assembly needs no eager divide or
            # dtype restore — for small tensors those eager ops cost more than
            # the collective itself.  Ints and compressed chunks keep the
            # assembly-time division (exact // semantics / post-merge denom).
            scale = None
            if (denom != 1 and ctx.compressor is None
                    and jnp.issubdtype(np.dtype(dtype), jnp.inexact)):
                scale = 1.0 / denom
                denom = 1
            nchunks = len(ctx.chunk_bounds)
            # buffer mode? block-sharded output?  (_route_shape)
            use_buffer, shard_out = self._route(ctx, out_shape,
                                                replicate_out)
            pending = _PendingTensor(
                handle, ctx, out_shape, op, denom, use_buffer,
                comm=self.comm, scale=scale, shard_out=shard_out,
                slot=update_slot, bucket=bucket,
                unit_stops={off: off + w
                            for off, w in self._unit_layout(ctx)}
                if use_buffer else None)
            if self.tracer.active:
                # windowed AND/OR sampled capture decided here; tctx is
                # None for pushes that record nothing
                step, tctx = self.tracer.start_push(name)
            else:  # keep the hot enqueue path lock-free when tracing is off
                step, tctx = 0, None
            if self.cfg.telemetry_on:
                # per-step accounting: same per-tensor step definition as
                # the tracer, independent of the trace window (whose
                # step, when one is armed, the tasks carry)
                tstep = self.step_stats.on_push(
                    name, est_nbytes,
                    len(bucket.shapes) if bucket is not None else 0)
                step = step or tstep
            pending.trace = tctx
            local_mode = local
            if bucket is not None:
                if (not bucket.warmed and self._warm_bucket(
                        bucket, ctx, use_buffer, scale, op)):
                    # the bucket's first push compiled its programs in
                    # here: as the dispatcher's unit that crosses a
                    # cache miss, this enqueue feeds "compile" and its
                    # span says so
                    ph_enq.feed = self.phase_feeds["compile"]
                    ph_enq.note(compiled=1)
                # one program: flat, stacked-sharded and padded to the
                # scatter layout, in place of the staging below for
                # every leaf
                flat = bucket.pack(self.comm, stacked)
            elif local:
                if use_buffer:
                    col_layout0, C0 = ctx.scatter_layout
                    n_pad0 = C0 * self.comm.n_ici
                    # Sharded staging only for SINGLE-chunk tensors (the
                    # planner's usual locked choice for tuned buckets):
                    # the chunk program's in-graph all-gather runs once,
                    # so gather + reduce-scatter is exactly an
                    # all-reduce's wire movement.  A multi-chunk tensor
                    # can dispatch as several runs, and EACH run's
                    # program would re-gather the whole flat tensor —
                    # replicated staging's one device fan-out is the
                    # cheaper wire plan there.
                    if self._sharded_staging_ok(col_layout0, C0):
                        # ONE n-byte host->device transfer; pad rides the
                        # same host memcpy, so no device pad program
                        # either.
                        flat = stage_local_sharded(self.comm, stacked, n_pad0)
                        local_mode = "sharded"
                if local_mode != "sharded":
                    # One n-byte host->device put + async on-device
                    # replication: replaces R host copies of the broadcast
                    # view (stage_local_replicated's docstring and the
                    # docs/performance.md "Host staging" table).
                    flat = stage_local_replicated(
                        self.comm, np.asarray(stacked).reshape(-1))
            else:
                flat = stacked.reshape(stacked.shape[0], -1)
                # Stage to the mesh once; chunk programs slice in-graph
                # (no per-chunk device_put / eager slice
                # materialization).  Since ISSUE 11 compressed chunks
                # ride the same staging: the fused quantized program
                # slices its chunk from the staged row, so the old
                # per-chunk host slice copies are gone.
                flat = _as_stacked(self.comm, flat)
            pending.local_mode = local_mode
            itemsize = np.dtype(dtype).itemsize
            if use_buffer:
                # Buffer-mode tasks are COLUMN slabs of the [n_ici, C] view
                # (offset/num in columns).  nbytes below is taken from
                # ctx.chunk_bounds (real element counts), so credit/telemetry
                # accounting excludes the tail chunk's alignment pad.
                col_layout, C = ctx.scatter_layout
                if local_mode != "sharded":
                    flat = pad_stacked(self.comm, flat, C * self.comm.n_ici)
                bounds = col_layout
            else:
                bounds = ctx.chunk_bounds
            # tasks enter the queue NOW: the queued span / queue
            # component start at the stamp that ends "enqueue", so the
            # two never double-count
            if ph_enq.ann is not None:
                ph_enq.note(step=step, tensor=name)
                if bucket is not None:
                    ph_enq.note(leaves=len(bucket.shapes))
            ph_enq.__exit__(None, None, None)
            t_enq = ph_enq.t1
            with _tracing.phase("bps.engine.submit",
                                self.phase_feeds["submit"]) as ph_sub:
                if ph_sub.ann is not None:
                    ph_sub.note(step=step, tensor=name)
                tasks = []
                for part_idx, (off, ln) in enumerate(bounds):
                    # uncompressed parts mode (debug-sample, odd shapes)
                    # needs the materialized chunk; buffer mode,
                    # single-chunk tensors, and COMPRESSED chunks (whose
                    # fused program slices in-graph from the staged row
                    # via offset_elems) pass the full flat
                    if (nchunks > 1 and not use_buffer
                            and ctx.compressor is None):
                        chunk = (flat[off:off + ln] if local
                                 else flat[:, off:off + ln])
                    else:
                        chunk = flat
                    tasks.append(ChunkTask(
                        name=name, key=ctx.key_list[part_idx],
                        priority=prio, version=version, offset_elems=off,
                        num_elems=ln,
                        nbytes=ctx.chunk_bounds[part_idx][1] * itemsize,
                        total_parts=nchunks,
                        data=chunk,
                        compression=(ctx.compressor[part_idx]
                                     if ctx.compressor else None),
                        scale=scale,
                        pending=pending,
                        step=step, t_enqueue=t_enq,
                        trace_id=tctx.trace_id if tctx is not None else 0,
                        # a buffer-mode unit retires in one pass
                        # (_finish_batch), not chunk by chunk
                        callback=None if use_buffer else
                        self._make_chunk_callback(pending, part_idx),
                    ))
                # in ONE step: the dispatcher never sees half a tensor,
                # so which units form does not depend on timing
                self.scheduler.add_tasks(tasks)
            # Auto-release on completion: the manager tracks only outstanding
            # work, so direct handle.wait() users don't leak table entries.
            # The same hook closes the planner's measurement window and frees
            # the tensor for repartitioning (inflight bookkeeping).
            def _on_done(h):
                with ctx.lock:
                    ctx.inflight -= 1
                if track_comp and h.status.code == StatusCode.OK:
                    # compressor-ladder sample: this push's wall time,
                    # charged to the codec it actually ran under
                    self.planner.observe_compression(
                        est_nbytes, codec_used,
                        time.monotonic() - t_plan0,
                        compiled=counters.get("engine.compile_cache_miss")
                        != miss0)
                if track_plan and h.status.code == StatusCode.OK:
                    self.planner.observe(
                        est_nbytes, part_used,
                        time.monotonic() - t_plan0,
                        compiled=counters.get("engine.compile_cache_miss")
                        != miss0)
                    if self.planner.locked(est_nbytes) and self.tracer.active:
                        # lock transition (track_plan implies it was unlocked
                        # at enqueue): the moment exploration ended, with the
                        # winning chunk size, visible in the timeline
                        t_now = time.monotonic()
                        self.tracer.record_span(
                            "engine.planner_locked", t_now, t_now,
                            tensor=name,
                            partition_bytes=self.planner.plan_partition(
                                est_nbytes))
                    self._apply_planned_credit()
                self.handles.release(h.id)

            handle.add_done_callback(_on_done)
            return handle
        except BaseException:
            # enqueue failed before the done-hook could own the
            # claim: release it or the tensor can never
            # repartition again
            with ctx.lock:
                ctx.inflight -= 1
            raise

    # ------------------------------------------------------- tree entry
    def push_pull_tree_async(self, leaves, names,
                             op: str = "average") -> TreeHandle:
        """Enqueue the leaves of one rank-stacked tree (flattening order,
        one name each): consecutive plain float leaves ride BUCKETS --
        one engine tensor, one pack and one unpack program for the run
        -- and every other leaf goes through :meth:`push_pull_async` by
        itself.  ``TreeHandle.wait()`` gives the reduced leaves in order.

        Chosen by what the input shows, per leaf (:meth:`_plan_tree`).
        A bucket is a tensor: its name is stable (the first leaf's name
        and the count of the rest), so declaration order -- priority --
        partitioning, credit, step accounting, deadline and epoch guards
        see it as they see any other."""
        names = tuple(names)
        sig = (names, op, tuple((leaf.shape, leaf.dtype) for leaf in leaves))
        plan = self._tree_plans.get(sig)
        if plan is None:
            plan = self._tree_plans[sig] = self._plan_tree(leaves, names, op)
        items, index = plan
        handles = []
        for start, stop, bucket in items:
            if bucket is None:
                handles.append(self.push_pull_async(
                    leaves[start], names[start], op=op))
            else:
                handles.append(self.push_pull_async(
                    leaves[start:stop], bucket.name, op=op, bucket=bucket))
        return TreeHandle(handles, index)

    def _plan_tree(self, leaves, names, op: str = "average") -> tuple:
        """The bucket plan of one tree signature: ``(items, index)``,
        ``items`` the pushes to make in order -- ``(start, stop,
        bucket)``, ``bucket`` None for a leaf that goes alone -- and
        ``index`` each leaf's place in their results (TreeHandle).

        A leaf rides a bucket unless the per-tensor path has something
        only it can give the leaf: exact ``//`` (a dtype that is not
        inexact), a codec (declared for the name, or the compressor
        ladder, which owns bare tensors by size), per-chunk debug
        sampling; or unless it has nothing to pack (no elements, a rank
        axis the per-tensor path will refuse by name) or is at or over
        the cap.  A run of one is that leaf.  Nothing here reads a
        clock: the plan is a pure function of the signature and the
        configuration.

        Making the plan declares its tensors.  Every push it will make
        reserves its registry key here, in order, so that priority
        (``-declared_key``) follows flattening order across buckets and
        lone leaves.  A bucket compiles its programs at its first push
        (_warm_bucket), and a leaf that goes alone for its size or dtype
        is declared here with its geometry (declare_tensor: one program
        per dispatch unit), so the step after the plan compiles
        nothing."""
        comm, cfg = self.comm, self.cfg
        R = comm.num_ranks
        per_leaf = self.planner.compress_active or cfg.debug_sample_tensor
        sigs, sizes, plain = [], [], []
        for leaf, name in zip(leaves, names):
            shape, dtype = tuple(leaf.shape), np.dtype(leaf.dtype)
            nbytes = self._est_nbytes(shape[1:], dtype) if shape else 0
            ctx = self.registry.get(name)
            # nothing but size or dtype can keep a plain leaf out
            plain.append(bool(nbytes) and shape[0] == R and not (
                per_leaf or (ctx is not None and (ctx.compression_kwargs
                                                  or ctx.compressor))))
            sigs.append((shape[1:], dtype.name) if plain[-1]
                        and jnp.issubdtype(dtype, jnp.inexact) else None)
            sizes.append(nbytes)
        runs = {start: stop for start, stop in bucket_bounds(
            sigs, sizes, BUCKET_CAP_PARTITIONS * cfg.partition_bytes)
            if stop - start > 1}
        items, index, i = [], [], 0
        while i < len(sigs):
            stop = runs.get(i)
            if stop is None:
                index.append((len(items), None))
                items.append((i, i + 1, None))
                i += 1
                continue
            shapes = tuple(sig[0] for sig in sigs[i:stop])
            bucket = _Bucket(
                f"{names[i]}+{stop - i - 1}", shapes, np.dtype(sigs[i][1]),
                comm.n_ici,
                tuple(self._leaf_shard_out(s, sigs[i][1]) for s in shapes),
                tuple(comm.stacked_sharding(extra_dims=len(s))
                      for s in shapes))
            index.extend((len(items), j) for j in range(stop - i))
            items.append((i, stop, bucket))
            i = stop
        # keys -- priority -- in item order, which is flattening order:
        # reserved for buckets and lone leaves alike before anything is
        # declared with its geometry, or every leaf that goes alone
        # would outrank the buckets around it
        for start, _, bucket in items:
            self.registry.declare(names[start] if bucket is None
                                  else bucket.name)
        for start, _, bucket in items:
            if bucket is None and plain[start]:
                self.declare_tensor(names[start], leaves[start].shape[1:],
                                    leaves[start].dtype, op=op, local=False)
        return items, index

    def _leaf_shard_out(self, shape, dtype_name: str) -> bool:
        """Would this leaf, pushed by itself, come back block-sharded
        (deferred gather)?  A bucket gives each leaf the layout its own
        assembly gives it, so the caller's jitted update sees the
        argument layouts it was compiled for: the per-tensor routing
        (:meth:`_route_shape`), asked at the configured partition size
        -- like the cap, not at the planner's tuned one, which moves
        with timing while it explores."""
        itemsize = np.dtype(dtype_name).itemsize
        n = int(np.prod(shape))
        return self._route_shape(
            shape, n * itemsize,
            chunk_bounds(n, itemsize, self.cfg.partition_bytes))[1]

    def _warm_bucket(self, bucket: _Bucket, ctx: TensorContext,
                     use_buffer: bool, scale, op: str) -> int:
        """A bucket's first push declares it: compile its pack and
        unpack programs and, as declare_tensor does for a declared
        tensor, the program of each of its dispatch units -- for a
        bucket, one (single process only: SPMD processes compile
        lazily, in lockstep).  Returns how many programs it compiled."""
        bucket.warmed = True
        if jax.process_count() > 1:
            return 0
        t0 = time.monotonic()
        n_compiled = 0
        try:
            n_compiled = aot_warm_bucket_programs(
                self.comm, shapes=bucket.shapes,
                dtype_name=bucket.dtype.name, n_pad=bucket.n_pad,
                shard_out=bucket.shard_out, buffered=use_buffer,
                scale_value=scale)
            if use_buffer:
                n_compiled += self._aot_warm(
                    ctx, bucket.dtype, op=op, local=False, assembled=False)
            if n_compiled and self.tracer.active:
                # as declare_tensor: the stall in the timeline where it
                # was paid (inside this push's "enqueue" span, which
                # then feeds "compile")
                self.tracer.record_span(
                    "engine.aot_warm", t0, time.monotonic(),
                    tensor=bucket.name, programs=n_compiled)
        except Exception as e:  # noqa: BLE001 — lazy jit is the fallback
            self._aot_warm_failed("bucket", bucket.name, e)
        return n_compiled

    @staticmethod
    def _est_nbytes(shape, dtype) -> int:
        """Logical payload bytes of one tensor (planner bucket key);
        shared by push_pull_async and declare_tensor so the bucket a
        tensor warms under is the bucket its pushes are tracked in."""
        shape = tuple(shape)
        return ((int(np.prod(shape)) if shape else 1)
                * np.dtype(dtype).itemsize)

    def _route_shape(self, shape, nbytes: int, bounds, layout=None,
                     replicate_out: bool = False):
        """How one uncompressed tensor of ``shape`` / ``nbytes``, carved
        into ``bounds``, travels: ``(use_buffer, shard_out, layout)``.
        The one copy of the routing policy -- push_pull_async, the AOT
        warm and the bucket plan (a leaf's output layout) all ask here.

        Buffer mode (the hot path): multi-chunk tensors -- and large
        single-chunk ones (>= buffer_min_bytes, e.g. after the planner
        locked chunk=whole) -- ride the fused slice -> reduce-scatter ->
        sharded-accumulator chunk programs; each dispatch consumes the
        previous accumulator by donation, and one assemble program
        scales/reshapes in a single order-identical pass.  Debug sampling
        needs per-chunk outputs, so it forces parts mode; so do chunk
        bounds the column layout can't express (non-power-of-2 meshes):
        ``layout`` is the tensor's cached ``scatter_layout`` (None: not
        computed yet; "ineligible": computed and rejected, so the check
        runs once per tensor, not once per call).

        Deferred-gather assembly (``shard_out``): the result stays
        block-sharded over the mesh when the output shape admits it --
        XLA materializes the all-gather only where a consumer needs
        replicated values, and mesh-aligned tensors assemble with zero
        cross-device movement.  ``replicate_out``: callers that will
        immediately read the full result on host (the torch/TF adapters'
        np.asarray) opt OUT -- eager assembly then runs the gather on the
        syncer thread, pipelined with other transport, instead of
        serializing it into the caller's wait."""
        use_buffer = (not self.cfg.debug_sample_tensor
                      and (len(bounds) > 1
                           or nbytes >= self.cfg.buffer_min_bytes))
        if use_buffer and layout is None:
            layout = scatter_layout(bounds, self.comm.n_ici) or "ineligible"
        use_buffer = use_buffer and layout != "ineligible"
        shard_out = (use_buffer and self.cfg.deferred_gather
                     and not replicate_out
                     and assemble_shardable(self.comm, shape))
        return use_buffer, shard_out, layout

    def _route(self, ctx: TensorContext, out_shape,
               replicate_out: bool = False):
        """``(use_buffer, shard_out)`` of a registered tensor at its
        current chunk bounds (:meth:`_route_shape`); compressed chunks
        ride parts mode.  Caches the scatter layout on the context."""
        if ctx.compressor is not None:
            return False, False
        use_buffer, shard_out, layout = self._route_shape(
            out_shape, ctx.nbytes, ctx.chunk_bounds, ctx.scatter_layout,
            replicate_out)
        if layout is not None and ctx.scatter_layout is None:
            with ctx.lock:
                if ctx.scatter_layout is None:
                    ctx.scatter_layout = layout
        return use_buffer, shard_out

    def _unit_layout(self, ctx: TensorContext) -> List[tuple]:
        """The dispatch units of a buffer-mode tensor, as column ranges
        ``[(col_off, width)]`` of its scatter layout: THE rule for what
        the dispatcher launches as one program, asked by the push (the
        pop takes a unit's chunks together: ``_PendingTensor.
        unit_stops``) and by the declare-time warm (one program per
        distinct unit), so the two cannot differ.

        A unit is a run of consecutive chunks (``unit_bounds``) holding
        at most what a bucket holds, ``BUCKET_CAP_PARTITIONS x
        partition_bytes``, and no more than the credit window where one
        is set: a unit larger than the window could only ever be popped
        in part.  Where the window still cuts a pop short (bytes of
        other tensors in flight), or two pushes of one tensor interleave
        in the queue, what was popped of the unit is launched chunk by
        chunk (``_plan_batch``): a cut always lands on a unit's or a
        chunk's width, and under a window the warm compiles both.  A
        mesh of several processes keeps one chunk a unit."""
        col_layout, _ = ctx.scatter_layout
        cap = 0
        if not self._one_chunk_units:
            cap = BUCKET_CAP_PARTITIONS * self.cfg.partition_bytes
            cap = min(cap, self.scheduler.credit_bytes or cap)
        itemsize = np.dtype(ctx.dtype_name).itemsize
        return [(col_layout[a][0],
                 sum(w for _, w in col_layout[a:b]))
                for a, b in unit_bounds(
                    [ln * itemsize for _, ln in ctx.chunk_bounds], cap)]

    def _sharded_staging_ok(self, col_layout, C: int) -> bool:
        """Sharded local staging is worth it only for SINGLE-run
        layouts (each dispatched run re-gathers the whole flat tensor
        in-graph) and possible only when the padded length divides the
        ranks (the mesh cannot hold an uneven 1-D block sharding).
        Shared by dispatch and AOT warm: a drifted copy would warm
        staging variants the push path never dispatches."""
        return (len(col_layout) == 1
                and (C * self.comm.n_ici) % self.comm.num_ranks == 0)

    def _apply_planned_credit(self) -> None:
        """Install the planner's tuned credit window on the scheduler
        (no-op until a bucket locks, or when the window is pinned).
        Both scheduler backends implement the full interrupt/wake/credit
        interface — the dispatch loop already assumes it, so no partial
        scheduler can run this engine anyway."""
        credit = self.planner.credit_bytes()
        if credit and self.scheduler.credit_bytes != credit:
            self.scheduler.set_credit_bytes(credit)
            gauges.set("engine.credit_bytes", credit)

    @staticmethod
    def _ef_error_leaves(state):
        """Every "error" leaf in a (possibly decorator-nested) compressor
        state dict — the error-feedback residual accumulators."""
        out = []
        if isinstance(state, dict):
            for k, v in state.items():
                if k == "error":
                    out.append(v)
                else:
                    out.extend(PushPullEngine._ef_error_leaves(v))
        return out

    def refresh_compression_gauges(self) -> None:
        """Scrape-time compression gauges (ISSUE 11 observability): per
        compressed tensor, the codec it currently carries
        (``compression.active{tensor=,codec=}``) and the error-feedback
        residual L2 norm (``compression.ef_norm{tensor=}`` — a norm that
        grows without bound means the codec is not keeping up with the
        gradient).  Reads device state, so it runs at scrape time
        (/metrics refresh, /debug/state), never on the push hot path.

        ``_comp_gauge_codecs`` remembers what this method last published
        per tensor: the registry has no series removal, so a ladder
        retune's RETIRED codec series is zeroed — a stale 1.0 would keep
        the old codec in the bps_top CODEC column forever."""
        for name in self.registry.names_in_declaration_order():
            ctx = self.registry.get(name)
            # snapshot once: a concurrent ladder retune can null
            # ctx.compressor between a check and the loop
            slots = ctx.compressor if ctx is not None else None
            prev = self._comp_gauge_codecs.get(name)
            if not slots:
                if prev is not None:
                    gauges.set("compression.active", 0.0, tensor=name,
                               codec=prev)
                    del self._comp_gauge_codecs[name]
                continue
            codec = ctx.compression_kwargs.get("compressor", "?")
            if prev is not None and prev != codec:
                gauges.set("compression.active", 0.0, tensor=name,
                           codec=prev)
            self._comp_gauge_codecs[name] = codec
            gauges.set("compression.active", 1.0, tensor=name,
                       codec=codec)
            norm_sq, found = 0.0, False
            for slot in slots:
                for err in self._ef_error_leaves(slot.wstates):
                    found = True
                    norm_sq += float(jnp.sum(jnp.square(
                        jnp.asarray(err, jnp.float32))))
            if found:
                gauges.set("compression.ef_norm", norm_sq ** 0.5,
                           tensor=name)

    def declare_tensor(self, name: str, shape, dtype=np.float32, *,
                       op: str = "average", local: Optional[bool] = None,
                       compression: Optional[Dict[str, str]] = None,
                       replicate_out: bool = False) -> TensorContext:
        """Declare a tensor WITH geometry and AOT-compile its steady-state
        program set (tentpole part 1: persistent compiled chunk programs).

        ``bps.declare(name)`` only reserves the key; given shape/dtype the
        engine can additionally pre-lower and compile every program the
        tensor's pushes will dispatch — one chunk-scatter executable per
        dispatch unit (_unit_layout), the pad and assembly
        programs, the single-chunk collective — and pre-stage the device
        scalars, so the first push_pull runs at steady-state speed and a
        declared stream compiles nothing afterwards.

        ``local``: compile for the single-process local-contribution
        staging (push_pull_local; the default when this process is the
        whole world) or the rank-stacked layout.  Compressed tensors and
        multi-process meshes skip the warm (per-chunk compressor state /
        SPMD lockstep) — they compile lazily exactly as before.
        """
        shape = tuple(shape)
        np_dtype = np.dtype(dtype)
        if compression:
            # a bad codec/decorator/param fails at declare, in the
            # caller's stack (ISSUE 11 satellite)
            compression_registry.validate_kwargs(compression)
            self._tree_plans.clear()    # as in push_pull_async
        est_nbytes = self._est_nbytes(shape, np_dtype)
        plan_bytes = (self.cfg.partition_bytes if compression
                      else self.planner.plan_partition(est_nbytes))
        ctx = self.registry.init_tensor(name, shape, np_dtype,
                                        compression_kwargs=compression,
                                        partition_bytes=plan_bytes)
        with ctx.lock:
            if ctx.compression_tuned is None:
                ctx.compression_tuned = (not compression
                                         and not ctx.compression_kwargs
                                         and self.planner.compress_active)
        if jax.process_count() > 1 or self.cfg.debug_sample_tensor:
            return ctx
        if compression or ctx.compression_kwargs:
            # ISSUE 11 tentpole: a compressed tensor pre-lowers and
            # compiles its whole steady-state program family at declare
            # time too — in-graph chunk slice, quantize, quantized
            # gather, Pallas-fused dequant-accumulate, merged
            # re-quantize, error-feedback state update — one program per
            # chunk codec, so the compressed stream also compiles zero
            # programs after warmup and the first push pays no stall.
            self._ensure_compression(ctx, np_dtype)
            if not ctx.compressor:
                return ctx          # below the compression size cutoff
            t0 = time.monotonic()
            try:
                n_compiled = aot_warm_compressed_programs(
                    self.comm, n_flat=ctx.num_elems,
                    dtype_name=ctx.dtype_name,
                    chunk_bounds=ctx.chunk_bounds, slots=ctx.compressor)
                if n_compiled:
                    get_logger().debug(
                        "AOT-compiled %d compressed program(s) for %s",
                        n_compiled, name)
                    if self.tracer.active:
                        self.tracer.record_span(
                            "engine.aot_warm", t0, time.monotonic(),
                            tensor=name, programs=n_compiled)
            except Exception as e:  # noqa: BLE001 — lazy jit is the fallback
                self._aot_warm_failed("compressed", name, e)
            return ctx
        if local is None:
            local = jax.process_count() == 1
        t0 = time.monotonic()
        try:
            n_compiled = self._aot_warm(ctx, np_dtype, op=op, local=local,
                                        replicate_out=replicate_out)
            if n_compiled:
                get_logger().debug("AOT-compiled %d program(s) for %s",
                                   n_compiled, name)
                if self.tracer.active:
                    # compile stalls belong in the timeline at declare
                    # time, where they were paid — not smeared over the
                    # first push's span
                    self.tracer.record_span(
                        "engine.aot_warm", t0, time.monotonic(),
                        tensor=name, programs=n_compiled)
        except Exception as e:  # noqa: BLE001 — lazy jit is the fallback
            self._aot_warm_failed("chunk-program", name, e)
        return ctx

    def declare_update(self, name: str, shape, dtype=np.float32, *,
                       tx, init_value=None,
                       restore=None) -> TensorContext:
        """Declare a tensor whose pull leg is the fused sharded weight
        update (ISSUE 20): registers geometry like declare_tensor, then
        builds the owner-resident slot — flat f32 master (seeded from
        ``init_value``, the caller's initial parameters), flat-shard
        optimizer state for ``tx`` — and AOT-warms the fused update
        program alongside the chunk programs, so the first
        push_pull_update dispatches compiled executables only.

        ``restore``: a ShardedUpdateSlot.export() snapshot (elastic
        resume); re-padded to THIS mesh's shard geometry, which is how
        an elastic shrink re-shards optimizer state.
        """
        if not self.cfg.sharded_update:
            raise ValueError(
                "declare_update requires sharded-update mode: set "
                "BYTEPS_SHARDED_UPDATE=1 or Config(sharded_update=True)")
        if jax.process_count() > 1:
            raise ValueError(
                "sharded update is single-controller only for now: the "
                "owner-resident master/optimizer state is device_put "
                "over the whole mesh, which a multi-process SPMD "
                "controller cannot address")
        np_dtype = np.dtype(dtype)
        if not jnp.issubdtype(np_dtype, jnp.inexact):
            raise ValueError(
                f"sharded update needs a float tensor (the optimizer "
                f"runs on the shard), got dtype {np_dtype}")
        ctx = self.declare_tensor(name, shape, np_dtype, op="average",
                                  local=True)
        with ctx.lock:
            # pin the gradient-compressor ladder OFF for this tensor:
            # compressed chunks ride parts mode, which would defeat the
            # owner-resident shard (and the pull-leg codec is a
            # different knob — sharded_param_codec)
            ctx.compression_tuned = False
        slot = ShardedUpdateSlot(
            self.comm, self.cfg, name, shape, np_dtype, tx,
            planner=self.planner, init_value=init_value, restore=restore)
        self.update_slots[name] = slot
        try:
            # mirror _aot_warm's denominator model for the local push
            # this slot's pushes will dispatch: float + denom=R rides
            # the fused-scale fast path (scaled=True)
            buffered = self._route(ctx, ctx.shape)[0]
            # buffer mode applies the fused 1/R scale inside the update
            # program; parts fallback receives the already-averaged
            # merged gradient (apply_full), so no scale arg there
            n = slot.warm(buffered=buffered, scaled=buffered, denom=1)
            if n:
                get_logger().debug(
                    "AOT-compiled sharded-update program for %s", name)
        except Exception as e:  # noqa: BLE001 — lazy jit is the fallback
            self._aot_warm_failed("sharded-update", name, e)
        return ctx

    @staticmethod
    def _aot_warm_failed(what: str, name: str, exc: Exception) -> None:
        """A declare-time warm that raised: the programs still compile
        lazily at first dispatch, but a refusal by the compiler must be
        seen — counted, and logged with the compiler's message."""
        counters.inc("engine.aot_compile_failed")
        get_logger().warning(
            "%s AOT warm failed for %s; programs compile lazily: %s: %s",
            what, name, type(exc).__name__, exc)

    def push_pull_update_async(self, x, name: str, *,
                               stacked: bool = False, **kw) -> Handle:
        """Contribute this process's gradient for ``name`` and receive
        the OWNER-COMPUTED optax updates tensor (block-sharded under
        deferred gather): ``optax.apply_updates(params, h.wait())`` is
        the sharded-update step.  Requires a prior declare_update.

        ``stacked=True``: ``x`` carries a leading rank axis (the
        DistributedOptimizer data model) and rides the stacked chunk
        collectives — the same programs the unsharded adapter path
        dispatches, so the merged gradient the slot integrates is
        bitwise the one the unsharded caller would have received."""
        slot = self.update_slots.get(name)
        if slot is None:
            raise ValueError(
                f"{name!r} has no sharded-update slot: call "
                f"declare_update(name, shape, dtype, tx=...) first")
        kw.setdefault("op", "average")
        if stacked:
            return self.push_pull_async(x, name, update_slot=slot, **kw)
        return self.push_pull_local_async(x, name, update_slot=slot, **kw)

    def push_pull_update(self, x, name: str, **kw):
        h = self.push_pull_update_async(x, name, **kw)
        out = h.wait()
        self.handles.release(h.id)
        return out

    def export_update_slots(self) -> Dict[str, dict]:
        """Host-side snapshots of every sharded-update slot (elastic
        suspend): logical-length state, re-importable on any world size
        via declare_update(restore=...)."""
        return {name: slot.export()
                for name, slot in self.update_slots.items()}

    def _aot_warm(self, ctx: TensorContext, np_dtype, *, op: str,
                  local: bool, replicate_out: bool = False,
                  assembled: bool = True) -> int:
        """Compile the program set for one uncompressed tensor's pushes.

        The denominator/scale model MUST mirror what push_pull will
        actually dispatch, or the warmed keys are dead weight: a LOCAL
        push divides out the local-replica over-count even for op="sum"
        (push_pull_local_async's denom), and any float denom != 1 rides
        the fused-scale fast path (scaled=True, denom folded to 1)."""
        R = self.comm.num_ranks
        inexact = jnp.issubdtype(np_dtype, jnp.inexact)
        if local:
            # single-process warm path (multi-process skips the warm):
            # local_size == num_ranks, over-counted for sum AND average
            base_denom = R
        else:
            base_denom = R if op == "average" else 1
        scaled = inexact and base_denom != 1
        scale_value = (1.0 / base_denom) if scaled else None
        denom = 1 if scaled else base_denom
        nchunks = len(ctx.chunk_bounds)
        use_buffer, shard_out = self._route(ctx, ctx.shape, replicate_out)
        if use_buffer:
            col_layout, C = ctx.scatter_layout
            # Warm the staging variant push_pull will dispatch: a
            # SINGLE-chunk local contribution whose padded length divides
            # the ranks rides the sharded staging (one n-byte transfer +
            # one in-graph gather), otherwise the replicated fan-out
            # (mirrors the staging decision in push_pull_async).
            local_eff = local
            if local and self._sharded_staging_ok(col_layout, C):
                local_eff = "sharded"
            # what the dispatcher can launch: the tensor's units and,
            # where a credit window can cut a pop short, its chunks
            units = self._unit_layout(ctx)
            if self.scheduler.credit_bytes:
                units = units + list(col_layout)
            return aot_warm_buffer_programs(
                self.comm, col_layout=col_layout, C=C, n=ctx.num_elems,
                out_shape=ctx.shape, dtype_name=ctx.dtype_name,
                local=local_eff, scaled=scaled, denom=denom,
                shard_out=shard_out,
                scale_value=scale_value, units=units,
                assembled=assembled)
        if nchunks == 1:
            return aot_warm_single_program(
                self.comm, n=ctx.num_elems, dtype_name=ctx.dtype_name,
                scaled=scaled, local=local, scale_value=scale_value)
        return 0

    def _ensure_compression(self, ctx: TensorContext, dtype) -> None:
        """Instantiate the per-chunk compressor chain on first use.

        Reference parity: one compressor per partition
        (BPSContext.compressor_list), instantiated at InitTensor when the
        tensor passes the BYTEPS_MIN_COMPRESS_BYTES cutoff
        (operations.cc:362-364).  Worker chain carries momentum+EF; the
        server chain (re-compression of the merged sum) never has momentum
        (compressor_registry.cc:39-56).
        """
        with ctx.lock:
            if ctx.compressor is not None or not ctx.compression_kwargs:
                return
            if ctx.nbytes < self.cfg.min_compress_bytes:
                ctx.compression_kwargs = {}
                return
            r = self.comm.num_ranks
            slots = []
            for off, ln in ctx.chunk_bounds:
                wc = compression_registry.create(
                    ctx.compression_kwargs, ln, dtype)
                sc = compression_registry.create(
                    ctx.compression_kwargs, ln, dtype, for_server=True)
                # State leaves are COMMITTED to the exact shardings the
                # fused program's in_specs declare (rank-stacked worker,
                # replicated server).  An uncommitted default-device
                # array would be re-sharded by every pjit call, and the
                # declare-time AOT executable — lowered against these
                # shardings — could not be called at all.  The shardings
                # come from the SAME state_structs the AOT warm lowers
                # against, so the two cannot drift.
                wstate = jax.tree.map(
                    lambda s: jnp.broadcast_to(
                        jnp.asarray(s)[None],
                        (r,) + jnp.asarray(s).shape),
                    wc.init_state())
                sstate = jax.tree.map(jnp.asarray, sc.init_state())
                from ..comm.compressed import state_structs
                w_structs, s_structs = state_structs(self.comm, wstate,
                                                     sstate)
                w_leaves, wdef = jax.tree.flatten(wstate)
                s_leaves, sdef = jax.tree.flatten(sstate)
                wstate = jax.tree.unflatten(
                    wdef, [jax.device_put(lf, st.sharding)
                           for lf, st in zip(w_leaves, w_structs)])
                sstate = jax.tree.unflatten(
                    sdef, [jax.device_put(lf, st.sharding)
                           for lf, st in zip(s_leaves, s_structs)])
                slots.append(_CompressionSlot(wc, sc, wstate, sstate))
            ctx.compressor = slots

    def _make_chunk_callback(self, pending: _PendingTensor, part_idx: int):
        def cb(data, status: Status):
            if status.code.name != "OK":
                pending.handle.set_result(None, status)
            elif pending.complete_part(part_idx, data):
                self._resolve(pending)
        return cb

    def _complete_run(self, pending: _PendingTensor, chunks: int,
                      err) -> None:
        """A buffer-mode dispatch unit of ``chunks`` chunks retired."""
        if err is not None:
            pending.handle.set_result(None, self._chunk_status(err))
        elif pending.complete_run(chunks):
            self._resolve(pending)

    @staticmethod
    def _resolve(pending: _PendingTensor) -> None:
        """Every chunk of the push has landed: assemble, resolve."""
        try:
            pending.handle.set_result(pending.assemble(), Status.ok())
        except Exception as e:  # noqa: BLE001
            pending.handle.set_result(None, Status.error(str(e)))

    def _debug_sample(self, task, out) -> None:
        """Stage-wise tensor sampling (reference BYTEPS_DEBUG_SAMPLE_TENSOR,
        core_loops.cc:37-67): when the configured substring matches the
        tensor name, log input/output summaries of the chunk's reduction —
        the grep-able breadcrumb for divergence hunting.  Called from the
        sync loop, after the collective completed: the host fetch here
        cannot stall dispatch pipelining."""
        pat = self.cfg.debug_sample_tensor
        if not pat or pat not in task.name:
            return
        try:
            i = np.asarray(task.data[0]).astype(np.float64)
            o = np.asarray(out).astype(np.float64)
            get_logger().warning(
                "sample %s key=%d off=%d in[sum=%.6g abs=%.6g first=%.6g] "
                "out[sum=%.6g abs=%.6g first=%.6g]",
                task.name, task.key, task.offset_elems,
                i.sum(), np.abs(i).sum(), i.flat[0],
                o.sum(), np.abs(o).sum(), o.flat[0])
        except Exception:  # noqa: BLE001 — sampling must never kill a loop
            # a dead sampler must be discoverable (e.g. non-addressable
            # shards under multi-host): say why once per failure
            get_logger().debug("debug sample for %s failed", task.name,
                               exc_info=True)

    def pause_dispatch(self, timeout: float = 10.0):
        """Hold the dispatcher: tasks enqueue but nothing pops until
        :meth:`resume_dispatch`.  Used where what a pop finds must be
        deterministic across TENSORS (the cross-tensor merge of
        parts-mode chunks, priority order between pushes: tests, the
        multichip dry-run) — one tensor's own units never were a race
        since its chunks enter the queue together.  Event handshake, not
        a timed sleep: the gate is cleared, a blocked pop is interrupted
        (one-shot scheduler wakeup), and this call returns only once the
        dispatcher has parked — any pop already in flight finishes its
        dispatch first, so after return nothing pops until resume."""
        self._dispatch_enabled.clear()
        self.scheduler.interrupt()
        if not self._parked.wait(timeout=timeout) and self._running:
            get_logger().warning(
                "pause_dispatch: dispatcher did not park within %.1fs",
                timeout)

    def resume_dispatch(self):
        self._dispatch_enabled.set()

    # ---------------------------------------------------------- loops
    def _dispatch_loop(self):
        self.step_stats.register_thread("dispatcher")
        while self._running:
            if not self._dispatch_enabled.is_set():
                # parked: zero-CPU wait on the resume event (the old
                # design re-woke every poll quantum to re-check flags)
                self._parked.set()
                self._dispatch_enabled.wait()
                self._parked.clear()
                continue
            # Wakeup-driven blocking pop: returns when a task is
            # eligible, or None when interrupted (pause handshake) /
            # woken (shutdown) — the idle dispatcher burns no CPU.
            task = self.scheduler.get_task(block=True)
            if task is None:
                continue
            # "plan": the pop returned -> the batch is carved into units
            # (the blocking wait for a task above is idleness, not work)
            feeds = self.phase_feeds
            with _tracing.phase("bps.engine.plan", feeds["plan"]) as ph:
                if ph.ann is not None:
                    ph.note(step=task.step)
                units = self._pop_and_plan(task)
            for kind, unit in units:
                if self.cfg.telemetry_on:
                    histograms.observe("engine.dispatch_unit_width",
                                       len(unit))
                # every compile-cache miss adds one program to the mesh's
                # cache (collectives._cached): its size is the miss
                # counter without the counter's lock, twice per unit
                programs = len(self.comm.jit_cache)
                with _tracing.phase("bps.engine.dispatch",
                                    feeds["dispatch"]) as ph:
                    if ph.ann is not None:
                        ph.note(step=unit[0].step, tensor=unit[0].name,
                                width=len(unit),
                                bytes=sum(t.nbytes for t in unit))
                        bucket = unit[0].pending.bucket
                        if bucket is not None:
                            ph.note(leaves=len(bucket.shapes))
                    # the phase's opening stamp is the unit's dispatch
                    # time (end of its chunks' queue wait)
                    if kind == "run":
                        self._dispatch_buffer_run(unit, ph.t0)
                    elif kind == "group":
                        self._dispatch_parts_group(unit, ph.t0)
                    else:
                        self._dispatch_single(unit[0], ph.t0)
                    if len(self.comm.jit_cache) != programs:
                        # jit compiles are synchronous inside the
                        # dispatch call (execution is async), so a unit
                        # that crossed a cache miss spent its wall
                        # compiling: it feeds "compile", not "dispatch",
                        # and its span says so (a TraceMe cannot be
                        # renamed once open) — both are real
                        # critical-path segments
                        ph.feed = feeds["compile"]
                        ph.note(compiled=1)
            # let go of the dispatched chunks before parking in the pop:
            # they hold their tensor's staged array (a bucket's 50 MB
            # packed tensor), which must not outlive its retirement
            # until the next step's first task arrives
            task = units = unit = None

    def _pop_and_plan(self, task: ChunkTask):
        """The dispatcher's work between a successful pop and its first
        program launch: gather the batch, drop stale chunks, carve
        dispatch units (``[]`` when every chunk was stale)."""
        if _fault.ENABLED:
            # chaos site "dispatch": delay/straggler stalls issue order
            _fault.fire("dispatch")
        batch = _pop_batch(self.scheduler, task, self._group_size)
        # Membership-epoch guard: chunks enqueued before a world
        # change (elastic shrink/rejoin, fault/membership.py) must
        # not be issued into a mesh that no longer exists — they are
        # dropped here with an ABORTED status so waiters unblock and
        # the caller re-pushes under the new epoch.
        ep = _membership.current_epoch()
        if any(t.pending is not None and t.pending.mepoch != ep
               for t in batch):
            fresh = []
            for t in batch:
                if t.pending is not None and t.pending.mepoch != ep:
                    counters.inc("membership.stale_chunks_dropped")
                    _flight.record("engine.stale_chunk", tensor=t.name,
                                   key=t.key, enq_epoch=t.pending.mepoch,
                                   epoch=ep)
                    self._sync_q.put(([t], None, None,
                                      _stale_epoch_error(t, ep), 0.0))
                else:
                    fresh.append(t)
            batch = fresh
            if not batch:
                return []
        if self.cfg.telemetry_on:
            # point-in-time dispatch-path gauges (queue depth feeds
            # the planner/overlap postmortems; sampling here costs
            # one scheduler lock round-trip per dispatch iteration)
            gauges.set("engine.sched_pending", self.scheduler.pending)
            gauges.set("engine.bytes_in_flight",
                       self.scheduler.bytes_in_flight)
        return _plan_batch(batch)

    def _dispatch_buffer_run(self, run: List[ChunkTask], now: float):
        """One device program for a contiguous run of column-slab chunks:
        slice -> reduce-scatter -> write shards into the tensor's
        block-sharded accumulator (donated, in place); a run that is the
        whole tensor reduce-scatters it into the accumulator outright."""
        t0 = run[0]
        pending = t0.pending
        for t in run:
            t.t_dispatch = now
        self.stats["dispatches"] += 1
        self.stats["chunks"] += len(run)
        self.stats["whole_units"] += len(run) == pending.total
        try:
            _, C = pending.scatter_layout_snap
            width = (run[-1].offset_elems + run[-1].num_elems
                     - t0.offset_elems)
            buf, token = push_pull_chunk_scatter(
                self.comm, t0.data, pending.buf, t0.offset_elems,
                width, C, local=pending.local_mode)
            pending.buf = buf
            self._sync_q.put((run, token, None, None,
                              time.monotonic()))
        except Exception as e:  # noqa: BLE001
            get_logger().error("dispatch failed for %s: %s", t0.name, e)
            _flight.record("engine.dispatch_failed", tensor=t0.name,
                           error=str(e))
            self._sync_q.put((run, None, None, e, 0.0))

    def _dispatch_parts_group(self, group: List[ChunkTask], now: float):
        """One program for k equal-shape uncompressed chunks of distinct
        tensors (push_pull_arrays_batched): one dispatch replaces k, the
        per-chunk results come back separately so every downstream
        consumer (assembly, debug sampling, callbacks) is unchanged."""
        t0 = group[0]
        for t in group:
            t.t_dispatch = now
        self.stats["dispatches"] += 1
        self.stats["chunks"] += len(group)
        try:
            outs = push_pull_arrays_batched(
                self.comm, [t.data for t in group], scale=t0.scale,
                local=t0.data.ndim == 1)
            self._sync_q.put((group, outs, None, None,
                              time.monotonic()))
        except Exception as e:  # noqa: BLE001
            get_logger().error("dispatch failed for %s: %s", t0.name, e)
            _flight.record("engine.dispatch_failed", tensor=t0.name,
                           error=str(e))
            self._sync_q.put((group, None, None, e, 0.0))

    def _dispatch_single(self, task: ChunkTask, now: float):
        task.t_dispatch = now
        self.stats["dispatches"] += 1
        self.stats["chunks"] += 1
        self.stats["whole_units"] += task.total_parts == 1
        try:
            slot = task.compression
            rollback = None
            if slot is not None:
                # the fused quantized program: in-graph chunk slice from
                # the staged row, quantize, quantized-payload gather,
                # dequant-accumulate, merged re-quantize, state update —
                # one persistent executable (AOT-compiled at declare)
                out, new_wst, new_sst = fused_compressed_push_pull(
                    self.comm, task.data, task.offset_elems,
                    slot.worker, slot.server, slot.wstates, slot.sstate)
                # Commit at dispatch time so a later step of the same
                # chunk (which can be dispatched before this one syncs)
                # sees the advanced EF/momentum/PRNG state; the syncer
                # rolls back to the pre-step snapshot if the async
                # execution later fails, so a transient device fault
                # does not poison the slot.
                rollback = (slot, slot.wstates, slot.sstate)
                slot.wstates = new_wst
                slot.sstate = new_sst
            elif task.scale is not None:
                out = push_pull_array_scaled(self.comm, task.data,
                                             task.scale,
                                             local=task.data.ndim == 1)
            else:
                out = push_pull_array(self.comm, task.data, op="sum",
                                      keep_acc=True,
                                      local=task.data.ndim == 1)
            self._sync_q.put(([task], out, rollback, None,
                              time.monotonic()))
        except Exception as e:  # noqa: BLE001
            get_logger().error("dispatch failed for %s: %s", task.name, e)
            _flight.record("engine.dispatch_failed", tensor=task.name,
                           error=str(e))
            self._sync_q.put(([task], None, None, e, 0.0))

    def _sync_loop(self):
        # Exits only on the sentinel, which shutdown enqueues *after* the
        # dispatcher has joined — so a completion the dispatcher put just
        # before stopping can never be lost to a flag/empty-queue race.
        #
        # Event-driven, per-UNIT retirement (ISSUE 5 tentpole part 2):
        # each wakeup drains every completed-dispatch unit already queued
        # and retires them one at a time in dispatch order — block on the
        # unit's token, return the whole unit's scheduler credits in ONE
        # call (the old path paid one credit lock per CHUNK), run its
        # callbacks immediately.  Units retire as they complete, never
        # behind a slower queue-mate: a whole-drain block_until_ready
        # sweep measured ~15% SLOWER on the cross-barrier workload — a
        # gate's handle sat unresolved until its batch's laggard
        # finished, which is exactly the just-in-time latency the xb
        # design sells.
        self.step_stats.register_thread("syncer")
        shutdown = False
        while not shutdown:
            # Retired units are dropped as they go (popleft) and before
            # the next blocking get (below): a unit's chunks hold their
            # tensor's staged array, and a reference kept here would keep
            # it on the device through the caller's optimizer update.
            items = collections.deque([self._sync_q.get()])
            while True:  # opportunistic drain of everything already queued
                try:
                    items.append(self._sync_q.get_nowait())
                except queue.Empty:
                    break
            while items:
                item = items.popleft()
                if item is _SHUTDOWN:
                    shutdown = True
                    continue
                tasks, out, rollback, err, t_disp = item
                # Per-unit data-path deadline: stamp the unit under
                # retirement so _deadline_loop can observe how long this
                # thread has been captive (a wedged block_until_ready
                # never returns, so the observation must be out-of-band).
                # The stamp covers the chaos "sync" site too — chaos
                # delays are the test double for a wedged collective.
                if self._deadline_on:
                    with self._sync_block_lock:
                        self._sync_block = (time.monotonic(),
                                            [t.name for t in tasks])
                head = tasks[0]
                blocks = err is None
                try:
                    # "sync": time this thread spends BLOCKED on device
                    # completion — the step's sync-stall share (the
                    # un-overlapped remainder of communication); a unit
                    # that failed at dispatch has nothing to block on
                    # and feeds nothing
                    with _tracing.phase(
                            "bps.engine.sync",
                            self.phase_feeds["sync"] if blocks else None
                            ) as ph_blk:
                        if ph_blk.ann is not None:
                            ph_blk.note(step=head.step, tensor=head.name)
                        if _fault.ENABLED:
                            # chaos site "sync": delay completion ->
                            # callback.  Deliberately inside the timed
                            # window: the delay is the test double for a
                            # wedged collective, so it must surface
                            # exactly like one — as sync stall (overlap
                            # collapse, the self-reported slowness feed)
                            # — not vanish into untimed bookkeeping
                            # around the block.
                            _fault.fire("sync")
                        if blocks:
                            try:
                                # For buffer runs ``out`` is the
                                # completion token, not the buffer: the
                                # buffer itself may already have been
                                # donated into a later chunk's program.
                                self._block(out)
                            except Exception as e:  # noqa: BLE001
                                err = e
                                if rollback is not None:
                                    slot, wst, sst = rollback
                                    slot.wstates = wst
                                    slot.sstate = sst
                    if blocks and self.cfg.telemetry_on:
                        # slowness feed: this process's own data-path
                        # latency — the self-reported half of
                        # gray-failure detection (the bus's step-barrier
                        # lags are the cross-rank half).  Imported
                        # lazily: utils pulls in checkpoint → core.api,
                        # a cycle at engine import time.
                        from ..utils import slowness as _slowness
                        _slowness.tracker().observe(
                            self.cfg.host_id, ph_blk.t1 - ph_blk.t0,
                            site="sync")
                finally:
                    if self._deadline_on:
                        with self._sync_block_lock:
                            self._sync_block = None
                # Unit credits back BEFORE callbacks, one lock op for the
                # whole run: the dispatcher can launch the next window
                # while this thread runs assembly.
                self.scheduler.report_finish(sum(t.nbytes for t in tasks))
                if self.cfg.telemetry_on:
                    # one call, one lock a retired unit: the
                    # lagging-tensor bookkeeping (the LAST retired unit
                    # before a step finalizes names the chain the step
                    # actually waited on), queue-wait attribution (how
                    # long this unit's head chunk sat in the priority
                    # queue before dispatch) and the unit's dispatch ->
                    # retire latency (engine.unit_sync_ms)
                    self.step_stats.retire_unit(
                        tasks[-1].name,
                        (head.t_dispatch - head.t_enqueue) * 1e3
                        if head.t_dispatch and head.t_enqueue else None,
                        (time.monotonic() - t_disp) * 1e3
                        if t_disp else None)
                # "assemble": assembly + callback wall, the retirement
                # work after the device block — the tail segment of a
                # push's critical path (step attribution, ISSUE 12)
                with _tracing.phase("bps.engine.assemble",
                                    self.phase_feeds["assemble"]) as ph:
                    if ph.ann is not None:
                        ph.note(step=head.step, tensor=head.name)
                    self._finish_batch(tasks, out, err)
                item = tasks = out = rollback = head = None

    def _deadline_loop(self):
        """Per-unit sync-deadline watchdog (BYTEPS_SYNC_DEADLINE_S): a
        unit the syncer has been blocked on past the deadline becomes
        data-path failure evidence (``failure_detector.
        data_path_stalled`` → the installed failure action — an elastic
        shrink/reconcile — with ``os._exit`` only as the uninstalled
        last resort).  One report per wedged unit: the action's own
        recovery (epoch guard up, suspend/resume) takes over from
        there."""
        deadline = self.cfg.sync_deadline_s
        period = max(0.05, min(1.0, deadline / 4.0))
        reported = None
        while not self._deadline_stop.wait(period):
            if not self._running:
                return
            with self._sync_block_lock:
                blk = self._sync_block
            if blk is None:
                reported = None
                continue
            t0, names = blk
            gap = time.monotonic() - t0
            if gap <= deadline or reported == t0:
                continue
            reported = t0
            counters.inc("engine.sync_deadline_trips")
            _flight.record("engine.sync_deadline", gap_s=round(gap, 3),
                           deadline_s=deadline, tensors=names[:8])
            get_logger().error(
                "engine: sync unit %s blocked %.1fs > "
                "BYTEPS_SYNC_DEADLINE_S=%.1f — reporting data-path "
                "failure evidence", names[:4], gap, deadline)
            try:
                from ..utils.failure_detector import data_path_stalled
                data_path_stalled(gap, detail=f"sync unit {names[:4]}")
            except Exception:  # noqa: BLE001 — the failure action owns
                # its own escalation; a raise through here (e.g. Evicted)
                # was already logged/handled there
                get_logger().error("sync-deadline failure action raised",
                                   exc_info=True)

    def _finish_batch(self, tasks, out, err):
        """Retire one dispatch unit.  A buffer run -- chunks of ONE push
        behind one token -- retires in one pass: its chunks' wire bytes
        are summed and counted once, and the push hears of all of them
        at once (``_complete_run``); the other kinds call back per
        task."""
        ep = _membership.current_epoch()
        run = _buffered(tasks[0])
        telemetry = self.cfg.telemetry_on
        wire_sum = pull_sum = param_sum = 0
        for idx, task in enumerate(tasks):
            # parts-group dispatches carry one output PER task
            out_t = out[idx] if isinstance(out, list) else out
            err_t = err
            if (err_t is None and task.pending is not None
                    and task.pending.mepoch != ep):
                # issued before a world change, completed after: the
                # result was computed over a mesh that no longer exists
                # — drop it (credits still return below)
                counters.inc("membership.stale_chunks_dropped")
                err_t = _stale_epoch_error(task, ep)
            if err_t is None and not run:
                self._debug_sample(task, out_t)
            # credits for this task were returned in the sync loop's bulk
            # report_finish — nothing per-chunk here
            if task.trace_id and self.tracer.active:
                # captured push (window or sample): record the chunk's
                # two spans against its trace id — NOT window-gated, the
                # capture decision was made at start_push — and the
                # per-PUSH flow arc: ``s`` anchored in the first chunk's
                # queued span, ``f`` at the last chunk's retirement.
                # This runs only on the single syncer thread, so the
                # pending's trace bookkeeping needs no lock.
                t_done = time.monotonic()
                # a chunk dropped before dispatch (stale epoch) has no
                # dispatch stamp: its whole life was the queue
                t_disp = task.t_dispatch or t_done
                self.tracer.record_traced(
                    task.trace_id, "queued", task.name,
                    task.t_enqueue, t_disp,
                    key=task.key, step=task.step, bytes=task.nbytes)
                if task.t_dispatch:
                    self.tracer.record_traced(
                        task.trace_id, "push_pull", task.name,
                        t_disp, t_done,
                        key=task.key, step=task.step, bytes=task.nbytes)
                p = task.pending
                if p is not None and p.trace is not None:
                    if not p.trace_started:
                        self.tracer.flow(task.trace_id, "s", task.name,
                                         task.t_enqueue)
                        p.trace_started = True
                    p.trace_left -= 1
                    if p.trace_left == 0:
                        self.tracer.flow(task.trace_id, "f", task.name,
                                         t_done)
            if telemetry:
                # push + pull wire bytes; compressed chunks report
                # payload size, which is the point of the feature.
                # Under a sharded-update slot the pull leg ships only
                # the owner's slice (or the parameter-codec payload) —
                # the halved-wire claim, measured per leg so /metrics
                # and bps_top can assert it (wire_bytes{leg=}: labeled
                # series beside the KV store's unlabeled total, which
                # stays the async-PS figure).  Summed here, counted once
                # a unit below.
                wire = (task.compression.worker.payload_nbytes()
                        if task.compression is not None else task.nbytes)
                p = task.pending
                slot = p.slot if p is not None else None
                pull = (slot.pull_share(task.nbytes, p.use_buffer)
                        if slot is not None else wire)
                wire_sum += wire
                pull_sum += pull
                if (slot is not None and slot.codec is not None
                        and err_t is None and p.use_buffer):
                    # quantized parameter leg: reported separately from
                    # the gradient ladder's compression.wire_bytes
                    param_sum += pull
                if task.compression is not None and err_t is None:
                    # quantized-wire accounting (ISSUE 11): what the
                    # reduce leg actually shipped, and the raw bytes it
                    # did NOT — the compression-ratio evidence beside
                    # the KV store's wire_bytes counters
                    counters.inc("compression.wire_bytes", wire)
                    counters.inc("compression.bytes_saved",
                                 max(0, task.nbytes - wire))
                    counters.inc("compression.compressed_chunks")
            if task.callback is not None:
                task.callback(None if err_t is not None else out_t,
                              self._chunk_status(err_t))
        if telemetry:
            self.speed.record(wire_sum + pull_sum)
            counters.inc("wire_bytes", wire_sum, leg="push")
            counters.inc("wire_bytes", pull_sum, leg="pull")
            self.step_stats.add_wire(wire_sum + pull_sum)
            if param_sum:
                counters.inc("compression.param_wire_bytes", param_sum)
        if run:
            # one pending, so one epoch: the last chunk's verdict is
            # every chunk's
            self._complete_run(tasks[0].pending, len(tasks), err_t)

    @staticmethod
    def _chunk_status(err) -> Status:
        """What a chunk's owner hears: stale-epoch drops carry ABORTED
        (a recognizable, retryable outcome); real failures stay
        errors."""
        if err is None:
            return Status.ok()
        if isinstance(err, StaleEpochError):
            return Status(StatusCode.ABORTED, str(err))
        return Status.error(str(err))

    # ---------------------------------------------------------- lifecycle
    def shutdown(self, wait: bool = True):
        if wait:
            # drain: wait for all outstanding handles — under ONE total
            # budget, not a per-handle 60s.  With a sync deadline armed
            # the operator has declared a unit blocked past it dead, so
            # the drain honors the same declaration: a reconcile after a
            # deadline trip must not stall its recovery behind the very
            # handle that is wedged (it resolves, if ever, as a
            # stale-epoch ABORT once the block returns).
            budget = (60.0 if self.cfg.sync_deadline_s <= 0
                      else max(5.0, self.cfg.sync_deadline_s))
            deadline = time.monotonic() + budget
            for h in self.handles.outstanding():
                try:
                    h.wait(timeout=max(0.1,
                                       deadline - time.monotonic()))
                except Exception:  # noqa: BLE001
                    pass
        self._running = False
        self._deadline_stop.set()
        # wake a dispatcher blocked in the (timeout-free) pop or parked
        # on the pause gate; the run flag is already down, so it exits
        self._dispatch_enabled.set()
        self.scheduler.wake()
        self._dispatcher.join(timeout=5)
        self._sync_q.put(_SHUTDOWN)
        self._syncer.join(timeout=5)
        self.handles.clear()
        # Tail preservation on a NORMAL exit (ISSUE 6 satellite): the
        # in-progress step's stats land, the comm trace flushes, and the
        # flight recorder dumps if BYTEPS_FLIGHT_DUMP_ON_EXIT asked
        # (same hooks also run from atexit for runs that never call
        # shutdown — both are idempotent).
        self.step_stats.flush()
        self.tracer.flush()
        _flight.record("engine.shutdown",
                       dispatches=self.stats["dispatches"],
                       chunks=self.stats["chunks"])
        _flight.maybe_exit_dump()

    def push_pull(self, stacked, name: str, **kw):
        """Synchronous push_pull; returns the reduced array."""
        h = self.push_pull_async(stacked, name, **kw)
        out = h.wait()
        self.handles.release(h.id)
        return out

    # -------------------------------------------------- contribution mode
    def push_pull_local_async(self, x, name: str, **kw) -> Handle:
        """Per-process (non-stacked) push_pull: this process contributes one
        tensor; the result is the sum/average over *processes*.

        This is the reference's native data model — every worker process
        owns one replica and calls push_pull on its own gradient
        (torch/__init__.py).  Under a single controller the local
        contribution is replicated across the process's devices and the
        over-count is divided back out, which also reproduces the
        reference's single-worker forced-distributed test mode
        (BYTEPS_FORCE_DISTRIBUTED, meta_test.py).
        """
        import jax as _jax
        op = kw.pop("op", "average")
        n_proc = _jax.process_count()
        local = self.comm.num_ranks // n_proc
        xn = np.asarray(x)
        # engine sums all ranks = local_size * (sum over processes); divide
        # the over-count (and the process count for averages) at assembly
        denom = local * n_proc if op == "average" else local
        if (n_proc == 1 and not kw.get("compression")
                and not self.cfg.debug_sample_tensor):
            # Single-process fast path: stage the contribution once and
            # replicate on-device (VERDICT r3 task 4 — host staging was
            # the realistic path's bottleneck).  Compression and debug
            # sampling need materialized per-rank rows, so they keep the
            # broadcast-view path below.
            return self.push_pull_async(xn, name, op=op, denom=denom,
                                        out_shape=xn.shape, local=True,
                                        **kw)
        # numpy broadcast is a zero-copy *view*: no R-times materialization
        # on host or device — device_put later reads one [1, n] slice per
        # device (a device-side jnp.broadcast_to would materialize R x n on
        # the default device first).  flatten first so every later
        # reshape/slice in push_pull_async stays a zero-copy view.
        flat = np.broadcast_to(xn.reshape(-1)[None],
                               (self.comm.num_ranks, xn.size))
        return self.push_pull_async(flat, name, op=op, denom=denom,
                                    out_shape=xn.shape, **kw)

    def push_pull_local(self, x, name: str, **kw):
        h = self.push_pull_local_async(x, name, **kw)
        out = h.wait()
        self.handles.release(h.id)
        return out
