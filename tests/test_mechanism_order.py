"""Deterministic dispatch-order proofs for the scheduling mechanisms.

Round-3 VERDICT Weak #5 / task 3: the wall-clock mechanism benches
(`tools/mechanism_bench.py`) are load-sensitive on a shared host, but the
*mechanisms themselves* — priority reordering, chunk-granular preemption
under a credit window — are deterministic at the scheduler level.  These
tests pin exactly the dispatch-order claims docs/performance.md makes, with
zero timing dependence, against the engine's one queue
(common/scheduler.py ChunkScheduler; reference scheduled_queue.cc:82-161).

The scenario modeled is the one the latency benches measure:

- the credit window (reference BYTEPS_SCHEDULING_CREDIT) creates the
  decision point: dispatch waits for completions, so the queue holds depth;
- priority decides what dispatches next (backward produces gradients
  last-layer-first; the next forward needs layer 0 first);
- partitioning sets the preemption granularity (an urgent tensor waits out
  one *chunk* of a bulk transfer, not the whole tensor).
"""

from __future__ import annotations

from byteps_tpu.common.registry import make_key
from byteps_tpu.common.scheduler import ChunkScheduler
from byteps_tpu.common.types import ChunkTask


def _task(name, key, priority, nbytes=100):
    return ChunkTask(name=name, key=key, priority=priority, version=0,
                     offset_elems=0, num_elems=nbytes // 4, nbytes=nbytes,
                     total_parts=1)


def _drain_order(s):
    """Pop everything, returning credits after each pop (a dispatch loop
    whose every collective completes before the next pop)."""
    order = []
    while True:
        t = s.get_task()
        if t is None:
            break
        order.append(t.name)
        s.report_finish(t.nbytes)
    return order


def test_backward_enqueue_order_dispatches_declaration_order():
    """K gradients enqueued in REVERSE declaration order (backward-pass
    production order) while the window is full dispatch in DECLARATION
    order once the window opens — the priority mechanism's core claim
    (priority = -declared_key, engine.py push_pull_async)."""
    s = ChunkScheduler(credit_bytes=100)
    blocker = _task("blocker", key=make_key(99, 0), priority=-99)
    s.add_task(blocker)
    assert s.get_task().name == "blocker"   # fills the window
    for i in reversed(range(6)):            # layer5 arrives first
        s.add_task(_task(f"layer{i}", key=make_key(10 + i, 0), priority=-i))
    assert s.get_task() is None             # window full: queue holds depth
    s.report_finish(blocker.nbytes)
    assert _drain_order(s) == [f"layer{i}" for i in range(6)]


def test_fifo_priorities_dispatch_in_arrival_order():
    """The FIFO baseline (priority pinned to arrival order, what a plain
    allreduce queue executes) dispatches in arrival order — the contrast
    that makes the previous test a mechanism proof, not a tautology."""
    s = ChunkScheduler(credit_bytes=100)
    blocker = _task("blocker", key=make_key(99, 0), priority=0)
    s.add_task(blocker)
    s.get_task()
    for pos, i in enumerate(reversed(range(6))):
        s.add_task(_task(f"layer{i}", key=make_key(10 + i, 0),
                         priority=-pos))
    s.report_finish(blocker.nbytes)
    assert _drain_order(s) == [f"layer{i}" for i in reversed(range(6))]


def test_urgent_preempts_partitioned_bulk_at_chunk_granularity():
    """With a bulk tensor split into 16 chunks and a 1-chunk credit window,
    an urgent tensor arriving mid-transfer dispatches after exactly ONE
    more bulk chunk — partitioning bounds head-of-line blocking to a chunk
    (reference operations.cc:140-180 partitioning rationale)."""
    s = ChunkScheduler(credit_bytes=100)
    for i in range(16):
        s.add_task(_task(f"bulk{i}", key=make_key(1, i), priority=-10))
    first = s.get_task()
    assert first.name == "bulk0"            # one chunk in flight
    assert s.get_task() is None             # window full
    s.add_task(_task("urgent", key=make_key(2, 0), priority=10, nbytes=50))
    s.report_finish(first.nbytes)
    nxt = s.get_task()
    assert nxt.name == "urgent"             # preempts 15 remaining chunks
    s.report_finish(nxt.nbytes)
    # the bulk transfer then resumes in chunk order
    assert _drain_order(s) == [f"bulk{i}" for i in range(1, 16)]


def test_unpartitioned_bulk_blocks_urgent_for_whole_tensor():
    """The contrast case: the same bytes as ONE task (no partitioning)
    occupy the window whole, so the urgent tensor waits out the entire
    transfer — 16x the dispatched-bytes head-of-line cost of the
    partitioned case above."""
    s = ChunkScheduler(credit_bytes=100)
    s.add_task(_task("bulk", key=make_key(1, 0), priority=-10, nbytes=1600))
    first = s.get_task()                    # oversized-but-idle clamp
    assert first.name == "bulk"
    s.add_task(_task("urgent", key=make_key(2, 0), priority=10, nbytes=50))
    # all 1600 bulk bytes are in flight; urgent cannot dispatch until the
    # WHOLE tensor completes
    assert s.get_task() is None
    s.report_finish(first.nbytes)
    assert s.get_task().name == "urgent"


def test_credit_window_admits_multiple_small_chunks():
    """The window is a byte budget, not a task count: two 100 B chunks fit
    a 250 B window simultaneously, a third waits (reference
    scheduled_queue.cc:136-150)."""
    s = ChunkScheduler(credit_bytes=250)
    for i in range(3):
        s.add_task(_task(f"c{i}", key=make_key(1, i), priority=0))
    assert s.get_task().name == "c0"
    assert s.get_task().name == "c1"
    assert s.get_task() is None
    s.report_finish(100)
    assert s.get_task().name == "c2"
