"""Persistent compiled programs + auto-tuned planner tests (ISSUE 5).

The tentpole's contract, pinned here:

- **zero compiles at steady state**: a push_pull stream of declared
  tensors triggers no new XLA compiles after warmup — the compiled chunk
  programs persist in ``comm.jit_cache`` and the planner's locked choice
  stops the program set from growing;
- **declare-time AOT warm**: ``bps.declare(name, shape=...)``
  pre-compiles the tensor's whole steady-state program set, so even the
  FIRST push compiles nothing;
- **the planner**: explores its candidate ladder, locks a winner per
  size bucket, never moves a pinned knob, never tunes multi-process, and
  discards samples polluted by a compile;
- **the event-driven scheduler**: interrupt/wake/set_credit on both the
  Python and native backends, and the pause_dispatch handshake that
  replaced the polling quantum;
- **repartition safety**: chunk bounds only move between pushes, and
  compressed tensors never repartition.
"""

import threading
import time

import numpy as np
import pytest

import byteps_tpu as bps
from byteps_tpu.common.config import Config, set_config
from byteps_tpu.common.scheduler import ChunkPlanner, ChunkScheduler
from byteps_tpu.common.telemetry import counters
from byteps_tpu.common.types import ChunkTask


@pytest.fixture
def bps_session():
    bps.init()
    yield bps
    bps.shutdown()


@pytest.fixture
def bps_autotune_small():
    # Small base bound: an 80 KB tensor is already "large" to the
    # planner, so exploration + lock complete in a handful of fast pushes
    # instead of needing megabyte tensors.
    set_config(Config(partition_bytes=16384, partition_pinned=False,
                      credit_pinned=False))
    bps.init()
    yield bps
    bps.shutdown()


def _task(key, nbytes=64, priority=0):
    return ChunkTask(name=f"t{key}", key=key, priority=priority, version=0,
                     offset_elems=0, num_elems=nbytes // 4, nbytes=nbytes,
                     total_parts=1)


# ---------------------------------------------------------------- headline


def test_steady_state_stream_compiles_nothing(bps_autotune_small):
    """The regression test the tentpole names: after warmup (declare-time
    AOT + planner exploration), a steady stream of push_pulls over the
    declared set triggers ZERO new XLA compiles."""
    eng = bps.core.api._engine
    rng = np.random.RandomState(0)
    shapes = {"z/a": (40_000,),       # 160 KB: multi-chunk, planner-tuned
              "z/b": (300, 33),       # odd 2-D, sub-bound single chunk
              "z/c": (1024,)}         # small parts-mode tensor
    for n, s in shapes.items():
        bps.declare(n, shape=s, dtype=np.float32)
    assert counters.get("engine.aot_compile_failed") == 0
    # Warmup: run until the planner has locked every tuned bucket (it
    # needs a few completed pushes per candidate), bounded hard.
    for _ in range(40):
        for n, s in shapes.items():
            x = rng.randn(*s).astype(np.float32)
            out = eng.push_pull_local(x, n)
            np.testing.assert_allclose(np.asarray(out), x, rtol=1e-5,
                                       atol=1e-6)
        if all(eng.planner.locked(int(np.prod(s)) * 4)
               for s in shapes.values()):
            break
    assert all(eng.planner.locked(int(np.prod(s)) * 4)
               for s in shapes.values())
    m0 = counters.get("engine.compile_cache_miss")
    for _ in range(5):
        for n, s in shapes.items():
            x = rng.randn(*s).astype(np.float32)
            out = eng.push_pull_local(x, n)
            np.testing.assert_allclose(np.asarray(out), x, rtol=1e-5,
                                       atol=1e-6)
    assert counters.get("engine.compile_cache_miss") == m0


def test_declare_aot_first_push_compiles_nothing(bps_session):
    """With the planner quiet (tensor under the base bound is a single
    chunk — nothing to explore), declare-time AOT covers the ENTIRE
    program set: even the first push is compile-free."""
    eng = bps.core.api._engine
    bps.declare("aot/w", shape=(300_000,), dtype=np.float32)
    assert counters.get("engine.aot_compiled") > 0
    assert counters.get("engine.aot_compile_failed") == 0
    m0 = counters.get("engine.compile_cache_miss")
    x = np.random.RandomState(1).randn(300_000).astype(np.float32)
    out = eng.push_pull_local(x, "aot/w")
    np.testing.assert_allclose(np.asarray(out), x, rtol=1e-5, atol=1e-6)
    assert counters.get("engine.compile_cache_miss") == m0


def test_declare_aot_sum_op_first_push_compiles_nothing(bps_session):
    """op="sum" warm must model the LOCAL path's over-count division
    (a float sum push rides the fused-scale fast path with scale =
    1/local_size) — an average-only model would warm dead keys and the
    first sum push would compile mid-dispatch."""
    eng = bps.core.api._engine
    bps.declare("aot/s", shape=(300_000,), dtype=np.float32, op="sum")
    assert counters.get("engine.aot_compile_failed") == 0
    m0 = counters.get("engine.compile_cache_miss")
    x = np.random.RandomState(2).randn(300_000).astype(np.float32)
    out = eng.push_pull_local(x, "aot/s", op="sum")
    np.testing.assert_allclose(np.asarray(out), x, rtol=1e-5, atol=1e-6)
    assert counters.get("engine.compile_cache_miss") == m0


def test_declare_with_shape_returns_key_and_orders(bps_session):
    k1 = bps.declare("ord/a", shape=(64,))
    k2 = bps.declare("ord/b")          # plain reservation still works
    assert k1 < k2


# ---------------------------------------------------------------- planner


def test_planner_explores_then_locks():
    cfg = Config(partition_bytes=16384, partition_pinned=False,
                 credit_pinned=False)
    p = ChunkPlanner(cfg, num_procs=1)
    nbytes = 160_000
    seen = []
    # feed every candidate enough clean samples; fastest candidate wins
    for i in range(64):
        cand = p.plan_partition(nbytes)
        seen.append(cand)
        p.observe(nbytes, cand, seconds=0.001 if cand == 16384 else 0.01)
        if p.locked(nbytes):
            break
    assert p.locked(nbytes)
    assert p.plan_partition(nbytes) == 16384      # the fast candidate
    assert len(set(seen)) > 1                     # it really explored
    snap = p.snapshot()
    b = snap["buckets"][str(nbytes.bit_length())]
    assert b["locked_partition_bytes"] == 16384
    assert snap["credit_bytes"] == 4 * 16384


def test_planner_small_tensors_never_tuned():
    cfg = Config(partition_bytes=16384, partition_pinned=False)
    p = ChunkPlanner(cfg, num_procs=1)
    assert p.plan_partition(1000) == 16384
    assert p.locked(1000)                      # nothing to explore
    assert p.snapshot()["buckets"] == {}


def test_planner_pinned_partition_is_never_moved():
    cfg = Config(partition_bytes=8192, partition_pinned=True)
    p = ChunkPlanner(cfg, num_procs=1)
    for _ in range(20):
        assert p.plan_partition(1_000_000) == 8192
        p.observe(1_000_000, 8192, 0.001)
    assert p.credit_bytes() == 0


def test_planner_multiprocess_is_inert():
    cfg = Config(partition_bytes=8192, partition_pinned=False,
                 credit_pinned=False)
    p = ChunkPlanner(cfg, num_procs=2)
    assert not p.active
    assert p.plan_partition(1_000_000) == 8192
    p.observe(1_000_000, 8192, 0.001)
    assert p.snapshot()["buckets"] == {}


def test_planner_compile_polluted_sample_discarded():
    cfg = Config(partition_bytes=16384, partition_pinned=False)
    p = ChunkPlanner(cfg, num_procs=1)
    nbytes = 160_000
    cand = p.plan_partition(nbytes)
    for _ in range(10):  # compiled=True samples must never advance it
        p.observe(nbytes, cand, 5.0, compiled=True)
    assert p.plan_partition(nbytes) == cand
    assert not p.locked(nbytes)


def test_planner_stale_inflight_sample_ignored():
    """A push carved under an earlier candidate completing late must not
    credit its timing to the current candidate."""
    cfg = Config(partition_bytes=16384, partition_pinned=False)
    p = ChunkPlanner(cfg, num_procs=1)
    nbytes = 160_000
    cand = p.plan_partition(nbytes)
    p.observe(nbytes, cand + 4096, 0.001)     # not the current candidate
    st = p._buckets[nbytes.bit_length()]
    assert st["samples"].get(cand + 4096) is None


# ------------------------------------------------------------- scheduler


def test_scheduler_interrupt_wakes_blocked_get():
    s = ChunkScheduler(credit_bytes=0)
    got = {}

    def worker():
        got["task"] = s.get_task(block=True)   # no timeout: event-driven

    t = threading.Thread(target=worker)
    t.start()
    time.sleep(0.05)
    assert t.is_alive()                        # parked, not polling
    s.interrupt()
    t.join(timeout=5)
    assert not t.is_alive()
    assert got["task"] is None


def test_scheduler_interrupt_is_one_shot():
    s = ChunkScheduler(credit_bytes=0)
    s.interrupt()                               # latched for the NEXT get
    assert s.get_task(block=True) is None       # consumed here
    s.add_task(_task(1))
    assert s.get_task(block=True) is not None   # back to normal popping


def test_scheduler_set_credit_unblocks_waiter():
    s = ChunkScheduler(credit_bytes=0)
    s.set_credit_bytes(64)
    assert s.credit_bytes == 64
    s.add_task(_task(1, nbytes=64))
    s.add_task(_task(2, nbytes=64))
    assert s.get_task() is not None
    assert s.get_task() is None                 # window exhausted
    got = {}

    def worker():
        got["task"] = s.get_task(block=True)

    t = threading.Thread(target=worker)
    t.start()
    time.sleep(0.05)
    s.set_credit_bytes(256)                     # widening window notifies
    t.join(timeout=5)
    assert not t.is_alive() and got["task"] is not None


def test_scheduler_wake_is_latched():
    s = ChunkScheduler(credit_bytes=0)
    s.wake()
    assert s.get_task(block=True) is None       # returns without waiting
    assert s.get_task(block=True) is None       # and keeps returning


def test_pause_dispatch_parks_without_polling(bps_session):
    """The pause handshake: pause returns only once the dispatcher has
    parked, tasks enqueued while paused stay queued, resume drains them.
    (The old design slept a polling quantum and hoped.)"""
    eng = bps.core.api._engine
    eng.pause_dispatch()
    try:
        assert eng._parked.is_set()
        h = eng.push_pull_local_async(np.ones(256, np.float32), "pause/t")
        time.sleep(0.1)
        assert not h.poll()                     # nothing pops while paused
    finally:
        eng.resume_dispatch()
    np.testing.assert_allclose(np.asarray(h.wait(timeout=30)), 1.0)


# ----------------------------------------------------------- repartition


def test_repartition_moves_bounds_between_pushes(bps_autotune_small):
    eng = bps.core.api._engine
    from byteps_tpu.common.registry import TensorRegistry
    x = np.ones(40_000, np.float32)
    eng.push_pull_local(x, "rp/w")
    ctx = eng.registry.get("rp/w")
    with ctx.lock:
        assert ctx.inflight == 0
        changed = TensorRegistry.repartition_locked(ctx, 65536)
    assert changed and ctx.partition_bytes == 65536
    assert len(ctx.key_list) == len(ctx.chunk_bounds)
    out = eng.push_pull_local(2 * x, "rp/w")    # correct under new bounds
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_repartition_refuses_compressed(bps_session):
    eng = bps.core.api._engine
    from byteps_tpu.common.registry import TensorRegistry
    x = np.ones((8, 4096), np.float32)
    bps.push_pull(x, "rp/c", compression={"compressor": "onebit"})
    ctx = eng.registry.get("rp/c")
    bounds = list(ctx.chunk_bounds)
    with ctx.lock:
        assert not TensorRegistry.repartition_locked(ctx, 1 << 20)
    assert ctx.chunk_bounds == bounds
