"""Unit tests for the common layer: config, partitioner, registry, scheduler,
handles.  Test strategy follows SURVEY.md §4: every scheduling/bookkeeping
behavior of the reference core gets a direct equivalent check here."""

import threading

import numpy as np
import pytest

from byteps_tpu.common import (
    ChunkScheduler,
    ChunkTask,
    Config,
    HandleManager,
    Status,
    TensorRegistry,
    chunk_bounds,
    make_key,
    split_key,
)
from byteps_tpu.common.config import ALIGN_BYTES, set_config


# --- config ----------------------------------------------------------------

def test_config_from_env(monkeypatch):
    monkeypatch.setenv("BYTEPS_PARTITION_BYTES", "1000000")
    monkeypatch.setenv("BYTEPS_SCHEDULING_CREDIT", "8388608")
    monkeypatch.setenv("DMLC_NUM_WORKER", "4")
    monkeypatch.setenv("DMLC_WORKER_ID", "2")
    cfg = Config.from_env()
    # partition bound is rounded up to alignment
    assert cfg.partition_bytes % ALIGN_BYTES == 0
    assert cfg.partition_bytes >= 1000000
    assert cfg.scheduling_credit == 8388608
    assert cfg.num_hosts == 4 and cfg.host_id == 2


def test_config_validation():
    with pytest.raises(ValueError):
        Config(partition_bytes=0)
    with pytest.raises(ValueError):
        Config(num_hosts=0)
    with pytest.raises(ValueError):
        Config(failure_exit_code=0)     # must survive a process exit status
    with pytest.raises(ValueError):
        Config(failure_exit_code=256)
    with pytest.raises(ValueError):
        Config(restart_limit=-1)
    assert Config(group_size=0).group_size == 0     # the engine reads 0 as 1


@pytest.mark.parametrize("via", ["Config", "BYTEPS_GROUP_SIZE"])
@pytest.mark.parametrize("value", [-1, -8])
def test_negative_group_size_is_rejected(monkeypatch, via, value):
    """A negative group_size selected drain mode (removed): it arrives from
    outside the program, so it is refused by name, not clamped."""
    with pytest.raises(ValueError, match="drain mode.*removed"):
        if via == "Config":
            Config(group_size=value)
        else:
            monkeypatch.setenv(via, str(value))
            Config.from_env()


def test_config_fault_tolerance_knobs_from_env(monkeypatch):
    """Satellite: BYTEPS_FAULT_SPEC / RESTART_LIMIT / FAILURE_EXIT_CODE /
    retry knobs ride Config.from_env like every other knob."""
    monkeypatch.setenv("BYTEPS_FAULT_SPEC", "delay:site=dcn:p=0.5:ms=10")
    monkeypatch.setenv("BYTEPS_FAULT_SEED", "99")
    monkeypatch.setenv("BYTEPS_RESTART_LIMIT", "4")
    monkeypatch.setenv("BYTEPS_FAILURE_EXIT_CODE", "42")
    monkeypatch.setenv("BYTEPS_RETRY_MAX_ATTEMPTS", "6")
    monkeypatch.setenv("BYTEPS_RETRY_BASE_DELAY", "0.25")
    monkeypatch.setenv("BYTEPS_RETRY_MAX_DELAY", "3.5")
    monkeypatch.setenv("BYTEPS_RETRY_DEADLINE", "45")
    cfg = Config.from_env()
    assert cfg.fault_spec == "delay:site=dcn:p=0.5:ms=10"
    assert cfg.fault_seed == 99
    assert cfg.restart_limit == 4
    assert cfg.failure_exit_code == 42
    assert cfg.retry_max_attempts == 6
    assert cfg.retry_base_delay_s == 0.25
    assert cfg.retry_max_delay_s == 3.5
    assert cfg.retry_deadline_s == 45.0


def test_config_fault_tolerance_defaults():
    cfg = Config()
    assert cfg.fault_spec == ""          # chaos off: zero-overhead path
    assert cfg.failure_exit_code == 17   # the historical detector exit
    assert cfg.restart_limit == 0        # supervision is opt-in


# --- keys ------------------------------------------------------------------

def test_key_encoding_roundtrip():
    # declared_key<<16 | part, as the reference carves the key space
    # (operations.cc:302-311)
    key = make_key(7, 42)
    assert split_key(key) == (7, 42)
    assert make_key(0, 0) == 0
    with pytest.raises(ValueError):
        make_key(1, 1 << 16)


# --- partitioner -----------------------------------------------------------

def test_small_tensor_single_chunk():
    assert chunk_bounds(1000, 4, 4096000) == [(0, 1000)]


def test_partition_covers_exactly():
    n = 3_000_000
    bounds = chunk_bounds(n, 4, 1 << 20)  # 1 MB chunks of f32
    assert bounds[0][0] == 0
    assert sum(ln for _, ln in bounds) == n
    for (o1, l1), (o2, _) in zip(bounds, bounds[1:]):
        assert o1 + l1 == o2
    # all chunks but last respect the byte bound
    for _, ln in bounds:
        assert ln * 4 <= 1 << 20


def test_partition_alignment():
    bounds = chunk_bounds(10_000_000, 4, 1 << 20)
    from byteps_tpu.common.partitioner import ALIGN_ELEMS
    for off, _ in bounds:
        assert off % ALIGN_ELEMS == 0


# --- registry --------------------------------------------------------------

def test_declare_order_gives_keys():
    reg = TensorRegistry()
    a = reg.declare("grad/a")
    b = reg.declare("grad/b")
    again = reg.declare("grad/a")
    assert a.declared_key == 0 and b.declared_key == 1
    assert again is a
    assert reg.names_in_declaration_order() == ["grad/a", "grad/b"]


def test_init_tensor_carves_keys():
    set_config(Config(partition_bytes=ALIGN_BYTES))  # tiny bound -> many chunks
    reg = TensorRegistry()
    ctx = reg.init_tensor("g", shape=(4096,), dtype=np.float32)
    assert ctx.initialized
    assert ctx.num_elems == 4096
    assert len(ctx.chunk_bounds) == len(ctx.key_list) >= 2
    assert all(split_key(k)[0] == ctx.declared_key for k in ctx.key_list)
    # idempotent
    ctx2 = reg.init_tensor("g", shape=(4096,), dtype=np.float32)
    assert ctx2 is ctx


# --- scheduler -------------------------------------------------------------

def _task(name, key, priority, nbytes=100):
    return ChunkTask(name=name, key=key, priority=priority, version=0,
                     offset_elems=0, num_elems=nbytes // 4, nbytes=nbytes,
                     total_parts=1)


@pytest.mark.parametrize("arrivals,expect", [
    ([(make_key(2, 0), -2), (make_key(0, 1), 0), (make_key(0, 0), 0),
      (make_key(1, 0), -1)],
     [make_key(0, 0), make_key(0, 1), make_key(1, 0), make_key(2, 0)]),
    ([(30, -3), (10, -1), (21, -2), (20, -2)], [10, 20, 21, 30]),
])
def test_priority_order(arrivals, expect):
    # priority desc, then key asc — the reference comparator
    # (scheduled_queue.cc:82-102)
    s = ChunkScheduler()
    for key, priority in arrivals:
        s.add_task(_task("t", key=key, priority=priority))
    assert [s.get_task().key for _ in arrivals] == expect


def test_credit_window_blocks_and_returns():
    s = ChunkScheduler(credit_bytes=250)
    s.add_task(_task("a", 0, 0, nbytes=100))
    s.add_task(_task("b", 1, 0, nbytes=100))
    s.add_task(_task("c", 2, 0, nbytes=100))
    assert s.get_task().name == "a"
    assert s.get_task().name == "b"
    # third would exceed 250 in-flight bytes
    assert s.get_task() is None
    assert s.bytes_in_flight == 200
    s.report_finish(100)
    assert s.get_task().name == "c"


@pytest.mark.parametrize("credit,huge", [(50, 1000), (64, 10_000)])
def test_oversized_task_still_runs(credit, huge):
    s = ChunkScheduler(credit_bytes=credit)
    s.add_task(_task("huge", 0, 0, nbytes=huge))
    assert s.get_task().name == "huge"  # window empty -> allowed through
    s.add_task(_task("next", 1, 0, nbytes=10))
    assert s.get_task() is None         # oversized still in flight
    s.report_finish(huge)
    assert s.get_task().name == "next"


def test_blocking_get_wakes_on_add():
    s = ChunkScheduler()
    got = []
    t = threading.Thread(
        target=lambda: got.append(s.get_task(block=True, timeout=5.0)))
    t.start()
    s.add_task(_task("late", 1, 0, nbytes=8))
    t.join(timeout=10)
    assert not t.is_alive() and got[0].name == "late"


def test_add_tasks_is_add_task_for_every_task(monkeypatch):
    """The list hand-over gives the order and the credit accounting of
    adding the same tasks one by one -- with one wake-up, not one each."""
    arrivals = [(30, -3), (10, -1), (21, -2), (20, -2), (11, -1), (5, -3)]
    one, many = ChunkScheduler(credit_bytes=250), ChunkScheduler(
        credit_bytes=250)
    wakes = []
    monkeypatch.setattr(many._cv, "notify",
                        lambda n=1: wakes.append(n), raising=False)
    for key, priority in arrivals:
        one.add_task(_task("t", key=key, priority=priority))
    many.add_tasks([_task("t", key=key, priority=priority)
                    for key, priority in arrivals])
    assert wakes == [1]
    assert many.pending == one.pending == len(arrivals)
    got = []
    for s in (one, many):
        keys = []
        while s.pending:
            t = s.get_task()
            if t is None:               # window full: two tasks are out
                assert s.bytes_in_flight == 200
                s.report_finish(200)
                continue
            keys.append((t.key, s.bytes_in_flight))
        got.append(keys)
    assert got[0] == got[1]
    assert [k for k, _ in got[1]] == [10, 11, 20, 21, 5, 30]
    many.add_tasks([])                  # an empty list adds nothing
    assert many.pending == 0


def test_add_tasks_wakes_a_blocked_consumer_once():
    s = ChunkScheduler()
    got = []
    t = threading.Thread(
        target=lambda: got.append(s.get_task(block=True, timeout=5.0)))
    t.start()
    s.add_tasks([_task("late", k, 0, nbytes=8) for k in (2, 1, 3)])
    t.join(timeout=10)
    assert not t.is_alive() and got[0].key == 1 and s.pending == 2


def test_a_concurrent_consumer_never_sees_a_prefix_of_the_list():
    """While lists of 13 are handed over, whoever holds the queue's lock
    finds a multiple of 13 in it: all of a tensor's chunks or none."""
    s = ChunkScheduler()
    n, rounds = 13, 200
    seen, stop = [], threading.Event()

    def consumer():
        popped = 0
        while popped < n * rounds and not stop.is_set():
            head = s.get_task(block=True, timeout=5.0)
            if head is None:
                continue
            # the head and every follower, in one hold of the lock
            rest = s.pop_while(lambda t: t.name == head.name)
            with s._cv:
                seen.append((1 + len(rest), len(s._heap)))
            popped += 1 + len(rest)

    t = threading.Thread(target=consumer)
    t.start()
    for r in range(rounds):
        s.add_tasks([_task(f"t{r}", make_key(r, i), -r, nbytes=8)
                     for i in range(n)])
    t.join(timeout=30)
    stop.set()
    assert not t.is_alive()
    assert sum(took for took, _ in seen) == n * rounds
    assert all(took == n and left % n == 0 for took, left in seen), seen[:5]


def test_pop_while_takes_neighbours_under_the_window():
    s = ChunkScheduler(credit_bytes=350)
    s.add_tasks([_task("a", k, 0) for k in range(5)] + [_task("b", 9, 0)])
    head = s.get_task()
    # the window admits three 100-byte tasks in all
    assert [t.key for t in s.pop_while(lambda t: t.name == "a")] == [1, 2]
    assert s.bytes_in_flight == 300
    s.report_finish(300)
    # a limit, then a task that does not belong: it stays the head
    assert [t.key for t in s.pop_while(lambda t: t.name == "a", 1)] == [3]
    assert [t.key for t in s.pop_while(lambda t: t.name == "a")] == [4]
    assert s.pop_while(lambda t: t.name == "a") == []
    assert head.key == 0 and s.get_task().name == "b"


def test_drain_returns_remaining():
    s = ChunkScheduler()
    for i in range(5):
        s.add_task(_task(f"t{i}", i, -i, nbytes=10))
    assert s.get_task().name == "t0"
    assert sorted(t.name for t in s.drain()) == ["t1", "t2", "t3", "t4"]
    assert s.pending == 0


# --- handles ---------------------------------------------------------------

def test_handle_wait_and_callback():
    hm = HandleManager()
    h = hm.allocate("g")
    assert not h.poll()
    fired = []
    h.add_done_callback(lambda hh: fired.append(hh.id))

    def complete():
        h.set_result(np.ones(3), Status.ok())

    t = threading.Thread(target=complete)
    t.start()
    out = h.wait(timeout=5)
    t.join()
    assert np.allclose(out, 1.0)
    assert fired == [h.id]
    assert h.poll()
    hm.release(h.id)
    assert hm.get(h.id) is None


def test_handle_error_propagates():
    hm = HandleManager()
    h = hm.allocate("g")
    h.set_result(None, Status.error("boom"))
    with pytest.raises(RuntimeError, match="boom"):
        h.wait(timeout=1)
