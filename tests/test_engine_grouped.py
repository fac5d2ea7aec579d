"""Dispatch-amortization tests (round-4 VERDICT task 3; the unit rule of
ISSUE 32): the dispatcher launches the fewest XLA programs -- one
chunk-scatter program per dispatch unit of a buffer-mode tensor (the
contiguous column range of it the queue holds, up to a bucket's worth of
bytes, whatever its chunks' widths), one batched collective per run of up
to `group_size` equal-shape small tensors -- with results bit-identical
to chunk-by-chunk dispatch and provably fewer dispatches.

The reference amortizes per-chunk launch overhead the same way with NCCL
group batching (nccl_manager.cc:130-134, BYTEPS_NCCL_GROUP_SIZE); here a
"group" is one jitted program instead of one ncclGroupStart/End bracket.
"""

import numpy as np
import pytest

import byteps_tpu as bps
from byteps_tpu.common import Config
from byteps_tpu.common.config import set_config
from byteps_tpu.common.partitioner import unit_bounds
from byteps_tpu.common.scheduler import ChunkScheduler
from byteps_tpu.core.engine import _plan_batch, _pop_batch
from byteps_tpu.common.types import ChunkTask


# ---------------------------------------------------------------- planning


class _FakePending:
    def __init__(self, use_buffer, unit_stops=None):
        self.use_buffer = use_buffer
        self.unit_stops = unit_stops or {}


class _Arr:
    def __init__(self, shape, dtype="float32"):
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self.ndim = len(shape)


def _task(name, key, off=0, ln=64, pending=None, data=None, scale=None,
          priority=0):
    t = ChunkTask(name=name, key=key, priority=priority, version=0,
                  offset_elems=off, num_elems=ln, nbytes=ln * 4,
                  total_parts=1, data=data, scale=scale, pending=pending)
    return t


def _tensor(name, widths, cap_bytes, key0=0, priority=0):
    """The chunk tasks of one buffer-mode push whose chunks are
    ``widths`` columns wide (4 bytes a column), its dispatch units cut
    by the engine's own function at ``cap_bytes``."""
    offs = np.concatenate([[0], np.cumsum(widths)]).tolist()
    p = _FakePending(True, {offs[a]: offs[b] for a, b in unit_bounds(
        [w * 4 for w in widths], cap_bytes)})
    return [_task(name, key0 + i, off=offs[i], ln=w, pending=p,
                  priority=priority) for i, w in enumerate(widths)]


def _drain(tasks, credit=0, group_size=4, in_flight=0):
    """Everything the dispatcher would launch for ``tasks`` handed over
    in one step: ``(kind, [(name, offset)])`` per unit, in dispatch
    order.  Each pop's credits come back before the next pop, but for
    ``in_flight`` bytes of some other tensor that stay out."""
    sched = ChunkScheduler(credit_bytes=credit)
    sched._in_flight = in_flight
    sched.add_tasks(tasks)
    out = []
    while sched.pending:
        head = sched.get_task()
        assert head is not None, "the window admits nothing"
        batch = _pop_batch(sched, head, group_size)
        sched.report_finish(sum(t.nbytes for t in batch))
        out += [(kind, [(t.name, t.offset_elems) for t in unit])
                for kind, unit in _plan_batch(batch)]
    return out


def _runs(widths_by_unit, name="w"):
    """Expected units of one tensor: runs of chunks of these widths."""
    out, off = [], 0
    for widths in widths_by_unit:
        unit = []
        for w in widths:
            unit.append((name, off))
            off += w
        out.append(("run", unit))
    return out


BIG = 1 << 30


@pytest.mark.parametrize("case,widths,cap,want", [
    # the parent merged equal widths only, at most group_size of them
    ("equal_widths_one_run", [64] * 8, BIG, [[64] * 8]),
    ("unequal_widths_and_the_tail", [64, 64, 32, 64, 16], BIG,
     [[64, 64, 32, 64, 16]]),
    ("a_narrower_tail_rides_along", [64] * 12 + [5], BIG,
     [[64] * 12 + [5]]),
    ("one_chunk", [64], BIG, [[64]]),
    ("the_byte_cap_31_chunks", [64] * 31, 16 * 256,
     [[64] * 16, [64] * 15]),
    ("the_byte_cap_counts_bytes_not_chunks", [64, 32, 32, 64, 64], 512,
     [[64, 32, 32], [64, 64]]),
    ("a_chunk_over_the_cap_goes_alone", [200, 64, 64], 512,
     [[200], [64, 64]]),
    ("several_processes_one_chunk_a_unit", [64] * 3, 0,
     [[64], [64], [64]]),
])
def test_a_unit_is_the_queued_column_range_under_the_cap(case, widths, cap,
                                                          want):
    """One rule: the queued chunks that continue the head, any widths,
    the tail included, up to the byte cap; group_size has no say."""
    for group_size in (1, 4):
        assert _drain(_tensor("w", widths, cap),
                      group_size=group_size) == _runs(want)


@pytest.mark.parametrize("case", ["foreign_tensor", "gap", "mid_unit_head",
                                  "two_pushes_interleaved"])
def test_a_run_stops_at_what_does_not_continue_it(case):
    """A run takes only what continues it; a unit popped in part goes
    chunk by chunk, so every launch has a width the warm compiled."""
    a = _tensor("a", [64] * 4, BIG)
    if case == "foreign_tensor":
        b = _tensor("b", [64], BIG, key0=2)         # sorts between a1, a3
        got = _drain([a[0], a[1], b[0], a[3]])
        assert got == [("run", [("a", 0)]), ("run", [("a", 64)]),
                       ("run", [("b", 0)]), ("run", [("a", 192)])]
    elif case == "gap":
        got = _drain([a[0], a[1], a[3]])
        assert got == [("run", [("a", 0)]), ("run", [("a", 64)]),
                       ("run", [("a", 192)])]
    elif case == "mid_unit_head":
        # the unit's head is gone: what is left of it never merges
        got = _drain(a[1:])
        assert got == [("run", [("a", off)]) for off in (64, 128, 192)]
    else:
        # the same tensor pushed twice before either is dispatched: equal
        # keys, so the queue alternates the two pushes' chunks
        a2 = _tensor("a", [64] * 4, BIG)
        got = _drain(a + a2)
        assert got == [("run", [("a", off)])
                       for off in (0, 0, 64, 64, 128, 128, 192, 192)]


@pytest.mark.parametrize("case,in_flight,want", [
    # 13 chunks under a window of four: the units ARE the window
    ("units_fit_the_window", 0, [[64] * 4, [64] * 4, [64] * 4, [64]]),
    # a chunk of another tensor is out: the first pop gets three of its
    # unit's four and launches them one by one
    ("a_cut_lands_on_chunk_widths", 256,
     [[64], [64], [64], [64], [64] * 4, [64] * 4, [64]]),
])
def test_the_credit_window_cuts_on_compiled_widths(case, in_flight, want):
    credit = 4 * 256
    tasks = _tensor("w", [64] * 13, min(BIG, credit))
    if in_flight:
        # ...and stays out until the first unit's chunks are done
        got = _drain(tasks[:4], credit=credit, in_flight=in_flight)
        got += _drain(tasks[4:], credit=credit)
    else:
        got = _drain(tasks, credit=credit)
    assert got == _runs(want)
    # a chunk's or a unit's width, never another
    assert {64 * len(unit) for _, unit in got} <= {64, 256}


def test_plan_groups_equal_shape_parts_tasks():
    d = _Arr((8, 64))
    batch = [_task(f"g{i}", i, data=d, scale=0.125) for i in range(5)]
    units = _plan_batch(batch)
    assert [(k, len(u)) for k, u in units] == [("group", 5)]
    # a width-1 "group" rides the single-task path (its program is
    # already cached) instead of compiling a k=1 batched program
    units = _plan_batch(batch[:1])
    assert [(k, len(u)) for k, u in units] == [("single", 1)]


@pytest.mark.parametrize("group_size,want", [(1, [1] * 5), (4, [4, 1]),
                                             (8, [5])])
def test_group_size_caps_the_cross_tensor_merge(group_size, want):
    """What group_size still caps: parts-mode chunks a pop."""
    d = _Arr((8, 64))
    tasks = [_task(f"g{i}", i, data=d, scale=0.125) for i in range(5)]
    got = _drain(tasks, group_size=group_size)
    assert [len(unit) for _, unit in got] == want


def test_a_parts_pop_leaves_buffer_chunks_to_their_unit():
    """A pop that starts at a parts-mode chunk takes no buffer-mode
    chunk along: that would be part of a unit."""
    d = _Arr((8, 16))
    tasks = ([_task("hi", 0, data=d, priority=2)]
             + _tensor("bulk", [64] * 3, BIG, key0=1, priority=1)
             + [_task("lo", 9, data=d)])
    assert _drain(tasks) == [("single", [("hi", 0)]),
                             ("run", [("bulk", 0), ("bulk", 64),
                                      ("bulk", 128)]),
                             ("single", [("lo", 0)])]


def test_plan_never_groups_incompatible_neighbors():
    batch = [_task("a", 0, data=_Arr((8, 64)), scale=0.125),
             _task("b", 1, data=_Arr((8, 32)), scale=0.125),   # shape
             _task("c", 2, data=_Arr((8, 32)), scale=None),    # scale
             _task("d", 3, data=_Arr((8, 32), "int32"))]       # dtype
    units = _plan_batch(batch)
    assert [k for k, _ in units] == ["single"] * 4


def test_plan_order_preserved_across_units():
    # priority order must survive planning: units come out in batch order
    d = _Arr((8, 16))
    bulk = _tensor("bulk", [64, 64], BIG, key0=1)
    batch = [_task("hi", 0, data=d, scale=None), *bulk,
             _task("lo", 3, data=d, scale=None)]
    kinds = [(k, [t.name for t in u]) for k, u in _plan_batch(batch)]
    assert kinds == [("single", ["hi"]), ("run", ["bulk", "bulk"]),
                     ("single", ["lo"])]


def test_a_later_tensor_of_higher_priority_goes_at_the_next_unit():
    """Units, not tensors, are what priority order is kept across: a
    tensor that arrives while a 31-chunk one is half dispatched goes
    before its second unit."""
    sched = ChunkScheduler()
    sched.add_tasks(_tensor("bulk", [64] * 31, 16 * 256, priority=1))
    first = _pop_batch(sched, sched.get_task(), 4)
    sched.add_tasks(_tensor("urgent", [64] * 2, BIG, key0=100, priority=5))
    order = [first]
    while sched.pending:
        order.append(_pop_batch(sched, sched.get_task(), 4))
    assert [(b[0].name, len(b)) for b in order] == [
        ("bulk", 16), ("urgent", 2), ("bulk", 15)]


# ------------------------------------------------------------- end-to-end


class _Gate:
    """Adapter from the old Event-style gate to the engine's first-class
    pause/resume hook (the one copy of the settle-the-in-flight-pop
    invariant lives in PushPullEngine.pause_dispatch)."""

    def __init__(self, eng):
        self._eng = eng

    def set(self):
        self._eng.resume_dispatch()


def _gated_engine(cfg):
    """bps session whose dispatcher is held until every push is enqueued:
    makes the merge widths deterministic (everything is in the queue
    when the gate opens, so every pop finds group_size chunks)."""
    set_config(cfg)
    bps.init()
    from byteps_tpu.core import api
    eng = api._engine
    eng.pause_dispatch()
    return eng, _Gate(eng)


@pytest.fixture
def no_session():
    yield
    bps.shutdown()


@pytest.mark.parametrize("group_size", [1, 8])
def test_grouped_buffer_tensor_fewer_dispatches_bitexact(no_session,
                                                         group_size):
    # 1 MiB f32 per rank / 4 KiB chunks = 256 column slabs; a unit is a
    # bucket's worth of them, 16, whatever group_size says, so they
    # execute as 16 programs and match chunk-by-chunk dispatch (the
    # parent's programs: one chunk a unit) bit for bit.
    rng = np.random.RandomState(7)
    x = rng.randn(8, 1 << 18).astype(np.float32)

    eng, gate = _gated_engine(Config(partition_bytes=4096,
                                     group_size=group_size,
                                     telemetry_on=False))
    eng._one_chunk_units = True
    h = eng.push_pull_async(x, "bulk", op="average")
    gate.set()
    ref = np.asarray(h.wait())
    base_stats = dict(eng.stats)
    bps.shutdown()

    eng, gate = _gated_engine(Config(partition_bytes=4096,
                                     group_size=group_size,
                                     telemetry_on=False))
    h = eng.push_pull_async(x, "bulk", op="average")
    gate.set()
    out = np.asarray(h.wait())
    grouped_stats = dict(eng.stats)

    np.testing.assert_array_equal(out, ref)
    assert base_stats["chunks"] == grouped_stats["chunks"] == 256
    assert base_stats["dispatches"] == 256         # one chunk a unit
    assert grouped_stats["dispatches"] == 16       # one program per 16 slabs
    assert grouped_stats["whole_units"] == base_stats["whole_units"] == 0


def test_grouped_small_tensors_fewer_dispatches(no_session):
    # 8 equal-shape gradients: group_size=8 batches them into one
    # program; results identical to sequential sync pushes through an
    # ungrouped engine.
    rng = np.random.RandomState(8)
    xs = [rng.randn(8, 300).astype(np.float32) for _ in range(8)]

    set_config(Config(group_size=1, telemetry_on=False))
    bps.init()
    ref = [np.asarray(bps.push_pull(x, f"g{i}", op="average"))
           for i, x in enumerate(xs)]
    bps.shutdown()

    eng, gate = _gated_engine(Config(group_size=8, telemetry_on=False))
    handles = [eng.push_pull_async(x, f"g{i}", op="average")
               for i, x in enumerate(xs)]
    gate.set()
    outs = [np.asarray(h.wait()) for h in handles]
    stats = dict(eng.stats)

    for o, r in zip(outs, ref):
        np.testing.assert_array_equal(o, r)
    assert stats["chunks"] == 8
    assert stats["dispatches"] == 1


def test_grouped_bitexact_on_dcn_mesh(no_session, monkeypatch):
    # code-review r5: on a (dcn=2, ici=4) mesh a single dispatch reduces
    # hierarchically (RS over ICI + psum over DCN); the batched group
    # program must use the SAME body, or grouping — a timing-dependent
    # decision — would change summation order and break bitwise
    # reproducibility between steps.
    monkeypatch.setenv("BYTEPS_DCN_SIZE", "2")
    rng = np.random.RandomState(9)
    xs = [rng.randn(8, 300).astype(np.float32) for _ in range(4)]

    set_config(Config(group_size=1, telemetry_on=False))
    bps.init()
    ref = [np.asarray(bps.push_pull(x, f"g{i}", op="average"))
           for i, x in enumerate(xs)]
    bps.shutdown()

    eng, gate = _gated_engine(Config(group_size=4, telemetry_on=False))
    assert eng.comm.n_dcn == 2
    handles = [eng.push_pull_async(x, f"g{i}", op="average")
               for i, x in enumerate(xs)]
    gate.set()
    outs = [np.asarray(h.wait()) for h in handles]
    assert eng.stats["dispatches"] == 1 and eng.stats["chunks"] == 4
    for o, r in zip(outs, ref):
        np.testing.assert_array_equal(o, r)


def test_grouped_mixed_dtypes_and_ints_still_exact(no_session):
    # int chunks keep the assembly // semantics through the batched path
    xs = {"f": np.random.RandomState(0).randn(8, 100).astype(np.float32),
          "i": np.arange(8 * 40, dtype=np.int32).reshape(8, 40),
          "h": np.random.RandomState(1).randn(8, 100).astype(np.float16)}
    set_config(Config(group_size=1, telemetry_on=False))
    bps.init()
    ref = {n: np.asarray(bps.push_pull(x, n, op="average"))
           for n, x in xs.items()}
    bps.shutdown()

    eng, gate = _gated_engine(Config(group_size=4, telemetry_on=False))
    hs = {n: eng.push_pull_async(x, n, op="average") for n, x in xs.items()}
    gate.set()
    for n, h in hs.items():
        np.testing.assert_array_equal(np.asarray(h.wait()), ref[n])
        assert np.asarray(h.wait()).dtype == xs[n].dtype


def test_batched_program_is_one_module_with_combined_collective():
    # Wire-level proof of "one dispatch executes k chunks": the batched
    # program compiles to ONE XLA module, and XLA's all-reduce combiner
    # merges the k psums into a single variadic all-reduce over a
    # k-tuple — strictly fewer wire operations than k single dispatches,
    # exactly the effect the reference buys with ncclGroupStart/End.
    import jax
    import jax.numpy as jnp

    from byteps_tpu.comm.collectives import _batched_all_reduce_fn
    from byteps_tpu.comm.mesh import CommContext, _build_mesh

    k, n = 4, 256
    comm = CommContext(mesh=_build_mesh(jax.devices()[:8], 1),
                       n_dcn=1, n_ici=8)
    fn = _batched_all_reduce_fn(comm, k, (8, n), jnp.float32,
                                scaled=True, local=False)
    xs = [jax.device_put(jnp.zeros((8, n), jnp.float32),
                         comm.stacked_sharding(extra_dims=1))
          for _ in range(k)]
    hlo = fn.lower(*xs, jnp.float32(0.125)).compile().as_text()
    ars = [ln for ln in hlo.splitlines()
           if "all-reduce(" in ln and "=" in ln
           and "get-tuple-element" not in ln]
    # Exactly ONE variadic all-reduce whose tuple result carries all k
    # chunks — the wire property docs/performance.md cites.  If an XLA
    # upgrade stops combining here, this fails as a canary: the batched
    # path would still be one dispatch but k wire ops, and the doc's
    # claim must be re-measured, not assumed.
    assert len(ars) == 1, ars
    assert ars[0].count(f"f32[{n}]") >= k, ars
