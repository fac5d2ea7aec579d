"""Dispatch-amortization tests (round-4 VERDICT task 3): the dispatcher
pops up to `group_size` eligible chunks and executes them as the fewest
XLA programs — one chunk-scatter program per contiguous buffer run, one
batched collective per run of equal-shape small tensors — with results
bit-identical to ungrouped dispatch and provably fewer dispatches.

The reference amortizes per-chunk launch overhead the same way with NCCL
group batching (nccl_manager.cc:130-134, BYTEPS_NCCL_GROUP_SIZE); here a
"group" is one jitted program instead of one ncclGroupStart/End bracket.
"""

import numpy as np
import pytest

import byteps_tpu as bps
from byteps_tpu.common import Config
from byteps_tpu.common.config import set_config
from byteps_tpu.core.engine import _plan_batch
from byteps_tpu.common.types import ChunkTask


# ---------------------------------------------------------------- planning


class _FakePending:
    def __init__(self, use_buffer):
        self.use_buffer = use_buffer


class _Arr:
    def __init__(self, shape, dtype="float32"):
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self.ndim = len(shape)


def _task(name, key, off=0, ln=64, pending=None, data=None, scale=None):
    t = ChunkTask(name=name, key=key, priority=0, version=0,
                  offset_elems=off, num_elems=ln, nbytes=ln * 4,
                  total_parts=1, data=data, scale=scale, pending=pending)
    return t


def test_plan_merges_contiguous_buffer_run():
    p = _FakePending(use_buffer=True)
    batch = [_task("w", k, off=k * 64, pending=p) for k in range(8)]
    units = _plan_batch(batch)
    assert [(k, len(u)) for k, u in units] == [("run", 8)]


def test_plan_splits_noncontiguous_and_foreign_runs():
    p1, p2 = _FakePending(True), _FakePending(True)
    batch = [_task("a", 0, off=0, pending=p1),
             _task("a", 1, off=64, pending=p1),
             _task("b", 2, off=0, pending=p2),      # different tensor
             _task("a", 3, off=192, pending=p1)]    # gap: not contiguous
    units = _plan_batch(batch)
    assert [(k, len(u)) for k, u in units] == [
        ("run", 2), ("run", 1), ("run", 1)]


def test_plan_groups_equal_shape_parts_tasks():
    d = _Arr((8, 64))
    batch = [_task(f"g{i}", i, data=d, scale=0.125) for i in range(5)]
    units = _plan_batch(batch)
    assert [(k, len(u)) for k, u in units] == [("group", 5)]
    # a width-1 "group" rides the single-task path (its program is
    # already cached) instead of compiling a k=1 batched program
    units = _plan_batch(batch[:1])
    assert [(k, len(u)) for k, u in units] == [("single", 1)]


def test_plan_never_groups_incompatible_neighbors():
    batch = [_task("a", 0, data=_Arr((8, 64)), scale=0.125),
             _task("b", 1, data=_Arr((8, 32)), scale=0.125),   # shape
             _task("c", 2, data=_Arr((8, 32)), scale=None),    # scale
             _task("d", 3, data=_Arr((8, 32), "int32"))]       # dtype
    units = _plan_batch(batch)
    assert [k for k, _ in units] == ["single"] * 4


def test_plan_order_preserved_across_units():
    # priority order must survive planning: units come out in batch order
    p = _FakePending(True)
    d = _Arr((8, 16))
    batch = [_task("hi", 0, data=d, scale=None),
             _task("bulk", 1, off=0, pending=p),
             _task("bulk", 2, off=64, pending=p),
             _task("lo", 3, data=d, scale=None)]
    kinds = [(k, [t.name for t in u]) for k, u in _plan_batch(batch)]
    assert kinds == [("single", ["hi"]), ("run", ["bulk", "bulk"]),
                     ("single", ["lo"])]


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


def test_grouped_buffer_tensor_fewer_dispatches_bitexact(no_session):
    # 1 MiB f32 per rank / 4 KiB chunks = 256 column slabs; group_size=8
    # must execute them as 32 programs of 8 contiguous slabs and match
    # the ungrouped result bit for bit.
    rng = np.random.RandomState(7)
    x = rng.randn(8, 1 << 18).astype(np.float32)

    eng, gate = _gated_engine(Config(partition_bytes=4096, group_size=1,
                                     telemetry_on=False))
    h = eng.push_pull_async(x, "bulk", op="average")
    gate.set()
    ref = np.asarray(h.wait())
    base_stats = dict(eng.stats)
    bps.shutdown()

    eng, gate = _gated_engine(Config(partition_bytes=4096, group_size=8,
                                     telemetry_on=False))
    h = eng.push_pull_async(x, "bulk", op="average")
    gate.set()
    out = np.asarray(h.wait())
    grouped_stats = dict(eng.stats)

    np.testing.assert_array_equal(out, ref)
    assert base_stats["chunks"] == grouped_stats["chunks"] == 256
    assert base_stats["dispatches"] == 256         # group_size=1: one each
    assert grouped_stats["dispatches"] == 32       # one program per 8 slabs


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
