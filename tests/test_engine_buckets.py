"""ISSUE 24 — one push per bucket of leaves.

``byteps_tpu.jax.push_pull(tree)`` hands the tree to
``PushPullEngine.push_pull_tree_async``, which packs runs of consecutive
plain float leaves into a few engine tensors.  Held here: the plan is a
pure function of the tree's signature; what a bucket cannot express goes
per leaf and the step's counters say so; the results, shapes and output
shardings are the per-leaf path's, leaf by leaf, on 1-, 4- and 8-rank
meshes; an epoch change aborts every leaf of a bucket; a warm step
compiles nothing.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import byteps_tpu as bps  # noqa: E402
from byteps_tpu.common.config import Config, set_config  # noqa: E402
from byteps_tpu.common.partitioner import bucket_bounds  # noqa: E402
from byteps_tpu.common.telemetry import counters  # noqa: E402
from byteps_tpu.core import engine as engine_mod  # noqa: E402
from byteps_tpu.fault import membership as mm  # noqa: E402
from byteps_tpu.jax import (DistributedOptimizer, push_pull,  # noqa: E402
                            push_pull_async)

PART = 1 << 16                      # partition_bytes of every engine here
CAP = engine_mod.BUCKET_CAP_PARTITIONS * PART       # 1 MiB
F32, BF16 = "float32", "bfloat16"


@pytest.fixture(autouse=True)
def _fresh_epoch():
    mm._reset_epoch_for_tests()
    yield
    mm._reset_epoch_for_tests()


def _init(ranks=8, **cfg):
    cfg.setdefault("partition_bytes", PART)
    cfg.setdefault("partition_pinned", True)
    set_config(Config(telemetry_on=True, **cfg))
    bps.init(devices=jax.devices()[:ranks])
    return bps.core.api._require()


def _stack(rng, ranks, shape, dtype=np.float32, integer=True):
    if integer:
        x = rng.randint(-8, 9, size=(ranks,) + tuple(shape))
    else:
        x = rng.standard_normal((ranks,) + tuple(shape))
    return jnp.asarray(x.astype(np.float32)).astype(dtype)


def _model_tree(ranks, integer=True, seed=0):
    """Three identical "layers" (a 256 KiB kernel, a 4 KiB bias, a scalar),
    a leaf over the cap, a bf16 pair, an int leaf and small stragglers:
    every kind of leaf the plan tells apart."""
    rng = np.random.RandomState(seed)

    def mk(shape, dtype=np.float32):
        return _stack(rng, ranks, shape, dtype, integer)

    def layer():
        return {"bias": mk((16, 64)), "kernel": mk((256, 16, 16)),
                "gain": mk(())}

    return {"l0": layer(), "l1": layer(), "l2": layer(),
            "m_big": mk((1024, 320)),             # 1.25 MiB: over the cap
            "n_half": {"a": mk((64,), jnp.bfloat16),
                       "b": mk((33, 7), jnp.bfloat16)},
            "o_int": jnp.asarray(rng.randint(0, 64, size=(ranks, 24)),
                                 jnp.int32),
            "p": mk((1024,)), "q": mk((8, 3))}


def _last_step(eng):
    eng.step_stats.flush()
    return eng.step_stats.history()[-1]


# -- the plan ---------------------------------------------------------------

LAYER = [((16, 64), F32), ((256, 16, 16), F32), ((), F32)]
LAYER_B = [4096, 262144, 4]


@pytest.mark.parametrize("case,sigs,nbytes,want", [
    ("greedy_fills_to_the_cap",
     [((8,), F32)] * 5, [400] * 5, [(0, 5)]),
    ("cap_cuts_a_run",
     [((8,), F32)] * 5, [CAP // 2 - 8] * 5, [(0, 2), (2, 4), (4, 5)]),
    ("leaf_at_the_cap_goes_alone",
     [((8,), F32), ((CAP // 4,), F32), ((8,), F32), ((8,), F32)],
     [32, CAP, 32, 32], [(0, 1), (2, 4)]),
    ("leaf_over_the_cap_goes_alone",
     [((8,), F32), ((8,), F32), ((CAP,), F32), ((8,), F32)],
     [32, 32, 4 * CAP, 32], [(0, 2), (3, 4)]),
    ("mixed_dtypes_cut",
     [((8,), F32), ((8,), F32), ((8,), BF16), ((8,), BF16), ((8,), F32)],
     [32, 32, 16, 16, 32], [(0, 2), (2, 4), (4, 5)]),
    ("unbucketable_leaf_cuts",
     [((8,), F32), ((8,), F32), None, ((8,), F32), ((8,), F32)],
     [32, 32, 96, 32, 32], [(0, 2), (3, 5)]),
    ("repeated_layers_make_identical_buckets",
     # 3 layers of 0.6 cap each: greedy would put 1 2/3 layers in the
     # first bucket; the repeat closes each at its layer
     [((16, 64), F32), ((CAP,), F32), ((), F32)] * 3 + [((5,), F32)],
     [4096, 6 * CAP // 10, 4] * 3 + [20], [(0, 3), (3, 6), (6, 10)]),
    ("a_repeat_under_half_the_cap_does_not_cut",
     LAYER * 3, LAYER_B * 3, [(0, 9)]),
    ("empty_tree", [], [], []),
])
def test_bucket_bounds(case, sigs, nbytes, want):
    assert bucket_bounds(sigs, nbytes, CAP) == want
    # pure: the same arguments, the same runs
    assert bucket_bounds(list(sigs), list(nbytes), CAP) == want


def _plan_summary(eng, tree, prefix="g"):
    leaves = jax.tree_util.tree_leaves(tree)
    names = bps.jax._leaf_names(tree, prefix)
    items, index = eng._plan_tree(leaves, names)
    return ([(a, b, None if bk is None else
              (bk.name, bk.shapes, bk.dtype.name, bk.n_pad, bk.shard_out))
             for a, b, bk in items], list(index))


@pytest.mark.parametrize("how", ["twice", "fresh_engine",
                                 "shuffled_timing"])
def test_plan_is_a_pure_function_of_the_signature(how):
    """Same tree, same plan: asked twice, in a fresh engine, and in an
    engine whose planner is exploring chunk sizes under jittered timing
    (its tuned partition size must not reach the cap)."""
    eng = _init()
    try:
        tree = _model_tree(bps.size())
        first = _plan_summary(eng, tree)
        if how == "twice":
            again = _plan_summary(eng, tree)
    finally:
        bps.shutdown()
    if how != "twice":
        tuned = how == "shuffled_timing"
        eng = _init(partition_pinned=not tuned)
        try:
            if tuned:
                assert eng.planner.active
                rng = np.random.RandomState(1)
                for i in range(6):      # the planner samples and moves
                    time.sleep(float(rng.uniform(0, 0.01)))
                    push_pull(tree, "g")
            again = _plan_summary(eng, tree)
        finally:
            bps.shutdown()
    assert again == first
    items, index = first
    # l0 / l1 / l2 -> one bucket (under half the cap each: no early
    # cut), m_big alone, the bf16 pair, the int alone, p + q
    assert [(a, b, bk is not None) for a, b, bk in items] == [
        (0, 9, True), (9, 10, False), (10, 12, True), (12, 13, False),
        (13, 15, True)]
    assert index[:9] == [(0, j) for j in range(9)]
    assert index[9] == (1, None) and index[12] == (3, None)
    assert items[0][2][0] == "g['l0']['bias']+8"


@pytest.mark.parametrize("warm", ["plan_only", "after_a_step"])
def test_priority_follows_the_plans_item_order(warm):
    """Registry keys -- priority is ``-declared_key`` -- are reserved in
    the order of the plan's pushes, which is flattening order: a leaf
    that goes alone (over the cap, an int) gets the key of its place in
    the tree, not one ahead of every bucket."""
    eng = _init()
    try:
        tree = _model_tree(bps.size())
        leaves = jax.tree_util.tree_leaves(tree)
        names = bps.jax._leaf_names(tree, "g")
        if warm == "plan_only":
            items, _ = eng._plan_tree(leaves, names)
        else:
            push_pull(tree, "g")
            (items, _), = eng._tree_plans.values()
        pushed = [names[a] if bk is None else bk.name for a, _, bk in items]
        assert pushed == ["g['l0']['bias']+8", "g['m_big']",
                          "g['n_half']['a']+1", "g['o_int']", "g['p']+1"]
        keys = [eng.registry.get(n).declared_key for n in pushed]
        assert keys == sorted(keys) and len(set(keys)) == len(keys), keys
        assert eng.registry.names_in_declaration_order() == pushed
    finally:
        bps.shutdown()


# -- equality with the per-leaf path ---------------------------------------

def _assert_same(got_tree, want_tree, exact, ranks, shardings=True):
    got = jax.tree_util.tree_flatten_with_path(got_tree)[0]
    want = jax.tree_util.tree_leaves(want_tree)
    assert len(got) == len(want)
    for (path, g), w in zip(got, want):
        where = jax.tree_util.keystr(path)
        assert g.shape == w.shape and g.dtype == w.dtype, where
        if shardings:
            assert g.sharding == w.sharding, (where, g.sharding, w.sharding)
        g64 = np.asarray(g.astype(jnp.float32), np.float64)
        w64 = np.asarray(w.astype(jnp.float32), np.float64)
        if exact:
            np.testing.assert_array_equal(g64, w64, err_msg=where)
        else:
            # another order of one R-way float32 sum
            eps = np.finfo(np.float32).eps if g.dtype != jnp.bfloat16 \
                else 2.0 ** -8
            np.testing.assert_allclose(
                g64, w64, rtol=0, atol=2 * ranks * eps
                * max(1.0, float(np.abs(w64).max())), err_msg=where)


@pytest.mark.parametrize("data", ["integer", "normal"])
@pytest.mark.parametrize("op", ["average", "sum"])
@pytest.mark.parametrize("ranks", [1, 4, 8])
def test_tree_equals_per_leaf_path(ranks, op, data):
    """``push_pull(tree)`` against one ``push_pull_async`` per leaf (the
    same engine, another prefix): values (exact on integer-valued data),
    shapes, dtypes and output shardings, leaf by leaf; and the step's
    counters name what rode a bucket."""
    eng = _init(ranks)
    try:
        tree = _model_tree(ranks, integer=data == "integer")
        treedef = jax.tree_util.tree_structure(tree)
        handles = push_pull_async(tree, "leafwise", op=op)
        assert len(handles) == treedef.num_leaves        # one per leaf
        want = jax.tree_util.tree_unflatten(
            treedef, [h.wait() for h in handles])
        before = _last_step(eng)
        assert (before.buckets, before.bucketed_leaves) == (0, 0)
        for _ in range(2):          # second call: the cached plan
            got = push_pull(tree, "bucketed", op=op)
            _assert_same(got, want, data == "integer", ranks)
        step = _last_step(eng)
        assert (step.pushes, step.buckets, step.bucketed_leaves) == (
            5, 3, 13), step
    finally:
        bps.shutdown()


@pytest.mark.parametrize("mesh", ["dcn2x4", "ranks6_parts_mode",
                                  "tiny_tree_single_chunk"])
def test_tree_equals_per_leaf_path_odd_meshes(mesh):
    """A two-level mesh (hierarchical collectives), a mesh the column
    layout cannot express (multi-chunk parts mode) and a tree under
    ``buffer_min_bytes`` (one all-reduce of the whole packed tree)."""
    if mesh == "dcn2x4":
        eng = _init(8, dcn_size=2)
    elif mesh == "ranks6_parts_mode":
        eng = _init(6)
    else:
        eng = _init(8, partition_bytes=1 << 22)
    try:
        ranks = bps.size()
        tree = _model_tree(ranks)
        if mesh == "tiny_tree_single_chunk":
            tree = {k: tree[k] for k in ("l0", "p", "q")}
        treedef = jax.tree_util.tree_structure(tree)
        want = jax.tree_util.tree_unflatten(treedef, [
            h.wait() for h in push_pull_async(tree, "leafwise")])
        got = push_pull(tree, "bucketed")
        # (a small leaf pushed alone over two levels comes back in
        # whatever layout its collective left it, ici-sharded; from a
        # bucket it is replicated)
        _assert_same(got, want, True, ranks, shardings=mesh != "dcn2x4")
        push_pull(tree, "bucketed")     # a step of buckets only
        step = _last_step(eng)
        assert step.buckets >= 1 and step.bucketed_leaves >= 5, step
        if mesh == "tiny_tree_single_chunk":
            assert (step.pushes, step.buckets, step.chunks) == (1, 1, 1)
    finally:
        bps.shutdown()


def test_host_leaves_are_staged_like_the_per_leaf_path():
    """numpy leaves (no sharding at all) ride a bucket too."""
    _init(8)
    try:
        rng = np.random.RandomState(3)
        tree = {"a": rng.randint(-4, 5, (8, 300)).astype(np.float32),
                "b": rng.randint(-4, 5, (8, 7, 9)).astype(np.float32)}
        got = push_pull(tree, "host", op="sum")
        for k in tree:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          tree[k].sum(axis=0))
    finally:
        bps.shutdown()


# -- fallbacks --------------------------------------------------------------

def _float_tree(ranks, seed=5):
    rng = np.random.RandomState(seed)
    return {f"w{i}": _stack(rng, ranks, (2048,)) for i in range(4)}


@pytest.mark.parametrize("why", ["int_leaf", "compression_declared",
                                 "compression_pushed_later",
                                 "debug_sample_tensor", "compress_autotune",
                                 "sharded_update"])
def test_fallbacks_take_the_per_leaf_path(why):
    """What a bucket cannot express goes per leaf, and
    ``bucketed_leaves`` says so."""
    cfg = {"debug_sample_tensor": {"debug_sample_tensor": "w1"},
           "compress_autotune": {"compress_autotune": True},
           "sharded_update": {"sharded_update": True}}.get(
               why, {"min_compress_bytes": 0})
    eng = _init(8, **cfg)
    try:
        ranks = bps.size()
        tree = _float_tree(ranks)
        want = {k: np.asarray(v).mean(axis=0) for k, v in tree.items()}
        onebit = {"compressor": "onebit"}
        if why == "int_leaf":
            tree["w1"] = tree["w1"].astype(jnp.int32)
            want["w1"] = np.asarray(tree["w1"]).sum(axis=0) // ranks
            expect = (3, 1, 2)      # w0 | w1 (int) | w2 + w3
        elif why == "compression_declared":
            eng.declare_tensor("g['w1']", (2048,), np.float32,
                               compression=onebit)
            expect = (3, 1, 2)
        elif why == "compression_pushed_later":
            # the plan made by the first call is dropped when a codec
            # is declared for one of its names
            push_pull(tree, "g")
            assert _last_step(eng).bucketed_leaves == 4
            eng.push_pull(tree["w2"], "g['w2']", compression=onebit)
            assert not eng._tree_plans
            got = push_pull(tree, "g")
            (items, _), = eng._tree_plans.values()
            assert [(a, b, bk is not None) for a, b, bk in items] == [
                (0, 2, True), (2, 3, False), (3, 4, False)]
            for k in ("w0", "w1", "w3"):
                np.testing.assert_allclose(np.asarray(got[k]), want[k],
                                           rtol=1e-6, err_msg=k)
            return
        elif why == "sharded_update":
            params = {k: jnp.zeros((2048,), jnp.float32) for k in tree}
            opt = DistributedOptimizer(optax.sgd(1.0))
            state = opt.init(params)
            updates, _ = opt.update(tree, state, params)
            for k in tree:
                np.testing.assert_allclose(np.asarray(updates[k]),
                                           -want[k], rtol=1e-6)
            step = _last_step(eng)
            assert (step.pushes, step.buckets, step.bucketed_leaves) == (
                4, 0, 0), step
            return
        else:
            expect = (4, 0, 0)
        got = push_pull(tree, "g")
        step = _last_step(eng)
        assert (step.pushes, step.buckets, step.bucketed_leaves) == expect, (
            step)
        for k in tree:
            if why.startswith("compression") and k in ("w1", "w2"):
                continue            # lossy by design
            if why == "compress_autotune":
                continue            # the ladder may pick a lossy codec
            np.testing.assert_allclose(np.asarray(got[k]), want[k],
                                       rtol=1e-6, err_msg=k)
    finally:
        bps.shutdown()


# -- a bucket is a tensor: epoch guard, compile-once, the optimizer --------

def test_epoch_change_aborts_every_leaf_of_a_bucket():
    eng = _init(8)
    try:
        tree = _float_tree(bps.size())
        leaves = jax.tree_util.tree_leaves(tree)
        names = bps.jax._leaf_names(tree, "g")
        eng.pause_dispatch()
        pushed = eng.push_pull_tree_async(leaves, names)
        assert len(pushed.handles) == 1 and len(pushed.index) == 4
        mm.advance_epoch()
        eng.resume_dispatch()
        # every leaf lies in the one handle that the epoch guard failed
        assert [k for k, _ in pushed.index] == [0] * 4
        with pytest.raises(RuntimeError, match="stale membership epoch"):
            pushed.handles[0].wait(timeout=20)
        with pytest.raises(RuntimeError, match="stale membership epoch"):
            pushed.wait(timeout=20)
        assert counters.get("membership.stale_chunks_dropped") >= 1
        # the new epoch's pushes flow
        got = push_pull(tree, "g", op="sum")
        np.testing.assert_array_equal(
            np.asarray(got["w0"]), np.asarray(tree["w0"]).sum(axis=0))
    finally:
        bps.shutdown()


def _unit_tree(ranks, dtype=np.float32, seed=11):
    """A bucket of 13 chunks (four distinct leaves, 12.6 partitions) and
    a leaf of 31 (30.5 partitions: over the cap, so it goes alone and
    makes two dispatch units, 16 + 15 chunks)."""
    rng = np.random.RandomState(seed)
    per = PART // np.dtype(jnp.dtype(dtype)).itemsize   # elements a chunk
    return {"a": _stack(rng, ranks, (3, per), dtype),
            "b": _stack(rng, ranks, (4, per), dtype),
            "c": _stack(rng, ranks, (5, per), dtype),
            "d": _stack(rng, ranks, (10, 1000), dtype),
            "e_big": _stack(rng, ranks, (61, per // 2), dtype)}


def test_a_bucket_is_one_dispatch_and_a_31_chunk_tensor_two():
    eng = _init(8)
    try:
        tree = _unit_tree(8)
        for _ in range(2):
            push_pull(tree, "g")
        step = _last_step(eng)
        assert (step.pushes, step.buckets, step.bucketed_leaves) == (2, 1, 4)
        assert (step.chunks, step.dispatches, step.whole_units) == (
            13 + 31, 1 + 2, 1), step
        assert bps.metrics_snapshot()["step"]["whole_units"] == 1
        widths = sorted(k[1] for k in eng.comm.jit_cache
                        if k[0] == "chunk_scatter")
        per = PART // 4 // 8                    # columns a chunk, 8 ranks
        # the bucket's whole row, and the leaf's 16 and 15 chunks (the
        # tail chunk is half a partition)
        assert widths == [12 * per + 10_000 // 8, 14 * per + per // 2,
                          16 * per], widths
    finally:
        bps.shutdown()


def _chunked_engine(ranks, **cfg):
    """The parent's dispatch, through the programs it had: one chunk a
    unit -- and one chunk in flight.  Dozens of collective programs
    queued at once can starve XLA:CPU's thread pool (one thread a
    virtual device: a later program's device takes the thread an earlier
    one's still needs, and the rendezvous aborts the process after 40
    s; seen on the DCN mesh, on the parent too at group_size 1)."""
    eng = _init(ranks, scheduling_credit=PART, **cfg)
    eng._one_chunk_units = True
    return eng


@pytest.mark.parametrize("dtype", [F32, BF16, "int32"])
@pytest.mark.parametrize("mesh", ["ranks1", "ranks2", "ranks4", "ranks8",
                                  "dcn2x4", "ranks6_parts_mode"])
def test_whole_range_units_equal_the_chunked_path_bit_for_bit(mesh, dtype):
    """A unit's program reduces every value over the same ranks in the
    same order as the chunk programs it replaces: normal data (sums that
    round), a bucket and a two-unit leaf, on every mesh these files
    build.  (The int leaves go alone: three more multi-unit tensors;
    six ranks take parts mode, which the rule leaves alone.)"""
    ranks = int(mesh[5]) if mesh.startswith("ranks") else 8
    cfg = {"dcn_size": 2} if mesh == "dcn2x4" else {}
    rng = np.random.RandomState(17)
    if dtype == "int32":
        tree = {k: jnp.asarray(rng.randint(-1000, 1000, v.shape), jnp.int32)
                for k, v in _unit_tree(ranks).items()}
    else:
        tree = jax.tree.map(
            lambda v: jnp.asarray(rng.standard_normal(v.shape),
                                  jnp.float32).astype(dtype),
            _unit_tree(ranks))
    got = {}
    for how in ("chunked", "units"):
        eng = (_chunked_engine if how == "chunked" else _init)(ranks, **cfg)
        try:
            out = push_pull(tree, "g")
            got[how] = (jax.tree.map(np.asarray, out),
                        _last_step(eng))
        finally:
            bps.shutdown()
    for k in tree:
        assert got["units"][0][k].dtype == got["chunked"][0][k].dtype
        np.testing.assert_array_equal(
            got["units"][0][k].view(np.uint8),
            got["chunked"][0][k].view(np.uint8), err_msg=k)
    chunked, units = got["chunked"][1], got["units"][1]
    assert chunked.chunks == units.chunks
    if mesh != "ranks6_parts_mode":
        assert chunked.dispatches == chunked.chunks
        assert 4 * units.dispatches < chunked.dispatches
        assert units.whole_units > chunked.whole_units


def test_second_step_compiles_nothing():
    """Pack, unpack and one program a dispatch unit are compiled when a
    bucket is first pushed (a leaf that goes alone: when the plan is
    made); every later step finds every program in the cache."""
    eng = _init(8)
    try:
        tree = _model_tree(bps.size())
        push_pull(tree, "g")
        assert counters.get("engine.aot_compiled") >= 2     # pack, unpack
        assert counters.get("engine.aot_compile_failed") == 0
        misses = counters.get("engine.compile_cache_miss")
        programs = len(eng.comm.jit_cache)
        for _ in range(3):
            push_pull(tree, "g")
        assert counters.get("engine.compile_cache_miss") == misses
        assert len(eng.comm.jit_cache) == programs
    finally:
        bps.shutdown()


def test_the_step_after_the_plan_compiles_nothing_whatever_the_timing():
    """Twenty fresh engines: the first step declares the plan and warms
    exactly the programs the unit rule can form, so the second step --
    dispatcher, syncer and caller racing as they will -- adds none."""
    tree = _unit_tree(8)
    for attempt in range(20):
        eng = _init(8)
        try:
            push_pull(tree, "g")
            misses = counters.get("engine.compile_cache_miss")
            programs = set(eng.comm.jit_cache)
            push_pull(tree, "g")
            assert set(eng.comm.jit_cache) == programs, attempt
            assert counters.get("engine.compile_cache_miss") == misses
            step = _last_step(eng)
            assert (step.dispatches, step.whole_units) == (3, 1), attempt
        finally:
            bps.shutdown()


def test_identical_buckets_share_their_programs():
    """Layers over half the cap close their own buckets, and the
    buckets' pack / unpack programs are one pair."""
    eng = _init(8)
    try:
        rng = np.random.RandomState(9)
        ranks = bps.size()
        tree = {f"layer_{i}": {"k": _stack(rng, ranks, (640, 256)),
                               "b": _stack(rng, ranks, (256,))}
                for i in range(4)}
        got = push_pull(tree, "g", op="sum")
        step = _last_step(eng)
        assert (step.buckets, step.bucketed_leaves) == (4, 8), step
        kinds = [k[0] for k in eng.comm.jit_cache
                 if k[0].startswith("bucket_")]
        assert sorted(kinds) == ["bucket_pack", "bucket_unpack"]
        for name, layer in tree.items():
            for k, v in layer.items():
                np.testing.assert_array_equal(
                    np.asarray(got[name][k]), np.asarray(v).sum(axis=0))
    finally:
        bps.shutdown()


@pytest.mark.parametrize("bpps", [1, 2])
def test_distributed_optimizer_steps_through_buckets(bpps):
    """The optimizer's trajectory over bucketed gradients is the plain
    optax one on the float32 mean."""
    eng = _init(8)
    try:
        ranks = bps.size()
        params = {k: jnp.ones(v.shape[1:], jnp.float32)
                  for k, v in _float_tree(ranks).items()}
        tx = optax.adam(1e-2)
        opt = DistributedOptimizer(tx, backward_passes_per_step=bpps)
        state, ref_state, ref = opt.init(params), tx.init(params), params
        for step in range(3):
            grads = _float_tree(ranks, seed=step)
            for _ in range(bpps):
                updates, state = opt.update(grads, state, params)
                params = optax.apply_updates(params, updates)
            mean = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
            ref_up, ref_state = tx.update(mean, ref_state, ref)
            ref = optax.apply_updates(ref, ref_up)
        for k in params:
            np.testing.assert_allclose(np.asarray(params[k]),
                                       np.asarray(ref[k]), rtol=1e-5,
                                       atol=1e-7, err_msg=k)
        assert _last_step(eng).bucketed_leaves == 4
    finally:
        bps.shutdown()
