"""Sharded weight update fused into push_pull (ISSUE 20).

What is pinned here:

- the float32 replay proof: ``sharded_update=True`` reproduces the
  unsharded engine trajectory **bit-for-bit** on the virtual 8-device
  mesh, on both the parts fallback and the buffer-mode hot path, through
  the ``DistributedOptimizer`` adapter, and ACROSS one elastic shrink
  (8 -> 4 via suspend/resume — the slot re-pad re-shards optimizer
  state);
- wire accounting: the per-leg ``wire_bytes{leg=push|pull}`` split
  (ISSUE satellite a), steady-state sharded wire-bytes/step <= 0.6x the
  unsharded figure (push N + pull N/R vs push N + pull N), and
  ``StepStats.wire_bytes_per_step``;
- the quantized parameter leg: reported separately
  (``compression.param_wire_bytes``), gated by the golden-error
  ceiling at declare time;
- the adapter contracts: ``init`` returns ``optax.EmptyState`` (state
  lives in the engine), declare-time validation, config validation of
  the BYTEPS_SHARDED_UPDATE knob family;
- shard-published serving cuts: ``ServingTier.cut()`` under sharded
  update publishes per-owner slices (never a full-parameter buffer —
  ``slot.params`` is monkeypatched to raise during the cut) and the
  reassembled read is bitwise the unsharded trajectory.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import byteps_tpu as bps
from byteps_tpu import jax as bpsjax
from byteps_tpu.comm.mesh import CommContext, _build_mesh
from byteps_tpu.common.config import Config
from byteps_tpu.common.telemetry import counters
from byteps_tpu.core.engine import PushPullEngine
from byteps_tpu.jax.async_opt import AsyncDistributedOptimizer
from byteps_tpu.server import KVStore

SHAPE = (256, 33)
N = int(np.prod(SHAPE))
R = 8


def _comm():
    devices = jax.devices()
    return CommContext(mesh=_build_mesh(devices, 1), n_dcn=1,
                       n_ici=len(devices))


def _unsharded_replay(comm, tx, p0, grads, **cfg_kw):
    """The reference arm: engine push_pull + caller-side eager optax —
    the trajectory the unsharded DistributedOptimizer produces.  (The
    merged gradient carries collective rounding, so comparing against
    raw-gradient optax would be vacuously loose: both arms must
    integrate the ENGINE's merge.)"""
    eng = PushPullEngine(comm, Config(**cfg_kw))
    eng.declare_tensor("w", p0.shape, np.float32, op="average", local=True)
    params = jnp.asarray(p0)
    state = tx.init(params)
    push0 = counters.get("wire_bytes", leg="push")
    pull0 = counters.get("wire_bytes", leg="pull")
    for g in grads:
        red = eng.push_pull_local(g, "w", op="average")
        upd, state = tx.update(jnp.asarray(red), state, params)
        params = optax.apply_updates(params, upd)
    wire = (counters.get("wire_bytes", leg="push") - push0,
            counters.get("wire_bytes", leg="pull") - pull0)
    eng.shutdown(wait=True)
    return np.asarray(params), wire


def _sharded_replay(comm, tx, p0, grads, **cfg_kw):
    eng = PushPullEngine(comm, Config(sharded_update=True, **cfg_kw))
    eng.declare_update("w", p0.shape, np.float32, tx=tx, init_value=p0)
    params = jnp.asarray(p0)
    push0 = counters.get("wire_bytes", leg="push")
    pull0 = counters.get("wire_bytes", leg="pull")
    for g in grads:
        upd = eng.push_pull_update(g, "w")
        params = optax.apply_updates(params, jnp.asarray(upd))
    wire = (counters.get("wire_bytes", leg="push") - push0,
            counters.get("wire_bytes", leg="pull") - pull0)
    master_ok = np.array_equal(eng.update_slots["w"].params(),
                               np.asarray(params))
    stats = eng.step_stats.last()
    eng.shutdown(wait=True)
    return np.asarray(params), wire, master_ok, stats


def _data(seed=0, steps=5, shape=SHAPE):
    rng = np.random.RandomState(seed)
    p0 = rng.randn(*shape).astype(np.float32)
    grads = [rng.randn(*shape).astype(np.float32) for _ in range(steps)]
    return p0, grads


def test_replay_bitexact_parts_path():
    comm = _comm()
    tx = optax.adam(1e-2)
    p0, grads = _data()
    ref, _ = _unsharded_replay(comm, tx, p0, grads)
    got, _, master_ok, _ = _sharded_replay(comm, tx, p0, grads)
    assert np.array_equal(ref, got)
    assert master_ok  # the engine-resident master IS the trajectory


def test_replay_bitexact_buffered_and_wire_ratio():
    """The buffer-mode hot path: bitexact AND the acceptance wire bound
    — sharded steady state ships push N + pull N/R, <= 0.6x the
    unsharded push N + pull N."""
    comm = _comm()
    tx = optax.adam(1e-2)
    p0, grads = _data(seed=1)
    ref, (push_u, pull_u) = _unsharded_replay(comm, tx, p0, grads,
                                              partition_bytes=4096)
    got, (push_s, pull_s), master_ok, stats = _sharded_replay(
        comm, tx, p0, grads, partition_bytes=4096, telemetry_on=True)
    assert np.array_equal(ref, got)
    assert master_ok
    assert push_s == push_u                     # push leg unchanged
    assert pull_s * R == pull_u                 # pull leg is 1/R exactly
    ratio = (push_s + pull_s) / (push_u + pull_u)
    assert ratio <= 0.6, ratio
    # ISSUE satellite a: the per-step figure lands in StepStats too
    assert stats is not None
    assert stats.wire_bytes_per_step == N * 4 + (N * 4) // R


def test_fused_mode_close_but_single_dispatch():
    """BYTEPS_SHARDED_UPDATE_FUSED: one fused program per step — the
    documented trade is ulp-level FMA-contraction drift, not equality."""
    comm = _comm()
    tx = optax.adam(1e-2)
    p0, grads = _data(seed=2, steps=3)
    ref, _ = _unsharded_replay(comm, tx, p0, grads)
    base = counters.get("engine.sharded_updates")
    got, _, _, _ = _sharded_replay(comm, tx, p0, grads,
                                   sharded_update_fused=True)
    assert counters.get("engine.sharded_updates") - base == len(grads)
    np.testing.assert_allclose(ref, got, rtol=0, atol=1e-6)


def test_adapter_parity_and_elastic_shrink():
    """DistributedOptimizer(sharded_update=True) == the optax update run
    op by op on the reduced gradients, bit-for-bit over 4 steps
    INCLUDING an 8 -> 4 suspend/resume at step 2: the suspend stash ->
    declare_update(restore=) re-pad re-shards the owner-resident
    optimizer state with no lost or doubled update.  The unsharded mode
    COMPILES the update (one donating program), so it follows the same
    trajectory to float32 rounding, as any jitted update does."""
    rng = np.random.RandomState(1)
    params = {"w": rng.randn(64, 33).astype(np.float32),
              "b": rng.randn(33).astype(np.float32)}
    grads_per_step = [
        {"w": rng.randn(8, 64, 33).astype(np.float32),
         "b": rng.randn(8, 33).astype(np.float32)} for _ in range(4)]

    class Eager:
        """push_pull, then the update op by op: what the sharded mode
        must reproduce to the bit."""
        tx = optax.adam(1e-2)
        init = staticmethod(tx.init)

        def update(self, g, s, p):
            return self.tx.update(bpsjax.push_pull(g, "g"), s, p)

    def run(mode, shrink_at=None):
        sharded = mode == "sharded"
        bps.init(config=Config(sharded_update=sharded),
                 devices=jax.devices())
        opt = Eager() if mode == "eager" else bpsjax.DistributedOptimizer(
            optax.adam(1e-2), name_prefix="g", sharded_update=sharded)
        p = jax.tree.map(jnp.asarray, params)
        s = opt.init(p)
        if sharded:
            assert isinstance(s, optax.EmptyState)
        for i, g in enumerate(grads_per_step):
            if shrink_at is not None and i == shrink_at:
                bps.suspend()
                bps.resume(config=Config(sharded_update=sharded),
                           devices=jax.devices()[:4])
            g = jax.tree.map(lambda a: a[: bps.size()], g)
            u, s = opt.update(g, s, p)
            # updates/state are mesh-placed (deferred gather): host-
            # materialize before mixing across the elastic transition
            s = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), s)
            p = jax.tree.map(
                lambda a, b: optax.apply_updates(
                    jnp.asarray(np.asarray(a)), jnp.asarray(np.asarray(b))),
                p, u)
        out = jax.tree.map(np.asarray, p)
        bps.shutdown()
        return out

    for shrink_at in (None, 2):
        ref = run("eager", shrink_at)
        got = run("sharded", shrink_at)
        compiled = run("compiled", shrink_at)
        for k in ref:
            assert np.array_equal(ref[k], got[k]), (shrink_at, k)
            np.testing.assert_allclose(compiled[k], ref[k], rtol=1e-6,
                                       atol=1e-7, err_msg=f"{shrink_at} {k}")


def test_async_adapter_parity():
    """AsyncDistributedOptimizer sharded mode: no gradient collective,
    so the async trajectory is bitwise the unsharded async one."""
    rng = np.random.RandomState(2)
    params = {"w": rng.randn(32, 17).astype(np.float32)}
    grads = [{"w": rng.randn(32, 17).astype(np.float32)}
             for _ in range(3)]

    def run(sharded):
        bps.init(config=Config(sharded_update=sharded),
                 devices=jax.devices())
        opt = AsyncDistributedOptimizer(optax.adam(1e-2), store=KVStore(),
                                        name_prefix="a",
                                        sharded_update=sharded)
        p = jax.tree.map(jnp.asarray, params)
        s = opt.init(p)
        for g in grads:
            p, s = opt.update_and_sync(jax.tree.map(jnp.asarray, g), s, p)
        out = jax.tree.map(np.asarray, p)
        bps.shutdown()
        return out

    ref = run(False)
    got = run(True)
    assert np.array_equal(ref["w"], got["w"])


def test_quantized_param_leg_reported_separately():
    comm = _comm()
    tx = optax.adam(1e-2)
    p0, grads = _data(seed=3, steps=3)
    base = counters.get("compression.param_wire_bytes")
    got, (push_s, pull_s), _, _ = _sharded_replay(
        comm, tx, p0, grads, partition_bytes=4096,
        min_compress_bytes=0, sharded_param_codec="dithering:64")
    param_wire = counters.get("compression.param_wire_bytes") - base
    assert param_wire > 0
    assert pull_s == param_wire       # the pull leg IS the codec payload
    assert pull_s < push_s            # quantized leg beats full precision
    assert not np.array_equal(got, p0)  # the lossy leg still trains


def test_quantized_param_leg_quality_gate():
    comm = _comm()
    eng = PushPullEngine(comm, Config(sharded_update=True,
                                      min_compress_bytes=0,
                                      sharded_param_codec="onebit",
                                      compress_error_ceiling=0.01))
    try:
        with pytest.raises(ValueError, match="quality gate"):
            eng.declare_update("w", SHAPE, np.float32,
                               tx=optax.adam(1e-2))
    finally:
        eng.shutdown(wait=True)


def test_config_validation():
    with pytest.raises(ValueError, match="requires sharded_update"):
        Config(sharded_update_fused=True)
    with pytest.raises(ValueError, match="requires sharded_update"):
        Config(sharded_param_codec="onebit")
    with pytest.raises(ValueError, match="sharded_param_codec"):
        Config(sharded_update=True, sharded_param_codec="a:b:c")
    Config(sharded_update=True, sharded_update_fused=True,
           sharded_param_codec="auto")  # the valid combination


def test_declare_update_validation():
    comm = _comm()
    eng = PushPullEngine(comm, Config())
    try:
        with pytest.raises(ValueError, match="sharded-update mode"):
            eng.declare_update("w", SHAPE, np.float32,
                               tx=optax.adam(1e-2))
    finally:
        eng.shutdown(wait=True)
    eng = PushPullEngine(comm, Config(sharded_update=True))
    try:
        with pytest.raises(ValueError, match="float tensor"):
            eng.declare_update("i", (8,), np.int32, tx=optax.adam(1e-2))
        with pytest.raises(ValueError, match="no sharded-update slot"):
            eng.push_pull_update(np.zeros(SHAPE, np.float32), "nope")
    finally:
        eng.shutdown(wait=True)


def test_adapter_requires_init_before_update():
    bps.init(config=Config(sharded_update=True), devices=jax.devices())
    try:
        opt = bpsjax.DistributedOptimizer(optax.adam(1e-2),
                                          sharded_update=True)
        with pytest.raises(RuntimeError, match="init"):
            opt.update({"w": np.zeros((8, 4), np.float32)},
                       optax.EmptyState())
    finally:
        bps.shutdown()


def test_serving_cut_shard_published():
    """ServingTier.cut() under sharded update: per-owner slices land as
    ring-routed keys with NO full-parameter materialization, and the
    reassembled read is bitwise what an unsharded cut would serve."""
    from byteps_tpu.server.serving_tier import (ServingHostCore,
                                                ServingTier, TierDirectory,
                                                assemble_shard_keys,
                                                inproc_host)
    comm = _comm()
    tx = optax.adam(1e-2)
    p0, grads = _data(seed=4, steps=3)
    ref, _ = _unsharded_replay(comm, tx, p0, grads)

    eng = PushPullEngine(comm, Config(sharded_update=True))
    eng.declare_update("w", p0.shape, np.float32, tx=tx, init_value=p0)
    for g in grads:
        eng.push_pull_update(g, "w")
    slot = eng.update_slots["w"]

    def boom(*a, **k):
        raise AssertionError("full-parameter materialization during cut")

    slot.params = boom
    d = TierDirectory(static_hosts={i: ("127.0.0.1", i + 1)
                                    for i in range(2)})
    for i in range(2):
        inproc_host(ServingHostCore(host_id=i))
    store = KVStore()
    tier = ServingTier(store, directory=d, replicas=1,
                       cut_interval_s=None,
                       update_slots=lambda: eng.update_slots)
    try:
        snap = tier.cut()
        # every published buffer is shard-sized, never full-parameter
        cap = slot.C * np.dtype(np.float32).itemsize
        shard_keys = [k for k in snap.refs if k.startswith("w@shard")
                      and not k.endswith("@shards")]
        assert len(shard_keys) == R
        assert all(snap.refs[k].nbytes <= cap for k in shard_keys)
        # the cut — and a client read through the tier — serve bitwise
        # the unsharded trajectory
        assert np.array_equal(
            assemble_shard_keys(snap.refs.__getitem__, "w"), ref)
        client = tier.client(max_staleness_s=0.0, stale_on_error=False)
        vals = client.pull()
        assert np.array_equal(
            assemble_shard_keys(vals.__getitem__, "w"), ref)
        # steady-state cut with no new steps publishes nothing
        before = counters.get("serve.shard_publishes")
        tier.cut()
        assert counters.get("serve.shard_publishes") == before
    finally:
        tier.close()
        eng.shutdown(wait=True)
