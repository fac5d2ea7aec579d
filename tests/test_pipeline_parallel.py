"""Pipeline parallelism tests on the 8-device CPU mesh.

The make-or-break property: the GPipe schedule is a *schedule*, not a
model — pipelined training from restacked parameters must match plain
single-device GPT training step for step (same loss, same updated
parameters), bubbles and all.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models.gpt import GPT, GPTConfig, lm_loss
from byteps_tpu.parallel.long_context import synthetic_lm_batch
from byteps_tpu.parallel.pipeline import (
    init_pipeline_params, make_dp_pp_train_step, make_pp_mesh,
    pipeline_params_to_gpt, shard_pipeline_params, shard_pp_batch)


def _cfg(num_layers=4):
    return GPTConfig(vocab_size=128, hidden_size=32, num_layers=num_layers,
                     num_heads=4, intermediate_size=64, max_position=64,
                     dtype=jnp.float32)


def test_restack_roundtrip():
    cfg = _cfg()
    rng = jax.random.PRNGKey(0)
    ids = jnp.zeros((1, 8), jnp.int32)
    pp = init_pipeline_params(cfg, rng, ids)
    assert jax.tree.leaves(pp["blocks"])[0].shape[0] == cfg.num_layers
    variables = pipeline_params_to_gpt(cfg, pp)
    ref = GPT(cfg).init(rng, ids)
    for (ka, a), (kb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(ref),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(variables),
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(ka))


@pytest.mark.parametrize("n_pp,microbatches", [(4, 4), (2, 2), (4, 8)])
def test_pp_training_matches_single_device(n_pp, microbatches):
    cfg = _cfg(num_layers=4)
    rng = jax.random.PRNGKey(1)
    # 16: per-dp-shard batch stays divisible by every microbatch count
    batch = synthetic_lm_batch(rng, cfg, batch=16, seq_len=16)
    pp_params = init_pipeline_params(cfg, rng, batch["input_ids"][:1])
    gpt_vars = pipeline_params_to_gpt(cfg, pp_params)
    tx = optax.sgd(0.1)
    model = GPT(cfg)

    @jax.jit
    def ref_step(p, o, b):
        loss, g = jax.value_and_grad(
            lambda q: lm_loss(model.apply(q, b["input_ids"]),
                              b["labels"]))(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    p_ref, o_ref = gpt_vars, tx.init(gpt_vars)
    for _ in range(3):
        p_ref, o_ref, loss_ref = ref_step(p_ref, o_ref, batch)

    mesh = make_pp_mesh(jax.devices()[:8], n_pp=n_pp)  # dp = 8/n_pp
    p_pp = shard_pipeline_params(mesh, pp_params)
    o_pp = jax.jit(tx.init)(p_pp)
    step = make_dp_pp_train_step(mesh, cfg, tx,
                                 num_microbatches=microbatches)
    b_pp = shard_pp_batch(mesh, batch)
    for _ in range(3):
        p_pp, o_pp, loss_pp = step(p_pp, o_pp, b_pp)

    np.testing.assert_allclose(float(loss_pp), float(loss_ref),
                               rtol=1e-5, atol=1e-6)
    got = pipeline_params_to_gpt(cfg, jax.device_get(p_pp))
    for (ka, a), (kb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(p_ref),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(got),
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=str(ka))


def test_pp_blocks_are_stage_sharded():
    cfg = _cfg(num_layers=4)
    mesh = make_pp_mesh(jax.devices()[:8], n_pp=4)
    rng = jax.random.PRNGKey(2)
    pp_params = init_pipeline_params(cfg, rng, jnp.zeros((1, 8), jnp.int32))
    sharded = shard_pipeline_params(mesh, pp_params)
    leaf = jax.tree.leaves(sharded["blocks"])[0]
    assert leaf.addressable_shards[0].data.shape[0] * 4 == leaf.shape[0]
    emb = jax.tree.leaves(sharded["embed"])[0]
    assert emb.addressable_shards[0].data.shape == emb.shape


def test_pp_trains_loss_decreases():
    cfg = _cfg(num_layers=4)
    rng = jax.random.PRNGKey(3)
    batch = synthetic_lm_batch(rng, cfg, batch=16, seq_len=16)
    mesh = make_pp_mesh(jax.devices()[:8], n_pp=4)
    pp_params = shard_pipeline_params(
        mesh, init_pipeline_params(cfg, rng, batch["input_ids"][:1]))
    tx = optax.adam(1e-2)
    opt_state = jax.jit(tx.init)(pp_params)
    step = make_dp_pp_train_step(mesh, cfg, tx, num_microbatches=4)
    b = shard_pp_batch(mesh, batch)
    losses = []
    for _ in range(10):
        pp_params, opt_state, loss = step(pp_params, opt_state, b)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses
