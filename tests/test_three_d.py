"""3D (dp, pp, tp) composite parallelism (parallel/three_d.py).

The oracle is the same as the pp and tp tests use individually: training
from restacked + sharded parameters must match plain single-device GPT
training step for step.  Layout assertions confirm tp actually shards
the block weights (this is a composition test, not just a numerics
test).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models.gpt import GPT, GPTConfig, lm_loss
from byteps_tpu.parallel.long_context import synthetic_lm_batch
from byteps_tpu.parallel.pipeline import (init_pipeline_params,
                                          pipeline_params_to_gpt)
from byteps_tpu.parallel.three_d import (init_3d_opt_state, make_3d_mesh,
                                         make_dp_pp_tp_train_step,
                                         shard_3d_batch, shard_3d_params)


def _cfg(num_layers=4):
    return GPTConfig(vocab_size=128, hidden_size=32, num_layers=num_layers,
                     num_heads=4, intermediate_size=64, max_position=64,
                     dtype=jnp.float32)


@pytest.mark.parametrize("n_pp,n_tp,microbatches", [(2, 2, 2), (2, 4, 4),
                                                    (4, 2, 2)])
def test_3d_training_matches_single_device(n_pp, n_tp, microbatches):
    cfg = _cfg(num_layers=4)
    rng = jax.random.PRNGKey(1)
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

    mesh = make_3d_mesh(jax.devices()[:8], n_pp=n_pp, n_tp=n_tp)
    p3 = shard_3d_params(mesh, pp_params)
    o3 = init_3d_opt_state(tx, p3)
    step = make_dp_pp_tp_train_step(mesh, cfg, tx,
                                    num_microbatches=microbatches)
    b3 = shard_3d_batch(mesh, batch)
    for _ in range(3):
        p3, o3, loss_3d = step(p3, o3, b3)

    np.testing.assert_allclose(float(loss_3d), float(loss_ref),
                               rtol=1e-5, atol=1e-6)
    got = pipeline_params_to_gpt(cfg, jax.device_get(p3))
    for (ka, a), (kb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(got),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(p_ref),
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=str(ka))


def test_3d_layout():
    """Blocks are sharded over BOTH pp (layer axis) and tp (inner dims);
    opt-state moments inherit the layout instead of replicating."""
    cfg = _cfg(num_layers=4)
    rng = jax.random.PRNGKey(0)
    ids = jnp.zeros((1, 8), jnp.int32)
    pp_params = init_pipeline_params(cfg, rng, ids)
    mesh = make_3d_mesh(jax.devices()[:8], n_pp=2, n_tp=2)
    p3 = shard_3d_params(mesh, pp_params)

    qkv = p3["blocks"]["attn"]["qkv"]["kernel"]  # [L, h, 3, heads, hd]
    local = qkv.addressable_shards[0].data.shape
    assert local[0] == cfg.num_layers // 2          # pp shards layers
    assert local[3] == cfg.num_heads // 2           # tp shards heads
    wte = p3["embed"]["wte"]["embedding"]
    assert wte.addressable_shards[0].data.shape[0] == cfg.vocab_size // 2

    tx = optax.adam(1e-3)
    o3 = init_3d_opt_state(tx, p3)
    mu_qkv = o3[0].mu["blocks"]["attn"]["qkv"]["kernel"]
    assert mu_qkv.addressable_shards[0].data.shape == local


def test_pp_step_body_reuse_unchanged():
    """The (dp, pp) path still trains after the body extraction."""
    import byteps_tpu.parallel as par
    cfg = _cfg(num_layers=2)
    rng = jax.random.PRNGKey(3)
    batch = synthetic_lm_batch(rng, cfg, batch=8, seq_len=16)
    pp_params = init_pipeline_params(cfg, rng, batch["input_ids"][:1])
    mesh = par.make_pp_mesh(jax.devices()[:8], n_pp=2)
    p = par.shard_pipeline_params(mesh, pp_params)
    o = jax.jit(optax.sgd(0.1).init)(p)
    step = par.make_dp_pp_train_step(mesh, cfg, optax.sgd(0.1),
                                     num_microbatches=2)
    p, o, loss = step(p, o, par.shard_pp_batch(mesh, batch))
    assert np.isfinite(float(loss))


def test_bf16_partial_manual_psum_canary():
    """Canary for the XLA CPU bug that forces f32 on the 3D path.

    Minimal repro (isolated in a subprocess — the failure mode is a
    process-killing compiler CHECK, "Invalid binary instruction opcode
    copy"): a bf16 psum inside a partial-manual shard_map.  While the
    bug exists, the subprocess dies and three_d.py's f32-on-CPU gating
    stays justified.  When an XLA upgrade fixes it, this test FAILS —
    that is the signal to drop the f32 gating and this canary together.
    """
    import subprocess
    import sys

    code = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax
jax.config.update('jax_platforms', 'cpu')
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
mesh = Mesh(devs, ("dp", "pp", "tp"))
w = jax.device_put(jnp.zeros((16, 16), jnp.bfloat16),
                   NamedSharding(mesh, P(None, None)))
x = jax.device_put(jnp.zeros((4, 16), jnp.bfloat16),
                   NamedSharding(mesh, P("dp", None)))
def body(x, w):
    g = jax.grad(lambda w: jnp.sum((x @ w).astype(jnp.float32)))(w)
    return lax.psum(g, ("dp", "pp"))
f = jax.jit(jax.shard_map(body, mesh=mesh,
                          in_specs=(P("dp", None), P(None, None)),
                          out_specs=P(None, None),
                          axis_names={"dp", "pp"}, check_vma=False))
f(x, w).block_until_ready()
print("BF16_PARTIAL_MANUAL_OK")
"""
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    if "BF16_PARTIAL_MANUAL_OK" in p.stdout:
        raise AssertionError(
            "XLA now compiles bf16 psum under partial-manual shard_map — "
            "remove the f32-on-CPU gating in parallel/three_d.py and this "
            "canary")
    # It must die with THE documented CHECK — any other failure (renamed
    # jax API, import error) means the canary no longer tests the bug.
    assert p.returncode != 0
    assert "Invalid binary instruction opcode copy" in (p.stderr or ""), (
        "repro subprocess failed for a different reason:\n"
        + (p.stderr or "")[-800:])
