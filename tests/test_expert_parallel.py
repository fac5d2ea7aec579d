"""Expert-parallel MoE tests on the 8-device CPU mesh.

The contract: moe_mlp over an ep axis is the same FUNCTION as
moe_mlp_reference on each token shard with the full expert stacks — the
all_to_all moves placement, never math.  Plus: training (router and
experts both update), capacity-drop semantics, and gradient parity of
the full (dp, ep) step against a hand-computed mean-of-shards objective.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from byteps_tpu.parallel.expert import (
    DP_AXIS, EP_AXIS, init_moe_params, make_dp_ep_train_step, make_ep_mesh,
    moe_mlp, moe_mlp_reference, shard_moe_params)

H, F, E = 16, 32, 8


def _params(seed=0):
    return init_moe_params(jax.random.PRNGKey(seed), H, F, E)


def _tokens(n, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, H), jnp.float32)


def test_reference_shapes_and_capacity_drop():
    p = _params()
    x = _tokens(64)
    out, aux = moe_mlp_reference(x, p, E, capacity_factor=1.25)
    assert out.shape == x.shape and np.isfinite(np.asarray(out)).all()
    assert float(aux) > 0
    # capacity so small that most tokens are dropped -> output rows zero
    out2, _ = moe_mlp_reference(x, p, E, capacity_factor=0.125)
    zero_rows = (np.abs(np.asarray(out2)).sum(axis=1) == 0).sum()
    assert zero_rows > (np.abs(np.asarray(out)).sum(axis=1) == 0).sum()


@pytest.mark.parametrize("n_ep,n_dp", [(4, 2), (8, 1), (2, 4)])
def test_distributed_matches_reference_per_shard(n_ep, n_dp):
    mesh = make_ep_mesh(jax.devices()[:8], n_ep=n_ep)
    full = _params()
    tokens_per_shard = 32
    n_shards = n_dp * n_ep
    x_all = _tokens(tokens_per_shard * n_shards)
    cf = 1.5

    def fwd(p_local, x):
        out, aux = moe_mlp(x, p_local, E, cf, axis_name=EP_AXIS)
        return out, aux[None]

    p_spec = jax.tree_util.tree_map_with_path(
        lambda path, l: P() if path[-1].key == "router" else P(EP_AXIS),
        full)
    mapped = jax.jit(jax.shard_map(
        fwd, mesh=mesh,
        in_specs=(p_spec, P((DP_AXIS, EP_AXIS))),
        out_specs=(P((DP_AXIS, EP_AXIS)), P((DP_AXIS, EP_AXIS)))))
    sharded = shard_moe_params(mesh, full)
    xg = jax.device_put(x_all, NamedSharding(mesh, P((DP_AXIS, EP_AXIS))))
    out, aux = mapped(sharded, xg)
    out, aux = np.asarray(out), np.asarray(aux)

    for g in range(n_shards):
        xs = x_all[g * tokens_per_shard:(g + 1) * tokens_per_shard]
        ref_out, ref_aux = moe_mlp_reference(xs, full, E, cf)
        np.testing.assert_allclose(
            out[g * tokens_per_shard:(g + 1) * tokens_per_shard],
            np.asarray(ref_out), rtol=1e-5, atol=1e-5,
            err_msg=f"shard {g}")
        np.testing.assert_allclose(aux[g], float(ref_aux), rtol=1e-5)


def test_dp_ep_training_matches_reference_gradients():
    """One step of the (dp, ep) trainer == one step of the hand-built
    mean-of-shards objective on one device."""
    mesh = make_ep_mesh(jax.devices()[:8], n_ep=4)
    full = _params(seed=2)
    n_shards = 8
    tokens_per_shard = 16
    x = _tokens(tokens_per_shard * n_shards, seed=3)
    y = _tokens(tokens_per_shard * n_shards, seed=4)
    cf, aux_w = 1.5, 0.01
    tx = optax.sgd(0.1)

    def shard_loss(out, batch):
        return jnp.mean((out - batch["y"]) ** 2)

    # reference: mean over shards of (mse + aux_w * aux)
    def ref_objective(p):
        tot = 0.0
        for g in range(n_shards):
            xs = x[g * tokens_per_shard:(g + 1) * tokens_per_shard]
            ys = y[g * tokens_per_shard:(g + 1) * tokens_per_shard]
            out, aux = moe_mlp_reference(xs, p, E, cf)
            tot = tot + jnp.mean((out - ys) ** 2) + aux_w * aux
        return tot / n_shards

    loss_ref, g_ref = jax.value_and_grad(ref_objective)(full)
    u, _ = tx.update(g_ref, tx.init(full), full)
    p_ref = optax.apply_updates(full, u)

    step = make_dp_ep_train_step(mesh, E, cf, tx, shard_loss,
                                 aux_weight=aux_w, donate=False)
    p_ep = shard_moe_params(mesh, full)
    o_ep = jax.jit(tx.init)(p_ep)
    batch = jax.device_put({"x": x, "y": y},
                           NamedSharding(mesh, P((DP_AXIS, EP_AXIS))))
    p_ep, o_ep, loss_ep = step(p_ep, o_ep, batch)

    np.testing.assert_allclose(float(loss_ep), float(loss_ref),
                               rtol=1e-5, atol=1e-6)
    for (ka, a), (kb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(p_ref),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(
                jax.device_get(p_ep)), key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg=str(ka))


def test_dp_ep_trains_and_stays_sharded():
    mesh = make_ep_mesh(jax.devices()[:8], n_ep=4)
    full = _params(seed=5)
    x = _tokens(128, seed=6)
    tx = optax.adam(3e-3)

    def shard_loss(out, batch):
        return jnp.mean((out - batch["y"]) ** 2)

    # donation + CPU device_put aliasing would delete `full`'s buffers;
    # snapshot the router before training for the learned-delta check
    router0 = np.array(full["router"])
    step = make_dp_ep_train_step(mesh, E, 1.5, tx, shard_loss)
    p = shard_moe_params(mesh, full)
    o = jax.jit(tx.init)(p)
    batch = jax.device_put(
        {"x": x, "y": jnp.tanh(x[:, ::-1])},
        NamedSharding(mesh, P((DP_AXIS, EP_AXIS))))
    losses = []
    for _ in range(25):
        p, o, loss = step(p, o, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[::6]
    w1 = p["w1"]
    assert w1.addressable_shards[0].data.shape[0] * 4 == w1.shape[0]
    # router actually learned (replicated, updated via summed cotangents)
    assert float(np.abs(np.asarray(p["router"]) - router0).max()) > 0
