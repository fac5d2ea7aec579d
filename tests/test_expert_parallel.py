"""Expert-parallel MoE tests on the 8-device CPU mesh.

The contract: moe_mlp over an ep axis is the same FUNCTION as
moe_mlp_reference on each token shard with the full expert stacks — the
all_to_all moves placement, never math.  Plus: training (router and
experts both update), capacity-drop semantics, and gradient parity of
the full (dp, ep) step against a hand-computed mean-of-shards objective.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from byteps_tpu.ops.moe_kernels import _ROW_CHUNK
from byteps_tpu.parallel.expert import dropless_moe_mlp, row_schedule
from byteps_tpu.parallel.switch_moe import (
    DP_AXIS, EP_AXIS, init_moe_params, make_dp_ep_train_step, make_ep_mesh,
    moe_mlp, moe_mlp_reference, shard_moe_params)

from .jaxpr_count import equations

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


# ------------------------- dropless experts WITHOUT a gate (relu2; PR 39)

_H, _F, _E = 16, 24, 64


def _ungated_params(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"router": jax.random.normal(k[0], (_H, _E)),
            "up": jax.random.normal(k[1], (_E, _H, _F)) / np.sqrt(_H),
            "down": jax.random.normal(k[2], (_E, _F, _H)) / np.sqrt(_F)}


def _one_by_one(x, scores, params, top_k, held, renormalize=True):
    """Each held expert on every token, times its weight or zero: sigmoid
    scores from outside, the ``top_k`` largest renormalised (+1e-20), two
    matrices an expert with ``relu(.)^2`` between, no gate."""
    first, count = held or (0, params["up"].shape[0])
    _, chosen = lax.top_k(scores, top_k)
    picked = (jnp.arange(scores.shape[-1]) == chosen[..., None]).any(-2)
    weight = jnp.where(picked, scores, 0.0)
    if renormalize:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    y = jnp.zeros_like(x)
    for i in range(count):
        hidden = jnp.maximum(x @ params["up"][i], 0.0) ** 2
        y = y + weight[:, first + i, None] * (hidden @ params["down"][i])
    return y


def _ungated_case(n, top_k, held, score_shift=None):
    params = _ungated_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (n, _H))
    logits = x @ params["router"]
    if score_shift is not None:
        logits = logits + score_shift
    stacks = {k: (params[k] if held is None
                  else params[k][held[0]:held[0] + held[1]])
              for k in ("up", "down")}

    def program(x, stacks, logits):
        return dropless_moe_mlp(
            x, stacks, top_k, interpret=True, held=held, renormalize=True,
            routing=(jax.nn.sigmoid(logits), None))

    def reference(x, stacks, logits):
        return _one_by_one(x, jax.nn.sigmoid(logits), stacks, top_k, held)

    return program, reference, (x, stacks, logits)


@pytest.mark.parametrize("held", [(0, 8), None], ids=["held_0_8", "all"])
def test_ungated_relu2_experts_match_the_one_by_one_reference(held):
    """Top-22 of 64 — 1 056 pair rows of which an eighth is live under
    ``held=(0, 8)`` — value and the gradient of rows, stacks and scores."""
    program, reference, args = _ungated_case(48, 22, held)
    weight = jax.random.normal(jax.random.PRNGKey(7), args[0].shape)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(program(*a)[0] * weight), (0, 1, 2)))(*args)
        want = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(reference(*a) * weight), (0, 1, 2)))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(
            g, w, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(w))))
    counts = program(*args)[3]
    assert int(counts.sum()) == 48 * 22


@pytest.mark.parametrize("shift,live", [
    # the held experts' scores pushed down: a few pairs, inside ONE chunk
    (-2.0, "fraction_of_a_chunk"),
    # ... and out of every token's top 22: an empty range, all zeros
    (-50.0, "empty")], ids=["fraction_of_a_chunk", "empty"])
def test_ungated_live_range_inside_one_chunk_or_empty(shift, live):
    held = (8, 8)
    bias = jnp.zeros((_E,)).at[held[0]:held[0] + held[1]].set(shift)
    program, reference, args = _ungated_case(512, 22, held, bias)
    with jax.default_matmul_precision("highest"):
        (y, _, _, counts), want = (jax.jit(program)(*args),
                                   jax.jit(reference)(*args))
        g = jax.jit(jax.grad(lambda *a: program(*a)[0].sum(), (0, 1)))(*args)
        gw = jax.jit(jax.grad(lambda *a: reference(*a).sum(), (0, 1)))(*args)
    chunk = math.gcd(512 * 22, _ROW_CHUNK)
    sched = row_schedule(np.asarray(counts), held, chunk)
    rows = int(sched["hi"] - sched["lo"])
    if live == "empty":
        assert rows == 0 and sched["end"] == sched["first"]
        assert not np.asarray(y).any()
    else:
        assert 0 < rows < chunk and sched["end"] - sched["first"] <= 2
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gw)):
        np.testing.assert_allclose(
            a, b, rtol=2e-5, atol=2e-5 * max(float(jnp.max(jnp.abs(b))), 1e-6))


def test_what_params_holds_says_whether_the_experts_are_gated():
    """The same ``up`` and ``down`` with and without a ``gate`` stack, both
    experts chosen (k = E = 2, so the weights are the softmax itself):
    ``down(relu(up x)^2)`` without, ``down(silu(gate x) * up x)`` with."""
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(k[0], (8, _H))
    params = {"router": jax.random.normal(k[1], (_H, 2)),
              "up": jax.random.normal(k[2], (2, _H, _F)) / np.sqrt(_H),
              "down": jax.random.normal(k[3], (2, _F, _H)) / np.sqrt(_F)}
    gate = jax.random.normal(k[4], (2, _H, _F)) / np.sqrt(_H)
    w = jax.nn.softmax(x @ params["router"])
    for stacks, act in (
            (params, lambda e: jnp.maximum(x @ params["up"][e], 0.0) ** 2),
            (dict(params, gate=gate),
             lambda e: jax.nn.silu(x @ gate[e]) * (x @ params["up"][e]))):
        with jax.default_matmul_precision("highest"):
            y = dropless_moe_mlp(x, stacks, 2, interpret=True)[0]
            want = sum(w[:, e, None] * (act(e) @ params["down"][e])
                       for e in range(2))
        np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)


def _gated(g, router=True):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    p = {"gate": jax.random.normal(k[0], (g, 32, 16)),
         "up": jax.random.normal(k[1], (g, 32, 16)),
         "down": jax.random.normal(k[2], (g, 16, 32))}
    if router:
        p["router"] = jax.random.normal(k[3], (32, 8))
    return p


_SCORES = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2), (48, 8)))
_BIAS = jnp.zeros((8,))


@pytest.mark.parametrize("model,params,kwargs,forward,backward", [
    ("olmoe_1b_7b", _gated(8), dict(top_k=2), (51, 1180), (155, 3422)),
    ("mellum2_12b", _gated(2), dict(top_k=2, held=(2, 2), renormalize=True),
     (66, 1398), (173, 3865)),
    ("zaya1_8b", _gated(4, router=False),
     dict(top_k=1, held=(4, 4), routing=(_SCORES, _BIAS)),
     (36, 1367), (77, 3765)),
    ("glm47_flash", _gated(2, router=False),
     dict(top_k=4, held=(2, 2), renormalize=True, routing=(_SCORES, _BIAS)),
     (40, 1371), (81, 3769)),
], ids=["olmoe_1b_7b", "mellum2_12b", "zaya1_8b", "glm47_flash"])
def test_gated_calls_trace_to_the_programs_they_were(model, params, kwargs,
                                                     forward, backward):
    """The call each of the four models with SiLU-gated experts makes is,
    equation for equation, what it was before experts without a gate
    existed but for the route stage, which selects in one kernel since
    PR 41 (fewer top-level equations, a kernel's worth more in all), and,
    in the three held ones, one ``_tie_gradients`` around the ``down``
    matmul's operands:
    (top-level equations, all equations) of forward and of forward +
    backward, counted on PR 41's tree."""
    x = jax.random.normal(jax.random.PRNGKey(1), (48, 32))

    def layer(x, p):
        return dropless_moe_mlp(x, p, interpret=True, **kwargs)

    def grad(x, p):
        return jax.grad(lambda x, p: layer(x, p)[0].sum(), (0, 1))(x, p)

    fwd = jax.make_jaxpr(layer)(x, params).jaxpr
    assert (len(fwd.eqns), equations(fwd)) == forward
    bwd = jax.make_jaxpr(grad)(x, params).jaxpr
    assert (len(bwd.eqns), equations(bwd)) == backward


# ------------- gated experts in windows, behind a group limit (PR 43)

def test_gated_experts_in_windows_behind_a_group_limit():
    """What ``models/ling.py`` asks of the layer: SiLU-GATED experts on the
    ``held_windows`` plan (8 of 512 held, top-8: 648 pair rows in windows
    of 24) with ``routing=(masked scores, None)`` — the sigmoid scores
    inside each token's 4 chosen groups of 8, zero outside — a combination
    no other model runs.  Value and the gradient of rows, stacks and
    logits against each held expert on every token."""
    from byteps_tpu.models.ling import group_limited
    from byteps_tpu.parallel.expert import layer_plan
    n, h, f, e, top_k, held = 81, 16, 24, 512, 8, (0, 8)
    plan = layer_plan(n * top_k, held[1], e)
    assert (plan.kind, plan.chunk, plan.window) == ("held_windows", 8, 24)
    k = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(k[0], (n, h))
    # the held group's experts favoured, so that the windows are not empty
    logits = jax.random.normal(k[1], (n, e)).at[:, :64].add(1.0)
    stacks = {"gate": jax.random.normal(k[2], (8, h, f)) / np.sqrt(h),
              "up": jax.random.normal(k[3], (8, h, f)) / np.sqrt(h),
              "down": jax.random.normal(k[4], (8, f, h)) / np.sqrt(f)}
    weight = jax.random.normal(k[5], (n, h))

    def masked(logits):
        return group_limited(jax.nn.sigmoid(logits), jnp.zeros((e,)), 8, 4)[0]

    def program(x, stacks, logits):
        return dropless_moe_mlp(x, stacks, top_k, interpret=True, held=held,
                                renormalize=True,
                                routing=(masked(logits), None))

    def reference(x, stacks, logits):
        p = masked(logits)
        _, chosen = lax.top_k(p, top_k)
        picked = (jnp.arange(e) == chosen[..., None]).any(-2)
        w = jnp.where(picked, p, 0.0)
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
        y = jnp.zeros_like(x)
        for i in range(held[1]):
            hidden = jax.nn.silu(x @ stacks["gate"][i]) * (x @ stacks["up"][i])
            y = y + w[:, held[0] + i, None] * (hidden @ stacks["down"][i])
        return y

    args = (x, stacks, logits)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(program(*a)[0] * weight), (0, 1, 2)))(*args)
        want = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(reference(*a) * weight), (0, 1, 2)))(*args)
        counts = program(*args)[3]
    assert int(counts.sum()) == n * top_k and int(counts[:8].sum()) > 0
    # nothing was chosen outside a token's four groups
    assert int((np.asarray(counts).reshape(8, 64).sum(1) > 0).sum()) >= 4
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(
            g, w, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(w))))
