"""``dropless_moe_mlp`` (PR 41) against the same layer with the route stage
as it was (``lax.top_k`` + gather + ``bincount``, kept in
``route_select_cases``): ``y``, ``aux``, ``z``, ``counts`` and every
gradient EQUAL — the selection is the same bits, so everything after it is
the same program on the same numbers — for all experts held, a share on
whole arrays and a share in windows, under the layer's softmax router and
under ``routing=`` with a bias; and ``_tie_gradients``, which the held
layer puts around its ``down`` matmul's operands."""

import jax
import jax.numpy as jnp
import numpy as np

from byteps_tpu.parallel import expert
from byteps_tpu.parallel.expert import _tie_gradients, dropless_moe_mlp

from .route_select_cases import (E, WINDOW, equation_stacks, layer_params,
                                 tokens, with_the_stage_as_it_was,
                                 with_the_window)

# name -> (held, the window ``layer_plan`` gives or "rule", top_k)
LAYERS = {"all_experts": (None, "rule", 2),
          "held_whole_arrays": ((2, 4), None, 2),
          "held_windowed": ((2, 4), WINDOW, 4)}


def value_and_grads(held, top_k, branch):
    params = layer_params(E if held is None else held[1])
    bias = jnp.round(4.0 * jax.random.normal(jax.random.PRNGKey(5), (E,))
                     ) / 4.0

    def objective(x, params, bias):
        routing = None
        if branch == "routing_with_bias":
            routing = (jax.nn.sigmoid(x @ params["router"]), bias)
        y, aux, z, counts = dropless_moe_mlp(
            x, params, top_k, interpret=True, held=held, routing=routing,
            renormalize=True)
        return jnp.sum(y ** 2) + aux + z, (y, aux, z, counts)
    return jax.jit(jax.value_and_grad(objective, (0, 1, 2), has_aux=True))(
        tokens(), params, bias)


def test_layer_equals_the_layer_with_the_stage_as_it_was(monkeypatch):
    for name, (held, window, top_k) in LAYERS.items():
        for branch in ("softmax_router", "routing_with_bias"):
            with monkeypatch.context() as patch:
                if window != "rule":
                    with_the_window(patch, window)
                got = value_and_grads(held, top_k, branch)
                with_the_stage_as_it_was(patch)
                want = value_and_grads(held, top_k, branch)
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(w), err_msg=f"{name} {branch}")
            assert np.asarray(got[1][1]["up"]).any()

    # ``_tie_gradients``: the identity, forward and backward; backward ONE
    # ``optimization_barrier`` over all the cotangents; once in a held layer
    a = jax.random.normal(jax.random.PRNGKey(0), (6, 4))
    b = jax.random.normal(jax.random.PRNGKey(1), (4, 3))

    def loss(tie):
        return lambda a, b: jnp.sum(jnp.tanh(tie(a, b)[0] @ tie(a, b)[1]))
    for g, w in zip(
            jax.tree.leaves(jax.value_and_grad(loss(_tie_gradients),
                                               (0, 1))(a, b)),
            jax.tree.leaves(jax.value_and_grad(loss(lambda *xs: xs),
                                               (0, 1))(a, b))):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def barriers(fn, *args):
        """Operand counts of the top-level barriers, and barriers in all."""
        jaxpr = jax.make_jaxpr(jax.grad(fn, (0, 1)))(*args).jaxpr
        top = [len(e.invars) for e in jaxpr.eqns
               if e.primitive.name == "optimization_barrier"]
        return top, sum(prim == "optimization_barrier"
                        for prim, _ in equation_stacks(jaxpr))
    assert barriers(lambda a, b: jnp.sum(
        _tie_gradients(a, b)[0] @ _tie_gradients(a, b)[1]), a, b)[0] == [2, 2]

    def held_layer(x, p):
        return jnp.sum(dropless_moe_mlp(x, p, 2, interpret=True,
                                        held=(2, 4))[0])
    tied = barriers(held_layer, tokens(), layer_params(4))[1]
    monkeypatch.setattr(expert, "_tie_gradients", lambda *xs: xs)
    assert tied == barriers(held_layer, tokens(), layer_params(4))[1] + 1
