"""What is left under the stage ``bps.moe.route`` (PR 41): in a
value-and-grad of ``dropless_moe_mlp`` no ``top_k``, ``sort``, ``gather``
or ``scatter*`` equation lies under it — forward or backward — ONE
``pallas_call`` does (the forward selection), and it lies under no other
``bps.moe.*`` scope.  The stage as it was holds all of them and no kernel:
the guard is not vacuous."""

import re

import jax
import jax.numpy as jnp

from byteps_tpu.parallel.expert import dropless_moe_mlp

from .route_select_cases import (E, equation_stacks, layer_params, tokens,
                                 with_the_stage_as_it_was)


def route_equations(held, branch):
    params = layer_params(E if held is None else held[1])

    def loss(x, params):
        routing = None
        if branch == "routing_with_bias":
            routing = (jax.nn.sigmoid(x @ params["router"]), jnp.ones(E))
        with jax.named_scope("block"):
            y, aux, z, _ = dropless_moe_mlp(x, params, 2, interpret=True,
                                            held=held, routing=routing)
        return jnp.sum(y) + aux + z

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(
        tokens(), params).jaxpr
    route = [(prim, stack) for prim, stack in equation_stacks(jaxpr)
             if "bps.moe.route" in stack]
    assert len(route) > 20
    refused = {prim for prim, _ in route
               if prim in ("top_k", "sort", "gather")
               or prim.startswith("scatter")}
    return refused, [stack for prim, stack in route if prim == "pallas_call"]


def test_nothing_sorts_gathers_or_scatters_under_the_route_stage(monkeypatch):
    cases = [(held, branch) for held in (None, (2, 4))
             for branch in ("softmax_router", "routing_with_bias")]
    for held, branch in cases:
        refused, kernels = route_equations(held, branch)
        assert not refused, (held, branch, refused)
        assert len(kernels) == 1, (held, branch, kernels)
        assert re.findall(r"bps\.moe\.\w+", kernels[0]) == ["bps.moe.route"]
        assert "transpose" not in kernels[0]
    with_the_stage_as_it_was(monkeypatch)
    for held, branch in cases:
        refused, kernels = route_equations(held, branch)
        assert refused >= {"top_k", "scatter-add"} and not kernels
