"""Expert MLPs as a switch MoE over an ``ep`` mesh axis.

**Switch (top-1, capacity, ``ep``)** — :func:`switch_dispatch`,
:func:`moe_mlp`, :func:`make_dp_ep_train_step`.  The reference is DP-only
(SURVEY.md §2.6); expert parallelism is the axis that scales *width*
sub-linearly in FLOPs — a Switch-Transformer MLP whose experts live
one-shard-per-device on an ``ep`` mesh axis.  TPU-native shape, matching
this repo's explicit-collective idiom (sequence.py, pipeline.py):
routing and capacity are computed per token shard, the dispatched
[experts, capacity, hidden] block crosses the ``ep`` axis as ONE
``lax.all_to_all`` each way (the same collective Ulysses uses for
heads), and every shape is static — dropped-token semantics via a
capacity factor, the published Switch design: top-1, GELU experts with
biases, a dense ``[N, E, C]`` one-hot contracted by ``einsum``.  This is
what ``models/gpt.py`` ``MoEMLP`` (``GPTConfig.moe_experts``) and
``parallel/moe_lm.py`` build.  The dropless top-k layer of the sparse
models is another design in another file (``parallel/expert.py``); the
two share nothing.

Parity contract: :func:`moe_mlp` (distributed, inside shard_map) and
:func:`moe_mlp_reference` (pure, single device, same token grouping)
compute the identical function — pinned to float tolerance by
tests/test_expert_parallel.py.  Routing semantics are shard-local
(capacity applies per token shard), so the math does not depend on the
mesh size — only the placement does.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh_util import jit_mapped_step, make_2d_mesh

DP_AXIS = "dp"
EP_AXIS = "ep"


def make_ep_mesh(devices, n_ep: int) -> Mesh:
    return make_2d_mesh(devices, n_ep, (DP_AXIS, EP_AXIS))


# ------------------------------------------------------------------ routing

def switch_dispatch(x, router_w, num_experts: int, capacity: int):
    """Top-1 (switch) routing of a token shard.

    x: [N, h] tokens.  Returns (dispatch [N, E, C] one-hot combine
    weights with the gate folded in, dispatched [E, C, h] expert inputs,
    aux load-balance loss).  Tokens beyond an expert's capacity are
    dropped (contribute zero), the standard static-shape trade.
    """
    n, h = x.shape
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)              # [N, E]
    expert = jnp.argmax(probs, axis=-1)                  # [N]
    gate = jnp.max(probs, axis=-1)                       # [N]
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.float32)
    # position of each token within its expert's queue (arrival order)
    pos = (jnp.cumsum(onehot, axis=0) - onehot) * onehot  # [N, E]
    keep = (pos < capacity) * onehot                      # [N, E]
    pos_oh = jax.nn.one_hot(jnp.sum(pos, axis=-1).astype(jnp.int32),
                            capacity, dtype=jnp.float32)  # [N, C]
    # dispatch tensor: token n -> (its expert, its slot), zero if dropped
    disp = keep[:, :, None] * pos_oh[:, None, :]          # [N, E, C]
    dispatched = jnp.einsum("nec,nh->ech", disp, x.astype(jnp.float32))
    # Switch aux loss: E * sum_e frac_tokens_e * frac_probs_e
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(frac_tokens * frac_probs)
    combine = disp * gate[:, None, None]                  # [N, E, C]
    return combine, dispatched, aux


def _expert_ffn(w1, b1, w2, b2, x):
    """x: [E_loc, S, h]; weights [E_loc, ...]: per-expert MLP."""
    y = jnp.einsum("esh,ehf->esf", x, w1) + b1[:, None, :]
    y = jax.nn.gelu(y)
    return jnp.einsum("esf,efh->esh", y, w2) + b2[:, None, :]


def moe_mlp(x, params, num_experts: int, capacity_factor: float,
            axis_name: Optional[str] = EP_AXIS):
    """Switch MoE MLP over a token shard [N, h].

    params: {"router": [h, E], "w1": [E_loc, h, f], "b1": [E_loc, f],
    "w2": [E_loc, f, h], "b2": [E_loc, h]} — expert weights hold only
    this device's E/ep experts when ``axis_name`` is set (pass the full
    [E, ...] stacks and axis_name=None for the single-device path).
    Returns (out [N, h] in x.dtype, aux loss scalar).
    """
    n, h = x.shape
    e_loc = params["w1"].shape[0]
    ep = 1 if axis_name is None else lax.axis_size(axis_name)
    e_total = e_loc * ep
    if e_total != num_experts:
        raise ValueError(f"expert weights carry {e_total} experts, "
                         f"config says {num_experts}")
    capacity = max(1, int(np.ceil(capacity_factor * n / num_experts)))
    combine, dispatched, aux = switch_dispatch(
        x, params["router"], num_experts, capacity)
    if axis_name is None:
        expert_in = dispatched                       # [E, C, h]
    else:
        # [E, C, h] -> [ep, E_loc, C, h]; tiled all_to_all over axis 0
        # swaps the leading ep block axis with the device axis:
        # afterwards THIS device holds, per source peer, the
        # [E_loc, C, h] block destined for its experts.  Fold sources
        # into the sequence axis for the expert FFN.
        blocks = dispatched.reshape(ep, e_loc, capacity, h)
        recv = lax.all_to_all(blocks, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)
        expert_in = jnp.moveaxis(recv, 0, 1).reshape(e_loc,
                                                     ep * capacity, h)
    expert_out = _expert_ffn(params["w1"], params["b1"], params["w2"],
                             params["b2"], expert_in.astype(
                                 params["w1"].dtype)).astype(jnp.float32)
    if axis_name is None:
        returned = expert_out                        # [E, C, h]
    else:
        back = jnp.moveaxis(
            expert_out.reshape(e_loc, ep, capacity, h), 1, 0)
        returned = lax.all_to_all(
            back, axis_name, split_axis=0, concat_axis=0, tiled=True
        ).reshape(e_total, capacity, h)
    out = jnp.einsum("nec,ech->nh", combine, returned)
    return out.astype(x.dtype), aux


def moe_mlp_reference(x, full_params, num_experts: int,
                      capacity_factor: float):
    """Single-device reference: identical math with the full expert
    stacks and no collective (the parity oracle for :func:`moe_mlp`)."""
    return moe_mlp(x, full_params, num_experts, capacity_factor,
                   axis_name=None)


def init_moe_params(rng, hidden: int, ffn: int, num_experts: int,
                    dtype=jnp.float32):
    """Full (unsharded) switch-MLP parameter stacks."""
    kr, k1, k2 = jax.random.split(rng, 3)
    scale_in = 1.0 / np.sqrt(hidden)
    scale_out = 1.0 / np.sqrt(ffn)
    return {
        "router": (jax.random.normal(kr, (hidden, num_experts),
                                     jnp.float32) * scale_in),
        "w1": (jax.random.normal(k1, (num_experts, hidden, ffn),
                                 dtype) * scale_in),
        "b1": jnp.zeros((num_experts, ffn), dtype),
        "w2": (jax.random.normal(k2, (num_experts, ffn, hidden),
                                 dtype) * scale_out),
        "b2": jnp.zeros((num_experts, hidden), dtype),
    }


def moe_pspec(path, leaf) -> P:
    """THE placement rule for MoE params (and any optax state wrapping
    them): router and scalar bookkeeping replicated, expert stacks
    (leading expert axis) sharded over ep.  Single source of truth for
    both device placement and shard_map specs."""
    if any(getattr(q, "key", None) == "router" for q in path):
        return P()
    if getattr(leaf, "ndim", 1) == 0:
        return P()
    return P(EP_AXIS)


def shard_moe_params(mesh: Mesh, params):
    """Place MoE params per :func:`moe_pspec`."""
    return jax.device_put(params, jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, moe_pspec(path, leaf)),
        params))


def make_dp_ep_train_step(mesh: Mesh, num_experts: int,
                          capacity_factor: float,
                          tx: optax.GradientTransformation,
                          loss_fn: Callable,
                          aux_weight: float = 0.01,
                          donate: bool = True) -> Callable:
    """Training step for an MoE regression/LM head over (dp, ep).

    ``loss_fn(out, batch) -> scalar`` consumes the MoE output for this
    token shard.  Tokens are sharded over BOTH axes (dp x ep rows all
    carry distinct tokens — ep devices contribute tokens too, as in
    Switch); expert weights are ep-sharded, the router replicated.  With
    VMA tracking, autodiff reduces each gradient over exactly the axes
    its parameter is unvarying along (the lesson pipeline.py encodes).
    """

    n_shards = int(mesh.shape[DP_AXIS] * mesh.shape[EP_AXIS])

    def step(params, opt_state, batch):
        x = batch["x"]

        def objective(p):
            out, aux = moe_mlp(x.reshape(-1, x.shape[-1]), p, num_experts,
                               capacity_factor, axis_name=EP_AXIS)
            main = loss_fn(out.reshape(x.shape), batch)
            # 1/n_shards: the global objective is the MEAN of the shard
            # objectives, and the VMA-aware transpose will SUM each
            # parameter's cotangents over the axes it is unvarying
            # along — pre-scaling makes that sum the exact mean-gradient.
            # The psum below stays out of the gradient path (the
            # long_context.py lesson).
            return (main + aux_weight * aux) / n_shards

        loss_local, grads = jax.value_and_grad(objective)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        loss = lax.psum(loss_local, (DP_AXIS, EP_AXIS))
        return params, opt_state, loss

    def spec_of(tree):
        return jax.tree_util.tree_map_with_path(moe_pspec, tree)

    return jit_mapped_step(mesh, step, spec_of, P((DP_AXIS, EP_AXIS)),
                           donate=donate)
