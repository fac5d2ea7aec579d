"""MoE language-model training over a (dp, ep) mesh.

Glues the GPT family's switch-MoE blocks (models/gpt.py
``GPTConfig.moe_experts``) to expert parallelism: sequences are sharded
over BOTH mesh axes (plain data parallelism for the dense layers —
attention and embeddings see only their own sequences), expert stacks
are sharded over ``ep``, and every MoE block's token dispatch crosses
the ep axis as all_to_all (parallel/switch_moe.py).  The Switch aux
load-balance losses are sown by the model (``moe_aux`` collection) and
folded into the objective here.

Routing is shard-local (capacity per token shard), so the math is
mesh-size independent — pinned against the single-device model in
tests/test_moe_lm.py.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.gpt import GPT, GPTConfig, token_nll
from .switch_moe import DP_AXIS, EP_AXIS, make_ep_mesh  # noqa: F401
from .mesh_util import jit_mapped_step


def moe_lm_pspec(path, leaf) -> P:
    """Expert stacks (under a */moe/* scope, except the replicated
    router) sharded over ep on their leading expert axis; everything
    else replicated."""
    keys = [getattr(q, "key", None) for q in path]
    if "moe" in keys and keys[-1] != "router" \
            and getattr(leaf, "ndim", 0) > 0:
        return P(EP_AXIS)
    return P()


def shard_moe_lm_params(mesh: Mesh, variables):
    return jax.device_put(variables, jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, moe_lm_pspec(path, leaf)),
        variables))


def shard_moe_lm_batch(mesh: Mesh, batch):
    """Sequences over (dp, ep) — every device carries distinct data."""
    return jax.device_put(batch,
                          NamedSharding(mesh, P((DP_AXIS, EP_AXIS))))


def make_moe_lm_train_step(mesh: Mesh, cfg: GPTConfig,
                           tx: optax.GradientTransformation,
                           aux_weight: float = 0.01,
                           donate: bool = True) -> Callable:
    """Jitted (params, opt_state, batch) -> (params, opt_state, loss)
    for an MoE GPT over (dp, ep); batch via :func:`shard_moe_lm_batch`,
    params via :func:`shard_moe_lm_params`."""
    if cfg.moe_experts <= 0:
        raise ValueError("cfg.moe_experts must be > 0 for the MoE step")
    model = GPT(cfg, ep_axis=EP_AXIS)
    n_shards = int(mesh.shape[DP_AXIS] * mesh.shape[EP_AXIS])

    def step(params, opt_state, batch):
        ids, labels = batch["input_ids"], batch["labels"]

        def objective(p):
            logits, sown = model.apply(p, ids, mutable=["moe_aux"])
            s, c = token_nll(logits, labels)
            aux = sum(jnp.sum(v) for v in
                      jax.tree.leaves(sown.get("moe_aux", {})))
            # token-weighted GLOBAL normalization, like long_context.py:
            # uneven valid-token counts across shards must not reweight
            # the objective.  The psum'd denominator is stop_gradient'd
            # (count carries no gradient) and the local numerator's
            # cotangents are summed by the VMA transpose
            # (mesh_util.jit_mapped_step), so grads are exact.
            denom = jnp.maximum(
                lax.psum(lax.stop_gradient(c), (DP_AXIS, EP_AXIS)), 1.0)
            return s / denom + aux_weight * aux / n_shards

        loss_local, grads = jax.value_and_grad(objective)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        loss = lax.psum(loss_local, (DP_AXIS, EP_AXIS))
        return params, opt_state, loss

    def spec_of(tree):
        return jax.tree_util.tree_map_with_path(moe_lm_pspec, tree)

    return jit_mapped_step(mesh, step, spec_of, P((DP_AXIS, EP_AXIS)),
                           donate=donate)
