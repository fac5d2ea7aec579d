"""Expert MLPs: a switch MoE over an ``ep`` mesh axis, and a dropless
top-k MoE over the experts a chip holds.

Two entries, two designs; they share nothing but this file.

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
``parallel/moe_lm.py`` still build.

Parity contract: :func:`moe_mlp` (distributed, inside shard_map) and
:func:`moe_mlp_reference` (pure, single device, same token grouping)
compute the identical function — pinned to float tolerance by
tests/test_expert_parallel.py.  Routing semantics are shard-local
(capacity applies per token shard), so the math does not depend on the
mesh size — only the placement does.

**Dropless top-k (the experts held here)** — :func:`dropless_moe_mlp`,
what ``models/olmoe.py`` and ``models/mellum.py`` build: softmax over all
experts, the k largest kept (their weights as they are, or renormalised
to sum to one), no capacity and no dropped token, bias-free SiLU-gated
experts.  The token–expert pairs are sorted by expert and the three
expert matmuls run as grouped matmuls over the ragged groups
(``_grouped_matmul``: JAX's Pallas megablox kernels; the interpreter off
the TPU), so the work is k experts a token and no tensor grows with
``E x C``.  By default every expert is local (``models/olmoe.py``: each
data-parallel replica holds all experts).  With ``held=(first, count)``
the layer is one chip's share of an expert-parallel deployment: it
routes over all E experts, holds the stacks of ``count`` consecutive
ones, and returns the part of the sum that those give for the pairs
routed to them — the local half of expert parallelism.  The other half,
the exchange (top-k dispatch by ``all_to_all`` over an ``ep`` axis, so
that a chip's experts see the tokens of every chip and a token the
experts of every chip), is NOT here yet (ROADMAP R1): a one-chip share
runs without it, and nothing stands in for the absent chips.  Routing,
the router loss and the counts are of the token shard, as the switch
path's are.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

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


# --------------------------------------------------- dropless top-k experts

# (rows, contraction, columns) tile of the grouped matmul: the fastest of
# six measured on a v5e at OLMoE's shape (65 536 pair rows, 64 groups of
# ~1 024, 2048 x 1024 bf16 matrices; PERF.md section 6, PR 25); two larger
# ones do not fit VMEM.  Each is clipped to the array.
_GMM_TILE = (512, 1024, 1024)


def _grouped_matmul(x, w, group_sizes, interpret: bool, first=None):
    """Rows of ``x`` [M, a], sorted into ``len(group_sizes)`` consecutive
    groups, times each group's own matrix of ``w`` [G, a, b] -> [M, b]:
    JAX's Pallas grouped matmul (megablox ``gmm``; its VJP is ``gmm``
    with the matrices transposed for the rows and ``tgmm`` for the
    matrices).  A group may be empty.  With ``first`` (an int32 scalar)
    ``w`` holds only the groups ``first .. first + G - 1`` of
    ``len(group_sizes)``: the kernels' grids cover those groups' row
    tiles alone (work in proportion to the live rows; ``tgmm`` returns
    ``G`` matrices), and megablox itself zeroes every row of the other
    groups in what ``gmm`` returns, forward and row gradient
    (``gmm.py`` ``_zero_uninitialized_memory``: one ``where`` over the
    result; tests/test_mellum.py pins it)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, a = x.shape
    rows = math.gcd(m, _GMM_TILE[0])
    if rows % 8:
        raise ValueError(
            f"the grouped matmul tiles its {m} rows (tokens x top_k) in "
            f"blocks of a multiple of 8 rows that divides them; {m} has "
            f"none")
    tile = (rows, min(a, _GMM_TILE[1]), min(w.shape[-1], _GMM_TILE[2]))
    return gmm(x, w, group_sizes, x.dtype, tile, first, interpret=interpret)


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a PERMUTATION of the rows.  The transpose of a
    gather is a scatter-add; of a permutation it is the gather by the
    inverse permutation, which is what the backward pass runs."""
    del inverse
    return x[perm]


def _permute_rows_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_rows_bwd(res, g):
    perm, inverse = res
    return g[inverse], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def dropless_moe_mlp(x, params, top_k: int,
                     interpret: Optional[bool] = None, *,
                     held: Optional[Tuple[int, int]] = None,
                     renormalize: bool = False):
    """Dropless top-k MoE MLP over a token shard ``x`` [N, h].

    params: ``{"router": [h, E] float32, "gate": [G, h, f], "up":
    [G, h, f], "down": [G, f, h]}``.  No biases.  ``held=None``: the
    stacks are all E experts (``G = E``).  ``held=(first, G)``: they are
    experts ``first .. first + G - 1`` of the E the router knows, one
    chip's share of an expert-parallel layer (module docstring).

        p      = softmax(x_f32 @ router)            over all E
        w, idx = top_k(p, k)                        renormalize: w /= sum_j w
        y      = sum_j w[:, j] * down_idx_j(silu(gate_idx_j x) * up_idx_j x)
                 over the j whose expert idx_j is held

    The weights are the model's: renormalised over the k chosen BEFORE the
    held experts are selected, so the shares of a layer add up to the
    whole layer; a token none of whose k experts is held gets exactly
    zero.  No pair routed to a held expert is ever dropped: the pair rows
    are the worst case, all ``N * k`` (every token could choose held
    experts only), the rows of pairs routed elsewhere ride along dead (the
    grouped matmuls' grids skip them and zero their results), and what
    their gathers cost is the price of the static shape (gauge
    ``moe.held_pair_share``).

    Returns ``(y [N, h] in x.dtype, aux, z, counts [E] int32)``:
    ``aux = E * sum_e f_e P_e`` with ``f_e`` = pairs routed to e / N and
    ``P_e`` = mean router probability (the Switch load-balance loss
    summed over the k choices), ``z = mean(logsumexp(logits)^2)``
    (ST-MoE router z-loss), ``counts`` the pairs each expert received —
    all three over all E experts, whatever is held.
    Router arithmetic is float32; the experts compute in ``x.dtype``.
    Shapes are static: exactly ``N * k`` pair rows, so dropless needs no
    padding and an expert may receive none.  ``interpret=None`` runs the
    grouped-matmul kernels on a real TPU backend and through the Pallas
    interpreter elsewhere (CPU tests), as ``ops.flash_attention`` does.
    """
    if interpret is None:
        from ..ops.pallas_kernels import on_tpu
        interpret = not on_tpu()
    n, h = x.shape
    e = params["router"].shape[-1]
    first = None
    if held is not None:
        start, count = held
        if not (0 <= start and 1 <= count and start + count <= e
                and params["gate"].shape[0] == count):
            raise ValueError(
                f"held={held}: the stacks carry {params['gate'].shape[0]} "
                f"experts and the router knows {e}")
        first = jnp.asarray(start, jnp.int32)
    with jax.named_scope("bps.moe.route"):
        logits = jnp.dot(x.astype(jnp.float32),
                         params["router"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)       # [N, E]
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = lax.top_k(probs, top_k)                  # [N, k]
        if renormalize:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        pair_expert = idx.reshape(n * top_k)
        counts = jnp.bincount(pair_expert, length=e).astype(jnp.int32)
        aux = e * jnp.sum(counts.astype(jnp.float32) / n
                          * jnp.mean(probs, axis=0))
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    with jax.named_scope("bps.moe.dispatch"):
        # pairs sorted by expert (stable: a token's order within its
        # group is its arrival order); each token's row gathered k times
        order = jnp.argsort(pair_expert, stable=True)           # [N k]
        inverse = jnp.argsort(order)
        xs = _permute_rows(jnp.repeat(x, top_k, axis=0), order, inverse)
    with jax.named_scope("bps.moe.experts"):
        dt = x.dtype
        gate = _grouped_matmul(xs, params["gate"].astype(dt), counts,
                               interpret, first)
        up = _grouped_matmul(xs, params["up"].astype(dt), counts, interpret,
                             first)
        ys = _grouped_matmul(jax.nn.silu(gate) * up,
                             params["down"].astype(dt), counts, interpret,
                             first)
    with jax.named_scope("bps.moe.combine"):
        pairs = _permute_rows(ys, inverse, order).reshape(n, top_k, h)
        y = jnp.sum(pairs.astype(jnp.float32) * weights[..., None], axis=1)
    return y.astype(x.dtype), aux, z, counts


def publish_moe_stats(counts, held: Optional[Tuple[int, int]] = None
                      ) -> None:
    """Set the load gauges ``bps.metrics_snapshot()`` reads from the
    per-expert pair counts of one batch: ``counts`` [E] or [layers, E]
    (``dropless_moe_mlp``'s fourth result; the models sow it into
    ``moe_stats``).  With ``held=(first, count)`` also the share's own:
    ``moe.held_pair_share`` (pairs routed to held experts over all pairs
    = the live share of the layer's ``N * k`` pair rows) and
    ``moe.held_load_max_over_mean`` (the fullest held expert over the held
    experts' mean, worst layer).  Host side: it reads the values, so call
    it outside any jitted step and off the step's critical path."""
    from ..common.metrics import gauges
    c = np.asarray(counts, np.float64).reshape(-1, np.shape(counts)[-1])
    gauges.set("moe.load_max_over_mean",
               float(np.max(c.max(axis=1) / c.mean(axis=1))))
    gauges.set("moe.tokens_per_expert_min", float(c.min()))
    gauges.set("moe.tokens_per_expert_max", float(c.max()))
    if held is not None:
        mine = c[:, held[0]:held[0] + held[1]]
        gauges.set("moe.held_pair_share", float(mine.sum() / c.sum()))
        gauges.set("moe.held_load_max_over_mean",
                   float(np.max(mine.max(axis=1) / mine.mean(axis=1))))
