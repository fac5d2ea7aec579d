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
what ``models/olmoe.py``, ``models/mellum.py``, ``models/zaya.py``,
``models/glm_lite.py`` and ``models/nemotron_h.py`` build: softmax over
all experts (the layer's own linear router, or
probabilities and a selection bias handed in from outside: ``routing=``),
the k largest kept (their weights as they are, or renormalised
to sum to one), no capacity and no dropped token, bias-free experts —
SiLU-gated (three matrices) or, where ``params`` holds no ``gate``,
ungated with ``relu(.)^2`` (two).  The token–expert pairs are sorted by
expert and the expert matmuls run as grouped matmuls over the ragged
groups
(``_grouped_matmul``: JAX's Pallas megablox kernels; the interpreter off
the TPU), so the work is k experts a token and no tensor grows with
``E x C``.  By default every expert is local (``models/olmoe.py``: each
data-parallel replica holds all experts).  With ``held=(first, count)``
the layer is one chip's share of an expert-parallel deployment: it
routes over all E experts, holds the stacks of ``count`` consecutive
ones, and returns the part of the sum that those give for the pairs
routed to them — the local half of expert parallelism, its row passes in proportion to the
rows that land here (:func:`row_schedule`; a thin share works in windows
of its live range, :func:`window_rows`).  The other half,
the exchange (top-k dispatch by ``all_to_all`` over an ``ep`` axis, so
that a chip's experts see the tokens of every chip and a token the
experts of every chip), is NOT here yet (ROADMAP R1): a one-chip share
runs without it, and nothing stands in for the absent chips.  Routing,
the router loss and the counts are of the token shard, as the switch
path's are.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
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
    ``G`` matrices) and every row of the other groups comes back zero:
    forward the kernels write over a zero buffer (``existing_out``), in
    the row gradient megablox zeroes them itself (``gmm.py``
    ``_zero_uninitialized_memory``: one ``where`` over the result;
    tests/test_mellum.py pins both)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, a = x.shape
    rows = math.gcd(m, _GMM_TILE[0])
    if rows % 8:
        raise ValueError(
            f"the grouped matmul tiles its {m} rows (tokens x top_k) in "
            f"blocks of a multiple of 8 rows that divides them; {m} has "
            f"none")
    tile = (rows, min(a, _GMM_TILE[1]), min(w.shape[-1], _GMM_TILE[2]))
    # over zeros, the kernels write the held groups' rows and megablox
    # makes no pass of its own over the result
    zeros = None if first is None else jnp.zeros((m, w.shape[-1]), x.dtype)
    return gmm(x, w, group_sizes, x.dtype, tile, first, zeros,
               interpret=interpret)


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


@jax.custom_vjp
def _tie_gradients(*xs):
    """``xs`` as they are; backward, their gradients pass ONE
    ``optimization_barrier``: none is read before all exist.  Around a
    grouped matmul's operands that keeps its two gradient kernels together,
    so the incoming row gradient — an array of all ``N k`` pair rows — dies
    when both have read it, whatever else the scheduler could run between
    (it put the matrix gradient last in Mellum's step once the route stage
    changed: one more ``[131072, 2304]`` array live at the peak, +0.53 GiB;
    PERF.md section 6, PR 41)."""
    return xs


_tie_gradients.defvjp(lambda *xs: (xs, None),
                      lambda _, g: lax.optimization_barrier(tuple(g)))


def _sorted_pairs(pair_expert, weights):
    """A held layer's pairs sorted by expert (stable: a token's order
    within its group is its arrival order) -> (``order`` [N k], the pairs
    in sorted order; ``scale`` [N k], their weights, which ride through
    the same sort and carry no gradient)."""
    rows = pair_expert.shape[0]
    _, order, scale = lax.sort(
        (pair_expert, lax.iota(jnp.int32, rows),
         lax.stop_gradient(weights).reshape(rows)),
        num_keys=1, is_stable=True)
    return order, scale


# ------------------------------------ a held share's row passes (live rows)

# Pair rows one grid step of a held layer's row kernels moves: twice the
# grouped matmul's row tile, clipped to a divisor of the rows — Mosaic lays
# a 1-D int32 SMEM block (the rows' tokens) out in 1 024s, and that many
# rows of 2304 columns, double-buffered in and out beside a 512-row
# float32 landing buffer, fit the VMEM asked for below (Mosaic's default
# 16 MiB does not hold them; PERF.md section 6, PR 30).
_ROW_CHUNK = 2 * _GMM_TILE[0]
_ROW_VMEM_BYTES = 40 * 2 ** 20
_DMA_GROUP = 4          # row DMAs started, and waited for, a loop trip


def row_schedule(counts, held: Tuple[int, int], chunk: int) -> dict:
    """Which rows of the sorted order a held layer's row passes visit:
    ``{"lo", "hi", "first", "end"}``.

    Pairs are sorted by expert, so the rows of the experts ``held =
    (start, count)`` are ONE range ``[lo, hi)`` of the sorted order, read
    off the per-expert pair ``counts`` [E] before any row moves.  A pass
    works on the row chunks ``first .. end - 1`` (``chunk`` rows each):
    those that meet the range, none when it is empty — ``(end - first) *
    chunk`` rows visited of ``sum(counts)``; every other chunk is written
    as zeros and nothing of it is read.  Pure arithmetic on ``counts``,
    host integers (numpy) or traced ones alike: the kernels take their
    bounds from here and ``publish_moe_stats`` its gauge (the manner of
    ``ops.flash_attention.block_schedule``)."""
    xp = jnp if isinstance(counts, jax.Array) else np
    start, count = held
    lo = xp.sum(counts[:start])
    hi = lo + xp.sum(counts[start:start + count])
    first = lo // chunk
    end = xp.where(hi > lo, (hi + chunk - 1) // chunk, first)
    return {"lo": lo, "hi": hi, "first": first, "end": end}


def _sched_words(sched):
    return jnp.stack([sched[k] for k in ("lo", "hi", "first", "end")]
                     ).astype(jnp.int32)


def _live_chunk(n_chunks):
    """Index map of an input the dead chunks do not need: their steps name
    the nearest live chunk's block, which the pipeline has already (or
    fetches once), so nothing of a dead chunk is read."""
    def index(c, words):
        last = jnp.maximum(words[3] - 1, words[2])
        return jnp.minimum(jnp.clip(c, words[2], last), n_chunks - 1), 0
    return index


def _spread_kernel(words, tok, src, *refs, chunk, part, sub, scaled):
    """One chunk of ``_spread_rows``, ``part`` rows at a time: the live
    rows' sources come by one DMA each from ``src`` [N, 1, h] float32 in
    HBM (a row of its own tile: Mosaic slices no single row off a 2-D
    array) into ``buf``, then leave in ``sub``-row pieces, masked to the
    range, scaled and dotted where asked.  Loops, not unrolled code: every
    layer's kernels are traced and lowered anew, and their size is set-up
    time (PERF.md section 6, PR 28 (5))."""
    if scaled:
        weight, dot, out, d, buf, sem = refs
    else:
        out, buf, sem = refs
    group = math.gcd(part, _DMA_GROUP)
    start = pl.program_id(0) * chunk

    def loop(trips, body):
        def trip(i, carry):
            body(i)
            return carry
        lax.fori_loop(0, trips, trip, 0)

    def one_part(p):
        base = pl.multiple_of(p * part, part)
        r0 = lax.min(lax.max(words[0] - (start + base), 0), part)
        r1 = lax.min(lax.max(words[1] - (start + base), 0), part)
        here = pl.ds(base, part)

        @pl.when(r1 <= r0)
        def _():
            out[here, :] = jnp.zeros((part, out.shape[1]), out.dtype)
            if scaled:
                d[here, :] = jnp.zeros((part, 1), d.dtype)

        @pl.when(r1 > r0)
        def _():
            # whole groups of rows that cover the live ones: a row too
            # many is a row of this chunk, fetched and masked
            first = lax.div(r0, group)
            groups = lax.div(r1 + group - 1, group) - first

            def fetch(g):
                for i in range(group):
                    r = (first + g) * group + i
                    pltpu.make_async_copy(src.at[tok[base + r]], buf.at[r],
                                          sem).start()

            def land(g):
                # a wait counts bytes: one for a group's worth
                pltpu.make_async_copy(src.at[pl.ds(0, group)],
                                      buf.at[pl.ds(0, group)], sem).wait()

            loop(groups, fetch)
            loop(groups, land)

            def piece(i):
                s = pl.multiple_of(i * sub, sub)
                at = pl.ds(base + s, sub)
                rows = buf[pl.ds(s, sub), 0, :]
                row = s + lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
                live = (row >= r0) & (row < r1)
                if scaled:
                    d[at, :] = jnp.where(live, jnp.sum(
                        rows * dot[at, :].astype(jnp.float32), axis=1,
                        keepdims=True), 0.0)
                    rows = rows * weight[at, :]
                out[at, :] = jnp.where(live, rows, 0.0).astype(out.dtype)

            loop(part // sub, piece)

    loop(chunk // part, one_part)


# jitted: the layers' calls share ONE traced and lowered copy of each kernel
# (a kernel's size is set-up time, every instance anew; XLA inlines the call)
@functools.partial(jax.jit, static_argnums=(3, 4))
def _spread_rows(src, token, sched, chunk, interpret, scale=None, dot=None):
    """Sorted order from token order, over the live rows alone:
    ``out[r] = src[token[r]]`` for ``lo <= r < hi`` and exactly zero
    elsewhere; [N, h] -> [N k, h], gathered from ``src`` by token with no
    ``repeat`` of it in between.  With ``scale`` [N k] float32 (a weight a
    pair, sorted order) and ``dot`` [N k, h] (sorted order) the row is
    scaled in float32 before it is rounded, and ``d[r] = <src[token[r]],
    dot[r]>`` in float32 (zero outside the range) comes with it ->
    ``(out, d [N k])``, ``out`` written over ``dot``."""
    m, h = token.shape[0], src.shape[1]
    n_chunks = m // chunk
    scaled = scale is not None
    part, sub = math.gcd(chunk, 512), math.gcd(chunk, 128)
    if scaled:
        # the float32 copy of ``src`` and the weights' column wait for
        # ``dot``: made as soon as ``src`` exists they sit through the
        # recomputed forward (0.2 GiB of the step's scratch)
        src, scale, dot = lax.optimization_barrier((src, scale, dot))
    block = pl.BlockSpec((chunk, h), lambda c, words: (c, 0))
    column = pl.BlockSpec((chunk, 1), lambda c, words: (c, 0))
    in_specs = [pl.BlockSpec((chunk,), lambda c, words: (c,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [_sched_words(sched), token, src.astype(jnp.float32)[:, None, :]]
    out_specs, out_shape = block, jax.ShapeDtypeStruct((m, h), src.dtype)
    if scaled:
        in_specs += [column, pl.BlockSpec((chunk, h), _live_chunk(n_chunks))]
        args += [scale[:, None], dot]
        out_specs = (block, column)
        out_shape = (out_shape, jax.ShapeDtypeStruct((m, 1), jnp.float32))
    got = pl.pallas_call(
        functools.partial(_spread_kernel, chunk=chunk, part=part, sub=sub,
                          scaled=scaled),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_chunks,), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((part, 1, h), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        # the scaled rows are written over ``dot`` (dead rows: zeros already)
        input_output_aliases={4: 0} if scaled else {},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_ROW_VMEM_BYTES),
        name="bps_moe_spread_scaled" if scaled else "bps_moe_spread",
        interpret=interpret)(*args)
    return (got[0], got[1].reshape(m)) if scaled else got


def _gather_sum_rows(rows, inverse, top_k, weights=None):
    """Token order from sorted order, the transpose of ``_spread_rows``:
    ``out[n] = sum_j weights[n, j] * rows[inverse[n k + j]]``, a float32
    sum in slot order (``weights=None``: ones) -> [N, h] in ``rows.dtype``.
    A dead pair's row is exactly zero, so nothing is selected: all ``N k``
    rows are fetched, the one row pass that does not follow the live rows
    yet (PERF.md section 7, PR 30: a fetch that skips the dead ones wants
    single-row DMAs off a 2-D bfloat16 array, which Mosaic refuses)."""
    m, h = rows.shape
    pairs = rows[inverse].reshape(m // top_k, top_k, h).astype(jnp.float32)
    if weights is not None:
        pairs = pairs * weights[..., None]
    return jnp.sum(pairs, axis=1).astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _dispatch_rows(x, token, inverse, sched, top_k, chunk, interpret):
    """``xs``: each token's row at its live pairs' places in the sorted
    order (``_spread_rows``); backward, a token's row gradient is the sum
    of its k pairs' (``_gather_sum_rows``)."""
    del inverse, top_k
    return _spread_rows(x, token, sched, chunk, interpret)


def _dispatch_rows_fwd(x, token, inverse, sched, top_k, chunk, interpret):
    return _spread_rows(x, token, sched, chunk, interpret), inverse


def _dispatch_rows_bwd(top_k, chunk, interpret, inverse, g):
    return _gather_sum_rows(g, inverse, top_k), None, None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _combine_rows(ys, weights, scale, token, inverse, sched, top_k, chunk,
                  interpret):
    """``y``: each token's weighted sum of its pairs' rows
    (``_gather_sum_rows``); ``scale`` is ``weights`` in sorted order.
    Backward runs in SORTED order over the live rows (one
    ``_spread_rows``): a pair's row gradient is its token's times its
    weight, its weight's gradient the dot of its row with its token's
    gradient — so the residuals are the sorted rows themselves and a
    recomputed forward has no gather to repeat."""
    del scale, token, sched
    return _gather_sum_rows(ys, inverse, top_k, weights)


def _combine_rows_fwd(ys, weights, scale, token, inverse, sched, top_k, chunk,
                      interpret):
    return (_gather_sum_rows(ys, inverse, top_k, weights),
            (ys, scale, token, inverse, sched))


def _combine_rows_bwd(top_k, chunk, interpret, res, g):
    ys, scale, token, inverse, sched = res
    g_ys, d = _spread_rows(g, token, sched, chunk, interpret, scale, dot=ys)
    g_w = d[inverse].reshape(-1, top_k)
    return g_ys, g_w, None, None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _gate_kernel(words, gate, up, *refs, sub, backward):
    """One chunk of ``silu(gate) * up`` (float32, rounded once), or of its
    two gradients; zeros where the schedule has no live row."""
    c = pl.program_id(0)
    live = (c >= words[2]) & (c < words[3])
    outs = refs[1:] if backward else refs

    @pl.when(jnp.logical_not(live))
    def _():
        for out in outs:
            out[...] = jnp.zeros_like(out)

    @pl.when(live)
    def _():
        def piece(i, carry):
            at = pl.ds(pl.multiple_of(i * sub, sub), sub)
            a = gate[at, :].astype(jnp.float32)
            b = up[at, :].astype(jnp.float32)
            sig = 1.0 / (1.0 + jnp.exp(-a))
            if backward:
                g = refs[0][at, :].astype(jnp.float32)
                outs[0][at, :] = (g * b * sig * (1.0 + a * (1.0 - sig))
                                  ).astype(outs[0].dtype)
                outs[1][at, :] = (g * a * sig).astype(outs[1].dtype)
            else:
                outs[0][at, :] = (a * sig * b).astype(outs[0].dtype)
            return carry
        lax.fori_loop(0, gate.shape[0] // sub, piece, 0)


def _relu2_kernel(words, x, *refs, sub, backward):
    """One chunk of ``relu(x)^2`` (float32, rounded once) or, backward, of
    its gradient FROM ITS RESULT: ``x`` is then ``act = relu(up)^2`` and
    the gradient ``2 sqrt(act) g`` (``sqrt(act) = relu(up)``), so that
    ``up`` is no residual; zeros where the schedule has no live row."""
    c = pl.program_id(0)
    live = (c >= words[2]) & (c < words[3])
    out = refs[-1]

    @pl.when(jnp.logical_not(live))
    def _():
        out[...] = jnp.zeros_like(out)

    @pl.when(live)
    def _():
        def piece(i, carry):
            at = pl.ds(pl.multiple_of(i * sub, sub), sub)
            a = x[at, :].astype(jnp.float32)
            if backward:
                a = 2.0 * jnp.sqrt(a) * refs[0][at, :].astype(jnp.float32)
            else:
                a = jnp.square(jnp.maximum(a, 0.0))
            out[at, :] = a.astype(out.dtype)
            return carry
        lax.fori_loop(0, x.shape[0] // sub, piece, 0)


@functools.partial(jax.jit, static_argnums=(1, 2),
                   static_argnames=("backward", "gated"))
def _gate_call(sched, chunk, interpret, *rows, backward, gated=True):
    m, f = rows[0].shape
    n_chunks = m // chunk
    shape = jax.ShapeDtypeStruct((m, f), rows[0].dtype)
    pair = backward and gated           # two gradients: the gate's, up's
    # columns a grid step: all of them where every operand's two buffers
    # fit three quarters of the VMEM asked for (an expert width of 896:
    # 17.5 MiB), else halves of them (2048 backward: 40 MiB -> 20), else
    # the largest share of them in whole lane tiles (2688 -> 896)
    width, blocks = f, len(rows) + (2 if pair else 1)

    def fits(width):
        return (2 * blocks * chunk * width * rows[0].dtype.itemsize
                <= 3 * _ROW_VMEM_BYTES // 4)

    while not fits(width) and width % 256 == 0:
        width //= 2
    if not fits(width):
        width = max([w for w in range(128, width, 128)
                     if f % w == 0 and fits(w)], default=width)
    live = _live_chunk(n_chunks)
    out = pl.BlockSpec((chunk, width), lambda c, j, words: (c, j))
    name = ("bps_moe_gate" if gated else "bps_moe_act") + (
        "_bwd" if backward else "")
    return pl.pallas_call(
        functools.partial(_gate_kernel if gated else _relu2_kernel,
                          sub=math.gcd(chunk, 256), backward=backward),
        out_shape=(shape, shape) if pair else shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_chunks, f // width),
            in_specs=[pl.BlockSpec(
                (chunk, width),
                lambda c, j, words: (live(c, words)[0], j))] * len(rows),
            out_specs=(out, out) if pair else out),
        # ungated, backward: the gradient is written over the incoming one,
        # which nothing reads again (forward, writing over ``up`` costs the
        # compiled step 0.9 GiB more: compile-only, PR 39)
        input_output_aliases={len(rows): 0} if backward and not gated else {},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_ROW_VMEM_BYTES),
        name=name, interpret=interpret)(_sched_words(sched), *rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _silu_gate_rows(gate, up, sched, chunk, interpret):
    """``silu(gate) * up`` over the live chunks, zero elsewhere (the dead
    rows of both are exact zeros, and so is their product)."""
    return _gate_call(sched, chunk, interpret, gate, up, backward=False)


def _silu_gate_rows_fwd(gate, up, sched, chunk, interpret):
    return (_silu_gate_rows(gate, up, sched, chunk, interpret),
            (gate, up, sched))


def _silu_gate_rows_bwd(chunk, interpret, res, g):
    gate, up, sched = res
    return (*_gate_call(sched, chunk, interpret, gate, up, g,
                        backward=True), None)


_silu_gate_rows.defvjp(_silu_gate_rows_fwd, _silu_gate_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _relu2_rows(up, sched, chunk, interpret):
    """``relu(up)^2`` over the live chunks, zero elsewhere: the activation
    of experts WITHOUT a gate (two matrices an expert).  Its backward
    reads the RESULT (``2 sqrt(act) g``, written over ``g``):
    the one ``[N k, f]`` residual is the one the ``down`` matmul keeps
    anyway (at 22 pairs a token such an array is 0.9 GiB a block)."""
    return _gate_call(sched, chunk, interpret, up, backward=False,
                      gated=False)


def _relu2_rows_fwd(up, sched, chunk, interpret):
    act = _relu2_rows(up, sched, chunk, interpret)
    return act, (act, sched)


def _relu2_rows_bwd(chunk, interpret, res, g):
    act, sched = res
    return (_gate_call(sched, chunk, interpret, act, g, backward=True,
                       gated=False), None)


_relu2_rows.defvjp(_relu2_rows_fwd, _relu2_rows_bwd)


# ------------------------------- a thin held share: windows of the live range

# A held layer works in windows where one window (twice the expected live
# rows, in whole chunks) is at most this share of the pair rows.  Measured
# at one shape below it: 8 of 512 experts held at top-22 over 8 192 tokens
# (windows of 6 144 of 180 224 rows, a 29th) ran forward + backward in 11.8
# ms where the whole arrays took 35.9 (v5e; PERF.md section 6, PR 40).  The
# next shape up the models have, an eighth live (a window a quarter of the
# rows), is not measured; the constant lies between.
_WINDOW_SHARE = 1 / 16


def window_rows(n: int, top_k: int, held_count: int, experts: int
                ) -> Optional[int]:
    """Rows ``W`` of a window of a held layer's sorted order, or ``None``
    where the layer works on all ``n * top_k`` pair rows at once.

    The rows that land on the ``held_count`` held experts of ``experts``
    are ``L = n k G / E`` under a balanced router: a window is the multiple
    of the row chunk that holds ``2 L``, so a batch near the expectation
    runs ONE window and a heavier one more (``window_trips``).  ``None``
    where such a window is more than ``_WINDOW_SHARE`` of the pair rows
    (the whole arrays then cost little more than the windows' glue).  Pure
    arithmetic on shapes, in the manner of ``row_schedule``: nothing
    chooses it but ``(N, k, G, E)``."""
    rows = n * top_k
    chunk = math.gcd(rows, _ROW_CHUNK)
    if chunk % 8:
        return None
    window = -(-2 * rows * held_count // (experts * chunk)) * chunk
    return window if window <= _WINDOW_SHARE * rows else None


def window_trips(counts, held: Tuple[int, int], window: int):
    """Windows of ``window`` rows that cover the held experts' live range
    ``[lo, hi)`` of the sorted order: the trip count of a windowed layer's
    loops (0 where nobody routed here, ``N k / window`` where everybody
    did), host integers or traced ones alike."""
    sched = row_schedule(counts, held, window)
    return (sched["hi"] - sched["lo"] + window - 1) // window


def _window_rows_of(order, scale, counts, held, window, i):
    """Window ``i`` of the live range: rows ``[s, s + W)`` of the sorted
    order with ``s = min(lo + i W, N k - W)`` -> their pairs (``order``),
    their weights (``scale``) and their group sizes ``[dead head, the held
    experts' counts clipped to the window's own rows ``[lo + i W, lo +
    (i + 1) W)``, dead tail]``: a window that the clamp moved back overlaps
    its neighbour, and holds the shared rows dead."""
    start, count = held
    lo = jnp.sum(counts[:start])
    edges = lo + jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                  jnp.cumsum(counts[start:start + count])])
    a = lo + i * window
    b = jnp.minimum(a + window, edges[-1])
    s = jnp.minimum(a, order.shape[0] - window)
    sizes = jnp.concatenate([(a - s)[None], jnp.diff(jnp.clip(edges, a, b)),
                             (s + window - b)[None]]).astype(jnp.int32)
    return (lax.dynamic_slice(order, (s,), (window,)),
            lax.dynamic_slice(scale, (s,), (window,)), sizes)


def _sum_rows_by_token(rows, token, n):
    """Token order from a window's rows: ``out[t] = sum of rows[r] over the
    r with token[r] == t``, float32 [n, h].  Dead rows are exact zeros and
    add nothing.  XLA's scatter-add: 0.36 ms at [6144, 1024] -> [8192, 1024]
    on a v5e, where a one-hot matmul in the three bfloat16 passes float32
    rows need took 1.67 (PERF.md section 6, PR 40)."""
    return jnp.zeros((n, rows.shape[1]), jnp.float32).at[token].add(
        rows.astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _dispatch_window(x32, token, sched, dtype, chunk, interpret):
    """A window's ``xs`` (``_spread_rows`` of the rows rounded to
    ``dtype``); backward, a token's float32 row gradient is the sum of its
    pairs' in the window."""
    return _spread_rows(x32.astype(dtype), token, sched, chunk, interpret)


def _dispatch_window_fwd(x32, token, sched, dtype, chunk, interpret):
    return (_dispatch_window(x32, token, sched, dtype, chunk, interpret),
            (token, x32))


def _dispatch_window_bwd(dtype, chunk, interpret, res, g):
    token, x32 = res                  # the rows' count: nothing of it read
    return _sum_rows_by_token(g, token, x32.shape[0]), None, None


_dispatch_window.defvjp(_dispatch_window_fwd, _dispatch_window_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _combine_window(ys, scale, token, sched, n, chunk, interpret):
    """A window's part of ``y``: each token's float32 sum of its pairs'
    rows in the window, each times its weight.  Backward is
    ``_combine_rows``'s, at the window's rows: one scaled ``_spread_rows``."""
    return _sum_rows_by_token(ys.astype(jnp.float32) * scale[:, None], token,
                              n)


def _combine_window_fwd(ys, scale, token, sched, n, chunk, interpret):
    return (_combine_window(ys, scale, token, sched, n, chunk, interpret),
            (ys, scale, token, sched))


def _combine_window_bwd(n, chunk, interpret, res, g):
    ys, scale, token, sched = res
    g_ys, d = _spread_rows(g.astype(ys.dtype), token, sched, chunk, interpret,
                           scale, dot=ys)
    return g_ys, d, None, None


_combine_window.defvjp(_combine_window_fwd, _combine_window_bwd)


def _window_part(x32, scale, stacks, token, sizes, interpret):
    """One window's part of ``y`` [N, h] float32: the held layer's code at
    ``W`` rows.  ``x32`` [N, h] float32 holds the rows' ``stacks``-dtype
    values; ``scale``, ``token`` [W] and ``sizes`` [G + 2] are the window's
    (``_window_rows_of``).  The grouped matmuls see ``G + 2`` groups of
    which the stacks hold ``1 .. G``, as the whole layer's see ``E`` of
    which they hold ``first .. first + G - 1``.  One outer scope: a
    transform (the backward loop's ``jax.vjp``) wraps the first scope
    entered after it, and the readers of the grouped matmuls' time look
    for ``bps.moe.experts/``, not ``jvp(bps.moe.experts)/``."""
    count, dt = stacks["up"].shape[0], stacks["up"].dtype
    chunk = math.gcd(token.shape[0], _ROW_CHUNK)
    first = jnp.asarray(1, jnp.int32)
    with jax.named_scope("bps.moe.window"):
        sched = row_schedule(sizes, (1, count), chunk)
        with jax.named_scope("bps.moe.dispatch"):
            xs = _dispatch_window(x32, token, sched, dt, chunk, interpret)
        with jax.named_scope("bps.moe.experts"):
            if "gate" in stacks:
                gate = _grouped_matmul(xs, stacks["gate"], sizes, interpret,
                                       first)
            up = _grouped_matmul(xs, stacks["up"], sizes, interpret, first)
        if "gate" in stacks:
            with jax.named_scope("bps.moe.gate"):
                act = _silu_gate_rows(gate, up, sched, chunk, interpret)
        else:
            with jax.named_scope("bps.moe.act"):
                act = _relu2_rows(up, sched, chunk, interpret)
        with jax.named_scope("bps.moe.experts"):
            ys = _grouped_matmul(act, stacks["down"], sizes, interpret, first)
        with jax.named_scope("bps.moe.combine"):
            return _combine_window(ys, scale, token, sched, x32.shape[0],
                                   chunk, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _windowed_experts(x, weights, stacks, scale, order, counts, held, top_k,
                      window, interpret):
    """The held experts' share of ``y`` [N, h] (``x.dtype``) in windows of
    ``window`` rows of the live range, ``window_trips`` of them: a loop
    with a runtime trip count, which JAX does not reverse — so ONE
    ``custom_vjp`` from ``(x, weights, stacks)``: forward adds each
    window's part (``_window_part``) to a float32 carry, backward takes
    ``jax.vjp`` of the same window and accumulates the gradients of ``x``
    and the stacks in float32 and the weights' at their ``[N, k]`` places
    (``order``).  ``scale`` [N k] is ``weights`` in sorted order, ``order``
    [N k] the sorted pairs: residuals are these, ``x`` and ``counts``, and
    the backward recomputes its windows' forward."""
    return _windowed_fwd(x, weights, stacks, scale, order, counts, held,
                         top_k, window, interpret)[0]


def _windowed_fwd(x, weights, stacks, scale, order, counts, held, top_k,
                  window, interpret):
    del weights                       # their values ride in ``scale``
    x32 = x.astype(jnp.float32)
    cast = {k: v.astype(x.dtype) for k, v in stacks.items()}

    def body(i, y):
        pairs, scale_w, sizes = _window_rows_of(order, scale, counts, held,
                                                window, i)
        return y + _window_part(x32, scale_w, cast, pairs // top_k, sizes,
                                interpret)

    y = lax.fori_loop(0, window_trips(counts, held, window), body,
                      jnp.zeros(x.shape, jnp.float32))
    return y.astype(x.dtype), (x, stacks, scale, order, counts)


def _windowed_bwd(held, top_k, window, interpret, res, g):
    x, stacks, scale, order, counts = res
    x32, g32 = x.astype(jnp.float32), g.astype(jnp.float32)
    cast = {k: v.astype(x.dtype) for k, v in stacks.items()}

    def body(i, carry):
        g_x, g_stacks, g_scale = carry
        pairs, scale_w, sizes = _window_rows_of(order, scale, counts, held,
                                                window, i)
        _, pull = jax.vjp(
            lambda x32, scale_w, cast: _window_part(
                x32, scale_w, cast, pairs // top_k, sizes, interpret),
            x32, scale_w, cast)
        d_x, d_scale, d_stacks = pull(g32)
        # dead rows give exact zeros, so a row two windows share adds once
        return (g_x + d_x,
                jax.tree.map(lambda a, d: a + d.astype(a.dtype), g_stacks,
                             d_stacks),
                g_scale.at[pairs].add(d_scale))

    g_x, g_stacks, g_scale = lax.fori_loop(
        0, window_trips(counts, held, window), body,
        (jnp.zeros(x.shape, jnp.float32),
         jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32), stacks),
         jnp.zeros(scale.shape, jnp.float32)))
    return (g_x.astype(x.dtype),
            g_scale.reshape(-1, top_k).astype(scale.dtype),
            jax.tree.map(lambda g, s: g.astype(s.dtype), g_stacks, stacks),
            None, None, None)


_windowed_experts.defvjp(_windowed_fwd, _windowed_bwd)


# --------------------------------------- the route stage: k of E in one pass

# Tokens (lanes) a grid step of the selection takes, and slices of 8 experts
# a trip of a round's scan: the fastest of nine pairs measured on a v5e at
# the four shapes the models send (PERF.md section 6, PR 41) — at [8192, 512]
# top-22 0.28 ms forward where (512, 4) took 0.37, (128, 4) 0.92 and
# (2048, 4) 0.36; at 64 and 16 experts every pair from 512 tokens up lies
# within 0.03 ms.  Each is clipped to the array.
_SELECT_TOKENS = 1024
_SELECT_UNROLL = 8
_TAKEN = np.iinfo(np.int32).min   # below every key (they are clamped above it)


def _select_kernel(probs, bias, idx, picked, counts, work, *, top_k, n):
    """One block of tokens, experts on sublanes and tokens on lanes
    (``probs`` [E, T]): a max over a token's experts is then elementwise
    over E / 8 slices and ONE 8-sublane reduction, where tokens on sublanes
    would pay a cross-lane reduction a round.  The scores are compared as
    int32 keys in XLA's total order (a sorting top-k's own: -0 below +0, NaN
    above +inf); a taken expert's key becomes ``_TAKEN``, which no score's
    key equals, so a row of ``-inf`` or of equal scores still gives k
    distinct experts.  A round is ONE pass over the slices: mark the
    previous round's pick, then carry per sublane the best key, its slice
    and its probability — strictly better only, so the earliest slice wins
    among equals — and the 8 sublanes are reduced to the lowest expert that
    holds the maximum.  Loops, but for ``_SELECT_UNROLL`` slices a trip (a
    kernel's size is set-up time)."""
    e, t = probs.shape
    slices = e // 8
    unroll = math.gcd(slices, _SELECT_UNROLL)
    sub = lax.broadcasted_iota(jnp.int32, (8, t), 0)

    def keys(i, carry):
        at = pl.ds(pl.multiple_of(i * 8, 8), 8)
        bits = lax.bitcast_convert_type(probs[at, :] + bias[at, :],
                                        jnp.int32)
        work[at, :] = jnp.maximum(
            jnp.where(bits < 0, bits ^ np.int32(0x7FFFFFFF), bits),
            _TAKEN + 1)
        return carry
    lax.fori_loop(0, slices, keys, 0)

    def mark(i, prev):
        """Slice ``i`` of the keys with the expert ``prev + sub`` taken."""
        at = pl.ds(pl.multiple_of(i * 8, 8), 8)
        w = jnp.where(prev == i * 8, _TAKEN, work[at, :])
        work[at, :] = w
        return at, w

    def one_round(j, prev):
        prev = prev - sub

        def scan(i, best):
            for s in range(unroll):       # by hand: Mosaic unrolls all or none
                key, where, prob = best
                at, w = mark(i * unroll + s, prev)
                better = w > key
                best = (jnp.where(better, w, key),
                        jnp.where(better, i * unroll + s, where),
                        jnp.where(better, probs[at, :], prob))
            return best
        key, where, prob = lax.fori_loop(
            0, slices // unroll, scan,
            (jnp.full((8, t), _TAKEN, jnp.int32), jnp.zeros((8, t), jnp.int32),
             jnp.zeros((8, t), jnp.float32)))
        expert = where * 8 + sub
        top = jnp.max(key, axis=0, keepdims=True)
        chosen = jnp.min(jnp.where(key == top, expert, e), axis=0,
                         keepdims=True)                          # [1, T]
        idx[pl.ds(j, 1), :] = chosen
        # one sublane holds the chosen expert: the sum is its probability
        picked[pl.ds(j, 1), :] = jnp.sum(
            jnp.where(expert == chosen, prob, 0.0), axis=0, keepdims=True)
        return chosen
    last = lax.fori_loop(0, top_k, one_round, jnp.full((1, t), -1, jnp.int32))

    @pl.when(pl.program_id(0) == 0)
    def _():
        counts[...] = jnp.zeros_like(counts)
    real = (pl.program_id(0) * t
            + lax.broadcasted_iota(jnp.int32, (1, t), 1)) < n   # not padding

    last = last - sub

    def count(i, carry):
        at, w = mark(i, last)
        took = ((w == _TAKEN) & real).astype(jnp.int32)
        counts[at, :] += sum(took[:, c:c + 128] for c in range(0, t, 128))
        return carry
    lax.fori_loop(0, slices, count, 0)


# jitted: a model's layers share ONE traced and lowered copy of the kernel
@functools.partial(jax.jit, static_argnums=(2, 3))
def _select_call(probs, bias, top_k, interpret):
    n, e = probs.shape
    if not 1 <= top_k <= e:
        raise ValueError(f"top_k={top_k}: the router knows {e} experts")
    if bias is None:
        bias = jnp.zeros((e,), jnp.float32)
    # experts on sublanes (whole slices of 8; a padded expert's -inf loses
    # every tie to a real one, which has the lower index), tokens on lanes
    rows, lanes = -(-e // 8) * 8, -(-n // 128) * 128
    t = math.gcd(lanes, _SELECT_TOKENS)
    scores = jnp.pad(probs.T, ((0, rows - e), (0, lanes - n)))
    bias = jnp.pad(bias.astype(jnp.float32), (0, rows - e),
                   constant_values=-jnp.inf)[:, None]
    pairs = pl.BlockSpec((top_k, t), lambda i: (0, i))
    idx, picked, counts = pl.pallas_call(
        functools.partial(_select_kernel, top_k=top_k, n=n),
        out_shape=(jax.ShapeDtypeStruct((top_k, lanes), jnp.int32),
                   jax.ShapeDtypeStruct((top_k, lanes), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 128), jnp.int32)),
        grid=(lanes // t,),
        in_specs=[pl.BlockSpec((rows, t), lambda i: (0, i)),
                  pl.BlockSpec((rows, 1), lambda i: (0, 0))],
        # the counts' block stays put: the grid's steps add to it in turn
        out_specs=(pairs, pairs, pl.BlockSpec((rows, 128), lambda i: (0, 0))),
        scratch_shapes=[pltpu.VMEM((rows, t), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="bps_moe_select", interpret=interpret)(scores, bias)
    return idx[:, :n].T, picked[:, :n].T, jnp.sum(counts[:e], axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _select_experts(probs, bias, top_k, interpret):
    """The route stage's selection, from ONE pass over the scores ``probs``
    [N, E] float32: ``(idx [N, k] int32, picked [N, k], counts [E] int32)``
    — the ``top_k`` largest of ``probs + bias`` a token (``bias`` [E] or
    ``None``: of ``probs``), descending, the lowest expert first among
    equals (a stable sort's order, bit for bit), ``probs`` read at them,
    and the pairs each expert received.  k rounds of max-and-mark over E
    experts (``_select_kernel``) in place of a sort of E: every model here
    keeps k <= E / 8.  The gradient reaches ``probs`` through ``picked``
    alone, as a gather's would: ``g_probs[n, e] = sum_j g[n, j] [idx[n, j]
    == e]``, a dense compare (a token's experts are distinct: one term at
    most, the scatter-add's bits); none reaches ``bias``."""
    return _select_call(probs, bias, top_k, interpret)


def _select_experts_fwd(probs, bias, top_k, interpret):
    out = _select_call(probs, bias, top_k, interpret)
    return out, out[0]


def _select_experts_bwd(top_k, interpret, idx, g):
    _, g_picked, g_counts = g
    # experts-major, as the forward reads the scores: [k, E, N] summed over k
    hit = idx.T[:, None, :] == lax.broadcasted_iota(
        jnp.int32, (1, g_counts.shape[0], 1), 1)
    return jnp.sum(jnp.where(hit, g_picked.T[:, None, :], 0.0), axis=0).T, None


_select_experts.defvjp(_select_experts_fwd, _select_experts_bwd)


def dropless_moe_mlp(x, params, top_k: int,
                     interpret: Optional[bool] = None, *,
                     held: Optional[Tuple[int, int]] = None,
                     renormalize: bool = False,
                     routing: Optional[Tuple] = None):
    """Dropless top-k MoE MLP over a token shard ``x`` [N, h].

    params: ``{"router": [h, E] float32, "gate": [G, h, f], "up":
    [G, h, f], "down": [G, f, h]}`` (``gate`` absent: ungated experts,
    below).  No biases.  ``held=None``: the
    stacks are all E experts (``G = E``).  ``held=(first, G)``: they are
    experts ``first .. first + G - 1`` of the E the router knows, one
    chip's share of an expert-parallel layer (module docstring).

        p      = softmax(x_f32 @ router)            over all E
        w, idx = the k largest of p, and where      renormalize: w /= sum_j w
        y      = sum_j w[:, j] * down_idx_j(silu(gate_idx_j x) * up_idx_j x)
                 over the j whose expert idx_j is held

    How the stage ``bps.moe.route`` selects (``_select_experts``): ONE
    pass over the [N, E] scores — a kernel, ``bps_moe_select``, experts on
    sublanes and tokens on lanes, k rounds of max-and-mark a token — gives
    the k indices (descending by score, the lowest expert first among
    equals: a stable descending sort's order and bits), the scores read
    at them and the per-expert counts; its backward is a dense compare.
    No sort of E, no scalar gather or scatter; k rounds over E beat the
    sort wherever k <= E / 8, which every model here keeps (PERF.md
    section 6, PR 41).

    ``routing=(p, beta)``: the probabilities come from OUTSIDE — a router
    of the model's own (an MLP, a state carried from layer to layer) — as
    ``p`` [N, E] float32, with a selection bias ``beta`` [E] or ``None``:
    the stage then takes the k largest of ``p + beta`` (formed inside the
    pass) and reads the weights from ``p`` at them (the bias chooses and is not
    weighed: no gradient reaches it; ``p``'s reaches the caller's router
    through the weights); ``params`` needs no ``router``; everything after
    is the same code, at any k: ``p`` need not sum to one (sigmoid scores:
    ``models/glm_lite.py``, k = 4), and ``renormalize`` then divides the k
    weights read by their sum + 1e-20 (as that family's code has it; a
    softmax's own weights keep the bare sum).  ``top_k = 1`` is covered
    like any k (``N`` pair rows, a token's one pair live iff its expert is
    held).

    Experts WITHOUT a gate: where ``params`` holds ``up`` and ``down`` and
    no ``gate``, an expert is two matrices, ``down_e(relu(up_e x)^2)``
    (``models/nemotron_h.py``: two grouped matmuls a pass instead of
    three; with ``held`` the activation is a row kernel over the live
    chunks, ``bps_moe_act`` / ``bps_moe_act_bwd`` under the scope
    ``bps.moe.act``, as the gate product is under ``bps.moe.gate``).  What
    ``params`` holds says which: the squared ReLU is the one ungated
    activation computed here, so there is no argument to name it.

    The weights are the model's: renormalised over the k chosen BEFORE the
    held experts are selected, so the shares of a layer add up to the
    whole layer; a token none of whose k experts is held gets exactly
    zero.  No pair routed to a held expert is ever dropped: the pair rows
    are the worst case, all ``N * k`` (every token could choose held
    experts only), and the rows of pairs routed elsewhere ride along dead.
    The shape is the contract; the work follows the live rows: the held
    experts' rows are one range of the sorted order (``row_schedule``),
    the grouped matmuls' grids cover it alone, and so do the row passes
    around them — the spread into sorted order and its weighted backward
    form (``_spread_rows``: one DMA a live row, zeros written for every
    other chunk, nothing of it read) and the gate product.  Gauges
    ``moe.held_pair_share`` (live rows) and ``moe.visited_row_share``
    (rows visited, in whole chunks).  The token-order half — the combine's
    gather and the dispatch's backward (``_gather_sum_rows``) — fetches
    every row, and every array above is written whole, zeros and all.

    Where the held share is thin — ``window_rows(N, k, G, E)`` says so
    from the shapes alone: a window of twice the expected live rows is at
    most a 16th of ``N k`` — none of those arrays exists.  The layer is
    then ``_windowed_experts``: the same pieces on ``W`` rows of the
    sorted order at a time, ``window_trips`` windows over the live range
    by a runtime trip count (one for a batch near the expectation, ``N k /
    W`` if every token chose held experts: nothing is dropped, the layer
    runs longer), and the token-order half is a float32 sum over the
    window's rows by token (``_sum_rows_by_token``) in place of the
    gathers.  Same result, same precision (rows in ``x.dtype``, scaling
    and sums in float32).  Gauges ``moe.window_trips`` and, for such a
    layer, ``moe.visited_row_share`` = trips x W / N k.

    Returns ``(y [N, h] in x.dtype, aux, z, counts [E] int32)``:
    ``aux = E * sum_e f_e P_e`` with ``f_e`` = pairs routed to e / N and
    ``P_e`` = mean router probability (the Switch load-balance loss
    summed over the k choices), ``z = mean(logsumexp(logits)^2)``
    (ST-MoE router z-loss; exactly 0 with ``routing``, whose logits stay
    with the caller), ``counts`` the pairs each expert received —
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
    e = (params["router"] if routing is None else routing[0]).shape[-1]
    gated = "gate" in params
    first = None
    if held is not None:
        start, count = held
        if not (0 <= start and 1 <= count and start + count <= e
                and params["up"].shape[0] == count):
            raise ValueError(
                f"held={held}: the stacks carry {params['up'].shape[0]} "
                f"experts and the router knows {e}")
        first = jnp.asarray(start, jnp.int32)
    with jax.named_scope("bps.moe.route"):
        if routing is None:
            logits = jnp.dot(x.astype(jnp.float32),
                             params["router"].astype(jnp.float32),
                             precision=lax.Precision.HIGHEST)   # [N, E]
            probs, bias = jax.nn.softmax(logits, axis=-1), None
        else:
            probs, bias = routing
            if probs.shape != (n, e) or probs.dtype != jnp.float32:
                raise ValueError(
                    f"routing: probabilities must be float32 [{n}, E], got "
                    f"{probs.dtype} {probs.shape}")
        idx, weights, counts = _select_experts(probs, bias, top_k, interpret)
        if renormalize:
            total = jnp.sum(weights, axis=-1, keepdims=True)
            if routing is not None:
                total = total + 1e-20
            weights = weights / total
        pair_expert = idx.reshape(n * top_k)
        aux = e * jnp.sum(counts.astype(jnp.float32) / n
                          * jnp.mean(probs, axis=0))
        z = (jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
             if routing is None else jnp.zeros((), jnp.float32))
    window = None if held is None else window_rows(n, top_k, held[1], e)
    if window is not None:
        # a thin share: no array of N k rows but the sort's three columns
        with jax.named_scope("bps.moe.dispatch"):
            order, scale = _sorted_pairs(pair_expert, weights)
        stacks = {k: v for k, v in params.items() if k != "router"}
        y = _windowed_experts(x, weights, stacks, scale, order, counts, held,
                              top_k, window, interpret)
        return y, aux, z, counts
    if held is not None:
        # the row passes below visit the chunks that meet the held
        # experts' rows, not all N k (``row_schedule``)
        chunk = math.gcd(n * top_k, _ROW_CHUNK)
        sched = row_schedule(counts, held, chunk)
    with jax.named_scope("bps.moe.dispatch"):
        # pairs sorted by expert (stable: a token's order within its
        # group is its arrival order); each token's row gathered k times
        if held is None:
            order = jnp.argsort(pair_expert, stable=True)       # [N k]
            inverse = jnp.argsort(order)
            xs = _permute_rows(jnp.repeat(x, top_k, axis=0), order, inverse)
        else:
            order, scale = _sorted_pairs(pair_expert, weights)
            inverse = jnp.argsort(order)
            token = order // top_k
            xs = _dispatch_rows(x, token, inverse, sched, top_k, chunk,
                                interpret)
    with jax.named_scope("bps.moe.experts"):
        dt = x.dtype
        if gated:
            gate = _grouped_matmul(xs, params["gate"].astype(dt), counts,
                                   interpret, first)
        up = _grouped_matmul(xs, params["up"].astype(dt), counts, interpret,
                             first)
        if held is None:
            act = (jax.nn.silu(gate) * up if gated
                   else jnp.square(jax.nn.relu(up)))
    if held is not None:
        # a kernel of its own scope: the readers of the grouped matmuls'
        # time take every ``pallas_call`` under ``bps.moe.experts``
        if gated:
            with jax.named_scope("bps.moe.gate"):
                act = _silu_gate_rows(gate, up, sched, chunk, interpret)
        else:
            with jax.named_scope("bps.moe.act"):
                act = _relu2_rows(up, sched, chunk, interpret)
    with jax.named_scope("bps.moe.experts"):
        down = params["down"].astype(dt)
        if held is not None:
            act, down = _tie_gradients(act, down)
        ys = _grouped_matmul(act, down, counts, interpret, first)
    with jax.named_scope("bps.moe.combine"):
        if held is None:
            pairs = _permute_rows(ys, inverse, order).reshape(n, top_k, h)
            y = jnp.sum(pairs.astype(jnp.float32) * weights[..., None],
                        axis=1)
        else:
            y = _combine_rows(ys, weights, scale, token, inverse, sched,
                              top_k, chunk, interpret)
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
    experts' mean, worst layer) and ``moe.visited_row_share`` (pair rows
    the layer's row passes visit over all of them: ``row_schedule``'s live
    chunks, the share rounded up to ``_ROW_CHUNK`` rows at either end; for
    a layer that works in windows, ``window_rows``, the windows it ran
    times their rows, with ``moe.window_trips`` = the windows of the worst
    layer).
    Host side: it reads the values, so call it outside any jitted step and
    off the step's critical path."""
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
        visited, trips = 0, []
        for layer in c.astype(np.int64):
            # the rule reads ``n * top_k`` alone: the layer's pair rows
            window = window_rows(int(layer.sum()), 1, held[1], len(layer))
            if window is not None:
                trips.append(int(window_trips(layer, held, window)))
                visited += trips[-1] * window
                continue
            chunk = math.gcd(int(layer.sum()), _ROW_CHUNK)
            sched = row_schedule(layer, held, chunk)
            visited += int(sched["end"] - sched["first"]) * chunk
        gauges.set("moe.visited_row_share", visited / float(c.sum()))
        if trips:
            gauges.set("moe.window_trips", float(max(trips)))
