"""Dropless top-k expert MLPs over the experts a chip holds
(:func:`dropless_moe_mlp`: ``models/olmoe.py``, ``mellum.py``, ``zaya.py``,
``glm_lite.py``, ``nemotron_h.py``, ``ling.py``, ``qwen3_next.py``): one
route stage (``_route``) and three implementations of the rest, chosen in
ONE place (:func:`layer_plan`) from the layer's shapes alone — every expert
local (OLMoE); a share on whole pair-row arrays (Mellum: a window would be
half the rows, ZAYA: all of them, GLM: a quarter); a thin share in windows
of its live range (Nemotron and Ling: a window a 32nd of the rows, Qwen3-Next:
an eighth).

With ``held=(first, count)`` the layer is one chip's share of an
expert-parallel deployment: it routes over all E experts, holds the stacks
of ``count`` consecutive ones, and returns what those give for the pairs
routed to them — the local half of expert parallelism.  The other half,
the exchange (top-k dispatch by ``all_to_all`` over an ``ep`` axis), is NOT
here yet (ROADMAP R1): a one-chip share runs without it, nothing stands in
for the absent chips.  Routing, router loss and counts: the token shard's.

Imports point one way: ``models/*`` -> this file -> ``ops/moe_kernels.py``
(its kernels are told which rows to visit; which are live is this file's
knowledge).  ``parallel/switch_moe.py`` and this file import neither other.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.moe_kernels import (_gate_call, _grouped_matmul, _row_chunk,
                               _select_call, _spread_rows, _sum_rows)


def held_range(experts_held, experts: int) -> Tuple[int, int]:
    """``(first, count)``, as ints, of the experts whose stacks live here:
    ``experts_held`` (a config file's list will do) or, for ``None``, all
    ``experts`` the router knows; ``ValueError`` where it is no range."""
    first, count = map(int, (0, experts) if experts_held is None
                       else experts_held)
    if not (0 <= first and 1 <= count and first + count <= experts):
        raise ValueError(f"experts_held={(first, count)} is no range of "
                         f"the {experts} experts")
    return first, count


# A held layer works in windows where one window (twice the expected live
# rows, in whole chunks) is at most this share of the pair rows.  Measured
# at two shapes (v5e; the layer alone, forward + backward): 8 of 512 experts
# held at top-22 over 8 192 tokens (windows of 6 144 of 180 224 rows, a
# 29th) ran in 11.8 ms where the whole arrays took 35.9 (PERF.md section 6,
# PR 40); 32 of 512 at top-10 over 32 768 tokens (windows of 40 960 of
# 327 680 rows, exactly an eighth) in 35.6 where they took 82.7, and the
# step of ``qwen3_next_80b.fused_1c`` went from 1 110 to 834 ms (PR 49).
# The first shape above it the models have, 8 of 64 at top-4 over 16 384
# tokens (GLM: a window a quarter of the rows), read +5.8 % tokens/s and
# -0.42 GiB in ONE scratch pair with the constant at a quarter (PR 49, not
# shipped there: a PR of its own, ROADMAP S4 (0)); Mellum's half is unmeasured.
_WINDOW_SHARE = 1 / 8


class LayerPlan(NamedTuple):
    kind: str                 # "all" | "held_rows" | "held_windows"
    chunk: int                # pair rows a grid step of the row kernels moves
    window: Optional[int]     # rows of a window ("held_windows"), else None


def layer_plan(rows: int, held_count: Optional[int], experts: int
               ) -> LayerPlan:
    """What a layer of ``rows = N k`` pair rows runs that holds
    ``held_count`` of ``experts`` experts (``None``: all are local): the
    layer's choice and ``publish_moe_stats``'s gauges both come from here.
    ``L = rows G / E`` rows land on the held experts under a balanced
    router: a window is the multiple of the row chunk that holds ``2 L``,
    so a batch near the expectation runs ONE window and a heavier one more
    (``window_trips``); past ``_WINDOW_SHARE`` of the pair rows the whole
    arrays cost little more than the windows' glue."""
    chunk = _row_chunk(rows)
    if held_count is None:
        return LayerPlan("all", chunk, None)
    window = -(-2 * rows * held_count // (experts * chunk)) * chunk
    if chunk % 8 == 0 and window <= _WINDOW_SHARE * rows:
        return LayerPlan("held_windows", chunk, window)
    return LayerPlan("held_rows", chunk, None)


def window_rows(n: int, top_k: int, held_count: int, experts: int
                ) -> Optional[int]:
    """Rows ``W`` of a window of a held layer's sorted order, or ``None``
    where it works on all ``n * top_k`` pair rows at once (``layer_plan``)."""
    return layer_plan(n * top_k, held_count, experts).window


def row_schedule(counts, held: Tuple[int, int], chunk: int) -> dict:
    """Which rows of the sorted order a held layer's row passes visit:
    ``{"lo", "hi", "first", "end"}``.  Pairs are sorted by expert, so the
    rows of the experts ``held = (start, count)`` are ONE range ``[lo, hi)``
    of the sorted order, read off the per-expert pair ``counts`` [E] before
    any row moves.  A pass works on the row chunks ``first .. end - 1``
    (``chunk`` rows each): those that meet the range, none when it is empty
    — ``(end - first) * chunk`` rows visited of ``sum(counts)``; every other
    chunk is written as zeros and nothing of it is read.  Pure arithmetic
    on ``counts``, host integers (numpy) or traced ones alike (the manner of
    ``ops.flash_attention.block_schedule``)."""
    xp = jnp if isinstance(counts, jax.Array) else np
    start, count = held
    lo = xp.sum(counts[:start])
    hi = lo + xp.sum(counts[start:start + count])
    first = lo // chunk
    end = xp.where(hi > lo, (hi + chunk - 1) // chunk, first)
    return {"lo": lo, "hi": hi, "first": first, "end": end}


def window_trips(counts, held: Tuple[int, int], window: int):
    """Windows of ``window`` rows that cover the held experts' live range
    ``[lo, hi)`` of the sorted order: the trip count of a windowed layer's
    loops (0 where nobody routed here, ``N k / window`` where everybody
    did), host integers or traced ones alike."""
    sched = row_schedule(counts, held, window)
    return (sched["hi"] - sched["lo"] + window - 1) // window


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a PERMUTATION of the rows.  The transpose of a
    gather is a scatter-add; of a permutation it is the gather by the
    inverse permutation, which is what the backward pass runs."""
    del inverse
    return x[perm]


_permute_rows.defvjp(lambda x, perm, inverse: (x[perm], inverse),
                     lambda inverse, g: (g[inverse], None, None))


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


_dispatch_rows.defvjp(
    lambda x, token, inverse, sched, top_k, chunk, interpret: (
        _spread_rows(x, token, sched, chunk, interpret), inverse),
    lambda top_k, chunk, interpret, inverse, g: (
        _gather_sum_rows(g, inverse, top_k), None, None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _combine_rows(ys, weights, scale, token, inverse, sched, top_k, chunk,
                  interpret):
    """``y``: each token's weighted sum of its pairs' rows
    (``_gather_sum_rows``); ``scale`` is ``weights`` in sorted order.  Backward
    runs in SORTED order over the live rows (one ``_spread_rows``): a pair's
    row gradient is its token's times its weight, its weight's gradient the
    dot of its row with its token's gradient — so the residuals are the sorted
    rows themselves and a recomputed forward has no gather to repeat."""
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _dispatch_window(x32, token, sched, dtype, chunk, interpret):
    """A window's ``xs`` (``_spread_rows`` of the rows rounded to ``dtype``);
    backward, a token's float32 row gradient sums its pairs' in the window."""
    return _spread_rows(x32.astype(dtype), token, sched, chunk, interpret)


def _dispatch_window_fwd(x32, token, sched, dtype, chunk, interpret):
    return (_dispatch_window(x32, token, sched, dtype, chunk, interpret),
            (token, sched, x32))


def _dispatch_window_bwd(dtype, chunk, interpret, res, g):
    token, sched, x32 = res           # the rows' count: nothing of it read
    return _sum_rows(g, token, sched, x32.shape[0], interpret), None, None


_dispatch_window.defvjp(_dispatch_window_fwd, _dispatch_window_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _combine_window(ys, scale, token, sched, n, chunk, interpret):
    """A window's part of ``y``: each token's float32 sum of its pairs'
    rows in the window, each times its weight.  Backward is
    ``_combine_rows``'s, at the window's rows: one scaled ``_spread_rows``."""
    return _sum_rows(ys.astype(jnp.float32) * scale[:, None], token, sched, n,
                     interpret)


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
    """One window's part of ``y`` [N, h] float32: ``_experts_held_rows``'s
    code at ``W`` rows, a copy because it runs under ``jax.vjp`` inside a loop
    with float32 carries: folding the two changes the whole-array layers'
    programs (ROADMAP D10, fold 1: a ledger's to judge).  ``x32`` [N, h]
    float32 holds the rows' ``stacks``-dtype values; ``scale``, ``token`` [W]
    and ``sizes`` [G + 2] are the window's (``_window_rows_of``): the grouped
    matmuls see ``G + 2`` groups of which the stacks hold ``1 .. G``, as the
    whole layer's see ``E`` of which they hold ``first .. first + G - 1``.
    One outer scope: a transform (the backward loop's ``jax.vjp``) wraps the
    first scope entered after it, and the readers of the grouped matmuls' time
    look for ``bps.moe.experts/``, not ``jvp(bps.moe.experts)/``."""
    count, dt = stacks["up"].shape[0], stacks["up"].dtype
    chunk = _row_chunk(token.shape[0])
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


def _route(x, params, top_k, renormalize, routing, interpret):
    """The stage ``bps.moe.route`` -> ``(pair_expert [N k], weights
    [N, k], counts [E], aux, z)``: the scores (the layer's softmax router,
    or ``routing``), ONE selection pass over them (``_select_experts``), the
    weights' renormalisation and the router losses, all float32."""
    n = x.shape[0]
    e = (params["router"] if routing is None else routing[0]).shape[-1]
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
    return pair_expert, weights, counts, aux, z


def _experts_all(x, params, pair_expert, weights, counts, held, top_k, plan,
                 interpret):
    """Every expert local: all ``N k`` pair rows are live.  Pairs sorted by
    expert (stable: a token's order within its group is its arrival
    order), each token's row gathered k times — a permutation both ways
    (``_permute_rows``); XLA's activation; a float32 weighted sum."""
    (n, h), dt = x.shape, x.dtype
    with jax.named_scope("bps.moe.dispatch"):
        order = jnp.argsort(pair_expert, stable=True)           # [N k]
        inverse = jnp.argsort(order)
        xs = _permute_rows(jnp.repeat(x, top_k, axis=0), order, inverse)
    with jax.named_scope("bps.moe.experts"):
        if "gate" in params:
            gate = _grouped_matmul(xs, params["gate"].astype(dt), counts,
                                   interpret)
        up = _grouped_matmul(xs, params["up"].astype(dt), counts, interpret)
        act = (jax.nn.silu(gate) * up if "gate" in params
               else jnp.square(jax.nn.relu(up)))
        ys = _grouped_matmul(act, params["down"].astype(dt), counts,
                             interpret)
    with jax.named_scope("bps.moe.combine"):
        pairs = _permute_rows(ys, inverse, order).reshape(n, top_k, h)
        y = jnp.sum(pairs.astype(jnp.float32) * weights[..., None], axis=1)
    return y.astype(dt)


def _experts_held_rows(x, params, pair_expert, weights, counts, held, top_k,
                       plan, interpret):
    """A held share on whole pair-row arrays.  No pair routed to a held
    expert is ever dropped: the arrays are the worst case, all ``N k`` rows
    (every token could choose held experts only), and the rows of pairs
    routed elsewhere ride along dead.  The shape is the contract; the work
    follows the live rows (``row_schedule``): the grouped matmuls' grids
    and the row kernels (``_spread_rows``, the activation) cover them alone.
    The token-order half (``_gather_sum_rows``) fetches every row, and
    every array is written whole, zeros and all.  What ``layer_plan`` gives
    the shares past an eighth: ``mellum2_12b`` (16 of 64 held), ``zaya1_8b``
    (8 of 16) and ``glm47_flash`` (8 of 64 at top-4)."""
    dt, chunk = x.dtype, plan.chunk
    first = jnp.asarray(held[0], jnp.int32)
    sched = row_schedule(counts, held, chunk)
    with jax.named_scope("bps.moe.dispatch"):
        order, scale = _sorted_pairs(pair_expert, weights)
        inverse = jnp.argsort(order)
        token = order // top_k
        xs = _dispatch_rows(x, token, inverse, sched, top_k, chunk, interpret)
    with jax.named_scope("bps.moe.experts"):
        if "gate" in params:
            gate = _grouped_matmul(xs, params["gate"].astype(dt), counts,
                                   interpret, first)
        up = _grouped_matmul(xs, params["up"].astype(dt), counts, interpret,
                             first)
    # a kernel of its own scope: the readers of the grouped matmuls' time
    # take every ``pallas_call`` under ``bps.moe.experts``
    if "gate" in params:
        with jax.named_scope("bps.moe.gate"):
            act = _silu_gate_rows(gate, up, sched, chunk, interpret)
    else:
        with jax.named_scope("bps.moe.act"):
            act = _relu2_rows(up, sched, chunk, interpret)
    with jax.named_scope("bps.moe.experts"):
        act, down = _tie_gradients(act, params["down"].astype(dt))
        ys = _grouped_matmul(act, down, counts, interpret, first)
    with jax.named_scope("bps.moe.combine"):
        y = _combine_rows(ys, weights, scale, token, inverse, sched, top_k,
                          chunk, interpret)
    return y.astype(dt)


def _experts_held_windows(x, params, pair_expert, weights, counts, held,
                          top_k, plan, interpret):
    """A thin held share: no array of ``N k`` rows but the sort's three
    columns; ``_windowed_experts`` runs the pieces above on ``plan.window``
    rows at a time (nothing is dropped: a heavier batch runs more windows).
    Same result, same precision (rows in ``x.dtype``, sums in float32).
    ``nemotron3_super`` (8 of 512 at top-22: windows of 6 144 of 180 224
    rows), ``ling3_flash`` (8 of 512 at top-8: 4 096 of 131 072) and, since
    PR 49, ``qwen3_next_80b`` (32 of 512 at top-10: 40 960 of 327 680, where
    the whole arrays cost 311 ms a step of glue around 28 ms of matmuls and
    the windows 76)."""
    with jax.named_scope("bps.moe.dispatch"):
        order, scale = _sorted_pairs(pair_expert, weights)
    stacks = {k: v for k, v in params.items() if k != "router"}
    return _windowed_experts(x, weights, stacks, scale, order, counts, held,
                             top_k, plan.window, interpret)


_EXPERTS = {"all": _experts_all, "held_rows": _experts_held_rows,
            "held_windows": _experts_held_windows}


def dropless_moe_mlp(
        x, params, top_k: int, interpret: Optional[bool] = None, *,
        held: Optional[Tuple[int, int]] = None, renormalize: bool = False,
        routing: Optional[Tuple] = None):
    """Dropless top-k MoE MLP over a token shard ``x`` [N, h].

    params: ``{"router": [h, E] float32, "gate": [G, h, f], "up":
    [G, h, f], "down": [G, f, h]}``.  No biases.  ``held=None``: the
    stacks are all E experts (``G = E``).  ``held=(first, G)``: they are
    experts ``first .. first + G - 1`` of the E the router knows.

        p      = softmax(x_f32 @ router)            over all E
        w, idx = the k largest of p, and where      renormalize: w /= sum_j w
        y      = sum_j w[:, j] * down_idx_j(silu(gate_idx_j x) * up_idx_j x)
                 over the j whose expert idx_j is held

    The pairs are sorted by expert and the expert matmuls run as grouped
    matmuls over the ragged groups (k experts a token; no tensor grows with
    ``E x C``), the rows moved by the implementation ``layer_plan`` names.
    The weights are the model's: renormalised over the k chosen BEFORE the
    held experts are selected, so the shares of a layer add up to the whole
    layer; a token none of whose k experts is held gets exactly zero.

    ``routing=(p, beta)``: the probabilities come from OUTSIDE — a router
    of the model's own (an MLP, a state carried from layer to layer) — as
    ``p`` [N, E] float32, with a selection bias ``beta`` [E] or ``None``:
    the k largest of ``p + beta`` are taken and the weights read from ``p``
    at them (the bias chooses and is not weighed: no gradient reaches it;
    ``p``'s reaches the caller's router through the weights).  ``p`` need
    not sum to one (sigmoid scores: ``models/glm_lite.py``), and
    ``renormalize`` then divides by the sum + 1e-20 (as that family's code
    has it; a softmax's own weights keep the bare sum).  ``params`` needs
    no ``router``.  Experts WITHOUT a gate: where ``params`` holds no
    ``gate``, an expert is two matrices, ``down_e(relu(up_e x)^2)``
    (``models/nemotron_h.py``); the squared ReLU is the one ungated
    activation computed here, so no argument names it.

    Returns ``(y [N, h] in x.dtype, aux, z, counts [E] int32)``:
    ``aux = E * sum_e f_e P_e`` with ``f_e`` = pairs routed to e / N and
    ``P_e`` = mean router probability (the Switch load-balance loss
    summed over the k choices), ``z = mean(logsumexp(logits)^2)``
    (ST-MoE router z-loss; exactly 0 with ``routing``, whose logits stay
    with the caller), ``counts`` the pairs each expert received — all
    three over all E experts, whatever is held.  Router arithmetic is
    float32; the experts compute in ``x.dtype``.  Shapes are static:
    exactly ``N * k`` pair rows, so dropless needs no padding and an expert
    may receive none.  ``interpret=None``: the kernels on a TPU backend,
    the Pallas interpreter elsewhere (CPU tests)."""
    if interpret is None:
        from ..ops.pallas_kernels import on_tpu
        interpret = not on_tpu()
    e = (params["router"] if routing is None else routing[0]).shape[-1]
    if held is not None:
        held = held_range(held, e)
        if params["up"].shape[0] != held[1]:
            raise ValueError(
                f"held={held}: the stacks carry {params['up'].shape[0]} "
                f"experts and the router knows {e}")
    pair_expert, weights, counts, aux, z = _route(
        x, params, top_k, renormalize, routing, interpret)
    plan = layer_plan(x.shape[0] * top_k, held and held[1], e)
    y = _EXPERTS[plan.kind](x, params, pair_expert, weights, counts, held,
                            top_k, plan, interpret)
    return y, aux, z, counts


def publish_moe_stats(counts, held: Optional[Tuple[int, int]] = None):
    """Set the load gauges ``bps.metrics_snapshot()`` reads from the
    per-expert pair counts of one batch: ``counts`` [E] or [layers, E]
    (``dropless_moe_mlp``'s fourth result; the models sow it into
    ``moe_stats``).  With ``held=(first, count)`` also the share's own:
    ``moe.held_pair_share`` (pairs routed to held experts over all pairs
    = the live share of the layer's ``N * k`` pair rows),
    ``moe.held_load_max_over_mean`` (the fullest held expert over the held
    experts' mean, worst layer) and ``moe.visited_row_share`` (pair rows
    the row passes visit over all of them under the layer's own
    ``layer_plan``: ``row_schedule``'s live chunks, or the windows run
    times their rows and ``moe.window_trips`` = those of the worst layer).
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
            # the plan reads ``N k`` alone: the layer's pair rows
            plan = layer_plan(int(layer.sum()), held[1], len(layer))
            if plan.kind == "held_windows":
                trips.append(int(window_trips(layer, held, plan.window)))
                visited += trips[-1] * plan.window
            else:
                sched = row_schedule(layer, held, plan.chunk)
                visited += int(sched["end"] - sched["first"]) * plan.chunk
        gauges.set("moe.visited_row_share", visited / float(c.sum()))
        if trips:
            gauges.set("moe.window_trips", float(max(trips)))
