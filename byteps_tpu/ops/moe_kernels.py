"""Mosaic kernels of the dropless expert layer (``parallel/expert.py``).

What runs on the chip and knows nothing of routing: the grouped matmul
over ragged row groups (``_grouped_matmul``: JAX's Pallas megablox
kernels), the row kernels of a layer that holds a share of the experts —
the spread into sorted order (``_spread_rows``), its transpose over a
window's rows (``_sum_rows``) and the activations (``_gate_call``) — and
the route stage's selection of k of E in one pass (``_select_call``).  The
row kernels take the rows to visit as an argument (``sched``: ``{"lo",
"hi", "first", "end"}``, four int32 words); which rows are live is the
layer's knowledge, not this file's.  Gradients
(the ``custom_vjp``s that string these calls together) are the layer's
too.  Every kernel takes ``interpret=`` so CPU tests run the same code.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ------------------------------------------------------- the grouped matmul

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


# ------------------------------------ a held share's row kernels (live rows)

# Pair rows one grid step of a held layer's row kernels moves: twice the
# grouped matmul's row tile, clipped to a divisor of the rows — Mosaic lays
# a 1-D int32 SMEM block (the rows' tokens) out in 1 024s, and that many
# rows of 2304 columns, double-buffered in and out beside a 512-row
# float32 landing buffer, fit the VMEM asked for below (Mosaic's default
# 16 MiB does not hold them; PERF.md section 6, PR 30).
_ROW_CHUNK = 2 * _GMM_TILE[0]
_ROW_VMEM_BYTES = 40 * 2 ** 20
_DMA_GROUP = 4          # row DMAs started, and waited for, a loop trip


def _row_chunk(rows: int) -> int:
    """Rows a grid step of the row kernels moves, of ``rows`` in all."""
    return math.gcd(rows, _ROW_CHUNK)


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


def _loop(trips, body):
    """``body(i)`` for ``i`` in ``0 .. trips - 1`` as a loop, not unrolled."""
    def trip(i, carry):
        body(i)
        return carry
    lax.fori_loop(0, trips, trip, 0)


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

            _loop(groups, fetch)
            _loop(groups, land)

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

            _loop(part // sub, piece)

    _loop(chunk // part, one_part)


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


# Tokens a grid step of the token-order sum owns, and window rows it
# fetches and adds at a time (a landing buffer of that many float32 rows).
# Measured on a v5e at the three windows the models send (PERF.md section 6,
# PR 49; ms a call, XLA's float32 scatter-add in brackets): [40 960, 2048]
# -> [32 768, 2048] with 20 582 live rows 2.27 (4.50), [4096, 2560] ->
# [16 384, 2560] 0.41 (6.72), [6144, 1024] -> [8192, 1024] 0.21 (0.38);
# (128, 128) lies 4-6 % behind at the first and third, (512, 256) level at
# the first two and 20 % behind at the third.
_SUM_TOKENS = 256
_SUM_PART = 256


def _sum_kernel(starts, toks, rows, src, out, buf, sem, *, tile, part):
    """One tile of ``_sum_rows``: the tile's tokens own the rows ``starts[c]
    .. starts[c + 1] - 1`` of the window's rows SORTED BY TOKEN (``toks``
    their tokens, ``rows`` their places in the window).  Each comes by one
    DMA from ``src`` [W, 1, h] float32 in HBM, ``part`` of them at a time,
    and is added to its token's row of the tile in the sorted order.  Loops,
    as in ``_spread_kernel``."""
    c = pl.program_id(0)
    first, end = starts[c], starts[c + 1]
    base = c * tile

    out[...] = jnp.zeros(out.shape, out.dtype)

    def one_part(p):
        at = first + p * part
        count = lax.min(end - at, part)

        def fetch(i):
            pltpu.make_async_copy(src.at[rows[at + i]], buf.at[i],
                                  sem).start()

        def land(i):
            # a wait counts bytes: one row's worth a trip
            pltpu.make_async_copy(src.at[0], buf.at[0], sem).wait()

        def add(i):
            t = pl.ds(toks[at + i] - base, 1)
            out[t, :] = out[t, :] + buf[i]

        _loop(count, fetch)
        _loop(count, land)
        _loop(count, add)

    _loop(lax.div(end - first + part - 1, part), one_part)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _sum_rows(src, token, sched, n, interpret):
    """Token order from a window's rows, the transpose of ``_spread_rows``:
    ``out[t] = sum of src[r] over the rows lo <= r < hi with token[r] ==
    t``, float32 [n, h], a token's rows added in their order in the window;
    ``src`` [W, h] float32, ``token`` [W], ``sched`` the window's live
    range.  The rows are sorted by token (dead ones last: never fetched),
    so a tile of tokens owns ONE range of the sorted rows."""
    w, h = src.shape
    tile = math.gcd(n, _SUM_TOKENS)
    row = lax.iota(jnp.int32, w)
    live = (row >= sched["lo"]) & (row < sched["hi"])
    toks, rows = lax.sort((jnp.where(live, token, n).astype(jnp.int32), row),
                          num_keys=1, is_stable=True)
    edges = jnp.arange(0, n + 1, tile, dtype=jnp.int32)
    starts = jnp.sum(toks[None, :] < edges[:, None], axis=1, dtype=jnp.int32)
    return pl.pallas_call(
        functools.partial(_sum_kernel, tile=tile, part=_SUM_PART),
        out_shape=jax.ShapeDtypeStruct((n, h), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, h), lambda c, *_: (c, 0)),
            scratch_shapes=[pltpu.VMEM((_SUM_PART, 1, h), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_ROW_VMEM_BYTES),
        name="bps_moe_sum", interpret=interpret)(
            starts, toks, rows, src.astype(jnp.float32)[:, None, :])


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
