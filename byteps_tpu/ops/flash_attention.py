"""Flash attention as Pallas TPU kernels (forward + backward).

The long-context path (parallel/sequence.py ring attention) and the model
families run attention through XLA's exact softmax — fine at BERT's seq
128, quadratic-memory-bound at long context.  This module implements the
standard flash decomposition (online softmax over key blocks, recompute
backward) as Pallas kernels so the hot op stays in VMEM:

- forward: per q sub-block, one pass over the key sub-blocks up to the
  causal diagonal; running (m, l, acc) in VMEM scratch (l spread over
  the 128 lanes and folded once at the end); emits the output and the
  log-sum-exp residual.
- backward: recompute P from (Q, K, lse) instead of storing the [T, T]
  probability matrix.  Where the whole q side of a head fits in VMEM
  (T <= 4096 at 128 bf16 lanes) ONE kernel does it in five matmuls a
  sub-block: per k/v sub-block, over the q sub-blocks from the diagonal
  on, dK/dV accumulate and dQ adds into a float32 VMEM scratch.  Beyond
  that the Dao (2022) two-kernel scheme stays (seven matmuls: dK/dV over
  q, dQ over k).

What is visited.  The unit is one ``block_q x block_k`` score sub-block
(512 x 512 by default).  A grid step holds a span of sub-blocks of one
side and the whole other side (or, for a context too long for VMEM, a
span of it: then the grid also walks those spans) and loops over
sub-blocks IN the kernel, with trip counts computed from the runtime
SMEM scalar ``q_off`` (parallel/ring_flash.py passes one per device):
sub-blocks wholly above the diagonal or in the padded key tail are never
computed; every visited one takes the mask (one compare and one select
a score: a second, unmasked body for interior sub-blocks measured no
faster and doubled what each kernel costs to lower).  ``block_schedule``
is the same rule on Python ints, and the gauge
``flash.visited_block_share`` its visited / total for the last traced
call.  Why 512: a sub-block's matmuls run near
the MXU's peak only when each is long enough to hide its fill and drain
(256 x 256 costs ~1.6x a score of 512 x 512), and the forward pays one
cross-lane max per row and sub-block — so at T = 1024 the schedule
visits 3 of 4 sub-blocks (the triangle is 0.50 + the diagonal blocks'
upper halves), at T = 4096 36 of 64.  Numbers: PERF.md section 6, PR 28.
A sliding ``window`` (row i sees keys ``i - window < j <= i``) bounds each
loop from the other side too: a q sub-block's key loop starts at the
sub-block that holds its first row's oldest key, a k/v sub-block's q loop
ends at the last sub-block whose first row still reaches its last column;
the mask takes one more compare.  At T = 8192 a causal call visits 136 of
256 sub-blocks, a window of 1024 visits 45 (and needs 45); where the grid
walks spans of the other side, a span wholly outside the band is a step
of zero trips (its DMA still runs).  ``window`` is static: the windowed
kernels are a second specialisation, and with ``window=None`` every
kernel's jaxpr is what it was before (tests/test_flash_attention.py
pins the equation counts: a kernel's size is set-up time).
A ``stair = (rows, cols)`` is the third static specialisation, for
``ops/eva_attention.py``'s set of chunk summaries: row i sees key columns
``c < cols * (i // rows)``, every column of every EARLIER step and none of
its own — no diagonal (``causal``, a ``window`` and a row offset are
refused beside it), steps of whole q sub-blocks, so a visited sub-block's
mask is the tail's one compare against the step's edge
and the key loops end there.  The kernels return ``(out, lse)`` and the
backward takes a merged ``lse`` and ``delta``: several key sets under ONE
softmax are several calls folded by ``_merge``.

Layout notes (Mosaic): all kernel operands are [BH, T, D] with D padded
to a lane multiple (128) and T padded to whole sub-blocks; the per-row
residuals (lse, delta) are carried as [BH, T, 128] lane-broadcast arrays
so every block spec keeps a full (8, 128)-or-larger tile — this image's
Mosaic rejects narrower output tiles (see ops/pallas_kernels.py).  The
``pallas_call``s carry no ``name=``: the benchmark finds them as the
``pallas_call``s under the models' ``attn`` scope.

The reference has no attention kernels at all (it is a gradient-
communication library, SURVEY.md §2); this is TPU-first capability the
rebuild adds, with `interpret=` giving the exact same code path on CPU
for tests (tests/test_flash_attention.py pins forward and gradients
against parallel/sequence.py full_attention).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_kernels import on_tpu

_NEG = -1e30
_LANES = 128
# Edge of one score sub-block: the unit the kernels' inner loops visit,
# skip or mask (``block_q`` / ``block_k`` of the public entry).
_SUB = 512
# Rows one grid step holds of the side its OUTER loop walks (q for the
# forward and dQ kernels, k/v for dK/dV): enough sub-blocks a step that
# the ~0.35 us a grid step costs stays small beside its matmuls.
_SPAN_ROWS = 2048
# Bytes of ONE operand of the other side (k or v; q or dO), which the
# INNER loop walks and which therefore stays resident in VMEM for the
# whole step: T = 4096 at 128 bf16 lanes.  A longer context falls back
# to a grid over spans of this size, each looped inside.
_RESIDENT_BYTES = 1 << 20
# Scoped VMEM a kernel may ask for (the default, 16 MiB, is under what the
# one-kernel backward holds at T = 4096: ~20 MiB, double-buffered).
_VMEM_LIMIT = 48 << 20


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _span(t: int, sub: int, cap_rows: int) -> int:
    """Rows of one grid step: the most whole sub-blocks that divide the
    padded length ``t`` and fit ``cap_rows`` (at least one)."""
    n = t // sub
    g = max(g for g in range(1, n + 1)
            if n % g == 0 and (g == 1 or g * sub <= cap_rows))
    return g * sub


def _spans(tq, tk, d, itemsize, bq, bk):
    """(outer span, resident span) of the q side and of the k side."""
    resident = max(_RESIDENT_BYTES // (d * itemsize), 1)
    return ((_span(tq, bq, _SPAN_ROWS), _span(tq, bq, resident)),
            (_span(tk, bk, _SPAN_ROWS), _span(tk, bk, resident)))


# --------------------------------------------------------------------------
# the schedule: which score sub-blocks are visited.  One rule, written
# over (max, min, floor division of non-negative ints): the kernels
# evaluate it on the runtime SMEM ``q_off``, ``block_schedule`` and the
# tests on Python ints.  On a traced scalar these are the bare lax
# primitives: every jnp wrapper (``//`` above all, a jitted
# sign-correcting floor_divide) is a nested function the Mosaic lowering
# re-emits in each of a step's 48 kernels, and that lowering is Python
# the job waits for before its first step (``setup_s``).
# --------------------------------------------------------------------------

def _ints(a, b):
    return isinstance(a, int) and isinstance(b, int)


def _max(a, b):
    return max(a, b) if _ints(a, b) else jax.lax.max(a, b)


def _min(a, b):
    return min(a, b) if _ints(a, b) else jax.lax.min(a, b)


def _div(a, b):
    return a // b if _ints(a, b) else jax.lax.div(a, b)


def _stair_limit(row0, stair):
    """Key columns the q sub-block that starts at ``row0`` sees under a
    staircase ``(rows, cols)``: row i sees columns ``c < cols * (i //
    rows)``.  A sub-block never straddles a step (``rows`` is a multiple
    of ``block_q``: ``_check_stair``), so one number serves all its rows."""
    rows, cols = stair
    return cols * _div(row0, rows)


def _check_stair(stair, bq, causal, window, q_off):
    """A staircase is its own mask: ``_live_queries`` and ``_stair_limit``
    count rows from 0 and know no diagonal, so what would make the
    schedule disagree with ``_mask`` is refused."""
    if stair is None:
        return
    if min(stair) < 1 or stair[0] % bq:
        raise ValueError(
            f"stair={stair}: a step of {stair[0]} rows must be whole q "
            f"sub-blocks of {bq}, and both edges at least 1")
    if causal or window is not None or not (
            isinstance(q_off, int) and q_off == 0):
        raise ValueError(
            f"stair={stair} with causal={causal}, window={window}, "
            f"q_off={q_off}: a staircase has no diagonal, no band and "
            f"counts its rows from the static 0")


def _live_keys(row0, bq, col0, n, bk, kv_len, causal, window=None,
               stair=None):
    """``(lo, hi)``: of the ``n`` key sub-blocks ``[col0 + j*bk, +bk)``
    those with ``lo <= j < hi`` hold a live score for q rows ``[row0,
    row0 + bq)``.  From ``hi`` on they lie wholly above the diagonal or in
    the padded key tail; under a ``window`` the first ``lo`` lie wholly
    before the first row's window (the sub-block that holds key ``row0 -
    window + 1`` is the first visited); under a ``stair`` those from
    ``hi`` on lie wholly past the rows' step.  ``lo`` may pass ``hi``:
    nothing is live."""
    hi = _min(n, _div(_max(kv_len - col0 + bk - 1, 0), bk))
    if causal:
        hi = _min(hi, _div(_max(row0 + bq - 1 - col0 + bk, 0), bk))
    if stair is not None:
        hi = _min(hi, _div(_max(_stair_limit(row0, stair) - col0 + bk - 1,
                                0), bk))
    if window is None:
        return 0, hi
    return _min(n, _div(_max(row0 - window + 1 - col0, 0), bk)), hi


def _live_queries(col0, bk, row0, n, bq, tail, causal, window=None,
                  stair=None):
    """The mirror, for dK/dV: ``(lo, hi)``, of the ``n`` q sub-blocks
    ``[row0 + i*bq, +bq)`` those with ``lo <= i < hi`` hold a live score
    against key columns ``[col0, col0 + bk)``.  The first ``lo`` lie
    above the diagonal (all ``n`` where the columns lie wholly in the
    padded tail); under a ``window`` those from ``hi`` on have left the
    columns behind (their first row's window starts past the last
    column); under a ``stair`` the first ``lo`` lie on steps that end
    before the first column.  The same set of sub-blocks as ``_live_keys``
    leaves, cut by columns: tests/test_flash_attention.py holds the two to
    each other."""
    lo = _min(n, _div(_max(col0 - row0, 0), bq)) if causal else 0
    if stair is not None:
        # the first row that sees column col0: rows * (col0 // cols + 1)
        rows, cols = stair
        lo = _min(n, _div(_max(rows * (_div(col0, cols) + 1) - row0, 0), bq))
    if tail is not None:
        # 1 where col0 >= tail, else 0
        lo = _max(lo, n * _min(_div(_max(col0 - tail + bk, 0), bk), 1))
    if window is None:
        return lo, n
    return lo, _min(n, _div(_max(col0 + bk + window - 2 - row0 + bq, 0), bq))


def block_schedule(tq: int, tk: int, causal: bool, q_off: int = 0, *,
                   block_q: int = _SUB, block_k: int = _SUB,
                   window: Optional[int] = None,
                   stair: Optional[tuple] = None) -> dict:
    """Score sub-blocks the kernels run at this shape: ``{"visited",
    "total", "needed"}``.

    ``total`` is the padded ``[Tq, Tk]`` square cut into ``block_q x
    block_k`` sub-blocks, ``needed`` those with at least one unmasked
    entry, ``visited`` those the forward and the backward kernels
    compute: counted here by q rows (``_live_keys``, the forward and dQ
    loops); dK/dV cuts the same set by key columns (``_live_queries``).  ``q_off`` is the global row of
    the first q row against key column 0 (``Tk - Tq`` for the public
    entry's decode alignment); ``window`` as ``flash_attention`` takes
    it; ``stair = (rows, cols)`` the staircase of ``ops/eva_attention.py``
    (row i sees columns ``c < cols * (i // rows)``; not causal, ``rows``
    whole q sub-blocks).  Pure arithmetic on the shapes — the
    manner of ``parallel.collective_schedule``: it reads, and changes no
    program."""
    bq, bk, tq_p, tk_p = _blocks(tq, tk, block_q, block_k)
    _check_stair(stair, bq, causal, window, q_off)
    nq, nk = tq_p // bq, tk_p // bk
    visited = needed = 0
    for i in range(nq):
        row0 = q_off + i * bq
        lo, hi = _live_keys(row0, bq, 0, nk, bk, tk, causal, window, stair)
        visited += max(hi - lo, 0)
        last_row = q_off + min((i + 1) * bq, tq) - 1
        for j in range(nk):
            first_col, last_col = j * bk, min((j + 1) * bk, tk) - 1
            # some row r of the sub-block sees some column c of it:
            # c <= r (causal) and r - window < c
            first_r = max(row0, first_col) if causal else row0
            last_r = last_row if window is None else min(
                last_row, last_col + window - 1)
            seen = first_col < tk and first_r <= last_r
            if stair is not None:
                seen = seen and first_col < _stair_limit(row0, stair)
            needed += seen
    return {"visited": visited, "total": nq * nk, "needed": needed}


def _tail(kv_len, tk):
    """``_mask``'s ``tail`` for ``kv_len`` real keys padded to ``tk``."""
    return kv_len if kv_len < tk else None


def _blocks(tq, tk, block_q, block_k):
    """(bq, bk, padded Tq, padded Tk) of a call."""
    bq = min(block_q, _ceil_to(tq, 8))
    bk = min(block_k, _ceil_to(tk, 8))
    return bq, bk, _ceil_to(tq, bq), _ceil_to(tk, bk)


def _mask(s, row0, col0, tail, causal, window=None, stair=None):
    """Causal, window, staircase and key-tail masks of one score sub-block
    whose corner is ``(row0, col0)``.  ``tail`` is the key length where the
    keys are padded beyond it, None where they are not.  A sub-block lies
    on ONE step of a ``stair``: its columns up to the step's edge are live
    for every row, one compare a score as the tail's."""
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = None
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        valid = (row - col) >= (col0 - row0)
        if window is not None:
            valid &= (row - col) < (col0 - row0 + window)
    if tail is not None:
        inside = col < (tail - col0)
        valid = inside if valid is None else valid & inside
    if stair is not None:
        under = col < (_stair_limit(row0, stair) - col0)
        valid = under if valid is None else valid & under
    return s if valid is None else jnp.where(valid, s, _NEG)


def _at(j, b, n):
    """Row offset of sub-block ``j`` of ``n`` (static when there is one:
    a lone sub-block may be any multiple of 8 rows)."""
    return 0 if n == 1 else pl.multiple_of(j * b, b)


def _loop(lo, hi, body):
    """``for j in range(lo, hi): body(j)`` with runtime bounds.  The
    bodies act on refs only: a lax.cond RETURNING values lowers to a
    select on this Mosaic (both sides run), so the schedule branches on
    effects — a loop's trip count, ``pl.when`` — never on values."""
    jax.lax.fori_loop(lo, hi, lambda j, c: (body(j), c)[1], 0)


def _for_live_keys(row0, bq, col_base, sk, bk, kv_len, causal, window,
                   body, stair=None):
    """``body(cols, col0)`` over the key sub-blocks of one resident span
    that hold a live score for q rows ``[row0, +bq)``."""
    n = sk // bk
    lo, hi = _live_keys(row0, bq, col_base, n, bk, kv_len, causal, window,
                        stair)

    def at(j):
        c = _at(j, bk, n)
        body(pl.ds(c, bk), col_base + c)

    _loop(lo, hi, at)


def _fold_lanes(x):
    """(rows, n * 128) -> (rows, 128), summing to the rows' sums: the sum
    of the lane tiles, or, where the width is no whole number of tiles (a
    lone sub-block shorter than 128), the row sum in lane 0."""
    rows, width = x.shape
    if width % _LANES:
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
        return jnp.where(lane == 0, x.sum(axis=1, keepdims=True), 0.0)
    return sum(x[:, t:t + _LANES] for t in range(0, width, _LANES))


def _scores(q, k, scale):
    # dots stay in the input dtype (bf16 rides the MXU's native path;
    # upcasting first would force slow f32 passes); accumulate f32.
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * scale


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(qoff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, kv_len, tail, bq, bk,
                window=None, stair=None):
    sq, sk = q_ref.shape[1], k_ref.shape[1]
    ik, nk = pl.program_id(2), pl.num_programs(2)
    row_base = qoff_ref[0] + pl.program_id(1) * sq
    col_base = ik * sk

    def q_block(i):
        r = _at(i, bq, sq // bq)
        rows = pl.ds(r, bq)
        row0 = row_base + r

        @pl.when(ik == 0)
        def _init():
            m_scr[rows, :] = jnp.full((bq, _LANES), _NEG, jnp.float32)
            l_scr[rows, :] = jnp.zeros((bq, _LANES), jnp.float32)
            acc_scr[rows, :] = jnp.zeros((bq, acc_scr.shape[1]),
                                         jnp.float32)

        def attend(cols, col0):
            v = v_ref[0, cols, :]
            s = _mask(_scores(q_ref[0, rows, :], k_ref[0, cols, :], scale),
                      row0, col0, tail, causal, window, stair)
            m_prev = m_scr[rows, :1]                       # (bq, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            if window is not None:
                # a visited sub-block may hold rows whose window has
                # already left it (the diagonal rule never does): their
                # masked scores must not become weights exp(0) = 1
                m_new = jnp.maximum(m_new, _NEG / 2)
            p = jnp.exp(s - m_new)                         # (bq, bk)
            alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
            # the running sum stays spread over the 128 lanes (VPU adds of
            # p's lane tiles) and is folded once, in _finish: a second
            # cross-lane reduction a sub-block was a third of the forward
            l_scr[rows, :] = l_scr[rows, :] * alpha + _fold_lanes(p)
            acc_scr[rows, :] = acc_scr[rows, :] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[rows, :] = jnp.broadcast_to(m_new, (bq, _LANES))

        _for_live_keys(row0, bq, col_base, sk, bk, kv_len, causal, window,
                       attend, stair)

        @pl.when(ik == nk - 1)
        def _finish():
            # rows no key reached (a ring block wholly in the future)
            # keep l = 0: out 0, lse ~ -1e30, weight 0 in the ring's merge
            l = jnp.maximum(l_scr[rows, :].sum(axis=1, keepdims=True), 1e-30)
            o_ref[0, rows, :] = (acc_scr[rows, :] / l).astype(o_ref.dtype)
            lse_ref[0, rows, :] = m_scr[rows, :] + jnp.log(
                jnp.broadcast_to(l, (bq, _LANES)))

    _loop(0, sq // bq, q_block)


def _call(kern, grid, in_specs, out_specs, out_shape, scratch, interpret):
    from jax.experimental.pallas import tpu as pltpu
    # no ``name=``: the benchmark's readers find these kernels as the
    # ``pallas_call``s under the models' ``attn`` scope (PERF.md section 7)
    return pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _fwd(q, k, v, scale, causal, q_off, kv_len, bq, bk, interpret,
         window=None, stair=None):
    """[BH, Tq, D] x [BH, Tk, D] (padded to whole ``bq`` / ``bk``
    sub-blocks) -> (out, lse[BH, Tq, 128]); ``v`` [BH, Tk, Dv] and the
    output keep v's width.  A row no key reaches (a ring block wholly in
    the future, a ``stair``'s first step) comes back as out 0, lse ~
    -1e30: weight 0 in ``_merge``."""
    _check_stair(stair, bq, causal, window, q_off)
    from jax.experimental.pallas import tpu as pltpu
    bh, tq, d = q.shape
    tk, dv = k.shape[1], v.shape[2]
    (sq, _), (_, sk) = _spans(tq, tk, d, q.dtype.itemsize, bq, bk)
    nq, nk = tq // sq, tk // sk
    qoff = jnp.asarray(q_off, jnp.int32).reshape(1)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             kv_len=kv_len, tail=_tail(kv_len, tk),
                             bq=bq, bk=bk, window=window, stair=stair)
    return _call(
        kern, (bh, nq, nk),
        [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, sq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, sk, dv), lambda b, i, j: (b, j, 0)),
        ],
        [
            pl.BlockSpec((1, sq, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, sq, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        [
            jax.ShapeDtypeStruct((bh, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, _LANES), jnp.float32),
        ],
        [
            pltpu.VMEM((sq, _LANES), jnp.float32),
            pltpu.VMEM((sq, _LANES), jnp.float32),
            pltpu.VMEM((sq, dv), jnp.float32),
        ],
        interpret)(qoff, q, k, v)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _p_ds(q, k, v, do, lse, delta, scale, row0, col0, tail, causal,
          window=None, stair=None):
    """Recompute the probabilities of the sub-block at (row0, col0) from
    (q, k, lse), and dS = P * (dO V^T - delta) * scale."""
    s = _mask(_scores(q, k, scale), row0, col0, tail, causal, window,
              stair)
    p = jnp.exp(s - lse)                                   # (bq, bk)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * scale


def _bwd_dkv_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, *refs, scale, causal, kv_len, tail, bq, bk,
                    fused, window=None, stair=None):
    """dK/dV of one k/v span against the resident q side, looped from the
    diagonal on.  ``fused`` (the whole q side is resident): dQ too, in a
    float32 scratch that lives across the head's k/v spans, so P and dS
    are recomputed once a sub-block and not once in each of two kernels."""
    if fused:
        dk_ref, dv_ref, dq_ref, dk_scr, dv_scr, dq_scr = refs
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = refs
    sq, sk = q_ref.shape[1], k_ref.shape[1]
    ik, iq, nq = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    row_base = qoff_ref[0] + iq * sq
    col_base = ik * sk
    n = sq // bq

    if fused:
        @pl.when(ik == 0)
        def _init_dq():
            dq_scr[...] = jnp.zeros_like(dq_scr)

    def k_block(j):
        c = _at(j, bk, sk // bk)
        cols = pl.ds(c, bk)
        col0 = col_base + c

        @pl.when(iq == 0)
        def _init():
            dk_scr[cols, :] = jnp.zeros((bk, dk_scr.shape[1]), jnp.float32)
            dv_scr[cols, :] = jnp.zeros((bk, dv_scr.shape[1]), jnp.float32)

        def accum(i):
            r = _at(i, bq, n)
            rows = pl.ds(r, bq)
            q, do, k = q_ref[0, rows, :], do_ref[0, rows, :], k_ref[0, cols, :]
            p, ds = _p_ds(
                q, k, v_ref[0, cols, :], do,
                lse_ref[0, rows, :1], delta_ref[0, rows, :1], scale,
                row_base + r, col0, tail, causal, window, stair)
            ds = ds.astype(q.dtype)
            # dV += P^T dO; dK += dS^T Q; dQ += dS K
            dv_scr[cols, :] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_scr[cols, :] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if fused:
                dq_scr[rows, :] += jax.lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        _loop(*_live_queries(col0, bk, row_base, n, bq, tail, causal,
                             window, stair), accum)

        @pl.when(iq == nq - 1)
        def _finish():
            dk_ref[0, cols, :] = dk_scr[cols, :].astype(dk_ref.dtype)
            dv_ref[0, cols, :] = dv_scr[cols, :].astype(dv_ref.dtype)

    _loop(0, sk // bk, k_block)

    if fused:
        @pl.when(ik == pl.num_programs(1) - 1)
        def _finish_dq():
            dq_ref[0, :, :] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dq_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr,
                   *, scale, causal, kv_len, tail, bq, bk, window=None,
                   stair=None):
    sq, sk = q_ref.shape[1], k_ref.shape[1]
    ik, nk = pl.program_id(2), pl.num_programs(2)
    row_base = qoff_ref[0] + pl.program_id(1) * sq
    col_base = ik * sk

    def q_block(i):
        r = _at(i, bq, sq // bq)
        rows = pl.ds(r, bq)
        row0 = row_base + r

        @pl.when(ik == 0)
        def _init():
            dq_scr[rows, :] = jnp.zeros((bq, dq_scr.shape[1]), jnp.float32)

        def accum(cols, col0):
            k = k_ref[0, cols, :]
            _, ds = _p_ds(
                q_ref[0, rows, :], k, v_ref[0, cols, :], do_ref[0, rows, :],
                lse_ref[0, rows, :1], delta_ref[0, rows, :1], scale,
                row0, col0, tail, causal, window, stair)
            dq_scr[rows, :] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _for_live_keys(row0, bq, col_base, sk, bk, kv_len, causal, window,
                       accum, stair)

        @pl.when(ik == nk - 1)
        def _finish():
            dq_ref[0, rows, :] = dq_scr[rows, :].astype(dq_ref.dtype)

    _loop(0, sq // bq, q_block)


def _bwd(res, g, scale, causal, q_off, kv_len, bq, bk, interpret,
         window=None):
    q, k, v, out, lse = res
    delta = _delta(g, out)
    return _bwd_impl(q, k, v, g, lse, delta, scale, causal, q_off,
                     kv_len, bq, bk, interpret, window)


def _delta(do, out):
    """rowsum(dO * O), lane-broadcast for tiling."""
    bh, tq, _ = do.shape
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    return jnp.broadcast_to(delta, (bh, tq, _LANES))


def _merge(o_acc, lse_acc, o_b, lse_b):
    """Fold one key set's normalized output into the running accumulator.

    Both inputs carry (normalized output, lse); the combine is the usual
    two-term log-sum-exp: weights exp(lse - m) renormalize each side.
    Fully-masked blocks come back with lse ~= -1e30 and weight exactly 0.
    (``parallel/ring_flash.py`` folds a ring's blocks with it,
    ``ops/eva_attention.py`` a window's keys and the summaries.)
    """
    m = jnp.maximum(lse_acc, lse_b)
    wa = jnp.exp(lse_acc - m)[:, :, :1]
    wb = jnp.exp(lse_b - m)[:, :, :1]
    denom = jnp.maximum(wa + wb, 1e-30)
    o_new = (o_acc * wa + o_b.astype(jnp.float32) * wb) / denom
    lse_new = m + jnp.log(denom)
    return o_new, lse_new


def _bwd_impl(q, k, v, do, lse, delta, scale, causal, q_off, kv_len,
              bq, bk, interpret, window=None, stair=None):
    from jax.experimental.pallas import tpu as pltpu
    _check_stair(stair, bq, causal, window, q_off)
    bh, tq, d = q.shape
    tk, d_v = k.shape[1], v.shape[2]    # v, dO and dV keep v's width
    (sq, rq), (sk, rk) = _spans(tq, tk, d, q.dtype.itemsize, bq, bk)
    qoff = jnp.asarray(q_off, jnp.int32).reshape(1)
    static = dict(scale=scale, causal=causal, kv_len=kv_len,
                  tail=_tail(kv_len, tk), bq=bq, bk=bk, window=window,
                  stair=stair)
    # one kernel, five matmuls a sub-block, where the whole q side (q, dO,
    # lse, delta and a float32 dQ) fits in VMEM beside a k/v span; two
    # kernels, seven, where the context is too long for that
    fused = rq == tq

    # dK/dV (and dQ): a k/v span a step, the q side resident
    def q_side(w):
        return pl.BlockSpec((1, rq, w), lambda b, j, i: (b, i, 0))

    def k_side(w):
        return pl.BlockSpec((1, sk, w), lambda b, j, i: (b, j, 0))

    def like(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    def acc(rows, w=d):
        return pltpu.VMEM((rows, w), jnp.float32)

    grads = _call(
        functools.partial(_bwd_dkv_kernel, fused=fused, **static),
        (bh, tk // sk, tq // rq),
        [pl.BlockSpec(memory_space=pltpu.SMEM), q_side(d), k_side(d),
         k_side(d_v), q_side(d_v), q_side(_LANES), q_side(_LANES)],
        [k_side(d), k_side(d_v)] + [q_side(d)] * fused,
        [like(k), like(v)] + [like(q)] * fused,
        [acc(sk), acc(sk, d_v)] + [acc(rq)] * fused,
        interpret)(qoff, q, k, v, do, lse, delta)
    if fused:
        dk, dv, dq = grads
        return dq, dk, dv
    dk, dv = grads

    # dQ: a q span a step, k/v resident and looped up to the diagonal
    def q_span(w):
        return pl.BlockSpec((1, sq, w), lambda b, i, j: (b, i, 0))

    def kv_resident(w):
        return pl.BlockSpec((1, rk, w), lambda b, i, j: (b, j, 0))

    dq = _call(
        functools.partial(_bwd_dq_kernel, **static),
        (bh, tq // sq, tk // rk),
        [pl.BlockSpec(memory_space=pltpu.SMEM), q_span(d), kv_resident(d),
         kv_resident(d_v), q_span(d_v), q_span(_LANES), q_span(_LANES)],
        q_span(d), like(q), [acc(sq)], interpret)(
            qoff, q, k, v, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom-vjp core on padded [BH, T, D] arrays
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, scale, causal, q_off, kv_len, blocks, interpret,
           window=None):
    out, _ = _fwd(q, k, v, scale, causal, q_off, kv_len,
                  blocks[0], blocks[1], interpret, window)
    return out


def _flash_fwd(q, k, v, scale, causal, q_off, kv_len, blocks, interpret,
               window):
    out, lse = _fwd(q, k, v, scale, causal, q_off, kv_len,
                    blocks[0], blocks[1], interpret, window)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, q_off, kv_len, blocks, interpret, window,
               res, g):
    return _bwd(res, g, scale, causal, q_off, kv_len,
                blocks[0], blocks[1], interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = _SUB, block_k: int = _SUB,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Flash attention.  [B, Tq, H, D] x [B, Tk, H, D] -> [B, Tq, H, D].
    ``v`` may have a head size of its own, [B, Tk, H, Dv] -> [B, Tq, H,
    Dv] (a latent whose q.k and v widths differ): v, the output, its
    accumulator, dO, dV and delta keep v's width padded to lanes, q, k, dQ
    and dK theirs; ``sm_scale`` defaults from q's.

    Same contract as parallel/sequence.py full_attention (including the
    decode-style alignment: with causal=True and Tq < Tk the q rows cover
    the LAST Tq key positions).  Differentiable via the flash backward
    kernels.  ``block_q`` / ``block_k`` are the edges of one score
    sub-block, the unit the kernels visit, skip or mask; how many of them
    one grid step holds follows from the shape (module docstring).
    ``window`` (a Python int, with ``causal=True`` only) is a sliding
    window in HF's convention: row i attends keys j with ``i - window <
    j <= i``, the row itself and the ``window - 1`` before it; sub-blocks
    wholly outside that band are skipped by trip count as those above
    the diagonal are.  ``window=None`` is the kernels as they were,
    equation for equation.
    ``interpret=None`` engages the Mosaic path on a real TPU backend and
    the interpreter elsewhere (CPU tests).  Tracing a call sets the gauge
    ``flash.visited_block_share`` (``block_schedule``'s visited / total;
    a windowed call also ``flash.visited_block_share.window``).
    """
    if interpret is None:
        interpret = not on_tpu()
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"flash_attention(window={window}) is a causal sliding window "
            f"of at least one key: it needs causal=True and window >= 1")
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if causal and tq > tk:
        # q_off would go negative: rows before the first key position are
        # fully masked, their lse underflows to ~-1e30 and the backward's
        # exp(s - lse) explodes.  No caller has this shape (decode-style
        # alignment always has Tq <= Tk); reject it rather than return
        # garbage. (round-2 advisor finding)
        raise ValueError(
            f"flash_attention(causal=True) requires Tq <= Tk, got "
            f"Tq={tq} > Tk={tk}")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    q_off = tk - tq  # decode alignment (0 when square)

    bq, bk, tq_p, tk_p = _blocks(tq, tk, block_q, block_k)
    d_v = v.shape[-1]
    sched = block_schedule(tq, tk, causal, q_off, block_q=block_q,
                           block_k=block_k, window=window)
    from ..common.metrics import gauges
    gauges.set("flash.visited_block_share.window" if window is not None
               else "flash.visited_block_share",
               sched["visited"] / sched["total"])

    def to3(x, t_p):
        w = x.shape[-1]
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, x.shape[1], w)
        return jnp.pad(x, ((0, 0), (0, t_p - x.shape[1]),
                           (0, _ceil_to(w, _LANES) - w)))

    q3, k3, v3 = to3(q, tq_p), to3(k, tk_p), to3(v, tk_p)
    out = _flash(q3, k3, v3, scale, causal, q_off, tk, (bq, bk),
                 bool(interpret), window)
    out = out[:, :tq, :d_v].reshape(b, h, tq, d_v)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
