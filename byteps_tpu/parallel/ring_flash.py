"""Ring-flash attention: the Pallas flash kernels inside ring sequence
parallelism.

`sequence.py ring_attention` folds each rotating K/V block with jnp
blockwise attention — correct, but at long context the per-block softmax
runs as XLA elementwise passes over [B, H, Tq/sp, Tk/sp] score tensors in
HBM.  This module keeps the ring's ppermute rotation and moves the
per-block math into the flash kernels (ops/flash_attention.py), so each
fold is one VMEM-resident Pallas program:

- forward: per ring step, run the flash forward on (local q, resident
  K/V block) with the causal offset ``(my - src) * t_local`` shipped to
  the kernel as a runtime SMEM scalar (it differs per device — a static
  offset cannot express a ring), then merge the returned normalized
  output into the running accumulator with the standard log-sum-exp
  combine.
- backward: re-rotate K/V, recompute each block's probabilities from the
  saved lse (the Dao backward), accumulate dQ locally while dK/dV ride
  the ring WITH their blocks — after the full n rotations every dK/dV
  shard arrives back at its owner.

Communication is identical to ring_attention (n-1 K/V hops forward, n
hops backward including the gradient return); only the per-block compute
changes.  Both custom_vjp passes are written out manually, so autodiff
never sees the ppermutes.

No reference analog (SURVEY.md §5: long-context absent in the
reference); pinned against ring_attention/full_attention in
tests/test_ring_flash.py.

Scoping: the striped token layout (sequence.striped_attention — balanced
causal rings) is implemented for the exact blockwise path only.  It
composes with this module conceptually (the kernel's causal offset would
become a per-(my, src) diagonal-ownership rule), but the flash kernels'
block masks are contiguous-layout today; use kind="striped" for balance
or kind="ring_flash" for VMEM-resident block math, not both.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.flash_attention import (_LANES, _NEG, _SUB, _bwd_impl, _ceil_to,
                                   _delta, _fwd, _merge)
from ..ops.pallas_kernels import on_tpu
from .sequence import SP_AXIS

__all__ = ["ring_flash_attention"]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(q3, k3, v3, axis_name, scale, causal, t_local, blocks,
                interpret):
    out, _ = _ring_fwd_loop(q3, k3, v3, axis_name, scale, causal,
                            t_local, blocks, interpret)
    return out.astype(q3.dtype)


def _ring_fwd_loop(q3, k3, v3, axis_name, scale, causal, t_local, blocks,
                   interpret):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    bq, bk = blocks
    bh, tq_p, d_p = q3.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    o_acc = jnp.zeros((bh, tq_p, d_p), jnp.float32)
    lse_acc = jnp.full((bh, tq_p, _LANES), 2 * _NEG, jnp.float32)

    def fold(carry, step):
        o_acc, lse_acc, k3, v3 = carry
        src = (my - step) % n
        # global causal offset of the local q rows against the resident
        # block's columns; runtime scalar (differs per device)
        q_off = (my - src) * t_local if causal else 0

        def attend(o_acc, lse_acc):
            o_b, lse_b = _fwd(q3, k3, v3, scale, causal, q_off, t_local,
                              bq, bk, interpret)
            return _merge(o_acc, lse_acc, o_b, lse_b)

        if causal:
            # Skip blocks entirely in the future: the kernel's in-kernel
            # loops run zero trips for one (its trip counts come from the
            # runtime q_off), but the block DMAs and the full-size merge
            # pass would still run.  Device-divergent
            # predicate is safe — attend() contains no collectives (same
            # pattern as sequence.py ring_attention).
            o_acc, lse_acc = lax.cond(
                src <= my, attend, lambda o, l: (o, l), o_acc, lse_acc)
        else:
            o_acc, lse_acc = attend(o_acc, lse_acc)
        return o_acc, lse_acc, k3, v3

    def body(step, carry):
        o_acc, lse_acc, k3, v3 = fold(carry, step)
        k3 = lax.ppermute(k3, axis_name, perm)
        v3 = lax.ppermute(v3, axis_name, perm)
        return o_acc, lse_acc, k3, v3

    # last fold outside the loop: its rotation result would be discarded
    carry = lax.fori_loop(0, n - 1, body, (o_acc, lse_acc, k3, v3))
    o_acc, lse_acc, _, _ = fold(carry, n - 1)
    return o_acc, lse_acc


def _ring_flash_fwd(q3, k3, v3, axis_name, scale, causal, t_local, blocks,
                    interpret):
    o_acc, lse_acc = _ring_fwd_loop(q3, k3, v3, axis_name, scale, causal,
                                    t_local, blocks, interpret)
    out = o_acc.astype(q3.dtype)
    return out, (q3, k3, v3, out, lse_acc)


def _ring_flash_bwd(axis_name, scale, causal, t_local, blocks, interpret,
                    res, g):
    q3, k3, v3, out, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    bq, bk = blocks
    perm = [(i, (i + 1) % n) for i in range(n)]
    delta = _delta(g, out)

    dq_acc = jnp.zeros(q3.shape, jnp.float32)
    dk_acc = jnp.zeros(k3.shape, jnp.float32)
    dv_acc = jnp.zeros(v3.shape, jnp.float32)

    def fold(carry, step):
        dq_acc, dk_acc, dv_acc, k3, v3 = carry
        src = (my - step) % n
        q_off = (my - src) * t_local if causal else 0

        def accum(dq_acc, dk_acc, dv_acc):
            dq_b, dk_b, dv_b = _bwd_impl(q3, k3, v3, g, lse, delta, scale,
                                         causal, q_off, t_local, bq, bk,
                                         interpret)
            return (dq_acc + dq_b.astype(jnp.float32),
                    dk_acc + dk_b.astype(jnp.float32),
                    dv_acc + dv_b.astype(jnp.float32))

        if causal:
            dq_acc, dk_acc, dv_acc = lax.cond(
                src <= my, accum, lambda a, b, c: (a, b, c),
                dq_acc, dk_acc, dv_acc)
        else:
            dq_acc, dk_acc, dv_acc = accum(dq_acc, dk_acc, dv_acc)
        return dq_acc, dk_acc, dv_acc, k3, v3

    def body(step, carry):
        dq_acc, dk_acc, dv_acc, k3, v3 = fold(carry, step)
        # dK/dV travel WITH their block: after the remaining rotations
        # they arrive back at the block's owner
        k3 = lax.ppermute(k3, axis_name, perm)
        v3 = lax.ppermute(v3, axis_name, perm)
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
        return dq_acc, dk_acc, dv_acc, k3, v3

    # last step outside the loop: its k3/v3 rotation would be discarded —
    # only the gradient accumulators need the final hop home
    carry = lax.fori_loop(0, n - 1, body, (dq_acc, dk_acc, dv_acc, k3, v3))
    dq_acc, dk_acc, dv_acc, _, _ = fold(carry, n - 1)
    dk_acc = lax.ppermute(dk_acc, axis_name, perm)
    dv_acc = lax.ppermute(dv_acc, axis_name, perm)
    return (dq_acc.astype(q3.dtype), dk_acc.astype(k3.dtype),
            dv_acc.astype(v3.dtype))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis_name: str = SP_AXIS, *,
                         causal: bool = False,
                         sm_scale: Optional[float] = None,
                         block_q: int = _SUB, block_k: int = _SUB,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Ring attention with flash-kernel block math.  Call inside
    shard_map; same contract as sequence.py ring_attention: q/k/v are the
    local [B, T/sp, H, D] shards (sequence axis in ring order), returns
    the local output shard.  ``block_q`` / ``block_k`` are the edges of
    one score sub-block, the unit the flash kernels' in-kernel loops
    visit, skip or mask (ops/flash_attention.py; until PR 28 they were
    the kernels' grid block, 512 x 1024): how many sub-blocks one grid
    step holds follows from the shape.
    """
    if interpret is None:
        interpret = not on_tpu()
    b, t_local, h, d = q.shape
    scale = (sm_scale if sm_scale is not None
             else 1.0 / math.sqrt(d))

    bq = min(block_q, _ceil_to(t_local, 8))
    bk = min(block_k, _ceil_to(t_local, 8))
    # one padded length serves both q and k/v (the ring rotates
    # same-shaped blocks), so snap the larger block to a multiple of the
    # smaller: then a multiple of the larger is a multiple of both
    if bk >= bq:
        bk = max((bk // bq) * bq, bq)
    else:
        bq = max((bq // bk) * bk, bk)
    t_p = _ceil_to(t_local, max(bq, bk))
    d_p = _ceil_to(d, _LANES)

    def to3(x):
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t_local, d)
        return jnp.pad(x, ((0, 0), (0, t_p - t_local), (0, d_p - d)))

    out = _ring_flash(to3(q), to3(k), to3(v), axis_name, scale, causal,
                      t_local, (bq, bk), bool(interpret))
    out = out[:, :t_local, :d].reshape(b, h, t_local, d)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
