"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
ICLR 2023, as the EvaByte release specialises it): ONE softmax a query row
over two key sets, composed from the flash kernels.

T positions in block-aligned windows of ``window`` and chunks of ``chunk``;
per head two learned vectors ``mu``, ``phi``.  Inside chunk c a softmax of
``k_j . mu`` pools the keys into ``k~_c`` and a softmax of ``k_j . phi`` the
values into ``v~_c`` (float32).  Row i, in window ``w = i // window``,
normalises over

- (L) the keys j of its OWN window with ``j <= i``, and
- (R) the summaries c of every EARLIER window, ``c < (window / chunk) w``,

together: ``o_i = (sum_L e^(q.k_j) v_j + sum_R e^(q.k~_c) v~_c) / Z_i``.
Window 0 has no summaries; a window's own chunks are seen exactly, never as
summaries.

How it runs.  ``ops/flash_attention.py``'s forward returns ``(out, lse)`` a
key set and its backward takes a MERGED ``lse`` and the ``delta`` of the
merged output (the way ``parallel/ring_flash.py`` folds a ring's blocks):

- (L) is the windows folded into the batch axis — ``[B H, T, D]`` read as
  ``[B H T / window, window, D]``, no copy — under plain ``causal=True``
  (at ``window`` 2048 the resident one-kernel backward);
- (R) is one call of all T rows against the ``T / chunk`` summaries under
  the staircase mask ``stair=(window, window / chunk)``: sub-blocks past a
  row block's step are skipped by trip count, window 0 runs zero trips and
  comes back as (0, -1e30);
- ``_merge`` folds the two; the backward runs ``_bwd_impl`` once a key set
  under the merged ``lse``, then the pooling's transpose into dk, dv, dmu,
  dphi.

No ``[T, T / chunk]`` or ``[T, window]`` score array exists outside VMEM.
The pooling is plain XLA (one read of k and v) under its scope; scopes
``bps.eva.pool | local | summary | merge``.  Tracing a call sets the gauges
``eva.visited_block_share`` (``block_schedule``'s visited / total over BOTH
key sets), ``eva.summary_keys`` (T / chunk) and ``eva.saved_lse_bytes``
(the float32 row a head the backward keeps).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import (_LANES, _SUB, _blocks, _bwd_impl, _ceil_to,
                              _delta, _fwd, _merge, block_schedule)
from . import pallas_kernels

__all__ = ["eva_attention", "pool_chunks", "eva_schedule"]


def pool_chunks(k, v, mu, phi, chunk: int):
    """k, v [B H, T, D]; mu, phi [H, D] -> (k~, v~) [B H, T / chunk, D]
    float32: inside each chunk a softmax of ``k . mu`` weighs the keys, one
    of ``k . phi`` the values.  Multiply-and-reduce, no matmul: each is one
    pass over k (and v)."""
    bh, t, d = k.shape
    h = mu.shape[0]
    kc = k.astype(jnp.float32).reshape(bh // h, h, t // chunk, chunk, d)
    vc = v.astype(jnp.float32).reshape(kc.shape[:-1] + (v.shape[-1],))

    def weights(w):                       # [H, D] -> [B, H, T / chunk, chunk]
        return jax.nn.softmax(
            jnp.sum(kc * w.astype(jnp.float32)[None, :, None, None, :], -1),
            -1)

    ks = jnp.sum(weights(mu)[..., None] * kc, -2)
    vs = jnp.sum(weights(phi)[..., None] * vc, -2)
    return (ks.reshape(bh, t // chunk, d),
            vs.reshape(bh, t // chunk, v.shape[-1]))


def eva_schedule(t: int, window: int, chunk: int, *, block_q: int = _SUB,
                 block_k: int = _SUB, summary_block_k: int = _SUB) -> dict:
    """``block_schedule`` of both key sets of one head at T positions:
    ``{"visited", "total", "needed"}`` summed over the ``T / window``
    causal window calls and the staircase call, and each set's own under
    ``"local"`` / ``"summary"``."""
    nw = t // window
    bq = _blocks(window, window, block_q, block_k)[0]
    one = block_schedule(window, window, True, block_q=block_q,
                         block_k=block_k)
    local = {k: nw * n for k, n in one.items()}
    summary = block_schedule(t, t // chunk, False, block_q=bq,
                             block_k=summary_block_k,
                             stair=(window, window // chunk))
    out = {k: local[k] + summary[k] for k in local}
    out.update(local=local, summary=summary)
    return out


def _summaries(k3, v3, mu, phi, chunk, rows, dtype):
    """The pooled keys and values as the staircase call reads them: in
    ``dtype``, padded to ``rows`` whole key sub-blocks."""
    ks, vs = pool_chunks(k3, v3, mu, phi, chunk)
    pad = ((0, 0), (0, rows - ks.shape[1]), (0, 0))
    return jnp.pad(ks.astype(dtype), pad), jnp.pad(vs.astype(dtype), pad)


def _folded(window):
    """[B H, T, w] -> [B H T / window, window, w]: the windows into the
    batch axis, no copy."""
    return lambda x: x.reshape(-1, window, x.shape[-1])


def _forward(q3, k3, v3, mu, phi, scale, window, chunk, blocks, interpret):
    bh, t, d = q3.shape
    bq, bk, bks, ns_p = blocks
    nw, ns = t // window, t // chunk
    fold = _folded(window)
    with jax.named_scope("bps.eva.local"):
        o_l, lse_l = _fwd(fold(q3), fold(k3), fold(v3), scale, True, 0,
                          window, bq, bk, interpret)
    o_l, lse_l = o_l.reshape(bh, t, -1), lse_l.reshape(bh, t, _LANES)
    if nw == 1:                    # one window: no earlier summaries
        return o_l, lse_l[:, :, :1]
    with jax.named_scope("bps.eva.pool"):
        ks, vs = _summaries(k3, v3, mu, phi, chunk, ns_p, q3.dtype)
    with jax.named_scope("bps.eva.summary"):
        o_s, lse_s = _fwd(q3, ks, vs, scale, False, 0, ns, bq, bks,
                          interpret, stair=(window, window // chunk))
    with jax.named_scope("bps.eva.merge"):
        # on ONE lane of the kernels' lane-broadcast rows: the fold is a
        # row's two weights, not 128 copies of them
        o, lse = _merge(o_l, lse_l[:, :, :1], o_s, lse_s[:, :, :1])
        return o.astype(q3.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _eva(q3, k3, v3, mu, phi, scale, window, chunk, blocks, interpret):
    return _forward(q3, k3, v3, mu, phi, scale, window, chunk, blocks,
                    interpret)[0]


def _eva_fwd(q3, k3, v3, mu, phi, scale, window, chunk, blocks, interpret):
    out, lse = _forward(q3, k3, v3, mu, phi, scale, window, chunk, blocks,
                        interpret)
    # the merged lse as ONE float32 a row (the kernels read it
    # lane-broadcast: the backward spreads it again)
    return out, (q3, k3, v3, mu, phi, out, lse)


def _eva_bwd(scale, window, chunk, blocks, interpret, res, g):
    q3, k3, v3, mu, phi, out, lse = res
    bh, t, d = q3.shape
    bq, bk, bks, ns_p = blocks
    nw, ns = t // window, t // chunk
    fold = _folded(window)
    with jax.named_scope("bps.eva.merge"):
        # of the MERGED output, under the merged lse: each key set's
        # probabilities are then its share of the one softmax
        delta = _delta(g, out)
        lse = jnp.broadcast_to(lse, (bh, t, _LANES))
    with jax.named_scope("bps.eva.local"):
        dq, dk, dv = (x.reshape(bh, t, -1) for x in _bwd_impl(
            fold(q3), fold(k3), fold(v3), fold(g), fold(lse), fold(delta),
            scale, True, 0, window, bq, bk, interpret))
    if nw == 1:
        return dq, dk, dv, jnp.zeros_like(mu), jnp.zeros_like(phi)
    with jax.named_scope("bps.eva.pool"):
        (ks, vs), pool_vjp = jax.vjp(
            lambda *a: _summaries(*a, chunk, ns_p, q3.dtype),
            k3, v3, mu, phi)
    with jax.named_scope("bps.eva.summary"):
        dq_s, dks, dvs = _bwd_impl(
            q3, ks, vs, g, lse, delta, scale, False, 0, ns, bq, bks,
            interpret, stair=(window, window // chunk))
    with jax.named_scope("bps.eva.pool"):
        dk_p, dv_p, dmu, dphi = pool_vjp((dks, dvs))
    with jax.named_scope("bps.eva.merge"):
        def add(a, b):
            return (a.astype(jnp.float32) + b.astype(jnp.float32)
                    ).astype(a.dtype)
        return add(dq, dq_s), add(dk, dk_p), add(dv, dv_p), dmu, dphi


_eva.defvjp(_eva_fwd, _eva_bwd)


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array, mu: jax.Array,
                  phi: jax.Array, *, window: int, chunk: int,
                  interpret: Optional[bool] = None, block_q: int = _SUB,
                  block_k: int = _SUB, summary_block_k: int = _SUB
                  ) -> jax.Array:
    """q, k, v [B, T, H, D] (k rotated already), ``mu``, ``phi`` [H, D] ->
    o [B, T, H, D]: the module docstring's one softmax, at 1 / sqrt(D),
    over a row's own window and the earlier windows' chunk summaries.
    Differentiable in all five.  ``interpret=None``: Mosaic on a TPU, the
    Pallas interpreter elsewhere.  The three sub-block sizes are the
    tests' hook (small windows, a key tail, several steps a sub-block):
    no model passes them — 512 is the flash kernels' own, and the best of
    128 / 256 / 512 for the summary set on a v5e (``PERF.md`` section 6,
    PR 50)."""
    if interpret is None:
        interpret = not pallas_kernels.on_tpu()
    b, t, h, d = q.shape
    if k.shape != q.shape or v.shape[:3] != q.shape[:3]:
        raise ValueError(
            f"eva_attention: q {q.shape}, k {k.shape}, v {v.shape}: one "
            f"length and one head count for all three (no grouped heads)")
    if mu.shape != (h, d) or phi.shape != (h, d):
        raise ValueError(f"eva_attention: mu {mu.shape} / phi {phi.shape} "
                         f"are a vector a head, [{h}, {d}]")
    if window < 1 or chunk < 1 or t % window or window % chunk:
        raise ValueError(
            f"eva_attention: T={t} must be whole windows of {window}, a "
            f"window whole chunks of {chunk}")
    bq, bk, wq, wk = _blocks(window, window, block_q, block_k)
    if (wq, wk) != (window, window):
        raise ValueError(
            f"eva_attention: a window of {window} is not whole sub-blocks "
            f"of {bq} x {bk}")
    ns = t // chunk
    bks = min(summary_block_k, _ceil_to(ns, 8))
    scale = 1.0 / math.sqrt(d)

    sched = eva_schedule(t, window, chunk, block_q=block_q, block_k=block_k,
                         summary_block_k=summary_block_k)
    from ..common.metrics import gauges
    gauges.set("eva.visited_block_share", sched["visited"] / sched["total"])
    gauges.set("eva.summary_keys", float(ns))
    gauges.set("eva.saved_lse_bytes", float(b * h * t * 4))

    def to3(x):
        w = x.shape[-1]
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, w)
        return jnp.pad(x, ((0, 0), (0, 0), (0, _ceil_to(w, _LANES) - w)))

    def lanes(w):
        return jnp.pad(w.astype(jnp.float32),
                       ((0, 0), (0, _ceil_to(d, _LANES) - d)))

    out = _eva(to3(q), to3(k), to3(v), lanes(mu), lanes(phi), scale, window,
               chunk, (bq, bk, bks, _ceil_to(ns, bks)), bool(interpret))
    d_v = v.shape[-1]
    out = out[:, :, :d_v].reshape(b, h, t, d_v)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
