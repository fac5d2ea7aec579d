"""The Mamba-2 state-space recurrence as a chunked scan: Pallas TPU kernels
(forward + backward) and the same algebra in plain ``jax.numpy``.

Per sequence and head h (its B/C group g = h // (H / G)), a state ``S``
[N, P] float32 that starts at zero::

    S_t = exp(dt_t A_h) S_(t-1) + dt_t B_t^T xs_t        y_t = C_t S_t + D_h xs_t

``xs`` [B, T, H, P], ``dt`` [B, T, H] float32 (already positive: the
caller's softplus), ``A`` [H] float32 (negative), ``B`` / ``C``
[B, T, G, N], ``D`` [H] -> ``y`` [B, T, H, P] in ``xs.dtype``.

In chunks of ``chunk`` positions (arXiv:2405.21060, section 6) the
recurrence is matrix work.  With ``a = dt A``, ``cum`` its inclusive sum
inside a chunk of Q positions, ``L_ij = exp(cum_i - cum_j)`` for ``j <= i``
(0 above the diagonal) and ``S`` the state the chunk starts from::

    Y  = ((C B^T) o L) (dt o xs)  +  exp(cum) o (C S)
    S' = exp(cum_Q) S + sum_j exp(cum_Q - cum_j) B_j^T (dt_j xs_j)

:func:`ssd_scan` runs that as two Mosaic kernels under one
``jax.custom_vjp``.  A grid step is one (sequence, group, chunk); the
chunk axis is the sequential one and the group's heads' states
[H / G, N, P] float32 live in VMEM scratch across it.  Decays, ``cum``,
``L`` and the state are float32; the matmul operands (``C``, ``B``, the
masked scores, ``dt o xs``, the state as an operand) are ``xs.dtype``
(bfloat16 on the chip) with float32 accumulation.  ``C B^T`` is computed
once a chunk for the whole group.  The backward walks the chunks in
reverse with the state's cotangent in the same scratch, and READS the
chunk-start states the differentiated forward STORED ([B, H, T / Q, N, P]
float32: gauge ``ssm.saved_state_bytes``, 64 MiB a call at 2 x 8192
positions, 16 heads of 128 x 64; written once and read once, ~0.16 ms of
HBM time beside a recomputation that would be a second forward sweep).
The forward that is not differentiated (the first pass under ``remat``)
stores none.  The cumulative sum, the head-major layouts the kernels want
and ``D xs`` are plain XLA around the kernels (``dt`` and ``cum`` are
1/64 of ``xs``).

:func:`ssd_scan_chunked` is the einsum form of the same algebra,
differentiable by ``jax.grad``: what the kernels are tested against (no
model runs it).  XLA materialises its ``L`` and masked scores as
[B, T / Q, H, Q, Q] float32 arrays.

Sizes are arguments.  Checked and refused: T not a multiple of ``chunk``
(pad the sequence with ``dt = 0`` positions, which carry the state through
unchanged and add nothing), H not a multiple of G.  On the chip ``chunk``
must be a multiple of 128 (one lane tile of the row-layout ``cum``) unless
it is the whole T.  ``interpret=None`` engages Mosaic on a real TPU and
the Pallas interpreter elsewhere (CPU tests), as ``ops.flash_attention``.
Kernel names: ``bps_ssd_fwd``, ``bps_ssd_bwd``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan", "ssd_scan_chunked"]

_VMEM_LIMIT = 48 << 20


def _check(xs, dt, A, B, C, D, chunk):
    b, t, h, p = xs.shape
    g, n = B.shape[2], B.shape[3]
    if t % chunk:
        raise ValueError(
            f"ssd_scan: T={t} is not a multiple of chunk={chunk}; pad the "
            f"sequence with dt = 0 positions (they leave the state as it is)")
    if h % g:
        raise ValueError(f"ssd_scan: {h} heads do not divide into {g} "
                         f"groups")
    want = {"dt": (b, t, h), "A": (h,), "B": (b, t, g, n), "C": (b, t, g, n),
            "D": (h,)}
    got = {"dt": dt.shape, "A": A.shape, "B": B.shape, "C": C.shape,
           "D": D.shape}
    if want != got:
        raise ValueError(f"ssd_scan: xs {xs.shape} wants {want}, got {got}")
    return b, t, h, p, g, n


def _chunk_cumsum(a, chunk):
    """Inclusive sum of ``a`` [B, T, H] inside each chunk of positions."""
    b, t, h = a.shape
    return jnp.cumsum(a.reshape(b, t // chunk, chunk, h), axis=2
                      ).reshape(b, t, h)


def _lower_mask(q):
    return (lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= lax.broadcasted_iota(jnp.int32, (q, q), 1))


# ------------------------------------------------------------ einsum form

def _chunk_starts(keep, added):
    """The state each chunk STARTS from, [nc, b, g, hg, n, p] float32:
    ``keep`` [nc, b, g, hg] is a chunk's whole decay, ``added``
    [nc, b, g, hg, n, p] what it adds to the state it hands on (zero
    before the first)."""
    def carry(state, chunk_in):
        keep_c, added_c = chunk_in
        return keep_c[..., None, None] * state + added_c, state

    return lax.scan(carry, jnp.zeros(added.shape[1:], jnp.float32),
                    (keep, added))[1]


def ssd_scan_chunked(xs, dt, A, B, C, D, *, chunk: int = 128):
    """The chunked scan in plain ``jax.numpy`` (module docstring):
    float32 decays and state, matmul operands in ``xs.dtype``."""
    b, t, h, p, g, n = _check(xs, dt, A, B, C, D, chunk)
    nc, hg, lp = t // chunk, h // g, xs.dtype
    dt = dt.astype(jnp.float32)
    cum = _chunk_cumsum(dt * A.astype(jnp.float32), chunk
                        ).reshape(b, nc, chunk, g, hg)
    xdt = (xs.astype(jnp.float32) * dt[..., None]).astype(lp)
    xdt = xdt.reshape(b, nc, chunk, g, hg, p)
    bc = B.reshape(b, nc, chunk, g, n)
    cc = C.reshape(b, nc, chunk, g, n)
    scores = jnp.einsum("bcign,bcjgn->bcgij", cc, bc,
                        preferred_element_type=jnp.float32)
    lower = _lower_mask(chunk)[:, :, None, None]
    # [b, nc, i, j, g, hg]: cum_i - cum_j, kept <= 0 before the exp
    gap = jnp.where(lower, cum[:, :, :, None] - cum[:, :, None, :], 0.0)
    decay = jnp.where(lower, jnp.exp(gap), 0.0)
    masked = (scores.transpose(0, 1, 3, 4, 2)[..., None] * decay).astype(lp)
    y = jnp.einsum("bcijgh,bcjghp->bcighp", masked, xdt,
                   preferred_element_type=jnp.float32)
    # what each chunk adds to the state it hands on
    to_end = jnp.exp(cum[:, :, -1:] - cum)              # [b, nc, q, g, hg]
    b_end = (bc.astype(jnp.float32)[..., None, :]
             * to_end[..., None]).astype(lp)            # [b, nc, q, g, hg, n]
    added = jnp.einsum("bcjghn,bcjghp->bcghnp", b_end, xdt,
                       preferred_element_type=jnp.float32)
    keep = jnp.exp(cum[:, :, -1])                       # [b, nc, g, hg]
    starts = _chunk_starts(
        keep.transpose(1, 0, 2, 3), added.transpose(1, 0, 2, 3, 4, 5)
    ).transpose(1, 0, 2, 3, 4, 5)                       # [b, nc, g, hg, n, p]
    read = jnp.einsum("bcign,bcghnp->bcighp", cc, starts.astype(lp),
                      preferred_element_type=jnp.float32)
    y = (y + jnp.exp(cum)[..., None] * read).reshape(b, t, h, p)
    return (y + D.astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
            ).astype(lp)


# ---------------------------------------------------------------- kernels

def _dot(x, y, dims):
    """Float32 accumulation; float32 OPERANDS (tests, the gradient check's
    scan alone) multiply at full precision, not in bfloat16 passes."""
    return lax.dot_general(
        x, y, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=(lax.Precision.HIGHEST if x.dtype == jnp.float32
                   else None))


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _head_terms(x_ref, dt_ref, cumc_ref, cumr_ref, h, lower):
    """What both kernels need of head ``h`` in the chunk: ``dt o xs`` as a
    matmul operand, the decay matrix ``L`` and the three decay columns."""
    q = lower.shape[0]
    x = x_ref[0, h]                                      # [Q, P]
    dt = dt_ref[0, 0, :, h:h + 1]                        # [Q, 1]
    col = cumc_ref[0, 0, :, h:h + 1]                     # [Q, 1]
    row = cumr_ref[0, 0, h:h + 1, :]                     # [1, Q]
    # the chunk's last ``cum`` as a SCALAR (a masked sum): Mosaic spreads a
    # [1, 1] vector over lanes or over sublanes, not over both at once
    last = jnp.sum(jnp.where(
        lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1, row, 0.0))
    xdt = (x.astype(jnp.float32) * dt).astype(x.dtype)
    decay = jnp.exp(jnp.where(lower, col - row, -jnp.inf))   # [Q, Q]
    keep = jnp.exp(last)
    return x, dt, xdt, decay, jnp.exp(col), jnp.exp(last - col), keep


def _fwd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref, y_ref,
                *refs, heads, save):
    if save:
        starts_ref, state = refs
    else:
        state, = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    bm, cm = b_ref[0, 0], c_ref[0, 0]                    # [Q, N]
    lp = bm.dtype
    lower = _lower_mask(bm.shape[0])
    scores = _dot(cm, bm, _NT)                           # [Q, Q], the group's
    for h in range(heads):
        _, _, xdt, decay, from_start, to_end, keep = _head_terms(
            x_ref, dt_ref, cumc_ref, cumr_ref, h, lower)
        s0 = state[h]                                    # [N, P] float32
        if save:
            starts_ref[0, h, 0] = s0
        y = _dot((scores * decay).astype(lp), xdt, _NN)
        y = y + from_start * _dot(cm, s0.astype(lp), _NN)
        y_ref[0, h] = y.astype(y_ref.dtype)
        b_end = (bm.astype(jnp.float32) * to_end).astype(lp)
        state[h] = keep * s0 + _dot(b_end, xdt, _TN)


def _bwd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref, starts_ref,
                dy_ref, dx_ref, ddt_ref, dcumc_ref, dcumr_ref, db_ref, dc_ref,
                dstate, *, heads):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    bm, cm = b_ref[0, 0], c_ref[0, 0]                    # [Q, N]
    lp, q = bm.dtype, bm.shape[0]
    bf, cf = bm.astype(jnp.float32), cm.astype(jnp.float32)
    lower = _lower_mask(q)
    lane = lax.broadcasted_iota(jnp.int32, (q, heads), 1)
    sublane = lax.broadcasted_iota(jnp.int32, (heads, q), 0)
    is_last = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    scores = _dot(cm, bm, _NT)
    d_scores = jnp.zeros((q, q), jnp.float32)
    db = jnp.zeros(bf.shape, jnp.float32)
    dc = jnp.zeros(cf.shape, jnp.float32)
    ddt = jnp.zeros((q, heads), jnp.float32)
    dcumc = jnp.zeros((q, heads), jnp.float32)
    dcumr = jnp.zeros((heads, q), jnp.float32)
    for h in range(heads):
        x, dt, xdt, decay, from_start, to_end, keep = _head_terms(
            x_ref, dt_ref, cumc_ref, cumr_ref, h, lower)
        s0 = starts_ref[0, h, 0]                         # [N, P] float32
        ds1 = dstate[h]                                  # d(chunk-end state)
        dy = dy_ref[0, h]                                # [Q, P]
        masked = scores * decay
        # y = masked xdt + from_start o (C S0);  S1 = keep S0 + b_end^T xdt
        dxdt = (_dot(masked.astype(lp), dy, _TN)
                + to_end * _dot(bm, ds1.astype(lp), _NN))          # [Q, P]
        dmasked = _dot(dy, xdt, _NT)                               # [Q, Q]
        d_scores = d_scores + dmasked * decay
        through = dmasked * masked          # d(decay) o decay: cum_i - cum_j
        dy_start = (from_start * dy.astype(jnp.float32)).astype(lp)
        dc_read = _dot(dy_start, s0.astype(lp), _NT)               # [Q, N]
        db_end = to_end * _dot(xdt, ds1.astype(lp), _NT)           # [Q, N]
        dc, db = dc + dc_read, db + db_end
        dstate[h] = keep * ds1 + _dot(cm, dy_start, _TN)
        # cum: rows of ``through`` and the read-out gain it, columns and the
        # hand-on lose it; the last position gains what the hand-on lost
        # and the carried state's share
        lost = jnp.sum(db_end * bf, axis=1, keepdims=True)         # [Q, 1]
        dlast = jnp.sum(lost) + keep * jnp.sum(ds1 * s0)
        dcol = (jnp.sum(through, axis=1, keepdims=True)
                + jnp.sum(dc_read * cf, axis=1, keepdims=True) - lost
                + jnp.where(is_last, dlast, 0.0))
        dcumc = dcumc + jnp.where(lane == h, dcol, 0.0)
        dcumr = dcumr - jnp.where(
            sublane == h, jnp.sum(through, axis=0, keepdims=True), 0.0)
        ddt = ddt + jnp.where(
            lane == h, jnp.sum(dxdt * x.astype(jnp.float32), axis=1,
                               keepdims=True), 0.0)
        dx_ref[0, h] = (dxdt * dt).astype(dx_ref.dtype)
    ddt_ref[0, 0] = ddt
    dcumc_ref[0, 0] = dcumc
    dcumr_ref[0, 0] = dcumr
    dc_ref[0, 0] = (dc + _dot(d_scores.astype(lp), bm, _NN)
                    ).astype(dc_ref.dtype)
    db_ref[0, 0] = (db + _dot(d_scores.astype(lp), cm, _TN)
                    ).astype(db_ref.dtype)


def _specs(g, hg, q, n, p, chunk_of):
    """Block specs of (xs, dt, cum as columns, cum as rows, B, C, the
    chunk-start states) for a grid (sequence, group, step); ``chunk_of``
    maps the step to the chunk it works on."""
    def at(*tail):
        return lambda b, j, c: (b, j, *[chunk_of(c) if x == "c" else 0
                                        for x in tail])
    heads = pl.BlockSpec((1, hg, q, p), at("c", 0))
    column = pl.BlockSpec((1, 1, q, hg), at("c", 0))
    row = pl.BlockSpec((1, 1, hg, q), at(0, "c"))
    group = pl.BlockSpec((1, 1, q, n), at("c", 0))
    starts = pl.BlockSpec((1, hg, 1, n, p), at("c", 0, 0))
    return heads, column, row, group, starts


def _shapes(xs, bm):
    b, h, t, p = xs.shape
    g, n = bm.shape[1], bm.shape[3]
    return b, h, t, p, g, n, h // g


# jitted: every block's call shares ONE traced and lowered copy of each
# kernel (a kernel's size is set-up time; XLA inlines the call)
@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _forward(xs, dt, cumc, cumr, bm, cm, chunk, interpret, save):
    b, h, t, p, g, n, hg = _shapes(xs, bm)
    nc = t // chunk
    heads, column, row, group, starts = _specs(g, hg, chunk, n, p,
                                               lambda c: c)
    out_specs, out_shape = [heads], [jax.ShapeDtypeStruct(xs.shape, xs.dtype)]
    if save:
        out_specs.append(starts)
        out_shape.append(jax.ShapeDtypeStruct((b, h, nc, n, p), jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=hg, save=save),
        grid=(b, g, nc),
        in_specs=[heads, column, column, row, group, group],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hg, n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="bps_ssd_fwd", interpret=interpret)(xs, dt, cumc, cumr, bm, cm)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _backward(xs, dt, cumc, cumr, bm, cm, starts, dy, chunk, interpret):
    b, h, t, p, g, n, hg = _shapes(xs, bm)
    nc = t // chunk
    heads, column, row, group, saved = _specs(g, hg, chunk, n, p,
                                              lambda c: nc - 1 - c)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=hg),
        grid=(b, g, nc),
        in_specs=[heads, column, column, row, group, group, saved, heads],
        out_specs=[heads, column, column, row, group, group],
        out_shape=[jax.ShapeDtypeStruct(xs.shape, xs.dtype),
                   jax.ShapeDtypeStruct(dt.shape, f32),
                   jax.ShapeDtypeStruct(cumc.shape, f32),
                   jax.ShapeDtypeStruct(cumr.shape, f32),
                   jax.ShapeDtypeStruct(bm.shape, bm.dtype),
                   jax.ShapeDtypeStruct(cm.shape, cm.dtype)],
        scratch_shapes=[pltpu.VMEM((hg, n, p), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="bps_ssd_bwd", interpret=interpret)(
            xs, dt, cumc, cumr, bm, cm, starts, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_core(xs, dt, cumc, cumr, bm, cm, chunk, interpret):
    """Head-major layouts in, ``y`` [B, H, T, P] out (no ``D xs``): xs
    [B, H, T, P]; dt, cum [B, G, T, H/G] (columns) and cum [B, G, H/G, T]
    (rows); B, C [B, G, T, N]."""
    return _forward(xs, dt, cumc, cumr, bm, cm, chunk, interpret, False)[0]


def _scan_core_fwd(xs, dt, cumc, cumr, bm, cm, chunk, interpret):
    y, starts = _forward(xs, dt, cumc, cumr, bm, cm, chunk, interpret, True)
    return y, (xs, dt, cumc, cumr, bm, cm, starts)


def _scan_core_bwd(chunk, interpret, res, dy):
    return tuple(_backward(*res, dy, chunk, interpret))


_scan_core.defvjp(_scan_core_fwd, _scan_core_bwd)


def ssd_scan(xs, dt, A, B, C, D, *, chunk: int = 128,
             interpret: Optional[bool] = None):
    """The chunked scan through the Mosaic kernels (module docstring).
    Tracing a call sets the gauges ``ssm.heads_held``, ``ssm.chunk``,
    ``ssm.chunks_per_seq``, ``ssm.state_bytes`` (the carried state of one
    sequence: H x N x P float32) and ``ssm.saved_state_bytes`` (the
    chunk-start states one differentiated call keeps for its backward:
    B x T / chunk of them)."""
    if interpret is None:
        from .pallas_kernels import on_tpu
        interpret = not on_tpu()
    b, t, h, p, g, n = _check(xs, dt, A, B, C, D, chunk)
    if not interpret and chunk % 128 and chunk != t:
        raise ValueError(f"ssd_scan: chunk={chunk} must be a multiple of 128 "
                         f"lanes on the chip (or the whole T={t})")
    from ..common.metrics import gauges
    state_bytes = 4 * h * n * p
    gauges.set("ssm.heads_held", float(h))
    gauges.set("ssm.chunk", float(chunk))
    gauges.set("ssm.chunks_per_seq", float(t // chunk))
    gauges.set("ssm.state_bytes", float(state_bytes))
    gauges.set("ssm.saved_state_bytes", float(b * (t // chunk) * state_bytes))
    hg = h // g
    dt = dt.astype(jnp.float32)
    cum = _chunk_cumsum(dt * A.astype(jnp.float32), chunk)

    def columns(v):                      # [B, T, H] -> [B, G, T, H/G]
        return v.reshape(b, t, g, hg).transpose(0, 2, 1, 3)

    y = _scan_core(xs.transpose(0, 2, 1, 3), columns(dt), columns(cum),
                   cum.reshape(b, t, g, hg).transpose(0, 2, 3, 1),
                   B.transpose(0, 2, 1, 3), C.transpose(0, 2, 1, 3),
                   chunk, bool(interpret))
    return (y.transpose(0, 2, 1, 3).astype(jnp.float32)
            + D.astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
            ).astype(xs.dtype)
