"""Kimi Delta Attention's recurrence — a gated delta rule whose decay is a
CHANNEL's — as a chunked scan: Pallas TPU kernels (forward + backward) and
the same algebra in plain ``jax.numpy``.

Per sequence and head a state ``S`` [d_k, d_v] float32 that starts at
zero (arXiv:2510.26692 section 3)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

``q``, ``k`` [B, T, H, d_k] (the caller has normalised and scaled them),
``v`` [B, T, H, d_v], ``g`` [B, T, H, d_k] float32 (the log-decay, <= 0),
``beta`` [B, T, H] float32 -> ``o`` [B, T, H, d_v] in ``q.dtype``.

In chunks of C positions the products of the transitions are a triangular
solve (the WY / UT form, the paper's eq. 6-9).  With ``G`` the inclusive
sum of ``g`` inside the chunk (``Gamma = exp(G)``), ``S`` the state the
chunk starts from and ``e(i, j) = exp(G_i - G_j)`` a vector over channels::

    A_ij = beta_i sum_c k_ic k_jc e(i, j)_c   (j <  i)    [C, C]
    P_ij =        sum_c q_ic k_jc e(i, j)_c   (j <= i)    [C, C]
    R  = (I + A)^-1 Diag(beta) (V - (Gamma o K) S)        [C, d_v]
    O  = (Gamma o Q) S + P R
    S' = Diag(Gamma_C) S + (exp(G_C - G) o K)^T R

(``R`` = the issue's ``U - W S`` with ``M = (I + A)^-1 Diag(beta)``,
``W = M (Gamma o K)``, ``U = M V``.)  The decay is a channel's, so ``e(i,
j)`` factors into a matmul only against a reference position, and
``1 / Gamma`` leaves float32 after 88 / 5 = 17 positions of the bounded
gate's ``g >= -5``: the score matrices are computed one ROW SUB-BLOCK of
``_SUB`` = 16 positions at a time, against the sub-block's FIRST position
— the query side's factor ``exp(G_i - G_first)`` is <= 1, the key side's
``exp(G_first - G_j)`` is <= 1 for keys before the sub-block and <=
exp(75) inside it, keys after it are masked to an exact zero (no
``inf``).  A sub-block's rows of ``P`` and of ``A`` come out of ONE
product (:func:`_scores`): its own 16 rows of the query side and of the
``beta k`` side, stacked to [32, d_k], against those keys — the matrix
unit latches the keys once and is pushed 32 rows, not 2 x C.  ``(I +
A)^-1`` is formed in float32 without a row-by-row substitution: the 16 x
16 diagonal blocks' inverses by the nilpotent series ``(I + X)(I + X^2)(I
+ X^4)(I + X^8)``, ``X = -A_diag``, then the block-strictly-lower rest
``Y = A_diag^-1 A_rest`` (nilpotent in C / 16 steps) by the same series:
twelve [C, C] products at C = 128, each THREE bfloat16 passes on float32
operands cut in two (:func:`_dot3`: ~2^-16 relative, 2e-6 on the inverse
of unit keys in general position, where one pass reads 8e-4), and its
derivative is ``dA = -T^T dT T^T``.  ``G`` is a product with the
lower-triangular ones on ``g`` cut in three exact pieces.  A chunk of one
head at C = 128 is so 52 products — 36 passes of the inverse and 3 of
``G`` on float32 operands whose values are bfloat16's, 8 stacked score
products and the five the algorithm needs (``(Gamma o K) S``, ``T rhs``,
``(Gamma o Q) S``, ``P R``, the state's update) on ``q.dtype`` — whose
operands are 5 632 KiB (gauge ``kda.matmul_operand_bytes_per_chunk``; 60
products and 6 336 KiB before PR 45).  Handing the passes' pieces over AS
bfloat16 (3 136 KiB; 3 040 matrix-unit operations a grid step for 4 288)
gave ``T`` and the scan's results bit for bit on the chip and moved the
kernels' time by +0.4 %: the twelve three-pass products of the inverse
DEPEND on each other, and it is their latency a grid step waits for, not
the pushes (PERF.md section 6, PR 45) — so they stay float32.  The state
is kept TRANSPOSED, [d_v, d_k], so that the channel decays scale its
lanes.

:func:`kda_scan` runs that as two Mosaic kernels (``bps_kda_fwd``,
``bps_kda_bwd``) under one ``jax.custom_vjp``.  A grid step is one
(sequence, ``_HEADS`` heads, chunk); the chunk axis is the sequential one
and the heads' states live in VMEM scratch across it.  ``g``, ``G``, the
decays, the solve and the state are float32; the other matmul operands are
``q.dtype`` (bfloat16 on the chip) with float32 accumulation, float32
operands (tests, the gradient check's scan alone) at ``HIGHEST``.  Both kernels run ONE text, :func:`_chunk_forward`
(a chunk of one head, two-dimensional arrays): the backward kernel walks
the chunks in reverse with the state's cotangent in scratch, READS the
chunk-start states the differentiated forward STORED ([B, H, T / C, d_v,
d_k] float32: gauge ``kda.saved_state_bytes``, 128 MiB a call at 1 x 8192
positions, 32 heads and C = 128; written once and read once, ~0.3 ms of
HBM time beside a recomputation that would be one more forward sweep of
the sequential axis, 7 ms) and takes ``jax.vjp`` of the chunk's forward inside the
kernel.  The forward that is not differentiated (the first pass under
``remat``) stores none.  The kernels read ``q``, ``k``, ``v``, ``g`` and
write ``o`` as [B, T, H d] (a head is a run of lanes): no head-major copy.

:func:`kda_scan_chunked` is the same chunk text under ``jax.vmap`` over
(sequence, head) and a ``lax.scan`` over the chunks, differentiable by
``jax.grad``; it never holds a [C, C, d_k] array.

Sizes are arguments.  Refused: T not a multiple of ``chunk`` (pad the
sequence with ``g = 0, beta = 0`` positions: they leave the state as it is
and add nothing), a chunk that is not whole sub-blocks (an odd H goes one
head a grid step).  ``chunk`` is the implementation's — the
result does not depend on it; on a v5e at 32 heads of 128 x 128 and 8192
positions a call's forward / forward + backward read 11.4 / 29.1 ms at
C = 32, 8.5 / 20.2 at 64, 7.2 / 16.9 at 128 (two heads a grid step; one
8.9 / 21.4, four 8.1 / 19.7 at C = 64; ``HIGHEST`` in the solve's place
10.3 / 24.2: PR 43's text, a host clock around a jitted call: PERF.md
section 6, PR 43), so ``models/ling.py`` asks for 128.  By the device
trace, C = 128, a kernel alone: forward 5.58 ms, the forward that stores
the states 5.62, backward 8.81 for PR 43's text; 5.14 / 5.18 / 7.84 for
this one (PR 45; twice that a call in ``ling3_flash.fused_1c``'s step of
two sequences: 10.3 and 15.7).  ``interpret=None`` engages Mosaic on a real
TPU and the Pallas interpreter elsewhere, as ``ops.flash_attention``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_scan", "kda_scan_chunked"]

_VMEM_LIMIT = 64 << 20
# positions a sub-block: 15 steps of g >= -5 keep exp(75) inside float32
_SUB = 16
# heads a grid step takes (their chains are independent: the scheduler
# interleaves them)
_HEADS = 2

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(x, y, dims):
    """Float32 accumulation; float32 OPERANDS multiply at full precision,
    not in bfloat16 passes."""
    return lax.dot_general(
        x, y, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=(lax.Precision.HIGHEST if x.dtype == jnp.float32
                   else None))


def _pieces(x, n):
    """x (float32) as ``n`` addends, all but the last bfloat16 VALUES in
    float32 (the top 16 bits of what is left, by a mask: a pair of casts is
    what a compiler may fold away as excess precision): the matrix unit
    multiplies each exactly in one pass (the last it rounds to nearest, as
    a cast would: handed over AS bfloat16 the pieces gave ``T`` bit for
    bit, half the pushes and latches, and no time — module docstring).
    Only inside a ``custom_vjp``: the mask has no derivative."""
    out = []
    for _ in range(n - 1):
        top = lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536)
        out.append(lax.bitcast_convert_type(top, jnp.float32))
        x = x - out[-1]
    return out + [x]


def _pass(x, y, dims):
    return lax.dot_general(x, y, (dims, ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=lax.Precision.DEFAULT)


def _dot3(x, y, dims):
    """A float32 product in THREE bfloat16 passes (hi hi + hi lo + lo hi:
    ~2^-16 relative, "bf16_3x") where ``HIGHEST`` takes six — for the
    solve's [C, C] products, whose operands are float32 by right and not by
    the test's choice.  Off the TPU each pass is a float32 product."""
    (xh, xl), (yh, yl) = _pieces(x, 2), _pieces(y, 2)
    return _pass(xh, yh, dims) + (_pass(xh, yl, dims) + _pass(xl, yh, dims))


def _ones_times(x, dims):
    """The lower-triangular ones [C, C] times x [C, d] float32 (``_TN``:
    their transpose): ``x`` in three bfloat16-valued pieces (24 bits: all
    of it), the ones exact, so three passes give float32's sum."""
    c = x.shape[0]
    ones = (lax.broadcasted_iota(jnp.int32, (c, c), 0)
            >= lax.broadcasted_iota(jnp.int32, (c, c), 1)).astype(jnp.float32)
    return sum(_pass(ones, piece, dims) for piece in _pieces(x, 3))


@jax.custom_vjp
def _chunk_cumsum(g):
    """Inclusive sum of g [C, d] float32 down the chunk."""
    return _ones_times(g, _NN)


def _chunk_cumsum_fwd(g):
    return _chunk_cumsum(g), None


def _chunk_cumsum_bwd(_, d_cum):
    return (_ones_times(d_cum, _TN),)


_chunk_cumsum.defvjp(_chunk_cumsum_fwd, _chunk_cumsum_bwd)


def _check(q, k, v, g, beta, chunk):
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    if t % chunk:
        raise ValueError(
            f"kda_scan: T={t} is not a multiple of chunk={chunk}; pad the "
            f"sequence with g = 0, beta = 0 positions (they leave the state "
            f"as it is)")
    if chunk % min(chunk, _SUB):
        raise ValueError(f"kda_scan: chunk={chunk} is not whole sub-blocks "
                         f"of {_SUB} positions")
    want = {"k": (b, t, h, dk), "v": (b, t, h, dv), "g": (b, t, h, dk),
            "beta": (b, t, h)}
    got = {"k": k.shape, "v": v.shape, "g": g.shape, "beta": beta.shape}
    if want != got:
        raise ValueError(f"kda_scan: q {q.shape} wants {want}, got {got}")
    return b, t, h, dk, dv


# ---------------------------------------------------------- a chunk's text

def _steps(n: int) -> int:
    """Squarings after which ``(I + X)(I + X^2)...`` holds every power of a
    matrix that is nilpotent in ``n`` steps."""
    return max(0, (n - 1).bit_length() - 1)


def _series(x, eye, steps: int):
    """``(I + x)(I + x^2)(I + x^4)...``: ``(I - x)^-1`` of a nilpotent x."""
    inv = eye + x
    for _ in range(steps):
        x = _dot3(x, x, _NN)
        inv = inv + _dot3(inv, x, _NN)
    return inv


def _inverse(a):
    c = a.shape[0]
    sub = min(c, _SUB)
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = (row == col).astype(jnp.float32)
    diag = jnp.where(row // sub == col // sub, a, 0.0)
    t_diag = _series(-diag, eye, _steps(sub))
    rest = _dot3(t_diag, a - diag, _NN)
    return _dot3(_series(-rest, eye, _steps(c // sub)), t_diag, _NN)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular float32 [C, C]."""
    return _inverse(a)


def _unit_lower_inverse_fwd(a):
    t = _inverse(a)
    return t, t


def _unit_lower_inverse_bwd(t, dt):
    return (-_dot3(_dot3(t, dt, _TN), t, _NT),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _at(cum, i):
    """Row i of cum [C, d], [1, d], exact."""
    pos = lax.broadcasted_iota(jnp.int32, (cum.shape[0], 1), 0)
    return jnp.sum(jnp.where(pos == i, cum, 0.0), axis=0, keepdims=True)


def _scores(qf, kf, cum, beta, lp):
    """The chunk's score matrices (module docstring) ``P`` (j <= i) and
    ``A`` (j < i) [C, C] float32 from q, k, ``cum`` = G [C, d_k] float32
    and beta [C, 1]: for each row sub-block ONE product, on operands of
    type ``lp``, of its own rows of q and of beta k, stacked, against the
    keys up to its end."""
    c = qf.shape[0]
    sub = min(c, _SUB)
    pos = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    firsts = [_at(cum, a * sub) for a in range(c // sub)]
    first = firsts[0]
    for a in range(1, c // sub):         # each row's own sub-block's first
        first = jnp.where(pos >= a * sub, firsts[a], first)
    from_first = jnp.exp(cum - first)                    # <= 1
    q_rows = (qf * from_first).astype(lp)
    k_rows = (kf * from_first * beta).astype(lp)
    slabs = []
    for a in range(c // sub):
        # keys up to the end of sub-block a, against its first position;
        # later keys are an exact zero
        to_first = jnp.exp(jnp.where(pos < (a + 1) * sub, firsts[a] - cum,
                                     -jnp.inf))
        keys = (kf * to_first).astype(lp)
        mine = slice(a * sub, (a + 1) * sub)
        slabs.append(_dot(jnp.concatenate([q_rows[mine], k_rows[mine]]),
                          keys, _NT))                    # [2 sub, C]
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    p = jnp.concatenate([both[:sub] for both in slabs])
    a_mat = jnp.concatenate([both[sub:] for both in slabs])
    return jnp.where(row >= col, p, 0.0), jnp.where(row > col, a_mat, 0.0)


def _chunk_forward(q, k, v, g, beta, state):
    """One chunk of one head (module docstring): q, k [C, d_k]; v
    [C, d_v]; g [C, d_k] float32; beta [C, 1] float32; ``state`` the
    TRANSPOSED state [d_v, d_k] float32 the chunk starts from -> (o
    [C, d_v] float32, the transposed state it hands on)."""
    f32, lp = jnp.float32, q.dtype
    qf, kf = q.astype(f32), k.astype(f32)
    cum = _chunk_cumsum(g)                               # G, [C, d_k]
    p, a_mat = _scores(qf, kf, cum, beta, lp)
    t_mat = _unit_lower_inverse(a_mat)
    from_start = jnp.exp(cum)                            # Gamma
    s_lp = state.astype(lp)
    rhs = beta * (v.astype(f32)
                  - _dot((kf * from_start).astype(lp), s_lp, _NT))
    r = _dot(t_mat.astype(lp), rhs.astype(lp), _NN)      # [C, d_v]
    r_lp = r.astype(lp)
    o = (_dot((qf * from_start).astype(lp), s_lp, _NT)
         + _dot(p.astype(lp), r_lp, _NN))
    last = _at(cum, q.shape[0] - 1)
    to_end = (kf * jnp.exp(last - cum)).astype(lp)
    return o, state * jnp.exp(last) + _dot(r_lp, to_end, _TN)


def _dot_operand_bytes(jaxpr) -> int:
    """Bytes of both operands of every ``dot_general`` of a jaxpr and of
    the jaxprs inside its equations (``custom_vjp``, ``pjit``)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            total += sum(x.aval.size * x.aval.dtype.itemsize
                         for x in eqn.invars)
        total += sum(_dot_operand_bytes(inner) for inner in
                     jax.core.jaxprs_in_params(eqn.params))
    return total


@functools.lru_cache(maxsize=None)
def _matmul_operand_bytes(chunk, dk, dv, dtype) -> int:
    """What ONE head's :func:`_chunk_forward` hands to the matrix unit a
    chunk, read off its own jaxpr at these shapes and types."""
    f32 = jnp.float32
    avals = [jax.ShapeDtypeStruct(shape, kind) for shape, kind in (
        ((chunk, dk), dtype), ((chunk, dk), dtype), ((chunk, dv), dtype),
        ((chunk, dk), f32), ((chunk, 1), f32), ((dv, dk), f32))]
    return _dot_operand_bytes(jax.make_jaxpr(_chunk_forward)(*avals).jaxpr)


# ------------------------------------------------------------ chunked form

def kda_scan_chunked(q, k, v, g, beta, *, chunk: int):
    """The chunked scan in plain ``jax.numpy`` (module docstring): the
    chunk's text over (sequence, head), the chunks in a ``lax.scan``.  It
    runs the SAME ``_chunk_forward`` as the kernels: its agreement with
    them guards the ``pallas_call`` wrapping (grids, block specs, the
    stored states, the backward's order), not the chunk algebra — for that
    the witness is the recurrence position by position
    (``tests/test_kda_scan.py``, ``benchmarks/tests/gradcheck_ling.py``)."""
    b, t, h, dk, dv = _check(q, k, v, g, beta, chunk)
    nc = t // chunk

    def chunks(x):            # [B, T, H, d] -> [T / C, B, H, C, d]
        return x.reshape(b, nc, chunk, h, -1).transpose(1, 0, 3, 2, 4)

    over_heads = jax.vmap(jax.vmap(_chunk_forward))

    def one_chunk(state, inputs):
        o, state = over_heads(*inputs, state)
        return state, o

    _, o = lax.scan(
        one_chunk, jnp.zeros((b, h, dv, dk), jnp.float32),
        (chunks(q), chunks(k), chunks(v), chunks(g.astype(jnp.float32)),
         chunks(beta.astype(jnp.float32)[..., None])))
    return o.transpose(1, 0, 3, 2, 4).reshape(b, t, h, dv).astype(q.dtype)


# ---------------------------------------------------------------- kernels

def _head_inputs(q_ref, k_ref, v_ref, g_ref, beta_ref, h, dk, dv):
    return (q_ref[0, :, h * dk:(h + 1) * dk], k_ref[0, :, h * dk:(h + 1) * dk],
            v_ref[0, :, h * dv:(h + 1) * dv], g_ref[0, :, h * dk:(h + 1) * dk],
            beta_ref[0, 0, :, h:h + 1])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *refs, heads,
                dk, dv, save):
    if save:
        starts_ref, state = refs
    else:
        state, = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for h in range(heads):
        s0 = state[h]
        if save:
            starts_ref[0, h, 0] = s0
        o, state[h] = _chunk_forward(
            *_head_inputs(q_ref, k_ref, v_ref, g_ref, beta_ref, h, dk, dv),
            s0)
        o_ref[0, :, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *, heads,
                dk, dv):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    c = q_ref.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (c, heads), 1)
    dbeta = jnp.zeros((c, heads), jnp.float32)
    for h in range(heads):
        _, pull = jax.vjp(
            _chunk_forward,
            *_head_inputs(q_ref, k_ref, v_ref, g_ref, beta_ref, h, dk, dv),
            starts_ref[0, h, 0])
        d_q, d_k, d_v, d_g, d_beta, dstate[h] = pull(
            (do_ref[0, :, h * dv:(h + 1) * dv].astype(jnp.float32),
             dstate[h]))
        dq_ref[0, :, h * dk:(h + 1) * dk] = d_q.astype(dq_ref.dtype)
        dk_ref[0, :, h * dk:(h + 1) * dk] = d_k.astype(dk_ref.dtype)
        dv_ref[0, :, h * dv:(h + 1) * dv] = d_v.astype(dv_ref.dtype)
        dg_ref[0, :, h * dk:(h + 1) * dk] = d_g
        dbeta = dbeta + jnp.where(lane == h, d_beta, 0.0)
    dbeta_ref[0, 0] = dbeta


def _specs(heads, chunk, dk, dv, chunk_of):
    """Block specs of (q / k / g, v / o, beta, the chunk-start states) for
    a grid (sequence, heads' step, step); ``chunk_of`` maps the step to
    the chunk it works on."""
    keys = pl.BlockSpec((1, chunk, heads * dk),
                        lambda b, j, c: (b, chunk_of(c), j))
    values = pl.BlockSpec((1, chunk, heads * dv),
                          lambda b, j, c: (b, chunk_of(c), j))
    column = pl.BlockSpec((1, 1, chunk, heads),
                          lambda b, j, c: (b, j, chunk_of(c), 0))
    starts = pl.BlockSpec((1, heads, 1, dv, dk),
                          lambda b, j, c: (b, j, chunk_of(c), 0, 0))
    return keys, values, column, starts


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


# jitted: every block's call shares ONE traced and lowered copy of each
# kernel (a kernel's size is set-up time; XLA inlines the call)
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _forward(q, k, v, g, beta, heads, chunk, interpret, save):
    b, t, _ = q.shape
    hs = beta.shape[1]
    dk, dv = q.shape[2] // (hs * heads), v.shape[2] // (hs * heads)
    nc = t // chunk
    keys, values, column, starts = _specs(heads, chunk, dk, dv, lambda c: c)
    out_specs, out_shape = [values], [jax.ShapeDtypeStruct(v.shape, q.dtype)]
    if save:
        out_specs.append(starts)
        out_shape.append(jax.ShapeDtypeStruct((b, hs * heads, nc, dv, dk),
                                              jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, dk=dk, dv=dv, save=save),
        grid=(b, hs, nc),
        in_specs=[keys, keys, values, keys, column],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=_params(), name="bps_kda_fwd",
        interpret=interpret)(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _backward(q, k, v, g, beta, starts, do, heads, chunk, interpret):
    t = q.shape[1]
    hs = beta.shape[1]
    dk, dv = q.shape[2] // (hs * heads), v.shape[2] // (hs * heads)
    nc = t // chunk
    keys, values, column, saved = _specs(heads, chunk, dk, dv,
                                         lambda c: nc - 1 - c)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, dk=dk, dv=dv),
        grid=(q.shape[0], hs, nc),
        in_specs=[keys, keys, values, keys, column, saved, values],
        out_specs=[keys, keys, values, keys, column],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, f32),
                   jax.ShapeDtypeStruct(beta.shape, f32)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), f32)],
        compiler_params=_params(), name="bps_kda_bwd",
        interpret=interpret)(q, k, v, g, beta, starts, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan_core(q, k, v, g, beta, heads, chunk, interpret):
    """q, k, g [B, T, H d_k]; v [B, T, H d_v]; beta [B, H / heads, T,
    heads] -> o [B, T, H d_v]."""
    return _forward(q, k, v, g, beta, heads, chunk, interpret, False)[0]


def _scan_core_fwd(q, k, v, g, beta, heads, chunk, interpret):
    o, starts = _forward(q, k, v, g, beta, heads, chunk, interpret, True)
    return o, (q, k, v, g, beta, starts)


def _scan_core_bwd(heads, chunk, interpret, res, do):
    return tuple(_backward(*res, do, heads, chunk, interpret))


_scan_core.defvjp(_scan_core_fwd, _scan_core_bwd)


def kda_scan(q, k, v, g, beta, *, chunk: int,
             interpret: Optional[bool] = None):
    """The chunked scan through the Mosaic kernels (module docstring);
    ``chunk`` has no default (the one caller, ``models/ling.py``, asks for
    its measured ``KDA_CHUNK``).
    Tracing a call sets the gauges ``kda.heads``, ``kda.chunk``,
    ``kda.chunks_per_seq``, ``kda.state_bytes`` (the carried state of one
    sequence: H x d_k x d_v float32), ``kda.saved_state_bytes`` (the
    chunk-start states one differentiated call keeps for its backward:
    B x T / chunk of them) and ``kda.matmul_operand_bytes_per_chunk`` (both
    operands of every matrix product of one head's chunk)."""
    if interpret is None:
        from .pallas_kernels import on_tpu
        interpret = not on_tpu()
    b, t, h, dk, dv = _check(q, k, v, g, beta, chunk)
    heads = _HEADS if h % _HEADS == 0 else 1
    if not interpret and (dk % 128 or dv % 128 or chunk % 8):
        raise ValueError(
            f"kda_scan: on the chip a head is whole lane tiles (d_k={dk}, "
            f"d_v={dv}: multiples of 128) and a chunk whole sublane tiles "
            f"(chunk={chunk})")
    from ..common.metrics import gauges
    state_bytes = 4 * h * dk * dv
    gauges.set("kda.heads", float(h))
    gauges.set("kda.chunk", float(chunk))
    gauges.set("kda.chunks_per_seq", float(t // chunk))
    gauges.set("kda.state_bytes", float(state_bytes))
    gauges.set("kda.saved_state_bytes", float(b * (t // chunk) * state_bytes))
    gauges.set("kda.matmul_operand_bytes_per_chunk",
               float(_matmul_operand_bytes(chunk, dk, dv, q.dtype)))
    columns = beta.astype(jnp.float32).reshape(b, t, h // heads, heads)
    o = _scan_core(q.reshape(b, t, h * dk), k.reshape(b, t, h * dk),
                   v.reshape(b, t, h * dv),
                   g.astype(jnp.float32).reshape(b, t, h * dk),
                   columns.transpose(0, 2, 1, 3), heads, chunk,
                   bool(interpret))
    return o.reshape(b, t, h, dv)
