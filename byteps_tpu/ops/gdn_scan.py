"""Gated DeltaNet's recurrence — a gated delta rule whose decay is a HEAD's,
unbounded, with fewer key heads than value heads — as a chunked scan:
Pallas TPU kernels (forward + backward) and the same algebra in plain
``jax.numpy``.  The sibling of ``ops/kda_scan.py`` (a decay a CHANNEL,
bounded), whose unit-lower inverse, products and kernel parameters it
imports: they exist once.

Per sequence and VALUE head ``h`` a state ``S`` [d_k, d_v] float32 that
starts at zero; value head ``h`` reads key head ``h // (H_v / H_k)``
(arXiv:2412.06464 section 3; ``model_type: qwen3_next``)::

    S_t = (I - beta_t k_t k_t^T) exp(g_t) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

``q``, ``k`` [B, T, H_k, d_k] (the caller has normalised and scaled them),
``v`` [B, T, H_v, d_v], ``g`` [B, T, H_v] float32 (the log-decay: <= 0, of
ANY magnitude), ``beta`` [B, T, H_v] float32 -> ``o`` [B, T, H_v, d_v] in
``q.dtype``.

In chunks of C positions, with ``G`` the inclusive sum of ``g`` inside the
chunk, ``Gamma = exp(G)`` and ``S`` the state the chunk starts from::

    D_ij = exp(G_i - G_j)  (j <= i; 0 above the diagonal)      [C, C]
    A = Diag(beta) (K K^T o D)  strictly lower
    P = (Q K^T o D)             lower with its diagonal
    R  = (I + A)^-1 Diag(beta) (V - Diag(Gamma) K S)           [C, d_v]
    O  = Diag(Gamma) Q S + P R
    S' = Gamma_C S + (Diag(exp(G_C - G)) K)^T R

The decay is ONE number a pair of positions: ``D`` is formed from the
masked DIFFERENCE ``G_i - G_j`` (<= 0 wherever the mask keeps it), so
nothing overflows whatever the gate — no reference position, no
sub-blocks of 16 positions, no bound on ``g`` (the channel form's
``exp(G_first - G_j)`` would reach ``exp(16 x 20)`` at this family's
gates) — and multiplies ``K K^T`` and ``Q K^T`` element-wise: one stacked
score product a chunk and KEY head (``[Q; K] K^T``, :func:`_key_scores`),
shared by the value heads that read the key head (``beta`` and ``g`` are
a value head's, so ``A``, the solve and the state are too).  ``G`` is a
float32 ``cumsum`` of ``g`` inside each chunk, taken by XLA in front of
the kernels, which read it twice — down the sublanes ([C, 1]) and along
the lanes ([1, C]) — so that no kernel transposes a column; a difference
of two sums of C gates carries ``C |g| 2^-24`` of absolute error (1e-4 at
64 positions of -30: relative to a factor that is then ``exp(-30)`` or
less a step).  ``(I + A)^-1`` is ``ops/kda_scan.py``
``_unit_lower_inverse`` (float32, three bfloat16 passes a product); the
state is kept TRANSPOSED, [d_v, d_k].

:func:`gdn_scan` runs that as two Mosaic kernels (``bps_gdn_fwd``,
``bps_gdn_bwd``) under one ``jax.custom_vjp``.  A grid step is one
(sequence, key heads' step, chunk): ``_KEY_HEADS`` key heads where every
value head has its own (H_v = H_k), else ONE key head and the H_v / H_k
value heads that read it; q and k are indexed by the key head and never
repeated in HBM.  The chunk axis is the sequential one and the value
heads' states live in VMEM scratch across it.  Both kernels run ONE text,
:func:`_group_forward` (a chunk of one key head and its value heads,
two-dimensional arrays): the backward kernel walks the chunks in reverse
with the states' cotangents in scratch, READS the chunk-start states the
differentiated forward STORED ([B, H_v, T / C, d_v, d_k] float32: gauge
``gdn.saved_state_bytes``) and takes ``jax.vjp`` of the text inside the
kernel — q's and k's cotangents come out summed over the value heads.
The forward that is not differentiated (the first pass under ``remat``)
stores none.  ``g``'s gradient is a scalar a position and value head: the
kernel writes the column's and the row's cotangents and ``jax`` takes
them back through the ``cumsum``.

:func:`gdn_scan_chunked` is the same text under ``jax.vmap`` over
(sequence, key head) and a ``lax.scan`` over the chunks, differentiable by
``jax.grad``.

Sizes are arguments.  Refused: T not a multiple of ``chunk`` (pad with
``g = 0, beta = 0`` positions), H_v not a multiple of H_k, a chunk that is
not whole 16-position blocks of the inverse.  ``chunk`` is the
implementation's: the result does not depend on it; on a v5e at 4 x 8192
positions, 16 key heads under 32 value heads of 128 x 128, bfloat16, a
call's forward / forward + backward read 29.2 / 68.7 ms at C = 64 and
23.4 / 52.1 at C = 128 (a host clock around a jitted call; PERF.md
section 6, PR 46), so ``models/qwen3_next.py`` asks for 128 — as the
channel form does: a chunk's time is the inverse's dependent chain, which
the one score product in eight's place hardly shortens (by the device
trace of ``qwen3_next_80b.fused_1c``'s step, C = 128: forward 4.73 ms a
sequence, backward 6.51, where the channel form reads 5.14 / 7.84).
``interpret=None`` engages Mosaic on a real TPU and the Pallas interpreter
elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda_scan import (_NN, _NT, _SUB, _TN, _at, _dot, _dot_operand_bytes,
                       _params, _unit_lower_inverse)

__all__ = ["gdn_scan", "gdn_scan_chunked"]

# key heads a grid step takes where H_v = H_k (their chains are
# independent: the scheduler interleaves them); one where a key head has
# several value heads
_KEY_HEADS = 2


def _check(q, k, v, g, beta, chunk):
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    if t % chunk:
        raise ValueError(
            f"gdn_scan: T={t} is not a multiple of chunk={chunk}; pad the "
            f"sequence with g = 0, beta = 0 positions (they leave the state "
            f"as it is)")
    if chunk % min(chunk, _SUB):
        raise ValueError(f"gdn_scan: chunk={chunk} is not whole blocks of "
                         f"{_SUB} positions of the inverse")
    if hv % hk:
        raise ValueError(f"gdn_scan: {hv} value heads do not divide over "
                         f"{hk} key heads")
    want = {"k": (b, t, hk, dk), "v": (b, t, hv, dv), "g": (b, t, hv),
            "beta": (b, t, hv)}
    got = {"k": k.shape, "v": v.shape, "g": g.shape, "beta": beta.shape}
    if want != got:
        raise ValueError(f"gdn_scan: q {q.shape} wants {want}, got {got}")
    return b, t, hk, hv, dk, dv


def _layout(hk, hv):
    """(key heads, value heads) of a grid step."""
    ratio = hv // hk
    kh = _KEY_HEADS if ratio == 1 and hk % _KEY_HEADS == 0 else 1
    return kh, kh * ratio


def _chunk_sums(g, chunk):
    """``G``: the inclusive sum of g [B, T, H] float32 inside each chunk."""
    b, t, h = g.shape
    return jnp.cumsum(g.astype(jnp.float32).reshape(b, t // chunk, chunk, h),
                      axis=2).reshape(b, t, h)


# ---------------------------------------------------------- a chunk's text

def _key_scores(q, k):
    """``Q K^T`` and ``K K^T`` [C, C] float32 of one key head's chunk, ONE
    product: q's rows stacked on k's, against k."""
    c = q.shape[0]
    both = _dot(jnp.concatenate([q, k]), k, _NT)         # [2 C, C]
    return both[:c], both[c:]


def _head_forward(qk, kk, q, k, v, g_col, g_row, beta, state):
    """One chunk of one VALUE head (module docstring): ``qk``, ``kk`` its
    key head's score products [C, C] float32; q, k [C, d_k]; v [C, d_v];
    ``g_col`` [C, 1] and ``g_row`` [1, C] the chunk's ``G`` float32;
    beta [C, 1] float32; ``state`` the TRANSPOSED state [d_v, d_k] float32
    the chunk starts from -> (o [C, d_v] float32, the transposed state it
    hands on)."""
    f32, lp = jnp.float32, q.dtype
    c = q.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # D: the difference is <= 0 where the mask keeps it; above the
    # diagonal an exact zero, never exp of a positive sum
    decay = jnp.exp(jnp.where(row >= col, g_col - g_row, -jnp.inf))
    a_mat = jnp.where(row > col, beta * (kk * decay), 0.0)
    p = qk * decay
    t_mat = _unit_lower_inverse(a_mat)
    qf, kf = q.astype(f32), k.astype(f32)
    from_start = jnp.exp(g_col)                          # Gamma, [C, 1]
    s_lp = state.astype(lp)
    rhs = beta * (v.astype(f32)
                  - _dot((kf * from_start).astype(lp), s_lp, _NT))
    r = _dot(t_mat.astype(lp), rhs.astype(lp), _NN)      # [C, d_v]
    r_lp = r.astype(lp)
    o = (_dot((qf * from_start).astype(lp), s_lp, _NT)
         + _dot(p.astype(lp), r_lp, _NN))
    last = _at(g_col, c - 1)                             # G_C, [1, 1]
    to_end = (kf * jnp.exp(last - g_col)).astype(lp)     # G_C - G <= 0
    return o, state * jnp.exp(last) + _dot(r_lp, to_end, _TN)


def _group_forward(q, k, vs, g_cols, g_rows, betas, states):
    """One chunk of one KEY head and the value heads that read it: q, k
    [C, d_k] and a tuple an operand of :func:`_head_forward`, one entry a
    value head -> (the heads' o, the states they hand on).  The score
    products are formed once for all of them."""
    qk, kk = _key_scores(q, k)
    outs = [_head_forward(qk, kk, q, k, *head)
            for head in zip(vs, g_cols, g_rows, betas, states)]
    return tuple(o for o, _ in outs), tuple(s for _, s in outs)


@functools.lru_cache(maxsize=None)
def _matmul_operand_bytes(chunk, ratio, dk, dv, dtype) -> int:
    """What ONE key head's :func:`_group_forward` hands to the matrix unit
    a chunk, read off its own jaxpr at these shapes and types."""
    f32 = jnp.float32

    def aval(shape, kind=f32):
        return jax.ShapeDtypeStruct(shape, kind)

    def per_head(shape, kind=f32):
        return tuple(aval(shape, kind) for _ in range(ratio))

    return _dot_operand_bytes(jax.make_jaxpr(_group_forward)(
        aval((chunk, dk), dtype), aval((chunk, dk), dtype),
        per_head((chunk, dv), dtype), per_head((chunk, 1)),
        per_head((1, chunk)), per_head((chunk, 1)),
        per_head((dv, dk))).jaxpr)


# ------------------------------------------------------------ chunked form

def gdn_scan_chunked(q, k, v, g, beta, *, chunk: int):
    """The chunked scan in plain ``jax.numpy`` (module docstring): the
    chunk's text over (sequence, key head), the chunks in a ``lax.scan``.
    It runs the SAME ``_group_forward`` as the kernels: its agreement with
    them guards the ``pallas_call`` wrapping (grids, block specs, the key
    head a value head reads, the stored states, the backward's order), not
    the chunk algebra — for that the witness is the recurrence position by
    position (``tests/test_gdn_scan.py``,
    ``benchmarks/tests/gradcheck_qwen3_next.py``)."""
    b, t, hk, hv, dk, dv = _check(q, k, v, g, beta, chunk)
    nc, ratio = t // chunk, hv // hk
    f32 = jnp.float32

    def key_chunks(x):        # [B, T, H_k, d] -> [T / C, B, H_k, C, d]
        return x.reshape(b, nc, chunk, hk, -1).transpose(1, 0, 3, 2, 4)

    def value_chunks(x):      # [B, T, H_v, d] -> [T / C, B, H_k, ratio, C, d]
        return x.reshape(b, nc, chunk, hk, ratio, -1).transpose(
            1, 0, 3, 4, 2, 5)

    def group(q, k, v, g_col, beta, state):
        # v [ratio, C, d_v]; g_col, beta [ratio, C, 1]; state [ratio, ..]
        o, state = _group_forward(
            q, k, tuple(v), tuple(g_col),
            tuple(jnp.swapaxes(g_col, 1, 2)), tuple(beta), tuple(state))
        return jnp.stack(o), jnp.stack(state)

    over_heads = jax.vmap(jax.vmap(group))

    def one_chunk(state, inputs):
        o, state = over_heads(*inputs, state)
        return state, o

    cum = _chunk_sums(g, chunk)
    _, o = lax.scan(
        one_chunk, jnp.zeros((b, hk, ratio, dv, dk), f32),
        (key_chunks(q), key_chunks(k), value_chunks(v),
         value_chunks(cum[..., None]),
         value_chunks(beta.astype(f32)[..., None])))
    # [T / C, B, H_k, ratio, C, d_v] -> [B, T, H_v, d_v]
    return o.transpose(1, 0, 4, 2, 3, 5).reshape(b, t, hv, dv).astype(q.dtype)


# ---------------------------------------------------------------- kernels

def _group_inputs(q_ref, k_ref, v_ref, gcol_ref, grow_ref, beta_ref, j,
                  ratio, dk, dv):
    """The operands of :func:`_group_forward` but the states: key head j
    of the grid step and its value heads."""
    heads = range(j * ratio, (j + 1) * ratio)
    keys = slice(j * dk, (j + 1) * dk)
    return (q_ref[0, :, keys], k_ref[0, :, keys],
            tuple(v_ref[0, :, h * dv:(h + 1) * dv] for h in heads),
            tuple(gcol_ref[0, 0, :, h:h + 1] for h in heads),
            tuple(grow_ref[0, 0, 0, h:h + 1, :] for h in heads),
            tuple(beta_ref[0, 0, :, h:h + 1] for h in heads))


def _fwd_kernel(q_ref, k_ref, v_ref, gcol_ref, grow_ref, beta_ref, o_ref,
                *refs, kh, ratio, dk, dv, save):
    if save:
        starts_ref, state = refs
    else:
        state, = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for j in range(kh):
        heads = range(j * ratio, (j + 1) * ratio)
        starts = tuple(state[h] for h in heads)
        if save:
            for h, s0 in zip(heads, starts):
                starts_ref[0, h, 0] = s0
        outs, states = _group_forward(
            *_group_inputs(q_ref, k_ref, v_ref, gcol_ref, grow_ref, beta_ref,
                           j, ratio, dk, dv), starts)
        for h, o, s in zip(heads, outs, states):
            o_ref[0, :, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)
            state[h] = s


def _bwd_kernel(q_ref, k_ref, v_ref, gcol_ref, grow_ref, beta_ref,
                starts_ref, do_ref, dq_ref, dk_ref, dv_ref, dgcol_ref,
                dgrow_ref, dbeta_ref, dstate, *, kh, ratio, dk, dv):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    c, vh = q_ref.shape[1], kh * ratio
    lane = lax.broadcasted_iota(jnp.int32, (c, vh), 1)
    sublane = lax.broadcasted_iota(jnp.int32, (vh, c), 0)
    dbeta = dgcol = jnp.zeros((c, vh), jnp.float32)
    dgrow = jnp.zeros((vh, c), jnp.float32)
    for j in range(kh):
        heads = range(j * ratio, (j + 1) * ratio)
        keys = slice(j * dk, (j + 1) * dk)
        _, pull = jax.vjp(
            _group_forward,
            *_group_inputs(q_ref, k_ref, v_ref, gcol_ref, grow_ref, beta_ref,
                           j, ratio, dk, dv),
            tuple(starts_ref[0, h, 0] for h in heads))
        d_q, d_k, d_vs, d_gcols, d_grows, d_betas, d_states = pull((
            tuple(do_ref[0, :, h * dv:(h + 1) * dv].astype(jnp.float32)
                  for h in heads),
            tuple(dstate[h] for h in heads)))
        # summed over the value heads that read the key head
        dq_ref[0, :, keys] = d_q.astype(dq_ref.dtype)
        dk_ref[0, :, keys] = d_k.astype(dk_ref.dtype)
        for h, d_v, d_gcol, d_grow, d_beta, d_state in zip(
                heads, d_vs, d_gcols, d_grows, d_betas, d_states):
            dv_ref[0, :, h * dv:(h + 1) * dv] = d_v.astype(dv_ref.dtype)
            dstate[h] = d_state
            dbeta = dbeta + jnp.where(lane == h, d_beta, 0.0)
            dgcol = dgcol + jnp.where(lane == h, d_gcol, 0.0)
            dgrow = dgrow + jnp.where(sublane == h, d_grow, 0.0)
    dbeta_ref[0, 0] = dbeta
    dgcol_ref[0, 0] = dgcol
    dgrow_ref[0, 0, 0] = dgrow


def _specs(kh, vh, chunk, dk, dv, chunk_of):
    """Block specs of (q / k, v / o, a column of ``G`` or beta, a row of
    ``G``, the chunk-start states) for a grid (sequence, key heads' step,
    step); ``chunk_of`` maps the step to the chunk it works on."""
    keys = pl.BlockSpec((1, chunk, kh * dk),
                        lambda b, j, c: (b, chunk_of(c), j))
    values = pl.BlockSpec((1, chunk, vh * dv),
                          lambda b, j, c: (b, chunk_of(c), j))
    column = pl.BlockSpec((1, 1, chunk, vh),
                          lambda b, j, c: (b, j, chunk_of(c), 0))
    row = pl.BlockSpec((1, 1, 1, vh, chunk),
                       lambda b, j, c: (b, j, chunk_of(c), 0, 0))
    starts = pl.BlockSpec((1, vh, 1, dv, dk),
                          lambda b, j, c: (b, j, chunk_of(c), 0, 0))
    return keys, values, column, row, starts


def _sizes(q, v, beta, kh):
    """(steps of key heads, value heads a step, d_k, d_v)."""
    steps, vh = beta.shape[1], beta.shape[3]
    return (steps, vh, q.shape[2] // (steps * kh), v.shape[2] // (steps * vh))


# jitted: every block's call shares ONE traced and lowered copy of each
# kernel (a kernel's size is set-up time; XLA inlines the call)
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _forward(q, k, v, g_col, g_row, beta, kh, chunk, interpret, save):
    b, t, _ = q.shape
    steps, vh, dk, dv = _sizes(q, v, beta, kh)
    nc = t // chunk
    keys, values, column, row, starts = _specs(kh, vh, chunk, dk, dv,
                                               lambda c: c)
    out_specs, out_shape = [values], [jax.ShapeDtypeStruct(v.shape, q.dtype)]
    if save:
        out_specs.append(starts)
        out_shape.append(jax.ShapeDtypeStruct((b, steps * vh, nc, dv, dk),
                                              jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, kh=kh, ratio=vh // kh, dk=dk, dv=dv,
                          save=save),
        grid=(b, steps, nc),
        in_specs=[keys, keys, values, column, row, column],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((vh, dv, dk), jnp.float32)],
        compiler_params=_params(), name="bps_gdn_fwd",
        interpret=interpret)(q, k, v, g_col, g_row, beta)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _backward(q, k, v, g_col, g_row, beta, starts, do, kh, chunk, interpret):
    t = q.shape[1]
    steps, vh, dk, dv = _sizes(q, v, beta, kh)
    nc = t // chunk
    keys, values, column, row, saved = _specs(kh, vh, chunk, dk, dv,
                                              lambda c: nc - 1 - c)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, kh=kh, ratio=vh // kh, dk=dk, dv=dv),
        grid=(q.shape[0], steps, nc),
        in_specs=[keys, keys, values, column, row, column, saved, values],
        out_specs=[keys, keys, values, column, row, column],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g_col.shape, f32),
                   jax.ShapeDtypeStruct(g_row.shape, f32),
                   jax.ShapeDtypeStruct(beta.shape, f32)],
        scratch_shapes=[pltpu.VMEM((vh, dv, dk), f32)],
        compiler_params=_params(), name="bps_gdn_bwd",
        interpret=interpret)(q, k, v, g_col, g_row, beta, starts, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan_core(q, k, v, g_col, g_row, beta, kh, chunk, interpret):
    """q, k [B, T, H_k d_k]; v [B, T, H_v d_v]; ``G`` as columns and beta
    [B, steps, T, heads a step], ``G`` as rows [B, steps, T / C, heads a
    step, C] -> o [B, T, H_v d_v]."""
    return _forward(q, k, v, g_col, g_row, beta, kh, chunk, interpret,
                    False)[0]


def _scan_core_fwd(q, k, v, g_col, g_row, beta, kh, chunk, interpret):
    o, starts = _forward(q, k, v, g_col, g_row, beta, kh, chunk, interpret,
                         True)
    return o, (q, k, v, g_col, g_row, beta, starts)


def _scan_core_bwd(kh, chunk, interpret, res, do):
    return tuple(_backward(*res, do, kh, chunk, interpret))


_scan_core.defvjp(_scan_core_fwd, _scan_core_bwd)


def gdn_scan(q, k, v, g, beta, *, chunk: int,
             interpret: Optional[bool] = None):
    """The chunked scan through the Mosaic kernels (module docstring);
    ``chunk`` has no default (the one caller, ``models/qwen3_next.py``,
    asks for its measured ``GDN_CHUNK``).  Tracing a call sets the gauges
    ``gdn.heads`` / ``gdn.key_heads``, ``gdn.chunk``,
    ``gdn.chunks_per_seq``, ``gdn.state_bytes`` (the carried state of one
    sequence: H_v x d_k x d_v float32), ``gdn.saved_state_bytes`` (the
    chunk-start states one differentiated call keeps for its backward:
    B x T / chunk of them) and ``gdn.matmul_operand_bytes_per_chunk``
    (both operands of every matrix product of one KEY head's chunk, its
    value heads' included)."""
    if interpret is None:
        from .pallas_kernels import on_tpu
        interpret = not on_tpu()
    b, t, hk, hv, dk, dv = _check(q, k, v, g, beta, chunk)
    if not interpret and (dk % 128 or dv % 128 or chunk % 8):
        raise ValueError(
            f"gdn_scan: on the chip a head is whole lane tiles (d_k={dk}, "
            f"d_v={dv}: multiples of 128) and a chunk whole sublane tiles "
            f"(chunk={chunk})")
    kh, vh = _layout(hk, hv)
    steps, nc = hv // vh, t // chunk
    from ..common.metrics import gauges
    state_bytes = 4 * hv * dk * dv
    gauges.set("gdn.heads", float(hv))
    gauges.set("gdn.key_heads", float(hk))
    gauges.set("gdn.chunk", float(chunk))
    gauges.set("gdn.chunks_per_seq", float(nc))
    gauges.set("gdn.state_bytes", float(state_bytes))
    gauges.set("gdn.saved_state_bytes", float(b * nc * state_bytes))
    gauges.set("gdn.matmul_operand_bytes_per_chunk", float(
        _matmul_operand_bytes(chunk, hv // hk, dk, dv, q.dtype)))

    def columns(x):           # [B, T, H_v] -> [B, steps, T, heads a step]
        return x.reshape(b, t, steps, vh).transpose(0, 2, 1, 3)

    cum = _chunk_sums(g, chunk)
    rows = cum.reshape(b, nc, chunk, steps, vh).transpose(0, 3, 1, 4, 2)
    o = _scan_core(q.reshape(b, t, hk * dk), k.reshape(b, t, hk * dk),
                   v.reshape(b, t, hv * dv), columns(cum), rows,
                   columns(beta.astype(jnp.float32)), kh, chunk,
                   bool(interpret))
    return o.reshape(b, t, hv, dv)
