"""The float32 row stages around a delta-rule scan as ONE pass over the
mixer's projection each: Pallas TPU kernels (forward + backward) in front
of ``ops/kda_scan.py`` ``kda_scan`` / ``ops/gdn_scan.py`` ``gdn_scan`` and
behind them.

``proj`` [B, T, ...] is the mixer's fused projection, ``[ q | k | v | f |
the output gate | beta ]`` for Kimi Delta Attention (``models/ling.py``
``LingKda``: H heads of d channels, five slices of ``H d`` lanes and H
columns) and ``[ q | k | v | the output gate ]`` for Gated DeltaNet
(``models/qwen3_next.py`` ``Qwen3NextGdn``: H_k key heads under H_v value
heads, slices of ``H_k d | H_k d | H_v d | H_v d`` lanes; its ``g`` and
``beta`` come from a projection of their own and stay plain text there).
What the plain text does with it, position t of a sequence, float32
throughout (``models/nemotron_h.py`` ``causal_conv``, ``models/ling.py``
``l2_normalize`` / ``log_decay``, ``models/qwen3_next.py`` ``gdn_pre``,
``models/llama.py`` ``RMSNorm``: the text the kernels are tested against,
``tests/test_kda_rows.py``)::

    c_t  = sum_j conv_kernel[j] * x_(t - K + 1 + j)   x = q | k | v, K taps,
                                                      zeros before t = 0
    s_t  = silu(c_t)
    q_t  = s_t rsqrt(sum_head s_t^2 + 1e-6) / sqrt(d)     k_t likewise, no
                                                      1 / sqrt(d); v_t = s_t
    g_t  = lower_bound sigmoid(exp(A_log_h) (f_t + dt_bias_h))   float32
    beta = sigmoid(the last H columns)                           float32
    y_t  = o_t rsqrt(mean_head o_t^2 + eps) weight * act(gate_t)

:func:`kda_pre` (every line above) and :func:`qkv_pre` (no ``f``, no
``g``, no ``beta``) read ``proj`` once and write the scan's operands once
(``bps_kda_pre_fwd``): ONE kernel text, in which the slices' widths are
read off the blocks' shapes and the decay slice, its two parameter rows
and ``g`` are there where a lower bound is given.  :func:`kda_post` reads
the scan's ``o`` and the gate once and writes ``y`` once
(``bps_kda_post_fwd``; ``act`` a sigmoid or SiLU).  No float32 copy of the
q | k | v columns exists in HBM: the cast, the taps, SiLU, the norms and
the gates happen on a block of ``_ROWS`` positions in VMEM, a head (a run
of d lanes, one lane tile at d = 128) at a time; q, k, v leave in
``proj.dtype``, ``g`` and ``beta`` in float32, as the plain text rounds
them.  The slices are read through index maps on the one ``proj`` array:
XLA makes no slice copies.  Every slice is walked in the same column
steps — ``_HEADS`` heads of the narrowest slice a step, so a slice twice
as wide has a block twice as wide (:func:`_cut`: at 16 key heads under 32
value heads of 128, 8 steps of 256 | 256 | 512 | 512 lanes; a slice's
first lane has to be whole blocks of its own width, and widths that are
not are refused).  The taps' three positions before a block come from a
second, 16-row view of the same array (zeros at a sequence's start: never
another sequence's rows).

Each is one ``jax.custom_vjp`` whose residuals are its inputs: the backward
kernels (``bps_kda_pre_bwd``, ``bps_kda_post_bwd``) recompute the float32
values from ``proj`` (``o``, the gate) and write the cotangent of each
slice once, in ``proj.dtype``.  The transposed taps need the convolution's
cotangent at the three positions AFTER a block: the kernel recomputes it
from 16-row views of ``proj`` and of dq, dk, dv behind the block, so no
grid step depends on another.  The parameters' gradients (``conv_kernel``,
``exp(A_log)`` and ``dt_bias`` a channel, the head norm's weight a lane)
are float32 sums over all positions, accumulated in an output block that
stays in VMEM across the (sequence, row block) axes of the grid — the
column steps' axis is the outermost.  The stage also hands the output
gate's columns on (``gate``, a copy in ``proj.dtype``) so that
``kda_post``'s cotangent of them comes back to its backward, which joins
every slice's (and ``beta``'s) cotangent into ``proj``'s in one
concatenation (not zero-padded sums).  ``beta``'s H columns and the [H] /
[H, d] parameter algebra are a few plain ``jax.numpy`` lines around the
kernels: a thousandth of the rows' bytes.

Sizes are arguments: any T (the last row block may be partial; positions
past T are masked out of every sum), any B, heads and d under the
interpreter; on the chip a head is whole lane tiles (d a multiple of 128)
and is refused otherwise, as the scans refuse it.  ``interpret=None``
engages Mosaic on a real TPU and the Pallas interpreter elsewhere.

On a v5e, bfloat16 ``proj``, a call's device time in the step's trace.
At 2 x 8192 positions, 32 heads of 128 (``ling3_flash.fused_1c``; PERF.md
section 6, PR 44): ``kda_pre`` 2.49 ms forward (1.49 GB moved: 73 % of the
HBM roof) and 3.68 backward, ``kda_post`` 0.93 and 1.37 — 59.5 ms a step
for five layers' forward, recomputed forward and backward, where XLA's
fusions and the layout copies between them took ~485.  ``_HEADS`` = 4
reads 53.5 ms a step and 2.5 s more of set-up (the bodies are unrolled
over a step's heads and lowered at every call site): 2 taken.  Stand-alone
calls read blocks of 128 .. 1024 positions the same to 5 %, and the same
bodies as a loop over runs of 16 .. 128 positions of a block 2.1 .. 1.0
times as long.  At 4 x 8192 positions, 16 key heads under 32 value heads
of 128 (``qwen3_next_80b.fused_1c``; PERF.md section 6, PR 47):
``qkv_pre`` 2.76 .. 2.82 ms forward (1.61 GB moved, z's copy included:
71 % of the HBM roof) and 3.77 backward, the one concatenation of
``proj``'s cotangent 2.4 — 35 ms a step for three layers' forward,
recomputed forward and backward, where XLA's fusions over 1 GiB float32
arrays and the joins of their cotangents took ~253.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_pre", "qkv_pre", "kda_post"]

_VMEM_LIMIT = 64 << 20
_ROWS = 256      # positions a grid step
_HEADS = 2       # heads a grid step: a block is _HEADS * d lanes wide
_L2_EPS = 1e-6   # models/ling.py l2_normalize


def _halo(rows: int) -> int:
    """Rows of the views before and behind a block (whole sublane tiles of
    either dtype where the block allows)."""
    return 16 if rows % 16 == 0 else 8


def _inside(first_pos, n, t_len):
    """[n, 1]: which of the positions ``first_pos ..`` lie in [0, T)."""
    pos = first_pos + lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    return (pos >= 0) & (pos < t_len)


def _fill(scr, parts, lanes, first_pos, t_len):
    """Stack ``parts`` ((ref, rows, masked) ...; blocks [1, rows, C]) as
    float32 rows of ``scr`` from row 0, lanes ``lanes`` of each;
    ``first_pos`` is the position of row 0.  Rows of a ``masked`` part
    outside [0, T) are zeros: the views in front of the first block and
    behind the last, and a last block that is partial."""
    at = 0
    for ref, n, masked in parts:
        x = ref[0, :, lanes].astype(jnp.float32)
        if masked:
            x = jnp.where(_inside(first_pos + at, n, t_len), x, 0.0)
        scr[at:at + n, :] = x
        at += n


def _taps(scr, w_ref, s, lanes, first, n, taps):
    """The convolution at rows ``first .. first + n - 1`` of ``scr``:
    row r reads r - taps + 1 .. r.  ``w_ref`` [3 taps, C]: slice s's taps."""
    return sum(w_ref[s * taps + j:s * taps + j + 1, lanes]
               * scr[first - taps + 1 + j:first - taps + 1 + j + n, :]
               for j in range(taps))


def _unit(act):
    """1 / ||act||_2 a row, [rows, 1]."""
    return lax.rsqrt(jnp.sum(act * act, axis=-1, keepdims=True) + _L2_EPS)


def _scale(s, d):
    """What slice s's unit rows are scaled by: q's 1 / sqrt(d)."""
    return 1.0 / math.sqrt(d) if s == 0 else 1.0


def _gate(f_ref, ea_ref, dtb_ref):
    """(``exp(A_log) (f + dt_bias)``'s sigmoid, ``f + dt_bias``)."""
    shifted = f_ref[0].astype(jnp.float32) + dtb_ref[...]
    return jax.nn.sigmoid(ea_ref[...] * shifted), shifted


# ------------------------------------------------------------ before the scan
#
# A slice's block is as many heads as its lanes hold (q, k, v may differ in
# width: all are walked in the same column steps); the decay slice ``f``,
# its two parameter rows and ``g`` are there where ``lower`` is given.

def _pre_fwd_kernel(xq, xk, xv, bq, bk, bv, *rest, d, taps, rows, halo, lower,
                    t_len):
    if lower is None:
        gate_ref, w_ref, q_ref, k_ref, v_ref, gate_out, scr = rest
    else:
        (f_ref, gate_ref, w_ref, ea_ref, dtb_ref, q_ref, k_ref, v_ref, g_ref,
         gate_out, scr) = rest
    start = pl.program_id(2) * rows
    for s, (x_ref, b_ref, o_ref) in enumerate(
            ((xq, bq, q_ref), (xk, bk, k_ref), (xv, bv, v_ref))):
        for h in range(x_ref.shape[-1] // d):
            lanes = slice(h * d, (h + 1) * d)
            # rows past T of a partial block reach no row before T
            _fill(scr, ((b_ref, halo, True), (x_ref, rows, False)), lanes,
                  start - halo, t_len)
            conv = _taps(scr, w_ref, s, lanes, halo, rows, taps)
            act = conv * jax.nn.sigmoid(conv)
            if s < 2:
                act = act * (_unit(act) * _scale(s, d))
            o_ref[0, :, lanes] = act.astype(o_ref.dtype)
    if lower is not None:
        g_ref[0] = lower * _gate(f_ref, ea_ref, dtb_ref)[0]
    gate_out[0] = gate_ref[0]


def _pre_bwd_kernel(xq, xk, xv, bq, bk, bv, aq, ak, av, *rest, d, taps, rows,
                    halo, lower, t_len):
    if lower is None:
        (w_ref, dq, dk, dv, adq, adk, adv, dxq, dxk, dxv, dw_ref, scr,
         dscr) = rest
        sums = (dw_ref,)
    else:
        (f_ref, w_ref, ea_ref, dtb_ref, dq, dk, dv, adq, adk, adv, dg_ref,
         dxq, dxk, dxv, df_ref, dw_ref, dea_ref, ddtb_ref, scr, dscr) = rest
        sums = (dw_ref, dea_ref, ddtb_ref)

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        for ref in sums:
            ref[...] = jnp.zeros_like(ref)

    start = pl.program_id(2) * rows
    ragged = t_len % rows != 0
    n = rows + halo                    # the block and the rows behind it
    for s, (x_ref, b_ref, a_ref, dy_ref, ady_ref, dx_ref) in enumerate(
            ((xq, bq, aq, dq, adq, dxq), (xk, bk, ak, dk, adk, dxk),
             (xv, bv, av, dv, adv, dxv))):
        for h in range(x_ref.shape[-1] // d):
            lanes = slice(h * d, (h + 1) * d)
            _fill(scr, ((b_ref, halo, True), (x_ref, rows, ragged),
                        (a_ref, halo, True)), lanes, start - halo, t_len)
            conv = _taps(scr, w_ref, s, lanes, halo, n, taps)
            sig = jax.nn.sigmoid(conv)
            _fill(dscr, ((dy_ref, rows, ragged), (ady_ref, halo, True)),
                  lanes, start, t_len)
            dact = dscr[...]
            if s < 2:
                act = conv * sig
                r = _unit(act)
                dact = (r * _scale(s, d)) * (dact - act * (
                    r * r * jnp.sum(dact * act, axis=-1, keepdims=True)))
            # the convolution's cotangent: zero past T, where dy is
            dscr[...] = dact * (sig * (1.0 + conv * (1.0 - sig)))
            dx_ref[0, :, lanes] = sum(
                w_ref[s * taps + j:s * taps + j + 1, lanes]
                * dscr[taps - 1 - j:taps - 1 - j + rows, :]
                for j in range(taps)).astype(dx_ref.dtype)
            for j in range(taps):
                dw_ref[s * taps + j:s * taps + j + 1, lanes] += jnp.sum(
                    dscr[0:rows, :]
                    * scr[halo - taps + 1 + j:halo - taps + 1 + j + rows, :],
                    axis=0, keepdims=True)
    if lower is None:
        return
    sg, shifted = _gate(f_ref, ea_ref, dtb_ref)
    du = dg_ref[0] * (lower * sg * (1.0 - sg))
    if ragged:
        live = _inside(start, rows, t_len)
        du, shifted = jnp.where(live, du, 0.0), jnp.where(live, shifted, 0.0)
    df_ref[0] = (du * ea_ref[...]).astype(df_ref.dtype)
    dea_ref[...] += jnp.sum(du * shifted, axis=0, keepdims=True)
    ddtb_ref[...] += jnp.sum(du * ea_ref[...], axis=0, keepdims=True)


def _layout(t, rows):
    rows = min(rows, -(-t // 8) * 8)
    return rows, _halo(rows), -(-t // rows)


def _specs(rows, halo, t):
    """Block specs for a grid (column step j, sequence b, row block i) over
    [B, T, lanes] arrays: ``cur(c, first)`` / ``before(c, first)`` /
    ``behind(c, first)`` read the ``first + j``-th run of ``c`` lanes (a
    slice of ``proj`` whose first lane is ``first * c``; no ``first``: an
    array of the slice's own width) — a block, the ``halo`` rows in front
    of it, the ``halo`` rows behind it; ``row(n, c)`` an [n, lanes]
    parameter's lanes."""
    per, last = rows // halo, -(-t // halo) - 1

    def col(first, j):
        return j if first is None else first + j

    def cur(c, first=None):
        return pl.BlockSpec((1, rows, c),
                            lambda j, b, i: (b, i, col(first, j)))

    def before(c, first=None):
        return pl.BlockSpec(
            (1, halo, c),
            lambda j, b, i: (b, jnp.maximum(i * per - 1, 0), col(first, j)))

    def behind(c, first=None):
        return pl.BlockSpec(
            (1, halo, c),
            lambda j, b, i: (b, jnp.minimum((i + 1) * per, last),
                             col(first, j)))

    def row(n, c):
        return pl.BlockSpec((n, c), lambda j, b, i: (0, j))

    return cur, before, behind, row


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _cut(proj, widths, decay, d):
    """How the column steps cut ``proj``'s slices q | k | v | (f) | gate —
    q, k, v's lanes given (``widths``), f's read off the decay rows' shape,
    the gate's what is left in front of ``beta``'s columns (a head's: they
    come with the decay slice) — at heads of ``d`` -> (steps, [(lanes a
    step, the slice's first block), ...], every slice's lanes, ``beta``'s
    columns).  Every slice is walked in the same steps — ``_HEADS`` heads
    of the narrowest a step, so a wider slice's block is wider — and a
    block spec counts in blocks, so a slice's first lane has to be whole
    blocks of ITS width."""
    f_width = decay[0].shape[1] if decay else 0
    betas = f_width // d
    widths = list(widths) + [f_width] * bool(decay)
    widths.append(proj.shape[-1] - sum(widths) - betas)
    unit = math.gcd(*widths)
    steps = unit // (math.gcd(unit // d, _HEADS) * d) if unit % d == 0 else 0
    firsts = [sum(widths[:s]) for s in range(len(widths))]
    if not steps or any(at % (w // steps) for at, w in zip(firsts, widths)):
        raise ValueError(
            f"kda_rows: slices of {' | '.join(map(str, widths))} lanes at "
            f"heads of {d} are not whole blocks of one number of column "
            f"steps (each slice's first lane a multiple of its block)")
    return steps, [(w // steps, at // (w // steps))
                   for at, w in zip(firsts, widths)], widths, betas


def _pack(conv_kernel, widths, steps):
    """``conv_kernel`` [K, q | k | v] as ONE float32 [3 K, steps c] array,
    c the widest slice's block: rows s K .. s K + K - 1 of column step j's
    c lanes hold slice s's taps for ITS block j in their first lanes
    (zeros behind a narrower slice's) — one parameter block a grid step,
    whatever the widths."""
    taps = conv_kernel.shape[0]
    c = max(widths) // steps
    at, parts = 0, []
    for n in widths:
        w = conv_kernel[:, at:at + n].astype(jnp.float32)
        parts.append(jnp.pad(w.reshape(taps, steps, n // steps),
                             ((0, 0), (0, 0), (0, c - n // steps))))
        at += n
    return jnp.concatenate(parts).reshape(3 * taps, steps * c)


def _beta(proj, betas):
    return jax.nn.sigmoid(
        proj[..., proj.shape[-1] - betas:].astype(jnp.float32))


# jitted: every layer's call shares ONE traced and lowered copy of each
# kernel (a kernel's size is set-up time; XLA inlines the call)
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _pre_forward(proj, w, decay, widths, d, lower, rows, interpret):
    b, t, _ = proj.shape
    taps = w.shape[0] // 3
    rows, halo, blocks = _layout(t, rows)
    steps, cuts, widths, betas = _cut(proj, widths, decay, d)
    qkv, f = cuts[:3], cuts[3:-1]              # f: one slice or none
    cur, before, _, row = _specs(rows, halo, t)
    shapes = [jax.ShapeDtypeStruct((b, t, n), proj.dtype) for n in widths]
    if f:
        shapes[3] = jax.ShapeDtypeStruct((b, t, widths[3]), jnp.float32)
    outs = pl.pallas_call(
        functools.partial(_pre_fwd_kernel, d=d, taps=taps, rows=rows,
                          halo=halo, lower=lower, t_len=t),
        grid=(steps, b, blocks),
        in_specs=([cur(*c) for c in qkv] + [before(*c) for c in qkv]
                  + [cur(*c) for c in cuts[3:]]            # (f), the gate
                  + [row(3 * taps, w.shape[1] // steps)]
                  + [row(1, c) for c, _ in f] * 2),
        out_specs=[cur(c) for c, _ in cuts], out_shape=shapes,
        scratch_shapes=[pltpu.VMEM((halo + rows, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        name="bps_kda_pre_fwd", interpret=interpret,
    )(*([proj] * (6 + len(cuts[3:]))), w, *decay)
    if not f:
        return tuple(outs)                     # q, k, v, the gate's copy
    q, k, v, g, gate = outs
    return q, k, v, gate, g, _beta(proj, betas)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _pre_backward(proj, w, decay, cts, widths, d, lower, rows, interpret):
    dq, dk, dv, dgate, *dgb = cts              # dgb: (dg, dbeta) or none
    b, t, _ = proj.shape
    taps = w.shape[0] // 3
    rows, halo, blocks = _layout(t, rows)
    steps, cuts, widths, betas = _cut(proj, widths, decay, d)
    qkv, f = cuts[:3], cuts[3:-1]              # f: one slice or none
    cur, before, behind, row = _specs(rows, halo, t)
    f32 = jnp.float32
    sums = [row(3 * taps, w.shape[1] // steps)] + [row(1, c) for c, _ in f] * 2
    outs = pl.pallas_call(
        functools.partial(_pre_bwd_kernel, d=d, taps=taps, rows=rows,
                          halo=halo, lower=lower, t_len=t),
        grid=(steps, b, blocks),
        in_specs=([cur(*c) for c in qkv] + [before(*c) for c in qkv]
                  + [behind(*c) for c in qkv] + [cur(*c) for c in f] + sums
                  + [cur(c) for c, _ in qkv] + [behind(c) for c, _ in qkv]
                  + [cur(c) for c, _ in f]),
        out_specs=[cur(c) for c, _ in qkv + f] + sums,
        out_shape=([jax.ShapeDtypeStruct((b, t, n), proj.dtype)
                    for n in widths[:-1]]
                   + [jax.ShapeDtypeStruct(a.shape, f32)
                      for a in (w, *decay)]),
        scratch_shapes=[pltpu.VMEM((2 * halo + rows, d), f32),
                        pltpu.VMEM((halo + rows, d), f32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        name="bps_kda_pre_bwd", interpret=interpret,
    )(*([proj] * (9 + len(f))), w, *decay, dq, dk, dv, dq, dk, dv,
      *dgb[:1])
    dx, (dw, *ddecay) = outs[:len(qkv + f)], outs[len(qkv + f):]
    pieces = [*dx, dgate]                      # dq | dk | dv | (df) | dgate
    if f:
        beta = _beta(proj, betas)
        pieces.append((dgb[1] * beta * (1.0 - beta)).astype(proj.dtype))
    # every column of proj's cotangent, written here once
    return jnp.concatenate(pieces, axis=-1), dw, tuple(ddecay)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _pre_core(proj, w, decay, widths, d, lower, rows, interpret):
    return _pre_forward(proj, w, decay, widths, d, lower, rows, interpret)


def _pre_core_fwd(proj, w, decay, widths, d, lower, rows, interpret):
    return (_pre_forward(proj, w, decay, widths, d, lower, rows, interpret),
            (proj, w, decay))


def _pre_core_bwd(widths, d, lower, rows, interpret, res, cts):
    return _pre_backward(*res, cts, widths, d, lower, rows, interpret)


_pre_core.defvjp(_pre_core_fwd, _pre_core_bwd)


def _on_chip(interpret, d, what):
    if interpret is None:
        from .pallas_kernels import on_tpu
        interpret = not on_tpu()
    if not interpret and d % 128:
        raise ValueError(f"{what}: on the chip a head is whole lane tiles "
                         f"(head size {d}: a multiple of 128)")
    return bool(interpret)


def _pre(what, proj, conv_kernel, widths, d, decay, lower, rows, interpret):
    """The stage on ``proj``'s first ``sum(widths)`` lanes q | k | v under
    ``conv_kernel``'s taps (module docstring)."""
    taps = conv_kernel.shape[0]
    if taps - 1 > _halo(_layout(proj.shape[1], rows)[0]):
        raise ValueError(f"{what}: {taps} taps reach past the rows read in "
                         f"front of a block")
    interpret = _on_chip(interpret, d, what)
    steps = _cut(proj, widths, decay, d)[0]
    return _pre_core(proj, _pack(conv_kernel, widths, steps), decay, widths,
                     d, lower, rows, interpret)


def kda_pre(proj, conv_kernel, a_log, dt_bias, *, lower_bound: float,
            rows: int = _ROWS, interpret: Optional[bool] = None):
    """``proj`` [B, T, 5 H d + H], ``conv_kernel`` [K, 3 H d], ``a_log``
    [H], ``dt_bias`` [H, d] -> the scan's operands ``q, k, v`` [B, T, H, d]
    in ``proj.dtype``, ``g`` [B, T, H, d] and ``beta`` [B, T, H] float32,
    and the output gate's columns ``gate`` [B, T, H d] as they lie in
    ``proj`` (module docstring)."""
    heads, d = dt_bias.shape
    inner = heads * d
    b, t, width = proj.shape
    if width != 5 * inner + heads or conv_kernel.shape[1] != 3 * inner:
        raise ValueError(
            f"kda_pre: {heads} heads of {d} want proj [.., {5 * inner + heads}"
            f"] and conv_kernel [.., {3 * inner}], got {proj.shape} and "
            f"{conv_kernel.shape}")
    f32 = jnp.float32
    decay = (jnp.repeat(jnp.exp(a_log.astype(f32)), d)[None],
             dt_bias.astype(f32).reshape(1, inner))
    q, k, v, gate, g, beta = _pre(
        "kda_pre", proj, conv_kernel, (inner,) * 3, d, decay,
        float(lower_bound), rows, interpret)
    by_head = (b, t, heads, d)
    return (q.reshape(by_head), k.reshape(by_head), v.reshape(by_head),
            g.reshape(by_head), beta, gate)


def qkv_pre(proj, conv_kernel, *, key_heads: int, value_heads: int,
            head_dim: int, rows: int = _ROWS,
            interpret: Optional[bool] = None):
    """The stage without a decay slice, at key heads under value heads
    (Gated DeltaNet, ``models/qwen3_next.py``: ``g`` and ``beta`` come from
    a projection of their own).  ``proj`` [B, T, 2 H_k d + 2 H_v d] = ``[ q
    | k | v | the output gate ]``, ``conv_kernel`` [K, 2 H_k d + H_v d] ->
    ``q, k`` [B, T, H_k, d] and ``v`` [B, T, H_v, d] in ``proj.dtype`` and
    the gate's columns ``gate`` [B, T, H_v d] as they lie in ``proj``."""
    d = head_dim
    wq, wv = key_heads * d, value_heads * d
    b, t, width = proj.shape
    if width != 2 * wq + 2 * wv or conv_kernel.shape[1] != 2 * wq + wv:
        raise ValueError(
            f"qkv_pre: {key_heads} key heads under {value_heads} value heads "
            f"of {d} want proj [.., q {wq} | k {wq} | v {wv} | gate {wv}] "
            f"and conv_kernel [.., {2 * wq + wv}], got {proj.shape} and "
            f"{conv_kernel.shape}")
    q, k, v, gate = _pre("qkv_pre", proj, conv_kernel, (wq, wq, wv), d, (),
                         None, rows, interpret)
    return (q.reshape(b, t, key_heads, d), k.reshape(b, t, key_heads, d),
            v.reshape(b, t, value_heads, d), gate)


# ------------------------------------------------------------ behind the scan

def _gate_act(x, silu):
    """(the output gate's activation of x, its derivative): a sigmoid, or
    with ``silu`` ``x sigmoid(x)``."""
    sg = jax.nn.sigmoid(x)
    if silu:
        return x * sg, sg * (1.0 + x * (1.0 - sg))
    return sg, sg * (1.0 - sg)


def _post_fwd_kernel(o_ref, gate_ref, w_ref, y_ref, *, heads, d, eps, silu):
    for h in range(heads):
        lanes = slice(h * d, (h + 1) * d)
        o = o_ref[0, :, lanes].astype(jnp.float32)
        r = lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        y_ref[0, :, lanes] = (
            o * r * w_ref[...]
            * _gate_act(gate_ref[0, :, lanes].astype(jnp.float32), silu)[0]
        ).astype(y_ref.dtype)


def _post_bwd_kernel(o_ref, gate_ref, w_ref, dy_ref, do_ref, dgate_ref,
                     dw_ref, *, heads, d, eps, rows, t_len, silu):
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    live = (_inside(pl.program_id(2) * rows, rows, t_len)
            if t_len % rows else None)
    for h in range(heads):
        lanes = slice(h * d, (h + 1) * d)

        def rows_of(ref):
            x = ref[0, :, lanes].astype(jnp.float32)
            return x if live is None else jnp.where(live, x, 0.0)

        o, dy = rows_of(o_ref), rows_of(dy_ref)
        r = lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        unit = o * r
        act, slope = _gate_act(rows_of(gate_ref), silu)
        dgate_ref[0, :, lanes] = (
            dy * (unit * w_ref[...]) * slope
        ).astype(dgate_ref.dtype)
        dn = dy * act
        dw_ref[:, lanes] += jnp.sum(dn * unit, axis=0, keepdims=True)
        dunit = dn * w_ref[...]
        do_ref[0, :, lanes] = (
            r * (dunit - unit * jnp.mean(dunit * unit, axis=-1,
                                         keepdims=True))
        ).astype(do_ref.dtype)


def _post_specs(heads, d, rows):
    c = heads * d
    block = pl.BlockSpec((1, rows, c), lambda j, b, i: (b, i, j))
    weight = pl.BlockSpec((1, d), lambda j, b, i: (0, 0))
    return block, weight, pl.BlockSpec((1, c), lambda j, b, i: (0, j))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _post_forward(o, gate, weight, heads, d, eps, rows, interpret, silu):
    b, t, inner = o.shape
    rows, _, blocks = _layout(t, rows)
    block, one, _ = _post_specs(heads, d, rows)
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, heads=heads, d=d, eps=eps,
                          silu=silu),
        grid=(inner // (heads * d), b, blocks),
        in_specs=[block, block, one], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=_params("parallel", "parallel", "parallel"),
        name="bps_kda_post_fwd", interpret=interpret)(o, gate, weight)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def _post_backward(o, gate, weight, dy, heads, d, eps, rows, interpret,
                   silu):
    b, t, inner = o.shape
    rows, _, blocks = _layout(t, rows)
    block, one, lane_sums = _post_specs(heads, d, rows)
    do, dgate, dw = pl.pallas_call(
        functools.partial(_post_bwd_kernel, heads=heads, d=d, eps=eps,
                          rows=rows, t_len=t, silu=silu),
        grid=(inner // (heads * d), b, blocks),
        in_specs=[block, block, one, block],
        out_specs=[block, block, lane_sums],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(gate.shape, gate.dtype),
                   jax.ShapeDtypeStruct((1, inner), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        name="bps_kda_post_bwd", interpret=interpret)(o, gate, weight, dy)
    # a lane's sum over positions -> the one weight every head shares
    return do, dgate, dw.reshape(inner // d, d).sum(0)[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _post_core(o, gate, weight, heads, d, eps, rows, interpret, silu):
    return _post_forward(o, gate, weight, heads, d, eps, rows, interpret,
                         silu)


def _post_core_fwd(o, gate, weight, heads, d, eps, rows, interpret, silu):
    return (_post_forward(o, gate, weight, heads, d, eps, rows, interpret,
                          silu), (o, gate, weight))


def _post_core_bwd(heads, d, eps, rows, interpret, silu, res, dy):
    return _post_backward(*res, dy, heads, d, eps, rows, interpret, silu)


_post_core.defvjp(_post_core_fwd, _post_core_bwd)


def kda_post(o, gate, weight, *, eps: float, gate_act: str = "sigmoid",
             rows: int = _ROWS, interpret: Optional[bool] = None):
    """``o`` [B, T, H, d] (the scan's), ``gate`` [B, T, H d], ``weight``
    [d] float32 -> ``y`` [B, T, H d] in ``o.dtype``: each head's RMSNorm
    under the one weight, times the gate's activation — ``gate_act``
    ``"sigmoid"`` (Kimi Delta Attention) or ``"silu"`` (Gated DeltaNet,
    ``models/qwen3_next.py``) — in float32 (module docstring)."""
    if gate_act not in ("sigmoid", "silu"):
        raise ValueError(f"kda_post: gate_act={gate_act!r} is neither "
                         f"'sigmoid' nor 'silu'")
    b, t, heads, d = o.shape
    if gate.shape != (b, t, heads * d) or weight.shape != (d,):
        raise ValueError(f"kda_post: o {o.shape} wants gate "
                         f"{(b, t, heads * d)} and weight {(d,)}, got "
                         f"{gate.shape} and {weight.shape}")
    interpret = _on_chip(interpret, d, "kda_post")
    return _post_core(o.reshape(b, t, heads * d), gate,
                      weight.astype(jnp.float32)[None], math.gcd(heads, _HEADS),
                      d, float(eps), rows, interpret, gate_act == "silu")
