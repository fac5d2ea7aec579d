"""Pallas TPU kernels for the compression hot path.

The reference's compressors are CPU C++ with sequential BitWriter loops
(compressor/impl/onebit.cc:34-140, compressor/utils.h); on TPU the hot
ops should stay on-chip.  These kernels implement the bandwidth-bound
pieces as single-pass Pallas programs:

- ``onebit_pack``:  sign-quantize + bit-pack 32x into uint32 *and*
  accumulate the L1 sum for the scale in the same pass over HBM (the
  jnp fallback reads the gradient twice: once for mean(|x|), once for
  the pack).
- ``onebit_unpack``: unpack + sign-scale in one pass.

Bit layout (shared with the jnp fallback in compression/onebit.py and the
numpy refs in tests/compression_refs.py): the flat gradient padded to
``32 * L`` elements is viewed as a (32, L) matrix, and bit ``i`` of word
``j`` is the sign of element ``(i, j)`` — i.e. element ``i*L + j`` of the
padded flat array.  Sublane-major packing makes the pack a pure
sublane-axis reduction and the unpack a broadcast: both map directly onto
the VPU's (8, 128) tiles with no cross-lane traffic, unlike the
word-major layout a CPU BitWriter produces.

All kernels take ``interpret=`` so CPU tests exercise the exact kernel
code path (the engine only dispatches to them on a real TPU backend).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # TPU lane width: word counts are padded to a multiple of this


def _pick_block(L: int) -> int:
    """Largest lane-block size that divides L (L is a multiple of 128)."""
    for cand in (2048, 1024, 512, 256, 128):
        if L % cand == 0:
            return cand
    raise ValueError(f"L={L} is not a multiple of {LANES}")


def padded_lanes(numel: int) -> int:
    """Number of uint32 words (= lanes) for a tensor of ``numel`` floats,
    rounded up so the packed row is lane-aligned."""
    words = -(-numel // 32)
    return -(-words // LANES) * LANES


# --- onebit ----------------------------------------------------------------

def _pack_kernel(x_ref, words_ref, abs_ref):
    xb = x_ref[...]                                        # (32, Lb) f32
    # Mosaic has no unsigned reductions; int32 two's-complement addition
    # is bit-identical, so shift-sum in int32 and bitcast to uint32
    bits = (xb >= 0).astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (32, 1), 0)
    packed = jnp.sum(bits << shifts, axis=0, keepdims=True,
                     dtype=jnp.int32)
    words_ref[...] = jax.lax.bitcast_convert_type(packed, jnp.uint32)

    # grid steps run sequentially on TPU: accumulate the L1 sum into one
    # revisited (1, 1) cell instead of per-step partials (Mosaic rejects
    # sub-(8,128) blocks that don't span the full array)
    @pl.when(pl.program_id(0) == 0)
    def _init():
        abs_ref[...] = jnp.zeros((1, 1), jnp.float32)

    abs_ref[...] += jnp.sum(jnp.abs(xb)).reshape(1, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def onebit_pack(x2d, interpret: bool = False):
    """(32, L) f32 -> ((L,) uint32 packed signs, scalar sum(|x|))."""
    L = x2d.shape[1]
    Lb = _pick_block(L)
    grid = L // Lb
    words, abs_sum = pl.pallas_call(
        _pack_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((32, Lb), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, Lb), lambda i: (0, i)),
                   pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, L), jnp.uint32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)],
        interpret=interpret,
    )(x2d)
    return words[0], abs_sum[0, 0]


def _expand_bits(words):
    """(1, Lb) uint32 -> (32, Lb) f32 of +-1 signs.  All-int32 arithmetic
    with explicit logical shifts: Mosaic lacks unsigned casts/shifts."""
    Lb = words.shape[-1]
    w_i = jnp.broadcast_to(jax.lax.bitcast_convert_type(words, jnp.int32),
                           (32, Lb))
    shifts = jnp.broadcast_to(
        jax.lax.broadcasted_iota(jnp.int32, (32, 1), 0), (32, Lb))
    bits = jax.lax.shift_right_logical(w_i, shifts) & jnp.int32(1)
    return bits.astype(jnp.float32) * 2.0 - 1.0


def _unpack_kernel(scale_ref, words_ref, out_ref):
    signs = _expand_bits(words_ref[...])                   # (32, Lb)
    out_ref[...] = signs * scale_ref[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def onebit_unpack(words, scale, interpret: bool = False):
    """((L,) uint32, scalar) -> (32, L) f32 of ``sign * scale``."""
    L = words.shape[0]
    Lb = _pick_block(L)
    grid = L // Lb
    return pl.pallas_call(
        _unpack_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, Lb), lambda i: (0, i))],
        out_specs=pl.BlockSpec((32, Lb), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((32, L), jnp.float32),
        interpret=interpret,
    )(scale.astype(jnp.float32).reshape(1), words.reshape(1, L))


def _unpack_sum_kernel(scales_ref, words_ref, out_ref):
    R = words_ref.shape[0]

    def body(r, acc):
        w = words_ref[pl.ds(r, 1), :]                        # (1, Lb) u32
        signs = _expand_bits(w)                              # (32, Lb)
        return acc + signs * scales_ref[r]

    out_ref[...] = jax.lax.fori_loop(
        0, R, body, jnp.zeros(out_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def onebit_unpack_sum(words, scales, interpret: bool = False):
    """Fused merge: ((R, L) uint32, (R,) f32) -> (32, L) f32 equal to
    ``sum_r sign_r * scale_r``.

    This is the "server" half of the compressed all-reduce
    (comm/compressed.py): after all-gathering R compressed payloads, the
    naive merge materializes R full (numel,) tensors before summing;
    this kernel streams the packed words once and accumulates in VMEM."""
    R, L = words.shape
    Lb = _pick_block(L)
    grid = L // Lb
    return pl.pallas_call(
        _unpack_sum_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((R, Lb), lambda i: (0, i))],
        out_specs=pl.BlockSpec((32, Lb), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((32, L), jnp.float32),
        interpret=interpret,
    )(scales.astype(jnp.float32), words)


def on_tpu() -> bool:
    """True when the default backend is a real TPU (kernels engaged).  A
    backend that fails to come up raises here: answering False would
    silently select interpret mode / the jnp codec on a broken chip."""
    return jax.default_backend() == "tpu"
