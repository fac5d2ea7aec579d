"""Jit-traceable push_pull / broadcast over pytrees.

Call these from *inside* a shard_map body (or any context where the mesh
axes are bound).  They are the building blocks of the fused training step —
the TPU-native equivalent of the reference's in-graph BytepsPushPull custom
op (reference tensorflow/ops.cc:208-231) — and of the compressed
cross-slice reduction (compression arrives via byteps_tpu.compression).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

AxisNames = Union[str, Sequence[str]]

# Minimum per-device DCN shard size (bytes) for the compressed hop to engage.
# The reference gates its compressors on BYTEPS_MIN_COMPRESS_BYTES
# (global.cc:137-139); here the knob gates the DCN hop specifically, because
# the measured crossover is about wire time vs compression compute: on the
# 8-device CPU mesh the onebit hop LOSES below ~2 MB/shard and wins above
# (a round-2 CPU-mesh run: 4 MB/rank = 1 MB shard -> 32.5 vs 21.6 ms;
# 16 MB/rank = 4 MB shard -> compressed faster; docs/performance.md has the
# table).  The crossover on real DCN is not measured; the env override
# exists for when it is.
DCN_COMPRESS_MIN_BYTES = 2 * 1024 * 1024


def dcn_compress_min_bytes() -> int:
    from ..common.config import _env_int
    # bpslint: ignore[env-knob] reason=read per trace so a mid-session env override re-gates the next compile (tests/test_wire_bytes.py); a Config snapshot would freeze it — documented in env.md Compression table
    return _env_int("BYTEPS_DCN_COMPRESS_MIN_BYTES",
                    DCN_COMPRESS_MIN_BYTES)


def _norm_axes(axis_names: AxisNames) -> Tuple[str, ...]:
    if isinstance(axis_names, str):
        return (axis_names,)
    return tuple(axis_names)


def push_pull_tree(tree, axis_names: AxisNames, op: str = "average"):
    """Sum or average every leaf across the named mesh axes.

    Horovod-style allreduce of a gradient pytree; the in-graph analog of
    bps.push_pull (reference tensorflow/__init__.py:40-81 applies
    compression then averages — here averaging is fused into the psum).
    """
    axes = _norm_axes(axis_names)

    def red(g):
        if op == "average":
            return lax.pmean(g, axes)
        return lax.psum(g, axes)

    return jax.tree.map(red, tree)


def broadcast_tree(tree, axis_names: AxisNames, root: int = 0):
    """Every shard receives the root shard's leaves.

    The reference implements broadcast as zero-non-root + sum push_pull
    (torch/__init__.py:259-291); identical trick, traced.
    """
    axes = _norm_axes(axis_names)

    def bcast(g):
        idx = _linear_axis_index(axes)
        mask = (idx == root).astype(g.dtype)
        return lax.psum(g * mask, axes)

    return jax.tree.map(bcast, tree)


def _linear_axis_index(axes: Tuple[str, ...]):
    """Global linear index across a tuple of mesh axes (row-major)."""
    idx = lax.axis_index(axes[0])
    for name in axes[1:]:
        idx = idx * lax.axis_size(name) + lax.axis_index(name)
    return idx


class _CompressorPair:
    """(compress, decompress) closures over the engine's Compressor classes
    for use as ``hierarchical_push_pull(compress=..., decompress=...)`` /
    ``make_dp_train_step(compress_dcn=...)``.

    hierarchical_push_pull always traces compress before decompress within
    one parameter's reduction, so the pair can carry the shard's static
    size (a Python int fixed at trace time) from one to the other — the
    payload itself has no numel field."""

    def __init__(self, factory):
        self._factory = factory
        self._comp = None

    def compress(self, shard):
        self._comp = self._factory(int(shard.size))
        payload, _ = self._comp.compress(shard, self._comp.init_state())
        return payload

    def decompress(self, payload):
        return self._comp.decompress(payload)

    def decompress_sum(self, gathered):
        """Fused decompress-and-sum over the gathered [n_dcn, ...]
        payloads — dispatches to the compressor's batched kernel (onebit's
        streaming merge, powersgd's single einsum) instead of a per-slice
        decompress loop."""
        return self._comp.decompress_sum(gathered)

    def as_pair(self):
        """(compress, decompress) with the fused sum attached as a
        function attribute, so existing two-element unpacking keeps
        working while hierarchical_push_pull can discover the fused
        path."""
        def decompress(payload):
            return self.decompress(payload)
        decompress.sum_fn = self.decompress_sum
        return self.compress, decompress


def make_onebit_pair(scaling: bool = True):
    """Onebit (sign+L1-scale) pair for the DCN hop: 32x fewer bytes cross
    the inter-slice network (reference's compressed push/pull,
    operations.cc:199-204); ICI stays full precision."""
    from ..compression.onebit import OnebitCompressor

    return _CompressorPair(
        lambda n: OnebitCompressor(n, scaling=scaling)).as_pair()


def make_powersgd_pair(rank: int = 4, iters: int = 2):
    """Low-rank pair for the DCN hop (compression/powersgd.py): the
    reduced ICI shard crosses DCN as (n+m)·r floats instead of n·m —
    ~sqrt(numel)/(2·r) x for square shards, e.g. 128x for a 4 MiB f32
    shard at rank 4 (vs onebit's fixed 32x), at f32 fidelity on the
    captured subspace.  This call site is stateless (the pair
    cold-starts each trace), so ``iters`` power iterations run inside
    compress — matmul+QR work on the MXU, the compressor whose compute
    is cheapest exactly where this hook runs."""
    from ..compression.powersgd import PowerSGDCompressor

    return _CompressorPair(
        lambda n: PowerSGDCompressor(n, rank=rank, iters=iters)).as_pair()


def hierarchical_push_pull(x, ici_axis: str = "ici", dcn_axis: str = "dcn",
                           op: str = "average",
                           compress=None, decompress=None,
                           compress_min_bytes: Optional[int] = None):
    """Two-level reduction of one array with an optional compressed DCN hop.

    Reproduces the reference's architecture (docs/architecture.md:14-41):
    reduce-scatter inside the slice (NCCL RS), exchange only the 1/n_ici
    shard across slices (push/pull to servers), all-gather inside the slice
    (NCCL AG).  ``compress``/``decompress`` wrap the DCN hop exactly where
    the reference's COMPRESS/DECOMPRESS pipeline stages sit
    (operations.cc:199-204): compressed bytes cross the slow network, full
    precision stays on ICI.

    The compressed hop only engages when the per-device DCN shard is at
    least ``compress_min_bytes`` (default: BYTEPS_DCN_COMPRESS_MIN_BYTES
    env or the measured crossover) — below that, compression compute costs
    more than the wire saves (reference's BYTEPS_MIN_COMPRESS_BYTES cutoff,
    global.cc:137-139).  Shapes are static under jit, so the decision is
    resolved at trace time per tensor.
    """
    orig_shape = x.shape
    orig_dtype = x.dtype
    n_ici = lax.axis_size(ici_axis)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n_ici
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = lax.psum_scatter(flat, ici_axis, scatter_dimension=0, tiled=True)
    if compress is not None:
        if compress_min_bytes is None:
            compress_min_bytes = dcn_compress_min_bytes()
        if shard.size * shard.dtype.itemsize < compress_min_bytes:
            # Size gate disables an explicitly supplied compressor: say so
            # (once per shape — this runs at trace time, not per step).
            # Callers wanting unconditional compression pass
            # compress_min_bytes=0.
            from ..common.logging import get_logger
            get_logger().debug(
                "hierarchical_push_pull: DCN shard %d B < compress_min_bytes"
                " %d B; compressed hop disabled for this tensor "
                "(pass compress_min_bytes=0 to force)",
                shard.size * shard.dtype.itemsize, compress_min_bytes)
            compress = None
    if compress is not None:
        # all_gather the compressed shards over DCN and decompress-sum:
        # the server-side "decompress each push, sum" semantics
        # (reference server.cc:87-113) without a server process.
        payload = compress(shard)
        gathered = lax.all_gather(payload, dcn_axis, axis=0)
        sum_fn = getattr(decompress, "sum_fn", None)
        if sum_fn is not None:
            # fused batched decompress-sum (one kernel over all slices'
            # payloads) when the pair provides it
            shard = sum_fn(gathered)
        else:
            n_dcn = lax.axis_size(dcn_axis)
            shard = sum(decompress(jax.tree.map(lambda p: p[i], gathered))
                        for i in range(n_dcn))
        shard = shard.astype(orig_dtype)
    else:
        shard = lax.psum(shard, dcn_axis)
    if op == "average":
        total = n_ici * lax.axis_size(dcn_axis)
        shard = (shard / total).astype(orig_dtype)
    out = lax.all_gather(shard, ici_axis, axis=0, tiled=True)
    if pad:
        out = out[:out.shape[0] - pad]
    return out.reshape(orig_shape)
