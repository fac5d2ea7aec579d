"""Fused compressed push_pull: the PS push/pull cycle with compression,
as ONE persistent XLA program on the mesh.

Reference flow (SURVEY.md §2.2 integration points): worker compresses its
gradient (COMPRESS stage), the server decompresses every worker's push and
sums (server.cc:87-113), re-compresses the merged result, and workers
decompress what they pull (DECOMPRESS stage).  Mathematically:

    out = D_s(C_s( sum_i D_w(C_w(g_i)) ))

This module reproduces both the math *and* the bandwidth economics without
a server: each rank all-gathers only its compressed payload (the "push" —
the quantized reduce leg: (R-1) x payload_bytes per rank versus
~2 x full_bytes for a psum allreduce), locally dequant-accumulates all
payloads in one pass (the "server"; onebit streams packed words through the
Pallas ``onebit_unpack_sum`` kernel on TPU backends), and bidirectional
compressors re-quantize the merged sum so the "pull" leg is quantized too.
With 32x onebit compression that is a real multi-x wire saving, which is
the whole point on bandwidth-scarce (DCN) links — the EQuARX crossover.

ISSUE 11 (fused quantized collectives on the AOT hot path): the whole
steady-state family — in-graph chunk slice, quantize, quantized gather,
dequant-accumulate, merged re-quantize, dequantize, error-feedback /
momentum / PRNG state update — is one program per (tensor width, chunk
codec) pair, pre-lowered and compiled at DECLARE time
(:func:`aot_warm_compressed_programs`), so a compressed push stream
compiles zero XLA programs after warmup, exactly like the uncompressed
buffer path (tests/test_compressed_aot.py pins the contract).  Compressor
state is engine-owned functional state (``_CompressionSlot``): the dict
pytrees are flattened to bare array leaves at this call boundary so the
:func:`~byteps_tpu.comm.collectives.aot_compile` signature guard — which
compares per-argument shapes/dtypes — can cover the whole argument list.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..compression.base import Compressor
from ..fault import injector as _fault
from .collectives import _cached, _cached_scalar, _struct, aot_compile
from .mesh import CommContext


def _fused_fn(comm: CommContext, worker_comp: Compressor,
              server_comp: Compressor, n_flat: int, wdef, sdef,
              nw: int, ns: int):
    """The persistent compressed chunk program.

    Signature: ``fn(flat [R, n_flat], off, *state_leaves) ->
    (merged [ln], *new_state_leaves)`` where ``ln = worker_comp.numel``
    (the chunk length this codec was built for) and the state leaves are
    ``nw`` rank-stacked worker leaves followed by ``ns`` replicated
    server leaves.  The chunk is sliced in-graph (``off`` is a traced
    device scalar, so every equal-length chunk of the tensor shares one
    executable), which is what lets the engine stage the flat tensor to
    the mesh ONCE per push instead of materializing a host slice per
    chunk — the compressed path's old per-chunk staging copy.
    """
    ln = worker_comp.numel
    axes = comm.dp_axes

    def build():
        def body(flat, off, *leaves):
            wst = jax.tree.unflatten(wdef, leaves[:nw])
            sst = jax.tree.unflatten(sdef, leaves[nw:])
            row = flat[0]                              # this rank's row
            x = lax.dynamic_slice(row, (off,), (ln,))
            wst0 = jax.tree.map(lambda s: s[0], wst)
            payload, wst2 = worker_comp.compress(x, wst0)
            # "push": only quantized bytes cross the interconnect
            gathered = jax.tree.map(
                lambda p: lax.all_gather(p, axes, axis=0), payload)
            # "server": dequant-accumulate every rank's payload in one
            # pass (Pallas onebit_unpack_sum on TPU; pure-XLA fallback)
            y = worker_comp.decompress_sum(gathered).astype(jnp.float32)
            if worker_comp.bidirectional:
                # "re-compressed pull" (server.cc re-compresses merged
                # data): the pull leg is quantized too
                p2, sst2 = server_comp.compress(y, sst)
                y = server_comp.decompress(p2).astype(jnp.float32)
            else:
                sst2 = sst
            out = y.astype(flat.dtype)
            w_out = jax.tree.leaves(jax.tree.map(lambda s: s[None], wst2))
            return tuple([out] + w_out + jax.tree.leaves(sst2))

        in_specs = tuple([P(axes), P()] + [P(axes)] * nw + [P()] * ns)
        out_specs = tuple([P()] + [P(axes)] * nw + [P()] * ns)
        return jax.jit(jax.shard_map(
            body, mesh=comm.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))

    # Keyed by config, not object identity: same-config chunks (e.g. N
    # equal-shaped layers, or equal-length chunks of one tensor) share
    # one compiled program.  n_flat rides the key because the in-graph
    # slice is over the full staged row.
    return _cached(comm, _fused_key(n_flat, worker_comp, server_comp),
                   build)


def _fused_key(n_flat: int, worker_comp: Compressor,
               server_comp: Compressor) -> tuple:
    return ("compressed", int(n_flat), worker_comp.cache_key(),
            server_comp.cache_key())


def fused_compressed_push_pull(comm: CommContext, flat, off_elems: int,
                               worker_comp: Compressor,
                               server_comp: Compressor,
                               worker_states, server_state) -> Tuple:
    """Reduce one compressed chunk of the staged flat tensor.

    ``flat``: the push's whole [R, n] rank-stacked array, staged to the
    mesh once (``collectives._as_stacked``); ``off_elems`` selects the
    chunk in-graph.  ``worker_states``: rank-stacked state pytree
    ([R, ...] leaves); ``server_state``: replicated pytree.  Returns
    (merged [ln] array, new worker_states, new server_state)."""
    if _fault.ENABLED:
        _fault.fire("dcn")
    w_leaves, wdef = jax.tree.flatten(worker_states)
    s_leaves, sdef = jax.tree.flatten(server_state)
    fn = _fused_fn(comm, worker_comp, server_comp, int(flat.shape[-1]),
                   wdef, sdef, len(w_leaves), len(s_leaves))
    offa = _cached_scalar(comm, int(off_elems), jnp.int32)
    outs = fn(flat, offa, *w_leaves, *s_leaves)
    nw = len(w_leaves)
    return (outs[0],
            jax.tree.unflatten(wdef, list(outs[1:1 + nw])),
            jax.tree.unflatten(sdef, list(outs[1 + nw:])))


def state_structs(comm: CommContext, worker_states, server_state):
    """ShapeDtypeStructs (sharding included) for a slot's state leaves —
    exactly the concrete layout :func:`fused_compressed_push_pull`
    passes, shared by the AOT warm and the engine's state staging so the
    two can never drift."""
    w_structs = [
        _struct(lf.shape, lf.dtype,
                comm.stacked_sharding(extra_dims=lf.ndim - 1))
        for lf in jax.tree.leaves(worker_states)]
    s_structs = [_struct(lf.shape, lf.dtype, comm.replicated_sharding())
                 for lf in jax.tree.leaves(server_state)]
    return w_structs, s_structs


def aot_warm_compressed_programs(comm: CommContext, *, n_flat: int,
                                 dtype_name: str, chunk_bounds,
                                 slots) -> int:
    """Pre-lower and compile the whole steady-state program family of one
    compressed tensor's pushes (ISSUE 11 tentpole): one fused program per
    distinct chunk codec (equal-length chunks share), plus the device
    scalars for every chunk offset.  Returns the number of executables
    AOT-compiled; the engine counts a failure as ``aot_compile_failed``
    and falls back to lazy jit exactly as before."""
    np_dtype = np.dtype(dtype_name)
    R = comm.num_ranks
    flat_struct = _struct((R, n_flat), np_dtype,
                          comm.stacked_sharding(extra_dims=1))
    off_struct = _struct((), jnp.int32, comm.replicated_sharding())
    compiled = 0
    warmed = set()
    for (off, _ln), slot in zip(chunk_bounds, slots):
        _cached_scalar(comm, int(off), jnp.int32)
        key = _fused_key(n_flat, slot.worker, slot.server)
        if key in warmed:
            continue
        warmed.add(key)
        if getattr(comm.jit_cache.get(key), "_bps_aot", False):
            # an earlier declare of an equal-config tensor already
            # swapped in the executable — counting it again would log
            # an AOT compile that never happened
            continue
        w_leaves, wdef = jax.tree.flatten(slot.wstates)
        s_leaves, sdef = jax.tree.flatten(slot.sstate)
        # build (or fetch) the lazy wrapper, then swap in the executable
        _fused_fn(comm, slot.worker, slot.server, n_flat, wdef, sdef,
                  len(w_leaves), len(s_leaves))
        w_structs, s_structs = state_structs(comm, slot.wstates,
                                             slot.sstate)
        compiled += aot_compile(
            comm, key, [flat_struct, off_struct] + w_structs + s_structs)
    return compiled
