"""Tensor partitioning: split a flat tensor into independently scheduled chunks.

Reference behavior (operations.cc:140-180 PartitionTensor; global.cc:134-144
partition bound): every tensor larger than BYTEPS_PARTITION_BYTES is split
into byte-bounded chunks, each with its own 64-bit key, scheduled and routed
independently.  That is what enables pipelining (later chunks overlap earlier
ones) and load balance.

TPU adaptation: chunk boundaries are aligned to a multiple of 512 elements so
every chunk maps cleanly onto the (8, 128) f32 / (16, 128) bf16 vreg tiling
and reduce-scatter shard sizes stay tile-friendly after the engine pads to
the mesh size.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

# Chunk boundaries land on multiples of this many elements (8 sublanes * 128
# lanes * 0.5, i.e. one bf16 tile is 16*128; 512 divides into both tilings).
ALIGN_ELEMS = 512


def chunk_bounds(num_elems: int, itemsize: int, partition_bytes: int
                 ) -> List[Tuple[int, int]]:
    """Return [(offset_elems, length_elems)] covering [0, num_elems).

    Chunks are at most ``partition_bytes`` big; all but the last are aligned
    to ALIGN_ELEMS elements.  A tensor at or under the bound is one chunk
    (the common case — the default bound is 4 MB and most layers are smaller).
    """
    if num_elems <= 0:
        return [(0, 0)] if num_elems == 0 else []
    max_elems = max(1, partition_bytes // itemsize)
    if num_elems <= max_elems:
        return [(0, num_elems)]
    # Align the per-chunk element count down so boundaries stay tiled.
    if max_elems > ALIGN_ELEMS:
        max_elems -= max_elems % ALIGN_ELEMS
    bounds = []
    off = 0
    while off < num_elems:
        ln = min(max_elems, num_elems - off)
        bounds.append((off, ln))
        off += ln
    return bounds


def bucket_bounds(sigs, nbytes, cap_bytes: int) -> List[Tuple[int, int]]:
    """Carve a tree's leaves, in flattening order, into buckets: runs
    ``[(start, stop)]`` of CONSECUTIVE leaves of one dtype whose bytes sum
    to at most ``cap_bytes``, each pushed as one engine tensor.

    ``sigs[i]`` is leaf i's ``(shape, dtype_name)``, or ``None`` for a
    leaf no bucket can carry; ``nbytes[i]`` its size.  Such a leaf, and
    one at or over the cap, is in no run (it goes the per-tensor way).

    Greedy, with one exception: a bucket at half the cap or more closes
    early when the leaves that follow repeat its shapes one for one, so
    that the repeated layers of a model make identical buckets and share
    one pack and one unpack program (at most twice the buckets of the
    plain greedy cut).  A pure function of its arguments: no timing
    enters it, so every run and every process of a job carves the same
    buckets."""
    runs, i, n = [], 0, len(sigs)
    while i < n:
        if sigs[i] is None or nbytes[i] >= cap_bytes:
            i += 1
            continue
        dtype = sigs[i][1]
        j, size = i, 0
        while (j < n and sigs[j] is not None and sigs[j][1] == dtype
               and size + nbytes[j] <= cap_bytes):
            size += nbytes[j]
            j += 1
            k = j - i
            if (2 * size >= cap_bytes and j + k <= n
                    and all(sigs[i + t] == sigs[j + t] for t in range(k))):
                break
        runs.append((i, j))
        i = j
    return runs


def unit_bounds(chunk_nbytes, cap_bytes: int) -> List[Tuple[int, int]]:
    """Carve a tensor's chunks into dispatch units: runs ``[(start,
    stop)]`` of CONSECUTIVE chunks whose bytes sum to at most
    ``cap_bytes``, greedy from chunk 0, a chunk over the cap (or any
    chunk under ``cap_bytes <= 0``) a unit by itself.  A pure function
    of the chunk sizes and the cap: the engine's dispatcher launches one
    program per unit and the declare-time warm compiles one per unit,
    both from this list."""
    units, start, total = [], 0, 0
    for i, nbytes in enumerate(chunk_nbytes):
        if i > start and total + nbytes > cap_bytes:
            units.append((start, i))
            start, total = i, 0
        total += nbytes
    if len(chunk_nbytes) > start:
        units.append((start, len(chunk_nbytes)))
    return units


def num_chunks(num_elems: int, itemsize: int, partition_bytes: int) -> int:
    return len(chunk_bounds(num_elems, itemsize, partition_bytes))


def flatten_array(arr) -> np.ndarray:
    """View an array as flat 1-D without copying when possible."""
    return arr.reshape(-1)
