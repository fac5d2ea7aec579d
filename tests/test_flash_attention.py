"""Flash-attention Pallas kernels vs exact attention (ops/flash_attention.py).

Forward and gradients are pinned against parallel/sequence.py
full_attention — the same oracle the ring/Ulysses sequence-parallel tests
use — in interpret mode (the identical kernel code runs compiled by
Mosaic on a real TPU backend; bench.py re-validates there).
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .jaxpr_count import kernel_equations
from byteps_tpu.ops.flash_attention import block_schedule, flash_attention
from byteps_tpu.parallel import full_attention

# ``byteps_tpu.ops.flash_attention`` the attribute is the function
_fa = importlib.import_module("byteps_tpu.ops.flash_attention")


def _rand(shape, dtype, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32
                             ).astype(dtype)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [128, 256])
def test_forward_matches_exact(causal, t):
    b, h, d = 2, 4, 64
    q = _rand((b, t, h, d), jnp.float32, 0)
    k = _rand((b, t, h, d), jnp.float32, 1)
    v = _rand((b, t, h, d), jnp.float32, 2)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_forward_ragged_shapes():
    """T not a block multiple, D not a lane multiple: padding is masked."""
    b, t, h, d = 2, 100, 3, 48
    q = _rand((b, t, h, d), jnp.float32, 3)
    k = _rand((b, t, h, d), jnp.float32, 4)
    v = _rand((b, t, h, d), jnp.float32, 5)
    for causal in (False, True):
        got = flash_attention(q, k, v, causal=causal, interpret=True)
        want = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_decode_alignment():
    """Tq < Tk with causal: q rows cover the LAST Tq key positions."""
    b, h, d = 1, 2, 64
    q = _rand((b, 64, h, d), jnp.float32, 6)
    k = _rand((b, 256, h, d), jnp.float32, 7)
    v = _rand((b, 256, h, d), jnp.float32, 8)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_causal_rejects_tq_gt_tk():
    """causal=True with Tq > Tk is rejected: fully-masked early rows would
    produce garbage forward values and exploding backward p = exp(s - lse)
    (round-2 advisor finding)."""
    b, h, d = 1, 2, 64
    q = _rand((b, 256, h, d), jnp.float32, 6)
    k = _rand((b, 64, h, d), jnp.float32, 7)
    v = _rand((b, 64, h, d), jnp.float32, 8)
    with pytest.raises(ValueError, match="Tq <= Tk"):
        flash_attention(q, k, v, causal=True, interpret=True)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_exact(causal):
    b, t, h, d = 2, 128, 2, 64
    q = _rand((b, t, h, d), jnp.float32, 9)
    k = _rand((b, t, h, d), jnp.float32, 10)
    v = _rand((b, t, h, d), jnp.float32, 11)
    # nontrivial downstream cotangent
    w = _rand((b, t, h, d), jnp.float32, 12)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) * w)

    def loss_exact(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal) * w)

    g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_exact, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


def test_gradients_ragged():
    b, t, h, d = 1, 72, 2, 32
    q = _rand((b, t, h, d), jnp.float32, 13)
    k = _rand((b, t, h, d), jnp.float32, 14)
    v = _rand((b, t, h, d), jnp.float32, 15)

    def loss(f):
        return lambda q, k, v: jnp.sum(
            f(q, k, v) * (1.0 + jnp.arange(d, dtype=jnp.float32)))

    g_got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss(lambda q, k, v: full_attention(
        q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


def test_bf16_forward():
    b, t, h, d = 2, 128, 2, 64
    q = _rand((b, t, h, d), jnp.bfloat16, 16)
    k = _rand((b, t, h, d), jnp.bfloat16, 17)
    v = _rand((b, t, h, d), jnp.bfloat16, 18)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


# (Tq, Tk, block_q, block_k): every case spans >= 3 key sub-blocks, so the
# kernels' inner loops run their unmasked range, their masked range and
# their skipped tail
_SCHEDULE_CASES = {
    "causal_square": (96, 96, 32, 32),
    "causal_square_wide_q": (128, 128, 64, 32),
    "causal_square_wide_k": (128, 128, 32, 64),
    "decode_q_off_64": (32, 96, 16, 32),
    "decode_q_off_96": (40, 136, 16, 32),
    "decode_q_off_128": (72, 200, 24, 40),
    "ragged_kv_len": (100, 100, 32, 32),
}


@pytest.fixture(params=["resident", "spans"])
def form(request, monkeypatch):
    """Both forms of the kernels at test sizes: the whole other side
    resident in VMEM (one grid step a head), and a grid over spans of two
    sub-blocks of either side, each looped inside (what a context too
    long for VMEM gets)."""
    if request.param == "spans":
        monkeypatch.setattr(_fa, "_SPAN_ROWS", 64)
        monkeypatch.setattr(_fa, "_RESIDENT_BYTES", 64 * 128 * 4)
    return request.param


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("case", sorted(_SCHEDULE_CASES))
def test_sub_block_schedule_matches_exact(case, causal, form):
    """Forward and all three gradients where the diagonal, the decode
    offset and the padded key tail cut through sub-blocks."""
    tq, tk, bq, bk = _SCHEDULE_CASES[case]
    b, h, d = 1, 2, 32
    q = _rand((b, tq, h, d), jnp.float32, 20)
    k = _rand((b, tk, h, d), jnp.float32, 21)
    v = _rand((b, tk, h, d), jnp.float32, 22)
    w = _rand((b, tq, h, d), jnp.float32, 23)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=bq,
                               block_k=bk, interpret=True)

    def exact(q, k, v):
        return full_attention(q, k, v, causal=causal)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(exact(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    g_got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: jnp.sum(exact(*a) * w), (0, 1, 2))(q, k, v)
    for a, b_ in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("q_off", [-64, 0, 32, 64])
def test_runtime_q_off(q_off, form):
    """The ring's contract: ``q_off`` is a runtime scalar that differs per
    device, so every loop bound comes from it inside the kernel; a block
    wholly in the future returns out 0 and lse ~ -1e30 (weight 0 in
    ring_flash's merge)."""
    bh, t, d, blk = 2, 64, 128, 16
    q = _rand((bh, t, d), jnp.float32, 30)
    k = _rand((bh, t, d), jnp.float32, 31)
    v = _rand((bh, t, d), jnp.float32, 32)
    fwd = jax.jit(lambda off: _fa._fwd(q, k, v, 0.1, True, off, t, blk, blk,
                                       True))
    out, lse = fwd(jnp.int32(q_off))
    if q_off < 0:
        assert np.all(np.asarray(out) == 0.0)
        assert np.all(np.asarray(lse) < -1e29)
        return
    s = jnp.einsum("bqd,bkd->bqk", q, k) * 0.1
    row = q_off + jnp.arange(t)[:, None]
    s = jnp.where(row >= jnp.arange(t)[None], s, -jnp.inf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(
        jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)),
        rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]), np.asarray(
        jax.scipy.special.logsumexp(s, -1)), rtol=2e-5, atol=2e-5)


def _count_by_mask(tq, tk, causal, q_off, bq, bk):
    """Sub-blocks of the padded square with at least one unmasked entry,
    counted from the mask itself."""
    tq_p, tk_p = -(-tq // bq) * bq, -(-tk // bk) * bk
    row = np.arange(tq_p)[:, None]
    col = np.arange(tk_p)[None, :]
    live = (row < tq) & (col < tk)
    if causal:
        live &= col <= q_off + row
    blocks = live.reshape(tq_p // bq, bq, tk_p // bk, bk).any(axis=(1, 3))
    return int(blocks.sum()), blocks.size


@pytest.mark.parametrize("tq,tk,q_off", [
    (1024, 1024, 0),      # gpt2_medium.fused_1c
    (4096, 4096, 0),      # olmoe_1b_7b.fused_1c
    (1000, 1000, 0), (256, 1024, 768), (40, 136, 96)])
def test_block_schedule_against_the_mask(tq, tk, q_off):
    for causal in (True, False):
        got = block_schedule(tq, tk, causal, q_off)
        needed, total = _count_by_mask(
            tq, tk, causal, q_off,
            *_fa._blocks(tq, tk, _fa._SUB, _fa._SUB)[:2])
        assert (got["needed"], got["total"]) == (needed, total)
        assert got["needed"] <= got["visited"] <= got["total"]
        if not causal:
            assert got["visited"] == got["total"]
    if tq == tk:
        share = block_schedule(tq, tk, True)
        assert share["visited"] / share["total"] <= 0.75
    assert block_schedule(1024, 1024, True) == {
        "visited": 3, "total": 4, "needed": 3}
    assert block_schedule(4096, 4096, True) == {
        "visited": 36, "total": 64, "needed": 36}
    # the parent's grid at this shape: two (512, 1024) steps, both live
    assert block_schedule(1024, 1024, True, block_q=512, block_k=1024) == {
        "visited": 2, "total": 2, "needed": 2}


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("tq,tk,q_off,bq,bk", [
    (1024, 1024, 0, 512, 512),       # gpt2_medium.fused_1c
    (4096, 4096, 0, 512, 512),       # olmoe_1b_7b.fused_1c
    (1000, 1000, 0, 512, 512),       # ragged: a key tail inside the last
    (200, 520, 320, 64, 128),        # decode-aligned, a whole tail block
    (4096, 4096, 4096, 512, 512),    # a ring block wholly in the past
    (4096, 4096, -4096, 512, 512),   # ... and wholly in the future
    (256, 384, 64, 32, 64)])
def test_dkv_mirror_visits_the_counted_set(tq, tk, q_off, bq, bk, causal):
    """``block_schedule`` counts the sub-blocks by q rows (``_live_keys``:
    the forward and dQ loops); dK/dV walks them by key columns
    (``_live_queries``).  The two rules leave the same set, and it holds
    every sub-block the mask leaves a score in."""
    bq, bk, tq_p, tk_p = _fa._blocks(tq, tk, bq, bk)
    nq, nk = tq_p // bq, tk_p // bk
    by_rows = {(i, j) for i in range(nq) for j in range(
        *_fa._live_keys(q_off + i * bq, bq, 0, nk, bk, tk, causal))}
    by_cols = {(i, j) for j in range(nk) for i in range(
        *_fa._live_queries(j * bk, bk, q_off, nq, bq, _fa._tail(tk, tk_p),
                           causal))}
    assert by_rows == by_cols
    got = block_schedule(tq, tk, causal, q_off, block_q=bq, block_k=bk)
    assert got["visited"] == len(by_rows) and got["total"] == nq * nk
    row = q_off + np.arange(tq_p)[:, None]
    col = np.arange(tk_p)[None, :]
    live = (col < tk) & ((col <= row) | (not causal))
    live = live.reshape(nq, bq, nk, bk).any(axis=(1, 3))
    assert all((i, j) in by_rows for i, j in zip(*np.nonzero(live)))


# ------------------------------------------------------ sliding window

def _banded(q, k, v, window, q_off=None):
    """Exact attention [B, T, H, D] under HF's sliding-window convention:
    row i (global: ``q_off`` + its index) attends keys j with ``i - window
    < j <= i``: itself and the ``window - 1`` before it."""
    tq, tk = q.shape[1], k.shape[1]
    i = (tk - tq if q_off is None else q_off) + jnp.arange(tq)[:, None]
    j = jnp.arange(tk)[None, :]
    keep = (j <= i) & (i - j < window)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", jnp.where(keep, p, 0.0), v)


@pytest.mark.parametrize("window", [7, 128, 1024])
@pytest.mark.parametrize("case", sorted(_SCHEDULE_CASES))
def test_window_matches_exact_banded_attention(case, window, form):
    """Forward and all three gradients over the schedule's shapes, where
    the window's edge cuts through sub-blocks (7), spans several (128
    covers some shapes whole) and covers every shape (1024 = causal), in
    both forms of the kernels (``form``: the long form's k/v spans and
    its two-kernel backward forced at test sizes)."""
    tq, tk, bq, bk = _SCHEDULE_CASES[case]
    b, h, d = 1, 2, 32
    q = _rand((b, tq, h, d), jnp.float32, 20)
    k = _rand((b, tk, h, d), jnp.float32, 21)
    v = _rand((b, tk, h, d), jnp.float32, 22)
    w = _rand((b, tq, h, d), jnp.float32, 23)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                               interpret=True, window=window)

    def exact(q, k, v):
        return _banded(q, k, v, window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(exact(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    g_got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: jnp.sum(exact(*a) * w), (0, 1, 2))(q, k, v)
    for a, b_ in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)
    if window >= tk:                         # the band is the triangle
        np.testing.assert_allclose(
            np.asarray(flash(q, k, v)),
            np.asarray(full_attention(q, k, v, causal=True)),
            rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 7, 24, 128])
@pytest.mark.parametrize("q_off", [-64, 0, 32, 64, 96])
def test_runtime_q_off_with_a_window(q_off, window, form):
    """The ring's contract holds under a window: ``q_off`` is a runtime
    scalar, forward and backward bounds come from it inside the kernels.
    Rows whose window has left the block (``q_off`` past it, or a block
    wholly in the future) return out 0 and lse < -1e29 — weight 0 in a
    merge — and take no gradient."""
    bh, t, d, blk = 2, 64, 128, 16
    q, k, v, do = (_rand((bh, t, d), jnp.float32, s) for s in (30, 31, 32,
                                                               33))

    @jax.jit
    def run(off):
        out, lse = _fa._fwd(q, k, v, 0.1, True, off, t, blk, blk, True,
                            window)
        grads = _fa._bwd_impl(q, k, v, do, lse, _fa._delta(do, out), 0.1,
                              True, off, t, blk, blk, True, window)
        return out, lse, grads

    out, lse, grads = run(jnp.int32(q_off))
    row = q_off + jnp.arange(t)[:, None]
    col = jnp.arange(t)[None]
    keep = col <= row
    if window is not None:
        keep &= row - col < window
    dead = ~np.asarray(keep.any(axis=1))

    def exact(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k) * 0.1
        p = jax.nn.softmax(jnp.where(keep, s, -1e30), -1)
        return jnp.einsum("bqk,bkd->bqd", jnp.where(keep, p, 0.0), v)

    assert np.all(np.asarray(out)[:, dead] == 0.0)
    assert np.all(np.asarray(lse)[:, dead] < -1e29)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exact(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    want = jax.grad(lambda *a: jnp.sum(exact(*a) * do), (0, 1, 2))(q, k, v)
    for a, b_ in zip(grads, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)
    if not dead.all():
        s = jnp.where(keep, jnp.einsum("bqd,bkd->bqk", q, k) * 0.1, -jnp.inf)
        np.testing.assert_allclose(
            np.asarray(lse[:, ~dead, 0]),
            np.asarray(jax.scipy.special.logsumexp(s, -1))[:, ~dead],
            rtol=2e-5, atol=2e-5)


def _live_by_mask(tq, tk, q_off, bq, bk, window):
    """[nq, nk] bool: sub-blocks of the padded square that hold at least
    one unmasked entry, counted from the band mask itself."""
    tq_p, tk_p = -(-tq // bq) * bq, -(-tk // bk) * bk
    row = q_off + np.arange(tq_p)[:, None]
    col = np.arange(tk_p)[None, :]
    live = (np.arange(tq_p)[:, None] < tq) & (col < tk) & (col <= row)
    if window is not None:
        live &= row - col < window
    return live.reshape(tq_p // bq, bq, tk_p // bk, bk).any(axis=(1, 3))


@pytest.mark.parametrize("window", [None, 1, 7, 128, 512, 1024, 1025])
@pytest.mark.parametrize("tq,tk,q_off,bq,bk", [
    (8192, 8192, 0, 512, 512),       # mellum2_12b.fused_1c
    (1024, 1024, 0, 512, 512),       # gpt2_medium.fused_1c
    (4096, 4096, 0, 512, 512),       # olmoe_1b_7b.fused_1c
    (1000, 1000, 0, 512, 512),       # ragged: a key tail inside the last
    (200, 520, 320, 64, 128),        # decode-aligned, a whole tail block
    (4096, 4096, 4096, 512, 512),    # a ring block wholly in the past
    (4096, 4096, -4096, 512, 512),   # ... and wholly in the future
    (256, 384, 64, 32, 64)])
def test_window_schedule_against_the_mask(tq, tk, q_off, bq, bk, window):
    """``block_schedule`` with a window equals a brute-force count of the
    sub-blocks that hold an unmasked entry; ``_live_keys`` (by q rows) and
    ``_live_queries`` (by key columns) leave the same set, and it holds
    every such sub-block."""
    bq, bk, tq_p, tk_p = _fa._blocks(tq, tk, bq, bk)
    nq, nk = tq_p // bq, tk_p // bk
    by_rows = {(i, j) for i in range(nq) for j in range(
        *_fa._live_keys(q_off + i * bq, bq, 0, nk, bk, tk, True, window))}
    by_cols = {(i, j) for j in range(nk) for i in range(
        *_fa._live_queries(j * bk, bk, q_off, nq, bq, _fa._tail(tk, tk_p),
                           True, window))}
    assert by_rows == by_cols
    got = block_schedule(tq, tk, True, q_off, block_q=bq, block_k=bk,
                         window=window)
    live = _live_by_mask(tq, tk, q_off, bq, bk, window)
    assert got == {"visited": len(by_rows), "total": nq * nk,
                   "needed": int(live.sum())}
    assert all((i, j) in by_rows for i, j in zip(*np.nonzero(live)))
    # a sub-block of 512 > window - 1 keys can be visited without need
    # only through the padded rows of a ragged q side
    if tq == tq_p and window is not None:
        assert got["visited"] == got["needed"]


def test_window_schedule_at_the_mellum_cell():
    assert block_schedule(8192, 8192, True, window=1024) == {
        "visited": 45, "total": 256, "needed": 45}
    assert block_schedule(8192, 8192, True) == {
        "visited": 136, "total": 256, "needed": 136}
    # the whole band in one span: a window as long as the context is causal
    assert block_schedule(8192, 8192, True, window=8192) == block_schedule(
        8192, 8192, True)


def test_window_is_causal_and_at_least_one_key():
    q = _rand((1, 32, 1, 32), jnp.float32, 50)
    with pytest.raises(ValueError, match="causal=True"):
        flash_attention(q, q, q, causal=False, window=8, interpret=True)
    with pytest.raises(ValueError, match="window >= 1"):
        flash_attention(q, q, q, causal=True, window=0, interpret=True)
    # window 1: every row attends itself alone
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, q, q, causal=True, window=1,
                                   interpret=True)), np.asarray(q),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("t,kv_len,want", [
    (1024, 1024, ([99], [86])),          # gpt2_medium: one-kernel backward
    (4096, 4096, ([99], [86])),          # olmoe
    (8192, 8192, ([99], [71, 71])),      # the long form: dK/dV, dQ
    (1024, 1000, ([102], [96]))])        # a padded key tail
def test_window_none_leaves_the_kernels_jaxprs_as_they_were(t, kv_len, want):
    """A kernel's size in jaxpr equations is set-up time (~1 ms an
    equation a layer on the chip host, PERF.md section 6 PR 28 (5)), and
    ``gpt2_medium.fused_1c`` has 24 layers of them: with ``window=None``
    each kernel is the parent commit's (PR 28), equation for equation.
    The numbers are that commit's; the windowed specialisation adds its
    few equations (two compares a mask, one bound a loop) on top."""
    q = jnp.zeros((1, t, 128), jnp.bfloat16)
    lse = jnp.zeros((1, t, 128), jnp.float32)

    def fwd(window):
        return kernel_equations(
            lambda q: _fa._fwd(q, q, q, 0.1, True, 0, kv_len, 512, 512,
                               False, window), q)

    def bwd(window):
        return kernel_equations(
            lambda q, lse: _fa._bwd_impl(q, q, q, q, lse, lse, 0.1, True, 0,
                                         kv_len, 512, 512, False, window),
            q, lse)

    assert (fwd(None), bwd(None)) == want
    windowed = fwd(1024) + bwd(1024)
    assert all(0 < w - n <= 16 for w, n in zip(windowed, want[0] + want[1]))


def test_visited_block_share_gauge():
    import byteps_tpu as bps
    q = _rand((1, 96, 1, 32), jnp.float32, 40)

    @jax.jit
    def f(q):
        return flash_attention(q, q, q, causal=True, block_q=32, block_k=32,
                               interpret=True)

    f(q)
    assert bps.metrics_snapshot()["gauges"][
        "flash.visited_block_share"] == pytest.approx(6 / 9)
    flash_attention(q, q, q, causal=False, block_q=32, block_k=32,
                    interpret=True)
    assert bps.metrics_snapshot()["gauges"][
        "flash.visited_block_share"] == 1.0
    # a windowed call has a gauge of its own: one step holds both kinds
    flash_attention(q, q, q, causal=True, block_q=32, block_k=32,
                    interpret=True, window=20)
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["flash.visited_block_share.window"] == pytest.approx(5 / 9)
    assert gauges["flash.visited_block_share"] == 1.0


def test_long_context_flash_mode():
    """attention='flash' trains the GPT long-context step on an sp=1 mesh
    and matches the exact-attention trajectory; sp>1 is rejected."""
    import optax
    from byteps_tpu.models.gpt import GPT, gpt_tiny
    from byteps_tpu.parallel import (make_dp_sp_train_step, make_sp_mesh,
                                     shard_lm_batch, synthetic_lm_batch)
    from byteps_tpu.parallel.long_context import replicate

    cfg = gpt_tiny()
    mesh = make_sp_mesh(jax.devices()[:8], n_sp=1)
    batch = synthetic_lm_batch(jax.random.PRNGKey(0), cfg, batch=8,
                               seq_len=32)
    params = GPT(cfg).init(jax.random.PRNGKey(1), batch["input_ids"][:1])
    tx = optax.sgd(0.1)

    losses = {}
    for kind in ("flash", "ring"):
        step = make_dp_sp_train_step(mesh, cfg, tx, attention=kind,
                                     donate=False)
        p = replicate(mesh, params)
        o = replicate(mesh, tx.init(params))
        ls = []
        for _ in range(3):
            p, o, loss = step(p, o, shard_lm_batch(mesh, batch))
            ls.append(float(loss))
        losses[kind] = ls
    # gpt_tiny computes in bf16: the two softmax decompositions agree to
    # bf16 resolution, not f32
    np.testing.assert_allclose(losses["flash"], losses["ring"],
                               rtol=5e-3, atol=5e-3)

    mesh2 = make_sp_mesh(jax.devices()[:8], n_sp=2)
    with pytest.raises(ValueError, match="needs sp=1"):
        make_dp_sp_train_step(mesh2, cfg, tx, attention="flash")


# ------------------------------------------ a value width of its own (PR 43)

def _exact_two_widths(q, k, v):
    """Causal softmax attention whose q.k and v widths differ."""
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None], s,
                  -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("d,dv", [(192, 128), (24, 16), (16, 40)])
def test_a_value_width_of_its_own_matches_exact(d, dv, form):
    """``v`` narrower (a latent whose q.k width is 192 and v width 128) or
    wider than ``q``: the output, dO and dV keep v's width, q, k, dQ and dK
    theirs; value and all three gradients, causal, in both forms."""
    b, t, h = 1, 256, 2
    q = _rand((b, t, h, d), jnp.float32, 50)
    k = _rand((b, t, h, d), jnp.float32, 51)
    v = _rand((b, t, h, dv), jnp.float32, 52)
    w = _rand((b, t, h, dv), jnp.float32, 53)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                               interpret=True)

    got = flash(q, k, v)
    assert got.shape == (b, t, h, dv)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_exact_two_widths(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    g_got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: jnp.sum(_exact_two_widths(*a) * w),
                      (0, 1, 2))(q, k, v)
    for a, b_ in zip(g_got, g_want):
        assert a.shape == b_.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


def test_two_widths_pad_each_to_its_own_lanes():
    """192 / 128: q and k go in at 256 lanes, v and the output at 128 — not
    both at 256 (a third of the kernels' ``P V`` work would be zeros)."""
    q = jnp.zeros((1, 512, 2, 192), jnp.bfloat16)
    v = jnp.zeros((1, 512, 2, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True, interpret=False)
        .astype(jnp.float32)), (0, 1, 2)))(q, q, v))
    assert "bf16[2,512,256]" in text and "bf16[2,512,128]" in text
    assert "f32[512,256]" in text            # dQ / dK accumulators
    assert text.count("bf16[2,512,192]") > 0


@pytest.mark.parametrize("shape,dtype,causal,want", [
    ((1, 256, 2, 64), jnp.float32, True, 209),
    ((2, 1024, 4, 128), jnp.bfloat16, True, 233),
    ((1, 8192, 2, 256), jnp.bfloat16, True, 290),
    ((1, 100, 3, 48), jnp.float32, False, 206)])
def test_equal_widths_trace_to_the_programs_they_were(shape, dtype, causal,
                                                      want):
    """A call whose q.k and v widths are equal — every call the six cells
    make — traces, forward and backward, to the parent commit's program,
    equation for equation (the numbers are that commit's, PR 42): the
    second width is shapes in block specs, nothing in the kernels."""
    from .jaxpr_count import equations
    q = jnp.zeros(shape, dtype)
    program = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=causal, interpret=False)
        .astype(jnp.float32)), (0, 1, 2)))(q, q, q)
    assert equations(program.jaxpr) == want


# ------------------------------------------------------------ staircase

def _stair_live(tq, tk, bq, bk, stair):
    """Sub-blocks of the padded rectangle with at least one score the
    staircase leaves — column c < cols * (row // rows) — from the mask."""
    bq, bk, tq_p, tk_p = _fa._blocks(tq, tk, bq, bk)
    rows, cols = stair
    row = np.arange(tq_p)[:, None]
    col = np.arange(tk_p)[None, :]
    live = (row < tq) & (col < tk) & (col < cols * (row // rows))
    return live.reshape(tq_p // bq, bq, tk_p // bk, bk).any(axis=(1, 3))


STAIRS = [
    # tq, tk, block_q, block_k, (rows, cols)
    (8192, 512, 512, 512, (2048, 128)),     # evabyte_6b5.fused_1c
    (8192, 512, 512, 128, (2048, 128)),     # ... a step-wide key sub-block
    (16384, 1024, 512, 512, (2048, 128)),   # the ladder's rung (a)
    (12288, 768, 512, 512, (2048, 128)),    # rung (b): a padded key tail
    (256, 32, 64, 8, (64, 8)),              # the CPU tests' size
    (384, 48, 32, 16, (64, 8)),
    (384, 48, 64, 48, (64, 8)),
    (256, 40, 32, 16, (128, 20))]           # steps no multiple of a sub-block


@pytest.mark.parametrize("tq,tk,bq,bk,stair", STAIRS)
def test_stair_schedule_against_the_mask(tq, tk, bq, bk, stair):
    """``block_schedule`` under a staircase against a brute-force count
    from the mask, and the dK/dV mirror (``_live_queries``) against the
    rows' rule (``_live_keys``): one set, which holds every sub-block the
    mask leaves a score in; ``needed == visited`` where a step's edge is a
    sub-block's."""
    live = _stair_live(tq, tk, bq, bk, stair)
    got = block_schedule(tq, tk, False, block_q=bq, block_k=bk, stair=stair)
    assert (got["needed"], got["total"]) == (int(live.sum()), live.size)
    bq, bk, tq_p, tk_p = _fa._blocks(tq, tk, bq, bk)
    nq, nk = tq_p // bq, tk_p // bk
    by_rows = {(i, j) for i in range(nq) for j in range(
        *_fa._live_keys(i * bq, bq, 0, nk, bk, tk, False, None, stair))}
    by_cols = {(i, j) for j in range(nk) for i in range(
        *_fa._live_queries(j * bk, bk, 0, nq, bq, _fa._tail(tk, tk_p),
                           False, None, stair))}
    assert by_rows == by_cols and got["visited"] == len(by_rows)
    assert set(zip(*np.nonzero(live))) == by_rows    # nothing dead visited
    # rows on the first step see nothing: zero trips
    assert all(_fa._live_keys(i * bq, bq, 0, nk, bk, tk, False, None,
                              stair)[1] == 0
               for i in range(stair[0] // bq))


def test_stair_schedule_at_the_evabyte_cell():
    """8 192 rows against 512 summaries, steps of 2 048 x 128: with the
    default 512-wide key sub-block the 12 row blocks past window 0 each
    visit the one key sub-block (12 of 16; up to 3/4 of it masked), with a
    step-wide one (128) they visit 4 x (1 + 2 + 3) = 24 of 64."""
    assert block_schedule(8192, 512, False, stair=(2048, 128)) == {
        "visited": 12, "total": 16, "needed": 12}
    assert block_schedule(8192, 512, False, block_k=128,
                          stair=(2048, 128)) == {
        "visited": 24, "total": 64, "needed": 24}


def test_stair_is_whole_q_sub_blocks():
    with pytest.raises(ValueError, match="whole q sub-blocks"):
        block_schedule(256, 32, False, block_q=48, stair=(64, 8))
    q = jnp.zeros((1, 256, 128), jnp.float32)
    with pytest.raises(ValueError, match="whole q sub-blocks"):
        _fa._fwd(q, q[:, :32], q[:, :32], 1.0, False, 0, 32, 48, 32, True,
                 stair=(64, 8))


@pytest.mark.parametrize("causal,window,q_off", [
    (True, None, 0), (False, 64, 0), (False, None, 64),
    (False, None, jnp.zeros((), jnp.int32))],
    ids=["causal", "window", "q_off", "runtime_q_off"])
def test_stair_refuses_a_diagonal_a_band_and_a_row_offset(causal, window,
                                                          q_off):
    """``_live_queries`` under a staircase replaces the causal bound and
    ``_stair_limit`` counts rows from 0: a schedule that could disagree
    with ``_mask`` is refused, in the arithmetic and in both kernels."""
    q = jnp.zeros((1, 256, 128), jnp.float32)
    k = q[:, :32]
    with pytest.raises(ValueError, match="no diagonal, no band"):
        _fa._fwd(q, k, k, 1.0, causal, q_off, 32, 64, 32, True,
                 window=window, stair=(64, 8))
    with pytest.raises(ValueError, match="no diagonal, no band"):
        _fa._bwd_impl(q, k, k, q, q, q, 1.0, causal, q_off, 32, 64, 32,
                      True, window=window, stair=(64, 8))
    if isinstance(q_off, int):
        with pytest.raises(ValueError, match="no diagonal, no band"):
            block_schedule(256, 32, causal, q_off, block_q=64, block_k=32,
                           window=window, stair=(64, 8))


@pytest.mark.parametrize("tq,tk,bq,bk,stair", STAIRS[4:])
def test_stair_kernels_match_the_masked_softmax(tq, tk, bq, bk, stair, form):
    """``_fwd`` and ``_bwd_impl`` under a staircase against the plain
    masked softmax: out, lse (rows of the first step: out 0, lse -1e30)
    and dQ, dK, dV under the call's own lse."""
    d = 16
    q, do = (_rand((2, tq, d), jnp.float32, s) for s in (60, 63))
    k, v = (_rand((2, tk, d), jnp.float32, s) for s in (61, 62))
    bq, bk, tq_p, tk_p = _fa._blocks(tq, tk, bq, bk)
    rows, cols = stair

    def pad(x, t_p):
        return jnp.pad(x, ((0, 0), (0, t_p - x.shape[1]), (0, 128 - d)))

    args = (pad(q, tq_p), pad(k, tk_p), pad(v, tk_p))
    out, lse = _fa._fwd(*args, 0.25, False, 0, tk, bq, bk, True, stair=stair)
    seen = (np.arange(tk)[None, :]
            < cols * (np.arange(tq)[:, None] // rows))

    def plain(q, k, v):
        s = jnp.where(seen, jnp.einsum("bqd,bkd->bqk", q, k) * 0.25,
                      -jnp.inf)
        p = jnp.where(seen, jax.nn.softmax(s, -1), 0.0)  # first step: 0 / 0
        return jnp.einsum("bqk,bkd->bqd", p, v)

    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(plain, q, k, v)
        want_grads = vjp(do)
    np.testing.assert_allclose(np.asarray(out[:, :tq, :d]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(out[:, :rows]) == 0)
    assert np.all(np.asarray(lse[:, :rows]) < -1e29)
    got = _fa._bwd_impl(*args, pad(do, tq_p), lse,
                        _fa._delta(pad(do, tq_p), out), 0.25, False, 0, tk,
                        bq, bk, True, stair=stair)
    for g, w, t in zip(got, want_grads, (tq, tk, tk)):
        np.testing.assert_allclose(np.asarray(g[:, :t, :d]), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


def test_stair_none_leaves_the_kernels_jaxprs_as_they_were():
    """``stair=None`` is every kernel as it was (the pinned counts above
    are untouched); the staircase specialisation adds its few equations
    (one compare a mask, one bound a loop)."""
    q = jnp.zeros((1, 8192, 128), jnp.bfloat16)
    k = jnp.zeros((1, 512, 128), jnp.bfloat16)
    lse = jnp.zeros((1, 8192, 128), jnp.float32)

    def counts(stair):
        return (kernel_equations(
            lambda q, k: _fa._fwd(q, k, k, 0.1, False, 0, 512, 512, 512,
                                  False, stair=stair), q, k)
            + kernel_equations(
                lambda q, k, lse: _fa._bwd_impl(
                    q, k, k, q, lse, lse, 0.1, False, 0, 512, 512, 512,
                    False, stair=stair), q, k, lse))

    plain, stairs = counts(None), counts((2048, 128))
    assert len(plain) == len(stairs) == 3          # forward, dK/dV, dQ
    assert all(0 < s - p <= 16 for s, p in zip(stairs, plain))
