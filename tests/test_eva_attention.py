"""``ops/eva_attention.py`` — one softmax over a row's own window and the
earlier windows' chunk summaries, composed from the flash kernels' (out,
lse) — against the plain reference's ONE masked softmax over the
concatenated keys (``tests/evabyte_reference.py`` ``eva_one_head``): o and
the gradients of q, k, v, mu, phi, the kernels in the interpreter; wrong
EVAs that have to FAIL the comparison; the refusals; the gauges."""

import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from . import evabyte_reference as reference

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tests"))
import gradcheck_evabyte as gradcheck  # noqa: E402

eva = importlib.import_module("byteps_tpu.ops.eva_attention")

WINDOW, CHUNK, HEADS, D = 64, 8, 2, 16
# float32 program against float32 reference: o and every gradient agree to
# ~2e-6 of the largest entry (sums in another order); the mildest wrong EVA
# (a chunk's plain mean for its learned pooling) reads 4e-2
ATOL_SHARE = 2e-5


def inputs(windows, seed=0, batch=1):
    t = windows * WINDOW
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, g = (jax.random.normal(keys[i], (batch, t, HEADS, D))
                  for i in (0, 1, 2, 5))
    mu, phi = (jax.random.normal(keys[i], (HEADS, D)) for i in (3, 4))
    return (q, k, v, mu, phi), g


def plain(q, k, v, mu, phi):
    """The reference, a (sequence, head) at a time."""
    one = functools.partial(reference.eva_one_head, window=WINDOW,
                            chunk=CHUNK)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([jnp.stack(
            [one(q[b, :, h], k[b, :, h], v[b, :, h], mu[h], phi[h])
             for h in range(q.shape[2])], 1) for b in range(q.shape[0])])


def side(fn, args, g):
    """o and the five gradients of ``sum(o * g)``."""
    o, vjp = jax.vjp(fn, *args)
    return [np.asarray(x) for x in (o, *vjp(g))]


def worst_share(got, want):
    """The largest deviation of any of the six arrays, as a share of that
    array's largest entry."""
    return max(float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))
               for a, b in zip(got, want))


@functools.lru_cache(maxsize=None)
def wanted(windows, batch=1):
    args, g = inputs(windows, batch=batch)
    return side(plain, args, g)


@pytest.mark.parametrize("windows,batch,blocks", [
    (4, 2, dict(block_q=64, block_k=32, summary_block_k=8)),
    (6, 1, dict(block_q=32, block_k=64, summary_block_k=32)),  # a key tail
    (1, 1, dict(block_q=64, block_k=64))])                     # no summaries
def test_eva_attention_matches_the_one_softmax(windows, batch, blocks):
    args, g = inputs(windows, batch=batch)
    got = side(lambda *a: eva.eva_attention(
        *a, window=WINDOW, chunk=CHUNK, interpret=True, **blocks), args, g)
    assert worst_share(got, wanted(windows, batch)) < ATOL_SHARE
    if windows == 1:
        assert not got[4].any() and not got[5].any()    # mu, phi unreached


@pytest.mark.parametrize("what", gradcheck.BREAKS[:-1])
def test_a_wrong_eva_fails(what):
    """Each wrong EVA of ``gradcheck_evabyte.py`` (all but the one that
    needs the unrotated keys: the model's tests read that) is told from the
    program's: ``None`` — the same text with the right sets — agrees."""
    args, g = inputs(4)
    want = wanted(4)
    right = side(functools.partial(gradcheck.wrong_eva(None), window=WINDOW,
                                   chunk=CHUNK), args, g)
    assert worst_share(right, want) < ATOL_SHARE
    wrong = side(functools.partial(gradcheck.wrong_eva(what), window=WINDOW,
                                   chunk=CHUNK), args, g)
    assert worst_share(wrong[:1], want[:1]) > 1000 * ATOL_SHARE   # o alone


@pytest.mark.parametrize("what", ["bf16_pool", "bf16_merge"])
def test_a_pooling_or_a_merge_in_bfloat16_fails(what):
    """The summaries, or the fold of the two key sets, computed in
    bfloat16 inside the PROGRAM (``gradcheck_evabyte.py``'s control, the
    one the chip's limits are read against) read 100 times the float32
    program's deviation."""
    args, g = inputs(4)
    with gradcheck.low_precision(**gradcheck.CONTROLS[what]):
        got = side(lambda *a: eva.eva_attention(
            *a, window=WINDOW, chunk=CHUNK, interpret=True), args, g)
    assert worst_share(got[:1], wanted(4)[:1]) > 100 * ATOL_SHARE


def test_refusals():
    (q, k, v, mu, phi), _ = inputs(2)
    call = functools.partial(eva.eva_attention, interpret=True)
    with pytest.raises(ValueError, match="whole windows"):
        call(q, k, v, mu, phi, window=48, chunk=8)          # T % window
    with pytest.raises(ValueError, match="whole chunks"):
        call(q, k, v, mu, phi, window=64, chunk=24)         # window % chunk
    with pytest.raises(ValueError, match="no grouped heads"):
        call(q, k[:, :, :1], v[:, :, :1], mu, phi, window=64, chunk=8)
    with pytest.raises(ValueError, match="a vector a head"):
        call(q, k, v, mu[:1], phi, window=64, chunk=8)
    with pytest.raises(ValueError, match="whole sub-blocks"):
        call(q, k, v, mu, phi, window=64, chunk=8, block_q=48)


def test_gauges_are_the_schedule_s():
    import byteps_tpu as bps
    (q, k, v, mu, phi), _ = inputs(4)
    blocks = dict(block_q=32, block_k=32, summary_block_k=8)
    jax.eval_shape(functools.partial(
        eva.eva_attention, window=WINDOW, chunk=CHUNK, interpret=True,
        **blocks), q, k, v, mu, phi)
    sched = eva.eva_schedule(4 * WINDOW, WINDOW, CHUNK, **blocks)
    # a window of 2 x 2 sub-blocks visits 3; the staircase 2 x (1 + 2 + 3)
    # of 8 x 4 step-wide sub-blocks
    assert sched["local"] == {"visited": 12, "total": 16, "needed": 12}
    assert sched["summary"] == {"visited": 12, "total": 32, "needed": 12}
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["eva.visited_block_share"] == 24 / 48
    assert gauges["eva.summary_keys"] == 32.0
    assert gauges["eva.saved_lse_bytes"] == 1 * HEADS * 256 * 4
    # the cell: four windows of 2 048, sub-blocks of 512
    cell = eva.eva_schedule(8192, 2048, 16)
    assert (cell["visited"], cell["total"]) == (4 * 10 + 12, 4 * 16 + 16)
