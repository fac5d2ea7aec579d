"""The held-experts layer's row passes (``parallel/expert.py``, PR 30) on
the CPU, through the Pallas interpreter: the spread into sorted order over
the live rows alone, against ``x[perm]``; its scaled form with the
row-wise dot, against the dense weighted arithmetic; both as transposes of
the token-order gather-sum (``jax.vjp``); the gate product's kernels; the
schedule against a brute-force count; the gauge.

Float32 inputs, so a moved row is compared EXACTLY and a weighted one to
float32 rounding of one product (rtol 1e-6): a wrong row, a wrong weight or
a row outside the range fails by orders of magnitude.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.parallel.expert import (_combine_rows, _dispatch_rows,
                                        _gather_sum_rows, _silu_gate_rows,
                                        _spread_rows, publish_moe_stats,
                                        row_schedule)

N, K, H, E, CHUNK = 48, 2, 32, 8, 16
M = N * K


def _pairs(held_pairs, held, seed=0):
    """[M] expert of each pair: ``held_pairs`` of them on the held experts
    (uniform over them), the rest on the others."""
    rng = np.random.default_rng(seed)
    start, count = held
    others = [e for e in range(E) if not start <= e < start + count]
    expert = rng.choice(others, size=M)
    where = rng.permutation(M)[:held_pairs]
    expert[where] = rng.integers(start, start + count, size=held_pairs)
    return expert


def _by_counts(counts):
    """[M] expert of each pair with exactly these per-expert counts."""
    expert = np.repeat(np.arange(E), counts)
    return np.random.default_rng(1).permutation(expert)


# name -> (expert of each pair, held)
CASES = {
    "first_quarter": (_pairs(24, (0, 2)), (0, 2)),
    "middle_quarter": (_pairs(24, (3, 2)), (3, 2)),
    "last_quarter": (_pairs(24, (6, 2)), (6, 2)),
    "none_live": (_pairs(0, (2, 2)), (2, 2)),
    "one_row": (_pairs(1, (4, 2)), (4, 2)),
    "all_rows": (_pairs(M, (2, 2)), (2, 2)),
    "an_empty_held_expert": (_by_counts([20, 10, 9, 0, 11, 16, 20, 10]),
                             (2, 3)),
    # lo = 16 on a chunk's edge, hi = 37 inside the third chunk
    "ends_inside_a_chunk": (_by_counts([16, 9, 12, 20, 10, 9, 10, 10]),
                            (1, 2)),
    # lo = 21 inside the second chunk, hi = 48 on an edge
    "starts_inside_a_chunk": (_by_counts([21, 17, 10, 12, 9, 9, 9, 9]),
                              (1, 2)),
}


def _routing(case):
    expert, held = CASES[case]
    counts = np.bincount(expert, minlength=E).astype(np.int32)
    order = np.argsort(expert, kind="stable")
    inverse = np.argsort(order)
    sched = row_schedule(counts, held, CHUNK)
    live = np.zeros(M, bool)
    live[int(sched["lo"]):int(sched["hi"])] = True
    return counts, order, inverse, held, live


def _traced(counts, held):
    return row_schedule(jnp.asarray(counts), held, CHUNK)


def _rows(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _weights(seed=5):
    return jax.nn.softmax(_rows((N, K), seed), -1)


@pytest.mark.parametrize("case", CASES)
def test_spread_is_the_gather_on_live_rows_and_exact_zero_elsewhere(case):
    counts, order, _, held, live = _routing(case)
    x = _rows((N, H), 1)

    @jax.jit
    def spread(x, order, counts):
        return _spread_rows(x, order // K, _traced(counts, held), CHUNK,
                            True)

    got = np.asarray(spread(x, jnp.asarray(order), jnp.asarray(counts)))
    want = np.asarray(jnp.repeat(x, K, axis=0))[order]      # the parent's
    np.testing.assert_array_equal(got[live], want[live])
    assert not got[~live].any()
    assert live.sum() == counts[held[0]:held[0] + held[1]].sum()


@pytest.mark.parametrize("case", CASES)
def test_scaled_spread_and_row_dot_match_the_dense_arithmetic(case):
    """The combine's backward: ``g_ys[r] = w(r) g[token(r)]`` and ``d[r] =
    <g[token(r)], ys[r]>`` on the live rows, zero on the others — what the
    parent computed over all ``N k`` rows in token order and gathered."""
    counts, order, _, held, live = _routing(case)
    g, ys, w = _rows((N, H), 2), _rows((M, H), 3), _weights()

    @jax.jit
    def spread(g, ys, w, order, counts):
        return _spread_rows(g, order // K, _traced(counts, held), CHUNK,
                            True, w.reshape(M)[order], dot=ys)

    g_ys, d = spread(g, ys, w, jnp.asarray(order), jnp.asarray(counts))
    pair_g = np.asarray(jnp.repeat(g, K, axis=0))[order]
    pair_w = np.asarray(w).reshape(M)[order]
    want_rows = np.where(live[:, None], pair_g * pair_w[:, None], 0)
    want_d = np.where(live, (pair_g * np.asarray(ys)).sum(-1), 0)
    np.testing.assert_allclose(g_ys, want_rows, rtol=1e-6, atol=0)
    np.testing.assert_allclose(d, want_d, rtol=1e-5, atol=1e-6)
    assert not np.asarray(g_ys)[~live].any() and not np.asarray(d)[~live].any()


@pytest.mark.parametrize("case", ["first_quarter", "middle_quarter",
                                  "last_quarter", "all_rows", "none_live",
                                  "starts_inside_a_chunk"])
def test_dispatch_and_combine_are_each_others_transposes(case):
    """``<spread(x), G> = <x, gather_sum(G)>`` and the same with weights,
    for ``G`` zero on the dead rows (as every grouped matmul leaves its
    results): the two primitives under their ``custom_vjp`` pair, forward
    against backward both ways round."""
    counts, order, inverse, held, live = _routing(case)
    order, inverse = jnp.asarray(order), jnp.asarray(inverse)
    sched = row_schedule(jnp.asarray(counts), held, CHUNK)
    x, w = _rows((N, H), 1), _weights()
    big = _rows((M, H), 4) * live[:, None]
    g = _rows((N, H), 6)

    token, scale = order // K, w.reshape(M)[order]
    xs, pull = jax.vjp(lambda x: _dispatch_rows(
        x, token, inverse, sched, K, CHUNK, True), x)
    # sums of ~1 500 products of unit normals: float32 noise ~1e-4
    np.testing.assert_allclose(jnp.vdot(xs, big), jnp.vdot(x, pull(big)[0]),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(pull(big)[0],
                               _gather_sum_rows(big, inverse, K), rtol=1e-6)

    y, pull = jax.vjp(lambda ys, w: _combine_rows(
        ys, w, scale, token, inverse, sched, K, CHUNK, True), big, w)
    g_ys, g_w = pull(g)
    np.testing.assert_allclose(jnp.vdot(y, g), jnp.vdot(big, g_ys),
                               rtol=1e-5, atol=1e-3)
    dense = jax.grad(lambda ys, w: jnp.vdot(_gather_sum_rows(
        ys, inverse, K, w), g), (0, 1))(big, w)
    np.testing.assert_allclose(g_ys, dense[0] * live[:, None], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(g_w, dense[1], rtol=1e-5, atol=1e-5)
    # the weighted spread IS the combine's transpose
    np.testing.assert_allclose(g_ys, _spread_rows(
        g, token, sched, CHUNK, True, scale, dot=big)[0], rtol=1e-6)


@pytest.mark.parametrize("case", ["first_quarter", "last_quarter",
                                  "none_live", "ends_inside_a_chunk"])
def test_gate_product_over_the_live_chunks_is_the_plain_product(case):
    counts, _, _, held, live = _routing(case)
    sched = row_schedule(jnp.asarray(counts), held, CHUNK)
    gate = _rows((M, 24), 7) * live[:, None]     # dead rows: exact zeros
    up = _rows((M, 24), 8) * live[:, None]
    cot = _rows((M, 24), 9) * live[:, None]

    def plain(a, b):
        return jax.nn.silu(a) * b

    got, pull = jax.vjp(lambda a, b: _silu_gate_rows(
        a, b, sched, CHUNK, True), gate, up)
    want, want_pull = jax.vjp(plain, gate, up)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    for a, b in zip(pull(cot), want_pull(cot)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("counts,held,chunk", [
    ([12, 12, 12, 12, 12, 12, 12, 12], (0, 2), 16),
    ([12, 12, 12, 12, 12, 12, 12, 12], (6, 2), 16),
    ([20, 10, 9, 0, 11, 16, 20, 10], (2, 3), 16),
    ([0, 0, 96, 0, 0, 0, 0, 0], (2, 1), 32),
    ([40, 0, 0, 56, 0, 0, 0, 0], (1, 2), 8),
    ([5, 0, 11, 8, 3, 13, 0, 56], (7, 1), 96),
    ([131072 - 30000, 30000], (1, 1), 1024),
], ids=str)
def test_row_schedule_against_a_brute_force_count(counts, held, chunk):
    counts = np.asarray(counts, np.int32)
    sched = row_schedule(counts, held, chunk)
    expert = np.repeat(np.arange(len(counts)), counts)       # sorted order
    mine = (expert >= held[0]) & (expert < held[0] + held[1])
    rows = np.flatnonzero(mine)
    visited = sorted({int(r) // chunk for r in rows})
    assert list(range(int(sched["first"]), int(sched["end"]))) == visited
    if len(rows):
        assert (int(sched["lo"]), int(sched["hi"])) == (rows[0], rows[-1] + 1)
    else:
        assert sched["lo"] == sched["hi"]
    traced = jax.jit(lambda c: row_schedule(c, held, chunk))(
        jnp.asarray(counts))
    assert {k: int(v) for k, v in traced.items()} == {
        k: int(v) for k, v in sched.items()}


def test_publish_moe_stats_sets_the_visited_row_share():
    """Rows visited / ``N k`` at ``_ROW_CHUNK`` (clipped to a divisor of
    the layer's rows), summed over the layers."""
    import byteps_tpu as bps
    counts = np.asarray([[100, 28, 500, 140, 0, 256, 0, 0],    # 1024 pairs
                         [128] * 8])
    publish_moe_stats(counts, held=(2, 2))                     # chunk 1024
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["moe.held_pair_share"] == pytest.approx(896 / 2048)
    assert gauges["moe.visited_row_share"] == 1.0
    counts = np.asarray([[3000, 1000, 96, 0, 0, 0, 0, 0],      # 4096 pairs
                         [512] * 8])
    publish_moe_stats(counts, held=(1, 2))
    # layer 0: rows 3000..4096 -> chunks 2, 3; layer 1: 512..1536 -> 0, 1
    share = bps.metrics_snapshot()["gauges"]["moe.visited_row_share"]
    assert share == pytest.approx((2 + 2) * 1024 / 8192)
    publish_moe_stats(counts)                  # no share: gauge untouched
    assert bps.metrics_snapshot()["gauges"]["moe.visited_row_share"] == share
