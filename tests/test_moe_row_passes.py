"""The held-experts layer's row passes (``parallel/expert.py``, PR 30) on
the CPU, through the Pallas interpreter: the spread into sorted order over
the live rows alone, against ``x[perm]``; its scaled form with the
row-wise dot, against the dense weighted arithmetic; both as transposes of
the token-order gather-sum (``jax.vjp``); the gate product's kernels; the
schedule against a brute-force count; the gauge.  And the layer in windows
of its live range (PR 40): against the whole-array held layer and the dense
arithmetic, the rule that sizes a window, the trip count, and the scopes
its kernels land under in a differentiated program; the windows' token-order
sum as a kernel against the scatter-add, and the crossover at an eighth
(PR 49).

Float32 inputs, so a moved row is compared EXACTLY and a weighted one to
float32 rounding of one product (rtol 1e-6): a wrong row, a wrong weight or
a row outside the range fails by orders of magnitude.
"""

import ast
import math
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops.moe_kernels import (_ROW_CHUNK, _SUM_PART, _spread_rows,
                                        _sum_rows)
from byteps_tpu.parallel import expert
from byteps_tpu.parallel.expert import (_combine_rows, _dispatch_rows,
                                        _gather_sum_rows, _silu_gate_rows,
                                        dropless_moe_mlp, layer_plan,
                                        publish_moe_stats, row_schedule,
                                        window_rows, window_trips)

from .route_select_cases import with_the_window

ROOT = pathlib.Path(__file__).resolve().parents[1]
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


# name -> (tokens n, window rows, live range, tokens of the rows)
_SUMS = {
    "a_quarter_live": (64, 96, (8, 70), None),
    "none_live": (64, 96, (40, 40), None),
    "all_live": (64, 96, (0, 96), None),
    # every live row one token's: more rows than a landing buffer holds
    "one_token_owns_them": (32, 2 * _SUM_PART + 24, (3, 2 * _SUM_PART + 20),
                            5),
    # the last token, the first token, tiles in between with no row
    "first_and_last_token": (512, 64, (0, 64), (0, 511)),
}


@pytest.mark.parametrize("case", _SUMS)
def test_sum_rows_is_the_scatter_add_of_the_live_rows(case):
    """The token-order sum of a window's rows (PR 49) against XLA's
    scatter-add of the live ones; rows outside the range are NOT zeros here
    and must not be read."""
    n, w, (lo, hi), tokens = _SUMS[case]
    rng = np.random.default_rng(3)
    if tokens is None:
        token = rng.integers(0, n, w)
    else:
        token = np.resize(np.asarray(tokens), w)
    rows = _rows((w, H), 12)
    live = (np.arange(w) >= lo) & (np.arange(w) < hi)
    want = np.zeros((n, H), np.float32)
    np.add.at(want, token[live], np.asarray(rows)[live])
    got = jax.jit(lambda r, t, lo, hi: _sum_rows(
        r, t, {"lo": lo, "hi": hi}, n, True))(
            rows, jnp.asarray(token, jnp.int32), jnp.int32(lo), jnp.int32(hi))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)
    assert got.dtype == jnp.float32
    assert not np.asarray(got)[np.setdiff1d(np.arange(n), token[live])].any()


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
    trips_before = bps.metrics_snapshot()["gauges"].get("moe.window_trips")
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
    # layers on whole arrays: the windows' gauge is not theirs to set
    assert bps.metrics_snapshot()["gauges"].get(
        "moe.window_trips") == trips_before
    # a thin share (2 of 128 experts, 32 768 pair rows): windows of 1 024.
    # Layer 0: 900 live rows from row 5 000 -> 1 trip; layer 1: 2 100 -> 3
    thin = np.zeros((2, 128), np.int64)
    thin[0, [0, 9, 10, 127]] = 5000, 600, 300, 32768 - 5900
    thin[1, [0, 9, 10, 127]] = 100, 2000, 100, 32768 - 2200
    publish_moe_stats(thin, held=(9, 2))
    gauges = bps.metrics_snapshot()["gauges"]
    assert window_rows(32768, 1, 2, 128) == 1024
    assert gauges["moe.window_trips"] == 3.0
    assert gauges["moe.visited_row_share"] == pytest.approx(
        (1 + 3) * 1024 / 65536)
    assert gauges["moe.held_pair_share"] == pytest.approx(3000 / 65536)


# ------------------- one plan for the layer and its gauges (PR 42)

# cell -> ((N k, held experts, experts), the plan), from
# benchmarks/configs/*.json and the traffic's tokens a chip
CELLS = {
    "olmoe_1b_7b": ((16384 * 8, None, 64), ("all", 1024, None)),
    "mellum2_12b": ((16384 * 8, 16, 64), ("held_rows", 1024, None)),
    "zaya1_8b": ((16384 * 1, 8, 16), ("held_rows", 1024, None)),
    "glm47_flash": ((16384 * 4, 8, 64), ("held_rows", 1024, None)),
    "nemotron3_super": ((8192 * 22, 8, 512), ("held_windows", 1024, 6144)),
    "ling3_flash": ((16384 * 8, 8, 512), ("held_windows", 1024, 4096)),
    "qwen3_next_80b": ((32768 * 10, 32, 512), ("held_windows", 1024, 40960)),
}
# cell -> (visited_row_share, window_trips) on balanced counts, as the rule
# plans it and with the other kind forced (windows of half the rows; none)
GAUGES = {
    "mellum2_12b": ((32768 / 131072, None), (65536 / 131072, 1.0)),
    "zaya1_8b": ((8192 / 16384, None), (8192 / 16384, 1.0)),
    "glm47_flash": ((8192 / 65536, None), (32768 / 65536, 1.0)),
    "nemotron3_super": ((6144 / 180224, 1.0), (3072 / 180224, None)),
    "qwen3_next_80b": ((40960 / 327680, 1.0), (20480 / 327680, None)),
}


@pytest.mark.parametrize("cell", CELLS)
def test_layer_plan_at_the_cells_shapes(cell):
    shape, want = CELLS[cell]
    assert tuple(layer_plan(*shape)) == want
    if shape[1] is not None:
        assert window_rows(shape[0], 1, *shape[1:]) == want[2]


@pytest.mark.parametrize("cell", GAUGES)
def test_the_gauges_describe_the_plan_the_layer_takes(cell, monkeypatch):
    """``publish_moe_stats`` has no rule of its own: on balanced counts it
    sets what ``layer_plan``'s kind implies, and follows the plan where
    another is forced on the layer."""
    import byteps_tpu as bps
    (rows, count, experts), (kind, _, _) = CELLS[cell]
    counts = np.full((2, experts), rows // experts)
    forced = rows // 2 if kind == "held_rows" else None
    for (share, trips), window in zip(GAUGES[cell], ("rule", forced)):
        before = bps.metrics_snapshot()["gauges"].get("moe.window_trips")
        with monkeypatch.context() as patch:
            if window != "rule":
                with_the_window(patch, window)
            publish_moe_stats(counts, held=(0, count))
        gauges = bps.metrics_snapshot()["gauges"]
        assert gauges["moe.visited_row_share"] == pytest.approx(share)
        assert gauges.get("moe.window_trips") == (
            before if trips is None else trips)
        assert gauges["moe.held_pair_share"] == pytest.approx(count / experts)


def _imported(path):
    """The modules a file of ``byteps_tpu`` imports anywhere in it, relative
    names resolved against its package (read with ``ast``, as
    ``tools/bpslint`` reads)."""
    package = path.relative_to(ROOT).with_suffix("").parts[:-1]
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found |= {alias.name for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level else ()
            module = ".".join(base + tuple(filter(None, [node.module])))
            found |= {module} | {f"{module}.{a.name}" for a in node.names}
    return found


def test_the_expert_layers_imports_point_one_way():
    """``models/*`` -> ``parallel/expert.py`` -> ``ops/moe_kernels.py``;
    ``parallel/switch_moe.py`` beside them, importing neither."""
    pkg = ROOT / "byteps_tpu"
    kernels = _imported(pkg / "ops" / "moe_kernels.py")
    assert not [m for m in kernels if m.startswith(
        ("byteps_tpu.parallel", "byteps_tpu.models"))]
    layer = _imported(pkg / "parallel" / "expert.py")
    switch = _imported(pkg / "parallel" / "switch_moe.py")
    assert "byteps_tpu.ops.moe_kernels" in layer
    assert not [m for m in layer if "switch_moe" in m or ".models" in m]
    assert not [m for m in switch if m.startswith(
        ("byteps_tpu.parallel.expert", "byteps_tpu.ops.moe_kernels",
         "byteps_tpu.models"))]
    for model in ("olmoe", "mellum", "zaya", "glm_lite", "nemotron_h", "ling",
                  "qwen3_next"):
        assert "byteps_tpu.parallel.expert.dropless_moe_mlp" in _imported(
            pkg / "models" / f"{model}.py")


# ------------------------------------------ the layer in windows (PR 40)

@pytest.mark.parametrize("n,top_k,held_count,experts,want", [
    (8192, 22, 8, 512, 6144),         # nemotron3_super.fused_1c
    (16384, 8, 16, 64, None),         # mellum2_12b.fused_1c: a quarter live
    (16384, 1, 8, 16, None),          # zaya1_8b.fused_1c: half
    (16384, 4, 8, 64, None),          # glm47_flash.fused_1c: an eighth
    (4096, 8, 64, 64, None),          # every expert held
    (32768, 10, 32, 512, 40960),      # qwen3_next_80b.fused_1c: a 16th live
    (8192, 22, 32, 512, 22528),       # exactly an eighth of 180 224
    (8192, 22, 33, 512, None),        # 23 552: over it
    (8192, 8, 1, 64, 2048),
    (66, 4, 1, 64, 16),               # chunks of 8 rows
    (33, 1, 1, 64, None),             # no chunk of whole sublane tiles
], ids=str)
def test_window_rows_against_a_brute_force_count(n, top_k, held_count,
                                                 experts, want):
    """The least whole number of row chunks that holds twice the expected
    live rows, where that is at most an eighth of the pair rows."""
    assert window_rows(n, top_k, held_count, experts) == want
    rows = n * top_k
    chunk = math.gcd(rows, _ROW_CHUNK)
    window = chunk
    while window * experts < 2 * rows * held_count:
        window += chunk
    brute = window if chunk % 8 == 0 and 8 * window <= rows else None
    assert brute == want


def test_the_crossover_is_a_window_of_an_eighth_of_the_pair_rows():
    """The edge itself (PR 49): a window of exactly ``rows / 8`` takes
    windows — 32 of 512 experts at top-10 over 32 768 tokens, 40 of 320
    chunks — and one held expert more does not: 33 of 512 wants 42."""
    rows = 32768 * 10
    assert layer_plan(rows, 32, 512) == ("held_windows", 1024, rows // 8)
    assert layer_plan(rows, 33, 512) == ("held_rows", 1024, None)
    assert -(-2 * rows * 33 // (512 * 1024)) == 42 > rows // 8 // 1024 == 40
    # the share alone decides: the same at a tenth of the tokens' pairs ...
    assert layer_plan(rows // 10, 32, 512) == ("held_windows", 1024, 4096)
    assert layer_plan(rows // 10, 33, 512).kind == "held_rows"
    # ... and GLM's quarter and Mellum's half stay on whole arrays
    assert layer_plan(16384 * 4, 8, 64).kind == "held_rows"
    assert layer_plan(16384 * 8, 16, 64).kind == "held_rows"


def test_a_sixteenth_held_in_windows_of_an_eighth_is_the_whole_array_layer(
        monkeypatch):
    """``qwen3_next_80b.fused_1c``'s share scaled down — 8 of 128 experts at
    top-8 over 1 024 tokens: 8 192 pair rows of which a 16th are live, ONE
    window of 1 024 by the rule itself — against the same layer forced on
    whole arrays: ``y`` and the gradients of the rows, the three stacks and
    the router, the model's own softmax router renormalised over its k."""
    n, top_k, held, e = 1024, 8, (40, 8), 128
    assert layer_plan(n * top_k, held[1], e) == ("held_windows", 1024, 1024)
    k = jax.random.split(jax.random.PRNGKey(49), 3)
    params = dict(_layer_params(held[1], True),
                  router=jax.random.normal(k[0], (_LH, e)))
    x = jax.random.normal(k[1], (n, _LH))
    cot = jax.random.normal(k[2], (n, _LH))

    def layer(x, params):
        return dropless_moe_mlp(x, params, top_k, interpret=True, held=held,
                                renormalize=True)

    def run():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda x, p: jnp.sum(layer(x, p)[0] * cot), (0, 1)))(x, params)

    asked = []
    with monkeypatch.context() as patch:
        patch.setattr(expert, "layer_plan", lambda *shape: (
            asked.append(shape), layer_plan(*shape))[1])
        windowed = run()
    assert asked == [(n * top_k, held[1], e)]
    counts = np.asarray(layer(x, params)[3])
    live = int(counts[held[0]:held[0] + held[1]].sum())
    assert 0.04 < live / (n * top_k) < 0.09
    assert int(window_trips(counts, held, 1024)) == 1
    with_the_window(monkeypatch, None)
    whole = run()
    np.testing.assert_allclose(windowed[0], whole[0], rtol=2e-5)
    for g, w in zip(jax.tree.leaves(windowed[1]), jax.tree.leaves(whole[1])):
        np.testing.assert_allclose(
            g, w, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(w))))


@pytest.mark.parametrize("counts,held,window,want", [
    ([40, 0, 0, 56, 0, 0, 0, 0], (1, 2), 16, 0),
    ([40, 0, 1, 55, 0, 0, 0, 0], (1, 2), 16, 1),
    ([40, 8, 8, 40, 0, 0, 0, 0], (1, 2), 16, 1),
    ([40, 8, 9, 39, 0, 0, 0, 0], (1, 2), 16, 2),
    ([0, 0, 0, 0, 0, 0, 90, 6], (7, 1), 16, 1),
    ([0, 48, 48, 0, 0, 0, 0, 0], (1, 2), 16, 6),
], ids=str)
def test_window_trips_cover_the_live_range(counts, held, window, want):
    counts = np.asarray(counts, np.int32)
    assert int(window_trips(counts, held, window)) == want
    traced = jax.jit(lambda c: window_trips(c, held, window))(
        jnp.asarray(counts))
    assert int(traced) == want
    sched = row_schedule(counts, held, window)
    assert want * window >= int(sched["hi"] - sched["lo"]) > (
        want - 1) * window


_LH, _LF, _LE, _LN, _LW = 32, 16, 8, 48, 16


def _layer_params(count, gated):
    k = jax.random.split(jax.random.PRNGKey(11), 3)
    p = {"up": jax.random.normal(k[0], (count, _LH, _LF)) / np.sqrt(_LH),
         "down": jax.random.normal(k[1], (count, _LF, _LH)) / np.sqrt(_LF)}
    if gated:
        p["gate"] = jax.random.normal(k[2], (count, _LH, _LF)) / np.sqrt(_LH)
    return p


# name -> (held, tokens that choose ONE held expert each; None: every
# token chooses held experts alone)
_LIVE = {
    "no_trip": ((2, 4), 0),                   # nobody routed here
    "one_trip": ((2, 4), 10),                 # ten pairs
    "worst_case": ((2, 4), None),             # every pair: N k / W trips
    "clamp_moves_it": ((6, 2), 10),           # the last ten rows: lo + W > N k
}


def _logits(held, chosen):
    """[N, E] router logits under which exactly ``chosen`` tokens have one
    held expert among their choices (the others none)."""
    first, count = held
    mine = (jnp.arange(_LE) >= first) & (jnp.arange(_LE) < first + count)
    logits = jax.random.normal(jax.random.PRNGKey(2), (_LN, _LE))
    if chosen is None:
        return jnp.where(mine, logits, -50.0)
    token = jnp.arange(_LN)[:, None]
    favoured = first + token % count == jnp.arange(_LE)
    return jnp.where(mine, jnp.where(favoured & (token < chosen), 9.0, -50.0),
                     logits)


def _dense(x, stacks, scores, top_k, held):
    """The held experts one by one over ALL rows, each weighted by the
    token's score where the expert is among its ``top_k``."""
    first, count = held
    _, chosen = jax.lax.top_k(scores, top_k)
    picked = (jnp.arange(scores.shape[-1]) == chosen[..., None]).any(-2)
    weight = jnp.where(picked, scores, 0.0)
    y = jnp.zeros_like(x)
    for i in range(count):
        up = x @ stacks["up"][i]
        hidden = (jax.nn.silu(x @ stacks["gate"][i]) * up if "gate" in stacks
                  else jnp.maximum(up, 0.0) ** 2)
        y = y + weight[:, first + i, None] * (hidden @ stacks["down"][i])
    return y


@pytest.mark.parametrize("top_k,gated", [(1, True), (1, False), (4, True),
                                         (4, False)],
                         ids=["top1_gated", "top1_relu2", "top4_gated",
                              "top4_relu2"])
@pytest.mark.parametrize("live", _LIVE)
def test_windowed_layer_is_the_whole_layer_and_the_dense_arithmetic(
        monkeypatch, live, top_k, gated):
    """``y`` and the gradients of the rows, both (three) stacks and the
    scores, through windows of 16 rows of 48 or 192: against the held
    layer on whole arrays (the rule refusing) and the dense arithmetic."""
    held, chosen = _LIVE[live]
    stacks = _layer_params(held[1], gated)
    x = jax.random.normal(jax.random.PRNGKey(1), (_LN, _LH))
    logits = _logits(held, chosen)
    cot = jax.random.normal(jax.random.PRNGKey(3), (_LN, _LH))

    def layer(x, stacks, logits):
        return dropless_moe_mlp(x, stacks, top_k, interpret=True, held=held,
                                routing=(jax.nn.sigmoid(logits), None))

    def run(fn):
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a) * cot), (0, 1, 2)))(x, stacks,
                                                               logits)

    asked = []
    with monkeypatch.context() as patch:
        with_the_window(patch, _LW, asked)
        windowed = run(lambda *a: layer(*a)[0])
        counts = np.asarray(layer(x, stacks, logits)[3])
    assert asked[0] == (_LN * top_k, held[1], _LE)
    with_the_window(monkeypatch, None)
    whole = run(lambda *a: layer(*a)[0])
    dense = run(lambda x, s, l: _dense(x, s, jax.nn.sigmoid(l), top_k, held))

    sched = row_schedule(counts, held, _LW)
    trips = int(window_trips(counts, held, _LW))
    rows = _LN * top_k
    if live == "no_trip":
        assert trips == 0 and not np.asarray(windowed[0]).any()
    elif live == "worst_case":
        assert trips == rows // _LW >= 3
    else:
        assert trips == 1 and int(sched["hi"] - sched["lo"]) == chosen
        assert (int(sched["lo"]) + _LW > rows) == (live == "clamp_moves_it")
    for want in (whole, dense):
        np.testing.assert_allclose(windowed[0], want[0], rtol=2e-5,
                                   atol=1e-6)
        for g, w in zip(jax.tree.leaves(windowed[1]),
                        jax.tree.leaves(want[1])):
            np.testing.assert_allclose(
                g, w, rtol=2e-5,
                atol=2e-5 * max(float(jnp.max(jnp.abs(w))), 1e-6))


def test_windows_that_overlap_count_their_shared_rows_once(monkeypatch):
    """Three windows of which the clamp moves the last back over the
    second: 40 live rows from row 152 of 192 in windows of 16 — the third
    starts at 176, not 184, and holds rows 176..183 dead."""
    held, top_k = (7, 1), 4
    stacks = _layer_params(1, False)
    x = jax.random.normal(jax.random.PRNGKey(1), (_LN, _LH))
    # 40 tokens choose expert 7 among their four; the sort puts it last
    logits = _logits(held, 40)

    def layer(x, stacks, logits):
        return dropless_moe_mlp(x, stacks, top_k, interpret=True, held=held,
                                routing=(jax.nn.sigmoid(logits), None))

    def run(window):
        with monkeypatch.context() as patch:
            with_the_window(patch, window)
            with jax.default_matmul_precision("highest"):
                return jax.jit(jax.value_and_grad(
                    lambda *a: jnp.sum(layer(*a)[0] ** 2), (0, 1, 2)))(
                        x, stacks, logits)

    windowed, whole = run(_LW), run(None)
    counts = np.asarray(layer(x, stacks, logits)[3])
    assert counts[7] == 40 and int(window_trips(counts, held, _LW)) == 3
    assert int(row_schedule(counts, held, _LW)["lo"]) + 3 * _LW > 192
    np.testing.assert_allclose(windowed[0], whole[0], rtol=2e-5)
    for g, w in zip(jax.tree.leaves(windowed[1]), jax.tree.leaves(whole[1])):
        np.testing.assert_allclose(
            g, w, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(w))))


def kernel_stacks(jaxpr, prefix=""):
    """The name stack of every ``pallas_call`` equation of a jaxpr, nested
    ones too, rendered as the lowering renders an HLO ``op_name``."""
    out = []
    for eqn in jaxpr.eqns:
        stack = "/".join(p for p in (prefix, str(eqn.source_info.name_stack))
                         if p)
        if eqn.primitive.name == "pallas_call":
            out.append(stack + "/pallas_call")
            continue
        for key, value in eqn.params.items():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if not hasattr(sub, "eqns"):
                    continue
                step = {"jit": f"jit({eqn.params.get('name')})",
                        "while": "while/" + key.split("_")[0]}.get(
                            eqn.primitive.name, "")
                out += kernel_stacks(sub, "/".join(
                    p for p in (stack, step) if p))
    return out


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
def test_windowed_kernels_land_under_the_scopes_the_readers_look_for(gated):
    r"""The readers of the grouped matmuls' time (``latent_moe_ms``,
    ``held_moe_ms``, ...) take every kernel whose ``op_name`` matches
    ``bps\.moe\.experts/.*pallas_call$``.  A transform wraps the FIRST
    scope entered after it: were the window's first scope ``bps.moe.
    experts``, the backward loop's ``jax.vjp`` would render its kernels
    ``jvp(bps.moe.experts)/...``, which the rule does not match, and the
    roofline would read the forward loop's kernels alone.  So: every
    ``gmm`` / ``tgmm`` of forward loop and backward loop matches, and no
    row kernel does."""
    n, top_k, held, e = 66, 4, (5, 1), 64
    assert window_rows(n, top_k, held[1], e) == 16
    stacks = _layer_params(1, gated)
    x = jax.random.normal(jax.random.PRNGKey(1), (n, _LH))
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2), (n, e)))

    def loss(x, stacks, scores):
        with jax.named_scope("mtp"):
            y = dropless_moe_mlp(x, stacks, top_k, interpret=True, held=held,
                                 routing=(scores, None))[0]
        return jnp.sum(y), y

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(
        x, stacks, scores).jaxpr
    stacks_ = kernel_stacks(jaxpr)
    # the selection (PR 41): ONE kernel, before the loops, under the route
    # stage and the module's scope
    select = [s for s in stacks_ if "bps_moe_select" in s]
    assert len(select) == 1 and re.search(
        r"\(mtp\)+/bps\.moe\.route/", select[0]), stacks_
    stacks_.remove(select[0])
    experts = [s for s in stacks_
               if re.search(r"bps\.moe\.experts/.*pallas_call$", s)]
    own = [s for s in stacks_ if s not in experts]
    mats = 3 if gated else 2
    # forward loop; backward loop: the forward again, then a row gradient
    # (gmm) and a matrix gradient (tgmm) a matrix
    assert len(experts) == mats + mats + 2 * mats, stacks_
    assert sum("jit(tgmm)" in s for s in experts) == mats
    assert all("jit(gmm)" in s or "jit(tgmm)" in s for s in experts)
    assert all("while/body" in s for s in stacks_)
    act = "bps_moe_gate" if gated else "bps_moe_act"
    # the token-order sum: the combine's (forward loop, and the forward the
    # backward loop's ``jax.vjp`` traces: dead there, XLA drops it) and the
    # dispatch's backward
    sums = [s for s in own if s.split("/")[-2] == "bps_moe_sum"]
    own = [s for s in own if s not in sums]
    assert sorted(re.search(r"bps\.moe\.(\w+)\)*/jit\(_sum_rows\)", s).group(1)
                  for s in sums) == ["combine", "combine", "dispatch"], sums
    assert sorted(s.split("/")[-2] for s in own) == sorted(
        ["bps_moe_spread"] * 2 + [act] * 2 + [act + "_bwd",
                                              "bps_moe_spread_scaled"]), own
    assert not any("gmm)" in s for s in own + sums)
    for s in own:
        scope = {"bps_moe_spread": "dispatch", "bps_moe_spread_scaled":
                 "combine"}.get(s.split("/")[-2], "gate" if gated else "act")
        assert f"/bps.moe.{scope}/" in s, s
    # the module's own scope reaches every kernel, backward ones too
    assert all(re.search(r"\(mtp\)+/while/body/", s) for s in stacks_)
