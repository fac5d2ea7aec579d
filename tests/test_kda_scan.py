"""``ops/kda_scan.py``: the Mosaic kernels (Pallas interpreter here) and the
chunked ``jax.numpy`` form against the gated delta rule position by
position, in value and in every input's gradient."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

kda = importlib.import_module("byteps_tpu.ops.kda_scan")


def recurrence(q, k, v, g, beta):
    """S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t
    v_t^T;  o_t = S_t^T q_t, from a zero state, one position at a time."""
    b, t, h, dk = q.shape

    def position(state, at):                    # state [B, H, d_k, d_v]
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    by_position = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)]
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(
            position, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
            tuple(by_position))
    return jnp.moveaxis(o, 0, 1)


def inputs(seed, b=2, t=64, h=2, dk=16, dv=8, g_range=(-5.0, 0.0),
           beta_range=(0.0, 1.0)):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (b, t, h, dk))
    k = jax.random.normal(keys[1], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (b, t, h, dv))
    g = jax.random.uniform(keys[3], (b, t, h, dk), jnp.float32, *g_range)
    beta = jax.random.uniform(keys[4], (b, t, h), jnp.float32, *beta_range)
    return q, k, v, g, beta


def kernels(*args, chunk):
    return kda.kda_scan(*args, chunk=chunk, interpret=True)


FORMS = {"kernels": kernels, "chunked": kda.kda_scan_chunked,
         "recurrence": lambda *a, chunk: recurrence(*a)}


@functools.lru_cache(maxsize=None)
def value_and_gradients(form, chunk):
    """``(o, gradients of sum(o * weight) by q, k, v, g, beta)`` of one
    form at one chunk, compiled once a shape: the cases below differ in
    their VALUES, and the Pallas interpreter and the chunk text's ``vjp``
    cost seconds to compile each (sub-blocks of 16: C = 8 and 16 are one,
    C = 32 two — a diagonal and an off-diagonal one; the chip's C = 128 is
    ``benchmarks/tests/gradcheck_ling.py``'s to read)."""
    def objective(weight, *a):
        o = FORMS[form](*a, chunk=chunk)
        return jnp.sum(o * weight), o

    return jax.jit(jax.value_and_grad(objective, argnums=(1, 2, 3, 4, 5),
                                      has_aux=True))


CASES = {
    # t, chunk, g's range, beta's range
    "one_chunk": (32, 32, (-5.0, 0.0), (0.0, 1.0)),
    "several_chunks": (64, 16, (-5.0, 0.0), (0.0, 1.0)),
    "two_sub_blocks_a_chunk": (64, 32, (-5.0, 0.0), (0.0, 1.0)),
    "small_chunk": (32, 8, (-5.0, 0.0), (0.0, 1.0)),
    "g_near_0": (64, 32, (-1e-3, 0.0), (0.0, 1.0)),
    "beta_near_0": (64, 32, (-5.0, 0.0), (0.0, 1e-3)),
    "beta_near_1": (64, 32, (-2.0, 0.0), (0.999, 1.0)),
}


def close(got, want, rtol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max() + 1e-30
    assert np.abs(got - want).max() <= rtol * scale, (
        np.abs(got - want).max() / scale)


def against_the_recurrence(form, chunk, args):
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    (_, out), got = value_and_gradients(form, chunk)(weight, *args)
    (_, want_out), want = value_and_gradients("recurrence", None)(
        weight, *args)
    assert np.isfinite(np.asarray(out)).all()
    close(out, want_out)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert np.isfinite(np.asarray(a)).all()
        try:
            close(a, b)
        except AssertionError as e:
            raise AssertionError(f"d{name}: {e}") from None


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("form", ["chunked", "kernels"])
def test_value_and_gradients_against_the_recurrence(form, case):
    t, chunk, g_range, beta_range = CASES[case]
    against_the_recurrence(form, chunk, inputs(
        3, t=t, g_range=g_range, beta_range=beta_range))


@pytest.mark.parametrize("form", ["chunked", "kernels"])
def test_a_whole_chunk_at_the_gate_s_floor(form):
    """g = -5 on every channel for a whole chunk of 32: 1 / Gamma would be
    exp(160) against the chunk's start, past float32's exp(88); the
    sub-blocks keep every factor finite, value and gradients."""
    q, k, v, g, beta = inputs(5, t=64)
    against_the_recurrence(form, 32, (q, k, v, g.at[:, :32].set(-5.0), beta))


def full_square_scores(q, k, cum, beta, sub=16):
    """The witness of ``_scores``, the form the text had before PR 45: for
    each row sub-block ALL the chunk's rows of q and of beta k against the
    sub-block's keys — two [C, C] products — of which a mask keeps the
    sub-block's own rows."""
    c = q.shape[0]
    pos = jnp.arange(c)[:, None]
    first = cum[(jnp.arange(c) // sub) * sub]
    q_rows = q * jnp.exp(cum - first)
    k_rows = k * jnp.exp(cum - first) * beta
    p = a_mat = jnp.zeros((c, c), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for a in range(c // sub):
            keys = k * jnp.exp(jnp.where(pos < (a + 1) * sub,
                                         cum[a * sub] - cum, -jnp.inf))
            mine = pos // sub == a
            p = p + jnp.where(mine, q_rows @ keys.T, 0.0)
            a_mat = a_mat + jnp.where(mine, k_rows @ keys.T, 0.0)
    return jnp.tril(p), jnp.tril(a_mat, -1)


@pytest.mark.parametrize("chunk", [32, 128])
def test_the_score_products_by_row_sub_block_are_the_full_squares(chunk):
    """``P`` and ``A`` of one seeded chunk both ways, its second sub-block
    whole at the gate's floor: every kept element is the sum of the same
    d_k channel products, so they agree to float32's rounding — and each
    sub-block's rows come out of ONE stacked product."""
    q, k, _, g, beta = [x[0, :, 0] for x in inputs(
        13, b=1, t=chunk, h=1, g_range=(-0.3, 0.0))]
    cum = jnp.cumsum(g.at[16:32].set(-5.0), axis=0)
    got = kda._scores(q, k, cum, beta[:, None], q.dtype)
    want = full_square_scores(q, k, cum, beta[:, None])
    for name, x, y in zip("PA", got, want):
        assert np.isfinite(np.asarray(x)).all() and x.shape == (chunk, chunk)
        assert np.abs(np.asarray(y)).max() > 0.01, name
        close(x, y, rtol=1e-6)
    text = str(jax.make_jaxpr(functools.partial(kda._scores, lp=q.dtype))(
        q, k, cum, beta[:, None]))
    assert text.count("dot_general") == chunk // 16


def test_the_result_does_not_depend_on_the_chunk():
    args = inputs(7, t=64)
    close(kernels(*args, chunk=16), kernels(*args, chunk=32), rtol=1e-5)


def test_the_kernels_keep_the_state_in_float32_under_bfloat16_operands():
    """bfloat16 q, k, v: the result stays within bfloat16's rounding of the
    float32 recurrence over 256 positions (a bfloat16 STATE would not:
    ``benchmarks/tests/gradcheck_ling.py`` shows that on the chip)."""
    q, k, v, g, beta = inputs(11, t=256, g_range=(-0.2, 0.0))
    lp = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    got = kernels(*lp, g, beta, chunk=32)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*[x.astype(jnp.float32) for x in lp], g, beta)
    close(got.astype(jnp.float32), want, rtol=3e-2)


@pytest.mark.parametrize("form", ["chunked", "kernels"])
def test_refusals(form):
    q, k, v, g, beta = inputs(1, t=48)
    with pytest.raises(ValueError, match="not a multiple of chunk"):
        FORMS[form](q, k, v, g, beta, chunk=32)
    with pytest.raises(ValueError, match="whole sub-blocks"):
        FORMS[form](q, k, v, g, beta, chunk=24)
    with pytest.raises(ValueError, match="wants"):
        FORMS[form](q, k, v, g, beta[:, :, :1], chunk=16)


def test_padding_positions_leave_the_state_as_it_is():
    """What the refusal tells the caller to do: g = 0, beta = 0."""
    q, k, v, g, beta = inputs(2, t=48)
    pad = lambda x: jnp.pad(  # noqa: E731
        x, [(0, 0), (0, 16)] + [(0, 0)] * (x.ndim - 2))
    out = kernels(*[pad(x) for x in (q, k, v, g, beta)], chunk=32)
    close(out[:, :48], recurrence(q, k, v, g, beta))


def test_a_differentiated_call_is_the_two_kernels_and_sets_the_gauges():
    import byteps_tpu as bps
    args = inputs(4, b=1, t=64)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kernels(*a, chunk=32))))(*args))
    assert text.count("name=bps_kda_fwd") == 1
    assert text.count("name=bps_kda_bwd") == 1
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["kda.heads"] == 2 and gauges["kda.chunk"] == 32
    assert gauges["kda.chunks_per_seq"] == 2
    assert gauges["kda.state_bytes"] == 4 * 2 * 16 * 8
    assert gauges["kda.saved_state_bytes"] == 2 * 4 * 2 * 16 * 8
    # both operands of every product of one head's chunk, float32 here: the
    # two stacked score products [16 + 16, 16] x [32, 16], 6 + 1 + 0 + 1 = 8
    # three-pass [32, 32] products of the inverse (sub-blocks of 16: three
    # squarings a diagonal block, none for the two-block rest), the three
    # passes of G, and the five products the algorithm needs
    square, rows = 4 * 32 * 32, 4 * 32 * 16
    assert gauges["kda.matmul_operand_bytes_per_chunk"] == (
        2 * (rows + rows) + 24 * 2 * square + 3 * (square + rows)
        + (rows + 4 * 8 * 16) + (square + 4 * 32 * 8) + (rows + 4 * 8 * 16)
        + (square + 4 * 32 * 8) + (4 * 32 * 8 + rows))


def test_the_cell_s_chunk_hands_the_matrix_unit_5632_KiB():
    """By tracing alone, at ``ling3_flash.fused_1c``'s shapes (C = 128,
    heads of 128 x 128, bfloat16 q, k, v) and as Mosaic gets it
    (``interpret=False``): the gauge reads 5 632 KiB where the text before
    PR 45 read 6 336 (60 products of [128, 128] x [128, 128], 16 of them
    score products over all 128 rows on bfloat16 operands: 8 stacked ones
    of [32, 128] x [128, 128] now)."""
    import byteps_tpu as bps
    shape = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    decay = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, 256, 2), jnp.float32)
    jax.eval_shape(functools.partial(kda.kda_scan, chunk=128,
                                     interpret=False),
                   shape, shape, shape, decay, beta)
    assert bps.metrics_snapshot()["gauges"][
        "kda.matmul_operand_bytes_per_chunk"] == (6336 - 16 * 64 + 8 * 40
                                                  ) * 1024 == 5632 * 1024


def test_the_inverse_in_three_passes_stays_float32_accurate(monkeypatch):
    """``(I + A)^-1`` by the nilpotent series with every [C, C] product as
    three passes whose OPERANDS the matrix unit rounds to bfloat16 (emulated
    here: a pass rounds both sides): within 1e-5 of the float64 inverse for
    unit keys in general position, where one pass a product reads 1e-3."""
    def rounded_pass(x, y, dims):
        low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa
        return jax.lax.dot_general(
            low(x), low(y), (dims, ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

    rng = np.random.default_rng(0)
    keys = rng.standard_normal((64, 128))
    keys /= np.linalg.norm(keys, axis=1, keepdims=True)
    a = np.tril(rng.uniform(0.2, 1.0, (64, 1)) * (keys @ keys.T), -1)
    want = np.linalg.inv(np.eye(64) + a)
    monkeypatch.setattr(kda, "_pass", rounded_pass)
    close(kda._inverse(jnp.asarray(a, jnp.float32)), want, rtol=1e-5)
    monkeypatch.setattr(kda, "_dot3", rounded_pass)
    one_pass = np.asarray(kda._inverse(jnp.asarray(a, jnp.float32)))
    assert np.abs(one_pass - want).max() > 3e-4


def test_the_operand_metric_s_entry_and_reader(monkeypatch):
    """``kda_matmul_operand_KiB`` (``benchmarks/layer_metrics/``): the entry
    is found by name, matches its reader file and names the Ling cell
    alone; the reader divides the gauge a traced ``kda_scan`` set and gives
    nothing, without raising, for a program without it (the parent's)."""
    import json
    import os
    import types
    import byteps_tpu as bps
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(os.path.join(root, "benchmarks"))
    from harness import spec
    name, cell = "kda_matmul_operand_KiB", "ling3_flash.fused_1c"
    reader = spec.load_module("layer_metrics", name)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m for m in bench["per_layer"] if m["name"] == name] == [{
        "name": name, "unit": reader.UNIT, "better": reader.BETTER,
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES, "workloads": [cell]}]
    assert reader.LAYER == "ops kernels" and reader.BETTER == "lower"
    for w in bench["workloads"]:
        names = {m["name"] for m in spec.metrics_for(bench, "per_layer",
                                                     w["name"])}
        assert (name in names) == (w["name"] == cell)
    jax.eval_shape(functools.partial(kernels, chunk=32), *inputs(4, b=1))
    run = types.SimpleNamespace(snap1=bps.metrics_snapshot(), info={})
    assert reader.read(run) == 241664 / 1024
    without = {k: v for k, v in run.snap1["gauges"].items() if k != (
        "kda.matmul_operand_bytes_per_chunk")}
    for snap in ({"gauges": without}, {}):
        assert reader.read(types.SimpleNamespace(snap1=snap, info={})) is None
