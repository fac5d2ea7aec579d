"""``ops/gdn_scan.py``: the Mosaic kernels (Pallas interpreter here) and the
chunked ``jax.numpy`` form against the gated delta rule with a HEAD's
decay position by position (``tests/qwen3_next_reference.py``
``delta_rule``), in value and in every input's gradient — at gates as
large as -30 a position, at ``g = 0``, with two value heads a key head and
with one, whatever the chunk."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from . import qwen3_next_reference as reference

gdn = importlib.import_module("byteps_tpu.ops.gdn_scan")
kda = importlib.import_module("byteps_tpu.ops.kda_scan")


def recurrence(*args):
    with jax.default_matmul_precision("highest"):
        return reference.delta_rule(*args)


def inputs(seed, b=2, t=64, hk=2, hv=4, dk=16, dv=8, g_range=(-30.0, 0.0),
           beta_range=(0.0, 1.0)):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (b, t, hk, dk))
    k = jax.random.normal(keys[1], (b, t, hk, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (b, t, hv, dv))
    g = jax.random.uniform(keys[3], (b, t, hv), jnp.float32, *g_range)
    beta = jax.random.uniform(keys[4], (b, t, hv), jnp.float32, *beta_range)
    return q, k, v, g, beta


def kernels(*args, chunk):
    return gdn.gdn_scan(*args, chunk=chunk, interpret=True)


FORMS = {"kernels": kernels, "chunked": gdn.gdn_scan_chunked,
         "recurrence": lambda *a, chunk: recurrence(*a)}


@functools.lru_cache(maxsize=None)
def value_and_gradients(form, chunk):
    """``(o, gradients of sum(o * weight) by q, k, v, g, beta)`` of one
    form at one chunk, compiled once a shape (the interpreter and the
    chunk text's ``vjp`` cost seconds to compile each)."""
    def objective(weight, *a):
        o = FORMS[form](*a, chunk=chunk)
        return jnp.sum(o * weight), o

    return jax.jit(jax.value_and_grad(objective, argnums=(1, 2, 3, 4, 5),
                                      has_aux=True))


CASES = {
    # t, chunk, (key heads, value heads), g's range, beta's range
    "gates_to_-30": (64, 16, (2, 4), (-30.0, 0.0), (0.0, 1.0)),
    "gates_to_-30_two_blocks_a_chunk": (64, 32, (2, 4), (-30.0, 0.0),
                                        (0.0, 1.0)),
    "one_chunk": (32, 32, (2, 4), (-5.0, 0.0), (0.0, 1.0)),
    "small_chunk": (32, 8, (2, 4), (-2.0, 0.0), (0.0, 1.0)),
    "g_is_0": (64, 16, (2, 4), (0.0, 0.0), (0.0, 1.0)),
    "equal_heads": (64, 16, (2, 2), (-30.0, 0.0), (0.0, 1.0)),
    "equal_heads_g_is_0": (64, 16, (2, 2), (0.0, 0.0), (0.0, 1.0)),
    "one_head": (64, 16, (1, 1), (-1.0, 0.0), (0.0, 1.0)),
    "four_value_heads_a_key_head": (32, 16, (1, 4), (-3.0, 0.0), (0.0, 1.0)),
    "beta_near_1": (64, 32, (2, 4), (-2.0, 0.0), (0.999, 1.0)),
}


def close(got, want, rtol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max() + 1e-30
    assert np.abs(got - want).max() <= rtol * scale, (
        np.abs(got - want).max() / scale)


def against_the_recurrence(form, chunk, args, rtol=1e-4):
    """``rtol`` of the largest entry: float32's rounding through a chunk's
    dozen products, the solve's three bfloat16-operand passes a product
    (2^-16, ``ops/kda_scan.py`` ``_dot3``) and ``G``'s differences (C |g|
    2^-24 = 3e-5 at 32 positions of -30) read 1e-6 .. 2e-5 here; a chunk
    text that drops a factor or a mask reads 1e-2 and more."""
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    (_, out), got = value_and_gradients(form, chunk)(weight, *args)
    (_, want_out), want = value_and_gradients("recurrence", None)(
        weight, *args)
    assert np.isfinite(np.asarray(out)).all()
    close(out, want_out, rtol)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert np.isfinite(np.asarray(a)).all()
        try:
            close(a, b, rtol)
        except AssertionError as e:
            raise AssertionError(f"d{name}: {e}") from None


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("form", ["chunked", "kernels"])
def test_value_and_gradients_against_the_recurrence(form, case):
    t, chunk, (hk, hv), g_range, beta_range = CASES[case]
    against_the_recurrence(form, chunk, inputs(
        3, t=t, hk=hk, hv=hv, g_range=g_range, beta_range=beta_range))


@pytest.mark.parametrize("form", ["chunked", "kernels"])
def test_a_whole_chunk_at_minus_30_and_the_channel_form_s_route(form):
    """g = -30 at every position of a chunk of 32: ``exp(G_first - G_j)``
    of the channel form's route — ``g`` broadcast over a head's channels
    into ``ops/kda_scan.py`` — is ``exp(15 x 30)`` inside a sub-block,
    past float32, and ITS result is not finite; the head form's ``D`` is
    made of differences that are <= 0 and stays exact, value and
    gradients.  So the broadcast route is not what runs."""
    q, k, v, g, beta = inputs(5, t=64, hk=2, hv=2, dk=16, dv=8)
    g = g.at[:, :32].set(-30.0)
    against_the_recurrence(form, 32, (q, k, v, g, beta))
    channel = kda.kda_scan_chunked(
        q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, chunk=32)
    assert not np.isfinite(np.asarray(channel)).all()


@pytest.mark.parametrize("fault", ["bf16_decays", "bf16_state"])
def test_a_lower_precision_in_the_scan_s_float32_side_shows(fault):
    """``g`` rounded to bfloat16 before the chunk sums, or the state
    rounded to bfloat16 as a chunk hands it on (the two precision faults of
    ``benchmarks/tests/gradcheck_qwen3_next.py``): on float32 operands
    either reads a hundred times the clean comparison's rounding (relative
    L2 of ``o``: 5e-4 against 4e-7; slow decays, so that the state and the
    gates' sums matter)."""
    q, k, v, g, beta = inputs(13, t=64, g_range=(-0.5, 0.0))
    want = np.asarray(recurrence(q, k, v, g, beta))
    if fault == "bf16_decays":
        got = gdn.gdn_scan_chunked(
            q, k, v, jax.lax.reduce_precision(g, 8, 7), beta, chunk=16)
    else:
        text = gdn._head_forward

        def rounded(*args):
            o, state = text(*args)
            return o, state.astype(jnp.bfloat16).astype(jnp.float32)

        gdn._head_forward = rounded
        try:
            got = gdn.gdn_scan_chunked(q, k, v, g, beta, chunk=16)
        finally:
            gdn._head_forward = text
    clean = np.asarray(gdn.gdn_scan_chunked(q, k, v, g, beta, chunk=16))

    def rel_l2(x):
        return np.linalg.norm(np.asarray(x) - want) / np.linalg.norm(want)

    assert rel_l2(clean) < 2e-6
    assert rel_l2(got) > 1e-4


def test_the_result_does_not_depend_on_the_chunk():
    args = inputs(7, t=64, g_range=(-8.0, 0.0))
    outs = [np.asarray(gdn.gdn_scan_chunked(*args, chunk=c))
            for c in (8, 16, 32, 64)]
    for other in outs[1:]:
        np.testing.assert_allclose(other, outs[0], rtol=0, atol=2e-5)


def test_kernels_and_chunked_form_run_one_text_to_the_bit_in_bfloat16():
    """bfloat16 q, k, v (the chip's operands): the kernels under the
    interpreter and the chunked form run the same ``_group_forward``, so
    they agree to float32's summation order, and both stay within
    bfloat16's rounding of the float32 recurrence."""
    q, k, v, g, beta = inputs(11, t=64, g_range=(-20.0, 0.0))
    low = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    got = np.asarray(kernels(*low, g, beta, chunk=16), np.float32)
    same = np.asarray(gdn.gdn_scan_chunked(*low, g, beta, chunk=16),
                      np.float32)
    np.testing.assert_allclose(got, same, rtol=0, atol=1e-6)
    want = np.asarray(recurrence(*[x.astype(jnp.float32) for x in low], g,
                                 beta))
    close(got, want, 2e-2)


def test_q_and_k_are_not_repeated_and_the_gate_is_a_scalar_a_head():
    """What the kernels are handed: q and k at the KEY heads' width, ``G``
    twice at one number a position and value head — no [T, H_v, d] gate,
    no repeated q / k."""
    args = inputs(2, b=1, t=32, hk=2, hv=4, dk=16, dv=8)
    jaxpr = jax.make_jaxpr(functools.partial(kernels, chunk=16))(*args)

    def pallas_calls(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from pallas_calls(inner)

    (call,) = pallas_calls(jaxpr.jaxpr)
    shapes = [v.aval.shape for v in call.invars]
    assert shapes == [(1, 32, 32), (1, 32, 32), (1, 32, 32),   # q, k, v
                      (1, 2, 32, 2), (1, 2, 2, 2, 16),         # G twice
                      (1, 2, 32, 2)]                           # beta


def test_the_gauges_of_a_traced_call():
    import byteps_tpu as bps
    args = inputs(0, b=2, t=64, hk=2, hv=4, dk=16, dv=8)
    jax.eval_shape(functools.partial(kernels, chunk=16), *args)
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["gdn.heads"] == 4 and gauges["gdn.key_heads"] == 2
    assert gauges["gdn.chunk"] == 16 and gauges["gdn.chunks_per_seq"] == 4
    assert gauges["gdn.state_bytes"] == 4 * 4 * 16 * 8
    assert gauges["gdn.saved_state_bytes"] == 2 * 4 * 4 * 4 * 16 * 8
    # a key head's chunk: one stacked score product [2 C, d_k] x [C, d_k]
    # in float32 (the test's operands), then per value head the solve's
    # three-pass products and the five the algorithm needs
    assert gauges["gdn.matmul_operand_bytes_per_chunk"] > 0


def test_sizes_that_do_not_fit_are_refused():
    q, k, v, g, beta = inputs(0, t=32, hk=2, hv=4)
    with pytest.raises(ValueError, match="not a multiple of chunk"):
        kernels(q, k, v, g, beta, chunk=24)
    with pytest.raises(ValueError, match="do not divide over"):
        kernels(q, k, v[:, :, :3], g[..., :3], beta[..., :3], chunk=16)
    with pytest.raises(ValueError, match="wants"):
        kernels(q, k, v, g[..., None], beta, chunk=16)
    with pytest.raises(ValueError, match="whole lane tiles"):
        gdn.gdn_scan(q, k, v, g, beta, chunk=16, interpret=False)
