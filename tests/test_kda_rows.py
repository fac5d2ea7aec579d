"""``ops/kda_rows.py``: the KDA mixer's row stages as kernels (under the
Pallas interpreter here) against the plain text they replace —
``causal_conv`` + ``silu`` + ``l2_normalize`` + ``log_decay`` in front of
the scan, the head RMSNorm times the gate's sigmoid behind it — values and
every gradient."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models.ling import l2_normalize, log_decay
from byteps_tpu.models.nemotron_h import causal_conv
from byteps_tpu.ops.kda_rows import kda_post, kda_pre

LOWER, EPS, TAPS = -5.0, 1e-6, 4

# (B, T, H, d, rows a block): what each case is there for
CASES = {
    "blocks_1head": (1, 24, 1, 16, 8),     # the taps cross two block edges
    "two_sequences": (2, 32, 2, 16, 16),   # sequence 1 starts on zeros
    "ragged_3heads": (2, 20, 3, 8, 8),     # T = 2.5 blocks, 3 steps of 1 head
    "ragged_halo16": (1, 40, 2, 16, 16),   # T = 2.5 blocks of 16 positions
    "one_block": (1, 16, 4, 8, 256),       # the block clipped to T, 2 steps
                                           # of 2 heads
}


def plain_pre(proj, conv_kernel, a_log, dt_bias):
    heads, d = dt_bias.shape
    inner = heads * d
    b, t, _ = proj.shape
    qkv = jax.nn.silu(causal_conv(
        proj[..., :3 * inner].astype(jnp.float32), conv_kernel, 0.0))
    qkv = qkv.reshape(b, t, 3, heads, d)
    q = (l2_normalize(qkv[:, :, 0]) / math.sqrt(d)).astype(proj.dtype)
    k = l2_normalize(qkv[:, :, 1]).astype(proj.dtype)
    v = qkv[:, :, 2].astype(proj.dtype)
    g = log_decay(proj[..., 3 * inner:4 * inner].reshape(b, t, heads, d),
                  a_log, dt_bias, LOWER)
    beta = jax.nn.sigmoid(proj[..., 5 * inner:].astype(jnp.float32))
    return q, k, v, g, beta, proj[..., 4 * inner:5 * inner]


def plain_post(o, gate, weight):
    b, t, heads, d = o.shape
    of = o.astype(jnp.float32)
    y = of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + EPS) * weight
    return (y.reshape(b, t, heads * d)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)


def inputs(case, dtype, seed=0):
    b, t, heads, d, _ = CASES[case]
    inner = heads * d
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    return dict(
        proj=jax.random.normal(keys[0], (b, t, 5 * inner + heads)
                               ).astype(dtype),
        conv_kernel=0.5 * jax.random.normal(keys[1], (TAPS, 3 * inner)),
        a_log=jnp.log(jax.random.uniform(keys[2], (heads,), minval=1.0,
                                         maxval=4.0)),
        dt_bias=jax.random.normal(keys[3], (heads, d)),
        o=jax.random.normal(keys[4], (b, t, heads, d)).astype(dtype),
        gate=jax.random.normal(keys[5], (b, t, inner)).astype(dtype),
        weight=1.0 + 0.1 * jax.random.normal(keys[6], (d,)),
        key=keys[7])


def values_and_grads(fn, key, args):
    """(fn's outputs, the gradient by every argument of the outputs' sum
    under fixed random weights — so one gradient reads every output's
    cotangent), one compiled program."""
    draw = np.random.default_rng(np.asarray(key).tolist())

    def loss(*a):
        outs = fn(*a)
        outs = outs if isinstance(outs, tuple) else (outs,)
        return sum(jnp.sum(draw.standard_normal(o.shape, np.float32)
                           * o.astype(jnp.float32)) for o in outs), outs

    (_, outs), grads = jax.jit(jax.value_and_grad(
        loss, tuple(range(len(args))), has_aux=True))(*args)
    return outs, grads


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


PRE = ("q k v g beta gate".split(), "proj conv_kernel A_log dt_bias".split())
POST = (["y"], "o gate weight".split())


@functools.lru_cache(maxsize=None)
def both(case, dtype, seed=0):
    """Per stage: (names of outputs and arguments, the kernels' values and
    gradients, the plain text's); computed once a case."""
    rows = CASES[case][4]
    x = inputs(case, dtype, seed)

    def pre(*a):
        return kda_pre(*a, lower_bound=LOWER, rows=rows)

    def post(*a):
        return kda_post(*a, eps=EPS, rows=rows)

    pre_args = (x["proj"], x["conv_kernel"], x["a_log"], x["dt_bias"])
    post_args = (x["o"], x["gate"], x["weight"])
    return {"pre": (PRE, values_and_grads(pre, x["key"], pre_args),
                    values_and_grads(plain_pre, x["key"], pre_args)),
            "post": (POST, values_and_grads(post, x["key"], post_args),
                     values_and_grads(plain_post, x["key"], post_args))}


@pytest.mark.parametrize("stage", ["pre", "post"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_the_plain_text_float32(case, stage):
    (outs, args), got, want = both(case, jnp.float32)[stage]
    for name, a, b in zip(outs, got[0], want[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-6, err_msg=name)
    for name, a, b in zip(args, got[1], want[1]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel(a, b) < 2e-5, (name, rel(a, b))


@pytest.mark.parametrize("stage", ["pre", "post"])
@pytest.mark.parametrize("case", ["ragged_halo16"])
def test_bfloat16_rows_round_where_the_plain_text_rounds(case, stage):
    """bfloat16 ``proj`` / ``o``: float32 inside, so q, k, v and y are the
    plain text's to a rounding of bfloat16 (2^-8), ``g`` and ``beta`` to
    float32's, the gate's copy exact, and the gradients to the rounding of
    the cotangents."""
    (outs, args), got, want = both(case, jnp.bfloat16, seed=1)[stage]
    for name, a, b in zip(outs, got[0], want[0]):
        assert a.dtype == b.dtype, name
        tight = name in ("g", "beta", "gate")
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-5 if tight else 2 ** -7, atol=2e-6 if tight else 1e-6,
            err_msg=name)
    for name, a, b in zip(args, got[1], want[1]):
        assert a.dtype == b.dtype, name
        assert rel(a, b) < 1e-2, (name, rel(a, b))


def test_a_sequence_starts_on_zeros_not_on_its_neighbour():
    """Positions 0 .. 2 read zeros on their left — sequence 1's too, whose
    left neighbour in memory is sequence 0's last rows, and whose
    gradient must not reach them."""
    rows = CASES["two_sequences"][4]
    x = inputs("two_sequences", jnp.float32, seed=2)
    proj = x["proj"]
    loud = proj.at[0, -3:].set(1e3)          # sequence 0's last three rows
    rest = (x["conv_kernel"], x["a_log"], x["dt_bias"])

    @jax.jit
    def kernels(p):
        return kda_pre(p, *rest, lower_bound=LOWER, rows=rows)

    quiet = kernels(proj)
    for a, b in zip(quiet, kernels(loud)):
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    inner = x["gate"].shape[-1]
    first = quiet[2][1, :3].reshape(3, inner)                     # v
    xv = proj[1, :3, 2 * inner:3 * inner]
    w = x["conv_kernel"][:, 2 * inner:]
    by_hand = jnp.stack([sum(w[TAPS - 1 - j] * xv[t - j]
                             for j in range(t + 1)) for t in range(3)])
    np.testing.assert_allclose(np.asarray(first),
                               np.asarray(jax.nn.silu(by_hand)), rtol=2e-5,
                               atol=2e-6)

    def only_sequence_1(p):
        return sum(jnp.sum(o[1].astype(jnp.float32) ** 2)
                   for o in kernels(p)[:3])

    grad = jax.jit(jax.grad(only_sequence_1))(proj)
    assert not np.asarray(grad[0]).any()
    assert np.asarray(grad[1, 0]).any()


def test_sizes_that_do_not_fit_are_refused():
    x = inputs("blocks_1head", jnp.float32)
    with pytest.raises(ValueError, match="kda_pre: 1 heads of 16 want"):
        kda_pre(x["proj"][..., :-1], x["conv_kernel"], x["a_log"],
                x["dt_bias"], lower_bound=LOWER)
    with pytest.raises(ValueError, match="whole lane tiles"):
        kda_pre(x["proj"], x["conv_kernel"], x["a_log"], x["dt_bias"],
                lower_bound=LOWER, interpret=False)
    with pytest.raises(ValueError, match="kda_post: o"):
        kda_post(x["o"], x["gate"][..., :-1], x["weight"], eps=EPS)
    with pytest.raises(ValueError, match="whole lane tiles"):
        kda_post(x["o"], x["gate"], x["weight"], eps=EPS, interpret=False)


def plain_post_silu(o, gate, weight):
    """``plain_post`` with the gate's activation SiLU (Gated DeltaNet's
    output stage, ``models/qwen3_next.py``)."""
    b, t, heads, d = o.shape
    of = o.astype(jnp.float32)
    y = of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + EPS) * weight
    return (y.reshape(b, t, heads * d)
            * jax.nn.silu(gate.astype(jnp.float32))).astype(o.dtype)


@pytest.mark.parametrize("gate_act", ["sigmoid", "silu"])
@pytest.mark.parametrize("case", ["two_sequences", "ragged_3heads"])
def test_post_takes_the_gate_s_activation_as_an_argument(case, gate_act):
    """``kda_post(gate_act=...)``: the sigmoid (the default, Kimi Delta
    Attention) and SiLU (Gated DeltaNet), values and the gradients by
    ``o``, the gate and the weight, against the plain text; the default is
    the kernel the existing cases run, to the bit."""
    rows = CASES[case][4]
    x = inputs(case, jnp.float32, seed=3)
    args = (x["o"], x["gate"], x["weight"])
    plain = plain_post if gate_act == "sigmoid" else plain_post_silu
    got = values_and_grads(
        lambda *a: kda_post(*a, eps=EPS, rows=rows, gate_act=gate_act),
        x["key"], args)
    want = values_and_grads(plain, x["key"], args)
    np.testing.assert_allclose(np.asarray(got[0][0]), np.asarray(want[0][0]),
                               rtol=2e-5, atol=2e-6)
    for name, a, b in zip(POST[1], got[1], want[1]):
        assert rel(a, b) < 2e-5, (name, rel(a, b))
    if gate_act == "sigmoid":
        default = kda_post(*args, eps=EPS, rows=rows)
        np.testing.assert_array_equal(np.asarray(default),
                                      np.asarray(got[0][0]))
    with pytest.raises(ValueError, match="gate_act"):
        kda_post(*args, eps=EPS, gate_act="tanh")
