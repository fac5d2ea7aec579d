"""``ops/kda_rows.py``: the KDA mixer's row stages as kernels (under the
Pallas interpreter here) against the plain text they replace —
``causal_conv`` + ``silu`` + ``l2_normalize`` + ``log_decay`` in front of
the scan, the head RMSNorm times the gate's sigmoid behind it — values and
every gradient; and the same kernels in Gated DeltaNet's form (``qkv_pre``:
key heads under value heads, no decay slice; the gate's activation SiLU)
against ``models/qwen3_next.py`` ``gdn_pre``'s lines."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models.ling import l2_normalize, log_decay
from byteps_tpu.models.nemotron_h import causal_conv
from byteps_tpu.models.qwen3_next import Qwen3NextConfig, gdn_pre
from byteps_tpu.ops.kda_rows import kda_post, kda_pre, qkv_pre

LOWER, EPS, TAPS = -5.0, 1e-6, 4

# (B, T, H, d, rows a block): what each case is there for; H = (key heads,
# value heads) is the DeltaNet form
CASES = {
    "blocks_1head": (1, 24, 1, 16, 8),     # the taps cross two block edges
    "two_sequences": (2, 32, 2, 16, 16),   # sequence 1 starts on zeros
    "ragged_3heads": (2, 20, 3, 8, 8),     # T = 2.5 blocks, 3 steps of 1 head
    "ragged_halo16": (1, 40, 2, 16, 16),   # T = 2.5 blocks of 16 positions
    "one_block": (1, 16, 4, 8, 256),       # the block clipped to T, 2 steps
                                           # of 2 heads
    # q | k | v of 16 | 16 | 32 lanes in ONE column step; sequence 1 starts
    # on zeros; T = 2.5 blocks
    "gdn_ragged_2seq": (2, 20, (2, 4), 8, 8),
    # 32 | 32 | 64 in two column steps: k's, v's and the gate's first
    # blocks are 2, 2 and 4 of their own widths
    "gdn_2steps": (1, 24, (4, 8), 8, 8),
    "gdn_halo16": (2, 40, (2, 4), 16, 16),  # T = 2.5 blocks of 16 positions
}


def deltanet(case):
    return isinstance(CASES[case][2], tuple)


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


def plain_qkv_pre(heads, d, proj, conv_kernel):
    """``models/qwen3_next.py`` ``gdn_pre``'s q, k, v (its ``g`` and
    ``beta`` read the other projection and stay plain text in the model),
    and the gate's columns."""
    hk, hv = heads
    b, t, _ = proj.shape
    cfg = Qwen3NextConfig(
        linear_num_key_heads=hk, linear_num_value_heads=hv,
        linear_key_head_dim=d, linear_value_head_dim=d, dtype=proj.dtype)
    conv_dim = conv_kernel.shape[1]
    return (*gdn_pre(proj[..., :conv_dim], jnp.zeros((b, t, 2 * hv)),
                     conv_kernel, jnp.zeros(hv), jnp.zeros(hv), cfg)[:3],
            proj[..., conv_dim:])


def plain_post(o, gate, weight, act=jax.nn.sigmoid):
    b, t, heads, d = o.shape
    of = o.astype(jnp.float32)
    y = of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + EPS) * weight
    return (y.reshape(b, t, heads * d)
            * act(gate.astype(jnp.float32))).astype(o.dtype)


def inputs(case, dtype, seed=0):
    b, t, heads, d, _ = CASES[case]
    if deltanet(case):       # [ q | k | v | gate ], no decay, no beta
        keys, heads = heads[0], heads[1]
        conv, width = (2 * keys + heads) * d, 2 * (keys + heads) * d
    else:
        conv, width = 3 * heads * d, 5 * heads * d + heads
    inner = heads * d
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    return dict(
        proj=jax.random.normal(keys[0], (b, t, width)).astype(dtype),
        conv_kernel=0.5 * jax.random.normal(keys[1], (TAPS, conv)),
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
QKV_PRE = ("q k v gate".split(), "proj conv_kernel".split())
POST = (["y"], "o gate weight".split())


def stages(case):
    """(names, kernels, plain text, keys of the arguments in ``inputs``) of
    the stage in front of the scan and of the one behind it, in the
    case's form."""
    _, _, heads, d, rows = CASES[case]
    if deltanet(case):
        hk, hv = heads
        return {
            "pre": (QKV_PRE,
                    functools.partial(qkv_pre, key_heads=hk, value_heads=hv,
                                      head_dim=d, rows=rows),
                    functools.partial(plain_qkv_pre, heads, d),
                    ("proj", "conv_kernel")),
            "post": (POST,
                     functools.partial(kda_post, eps=EPS, rows=rows,
                                       gate_act="silu"),
                     functools.partial(plain_post, act=jax.nn.silu),
                     ("o", "gate", "weight"))}
    return {
        "pre": (PRE, functools.partial(kda_pre, lower_bound=LOWER, rows=rows),
                plain_pre, ("proj", "conv_kernel", "a_log", "dt_bias")),
        "post": (POST, functools.partial(kda_post, eps=EPS, rows=rows),
                 plain_post, ("o", "gate", "weight"))}


@functools.lru_cache(maxsize=None)
def both(case, dtype, seed=0):
    """Per stage: (names of outputs and arguments, the kernels' values and
    gradients, the plain text's); computed once a case."""
    x = inputs(case, dtype, seed)
    return {stage: (names,
                    values_and_grads(kernels, x["key"],
                                     tuple(x[a] for a in args)),
                    values_and_grads(plain, x["key"],
                                     tuple(x[a] for a in args)))
            for stage, (names, kernels, plain, args) in stages(case).items()}


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
@pytest.mark.parametrize("case", ["ragged_halo16", "gdn_halo16"])
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


@pytest.mark.parametrize("case", ["two_sequences", "gdn_ragged_2seq"])
def test_a_sequence_starts_on_zeros_not_on_its_neighbour(case):
    """Positions 0 .. 2 read zeros on their left — sequence 1's too, whose
    left neighbour in memory is sequence 0's last rows, and whose
    gradient must not reach them."""
    x = inputs(case, jnp.float32, seed=2)
    _, pre, _, args = stages(case)["pre"]
    proj = x["proj"]
    loud = proj.at[0, -3:].set(1e3)          # sequence 0's last three rows
    rest = tuple(x[a] for a in args[1:])

    @jax.jit
    def kernels(p):
        return pre(p, *rest)

    quiet = kernels(proj)
    for a, b in zip(quiet, kernels(loud)):
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    inner = x["gate"].shape[-1]                       # v's lanes: the last
    conv = x["conv_kernel"].shape[1]                  # of the taps' columns
    first = quiet[2][1, :3].reshape(3, inner)                     # v
    xv = proj[1, :3, conv - inner:conv]
    w = x["conv_kernel"][:, conv - inner:]
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
    # the DeltaNet form: the message names the widths it wants ...
    x = inputs("gdn_ragged_2seq", jnp.float32)
    heads = dict(key_heads=2, value_heads=4, head_dim=8)
    with pytest.raises(ValueError, match=r"qkv_pre: 2 key heads under 4 "
                       r"value heads of 8 want proj \[.., q 16 \| k 16 \| "
                       r"v 32 \| gate 32\] and conv_kernel \[.., 64\]"):
        qkv_pre(x["proj"][..., :-8], x["conv_kernel"], **heads)
    with pytest.raises(ValueError, match="qkv_pre: 2 key heads"):
        qkv_pre(x["proj"], x["conv_kernel"][:, :-8], **heads)
    with pytest.raises(ValueError, match="whole lane tiles"):
        qkv_pre(x["proj"], x["conv_kernel"], **heads, interpret=False)
    # ... and widths no one number of column steps cuts into whole blocks
    # (v's first lane, 16, is no multiple of its 24)
    with pytest.raises(ValueError, match=r"slices of 8 \| 8 \| 24 \| 24 "
                       r"lanes at heads of 8"):
        qkv_pre(jnp.zeros((1, 8, 64)), jnp.zeros((TAPS, 40)), key_heads=1,
                value_heads=3, head_dim=8)


plain_post_silu = functools.partial(plain_post, act=jax.nn.silu)


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
