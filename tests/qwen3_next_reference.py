"""Qwen3-Next in plain float32 ``jax.numpy``: what
``byteps_tpu/models/qwen3_next.py`` is tested against.  Written from the
equations of ISSUE 46 on the model's parameter tree and importing nothing
of the program.  ``benchmarks/families/qwen3_next.py`` carries a copy of
the text between the two ``reference`` marks
(``benchmarks/tests/test_qwen3_next_cell.py`` holds the two equal).

The delta rule is a ``lax.scan`` over POSITIONS on the [value heads, d_k,
d_v] state (no chunk algebra, no solve; value head h reads key head h //
(H_v / H_k)), nested in segments of 128 under ``jax.checkpoint`` so that
its backward keeps a state a segment and not one a position; a DeltaNet
mixer's key heads two at a time; exact softmax attention one (sequence,
head) and one block of 1 024 query rows at a time; the router a float32
softmax and ``lax.top_k``; the HELD experts one by one in their dense
form; the shared expert in blocks of rows; the head over blocks of 512
positions.  Each layer under ``jax.checkpoint``: blocking and
rematerialising change memory, not mathematics.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# --------------------------------------------------------------- reference

HEAD_BLOCK = 512          # positions per block of the vocabulary head
QUERY_BLOCK = 1024        # query rows per block of the exact attention
ROW_BLOCK = 2048          # rows per block of a SwiGLU
SCAN_SEGMENT = 128        # positions per rematerialised run of the recurrence
GDN_KEY_GROUP = 2         # key heads of a DeltaNet mixer computed at a time


def norm0(x, w, eps):
    """Zero-centred: ``x rsqrt(mean x^2 + eps) (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def norm1(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def delta_rule(q, k, v, g, beta):
    """q, k [B, T, H_k, d_k], v [B, T, H_v, d_v], g and beta [B, T, H_v]
    -> o [B, T, H_v, d_v]: position by position from a zero state, value
    head h on key head h // (H_v / H_k),
    ``S = exp(g) S;  S = S + beta k (v - S^T k)^T;  o = S^T q``."""
    bsz, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    seg = math.gcd(t, SCAN_SEGMENT)

    def position(state, at):                  # state [B, H_k, r, d_k, d_v]
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None, None] * state
        seen = jnp.einsum("bhk,bhrkv->bhrv", k_t, state)
        state = state + jnp.einsum(
            "bhk,bhrv->bhrkv", k_t, beta_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhk,bhrkv->bhrv", q_t, state)

    @jax.checkpoint
    def segment(state, run):
        return jax.lax.scan(position, state, run)

    def by_segment(x):                 # [B, T, ...] -> [T/seg, seg, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(t // seg, seg, *x.shape[1:])

    _, o = jax.lax.scan(
        segment, jnp.zeros((bsz, hk, r, dk, dv), jnp.float32),
        tuple(by_segment(x) for x in (
            q, k, v.reshape(bsz, t, hk, r, dv), g.reshape(bsz, t, hk, r),
            beta.reshape(bsz, t, hk, r))))
    return jnp.moveaxis(o.reshape(t, bsz, hv, dv), 0, 1)


def gdn(a, p, *, eps, key_heads):
    """a: [B, T, h] -> the Gated DeltaNet mixer's output.  A key head and
    its value heads meet no other between the projections and ``W_o``, so
    the key heads go ``GDN_KEY_GROUP`` at a time, each group under
    ``jax.checkpoint``, and their ``W_o`` products are summed: the same
    arithmetic, an eighth of the float32 rows alive."""
    bsz, t, h = a.shape
    hk, hv = key_heads, p["A_log"].shape[0]
    dv = p["o_norm"]["scale"].shape[0]
    r, size = hv // hk, math.gcd(hk, GDN_KEY_GROUP)
    wide = p["in_proj_qkvz"]["kernel"]               # [q | k | v | z]
    dk = (wide.shape[1] - 2 * hv * dv) // (2 * hk)
    ends = (hk * dk, 2 * hk * dk, 2 * hk * dk + hv * dv)
    taps = p["conv_kernel"].shape[0]

    def groups(x, axis):            # the key heads' axis -> [groups, size]
        x = x.reshape(*x.shape[:axis], hk // size, size,
                      *x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    def keys(x):                    # [.., H_k d_k] -> [groups, .., size, d_k]
        return groups(x.reshape(*x.shape[:-1], hk, dk), x.ndim - 1)

    def values(x):                  # [.., H_v d_v] -> [groups, .., size r, d_v]
        x = groups(x.reshape(*x.shape[:-1], hk, r * dv), x.ndim - 1)
        return x.reshape(*x.shape[:-2], size * r, dv)

    def conv_silu(x, w):
        # depthwise causal convolution: tap j reads position t - (K - 1) +
        # j, zeros before the sequence; no bias
        return jax.nn.silu(sum(
            w[j] * jnp.concatenate(
                [jnp.zeros_like(x[:, :taps - 1 - j]),
                 x[:, :t - (taps - 1 - j)]], axis=1)
            for j in range(taps)))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    @jax.checkpoint
    def one_group(w):
        wq, wk, wv, wz, wb, wa, cq, ck, cv, a_log, dt_bias, w_o = w
        q = conv_silu(jnp.einsum("bth,hnd->btnd", a, wq), cq)
        k = conv_silu(jnp.einsum("bth,hnd->btnd", a, wk), ck)
        v = conv_silu(jnp.einsum("bth,hnd->btnd", a, wv), cv)
        z = jnp.einsum("bth,hnd->btnd", a, wz)
        beta = jax.nn.sigmoid(a @ wb)                    # [B, T, size r]
        g = -jnp.exp(a_log) * jax.nn.softplus(a @ wa + dt_bias)
        o = delta_rule(unit(q) / math.sqrt(dk), unit(k), v, g, beta)
        # the norm over each head's channels, one weight for all heads
        y = norm1(o, p["o_norm"]["scale"], eps) * jax.nn.silu(z)
        return jnp.einsum("btnd,ndh->bth", y, w_o)

    def heads(x):                   # [.., H_v] -> [groups, .., size r]
        x = groups(x.reshape(*x.shape[:-1], hk, r), x.ndim - 1)
        return x.reshape(*x.shape[:-2], size * r)

    ba, conv = p["in_proj_ba"]["kernel"], p["conv_kernel"]
    return jax.lax.scan(lambda y, w: (y + one_group(w), None),
                        jnp.zeros_like(a), (
        keys(wide[:, :ends[0]]), keys(wide[:, ends[0]:ends[1]]),
        values(wide[:, ends[1]:ends[2]]), values(wide[:, ends[2]:]),
        heads(ba[:, :hv]), heads(ba[:, hv:]),
        keys(conv[:, :ends[0]]), keys(conv[:, ends[0]:ends[1]]),
        values(conv[:, ends[1]:]), heads(p["A_log"]), heads(p["dt_bias"]),
        jnp.moveaxis(values(jnp.moveaxis(p["o_proj"]["kernel"], 0, 1)),
                     1, -1)))[0]


def rotate(x, theta):
    """Rotate-half over the whole last axis of x [B, T, ..., r] at
    positions 0 .. T - 1."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape(1, x.shape[1], *[1] * (x.ndim - 3), r // 2)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def causal_softmax_attention(q, k, v):
    """q, k: [T, D], v: [T, Dv] of one sequence and head -> [T, Dv]: exact
    softmax over keys j <= i at scale 1/sqrt(D), one block of query rows
    at a time."""
    t, d = q.shape
    rows = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(q1, first_row):                # [rows, D]
        i = first_row + jnp.arange(rows)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= i,
                      q1 @ k.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(s, -1) @ v

    return jax.lax.map(lambda blk: one_block(*blk),
                       (q.reshape(t // rows, rows, d),
                        jnp.arange(0, t, rows))).reshape(t, -1)


def attention(a, p, *, eps, rotary, theta):
    """a: [B, T, h] -> gated softmax attention: a head's query and its
    gate from one projection, zero-centred q / k norms, the first
    ``rotary`` lanes turned, query head n on key/value head n // (H /
    H_kv), the output times the gate's sigmoid.  One (sequence, head) at a
    time."""
    qg = jnp.einsum("bth,hnd->btnd", a, p["q_proj"]["kernel"])
    d = qg.shape[-1] // 2
    q, gamma = qg[..., :d], qg[..., d:]
    k = jnp.einsum("bth,hnd->btnd", a, p["k_proj"]["kernel"])
    v = jnp.einsum("bth,hnd->btnd", a, p["v_proj"]["kernel"])
    q = norm0(q, p["q_norm"]["scale"], eps)
    k = norm0(k, p["k_norm"]["scale"], eps)
    q = jnp.concatenate([rotate(q[..., :rotary], theta), q[..., rotary:]], -1)
    k = jnp.concatenate([rotate(k[..., :rotary], theta), k[..., rotary:]], -1)
    per = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, per, axis=2), jnp.repeat(v, per, axis=2)
    one_head = jax.checkpoint(causal_softmax_attention)
    ctx = jax.lax.map(
        lambda seq: jax.lax.map(lambda qkv: one_head(*qkv), seq),
        tuple(jnp.moveaxis(x, 2, 1) for x in (q, k, v)))   # [B, H, T, D]
    ctx = jnp.moveaxis(ctx, 1, 2) * jax.nn.sigmoid(gamma)
    return jnp.einsum("btnd,ndh->bth", ctx, p["o_proj"]["kernel"])


def in_row_blocks(fn, m):
    """``fn`` over blocks of ``ROW_BLOCK`` rows of m [N, h], each under
    ``jax.checkpoint``."""
    n, h = m.shape
    rows = math.gcd(n, ROW_BLOCK)
    return jax.lax.map(jax.checkpoint(fn),
                       m.reshape(n // rows, rows, h)).reshape(n, -1)


def swiglu(m, p):
    return in_row_blocks(
        lambda mb: (jax.nn.silu(mb @ p["gate_proj"]["kernel"])
                    * (mb @ p["up_proj"]["kernel"]))
        @ p["down_proj"]["kernel"], m)


def sparse_moe(m, p, *, held, top_k, renormalize):
    """m: [N, h] -> the held routed experts' part of the sum plus the
    shared expert behind its gate (whole on every chip: counted once)."""
    first, count = held
    prob = jax.nn.softmax(m @ p["router"], -1)           # [N, E]
    _, experts = jax.lax.top_k(prob, top_k)
    picked = (jnp.arange(prob.shape[-1]) == experts[..., None]).any(-2)
    weight = jnp.where(picked, prob, 0.0)
    if renormalize:
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight[:, first:first + count]              # the experts held

    @jax.checkpoint
    def one_expert(m, gate, up, down, w_e):
        return w_e[:, None] * ((jax.nn.silu(m @ gate) * (m @ up)) @ down)

    # one by one: a scan over the held experts' matrices
    routed, _ = jax.lax.scan(
        lambda routed, e: (routed + one_expert(m, *e), None),
        jnp.zeros_like(m), (p["gate"], p["up"], p["down"], weight.T))
    gate = jax.nn.sigmoid(m @ p["shared_expert_gate"]["kernel"])   # [N, 1]
    return routed + gate * swiglu(m, p["shared_expert"])


def mixer(x, p, *, model):
    """``x + mixer(N0(x))``: the mixer is what the parameters are."""
    eps = model["eps"]
    a = norm0(x, p["input_layernorm"]["scale"], eps)
    if "mixer_gdn" in p:
        return x + gdn(a, p["mixer_gdn"], eps=eps,
                       key_heads=model["key_heads"])
    return x + attention(a, p["attn"], eps=eps, rotary=model["rotary"],
                         theta=model["theta"])


def mlp(x, p, *, model):
    """``x + moe(N0(x))``."""
    bsz, t, h = x.shape
    m = norm0(x, p["post_attention_layernorm"]["scale"], model["eps"]
              ).reshape(bsz * t, h)
    y = sparse_moe(m, p["moe"], held=model["held"], top_k=model["top_k"],
                   renormalize=model["renormalize"])
    return x + y.reshape(bsz, t, h)


def layer(x, p, *, model):
    """One layer; each half under a ``jax.checkpoint`` of its own, so that
    the layer's backward holds one half's intermediates at a time."""
    x = jax.checkpoint(functools.partial(mixer, model=model))(x, p)
    return jax.checkpoint(functools.partial(mlp, model=model))(x, p)


def reference_hidden(params, ids, **model):
    """-> the rows the head reads, [B, T, h].  No layer mixes sequences,
    so they go one at a time, each under ``jax.checkpoint``: the float32
    intermediates of ONE sequence are alive, whatever the batch."""
    p = params["params"]

    @jax.checkpoint
    def one_sequence(ids):                           # [T]
        x = p["wte"]["embedding"][ids[None]]
        step = jax.checkpoint(functools.partial(layer, model=model))
        i = 0
        while f"h{i}" in p:
            x = step(x, p[f"h{i}"])
            i += 1
        return norm0(x, p["norm_f"]["scale"], model["eps"])[0]

    return jax.lax.map(one_sequence, ids)


def head_nll(x, head, labels):
    """Mean negative log-likelihood of ``labels`` (-1: no label) under
    ``x head`` (head [h, V]), over blocks of positions (x: [N, h], labels:
    [N])."""
    n = x.shape[0]
    rows = math.gcd(n, HEAD_BLOCK)

    @jax.checkpoint
    def one_block(xl):
        xb, lb = xl
        valid = lb >= 0
        logp = jax.nn.log_softmax(xb @ head, -1)
        ll = jnp.take_along_axis(logp, jnp.where(valid, lb, 0)[:, None],
                                 -1)[:, 0]
        return -(ll * valid).sum(), valid.sum()

    nll, count = jax.lax.map(one_block, (x.reshape(n // rows, rows, -1),
                                         labels.reshape(n // rows, rows)))
    return nll.sum() / count.sum()


def reference_loss(params, batch, **model):
    """Cross-entropy of the next token over the rows held.  Departures: no
    auxiliary loss, no multi-token-prediction module."""
    with jax.default_matmul_precision("highest"):
        x = reference_hidden(params, batch["input_ids"], **model)
        b, t, h = x.shape
        return head_nll(x.reshape(b * t, h), params["params"]["lm_head"],
                        batch["labels"].reshape(b * t))

# ----------------------------------------------------------- end reference


def model_of(cfg) -> dict:
    """The reference's keyword arguments for a ``Qwen3NextConfig``."""
    return dict(eps=cfg.rms_norm_eps, key_heads=cfg.linear_num_key_heads,
                rotary=cfg.rotary_dim, theta=float(cfg.rope_theta),
                held=cfg.held, top_k=cfg.num_experts_per_tok,
                renormalize=cfg.norm_topk_prob)
