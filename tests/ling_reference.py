"""Ling-3.0-flash in plain float32 ``jax.numpy``: what
``byteps_tpu/models/ling.py`` is tested against.  Written from the
equations of ISSUE 43 on the model's parameter tree and importing nothing
of the program.  ``benchmarks/families/ling.py`` carries a copy of the
text between the two ``reference`` marks
(``benchmarks/tests/test_ling_cell.py`` holds the two equal).

The delta rule is a ``lax.scan`` over POSITIONS on the [heads, d_k, d_v]
state (no chunk algebra, no solve), nested in segments of 128 under
``jax.checkpoint`` so that its backward keeps a state a segment and not
one a position (2 MiB each at 32 heads of 128 x 128); exact softmax
attention one (sequence, head) and one block of 1 024 query rows at a
time, v at its own width; the group-limited router in plain code (a
group's two largest by ``lax.top_k``, the groups by ``lax.top_k``, the
experts by ``lax.top_k``); the HELD experts one by one in their dense
form; the shared expert and the dense MLPs in blocks of rows; the head
over blocks of 512 positions.  Each layer under ``jax.checkpoint``:
blocking and rematerialising change memory, not mathematics.
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
KDA_HEAD_GROUP = 4        # heads of a KDA mixer computed at a time


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def delta_rule(q, k, v, g, beta):
    """q, k, g [B, T, H, d_k], v [B, T, H, d_v], beta [B, T, H] -> o
    [B, T, H, d_v]: position by position from a zero state,
    ``S = Diag(exp(g)) S;  S = S + beta k (v - S^T k)^T;  o = S^T q``."""
    bsz, t, h, dk = q.shape
    seg = math.gcd(t, SCAN_SEGMENT)

    def position(state, at):                       # state [B, H, d_k, d_v]
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    @jax.checkpoint
    def segment(state, run):
        return jax.lax.scan(position, state, run)

    def by_segment(x):                 # [B, T, ...] -> [T/seg, seg, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(t // seg, seg, *x.shape[1:])

    _, o = jax.lax.scan(
        segment, jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32),
        tuple(by_segment(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(t, bsz, h, -1), 0, 1)


def kda(a, p, *, eps, lower_bound):
    """a: [B, T, h] -> the Kimi Delta Attention mixer's output (heads and
    head size read off ``dt_bias``).  A head meets no other head between
    the projections and ``W_o``, so the heads go ``KDA_HEAD_GROUP`` at a
    time, each group under ``jax.checkpoint``, and their ``W_o`` products
    are summed: the same arithmetic, an eighth of the float32 rows alive."""
    bsz, t, h = a.shape
    heads, d = p["dt_bias"].shape
    inner, size = heads * d, math.gcd(heads, KDA_HEAD_GROUP)
    taps = p["conv_kernel"].shape[0]
    kernel = p["in_proj"]["kernel"]     # [q | k | v | f | output gate | beta]

    def groups(x, axis):                # the heads' axis -> [groups, size]
        x = x.reshape(*x.shape[:axis], heads // size, size,
                      *x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    @jax.checkpoint
    def one_group(w):
        wide, w_beta, conv_kernel, a_log, dt_bias, w_o = w
        proj = jnp.einsum("bth,hjnd->btjnd", a, wide)    # [B, T, 5, size, d]
        qkv = proj[:, :, :3]
        # depthwise causal convolution: tap j reads position t - (K - 1) +
        # j, zeros before the sequence; no bias
        conv = sum(
            conv_kernel[j] * jnp.concatenate(
                [jnp.zeros_like(qkv[:, :taps - 1 - j]),
                 qkv[:, :t - (taps - 1 - j)]], axis=1)
            for j in range(taps))
        qkv = jax.nn.silu(conv)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

        def unit(x):
            return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

        g = lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None] * (proj[:, :, 3] + dt_bias))
        beta = jax.nn.sigmoid(a @ w_beta)                # [B, T, size]
        o = delta_rule(unit(q) / math.sqrt(d), unit(k), v, g, beta)
        # the norm over each head's channels, one weight for all heads
        y = rms_norm(o, p["o_norm"]["scale"], eps)
        return jnp.einsum("btnd,ndh->bth", y * jax.nn.sigmoid(proj[:, :, 4]),
                          w_o)

    return jax.lax.scan(lambda y, w: (y + one_group(w), None),
                        jnp.zeros_like(a), (
        groups(kernel[:, :5 * inner].reshape(h, 5, heads, d), 2),
        groups(kernel[:, 5 * inner:], 1),
        groups(p["conv_kernel"].reshape(taps, 3, heads, d), 2),
        groups(p["A_log"], 0), groups(p["dt_bias"], 0),
        groups(p["o_proj"]["kernel"].reshape(heads, d, h), 0)))[0]


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


def mla(a, p, *, eps, rank, nope, theta):
    """a: [B, T, h] -> latent attention without a query latent, gated a
    head.  One (sequence, head) at a time."""
    q = jnp.einsum("bth,hnd->btnd", a, p["q_proj"]["kernel"])
    ckv = a @ p["kv_a_proj_with_mqa"]["kernel"]
    c = rms_norm(ckv[..., :rank], p["kv_a_layernorm"]["scale"], eps)
    kv = jnp.einsum("btr,rnd->btnd", c, p["kv_b_proj"]["kernel"])
    heads = q.shape[2]
    k_rope = rotate(ckv[..., rank:], theta)          # ONE key, every head's
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope[:, :, None], k_rope.shape[:2]
                          + (heads, k_rope.shape[-1]))], -1)
    v = kv[..., nope:]
    one_head = jax.checkpoint(causal_softmax_attention)
    ctx = jax.lax.map(
        lambda seq: jax.lax.map(lambda qkv: one_head(*qkv), seq),
        tuple(jnp.moveaxis(x, 2, 1) for x in (q, k, v)))   # [B, H, T, dv]
    gate = jax.nn.sigmoid(a @ p["g_proj"]["kernel"])       # [B, T, H]
    ctx = jnp.moveaxis(ctx, 1, 2) * gate[..., None]
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


def chosen_experts(scores, bias, *, n_group, topk_group, top_k):
    """[N, E] bool: the ``top_k`` largest ``scores + bias`` inside each
    token's ``topk_group`` groups of largest group score (the sum of a
    group's two largest ``scores + bias``)."""
    n, e = scores.shape
    c = scores + jax.lax.stop_gradient(bias)
    two, _ = jax.lax.top_k(c.reshape(n, n_group, e // n_group), 2)
    _, groups = jax.lax.top_k(two.sum(-1), topk_group)
    kept = (jnp.arange(n_group) == groups[..., None]).any(-2)   # [N, groups]
    inside = jnp.repeat(kept, e // n_group, axis=1)
    _, experts = jax.lax.top_k(jnp.where(inside, c, -jnp.inf), top_k)
    return (jnp.arange(e) == experts[..., None]).any(-2)


def sparse_moe(m, p, *, held, n_group, topk_group, top_k, scaling,
               renormalize):
    """m: [N, h] -> the held routed experts' part of the sum (scaled) plus
    the shared expert (whole on every chip: counted once)."""
    first, count = held
    scores = jax.nn.sigmoid(m @ p["router"])             # [N, E]
    # departure: the bias is the zeros it starts as; it chooses only
    picked = chosen_experts(scores, p["expert_bias"], n_group=n_group,
                            topk_group=topk_group, top_k=top_k)
    weight = jnp.where(picked, scores, 0.0)
    if renormalize:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = scaling * weight[:, first:first + count]    # the experts held

    @jax.checkpoint
    def one_expert(m, gate, up, down, w_e):
        return w_e[:, None] * ((jax.nn.silu(m @ gate) * (m @ up)) @ down)

    # one by one: a scan over the held experts' matrices
    routed, _ = jax.lax.scan(
        lambda routed, e: (routed + one_expert(m, *e), None),
        jnp.zeros_like(m), (p["gate"], p["up"], p["down"], weight.T))
    return routed + swiglu(m, p["shared_expert"])


def mixer(x, p, *, model):
    """``x + mixer(RMSNorm(x))``: the mixer is what the parameters are."""
    eps = model["eps"]
    a = rms_norm(x, p["input_layernorm"]["scale"], eps)
    if "mixer_kda" in p:
        return x + kda(a, p["mixer_kda"], eps=eps,
                       lower_bound=model["lower_bound"])
    return x + mla(a, p["attn_mla"], eps=eps, rank=model["rank"],
                   nope=model["nope"], theta=model["theta"])


def mlp(x, p, *, model):
    """``x + mlp(RMSNorm(x))``: dense or sparse, as the parameters are."""
    bsz, t, h = x.shape
    m = rms_norm(x, p["post_attention_layernorm"]["scale"], model["eps"]
                 ).reshape(bsz * t, h)
    if "mlp" in p:
        y = swiglu(m, p["mlp"])
    else:
        y = sparse_moe(m, p["moe"], held=model["held"],
                       n_group=model["n_group"],
                       topk_group=model["topk_group"], top_k=model["top_k"],
                       scaling=model["scaling"],
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
        return rms_norm(x, p["norm_f"]["scale"], model["eps"])[0]

    return jax.lax.map(one_sequence, ids)


def head_nll(x, head, labels):
    """Mean negative log-likelihood of ``labels`` (-1: no label) under
    ``x head^T``, over blocks of positions (x: [N, h], labels: [N])."""
    n = x.shape[0]
    rows = math.gcd(n, HEAD_BLOCK)

    @jax.checkpoint
    def one_block(xl):
        xb, lb = xl
        valid = lb >= 0
        logp = jax.nn.log_softmax(jnp.einsum("nh,vh->nv", xb, head), -1)
        ll = jnp.take_along_axis(logp, jnp.where(valid, lb, 0)[:, None],
                                 -1)[:, 0]
        return -(ll * valid).sum(), valid.sum()

    nll, count = jax.lax.map(one_block, (x.reshape(n // rows, rows, -1),
                                         labels.reshape(n // rows, rows)))
    return nll.sum() / count.sum()


def reference_loss(params, batch, **model):
    """Cross-entropy of the next token over the rows held.  Departures: no
    auxiliary loss, no z-loss, no multi-token-prediction module."""
    with jax.default_matmul_precision("highest"):
        x = reference_hidden(params, batch["input_ids"], **model)
        b, t, h = x.shape
        return head_nll(x.reshape(b * t, h), params["params"]["lm_head"],
                        batch["labels"].reshape(b * t))

# ----------------------------------------------------------- end reference


def model_of(cfg) -> dict:
    """The reference's keyword arguments for a ``LingConfig``."""
    return dict(eps=cfg.rms_norm_eps, lower_bound=float(cfg.kda_lower_bound),
                rank=cfg.kv_lora_rank, nope=cfg.qk_nope_head_dim,
                theta=float(cfg.rope_theta), held=cfg.held,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                top_k=cfg.num_experts_per_tok,
                scaling=float(cfg.routed_scaling_factor),
                renormalize=cfg.norm_topk_prob)
