"""Plain reference for ``byteps_tpu/models/glm_lite.py``: GLM-4.7-Flash's
forward pass and loss in float32 ``jax.numpy`` on the model's own parameter
tree, written from the equations of ISSUE 35 (``zai-org/GLM-4.7-Flash``'s
``config.json``, whose keys are DeepSeek-V3's and mean what that model's
report, arXiv:2412.19437 sections 2.1-2.2, says) and importing nothing of
the program.  Callers set ``jax.default_matmul_precision("highest")``;
:func:`loss` and :func:`logits` set it themselves.

Per block, T positions: RMSNorm; the query through its low-rank latent and
the latent's own norm; ONE projection cut into the key/value latent
(normed) and the rotary key (not normed, turned, ONE for all heads); keys
and values up-projected a head; q = [q_nope | rot(q_rope)] and k = [k_nope
| the rotary key repeated over the heads] by explicit concatenation; a
dense [T, T] masked softmax at scale 1/sqrt(nope + rope); the output
projection; RMSNorm; then the dense SwiGLU where the block's parameters
hold one (``mlp``), else sigmoid scores over ALL routed experts, the
``top_k`` largest of ``score + bias``, their scores renormalised (+1e-20)
and scaled, a loop over the HELD experts in their DENSE form (each on
every token, times its weight or zero), the shared expert added once.
``held=(first, count)`` says which experts the ``count`` stacks are
(``None``: all).  The multi-token-prediction module: both norms, the
concatenation ``[h ; Emb(next token)]`` through ``eh_proj``, one more
sparse block, its own last norm, the MAIN head; ids and labels shifted
explicitly.

Departures from the published model, each at its line below: (1) the
selection bias stays the zeros it starts as (the report's balancing rule
is outside the gradient and has no key); (2) no auxiliary or z-loss stands
in for it; (3) lambda is the report's first-phase 0.3 (the caller's
``mtp_weight``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotate(x, theta, positions=None):
    """x: [B, T, H, R]; pairs (x[i], x[i + R/2]) turned by pos theta^(-2i/R)
    (rotate-half over the whole slice); ``positions`` [T] default 0..T-1."""
    t, rot = x.shape[1], x.shape[-1]
    if positions is None:
        positions = jnp.arange(t)
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def latent_qkv(a, p, *, nope, theta, eps, positions=None):
    """-> q, k [B, T, H, nope + rope] and v [B, T, H, v] of one block."""
    heads = p["q_b_proj"]["kernel"].shape[1]
    rank = p["kv_a_layernorm"]["scale"].shape[0]
    c_q = rms_norm(a @ p["q_a_proj"]["kernel"], p["q_a_layernorm"]["scale"],
                   eps)
    q = jnp.einsum("btr,rnd->btnd", c_q, p["q_b_proj"]["kernel"])
    ckv = a @ p["kv_a_proj_with_mqa"]["kernel"]       # [B, T, rank + rope]
    c_kv = rms_norm(ckv[..., :rank], p["kv_a_layernorm"]["scale"], eps)
    k_rope = rotate(ckv[:, :, None, rank:], theta, positions)  # ONE key
    kv = jnp.einsum("btr,rnd->btnd", c_kv, p["kv_b_proj"]["kernel"])
    q = jnp.concatenate(
        [q[..., :nope], rotate(q[..., nope:], theta, positions)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_rope, heads, axis=2)],
                        -1)
    return q, k, kv[..., nope:]


def latent_attention(a, p, **kw):
    q, k, v = latent_qkv(a, p, **kw)
    t, d = q.shape[1], q.shape[-1]
    s = jnp.einsum("bind,bjnd->bnij", q, k) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ctx = jnp.einsum("bnij,bjnd->bind", jax.nn.softmax(s, -1), v)
    return jnp.einsum("btnd,ndh->bth", ctx, p["o_proj"]["kernel"])


def swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def routed_weights(m, p, *, top_k, scaling, renormalize):
    """m: [N, h] -> (w [N, E], counts [E]): the weight of every routed
    expert on every token (zero where it was not chosen)."""
    scores = jax.nn.sigmoid(m @ p["router"])
    e = scores.shape[-1]
    # departure (1): the bias is whatever the tree holds (zeros in the
    # program: no rule moves it); it chooses only and is not weighed
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["e_score_correction_bias"]), top_k)
    picked = (jnp.arange(e) == chosen[..., None]).any(-2)
    w = jnp.where(picked, scores, 0.0)
    if renormalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return scaling * w, picked.sum(0).astype(jnp.int32)


def routed_experts(m, p, *, held=None, **kw):
    """The held routed experts' part of the (scaled) routed sum, and the
    pair counts of ALL experts."""
    w, counts = routed_weights(m, p, **kw)
    first, count = held or (0, w.shape[-1])
    y = jnp.zeros_like(m)
    for i in range(count):
        y = y + w[:, first + i, None] * swiglu(
            m, p["gate"][i], p["up"][i], p["down"][i])
    return y, counts


def shared_expert(m, p):
    s = p["shared_experts"]
    return swiglu(m, s["gate_proj"]["kernel"], s["up_proj"]["kernel"],
                  s["down_proj"]["kernel"])


def block(x, p, *, nope, theta, eps, **moe):
    """One block -> (x, counts or None); its MLP kind is what its
    parameters are."""
    b, t, h = x.shape
    x = x + latent_attention(
        rms_norm(x, p["input_layernorm"]["scale"], eps), p["attn_mla"],
        nope=nope, theta=theta, eps=eps)
    m = rms_norm(x, p["post_attention_layernorm"]["scale"], eps
                 ).reshape(b * t, h)
    if "mlp" in p:
        d = p["mlp"]
        return x + swiglu(m, d["gate_proj"]["kernel"], d["up_proj"]["kernel"],
                          d["down_proj"]["kernel"]).reshape(b, t, h), None
    y, counts = routed_experts(m, p["moe"], **moe)
    # the shared expert: whole on every chip, added once, unscaled
    return x + (y + shared_expert(m, p["moe"])).reshape(b, t, h), counts


def forward(params, ids, *, layers, **kw):
    """-> (rows the main head reads, rows the module's head reads or None,
    pair counts [sparse blocks, E]: the module's block last)."""
    p = params["params"]
    table, eps = p["wte"]["embedding"], kw["eps"]
    x, counts = table[ids], []
    for i in range(layers):
        x, c = block(x, p[f"h{i}"], **kw)
        if c is not None:
            counts.append(c)
    g = None
    if "mtp" in p:
        mtp = p["mtp"]
        # position i reads the embedding of token i + 1: an explicit shift;
        # the last position has no next token (a zero row; nothing scores it
        # and, causal, nothing before it reads it)
        emb_next = jnp.concatenate(
            [table[ids[:, 1:]], jnp.zeros_like(x[:, :1])], axis=1)
        joined = jnp.concatenate(
            [rms_norm(x, mtp["hnorm"]["scale"], eps),
             rms_norm(emb_next, mtp["enorm"]["scale"], eps)], axis=-1)
        g, c = block(joined @ mtp["eh_proj"]["kernel"], mtp["block"], **kw)
        counts.append(c)
        g = rms_norm(g, mtp["norm"]["scale"], eps)
    return rms_norm(x, p["norm_f"]["scale"], eps), g, jnp.stack(counts)


def logits(params, ids, **kw):
    """Both heads' logits [B, T, V] (the module's: ``None`` without one)."""
    with jax.default_matmul_precision("highest"):
        x, g, _ = forward(params, ids, **kw)
        head = params["params"]["lm_head"]
        return x @ head.T, None if g is None else g @ head.T


def mean_nll(logits, labels):
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                             -1)[..., 0]
    return -(ll * valid).sum() / valid.sum()


def loss(params, batch, *, mtp_weight, **kw):
    """Cross-entropy of the next token + lambda x cross-entropy of the one
    after.  Departure (2): no auxiliary loss and no z-loss; (3): lambda."""
    main, module = logits(params, batch["input_ids"], **kw)
    labels = batch["labels"]
    total = mean_nll(main, labels)
    if module is None:
        return total
    # the module at position i predicts token i + 2 = labels[i + 1]: an
    # explicit shift; the last position has no label
    after = jnp.concatenate(
        [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
    return total + mtp_weight * mean_nll(module, after)
