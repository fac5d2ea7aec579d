"""Plain reference for ``byteps_tpu/models/olmoe.py``: OLMoE's forward
pass and loss in float32 ``jax.numpy`` on the model's own parameter tree.

Follows the published description (arXiv:2409.02060; HF
``modeling_olmoe.py``): RMSNorm, bias-free q/k/v/o, q_norm / k_norm over
the whole projected vector before the head split, rotate-half RoPE,
exact causal softmax attention, a softmax router whose k largest
probabilities weigh the experts WITHOUT renormalisation, SiLU-gated
experts, untied head; loss = cross-entropy + 0.01 x sum of load-balance
losses + 0.001 x sum of router z-losses.

The expert layer is in its DENSE form: a scan over ALL experts, each run
on EVERY token and multiplied by its weight or zero.  It shares neither
the sort nor the grouped matmul, nor ``lax.top_k``, with the code under
test (the k-th largest probability is a threshold).  No kernels, no
cache, no batching tricks; matmuls at ``highest`` precision (on a TPU a
float32 matmul is otherwise computed in bf16 passes).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

AUX_COEF = 0.01
Z_COEF = 0.001


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotate_half(x, theta):
    """x: [B, T, H, D]; pairs (x[i], x[i + D/2]) rotated by t * theta^(-2i/D)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv          # [T, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, p, heads, theta, eps):
    b, t, h = x.shape
    q = rms_norm(x @ p["q_proj"]["kernel"], p["q_norm"]["scale"], eps)
    k = rms_norm(x @ p["k_proj"]["kernel"], p["k_norm"]["scale"], eps)
    v = x @ p["v_proj"]["kernel"]
    q, k, v = (a.reshape(b, t, heads, h // heads) for a in (q, k, v))
    q, k = rotate_half(q, theta), rotate_half(k, theta)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(h // heads)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return ctx.reshape(b, t, h) @ p["o_proj"]["kernel"]


def moe(x, p, top_k):
    """x: [N, h] -> (y, aux, z, pairs per expert)."""
    n, e = x.shape[0], p["router"].shape[-1]
    logits = x @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    kth = jnp.sort(probs, -1)[:, e - top_k][:, None]
    chosen = probs >= kth                                   # [N, E]
    weight = jnp.where(chosen, probs, 0.0)                  # not renormalised

    def one_expert(y, ew):
        gate, up, down, w_e = ew
        return y + w_e[:, None] * (
            (jax.nn.silu(x @ gate) * (x @ up)) @ down), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (p["gate"], p["up"], p["down"], weight.T))
    counts = chosen.sum(0)
    aux = e * jnp.sum(counts / n * probs.mean(0))
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return y, aux, z, counts


def forward(params, input_ids, *, heads, top_k, theta=10000.0, eps=1e-5):
    """-> (logits [B, T, V], sum of aux, sum of z, counts [layers, E])."""
    p = params["params"]
    x = p["wte"]["embedding"][input_ids]
    b, t, h = x.shape
    aux = z = 0.0
    counts = []
    for i in range(sum(1 for k in p if k[0] == "h" and k[1:].isdigit())):
        blk = p[f"h{i}"]
        x = x + attention(rms_norm(x, blk["attn_norm"]["scale"], eps),
                          blk["attn"], heads, theta, eps)
        y, a, zz, c = moe(rms_norm(x, blk["moe_norm"]["scale"],
                                   eps).reshape(b * t, h), blk["moe"], top_k)
        x, aux, z = x + y.reshape(b, t, h), aux + a, z + zz
        counts.append(c)
    x = rms_norm(x, p["norm_f"]["scale"], eps)
    return x @ p["lm_head"]["kernel"], aux, z, jnp.stack(counts)


def loss(params, batch, **kw):
    with jax.default_matmul_precision("highest"):
        logits, aux, z, _ = forward(params, batch["input_ids"], **kw)
        labels = batch["labels"]
        valid = labels >= 0
        ll = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                 jnp.where(valid, labels, 0)[..., None],
                                 -1)[..., 0]
        return (-(ll * valid).sum() / valid.sum()
                + AUX_COEF * aux + Z_COEF * z)
