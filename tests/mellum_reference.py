"""Plain reference for ``byteps_tpu/models/mellum.py``: Mellum 2's forward
pass and loss in float32 ``jax.numpy`` on the model's own parameter tree,
written from the equations of ``JetBrains/Mellum2-12B-A2.5B-Instruct``'s
``config.json`` (ISSUE 29) and importing nothing of the program.

Per layer: RMSNorm, bias-free q/k/v/o with ``heads`` query heads and
``kv_heads`` key/value heads, an RMSNorm over each head's vector on q and
k, rotate-half rotary with the layer type's frequencies (sliding layers:
``theta^(-2i/D)``; full layers: YaRN's blend, cos and sin times
``attention_factor``), exact softmax attention under the band mask
``(j <= i) & (i - j < window)`` (full layers: ``j <= i``), query head g
reading k/v head ``g // (heads // kv_heads)`` by indexing; then RMSNorm,
a softmax router whose k largest probabilities are divided by their sum,
and SiLU-gated experts in their DENSE form: each HELD expert on EVERY
token, times its renormalised weight or zero — no sort, no grouped
matmul, no ``lax.top_k`` (the k-th largest probability is a threshold).
``held=(first, count)`` says which experts the ``count`` stacks are; what
the others would add is left out, as in the program.  Loss =
cross-entropy over the vocabulary rows held + ``aux_coef`` x sum of the
load-balance losses (over all experts).  Matmuls at ``highest``
precision.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

AUX_COEF = 0.001
SLIDING, FULL = "sliding_attention", "full_attention"


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def inv_freq(d, rope):
    """[D/2] inverse frequencies and the factor on cos / sin."""
    theta = float(rope["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (2 * i / d)
    if rope["rope_type"] == "default":
        return 1.0 / f, 1.0
    # YaRN: lo, hi = floor / ceil of (D/2) ln(L / (beta 2 pi)) / ln theta
    def pair(beta):
        return (d / 2) * math.log(
            rope["original_max_position_embeddings"] / (beta * 2 * math.pi)
        ) / math.log(theta)
    lo = max(math.floor(pair(rope["beta_fast"])), 0)
    hi = min(math.ceil(pair(rope["beta_slow"])), d - 1)
    r = 1.0 - np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return ((1.0 - r) / (rope["factor"] * f) + r / f,
            float(rope["attention_factor"]))


def rotate_half(x, rope):
    """x: [B, T, H, D]; pairs (x[i], x[i + D/2]) turned by t inv_freq_i."""
    t, d = x.shape[1], x.shape[-1]
    inv, factor = inv_freq(d, rope)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32))
    cos = (jnp.cos(ang) * factor)[None, :, None]
    sin = (jnp.sin(ang) * factor)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, p, *, heads, kv_heads, rope, window, eps):
    b, t, _ = x.shape
    d = p["q_proj"]["kernel"].shape[-1]
    q = jnp.einsum("bth,hnd->btnd", x, p["q_proj"]["kernel"])
    k = jnp.einsum("bth,hnd->btnd", x, p["k_proj"]["kernel"])
    v = jnp.einsum("bth,hnd->btnd", x, p["v_proj"]["kernel"])
    q = rotate_half(rms_norm(q, p["q_norm"]["scale"], eps), rope)
    k = rotate_half(rms_norm(k, p["k_norm"]["scale"], eps), rope)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = (j <= i) if window is None else (j <= i) & (i - j < window)
    group = jnp.arange(heads) // (heads // kv_heads)       # g -> g // 8
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k[:, :, group]) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v[:, :, group])
    return jnp.einsum("btnd,ndh->bth", ctx, p["o_proj"]["kernel"])


def moe(x, p, top_k, held=None, renormalize=True):
    """x: [N, h] -> (y, aux, pairs per expert [E])."""
    n, e = x.shape[0], p["router"].shape[-1]
    first, count = held or (0, e)
    probs = jax.nn.softmax(x @ p["router"], -1)
    kth = jnp.sort(probs, -1)[:, e - top_k][:, None]
    chosen = probs >= kth                                   # [N, E]
    weight = jnp.where(chosen, probs, 0.0)
    if renormalize:
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight[:, first:first + count]                 # held experts

    def one_expert(y, ew):
        gate, up, down, w_e = ew
        return y + w_e[:, None] * (
            (jax.nn.silu(x @ gate) * (x @ up)) @ down), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (p["gate"], p["up"], p["down"], weight.T))
    counts = chosen.sum(0)
    aux = e * jnp.sum(counts / n * probs.mean(0))
    return y, aux, counts


def forward(params, input_ids, *, layer_types, heads, kv_heads, window,
            rope_parameters, top_k, held=None, eps=1e-6):
    """-> (logits [B, T, V], sum of aux, counts [layers, E])."""
    p = params["params"]
    x = p["wte"]["embedding"][input_ids]
    b, t, h = x.shape
    aux = 0.0
    counts = []
    for i, kind in enumerate(layer_types):
        blk = p[f"h{i}"]
        sliding = kind == SLIDING
        x = x + attention(
            rms_norm(x, blk["attn_norm"]["scale"], eps),
            blk["attn_swa" if sliding else "attn"], heads=heads,
            kv_heads=kv_heads, rope=rope_parameters[kind],
            window=window if sliding else None, eps=eps)
        y, a, c = moe(rms_norm(x, blk["moe_norm"]["scale"],
                               eps).reshape(b * t, h), blk["moe"], top_k,
                      held)
        x, aux = x + y.reshape(b, t, h), aux + a
        counts.append(c)
    x = rms_norm(x, p["norm_f"]["scale"], eps)
    return x @ p["lm_head"]["kernel"], aux, jnp.stack(counts)


def loss(params, batch, **kw):
    with jax.default_matmul_precision("highest"):
        logits, aux, _ = forward(params, batch["input_ids"], **kw)
        labels = batch["labels"]
        valid = labels >= 0
        ll = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                 jnp.where(valid, labels, 0)[..., None],
                                 -1)[..., 0]
        return -(ll * valid).sum() / valid.sum() + AUX_COEF * aux
