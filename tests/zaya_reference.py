"""Plain reference for ``byteps_tpu/models/zaya.py``: ZAYA1's forward pass
and loss in float32 ``jax.numpy`` on the model's own parameter tree,
written from the equations of ISSUE 31 (Zyphra's CCA paper,
arXiv:2510.04476, and the ZAYA1 report, arXiv:2511.17127, as far as
``Zyphra/ZAYA1-8B``'s ``config.json`` pins them) and importing nothing of
the program.  Matmuls at ``highest`` precision.

Per layer, with T positions and ``groups = heads // kv_heads``:
RMSNorm; q~ (heads x D) and k~ (kv_heads x D) projected into the latent;
the q-k mean; the two causal convolutions of kernel 2 over the packed
[q~ ; k~] written as explicit shifts (padded ONCE before both, so the
per-head convolution sees ``b0`` at position -1, not zero); the value
shift (key/value head 1 from the token before); L2-normalised heads with
``sqrt(D)`` on both sides and a learned temperature a key/value head;
rotary over the first ``rot`` channels of a head; a dense [T, T] masked
softmax, query head g reading key/value head ``g // groups`` by indexing;
then RMSNorm, the router (down-projection, ``gamma`` x the state of the
layer before — an explicit loop carries it —, RMSNorm, two GELU layers,
16 scores, softmax), ``argmax(p + beta)`` and the HELD experts in their
DENSE form, each on every token times ``p`` at the chosen expert or zero.
``held=(first, count)`` says which experts the ``count`` stacks are.
Head: RMSNorm, the tied table.

Departures from the published model, each at its line below: (1) ``beta``
stays the zeros it starts as (the report's balancing rule is outside the
gradient and has no key); (2) no auxiliary or z-loss stands in for it;
(3) the report's learned residual scaling is left out (no key); (4) the
chosen expert's weight is its probability, not renormalised.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def before(x, first=0.0):
    """x[t - 1] along axis 1; ``first`` stands at t = 0."""
    head = jnp.broadcast_to(jnp.asarray(first, x.dtype), x[:, :1].shape)
    return jnp.concatenate([head, x[:, :-1]], axis=1)


def rotate_first(x, theta, rot):
    """x: [B, T, H, D]; pairs (x[i], x[i + rot/2]), i < rot/2, turned by
    t theta^(-2i/rot); channels rot .. D - 1 untouched."""
    t = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


def unit(x):
    """x / max(|x|, 1e-12) (``F.normalize``)."""
    return x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, -1, keepdims=True)),
                           1e-12)


def cca(a, p, heads, kv_heads, theta, rot):
    b, t, _ = a.shape
    d = p["q_proj"]["kernel"].shape[-1]
    groups = heads // kv_heads
    q_lat = jnp.einsum("bth,hnd->btnd", a, p["q_proj"]["kernel"])
    k_lat = jnp.einsum("bth,hnd->btnd", a, p["k_proj"]["kernel"])
    # the q-k mean: query head g with ITS key/value head g // groups
    m_q = (q_lat + k_lat[:, :, jnp.arange(heads) // groups]) / 2
    m_k = jnp.stack([m_q[:, :, j * groups:(j + 1) * groups].mean(2)
                     for j in range(kv_heads)], axis=2)
    u = jnp.concatenate([q_lat, k_lat], axis=2)          # [B, T, 10, D]
    w0, b0 = p["conv0_kernel"], p["conv0_bias"]
    w1, b1 = p["conv1_kernel"], p["conv1_bias"]
    assert w0.shape[-1] == 2 and w1.shape[1] == 2, "two taps written out"
    # conv 0, depthwise: c0[t] = b0 + w0[.., 0] u[t-1] + w0[.., 1] u[t]
    c0 = b0 + w0[..., 0] * before(u) + w0[..., 1] * u
    # conv 1, one group a head; the listing pads once, before both, so
    # c0[-1] = b0 + w0 . (0, 0) = b0, not 0
    c = (b1 + jnp.einsum("btcd,cde->btce", before(c0, b0[None, None]),
                         w1[:, 0])
         + jnp.einsum("btcd,cde->btce", c0, w1[:, 1]))
    q = c[:, :, :heads] + m_q
    k = c[:, :, heads:] + m_k
    # the value shift: head 0 from this token, head 1 from the one before
    v = jnp.stack([a @ p["v_proj1"]["kernel"],
                   before(a @ p["v_proj2"]["kernel"])], axis=2)
    q = math.sqrt(d) * unit(q)
    k = p["k_temperature"][:, None] * math.sqrt(d) * unit(k)
    q, k = rotate_first(q, theta, rot), rotate_first(k, theta, rot)
    causal = jnp.tril(jnp.ones((t, t), bool))
    ctx = []
    for g in range(heads):                               # dense [T, T]
        j = g // groups
        s = jnp.einsum("bqd,bkd->bqk", q[:, :, g], k[:, :, j]) / math.sqrt(d)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        ctx.append(jnp.einsum("bqk,bkd->bqd", w, v[:, :, j]))
    return jnp.einsum("btnd,ndh->bth", jnp.stack(ctx, axis=2),
                      p["o_proj"]["kernel"])


def router(m, p, r_before, eps):
    """-> (probabilities [.., E], this layer's state [.., 256])"""
    r = m @ p["down"]["kernel"] + p["down"]["bias"]
    if r_before is not None:
        r = r + p["gamma"] * r_before                    # depth averaging
    hdn = rms_norm(r, p["norm"]["scale"], eps)
    hdn = jax.nn.gelu(hdn @ p["fc1"]["kernel"] + p["fc1"]["bias"],
                      approximate=False)
    hdn = jax.nn.gelu(hdn @ p["fc2"]["kernel"] + p["fc2"]["bias"],
                      approximate=False)
    return jax.nn.softmax(hdn @ p["out"]["kernel"], -1), r


def experts(m, p, probs, held):
    """m: [N, h], probs [N, E] -> (the held experts' part of y, counts)."""
    e = probs.shape[-1]
    first, count = held or (0, e)
    # departure (1): beta is the zeros it starts as; it chooses only
    chosen = jnp.argmax(probs + jax.lax.stop_gradient(p["balance_bias"]), -1)
    # departure (4): the weight is p at the chosen expert, not renormalised
    weight = jnp.where(jnp.arange(e) == chosen[:, None], probs, 0.0)
    y = jnp.zeros_like(m)
    for i in range(count):                               # the HELD experts
        act = jax.nn.silu(m @ p["gate"][i]) * (m @ p["up"][i])
        y = y + weight[:, first + i, None] * (act @ p["down"][i])
    return y, jnp.bincount(chosen, length=e)


def forward(params, ids, *, layers, heads, kv_heads, theta, rot, held, eps):
    """-> (the last norm's output [B, T, h], per-layer expert counts
    [layers, E])."""
    p = params["params"]
    x = p["wte"]["embedding"][ids]
    b, t, h = x.shape
    r, counts = None, []
    for i in range(layers):                   # the loop carries (x, r)
        blk = p[f"h{i}"]
        x = x + cca(rms_norm(x, blk["attn_norm"]["scale"], eps),
                    blk["attn_cca"], heads, kv_heads, theta, rot)
        m = rms_norm(x, blk["moe_norm"]["scale"], eps)
        probs, r = router(m, blk["moe"]["router"], r, eps)
        y, c = experts(m.reshape(b * t, h), blk["moe"],
                       probs.reshape(b * t, -1), held)
        # departure (3): no learned scale on either residual addition
        x = x + y.reshape(b, t, h)
        counts.append(c)
    return rms_norm(x, p["norm_f"]["scale"], eps), jnp.stack(counts)


def logits(params, ids, **model):
    with jax.default_matmul_precision("highest"):
        x, _ = forward(params, ids, **model)
        return x @ params["params"]["wte"]["embedding"].T     # tied


def loss(params, batch, **model):
    """Next-token cross-entropy over the table's rows.  Departure (2): no
    auxiliary loss, no z-loss."""
    lg = logits(params, batch["input_ids"], **model)
    labels = batch["labels"]
    valid = labels >= 0
    logp = jax.nn.log_softmax(lg, -1)
    ll = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                             -1)[..., 0]
    return -(ll * valid).sum() / jnp.maximum(valid.sum(), 1)
