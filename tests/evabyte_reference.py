"""EvaByte in plain float32 ``jax.numpy``: what
``byteps_tpu/models/evabyte.py`` is tested against.  Written from the
equations of ISSUE 50 on the model's parameter tree and importing nothing
of the program.  ``benchmarks/families/evabyte.py`` carries a copy of the
text between the two ``reference`` marks
(``benchmarks/tests/test_evabyte_cell.py`` holds the two equal).

EVA attention is ONE masked softmax a query row over the concatenated
``[T + T / chunk]`` keys — the T keys themselves and the T / chunk chunk
summaries — with the mask written from the two sets of the equations: the
keys of the row's own window up to the row, the summaries of every earlier
window.  No windows folded into a batch, no log-sum-exp, no merge.  The
pooling is a ``reshape`` and two ``softmax``es.  One head at a time from
its projections to its ``W_o`` product, one sequence and one block of
1 024 query rows at a time, the SwiGLU in blocks of 1 024 rows, the eight heads over blocks of 512 positions, each half layer and
each layer under ``jax.checkpoint``: blocking and rematerialising change
memory, not mathematics.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# --------------------------------------------------------------- reference

HEAD_BLOCK = 512          # positions per block of the prediction heads
QUERY_BLOCK = 1024        # query rows per block of the exact attention
ROW_BLOCK = 1024          # rows per block of the SwiGLU


def norm0(x, w, eps):
    """Unit offset: ``x rsqrt(mean x^2 + eps) (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


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


def chunk_summaries(k, v, mu, phi, chunk):
    """k, v [T, D] of one head, mu, phi [D] -> (k~, v~) [T / chunk, D]:
    inside a chunk a softmax of ``k_j . mu`` pools the keys, one of ``k_j .
    phi`` the values."""
    t, d = k.shape
    kc, vc = k.reshape(t // chunk, chunk, d), v.reshape(t // chunk, chunk, -1)
    alpha = jax.nn.softmax(kc @ mu, -1)                  # [T / chunk, chunk]
    beta = jax.nn.softmax(kc @ phi, -1)
    return (jnp.einsum("nc,ncd->nd", alpha, kc),
            jnp.einsum("nc,ncd->nd", beta, vc))


def eva_one_head(q, k, v, mu, phi, *, window, chunk):
    """q, k, v [T, D] of one sequence and head (q, k rotated) -> [T, D]:
    row i, in window w = i // window, takes one softmax at scale 1/sqrt(D)
    over L_i = {j : j // window == w, j <= i} (its own window, causal) and
    R_i = {c : c < (window / chunk) w} (every chunk of every earlier
    window), one block of query rows at a time."""
    t, d = q.shape
    ks, vs = chunk_summaries(k, v, mu, phi, chunk)
    keys, values = jnp.concatenate([k, ks]), jnp.concatenate([v, vs])
    rows = math.gcd(t, QUERY_BLOCK)
    j, c = jnp.arange(t)[None, :], jnp.arange(t // chunk)[None, :]

    @jax.checkpoint
    def one_block(q1, first_row):                # [rows, D]
        i = first_row + jnp.arange(rows)[:, None]
        w = i // window
        own = (j // window == w) & (j <= i)
        earlier = c < (window // chunk) * w
        s = jnp.where(jnp.concatenate([own, earlier], 1),
                      q1 @ keys.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(s, -1) @ values

    return jax.lax.map(lambda blk: one_block(*blk),
                       (q.reshape(t // rows, rows, d),
                        jnp.arange(0, t, rows))).reshape(t, -1)


def eva(a, p, *, theta, window, chunk):
    """a: [B, T, h] -> the mixer's output.  A head meets no other between
    the projections and ``W_o``, so the heads go one at a time, each under
    ``jax.checkpoint``, and their ``W_o`` products are summed: q, k, v of
    ONE head, q and k rotated over all lanes, the pooling AFTER the
    rotation, one sequence at a time."""

    @jax.checkpoint
    def one_head(w):
        wq, wk, wv, mu, phi, w_o = w           # [h, D] x 3, [D] x 2, [D, h]
        q, k, v = rotate(a @ wq, theta), rotate(a @ wk, theta), a @ wv
        ctx = jax.lax.map(
            lambda x: eva_one_head(*x, mu, phi, window=window, chunk=chunk),
            (q, k, v))                                       # [B, T, D]
        return ctx @ w_o

    def by_head(kernel):                       # [h, H, D] -> [H, h, D]
        return jnp.moveaxis(kernel, 1, 0)

    return jax.lax.scan(
        lambda y, w: (y + one_head(w), None), jnp.zeros_like(a),
        (by_head(p["q_proj"]["kernel"]), by_head(p["k_proj"]["kernel"]),
         by_head(p["v_proj"]["kernel"]), p["mu"], p["phi"],
         p["o_proj"]["kernel"]))[0]


def swiglu(m, p):
    """m: [N, h], in blocks of ``ROW_BLOCK`` rows."""
    n, h = m.shape
    rows = math.gcd(n, ROW_BLOCK)

    @jax.checkpoint
    def one_block(mb):
        return (jax.nn.silu(mb @ p["gate_proj"]["kernel"])
                * (mb @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]

    return jax.lax.map(one_block, m.reshape(n // rows, rows, h)).reshape(n, h)


def mixer(x, p, *, model):
    """``x + eva(N(x))``."""
    a = norm0(x, p["input_layernorm"]["scale"], model["eps"])
    return x + eva(a, p["attn"], theta=model["theta"],
                   window=model["window"], chunk=model["chunk"])


def mlp(x, p, *, model):
    """``x + W_d(silu(W_g m) * W_u m)``, ``m = N(x)``."""
    bsz, t, h = x.shape
    m = norm0(x, p["post_attention_layernorm"]["scale"], model["eps"])
    return x + swiglu(m.reshape(bsz * t, h), p["mlp"]).reshape(bsz, t, h)


def layer(x, p, *, model):
    """One layer; each half under a ``jax.checkpoint`` of its own."""
    x = jax.checkpoint(functools.partial(mixer, model=model))(x, p)
    return jax.checkpoint(functools.partial(mlp, model=model))(x, p)


def reference_hidden(params, ids, **model):
    """-> the rows the heads read, [B, T, h], one sequence at a time."""
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


def head_labels(labels, heads):
    """labels [B, T] (the next byte; -1: none) -> [B, T, heads]: head p at
    position t is asked for byte t + 1 + p, -1 past the sequence's end."""
    return jnp.stack(
        [jnp.concatenate([labels[:, p:], jnp.full_like(labels[:, :p], -1)], 1)
         for p in range(heads)], -1)


def reference_logits(params, ids, **model):
    """-> the eight heads' logits [B, T, heads, V]: one matrix [h, heads
    V], head p its p-th run of V columns."""
    x = reference_hidden(params, ids, **model)
    logits = x @ params["params"]["lm_head"]
    return logits.reshape(*x.shape[:2], model["heads"], -1)


def heads_nll(x, head, labels):
    """Mean negative log-likelihood over the heads and the positions that
    have a label, equal weights (x: [N, h], head [h, heads V], labels
    [N, heads]; -1: no label), over blocks of positions."""
    n, heads = labels.shape
    rows = math.gcd(n, HEAD_BLOCK)

    @jax.checkpoint
    def one_block(xl):
        xb, lb = xl
        valid = lb >= 0
        logp = jax.nn.log_softmax((xb @ head).reshape(rows, heads, -1), -1)
        ll = jnp.take_along_axis(logp, jnp.where(valid, lb, 0)[..., None],
                                 -1)[..., 0]
        return -(ll * valid).sum(), valid.sum()

    nll, count = jax.lax.map(one_block, (x.reshape(n // rows, rows, -1),
                                         labels.reshape(n // rows, rows,
                                                        heads)))
    return nll.sum() / count.sum()


def reference_loss(params, batch, **model):
    """The eight heads' mean cross-entropy; no auxiliary term."""
    with jax.default_matmul_precision("highest"):
        x = reference_hidden(params, batch["input_ids"], **model)
        b, t, h = x.shape
        labels = head_labels(batch["labels"], model["heads"])
        return heads_nll(x.reshape(b * t, h), params["params"]["lm_head"],
                         labels.reshape(b * t, -1))

# ----------------------------------------------------------- end reference


def model_of(cfg) -> dict:
    """The reference's keyword arguments for a model configuration."""
    return dict(eps=cfg.rms_norm_eps, theta=float(cfg.rope_theta),
                window=cfg.window_size, chunk=cfg.chunk_size,
                heads=cfg.num_pred_heads)
