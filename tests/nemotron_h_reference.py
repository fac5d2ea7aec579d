"""Plain reference for ``byteps_tpu/models/nemotron_h.py``: Nemotron 3
Super's forward pass and loss in float32 ``jax.numpy`` on the model's own
parameter tree, written from the equations of ISSUE 39
(``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``'s ``config.json``,
``model_type: nemotron_h``) and importing nothing of the program.
:func:`reference_loss` sets ``jax.default_matmul_precision("highest")``
itself.  ``benchmarks/families/nemotron_h.py`` carries a copy of everything
between the two ``reference`` marks (``benchmarks/tests/
test_nemotron_h_cell.py`` holds the copy to this text).

Per block, ``x = x + mixer(RMSNorm(x))``, the mixer's kind a letter of the
pattern.  ``M``: one projection cut into ``z``, ``xBC`` and ``dt``; the
depthwise causal convolution as an explicit sum of shifted copies, then
silu; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state-space
recurrence ``S_t = exp(dt_t A) S_(t-1) + dt_t B_t^T xs_t``, ``y_t = C_t S_t
+ D xs_t`` as a ``lax.scan`` over POSITIONS (no chunk algebra), nested in
segments under ``jax.checkpoint`` so that its backward keeps a state a
segment and not a state a position; ``y * silu(z)`` normed over each
group's channels; the output projection.  ``*``: q, k, v by their
projections, query head h on key/value head ``h // (heads / kv heads)``, no
rotation, an exact masked softmax at ``1 / sqrt(head size)`` one block of
query rows at a time.  ``E``: sigmoid scores over ALL routed experts, the
``top_k`` largest of ``score + bias``, their scores renormalised (+1e-20)
and scaled; the latent ``l = m W_dn``; the HELD experts one by one in
their DENSE form (each on every token, times its weight or zero: no sort,
no grouped matmul), ``relu(.)^2`` between their two matrices, no gate; the
routed sum through ``W_up``; the shared expert on the full hidden added
once.  The module: both norms, ``[h ; Emb(next token)]`` through
``eh_proj``, the blocks of its own pattern, its own last norm, the MAIN
head; ids and labels shifted explicitly.  Both heads over the rows held,
in blocks.  Each block of the model under ``jax.checkpoint``, and runs of
four under one more: blocking and rematerialising change memory, not
mathematics.

What a share holds is what the parameter tree holds (heads, groups, query
and key/value heads, rows of the vocabulary) and ``held = (first, count)``
for the routed experts: the reference computes the same partial results.

Departures from the published model, each at its line: (1) the selection
bias stays the zeros it starts as; (2) no auxiliary or z-loss stands in
for its rule; (3) lambda = 0.3 (the caller's ``mtp_weight``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# --------------------------------------------------------------- reference

HEAD_BLOCK = 512          # positions per block of a vocabulary head
QUERY_BLOCK = 1024        # query rows per block of the exact attention
ROW_BLOCK = 2048          # rows per block of the shared expert
SCAN_SEGMENT = 128        # positions per rematerialised run of the recurrence
BLOCK_GROUP = 4           # blocks per outer rematerialised run of the model


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def causal_softmax_attention(q, k, v):
    """q, k, v: [T, D] of one sequence and head -> [T, D]: exact softmax
    over keys j <= i at scale 1/sqrt(D), one block of query rows at a
    time."""
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
                        jnp.arange(0, t, rows))).reshape(t, d)


def attention(a, p):
    """a: [B, T, h]; the share's query heads, each on its key/value head
    (repeated to the query heads by an explicit index); no rotation.  One
    (sequence, head) at a time."""
    q = jnp.einsum("bth,hnd->bntd", a, p["q_proj"]["kernel"])
    k = jnp.einsum("bth,hnd->bntd", a, p["k_proj"]["kernel"])
    v = jnp.einsum("bth,hnd->bntd", a, p["v_proj"]["kernel"])
    kv_of = jnp.arange(q.shape[1]) // (q.shape[1] // k.shape[1])
    one_head = jax.checkpoint(causal_softmax_attention)
    ctx = jax.lax.map(
        lambda seq: jax.lax.map(lambda qkv: one_head(*qkv), seq),
        (q, k[:, kv_of], v[:, kv_of]))                 # [B, H, T, d]
    return jnp.einsum("bntd,ndh->bth", ctx, p["o_proj"]["kernel"])


def state_space_recurrence(xs, dt, a, b_in, c_in, d_skip):
    """xs [B, T, H, P], dt [B, T, H], a [H] (negative), b_in / c_in
    [B, T, G, N], d_skip [H] -> y [B, T, H, P]: the recurrence position by
    position from a zero state, head h on group ``h // (H / G)``."""
    bsz, t, h, p = xs.shape
    g, n = b_in.shape[2], b_in.shape[3]
    seg = math.gcd(t, SCAN_SEGMENT)
    b_h = jnp.repeat(b_in, h // g, axis=2)             # [B, T, H, N]
    c_h = jnp.repeat(c_in, h // g, axis=2)

    def position(state, at):                           # state [B, H, N, P]
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :])
        return state, jnp.einsum("bhn,bhnp->bhp", c_t, state)

    @jax.checkpoint
    def segment(state, run):
        return jax.lax.scan(position, state, run)

    def by_segment(v):                 # [B, T, ...] -> [T/seg, seg, B, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(t // seg, seg, *v.shape[1:])

    _, y = jax.lax.scan(segment, jnp.zeros((bsz, h, n, p), jnp.float32),
                        tuple(by_segment(v) for v in (xs, dt, b_h, c_h)))
    y = jnp.moveaxis(y.reshape(t, bsz, h, p), 0, 1)
    return y + d_skip[:, None] * xs


def mamba(u, p, *, state, head_dim, eps):
    """u: [B, T, h] -> the mixer's output (the share's heads and groups:
    read off the parameters' shapes)."""
    bsz, t, _ = u.shape
    heads = p["A_log"].shape[0]
    inner = heads * head_dim
    proj = u @ p["in_proj"]["kernel"]
    groups = (proj.shape[-1] - 2 * inner - heads) // (2 * state)
    bc = groups * state
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * bc],
                  proj[..., 2 * inner + 2 * bc:])
    # depthwise causal convolution: tap k reads position t - (K - 1) + k,
    # zeros before the sequence
    taps = p["conv_kernel"].shape[0]
    conv = p["conv_bias"] + sum(
        p["conv_kernel"][k] * jnp.concatenate(
            [jnp.zeros_like(xbc[:, :taps - 1 - k]),
             xbc[:, :t - (taps - 1 - k)]], axis=1)
        for k in range(taps))
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :inner].reshape(bsz, t, heads, head_dim)
    b_in = xbc[..., inner:inner + bc].reshape(bsz, t, groups, state)
    c_in = xbc[..., inner + bc:].reshape(bsz, t, groups, state)
    dt = jax.nn.softplus(dt + p["dt_bias"])            # not clamped
    y = state_space_recurrence(xs, dt, -jnp.exp(p["A_log"]), b_in, c_in,
                               p["D"])
    # gate first, then the norm over each group's channels
    gated = (y.reshape(bsz, t, groups, inner // groups)
             * jax.nn.silu(z).reshape(bsz, t, groups, inner // groups))
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + eps)
    return (normed.reshape(bsz, t, inner) * p["norm_scale"]
            ) @ p["out_proj"]["kernel"]


def in_row_blocks(fn, m):
    """``fn`` over blocks of ``ROW_BLOCK`` rows of m [N, h], each under
    ``jax.checkpoint``."""
    n, h = m.shape
    rows = math.gcd(n, ROW_BLOCK)
    return jax.lax.map(jax.checkpoint(fn),
                       m.reshape(n // rows, rows, h)).reshape(n, -1)


def unstacked(stack):
    """The matrices of a [count, ., .] stack, cut ONCE (one concatenation
    in the backward, not ``count`` zero-padded stacks)."""
    return [jnp.squeeze(one, 0) for one in jnp.split(stack, stack.shape[0])]


def latent_moe(m, p, *, top_k, held, scaling, renormalize):
    """m: [N, h] -> the held routed experts' part of the sum (scaled,
    through ``W_up``) plus the shared expert (whole on every chip: counted
    once)."""
    first, count = held
    scores = jax.nn.sigmoid(m @ p["router"])             # [N, E]
    e = scores.shape[-1]
    # departure: the bias is the zeros it starts as; it chooses only
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["e_score_correction_bias"]), top_k)
    picked = (jnp.arange(e) == chosen[..., None]).any(-2)
    weight = jnp.where(picked, scores, 0.0)
    if renormalize:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = scaling * weight[:, first:first + count]    # the experts held
    latent = m @ p["fc1_latent_proj"]["kernel"]          # [N, latent]

    @jax.checkpoint
    def one_expert(latent, up, down, w_e):
        return w_e[:, None] * (relu2(latent @ up) @ down)

    routed = jnp.zeros_like(latent)
    for i, matrices in enumerate(zip(unstacked(p["up"]),
                                     unstacked(p["down"]))):
        routed = routed + one_expert(latent, *matrices, weight[:, i])
        routed, latent = jax.lax.optimization_barrier((routed, latent))
    return routed @ p["fc2_latent_proj"]["kernel"] + in_row_blocks(
        lambda mb: relu2(mb @ p["shared_up_proj"]["kernel"])
        @ p["shared_down_proj"]["kernel"], m)


def block(x, p, *, model):
    """One block: its mixer's kind is what its parameters are."""
    eps = model["eps"]
    bsz, t, h = x.shape
    u = rms_norm(x, p["norm"]["scale"], eps)
    if "mixer_ssm" in p:
        y = mamba(u, p["mixer_ssm"], state=model["state"],
                  head_dim=model["head_dim"], eps=eps)
    elif "attn" in p:
        y = attention(u, p["attn"])
    else:
        y = latent_moe(u.reshape(bsz * t, h), p["moe"], top_k=model["top_k"],
                       held=model["held"], scaling=model["scaling"],
                       renormalize=model["renormalize"]).reshape(bsz, t, h)
    return x + y


def blocks(x, tree, prefix, model):
    """``x`` through ``tree[prefix + "0"]``, ``tree[prefix + "1"]``, ...:
    each block under a ``jax.checkpoint`` of its own, and runs of
    ``BLOCK_GROUP`` blocks under one more, so that the backward keeps a
    float32 [B, T, h] input a GROUP (and the blocks' of one group at a
    time), not one a block: 13 x 128 MiB would not fit beside the
    harness's parameters, moments and gradient."""
    step = jax.checkpoint(functools.partial(block, model=model))
    names = []
    while f"{prefix}{len(names)}" in tree:
        names.append(f"{prefix}{len(names)}")

    @jax.checkpoint
    def group(x, params):
        for p in params:
            x = step(x, p)
        return x

    for i in range(0, len(names), BLOCK_GROUP):
        x = group(x, [tree[n] for n in names[i:i + BLOCK_GROUP]])
    return x


def module_rows(x, table, ids, mtp, model):
    """The module: position i reads the last block's output (before the
    final norm) and the embedding of token i + 1 — an explicit shift; the
    LAST position has no next token: a zero embedding there, and nothing
    scores it."""
    eps = model["eps"]
    emb_next = jnp.concatenate(
        [table[ids[:, 1:]], jnp.zeros_like(x[:, :1])], axis=1)
    joined = jnp.concatenate(
        [rms_norm(x, mtp["hnorm"]["scale"], eps),
         rms_norm(emb_next, mtp["enorm"]["scale"], eps)], axis=-1)
    g = blocks(joined @ mtp["eh_proj"]["kernel"], mtp, "b", model)
    return rms_norm(g, mtp["norm"]["scale"], eps)


def reference_hidden(params, ids, **model):
    """-> (rows the main head reads, rows the module's head reads), each
    [B, T, h] (the second ``None`` without a module)."""
    p = params["params"]
    table = p["wte"]["embedding"]
    x = blocks(table[ids], p, "h", model)
    g = None
    if "mtp" in p:
        g = jax.checkpoint(functools.partial(module_rows, model=model))(
            x, table, ids, p["mtp"])
    return rms_norm(x, p["norm_f"]["scale"], model["eps"]), g


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


def reference_loss(params, batch, *, mtp_weight, **model):
    """Cross-entropy of the next token + lambda x cross-entropy of the one
    after, over the rows held.  Departures: no auxiliary loss and no
    z-loss; lambda = 0.3."""
    with jax.default_matmul_precision("highest"):
        x, g = reference_hidden(params, batch["input_ids"], **model)
        b, t, h = x.shape
        head, labels = params["params"]["lm_head"], batch["labels"]
        loss = head_nll(x.reshape(b * t, h), head, labels.reshape(b * t))
        if g is None:
            return loss
        # the module at position i predicts token i + 2 = labels[i + 1]:
        # an explicit shift; the last position has no label
        after = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
        return loss + mtp_weight * head_nll(g.reshape(b * t, h), head,
                                            after.reshape(b * t))

# ----------------------------------------------------------- end reference
