"""Family ``ling``: byteps_tpu.models.ling under next-token prediction.

Configuration keys as in the source's ``config.json`` (``model_type:
bailing_hybrid``, the language model's keys of
``inclusionAI/Ling-3.0-flash-VL``), plus the chip's share of a stated
deployment: the keys that COUNT experts, rows and layers give what is held
here (``num_experts`` with ``experts_held`` saying which, ``vocab_size``,
``num_hidden_layers``), each beside its ``_published`` twin; every width is
the source's.

The plain reference is float32 ``jax.numpy`` on the same parameter tree,
written from the equations of ISSUE 43 and importing nothing of the
program: a copy of ``tests/ling_reference.py`` between the two
``reference`` marks (``benchmarks/tests/test_ling_cell.py`` holds the two
texts equal).  The delta rule is a ``lax.scan`` over POSITIONS on the
[32, 128, 128] state (no chunk algebra, no solve), nested in segments of
128 under ``jax.checkpoint`` (a state a segment, not 2 MiB a position);
exact softmax attention one (sequence, head) and one block of 1 024 query
rows at a time with v at 128; the group-limited router in plain code of
its own (``lax.top_k`` for a group's two largest, the four groups and the
eight experts); the HELD experts one by one in their dense form with the
same partial sum; the shared expert and the dense MLPs in blocks of 2 048
rows; the head over the slice in blocks of 512 positions; each layer under
``jax.checkpoint``: blocking and rematerialising change memory, not
mathematics.
"""

from __future__ import annotations

import functools
import math
import types

import jax
import jax.numpy as jnp

from harness import spec

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


# ------------------------------------------------- operations and bytes

KDA_WORK_CHUNK = 64       # the chunk ``kda_work`` is STATED at


def _kinds(config: dict) -> dict:
    """How many layers of each kind are built: mixers by
    ``layer_group_size``, MLPs by ``first_k_dense_replace``."""
    n, period = config["num_hidden_layers"], config["layer_group_size"]
    mla = sum((i + 1) % period == 0 for i in range(n))
    dense = min(config["first_k_dense_replace"], n)
    return {"kda": n - mla, "mla": mla, "dense": dense, "sparse": n - dense}


def _weights(config: dict) -> dict:
    """Matmul weights a token meets in one mixer or MLP of each kind, at
    the share held: KDA the fused projection (q, k, v, the decay's ``f``,
    the output gate, ``beta``) and ``W_o`` (the short convolution is no
    matmul); MLA q, the joint latent, its up-projection, the gate and
    ``W_o``; a dense SwiGLU; a sparse layer its router over the PUBLISHED
    experts, the shared expert and the token's pairs that fall on held
    experts — ``held / published`` of its ``num_experts_per_tok`` in
    expectation, three matrices a pair."""
    h, heads, d = (config["hidden_size"], config["num_attention_heads"],
                   config["head_dim"])
    inner = heads * d
    nope, rope, dv, rank = (config["qk_nope_head_dim"],
                            config["qk_rope_head_dim"], config["v_head_dim"],
                            config["kv_lora_rank"])
    f = config["moe_intermediate_size"]
    pairs_here = (config["num_experts_per_tok"] * config["num_experts"]
                  / config["num_experts_published"])
    return {"kda": h * (5 * inner + heads) + inner * h,
            "mla": (h * heads * (nope + rope) + h * (rank + rope)
                    + rank * heads * (nope + dv) + h * heads
                    + heads * dv * h),
            "dense": 3 * h * config["intermediate_size"],
            "sparse": (h * config["num_experts_published"]
                       + 3 * h * config["moe_shared_expert_intermediate_size"]
                       + pairs_here * 3 * h * f)}


def kda_forward_flops_per_token(config: dict) -> float:
    """Operations of the delta rule's chunked (WY) FORWARD per token of one
    KDA layer at the STATED chunk C = 64, every head: per chunk and head
    the two [C, C, d] score products (``A``, ``P``: 2 C d multiply-adds a
    token), the solve (a dense [C, C] inverse's worth: C^2), ``W`` and
    ``U`` (C d each), ``W S`` and the read-out ``(Gamma o Q) S`` (d^2
    each), the intra-chunk product ``P (U - W S)`` (C d) and the state's
    update (d^2): 94 208 multiply-adds a token and head at d = 128."""
    heads, d, c = (config["num_attention_heads"], config["head_dim"],
                   KDA_WORK_CHUNK)
    macs = 2 * c * d + c * c + 2 * c * d + 2 * d * d + c * d + d * d
    return heads * 2.0 * macs


def share_params(config: dict) -> int:
    """Parameters of the chip's share: the model's own leaf count."""
    model, _ = _model(config, {"remat": False})
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, KDA_WORK_CHUNK), jnp.int32)))
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(shapes))


def _score_flops_per_row_pair(config: dict) -> float:
    """Operations a (query, key) pair of one head costs, forward and
    backward: ``q k^T`` and ``P V`` forward (2 (qk + v)); the scores again,
    ``dP``, ``dV``, ``dK``, ``dQ`` backward (2 (3 qk + 2 v))."""
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return 2.0 * (4 * qk + 3 * config["v_head_dim"])


def flops_per_token(config: dict, seq_len: int) -> float:
    """Required matmul operations of THIS CHIP's share per trained token:
    6 per weight a token meets (``_weights``; the head once — the embedding
    side is a gather) + the KDA scans, forward and a backward of twice the
    forward + latent attention's scores, the causal half at 32 heads with
    q.k at 192 and v at 128 (so that ``mla_flash_roofline`` and ``mfu_pct``
    count one work).  Recomputation under ``remat`` is not counted."""
    kinds, per = _kinds(config), _weights(config)
    weights = (sum(kinds[k] * per[k] for k in kinds)
               + config["hidden_size"] * config["vocab_size"])
    scores = (kinds["mla"] * config["num_attention_heads"]
              * _score_flops_per_row_pair(config) * seq_len / 2)
    return (6.0 * weights + scores
            + kinds["kda"] * 3.0 * kda_forward_flops_per_token(config))


def flash_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2
               ) -> dict:
    """Required operations and HBM bytes of one step's flash calls under
    the scope ``attn_mla`` on one chip, every MLA layer's.  Operations: the
    causal half of 32 heads, q.k at 192 lanes and v at 128, whatever the
    program pads.  Bytes: what the ALGORITHM moves — q (read forward and
    backward) and dQ at 32 x 192; k (twice) and dK at 32 x 128 and ONE
    64-wide rotary key (repeating it over the heads before the call is the
    program's choice); v (twice), dV, o (written, read again) and dO at
    32 x 128; three float32 rows a head (lse; lse and delta again).  The
    forward recomputed under ``remat`` is the program's work: not
    counted."""
    heads = config["num_attention_heads"]
    nope, rope, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    n, rows = _kinds(config)["mla"], seqs * seq_len
    flops = (n * seqs * heads * _score_flops_per_row_pair(config)
             * seq_len * seq_len / 2)
    layer_bytes = (rows * 3 * (heads * (nope + rope) + heads * nope + rope
                               + 2 * heads * dv) * itemsize
                   + 3 * 4.0 * rows * heads)
    return {"flops": flops, "bytes": n * layer_bytes,
            "op_name_re": r"/attn_mla/pallas_call$"}


def kda_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2,
             remat: bool = True) -> dict:
    """Required operations and HBM bytes of one step's delta-rule scans on
    one chip, every KDA layer: the ALGORITHM's in the WY form at the stated
    chunk, whatever chunk or kernel implements it.  Operations: the
    forward (``kda_forward_flops_per_token``), a backward of twice that,
    and the forward once more where ``remat`` recomputes it.  Bytes: ``q``,
    ``k``, ``v``, ``o`` (heads x head size) in the compute type, ``g``
    float32 a channel, ``beta`` float32 a head, each and its gradient read
    or written once."""
    heads, d = config["num_attention_heads"], config["head_dim"]
    layers, tokens = _kinds(config)["kda"], seqs * seq_len
    passes = 4.0 if remat else 3.0
    row_bytes = 4 * heads * d * itemsize + 4.0 * heads * d + 4.0 * heads
    return {"flops": layers * passes * tokens
            * kda_forward_flops_per_token(config),
            "bytes": layers * 2.0 * tokens * row_bytes,
            # the kernels carry ``name=`` (bps_kda_fwd / bps_kda_bwd) under
            # the mixer's ``bps.kda.scan`` scope
            "op_name_re": r"bps\.kda\.scan\)*/.*pallas_call$"}


def moe_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2,
             pair_share=None) -> dict:
    """Required operations and HBM bytes of the held routed experts'
    grouped matmuls of ONE step on one chip, every sparse layer: the pair
    rows that fall on held experts — the expected ``held / published`` of
    all ``tokens x num_experts_per_tok`` (8 / 512), or ``pair_share`` of
    them where the batch's own share is known — through THREE matmuls
    (gate, up, down) in three passes (forward, row gradient, matrix
    gradient), each 2 M h f.  Bytes: a pass touches every HELD expert's
    matrix once and each matmul's live row blocks in and out once.  Dead
    rows and the rest of a window need nothing; the forward recomputed
    under ``remat`` is not the algorithm's (``families/nemotron_h.py``
    ``moe_work`` with a gate)."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    held = config["num_experts"]
    if pair_share is None:
        pair_share = held / config["num_experts_published"]
    m = seqs * seq_len * config["num_experts_per_tok"] * pair_share
    n = _kinds(config)["sparse"]
    matmuls, passes = 3, 3
    return {"flops": n * matmuls * passes * 2.0 * m * h * f,
            "bytes": n * matmuls * passes * float(itemsize) * (
                held * h * f + m * (h + f)),
            # megablox's kernels carry no name of their own: the
            # pallas_calls under the layer's ``bps.moe.experts`` scope
            "op_name_re": r"bps\.moe\.experts/.*pallas_call$"}


# ----------------------------------------------------------------- build

SAME_NAME_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "layer_group_size",
    "first_k_dense_replace", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_kv_heads_for_linear_attn",
    "short_conv_kernel_size", "linear_silu", "use_qk_norm",
    "group_norm_size", "kda_safe_gate", "kda_lower_bound", "no_kda_lora",
    "use_kda_lora", "mtp_use_kda", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rotary_dim",
    "partial_rotary_factor", "rope_theta", "use_mla_nope",
    "gated_attention_proj_granularity_type", "num_experts_per_tok",
    "n_group", "topk_group", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "moe_router_enable_expert_bias",
    "score_function", "norm_topk_prob", "routed_scaling_factor",
    "scale_router_input", "expert_swiglu_limit_list",
    "share_expert_swiglu_limit_list", "use_nGPT", "value_norm",
    "up_proj_norm", "rms_norm_eps", "max_position_embeddings")


def _model(config: dict, traffic: dict):
    """(the model at the share the file states, the flash function or
    None)."""
    from byteps_tpu.models.ling import Ling, LingConfig
    spec.fixed(config, param_dtype="float32",
               norm_eps=config["rms_norm_eps"])
    first, count = config["experts_held"]
    if count != config["num_experts"]:
        raise spec.SpecError(
            f"num_experts ({config['num_experts']}) counts the experts "
            f"held; experts_held says {count}")
    # every other key goes to the model file under its own name, which
    # refuses what it cannot compute; the key that COUNTS the experts held
    # goes beside its published twin
    cfg = LingConfig(
        **{k: config[k] for k in SAME_NAME_KEYS},
        num_experts=config["num_experts_published"],
        experts_held=(first, count),
        tie_word_embeddings=bool(config.get("tie_word_embeddings", False)),
        dtype=jnp.dtype(config["compute_dtype"]).type,
        remat=bool(traffic.get("remat", False)))
    attention_kind = traffic.get("attention", "exact")
    if attention_kind == "flash":
        from byteps_tpu.ops import flash_attention as attn_fn
    elif attention_kind == "exact":
        attn_fn = None
    else:
        raise ValueError(f"unknown attention {attention_kind!r}")
    return Ling(cfg, attn_fn=attn_fn), attn_fn


def build(config: dict, traffic: dict):
    from byteps_tpu.models import ling
    model, attn_fn = _model(config, traffic)
    cfg = model.cfg
    if traffic["objective"] != "clm":
        raise ValueError(f"family ling has no objective "
                         f"{traffic['objective']!r}")
    seq = traffic["seq_len"]
    if seq > cfg.max_position_embeddings:
        raise ValueError(f"seq_len {seq} exceeds the model's context "
                         f"{cfg.max_position_embeddings}")
    remat = bool(traffic.get("remat", False))

    def init_params(key):
        return model.init(key, jnp.zeros((1, seq), jnp.int32))

    def make_batch(key, n_seqs):
        # token ids are drawn from the slice of the vocabulary held here
        ids = jax.random.randint(key, (n_seqs, seq), 0, cfg.vocab_size)
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full((n_seqs, 1), -1, ids.dtype)], axis=1)
        return {"input_ids": ids, "labels": labels}

    def kernel_work(seqs_per_chip):
        work = {"kda": kda_work(config, seq, seqs_per_chip, remat=remat),
                # what ``moe_ms`` times and ``moe_roofline`` stands against
                "moe": moe_work(config, seq, seqs_per_chip)}
        if attn_fn is not None:
            # one set of kernels under two names: what ``flash_ms`` times
            # and ``flash_roofline`` / ``mla_flash_roofline`` stand against
            work["flash"] = flash_work(config, seq, seqs_per_chip)
            work["mla_flash"] = dict(work["flash"])
        return work

    reference = dict(
        eps=cfg.rms_norm_eps, lower_bound=float(cfg.kda_lower_bound),
        rank=cfg.kv_lora_rank, nope=cfg.qk_nope_head_dim,
        theta=float(cfg.rope_theta), held=cfg.held, n_group=cfg.n_group,
        topk_group=cfg.topk_group, top_k=cfg.num_experts_per_tok,
        scaling=float(cfg.routed_scaling_factor),
        renormalize=cfg.norm_topk_prob)
    return types.SimpleNamespace(
        init_params=init_params,
        loss_fn=functools.partial(ling.ling_loss, model),
        make_batch=make_batch,
        reference_loss=functools.partial(reference_loss, **reference),
        tokens_per_seq=seq, flops_per_token=flops_per_token(config, seq),
        kernel_work=kernel_work,
        # the rows the head reads, [B, T, h], of the program and of the
        # reference (``benchmarks/tests/gradcheck_ling.py``)
        hidden=model.apply,
        reference_hidden=functools.partial(reference_hidden, **reference),
        compute_dtype=cfg.dtype,
        # one layer's scan: sequences, T, heads, head size
        kda_shape=(int(traffic["seqs_per_chip"]), seq,
                   cfg.num_attention_heads, cfg.head_dim),
        log_decay_floor=float(cfg.kda_lower_bound),
        # the share, its [sparse layers, 512] pair counts of one batch and
        # the share of tokens whose chosen groups include its group
        experts_held=cfg.held,
        expert_counts=lambda p, b: ling.expert_counts(
            model, p, b["input_ids"]),
        group_hit_share=lambda p, b: ling.group_hit_share(
            model, p, b["input_ids"]),
        publish_group_stats=ling.publish_group_stats)
