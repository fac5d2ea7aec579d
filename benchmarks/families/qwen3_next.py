"""Family ``qwen3_next``: byteps_tpu.models.qwen3_next under next-token
prediction.

Configuration keys as in the source's ``config.json`` (``model_type:
qwen3_next``, ``Qwen/Qwen3-Next-80B-A3B-Instruct``), plus the chip's share
of a stated deployment: the keys that COUNT experts, rows and layers give
what is held here (``num_experts`` with ``experts_held`` saying which,
``vocab_size``, ``num_hidden_layers``), each beside its ``_published``
twin; every width is the source's.

The plain reference is float32 ``jax.numpy`` on the same parameter tree,
written from the equations of ISSUE 46 and importing nothing of the
program: a copy of ``tests/qwen3_next_reference.py`` between the two
``reference`` marks (``benchmarks/tests/test_qwen3_next_cell.py`` holds the
two texts equal).  The delta rule is a ``lax.scan`` over POSITIONS on the
[32, 128, 128] state (no chunk algebra, no solve), nested in segments of
128 under ``jax.checkpoint``, a mixer's key heads two at a time; exact
softmax attention one (sequence, head) and one block of 1 024 query rows
at a time; the router a float32 softmax and ``lax.top_k``; the HELD
experts one by one in their dense form with the same partial sum; the
shared expert in blocks of 2 048 rows; the head over the slice in blocks
of 512 positions; each layer under ``jax.checkpoint``: blocking and
rematerialising change memory, not mathematics.
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


# ------------------------------------------------- operations and bytes

GDN_WORK_CHUNK = 64       # the chunk ``gdn_work`` is STATED at


def _kinds(config: dict) -> dict:
    """How many layers of each mixer are built, by
    ``full_attention_interval``."""
    n, period = config["num_hidden_layers"], config["full_attention_interval"]
    attn = sum((i + 1) % period == 0 for i in range(n))
    return {"gdn": n - attn, "attn": attn, "sparse": n}


def _weights(config: dict) -> dict:
    """Matmul weights a token meets in one mixer or MLP of each kind, at
    the share held: a DeltaNet mixer its two fused projections and ``W_o``
    (the short convolution is no matmul); attention q with its gate, k, v
    and ``W_o``; a sparse layer its router over the PUBLISHED experts, the
    shared expert with its gate and the token's pairs that fall on held
    experts — ``held / published`` of its ``num_experts_per_tok`` in
    expectation, three matrices a pair."""
    h = config["hidden_size"]
    keys = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    values = (config["linear_num_value_heads"]
              * config["linear_value_head_dim"])
    heads, kv, d = (config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    f = config["moe_intermediate_size"]
    pairs_here = (config["num_experts_per_tok"] * config["num_experts"]
                  / config["num_experts_published"])
    return {"gdn": (h * (2 * keys + 2 * values)
                    + h * 2 * config["linear_num_value_heads"] + values * h),
            "attn": h * heads * 2 * d + 2 * h * kv * d + heads * d * h,
            "sparse": (h * config["num_experts_published"] + h
                       + 3 * h * config["shared_expert_intermediate_size"]
                       + pairs_here * 3 * h * f)}


def gdn_forward_flops_per_token(config: dict) -> float:
    """Operations of the head-decay delta rule's chunked (WY) FORWARD per
    token of one DeltaNet layer at the STATED chunk C = 64: once a KEY
    head the two [C, C, d] score products (2 C d multiply-adds a token);
    once a VALUE head ``D`` on both of them (2 C), the solve (a dense
    [C, C] inverse's worth: C^2), ``W`` and ``U`` (C d each), ``W S`` and
    the read-out ``Diag(Gamma) Q S`` (d^2 each), the intra-chunk product
    ``P R`` (C d) and the state's update (d^2): 16 384 multiply-adds a
    token and key head, 77 952 a token and value head at d = 128."""
    d, c = config["linear_key_head_dim"], GDN_WORK_CHUNK
    if config["linear_value_head_dim"] != d:
        raise spec.SpecError("gdn_work is stated for d_k = d_v")
    a_key = 2 * c * d
    a_value = 2 * c + c * c + 3 * c * d + 3 * d * d
    return 2.0 * (config["linear_num_key_heads"] * a_key
                  + config["linear_num_value_heads"] * a_value)


def share_params(config: dict) -> int:
    """Parameters of the chip's share: the model's own leaf count."""
    model, _ = _model(config, {"remat": False})
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, GDN_WORK_CHUNK), jnp.int32)))
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(shapes))


def _score_flops_per_row_pair(config: dict) -> float:
    """Operations a (query, key) pair of one head costs, forward and
    backward: ``q k^T`` and ``P V`` forward (4 D); the scores again,
    ``dP``, ``dV``, ``dK``, ``dQ`` backward (10 D)."""
    return 14.0 * config["head_dim"]


def flops_per_token(config: dict, seq_len: int) -> float:
    """Required matmul operations of THIS CHIP's share per trained token:
    6 per weight a token meets (``_weights``; the head once — the embedding
    side is a gather) + the DeltaNet scans, forward and a backward of
    twice the forward + attention's scores, the causal half at 16 heads of
    256 (so that ``flash_roofline`` and ``mfu_pct`` count one work).
    Recomputation under ``remat`` is not counted."""
    kinds, per = _kinds(config), _weights(config)
    weights = (sum(kinds[k] * per[k] for k in kinds)
               + config["hidden_size"] * config["vocab_size"])
    scores = (kinds["attn"] * config["num_attention_heads"]
              * _score_flops_per_row_pair(config) * seq_len / 2)
    return (6.0 * weights + scores
            + kinds["gdn"] * 3.0 * gdn_forward_flops_per_token(config))


def flash_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2
               ) -> dict:
    """Required operations and HBM bytes of one step's flash calls under
    the scope ``attn`` on one chip, every attention layer's.  Operations:
    the causal half of 16 heads of 256.  Bytes: what the ALGORITHM moves —
    q (read forward and backward), dQ, o (written, read again) and dO at
    16 x 256; k and v (each twice), dK and dV at the TWO key/value heads
    (repeating them over the query heads before the call is the program's
    choice); three float32 rows a head (lse; lse and delta again).  The
    forward recomputed under ``remat`` is the program's work: not
    counted."""
    heads, kv, d = (config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    n, rows = _kinds(config)["attn"], seqs * seq_len
    flops = (n * seqs * heads * _score_flops_per_row_pair(config)
             * seq_len * seq_len / 2)
    layer_bytes = (rows * 3 * (2 * heads * d + 2 * kv * d) * itemsize
                   + 3 * 4.0 * rows * heads)
    return {"flops": flops, "bytes": n * layer_bytes,
            "op_name_re": r"/attn/pallas_call$"}


def gdn_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2,
             remat: bool = True) -> dict:
    """Required operations and HBM bytes of one step's delta-rule scans on
    one chip, every DeltaNet layer: the ALGORITHM's in the head-decay WY
    form at the stated chunk, whatever chunk or kernel implements it.
    Operations: the forward (``gdn_forward_flops_per_token``), a backward
    of twice that, and the forward once more where ``remat`` recomputes
    it.  Bytes: ``q``, ``k`` (key heads x head size) and ``v``, ``o``
    (value heads x head size) in the compute type, ``g`` and ``beta``
    float32 a value head, each and its gradient read or written once."""
    d = config["linear_key_head_dim"]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    layers, tokens = _kinds(config)["gdn"], seqs * seq_len
    passes = 4.0 if remat else 3.0
    row_bytes = (2 * hk + 2 * hv) * d * itemsize + 2 * 4.0 * hv
    return {"flops": layers * passes * tokens
            * gdn_forward_flops_per_token(config),
            "bytes": layers * 2.0 * tokens * row_bytes,
            # the kernels carry ``name=`` (bps_gdn_fwd / bps_gdn_bwd) under
            # the mixer's ``bps.gdn.scan`` scope
            "op_name_re": r"bps\.gdn\.scan\)*/.*pallas_call$"}


def moe_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2,
             pair_share=None) -> dict:
    """Required operations and HBM bytes of the held routed experts'
    grouped matmuls of ONE step on one chip, every layer: the pair rows
    that fall on held experts — the expected ``held / published`` of all
    ``tokens x num_experts_per_tok`` (32 / 512), or ``pair_share`` of them
    where the batch's own share is known — through THREE matmuls (gate,
    up, down) in three passes (forward, row gradient, matrix gradient),
    each 2 M h f.  Bytes: a pass touches every HELD expert's matrix once
    and each matmul's live row blocks in and out once.  Dead rows need
    nothing; the forward recomputed under ``remat`` is not the
    algorithm's (``families/ling.py`` ``moe_work``)."""
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
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "full_attention_interval", "num_attention_heads", "num_key_value_heads",
    "head_dim", "partial_rotary_factor", "rope_theta", "rope_scaling",
    "use_sliding_window", "linear_conv_kernel_dim", "linear_key_head_dim",
    "linear_value_head_dim", "linear_num_key_heads",
    "linear_num_value_heads", "decoder_sparse_step", "mlp_only_layers",
    "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "norm_topk_prob", "hidden_act",
    "rms_norm_eps", "tie_word_embeddings", "max_position_embeddings")


def _model(config: dict, traffic: dict):
    """(the model at the share the file states, the flash function or
    None)."""
    from byteps_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig
    spec.fixed(config, model_type="qwen3_next", param_dtype="float32",
               norm_eps=config["rms_norm_eps"])
    first, count = config["experts_held"]
    if count != config["num_experts"]:
        raise spec.SpecError(
            f"num_experts ({config['num_experts']}) counts the experts "
            f"held; experts_held says {count}")
    # every other key goes to the model file under its own name, which
    # refuses what it cannot compute; the key that COUNTS the experts held
    # goes beside its published twin
    cfg = Qwen3NextConfig(
        **{k: config[k] for k in SAME_NAME_KEYS},
        num_experts=config["num_experts_published"],
        experts_held=(first, count),
        dtype=jnp.dtype(config["compute_dtype"]).type,
        remat=bool(traffic.get("remat", False)))
    attention_kind = traffic.get("attention", "exact")
    if attention_kind == "flash":
        from byteps_tpu.ops import flash_attention as attn_fn
    elif attention_kind == "exact":
        attn_fn = None
    else:
        raise ValueError(f"unknown attention {attention_kind!r}")
    return Qwen3Next(cfg, attn_fn=attn_fn), attn_fn


def build(config: dict, traffic: dict):
    from byteps_tpu.models import qwen3_next
    model, attn_fn = _model(config, traffic)
    cfg = model.cfg
    if traffic["objective"] != "clm":
        raise ValueError(f"family qwen3_next has no objective "
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
        work = {"gdn": gdn_work(config, seq, seqs_per_chip, remat=remat),
                # what ``moe_ms`` times and ``moe_roofline`` stands against
                "moe": moe_work(config, seq, seqs_per_chip)}
        if attn_fn is not None:
            work["flash"] = flash_work(config, seq, seqs_per_chip)
        return work

    reference = dict(
        eps=cfg.rms_norm_eps, key_heads=cfg.linear_num_key_heads,
        rotary=cfg.rotary_dim, theta=float(cfg.rope_theta), held=cfg.held,
        top_k=cfg.num_experts_per_tok, renormalize=cfg.norm_topk_prob)
    return types.SimpleNamespace(
        init_params=init_params,
        loss_fn=functools.partial(qwen3_next.qwen3_next_loss, model),
        make_batch=make_batch,
        reference_loss=functools.partial(reference_loss, **reference),
        tokens_per_seq=seq, flops_per_token=flops_per_token(config, seq),
        kernel_work=kernel_work,
        # the rows the head reads, [B, T, h], of the program and of the
        # reference (``benchmarks/tests/gradcheck_qwen3_next.py``)
        hidden=model.apply,
        reference_hidden=functools.partial(reference_hidden, **reference),
        compute_dtype=cfg.dtype,
        # one layer's scan: sequences, T, key heads, value heads, head size
        gdn_shape=(int(traffic["seqs_per_chip"]), seq,
                   cfg.linear_num_key_heads, cfg.linear_num_value_heads,
                   cfg.linear_key_head_dim),
        # the share and its [layers, 512] pair counts of one batch
        experts_held=cfg.held,
        expert_counts=lambda p, b: qwen3_next.expert_counts(
            model, p, b["input_ids"]))
