"""Family ``glm_lite``: byteps_tpu.models.glm_lite under next-token
prediction with a depth-1 multi-token-prediction module.

Configuration keys as in the source's ``config.json`` (``model_type:
glm4_moe_lite``), plus the chip's share of a stated deployment:
``n_routed_experts`` counts the routed experts HELD here (``experts_held``
says which), beside ``n_routed_experts_published``, the published count and
the router's width; ``vocab_size`` is the slice of ``vocab_size_published``
rows of the embedding and of the head held; ``num_hidden_layers`` the
leading dense block and the sparse blocks that follow it here.

The plain reference is float32 ``jax.numpy`` on the same parameter tree,
written from the equations of ISSUE 35 (the keys are DeepSeek-V3's:
arXiv:2412.19437 sections 2.1-2.2) and importing nothing of the program.
Per block: RMSNorm; the query through its rank-768 latent and the latent's
own norm; the joint key/value latent (512, normed) and the ONE rotary key
(64) cut from one projection; keys and values up-projected a head; q and k
built by explicit concatenation, the one rotary key in every head's k; an
exact masked softmax at scale 1/sqrt(256); RMSNorm; the dense SwiGLU in
the leading block, else sigmoid scores over all 64 experts, the 4 largest
of ``score + bias``, their scores renormalised (+1e-20) and scaled by 1.8,
the HELD experts in their DENSE form (each on every token, times its weight
or zero: no sort, no grouped matmul), the shared expert added once.  The
module: both norms, the concatenation ``[h ; Emb(next token)]`` through
``M``, one more sparse block, its own last norm; ids and labels shifted
explicitly.  Both heads over the slice, in blocks.  Departures from the
published model are noted at their lines: the bias stays zero, no auxiliary
loss stands in for its rule, lambda is the report's first-phase 0.3.

At the published widths it has to be lean: beside it the harness keeps
float32 parameters, two moments and a gradient (4 x 2.83 GB).  So it
computes in blocks under ``jax.checkpoint`` — each block of the model,
attention one (sequence, head) at a time from the latents on and one block
of 1 024 query rows at a time inside, the
dense MLP and the shared expert in blocks of 2 048 rows, the held experts
one after another, the heads and their log-softmax 512 positions at a time.  Blocking and rematerialising change
memory, not mathematics.
"""

from __future__ import annotations

import functools
import math
import types

import jax
import jax.numpy as jnp

from harness import flops as F
from harness import spec

HEAD_BLOCK = 512          # positions per block of a vocabulary head
QUERY_BLOCK = 1024        # query rows per block of the exact attention
ROW_BLOCK = 2048          # rows per block of the dense MLP, the shared expert


# ------------------------------------------------------------- reference

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotate(x, theta):
    """x: [B, T, H, R]; pairs (x[i], x[i + R/2]) turned by t theta^(-2i/R)
    (rotate-half: ``assumed.rotary`` of the configuration file)."""
    t, rot = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


def latent_attention(a, p, *, nope, theta, eps):
    """One (sequence, head) at a time, its q, k and v made from the two
    latents inside the loop: whole, ``[B, T, 20, 256]`` float32 q, k, v
    and their cotangents are 2 GiB a block."""
    rank = p["kv_a_layernorm"]["scale"].shape[0]
    c_q = rms_norm(a @ p["q_a_proj"]["kernel"], p["q_a_layernorm"]["scale"],
                   eps)                                  # [B, T, 768]
    ckv = a @ p["kv_a_proj_with_mqa"]["kernel"]          # [B, T, 512 + 64]
    c_kv = rms_norm(ckv[..., :rank], p["kv_a_layernorm"]["scale"], eps)
    k_rope = rotate(ckv[:, :, None, rank:], theta)[:, :, 0]   # ONE key

    @jax.checkpoint
    def one_head(c_q1, c_kv1, k_rope1, w_uq, w_ukv):
        q = c_q1 @ w_uq                                  # [T, 192 + 64]
        q = jnp.concatenate(
            [q[:, :nope], rotate(q[None, :, None, nope:], theta)[0, :, 0]],
            -1)
        kv = c_kv1 @ w_ukv                               # [T, 192 + 256]
        # k = [k_nope | the rotary key]: the SAME 64 lanes in every head
        k = jnp.concatenate([kv[:, :nope], k_rope1], -1)
        return causal_softmax_attention(q, k, kv[:, nope:])

    by_head = (p["q_b_proj"]["kernel"].transpose(1, 0, 2),
               p["kv_b_proj"]["kernel"].transpose(1, 0, 2))
    ctx = jax.lax.map(
        lambda seq: jax.lax.map(lambda w: one_head(*seq, *w), by_head),
        (c_q, c_kv, k_rope))                             # [B, H, T, 256]
    return jnp.einsum("bntd,ndh->bth", ctx, p["o_proj"]["kernel"])


def swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def in_row_blocks(fn, m):
    """``fn`` over blocks of ``ROW_BLOCK`` rows of m [N, h], each under
    ``jax.checkpoint``: a [N, 10 240] float32 activation would not fit."""
    n, h = m.shape
    rows = math.gcd(n, ROW_BLOCK)
    return jax.lax.map(jax.checkpoint(fn),
                       m.reshape(n // rows, rows, h)).reshape(n, -1)


def dense_mlp(m, p):
    return in_row_blocks(
        lambda mb: swiglu(mb, p["gate_proj"]["kernel"],
                          p["up_proj"]["kernel"], p["down_proj"]["kernel"]),
        m)


def unstacked(stack):
    """The matrices of a [count, ., .] stack, cut ONCE: the cotangent of
    ``stack[i]`` taken ``count`` times is ``count`` zero-padded stacks and
    their sum (1.5 GiB a block); a split's is one concatenation."""
    return [jnp.squeeze(one, 0) for one in jnp.split(stack, stack.shape[0])]


def sparse_mlp(m, p, *, top_k, held, scaling, renormalize):
    """m: [N, h] -> the held routed experts' part of the sum, scaled, plus
    the shared expert (whole on every chip: counted once)."""
    first, count = held
    scores = jax.nn.sigmoid(m @ p["router"])             # [N, 64]
    e = scores.shape[-1]
    # departure: the bias is the zeros it starts as (the report's balancing
    # rule is outside the gradient and has no key); it chooses only
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["e_score_correction_bias"]), top_k)
    picked = (jnp.arange(e) == chosen[..., None]).any(-2)
    weight = jnp.where(picked, scores, 0.0)
    if renormalize:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = scaling * weight[:, first:first + count]    # the experts held

    @jax.checkpoint
    def one_expert(m, gate, up, down, w_e):
        return w_e[:, None] * swiglu(m, gate, up, down)

    # each held expert in its DENSE form: on every row, times its weight
    # or zero (no sort, no grouped matmul).  Unrolled, not scanned: XLA
    # gives every loop-carried buffer an allocation of its own that nothing
    # else shares (PERF.md section 6, PRs 29-31) — and one AFTER another
    # (the barrier ties expert i + 1's input to expert i's sum, forward
    # and backward): side by side, eight experts' [N, 1536] float32
    # activations and cotangents are 4.9 GiB
    y = jnp.zeros_like(m)
    for i, matrices in enumerate(zip(*(unstacked(p[k]) for k in
                                       ("gate", "up", "down")))):
        y = y + one_expert(m, *matrices, weight[:, i])
        y, m = jax.lax.optimization_barrier((y, m))
    shared = p["shared_experts"]
    return y + in_row_blocks(
        lambda mb: swiglu(mb, shared["gate_proj"]["kernel"],
                          shared["up_proj"]["kernel"],
                          shared["down_proj"]["kernel"]), m)


def block(x, p, *, model):
    """One block of the model; its MLP kind is what its parameters are.
    Attention and MLP each under a ``jax.checkpoint`` of its own inside the
    block's: what one saves for its backward pass is gone during the
    other's."""
    eps = model["eps"]
    b, t, h = x.shape
    attend = jax.checkpoint(functools.partial(
        latent_attention, nope=model["nope"], theta=model["theta"], eps=eps))
    x = x + attend(rms_norm(x, p["input_layernorm"]["scale"], eps),
                   p["attn_mla"])
    m = rms_norm(x, p["post_attention_layernorm"]["scale"], eps
                 ).reshape(b * t, h)
    if "mlp" in p:
        y = jax.checkpoint(dense_mlp)(m, p["mlp"])
    else:
        y = jax.checkpoint(functools.partial(
            sparse_mlp, top_k=model["top_k"], held=model["held"],
            scaling=model["scaling"], renormalize=model["renormalize"]))(
                m, p["moe"])
    return x + y.reshape(b, t, h)


def reference_hidden(params, ids, **model):
    """-> (rows the main head reads, rows the module's head reads), each
    [B, T, h]; the module's LAST position has no next token: its input is
    a zero embedding there and nothing scores it."""
    p = params["params"]
    table, eps = p["wte"]["embedding"], model["eps"]
    step = jax.checkpoint(functools.partial(block, model=model))
    x = table[ids]
    for i in range(model["layers"]):
        x = step(x, p[f"h{i}"])
    g = None
    if "mtp" in p:
        mtp = p["mtp"]
        # position i reads the embedding of token i + 1: an explicit shift
        emb_next = jnp.concatenate(
            [table[ids[:, 1:]], jnp.zeros_like(x[:, :1])], axis=1)
        joined = jnp.concatenate(
            [rms_norm(x, mtp["hnorm"]["scale"], eps),
             rms_norm(emb_next, mtp["enorm"]["scale"], eps)], axis=-1)
        g = step(joined @ mtp["eh_proj"]["kernel"], mtp["block"])
        g = rms_norm(g, mtp["norm"]["scale"], eps)
    return rms_norm(x, p["norm_f"]["scale"], eps), g


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
    after, over the slice.  Departures: no auxiliary loss and no z-loss
    (the family balances by the selection bias); lambda = 0.3, the
    report's first phase."""
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


# ------------------------------------------------- operations and bytes

def _attention_params(config: dict) -> int:
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rq, rkv, v = (config["q_lora_rank"], config["kv_lora_rank"],
                  config["v_head_dim"])
    return (h * rq + rq * heads * (nope + rope) + h * (rkv + rope)
            + rkv * heads * (nope + v) + heads * v * h)


def _blocks(config: dict):
    """(dense blocks, sparse blocks): the model's, and the module's one
    sparse block with them."""
    dense = config["first_k_dense_replace"]
    return dense, (config["num_hidden_layers"] - dense
                   + config["num_nextn_predict_layers"])


def share_params(config: dict) -> int:
    """Parameters of the chip's share: per block the five attention
    matrices, the two latent norms and the two block norms; the dense
    block's MLP; per sparse block the router, the selection bias, the
    shared expert and the HELD routed experts; embedding, head and the
    last norm; the module's ``M`` and its three norms."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    dense, sparse = _blocks(config)
    routed = config["n_routed_experts_published"]
    every = (_attention_params(config) + config["q_lora_rank"]
             + config["kv_lora_rank"] + 2 * h)
    moe = (h * routed + routed + 3 * h * f * config["n_shared_experts"]
           + config["n_routed_experts"] * 3 * h * f)
    mtp = config["num_nextn_predict_layers"] * (2 * h * h + 3 * h)
    return ((dense + sparse) * every
            + dense * 3 * h * config["intermediate_size"] + sparse * moe
            + 2 * config["vocab_size"] * h + h + mtp)


def flops_per_token(config: dict, seq_len: int) -> float:
    """Required matmul operations of THIS CHIP's share per trained token:
    in every block the five attention projections; the dense MLP; per
    sparse block the router, the shared expert and the token's pairs that
    fall on held experts — ``held / routed`` of its ``num_experts_per_tok``
    in expectation (the others are computed on the other chips); ``M``;
    the head TWICE (the embedding side is a gather) — 6 per weight.
    Attention, forward + backward: the causal half at ``heads x 256``, 4 x
    head size a score forward and 10 backward, the flash algorithm's five
    backward matmuls as ``harness/flops.py`` ``flash_backward`` counts them
    (so that ``mla_flash_roofline`` and ``mfu_pct`` count one work; the 12
    of ``attention_flops_per_token`` would read 3.63 G for 3.88).
    Recomputation under ``remat`` is not counted."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    heads, d = config["num_attention_heads"], config["v_head_dim"]
    dense, sparse = _blocks(config)
    routed = config["n_routed_experts_published"]
    pairs_here = (config["num_experts_per_tok"] * config["n_routed_experts"]
                  / routed)
    weights = ((dense + sparse) * _attention_params(config)
               + dense * 3 * h * config["intermediate_size"]
               + sparse * (h * routed
                           + 3 * h * f * config["n_shared_experts"]
                           + pairs_here * 3 * h * f)
               + config["num_nextn_predict_layers"] * 2 * h * h
               + (1 + config["num_nextn_predict_layers"])
               * h * config["vocab_size"])
    scores = (dense + sparse) * 14.0 * d * heads * seq_len / 2
    return 6.0 * weights + scores


def flash_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2
               ) -> dict:
    """``{"flash", "mla_flash"}`` (one set of kernels under two names: what
    ``flash_ms`` times, and what ``mla_flash_roofline`` stands against):
    required operations and HBM bytes of one step's flash calls under the
    scope ``attn_mla`` on one chip, every block's (the module's too).
    Operations: the causal half of ``[20, seq, 256]`` a sequence and
    block, 4 x head size a score forward and 10 backward
    (``harness/flops.py``).  Bytes: what the ALGORITHM moves — q, o (read
    again in the backward), dO and dQ at 20 x 256; k and dK at 20 x 192
    and ONE 64-wide rotary key (repeating it over the 20 heads before the
    call is the program's choice, not the algorithm's); v and dV at 20 x
    256; three float32 rows a head (lse; lse and delta again).  The
    forward recomputed under ``remat`` is the program's work: not
    counted."""
    heads, d = config["num_attention_heads"], config["v_head_dim"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    n = sum(_blocks(config))
    flops = n * (F.flash_forward(seqs, heads, seq_len, d, True)["flops"]
                 + F.flash_backward(seqs, heads, seq_len, d, True)["flops"])
    rows = seqs * seq_len
    block_bytes = (rows * (6 * heads * d + 2 * (heads * nope + rope)
                           + 2 * heads * d) * itemsize
                   + 3 * 4.0 * rows * heads)
    work = {"flops": flops, "bytes": n * block_bytes,
            "op_name_re": r"/attn_mla/pallas_call$"}
    return {"flash": work, "mla_flash": dict(work)}


def moe_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2,
             pair_share=None) -> dict:
    """Required operations and HBM bytes of the held routed experts'
    grouped matmuls of ONE step on one chip, all sparse blocks: the pair
    rows that fall on held experts — the expected ``held / routed`` of all
    ``tokens x num_experts_per_tok`` (an eighth), or ``pair_share`` of them
    where the batch's own share is known — through three matmuls (gate,
    up, down) in three passes (forward, row gradient, matrix gradient),
    each 2 M h f.  Bytes: a pass touches every HELD expert's matrix once
    and each matmul's live row blocks in and out once.  Dead rows need
    nothing; the forward recomputed under ``remat`` is not the
    algorithm's."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    held = config["n_routed_experts"]
    if pair_share is None:
        pair_share = held / config["n_routed_experts_published"]
    m = seqs * seq_len * config["num_experts_per_tok"] * pair_share
    n = _blocks(config)[1]
    matmuls, passes = 3, 3
    return {"flops": n * matmuls * passes * 2.0 * m * h * f,
            "bytes": n * matmuls * passes * float(itemsize) * (
                held * h * f + m * (h + f)),
            # megablox's kernels carry no name of their own: they are the
            # pallas_calls under the layer's ``bps.moe.experts`` scope
            "op_name_re": r"bps\.moe\.experts/.*pallas_call$"}


# ----------------------------------------------------------------- build

def build(config: dict, traffic: dict):
    from byteps_tpu.models.glm_lite import (GlmLite, GlmLiteConfig,
                                            expert_counts, glm_lite_loss)
    spec.fixed(config, model_type="glm4_moe_lite", param_dtype="float32")
    first, count = config["experts_held"]
    if count != config["n_routed_experts"]:
        raise spec.SpecError(
            f"n_routed_experts ({config['n_routed_experts']}) counts the "
            f"experts held; experts_held says {count}")
    # every other key goes to the model file under its own name, which
    # refuses what it cannot compute
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "first_k_dense_replace", "intermediate_size",
            "moe_intermediate_size", "n_shared_experts",
            "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
            "topk_method", "n_group", "topk_group",
            "num_nextn_predict_layers", "rope_theta",
            "partial_rotary_factor", "rope_scaling",
            "max_position_embeddings", "rms_norm_eps", "attention_bias",
            "tie_word_embeddings", "hidden_act", "mtp_loss_weight")
    cfg = GlmLiteConfig(
        **{k: config[k] for k in keys},
        n_routed_experts=config["n_routed_experts_published"],
        experts_held=(first, count),
        dtype=jnp.dtype(config["compute_dtype"]).type,
        remat=bool(traffic.get("remat", False)))
    if traffic["objective"] != "clm":
        raise ValueError(f"family glm_lite has no objective "
                         f"{traffic['objective']!r}")
    attention_kind = traffic.get("attention", "exact")
    if attention_kind == "flash":
        from byteps_tpu.ops import flash_attention as attn_fn
    elif attention_kind == "exact":
        attn_fn = None
    else:
        raise ValueError(f"unknown attention {attention_kind!r}")
    model = GlmLite(cfg, attn_fn=attn_fn)
    seq = traffic["seq_len"]
    if seq > cfg.max_position_embeddings:
        raise ValueError(f"seq_len {seq} exceeds the model's context "
                         f"{cfg.max_position_embeddings}")

    def init_params(key):
        return model.init(key, jnp.zeros((1, seq), jnp.int32))

    def make_batch(key, n_seqs):
        # token ids are drawn from the slice of the vocabulary held here
        ids = jax.random.randint(key, (n_seqs, seq), 0, cfg.vocab_size)
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full((n_seqs, 1), -1, ids.dtype)], axis=1)
        return {"input_ids": ids, "labels": labels}

    def kernel_work(seqs_per_chip):
        work = {"routed_moe": moe_work(config, seq, seqs_per_chip),
                # every Mosaic kernel of the module's block, whatever it is
                "mtp": {"op_name_re": r"/mtp/.*pallas_call$"}}
        if attention_kind == "flash":
            work.update(flash_work(config, seq, seqs_per_chip))
        return work

    reference = dict(
        layers=cfg.num_hidden_layers, nope=cfg.qk_nope_head_dim,
        theta=float(cfg.rope_theta), top_k=cfg.num_experts_per_tok,
        held=(first, count), scaling=float(cfg.routed_scaling_factor),
        renormalize=cfg.norm_topk_prob, eps=cfg.rms_norm_eps)
    return types.SimpleNamespace(
        init_params=init_params,
        loss_fn=functools.partial(glm_lite_loss, model),
        make_batch=make_batch,
        reference_loss=functools.partial(
            reference_loss, mtp_weight=float(cfg.mtp_loss_weight),
            **reference),
        tokens_per_seq=seq, flops_per_token=flops_per_token(config, seq),
        kernel_work=kernel_work,
        # the rows both heads read, [B, T, h] each, of the program and of
        # the reference: at this vocabulary the logits are compared in
        # blocks (``benchmarks/tests/gradcheck_glm_lite.py``)
        hidden=model.apply,
        reference_hidden=functools.partial(reference_hidden, **reference),
        # the share, and its [sparse blocks, 64] pair counts of one batch
        experts_held=(first, count),
        routed_moe_work=functools.partial(moe_work, config, seq),
        expert_counts=lambda p, b: expert_counts(model, p, b["input_ids"]))
