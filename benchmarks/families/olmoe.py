"""Family ``olmoe``: byteps_tpu.models.olmoe under next-token prediction.

Configuration keys as in the source's ``config.json`` (``model_type:
olmoe``).  The plain reference is float32 ``jax.numpy`` on the same
parameter tree: RMSNorm, bias-free q/k/v/o, q_norm / k_norm over the
whole projected vector before the head split, rotate-half RoPE, exact
causal softmax attention, a softmax router whose k largest probabilities
weigh the experts WITHOUT renormalisation, SiLU-gated experts in their
DENSE form (every expert on every token, times its weight or zero: no
sort, no grouped matmul, no ``lax.top_k``), untied head; loss =
cross-entropy + ``router_aux_loss_coef`` x sum of load-balance losses +
``router_z_loss_coef`` x sum of router z-losses, over the tokens it is
given (the router terms are of the token shard, so the traffic file's
``reference_microbatch`` is the chip's whole shard).

At the published widths the reference has to be lean: the harness keeps
float32 parameters, two moments, a gradient and a gradient sum alive
(5 x 2.5 GB), which leaves ~4 GiB of a v5e.  So it computes in blocks
under ``jax.checkpoint`` — attention one head at a time, the experts one
at a time, the head and its log-softmax over blocks of positions.
Blocking and rematerialising change memory, not mathematics.
"""

from __future__ import annotations

import functools
import math
import types

import jax
import jax.numpy as jnp

from harness import flops as F
from harness import spec

HEAD_BLOCK = 512          # positions per block of the vocabulary head


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotate_half(x, theta):
    """x: [B, T, H, D]; pairs (x[i], x[i + D/2]) turned by t theta^(-2i/D)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
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
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(qkv):                       # each [T, D]
        q1, k1, v1 = qkv
        s = jnp.where(causal, q1 @ k1.T / math.sqrt(h // heads), -jnp.inf)
        return jax.nn.softmax(s, -1) @ v1

    # [B, T, H, D] -> [B H, T, D]: one (sequence, head) at a time
    flat = [a.transpose(0, 2, 1, 3).reshape(b * heads, t, h // heads)
            for a in (q, k, v)]
    ctx = jax.lax.map(one_head, tuple(flat))
    ctx = ctx.reshape(b, heads, t, h // heads).transpose(0, 2, 1, 3)
    return ctx.reshape(b, t, h) @ p["o_proj"]["kernel"]


def moe(x, p, top_k):
    """x: [N, h] -> (y, load-balance loss, router z-loss)."""
    n, e = x.shape[0], p["router"].shape[-1]
    logits = x @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    kth = jnp.sort(probs, -1)[:, e - top_k][:, None]
    chosen = probs >= kth
    weight = jnp.where(chosen, probs, 0.0)             # not renormalised

    @jax.checkpoint
    def one_expert(x, gate, up, down, w_e):
        return w_e[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down)

    def add_expert(y, ew):
        return y + one_expert(x, *ew), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (p["gate"], p["up"], p["down"], weight.T))
    aux = e * jnp.sum(chosen.sum(0) / n * probs.mean(0))
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return y, aux, z


def head_nll(x, kernel, labels):
    """Sum of next-token negative log-likelihoods and the count of valid
    positions, over blocks of positions (x: [N, h], labels: [N])."""
    n = x.shape[0]
    block = math.gcd(n, HEAD_BLOCK)

    @jax.checkpoint
    def one_block(xl):
        xb, lb = xl
        valid = lb >= 0
        logp = jax.nn.log_softmax(xb @ kernel, -1)
        ll = jnp.take_along_axis(logp, jnp.where(valid, lb, 0)[:, None],
                                 -1)[:, 0]
        return -(ll * valid).sum(), valid.sum()

    nll, count = jax.lax.map(one_block, (x.reshape(n // block, block, -1),
                                         labels.reshape(n // block, block)))
    return nll.sum(), count.sum()


def reference_loss(params, batch, *, heads, top_k, theta, eps, aux_coef,
                   z_coef):
    p = params["params"]
    ids, labels = batch["input_ids"], batch["labels"]
    with jax.default_matmul_precision("highest"):
        x = p["wte"]["embedding"][ids]
        b, t, h = x.shape
        aux = z = 0.0
        for i in range(sum(1 for k in p if k[0] == "h" and k[1:].isdigit())):
            blk = p[f"h{i}"]
            x = x + attention(rms_norm(x, blk["attn_norm"]["scale"], eps),
                              blk["attn"], heads, theta, eps)
            y, a, zz = moe(rms_norm(x, blk["moe_norm"]["scale"],
                                    eps).reshape(b * t, h), blk["moe"], top_k)
            x, aux, z = x + y.reshape(b, t, h), aux + a, z + zz
        x = rms_norm(x, p["norm_f"]["scale"], eps)
        nll, count = head_nll(x.reshape(b * t, h), p["lm_head"]["kernel"],
                              labels.reshape(b * t))
        return nll / count + aux_coef * aux + z_coef * z


def flops_per_token(config: dict, seq_len: int) -> float:
    """Every position meets, in each layer, q/k/v/o (4 h^2), the router
    (h E) and the ``num_experts_per_tok`` ACTIVE experts (3 h f each: a
    token never meets the other experts, and counting all of them would
    read an MFU no chip can give), then the untied vocabulary head;
    ``wte`` is a gather.  Causal attention: half."""
    h, f = config["hidden_size"], config["intermediate_size"]
    layers = config["num_hidden_layers"]
    per_token = (layers * (4 * h * h
                           + config["num_experts_per_tok"] * 3 * h * f
                           + h * config["num_experts"])
                 + h * config["vocab_size"])
    return F.train_flops_per_token(per_token, layers, seq_len, h,
                                   causal=True)


def flash_work(config: dict, seq_len: int, seqs: int) -> dict:
    """As ``families/gpt.py``: all flash forward and backward calls of one
    step on one chip, at the model's head size (128: the kernel's native
    lane width, nothing padded)."""
    heads = config["num_attention_heads"]
    d = config["hidden_size"] // heads
    fwd = F.flash_forward(seqs, heads, seq_len, d, causal=True)
    bwd = F.flash_backward(seqs, heads, seq_len, d, causal=True)
    n = config["num_hidden_layers"]
    return {"flops": n * (fwd["flops"] + bwd["flops"]),
            "bytes": n * (fwd["bytes"] + bwd["bytes"]),
            "op_name_re": r"/attn/pallas_call$"}


def moe_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2
             ) -> dict:
    """Required operations and HBM bytes of the grouped matmuls of ONE
    step on one chip: per layer three matmuls (gate, up, down) in three
    passes (forward, row gradient, matrix gradient), each 2 M h f with
    M = tokens x experts per token pair rows.  Bytes: a pass touches
    every expert's matrix once (read, or written as its gradient) and
    each matmul's row blocks in and out once ([M, h] on one side, [M, f]
    on the other)."""
    h, f = config["hidden_size"], config["intermediate_size"]
    m = seqs * seq_len * config["num_experts_per_tok"]
    n = config["num_hidden_layers"]
    matmuls, passes = 3, 3
    return {"flops": n * matmuls * passes * 2.0 * m * h * f,
            "bytes": n * matmuls * passes * float(itemsize) * (
                config["num_experts"] * h * f + m * (h + f)),
            # megablox's kernels carry no name of their own: they are the
            # pallas_calls under the layer's ``bps.moe.experts`` scope
            "op_name_re": r"bps\.moe\.experts/.*pallas_call$"}


def build(config: dict, traffic: dict):
    from byteps_tpu.models.olmoe import (Olmoe, OlmoeConfig, expert_counts,
                                         olmoe_loss)
    # models/olmoe.py has no switch for these (module docstring)
    spec.fixed(config, model_type="olmoe", hidden_act="silu",
               attention_bias=False, clip_qkv=None, rope_scaling=None,
               norm_topk_prob=False, tie_word_embeddings=False,
               param_dtype="float32")
    cfg = OlmoeConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        max_position_embeddings=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        router_aux_loss_coef=config["router_aux_loss_coef"],
        router_z_loss_coef=config["router_z_loss_coef"],
        dtype=jnp.dtype(config["compute_dtype"]).type,
        remat=bool(traffic.get("remat", False)))
    if traffic["objective"] != "clm":
        raise ValueError(f"family olmoe has no objective "
                         f"{traffic['objective']!r}")
    attention_kind = traffic.get("attention", "exact")
    if attention_kind == "flash":
        from byteps_tpu.ops import flash_attention as attn_fn
    elif attention_kind == "exact":
        attn_fn = None
    else:
        raise ValueError(f"unknown attention {attention_kind!r}")
    model = Olmoe(cfg, attn_fn=attn_fn)
    seq = traffic["seq_len"]
    if seq > cfg.max_position_embeddings:
        raise ValueError(f"seq_len {seq} exceeds the model's context "
                         f"{cfg.max_position_embeddings}")

    def init_params(key):
        return model.init(key, jnp.zeros((1, seq), jnp.int32))

    def make_batch(key, n_seqs):
        ids = jax.random.randint(key, (n_seqs, seq), 0, cfg.vocab_size)
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full((n_seqs, 1), -1, ids.dtype)], axis=1)
        return {"input_ids": ids, "labels": labels}

    def kernel_work(seqs_per_chip):
        work = {"moe": moe_work(config, seq, seqs_per_chip)}
        if attention_kind == "flash":
            work["flash"] = flash_work(config, seq, seqs_per_chip)
        return work

    return types.SimpleNamespace(
        init_params=init_params,
        loss_fn=functools.partial(olmoe_loss, model),
        make_batch=make_batch,
        reference_loss=functools.partial(
            reference_loss, heads=cfg.num_attention_heads,
            top_k=cfg.num_experts_per_tok, theta=cfg.rope_theta,
            eps=cfg.rms_norm_eps, aux_coef=cfg.router_aux_loss_coef,
            z_coef=cfg.router_z_loss_coef),
        tokens_per_seq=seq, flops_per_token=flops_per_token(config, seq),
        kernel_work=kernel_work,
        # [layers, experts] pair counts of one batch, for the load gauges
        expert_counts=lambda p, b: expert_counts(model, p, b["input_ids"]))
