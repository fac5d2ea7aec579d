"""Family ``mellum``: byteps_tpu.models.mellum under next-token prediction.

Configuration keys as in the source's ``config.json`` (``model_type:
mellum``), plus the chip's share of a stated deployment: ``num_experts``
counts the experts HELD here (``experts_held`` says which), beside
``num_routed_experts``, the published count and the router's width;
``vocab_size`` is the slice of ``vocab_size_published`` rows held; the
layers are the first ``num_hidden_layers`` of ``layer_types``.

The plain reference is float32 ``jax.numpy`` on the same parameter tree,
written from the config's equations and importing nothing of the program:
RMSNorm, bias-free q/k/v/o with 32 query and 4 key/value heads of 128, an
RMSNorm over each head's vector on q and k, rotate-half rotary with the
layer type's frequencies (sliding layers ``theta^(-2i/D)``; full layers
YaRN's blend from the formula below, cos and sin times
``attention_factor``), exact softmax attention under the band mask
``(j <= i) & (i - j < window)`` (full layers ``j <= i``) with query head
g indexing k/v head ``g // 8``; a softmax router over all 64 experts
whose k largest probabilities are divided by their sum, and the HELD
experts in their DENSE form (each on every token, times its renormalised
weight or zero: no sort, no grouped matmul, no ``lax.top_k``); the head
over the slice; loss = cross-entropy + ``router_aux_loss_coef`` x the sum
of the layers' load-balance losses (over all 64 experts, of the token
shard it is given: ``reference_microbatch`` is the chip's whole shard).

At the published widths it has to be lean: beside it the harness keeps
float32 parameters, two moments and a gradient (4 x 2.4 GB).  So it
computes in blocks under ``jax.checkpoint`` — each layer, attention one
(sequence, head) at a time, the experts one at a time, the head and its
log-softmax 512 positions at a time.  Blocking and rematerialising change
memory, not mathematics.
"""

from __future__ import annotations

import functools
import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from harness import flops as F
from harness import spec

SLIDING, FULL = "sliding_attention", "full_attention"


# ------------------------------------------------------------- reference

# the same mathematics as the sibling reference's: one copy under
# ``benchmarks/`` (RMSNorm; the head's log-softmax 512 positions at a time)
_OLMOE = spec.load_module("families", "olmoe")
rms_norm, head_nll = _OLMOE.rms_norm, _OLMOE.head_nll


def inv_freq(d: int, rope: dict):
    """([D/2] inverse frequencies, the factor on cos and sin).  YaRN (HF
    ``_compute_yarn_parameters``): with ``f_i = theta^(2i/D)``,
    ``inv_i = (1 - r_i) / (factor f_i) + r_i / f_i``, ``r_i = 1 -
    clip((i - lo) / (hi - lo), 0, 1)``, ``lo, hi`` = floor / ceil of
    ``(D/2) ln(L / (beta 2 pi)) / ln theta`` at ``beta_fast`` /
    ``beta_slow``, clamped to [0, D - 1]."""
    theta = float(rope["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (2 * i / d)
    if rope["rope_type"] == "default":
        return 1.0 / f, 1.0

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


def attention(x, p, heads, kv_heads, rope, window, eps):
    b, t, _ = x.shape
    d = p["q_proj"]["kernel"].shape[-1]
    q = jnp.einsum("bth,hnd->btnd", x, p["q_proj"]["kernel"])
    k = jnp.einsum("bth,hnd->btnd", x, p["k_proj"]["kernel"])
    v = jnp.einsum("bth,hnd->btnd", x, p["v_proj"]["kernel"])
    q = rotate_half(rms_norm(q, p["q_norm"]["scale"], eps), rope)
    k = rotate_half(rms_norm(k, p["k_norm"]["scale"], eps), rope)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = (j <= i) if window is None else (j <= i) & (i - j < window)

    groups = heads // kv_heads

    @jax.checkpoint
    def one_head(q1, k1, v1):                # each [T, D]
        s = jnp.where(keep, q1 @ k1.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(s, -1) @ v1

    def one_kv_head(qkv):
        """The ``groups`` query heads g with g // groups == this k/v
        head, one at a time."""
        qs, k1, v1 = qkv
        return jax.lax.map(lambda q1: one_head(q1, k1, v1), qs)

    # [B, T, H, D] -> [B Hkv, (groups,) T, D]: query head g of a sequence
    # sits at (g // groups, g % groups), i.e. with k/v head g // groups
    def by_kv_head(a):
        return a.transpose(0, 2, 1, 3).reshape(b * kv_heads, -1, t, d)

    ctx = jax.lax.map(one_kv_head, (by_kv_head(q), by_kv_head(k)[:, 0],
                                    by_kv_head(v)[:, 0]))
    ctx = ctx.reshape(b, heads, t, d).transpose(0, 2, 1, 3)
    return jnp.einsum("btnd,ndh->bth", ctx, p["o_proj"]["kernel"])


def moe(x, p, top_k, held):
    """x: [N, h] -> (the held experts' part of y, load-balance loss)."""
    n, e = x.shape[0], p["router"].shape[-1]
    first, count = held
    probs = jax.nn.softmax(x @ p["router"], -1)
    kth = jnp.sort(probs, -1)[:, e - top_k][:, None]
    chosen = probs >= kth
    weight = jnp.where(chosen, probs, 0.0)
    weight = weight / weight.sum(-1, keepdims=True)     # norm_topk_prob
    weight = weight[:, first:first + count]             # the experts held

    @jax.checkpoint
    def one_expert(x, gate, up, down, w_e):
        return w_e[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down)

    def add_expert(y, ew):
        return y + one_expert(x, *ew), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (p["gate"], p["up"], p["down"], weight.T))
    aux = e * jnp.sum(chosen.sum(0) / n * probs.mean(0))
    return y, aux


def reference_hidden(params, ids, *, layer_types, heads, kv_heads, window,
                     rope_parameters, top_k, held, eps):
    """-> (the last norm's output [B, T, h], sum of the layers'
    load-balance losses)."""
    p = params["params"]
    x = p["wte"]["embedding"][ids]
    b, t, h = x.shape
    aux = 0.0
    for i, kind in enumerate(layer_types):

        @jax.checkpoint
        def layer(x, blk, sliding=kind == SLIDING, kind=kind):
            x = x + attention(
                rms_norm(x, blk["attn_norm"]["scale"], eps),
                blk["attn_swa" if sliding else "attn"], heads, kv_heads,
                rope_parameters[kind], window if sliding else None, eps)
            y, a = moe(rms_norm(x, blk["moe_norm"]["scale"],
                                eps).reshape(b * t, h), blk["moe"],
                       top_k, held)
            return x + y.reshape(b, t, h), a

        x, a = layer(x, p[f"h{i}"])
        aux = aux + a
    return rms_norm(x, p["norm_f"]["scale"], eps), aux


def reference_loss(params, batch, *, aux_coef, **model):
    with jax.default_matmul_precision("highest"):
        x, aux = reference_hidden(params, batch["input_ids"], **model)
        b, t, h = x.shape
        nll, count = head_nll(x.reshape(b * t, h),
                              params["params"]["lm_head"]["kernel"],
                              batch["labels"].reshape(b * t))
        return nll / count + aux_coef * aux


def reference_logits(params, ids, **model):
    """Float32 logits [B, T, V] over the slice (for the comparisons of a
    few sequences: ``benchmarks/tests/gradcheck_mellum.py``)."""
    with jax.default_matmul_precision("highest"):
        x, _ = reference_hidden(params, ids, **model)
        return x @ params["params"]["lm_head"]["kernel"]


# ------------------------------------------------- operations and bytes

def _layers(config: dict) -> list:
    return list(config["layer_types"][:config["num_hidden_layers"]])


def band_keys(seq_len: int, window: int) -> int:
    """Scores one head needs under the window: row i sees ``min(i + 1,
    window)`` keys."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def share_params(config: dict) -> int:
    """Parameters of the chip's share: per layer q, k, v, o, the two
    per-head norms, the two RMSNorms, the router over all experts and the
    held experts' three matrices; embedding, head and the last norm."""
    h, d = config["hidden_size"], config["head_dim"]
    qo = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    layer = (2 * h * qo + 2 * h * kv + 2 * d + 2 * h
             + h * config["num_routed_experts"]
             + config["num_experts"] * 3 * h * config["moe_intermediate_size"])
    return (config["num_hidden_layers"] * layer
            + 2 * config["vocab_size"] * h + h)


def flops_per_token(config: dict, seq_len: int) -> float:
    """Required matmul operations of THIS CHIP's share per trained token:
    in each layer q/k/v/o, the router (h x 64) and the token's pairs that
    fall on held experts — ``num_experts_per_tok x held / routed`` of them
    in expectation (2 of 8: the others are computed on other chips, and
    counting them would read an MFU no chip can give) — 6 per weight;
    the head over the slice; ``wte`` is a gather.  Attention, forward +
    backward: 12 x (keys a row sees, averaged) x (heads x head_dim) a
    layer: ``seq / 2`` keys on a full layer (``harness/flops.py``'s causal
    half), ``band_keys / seq`` on a sliding one.  Recomputation is not
    counted."""
    h, d = config["hidden_size"], config["head_dim"]
    qo = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    pairs_here = (config["num_experts_per_tok"] * config["num_experts"]
                  / config["num_routed_experts"])
    per_layer = (2 * h * qo + 2 * h * kv + h * config["num_routed_experts"]
                 + pairs_here * 3 * h * config["moe_intermediate_size"])
    kinds = _layers(config)
    attn = sum(
        F.attention_flops_per_token(seq_len, qo, causal=True)
        if kind == FULL else
        12.0 * qo * band_keys(seq_len, config["sliding_window"]) / seq_len
        for kind in kinds)
    return (6.0 * (len(kinds) * per_layer + h * config["vocab_size"])
            + attn)


def _flash_bytes(config, seq_len, seqs, itemsize=2):
    """HBM bytes the ALGORITHM moves in one layer's forward + backward
    flash calls: q, o (read again in the backward), dO and dQ at the 32
    query heads, k, v, dK, dV at the 4 key/value heads (grouped-query
    attention reads each k/v head once for its 8 query heads; repeating
    them to 32 is the program's choice, not the algorithm's), and three
    float32 rows a query head (lse; lse and delta again)."""
    d = config["head_dim"]
    rows = seqs * seq_len
    q_side = rows * config["num_attention_heads"]
    kv_side = rows * config["num_key_value_heads"]
    forward = (2 * q_side + 2 * kv_side) * d * itemsize + 4.0 * q_side
    backward = (4 * q_side + 4 * kv_side) * d * itemsize + 2 * 4.0 * q_side
    return forward + backward


def flash_work(config: dict, seq_len: int, seqs: int) -> dict:
    """``{"flash", "swa_flash", "full_flash"}``: required operations and
    bytes of one step's flash calls on one chip — all of them (what
    ``flash_ms`` times: both scopes), the sliding layers' (scope
    ``attn_swa``) and the full layers' (scope ``attn``).  A score is 4
    operations x head_dim forward (QK^T, PV) and 10 backward (five
    matmuls).  A sliding layer needs ``band_keys`` scores a head, counted
    row by row (``min(i + 1, window)`` keys); a full layer the causal
    half, as ``harness/flops.py`` counts it.  The forward recomputed under
    ``remat`` is the program's work, not the algorithm's: not counted."""
    heads, d = config["num_attention_heads"], config["head_dim"]
    kinds = _layers(config)
    n_swa, n_full = kinds.count(SLIDING), kinds.count(FULL)
    band = 14.0 * seqs * heads * d * band_keys(seq_len,
                                               config["sliding_window"])
    full = (F.flash_forward(seqs, heads, seq_len, d, causal=True)["flops"]
            + F.flash_backward(seqs, heads, seq_len, d, causal=True)["flops"])
    layer_bytes = _flash_bytes(config, seq_len, seqs)
    swa = {"flops": n_swa * band, "bytes": n_swa * layer_bytes,
           "op_name_re": r"/attn_swa/pallas_call$"}
    whole = {"flops": n_full * full, "bytes": n_full * layer_bytes,
             "op_name_re": r"/attn/pallas_call$"}
    return {"swa_flash": swa, "full_flash": whole,
            "flash": {"flops": swa["flops"] + whole["flops"],
                      "bytes": swa["bytes"] + whole["bytes"],
                      "op_name_re": r"/attn(_swa)?/pallas_call$"}}


def moe_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2,
             pair_share=None) -> dict:
    """Required operations and HBM bytes of the held experts' grouped
    matmuls of ONE step on one chip: the pair rows that fall on held
    experts — the expected ``held / routed`` of all ``tokens x
    num_experts_per_tok`` (a quarter), or ``pair_share`` of them where the
    batch's own share is known — through three matmuls (gate, up, down)
    in three passes (forward, row gradient, matrix gradient), each 2 M h
    f.  Bytes: a pass touches every HELD expert's matrix once and each
    matmul's live row blocks in and out once.  Dead rows need nothing."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    if pair_share is None:
        pair_share = config["num_experts"] / config["num_routed_experts"]
    m = seqs * seq_len * config["num_experts_per_tok"] * pair_share
    n = config["num_hidden_layers"]
    matmuls, passes = 3, 3
    return {"flops": n * matmuls * passes * 2.0 * m * h * f,
            "bytes": n * matmuls * passes * float(itemsize) * (
                config["num_experts"] * h * f + m * (h + f)),
            # megablox's kernels carry no name of their own: they are the
            # pallas_calls under the layer's ``bps.moe.experts`` scope
            "op_name_re": r"bps\.moe\.experts/.*pallas_call$"}


# ----------------------------------------------------------------- build

def build(config: dict, traffic: dict):
    from byteps_tpu.models.mellum import (Mellum, MellumConfig,
                                          expert_counts, mellum_loss)
    # models/mellum.py has no switch for these (module docstring)
    spec.fixed(config, model_type="mellum", hidden_act="silu",
               attention_bias=False, tie_word_embeddings=False,
               use_sliding_window=True, norm_topk_prob=True,
               param_dtype="float32")
    kinds = _layers(config)
    if config["mlp_layer_types"][:len(kinds)] != ["sparse"] * len(kinds):
        raise spec.SpecError("models/mellum.py builds sparse MLPs only")
    first, count = config["experts_held"]
    if count != config["num_experts"]:
        raise spec.SpecError(
            f"num_experts ({config['num_experts']}) counts the experts "
            f"held; experts_held says {count}")
    cfg = MellumConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=len(kinds), layer_types=tuple(kinds),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        rope_parameters=config["rope_parameters"],
        num_experts=config["num_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        experts_held=(first, count),
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"],
        router_aux_loss_coef=config["router_aux_loss_coef"],
        dtype=jnp.dtype(config["compute_dtype"]).type,
        remat=bool(traffic.get("remat", False)))
    if traffic["objective"] != "clm":
        raise ValueError(f"family mellum has no objective "
                         f"{traffic['objective']!r}")
    attention_kind = traffic.get("attention", "exact")
    if attention_kind == "flash":
        from byteps_tpu.ops import flash_attention as attn_fn
    elif attention_kind == "exact":
        attn_fn = None
    else:
        raise ValueError(f"unknown attention {attention_kind!r}")
    model = Mellum(cfg, attn_fn=attn_fn)
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
        work = {"held_moe": moe_work(config, seq, seqs_per_chip)}
        if attention_kind == "flash":
            work.update(flash_work(config, seq, seqs_per_chip))
        return work

    reference = dict(
        layer_types=tuple(kinds), heads=cfg.num_attention_heads,
        kv_heads=cfg.num_key_value_heads, window=cfg.sliding_window,
        rope_parameters=config["rope_parameters"],
        top_k=cfg.num_experts_per_tok, held=(first, count),
        eps=cfg.rms_norm_eps)
    return types.SimpleNamespace(
        init_params=init_params,
        loss_fn=functools.partial(mellum_loss, model),
        make_batch=make_batch,
        reference_loss=functools.partial(
            reference_loss, aux_coef=cfg.router_aux_loss_coef, **reference),
        tokens_per_seq=seq, flops_per_token=flops_per_token(config, seq),
        kernel_work=kernel_work,
        # logits of the program and of the reference, [B, T, vocab slice]
        logits=model.apply,
        reference_logits=functools.partial(reference_logits, **reference),
        # the share, and its [layers, 64] pair counts of one batch
        experts_held=(first, count),
        held_moe_work=functools.partial(moe_work, config, seq),
        expert_counts=lambda p, b: expert_counts(model, p, b["input_ids"]))
