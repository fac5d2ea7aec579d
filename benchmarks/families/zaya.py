"""Family ``zaya``: byteps_tpu.models.zaya under next-token prediction.

Configuration keys as in the source's ``config.json`` (``model_type:
zaya``), plus the chip's share of a stated deployment: ``num_experts``
counts the experts HELD here (``experts_held`` says which), beside
``num_routed_experts``, the published count and the router's width;
``vocab_size`` is the slice of ``vocab_size_published`` rows of the tied
table held; the layers are the first ``num_hidden_layers`` of
``layer_types``.

The plain reference is float32 ``jax.numpy`` on the same parameter tree,
written from the equations of ISSUE 31 (Zyphra's CCA paper,
arXiv:2510.04476; the ZAYA1 report, arXiv:2511.17127) and importing
nothing of the program.  Per layer: RMSNorm; q~ (8 x 128) and k~ (2 x 128)
projected into the latent; the q-k mean; two causal convolutions of
kernel 2 over the packed [q~ ; k~] as explicit shifts (padded ONCE before
both: the per-head convolution sees ``b0`` at position -1); the value
shift (key/value head 1 from the token before); L2-normalised heads,
``sqrt(128)`` on both sides, a learned temperature a key/value head;
rotary over the first 64 of a head's 128; an exact masked softmax, query
head g indexing key/value head ``g // 4``; RMSNorm; the router (down to
256, ``gamma`` x the state of the layer before, carried by an explicit
loop, RMSNorm, two GELU layers, 16 scores, softmax); ``argmax(p + beta)``
and the HELD experts in their DENSE form (each on every token, times ``p``
at the chosen expert or zero: no sort, no grouped matmul); the tied head
over the slice.  Departures from the published model are noted at their
lines: ``beta`` stays zero, no auxiliary loss stands in for its rule, no
learned residual scaling, the chosen weight not renormalised.

At the published widths it has to be lean: beside it the harness keeps
float32 parameters, two moments and a gradient (4 x 2.8 GB).  So it
computes in blocks under ``jax.checkpoint`` — each layer, attention one
(sequence, head, block of 1 024 query rows) at a time, the experts one at
a time, the head and its log-softmax 512 positions at a time.  Blocking
and rematerialising change memory, not mathematics.
"""

from __future__ import annotations

import functools
import math
import types

import jax
import jax.numpy as jnp

from harness import flops as F
from harness import spec

HYBRID = "hybrid"
HEAD_BLOCK = 512          # positions per block of the vocabulary head
QUERY_BLOCK = 1024        # query rows per block of the exact attention


# ------------------------------------------------------------- reference

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


def causal_softmax_attention(q, k, v, groups):
    """q: [B, T, H, D], k / v: [B, T, Hkv, D] -> [B, T, H, D]: exact
    softmax over keys j <= i, one (sequence, key/value head, query head of
    its group, block of query rows) at a time."""
    b, t, heads, d = q.shape
    kv_heads = heads // groups
    rows = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(q1, first_row, k1, v1):        # [rows, D], [T, D]
        i = first_row + jnp.arange(rows)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= i,
                      q1 @ k1.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(s, -1) @ v1

    def one_head(q1, k1, v1):                    # q1: [T, D]
        return jax.lax.map(
            lambda blk: one_block(blk[0], blk[1], k1, v1),
            (q1.reshape(t // rows, rows, d), jnp.arange(0, t, rows))
        ).reshape(t, d)

    def one_kv_head(qkv):
        qs, k1, v1 = qkv                         # qs: [groups, T, D]
        return jax.lax.map(lambda q1: one_head(q1, k1, v1), qs)

    # [B, T, H, D] -> [B Hkv, (groups,) T, D]: query head g of a sequence
    # sits at (g // groups, g % groups), i.e. with k/v head g // groups
    def by_kv_head(a):
        return a.transpose(0, 2, 1, 3).reshape(b * kv_heads, -1, t, d)

    ctx = jax.lax.map(one_kv_head, (by_kv_head(q), by_kv_head(k)[:, 0],
                                    by_kv_head(v)[:, 0]))
    return ctx.reshape(b, heads, t, d).transpose(0, 2, 1, 3)


def cca(a, p, heads, kv_heads, theta, rot):
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
    if w0.shape[-1] != 2 or w1.shape[1] != 2:
        raise spec.SpecError("the reference writes out two taps "
                             "(cca_time0 = cca_time1 = 2)")
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
    ctx = causal_softmax_attention(q, k, v, groups)
    return jnp.einsum("btnd,ndh->bth", ctx, p["o_proj"]["kernel"])


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
    """m: [N, h], probs [N, E] -> the held experts' part of y."""
    e = probs.shape[-1]
    first, count = held
    # departure: beta is the zeros it starts as (the report's balancing
    # rule is outside the gradient and has no key); it chooses only
    chosen = jnp.argmax(probs + jax.lax.stop_gradient(p["balance_bias"]), -1)
    # departure: the weight is p at the chosen expert, not renormalised
    weight = jnp.where(jnp.arange(e) == chosen[:, None], probs, 0.0)
    weight = weight[:, first:first + count]              # the experts held

    @jax.checkpoint
    def one_expert(m, gate, up, down, w_e):
        return w_e[:, None] * ((jax.nn.silu(m @ gate) * (m @ up)) @ down)

    # unrolled, not scanned: XLA gives every loop-carried [N, h] buffer an
    # allocation of its own that nothing else shares, in every layer
    # (1.05 GiB a layer scanned, compile-only; PERF.md section 6 PR 30 met
    # the same), and the harness's two moments leave this program 5 GiB
    y = jnp.zeros_like(m)
    for i in range(count):
        y = y + one_expert(m, p["gate"][i], p["up"][i], p["down"][i],
                           weight[:, i])
    return y


def reference_hidden(params, ids, *, layers, heads, kv_heads, theta, rot,
                     held, eps):
    """-> the last norm's output [B, T, h]."""
    p = params["params"]
    x = p["wte"]["embedding"][ids]
    b, t, h = x.shape
    r = None
    for i in range(layers):                   # the loop carries (x, r)

        @jax.checkpoint
        def layer(x, r, blk):
            x = x + cca(rms_norm(x, blk["attn_norm"]["scale"], eps),
                        blk["attn_cca"], heads, kv_heads, theta, rot)
            m = rms_norm(x, blk["moe_norm"]["scale"], eps)
            probs, r = router(m, blk["moe"]["router"], r, eps)
            y = experts(m.reshape(b * t, h), blk["moe"],
                        probs.reshape(b * t, -1), held)
            # departure: no learned scale on either residual addition
            return x + y.reshape(b, t, h), r

        x, r = layer(x, r, p[f"h{i}"])
    return rms_norm(x, p["norm_f"]["scale"], eps)


def head_nll(x, table, labels):
    """Sum of next-token negative log-likelihoods and the count of valid
    positions against the TIED table [V, h], over blocks of positions
    (x: [N, h], labels: [N])."""
    n = x.shape[0]
    block = math.gcd(n, HEAD_BLOCK)

    @jax.checkpoint
    def one_block(xl):
        xb, lb = xl
        valid = lb >= 0
        logp = jax.nn.log_softmax(jnp.einsum("nh,vh->nv", xb, table), -1)
        ll = jnp.take_along_axis(logp, jnp.where(valid, lb, 0)[:, None],
                                 -1)[:, 0]
        return -(ll * valid).sum(), valid.sum()

    nll, count = jax.lax.map(one_block, (x.reshape(n // block, block, -1),
                                         labels.reshape(n // block, block)))
    return nll.sum(), count.sum()


def reference_loss(params, batch, **model):
    """Cross-entropy over the slice.  Departure: no auxiliary loss and no
    z-loss (the family balances by the selection bias)."""
    with jax.default_matmul_precision("highest"):
        x = reference_hidden(params, batch["input_ids"], **model)
        b, t, h = x.shape
        nll, count = head_nll(x.reshape(b * t, h),
                              params["params"]["wte"]["embedding"],
                              batch["labels"].reshape(b * t))
        return nll / count


# ------------------------------------------------- operations and bytes

def share_params(config: dict) -> int:
    """Parameters of the chip's share: per layer q, k, the two value
    halves, o, both convolutions with their biases, the temperatures, the
    two RMSNorms, the router (down + bias, two hidden layers + biases, its
    norm, the 16 scores; ``gamma`` from layer 1 on), the selection bias
    and the held experts' three matrices; the tied table and the last
    norm."""
    h, d = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    width, routed = config["router_hidden_size"], config["num_routed_experts"]
    packed = heads + kv
    attn = (2 * h * heads * d + h * kv * d + 2 * h * d
            + packed * d * (config["cca_time0"] + 1)
            + packed * d * (config["cca_time1"] * d + 1) + kv)
    route = (h * width + width + 2 * (width * width + width) + width
             + width * routed)
    layer = (attn + 2 * h + route + routed
             + config["num_experts"] * 3 * h * config["moe_intermediate_size"])
    n = config["num_hidden_layers"]
    return n * layer + (n - 1) + config["vocab_size"] * h + h


def flops_per_token(config: dict, seq_len: int) -> float:
    """Required matmul operations of THIS CHIP's share per trained token:
    in each layer q, k, both value halves, o, the per-head convolution
    (10 heads x 2 taps x 128 x 128; the depthwise one is no matmul), the
    router's four matrices and the token's one pair if it falls on a held
    expert — ``held / routed`` of a pair in expectation (the others are
    computed on the other chip) — 6 per weight; the tied head over the
    slice (the embedding side is a gather).  Attention, forward +
    backward: the causal half at the latent's 8 x 128
    (``harness/flops.py``).  Recomputation is not counted."""
    h, d = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    width = config["router_hidden_size"]
    pairs_here = (config["num_experts_per_tok"] * config["num_experts"]
                  / config["num_routed_experts"])
    per_layer = (2 * h * heads * d + h * kv * d + 2 * h * d
                 + (heads + kv) * config["cca_time1"] * d * d
                 + h * width + 2 * width * width
                 + width * config["num_routed_experts"]
                 + pairs_here * 3 * h * config["moe_intermediate_size"])
    n = config["num_hidden_layers"]
    return (6.0 * (n * per_layer + h * config["vocab_size"])
            + n * F.attention_flops_per_token(seq_len, heads * d,
                                              causal=True))


def flash_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2
               ) -> dict:
    """``{"flash", "cca_flash"}`` (one set of kernels under two names: what
    ``flash_ms`` times, and what ``cca_flash_roofline`` stands against):
    required operations and HBM bytes of one step's flash calls under the
    scope ``attn_cca`` on one chip.  Operations: the causal half of
    ``[8, seq, 128]`` a sequence and layer, 4 x head_dim a score forward
    and 10 backward (``harness/flops.py``).  Bytes: what the ALGORITHM
    moves — q, o (read again in the backward), dO and dQ at the 8 query
    heads; k, v, dK, dV at the 2 key/value heads (grouped-query attention
    reads each key/value head once for its 4 query heads; repeating them
    to 8 before the call is the program's choice, not the algorithm's);
    three float32 rows a query head (lse; lse and delta again).  The
    forward recomputed under ``remat`` is the program's work: not
    counted."""
    heads, kv, d = (config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    n = config["num_hidden_layers"]
    flops = n * (F.flash_forward(seqs, heads, seq_len, d, True)["flops"]
                 + F.flash_backward(seqs, heads, seq_len, d, True)["flops"])
    q_side, kv_side = seqs * seq_len * heads, seqs * seq_len * kv
    layer_bytes = ((6 * q_side + 6 * kv_side) * d * itemsize
                   + 3 * 4.0 * q_side)
    work = {"flops": flops, "bytes": n * layer_bytes,
            "op_name_re": r"/attn_cca/pallas_call$"}
    return {"flash": work, "cca_flash": dict(work)}


def moe_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2,
             pair_share=None) -> dict:
    """Required operations and HBM bytes of the held experts' grouped
    matmuls of ONE step on one chip: the pair rows that fall on held
    experts — the expected ``held / routed`` of all ``tokens x
    num_experts_per_tok`` (a half), or ``pair_share`` of them where the
    batch's own share is known — through three matmuls (gate, up, down)
    in three passes (forward, row gradient, matrix gradient), each 2 M h
    f.  Bytes: a pass touches every HELD expert's matrix once and each
    matmul's live row blocks in and out once.  Dead rows need nothing;
    the forward recomputed under ``remat`` is not the algorithm's."""
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
    from byteps_tpu.models.zaya import (Zaya, ZayaConfig, expert_counts,
                                        zaya_loss)
    # models/zaya.py has no switch for these (module docstring)
    spec.fixed(config, model_type="zaya", hidden_act="silu",
               attention_bias=False, lm_head_bias=False,
               tie_word_embeddings=True, sliding_window=None,
               num_experts_per_tok=1, param_dtype="float32")
    n = config["num_hidden_layers"]
    kinds = list(config["layer_types"][:n])
    first, count = config["experts_held"]
    if count != config["num_experts"]:
        raise spec.SpecError(
            f"num_experts ({config['num_experts']}) counts the experts "
            f"held; experts_held says {count}")
    cfg = ZayaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=n, layer_types=tuple(kinds),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], cca_time0=config["cca_time0"],
        cca_time1=config["cca_time1"],
        partial_rotary_factor=config["partial_rotary_factor"],
        rope_parameters=config["rope_parameters"],
        num_experts=config["num_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        router_hidden_size=config["router_hidden_size"],
        experts_held=(first, count),
        tie_word_embeddings=config["tie_word_embeddings"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(config["compute_dtype"]).type,
        remat=bool(traffic.get("remat", False)))
    if traffic["objective"] != "clm":
        raise ValueError(f"family zaya has no objective "
                         f"{traffic['objective']!r}")
    attention_kind = traffic.get("attention", "exact")
    if attention_kind == "flash":
        from byteps_tpu.ops import flash_attention as attn_fn
    elif attention_kind == "exact":
        attn_fn = None
    else:
        raise ValueError(f"unknown attention {attention_kind!r}")
    model = Zaya(cfg, attn_fn=attn_fn)
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
        work = {"top1_moe": moe_work(config, seq, seqs_per_chip)}
        if attention_kind == "flash":
            work.update(flash_work(config, seq, seqs_per_chip))
        return work

    rope = config["rope_parameters"][HYBRID]
    reference = dict(
        layers=n, heads=cfg.num_attention_heads,
        kv_heads=cfg.num_key_value_heads, theta=float(rope["rope_theta"]),
        rot=cfg.rotary_dim, held=(first, count), eps=cfg.rms_norm_eps)
    return types.SimpleNamespace(
        init_params=init_params,
        loss_fn=functools.partial(zaya_loss, model),
        make_batch=make_batch,
        reference_loss=functools.partial(reference_loss, **reference),
        tokens_per_seq=seq, flops_per_token=flops_per_token(config, seq),
        kernel_work=kernel_work,
        # the last norm's output [B, T, h], of the program and of the
        # reference: at this vocabulary the logits are compared in blocks
        # (``benchmarks/tests/gradcheck_zaya.py``)
        hidden=model.apply,
        reference_hidden=functools.partial(reference_hidden, **reference),
        # the share, and its [layers, 16] pair counts of one batch
        experts_held=(first, count),
        top1_moe_work=functools.partial(moe_work, config, seq),
        expert_counts=lambda p, b: expert_counts(model, p, b["input_ids"]))
