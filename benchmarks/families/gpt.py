"""Family ``gpt``: byteps_tpu.models.gpt under next-token prediction.

Configuration keys as in the source's GPT-2 ``config.json``.  The plain
reference is float32 ``jax.numpy`` with exact causal softmax attention on
the same parameter tree, so it also checks whichever attention the
traffic file plugs in (``flash``: ops.flash_attention).  Departures of
``models/gpt.py`` that the reference follows: LayerNorm epsilon 1e-6,
``lm_head`` NOT tied to ``wte``.
"""

from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp

from harness import flops as F
from harness import spec
from harness.plain import dense, gelu, layer_norm, scan_layers


def reference_loss(params, batch):
    p = params["params"]
    ids, labels = batch["input_ids"], batch["labels"]
    t = ids.shape[1]
    x = p["wte"]["embedding"][ids] + p["wpe"]["embedding"][:t][None]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, blk):
        h = layer_norm(x, blk["ln1"])
        qkv = (jnp.einsum("btd,dchk->btchk", h, blk["attn"]["qkv"]["kernel"])
               + blk["attn"]["qkv"]["bias"])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", pr, v)
        x = x + (jnp.einsum("bqhd,hdo->bqo", ctx,
                            blk["attn"]["out"]["kernel"])
                 + blk["attn"]["out"]["bias"])
        h = layer_norm(x, blk["ln2"])
        return x + dense(gelu(dense(h, blk["mlp_in"])), blk["mlp_out"])

    x = scan_layers(block, x, p, "h")
    logp = jax.nn.log_softmax(dense(layer_norm(x, p["ln_f"]), p["lm_head"]),
                              axis=-1)
    valid = labels >= 0
    ll = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                             axis=-1)[..., 0]
    return -(ll * valid).sum() / valid.sum()


def flops_per_token(config: dict, seq_len: int) -> float:
    """Every position meets the 24 blocks and the (untied) vocabulary
    head; ``wte`` / ``wpe`` are gathers.  Causal attention: half."""
    h, f, layers = config["n_embd"], config["n_inner"], config["n_layer"]
    per_token = (layers * F.transformer_layer_matmul_params(h, f)
                 + h * config["vocab_size"])
    return F.train_flops_per_token(per_token, layers, seq_len, h,
                                   causal=True)


def flash_work(config: dict, seq_len: int, seqs: int) -> dict:
    """Required operations and HBM bytes of all flash forward and
    backward calls of ONE step on one chip (one call of each per layer),
    at the model's real head size (the kernel pads 64 to 128 lanes:
    that is the kernel's cost, not the algorithm's)."""
    heads = config["n_head"]
    d = config["n_embd"] // heads
    fwd = F.flash_forward(seqs, heads, seq_len, d, causal=True)
    bwd = F.flash_backward(seqs, heads, seq_len, d, causal=True)
    n = config["n_layer"]
    return {"flops": n * (fwd["flops"] + bwd["flops"]),
            "bytes": n * (fwd["bytes"] + bwd["bytes"]),
            # the kernels carry no name of their own: they are the
            # pallas_calls under each block's ``attn`` scope
            "op_name_re": r"/attn/pallas_call$"}


def build(config: dict, traffic: dict):
    from byteps_tpu.models.gpt import GPT, GPTConfig, lm_loss
    # models/gpt.py has no switch for these (no dropout, flax's GELU and
    # LayerNorm defaults, an lm_head of its own, float32 parameters)
    spec.fixed(config, activation_function="gelu_new",
               layer_norm_epsilon=1e-6, tie_word_embeddings=False,
               attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0,
               n_ctx=config["n_positions"], param_dtype="float32")
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        intermediate_size=config["n_inner"],
        max_position=config["n_positions"],
        dtype=jnp.dtype(config["compute_dtype"]).type,
        remat=bool(traffic.get("remat", False)))
    if traffic["objective"] != "clm":
        raise ValueError(f"family gpt has no objective "
                         f"{traffic['objective']!r}")
    attention = traffic.get("attention", "exact")
    if attention == "flash":
        from byteps_tpu.ops import flash_attention as attn_fn
    elif attention == "exact":
        attn_fn = None
    else:
        raise ValueError(f"unknown attention {attention!r}")
    model = GPT(cfg, attn_fn=attn_fn)
    seq = traffic["seq_len"]

    def init_params(key):
        return model.init(key, jnp.zeros((1, seq), jnp.int32))

    def loss_fn(p, b):
        return lm_loss(model.apply(p, b["input_ids"]), b["labels"])

    def make_batch(key, n_seqs):
        ids = jax.random.randint(key, (n_seqs, seq), 0, cfg.vocab_size)
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full((n_seqs, 1), -1, ids.dtype)], axis=1)
        return {"input_ids": ids, "labels": labels}

    def kernel_work(seqs_per_chip):
        if attention != "flash":
            return {}
        return {"flash": flash_work(config, seq, seqs_per_chip)}

    return types.SimpleNamespace(
        init_params=init_params, loss_fn=loss_fn, make_batch=make_batch,
        reference_loss=reference_loss, tokens_per_seq=seq,
        flops_per_token=flops_per_token(config, seq),
        kernel_work=kernel_work)
