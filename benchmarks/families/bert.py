"""Family ``bert``: byteps_tpu.models.bert under the masked-LM objective.

Builds, from a configuration file (keys as in the source's
``bert_config.json``) and a traffic file: the system's model and loss,
a seeded batch maker, the required operations per token, and the PLAIN
REFERENCE — the same mathematics in straightforward float32
``jax.numpy`` on the same parameter tree, with none of the repo's model
code.  Departures of ``models/bert.py`` from the published model, which
the reference follows so that it checks the system and not the paper:
tanh-approximated GELU, LayerNorm epsilon 1e-6 (flax defaults), the MLM
head run on the masked positions only and not tied to the embedding.
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
    """float32 forward + masked-LM loss; exact softmax attention."""
    p = params["params"]
    enc = p["encoder"]
    ids = batch["input_ids"]
    t = ids.shape[1]
    x = (enc["word_embeddings"]["embedding"][ids]
         + enc["position_embeddings"]["embedding"][:t][None]
         + enc["token_type_embeddings"]["embedding"][0][None, None])
    x = layer_norm(x, enc["ln_emb"])
    mask = (1.0 - batch["attention_mask"].astype(jnp.float32)) * -1e9

    def layer(x, lp):
        a = lp["attention"]
        q, k, v = (jnp.einsum("btd,dhk->bthk", x, a[n]["kernel"])
                   + a[n]["bias"] for n in ("query", "key", "value"))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
        pr = jax.nn.softmax(s + mask[:, None, None, :], axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", pr, v)
        out = (jnp.einsum("bqhd,hdo->bqo", ctx, a["out"]["kernel"])
               + a["out"]["bias"])
        x = layer_norm(x + out, lp["ln_att"])
        h = dense(gelu(dense(x, lp["mlp_in"])), lp["mlp_out"])
        return layer_norm(x + h, lp["ln_mlp"])

    x = scan_layers(layer, x, enc, "layer_")
    x = jnp.take_along_axis(x, batch["masked_positions"][..., None], axis=1)
    x = layer_norm(gelu(dense(x, p["mlm_transform"])), p["mlm_ln"])
    logp = jax.nn.log_softmax(dense(x, p["mlm_out"]), axis=-1)
    ll = jnp.take_along_axis(logp, batch["masked_labels"][..., None],
                             axis=-1)[..., 0]
    return -ll.mean()


def n_masked(seq_len: int, mask_frac: float) -> int:
    return max(1, int(seq_len * mask_frac))


def flops_per_token(config: dict, seq_len: int, mask_frac: float) -> float:
    """Encoder weights meet every token; the MLM head (transform h^2 and
    the vocabulary projection h*V) meets only the masked share."""
    h, f = config["hidden_size"], config["intermediate_size"]
    layers = config["num_hidden_layers"]
    head = (h * h + h * config["vocab_size"]) \
        * n_masked(seq_len, mask_frac) / seq_len
    per_token = layers * F.transformer_layer_matmul_params(h, f) + head
    return F.train_flops_per_token(per_token, layers, seq_len, h,
                                   causal=False)


def build(config: dict, traffic: dict):
    from byteps_tpu.models.bert import (BertConfig, BertForMLM, mlm_loss,
                                        synthetic_batch)
    # models/bert.py has no switch for these (one dropout rate, flax's
    # GELU and LayerNorm defaults, float32 parameters)
    spec.fixed(config, hidden_act="gelu_tanh", layer_norm_eps=1e-6,
               attention_probs_dropout_prob=config["hidden_dropout_prob"],
               param_dtype="float32")
    cfg = BertConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        dropout_rate=config["hidden_dropout_prob"],
        dtype=jnp.dtype(config["compute_dtype"]).type,
        remat=bool(traffic.get("remat", False)))
    if traffic["objective"] != "mlm":
        raise ValueError(f"family bert has no objective "
                         f"{traffic['objective']!r}")
    model = BertForMLM(cfg)
    seq, frac = traffic["seq_len"], traffic["mask_frac"]

    def init_params(key):
        return model.init(key, jnp.zeros((1, seq), jnp.int32),
                          jnp.ones((1, seq), jnp.int32))

    def loss_fn(p, b):
        logits = model.apply(p, b["input_ids"], b["attention_mask"],
                             masked_positions=b["masked_positions"])
        return mlm_loss(logits, b["masked_labels"])

    def make_batch(key, n_seqs):
        b = synthetic_batch(key, cfg, batch=n_seqs, seq_len=seq,
                            mask_frac=frac)
        b.pop("labels")        # the gathered head never reads them
        return b

    return types.SimpleNamespace(
        init_params=init_params, loss_fn=loss_fn, make_batch=make_batch,
        reference_loss=reference_loss, tokens_per_seq=seq,
        flops_per_token=flops_per_token(config, seq, frac),
        kernel_work=lambda seqs_per_chip: {})
