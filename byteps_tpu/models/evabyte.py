"""EvaByte decoder LM (``model_type: evabyte``): a byte-level model whose
attention is EVA — one softmax a query over its own block-aligned window of
keys and the chunk summaries of every earlier window — under a float32
residual stream, with several linear prediction heads.

``EvaByte/EvaByte`` ``config.json``.  :class:`EvaByteConfig`'s fields carry
the source's key names; what the config has no key for is fixed here and
listed, with its reason, under ``assumed`` in
``benchmarks/configs/evabyte_6b5.json``.  ``N(x; w) = x rsqrt(mean x^2 +
eps) (1 + w)`` in float32, ``w`` from ZERO (``norm_add_unit_offset``:
``models/qwen3_next.py``'s ``ZeroCentredNorm``).  The residual ``x`` is
float32 (``fp32_skip_add``) under ``dtype`` compute: the embedding's rows,
a block's two adds and the block inputs a ``remat`` keeps.  T positions, a
multiple of ``window_size``::

    x = x + eva(N(x; w_a));   x = x + W_d(silu(W_g m) * W_u m), m = N(x; w_m)
    eva  q, k, v = a W_q, a W_k, a W_v        h -> H x D each, no bias
         q_h, k_h rotate-half over all D lanes, rope_theta
         chunk c = positions [chunk_size c, +chunk_size); per head learned
         mu_h, phi_h [D] float32:
             k~_c = sum_j softmax_j(k_j . mu_h) k_j
             v~_c = sum_j softmax_j(k_j . phi_h) v_j        (j in c)
         row i, w = i // window_size: ONE softmax at 1/sqrt(D) over
             L_i = {j : j // window_size == w, j <= i}  and
             R_i = {c : c < (window_size / chunk_size) w}
         (``ops/eva_attention.py``: the flash kernels' (out, lse) a key
         set, merged);  x = x + o W_o
    head h = N(x; w_f);  logits = h W_head, W_head [h, P V] float32
         (``fp32_logits``); head p at position t scores byte t + 1 + p

:func:`evabyte_loss` = the mean over the ``num_pred_heads`` heads and the
positions that have a byte ``t + 1 + p`` of the negative log-likelihood,
equal weights, each head through ``models/gpt.py``
:func:`blocked_token_nll` on its own columns and its own shifted labels.
Initialisation: normal(``init_std``) for every matrix, the table and the
heads; ``mu`` and ``phi`` a normal clipped to +-1 times D^-1/2; norm
weights zero.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.eva_attention import eva_attention
from .gpt import blocked_token_nll
from .llama import apply_rope, rope_frequencies
from .qwen3_next import ZeroCentredNorm

__all__ = ["EvaByteConfig", "EvaByte", "evabyte_tiny", "evabyte_loss",
           "head_labels"]


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """Defaults are EvaByte 6.5B as published (32 layers)."""

    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    attention_class: str = "eva"
    attention_bias: bool = False
    window_size: int = 2048
    chunk_size: int = 16
    num_chunks: Any = None
    num_pred_heads: int = 8
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    norm_add_unit_offset: bool = True
    rope_theta: float = 100000.0
    rope_scaling: Any = None
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 32768
    init_std: float = 0.01275
    fp32_skip_add: bool = True
    fp32_logits: bool = True
    mixedp_attn: bool = True
    dtype: Any = jnp.bfloat16
    remat: bool = False

    def __post_init__(self):
        refused = {
            "attention_class": self.attention_class != "eva",
            "attention_bias": bool(self.attention_bias),
            "num_chunks": self.num_chunks is not None,
            "rope_scaling": self.rope_scaling is not None,
            "tie_word_embeddings": bool(self.tie_word_embeddings),
            "hidden_act": self.hidden_act != "silu",
            "norm_add_unit_offset": not self.norm_add_unit_offset,
            "fp32_logits": not self.fp32_logits,
            "mixedp_attn": not self.mixedp_attn,
        }
        for key, bad in refused.items():
            if bad:
                raise ValueError(
                    f"{key}={getattr(self, key)!r}: computed here are EVA "
                    f"attention without biases over every chunk "
                    f"(num_chunks null), an unscaled rotation, an untied "
                    f"head, silu, unit-offset norms, float32 logits and "
                    f"float32 softmax statistics")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                f"num_key_value_heads={self.num_key_value_heads} under "
                f"num_attention_heads={self.num_attention_heads}: the "
                f"chunk summaries are a head's own (no grouped heads)")
        if self.hidden_size % self.num_attention_heads or self.head_dim % 2:
            raise ValueError("hidden_size must divide into heads of an "
                             "even size")
        if self.window_size % self.chunk_size:
            raise ValueError(
                f"window_size={self.window_size} is not whole chunks of "
                f"chunk_size={self.chunk_size}")
        if self.num_pred_heads < 1:
            raise ValueError("num_pred_heads must be at least 1")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def residual_dtype(self):
        return jnp.float32 if self.fp32_skip_add else self.dtype


def evabyte_tiny(**overrides) -> EvaByteConfig:
    """CPU tests: float32 end to end, two layers, two heads of 16, windows
    of 64 in chunks of 8, eight heads over 32 rows."""
    return EvaByteConfig(**{**dict(
        vocab_size=32, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        window_size=64, chunk_size=8, max_position_embeddings=512,
        dtype=jnp.float32), **overrides})


def _dense(features, name, cfg: EvaByteConfig, axis=-1):
    return nn.DenseGeneral(
        features, axis=axis, use_bias=False, dtype=cfg.dtype, name=name,
        kernel_init=nn.initializers.normal(cfg.init_std))


def _pool_init(key, shape, dtype=jnp.float32):
    """``mu`` / ``phi`` [H, D]: a normal clipped to +-1, times D^-1/2."""
    return (jnp.clip(jax.random.normal(key, shape, dtype), -1.0, 1.0)
            / math.sqrt(shape[-1]))


def head_labels(labels, heads: int):
    """labels [B, T] (the next byte; -1: none) -> [heads, B, T]: head p at
    position t is asked for byte t + 1 + p, -1 past the sequence's end."""
    return jnp.stack(
        [jnp.concatenate([labels[:, p:], jnp.full_like(labels[:, :p], -1)],
                         axis=1) for p in range(heads)])


class EvaAttention(nn.Module):
    """The EVA mixer on the normed rows ``a`` [B, T, h] (module docstring);
    the rotation under ``bps.eva.rope``, the rest under ``ops/
    eva_attention.py``'s own ``bps.eva.*`` scopes."""

    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, a, positions):
        cfg = self.cfg
        heads, hd = cfg.num_attention_heads, cfg.head_dim
        q = _dense((heads, hd), "q_proj", cfg)(a)
        k = _dense((heads, hd), "k_proj", cfg)(a)
        v = _dense((heads, hd), "v_proj", cfg)(a)
        mu = self.param("mu", _pool_init, (heads, hd), jnp.float32)
        phi = self.param("phi", _pool_init, (heads, hd), jnp.float32)
        with jax.named_scope("bps.eva.rope"):
            cos, sin = rope_frequencies(hd, positions, cfg.rope_theta)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        # the pooling reads the ROTATED keys
        ctx = eva_attention(q, k, v, mu, phi, window=cfg.window_size,
                            chunk=cfg.chunk_size)
        return _dense(cfg.hidden_size, "o_proj", cfg, axis=(-2, -1))(ctx)


class EvaMLP(nn.Module):
    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, m):
        cfg = self.cfg
        gate = _dense(cfg.intermediate_size, "gate_proj", cfg)(m)
        up = _dense(cfg.intermediate_size, "up_proj", cfg)(m)
        return _dense(cfg.hidden_size, "down_proj", cfg)(
            jax.nn.silu(gate) * up)


class EvaByteBlock(nn.Module):
    """``x`` float32 in, float32 out: the two adds are the residual's."""

    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        res = cfg.residual_dtype
        a = ZeroCentredNorm(cfg.rms_norm_eps, cfg.dtype,
                            name="input_layernorm")(x)
        x = x + EvaAttention(cfg, name="attn")(a, positions).astype(res)
        m = ZeroCentredNorm(cfg.rms_norm_eps, cfg.dtype,
                            name="post_attention_layernorm")(x)
        return x + EvaMLP(cfg, name="mlp")(m).astype(res)


class EvaByte(nn.Module):
    """``wte`` -> the layers -> ``N``.  Returns the rows [B, T, h] the
    heads read (``lm_head`` [h, P V]; the loss computes their logits in
    blocks) — or, with ``logits=True``, the float32 logits [B, T, P, V] of
    all ``num_pred_heads`` heads whole (tests)."""

    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, input_ids, *, logits: bool = False):
        cfg = self.cfg
        b, t = input_ids.shape
        if t % cfg.window_size:
            raise ValueError(
                f"{t} positions are not whole windows of {cfg.window_size}")
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        init = nn.initializers.normal(cfg.init_std)
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                       dtype=cfg.residual_dtype, embedding_init=init,
                       name="wte")
        head = self.param(
            "lm_head", init,
            (cfg.hidden_size, cfg.num_pred_heads * cfg.vocab_size),
            jnp.float32)
        x = wte(input_ids)
        block = nn.remat(EvaByteBlock) if cfg.remat else EvaByteBlock
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"h{i}")(x, positions)
        x = ZeroCentredNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
        if not logits:
            return x
        out = jax.lax.dot_general(
            x, head.astype(cfg.dtype), (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return out.reshape(b, t, cfg.num_pred_heads, cfg.vocab_size)


def evabyte_loss(model: EvaByte, params, batch):
    """The heads' mean cross-entropy, each head through the blocked head on
    its own columns of ``lm_head``.  ``batch``: ``input_ids`` [B, T] and
    ``labels`` (the next byte, already shifted; -1 = ignored)."""
    cfg = model.cfg
    x = model.apply(params, batch["input_ids"])
    b, t, h = x.shape
    rows, head = x.reshape(b * t, h), params["params"]["lm_head"]
    labels = head_labels(batch["labels"], cfg.num_pred_heads)
    v = cfg.vocab_size
    parts = [blocked_token_nll(rows, head[:, p * v:(p + 1) * v],
                               labels[p].reshape(b * t), -1, True)
             for p in range(cfg.num_pred_heads)]
    return (sum(s for s, _ in parts)
            / jnp.maximum(sum(c for _, c in parts), 1.0))
