"""Llama-family decoder LM: RoPE, RMSNorm, SwiGLU, grouped-query attention.

The reference has no model zoo of its own — its benchmarks drive framework
models (BERT/ResNet/VGG via GluonNLP/torchvision, reference README.md:35-41,
docs/performance.md) — but BASELINE.json's stretch config names a modern
LLM ("Llama-3-8B via byteps/jax DistributedOptimizer") as the flagship
workload for the FSDP/TP machinery.  This is that family, TPU-first:

- bf16 compute over f32 params, MXU-aligned head dims, static shapes;
- RMSNorm statistics in f32 (bf16 mean-of-squares loses the small-residual
  regime);
- rotary embeddings computed in f32 and cast once;
- GQA: ``num_kv_heads < num_heads`` shrinks the KV projections; K/V heads
  are repeated to the query-head count before the attention callable, so
  the same parameters run with exact, flash, ring or Ulysses attention
  (the established pluggable-``attn_fn`` pattern, models/gpt.py).

Weights follow the Llama layout: no biases anywhere, untied embedding and
lm head, SwiGLU gate/up/down MLP.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .gpt import lm_loss, token_nll  # shared loss (same LM contract)

__all__ = [
    "LlamaConfig", "Llama", "llama3_8b", "llama_tiny", "lm_loss",
    "token_nll", "rope_frequencies", "yarn_inv_freq", "apply_rope",
    "repeat_kv",
]

AttnFn = Callable  # (q, k, v, *, causal, sm_scale) -> out


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8            # GQA group count
    intermediate_size: int = 14336   # SwiGLU width
    max_position: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads ({self.num_heads}) must be divisible by "
                f"num_kv_heads ({self.num_kv_heads})")
        if self.hidden_size % self.num_heads:
            raise ValueError("hidden_size must be divisible by num_heads")


def llama3_8b() -> LlamaConfig:
    """Llama-3-8B geometry (the BASELINE.json configs[4] stretch target)."""
    return LlamaConfig()


def llama_tiny() -> LlamaConfig:
    """CPU-mesh tests / multichip dry-runs; keeps GQA non-trivial (4 q
    heads over 2 kv heads)."""
    return LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=128,
                       max_position=512, rope_theta=10000.0)


def llama_tiny_f32() -> LlamaConfig:
    """Even smaller, f32 end to end: the parity tests need bit-comparable
    math (one definition so every test pins the same geometry)."""
    return LlamaConfig(vocab_size=128, hidden_size=32, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=64,
                       max_position=64, rope_theta=10000.0,
                       dtype=jnp.float32)


# ----------------------------------------------------------------- rotary

def rope_frequencies(head_dim: int, positions, theta: float,
                     yarn: Optional[Mapping] = None):
    """(cos, sin) tables [*, T, head_dim/2] in f32 for the given absolute
    positions (sharded-sequence callers pass their own offsets, as with
    GPT's ``positions`` argument).  ``yarn`` (the keys of an HF
    ``rope_type: yarn`` section: ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``) swaps in :func:`yarn_inv_freq` and scales both
    tables by ``attention_factor``."""
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                               / head_dim))
        ang = positions[..., None].astype(jnp.float32) * inv  # [*, T, D/2]
        return jnp.cos(ang), jnp.sin(ang)
    inv = jnp.asarray(yarn_inv_freq(
        head_dim, theta, yarn["factor"],
        yarn["original_max_position_embeddings"], yarn["beta_fast"],
        yarn["beta_slow"]), jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * inv
    scale = jnp.float32(yarn["attention_factor"])
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN's blended inverse frequencies (HF ``_compute_yarn_parameters``),
    [head_dim/2] float64: pair i keeps its own frequency ``1 / f_i``
    (``f_i = theta^(2i/D)``) where it turns more than ``beta_fast`` times
    over the original context, takes the interpolated ``1 / (factor
    f_i)`` where it turns fewer than ``beta_slow`` times, and a linear
    blend between the two pairs where those counts fall (floor / ceil,
    clamped to [0, D - 1])."""
    half = head_dim // 2
    f = theta ** (np.arange(half, dtype=np.float64) * 2 / head_dim)

    def pair_turning(turns):
        return (head_dim * math.log(original_max_position
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(pair_turning(beta_fast)), 0)
    hi = min(math.ceil(pair_turning(beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001                                  # HF: no singularity
    keep = 1.0 - np.clip((np.arange(half) - lo) / (hi - lo), 0.0, 1.0)
    return (1.0 - keep) / (factor * f) + keep / f


def repeat_kv(k, v, groups: int):
    """K/V heads [B, T, Hkv, D] repeated to the query-head count (query
    head g reads k/v head ``g // groups``): numerically identical to
    grouped attention, and keeps the pluggable ``attn_fn`` contract
    (flash / ring / Ulysses) head-uniform."""
    if groups == 1:
        return k, v
    return jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)


def apply_rope(x, cos, sin, rotary_dim: Optional[int] = None):
    """Rotate pairs (x[i], x[i + D/2]) — the *rotate-half* convention used
    by Llama checkpoints as distributed (HF ``rotate_half``), so pretrained
    q/k projections import without permutation.  x is [B, T, H, D], tables
    broadcast over the head axis.  (The interleaved (x[2i], x[2i+1])
    convention is the same rotation under a fixed channel permutation; we
    pin the checkpoint-compatible one.)

    ``rotary_dim`` is the rotary width (HF ``partial_rotary_factor`` x
    head size): only the first ``rotary_dim`` of a head's D channels turn —
    pairs (x[i], x[i + rotary_dim/2]), tables [*, T, rotary_dim/2] from
    ``rope_frequencies(rotary_dim, ...)`` — and the rest pass through as
    they are.  ``None`` (or D) turns the whole head."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rotary_dim], cos, sin),
             x[..., rotary_dim:]], axis=-1)
    d2 = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d2], xf[..., d2:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        xf = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                            + self.eps)
        return (xf * rms * scale).astype(self.dtype)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        hd = cfg.hidden_size // cfg.num_heads
        groups = cfg.num_heads // cfg.num_kv_heads
        q = nn.DenseGeneral((cfg.num_heads, hd), use_bias=False,
                            dtype=cfg.dtype, name="q")(x)
        k = nn.DenseGeneral((cfg.num_kv_heads, hd), use_bias=False,
                            dtype=cfg.dtype, name="k")(x)
        v = nn.DenseGeneral((cfg.num_kv_heads, hd), use_bias=False,
                            dtype=cfg.dtype, name="v")(x)
        cos, sin = rope_frequencies(hd, positions, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        k, v = repeat_kv(k, v, groups)
        attn = self.attn_fn
        if attn is None:
            from ..parallel.sequence import full_attention as attn
        ctx = attn(q, k, v, causal=True, sm_scale=1.0 / math.sqrt(hd))
        return nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1),
                               use_bias=False, dtype=cfg.dtype,
                               name="out")(ctx)


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        g = nn.Dense(cfg.intermediate_size, use_bias=False, dtype=cfg.dtype,
                     name="gate")(x)
        u = nn.Dense(cfg.intermediate_size, use_bias=False, dtype=cfg.dtype,
                     name="up")(x)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                        name="down")(jax.nn.silu(g) * u)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="attn_norm")(x)
        x = x + LlamaAttention(cfg, self.attn_fn, name="attn")(h, positions)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="mlp_norm")(x)
        return x + LlamaMLP(cfg, name="mlp")(h)


class Llama(nn.Module):
    """Decoder-only Llama.  ``positions`` must be passed when the sequence
    axis is sharded (each shard holds positions [off, off + T/sp))."""

    cfg: LlamaConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, input_ids, positions=None):
        cfg = self.cfg
        b, t = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        elif positions.ndim == 1:
            positions = jnp.broadcast_to(positions[None], (b, t))
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="wte")(input_ids)
        block = LlamaBlock
        if cfg.remat:
            block = nn.remat(LlamaBlock)
        for i in range(cfg.num_layers):
            x = block(cfg, self.attn_fn, name=f"h{i}")(x, positions)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm_f")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          name="lm_head")(x)
        return logits.astype(jnp.float32)
