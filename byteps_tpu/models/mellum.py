"""Mellum 2 decoder LM: sliding-window and full attention layers mixed,
GQA, per-head q/k-norm, two rotary schedules, renormalised top-k SwiGLU
experts — and one chip's share of them.

``JetBrains/Mellum2-12B-A2.5B-Instruct`` ``config.json`` (``model_type:
mellum``): every layer is ``x + attn(RMSNorm(x))`` then
``x + moe(RMSNorm(x))``; no dense MLP (``intermediate_size`` is used by
no layer), no shared expert, no biases.  :class:`MellumConfig`'s fields
carry the source's key names.  Per layer ``i``:

- ``layer_types[i]`` is ``sliding_attention`` (row t attends keys
  ``t - sliding_window < j <= t``) or ``full_attention`` (causal); the
  published pattern is three sliding layers to one full layer.  The two
  kinds are submodules ``attn_swa`` and ``attn``, so a trace tells their
  kernels apart by scope;
- ``num_attention_heads`` query heads and ``num_key_value_heads`` key /
  value heads of ``head_dim`` (32 x 128 = 4096 != hidden 2304): query
  head g reads k/v head ``g // groups`` (``models/llama.py``
  ``repeat_kv``);
- ``rope_parameters[layer type]``: ``default`` rotary on sliding layers,
  YaRN on full layers (blended inverse frequencies, cos / sin times
  ``attention_factor``; ``models/llama.py`` ``rope_frequencies``);
- the router is a bias-free linear map, softmax over all experts in
  float32, the k largest kept and divided by their sum
  (``norm_topk_prob``).

What the source's config has no key for, and this file fixes (each is
listed under ``assumed`` in ``benchmarks/configs/mellum2_12b.json``):
``q_norm`` / ``k_norm`` are RMSNorms over each head's ``head_dim``,
before the rotation (the Qwen3-MoE convention, whose key names this
config uses); rotate-half RoPE; the loss (:func:`mellum_loss`) adds
``router_aux_loss_coef`` x the sum over layers of the load-balance loss
(0.001, HF's default for that family) and no z-loss; the MTP head that
the model card mentions has no key and is left out.

The share: ``experts_held=(first, count)`` gives the expert stacks of
``count`` consecutive experts of the ``num_experts`` the router knows —
what one chip of an expert-parallel deployment holds — and
``vocab_size`` is the chip's slice of the vocabulary's rows.  The expert
layer is ``parallel/expert.py`` :func:`dropless_moe_mlp` with ``held``:
it routes over all experts and returns the held experts' part of the
sum; there is no exchange on this path (one chip runs without it).

bf16 compute over float32 parameters; norms and the router in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.core import freeze

from ..parallel.expert import dropless_moe_mlp, held_range
from .gpt import lm_loss
from .llama import AttnFn, RMSNorm, apply_rope, repeat_kv, rope_frequencies

__all__ = ["MellumConfig", "Mellum", "mellum_tiny", "mellum_loss",
           "expert_counts"]

SLIDING, FULL = "sliding_attention", "full_attention"
_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)
_ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
           "original_max_position_embeddings": 8192, "beta_fast": 32.0,
           "beta_slow": 1.0, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000.0},
}


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """Defaults are Mellum2-12B-A2.5B as published (28 layers, 12.15 B
    parameters, every expert and the whole vocabulary held)."""

    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    layer_types: Tuple[str, ...] = _PERIOD * 7
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_parameters: Mapping[str, Mapping[str, Any]] = freeze(_ROPE)
    num_experts: int = 64               # the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896    # width of ONE expert
    norm_topk_prob: bool = True
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    router_aux_loss_coef: float = 0.001
    dtype: Any = jnp.bfloat16
    remat: bool = False

    def __post_init__(self):
        # hashable whatever the caller passed (a config file's lists and
        # dicts): flax modules carry the config as a static attribute
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "rope_parameters",
                           freeze(dict(self.rope_parameters)))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", held_range(
                self.experts_held, self.num_experts))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        for kind in set(self.layer_types):
            if kind not in (SLIDING, FULL):
                raise ValueError(f"unknown layer type {kind!r}")
            rope = self.rope_parameters.get(kind)
            if rope is None or rope["rope_type"] not in ("default", "yarn"):
                raise ValueError(f"rope_parameters[{kind!r}] must be a "
                                 f"'default' or 'yarn' section, got {rope}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be divisible by "
                             "num_key_value_heads")
        if not 1 <= self.num_experts_per_tok <= self.num_experts:
            raise ValueError("num_experts_per_tok must lie in "
                             "[1, num_experts]")

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts whose stacks live here."""
        return held_range(self.experts_held, self.num_experts)


def mellum_tiny(experts_held: Optional[Tuple[int, int]] = None
                ) -> MellumConfig:
    """CPU tests: float32 end to end, two periods of four layers, a window
    shorter than the test sequences, GQA 4/2, 8 experts top-2, YaRN over
    an original context of 16."""
    rope = {SLIDING: {"rope_type": "default", "rope_theta": 10000.0},
            FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                   "original_max_position_embeddings": 16, "beta_fast": 4.0,
                   "beta_slow": 1.0,
                   "attention_factor": 0.1 * math.log(4.0) + 1.0}}
    return MellumConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=8,
        layer_types=_PERIOD * 2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, sliding_window=8,
        rope_parameters=rope, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=16, experts_held=experts_held,
        max_position_embeddings=64, dtype=jnp.float32)


def banded_attention(q, k, v, *, causal: bool = True,
                     sm_scale: Optional[float] = None,
                     window: Optional[int] = None):
    """Exact softmax attention [B, T, H, D] with the causal band ``t -
    window < j <= t`` (``window=None``: causal): what a model built
    without an ``attn_fn`` runs."""
    if not causal:
        raise ValueError("banded_attention is causal")
    t = q.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    row, col = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = col <= row
    if window is not None:
        keep &= row - col < window
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


class MellumAttention(nn.Module):
    """One layer's attention; ``kind`` is its entry of ``layer_types``."""

    cfg: MellumConfig
    kind: str
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        heads, kv_heads, hd = (cfg.num_attention_heads,
                               cfg.num_key_value_heads, cfg.head_dim)

        def proj(name, n):
            return nn.DenseGeneral((n, hd), use_bias=False, dtype=cfg.dtype,
                                   name=name)

        # per-head norms over head_dim, before the rotation
        q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(
            proj("q_proj", heads)(x))
        k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(
            proj("k_proj", kv_heads)(x))
        v = proj("v_proj", kv_heads)(x)
        rope = cfg.rope_parameters[self.kind]
        cos, sin = rope_frequencies(
            hd, positions, float(rope["rope_theta"]),
            yarn=rope if rope["rope_type"] == "yarn" else None)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        k, v = repeat_kv(k, v, heads // kv_heads)
        attn = self.attn_fn or banded_attention
        window = {"window": cfg.sliding_window} if self.kind == SLIDING \
            else {}
        ctx = attn(q, k, v, causal=True, sm_scale=1.0 / math.sqrt(hd),
                   **window)
        return nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1),
                               use_bias=False, dtype=cfg.dtype,
                               name="o_proj")(ctx)


class MellumSparseMoe(nn.Module):
    """The expert MLP of one layer: the router over all ``num_experts``,
    the stacks of the experts held here.  Sows the load-balance loss into
    ``moe_aux`` (``aux``) and the per-expert pair counts (all experts)
    into ``moe_stats`` (``counts``): apply with the collection you want
    ``mutable`` (:func:`mellum_loss`, :func:`expert_counts`); a plain
    ``apply`` sows nothing."""

    cfg: MellumConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        g = cfg.held[1]
        stack = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                             batch_axis=(0,))
        params = {
            "router": self.param("router", nn.initializers.lecun_normal(),
                                 (h, cfg.num_experts), jnp.float32),
            "gate": self.param("gate", stack, (g, h, f), jnp.float32),
            "up": self.param("up", stack, (g, h, f), jnp.float32),
            "down": self.param("down", stack, (g, f, h), jnp.float32),
        }
        b, t, _ = x.shape
        y, aux, _, counts = dropless_moe_mlp(
            x.reshape(b * t, h), params, cfg.num_experts_per_tok,
            held=cfg.experts_held, renormalize=cfg.norm_topk_prob)
        if not self.is_initializing():   # init returns parameters only
            self.sow("moe_aux", "aux", aux)
            self.sow("moe_stats", "counts", counts)
        return y.reshape(b, t, h)


def _attn_name(kind: str) -> str:
    return "attn_swa" if kind == SLIDING else "attn"


class MellumBlock(nn.Module):
    cfg: MellumConfig
    kind: str
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="attn_norm")(x)
        x = x + MellumAttention(cfg, self.kind, self.attn_fn,
                                name=_attn_name(self.kind))(h, positions)
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="moe_norm")(x)
        return x + MellumSparseMoe(cfg, name="moe")(h)


class Mellum(nn.Module):
    """Decoder-only Mellum: ``wte`` -> blocks -> RMSNorm -> untied
    ``lm_head``; float32 logits over the ``vocab_size`` rows held."""

    cfg: MellumConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, input_ids, positions=None):
        cfg = self.cfg
        b, t = input_ids.shape
        if positions is None:
            positions = jnp.arange(t)
        if positions.ndim == 1:
            positions = jnp.broadcast_to(positions[None], (b, t))
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="wte")(input_ids)
        block = nn.remat(MellumBlock) if cfg.remat else MellumBlock
        for i, kind in enumerate(cfg.layer_types):
            x = block(cfg, kind, self.attn_fn, name=f"h{i}")(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          name="lm_head")(x)
        return logits.astype(jnp.float32)


def _sown(model: Mellum, tree, key: str):
    """What each layer's ``MellumSparseMoe`` sowed under ``key``, in layer
    order."""
    return [tree[f"h{i}"]["moe"][key][0]
            for i in range(model.cfg.num_hidden_layers)]


def mellum_loss(model: Mellum, params, batch):
    """Next-token cross-entropy + ``router_aux_loss_coef`` x sum over
    layers of the load-balance loss.  ``batch``: ``input_ids`` [B, T] and
    ``labels`` (already shifted; -1 = ignored).  The router term is of
    THIS token shard, over all ``num_experts`` (as ``olmoe_loss``)."""
    logits, sown = model.apply(params, batch["input_ids"],
                               mutable=["moe_aux"])
    aux = sum(_sown(model, sown["moe_aux"], "aux"))
    return (lm_loss(logits, batch["labels"])
            + model.cfg.router_aux_loss_coef * aux)


def expert_counts(model: Mellum, params, input_ids):
    """Token–expert pairs each of the ``num_experts`` experts received,
    [layers, experts] int32: what ``parallel.expert.publish_moe_stats``
    takes (with ``held=model.cfg.experts_held``)."""
    _, sown = model.apply(params, input_ids, mutable=["moe_stats"])
    return jnp.stack(_sown(model, sown["moe_stats"], "counts"))
