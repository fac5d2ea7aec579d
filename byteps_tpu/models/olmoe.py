"""OLMoE decoder LM: RMSNorm, RoPE, q/k-norm, dropless top-k SwiGLU experts.

The plainest real sparse-expert model (OLMoE-1B-7B, arXiv:2409.02060;
``allenai/OLMoE-1B-7B-0125-Instruct`` ``config.json``, ``model_type:
olmoe``): every layer is ``x + attn(RMSNorm(x))`` then
``x + moe(RMSNorm(x))``; no dense MLP, no shared expert, no biases.
:class:`OlmoeConfig`'s fields carry the source's key names.  What the
source's config has no key for, and this file fixes as HF
``modeling_olmoe.py`` computes it:

- ``q_norm`` / ``k_norm`` are RMSNorms over the WHOLE projected vector
  (all heads), applied before the head split and the rotation;
- rotate-half RoPE (``models/llama.py``);
- the router is a bias-free linear map, softmax over all experts, the k
  largest kept and NOT renormalised (``norm_topk_prob`` false);
- the loss (:func:`olmoe_loss`) adds, per layer, the load-balance loss x
  ``router_aux_loss_coef`` (HF's default 0.01) and the router z-loss x
  ``router_z_loss_coef`` (the paper's 0.001).

The head is untied: ``lm_head/kernel`` [h, V].  A plain ``apply`` returns
float32 logits [B, T, V]; the training loss never holds them — it takes
the normed hidden rows (``logits=False``) and the kernel as it lies
through ``models/gpt.py`` :func:`blocked_token_nll` (``kernel=True``),
one block of rows' float32 logits at a time.

bf16 compute over float32 parameters; norms and the router in float32.
The expert layer is ``parallel/expert.py`` :func:`dropless_moe_mlp` with
every expert held (``held=None``: each data-parallel replica has all 64;
``models/mellum.py`` runs the same layer as one chip's share).  The
exchange of expert parallelism — ``all_to_all`` over an ``ep`` axis — is
still switch-only (``models/gpt.py`` ``MoEMLP``).  Attention is MHA
(``num_key_value_heads == num_attention_heads``, as published) through
the pluggable ``attn_fn`` of the sibling models.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.expert import dropless_moe_mlp
from .gpt import blocked_lm_loss
from .llama import AttnFn, RMSNorm, apply_rope, rope_frequencies

__all__ = ["OlmoeConfig", "Olmoe", "olmoe_tiny", "olmoe_loss",
           "expert_counts"]


@dataclasses.dataclass(frozen=True)
class OlmoeConfig:
    """Defaults are OLMoE-1B-7B as published (16 layers, 6.9 B parameters,
    1.3 B active a token)."""

    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 1024       # width of ONE expert
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    router_aux_loss_coef: float = 0.01
    router_z_loss_coef: float = 0.001
    dtype: Any = jnp.bfloat16
    remat: bool = False

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "OLMoE attention is MHA: num_key_value_heads "
                f"({self.num_key_value_heads}) must equal "
                f"num_attention_heads ({self.num_attention_heads})")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must be divisible by "
                             "num_attention_heads")
        if not 1 <= self.num_experts_per_tok <= self.num_experts:
            raise ValueError("num_experts_per_tok must lie in "
                             "[1, num_experts]")


def olmoe_tiny() -> OlmoeConfig:
    """CPU tests: float32 end to end, 8 experts, top-2."""
    return OlmoeConfig(vocab_size=128, hidden_size=32, intermediate_size=16,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=4, num_experts=8,
                       num_experts_per_tok=2, max_position_embeddings=64,
                       dtype=jnp.float32)


class OlmoeAttention(nn.Module):
    cfg: OlmoeConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        heads = cfg.num_attention_heads
        hd = cfg.hidden_size // heads

        def proj(name):
            return nn.Dense(cfg.hidden_size, use_bias=False,
                            dtype=cfg.dtype, name=name)

        q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(
            proj("q_proj")(x))
        k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(
            proj("k_proj")(x))
        v = proj("v_proj")(x)
        q, k, v = (a.reshape(a.shape[:2] + (heads, hd)) for a in (q, k, v))
        cos, sin = rope_frequencies(hd, positions, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        attn = self.attn_fn
        if attn is None:
            from ..parallel.sequence import full_attention as attn
        ctx = attn(q, k, v, causal=True, sm_scale=1.0 / math.sqrt(hd))
        return proj("o_proj")(ctx.reshape(x.shape))


class OlmoeSparseMoe(nn.Module):
    """The expert MLP of one layer.  Sows the two router losses into
    ``moe_aux`` (``aux``, ``z``) and the per-expert pair counts into
    ``moe_stats`` (``counts``): apply with the collection you want
    ``mutable`` (:func:`olmoe_loss`, :func:`expert_counts`); a plain
    ``apply`` sows nothing."""

    cfg: OlmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
        stack = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                             batch_axis=(0,))
        params = {
            "router": self.param("router", nn.initializers.lecun_normal(),
                                 (h, e), jnp.float32),
            "gate": self.param("gate", stack, (e, h, f), jnp.float32),
            "up": self.param("up", stack, (e, h, f), jnp.float32),
            "down": self.param("down", stack, (e, f, h), jnp.float32),
        }
        b, t, _ = x.shape
        y, aux, z, counts = dropless_moe_mlp(
            x.reshape(b * t, h), params, cfg.num_experts_per_tok)
        if not self.is_initializing():   # init returns parameters only
            self.sow("moe_aux", "aux", aux)
            self.sow("moe_aux", "z", z)
            self.sow("moe_stats", "counts", counts)
        return y.reshape(b, t, h)


class OlmoeBlock(nn.Module):
    cfg: OlmoeConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="attn_norm")(x)
        x = x + OlmoeAttention(cfg, self.attn_fn, name="attn")(h, positions)
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="moe_norm")(x)
        return x + OlmoeSparseMoe(cfg, name="moe")(h)


class Olmoe(nn.Module):
    """Decoder-only OLMoE: ``wte`` -> blocks -> RMSNorm -> untied
    ``lm_head``; float32 logits [B, T, V] — or, with ``logits=False``,
    the normed hidden rows [B, T, h] the head would read
    (:func:`olmoe_loss` computes the head in blocks from them; ``init``
    makes ``lm_head/kernel`` either way)."""

    cfg: OlmoeConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, *, logits: bool = True):
        cfg = self.cfg
        b, t = input_ids.shape
        if positions is None:
            positions = jnp.arange(t)
        if positions.ndim == 1:
            positions = jnp.broadcast_to(positions[None], (b, t))
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="wte")(input_ids)
        block = nn.remat(OlmoeBlock) if cfg.remat else OlmoeBlock
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, self.attn_fn, name=f"h{i}")(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        name="lm_head")
        if not logits and not self.is_initializing():
            return x
        return head(x).astype(jnp.float32)


def _sown(model: "Olmoe", tree, key: str):
    """What each layer's ``OlmoeSparseMoe`` sowed under ``key``, in layer
    order."""
    return [tree[f"h{i}"]["moe"][key][0]
            for i in range(model.cfg.num_hidden_layers)]


def olmoe_loss(model: Olmoe, params, batch):
    """Next-token cross-entropy + ``router_aux_loss_coef`` x sum over
    layers of the load-balance loss + ``router_z_loss_coef`` x sum over
    layers of the router z-loss.  ``batch``: ``input_ids`` [B, T] and
    ``labels`` (already shifted; -1 = ignored).  Both router terms are
    of THIS token shard (``parallel/moe_lm.py`` documents the same for
    the switch path).  The cross-entropy is :func:`~byteps_tpu.models.gpt.
    blocked_lm_loss` of the final hidden rows and ``lm_head``'s kernel
    [h, V] as the parameter lies: no [tokens, vocabulary] logits."""
    cfg = model.cfg
    x, sown = model.apply(params, batch["input_ids"], logits=False,
                          mutable=["moe_aux"])
    aux = sum(_sown(model, sown["moe_aux"], "aux"))
    z = sum(_sown(model, sown["moe_aux"], "z"))
    b, t, h = x.shape
    return (blocked_lm_loss(x.reshape(b * t, h),
                            params["params"]["lm_head"]["kernel"],
                            batch["labels"].reshape(b * t), kernel=True)
            + cfg.router_aux_loss_coef * aux + cfg.router_z_loss_coef * z)


def expert_counts(model: Olmoe, params, input_ids):
    """Token–expert pairs each expert received, [layers, experts] int32:
    what ``parallel.expert.publish_moe_stats`` takes."""
    _, sown = model.apply(params, input_ids, mutable=["moe_stats"])
    return jnp.stack(_sown(model, sown["moe_stats"], "counts"))
