"""GLM-4.7-Flash decoder LM (``model_type: glm4_moe_lite``): multi-head
latent attention with a low-rank query and a jointly compressed key/value,
a leading dense layer, sigmoid-scored top-k experts chosen by a bias and
joined by a shared expert, a depth-1 multi-token-prediction module on the
main model's table and head — and one chip's share of them.

``zai-org/GLM-4.7-Flash`` ``config.json``; its keys are DeepSeek-V3's letter
for letter and mean what that model's report (arXiv:2412.19437 sections
2.1-2.2) says they mean.  :class:`GlmLiteConfig`'s fields carry the
source's key names; what the config has no key for is fixed here and
listed, with its reason, under ``assumed`` in
``benchmarks/configs/glm47_flash.json``.  One block, T positions::

    a    = RMSNorm(x)
    c_q  = RMSNorm(a W_dq)            [T, q_lora_rank]
    q    = c_q W_uq                   [T, H, nope + rope] = [q_nope | q_rope]
    ckv  = a W_dkv                    [T, kv_lora_rank + rope]
    c_kv = RMSNorm(ckv[:, :kv_lora_rank])
    k_r  = rot(ckv[:, kv_lora_rank:]) [T, 1, rope]: ONE rotary key, all heads
    kv   = c_kv W_ukv                 [T, H, nope + v] = [k_nope | v]
    q    = [q_nope | rot(q_rope)]     k = [k_nope | k_r over the H heads]
    o    = softmax(q k^T / sqrt(nope + rope), causal) v;   x = x + o W_o
    m    = RMSNorm(x)
    block i < first_k_dense_replace:  x = x + down(silu(gate m) * up m)
    from it on:  s = sigmoid(m_f32 W_r)   [T, n_routed_experts]
                 S = top-k(s + b);  w_e = f * s_e / (sum_S s + 1e-20)
                 x = x + sum_{e in S} w_e E_e(m) + E_shared(m)

``f`` = ``routed_scaling_factor``; ``b`` (``e_score_correction_bias``)
chooses only — no gradient reaches it — and is held at the zero it starts
as: the report moves it by a rule outside the gradient whose speed has no
key (left out, as ``models/zaya.py``'s ``beta``), and AdamW leaves a zero
leaf with a zero gradient where it is.  ``f`` is one multiply on the routed
sum in :class:`GlmLiteSparseMoe`, fused with the shared expert's add;
``dropless_moe_mlp`` has no option for it.  The rotation is rotate-half
over the whole ``qk_rope_head_dim`` slice (``models/llama.py``
``apply_rope``: the interleaved pairing is the same rotation under a fixed
permutation of ``W_uq``'s and ``W_dkv``'s rotary columns).  The flash
kernels need one head size, so ``qk_nope_head_dim + qk_rope_head_dim`` must
equal ``v_head_dim`` (256 = 192 + 64 here; a latent whose two widths differ
is not runnable).

The multi-token-prediction module (``num_nextn_predict_layers`` 1, scope
``mtp``), with ``h`` the last block's output BEFORE the final norm::

    h'_i = M [RMSNorm(h_i) ; RMSNorm(Emb(t_(i+1)))]   M: 2 hidden -> hidden
    g    = Block(h')       one more sparse block, its own weights
    logits'_i = RMSNorm(g_i) W_head^T                 predicts t_(i+2)

``Emb`` and ``W_head`` ARE the main model's, one leaf each, whose gradient
is the sum of both uses.  It runs at the full T — ``Emb(t_(i+1))`` is a
roll of the ids, and the positions with no ``t_(i+2)`` are masked out of
its loss (causal: they reach nothing before them) — so every kernel keeps
one shape.  :func:`glm_lite_loss` = the main head's mean cross-entropy +
``mtp_loss_weight`` x the module's, both through ``models/gpt.py``
:func:`blocked_token_nll` (no ``[tokens, vocabulary]`` logits); no
auxiliary or z-loss stands in for the bias rule.

One chip's share: ``experts_held = (first, count)`` are the routed experts
whose stacks live here (``None``: all ``n_routed_experts``, which stays the
router's width), ``vocab_size`` the rows of table and head held.  The
shared expert is whole on every chip.  There is no exchange on this path.

bf16 compute over float32 parameters; norms, the rotation, the router's
scores and every softmax in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..common.metrics import gauges
from ..parallel.expert import dropless_moe_mlp, held_range
from .gpt import blocked_lm_loss
from .llama import AttnFn, RMSNorm, apply_rope, rope_frequencies
from .mellum import banded_attention

__all__ = ["GlmLiteConfig", "GlmLite", "glm_lite_tiny", "glm_lite_loss",
           "expert_counts"]


@dataclasses.dataclass(frozen=True)
class GlmLiteConfig:
    """Defaults are GLM-4.7-Flash as published (47 layers, every expert
    and the whole vocabulary held)."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    num_attention_heads: int = 20
    num_key_value_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    first_k_dense_replace: int = 1
    intermediate_size: int = 10240      # width of a dense block's MLP
    moe_intermediate_size: int = 1536   # width of ONE expert
    n_routed_experts: int = 64          # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    num_nextn_predict_layers: int = 1
    rope_theta: float = 1000000.0
    partial_rotary_factor: float = 1.0
    rope_scaling: Any = None
    max_position_embeddings: int = 202752
    rms_norm_eps: float = 1e-5
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    mtp_loss_weight: float = 0.3
    dtype: Any = jnp.bfloat16
    remat: bool = False

    def __post_init__(self):
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", held_range(
                self.experts_held, self.n_routed_experts))
        if self.topk_method != "noaux_tc":
            raise ValueError(
                f"topk_method={self.topk_method!r}: the router computed "
                f"here is 'noaux_tc' (sigmoid scores, a bias that chooses)")
        if (self.n_group, self.topk_group) != (1, 1):
            raise ValueError(
                f"n_group={self.n_group} / topk_group={self.topk_group}: "
                f"the choice is over ONE group of all the experts")
        if self.qk_nope_head_dim + self.qk_rope_head_dim != self.v_head_dim:
            raise ValueError(
                f"qk_nope_head_dim + qk_rope_head_dim "
                f"({self.qk_nope_head_dim} + {self.qk_rope_head_dim}) != "
                f"v_head_dim ({self.v_head_dim}): ops/flash_attention.py "
                f"has one head size, so a latent whose q.k and v widths "
                f"differ is not runnable")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                f"num_nextn_predict_layers={self.num_nextn_predict_layers}:"
                f" at most one multi-token-prediction module (depth 1)")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "num_key_value_heads must equal num_attention_heads: every "
                "head has its own slice of the up-projected latent")
        if self.partial_rotary_factor != 1 or self.rope_scaling is not None:
            raise ValueError(
                "partial_rotary_factor must be 1 and rope_scaling null: the "
                "whole qk_rope_head_dim slice turns, unscaled")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        if self.attention_bias or self.tie_word_embeddings:
            raise ValueError("attention_bias and tie_word_embeddings must "
                             "be false (bias-free projections, a head of "
                             "its own)")
        if self.hidden_act != "silu":
            raise ValueError(f"hidden_act={self.hidden_act!r}: the MLPs "
                             f"are SwiGLU")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace must lie in "
                             "[0, num_hidden_layers]")
        if not 1 <= self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError("num_experts_per_tok must lie in "
                             "[1, n_routed_experts]")
        if self.n_shared_experts < 1:
            raise ValueError("n_shared_experts must be at least 1")

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the routed experts whose stacks live here."""
        return held_range(self.experts_held, self.n_routed_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def glm_lite_tiny(experts_held: Optional[Tuple[int, int]] = None,
                  **overrides) -> GlmLiteConfig:
    """CPU tests: float32 end to end, 1 dense + 2 sparse blocks + the
    module, 4 heads of 24 + 8 / 32, ranks 24 / 16, 8 experts top-2."""
    return GlmLiteConfig(**{**dict(
        vocab_size=128, hidden_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
        v_head_dim=32, first_k_dense_replace=1, intermediate_size=48,
        moe_intermediate_size=16, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=2, rope_theta=10000.0,
        max_position_embeddings=64, experts_held=experts_held,
        dtype=jnp.float32), **overrides})


def _dense(features, name, dtype):
    return nn.DenseGeneral(features, use_bias=False, dtype=dtype, name=name)


def score_scale(cfg: GlmLiteConfig) -> float:
    """What q.k is divided by: the square root of the WHOLE head a score is
    summed over (un-rotated + rotary lanes), not of the un-rotated part."""
    return 1.0 / math.sqrt(cfg.qk_head_dim)


def router_scores(rows, router):
    """Sigmoid scores of float32 rows [N, h] over all routed experts."""
    return jax.nn.sigmoid(jnp.dot(rows, router,
                                  precision=lax.Precision.HIGHEST))


def join_experts(routed, shared, scaling, dtype):
    """``scaling`` x the routed sum + the shared expert (unscaled)."""
    return (scaling * routed.astype(jnp.float32)
            + shared.astype(jnp.float32)).astype(dtype)


def next_tokens(input_ids):
    """Position i holds ``t_(i+1)``; the last holds a wrapped token, which
    the module's loss masks out and, causal, no earlier position reads."""
    return jnp.roll(input_ids, -1, axis=1)


class GlmLiteAttention(nn.Module):
    """Multi-head latent attention (module docstring).  The flash call sits
    directly under this module's scope (``attn_mla``); all that lies
    between the normed input and it under ``bps.mla.latent``."""

    cfg: GlmLiteConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, a, positions):
        cfg = self.cfg
        heads, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim)
        gauges.set("mla.head_dim", float(cfg.qk_head_dim))
        gauges.set("mla.kv_latent_dim", float(cfg.kv_lora_rank + rope))
        with jax.named_scope("bps.mla.latent"):
            c_q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_a_layernorm")(
                _dense(cfg.q_lora_rank, "q_a_proj", cfg.dtype)(a))
            q = _dense((heads, cfg.qk_head_dim), "q_b_proj", cfg.dtype)(c_q)
            ckv = _dense(cfg.kv_lora_rank + rope, "kv_a_proj_with_mqa",
                         cfg.dtype)(a)
            c_kv = RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                           name="kv_a_layernorm")(ckv[..., :cfg.kv_lora_rank])
            kv = _dense((heads, nope + cfg.v_head_dim), "kv_b_proj",
                        cfg.dtype)(c_kv)
            cos, sin = rope_frequencies(rope, positions, cfg.rope_theta)
            # the one rotary key, turned once and read by every head
            k_rope = apply_rope(ckv[..., None, cfg.kv_lora_rank:], cos, sin)
            q = jnp.concatenate(
                [q[..., :nope], apply_rope(q[..., nope:], cos, sin)], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope, k_rope.shape[:2] + (heads, rope))],
                axis=-1)
            v = kv[..., nope:]
        attn = self.attn_fn or banded_attention
        ctx = attn(q, k, v, causal=True, sm_scale=score_scale(cfg))
        return nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, name="o_proj")(ctx)


class GlmLiteMLP(nn.Module):
    """SwiGLU of width ``width``: a dense block's MLP, the shared expert."""

    cfg: GlmLiteConfig
    width: int

    @nn.compact
    def __call__(self, m):
        dtype = self.cfg.dtype
        gate = _dense(self.width, "gate_proj", dtype)(m)
        up = _dense(self.width, "up_proj", dtype)(m)
        return _dense(self.cfg.hidden_size, "down_proj", dtype)(
            jax.nn.silu(gate) * up)


class GlmLiteSparseMoe(nn.Module):
    """A sparse block's MLP on the float32 normed rows ``m`` [B, T, h]: the
    router over all ``n_routed_experts``, the stacks of the routed experts
    held here, the shared expert whole.  Sows the per-expert pair counts
    (all experts) into ``moe_stats`` (``counts``): apply with the collection
    ``mutable`` (:func:`expert_counts`); a plain ``apply`` sows nothing."""

    cfg: GlmLiteConfig

    @nn.compact
    def __call__(self, m):
        cfg = self.cfg
        h, f, e = (cfg.hidden_size, cfg.moe_intermediate_size,
                   cfg.n_routed_experts)
        g = cfg.held[1]
        b, t, _ = m.shape
        router = self.param("router", nn.initializers.lecun_normal(), (h, e),
                            jnp.float32)
        # chooses only; held at zero (module docstring)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (e,), jnp.float32)
        stack = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                             batch_axis=(0,))
        params = {"gate": self.param("gate", stack, (g, h, f), jnp.float32),
                  "up": self.param("up", stack, (g, h, f), jnp.float32),
                  "down": self.param("down", stack, (g, f, h), jnp.float32)}
        rows = m.reshape(b * t, h)
        with jax.named_scope("bps.moe.score"):
            scores = router_scores(rows, router)
        rows = rows.astype(cfg.dtype)
        y, _, _, counts = dropless_moe_mlp(
            rows, params, cfg.num_experts_per_tok, held=cfg.experts_held,
            renormalize=cfg.norm_topk_prob, routing=(scores, bias))
        if not self.is_initializing():   # init returns parameters only
            self.sow("moe_stats", "counts", counts)
        with jax.named_scope("bps.moe.shared"):
            shared = GlmLiteMLP(cfg, f * cfg.n_shared_experts,
                                name="shared_experts")(rows)
            y = join_experts(y, shared, cfg.routed_scaling_factor, cfg.dtype)
        return y.reshape(b, t, h)


class GlmLiteBlock(nn.Module):
    """One block; ``dense`` is its MLP kind (``first_k_dense_replace``)."""

    cfg: GlmLiteConfig
    dense: bool
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        a = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x)
        x = x + GlmLiteAttention(cfg, self.attn_fn, name="attn_mla")(
            a, positions)
        norm = "post_attention_layernorm"
        if self.dense:
            m = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=norm)(x)
            return x + GlmLiteMLP(cfg, cfg.intermediate_size, name="mlp")(m)
        m = RMSNorm(cfg.rms_norm_eps, jnp.float32, name=norm)(x)
        return x + GlmLiteSparseMoe(cfg, name="moe")(m)


class GlmLiteMtp(nn.Module):
    """The multi-token-prediction module: the last block's output ``h``
    and the embedding of the NEXT token -> the rows its head reads."""

    cfg: GlmLiteConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, h, emb_next, positions):
        cfg = self.cfg
        joined = jnp.concatenate(
            [RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="hnorm")(h),
             RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="enorm")(emb_next)],
            axis=-1)
        x = _dense(cfg.hidden_size, "eh_proj", cfg.dtype)(joined)
        block = nn.remat(GlmLiteBlock) if cfg.remat else GlmLiteBlock
        x = block(cfg, False, self.attn_fn, name="block")(x, positions)
        return RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)


class GlmLite(nn.Module):
    """Decoder-only GLM-4.7-Flash: ``wte`` -> blocks -> RMSNorm, and the
    module beside the last block.  Returns ``(x, g)``, the rows [B, T, h]
    the main head and the module's head read (``g`` is ``None`` without a
    module) — both heads are ``lm_head`` [V, h], and at this vocabulary
    their logits are computed in blocks by the loss
    (:func:`glm_lite_loss`) — or, with ``logits=True``, both heads' float32
    logits [B, T, vocab_size] whole (tests, a few short sequences)."""

    cfg: GlmLiteConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, *, logits: bool = False):
        cfg = self.cfg
        b, t = input_ids.shape
        if positions is None:
            positions = jnp.arange(t)
        if positions.ndim == 1:
            positions = jnp.broadcast_to(positions[None], (b, t))
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       name="wte")
        head = self.param("lm_head", nn.initializers.lecun_normal(
            in_axis=-1, out_axis=-2), (cfg.vocab_size, cfg.hidden_size),
            jnp.float32)
        x = wte(input_ids)
        block = nn.remat(GlmLiteBlock) if cfg.remat else GlmLiteBlock
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, i < cfg.first_k_dense_replace, self.attn_fn,
                      name=f"h{i}")(x, positions)
        g = None
        if cfg.num_nextn_predict_layers:
            g = GlmLiteMtp(cfg, self.attn_fn, name="mtp")(
                x, wte(next_tokens(input_ids)), positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
        if not logits:
            return x, g

        def apply_head(rows):
            return None if rows is None else lax.dot_general(
                rows, head.astype(cfg.dtype), (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        return apply_head(x), apply_head(g)


def mtp_labels(labels):
    """The module's labels from the main head's (already shifted: position
    i holds ``t_(i+1)``, -1 = ignored): position i holds ``t_(i+2)``, and
    the last position, which has none, is ignored."""
    return jnp.concatenate(
        [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)


def glm_lite_loss(model: GlmLite, params, batch):
    """Mean next-token cross-entropy of the main head + ``mtp_loss_weight``
    x the module's mean cross-entropy of the token after, both over the rows
    of the head held, through the blocked head.  ``batch``: ``input_ids``
    [B, T] and ``labels`` (already shifted; -1 = ignored)."""
    cfg = model.cfg
    x, g = model.apply(params, batch["input_ids"])
    b, t, h = x.shape
    head = params["params"]["lm_head"]
    labels = batch["labels"]
    loss = blocked_lm_loss(x.reshape(b * t, h), head, labels.reshape(b * t))
    if g is None:
        return loss
    gauges.set("mtp.loss_weight", float(cfg.mtp_loss_weight))
    gauges.set("mtp.positions", float(b * max(t - 2, 0)))
    return loss + cfg.mtp_loss_weight * blocked_lm_loss(
        g.reshape(b * t, h), head, mtp_labels(labels).reshape(b * t))


def sparse_blocks(cfg: GlmLiteConfig):
    """Paths of the sparse blocks in the parameter tree, in order: the
    model's, then the module's."""
    paths = [(f"h{i}",) for i in range(cfg.first_k_dense_replace,
                                       cfg.num_hidden_layers)]
    if cfg.num_nextn_predict_layers:
        paths.append(("mtp", "block"))
    return paths


def expert_counts(model: GlmLite, params, input_ids):
    """Token-expert pairs each of the ``n_routed_experts`` experts
    received, [sparse blocks, experts] int32 (the module's block last):
    what ``parallel.expert.publish_moe_stats`` takes (with
    ``held=model.cfg.experts_held``)."""
    _, sown = model.apply(params, input_ids, mutable=["moe_stats"])
    rows = []
    for path in sparse_blocks(model.cfg):
        node = sown["moe_stats"]
        for key in path:
            node = node[key]
        rows.append(node["moe"]["counts"][0])
    return jnp.stack(rows)
