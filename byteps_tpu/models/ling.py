"""Ling-3.0-flash decoder LM (``model_type: bailing_hybrid``): Kimi Delta
Attention mixers with a latent-attention mixer closing every group of
``layer_group_size`` layers, leading dense SwiGLU layers, sigmoid-scored
experts under group-limited routing beside a shared expert — and one
chip's share of experts and vocabulary.

``inclusionAI/Ling-3.0-flash-VL`` ``config.json``, the LANGUAGE model's
keys (the vision tower has none there and is left out: ids are text ids).
:class:`LingConfig`'s fields carry the source's key names; what the config
has no key for is fixed here and listed, with its reason, under ``assumed``
in ``benchmarks/configs/ling3_flash.json``.  Pre-norm residual blocks ``x
= x + mixer(RMSNorm(x)); x = x + mlp(RMSNorm(x))``; layer ``i`` is latent
attention (MLA) where ``(i + 1) % layer_group_size == 0`` and Kimi Delta
Attention (KDA) otherwise; layers below ``first_k_dense_replace`` have a
dense SwiGLU of ``intermediate_size``, the rest are sparse.  T positions::

    KDA  (arXiv:2510.26692 section 3; H heads, d_k = d_v = head_dim; every
         head its own k, v: num_kv_heads_for_linear_attn 0)
         a = RMSNorm(x)
         q, k, v = silu(conv(a W_q)), silu(conv(a W_k)), silu(conv(a W_v))
               depthwise causal, short_conv_kernel_size taps, zeros on the
               left, no bias (linear_silu)
         q, k = q / ||q||_2, k / ||k||_2  per head (x rsqrt(sum x^2 +
               1e-6): use_qk_norm);  q = q / sqrt(head_dim);  NO rotation
         g = kda_lower_bound * sigmoid(exp(A_log_h) (a W_f + dt_bias))
               [T, H, head_dim] float32: a log-decay a CHANNEL in
               (kda_lower_bound, 0) (kda_safe_gate); W_f full rank
               (no_kda_lora)
         beta = sigmoid(a W_b)   [T, H]
         S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1)
               + beta_t k_t v_t^T        per head, S float32, S_0 = 0
         o_t = S_t^T q_t
         y = RMSNorm_head(o) * sigmoid(a W_g)    the norm over each head's
               channels (group_norm_size 1), one weight [head_dim]; W_g
               full rank
         x = x + y W_o
    MLA  (arXiv:2405.04434 section 2.1 with q_lora_rank null)
         a = RMSNorm(x);  q = a W_q [T, H, nope + rope]
         [c | k_r] = a W_dkv  (kv_lora_rank | rope);  c = RMSNorm(c)
         [k_n | v] = c W_ukv  [T, H, nope | v_head_dim]
         q = [q_n | rot(q_r)],  k = [k_n | rot(k_r) read by all H heads]
         o = softmax(q k^T / sqrt(nope + rope), j <= i) v  [T, H, v_head_dim]
         o = o * sigmoid(a W_gh)[..., None]     W_gh: hidden -> H, a gate a
               head (gated_attention_proj_granularity_type head_wise)
         x = x + o W_o
    MoE  m = RMSNorm(x);  s = sigmoid(m_f32 W_r) [T, num_experts]
         c = s + b        b chooses and is not weighed
         groups of num_experts / n_group: G_j = the sum of the 2 largest c
         in group j; keep the topk_group groups of largest G;  S = the
         num_experts_per_tok largest c inside them
         w_e = routed_scaling_factor s_e / (sum_S s + 1e-20)
         x = x + sum_{e in S} w_e W2_e (silu(W1g_e m) * W1u_e m)
               + V2 (silu(V1g m) * V1u m)       one shared expert
    head logits = RMSNorm(x_last) W_head^T

The q.k width (``qk_nope_head_dim + qk_rope_head_dim``) and
``v_head_dim`` may differ (192 / 128): ``ops.flash_attention`` keeps a
value width of its own.  The rotation is rotate-half over the whole
``qk_rope_head_dim`` slice at ``rope_theta`` (``rotary_dim`` must equal it;
``partial_rotary_factor`` is carried: the KDA blocks have no rotation).
The group-limited choice is made HERE (scope ``bps.moe.group_limit``): two
max passes a group and a rank by comparisons over the ``n_group`` group
scores, ties to the lower index — no sort — and the expert layer is handed
``routing=(p, None)`` with ``p = s`` inside the token's chosen groups and
0 outside: sigmoid scores are positive, so the ``num_experts_per_tok``
largest of ``p`` are the largest inside the chosen groups and the weights
read from ``p`` are ``s`` at them, gradient included.  ``b`` is HELD AT ITS
INITIAL ZERO (the family moves it by a rule outside the gradient that has
no key: left out, as ``models/glm_lite.py`` leaves its own; no auxiliary or
z-loss stands in) — it enters the group scores, and the choice inside the
groups is by ``s``.  The multi-token-prediction module is left out (the
config counts none).  ``expert_swiglu_limit_list`` /
``share_expert_swiglu_limit_list`` clamp the gated activation of late
layers in a form no key gives: a non-zero entry in a layer this model
builds is refused.  Initialisation: ``A_log = log U[1, 16]``, ``dt_bias``
the inverse softplus of a log-uniform [0.001, 0.1], normal(0.02)
elsewhere, norm weights 1.  The recurrence runs as ``ops/kda_scan.py``
``kda_scan`` and the row stages around it — the short convolution, SiLU,
the L2 norms, ``g``, ``beta`` in front, the head norm times the output
gate behind — as ``ops/kda_rows.py`` ``kda_pre`` / ``kda_post``: one pass
over the projection each, float32 inside the kernel (Mosaic kernels,
forward and backward, interpreted off the TPU).  :func:`l2_normalize` and
:func:`log_decay` here are the plain text those kernels are tested
against.

One chip's share (none given: everything): ``experts_held = (first,
count)`` of the ``num_experts`` the router scores — inside ONE routing
group and dividing it, as an expert-parallel layout that keeps a group on
neighbouring chips has them; the routed sum is partial — and
``vocab_size`` the rows of table and head held.  Mixers, router, shared
expert and dense MLPs are whole on every chip.  There is no exchange on
this path: the routed sum and the loss are this chip's partial results.

:func:`ling_loss` = the mean next-token cross-entropy through
``models/gpt.py`` :func:`blocked_lm_loss`.  bf16 compute over float32
parameters; norms, ``g``, ``beta``, the decays, the state, the router and
every softmax in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common.metrics import gauges
from ..parallel.expert import dropless_moe_mlp, held_range
from .glm_lite import join_experts, router_scores
from .gpt import blocked_lm_loss
from .llama import AttnFn, RMSNorm, apply_rope, rope_frequencies
from .mellum import banded_attention

__all__ = ["LingConfig", "Ling", "ling_tiny", "ling_loss", "expert_counts",
           "group_hit_share", "publish_group_stats"]

KDA_CHUNK = 128     # positions a chunk of ops/kda_scan.py (its docstring)

_MUST_BE_FALSE = ("use_nGPT", "value_norm", "up_proj_norm",
                  "scale_router_input", "use_kda_lora", "use_mla_nope",
                  "tie_word_embeddings")


@dataclasses.dataclass(frozen=True)
class LingConfig:
    """Defaults are Ling-3.0-flash-VL's language model as published (42
    layers, every expert and the whole vocabulary held)."""

    vocab_size: int = 157184
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    layer_group_size: int = 6
    first_k_dense_replace: int = 2
    intermediate_size: int = 6144        # width of a dense layer's MLP
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 128                  # KDA's d_k = d_v
    num_kv_heads_for_linear_attn: int = 0
    short_conv_kernel_size: int = 4
    linear_silu: bool = True
    use_qk_norm: bool = True
    group_norm_size: int = 1
    kda_safe_gate: bool = True
    kda_lower_bound: float = -5.0
    no_kda_lora: bool = True
    use_kda_lora: bool = False
    mtp_use_kda: bool = False            # carried: no module is built
    q_lora_rank: Any = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rotary_dim: int = 64
    partial_rotary_factor: float = 0.5   # carried: rotary_dim says it
    rope_theta: float = 6000000.0
    use_mla_nope: bool = False
    gated_attention_proj_granularity_type: str = "head_wise"
    num_experts: int = 512               # the router's width
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    moe_intermediate_size: int = 768     # width of ONE expert
    moe_shared_expert_intermediate_size: int = 768
    moe_router_enable_expert_bias: bool = True
    score_function: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scale_router_input: bool = False
    expert_swiglu_limit_list: Tuple = ()
    share_expert_swiglu_limit_list: Tuple = ()
    use_nGPT: bool = False
    value_norm: bool = False
    up_proj_norm: bool = False
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 131072
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    dtype: Any = jnp.bfloat16
    remat: bool = False

    def __post_init__(self):
        for name in ("expert_swiglu_limit_list",
                     "share_expert_swiglu_limit_list"):
            limits = tuple(getattr(self, name))
            object.__setattr__(self, name, limits)
            if any(limits[:self.num_hidden_layers]):
                raise ValueError(
                    f"{name}: a non-zero limit in a layer this model builds "
                    f"({list(limits[:self.num_hidden_layers])}); the clamp's "
                    f"form has no key")
        if self.num_experts % self.n_group:
            raise ValueError(
                f"num_experts={self.num_experts} does not divide into "
                f"n_group={self.n_group} routing groups")
        if self.experts_held is not None:
            first, count = held_range(self.experts_held, self.num_experts)
            object.__setattr__(self, "experts_held", (first, count))
            per_group = self.num_experts // self.n_group
            if (first // per_group != (first + count - 1) // per_group
                    or per_group % count):
                raise ValueError(
                    f"experts_held={(first, count)}: a share lies inside "
                    f"ONE routing group of {per_group} experts and divides "
                    f"it (a group lives on neighbouring chips)")
        for name in _MUST_BE_FALSE:
            if getattr(self, name):
                raise ValueError(f"{name} must be false: not computed here")
        if self.q_lora_rank is not None:
            raise ValueError(f"q_lora_rank={self.q_lora_rank!r}: the query "
                             f"projection here is full rank (null)")
        if self.num_kv_heads_for_linear_attn != 0:
            raise ValueError(
                "num_kv_heads_for_linear_attn must be 0: every KDA head has "
                "its own k and v")
        if self.group_norm_size != 1:
            raise ValueError("group_norm_size must be 1: the KDA output norm "
                             "is over each head's channels")
        if not (self.no_kda_lora and self.kda_safe_gate and self.linear_silu
                and self.use_qk_norm):
            raise ValueError(
                "no_kda_lora, kda_safe_gate, linear_silu and use_qk_norm "
                "must be true: full-rank gates, the bounded log-decay, silu "
                "after the short convolutions, L2-normed q and k")
        if not self.kda_lower_bound < 0:
            raise ValueError("kda_lower_bound must be negative")
        if self.gated_attention_proj_granularity_type != "head_wise":
            raise ValueError(
                f"gated_attention_proj_granularity_type="
                f"{self.gated_attention_proj_granularity_type!r}: the MLA "
                f"gate computed here is one sigmoid a head ('head_wise')")
        if self.rotary_dim != self.qk_rope_head_dim or self.rotary_dim % 2:
            raise ValueError(
                f"rotary_dim={self.rotary_dim} must equal qk_rope_head_dim="
                f"{self.qk_rope_head_dim} and be even: the whole rotary "
                f"slice turns")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "num_key_value_heads must equal num_attention_heads: every "
                "head has its own slice of the up-projected latent")
        if (self.score_function != "sigmoid"
                or not self.moe_router_enable_expert_bias):
            raise ValueError(
                f"score_function={self.score_function!r} / "
                f"moe_router_enable_expert_bias="
                f"{self.moe_router_enable_expert_bias}: the router computed "
                f"here is sigmoid scores and a bias that chooses")
        if not 1 <= self.topk_group <= self.n_group:
            raise ValueError("topk_group must lie in [1, n_group]")
        if not (1 <= self.num_experts_per_tok
                <= self.topk_group * (self.num_experts // self.n_group)):
            raise ValueError("num_experts_per_tok must fit inside the "
                             "topk_group chosen groups")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace must lie in "
                             "[0, num_hidden_layers]")

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the routed experts whose stacks live here."""
        return held_range(self.experts_held, self.num_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def is_mla(self, i: int) -> bool:
        return (i + 1) % self.layer_group_size == 0

    def is_dense(self, i: int) -> bool:
        return i < self.first_k_dense_replace


def ling_tiny(experts_held: Optional[Tuple[int, int]] = None,
              **overrides) -> LingConfig:
    """CPU tests: float32 end to end, 6 layers holding both mixers (5 KDA,
    1 MLA) and both MLP kinds (2 dense, 4 sparse); 2 heads of 16, a latent
    of 16 + 8 with q.k 24 / v 16, 16 experts in 4 groups of which 2, top-4."""
    return LingConfig(**{**dict(
        vocab_size=128, hidden_size=32, num_hidden_layers=6,
        layer_group_size=6, first_k_dense_replace=2, intermediate_size=48,
        num_attention_heads=2, num_key_value_heads=2, head_dim=16,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rotary_dim=8, rope_theta=10000.0, num_experts=16,
        num_experts_per_tok=4, n_group=4, topk_group=2,
        moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
        max_position_embeddings=128, experts_held=experts_held,
        dtype=jnp.float32), **overrides})


_INIT = nn.initializers.normal(0.02)


def _dense(features, name, dtype, axis=-1):
    return nn.DenseGeneral(features, axis=axis, use_bias=False, dtype=dtype,
                           name=name, kernel_init=_INIT)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(0.001),
                                    math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))           # softplus^-1(dt)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def l2_normalize(x, eps: float = 1e-6):
    """x / ||x||_2 over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def log_decay(f, a_log, dt_bias, lower_bound: float):
    """The bounded gate: f [B, T, H, d] -> the float32 log-decay a channel,
    in (lower_bound, 0); ``a_log`` [H], ``dt_bias`` [H, d]."""
    return lower_bound * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None] * (f.astype(jnp.float32) + dt_bias))


def mla_gate(x):
    """The gate a head of the latent attention, from ``a W_gh``."""
    return jax.nn.sigmoid(x.astype(jnp.float32))


def group_limited(scores, bias, n_group: int, topk_group: int):
    """``scores`` [N, E] float32 (positive), ``bias`` [E] -> (``p`` = the
    scores inside each token's ``topk_group`` chosen groups and 0 outside,
    ``chosen`` [N, n_group] bool).  A group's score is the sum of its two
    largest ``scores + bias``; among equal group scores the lower index
    wins.  No gradient flows through the choice."""
    n, e = scores.shape
    c = jax.lax.stop_gradient(scores + bias).reshape(n, n_group, e // n_group)
    best = c.max(-1, keepdims=True)
    is_best = c == best
    second = jnp.where(is_best.sum(-1) > 1, best[..., 0],
                       jnp.where(is_best, -jnp.inf, c).max(-1))
    group = best[..., 0] + second                       # [N, n_group]
    mine, other = group[:, :, None], group[:, None, :]
    index = jnp.arange(n_group)
    ahead = (other > mine) | ((other == mine)
                              & (index[None, None, :] < index[None, :, None]))
    chosen = ahead.sum(-1) < topk_group                 # [N, n_group]
    inside = jnp.repeat(chosen, e // n_group, axis=1)
    return jnp.where(inside, scores, 0.0), chosen


class _Scale(nn.Module):
    """A norm's weight [n] by itself (``<name>/scale``, ones), for a norm
    that a kernel computes."""

    @nn.compact
    def __call__(self, n):
        return self.param("scale", nn.initializers.ones, (n,), jnp.float32)


class LingKda(nn.Module):
    """The Kimi Delta Attention mixer on the normed rows ``a`` [B, T, h]
    (module docstring); each stage under a ``bps.kda.*`` scope: the
    projection (``proj``), the row stages in front of the scan as ONE pass
    over it (``pre``: ``ops/kda_rows.py`` ``kda_pre`` — the short
    convolution, SiLU, the L2 norms, ``g`` and ``beta`` in float32 inside
    the kernel, q, k, v rounded to ``cfg.dtype`` where the scan reads
    them), the scan's kernels (``scan``), the head norm times the gate as
    one pass (``kda_post``) and ``W_o`` (``out``)."""

    cfg: LingConfig

    @nn.compact
    def __call__(self, a):
        from ..ops.kda_rows import kda_post, kda_pre
        from ..ops.kda_scan import kda_scan
        cfg = self.cfg
        heads, d = cfg.num_attention_heads, cfg.head_dim
        inner = heads * d
        t = a.shape[1]
        gauges.set("kda.log_decay_floor", float(cfg.kda_lower_bound))
        with jax.named_scope("bps.kda.proj"):
            # [ q | k | v | f | the output gate | beta ]
            proj = _dense(5 * inner + heads, "in_proj", cfg.dtype)(a)
        kernel = self.param("conv_kernel", _INIT,
                            (cfg.short_conv_kernel_size, 3 * inner),
                            jnp.float32)
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads, d),
                             jnp.float32)
        with jax.named_scope("bps.kda.pre"):
            # float32 up to the L2 norms, inside the kernel: rounding the
            # convolution's result to bfloat16 first read 0.20 against
            # 0.075 on the decays' leaves (gradcheck_ling.py --rehearsal)
            q, k, v, g, beta, gate = kda_pre(
                proj, kernel, a_log, dt_bias,
                lower_bound=cfg.kda_lower_bound)
        with jax.named_scope("bps.kda.scan"):
            o = kda_scan(q, k, v, g, beta, chunk=math.gcd(t, KDA_CHUNK))
        with jax.named_scope("bps.kda.out"):
            # one weight [head_dim] for every head's norm
            y = kda_post(o, gate, _Scale(name="o_norm")(d),
                         eps=cfg.rms_norm_eps)
            return _dense(cfg.hidden_size, "o_proj", cfg.dtype)(y)


class LingMla(nn.Module):
    """Latent attention without a query latent and with a gate a head
    (module docstring).  The flash call sits directly under this module's
    scope (``attn_mla``); what lies between the normed input and it under
    ``bps.mla.latent``, the gate under ``bps.mla.gate``."""

    cfg: LingConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, a, positions):
        cfg = self.cfg
        heads, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim)
        gauges.set("mla.head_dim", float(cfg.qk_head_dim))
        gauges.set("mla.v_head_dim", float(cfg.v_head_dim))
        gauges.set("mla.kv_latent_dim", float(cfg.kv_lora_rank + rope))
        with jax.named_scope("bps.mla.latent"):
            q = _dense((heads, cfg.qk_head_dim), "q_proj", cfg.dtype)(a)
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
        ctx = attn(q, k, v, causal=True,
                   sm_scale=1.0 / math.sqrt(cfg.qk_head_dim))
        with jax.named_scope("bps.mla.gate"):
            gate = mla_gate(_dense(heads, "g_proj", cfg.dtype)(a))
            ctx = (ctx.astype(jnp.float32) * gate[..., None]
                   ).astype(cfg.dtype)
        return _dense(cfg.hidden_size, "o_proj", cfg.dtype,
                      axis=(-2, -1))(ctx)


class LingMLP(nn.Module):
    """SwiGLU of width ``width``: a dense layer's MLP, the shared expert."""

    cfg: LingConfig
    width: int

    @nn.compact
    def __call__(self, m):
        dtype = self.cfg.dtype
        gate = _dense(self.width, "gate_proj", dtype)(m)
        up = _dense(self.width, "up_proj", dtype)(m)
        return _dense(self.cfg.hidden_size, "down_proj", dtype)(
            jax.nn.silu(gate) * up)


class LingSparseMoe(nn.Module):
    """A sparse layer's MLP on the float32 normed rows ``m`` [B, T, h]: the
    router over all ``num_experts`` under the group limit, the stacks of
    the routed experts held here, the shared expert whole.  Sows into
    ``moe_stats`` the per-expert pair counts (``counts``) and the share of
    tokens whose chosen groups include the held experts' (``group_hit``)."""

    cfg: LingConfig

    @nn.compact
    def __call__(self, m):
        cfg = self.cfg
        h, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        first, g = cfg.held
        b, t, _ = m.shape
        gauges.set("moe.groups", float(cfg.n_group))
        gauges.set("moe.groups_chosen", float(cfg.topk_group))
        router = self.param("router", _INIT, (h, e), jnp.float32)
        # chooses only; held at zero (module docstring)
        bias = self.param("expert_bias", nn.initializers.zeros, (e,),
                          jnp.float32)
        params = {"gate": self.param("gate", _INIT, (g, h, f), jnp.float32),
                  "up": self.param("up", _INIT, (g, h, f), jnp.float32),
                  "down": self.param("down", _INIT, (g, f, h), jnp.float32)}
        rows = m.reshape(b * t, h)
        with jax.named_scope("bps.moe.score"):
            scores = router_scores(rows, router)
        with jax.named_scope("bps.moe.group_limit"):
            inside, chosen = group_limited(scores, bias, cfg.n_group,
                                           cfg.topk_group)
        rows = rows.astype(cfg.dtype)
        y, _, _, counts = dropless_moe_mlp(
            rows, params, cfg.num_experts_per_tok, held=cfg.experts_held,
            renormalize=cfg.norm_topk_prob, routing=(inside, None))
        if not self.is_initializing():   # init returns parameters only
            self.sow("moe_stats", "counts", counts)
            self.sow("moe_stats", "group_hit", jnp.mean(
                chosen[:, first // (e // cfg.n_group)].astype(jnp.float32)))
        with jax.named_scope("bps.moe.shared"):
            shared = LingMLP(cfg, cfg.moe_shared_expert_intermediate_size,
                             name="shared_expert")(rows)
            y = join_experts(y, shared, cfg.routed_scaling_factor, cfg.dtype)
        return y.reshape(b, t, h)


class LingBlock(nn.Module):
    """Layer ``index``: its mixer by ``layer_group_size``, its MLP by
    ``first_k_dense_replace``."""

    cfg: LingConfig
    index: int
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        a = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x)
        if cfg.is_mla(self.index):
            x = x + LingMla(cfg, self.attn_fn, name="attn_mla")(a, positions)
        else:
            x = x + LingKda(cfg, name="mixer_kda")(a)
        norm = "post_attention_layernorm"
        if cfg.is_dense(self.index):
            m = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name=norm)(x)
            return x + LingMLP(cfg, cfg.intermediate_size, name="mlp")(m)
        m = RMSNorm(cfg.rms_norm_eps, jnp.float32, name=norm)(x)
        return x + LingSparseMoe(cfg, name="moe")(m)


class Ling(nn.Module):
    """``wte`` -> the layers -> RMSNorm.  Returns the rows [B, T, h] the
    head reads (``lm_head`` [V, h]; its logits are computed in blocks by the
    loss, :func:`ling_loss`) — or, with ``logits=True``, the head's float32
    logits [B, T, vocab_size] whole (tests)."""

    cfg: LingConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, input_ids, *, logits: bool = False):
        cfg = self.cfg
        b, t = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       embedding_init=_INIT, name="wte")
        head = self.param("lm_head", _INIT,
                          (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = wte(input_ids)
        block = nn.remat(LingBlock) if cfg.remat else LingBlock
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, i, self.attn_fn, name=f"h{i}")(x, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
        if not logits:
            return x
        return jax.lax.dot_general(
            x, head.astype(cfg.dtype), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def ling_loss(model: Ling, params, batch):
    """Mean next-token cross-entropy over the rows of the head held,
    through the blocked head.  ``batch``: ``input_ids`` [B, T] and
    ``labels`` (already shifted; -1 = ignored)."""
    x = model.apply(params, batch["input_ids"])
    b, t, h = x.shape
    return blocked_lm_loss(x.reshape(b * t, h), params["params"]["lm_head"],
                           batch["labels"].reshape(b * t))


def _sown(model: Ling, params, input_ids, name):
    _, sown = model.apply(params, input_ids, mutable=["moe_stats"])
    cfg = model.cfg
    return jnp.stack([
        sown["moe_stats"][f"h{i}"]["moe"][name][0]
        for i in range(cfg.first_k_dense_replace, cfg.num_hidden_layers)])


def expert_counts(model: Ling, params, input_ids):
    """Token-expert pairs each of the ``num_experts`` experts received,
    [sparse layers, experts] int32: what
    ``parallel.expert.publish_moe_stats`` takes (with
    ``held=model.cfg.experts_held``)."""
    return _sown(model, params, input_ids, "counts")


def group_hit_share(model: Ling, params, input_ids):
    """Share of tokens whose ``topk_group`` chosen groups include the held
    experts' group, [sparse layers] float32 (``topk_group / n_group`` under
    a balanced router): what :func:`publish_group_stats` takes."""
    return _sown(model, params, input_ids, "group_hit")


def publish_group_stats(hits) -> None:
    """Set the gauge ``moe.group_hit_share`` (the mean over the sparse
    layers) from one batch's :func:`group_hit_share`.  Host side."""
    gauges.set("moe.group_hit_share", float(jnp.mean(jnp.asarray(hits))))
