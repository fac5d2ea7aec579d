"""Qwen3-Next decoder LM (``model_type: qwen3_next``): three Gated DeltaNet
mixers to one gated softmax-attention mixer, every layer a sparse MLP — a
softmax router's top-k beside a shared expert behind a sigmoid gate —
zero-centred RMSNorms, and one chip's share of experts and vocabulary.

``Qwen/Qwen3-Next-80B-A3B-Instruct`` ``config.json``.
:class:`Qwen3NextConfig`'s fields carry the source's key names; what the
config has no key for is fixed here and listed, with its reason, under
``assumed`` in ``benchmarks/configs/qwen3_next_80b.json``.  ``N0(x; w) = x
rsqrt(mean x^2 + eps) (1 + w)`` in float32, ``w`` from ZERO (the block
norms, the final norm, the per-head q / k norms); ``N1(x; w)`` the same
with ``w`` in the place of ``1 + w``, from one (the DeltaNet head norm).
Layer ``i`` is softmax attention where ``(i + 1) % full_attention_interval
== 0`` and Gated DeltaNet otherwise; ``x = x + mixer(N0(x)); x = x +
moe(N0(x))``.  T positions::

    GDN  (arXiv:2412.06464; H_k key heads under H_v value heads of d)
         [q | k | v | z] = a W_qkvz     h -> 2 H_k d + 2 H_v d, no bias
         [b | alpha]     = a W_ba       h -> 2 H_v
         [q | k | v] = silu(conv([q | k | v]))   depthwise causal,
               linear_conv_kernel_dim taps, zeros on the left, no bias
         q = q rsqrt(sum q^2 + 1e-6) / sqrt(d);  k = k rsqrt(sum k^2 +
               1e-6)    per key head, float32
         beta = sigmoid(b);  g = -exp(A_log) softplus(alpha + dt_bias)
               [T, H_v] float32: a log-decay a HEAD, <= 0, unbounded
         S_t = (I - beta_t k_t k_t^T) exp(g_t) S_(t-1) + beta_t k_t v_t^T
               per value head h with key head h // (H_v / H_k), S float32
         o_t = S_t^T q_t
         y = N1(o_h; w_n [d]) * silu(z_h);  x = x + y W_o
    attn [q_h | gamma_h] = a W_q   h -> H (D + D): a head's query, its gate
         k, v = a W_k, a W_v       h -> H_kv D
         q_h = N0(q_h; w_q [D]);  k_j = N0(k_j; w_k [D])
         the first partial_rotary_factor D lanes of q_h and k_j rotate
               (rotate-half over those lanes, rope_theta); the rest pass
         o_h = softmax(q_h k_(h // (H / H_kv))^T / sqrt(D), j <= i) v
         o_h = o_h * sigmoid(gamma_h);  x = x + o W_o
    MoE  m = N0(x);  p = softmax(m_f32 W_r) over all num_experts
         the num_experts_per_tok largest;  w_e = p_e / sum of the chosen
               (norm_topk_prob)
         x = x + sum_{e chosen and HELD} w_e W_d,e(silu(W_g,e m) * W_u,e m)
               + sigmoid(m w_s) * E_shared(m)      w_s: h -> 1
    head logits = N0(x_last) W_head          untied, [h, V]

The column order of the two fused projections is this file's (the source
interleaves them by key-head group): a permutation of the same matrix.
The recurrence runs as ``ops/gdn_scan.py`` ``gdn_scan`` (Mosaic kernels,
forward and backward, interpreted off the TPU), the head norm times the
output gate as ``ops/kda_rows.py`` ``kda_post`` with the gate's activation
SiLU; the convolution, SiLU and the L2 norms in front of the scan as ONE
pass over ``W_qkvz``'s result (``ops/kda_rows.py`` ``qkv_pre``: float32
inside the kernel, q, k, v rounded to ``dtype`` where the scan reads them,
z's columns handed on to ``kda_post``), ``g`` and ``beta`` [T, H_v] a few
plain lines beside it, all under the scope ``bps.gdn.pre``;
:func:`gdn_pre` is the stage's plain text, which the kernels are tested
against.  k and v are repeated H_kv -> H heads in front of the flash
call.  The multi-token-prediction module is left out (the config
counts none), and there is no auxiliary loss.  Initialisation: ``A_log =
log U[1, 16]``, ``dt_bias`` 1, normal(0.02) for every matrix, zero-centred
norm weights 0, ``w_n`` 1.

One chip's share (none given: everything): ``experts_held = (first,
count)`` of the ``num_experts`` the router scores, and ``vocab_size`` the
rows of table and head held.  Mixers, router and shared expert are whole
on every chip.  There is no exchange on this path: the routed sum and the
loss are this chip's partial results.

:func:`qwen3_next_loss` = the mean next-token cross-entropy through
``models/gpt.py`` :func:`blocked_lm_loss`.  bf16 compute over float32
parameters; norms, ``g``, ``beta``, the decays, the solve, the state, the
router and every softmax in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel.expert import dropless_moe_mlp, held_range
from .gpt import blocked_lm_loss
from .ling import _a_log_init, _dense, _INIT, _Scale, l2_normalize
from .llama import AttnFn, apply_rope, repeat_kv, rope_frequencies
from .mellum import banded_attention
from .nemotron_h import causal_conv

__all__ = ["Qwen3NextConfig", "Qwen3Next", "qwen3_next_tiny",
           "qwen3_next_loss", "expert_counts"]

GDN_CHUNK = 128     # positions a chunk of ops/gdn_scan.py (its docstring)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """Defaults are Qwen3-Next-80B-A3B as published (48 layers, every
    expert and the whole vocabulary held)."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 5120        # carried: no layer's MLP is dense
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rope_scaling: Any = None
    use_sliding_window: bool = False
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple = ()
    num_experts: int = 512               # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512     # width of ONE expert
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 262144
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    dtype: Any = jnp.bfloat16
    remat: bool = False

    def __post_init__(self):
        object.__setattr__(self, "mlp_only_layers",
                           tuple(self.mlp_only_layers))
        if self.mlp_only_layers or self.decoder_sparse_step != 1:
            raise ValueError(
                f"mlp_only_layers={list(self.mlp_only_layers)} / "
                f"decoder_sparse_step={self.decoder_sparse_step}: every "
                f"layer built here is sparse ([] and 1)")
        if self.rope_scaling is not None or self.use_sliding_window:
            raise ValueError(
                f"rope_scaling={self.rope_scaling!r} / use_sliding_window="
                f"{self.use_sliding_window}: the rotation computed here is "
                f"unscaled and the attention causal over every key")
        if self.hidden_act != "silu":
            raise ValueError(f"hidden_act={self.hidden_act!r}: the gated "
                             f"activation computed here is silu")
        if self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings must be false: the head "
                             "is its own matrix")
        if self.experts_held is not None:
            # refuses a share that is not whole experts of the router's
            object.__setattr__(self, "experts_held", held_range(
                self.experts_held, self.num_experts))
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"linear_num_value_heads={self.linear_num_value_heads} do "
                f"not divide over linear_num_key_heads="
                f"{self.linear_num_key_heads}")
        if self.linear_key_head_dim != self.linear_value_head_dim:
            raise ValueError(
                f"linear_key_head_dim={self.linear_key_head_dim} and "
                f"linear_value_head_dim={self.linear_value_head_dim} differ: "
                f"the row kernels in front of the scan walk heads of one "
                f"size")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be divisible by "
                             "num_key_value_heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"partial_rotary_factor={self.partial_rotary_factor} of "
                f"head_dim={self.head_dim} is {self.rotary_dim} lanes: an "
                f"even number of them turns")
        if not 1 <= self.num_experts_per_tok <= self.num_experts:
            raise ValueError("num_experts_per_tok must lie in "
                             "[1, num_experts]")
        if self.full_attention_interval < 1:
            raise ValueError("full_attention_interval must be positive")

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the routed experts whose stacks live here."""
        return held_range(self.experts_held, self.num_experts)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_attention(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0


def qwen3_next_tiny(experts_held: Optional[Tuple[int, int]] = None,
                    **overrides) -> Qwen3NextConfig:
    """CPU tests: float32 end to end, one period (3 DeltaNet, 1 attention);
    2 key heads under 4 value heads of 16, attention 4 / 2 heads of 16 of
    which 8 lanes rotate, 16 experts top-4 + the shared expert."""
    return Qwen3NextConfig(**{**dict(
        vocab_size=128, hidden_size=32, intermediate_size=48,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, partial_rotary_factor=0.5, rope_theta=10000.0,
        linear_key_head_dim=16, linear_value_head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, max_position_embeddings=128,
        experts_held=experts_held, dtype=jnp.float32), **overrides})


class ZeroCentredNorm(nn.Module):
    """``N0``: ``x rsqrt(mean x^2 + eps) (1 + scale)`` over the last axis
    in float32, ``scale`` from zero."""

    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                           jnp.float32)
        xf = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                            + self.eps)
        return (xf * rms * (1.0 + scale)).astype(self.dtype)


# the per-head q / k norms of the attention, a seam of their own (the
# benchmarks' breaks leave them out: ``gradcheck_qwen3_next.py`` ``broken``)
QkNorm = ZeroCentredNorm


def gdn_log_decay(alpha, a_log, dt_bias):
    """The gate: alpha [B, T, H_v] -> the float32 log-decay a head, ``-
    exp(A_log) softplus(alpha + dt_bias)``: <= 0 and unbounded below."""
    return -jnp.exp(a_log) * jax.nn.softplus(
        alpha.astype(jnp.float32) + dt_bias)


def gdn_pre(qkv, ba, conv_kernel, a_log, dt_bias, cfg: Qwen3NextConfig):
    """The row stages in front of the scan as plain text (the mixer runs
    ``ops/kda_rows.py`` ``qkv_pre``, whose tests read this), float32 up to
    the rounding of q, k, v to ``cfg.dtype``: ``qkv`` [B, T, 2 H_k d + H_v
    d] and ``ba`` [B, T, 2 H_v] -> q, k [B, T, H_k, d], v [B, T, H_v, d],
    g and beta [B, T, H_v] float32."""
    b, t, _ = qkv.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    x = jax.nn.silu(causal_conv(qkv.astype(jnp.float32), conv_kernel, 0.0))
    q = l2_normalize(x[..., :hk * dk].reshape(b, t, hk, dk)) / math.sqrt(dk)
    k = l2_normalize(x[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk))
    v = x[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv].astype(jnp.float32))
    g = gdn_log_decay(ba[..., hv:], a_log, dt_bias)
    return (q.astype(cfg.dtype), k.astype(cfg.dtype), v.astype(cfg.dtype), g,
            beta)


def attention_gate(ctx, gamma, dtype):
    """``o * sigmoid(gamma)``, element-wise, in float32."""
    return (ctx.astype(jnp.float32)
            * jax.nn.sigmoid(gamma.astype(jnp.float32))).astype(dtype)


def join_shared(routed, shared, gate_logit, dtype):
    """The routed sum + ``sigmoid(m w_s)`` x the shared expert."""
    return (routed.astype(jnp.float32)
            + jax.nn.sigmoid(gate_logit.astype(jnp.float32))
            * shared.astype(jnp.float32)).astype(dtype)


class Qwen3NextGdn(nn.Module):
    """The Gated DeltaNet mixer on the normed rows ``a`` [B, T, h] (module
    docstring); each stage under a ``bps.gdn.*`` scope: the projections
    (``proj``), the row stages in front of the scan as one pass over
    ``in_proj_qkvz``'s result, with ``g`` and ``beta`` (``pre``), the
    scan's kernels (``scan``), the head norm times ``silu(z)`` as one pass
    and ``W_o`` (``out``)."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, a):
        from ..ops.gdn_scan import gdn_scan
        from ..ops.kda_rows import kda_post, qkv_pre
        cfg = self.cfg
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        conv_dim = 2 * hk * dk + hv * dv
        t = a.shape[1]
        with jax.named_scope("bps.gdn.proj"):
            qkvz = _dense(conv_dim + hv * dv, "in_proj_qkvz", cfg.dtype)(a)
            ba = _dense(2 * hv, "in_proj_ba", cfg.dtype)(a)
        kernel = self.param("conv_kernel", _INIT,
                            (cfg.linear_conv_kernel_dim, conv_dim),
                            jnp.float32)
        a_log = self.param("A_log", _a_log_init, (hv,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,),
                             jnp.float32)
        with jax.named_scope("bps.gdn.pre"):
            # gdn_pre's rows as one pass over the projection, float32
            # inside the kernel; z's columns handed on as they lie
            q, k, v, z = qkv_pre(qkvz, kernel, key_heads=hk, value_heads=hv,
                                 head_dim=dk)
            beta = jax.nn.sigmoid(ba[..., :hv].astype(jnp.float32))
            g = gdn_log_decay(ba[..., hv:], a_log, dt_bias)
        with jax.named_scope("bps.gdn.scan"):
            o = gdn_scan(q, k, v, g, beta, chunk=math.gcd(t, GDN_CHUNK))
        with jax.named_scope("bps.gdn.out"):
            # one weight [d] for every head's norm, from one
            y = kda_post(o, z, _Scale(name="o_norm")(dv),
                         eps=cfg.rms_norm_eps, gate_act="silu")
            return _dense(cfg.hidden_size, "o_proj", cfg.dtype)(y)


class Qwen3NextAttention(nn.Module):
    """Gated softmax attention (module docstring).  The flash call sits
    directly under this module's scope (``attn``); the q / k norms, the
    rotation and the gate under ``bps.attn.gate``."""

    cfg: Qwen3NextConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, a, positions):
        cfg = self.cfg
        heads, kv_heads, hd = (cfg.num_attention_heads,
                               cfg.num_key_value_heads, cfg.head_dim)
        # a head's query, then its gate
        qg = _dense((heads, 2 * hd), "q_proj", cfg.dtype)(a)
        k = _dense((kv_heads, hd), "k_proj", cfg.dtype)(a)
        v = _dense((kv_heads, hd), "v_proj", cfg.dtype)(a)
        with jax.named_scope("bps.attn.gate"):
            q = QkNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(
                qg[..., :hd])
            k = QkNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
            cos, sin = rope_frequencies(cfg.rotary_dim, positions,
                                        cfg.rope_theta)
            q = apply_rope(q, cos, sin, cfg.rotary_dim)
            k = apply_rope(k, cos, sin, cfg.rotary_dim)
            k, v = repeat_kv(k, v, heads // kv_heads)
        attn = self.attn_fn or banded_attention
        ctx = attn(q, k, v, causal=True, sm_scale=1.0 / math.sqrt(hd))
        with jax.named_scope("bps.attn.gate"):
            ctx = attention_gate(ctx, qg[..., hd:], cfg.dtype)
        return _dense(cfg.hidden_size, "o_proj", cfg.dtype,
                      axis=(-2, -1))(ctx)


class Qwen3NextMLP(nn.Module):
    """SwiGLU of width ``width``: the shared expert."""

    cfg: Qwen3NextConfig
    width: int

    @nn.compact
    def __call__(self, m):
        dtype = self.cfg.dtype
        gate = _dense(self.width, "gate_proj", dtype)(m)
        up = _dense(self.width, "up_proj", dtype)(m)
        return _dense(self.cfg.hidden_size, "down_proj", dtype)(
            jax.nn.silu(gate) * up)


class Qwen3NextSparseMoe(nn.Module):
    """A layer's MLP on the normed rows ``m`` [B, T, h]: the expert
    layer's own softmax router over all ``num_experts``, the stacks of the
    routed experts held here, the shared expert whole behind its gate.
    Sows the per-expert pair counts into ``moe_stats`` (``counts``)."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, m):
        cfg = self.cfg
        h, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        g = cfg.held[1]
        b, t, _ = m.shape
        params = {"router": self.param("router", _INIT, (h, e), jnp.float32),
                  "gate": self.param("gate", _INIT, (g, h, f), jnp.float32),
                  "up": self.param("up", _INIT, (g, h, f), jnp.float32),
                  "down": self.param("down", _INIT, (g, f, h), jnp.float32)}
        rows = m.reshape(b * t, h)
        y, _, _, counts = dropless_moe_mlp(
            rows, params, cfg.num_experts_per_tok, held=cfg.experts_held,
            renormalize=cfg.norm_topk_prob)
        if not self.is_initializing():   # init returns parameters only
            self.sow("moe_stats", "counts", counts)
        with jax.named_scope("bps.moe.shared"):
            shared = Qwen3NextMLP(cfg, cfg.shared_expert_intermediate_size,
                                  name="shared_expert")(rows)
            y = join_shared(y, shared,
                            _dense(1, "shared_expert_gate", cfg.dtype)(rows),
                            cfg.dtype)
        return y.reshape(b, t, h)


class Qwen3NextBlock(nn.Module):
    """Layer ``index``: its mixer by ``full_attention_interval``."""

    cfg: Qwen3NextConfig
    index: int
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        a = ZeroCentredNorm(cfg.rms_norm_eps, cfg.dtype,
                            name="input_layernorm")(x)
        if cfg.is_attention(self.index):
            x = x + Qwen3NextAttention(cfg, self.attn_fn, name="attn")(
                a, positions)
        else:
            x = x + Qwen3NextGdn(cfg, name="mixer_gdn")(a)
        m = ZeroCentredNorm(cfg.rms_norm_eps, cfg.dtype,
                            name="post_attention_layernorm")(x)
        return x + Qwen3NextSparseMoe(cfg, name="moe")(m)


class Qwen3Next(nn.Module):
    """``wte`` -> the layers -> ``N0``.  Returns the rows [B, T, h] the
    head reads (``lm_head`` [h, V]; its logits are computed in blocks by
    the loss, :func:`qwen3_next_loss`) — or, with ``logits=True``, the
    head's float32 logits [B, T, vocab_size] whole (tests)."""

    cfg: Qwen3NextConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, input_ids, *, logits: bool = False):
        cfg = self.cfg
        b, t = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       embedding_init=_INIT, name="wte")
        head = self.param("lm_head", _INIT,
                          (cfg.hidden_size, cfg.vocab_size), jnp.float32)
        x = wte(input_ids)
        block = nn.remat(Qwen3NextBlock) if cfg.remat else Qwen3NextBlock
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, i, self.attn_fn, name=f"h{i}")(x, positions)
        x = ZeroCentredNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
        if not logits:
            return x
        return jax.lax.dot_general(
            x, head.astype(cfg.dtype), (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def qwen3_next_loss(model: Qwen3Next, params, batch):
    """Mean next-token cross-entropy over the rows of the head held,
    through the blocked head.  ``batch``: ``input_ids`` [B, T] and
    ``labels`` (already shifted; -1 = ignored)."""
    x = model.apply(params, batch["input_ids"])
    b, t, h = x.shape
    return blocked_lm_loss(x.reshape(b * t, h), params["params"]["lm_head"],
                           batch["labels"].reshape(b * t), kernel=True)


def expert_counts(model: Qwen3Next, params, input_ids):
    """Token-expert pairs each of the ``num_experts`` experts received,
    [layers, experts] int32: what ``parallel.expert.publish_moe_stats``
    takes (with ``held=model.cfg.experts_held``)."""
    _, sown = model.apply(params, input_ids, mutable=["moe_stats"])
    return jnp.stack([sown["moe_stats"][f"h{i}"]["moe"]["counts"][0]
                      for i in range(model.cfg.num_hidden_layers)])
