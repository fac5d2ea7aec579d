"""ZAYA1 decoder LM: compressed convolutional attention (CCA) in an 8/2-head
latent, a top-1 expert layer behind an MLP router that carries its state
from layer to layer, a tied embedding whose head is computed in blocks —
and one chip's share of them.

``Zyphra/ZAYA1-8B`` ``config.json`` (``model_type: zaya``): every layer is
an attention sublayer and an expert sublayer (no dense MLP, no shared
expert, no biases on the projections).  :class:`ZayaConfig`'s fields carry
the source's key names.  The equations follow Zyphra's CCA paper
(arXiv:2510.04476) and the ZAYA1 report (arXiv:2511.17127); what the
config has no key for is fixed here and listed, with its reason, under
``assumed`` in ``benchmarks/configs/zaya1_8b.json``.  One layer, T
positions, ``rep`` = key/value head j copied to its group's query heads::

    a   = RMSNorm(x)
    q~  = a Wq [T, 8, 128]     k~ = a Wk [T, 2, 128]      into the latent
    m_q = (q~ + rep(k~)) / 2   m_k = mean of m_q over each group
    u   = [q~ ; k~], 10 heads of 128, along t, left-padded ONCE with
          (cca_time0 - 1) + (cca_time1 - 1) zeros
    c0  = conv 0: depthwise over t, kernel cca_time0, bias b0
    c   = conv 1: one group a head (128 -> 128), kernel cca_time1, bias b1
    q   = c_q + m_q            k = c_k + m_k
    v   = [a_t Wv1 ; a_(t-1) Wv2]      head 1 reads the token before
    q^  = sqrt(128) q / |q|    k^ = tau_h sqrt(128) k / |k|
    q^, k^ = rotary over the first partial_rotary_factor x 128 of a head
    o   = softmax(q^ k^T / sqrt(128), causal) v;  x = x + o Wo

    m   = RMSNorm(x)
    r_l = m Wd + bd (+ gamma_l r_(l-1) for l > 0)  [T, 256]: the router's
          state, handed to layer l + 1 beside x
    p   = softmax(W3 gelu(W2 gelu(W1 RMSNorm(r_l) + b1) + b2))   float32
    e*  = argmax(p + beta);  x = x + p_(e*) expert_(e*)(m)

so the layer loop carries TWO streams, ``(x, r)``, and ``remat`` wraps the
pair.  Position t reads positions t - 2 .. t of its own sequence before
the attention and nothing later (tests/test_zaya.py perturbs a token and
looks).  The weight of the one chosen expert is its probability, NOT
renormalised (1 would cut the router's gradient); ``beta`` chooses only
and is held at zero (the report trains it by a rule outside the gradient,
which has no key: left out, as is the report's learned residual scaling).
The embedding is tied: ``wte`` receives the gather's gradient and the
head's, and the head (:func:`zaya_loss`) goes through
``models/gpt.py`` :func:`blocked_token_nll`, which never holds the
``[tokens, vocabulary]`` logits.

One chip's share (``benchmarks/configs/zaya1_8b.json``): ``experts_held =
(first, count)`` are the experts whose stacks live here (``None``: all
``num_experts``, which stays the router's width), ``vocab_size`` the rows
of the table held; ``parallel/expert.py`` :func:`dropless_moe_mlp` is
handed the router's probabilities (``routing=``) and returns the held
experts' part: exactly zero for a token whose expert lives elsewhere.
There is no exchange on this path.

bf16 compute over float32 parameters; norms, the convolutions' depthwise
half, the L2 norms, the rotation, the router and every softmax in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.core import freeze
from jax import lax

from ..parallel.expert import dropless_moe_mlp, held_range
from .gpt import blocked_lm_loss
from .llama import AttnFn, RMSNorm, apply_rope, repeat_kv, rope_frequencies
from .mellum import banded_attention

__all__ = ["ZayaConfig", "Zaya", "zaya_tiny", "zaya_loss", "expert_counts"]

HYBRID = "hybrid"
_ROPE = {
    HYBRID: {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
             "rope_type": "default"},
    "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                       "rope_type": "default"},
    "rope_type": "default",
}


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    """Defaults are ZAYA1-8B as published (40 layers, every expert and the
    whole vocabulary held)."""

    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = (HYBRID,) * 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2                  # kernel of the depthwise convolution
    cca_time1: int = 2                  # kernel of the per-head convolution
    partial_rotary_factor: float = 0.5
    rope_parameters: Mapping[str, Any] = freeze(_ROPE)
    num_experts: int = 16               # the router's width
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048   # width of ONE expert
    router_hidden_size: int = 256
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
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
        if self.layer_types != (HYBRID,) * self.num_hidden_layers:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} "
                f"{HYBRID!r} layers (no layer of the source is anything "
                f"else), got {self.layer_types}")
        rope = self.rope_parameters.get(HYBRID)
        if rope is None or rope.get("rope_type") != "default":
            raise ValueError(f"rope_parameters[{HYBRID!r}] must be a "
                             f"'default' section, got {rope}")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of "
                f"head_dim {self.head_dim} is no even rotary width")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be divisible by "
                             "num_key_value_heads")
        if self.num_key_value_heads != 2:
            raise ValueError("the value shift gives key/value head 0 this "
                             "token and head 1 the one before: exactly 2 "
                             "key/value heads")
        if min(self.cca_time0, self.cca_time1) < 1:
            raise ValueError("cca_time0 / cca_time1 are kernel sizes >= 1")
        if not 1 <= self.num_experts_per_tok <= self.num_experts:
            raise ValueError("num_experts_per_tok must lie in "
                             "[1, num_experts]")
        if not self.tie_word_embeddings:
            raise ValueError("the head is the embedding's table "
                             "(tie_word_embeddings)")

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts whose stacks live here."""
        return held_range(self.experts_held, self.num_experts)

    @property
    def rotary_dim(self) -> int:
        """Channels of a head the rotation turns (the first ones)."""
        return int(self.rope_parameters[HYBRID].get(
            "partial_rotary_factor", self.partial_rotary_factor)
            * self.head_dim)


def zaya_tiny(experts_held: Optional[Tuple[int, int]] = None,
              **overrides) -> ZayaConfig:
    """CPU tests: float32 end to end, 3 layers (the router's state crosses
    two joints), 4/2 heads of 16, 8 experts top-1, router width 8."""
    rope = {HYBRID: {"partial_rotary_factor": 0.5, "rope_theta": 10000.0,
                     "rope_type": "default"}}
    return ZayaConfig(**{**dict(
        vocab_size=128, hidden_size=32, num_hidden_layers=3,
        layer_types=(HYBRID,) * 3, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, rope_parameters=rope,
        num_experts=8, num_experts_per_tok=1, moe_intermediate_size=16,
        router_hidden_size=8, experts_held=experts_held,
        max_position_embeddings=64, dtype=jnp.float32), **overrides})


def causal_convs(u, w0, b0, w1, b1, dtype):
    """CCA's two causal convolutions along t over the packed latent ``u``
    [B, T, C, D] float32 (C heads of D channels) -> [B, T, C, D] float32,
    written as the shift-and-multiply they are.  Conv 0 is depthwise
    (``w0`` [C, D, k0], ``b0`` [C, D]); conv 1 mixes the D channels of
    each head (``w1`` [C, k1, D, D], ``b1`` [C, D]: one group a head), its
    matmuls in ``dtype``, operands and result (the taps are summed in
    float32).  ``u`` is left-padded
    ONCE, with ``(k0 - 1) + (k1 - 1)`` zeros, before both: conv 1 sees
    conv 0's output at the padded positions (``b0`` at k0 = 2), not zeros.
    Position t reads ``u[t - (k0 - 1) - (k1 - 1) .. t]``."""
    t = u.shape[1]
    k0, k1 = w0.shape[-1], w1.shape[1]
    up = jnp.pad(u, ((0, 0), (k0 + k1 - 2, 0), (0, 0), (0, 0)))
    n0 = t + k1 - 1                     # conv 0 at positions -(k1-1) .. T-1
    c0 = b0 + sum(w0[..., j] * up[:, j:j + n0] for j in range(k0))
    c0 = c0.astype(dtype)
    return b1 + sum(
        jnp.einsum("btcd,cde->btce", c0[:, j:j + t], w1[:, j].astype(dtype)
                   ).astype(jnp.float32) for j in range(k1))


def qk_mean(q, k, groups: int):
    """CCA's q-k mean over latents q [B, T, H, D] and k [B, T, Hkv, D]:
    ``m_q`` = each query head averaged with its group's key head, ``m_k``
    = ``m_q`` averaged over each group's query heads."""
    b, t, kv_heads, d = k.shape
    m_q = 0.5 * (q + jnp.repeat(k, groups, axis=2))
    return m_q, jnp.mean(m_q.reshape(b, t, kv_heads, groups, d), axis=3)


def token_before(x):
    """x[t - 1] along axis 1, zeros at t = 0."""
    return jnp.pad(x, ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))[:, :-1]


def l2_normalize(x, eps: float = 1e-12):
    """x / max(|x|, eps) over the last axis (``F.normalize``)."""
    norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x / jnp.maximum(norm, eps)


class ZayaAttention(nn.Module):
    """Compressed convolutional attention (module docstring).  The flash
    call sits directly under this module's scope (``attn_cca``); all that
    lies between the projections and it under ``bps.cca.mix``."""

    cfg: ZayaConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, a, positions):
        cfg = self.cfg
        heads, kv_heads, hd = (cfg.num_attention_heads,
                               cfg.num_key_value_heads, cfg.head_dim)
        groups, packed = heads // kv_heads, heads + kv_heads

        def proj(name, shape):
            return nn.DenseGeneral(shape, use_bias=False, dtype=cfg.dtype,
                                   name=name)

        q_lat = proj("q_proj", (heads, hd))(a)            # [B, T, 8, 128]
        k_lat = proj("k_proj", (kv_heads, hd))(a)         # [B, T, 2, 128]
        v_now = proj("v_proj1", hd)(a)                    # [B, T, 128]
        v_before = proj("v_proj2", hd)(a)
        taps = nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                                in_axis=-1, out_axis=())
        mixer = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=(1, 2), out_axis=3,
            batch_axis=(0,))
        w0 = self.param("conv0_kernel", taps, (packed, hd, cfg.cca_time0),
                        jnp.float32)
        b0 = self.param("conv0_bias", nn.initializers.zeros, (packed, hd),
                        jnp.float32)
        w1 = self.param("conv1_kernel", mixer,
                        (packed, cfg.cca_time1, hd, hd), jnp.float32)
        b1 = self.param("conv1_bias", nn.initializers.zeros, (packed, hd),
                        jnp.float32)
        tau = self.param("k_temperature", nn.initializers.ones, (kv_heads,),
                         jnp.float32)
        with jax.named_scope("bps.cca.mix"):
            qf, kf = q_lat.astype(jnp.float32), k_lat.astype(jnp.float32)
            m_q, m_k = qk_mean(qf, kf, groups)
            c = causal_convs(jnp.concatenate([qf, kf], axis=2), w0, b0, w1,
                             b1, cfg.dtype)
            q = math.sqrt(hd) * l2_normalize(c[:, :, :heads] + m_q)
            k = (math.sqrt(hd) * tau[:, None]
                 * l2_normalize(c[:, :, heads:] + m_k))
            rope = cfg.rope_parameters[HYBRID]
            cos, sin = rope_frequencies(cfg.rotary_dim, positions,
                                        float(rope["rope_theta"]))
            q = apply_rope(q, cos, sin, cfg.rotary_dim).astype(cfg.dtype)
            k = apply_rope(k, cos, sin, cfg.rotary_dim).astype(cfg.dtype)
            # the value shift: head 1 is the token before's
            v = jnp.stack([v_now, token_before(v_before)], axis=2)
            k, v = repeat_kv(k, v, groups)
        attn = self.attn_fn or banded_attention
        ctx = attn(q, k, v, causal=True, sm_scale=1.0 / math.sqrt(hd))
        return nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1),
                               use_bias=False, dtype=cfg.dtype,
                               name="o_proj")(ctx)


class ZayaRouter(nn.Module):
    """The router of one layer: ``m`` [B, T, h] float32 and the state of
    the layer before (``None`` in layer 0, which has no ``gamma``) ->
    (probabilities [B, T, E] float32, this layer's state [B, T, 256]).
    Float32 at full matmul precision throughout: a top-1 choice has no
    second expert to soften a flipped one."""

    cfg: ZayaConfig

    @nn.compact
    def __call__(self, m, r_before):
        cfg = self.cfg
        width = cfg.router_hidden_size

        def dense(name, n, use_bias=True):
            return nn.Dense(n, use_bias=use_bias, dtype=jnp.float32,
                            precision=lax.Precision.HIGHEST, name=name)

        with jax.named_scope("bps.zaya.router"):
            r = dense("down", width)(m)
            if r_before is not None:
                gamma = self.param("gamma", nn.initializers.ones, (),
                                   jnp.float32)
                r = r + gamma * r_before
            hdn = RMSNorm(cfg.rms_norm_eps, jnp.float32, name="norm")(r)
            hdn = jax.nn.gelu(dense("fc1", width)(hdn), approximate=False)
            hdn = jax.nn.gelu(dense("fc2", width)(hdn), approximate=False)
            scores = dense("out", cfg.num_experts, use_bias=False)(hdn)
            return jax.nn.softmax(scores, axis=-1), r


class ZayaSparseMoe(nn.Module):
    """The expert sublayer: the router over all ``num_experts``, the stacks
    of the experts held here.  Sows the per-expert pair counts (all
    experts) into ``moe_stats`` (``counts``): apply with the collection
    ``mutable`` (:func:`expert_counts`); a plain ``apply`` sows nothing."""

    cfg: ZayaConfig

    @nn.compact
    def __call__(self, m, r_before):
        cfg = self.cfg
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        g = cfg.held[1]
        probs, r = ZayaRouter(cfg, name="router")(m, r_before)
        # chooses only; held at zero (module docstring)
        beta = self.param("balance_bias", nn.initializers.zeros,
                          (cfg.num_experts,), jnp.float32)
        stack = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                             batch_axis=(0,))
        params = {"gate": self.param("gate", stack, (g, h, f), jnp.float32),
                  "up": self.param("up", stack, (g, h, f), jnp.float32),
                  "down": self.param("down", stack, (g, f, h), jnp.float32)}
        b, t, _ = m.shape
        y, _, _, counts = dropless_moe_mlp(
            m.reshape(b * t, h).astype(cfg.dtype), params,
            cfg.num_experts_per_tok, held=cfg.experts_held,
            routing=(probs.reshape(b * t, cfg.num_experts), beta))
        if not self.is_initializing():   # init returns parameters only
            self.sow("moe_stats", "counts", counts)
        return y.reshape(b, t, h), r


class ZayaBlock(nn.Module):
    """One layer: ``(x, r_before) -> (x, r)``."""

    cfg: ZayaConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x, r_before, positions):
        cfg = self.cfg
        a = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="attn_norm")(x)
        x = x + ZayaAttention(cfg, self.attn_fn, name="attn_cca")(
            a, positions)
        m = RMSNorm(cfg.rms_norm_eps, jnp.float32, name="moe_norm")(x)
        y, r = ZayaSparseMoe(cfg, name="moe")(m, r_before)
        return x + y, r


class Zaya(nn.Module):
    """Decoder-only ZAYA: ``wte`` -> blocks carrying ``(x, r)`` -> RMSNorm.
    Returns the final hidden rows [B, T, h] — the head is the tied table
    and at this vocabulary its logits are computed in blocks by the loss
    (:func:`zaya_loss`) — or, with ``logits=True``, the float32 logits
    [B, T, vocab_size] whole (tests, a few short sequences)."""

    cfg: ZayaConfig
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
        x, r = wte(input_ids), None
        block = nn.remat(ZayaBlock) if cfg.remat else ZayaBlock
        for i in range(cfg.num_hidden_layers):
            x, r = block(cfg, self.attn_fn, name=f"h{i}")(x, r, positions)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm_f")(x)
        if not logits:
            return x
        return lax.dot_general(
            x, wte.embedding.astype(cfg.dtype), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def zaya_loss(model: Zaya, params, batch):
    """Next-token cross-entropy over the rows of the table held, through
    the blocked head; no auxiliary or z-loss (the family balances by the
    selection bias).  ``batch``: ``input_ids`` [B, T] and ``labels``
    (already shifted; -1 = ignored)."""
    x = model.apply(params, batch["input_ids"])
    b, t, h = x.shape
    return blocked_lm_loss(x.reshape(b * t, h),
                           params["params"]["wte"]["embedding"],
                           batch["labels"].reshape(b * t))


def expert_counts(model: Zaya, params, input_ids):
    """Token–expert pairs (top-1: tokens) each of the ``num_experts``
    experts received, [layers, experts] int32: what
    ``parallel.expert.publish_moe_stats`` takes (with
    ``held=model.cfg.experts_held``)."""
    _, sown = model.apply(params, input_ids, mutable=["moe_stats"])
    return jnp.stack([sown["moe_stats"][f"h{i}"]["moe"]["counts"][0]
                      for i in range(model.cfg.num_hidden_layers)])
