"""Nemotron 3 Super decoder LM (``model_type: nemotron_h``): a hybrid whose
every block is ONE mixer behind one pre-norm, its kind a letter of
``hybrid_override_pattern`` — ``M`` a Mamba-2 state-space mixer, ``E`` a
LatentMoE, ``*`` grouped-query attention — with a depth-1 multi-token-
prediction module on the model's own table and head, and one chip's share
of heads, groups, experts and vocabulary.

``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16`` ``config.json``;
:class:`NemotronHConfig`'s fields carry the source's key names.  What the
config has no key for is fixed here and listed, with its reason, under
``assumed`` in ``benchmarks/configs/nemotron3_super.json``.  T positions,
``x = x + mixer(RMSNorm(x))``::

    M   u = RMSNorm(x);  [ z | xBC | dt ] = u W_in      z: heads x head_dim
        xBC = silu(conv(xBC))       depthwise causal, conv_kernel taps,
                                    zeros on the left, a bias
        [ xs [T, heads, head_dim] | B [T, groups, state] | C [T, groups, state] ] = xBC
        dt = softplus(dt + dt_bias) [T, heads];  A = -exp(A_log) [heads]
        S_t = exp(dt_t A) S_(t-1) + dt_t B_t^T xs_t   (per head, S [state,
              head_dim], S_0 = 0; head h reads group h // (heads / groups))
        y_t = C_t S_t + D xs_t
        y = RMSNorm_group(y * silu(z))    over each GROUP's channels
        x = x + y W_out
    *   a = RMSNorm(x);  q = a W_q [T, heads, head_dim];  k, v = a W_k, a W_v
        [T, kv heads, head_dim] (query head h reads key/value head
        h // (heads / kv heads));  NO rotation
        o = softmax(q k^T / sqrt(head_dim), j <= i) v;   x = x + o W_o
    E   m = RMSNorm(x);  s = sigmoid(m_f32 W_r) [T, n_routed_experts]
        S = top-k(s + b);  w_e = f * s_e / (sum_S s + 1e-20)
        l = m W_dn [T, moe_latent_size]
        r = sum_{e in S} w_e W2_e relu(W1_e l)^2        no gate, no bias
        x = x + r W_up + V2 relu(V1 m)^2                the shared expert on m
    head   logits = RMSNorm(x_last) W_head^T
    MTP    h'_i = P [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_(i+1)))]
           (2 hidden -> hidden, h before the final norm)
           g = the blocks of ``mtp_hybrid_override_pattern`` on h'
           logits'_i = RMSNorm(g_i) W_head^T   predicts t_(i+2)

``f`` = ``routed_scaling_factor``; ``b`` chooses only, no gradient reaches
it, and it is HELD AT ITS INITIAL ZERO (the family balances it by a rule
outside the gradient that has no key: left out, as ``models/glm_lite.py``
and ``models/zaya.py`` leave theirs; no auxiliary or z-loss stands in).
The router and the shared expert read the full hidden ``m``; only the
routed experts live in the latent.  The gated norm multiplies by
``silu(z)`` BEFORE it normalises.  ``dt`` is not clamped after the
softplus (``time_step_min / max / floor`` are initialisation keys:
``softplus(dt_bias)`` is drawn log-uniform in [min, max] and floored,
``A_log = log U[1, 16]``, ``D = 1``).  ``rope_theta`` and
``partial_rotary_factor`` are carried and unused: the family's attention
has no positional embedding.  ``rescale_prenorm_residual`` is an
initialisation rule: every block's output projection (``W_out``, ``W_o``,
``W_up`` and the shared expert's ``V2``) starts 1/sqrt(2 x the PUBLISHED
depth) of its fan-in draw.  The recurrence runs as
``ops/ssd_scan.py`` ``ssd_scan`` (Mosaic kernels, forward and backward,
interpreted off the TPU) in chunks of ``chunk_size``.

One chip's share (none given: everything).  ``mamba_heads_held`` of the
``mamba_num_heads`` Mamba heads with ``groups_held`` of the ``n_groups``
B/C groups — whole groups only: a group's heads share its B and C, and the
gated norm is per group, so a chip's part of the mixer is exact and
``W_out`` gives a partial sum; ``heads_held`` query heads on
``kv_heads_held`` key/value heads, an equal run of each held key/value
head's own group (a key/value head may serve several chips: its
projection is then computed alike on each; ``W_o`` gives a partial sum); ``experts_held = (first, count)`` of the
``n_routed_experts`` the router scores (the routed sum partial; ``W_up``
is linear, so the shares' ``r W_up`` add up as the ``r`` do);
``vocab_size`` the rows of table and head held.  Router, both latent
projections and the shared expert are whole on every chip.  There is no
exchange on this path: every mixer's output, the routed sum and both
losses are this chip's partial results.

:func:`nemotron_loss` = the main head's mean cross-entropy +
``mtp_loss_weight`` x the module's, both through ``models/gpt.py``
:func:`blocked_lm_loss`.  bf16 compute over float32 parameters; norms,
``dt``, ``A``, the decays, the state, the router and every softmax in
float32.
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
from .glm_lite import join_experts, mtp_labels, next_tokens, router_scores
from .gpt import blocked_lm_loss
from .llama import AttnFn, RMSNorm
from .mellum import banded_attention

__all__ = ["NemotronHConfig", "NemotronH", "nemotron_h_tiny",
           "nemotron_loss", "expert_counts"]

PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Defaults are Nemotron 3 Super as published (88 blocks, every head,
    group and expert and the whole vocabulary held)."""

    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    num_nextn_predict_layers: int = 1
    mtp_hybrid_override_pattern: str = "*E"
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_hidden_act: str = "silu"
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    sliding_window: Any = None
    rope_theta: float = 10000.0          # carried, unused: no rotation
    partial_rotary_factor: float = 1.0   # carried, unused
    n_routed_experts: int = 512          # the router's width
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688    # width of ONE routed expert
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    moe_shared_expert_overlap: bool = False
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    use_bias: bool = False
    layer_norm_epsilon: float = 1e-5
    rescale_prenorm_residual: bool = True
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 262144
    num_hidden_layers_published: Optional[int] = None   # the init rule's
    mamba_heads_held: Optional[int] = None
    groups_held: Optional[int] = None
    heads_held: Optional[int] = None
    kv_heads_held: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None      # (first, count)
    mtp_loss_weight: float = 0.3
    dtype: Any = jnp.bfloat16
    remat: bool = False

    def __post_init__(self):
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held", held_range(
                self.experts_held, self.n_routed_experts))
        pattern = self.hybrid_override_pattern
        if (len(pattern) != self.num_hidden_layers
                or set(pattern + self.mtp_hybrid_override_pattern)
                - set("ME*")):
            raise ValueError(
                f"hybrid_override_pattern={pattern!r}: one letter of 'M', "
                f"'E', '*' for each of the {self.num_hidden_layers} blocks "
                f"(a '-' MLP block is not computed here)")
        if (self.n_group, self.topk_group) != (1, 1):
            raise ValueError(
                f"n_group={self.n_group} / topk_group={self.topk_group}: "
                f"the choice is over ONE group of all the experts")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                f"num_nextn_predict_layers={self.num_nextn_predict_layers}:"
                f" at most one multi-token-prediction module")
        if self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings must be false: the head "
                             "is a matrix of its own")
        if self.sliding_window is not None:
            raise ValueError(f"sliding_window={self.sliding_window!r}: the "
                             f"attention blocks are full causal attention")
        if self.moe_shared_expert_overlap:
            raise ValueError("moe_shared_expert_overlap must be false: the "
                             "shared expert is added to the routed sum, not "
                             "scheduled beside an exchange")
        if (self.attention_bias or self.mlp_bias or self.use_bias
                or self.mamba_proj_bias or not self.use_conv_bias):
            raise ValueError("attention_bias, mlp_bias, use_bias and "
                             "mamba_proj_bias must be false and "
                             "use_conv_bias true: no bias but the "
                             "convolution's")
        if (self.mlp_hidden_act, self.mamba_hidden_act) != ("relu2", "silu"):
            raise ValueError(
                f"mlp_hidden_act={self.mlp_hidden_act!r} / mamba_hidden_act="
                f"{self.mamba_hidden_act!r}: the experts are relu2, the "
                f"mixer's convolution and gate silu")
        if self.n_shared_experts != 1:
            raise ValueError("n_shared_experts must be 1: one shared expert "
                             "of moe_shared_expert_intermediate_size")
        if self.mamba_num_heads * self.mamba_head_dim != (
                self.expand * self.hidden_size):
            raise ValueError(
                f"mamba_num_heads x mamba_head_dim ({self.mamba_num_heads} x "
                f"{self.mamba_head_dim}) != expand x hidden_size "
                f"({self.expand} x {self.hidden_size})")
        if not 1 <= self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError("num_experts_per_tok must lie in "
                             "[1, n_routed_experts]")
        for heads, groups, names in (
                (self.mamba_num_heads, self.n_groups,
                 "mamba_num_heads / n_groups"),
                (self.num_attention_heads, self.num_key_value_heads,
                 "num_attention_heads / num_key_value_heads")):
            if heads % groups:
                raise ValueError(f"{names}: {heads} heads do not divide "
                                 f"into {groups} groups")
        per_group = self.mamba_num_heads // self.n_groups
        if not (1 <= self.ssm_groups <= self.n_groups
                and self.ssm_heads == self.ssm_groups * per_group):
            raise ValueError(
                f"mamba_heads_held / groups_held = {self.ssm_heads} / "
                f"{self.ssm_groups}: a share is whole groups of {per_group} "
                f"heads (a group's heads share its B and C, and the gated "
                f"norm is over the group); held heads that split a group "
                f"are not computed")
        per_kv = self.num_attention_heads // self.num_key_value_heads
        each = self.attn_heads // max(self.attn_kv_heads, 1)
        if not (1 <= self.attn_kv_heads <= self.num_key_value_heads
                and self.attn_heads == each * self.attn_kv_heads
                and 1 <= each <= per_kv):
            raise ValueError(
                f"heads_held / kv_heads_held = {self.attn_heads} / "
                f"{self.attn_kv_heads}: each held key/value head serves an "
                f"equal run of at most {per_kv} query heads of its own "
                f"group; held query heads that straddle key/value groups "
                f"are not computed")

    @property
    def ssm_heads(self) -> int:
        return (self.mamba_num_heads if self.mamba_heads_held is None
                else self.mamba_heads_held)

    @property
    def ssm_groups(self) -> int:
        return self.n_groups if self.groups_held is None else self.groups_held

    @property
    def attn_heads(self) -> int:
        return (self.num_attention_heads if self.heads_held is None
                else self.heads_held)

    @property
    def attn_kv_heads(self) -> int:
        return (self.num_key_value_heads if self.kv_heads_held is None
                else self.kv_heads_held)

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the routed experts whose stacks live here."""
        return held_range(self.experts_held, self.n_routed_experts)

    @property
    def out_scale(self) -> float:
        """``rescale_prenorm_residual``: what an output projection's draw
        is multiplied by."""
        depth = self.num_hidden_layers_published or self.num_hidden_layers
        return (1.0 / math.sqrt(2.0 * depth)
                if self.rescale_prenorm_residual else 1.0)


def nemotron_h_tiny(**overrides) -> NemotronHConfig:
    """CPU tests: float32 end to end, all three kinds of block and the
    module, 4 Mamba heads of 8 in 2 groups, state 16, chunks of 8;
    4 query heads on 2 key/value heads; 8 experts top-3 in a latent of 16."""
    return NemotronHConfig(**{**dict(
        vocab_size=128, hidden_size=32, num_hidden_layers=4,
        hybrid_override_pattern="ME*E", mamba_num_heads=8, mamba_head_dim=8,
        n_groups=2, ssm_state_size=16, chunk_size=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, n_routed_experts=8,
        num_experts_per_tok=3, moe_latent_size=16, moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=40, max_position_embeddings=64,
        dtype=jnp.float32), **overrides})


def _dense(features, name, dtype, scale: float = 1.0, axis=-1):
    """Bias-free projection; ``scale`` x flax's default fan-in draw
    (``lecun_normal`` is this initializer at ``scale`` 1)."""
    return nn.DenseGeneral(
        features, axis=axis, use_bias=False, dtype=dtype, name=name,
        kernel_init=nn.initializers.variance_scaling(
            scale * scale, "fan_in", "truncated_normal"))


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def causal_conv(x, kernel, bias):
    """Depthwise causal convolution over positions: x [B, T, C] float32,
    kernel [K, C], bias [C]; ``K - 1`` zeros on the left, so position t
    reads t - K + 1 .. t."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(kernel[k] * padded[:, k:k + t] for k in range(taps))


def group_rms_norm(x, scale, groups: int, eps: float):
    """RMSNorm over each of ``groups`` equal runs of the last axis."""
    shape = x.shape
    x = x.reshape(*shape[:-1], groups, shape[-1] // groups)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x.reshape(shape) * scale


def _dt_bias_init(cfg: NemotronHConfig):
    def init(key, shape, dtype=jnp.float32):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, dtype, lo, hi)), cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class NemotronMamba(nn.Module):
    """The Mamba-2 mixer on the normed rows ``u`` [B, T, h] (module
    docstring); each stage under a ``bps.ssm.*`` scope, the scan's kernels
    under ``bps.ssm.scan``."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        from ..ops.ssd_scan import ssd_scan
        cfg = self.cfg
        heads, groups, p, n = (cfg.ssm_heads, cfg.ssm_groups,
                               cfg.mamba_head_dim, cfg.ssm_state_size)
        inner, bc = heads * p, groups * n
        b, t, _ = u.shape
        with jax.named_scope("bps.ssm.in_proj"):
            proj = _dense(2 * inner + 2 * bc + heads, "in_proj", cfg.dtype)(u)
            z = proj[..., :inner]
            xbc = proj[..., inner:2 * inner + 2 * bc]
            dt = proj[..., 2 * inner + 2 * bc:]
        with jax.named_scope("bps.ssm.conv"):
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(
                in_axis=0, out_axis=1), (cfg.conv_kernel, inner + 2 * bc),
                jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (inner + 2 * bc,), jnp.float32)
            xbc = jax.nn.silu(causal_conv(xbc.astype(jnp.float32), kernel,
                                          bias)).astype(cfg.dtype)
        xs = xbc[..., :inner].reshape(b, t, heads, p)
        b_in = xbc[..., inner:inner + bc].reshape(b, t, groups, n)
        c_in = xbc[..., inner + bc:].reshape(b, t, groups, n)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (heads,),
                             jnp.float32)
        a_log = self.param("A_log", _a_log_init, (heads,), jnp.float32)
        d_skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        with jax.named_scope("bps.ssm.scan"):
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            y = ssd_scan(xs, dt, -jnp.exp(a_log), b_in, c_in, d_skip,
                         chunk=math.gcd(t, cfg.chunk_size))
        with jax.named_scope("bps.ssm.gate_norm"):
            scale = self.param("norm_scale", nn.initializers.ones, (inner,),
                               jnp.float32)
            gated = (y.reshape(b, t, inner).astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32)))
            y = group_rms_norm(gated, scale, groups, cfg.layer_norm_epsilon
                               ).astype(cfg.dtype)
        with jax.named_scope("bps.ssm.out_proj"):
            return _dense(cfg.hidden_size, "out_proj", cfg.dtype,
                          cfg.out_scale)(y)


class NemotronAttention(nn.Module):
    """Grouped-query causal attention without a rotation; the flash call
    sits directly under this module's scope (``attn``), k and v repeated
    to the query heads before it."""

    cfg: NemotronHConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, a):
        cfg = self.cfg
        heads, kv, d = cfg.attn_heads, cfg.attn_kv_heads, cfg.head_dim
        q = _dense((heads, d), "q_proj", cfg.dtype)(a)
        k = _dense((kv, d), "k_proj", cfg.dtype)(a)
        v = _dense((kv, d), "v_proj", cfg.dtype)(a)
        k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
        attn = self.attn_fn or banded_attention
        ctx = attn(q, k, v, causal=True, sm_scale=1.0 / math.sqrt(d))
        return _dense(cfg.hidden_size, "o_proj", cfg.dtype, cfg.out_scale,
                      axis=(-2, -1))(ctx)


class NemotronMoe(nn.Module):
    """The LatentMoE on the float32 normed rows ``m`` [B, T, h]: the router
    over all ``n_routed_experts``, the latent projections whole, the
    two-matrix stacks of the routed experts held here, the shared expert
    whole.  Sows the per-expert pair counts into ``moe_stats``
    (:func:`expert_counts`)."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, m):
        cfg = self.cfg
        h, lat, f, e = (cfg.hidden_size, cfg.moe_latent_size,
                        cfg.moe_intermediate_size, cfg.n_routed_experts)
        g = cfg.held[1]
        b, t, _ = m.shape
        gauges.set("moe.latent_dim", float(lat))
        router = self.param("router", nn.initializers.lecun_normal(), (h, e),
                            jnp.float32)
        # chooses only; held at zero (module docstring)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (e,), jnp.float32)
        stack = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                             batch_axis=(0,))
        params = {"up": self.param("up", stack, (g, lat, f), jnp.float32),
                  "down": self.param("down", stack, (g, f, lat), jnp.float32)}
        rows = m.reshape(b * t, h)
        with jax.named_scope("bps.moe.score"):
            scores = router_scores(rows, router)
        rows = rows.astype(cfg.dtype)
        with jax.named_scope("bps.moe.latent_down"):
            latent = _dense(lat, "fc1_latent_proj", cfg.dtype)(rows)
        routed, _, _, counts = dropless_moe_mlp(
            latent, params, cfg.num_experts_per_tok, held=cfg.experts_held,
            renormalize=cfg.norm_topk_prob, routing=(scores, bias))
        if not self.is_initializing():   # init returns parameters only
            self.sow("moe_stats", "counts", counts)
        with jax.named_scope("bps.moe.latent_up"):
            routed = (cfg.routed_scaling_factor * routed.astype(jnp.float32)
                      ).astype(cfg.dtype)
            routed = _dense(h, "fc2_latent_proj", cfg.dtype,
                            cfg.out_scale)(routed)
        with jax.named_scope("bps.moe.shared"):
            width = cfg.moe_shared_expert_intermediate_size
            shared = _dense(h, "shared_down_proj", cfg.dtype, cfg.out_scale)(
                relu2(_dense(width, "shared_up_proj", cfg.dtype)(rows)))
            y = join_experts(routed, shared, 1.0, cfg.dtype)
        return y.reshape(b, t, h)


MIXER_SCOPE = {"M": "mixer_ssm", "*": "attn", "E": "moe"}


class NemotronBlock(nn.Module):
    """``x + mixer(RMSNorm(x))``; ``kind`` is the block's letter."""

    cfg: NemotronHConfig
    kind: str
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        name = MIXER_SCOPE[self.kind]
        # the experts' router reads float32 rows
        u = RMSNorm(cfg.layer_norm_epsilon,
                    jnp.float32 if self.kind == "E" else cfg.dtype,
                    name="norm")(x)
        if self.kind == "M":
            y = NemotronMamba(cfg, name=name)(u)
        elif self.kind == "*":
            y = NemotronAttention(cfg, self.attn_fn, name=name)(u)
        else:
            y = NemotronMoe(cfg, name=name)(u)
        return x + y


def _blocks(cfg, pattern, attn_fn, x, prefix):
    block = nn.remat(NemotronBlock) if cfg.remat else NemotronBlock
    for i, kind in enumerate(pattern):
        x = block(cfg, kind, attn_fn, name=f"{prefix}{i}")(x)
    return x


class NemotronMtp(nn.Module):
    """The multi-token-prediction module: the last block's output ``h``
    and the embedding of the NEXT token -> the rows its head reads."""

    cfg: NemotronHConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, h, emb_next):
        cfg = self.cfg
        eps = cfg.layer_norm_epsilon
        joined = jnp.concatenate(
            [RMSNorm(eps, cfg.dtype, name="hnorm")(h),
             RMSNorm(eps, cfg.dtype, name="enorm")(emb_next)], axis=-1)
        x = _dense(cfg.hidden_size, "eh_proj", cfg.dtype)(joined)
        x = _blocks(cfg, cfg.mtp_hybrid_override_pattern, self.attn_fn, x,
                    "b")
        return RMSNorm(eps, cfg.dtype, name="norm")(x)


class NemotronH(nn.Module):
    """``wte`` -> the blocks of the pattern -> RMSNorm, and the module
    beside the last block.  Returns ``(x, g)``, the rows [B, T, h] the main
    head and the module's head read (``g`` is ``None`` without a module):
    both heads are ``lm_head`` [V, h] and their logits are computed in
    blocks by the loss (:func:`nemotron_loss`) — or, with ``logits=True``,
    both heads' float32 logits [B, T, vocab_size] whole (tests)."""

    cfg: NemotronHConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, input_ids, *, logits: bool = False):
        cfg = self.cfg
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       name="wte")
        head = self.param("lm_head", nn.initializers.lecun_normal(
            in_axis=-1, out_axis=-2), (cfg.vocab_size, cfg.hidden_size),
            jnp.float32)
        x = _blocks(cfg, cfg.hybrid_override_pattern, self.attn_fn,
                    wte(input_ids), "h")
        g = None
        if cfg.num_nextn_predict_layers:
            g = NemotronMtp(cfg, self.attn_fn, name="mtp")(
                x, wte(next_tokens(input_ids)))
        x = RMSNorm(cfg.layer_norm_epsilon, cfg.dtype, name="norm_f")(x)
        if not logits:
            return x, g

        def apply_head(rows):
            return None if rows is None else jax.lax.dot_general(
                rows, head.astype(cfg.dtype), (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        return apply_head(x), apply_head(g)


def nemotron_loss(model: NemotronH, params, batch):
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


def moe_blocks(cfg: NemotronHConfig):
    """Paths of the ``E`` blocks in the parameter tree, in order: the
    model's, then the module's."""
    paths = [(f"h{i}",) for i, kind in enumerate(cfg.hybrid_override_pattern)
             if kind == "E"]
    if cfg.num_nextn_predict_layers:
        paths += [("mtp", f"b{i}") for i, kind in
                  enumerate(cfg.mtp_hybrid_override_pattern) if kind == "E"]
    return paths


def expert_counts(model: NemotronH, params, input_ids):
    """Token-expert pairs each of the ``n_routed_experts`` experts
    received, [E blocks, experts] int32 (the module's last): what
    ``parallel.expert.publish_moe_stats`` takes (with
    ``held=model.cfg.experts_held``)."""
    _, sown = model.apply(params, input_ids, mutable=["moe_stats"])
    rows = []
    for path in moe_blocks(model.cfg):
        node = sown["moe_stats"]
        for key in path:
            node = node[key]
        rows.append(node["moe"]["counts"][0])
    return jnp.stack(rows)
