"""Causal transformer LM with pluggable attention — the long-context
flagship.

The reference has no sequence dimension anywhere (SURVEY.md §5
"Long-context: absent"); this model exists to exercise the framework's
sequence-parallel attention (parallel/sequence.py) end to end: the
attention callable is injected, so the same parameters run with exact
full attention on one device or ring/Ulysses attention over an sp mesh
axis — outputs match to float tolerance (tests/test_long_context.py).

TPU-first: bf16 compute / f32 params, MXU-aligned dims, static shapes,
optional per-layer remat.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

AttnFn = Callable  # (q, k, v, *, causal, sm_scale) -> out


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 32768
    hidden_size: int = 512
    num_layers: int = 8
    num_heads: int = 8
    intermediate_size: int = 2048
    max_position: int = 32768        # long-context by default
    dtype: Any = jnp.bfloat16
    remat: bool = False
    # Mixture-of-experts (switch) MLPs: 0 = dense everywhere; >0 turns
    # every ``moe_every``-th block's MLP into a switch layer with that
    # many experts (parallel/switch_moe.py moe_mlp; ep-shardable)
    moe_experts: int = 0
    moe_every: int = 2
    moe_capacity: float = 1.25

    def __post_init__(self):
        if self.moe_experts > 0 and self.moe_every < 1:
            raise ValueError(
                "moe_every must be >= 1 when moe_experts > 0 (a value "
                "of 0 would silently produce a fully dense model)")


def gpt_small() -> GPTConfig:
    return GPTConfig()


def gpt_tiny() -> GPTConfig:
    """CPU-mesh tests / multichip dry-runs."""
    return GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, intermediate_size=128, max_position=512)


class CausalSelfAttention(nn.Module):
    cfg: GPTConfig
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        head_dim = cfg.hidden_size // cfg.num_heads
        qkv = nn.DenseGeneral((3, cfg.num_heads, head_dim), dtype=cfg.dtype,
                              name="qkv")(x)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        attn = self.attn_fn
        if attn is None:
            # lazy: parallel/__init__ imports models.gpt (long_context),
            # so a top-level import back into parallel would be circular
            from ..parallel.sequence import full_attention as attn
        ctx = attn(q, k, v, causal=True,
                   sm_scale=1.0 / math.sqrt(head_dim))
        return nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1),
                               dtype=cfg.dtype, name="out")(ctx)


class MoEMLP(nn.Module):
    """Switch-MoE MLP block: parameters are the FULL expert stacks at
    init; under an ep mesh each device's slice flows through apply (flax
    only checks shapes at init, the same trick pipeline.py uses for
    stage-local layer slices).  The aux load-balance loss is sown into
    the ``moe_aux`` collection — train steps apply with
    ``mutable=["moe_aux"]`` and fold the sown values into the loss."""

    cfg: GPTConfig
    ep_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        from jax import lax as _lax
        from ..parallel.switch_moe import moe_mlp
        cfg = self.cfg
        h, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.moe_experts
        # declared expert-stack size: the LOCAL shard when running under
        # an ep axis (flax validates self.param shapes at apply; sharded
        # leaves carry e/ep experts), the full stack otherwise (init and
        # single-device reference both use ep_axis=None)
        e_decl = e if self.ep_axis is None \
            else e // _lax.axis_size(self.ep_axis)
        params = {
            "router": self.param("router", nn.initializers.lecun_normal(),
                                 (h, e), jnp.float32),
            "w1": self.param("w1", nn.initializers.lecun_normal(),
                             (e_decl, h, f), jnp.float32),
            "b1": self.param("b1", nn.initializers.zeros, (e_decl, f),
                             jnp.float32),
            "w2": self.param("w2", nn.initializers.lecun_normal(),
                             (e_decl, f, h), jnp.float32),
            "b2": self.param("b2", nn.initializers.zeros, (e_decl, h),
                             jnp.float32),
        }
        # compute in cfg.dtype like the dense MLP path (params stay f32;
        # moe_mlp casts expert inputs to the weight dtype, so casting the
        # stacks here puts both big einsums on the bf16 MXU path)
        params = {k: (v if k == "router" else v.astype(cfg.dtype))
                  for k, v in params.items()}
        b, t, _ = x.shape
        out, aux = moe_mlp(x.reshape(b * t, h), params, e,
                           cfg.moe_capacity, axis_name=self.ep_axis)
        self.sow("moe_aux", "aux", aux)
        return out.reshape(b, t, h)


class Block(nn.Module):
    cfg: GPTConfig
    attn_fn: Optional[AttnFn] = None
    moe: bool = False
    ep_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln1")(x)
        x = x + CausalSelfAttention(cfg, self.attn_fn, name="attn")(h)
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln2")(x)
        if self.moe:
            return x + MoEMLP(cfg, self.ep_axis, name="moe")(h)
        h = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype, name="mlp_in")(h)
        h = jax.nn.gelu(h)
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="mlp_out")(h)
        return x + h


class GPT(nn.Module):
    """Decoder-only LM.  ``positions`` must be passed when the sequence
    axis is sharded (each shard holds positions [off, off + T/sp))."""

    cfg: GPTConfig
    attn_fn: Optional[AttnFn] = None

    ep_axis: Optional[str] = None

    @nn.compact
    def __call__(self, input_ids, positions=None):
        cfg = self.cfg
        b, t = input_ids.shape
        if positions is None:
            positions = jnp.arange(t)[None]
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="wte")(input_ids)
        x = x + nn.Embed(cfg.max_position, cfg.hidden_size, dtype=cfg.dtype,
                         name="wpe")(positions)
        block = Block
        if cfg.remat:
            block = nn.remat(Block)
        for i in range(cfg.num_layers):
            moe = (cfg.moe_experts > 0
                   and i % cfg.moe_every == cfg.moe_every - 1)
            x = block(cfg, self.attn_fn, moe=moe, ep_axis=self.ep_axis,
                      name=f"h{i}")(x)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype, name="lm_head")(x)
        return logits.astype(jnp.float32)


def token_nll(logits, labels, ignore: int = -1):
    """(sum of per-token NLL over valid positions, valid-token count).
    Shared by local (:func:`lm_loss`) and mesh-global (psum'd,
    parallel/long_context.py) normalizations."""
    valid = labels != ignore
    safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits)
    ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    w = valid.astype(jnp.float32)
    return -(ll * w).sum(), w.sum()


def lm_loss(logits, labels, ignore: int = -1):
    """Next-token cross-entropy; ``labels == ignore`` positions skipped.
    Callers shift: labels[t] is the target for logits[t]."""
    s, c = token_nll(logits, labels, ignore)
    return s / jnp.maximum(c, 1.0)


# ------------------------------------------- a head that never holds [N, V]

# float32 logits of one block of rows: the most the blocked head keeps of
# the [N, V] square at a time (1 GiB = 2048 rows of a 131 072-row table)
_LOGIT_BLOCK_BYTES = 2 ** 30


def logit_block_rows(n: int, vocab: int) -> int:
    """Rows of one block of :func:`blocked_token_nll`, from the shapes
    alone: the largest power-of-two divisor of ``n`` whose float32 logits
    [rows, vocab] stay within ``_LOGIT_BLOCK_BYTES`` (at least one row)."""
    rows = n & -n                       # largest power of two dividing n
    while rows > 1 and rows * vocab * 4 > _LOGIT_BLOCK_BYTES:
        rows //= 2
    return rows


def _block_logits(xb, w, kernel: bool = False):
    """float32 logits [rows, V] of one block from ``x.dtype`` operands:
    ``w`` a table [V, h], or with ``kernel`` a dense kernel [h, V]."""
    return lax.dot_general(xb, w, (((1,), (0 if kernel else 1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _nll_blocks(x, head, labels, ignore: int, kernel: bool, grads: bool):
    """The head in blocks of rows.  Per block: float32 logits from
    ``x.dtype`` operands, the log-sum-exp, the label's logit; with
    ``grads`` also the block's cotangent ``softmax - onehot`` (rounded to
    ``x.dtype`` for the two matmuls that consume it at once: the rows'
    gradient, and the head matrix's, accumulated in float32 over the
    blocks).  ``head`` is read as it lies, [V, h] or with ``kernel``
    [h, V]: the two layouts are the same three matmuls under other
    dimension numbers, and its gradient comes back in its own layout."""
    n, h = x.shape
    v = head.shape[1 if kernel else 0]
    rows = logit_block_rows(n, v)
    from ..common.metrics import gauges
    gauges.set("head.logit_block_bytes", float(rows * v * 4))
    gauges.set("head.logit_blocks", float(n // rows))
    def one_block(g_head, block):
        xb, lb = block
        valid = lb != ignore
        safe = jnp.where(valid, lb, 0)
        # two arguments in the table's layout: the benchmarks' breaks
        # patch ``_block_logits`` with a function of (xb, w)
        logits = (_block_logits(xb, w, kernel=True) if kernel
                  else _block_logits(xb, w))
        top = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp(logits - top)
        total = jnp.sum(e, axis=-1, keepdims=True)
        lse = (top + jnp.log(total))[:, 0]
        ll = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0] - lse
        m = valid.astype(jnp.float32)
        out = (-(ll * m).sum(), m.sum())
        if not grads:
            return g_head, out
        hit = lax.broadcasted_iota(jnp.int32, logits.shape, 1) == safe[:, None]
        d = ((e / total - hit.astype(jnp.float32)) * m[:, None]
             ).astype(x.dtype)                                  # [rows, V]
        g_x = lax.dot_general(d, w, (((1,), (1 if kernel else 0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        g_head = g_head + lax.dot_general(
            *((xb, d) if kernel else (d, xb)), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return g_head, out + (g_x.astype(x.dtype),)

    with jax.named_scope("bps.head"):
        w = head.astype(x.dtype)
        g_head, out = lax.scan(
            one_block, jnp.zeros(head.shape if grads else (), jnp.float32),
            (x.reshape(n // rows, rows, h), labels.reshape(n // rows, rows)))
    nll, count = out[0].sum(), out[1].sum()
    if not grads:
        return nll, count
    return nll, count, out[2].reshape(n, h), g_head


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def blocked_token_nll(x, table, labels, ignore: int = -1,
                      kernel: bool = False):
    """:func:`token_nll` of the head's logits without ever holding them:
    ``x`` [N, h] the final hidden rows, ``table`` the head matrix as its
    parameter lies — [V, h], a (tied) embedding (logits ``x @ table.T``),
    or with ``kernel=True`` [h, V], the kernel of an untied ``nn.Dense``
    head (logits ``x @ table``) — ``labels`` [N] -> (sum of per-token NLL
    over valid positions, valid-token count).  The layout is said, never
    guessed from the shapes (``V == h`` is legal), and nothing is
    transposed for it: the matrix, its gradient and so its optimizer
    moments keep the parameter's layout.

    For a vocabulary of 100 k rows and more the float32 logits of a step
    are gigabytes (16 384 tokens x 131 136 rows: 8.6 GB, and as much again
    for their cotangent).  Here they exist one block of rows at a time
    (:func:`logit_block_rows`: the block's float32 logits <= 1 GiB, from
    the shapes; gauges ``head.logit_block_bytes`` / ``head.logit_blocks``),
    in float32 from ``x.dtype`` operands, forward AND backward: under
    differentiation the forward pass forms both gradients block by block
    — each block's cotangent is made and consumed inside the block — and
    the backward pass only scales them by the incoming cotangent, so the
    head costs three matmuls, not the four of recomputing a block's logits
    in the backward pass.  The matrix's float32 gradient is the one
    full-size array, and the step has to hold it anyway."""
    return _nll_blocks(x, table, labels, ignore, kernel, grads=False)


def _blocked_token_nll_fwd(x, table, labels, ignore, kernel):
    nll, count, g_x, g_table = _nll_blocks(x, table, labels, ignore, kernel,
                                           grads=True)
    return (nll, count), (g_x, g_table)


def _blocked_token_nll_bwd(ignore, kernel, res, g):
    g_x, g_table = res
    g_nll = g[0]                         # the count has no gradient
    return ((g_x.astype(jnp.float32) * g_nll).astype(g_x.dtype),
            g_table * g_nll, None)


blocked_token_nll.defvjp(_blocked_token_nll_fwd, _blocked_token_nll_bwd)


def blocked_lm_loss(x, table, labels, ignore: int = -1,
                    kernel: bool = False):
    """:func:`lm_loss` through :func:`blocked_token_nll`."""
    s, c = blocked_token_nll(x, table, labels, ignore, kernel)
    return s / jnp.maximum(c, 1.0)
