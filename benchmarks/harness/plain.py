"""Building blocks of the plain references: float32 ``jax.numpy`` on a
flax parameter tree, none of the repo's model code.  The constants are
what ``models/bert.py`` and ``models/gpt.py`` compute (flax defaults:
LayerNorm epsilon 1e-6, tanh-approximated GELU), so the references check
the system and not the papers."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_norm(x, p, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def scan_layers(body, x, tree, prefix: str):
    """``x`` through ``tree[prefix + "0"]``, ``tree[prefix + "1"]``, ... in
    turn.  The per-layer dicts are stacked on a leading axis and ONE
    rematerialised layer body is scanned instead of n unrolled ones: the
    same mathematics, a tenth of the compile time, and only each layer's
    input kept for the backward pass (unrolled or unrematerialised, the
    float32 residuals of 24 layers do not fit beside the optimizer
    state: 11.7 GiB of scratch against 3.4, compile-only, PR 22)."""
    n = sum(1 for k in tree if k.startswith(prefix)
            and k[len(prefix):].isdigit())
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[tree[f"{prefix}{i}"] for i in range(n)])
    x, _ = jax.lax.scan(jax.checkpoint(lambda x, p: (body(x, p), None)),
                        x, stacked)
    return x
