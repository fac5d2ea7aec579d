"""Operations and bytes from shapes: what the ALGORITHM needs, never what
an implementation happens to do (padding, recomputation and extra
matmuls are waste, not work).

A matmul of [m, k] x [k, n] is 2*m*k*n operations.  Training a dense
layer costs three such matmuls per weight (forward, input gradient,
weight gradient): 6 operations per weight per token.
"""

from __future__ import annotations


def transformer_layer_matmul_params(hidden: int, ffn: int) -> int:
    """Weights of one layer that every token is multiplied by: q, k, v,
    out (4 h^2) and the two MLP matrices (2 h f).  Biases and layer
    norms are not matmuls."""
    return 4 * hidden * hidden + 2 * hidden * ffn


def attention_flops_per_token(seq: int, hidden: int, causal: bool) -> float:
    """Forward + backward score/value matmuls of ONE layer, per token.

    Forward: QK^T and PV, each 2*seq*hidden per token; backward twice
    that (dQ, dK, dV, dP): 12*seq*hidden in all.  A causal mask needs
    only the lower triangle: half."""
    full = 12.0 * seq * hidden
    return full / 2 if causal else full


def train_flops_per_token(matmul_params_per_token: float, layers: int,
                          seq: int, hidden: int, causal: bool) -> float:
    """Required matmul operations per trained token: 6 per weight a
    token meets (embedding GATHERS meet no matmul and are left out) plus
    the attention term.  Recomputation is not counted."""
    return (6.0 * matmul_params_per_token
            + layers * attention_flops_per_token(seq, hidden, causal))


def flash_forward(batch: int, heads: int, seq: int, head_dim: int,
                  causal: bool, itemsize: int = 2) -> dict:
    """One flash-attention forward call: QK^T and PV
    (2 * 2*b*h*t*t*d, halved when causal); HBM traffic is Q, K, V read
    and O written once, plus the float32 log-sum-exp row."""
    flops = 4.0 * batch * heads * seq * seq * head_dim
    if causal:
        flops /= 2
    bytes_ = (4.0 * batch * heads * seq * head_dim * itemsize
              + 4.0 * batch * heads * seq)
    return {"flops": flops, "bytes": bytes_}


def flash_backward(batch: int, heads: int, seq: int, head_dim: int,
                   causal: bool, itemsize: int = 2) -> dict:
    """One flash-attention backward: five matmuls (recompute S, dP, dV,
    dK, dQ) = 2.5x the forward; reads Q, K, V, O, dO and the two
    float32 rows (lse, delta), writes dQ, dK, dV."""
    flops = 10.0 * batch * heads * seq * seq * head_dim
    if causal:
        flops /= 2
    bytes_ = (8.0 * batch * heads * seq * head_dim * itemsize
              + 2 * 4.0 * batch * heads * seq)
    return {"flops": flops, "bytes": bytes_}


def roofline(flops: float, bytes_: float, peaks: dict) -> dict:
    """Least seconds the chip could take, and which roof sets it."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = bytes_ / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_compute, t_memory),
            "bound": "compute" if t_compute >= t_memory else "memory"}
