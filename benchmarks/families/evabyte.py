"""Family ``evabyte``: byteps_tpu.models.evabyte under byte prediction by
eight heads.

Configuration keys as in the source's ``config.json`` (``model_type:
evabyte``, ``EvaByte/EvaByte``); the one key that differs is
``num_hidden_layers``, beside its ``_published`` twin: depth alone is cut,
every layer whole, every width, head count and row as published.

The plain reference is float32 ``jax.numpy`` on the same parameter tree,
written from the equations of ISSUE 50 and importing nothing of the
program: a copy of ``tests/evabyte_reference.py`` between the two
``reference`` marks (``benchmarks/tests/test_evabyte_cell.py`` holds the
two texts equal).  EVA attention there is ONE masked softmax a query row
over the concatenated ``[T + T / 16]`` keys, the mask written from the two
sets of the equations; one head at a time from its projections to its
``W_o`` product, one sequence and one block of 1 024 query rows at a time,
the SwiGLU in blocks of 1 024 rows, the heads over blocks
of 512 positions, each half layer and each layer under ``jax.checkpoint``:
blocking and rematerialising change memory, not mathematics.
"""

from __future__ import annotations

import functools
import math
import types

import jax
import jax.numpy as jnp

from harness import spec

# --------------------------------------------------------------- reference

HEAD_BLOCK = 512          # positions per block of the prediction heads
QUERY_BLOCK = 1024        # query rows per block of the exact attention
ROW_BLOCK = 1024          # rows per block of the SwiGLU


def norm0(x, w, eps):
    """Unit offset: ``x rsqrt(mean x^2 + eps) (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def rotate(x, theta):
    """Rotate-half over the whole last axis of x [B, T, ..., r] at
    positions 0 .. T - 1."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape(1, x.shape[1], *[1] * (x.ndim - 3), r // 2)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def chunk_summaries(k, v, mu, phi, chunk):
    """k, v [T, D] of one head, mu, phi [D] -> (k~, v~) [T / chunk, D]:
    inside a chunk a softmax of ``k_j . mu`` pools the keys, one of ``k_j .
    phi`` the values."""
    t, d = k.shape
    kc, vc = k.reshape(t // chunk, chunk, d), v.reshape(t // chunk, chunk, -1)
    alpha = jax.nn.softmax(kc @ mu, -1)                  # [T / chunk, chunk]
    beta = jax.nn.softmax(kc @ phi, -1)
    return (jnp.einsum("nc,ncd->nd", alpha, kc),
            jnp.einsum("nc,ncd->nd", beta, vc))


def eva_one_head(q, k, v, mu, phi, *, window, chunk):
    """q, k, v [T, D] of one sequence and head (q, k rotated) -> [T, D]:
    row i, in window w = i // window, takes one softmax at scale 1/sqrt(D)
    over L_i = {j : j // window == w, j <= i} (its own window, causal) and
    R_i = {c : c < (window / chunk) w} (every chunk of every earlier
    window), one block of query rows at a time."""
    t, d = q.shape
    ks, vs = chunk_summaries(k, v, mu, phi, chunk)
    keys, values = jnp.concatenate([k, ks]), jnp.concatenate([v, vs])
    rows = math.gcd(t, QUERY_BLOCK)
    j, c = jnp.arange(t)[None, :], jnp.arange(t // chunk)[None, :]

    @jax.checkpoint
    def one_block(q1, first_row):                # [rows, D]
        i = first_row + jnp.arange(rows)[:, None]
        w = i // window
        own = (j // window == w) & (j <= i)
        earlier = c < (window // chunk) * w
        s = jnp.where(jnp.concatenate([own, earlier], 1),
                      q1 @ keys.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(s, -1) @ values

    return jax.lax.map(lambda blk: one_block(*blk),
                       (q.reshape(t // rows, rows, d),
                        jnp.arange(0, t, rows))).reshape(t, -1)


def eva(a, p, *, theta, window, chunk):
    """a: [B, T, h] -> the mixer's output.  A head meets no other between
    the projections and ``W_o``, so the heads go one at a time, each under
    ``jax.checkpoint``, and their ``W_o`` products are summed: q, k, v of
    ONE head, q and k rotated over all lanes, the pooling AFTER the
    rotation, one sequence at a time."""

    @jax.checkpoint
    def one_head(w):
        wq, wk, wv, mu, phi, w_o = w           # [h, D] x 3, [D] x 2, [D, h]
        q, k, v = rotate(a @ wq, theta), rotate(a @ wk, theta), a @ wv
        ctx = jax.lax.map(
            lambda x: eva_one_head(*x, mu, phi, window=window, chunk=chunk),
            (q, k, v))                                       # [B, T, D]
        return ctx @ w_o

    def by_head(kernel):                       # [h, H, D] -> [H, h, D]
        return jnp.moveaxis(kernel, 1, 0)

    return jax.lax.scan(
        lambda y, w: (y + one_head(w), None), jnp.zeros_like(a),
        (by_head(p["q_proj"]["kernel"]), by_head(p["k_proj"]["kernel"]),
         by_head(p["v_proj"]["kernel"]), p["mu"], p["phi"],
         p["o_proj"]["kernel"]))[0]


def swiglu(m, p):
    """m: [N, h], in blocks of ``ROW_BLOCK`` rows."""
    n, h = m.shape
    rows = math.gcd(n, ROW_BLOCK)

    @jax.checkpoint
    def one_block(mb):
        return (jax.nn.silu(mb @ p["gate_proj"]["kernel"])
                * (mb @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]

    return jax.lax.map(one_block, m.reshape(n // rows, rows, h)).reshape(n, h)


def mixer(x, p, *, model):
    """``x + eva(N(x))``."""
    a = norm0(x, p["input_layernorm"]["scale"], model["eps"])
    return x + eva(a, p["attn"], theta=model["theta"],
                   window=model["window"], chunk=model["chunk"])


def mlp(x, p, *, model):
    """``x + W_d(silu(W_g m) * W_u m)``, ``m = N(x)``."""
    bsz, t, h = x.shape
    m = norm0(x, p["post_attention_layernorm"]["scale"], model["eps"])
    return x + swiglu(m.reshape(bsz * t, h), p["mlp"]).reshape(bsz, t, h)


def layer(x, p, *, model):
    """One layer; each half under a ``jax.checkpoint`` of its own."""
    x = jax.checkpoint(functools.partial(mixer, model=model))(x, p)
    return jax.checkpoint(functools.partial(mlp, model=model))(x, p)


def reference_hidden(params, ids, **model):
    """-> the rows the heads read, [B, T, h], one sequence at a time."""
    p = params["params"]

    @jax.checkpoint
    def one_sequence(ids):                           # [T]
        x = p["wte"]["embedding"][ids[None]]
        step = jax.checkpoint(functools.partial(layer, model=model))
        i = 0
        while f"h{i}" in p:
            x = step(x, p[f"h{i}"])
            i += 1
        return norm0(x, p["norm_f"]["scale"], model["eps"])[0]

    return jax.lax.map(one_sequence, ids)


def head_labels(labels, heads):
    """labels [B, T] (the next byte; -1: none) -> [B, T, heads]: head p at
    position t is asked for byte t + 1 + p, -1 past the sequence's end."""
    return jnp.stack(
        [jnp.concatenate([labels[:, p:], jnp.full_like(labels[:, :p], -1)], 1)
         for p in range(heads)], -1)


def reference_logits(params, ids, **model):
    """-> the eight heads' logits [B, T, heads, V]: one matrix [h, heads
    V], head p its p-th run of V columns."""
    x = reference_hidden(params, ids, **model)
    logits = x @ params["params"]["lm_head"]
    return logits.reshape(*x.shape[:2], model["heads"], -1)


def heads_nll(x, head, labels):
    """Mean negative log-likelihood over the heads and the positions that
    have a label, equal weights (x: [N, h], head [h, heads V], labels
    [N, heads]; -1: no label), over blocks of positions."""
    n, heads = labels.shape
    rows = math.gcd(n, HEAD_BLOCK)

    @jax.checkpoint
    def one_block(xl):
        xb, lb = xl
        valid = lb >= 0
        logp = jax.nn.log_softmax((xb @ head).reshape(rows, heads, -1), -1)
        ll = jnp.take_along_axis(logp, jnp.where(valid, lb, 0)[..., None],
                                 -1)[..., 0]
        return -(ll * valid).sum(), valid.sum()

    nll, count = jax.lax.map(one_block, (x.reshape(n // rows, rows, -1),
                                         labels.reshape(n // rows, rows,
                                                        heads)))
    return nll.sum() / count.sum()


def reference_loss(params, batch, **model):
    """The eight heads' mean cross-entropy; no auxiliary term."""
    with jax.default_matmul_precision("highest"):
        x = reference_hidden(params, batch["input_ids"], **model)
        b, t, h = x.shape
        labels = head_labels(batch["labels"], model["heads"])
        return heads_nll(x.reshape(b * t, h), params["params"]["lm_head"],
                         labels.reshape(b * t, -1))

# ----------------------------------------------------------- end reference


# ------------------------------------------------- operations and bytes

def share_params(config: dict) -> int:
    """Parameters of the chip's share: the model's own leaf count."""
    model = _model(config, {"remat": False})
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, config["window_size"]), jnp.int32)))
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(shapes))


def _heads(config: dict):
    heads = config["num_attention_heads"]
    return heads, config["hidden_size"] // heads


def _score_flops_per_row_pair(config: dict) -> float:
    """Operations a (query, key) pair of one head costs, forward and
    backward: ``q k^T`` and ``P V`` forward (4 D); the scores again,
    ``dP``, ``dV``, ``dK``, ``dQ`` backward (10 D)."""
    return 14.0 * _heads(config)[1]


def _windows(config: dict, seq_len: int) -> int:
    if seq_len % config["window_size"]:
        raise spec.SpecError(f"seq_len {seq_len} is not whole windows of "
                             f"{config['window_size']}")
    return seq_len // config["window_size"]


def summary_pairs_per_seq(config: dict, seq_len: int) -> float:
    """(query row, summary) pairs a head NEEDS along one sequence: row i
    sees ``(window / chunk) (i // window)`` summaries."""
    w, nw = config["window_size"], _windows(config, seq_len)
    return float(w * (w // config["chunk_size"]) * nw * (nw - 1) // 2)


def local_pairs_per_seq(config: dict, seq_len: int) -> float:
    """(query row, key) pairs a head needs in the rows' own windows: the
    causal half of each ``window x window`` square."""
    w = config["window_size"]
    return _windows(config, seq_len) * w * w / 2.0


def flops_per_token(config: dict, seq_len: int) -> float:
    """Required matmul operations per trained token: 6 per weight a token
    meets (q, k, v, ``W_o``, the SwiGLU's three matrices, the eight heads'
    one matrix; the table is a gather) + EVA's scores over BOTH key sets by
    their needed pairs, forward and backward (so that ``flash_roofline``
    and ``mfu_pct`` count one work).  The pooling is no matmul;
    recomputation under ``remat`` is not counted."""
    h, f = config["hidden_size"], config["intermediate_size"]
    layers, heads = config["num_hidden_layers"], _heads(config)[0]
    weights = (layers * (4 * h * h + 3 * h * f)
               + h * config["num_pred_heads"] * config["vocab_size"])
    pairs = (local_pairs_per_seq(config, seq_len)
             + summary_pairs_per_seq(config, seq_len)) / seq_len
    return (6.0 * weights
            + layers * heads * _score_flops_per_row_pair(config) * pairs)


def eva_summary_work(config: dict, seq_len: int, seqs: int,
                     itemsize: int = 2) -> dict:
    """Required operations and HBM bytes of one step's attention over the
    SUMMARY set on one chip, every layer: for every query row the scores
    and sums over ``(window / chunk) (i // window)`` summaries, forward and
    backward, counted as the other families' ``flash_work`` counts a call;
    bytes as the ALGORITHM moves them — q (read forward and backward), dQ,
    o (written, read again) and dO at [T, heads, D]; k~ and v~ (each
    twice), dk~ and dv~ at [T / chunk, heads, D]; three float32 rows a head
    (lse; lse and delta again).  The same whatever sub-block or kernel
    implements it; the forward recomputed under ``remat`` is the program's
    work: not counted."""
    heads, d = _heads(config)
    n, rows = config["num_hidden_layers"], seqs * seq_len
    flops = (n * seqs * heads * _score_flops_per_row_pair(config)
             * summary_pairs_per_seq(config, seq_len))
    layer_bytes = ((rows + rows // config["chunk_size"]) * 6 * heads * d
                   * itemsize + 3 * 4.0 * rows * heads)
    return {"flops": flops, "bytes": n * layer_bytes,
            # a transform wraps the first scope entered after it
            "op_name_re": r"bps\.eva\.summary\)*/.*pallas_call$"}


def flash_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2
               ) -> dict:
    """Required operations and HBM bytes of one step's flash calls under
    the scope ``attn`` on one chip, every layer's, BOTH key sets: the
    own-window causal halves (``T / window`` calls' worth at T = window:
    q, k, v, o and their gradients at [T, heads, D], three float32 rows a
    head) plus ``eva_summary_work``."""
    heads, d = _heads(config)
    n, rows = config["num_hidden_layers"], seqs * seq_len
    local_flops = (n * seqs * heads * _score_flops_per_row_pair(config)
                   * local_pairs_per_seq(config, seq_len))
    local_bytes = n * (rows * 12 * heads * d * itemsize
                       + 3 * 4.0 * rows * heads)
    summary = eva_summary_work(config, seq_len, seqs, itemsize)
    return {"flops": local_flops + summary["flops"],
            "bytes": local_bytes + summary["bytes"],
            "op_name_re": r"bps\.eva\.(local|summary)\)*/.*pallas_call$"}


# ----------------------------------------------------------------- build

SAME_NAME_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "attention_class",
    "attention_bias", "window_size", "chunk_size", "num_chunks",
    "num_pred_heads", "hidden_act", "rms_norm_eps", "norm_add_unit_offset",
    "rope_theta", "rope_scaling", "tie_word_embeddings",
    "max_position_embeddings", "init_std", "fp32_skip_add", "fp32_logits",
    "mixedp_attn")


def _model(config: dict, traffic: dict):
    from byteps_tpu.models.evabyte import EvaByte, EvaByteConfig
    spec.fixed(config, model_type="evabyte", param_dtype="float32",
               norm_eps=config["rms_norm_eps"])
    # every key goes to the model file under its own name, which refuses
    # what it cannot compute
    return EvaByte(EvaByteConfig(
        **{k: config[k] for k in SAME_NAME_KEYS},
        dtype=jnp.dtype(config["compute_dtype"]).type,
        remat=bool(traffic.get("remat", False))))


def build(config: dict, traffic: dict):
    from byteps_tpu.models import evabyte
    model = _model(config, traffic)
    cfg = model.cfg
    if traffic["objective"] != "clm":
        raise ValueError(f"family evabyte has no objective "
                         f"{traffic['objective']!r}")
    if traffic.get("attention", "flash") != "flash":
        raise ValueError("family evabyte computes its attention through "
                         "the flash kernels only (attention: flash)")
    seq = traffic["seq_len"]
    if seq > cfg.max_position_embeddings:
        raise ValueError(f"seq_len {seq} exceeds the model's context "
                         f"{cfg.max_position_embeddings}")
    _windows(config, seq)

    def init_params(key):
        return model.init(key, jnp.zeros((1, seq), jnp.int32))

    def make_batch(key, n_seqs):
        ids = jax.random.randint(key, (n_seqs, seq), 0, cfg.vocab_size)
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full((n_seqs, 1), -1, ids.dtype)], axis=1)
        return {"input_ids": ids, "labels": labels}

    def kernel_work(seqs_per_chip):
        return {"flash": flash_work(config, seq, seqs_per_chip),
                "eva_summary": eva_summary_work(config, seq, seqs_per_chip)}

    reference = dict(eps=cfg.rms_norm_eps, theta=float(cfg.rope_theta),
                     window=cfg.window_size, chunk=cfg.chunk_size,
                     heads=cfg.num_pred_heads)
    return types.SimpleNamespace(
        init_params=init_params,
        loss_fn=functools.partial(evabyte.evabyte_loss, model),
        make_batch=make_batch,
        reference_loss=functools.partial(reference_loss, **reference),
        tokens_per_seq=seq, flops_per_token=flops_per_token(config, seq),
        kernel_work=kernel_work,
        # the rows the heads read, [B, T, h], of the program and of the
        # reference (``benchmarks/tests/gradcheck_evabyte.py``)
        hidden=model.apply,
        reference_hidden=functools.partial(reference_hidden, **reference),
        compute_dtype=cfg.dtype,
        # one layer's attention: sequences, T, heads, head size, window,
        # chunk; and the reference's one (sequence, head)
        eva_shape=(int(traffic["seqs_per_chip"]), seq,
                   cfg.num_attention_heads, cfg.head_dim, cfg.window_size,
                   cfg.chunk_size),
        eva_one_head=eva_one_head)
