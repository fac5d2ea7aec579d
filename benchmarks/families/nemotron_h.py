"""Family ``nemotron_h``: byteps_tpu.models.nemotron_h under next-token
prediction with a depth-1 multi-token-prediction module.

Configuration keys as in the source's ``config.json`` (``model_type:
nemotron_h``), plus the chip's share of a stated deployment: the keys that
COUNT heads, groups, experts, rows and blocks give what is held here
(``mamba_num_heads``, ``n_groups``, ``num_attention_heads``,
``num_key_value_heads``, ``n_routed_experts`` with ``experts_held`` saying
which, ``vocab_size``, ``num_hidden_layers`` with its
``hybrid_override_pattern``), each beside its ``_published`` twin; every
width is the source's.

The plain reference is float32 ``jax.numpy`` on the same parameter tree,
written from the equations of ISSUE 39 and importing nothing of the
program: a copy of ``tests/nemotron_h_reference.py`` between the two
``reference`` marks (``benchmarks/tests/test_nemotron_h_cell.py`` holds
the two texts equal).  Per block ``x = x + mixer(RMSNorm(x))``: the
state-space recurrence as a ``lax.scan`` over POSITIONS (no chunk
algebra), nested in segments of 128 under ``jax.checkpoint`` so that its
backward keeps a state a segment and not a state a position (8192 x 16 x
128 x 64 x 4 B would be 4.3 GB a block); exact softmax attention one
(sequence, head) and one block of 1 024 query rows at a time, no rotation;
sigmoid scores over all 512 experts, the 22 largest of ``score + bias``,
renormalised (+1e-20) and scaled by 5, the HELD experts one by one in
their dense form on the 1024-wide latent with ``relu(.)^2`` and no gate,
the shared expert in blocks of 2 048 rows; the module; both heads over the
slice in blocks of 512 positions.  Each block of the model under
``jax.checkpoint`` and runs of four under one more (its compile-only
footprint on a described v5e: 3.12 GiB parameters + 3.12 GiB gradient +
2.31 GiB temp beside the harness's two moments, 6.25 GiB; one checkpoint
a block read 3.65 GiB of temp, over the chip): blocking and
rematerialising change memory, not mathematics.
"""

from __future__ import annotations

import functools
import math
import types

import jax
import jax.numpy as jnp

from harness import flops as F
from harness import spec

# --------------------------------------------------------------- reference

HEAD_BLOCK = 512          # positions per block of a vocabulary head
QUERY_BLOCK = 1024        # query rows per block of the exact attention
ROW_BLOCK = 2048          # rows per block of the shared expert
SCAN_SEGMENT = 128        # positions per rematerialised run of the recurrence
BLOCK_GROUP = 4           # blocks per outer rematerialised run of the model


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def causal_softmax_attention(q, k, v):
    """q, k, v: [T, D] of one sequence and head -> [T, D]: exact softmax
    over keys j <= i at scale 1/sqrt(D), one block of query rows at a
    time."""
    t, d = q.shape
    rows = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(q1, first_row):                # [rows, D]
        i = first_row + jnp.arange(rows)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= i,
                      q1 @ k.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(s, -1) @ v

    return jax.lax.map(lambda blk: one_block(*blk),
                       (q.reshape(t // rows, rows, d),
                        jnp.arange(0, t, rows))).reshape(t, d)


def attention(a, p):
    """a: [B, T, h]; the share's query heads, each on its key/value head
    (repeated to the query heads by an explicit index); no rotation.  One
    (sequence, head) at a time."""
    q = jnp.einsum("bth,hnd->bntd", a, p["q_proj"]["kernel"])
    k = jnp.einsum("bth,hnd->bntd", a, p["k_proj"]["kernel"])
    v = jnp.einsum("bth,hnd->bntd", a, p["v_proj"]["kernel"])
    kv_of = jnp.arange(q.shape[1]) // (q.shape[1] // k.shape[1])
    one_head = jax.checkpoint(causal_softmax_attention)
    ctx = jax.lax.map(
        lambda seq: jax.lax.map(lambda qkv: one_head(*qkv), seq),
        (q, k[:, kv_of], v[:, kv_of]))                 # [B, H, T, d]
    return jnp.einsum("bntd,ndh->bth", ctx, p["o_proj"]["kernel"])


def state_space_recurrence(xs, dt, a, b_in, c_in, d_skip):
    """xs [B, T, H, P], dt [B, T, H], a [H] (negative), b_in / c_in
    [B, T, G, N], d_skip [H] -> y [B, T, H, P]: the recurrence position by
    position from a zero state, head h on group ``h // (H / G)``."""
    bsz, t, h, p = xs.shape
    g, n = b_in.shape[2], b_in.shape[3]
    seg = math.gcd(t, SCAN_SEGMENT)
    b_h = jnp.repeat(b_in, h // g, axis=2)             # [B, T, H, N]
    c_h = jnp.repeat(c_in, h // g, axis=2)

    def position(state, at):                           # state [B, H, N, P]
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :])
        return state, jnp.einsum("bhn,bhnp->bhp", c_t, state)

    @jax.checkpoint
    def segment(state, run):
        return jax.lax.scan(position, state, run)

    def by_segment(v):                 # [B, T, ...] -> [T/seg, seg, B, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(t // seg, seg, *v.shape[1:])

    _, y = jax.lax.scan(segment, jnp.zeros((bsz, h, n, p), jnp.float32),
                        tuple(by_segment(v) for v in (xs, dt, b_h, c_h)))
    y = jnp.moveaxis(y.reshape(t, bsz, h, p), 0, 1)
    return y + d_skip[:, None] * xs


def mamba(u, p, *, state, head_dim, eps):
    """u: [B, T, h] -> the mixer's output (the share's heads and groups:
    read off the parameters' shapes)."""
    bsz, t, _ = u.shape
    heads = p["A_log"].shape[0]
    inner = heads * head_dim
    proj = u @ p["in_proj"]["kernel"]
    groups = (proj.shape[-1] - 2 * inner - heads) // (2 * state)
    bc = groups * state
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * bc],
                  proj[..., 2 * inner + 2 * bc:])
    # depthwise causal convolution: tap k reads position t - (K - 1) + k,
    # zeros before the sequence
    taps = p["conv_kernel"].shape[0]
    conv = p["conv_bias"] + sum(
        p["conv_kernel"][k] * jnp.concatenate(
            [jnp.zeros_like(xbc[:, :taps - 1 - k]),
             xbc[:, :t - (taps - 1 - k)]], axis=1)
        for k in range(taps))
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :inner].reshape(bsz, t, heads, head_dim)
    b_in = xbc[..., inner:inner + bc].reshape(bsz, t, groups, state)
    c_in = xbc[..., inner + bc:].reshape(bsz, t, groups, state)
    dt = jax.nn.softplus(dt + p["dt_bias"])            # not clamped
    y = state_space_recurrence(xs, dt, -jnp.exp(p["A_log"]), b_in, c_in,
                               p["D"])
    # gate first, then the norm over each group's channels
    gated = (y.reshape(bsz, t, groups, inner // groups)
             * jax.nn.silu(z).reshape(bsz, t, groups, inner // groups))
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + eps)
    return (normed.reshape(bsz, t, inner) * p["norm_scale"]
            ) @ p["out_proj"]["kernel"]


def in_row_blocks(fn, m):
    """``fn`` over blocks of ``ROW_BLOCK`` rows of m [N, h], each under
    ``jax.checkpoint``."""
    n, h = m.shape
    rows = math.gcd(n, ROW_BLOCK)
    return jax.lax.map(jax.checkpoint(fn),
                       m.reshape(n // rows, rows, h)).reshape(n, -1)


def unstacked(stack):
    """The matrices of a [count, ., .] stack, cut ONCE (one concatenation
    in the backward, not ``count`` zero-padded stacks)."""
    return [jnp.squeeze(one, 0) for one in jnp.split(stack, stack.shape[0])]


def latent_moe(m, p, *, top_k, held, scaling, renormalize):
    """m: [N, h] -> the held routed experts' part of the sum (scaled,
    through ``W_up``) plus the shared expert (whole on every chip: counted
    once)."""
    first, count = held
    scores = jax.nn.sigmoid(m @ p["router"])             # [N, E]
    e = scores.shape[-1]
    # departure: the bias is the zeros it starts as; it chooses only
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["e_score_correction_bias"]), top_k)
    picked = (jnp.arange(e) == chosen[..., None]).any(-2)
    weight = jnp.where(picked, scores, 0.0)
    if renormalize:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = scaling * weight[:, first:first + count]    # the experts held
    latent = m @ p["fc1_latent_proj"]["kernel"]          # [N, latent]

    @jax.checkpoint
    def one_expert(latent, up, down, w_e):
        return w_e[:, None] * (relu2(latent @ up) @ down)

    routed = jnp.zeros_like(latent)
    for i, matrices in enumerate(zip(unstacked(p["up"]),
                                     unstacked(p["down"]))):
        routed = routed + one_expert(latent, *matrices, weight[:, i])
        routed, latent = jax.lax.optimization_barrier((routed, latent))
    return routed @ p["fc2_latent_proj"]["kernel"] + in_row_blocks(
        lambda mb: relu2(mb @ p["shared_up_proj"]["kernel"])
        @ p["shared_down_proj"]["kernel"], m)


def block(x, p, *, model):
    """One block: its mixer's kind is what its parameters are."""
    eps = model["eps"]
    bsz, t, h = x.shape
    u = rms_norm(x, p["norm"]["scale"], eps)
    if "mixer_ssm" in p:
        y = mamba(u, p["mixer_ssm"], state=model["state"],
                  head_dim=model["head_dim"], eps=eps)
    elif "attn" in p:
        y = attention(u, p["attn"])
    else:
        y = latent_moe(u.reshape(bsz * t, h), p["moe"], top_k=model["top_k"],
                       held=model["held"], scaling=model["scaling"],
                       renormalize=model["renormalize"]).reshape(bsz, t, h)
    return x + y


def blocks(x, tree, prefix, model):
    """``x`` through ``tree[prefix + "0"]``, ``tree[prefix + "1"]``, ...:
    each block under a ``jax.checkpoint`` of its own, and runs of
    ``BLOCK_GROUP`` blocks under one more, so that the backward keeps a
    float32 [B, T, h] input a GROUP (and the blocks' of one group at a
    time), not one a block: 13 x 128 MiB would not fit beside the
    harness's parameters, moments and gradient."""
    step = jax.checkpoint(functools.partial(block, model=model))
    names = []
    while f"{prefix}{len(names)}" in tree:
        names.append(f"{prefix}{len(names)}")

    @jax.checkpoint
    def group(x, params):
        for p in params:
            x = step(x, p)
        return x

    for i in range(0, len(names), BLOCK_GROUP):
        x = group(x, [tree[n] for n in names[i:i + BLOCK_GROUP]])
    return x


def module_rows(x, table, ids, mtp, model):
    """The module: position i reads the last block's output (before the
    final norm) and the embedding of token i + 1 — an explicit shift; the
    LAST position has no next token: a zero embedding there, and nothing
    scores it."""
    eps = model["eps"]
    emb_next = jnp.concatenate(
        [table[ids[:, 1:]], jnp.zeros_like(x[:, :1])], axis=1)
    joined = jnp.concatenate(
        [rms_norm(x, mtp["hnorm"]["scale"], eps),
         rms_norm(emb_next, mtp["enorm"]["scale"], eps)], axis=-1)
    g = blocks(joined @ mtp["eh_proj"]["kernel"], mtp, "b", model)
    return rms_norm(g, mtp["norm"]["scale"], eps)


def reference_hidden(params, ids, **model):
    """-> (rows the main head reads, rows the module's head reads), each
    [B, T, h] (the second ``None`` without a module)."""
    p = params["params"]
    table = p["wte"]["embedding"]
    x = blocks(table[ids], p, "h", model)
    g = None
    if "mtp" in p:
        g = jax.checkpoint(functools.partial(module_rows, model=model))(
            x, table, ids, p["mtp"])
    return rms_norm(x, p["norm_f"]["scale"], model["eps"]), g


def head_nll(x, head, labels):
    """Mean negative log-likelihood of ``labels`` (-1: no label) under
    ``x head^T``, over blocks of positions (x: [N, h], labels: [N])."""
    n = x.shape[0]
    rows = math.gcd(n, HEAD_BLOCK)

    @jax.checkpoint
    def one_block(xl):
        xb, lb = xl
        valid = lb >= 0
        logp = jax.nn.log_softmax(jnp.einsum("nh,vh->nv", xb, head), -1)
        ll = jnp.take_along_axis(logp, jnp.where(valid, lb, 0)[:, None],
                                 -1)[:, 0]
        return -(ll * valid).sum(), valid.sum()

    nll, count = jax.lax.map(one_block, (x.reshape(n // rows, rows, -1),
                                         labels.reshape(n // rows, rows)))
    return nll.sum() / count.sum()


def reference_loss(params, batch, *, mtp_weight, **model):
    """Cross-entropy of the next token + lambda x cross-entropy of the one
    after, over the rows held.  Departures: no auxiliary loss and no
    z-loss; lambda = 0.3."""
    with jax.default_matmul_precision("highest"):
        x, g = reference_hidden(params, batch["input_ids"], **model)
        b, t, h = x.shape
        head, labels = params["params"]["lm_head"], batch["labels"]
        loss = head_nll(x.reshape(b * t, h), head, labels.reshape(b * t))
        if g is None:
            return loss
        # the module at position i predicts token i + 2 = labels[i + 1]:
        # an explicit shift; the last position has no label
        after = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
        return loss + mtp_weight * head_nll(g.reshape(b * t, h), head,
                                            after.reshape(b * t))

# ----------------------------------------------------------- end reference


# ------------------------------------------------- operations and bytes

def _kinds(config: dict) -> dict:
    """How many blocks of each kind run here: the model's pattern and, with
    a module, the module's."""
    pattern = config["hybrid_override_pattern"]
    if config["num_nextn_predict_layers"]:
        pattern += config["mtp_hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in "ME*"}


def _ssm_sizes(config: dict):
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    return heads, p, config["n_groups"], config["ssm_state_size"]


def _mixer_weights(config: dict) -> dict:
    """Matmul weights a token meets in one block of each kind, at the
    share held: ``M`` the two projections; ``*`` q, k, v, o; ``E`` the
    router, both latent projections, the shared expert's two matrices and
    the token's pairs that fall on held experts — ``held / routed`` of its
    ``num_experts_per_tok`` in expectation, two matrices a pair."""
    h = config["hidden_size"]
    heads, p, groups, n = _ssm_sizes(config)
    inner = heads * p
    q_heads, kv, d = (config["num_attention_heads"],
                      config["num_key_value_heads"], config["head_dim"])
    lat, f = config["moe_latent_size"], config["moe_intermediate_size"]
    routed = config["n_routed_experts_published"]
    pairs_here = (config["num_experts_per_tok"] * config["n_routed_experts"]
                  / routed)
    return {"M": h * (2 * inner + 2 * groups * n + heads) + inner * h,
            "*": 2 * h * q_heads * d + 2 * h * kv * d,
            "E": (h * routed + 2 * h * lat
                  + 2 * h * config["moe_shared_expert_intermediate_size"]
                  + pairs_here * 2 * lat * f)}


def ssd_forward_flops_per_token(config: dict) -> float:
    """Operations of the chunked scan's FORWARD per token of one ``M``
    block, at the share held and the source's ``chunk_size`` Q: per group
    ``C B^T`` (2 Q N a token); per head the masked scores times ``dt o
    xs`` (2 Q P), the state's read-out ``C S`` (2 N P) and its update
    (2 N P)."""
    heads, p, groups, n = _ssm_sizes(config)
    q = config["chunk_size"]
    return groups * 2.0 * q * n + heads * (2.0 * q * p + 4.0 * n * p)


def share_params(config: dict) -> int:
    """Parameters of the chip's share: the model's own leaf count."""
    model, _ = _model(config, {"remat": False})
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, config["chunk_size"]), jnp.int32)))
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(shapes))


def flops_per_token(config: dict, seq_len: int) -> float:
    """Required matmul operations of THIS CHIP's share per trained token:
    6 per weight a token meets (``_mixer_weights``; the module's ``P``; the
    head once for each head — the embedding side is a gather) + the scan,
    forward and a backward of twice the forward + attention's scores, the
    causal half at ``heads x 128``, 4 x head size a score forward and 10
    backward (``harness/flops.py`` ``flash_backward``, so that
    ``flash_roofline`` and ``mfu_pct`` count one work).  Recomputation
    under ``remat`` is not counted."""
    h = config["hidden_size"]
    kinds, per_block = _kinds(config), _mixer_weights(config)
    mtp = config["num_nextn_predict_layers"]
    weights = (sum(kinds[k] * per_block[k] for k in kinds)
               + mtp * 2 * h * h + (1 + mtp) * h * config["vocab_size"])
    scores = (kinds["*"] * 14.0 * config["head_dim"]
              * config["num_attention_heads"] * seq_len / 2)
    return (6.0 * weights + scores
            + kinds["M"] * 3.0 * ssd_forward_flops_per_token(config))


def flash_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2
               ) -> dict:
    """Required operations and HBM bytes of one step's flash calls under
    the scope ``attn`` on one chip, every ``*`` block's (the module's
    too).  Operations: the causal half of ``[4, seq, 128]`` a sequence and
    block, 4 x head size a score forward and 10 backward.  Bytes: what the
    ALGORITHM moves — q, o (read again in the backward), dO and dQ at the
    4 query heads; k, v, dK and dV at the ONE key/value head the algorithm
    reads (repeating it over the query heads before the call is the
    program's choice); three float32 rows a query head.  The forward
    recomputed under ``remat`` is the program's work: not counted."""
    heads, kv, d = (config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    n = _kinds(config)["*"]
    flops = n * (F.flash_forward(seqs, heads, seq_len, d, True)["flops"]
                 + F.flash_backward(seqs, heads, seq_len, d, True)["flops"])
    rows = seqs * seq_len
    block_bytes = (rows * (6 * heads * d + 4 * kv * d) * itemsize
                   + 3 * 4.0 * rows * heads)
    return {"flops": flops, "bytes": n * block_bytes,
            "op_name_re": r"/attn/pallas_call$"}


def ssd_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2,
             remat: bool = True) -> dict:
    """Required operations and HBM bytes of one step's state-space scans
    on one chip, every ``M`` block: the ALGORITHM's, whatever implements
    it.  Operations: the chunked form's forward
    (``ssd_forward_flops_per_token``), a backward of twice that, and the
    forward once more where ``remat`` recomputes it.  Bytes: ``xs`` and
    ``y`` (heads x head size), ``B`` and ``C`` (groups x state) in the
    compute type, ``dt`` float32, each and its gradient read or written
    once."""
    heads, p, groups, n = _ssm_sizes(config)
    blocks, tokens = _kinds(config)["M"], seqs * seq_len
    passes = 4.0 if remat else 3.0
    row_bytes = (2 * heads * p + 2 * groups * n) * itemsize + 4.0 * heads
    return {"flops": blocks * passes * tokens
            * ssd_forward_flops_per_token(config),
            "bytes": blocks * 2.0 * tokens * row_bytes,
            # the kernels carry ``name=`` (bps_ssd_fwd / bps_ssd_bwd) under
            # the mixer's ``bps.ssm.scan`` scope
            "op_name_re": r"bps\.ssm\.scan/.*pallas_call$"}


def moe_work(config: dict, seq_len: int, seqs: int, itemsize: int = 2,
             pair_share=None) -> dict:
    """Required operations and HBM bytes of the held routed experts'
    grouped matmuls of ONE step on one chip, all ``E`` blocks: the pair
    rows that fall on held experts — the expected ``held / routed`` of all
    ``tokens x num_experts_per_tok`` (8 / 512), or ``pair_share`` of them
    where the batch's own share is known — through TWO matmuls (up, down:
    no gate) in three passes (forward, row gradient, matrix gradient),
    each 2 M latent f.  Bytes: a pass touches every HELD expert's matrix
    once and each matmul's live row blocks in and out once.  Dead rows
    need nothing; the forward recomputed under ``remat`` is not the
    algorithm's."""
    lat, f = config["moe_latent_size"], config["moe_intermediate_size"]
    held = config["n_routed_experts"]
    if pair_share is None:
        pair_share = held / config["n_routed_experts_published"]
    m = seqs * seq_len * config["num_experts_per_tok"] * pair_share
    n = _kinds(config)["E"]
    matmuls, passes = 2, 3
    return {"flops": n * matmuls * passes * 2.0 * m * lat * f,
            "bytes": n * matmuls * passes * float(itemsize) * (
                held * lat * f + m * (lat + f)),
            "op_name_re": r"bps\.moe\.experts/.*pallas_call$"}


# ----------------------------------------------------------------- build

SAME_NAME_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "num_nextn_predict_layers",
    "mtp_hybrid_override_pattern", "mamba_head_dim", "ssm_state_size",
    "conv_kernel", "chunk_size", "expand", "use_conv_bias",
    "mamba_proj_bias", "mamba_hidden_act", "time_step_min", "time_step_max",
    "time_step_floor", "head_dim", "attention_bias", "sliding_window",
    "rope_theta", "partial_rotary_factor", "num_experts_per_tok",
    "moe_latent_size", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "n_shared_experts",
    "moe_shared_expert_overlap", "routed_scaling_factor", "norm_topk_prob",
    "n_group", "topk_group", "mlp_hidden_act", "mlp_bias", "use_bias",
    "layer_norm_epsilon", "rescale_prenorm_residual", "tie_word_embeddings",
    "max_position_embeddings", "num_hidden_layers_published",
    "mtp_loss_weight")


def _model(config: dict, traffic: dict):
    """(the model at the share the file states, the flash function or
    None)."""
    from byteps_tpu.models.nemotron_h import NemotronH, NemotronHConfig
    spec.fixed(config, model_type="nemotron_h", param_dtype="float32",
               norm_eps=config["layer_norm_epsilon"])
    first, count = config["experts_held"]
    if count != config["n_routed_experts"]:
        raise spec.SpecError(
            f"n_routed_experts ({config['n_routed_experts']}) counts the "
            f"experts held; experts_held says {count}")
    # every other key goes to the model file under its own name, which
    # refuses what it cannot compute; the keys that COUNT what is held go
    # beside their published twins
    cfg = NemotronHConfig(
        **{k: config[k] for k in SAME_NAME_KEYS},
        mamba_num_heads=config["mamba_num_heads_published"],
        mamba_heads_held=config["mamba_num_heads"],
        n_groups=config["n_groups_published"],
        groups_held=config["n_groups"],
        num_attention_heads=config["num_attention_heads_published"],
        heads_held=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads_published"],
        kv_heads_held=config["num_key_value_heads"],
        n_routed_experts=config["n_routed_experts_published"],
        experts_held=(first, count),
        dtype=jnp.dtype(config["compute_dtype"]).type,
        remat=bool(traffic.get("remat", False)))
    attention_kind = traffic.get("attention", "exact")
    if attention_kind == "flash":
        from byteps_tpu.ops import flash_attention as attn_fn
    elif attention_kind == "exact":
        attn_fn = None
    else:
        raise ValueError(f"unknown attention {attention_kind!r}")
    return NemotronH(cfg, attn_fn=attn_fn), attn_fn


def build(config: dict, traffic: dict):
    from byteps_tpu.models.nemotron_h import expert_counts, nemotron_loss
    model, attn_fn = _model(config, traffic)
    cfg = model.cfg
    if traffic["objective"] != "clm":
        raise ValueError(f"family nemotron_h has no objective "
                         f"{traffic['objective']!r}")
    seq = traffic["seq_len"]
    if seq > cfg.max_position_embeddings:
        raise ValueError(f"seq_len {seq} exceeds the model's context "
                         f"{cfg.max_position_embeddings}")
    remat = bool(traffic.get("remat", False))

    def init_params(key):
        return model.init(key, jnp.zeros((1, seq), jnp.int32))

    def make_batch(key, n_seqs):
        # token ids are drawn from the slice of the vocabulary held here
        ids = jax.random.randint(key, (n_seqs, seq), 0, cfg.vocab_size)
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full((n_seqs, 1), -1, ids.dtype)], axis=1)
        return {"input_ids": ids, "labels": labels}

    def kernel_work(seqs_per_chip):
        work = {"ssd": ssd_work(config, seq, seqs_per_chip, remat=remat),
                "latent_moe": moe_work(config, seq, seqs_per_chip)}
        if cfg.num_nextn_predict_layers:
            # every Mosaic kernel of the module's two blocks (its flash
            # calls, its grouped matmuls, its row passes): ``mtp_kernel_ms``
            work["mtp"] = {"op_name_re": r"/mtp/.*pallas_call$"}
        if attn_fn is not None:
            work["flash"] = flash_work(config, seq, seqs_per_chip)
        return work

    reference = dict(
        eps=cfg.layer_norm_epsilon, state=cfg.ssm_state_size,
        head_dim=cfg.mamba_head_dim, top_k=cfg.num_experts_per_tok,
        held=cfg.held, scaling=float(cfg.routed_scaling_factor),
        renormalize=cfg.norm_topk_prob)
    return types.SimpleNamespace(
        init_params=init_params,
        loss_fn=functools.partial(nemotron_loss, model),
        make_batch=make_batch,
        reference_loss=functools.partial(
            reference_loss, mtp_weight=float(cfg.mtp_loss_weight),
            **reference),
        tokens_per_seq=seq, flops_per_token=flops_per_token(config, seq),
        kernel_work=kernel_work,
        # the rows both heads read, [B, T, h] each, of the program and of
        # the reference (``benchmarks/tests/gradcheck_nemotron_h.py``)
        hidden=model.apply,
        reference_hidden=functools.partial(reference_hidden, **reference),
        # one block's scan: (sequences are the caller's) T, heads, head
        # size, groups, state, chunk
        compute_dtype=cfg.dtype,
        ssm_shape=(int(traffic["seqs_per_chip"]), seq, cfg.ssm_heads,
                   cfg.mamba_head_dim, cfg.ssm_groups, cfg.ssm_state_size,
                   math.gcd(seq, cfg.chunk_size)),
        # the share, and its [E blocks, 512] pair counts of one batch
        experts_held=cfg.held,
        latent_moe_work=functools.partial(moe_work, config, seq),
        expert_counts=lambda p, b: expert_counts(model, p, b["input_ids"]))
