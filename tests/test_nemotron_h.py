"""``models/nemotron_h.py`` against its plain reference
(``tests/nemotron_h_reference.py``): loss and gradients for a pattern that
holds all three kinds of block and the module, whole and as one chip's
share; the constructor's refusals; and the test that ties a share to the
model — the partial results of ALL shares add up to the uncut block."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models.nemotron_h import (NemotronBlock, NemotronH,
                                          nemotron_h_tiny, nemotron_loss)

from . import nemotron_h_reference as reference

SHARE = dict(mamba_heads_held=2, groups_held=1, heads_held=2,
             kv_heads_held=1, experts_held=(2, 3))
# fewer, wider Mamba heads than ``nemotron_h_tiny``: the scan's kernels
# unroll a group's heads, and the interpreter compiles every one
SMALL = dict(mamba_num_heads=4, mamba_head_dim=16)


def reference_kwargs(cfg):
    return dict(eps=cfg.layer_norm_epsilon, state=cfg.ssm_state_size,
                head_dim=cfg.mamba_head_dim, top_k=cfg.num_experts_per_tok,
                held=cfg.held, scaling=cfg.routed_scaling_factor,
                renormalize=cfg.norm_topk_prob)


def setup(cfg, seed=0, seqs=2, t=32):
    model = NemotronH(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (seqs, t), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(seed), ids)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((seqs, 1), -1)], axis=1)
    return model, params, {"input_ids": ids, "labels": labels}


@pytest.fixture
def einsum_scan(monkeypatch):
    """The mixer's scan as ``ssd_scan_chunked``, the einsum form the
    kernels are tested against (``tests/test_ssd_scan.py``): the Pallas
    interpreter compiles every head of every call, and these tests are
    about the blocks around the scan."""
    from byteps_tpu.ops import ssd_scan
    monkeypatch.setattr(
        ssd_scan, "ssd_scan", lambda *a, chunk, interpret=None:
        ssd_scan.ssd_scan_chunked(*a, chunk=chunk))


# one expert of 64 held, 3 x 24 tokens: 216 pair rows a block in chunks of
# 8, thin enough for ``parallel.expert.window_rows`` (windows of 8 rows)
THIN = dict(n_routed_experts=64, experts_held=(5, 1))


@pytest.mark.parametrize("scan,share,shape", [
    ("einsum_scan", None, (2, 16)), ("einsum_scan", SHARE, (2, 16)),
    (None, SHARE, (2, 16)), ("einsum_scan", THIN, (3, 24))],
    ids=["whole-einsum", "share-einsum", "share-kernels", "thin-windowed"])
def test_loss_and_gradients_match_the_reference(scan, share, shape, request):
    """Pattern ME*E + the module (*E): every kind of block, float32; the
    program (grouped matmuls, the scan's kernels interpreted, the blocked
    head) against the reference (dense experts, the recurrence position by
    position) to float32's own rounding."""
    if scan:
        request.getfixturevalue(scan)
    cfg = nemotron_h_tiny(**SMALL, **(share or {}))
    model, params, batch = setup(cfg, seqs=shape[0], t=shape[1])
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: nemotron_loss(model, p, batch)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(functools.partial(
        reference.reference_loss, mtp_weight=cfg.mtp_loss_weight,
        **reference_kwargs(cfg))))(params, batch)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)),
        grads, want_grads)
    flat = jax.tree_util.tree_leaves_with_path(worst)
    assert max(v for _, v in flat) < 2e-5, sorted(
        flat, key=lambda kv: -kv[1])[:3]
    # the bias chooses only: no gradient reaches it
    assert not np.any(grads["params"]["h1"]["moe"]["e_score_correction_bias"])


def test_without_the_module_only_the_main_head_is_scored(einsum_scan):
    cfg = nemotron_h_tiny(num_nextn_predict_layers=0,
                          hybrid_override_pattern="M*E", num_hidden_layers=3)
    model, params, batch = setup(cfg, t=16)
    assert "mtp" not in params["params"]
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(functools.partial(nemotron_loss, model))(params, batch)
    want = jax.jit(functools.partial(
        reference.reference_loss, mtp_weight=0.3,
        **reference_kwargs(cfg)))(params, batch)
    np.testing.assert_allclose(loss, want, rtol=1e-5)


def test_every_M_block_traces_to_the_scan_kernels():
    """No option takes the mixer off ``ssd_scan``: the differentiated loss
    of MEM*E + the module holds one ``bps_ssd_fwd`` and one ``bps_ssd_bwd``
    a ``M`` block: what ``ssm_scan_ms`` reads."""
    from .jaxpr_count import _inner_jaxprs
    cfg = nemotron_h_tiny(hybrid_override_pattern="MEM*E",
                          num_hidden_layers=5, **SMALL)
    model, params, batch = setup(cfg, t=16)
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(str(eqn.params.get("name")))
            for inner in _inner_jaxprs(eqn):
                walk(inner)

    walk(jax.make_jaxpr(jax.grad(
        lambda p: nemotron_loss(model, p, batch)))(params).jaxpr)
    assert sum("bps_ssd_fwd" in n for n in names) == 2
    assert sum("bps_ssd_bwd" in n for n in names) == 2


@pytest.mark.parametrize("overrides,match", [
    (dict(n_group=2), "n_group"),
    (dict(topk_group=2), "topk_group"),
    (dict(num_nextn_predict_layers=2), "at most one"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(sliding_window=1024), "sliding_window"),
    (dict(moe_shared_expert_overlap=True), "moe_shared_expert_overlap"),
    (dict(mamba_heads_held=3, groups_held=1), "split a group"),
    (dict(heads_held=3, kv_heads_held=2), "straddle"),
    (dict(experts_held=(6, 4)), "experts_held"),
    (dict(hybrid_override_pattern="ME-E"), "hybrid_override_pattern"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(use_conv_bias=False), "use_conv_bias"),
    (dict(expand=4), "expand"),
])
def test_the_constructor_refuses_by_key_what_it_does_not_compute(overrides,
                                                                 match):
    with pytest.raises(ValueError, match=match):
        nemotron_h_tiny(**overrides)


# ---------------------------------------------- the shares add up (guide 4)

def one_block(kind, cfg, x, params):
    with jax.default_matmul_precision("highest"):
        return jax.jit(NemotronBlock(cfg, kind).apply)({"params": params}, x)


def whole_block(kind, seed=0):
    cfg = nemotron_h_tiny(rescale_prenorm_residual=False)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 16, cfg.hidden_size))
    params = NemotronBlock(cfg, kind).init(jax.random.PRNGKey(seed + 1),
                                           x)["params"]
    want = jax.jit(functools.partial(
        reference.block, model=dict(reference_kwargs(cfg))))(x, params)
    return cfg, x, params, want


def columns(matrix, runs):
    """The columns of the last axis in the (start, stop) runs given."""
    return jnp.concatenate([matrix[..., a:b] for a, b in runs], axis=-1)


def test_mamba_head_shares_add_up_to_the_uncut_block(einsum_scan):
    """8 heads in 2 groups, one group a chip: a chip holds its heads' z, xs
    and dt columns of ``in_proj``, its group's B and C, the same channels
    of the convolution and the norm, the matching rows of ``out_proj``; the
    two partial outputs add up (the residual ``x`` counted once)."""
    with jax.default_matmul_precision("highest"):
        cfg, x, params, want = whole_block("M")
    p = params["mixer_ssm"]
    heads, hp, n = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size
    inner, per = heads * hp, heads // cfg.n_groups
    total = jnp.zeros_like(x)
    for g in range(cfg.n_groups):
        ch = (g * per * hp, (g + 1) * per * hp)         # the heads' channels
        conv = [ch, (inner + g * n, inner + (g + 1) * n),
                (inner + cfg.n_groups * n + g * n,
                 inner + cfg.n_groups * n + (g + 1) * n)]
        proj = ([ch] + [(inner + a, inner + b) for a, b in conv]
                + [(2 * inner + 2 * cfg.n_groups * n + g * per,
                    2 * inner + 2 * cfg.n_groups * n + (g + 1) * per)])
        share = {"norm": params["norm"], "mixer_ssm": {
            "in_proj": {"kernel": columns(p["in_proj"]["kernel"], proj)},
            "conv_kernel": columns(p["conv_kernel"], conv),
            "conv_bias": columns(p["conv_bias"], conv),
            "dt_bias": p["dt_bias"][g * per:(g + 1) * per],
            "A_log": p["A_log"][g * per:(g + 1) * per],
            "D": p["D"][g * per:(g + 1) * per],
            "norm_scale": p["norm_scale"][ch[0]:ch[1]],
            "out_proj": {"kernel": p["out_proj"]["kernel"][ch[0]:ch[1]]}}}
        held = nemotron_h_tiny(rescale_prenorm_residual=False,
                               mamba_heads_held=per, groups_held=1)
        total = total + one_block("M", held, x, share) - x
    np.testing.assert_allclose(x + total, want, rtol=2e-5, atol=2e-6)


def test_attention_head_shares_add_up_to_the_uncut_block():
    """4 query heads on 2 key/value heads over 4 chips: a chip holds ONE
    query head and the key/value head it reads (two chips compute the same
    key/value projection alike); ``o_proj``'s partial products add up."""
    with jax.default_matmul_precision("highest"):
        cfg, x, params, want = whole_block("*")
    p = params["attn"]
    per_kv = cfg.num_attention_heads // cfg.num_key_value_heads
    held = nemotron_h_tiny(rescale_prenorm_residual=False, heads_held=1,
                           kv_heads_held=1)
    total = jnp.zeros_like(x)
    for h in range(cfg.num_attention_heads):
        kv = h // per_kv
        share = {"norm": params["norm"], "attn": {
            "q_proj": {"kernel": p["q_proj"]["kernel"][:, h:h + 1]},
            "k_proj": {"kernel": p["k_proj"]["kernel"][:, kv:kv + 1]},
            "v_proj": {"kernel": p["v_proj"]["kernel"][:, kv:kv + 1]},
            "o_proj": {"kernel": p["o_proj"]["kernel"][h:h + 1]}}}
        total = total + one_block("*", held, x, share) - x
    np.testing.assert_allclose(x + total, want, rtol=2e-5, atol=2e-6)


def test_expert_shares_add_up_to_the_uncut_block():
    """8 routed experts over 4 chips of 2: every chip routes over all 8 and
    computes its experts' part of the routed sum through ``W_up`` (linear,
    so the parts add up as the sums do); the shared expert, which every
    chip computes alike, is counted ONCE."""
    with jax.default_matmul_precision("highest"):
        cfg, x, params, want = whole_block("E")
    p = params["moe"]
    zero_shared = jnp.zeros_like(p["shared_down_proj"]["kernel"])
    total = jnp.zeros_like(x)
    for chip, first in enumerate(range(0, cfg.n_routed_experts, 2)):
        share = {"norm": params["norm"], "moe": dict(
            p, up=p["up"][first:first + 2], down=p["down"][first:first + 2],
            # every chip's shared expert gives the same rows: one counts
            shared_down_proj={"kernel": p["shared_down_proj"]["kernel"]
                              if chip == 0 else zero_shared})}
        held = nemotron_h_tiny(rescale_prenorm_residual=False,
                               experts_held=(first, 2))
        total = total + one_block("E", held, x, share) - x
    np.testing.assert_allclose(x + total, want, rtol=2e-5, atol=2e-6)


def test_a_thin_held_share_runs_in_windows_of_its_live_range(einsum_scan):
    """One held expert of 64 at top-3 over 3 x 24 tokens: 216 pair rows a
    block, chunks of 8, ``window_rows`` = 8.  The differentiated loss
    then holds NO array of 216 rows — no kernel's result, no gather, no
    zero fill — only columns (the sort's, the count's indices), and the
    activation's kernels sit inside the loops over the windows
    (``parallel/expert.py``, PR 40)."""
    from byteps_tpu.parallel.expert import window_rows

    from .jaxpr_count import _inner_jaxprs
    cfg = nemotron_h_tiny(**THIN, **SMALL)
    model, params, batch = setup(cfg, seqs=3, t=24)
    rows = 3 * 24 * cfg.num_experts_per_tok
    assert window_rows(3 * 24, cfg.num_experts_per_tok, 1, 64) == 8
    pair_arrays, act = [], []

    def walk(jaxpr, in_while):
        for eqn in jaxpr.eqns:
            pair_arrays.extend(
                (eqn.primitive.name, v.aval.shape) for v in eqn.outvars
                if rows in getattr(v.aval, "shape", ())
                and v.aval.size > rows)         # [216] / [216, 1]: columns
            if (eqn.primitive.name == "pallas_call"
                    and "bps_moe_act" in str(eqn.params.get("name"))):
                act.append(in_while)
            for inner in _inner_jaxprs(eqn):
                walk(inner, in_while or eqn.primitive.name == "while")

    walk(jax.make_jaxpr(jax.grad(
        lambda p: nemotron_loss(model, p, batch)))(params).jaxpr, False)
    assert not pair_arrays, pair_arrays[:5]
    # three E blocks (two + the module's): forward, and the backward
    # loop's forward and backward
    assert len(act) == 9 and all(act)
