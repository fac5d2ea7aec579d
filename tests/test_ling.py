"""``models/ling.py`` against its plain reference
(``tests/ling_reference.py``): loss and gradients for a 6-layer stack that
holds both mixers (KDA, MLA) and both MLP kinds, as one chip's
share; the group-limited choice against the reference's own; the
constructor's refusals; and the test that ties a share to the model — the
routed sums of ALL shares add up to the uncut layer."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import ling
from byteps_tpu.models.ling import (Ling, LingConfig, LingSparseMoe,
                                    group_limited, ling_loss, ling_tiny)

from . import ling_reference as reference


@functools.lru_cache(maxsize=None)      # a model's init traces its forward
def setup(cfg, seed=0, seqs=2, t=32):
    model = Ling(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (seqs, t), 0,
                             cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), ids)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((seqs, 1), -1)], axis=1)
    return model, params, {"input_ids": ids, "labels": labels}


def livelier(params, seed=7):
    """normal(0.02) weights at hidden size 32 leave every mixer's output a
    rounding error beside the residual: scale the matrices up so that a
    wrong mixer or router shows."""
    keys = iter(np.asarray(jax.random.split(jax.random.PRNGKey(seed), 400)))

    def one(path, leaf):
        name = jax.tree_util.keystr(path)
        if leaf.ndim >= 2 and "conv" not in name and "dt_bias" not in name:
            return leaf * 8.0
        if "expert_bias" in name:
            return leaf
        return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(one, p))(params)


@pytest.fixture
def chunked_scan(monkeypatch):
    """The mixer's scan as ``kda_scan_chunked`` (``tests/test_kda_scan.py``
    holds both forms against the recurrence): the Pallas interpreter
    compiles every head of every call, and these tests are about the
    layers around the scan."""
    import importlib
    kda = importlib.import_module("byteps_tpu.ops.kda_scan")
    monkeypatch.setattr(
        kda, "kda_scan", lambda *a, chunk, interpret=None:
        kda.kda_scan_chunked(*a, chunk=chunk))


def test_loss_and_gradients_match_the_reference():
    """5 KDA + 1 MLA, 2 dense + 4 sparse, float32: the program (the scan's
    kernels interpreted, flash-free exact attention, grouped matmuls, the
    blocked head) against the reference (the delta rule position by
    position, dense experts) to float32's own rounding — but for the
    scan's solve, whose [C, C] products are three bfloat16-operand passes
    (2^-16 a product, ``ops/kda_scan.py`` ``_dot3``): the decays' leaves
    (``A_log``, ``dt_bias``) read up to 3e-4 under these lively weights,
    every other leaf under 1e-4."""
    cfg = ling_tiny(experts_held=(4, 2))
    model, params, batch = setup(cfg)
    params = livelier(params)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: ling_loss(model, p, batch)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(functools.partial(
        reference.reference_loss, **reference.model_of(cfg))))(params, batch)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    grads, want_grads = jax.tree.map(np.asarray, (grads, want_grads))
    worst = jax.tree.map(
        lambda a, b: float(np.abs(a - b).max() / (np.abs(b).max() + 1e-12)),
        grads, want_grads)
    flat = [(jax.tree_util.keystr(path), v) for path, v in
            jax.tree_util.tree_leaves_with_path(worst)]
    decays = ("A_log']", "dt_bias']")
    assert max(v for k, v in flat if not k.endswith(decays)) < 1e-4, sorted(
        flat, key=lambda kv: -kv[1])[:3]
    assert max(v for k, v in flat if k.endswith(decays)) < 5e-4
    # the bias chooses only: no gradient reaches it
    assert not np.any(grads["params"]["h2"]["moe"]["expert_bias"])
    # every kind of leaf moved
    assert all(np.abs(g).max() > 0 for path, g in
               jax.tree_util.tree_leaves_with_path(want_grads)
               if "expert_bias" not in jax.tree_util.keystr(path))


def test_flash_attention_takes_the_latent_s_two_widths(chunked_scan):
    """q.k at 24 lanes, v at 16, through the flash kernels (interpreted):
    the same loss as through exact attention."""
    from byteps_tpu.ops import flash_attention
    cfg = ling_tiny(num_hidden_layers=2, layer_group_size=2,
                    first_k_dense_replace=2)         # KDA, MLA; dense MLPs
    model, params, batch = setup(cfg)
    flash = Ling(cfg, attn_fn=functools.partial(flash_attention,
                                                interpret=True))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            jax.jit(functools.partial(ling_loss, flash))(params, batch),
            jax.jit(functools.partial(ling_loss, model))(params, batch),
            rtol=1e-6)


def test_every_kda_layer_traces_to_the_scan_kernels():
    """No option takes the mixer off ``kda_scan``: the differentiated loss
    holds one ``bps_kda_fwd`` and one ``bps_kda_bwd`` a KDA layer — what
    ``kda_scan_ms`` reads."""
    from .jaxpr_count import _inner_jaxprs
    cfg = ling_tiny(num_hidden_layers=3, layer_group_size=3,
                    first_k_dense_replace=3)
    model, params, batch = setup(cfg, t=16)
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(str(eqn.params.get("name")))
            for inner in _inner_jaxprs(eqn):
                walk(inner)

    walk(jax.make_jaxpr(jax.grad(
        lambda p: ling_loss(model, p, batch)))(params).jaxpr)
    assert names.count("bps_kda_fwd") == 2 and names.count("bps_kda_bwd") == 2


# ------------------------------------------------- the group-limited choice

def reference_choice(scores, bias, n_group, topk_group, top_k):
    return np.asarray(reference.chosen_experts(
        jnp.asarray(scores), jnp.asarray(bias), n_group=n_group,
        topk_group=topk_group, top_k=top_k))


def program_choice(scores, bias, n_group, topk_group, top_k):
    """What the expert layer picks from ``group_limited``'s ``p``: the
    ``top_k`` largest, ties to the lower index (``lax.top_k``'s order, the
    selection kernel's too)."""
    p, chosen = group_limited(jnp.asarray(scores), jnp.asarray(bias),
                              n_group, topk_group)
    _, idx = jax.lax.top_k(p, top_k)
    picked = (np.arange(scores.shape[1]) == np.asarray(idx)[..., None]).any(-2)
    return picked, np.asarray(p), np.asarray(chosen)


def test_a_token_s_best_experts_outside_its_chosen_groups_are_not_taken():
    """16 experts in 4 groups, 2 groups kept, top-4.  Group 3 holds the
    single largest score (0.99) and nothing else: by the sum of two it
    loses to groups 0 and 1, and the token's best expert is NOT taken."""
    s = np.full((1, 16), 0.05, np.float32)
    s[0, 12] = 0.99                       # group 3: 0.99 + 0.05
    s[0, 0:2] = 0.6                       # group 0: 1.2
    s[0, 4:6] = 0.55                      # group 1: 1.1
    s[0, 8:10] = 0.5                      # group 2: 1.0
    bias = np.zeros(16, np.float32)
    picked, p, chosen = program_choice(s, bias, 4, 2, 4)
    assert chosen.tolist() == [[True, True, False, False]]
    assert not picked[0, 12] and p[0, 12] == 0
    assert sorted(np.flatnonzero(picked[0])) == [0, 1, 4, 5]
    np.testing.assert_array_equal(picked, reference_choice(s, bias, 4, 2, 4))


def test_ties_between_groups_and_inside_a_group_go_to_the_lower_index():
    s = np.full((2, 16), 0.1, np.float32)
    s[0, [0, 1, 4, 5, 8, 9]] = 0.7       # groups 0, 1, 2 tie at 1.4
    s[1] = 0.3                            # everything ties
    bias = np.zeros(16, np.float32)
    picked, _, chosen = program_choice(s, bias, 4, 2, 4)
    assert chosen.tolist() == [[True, True, False, False]] * 2
    assert sorted(np.flatnonzero(picked[1])) == [0, 1, 2, 3]
    np.testing.assert_array_equal(picked, reference_choice(s, bias, 4, 2, 4))


def test_the_choice_matches_the_reference_on_random_scores():
    s = np.asarray(jax.nn.sigmoid(jax.random.normal(
        jax.random.PRNGKey(3), (256, 64))))
    bias = np.zeros(64, np.float32)
    picked, p, _ = program_choice(s, bias, 8, 4, 8)
    np.testing.assert_array_equal(picked, reference_choice(s, bias, 8, 4, 8))
    assert ((p == 0) | (p == s)).all()
    assert (p != 0).sum(1).tolist() == [32] * 256     # 4 groups of 8


def test_a_group_s_score_is_the_sum_of_its_two_largest_also_when_equal():
    s = np.full((1, 8), 0.1, np.float32)
    s[0, 0:2] = 0.5                       # group 0: 0.5 + 0.5 (a tie inside)
    s[0, 4] = 0.8                         # group 1: 0.8 + 0.1 < 1.0
    _, chosen = group_limited(jnp.asarray(s), jnp.zeros(8), 2, 1)
    assert np.asarray(chosen).tolist() == [[True, False]]


# ------------------------------------------------------------ the refusals

REFUSALS = {
    "expert_swiglu_limit_list": (dict(
        num_hidden_layers=6, expert_swiglu_limit_list=[0] * 5 + [4]),
        "expert_swiglu_limit_list"),
    "share_expert_swiglu_limit_list": (dict(
        share_expert_swiglu_limit_list=[0, 0, 5]),
        "share_expert_swiglu_limit_list"),
    "q_lora_rank": (dict(q_lora_rank=768), "q_lora_rank"),
    "use_mla_nope": (dict(use_mla_nope=True), "use_mla_nope"),
    "use_nGPT": (dict(use_nGPT=True), "use_nGPT"),
    "value_norm": (dict(value_norm=True), "value_norm"),
    "up_proj_norm": (dict(up_proj_norm=True), "up_proj_norm"),
    "scale_router_input": (dict(scale_router_input=True),
                           "scale_router_input"),
    "use_kda_lora": (dict(use_kda_lora=True), "use_kda_lora"),
    "num_kv_heads_for_linear_attn": (dict(num_kv_heads_for_linear_attn=8),
                                     "num_kv_heads_for_linear_attn"),
    "group_norm_size": (dict(group_norm_size=4), "group_norm_size"),
    "tie_word_embeddings": (dict(tie_word_embeddings=True),
                            "tie_word_embeddings"),
    "n_group": (dict(num_experts=16, n_group=3), "n_group"),
    "held_across_groups": (dict(experts_held=(2, 4)), "ONE routing group"),
    "held_does_not_divide": (dict(experts_held=(0, 3)), "ONE routing group"),
    "held_outside": (dict(experts_held=(14, 4)), "experts_held"),
    "head_wise": (dict(gated_attention_proj_granularity_type="elementwise"),
                  "head_wise"),
    "rotary_dim": (dict(rotary_dim=4), "rotary_dim"),
    "score_function": (dict(score_function="softmax"), "score_function"),
    "kda_safe_gate": (dict(kda_safe_gate=False), "kda_safe_gate"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_the_constructor_refuses_by_key_what_it_does_not_compute(what):
    overrides, match = REFUSALS[what]
    with pytest.raises(ValueError, match=match):
        ling_tiny(**overrides)


def test_the_published_limits_beyond_the_layers_built_are_no_refusal():
    """The source's lists clamp layers 34-41: a model of layers 0-5 builds
    none of them."""
    limits = [0] * 35 + [4] * 7
    assert ling_tiny(expert_swiglu_limit_list=limits,
                     share_expert_swiglu_limit_list=limits
                     ).expert_swiglu_limit_list == tuple(limits)
    assert LingConfig().is_mla(5) and not LingConfig().is_mla(4)
    assert LingConfig().is_dense(1) and not LingConfig().is_dense(2)


# ------------------------------------------- the share is tied to the model

def test_the_eight_shares_of_a_sparse_layer_add_up_to_the_uncut_layer():
    """64 experts in 8 groups, 4 groups kept, 8 a token: each of 8 chips
    holds one group's 8 experts.  The routed sums of ALL shares, with the
    shared expert counted once, are the uncut reference's layer."""
    base = dict(num_experts=64, n_group=8, topk_group=4,
                num_experts_per_tok=8)
    whole = ling_tiny(**base)
    m = jax.random.normal(jax.random.PRNGKey(2), (2, 8, whole.hidden_size))
    params = jax.jit(LingSparseMoe(whole).init)(jax.random.PRNGKey(5), m)
    params = livelier(params)
    tree, rows = params["params"], m.reshape(16, -1)
    with jax.default_matmul_precision("highest"):
        shared = reference.swiglu(rows, tree["shared_expert"])
        want = reference.sparse_moe(rows, tree,
                                    **_moe_kwargs(whole))
        total = shared
        for first in range(0, 64, 8):
            cfg = ling_tiny(experts_held=(first, 8), **base)
            mine = {"params": {**tree, **{
                name: tree[name][first:first + 8]
                for name in ("gate", "up", "down")}}}
            part = LingSparseMoe(cfg).apply(mine, m).reshape(16, -1)
            np.testing.assert_allclose(
                part, reference.sparse_moe(rows, mine["params"],
                                           **_moe_kwargs(cfg)),
                rtol=1e-4, atol=1e-6)
            total = total + (part - shared)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-3
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-6)


def _moe_kwargs(cfg):
    model = reference.model_of(cfg)
    return {k: model[k] for k in ("held", "n_group", "topk_group", "top_k",
                                  "scaling", "renormalize")}


def test_counts_group_hits_and_gauges():
    import byteps_tpu as bps
    from byteps_tpu.parallel.expert import publish_moe_stats
    cfg = ling_tiny(experts_held=(4, 2))
    model, params, batch = setup(cfg)
    counts, hits = jax.jit(lambda p, ids: (
        ling.expert_counts(model, p, ids),
        ling.group_hit_share(model, p, ids)))(params, batch["input_ids"])
    assert counts.shape == (4, 16) and hits.shape == (4,)
    assert (np.asarray(counts).sum(1) == 2 * 32 * 4).all()
    assert ((0 <= np.asarray(hits)) & (np.asarray(hits) <= 1)).all()
    publish_moe_stats(counts, held=cfg.experts_held)
    ling.publish_group_stats(hits)
    gauges = bps.metrics_snapshot()["gauges"]
    np.testing.assert_allclose(gauges["moe.group_hit_share"],
                               float(np.mean(hits)), rtol=1e-6)
    assert gauges["moe.groups"] == 4 and gauges["moe.groups_chosen"] == 2
    assert gauges["kda.log_decay_floor"] == -5.0
    assert gauges["mla.head_dim"] == 24 and gauges["mla.v_head_dim"] == 16
    assert gauges["mla.kv_latent_dim"] == 24
    assert 0 < gauges["moe.held_pair_share"] < 1
