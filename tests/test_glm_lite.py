"""GLM-4.7-Flash on the CPU: the whole model (latent attention with its
one rotary key, the leading dense block, sigmoid top-k routing behind a
choosing bias, the shared expert, the multi-token-prediction module on the
main table and head) against the plain reference
(tests/glm_lite_reference.py) with every expert held and with a chip's
share; the four shares of the expert sublayer against the uncut layer; the
bias, the weights' sum, the rotary key and the module's shift each alone;
the flash kernels at head size 256; the fused DP step.

Tolerances.  Both sides compute in float32 at full precision and differ
in SUMMATION ORDER only (the grouped matmul accumulates per tile, the
flash kernels fold the softmax blockwise, the head sums its blocks).
Logits and loss agree to rtol 1e-5 (with an absolute floor of 1e-5 of
each array's largest magnitude); the gradients are held to 5e-5 of each
leaf's largest magnitude.  A layer computed in bfloat16 (ulp 4e-3) fails
either by two orders of magnitude; the bfloat16 case below is held to the
loss alone.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from . import glm_lite_reference as ref
from byteps_tpu.comm.mesh import CommContext, _build_mesh
from byteps_tpu.models import glm_lite as mod
from byteps_tpu.models import gpt
from byteps_tpu.models.glm_lite import (GlmLite, GlmLiteAttention,
                                        GlmLiteConfig, GlmLiteSparseMoe,
                                        expert_counts, glm_lite_loss,
                                        glm_lite_tiny)
from byteps_tpu.models.olmoe import olmoe_tiny
from byteps_tpu.ops import flash_attention
from byteps_tpu.parallel import make_dp_train_step, replicate
from byteps_tpu.parallel.expert import dropless_moe_mlp
from byteps_tpu.parallel.sequence import full_attention

RTOL = 1e-5
GRAD_RTOL = 5e-5
SHARES = [(0, 2), (2, 2), (4, 2), (6, 2)]
flash = functools.partial(flash_attention, interpret=True, block_q=8,
                          block_k=8)


def _gradcheck():
    """``benchmarks/tests/gradcheck_glm_lite.py``: the deliberate breaks
    are defined once, beside the chip's comparison."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "tests",
        "gradcheck_glm_lite.py")
    spec = importlib.util.spec_from_file_location("gradcheck_glm_lite", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


GRADCHECK = _gradcheck()


def deviation(got, want) -> float:
    """Largest difference relative to the array's largest magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(1e-30, np.abs(want).max()))


def assert_close(got, want, what="", rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=rtol * max(1e-30, np.abs(want).max()),
                               err_msg=what)


def assert_trees_close(got, want, rtol=RTOL):
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        assert_close(g, flat_want[path], jax.tree_util.keystr(path), rtol)


# ------------------------------------------------------------ whole model

def moved(params, bias=0.05):
    """Every leaf off its initial value by noise of 0.1 (the selection
    bias by ``bias``: it is added to sigmoids of ~0.5 and must not choose
    alone), so a missing norm or an unused bias would show."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + (bias if "e_score_correction_bias" in
                             jax.tree_util.keystr(path) else 0.1)
        * jax.random.normal(jax.random.PRNGKey(9), a.shape), params)


def model_and_batch(cfg, attn_fn=None, seqs=2, seq_len=24, seed=0):
    """The parameters of a share are drawn for the share (its own
    stacks)."""
    model = GlmLite(cfg, attn_fn=attn_fn)
    ids = jax.random.randint(jax.random.PRNGKey(seed), (seqs, seq_len), 0,
                             cfg.vocab_size)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((seqs, 1), -1)], axis=1)
    params = moved(model.init(jax.random.PRNGKey(seed + 1), ids))
    return model, params, {"input_ids": ids, "labels": labels}


def reference_kw(cfg):
    return dict(layers=cfg.num_hidden_layers, nope=cfg.qk_nope_head_dim,
                theta=float(cfg.rope_theta), top_k=cfg.num_experts_per_tok,
                held=cfg.experts_held,
                scaling=float(cfg.routed_scaling_factor),
                renormalize=cfg.norm_topk_prob, eps=cfg.rms_norm_eps)


def loss_kw(cfg):
    return dict(reference_kw(cfg), mtp_weight=float(cfg.mtp_loss_weight))


def test_tiny_has_a_dense_block_two_sparse_ones_and_the_module():
    cfg = glm_lite_tiny()
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.num_nextn_predict_layers) == (3, 1, 1)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.qk_head_dim) == (24, 8, 32, 32)
    _, params, _ = model_and_batch(cfg)
    p = params["params"]
    assert list(params) == ["params"]                # init sows nothing
    assert "mlp" in p["h0"] and "moe" not in p["h0"]
    assert "moe" in p["h1"] and "moe" in p["h2"] and "mlp" not in p["h1"]
    assert p["h1"]["moe"]["router"].shape == (32, 8)
    assert p["h1"]["moe"]["e_score_correction_bias"].shape == (8,)
    attn = p["h0"]["attn_mla"]
    assert attn["q_a_proj"]["kernel"].shape == (32, 24)
    assert attn["q_b_proj"]["kernel"].shape == (24, 4, 32)
    assert attn["kv_a_proj_with_mqa"]["kernel"].shape == (32, 16 + 8)
    assert attn["kv_b_proj"]["kernel"].shape == (16, 4, 24 + 32)
    assert attn["o_proj"]["kernel"].shape == (4, 32, 32)
    assert attn["kv_a_layernorm"]["scale"].shape == (16,)   # not the key
    assert p["mtp"]["eh_proj"]["kernel"].shape == (64, 32)
    assert "moe" in p["mtp"]["block"]
    # ONE table and ONE head: the module has neither of its own
    assert set(p) == {"wte", "lm_head", "h0", "h1", "h2", "mtp", "norm_f"}
    assert set(p["mtp"]) == {"hnorm", "enorm", "eh_proj", "block", "norm"}


def test_published_defaults_are_the_source_s():
    cfg = GlmLiteConfig()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank) == (2048, 20, 768, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.qk_head_dim) == (192, 64, 256, 256)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.n_shared_experts, cfg.routed_scaling_factor) == (
                64, 4, 1, 1.8)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.first_k_dense_replace, cfg.num_hidden_layers) == (
                10240, 1536, 1, 47)
    assert cfg.vocab_size == 154880 and cfg.rope_theta == 1e6
    assert cfg.held == (0, 64) and cfg.mtp_loss_weight == 0.3
    assert mod.score_scale(cfg) == 1 / 16            # sqrt(256), not 192


@pytest.mark.parametrize("held,attn,remat", [
    (None, "exact", False), ((2, 2), "exact", True),
    ((0, 2), "flash", True), ((6, 2), "flash", False)], ids=str)
def test_model_loss_and_gradients_match_the_reference(held, attn, remat):
    cfg = glm_lite_tiny(held, remat=remat)
    model, params, batch = model_and_batch(
        cfg, flash if attn == "flash" else None)
    assert params["params"]["h1"]["moe"]["gate"].shape[0] == cfg.held[1]
    loss, grads = jax.jit(jax.value_and_grad(
        functools.partial(glm_lite_loss, model)))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        functools.partial(ref.loss, **loss_kw(cfg))))(params, batch)
    assert_close(loss, want)
    assert_trees_close(grads, want_grads, GRAD_RTOL)
    # the bias chooses only: no gradient reaches it, on either side
    for block in (grads["params"]["h1"], grads["params"]["mtp"]["block"]):
        assert not np.asarray(block["moe"]["e_score_correction_bias"]).any()


@pytest.mark.parametrize("held", [None, (4, 2)], ids=str)
def test_both_heads_logits_and_counts_match_the_reference(held):
    cfg = glm_lite_tiny(held, remat=True)
    model, params, batch = model_and_batch(cfg, flash)
    main, module = jax.jit(functools.partial(model.apply, logits=True))(
        params, batch["input_ids"])
    want_main, want_module = ref.logits(params, batch["input_ids"],
                                        **reference_kw(cfg))
    assert main.shape == module.shape == (2, 24, cfg.vocab_size)
    assert_close(main, want_main)
    # the last position has no next token (a wrapped one here, a zero row
    # there) and nothing scores it
    assert_close(module[:, :-1], want_module[:, :-1])
    with jax.default_matmul_precision("highest"):
        _, _, want_counts = ref.forward(params, batch["input_ids"],
                                        **reference_kw(cfg))
    counts = expert_counts(model, params, batch["input_ids"])
    assert counts.shape == (3, cfg.n_routed_experts)     # h1, h2, the module
    np.testing.assert_array_equal(counts[:2], want_counts[:2])
    assert int(counts.sum()) == 3 * 48 * 2               # top-2 pairs
    # the module's counts differ by its last position's two pairs at most
    assert np.abs(np.asarray(counts[2]) - want_counts[2]).sum() <= 2 * 2 * 2
    assert ((np.asarray(counts) > 0).sum(axis=1) >= 4).all()   # a real choice


def test_bfloat16_model_stays_near_the_float32_reference():
    """bf16 compute over the same float32 parameters: the loss within 2 %
    (8 mantissa bits through four blocks at width 32; the chip's own
    comparison at the published widths is ``gradcheck_glm_lite.py``)."""
    cfg = glm_lite_tiny(dtype=jnp.bfloat16)
    model, params, batch = model_and_batch(cfg, flash)
    loss = jax.jit(functools.partial(glm_lite_loss, model))(params, batch)
    want = ref.loss(params, batch, **loss_kw(cfg))
    assert np.isfinite(float(loss))
    assert abs(float(loss) - float(want)) < 2e-2 * float(want)


@functools.lru_cache(maxsize=None)
def _jitted_reference(cfg):
    return jax.jit(jax.value_and_grad(
        functools.partial(ref.loss, **loss_kw(cfg))))


@pytest.mark.parametrize("what", GRADCHECK.BREAKS)
def test_the_comparison_fails_each_deliberate_break(what):
    """The tolerances are tight enough: each fault moves the loss or some
    gradient leaf past its tolerance by a factor of ten or more, in
    float32 — but a block of logits rounded to bfloat16, which moves the
    loss by the rounding's own size (2e-5 here) and is the head's own
    check's to see (``benchmarks/tests/test_glm_lite_cell.py``)."""
    cfg = glm_lite_tiny((0, 2))
    with GRADCHECK.broken(what):
        model, params, batch = model_and_batch(cfg)
        loss, grads = jax.jit(jax.value_and_grad(
            functools.partial(glm_lite_loss, model)))(params, batch)
    want, want_grads = _jitted_reference(cfg)(params, batch)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    worst = max(deviation(g, flat_want[path]) for path, g in
                jax.tree_util.tree_flatten_with_path(grads)[0])
    factor = 1 if what == "logits_rounded_to_bf16" else 10
    assert max(worst / GRAD_RTOL, deviation(loss, want) / RTOL) > factor, what
    # undone on exit
    assert mod.dropless_moe_mlp is dropless_moe_mlp
    assert mod.RMSNorm.__name__ == "RMSNorm"
    assert mod.next_tokens.__name__ == "next_tokens"
    assert gpt._block_logits.__name__ == "_block_logits"


@pytest.mark.parametrize("kw,match", [
    (dict(n_group=2), "n_group"), (dict(topk_group=2), "topk_group"),
    (dict(topk_method="greedy"), "topk_method"),
    (dict(v_head_dim=128), r"v_head_dim.*not runnable"),
    (dict(num_nextn_predict_layers=2), "num_nextn_predict_layers"),
    (dict(experts_held=(60, 8)), "experts_held"),
    (dict(num_key_value_heads=4), "num_key_value_heads"),
    (dict(partial_rotary_factor=0.5), "partial_rotary_factor"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(first_k_dense_replace=48), "first_k_dense_replace"),
    (dict(num_experts_per_tok=65), "num_experts_per_tok"),
    (dict(hidden_act="gelu"), "hidden_act")], ids=lambda v: str(v)[:24])
def test_config_refuses_what_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        GlmLiteConfig(**kw)


def test_a_model_without_the_module_has_one_head_and_one_loss():
    cfg = glm_lite_tiny(num_nextn_predict_layers=0)
    model, params, batch = model_and_batch(cfg)
    assert "mtp" not in params["params"]
    x, g = model.apply(params, batch["input_ids"])
    assert g is None and x.shape == (2, 24, 32)
    assert_close(glm_lite_loss(model, params, batch),
                 ref.loss(params, batch, **loss_kw(cfg)))


def test_dp_step_is_the_mean_of_the_shards():
    """One ``make_dp_train_step`` step of a share on a 2-device mesh:
    routing is shard-local, so loss and update are those of the MEAN of
    the two shards' single-device losses and gradients."""
    cfg = glm_lite_tiny((4, 2))
    model, params, batch = model_and_batch(cfg, seqs=2, seq_len=16)
    loss_fn = functools.partial(glm_lite_loss, model)
    tx = optax.sgd(1.0)                  # the update IS the mean gradient
    comm = CommContext(mesh=_build_mesh(jax.devices()[:2], 1),
                       n_dcn=1, n_ici=2)
    step = make_dp_train_step(comm, loss_fn, tx, donate=False)
    new_params, _, loss = step(replicate(comm, params),
                               replicate(comm, tx.init(params)), batch)
    one_shard = jax.jit(jax.value_and_grad(loss_fn))
    shards = [one_shard(params, jax.tree.map(lambda a: a[i:i + 1], batch))
              for i in range(2)]
    assert_close(loss, np.mean([float(s[0]) for s in shards]))
    mean_grads = jax.tree.map(lambda *g: sum(g) / 2, *[s[1] for s in shards])
    # the update is read back as a difference of parameters of ~1
    assert_trees_close(jax.tree.map(jnp.subtract, params, new_params),
                       mean_grads, 1e-3)


def test_adamw_leaves_the_zero_bias_where_it_is():
    """The bias is a leaf of zeros no gradient reaches: AdamW's moments
    stay zero and its decay of a zero is zero, step after step."""
    cfg = glm_lite_tiny((0, 2))
    model = GlmLite(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 128)
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
    params = model.init(jax.random.PRNGKey(1), ids)
    tx = optax.adamw(1e-2)
    state = tx.init(params)
    start = params
    @jax.jit
    def step(params, state):
        grads = jax.grad(functools.partial(glm_lite_loss, model))(params,
                                                                  batch)
        updates, state = tx.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    for _ in range(3):
        params, state = step(params, state)
    for path in mod.sparse_blocks(cfg):
        node, was = params["params"], start["params"]
        for key in path:
            node, was = node[key], was[key]
        assert not np.asarray(node["moe"]["e_score_correction_bias"]).any()
        assert np.abs(np.asarray(node["moe"]["router"] - was["moe"]["router"])
                      ).max() > 1e-3                 # the router does move


# ------------------------------------------- the share ties to the model

def sparse_layer(seed=3, n=48):
    cfg = glm_lite_tiny()
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    m = jax.random.normal(k[0], (2, n // 2, cfg.hidden_size))
    params = moved(GlmLiteSparseMoe(cfg).init(k[1], m))
    return cfg, m, params["params"]


def routing_kw(cfg):
    return dict(top_k=cfg.num_experts_per_tok,
                scaling=float(cfg.routed_scaling_factor),
                renormalize=cfg.norm_topk_prob)


def test_the_four_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Guide section 4: the parts of the sparse MLP's result that the four
    chips' shares give — with what every chip computes alike, the shared
    expert, counted ONCE — add up to what the uncut reference gives for
    the whole layer; every share counts the same pairs over all experts."""
    cfg, m, p = sparse_layer()
    h = cfg.hidden_size
    rows = m.reshape(48, h)
    with jax.default_matmul_precision("highest"):
        want_routed, want_counts = ref.routed_experts(rows, p,
                                                      **routing_kw(cfg))
        shared = ref.shared_expert(rows, p)
    parts = []
    for first, count in SHARES:
        share = {"params": {**p, **{key: p[key][first:first + count]
                                    for key in ("gate", "up", "down")}}}
        y, sown = GlmLiteSparseMoe(glm_lite_tiny((first, count))).apply(
            share, m, mutable=["moe_stats"])
        np.testing.assert_array_equal(sown["moe_stats"]["counts"][0],
                                      want_counts)
        parts.append(np.asarray(y).reshape(48, h))
    # each part holds the shared expert once: take three of the four away
    assert_close(sum(parts) - 3 * np.asarray(shared), want_routed + shared)
    # and the uncut layer of the program is the same sum
    whole = GlmLiteSparseMoe(cfg).apply({"params": p}, m)
    assert_close(whole.reshape(48, h), want_routed + shared)
    # top-2 of 8 over four chips: a token's routed part lives on one or two
    routed = [np.abs(part - np.asarray(shared)).max(axis=1) > 1e-6
              for part in parts]
    on = np.sum(routed, axis=0)
    assert set(on.tolist()) <= {1, 2} and (on == 2).sum() > 10
    assert int(want_counts.sum()) == 2 * 48


def test_the_bias_chooses_and_is_not_weighed():
    """A large ``b_e`` puts expert e into every token's choice and changes
    NO weight of the experts that stay but through the renormalisation's
    sum: the weight read for e is its sigmoid, not sigmoid + bias."""
    cfg, m, p = sparse_layer()
    rows = m.reshape(48, -1)
    scores = mod.router_scores(rows, p["router"])
    stacks = {k: p[k] for k in ("gate", "up", "down")}

    def layer(bias, renormalize=True):
        return dropless_moe_mlp(rows, stacks, 2, renormalize=renormalize,
                                routing=(scores, bias))

    zero = jnp.zeros(8)
    _, _, _, counts0 = layer(zero)
    steer = zero.at[5].set(10.0)
    y, aux, z, counts = layer(steer)
    assert int(counts[5]) == 48 and int(counts0[5]) < 40    # S changed
    assert int(counts.sum()) == int(counts0.sum()) == 96
    p_steer = {**p, "e_score_correction_bias": steer}
    with jax.default_matmul_precision("highest"):
        want, want_counts = ref.routed_experts(
            rows, p_steer, top_k=2, scaling=1.0, renormalize=True)
        w, _ = ref.routed_weights(rows, p_steer, top_k=2, scaling=1.0,
                                  renormalize=False)
    assert_close(y, want)
    np.testing.assert_array_equal(counts, want_counts)
    # un-renormalised, the weights ARE the sigmoids of the chosen: expert
    # 5's is s_5 (not s_5 + 10), the other chosen one keeps its own
    assert_close(w[:, 5], scores[:, 5])
    assert (np.asarray(w) <= 1.0).all()
    with jax.default_matmul_precision("highest"):
        want_raw, _ = ref.routed_experts(rows, p_steer, top_k=2, scaling=1.0,
                                         renormalize=False)
    assert_close(layer(steer, renormalize=False)[0], want_raw)
    # the gradient reaches the scores through the weights alone
    g_s, g_b = jax.grad(lambda s, b: dropless_moe_mlp(
        rows, stacks, 2, renormalize=True, routing=(s, b))[0].sum(),
        (0, 1))(scores, steer)
    assert not np.asarray(g_b).any()
    assert np.abs(np.asarray(g_s)[:, 5]).min() > 0
    assert float(z) == 0.0 and np.isfinite(float(aux))


def test_the_four_weights_sum_to_the_scaling_factor():
    """The reference's [N, E] weights — which the program's output
    matches, above — sum to 1.8 a token over exactly ``top_k`` experts; in
    the program, with every expert made the SAME expert, the routed sum is
    that expert's output times the weights' sum: 1 out of
    ``dropless_moe_mlp``, 1.8 after the model's one multiply."""
    cfg, m, p = sparse_layer()
    rows = m.reshape(48, -1)
    with jax.default_matmul_precision("highest"):
        w, _ = ref.routed_weights(rows, p, **routing_kw(cfg))
        shared = ref.shared_expert(rows, p)
        one = ref.swiglu(rows, p["gate"][0], p["up"][0], p["down"][0])
    assert_close(w.sum(-1), np.full(48, 1.8))
    assert ((np.asarray(w) > 0).sum(-1) == 2).all()
    same = {k: jnp.broadcast_to(p[k][:1], p[k].shape)
            for k in ("gate", "up", "down")}
    y, _, _, _ = dropless_moe_mlp(
        rows, same, 2, renormalize=True,
        routing=(mod.router_scores(rows, p["router"]),
                 p["e_score_correction_bias"]))
    assert_close(y, one)
    layer = GlmLiteSparseMoe(cfg).apply({"params": {**p, **same}}, m)
    assert_close(layer.reshape(48, -1), 1.8 * one + shared)


def test_routing_from_outside_at_top_8_is_the_layer_s_own_router():
    """``routing=(softmax(x @ router), None)`` at ``top_k = 8`` is
    ``routing=None`` on the OLMoE toy's expert layer, renormalised or not,
    to the bit: the 1e-20 in the denominator vanishes beside a sum of
    probabilities."""
    cfg = olmoe_tiny()
    h, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    params = {"router": jax.random.normal(k[0], (h, e)),
              "gate": jax.random.normal(k[1], (e, h, f)) / np.sqrt(h),
              "up": jax.random.normal(k[2], (e, h, f)) / np.sqrt(h),
              "down": jax.random.normal(k[3], (e, f, h)) / np.sqrt(f)}
    x = jax.random.normal(k[4], (48, h))
    probs = jax.nn.softmax(jnp.dot(x, params["router"],
                                   precision=jax.lax.Precision.HIGHEST), -1)
    stacks = {key: v for key, v in params.items() if key != "router"}
    top_k = min(8, e)
    for renormalize in (False, True):
        own = jax.jit(functools.partial(
            dropless_moe_mlp, top_k=top_k, renormalize=renormalize))(
                x, params)
        given = jax.jit(lambda x, s, p: dropless_moe_mlp(
            x, s, top_k, renormalize=renormalize, routing=(p, None)))(
                x, stacks, probs)
        for i in (0, 1, 3):
            np.testing.assert_array_equal(given[i], own[i])


# ------------------------------------------- the latent and its one key

def attention_inputs(seed=4, t=12):
    cfg = glm_lite_tiny()
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    a = jax.random.normal(k[0], (2, t, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(t)[None], (2, t))
    params = moved(GlmLiteAttention(cfg).init(k[1], a, pos))
    return cfg, a, pos, params


def captured_qkv(cfg, params, a, pos):
    """What the module hands its attention function."""
    seen = {}

    def spy(q, k, v, *, causal, sm_scale):
        seen.update(q=q, k=k, v=v, causal=causal, sm_scale=sm_scale)
        return mod.banded_attention(q, k, v, causal=causal,
                                    sm_scale=sm_scale)

    out = GlmLiteAttention(cfg, spy).apply(params, a, pos)
    return seen, out


def test_one_rotary_key_reaches_every_head_and_the_rest_is_position_free():
    cfg, a, pos, params = attention_inputs()
    nope = cfg.qk_nope_head_dim
    seen, out = captured_qkv(cfg, params, a, pos)
    q, k, v = seen["q"], seen["k"], seen["v"]
    assert q.shape == k.shape == v.shape == (2, 12, 4, 32)
    assert seen["causal"] is True
    assert seen["sm_scale"] == pytest.approx(32 ** -0.5)
    # ONE rotary key: the 8 rotary lanes of k are the same in all 4 heads,
    # the 24 un-rotated ones are not
    for head in range(1, 4):
        np.testing.assert_array_equal(k[:, :, head, nope:], k[:, :, 0, nope:])
        assert np.abs(np.asarray(k[:, :, head, :nope]
                                 - k[:, :, 0, :nope])).max() > 0.1
    assert np.abs(np.asarray(q[:, :, 1, nope:] - q[:, :, 0, nope:])
                  ).max() > 0.1                      # a rotary part a head
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        want = ref.latent_qkv(a, p, nope=nope, theta=cfg.rope_theta,
                              eps=cfg.rms_norm_eps)
        want_out = ref.latent_attention(a, p, nope=nope,
                                        theta=cfg.rope_theta,
                                        eps=cfg.rms_norm_eps)
    for got, w in zip((q, k, v), want):
        assert_close(got, w)
    assert_close(out, want_out)
    # shift every position by 5: the 24 un-rotated lanes of q and k (and v)
    # are what they were, to the bit; the rotary lanes turn
    shifted, _ = captured_qkv(cfg, params, a, pos + 5)
    for name in ("q", "k"):
        np.testing.assert_array_equal(shifted[name][..., :nope],
                                      seen[name][..., :nope])
        assert np.abs(np.asarray(shifted[name][..., nope:]
                                 - seen[name][..., nope:])).max() > 0.1
    np.testing.assert_array_equal(shifted["v"], v)
    # ... and q.k depends on the DIFFERENCE of positions only: the scores,
    # so the output, are unchanged by a common shift
    shifted_out = GlmLiteAttention(cfg).apply(params, a, pos + 5)
    assert_close(shifted_out, out, rtol=1e-4)
    with jax.default_matmul_precision("highest"):
        assert_close(shifted["k"], ref.latent_qkv(
            a, p, nope=nope, theta=cfg.rope_theta, eps=cfg.rms_norm_eps,
            positions=jnp.arange(12) + 5)[1])


def test_the_attention_publishes_its_widths():
    import byteps_tpu as bps
    cfg, a, pos, params = attention_inputs()
    GlmLiteAttention(cfg).apply(params, a, pos)
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["mla.head_dim"] == 32.0
    assert gauges["mla.kv_latent_dim"] == 16.0 + 8.0


# ------------------------------------------------- the module's shift

@pytest.mark.parametrize("attn", ["exact", "flash"])
def test_the_module_reads_the_next_token_and_nothing_reads_the_future(attn):
    """Perturb token p.  The main head at position j reads tokens <= j:
    unchanged to the bit for j < p, changed at p.  The module's head at j
    reads the stream up to j AND token j + 1: unchanged for j < p - 1,
    changed at p - 1 — one position earlier, which is the shift."""
    cfg = glm_lite_tiny()
    model, params, batch = model_and_batch(
        cfg, flash if attn == "flash" else None)
    ids, p = batch["input_ids"], 13
    apply = jax.jit(functools.partial(model.apply, logits=True))
    main, module = (np.asarray(x) for x in apply(params, ids))
    main2, module2 = (np.asarray(x) for x in apply(
        params, ids.at[:, p].set((ids[:, p] + 1) % 128)))
    np.testing.assert_array_equal(main2[:, :p], main[:, :p])
    assert np.abs(main2[:, p] - main[:, p]).max() > 1e-3
    np.testing.assert_array_equal(module2[:, :p - 1], module[:, :p - 1])
    assert np.abs(module2[:, p - 1] - module[:, p - 1]).max() > 1e-3


def test_the_module_s_labels_are_the_token_after_next():
    ids = jnp.arange(20).reshape(2, 10)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((2, 1), -1)], axis=1)
    after = np.asarray(mod.mtp_labels(labels))
    np.testing.assert_array_equal(after[:, :-2], ids[:, 2:])
    assert (after[:, -2:] == -1).all()               # no t_(i+2) there
    np.testing.assert_array_equal(mod.next_tokens(ids)[:, :-1], ids[:, 1:])


def test_table_and_head_are_one_leaf_each_with_both_uses_gradients_summed():
    """The gradient of the table (and of the head) under the whole loss is
    the main head's term's + lambda x the module's term's, each computed
    alone — and the module's is not zero: its gather and its head reach
    the same two leaves."""
    import byteps_tpu as bps
    cfg = glm_lite_tiny()
    model, params, batch = model_and_batch(cfg)

    def grads_at(weight):
        m = GlmLite(glm_lite_tiny(mtp_loss_weight=weight))
        g = jax.jit(jax.grad(functools.partial(glm_lite_loss, m)))(params,
                                                                   batch)
        return g["params"]["wte"]["embedding"], g["params"]["lm_head"]

    both, main_only = grads_at(0.3), grads_at(0.0)
    # the module's term alone: (total at 1.0) - (main head's)
    module_only = [a - b for a, b in zip(grads_at(1.0), main_only)]
    for whole, first, second in zip(both, main_only, module_only):
        assert np.abs(np.asarray(second)).max() > 1e-4
        assert_close(whole, first + 0.3 * second, rtol=1e-4)
    # against the reference, leaf by leaf, is the whole-model test's; the
    # gauges say what the second head scored
    glm_lite_loss(model, params, batch)
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["mtp.loss_weight"] == pytest.approx(0.3)
    assert gauges["mtp.positions"] == 2 * (24 - 2)


# ----------------------------------- the flash kernels at head size 256

@pytest.fixture(params=["resident", "spans"])
def form(request, monkeypatch):
    """Both forms of the kernels at test sizes (as
    ``tests/test_flash_attention.py``): the other side resident in VMEM,
    and a grid over spans of two sub-blocks (what 8 192 positions at 256
    lanes get: four spans)."""
    import importlib
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    if request.param == "spans":
        monkeypatch.setattr(fa, "_SPAN_ROWS", 32)
        monkeypatch.setattr(fa, "_RESIDENT_BYTES", 32 * 256 * 4)
    return request.param


def test_flash_at_head_size_256_matches_exact_attention(form):
    """q.k over 192 + 64 lanes with the 64 shared by all heads, v at 256:
    forward and all three gradients through the Pallas interpreter."""
    b, t, h, d = 1, 64, 2, 256
    k_ = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(k_[0], (b, t, h, d))
    k_nope = jax.random.normal(k_[1], (b, t, h, 192))
    k_rope = jnp.broadcast_to(jax.random.normal(k_[2], (b, t, 1, 64)),
                              (b, t, h, 64))
    k = jnp.concatenate([k_nope, k_rope], -1)
    v = jax.random.normal(k_[3], (b, t, h, d))
    w = jax.random.normal(k_[4], (b, t, h, d))

    def got(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                               sm_scale=1 / 16, interpret=True)

    def want(q, k, v):
        return full_attention(q, k, v, causal=True, sm_scale=1 / 16)

    assert_close(got(q, k, v), want(q, k, v))
    g = jax.grad(lambda *a: jnp.sum(got(*a) * w), (0, 1, 2))(q, k, v)
    e = jax.grad(lambda *a: jnp.sum(want(*a) * w), (0, 1, 2))(q, k, v)
    for a, bb, name in zip(g, e, "qkv"):
        assert_close(a, bb, name, rtol=2e-5)


def test_the_cell_s_shape_is_the_long_form_with_four_spans():
    import importlib
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    (q_outer, q_res), (k_outer, k_res) = fa._spans(8192, 8192, 256, 2, 512,
                                                   512)
    assert (q_outer, q_res, k_outer, k_res) == (2048, 2048, 2048, 2048)
    assert 8192 // q_res == 4                        # Mellum's 128: two
    assert fa._spans(8192, 8192, 128, 2, 512, 512)[0][1] == 4096
    assert fa.block_schedule(8192, 8192, True) == {
        "visited": 136, "total": 256, "needed": 136}


# ----------------------------------------------- the head, in blocks

def test_no_whole_logits_in_the_compiled_step(monkeypatch):
    """The step's program holds no [tokens, vocabulary] array, forward or
    backward, for either head; the same model under ``lm_loss`` on whole
    logits does (so the search would find one)."""
    monkeypatch.setattr(gpt, "_LOGIT_BLOCK_BYTES", 16 * 136 * 4)
    cfg = glm_lite_tiny(vocab_size=136)  # 128 is also 4 heads x 32 lanes
    model, params, batch = model_and_batch(cfg, seqs=2, seq_len=32)

    def whole(params, batch):
        main, module = model.apply(params, batch["input_ids"], logits=True)
        return (gpt.lm_loss(main, batch["labels"])
                + 0.3 * gpt.lm_loss(module, mod.mtp_labels(batch["labels"])))

    def text(loss_fn):
        return jax.jit(jax.value_and_grad(loss_fn)).lower(
            params, batch).compile().as_text()

    square = ("f32[64,136]", "f32[2,32,136]")
    blocked = text(functools.partial(glm_lite_loss, model))
    assert not any(s in blocked for s in square)
    assert "f32[16,136]" in blocked                  # a block of 16 rows
    assert any(s in text(whole) for s in square)
    assert_close(glm_lite_loss(model, params, batch), whole(params, batch))
