"""OLMoE on the CPU: the dropless top-k expert layer and the whole model
against the plain reference (tests/olmoe_reference.py), the fused DP
step on the 4-device mesh, and the load gauges.  (The layer's compile for
a described v5e at the published widths is in tests/test_v5e_compile.py,
with every other test that describes a TPU topology: one libtpu per
process, so one file.)

Tolerance: rtol 1e-5 (with an absolute floor of 1e-5 of each array's
largest magnitude).  Both sides compute in float32 at full precision;
they differ in SUMMATION ORDER only — the layer adds k expert outputs
per token, the reference 64 terms of which all but k are zero, the
grouped matmul accumulates per tile — so they agree to a few float32
ulps (~1e-6), and a layer computed in bfloat16 (ulp 4e-3) fails by
three orders of magnitude.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from . import jaxpr_count
from . import olmoe_reference as ref
from byteps_tpu.comm.mesh import CommContext, _build_mesh
from byteps_tpu.models import gpt
from byteps_tpu.models.gpt import lm_loss
from byteps_tpu.models.olmoe import (Olmoe, OlmoeConfig, _sown,
                                     expert_counts, olmoe_loss, olmoe_tiny)
from byteps_tpu.parallel import make_dp_train_step, replicate
from byteps_tpu.parallel.expert import dropless_moe_mlp, publish_moe_stats

RTOL = 1e-5
H, F, E = 32, 16, 8


def assert_close(got, want, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=RTOL,
                               atol=RTOL * max(1e-30, np.abs(want).max()),
                               err_msg=what)


def assert_trees_close(got, want):
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        assert_close(g, flat_want[path], jax.tree_util.keystr(path))


def layer_params(seed=0, router=None):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"router": (jax.random.normal(k[0], (H, E)) if router is None
                       else router),
            "gate": jax.random.normal(k[1], (E, H, F)) / np.sqrt(H),
            "up": jax.random.normal(k[2], (E, H, F)) / np.sqrt(H),
            "down": jax.random.normal(k[3], (E, F, H)) / np.sqrt(F)}


def tokens(n, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, H))


def layer_objective(fn, top_k):
    """Scalar through every output (y against a fixed cotangent, both
    router losses), so one gradient checks all of them."""
    def f(params, x):
        y, aux, z, _ = fn(x, params, top_k)
        cot = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)
        return jnp.sum(y * cot) + 0.3 * aux + 0.7 * z
    return f


def reference_layer(x, params, top_k):
    with jax.default_matmul_precision("highest"):
        return ref.moe(x, params, top_k)


@pytest.mark.parametrize("top_k", [1, 2, 8])
def test_layer_matches_dense_reference_outputs_and_gradients(top_k):
    params, x = layer_params(), tokens(48)
    got = jax.jit(functools.partial(dropless_moe_mlp, top_k=top_k))(x, params)
    want = reference_layer(x, params, top_k)
    for g, w, what in zip(got, want, ("y", "aux", "z")):
        assert_close(g, w, what)
    np.testing.assert_array_equal(got[3], want[3])
    assert int(got[3].sum()) == 48 * top_k           # dropless: every pair
    grads = jax.jit(jax.grad(layer_objective(dropless_moe_mlp, top_k),
                             argnums=(0, 1)))(params, x)
    want_grads = jax.grad(layer_objective(reference_layer, top_k),
                          argnums=(0, 1))(params, x)
    assert_trees_close(grads, want_grads)


def test_layer_with_an_empty_expert_and_one_that_takes_every_token():
    # column 3 of the router sees a huge logit for every (positive-sum)
    # token, column 5 a hugely negative one: expert 3 is every token's
    # first choice, expert 5 nobody's
    x = jnp.abs(tokens(32)) + 0.1
    router = jax.random.normal(jax.random.PRNGKey(7), (H, E)) * 0.1
    router = router.at[:, 3].set(2.0).at[:, 5].set(-2.0)
    params = layer_params(router=router)
    got = jax.jit(functools.partial(dropless_moe_mlp, top_k=2))(x, params)
    counts = np.asarray(got[3])
    assert counts[3] == 32 and counts[5] == 0 and counts.sum() == 64
    want = reference_layer(x, params, 2)
    for g, w, what in zip(got, want, ("y", "aux", "z")):
        assert_close(g, w, what)
    grads = jax.jit(jax.grad(layer_objective(dropless_moe_mlp, 2),
                             argnums=(0, 1)))(params, x)
    want_grads = jax.grad(layer_objective(reference_layer, 2),
                          argnums=(0, 1))(params, x)
    assert_trees_close(grads, want_grads)
    for name in ("gate", "up", "down"):             # no token, no gradient
        assert not np.asarray(grads[0][name][5]).any()


def test_top_k_weights_are_not_renormalised():
    """With ONE expert a token (k = 1) the output is p_max * expert(x); a
    renormalised weight would be exactly 1.  Checked without the
    reference, so a renormalising reference could not hide it."""
    router = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (H, E))
    params, x = layer_params(router=router), tokens(16)
    y, _, _, _ = dropless_moe_mlp(x, params, 1)
    probs = jax.nn.softmax(x @ params["router"], -1)
    top, p_top = jnp.argmax(probs, -1), jnp.max(probs, -1)
    with jax.default_matmul_precision("highest"):
        expert = jnp.einsum(
            "nf,nfh->nh",
            jax.nn.silu(jnp.einsum("nh,nhf->nf", x, params["gate"][top]))
            * jnp.einsum("nh,nhf->nf", x, params["up"][top]),
            params["down"][top])
    assert float(p_top.max()) < 0.6                  # far from 1
    assert_close(y, p_top[:, None] * expert)
    assert not np.allclose(y, expert, rtol=1e-2)


def test_rows_must_tile():
    with pytest.raises(ValueError, match="multiple of 8"):
        dropless_moe_mlp(tokens(5), layer_params(), 1)


# ------------------------------------------------------------ whole model

def model_and_batch(cfg=None, seqs=2, seq_len=16, seed=0):
    cfg = cfg or olmoe_tiny()
    model = Olmoe(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed), (seqs, seq_len), 0,
                             cfg.vocab_size)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((seqs, 1), -1)], axis=1)
    params = model.init(jax.random.PRNGKey(seed + 1), ids)
    # scales and router away from their symmetric initial values, so a
    # missing norm or a transposed router would show
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(9), a.shape),
        params)
    return model, params, {"input_ids": ids, "labels": labels}


def reference_kw(cfg):
    return dict(heads=cfg.num_attention_heads,
                top_k=cfg.num_experts_per_tok, theta=cfg.rope_theta,
                eps=cfg.rms_norm_eps)


@pytest.mark.parametrize("remat", [False, True])
def test_model_loss_and_gradients_match_the_reference(remat):
    cfg = dataclasses.replace(olmoe_tiny(), remat=remat)
    model, params, batch = model_and_batch(cfg)
    assert list(params) == ["params"]                # init sows nothing
    loss, grads = jax.jit(jax.value_and_grad(
        functools.partial(olmoe_loss, model)))(params, batch)
    want, want_grads = jax.value_and_grad(
        functools.partial(ref.loss, **reference_kw(cfg)))(params, batch)
    assert_close(loss, want)
    assert_trees_close(grads, want_grads)
    # both router terms are in that loss (>= 0.01 x layers and ~0.001 x
    # layers x log(E)^2 of a loss of ~5): dropping either fails rtol 1e-5


def test_model_logits_and_counts_match_the_reference():
    model, params, batch = model_and_batch()
    cfg = model.cfg
    logits = model.apply(params, batch["input_ids"])
    with jax.default_matmul_precision("highest"):
        want, _, _, want_counts = ref.forward(params, batch["input_ids"],
                                              **reference_kw(cfg))
    assert_close(logits, want)
    counts = expert_counts(model, params, batch["input_ids"])
    assert counts.shape == (cfg.num_hidden_layers, cfg.num_experts)
    np.testing.assert_array_equal(counts, want_counts)


# ----------------------------------------- the loss through the blocked head

def loss_on_whole_logits(model, params, batch):
    """``olmoe_loss`` as it was before the head went through
    ``blocked_token_nll``: ``lm_loss`` on the [B, T, V] float32 logits of
    the default ``apply``, plus the two router terms."""
    cfg = model.cfg
    logits, sown = model.apply(params, batch["input_ids"],
                               mutable=["moe_aux"])
    return (lm_loss(logits, batch["labels"])
            + cfg.router_aux_loss_coef * sum(_sown(model, sown["moe_aux"],
                                                   "aux"))
            + cfg.router_z_loss_coef * sum(_sown(model, sown["moe_aux"],
                                                 "z")))


@pytest.mark.parametrize("labels", ["shifted", "some_ignored",
                                    "all_ignored"])
@pytest.mark.parametrize("blocks", [1, 4])
def test_loss_through_the_blocked_head_is_lm_loss_on_whole_logits(
        monkeypatch, labels, blocks):
    """Value and every parameter's gradient — ``lm_head/kernel`` [h, V] in
    its own layout, and everything below the hidden rows — in one block
    and in four."""
    import byteps_tpu as bps
    model, params, batch = model_and_batch()
    v = model.cfg.vocab_size
    monkeypatch.setattr(gpt, "_LOGIT_BLOCK_BYTES", 32 // blocks * v * 4)
    if labels == "some_ignored":
        keep = jax.random.uniform(jax.random.PRNGKey(3), (2, 16)) < 0.6
        batch["labels"] = jnp.where(keep, batch["labels"], -1)
    elif labels == "all_ignored":
        batch["labels"] = jnp.full((2, 16), -1)
    loss, grads = jax.jit(jax.value_and_grad(
        functools.partial(olmoe_loss, model)))(params, batch)
    want, want_grads = jax.value_and_grad(
        functools.partial(loss_on_whole_logits, model))(params, batch)
    assert_close(loss, want)
    assert_trees_close(grads, want_grads)
    kernel = grads["params"]["lm_head"]["kernel"]
    assert kernel.shape == (32, v) and kernel.dtype == jnp.float32
    assert bool(jnp.any(kernel != 0)) == (labels != "all_ignored")
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["head.logit_blocks"] == blocks
    assert gauges["head.logit_block_bytes"] == 32 // blocks * v * 4


def test_the_loss_holds_no_whole_logits():
    """Nothing [tokens, vocabulary]-sized outside the head's scan, forward
    or backward; the default ``apply`` still makes them."""
    model, params, batch = model_and_batch(seqs=3)   # 48 rows: not h
    n, v = 48, model.cfg.vocab_size

    def sizes(jaxpr, out, inside=False):
        for eqn in jaxpr.eqns:
            if not inside:
                out.update(var.aval.shape for var in eqn.outvars)
            for inner in jaxpr_count._inner_jaxprs(eqn):
                sizes(inner, out, inside or eqn.primitive.name == "scan")
        return out

    loss = functools.partial(olmoe_loss, model)
    seen = sizes(jax.make_jaxpr(jax.value_and_grad(loss))(params, batch).jaxpr,
                 set())
    assert not [s for s in seen if s[-1:] == (v,) and np.prod(s) == n * v]
    seen = sizes(jax.make_jaxpr(
        lambda p: model.apply(p, batch["input_ids"]))(params).jaxpr, set())
    assert (3, 16, v) in seen


def test_default_apply_and_init_are_what_they_were():
    """float32 logits [B, T, V] unless asked for the hidden rows, and the
    parameter tree of ``init`` key for key and shape for shape (the
    checkpoints' and the benchmark family's), however ``init`` is
    called."""
    model, params, batch = model_and_batch()
    cfg, ids = model.cfg, batch["input_ids"]
    logits = model.apply(params, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    rows = model.apply(params, ids, logits=False)
    assert rows.shape == (2, 16, cfg.hidden_size)
    with jax.default_matmul_precision("highest"):
        assert_close(rows @ params["params"]["lm_head"]["kernel"], logits)
    h, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    layer = {"attn_norm": {"scale": (h,)}, "moe_norm": {"scale": (h,)},
             "attn": {**{f"{p}_proj": {"kernel": (h, h)} for p in "qkvo"},
                      "q_norm": {"scale": (h,)}, "k_norm": {"scale": (h,)}},
             "moe": {"router": (h, e), "gate": (e, h, f), "up": (e, h, f),
                     "down": (e, f, h)}}
    want = {"params": {"wte": {"embedding": (cfg.vocab_size, h)},
                       "h0": layer, "h1": layer,
                       "norm_f": {"scale": (h,)},
                       "lm_head": {"kernel": (h, cfg.vocab_size)}}}
    for kw in ({}, {"logits": False}):
        made = model.init(jax.random.PRNGKey(1), ids, **kw)
        assert jax.tree.map(lambda a: a.shape, made) == want
        assert {a.dtype for a in jax.tree.leaves(made)} == {
            jnp.dtype("float32")}


def test_config_refuses_grouped_kv_heads():
    with pytest.raises(ValueError, match="MHA"):
        OlmoeConfig(num_key_value_heads=4)


def test_dp_step_is_the_mean_of_the_shards():
    """One make_dp_train_step step on the 4-device mesh: routing and both
    router terms are shard-local, so loss and update are those of the MEAN
    of the four shards' single-device losses and gradients."""
    model, params, batch = model_and_batch(seqs=4)
    loss_fn = functools.partial(olmoe_loss, model)
    tx = optax.sgd(1.0)                  # the update IS the mean gradient
    comm = CommContext(mesh=_build_mesh(jax.devices()[:4], 1),
                       n_dcn=1, n_ici=4)
    step = make_dp_train_step(comm, loss_fn, tx, donate=False)
    new_params, _, loss = step(replicate(comm, params),
                               replicate(comm, tx.init(params)), batch)
    one_shard = jax.jit(jax.value_and_grad(loss_fn))
    shards = [one_shard(params, jax.tree.map(lambda a: a[i:i + 1], batch))
              for i in range(4)]
    assert_close(loss, np.mean([float(s[0]) for s in shards]))
    mean_grads = jax.tree.map(lambda *g: sum(g) / 4, *[s[1] for s in shards])
    assert_trees_close(jax.tree.map(jnp.subtract, params, new_params),
                       mean_grads)
    # and NOT the gradient of the whole batch routed as one shard: the
    # load-balance term of a mean of shards differs from that of the batch
    whole = jax.jit(jax.grad(loss_fn))(params, batch)
    router = lambda t: np.asarray(t["params"]["h0"]["moe"]["router"])
    assert not np.allclose(router(mean_grads), router(whole), rtol=1e-3)


def test_publish_moe_stats_sets_the_gauges():
    import byteps_tpu as bps
    model, params, batch = model_and_batch()
    counts = np.asarray(expert_counts(model, params, batch["input_ids"]))
    publish_moe_stats(counts)
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["moe.tokens_per_expert_min"] == counts.min()
    assert gauges["moe.tokens_per_expert_max"] == counts.max()
    assert gauges["moe.load_max_over_mean"] == pytest.approx(
        (counts.max(1) / counts.mean(1)).max())
    publish_moe_stats(np.full((8,), 5))              # one layer, balanced
    assert bps.metrics_snapshot()["gauges"]["moe.load_max_over_mean"] == 1.0
