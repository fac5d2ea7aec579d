"""Mellum 2 on the CPU: the whole model (sliding-window and full layers,
GQA, per-head q/k-norm, YaRN, renormalised top-k) against the plain
reference (tests/mellum_reference.py) with every expert held and with
each chip's share; the shares of the expert layer against the uncut
layer; YaRN's tables against the formula; the fused DP step.

Tolerances.  Both sides compute in float32 at full precision and differ
in SUMMATION ORDER only (the layer adds k expert outputs a token, the
reference ``count`` terms of which all but a few are zero; the grouped
matmul accumulates per tile; the flash kernels fold the softmax
blockwise).  Logits, loss and the expert layer agree to rtol 1e-5 (with
an absolute floor of 1e-5 of each array's largest magnitude).  The
gradients of the EIGHT-layer model are held to 5e-5 of each leaf's
largest magnitude: a share's router gradient is what is left after the
softmax's terms cancel, and float32 noise through eight layers reaches
2.3e-5 there (6.9e-6 with exact attention) where every other leaf stays
under 5e-6.  A layer computed in bfloat16 (ulp 4e-3) fails either by two
orders of magnitude.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from . import mellum_reference as ref
from .jaxpr_count import equations
from byteps_tpu.comm.mesh import CommContext, _build_mesh
from byteps_tpu.models.llama import (apply_rope, repeat_kv,
                                     rope_frequencies, yarn_inv_freq)
from byteps_tpu.models.mellum import (FULL, SLIDING, Mellum, MellumConfig,
                                      banded_attention, expert_counts,
                                      mellum_loss, mellum_tiny)
from byteps_tpu.ops import flash_attention
from byteps_tpu.ops.moe_kernels import _grouped_matmul
from byteps_tpu.parallel import make_dp_train_step, replicate
from byteps_tpu.parallel.expert import dropless_moe_mlp, publish_moe_stats

RTOL = 1e-5
GRAD_RTOL = 5e-5
H, F, E = 32, 16, 8
SHARES = [(0, 2), (2, 2), (4, 2), (6, 2)]
flash = functools.partial(flash_attention, interpret=True, block_q=8,
                          block_k=8)


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

def model_and_batch(cfg, attn_fn=None, seqs=2, seq_len=24, seed=0):
    """The parameters of a share are drawn for the share (its own stacks);
    scales and router moved off their symmetric initial values, so a
    missing norm or a transposed router would show."""
    model = Mellum(cfg, attn_fn=attn_fn)
    ids = jax.random.randint(jax.random.PRNGKey(seed), (seqs, seq_len), 0,
                             cfg.vocab_size)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((seqs, 1), -1)], axis=1)
    params = model.init(jax.random.PRNGKey(seed + 1), ids)
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(9), a.shape),
        params)
    return model, params, {"input_ids": ids, "labels": labels}


def reference_kw(cfg):
    return dict(layer_types=cfg.layer_types, heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads, window=cfg.sliding_window,
                rope_parameters=cfg.rope_parameters,
                top_k=cfg.num_experts_per_tok, held=cfg.experts_held,
                eps=cfg.rms_norm_eps)


def test_tiny_is_two_periods_with_a_window_shorter_than_the_sequence():
    cfg = mellum_tiny()
    assert cfg.layer_types == (SLIDING, SLIDING, SLIDING, FULL) * 2
    assert cfg.sliding_window < 24 and cfg.held == (0, 8)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads) == (4, 2)
    assert cfg.rope_parameters[FULL]["rope_type"] == "yarn"
    published = MellumConfig()
    assert published.layer_types.count(SLIDING) == 21
    assert published.rope_parameters[FULL]["attention_factor"] == (
        pytest.approx(0.1 * math.log(16) + 1))


@pytest.mark.parametrize("held,attn", [
    (None, "exact"), ((2, 2), "exact"),      # the model's own exact band
    *[(h, "flash") for h in [None] + SHARES]], ids=str)
def test_model_loss_and_gradients_match_the_reference(held, attn):
    cfg = mellum_tiny(held)
    model, params, batch = model_and_batch(
        cfg, flash if attn == "flash" else None)
    assert list(params) == ["params"]                # init sows nothing
    assert params["params"]["h0"]["moe"]["gate"].shape[0] == cfg.held[1]
    assert params["params"]["h0"]["moe"]["router"].shape == (32, 8)
    loss, grads = jax.jit(jax.value_and_grad(
        functools.partial(mellum_loss, model)))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        functools.partial(ref.loss, **reference_kw(cfg))))(params, batch)
    assert_close(loss, want)
    assert_trees_close(grads, want_grads, GRAD_RTOL)


@pytest.mark.parametrize("held", [None, (4, 2)], ids=str)
def test_model_logits_and_counts_match_the_reference(held):
    cfg = dataclasses.replace(mellum_tiny(held), remat=True)
    model, params, batch = model_and_batch(cfg, flash)
    logits = jax.jit(model.apply)(params, batch["input_ids"])
    with jax.default_matmul_precision("highest"):
        want, _, want_counts = ref.forward(params, batch["input_ids"],
                                           **reference_kw(cfg))
    assert_close(logits, want)
    counts = expert_counts(model, params, batch["input_ids"])
    assert counts.shape == (cfg.num_hidden_layers, cfg.num_experts)
    np.testing.assert_array_equal(counts, want_counts)


def _break(monkeypatch, what):
    """One deliberate fault in what the program computes."""
    import byteps_tpu.models.mellum as mod
    if what == "window_off_by_a_sub_block":
        real = mod.banded_attention
        monkeypatch.setattr(mod, "banded_attention", lambda *a, **kw: real(
            *a, **{**kw, **({"window": kw["window"] + 8}
                            if "window" in kw else {})}))
    elif what == "weights_not_renormalised":
        real = mod.dropless_moe_mlp
        monkeypatch.setattr(mod, "dropless_moe_mlp", lambda *a, **kw: real(
            *a, **{**kw, "renormalize": False}))
    elif what == "no_attention_factor":
        real = mod.rope_frequencies

        def plain_scale(d, pos, theta, yarn=None):
            if yarn is not None:
                yarn = {**yarn, "attention_factor": 1.0}
            return real(d, pos, theta, yarn=yarn)
        monkeypatch.setattr(mod, "rope_frequencies", plain_scale)


@pytest.mark.parametrize("what", ["window_off_by_a_sub_block",
                                  "weights_not_renormalised",
                                  "no_attention_factor"])
def test_the_comparison_fails_each_deliberate_break(monkeypatch, what):
    """The tolerance is tight enough: a window one (8-key) sub-block too
    wide, unrenormalised weights and a missing ``attention_factor`` each
    move the logits past it by orders of magnitude."""
    cfg = mellum_tiny((2, 2))
    _break(monkeypatch, what)
    model, params, batch = model_and_batch(cfg)
    logits = model.apply(params, batch["input_ids"])
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.forward(params, batch["input_ids"],
                                 **reference_kw(cfg))
    err = float(jnp.abs(logits - want).max() / jnp.abs(want).max())
    assert err > 100 * RTOL, (what, err)


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(ValueError, match="layer_types"):
        MellumConfig(num_hidden_layers=4)
    with pytest.raises(ValueError, match="unknown layer type"):
        MellumConfig(num_hidden_layers=1, layer_types=("chunked_attention",))
    with pytest.raises(ValueError, match="experts_held"):
        MellumConfig(experts_held=(56, 16))
    with pytest.raises(ValueError, match="divisible"):
        MellumConfig(num_key_value_heads=5)


def test_dp_step_is_the_mean_of_the_shards():
    """One ``make_dp_train_step`` step of a share on the 4-device mesh:
    routing and the router term are shard-local, so loss and update are
    those of the MEAN of the four shards' single-device losses and
    gradients."""
    cfg = dataclasses.replace(mellum_tiny((2, 2)), num_hidden_layers=4,
                              layer_types=(SLIDING,) * 3 + (FULL,))
    model, params, batch = model_and_batch(cfg, seqs=4, seq_len=16)
    loss_fn = functools.partial(mellum_loss, model)
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
                       mean_grads, GRAD_RTOL)


# ------------------------------------------- the share ties to the model

def layer_params(seed=0, router=None):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"router": (jax.random.normal(k[0], (H, E)) if router is None
                       else router),
            "gate": jax.random.normal(k[1], (E, H, F)) / np.sqrt(H),
            "up": jax.random.normal(k[2], (E, H, F)) / np.sqrt(H),
            "down": jax.random.normal(k[3], (E, F, H)) / np.sqrt(F)}


def share_of(params, held):
    first, count = held
    return {"router": params["router"],
            **{k: params[k][first:first + count]
               for k in ("gate", "up", "down")}}


def tokens(n, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, H))


def held_layer(x, params, held, top_k=2, renormalize=True):
    return jax.jit(functools.partial(
        dropless_moe_mlp, top_k=top_k, held=held,
        renormalize=renormalize))(x, share_of(params, held))


@pytest.mark.parametrize("renormalize", [True, False])
def test_the_four_shares_add_up_to_the_uncut_layer(renormalize):
    """Guide section 4: the parts of the result that all the shares give
    add up to what the uncut reference gives for the whole layer; the
    router statistics are the whole layer's in every share."""
    params, x = layer_params(), tokens(48)
    with jax.default_matmul_precision("highest"):
        want_y, want_aux, want_counts = ref.moe(x, params, 2, None,
                                                renormalize)
    parts = [held_layer(x, params, h, renormalize=renormalize)
             for h in SHARES]
    assert_close(sum(p[0] for p in parts), want_y)
    for (y, aux, _, counts), h in zip(parts, SHARES):
        assert_close(aux, want_aux)
        np.testing.assert_array_equal(counts, want_counts)
        with jax.default_matmul_precision("highest"):
            assert_close(y, ref.moe(x, share_of(params, h), 2, h,
                                    renormalize)[0], str(h))
    whole = jax.jit(functools.partial(dropless_moe_mlp, top_k=2,
                                      renormalize=renormalize))(x, params)
    assert_close(whole[0], want_y)


def test_a_token_with_no_held_expert_gets_exactly_zero():
    params, x = layer_params(), tokens(48)
    held = (2, 2)
    y, _, _, counts = held_layer(x, params, held)
    probs = jax.nn.softmax(x @ params["router"], -1)
    chosen = np.asarray(jax.lax.top_k(probs, 2)[1])
    none_held = ~np.isin(chosen, [2, 3]).any(axis=1)
    assert 5 < none_held.sum() < 43                  # both kinds of token
    assert not np.asarray(y)[none_held].any()        # exactly zero
    assert np.abs(np.asarray(y)[~none_held]).min(axis=1).max() > 0
    assert int(counts.sum()) == 96                   # counts: every pair


def _steered(columns, value):
    router = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (H, E))
    for c in columns:
        router = router.at[:, c].set(value)
    return router


def test_no_pair_routed_to_a_held_expert_is_dropped_at_any_routing():
    """Every token to the held experts: the share IS the layer, all
    ``N x k`` pair rows live.  Every token to none of them: zero output,
    zero gradient to the stacks, the router statistics unchanged."""
    x = jnp.abs(tokens(32)) + 0.1                    # positive-sum tokens
    held = (2, 2)
    params = layer_params(router=_steered((2, 3), 2.0))
    y, aux, _, counts = held_layer(x, params, held)
    assert np.asarray(counts)[2:4].tolist() == [32, 32]
    with jax.default_matmul_precision("highest"):
        want, want_aux, _ = ref.moe(x, params, 2)
    assert_close(y, want)
    assert_close(aux, want_aux)

    params = layer_params(router=_steered((2, 3), -2.0))
    y, aux, _, counts = held_layer(x, params, held)
    assert np.asarray(counts)[2:4].tolist() == [0, 0]
    assert int(counts.sum()) == 64 and not np.asarray(y).any()

    def objective(p):
        return jnp.sum(dropless_moe_mlp(x, p, 2, held=held,
                                        renormalize=True)[0] ** 2)
    grads = jax.jit(jax.grad(objective))(share_of(params, held))
    assert not any(np.asarray(g).any() for g in jax.tree.leaves(grads))


def test_share_gradients_match_the_dense_reference():
    params, x = layer_params(), tokens(48)
    held = (4, 2)
    cot = jnp.cos(jnp.arange(48 * H, dtype=jnp.float32)).reshape(48, H)

    def got(p, x):
        y, aux, _, _ = dropless_moe_mlp(x, p, 2, held=held,
                                        renormalize=True)
        return jnp.sum(y * cot) + 0.3 * aux

    def want(p, x):
        with jax.default_matmul_precision("highest"):
            y, aux, _ = ref.moe(x, p, 2, held)
        return jnp.sum(y * cot) + 0.3 * aux

    share = share_of(params, held)
    assert_trees_close(jax.jit(jax.grad(got, (0, 1)))(share, x),
                       jax.grad(want, (0, 1))(share, x))


def test_grouped_matmul_zeroes_the_rows_of_groups_it_does_not_hold():
    """Read from megablox's source (``gmm.py``
    ``_zero_uninitialized_memory``) and pinned here: with a group offset
    the rows of every other group come back exactly zero, whatever their
    inputs hold, and the held groups' rows are the plain products."""
    sizes = jnp.asarray([5, 0, 11, 8, 3, 13, 0, 8], jnp.int32)   # 48 rows
    x = tokens(48).at[:5].set(1e30).at[27:].set(-1e30)  # dead rows: junk
    w = layer_params()["gate"][2:5]
    out = np.asarray(jax.jit(functools.partial(
        _grouped_matmul, interpret=True))(x, w, sizes,
                                          first=jnp.int32(2)))
    assert not out[:5].any() and not out[27:].any()
    with jax.default_matmul_precision("highest"):
        assert_close(out[5:16], x[5:16] @ w[0])
        assert_close(out[16:24], x[16:24] @ w[1])
        assert_close(out[24:27], x[24:27] @ w[2])


def test_held_none_traces_to_the_program_it_was():
    """``held=None, renormalize=False`` is OLMoE's layer, equation for
    equation: the counts are PR 41's (the route stage selects in one
    kernel; before it they were PR 28's 59 / 1074 and 155 / 3306), forward
    and forward + backward, nested jaxprs included."""
    params, x = layer_params(), tokens(48)

    def layer(x, p):
        return dropless_moe_mlp(x, p, 2, interpret=True)

    def grad(x, p):
        return jax.grad(lambda x, p: layer(x, p)[0].sum(), (0, 1))(x, p)

    fwd = jax.make_jaxpr(layer)(x, params).jaxpr
    assert (len(fwd.eqns), equations(fwd)) == (51, 1180)
    bwd = jax.make_jaxpr(grad)(x, params).jaxpr
    assert (len(bwd.eqns), equations(bwd)) == (155, 3422)


def test_publish_moe_stats_sets_the_share_gauges():
    import byteps_tpu as bps
    counts = np.asarray([[4, 4, 10, 2, 4, 4, 4, 0],
                         [4, 4, 4, 4, 4, 4, 4, 4]])
    publish_moe_stats(counts)
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["moe.load_max_over_mean"] == pytest.approx(10 / 4)
    publish_moe_stats(counts, held=(2, 2))
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["moe.load_max_over_mean"] == pytest.approx(10 / 4)
    assert gauges["moe.held_pair_share"] == pytest.approx(20 / 64)
    assert gauges["moe.held_load_max_over_mean"] == pytest.approx(10 / 6)


# ------------------------------------------------------ rotary, GQA, band

def test_yarn_tables_match_the_formula_at_the_published_constants():
    """HF ``_compute_yarn_parameters`` written out with the config's
    numbers: theta 500 000, factor 16, original context 8192, beta 32 / 1,
    64 pairs; cos and sin times 0.1 ln 16 + 1."""
    d, theta, factor, orig = 128, 500000.0, 16.0, 8192
    lo = math.floor(64 * math.log(orig / (32 * 2 * math.pi))
                    / math.log(theta))
    hi = math.ceil(64 * math.log(orig / (1 * 2 * math.pi))
                   / math.log(theta))
    assert (lo, hi) == (18, 35)
    i = np.arange(64)
    f = theta ** (2 * i / d)
    r = 1 - np.clip((i - lo) / (hi - lo), 0, 1)
    want_inv = (1 - r) / (factor * f) + r / f
    np.testing.assert_allclose(yarn_inv_freq(d, theta, factor, orig, 32, 1),
                               want_inv, rtol=1e-12)
    assert want_inv[18] == pytest.approx(1 / f[18])            # kept
    assert want_inv[35] == pytest.approx(1 / (16 * f[35]))     # stretched
    yarn = MellumConfig().rope_parameters[FULL]
    pos = jnp.arange(0, 8192, 37)[None]
    cos, sin = rope_frequencies(d, pos, theta, yarn=yarn)
    ang = np.asarray(pos, np.float64)[..., None] * want_inv
    scale = 0.1 * math.log(16) + 1
    # float32 angles of up to 8192 radians: 8192 x 2^-24 = 5e-4 absolute
    np.testing.assert_allclose(cos, np.cos(ang) * scale, atol=2e-3)
    np.testing.assert_allclose(sin, np.sin(ang) * scale, atol=2e-3)
    assert float(jnp.max(cos)) == pytest.approx(scale)


def test_default_rotary_path_is_unchanged():
    pos = jnp.arange(64)[None]
    cos, sin = rope_frequencies(16, pos, 10000.0)
    inv = 1.0 / (10000.0 ** (jnp.arange(0, 16, 2, dtype=jnp.float32) / 16))
    ang = pos[..., None].astype(jnp.float32) * inv
    np.testing.assert_array_equal(cos, jnp.cos(ang))
    np.testing.assert_array_equal(sin, jnp.sin(ang))
    text = str(jax.make_jaxpr(lambda p: rope_frequencies(16, p, 1e4))(pos))
    assert "mul" in text and text.count("cos") == 1
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, 16))
    assert apply_rope(x, cos, sin).shape == x.shape


def test_repeat_kv_is_grouped_attention():
    k = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 4))
    rk, rv = repeat_kv(k, 2 * k, 3)
    assert rk.shape == (1, 8, 6, 4)
    for g in range(6):                       # query head g reads g // 3
        np.testing.assert_array_equal(rk[:, :, g], k[:, :, g // 3])
        np.testing.assert_array_equal(rv[:, :, g], 2 * k[:, :, g // 3])
    assert repeat_kv(k, k, 1)[0] is k


def test_banded_attention_is_the_band_mask():
    q, k, v = (jax.random.normal(jax.random.PRNGKey(s), (1, 12, 2, 8))
               for s in range(3))
    out = banded_attention(q, k, v, window=5)
    i, j = np.arange(12)[:, None], np.arange(12)[None]
    keep = (j <= i) & (i - j < 5)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(8)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    assert_close(out, jnp.einsum("bhqk,bkhd->bqhd", p, v))
    assert keep[7].sum() == 5 and keep[2].sum() == 3   # itself + 4 before
