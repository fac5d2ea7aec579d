"""ZAYA1 on the CPU: the whole model (compressed convolutional attention,
the router's carried state, top-1 experts, the tied head in blocks)
against the plain reference (tests/zaya_reference.py) with every expert
held and with each chip's share; the shares of the expert sublayer
against the uncut layer; causality of the convolutions and of the value
shift; routing handed to ``dropless_moe_mlp`` from outside; the blocked
head against ``lm_loss`` on whole logits; the fused DP step.

Tolerances.  Both sides compute in float32 at full precision and differ
in SUMMATION ORDER only (the grouped matmul accumulates per tile, the
flash kernels fold the softmax blockwise, the head sums its blocks).
Logits and loss agree to rtol 1e-5 (with an absolute floor of 1e-5 of
each array's largest magnitude); the gradients are held to 5e-5 of each
leaf's largest magnitude (the router's leaves are what is left after a
softmax's terms cancel: ``gamma`` reads 2e-5 on the benchmark's toy).  A
layer computed in bfloat16 (ulp 4e-3) fails either by two orders of
magnitude; the bfloat16 case below is held to the loss alone.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from . import jaxpr_count
from . import zaya_reference as ref
from byteps_tpu.comm.mesh import CommContext, _build_mesh
from byteps_tpu.models import gpt
from byteps_tpu.models.gpt import (blocked_lm_loss, blocked_token_nll,
                                   lm_loss, logit_block_rows, token_nll)
from byteps_tpu.models.llama import apply_rope, rope_frequencies
from byteps_tpu.models.zaya import (HYBRID, Zaya, ZayaConfig, ZayaSparseMoe,
                                    causal_convs, expert_counts,
                                    token_before, zaya_loss, zaya_tiny)
from byteps_tpu.ops import flash_attention
from byteps_tpu.parallel import make_dp_train_step, replicate
from byteps_tpu.parallel.expert import (dropless_moe_mlp, publish_moe_stats,
                                        row_schedule)

RTOL = 1e-5
GRAD_RTOL = 5e-5
SHARES = [(0, 4), (4, 4)]
flash = functools.partial(flash_attention, interpret=True, block_q=8,
                          block_k=8)


def _gradcheck():
    """``benchmarks/tests/gradcheck_zaya.py``: the six deliberate breaks
    are defined once, beside the chip's comparison."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "tests",
        "gradcheck_zaya.py")
    spec = importlib.util.spec_from_file_location("gradcheck_zaya", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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

def moved(params):
    """Every leaf off its initial value by noise of 0.1 (``beta`` by 0.01:
    it is added to probabilities of ~1/8 and must not choose alone)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + (0.01 if "balance_bias" in jax.tree_util.keystr(
            path) else 0.1) * jax.random.normal(jax.random.PRNGKey(9),
                                                a.shape), params)


def model_and_batch(cfg, attn_fn=None, seqs=2, seq_len=24, seed=0):
    """The parameters of a share are drawn for the share (its own stacks);
    scales, biases, temperatures, ``gamma`` and ``beta`` moved off their
    symmetric initial values, so a missing norm, a dropped bias or an
    unused selection bias would show."""
    model = Zaya(cfg, attn_fn=attn_fn)
    ids = jax.random.randint(jax.random.PRNGKey(seed), (seqs, seq_len), 0,
                             cfg.vocab_size)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((seqs, 1), -1)], axis=1)
    params = moved(model.init(jax.random.PRNGKey(seed + 1), ids))
    return model, params, {"input_ids": ids, "labels": labels}


def reference_kw(cfg):
    return dict(layers=cfg.num_hidden_layers, heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads,
                theta=float(cfg.rope_parameters[HYBRID]["rope_theta"]),
                rot=cfg.rotary_dim, held=cfg.experts_held,
                eps=cfg.rms_norm_eps)


def test_tiny_carries_the_router_state_across_two_joints():
    cfg = zaya_tiny()
    assert cfg.num_hidden_layers == 3 and cfg.rotary_dim == 8
    assert (cfg.cca_time0, cfg.cca_time1, cfg.num_experts_per_tok) == (2, 2, 1)
    _, params, _ = model_and_batch(cfg)
    p = params["params"]
    assert list(params) == ["params"]                # init sows nothing
    assert "gamma" not in p["h0"]["moe"]["router"]   # no layer before it
    assert p["h1"]["moe"]["router"]["gamma"].shape == ()
    assert p["h0"]["attn_cca"]["conv1_kernel"].shape == (6, 2, 16, 16)
    assert "lm_head" not in p                        # the table is tied


def test_published_defaults_are_the_source_s():
    cfg = ZayaConfig()
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (2048, 8, 2, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.router_hidden_size) == (
                16, 1, 2048, 256)
    assert cfg.rotary_dim == 64 and cfg.vocab_size == 262272
    assert cfg.rope_parameters[HYBRID]["rope_theta"] == 5000000
    assert cfg.held == (0, 16)


@pytest.mark.parametrize("held,attn,remat", [
    (None, "exact", False), ((4, 4), "exact", True),
    ((0, 4), "flash", True), ((4, 4), "flash", False)], ids=str)
def test_model_loss_and_gradients_match_the_reference(held, attn, remat):
    cfg = zaya_tiny(held, remat=remat)
    model, params, batch = model_and_batch(
        cfg, flash if attn == "flash" else None)
    assert params["params"]["h0"]["moe"]["gate"].shape[0] == cfg.held[1]
    loss, grads = jax.jit(jax.value_and_grad(
        functools.partial(zaya_loss, model)))(params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(
        functools.partial(ref.loss, **reference_kw(cfg))))(params, batch)
    assert_close(loss, want)
    assert_trees_close(grads, want_grads, GRAD_RTOL)
    # beta chooses only: no gradient reaches it, on either side
    assert not np.asarray(grads["params"]["h1"]["moe"]["balance_bias"]).any()


@pytest.mark.parametrize("held", [None, (4, 4)], ids=str)
def test_model_logits_and_counts_match_the_reference(held):
    cfg = zaya_tiny(held, remat=True)
    model, params, batch = model_and_batch(cfg, flash)
    logits = jax.jit(functools.partial(model.apply, logits=True))(
        params, batch["input_ids"])
    assert_close(logits, ref.logits(params, batch["input_ids"],
                                    **reference_kw(cfg)))
    with jax.default_matmul_precision("highest"):
        _, want_counts = ref.forward(params, batch["input_ids"],
                                     **reference_kw(cfg))
    counts = expert_counts(model, params, batch["input_ids"])
    assert counts.shape == (cfg.num_hidden_layers, cfg.num_experts)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == 3 * 48               # top-1: pairs = tokens
    assert ((np.asarray(counts) > 0).sum(axis=1) >= 3).all()   # a real choice


def test_bfloat16_model_stays_near_the_float32_reference():
    """bf16 compute over the same float32 parameters: the loss within 2 %
    (8 mantissa bits through three layers at width 32; the chip's own
    comparison at the published widths is ``gradcheck_zaya.py``)."""
    cfg = zaya_tiny(dtype=jnp.bfloat16)
    model, params, batch = model_and_batch(cfg, flash)
    loss = jax.jit(functools.partial(zaya_loss, model))(params, batch)
    want = ref.loss(params, batch, **reference_kw(cfg))
    assert np.isfinite(float(loss))
    assert abs(float(loss) - float(want)) < 2e-2 * float(want)


@functools.lru_cache(maxsize=None)
def _jitted_reference(cfg):
    return jax.jit(jax.value_and_grad(
        functools.partial(ref.loss, **reference_kw(cfg))))


def _reference_grads(cfg, params, batch):
    return _jitted_reference(cfg)(params, batch)


@pytest.mark.parametrize("what", GRADCHECK.BREAKS)
def test_the_comparison_fails_each_deliberate_break(what):
    """The tolerances are tight enough: each of the six faults moves the
    loss or some gradient leaf past its tolerance by a factor of ten or
    more, in float32."""
    cfg = zaya_tiny((0, 4))
    with GRADCHECK.broken(what):
        model, params, batch = model_and_batch(cfg)
        loss, grads = jax.jit(jax.value_and_grad(
            functools.partial(zaya_loss, model)))(params, batch)
    want, want_grads = _reference_grads(cfg, params, batch)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    worst = max(deviation(g, flat_want[path]) for path, g in
                jax.tree_util.tree_flatten_with_path(grads)[0])
    assert max(worst / GRAD_RTOL, deviation(loss, want) / RTOL) > 10, what
    import byteps_tpu.models.zaya as mod                # undone on exit
    assert mod.token_before is token_before
    assert mod.dropless_moe_mlp is dropless_moe_mlp
    assert mod.ZayaRouter.__call__.__name__ == "__call__"


def test_config_refuses_what_it_cannot_run():
    with pytest.raises(ValueError, match="layer_types"):
        ZayaConfig(num_hidden_layers=4)
    with pytest.raises(ValueError, match="layer_types"):
        ZayaConfig(num_hidden_layers=1, layer_types=("hybrid_sliding",))
    with pytest.raises(ValueError, match="experts_held"):
        ZayaConfig(experts_held=(12, 8))
    with pytest.raises(ValueError, match="exactly 2"):
        ZayaConfig(num_key_value_heads=4)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        ZayaConfig(tie_word_embeddings=False)
    with pytest.raises(ValueError, match="rotary width"):
        ZayaConfig(head_dim=6, partial_rotary_factor=0.5)


def test_dp_step_is_the_mean_of_the_shards():
    """One ``make_dp_train_step`` step of a share on a 2-device mesh:
    routing is shard-local, so loss and update are those of the MEAN of
    the two shards' single-device losses and gradients."""
    cfg = zaya_tiny((4, 4))
    model, params, batch = model_and_batch(cfg, seqs=2, seq_len=16)
    loss_fn = functools.partial(zaya_loss, model)
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
    # the update is read back as a difference of parameters of ~1 (a
    # scalar ``gamma`` of 1.1 moves by 2e-4: float32 leaves it 4 digits)
    assert_trees_close(jax.tree.map(jnp.subtract, params, new_params),
                       mean_grads, 1e-3)


# ---------------------------------------------- causality, state, rotary

@pytest.mark.parametrize("attn", ["exact", "flash"])
def test_a_token_reaches_no_position_before_it(attn):
    """Perturb token t: the final rows of positions < t are unchanged to
    the bit (both convolutions, the value shift and the attention read
    the past only), and position t changes."""
    cfg = zaya_tiny()
    model, params, batch = model_and_batch(
        cfg, flash if attn == "flash" else None)
    ids, t = batch["input_ids"], 13
    apply = jax.jit(model.apply)
    base = np.asarray(apply(params, ids))
    moved = np.asarray(apply(params, ids.at[:, t].set((ids[:, t] + 1) % 128)))
    np.testing.assert_array_equal(moved[:, :t], base[:, :t])
    assert np.abs(moved[:, t:] - base[:, t:]).max() > 1e-3


def test_the_convolutions_read_t_minus_2_to_t_and_nothing_else():
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    u = jax.random.normal(k[0], (1, 12, 6, 16))
    w0, b0 = jax.random.normal(k[1], (6, 16, 2)), jax.random.normal(
        k[2], (6, 16))
    w1, b1 = jax.random.normal(k[3], (6, 2, 16, 16)), jax.random.normal(
        k[4], (6, 16))
    conv = functools.partial(causal_convs, w0=w0, b0=b0, w1=w1, b1=b1,
                             dtype=jnp.float32)
    base = np.asarray(conv(u))
    changed = np.abs(np.asarray(conv(u.at[:, 5].add(1.0))) - base).max(
        axis=(0, 2, 3)) > 0
    assert changed.tolist() == [False] * 5 + [True] * 3 + [False] * 4
    # padded ONCE before both: at t = 0 conv 1 sees conv 0's bias at -1
    c0 = b0 + w0[..., 1] * u[0, 0]
    with jax.default_matmul_precision("highest"):
        want = (b1 + jnp.einsum("cd,cde->ce", b0, w1[:, 0])
                + jnp.einsum("cd,cde->ce", c0, w1[:, 1]))
    assert_close(base[0, 0], want)
    # against the reference's explicit shifts, every position
    with jax.default_matmul_precision("highest"):
        c0_all = b0 + w0[..., 0] * ref.before(u) + w0[..., 1] * u
        want_all = (b1 + jnp.einsum("btcd,cde->btce",
                                    ref.before(c0_all, b0[None, None]),
                                    w1[:, 0])
                    + jnp.einsum("btcd,cde->btce", c0_all, w1[:, 1]))
    assert_close(base, want_all)


def test_the_value_shift_reads_the_token_before():
    x = jnp.arange(24, dtype=jnp.float32).reshape(2, 4, 3)
    got = np.asarray(token_before(x))
    assert not got[:, 0].any()
    np.testing.assert_array_equal(got[:, 1:], x[:, :-1])


def test_the_router_state_reaches_the_next_layer():
    """Zeroing layer 1's ``gamma`` changes layer 1's choices (and what
    follows), not layer 0's: the state is handed on beside the stream."""
    cfg = zaya_tiny()
    model, params, batch = model_and_batch(cfg)
    counts = np.asarray(expert_counts(model, params, batch["input_ids"]))
    cut = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if jax.tree_util.keystr(path).endswith("['h1']['moe']['router']"
                                               "['gamma']") else a, params)
    cut_counts = np.asarray(expert_counts(model, cut, batch["input_ids"]))
    np.testing.assert_array_equal(cut_counts[0], counts[0])
    assert (cut_counts[1] != counts[1]).any()


def test_partial_rotary_leaves_the_upper_half_of_a_head_untouched():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    cos, sin = rope_frequencies(8, pos, 10000.0)
    out = apply_rope(x, cos, sin, rotary_dim=8)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(out[..., :8],
                                  apply_rope(x[..., :8], cos, sin))
    assert_close(out, ref.rotate_first(x, 10000.0, 8))
    assert np.abs(np.asarray(out[:, 1:, :, :8] - x[:, 1:, :, :8])).max() > 0.1
    # the whole-head path is what it was, rotary_dim given or not
    cos, sin = rope_frequencies(16, pos, 10000.0)
    np.testing.assert_array_equal(apply_rope(x, cos, sin, rotary_dim=16),
                                  apply_rope(x, cos, sin))
    assert str(jax.make_jaxpr(lambda x: apply_rope(x, cos, sin, 16))(x)) == \
        str(jax.make_jaxpr(lambda x: apply_rope(x, cos, sin))(x))


# ------------------------------------------- the share ties to the model

def test_the_two_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the parts of the expert sublayer's result that the
    two chips' shares give add up to what the uncut reference gives for
    the whole layer; the router (computed alike on both, counted once)
    gives both the same state and the same counts."""
    cfg = zaya_tiny()
    h = cfg.hidden_size
    layer = ZayaSparseMoe(cfg)
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    m = jax.random.normal(k[0], (2, 24, h))
    r_before = jax.random.normal(k[1], (2, 24, cfg.router_hidden_size))
    params = moved(layer.init(k[2], m, r_before))
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        probs, want_r = ref.router(m, p["router"], r_before, cfg.rms_norm_eps)
        want_y, want_counts = ref.experts(m.reshape(48, h), p,
                                          probs.reshape(48, -1), None)
    parts = []
    for first, count in SHARES:
        share = {"params": {**p, **{key: p[key][first:first + count]
                                    for key in ("gate", "up", "down")}}}
        (y, r), sown = ZayaSparseMoe(zaya_tiny((first, count))).apply(
            share, m, r_before, mutable=["moe_stats"])
        assert_close(r, want_r)
        np.testing.assert_array_equal(sown["moe_stats"]["counts"][0],
                                      want_counts)
        parts.append(np.asarray(y).reshape(48, h))
    assert_close(parts[0] + parts[1], want_y)
    # top-1: a token's one expert lives on exactly one of the two chips
    live = [np.abs(part).max(axis=1) > 0 for part in parts]
    assert not (live[0] & live[1]).any() and (live[0] | live[1]).all()
    assert 5 < live[0].sum() < 43


# -------------------------------- routing handed to the expert layer

H, F, E = 32, 16, 8


def layer_params(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"router": jax.random.normal(k[0], (H, E)),
            "gate": jax.random.normal(k[1], (E, H, F)) / np.sqrt(H),
            "up": jax.random.normal(k[2], (E, H, F)) / np.sqrt(H),
            "down": jax.random.normal(k[3], (E, F, H)) / np.sqrt(F)}


def tokens(n, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, H))


def own_router(x, params):
    return jax.nn.softmax(jnp.dot(x, params["router"],
                                  precision=jax.lax.Precision.HIGHEST), -1)


@pytest.mark.parametrize("top_k,held,renormalize", [
    (2, None, False), (8, None, False), (2, (2, 4), True), (1, (4, 4), False)],
    ids=str)
def test_routing_from_outside_reproduces_the_layer_s_own_bit_for_bit(
        top_k, held, renormalize):
    """``routing=(softmax(x @ router), None)`` is ``routing=None``: output,
    load-balance loss and counts to the bit (the z-loss is of logits the
    layer no longer sees: 0)."""
    params, x = layer_params(), tokens(48)
    if held is not None:
        params = {"router": params["router"],
                  **{k: params[k][held[0]:held[0] + held[1]]
                     for k in ("gate", "up", "down")}}
    kw = dict(top_k=top_k, held=held, renormalize=renormalize)
    own = jax.jit(functools.partial(dropless_moe_mlp, **kw))(x, params)
    stacks = {k: v for k, v in params.items() if k != "router"}
    given = jax.jit(lambda x, stacks, p: dropless_moe_mlp(
        x, stacks, routing=(p, None), **kw))(x, stacks,
                                             own_router(x, params))
    for i in (0, 1, 3):
        np.testing.assert_array_equal(given[i], own[i])
    assert float(given[2]) == 0.0 and float(own[2]) > 0.0


def test_a_selection_bias_chooses_and_is_not_weighed():
    params, x = layer_params(), tokens(48)
    probs = own_router(x, params)
    bias = jnp.zeros(E).at[5].set(10.0)              # everyone to expert 5
    stacks = {k: v for k, v in params.items() if k != "router"}
    y, aux, _, counts = jax.jit(lambda x, s, p: dropless_moe_mlp(
        x, s, 1, routing=(p, bias)))(x, stacks, probs)
    assert np.asarray(counts).tolist() == [0] * 5 + [48] + [0] * 2
    with jax.default_matmul_precision("highest"):
        act = jax.nn.silu(x @ params["gate"][5]) * (x @ params["up"][5])
        want = probs[:, 5:6] * (act @ params["down"][5])   # p, not p + beta
    assert_close(y, want)
    assert_close(aux, E * float(jnp.mean(probs, 0)[5]))
    # the gradient reaches the probabilities through the weight alone
    g_p, g_b = jax.grad(lambda p, b: dropless_moe_mlp(
        x, stacks, 1, routing=(p, b))[0].sum(), (0, 1))(probs, bias)
    assert not np.asarray(g_b).any()
    assert np.abs(np.asarray(g_p)[:, 5]).min() > 0
    assert not np.delete(np.asarray(g_p), 5, axis=1).any()
    with pytest.raises(ValueError, match="float32"):
        dropless_moe_mlp(x, stacks, 1, routing=(probs[:40], None))


def test_top_1_through_a_share_drops_no_pair_and_zeroes_the_rest():
    """``top_k = 1`` through ``held=``: ``N`` pair rows; a token whose
    expert is held gets ``p x expert(x)`` whatever the routing (all of
    them here, none of them there), every other token exactly zero."""
    params, x = layer_params(), tokens(64)
    held = (2, 4)
    stacks = {k: params[k][2:6] for k in ("gate", "up", "down")}
    probs = own_router(x, params)
    layer = jax.jit(lambda x, s, p, b: dropless_moe_mlp(
        x, s, 1, held=held, routing=(p, b)))
    y, _, _, counts = layer(x, stacks, probs, jnp.zeros(E))
    chosen = np.asarray(jnp.argmax(probs, -1))
    mine = (chosen >= 2) & (chosen < 6)
    assert 5 < mine.sum() < 59 and int(counts.sum()) == 64
    assert not np.asarray(y)[~mine].any()            # exactly zero
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(x, {**stacks, "balance_bias": jnp.zeros(E)},
                              probs, held)
    assert_close(y, want)
    for steer, n_here in ((10.0, 64), (-10.0, 0)):   # all held / none held
        bias = jnp.zeros(E).at[2:6].set(steer)
        y, _, _, counts = layer(x, stacks, probs, bias)
        assert int(np.asarray(counts)[2:6].sum()) == n_here
        with jax.default_matmul_precision("highest"):
            want, _ = ref.experts(x, {**stacks, "balance_bias": bias},
                                  probs, held)
        assert_close(y, want)
        assert bool(np.asarray(y).any()) == bool(n_here)


def test_row_schedule_at_the_cell_s_sixteen_chunks():
    """N = 16 384 pair rows at top-1 are sixteen 1 024-row chunks; a
    balanced router leaves the first eight live, a skewed one as many as
    meet the held experts' range."""
    sched = row_schedule(np.full(16, 1024), (0, 8), 1024)
    assert (int(sched["lo"]), int(sched["hi"]), int(sched["first"]),
            int(sched["end"])) == (0, 8192, 0, 8)
    counts = np.asarray([3000, 100, 0, 700, 2000, 1500, 900, 300,
                         1000, 1000, 1000, 1000, 1000, 1000, 884, 1000])
    assert counts.sum() == 16384
    sched = row_schedule(counts, (0, 8), 1024)
    assert (int(sched["hi"]), int(sched["end"])) == (8500, 9)
    sched = row_schedule(counts, (8, 8), 1024)
    assert (int(sched["lo"]), int(sched["first"]), int(sched["end"])) == (
        8500, 8, 16)


def test_publish_moe_stats_at_top_1():
    """Pairs = tokens: the share gauge is the share of TOKENS whose expert
    is held."""
    import byteps_tpu as bps
    counts = np.asarray([[10, 6, 0, 0, 20, 4, 4, 4],
                         [6, 6, 6, 6, 6, 6, 6, 6]])
    publish_moe_stats(counts, held=(0, 4))
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["moe.held_pair_share"] == pytest.approx(40 / 96)
    assert gauges["moe.load_max_over_mean"] == pytest.approx(20 / 6)
    assert gauges["moe.held_load_max_over_mean"] == pytest.approx(10 / 4)
    # in whole chunks of gcd(48, 1024) = 16 rows: 16 of layer 0's 48 rows
    # (its 16 held pairs), 32 of layer 1's (its 24)
    assert gauges["moe.visited_row_share"] == pytest.approx(48 / 96)


# ----------------------------------------------- the head, in blocks

def head_inputs(n=128, h=32, v=300, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (n, h)),
            0.3 * jax.random.normal(k[1], (v, h)),
            jax.random.randint(k[2], (n,), -1, v))


# the head matrix as its parameter lies: a (tied) table [V, h], or the
# kernel [h, V] of an untied ``nn.Dense`` head (``kernel=True``)
LAYOUTS = pytest.mark.parametrize("kernel", [False, True],
                                  ids=["table_Vh", "kernel_hV"])


def lay(table, kernel):
    """``table`` [V, h] laid out as the head is told to read it."""
    return table.T if kernel else table


@LAYOUTS
@pytest.mark.parametrize("block_bytes,blocks", [(32 * 300 * 4, 4),
                                                (2 ** 30, 1)])
def test_blocked_head_equals_lm_loss_on_whole_logits(monkeypatch,
                                                     block_bytes, blocks,
                                                     kernel):
    """Values and both gradients, to float32 rounding, whatever the
    cotangent that comes in (the forward pass forms both gradients; the
    backward pass scales them); in the kernel's layout also against the
    same matrix transposed through the table's."""
    import byteps_tpu as bps
    monkeypatch.setattr(gpt, "_LOGIT_BLOCK_BYTES", block_bytes)
    x, table, labels = head_inputs()
    assert 128 // logit_block_rows(128, 300) == blocks

    def blocked(x, w, kernel=kernel):
        return 3.0 * blocked_lm_loss(x, w, labels, kernel=kernel)

    def whole(x, table):
        with jax.default_matmul_precision("highest"):
            return 3.0 * lm_loss(x @ table.T, labels)

    got, (g_x, g_w) = jax.jit(jax.value_and_grad(blocked, (0, 1)))(
        x, lay(table, kernel))
    want, (w_x, w_t) = jax.value_and_grad(whole, (0, 1))(x, table)
    assert g_w.shape == ((32, 300) if kernel else (300, 32))
    assert_close(got, want)
    assert_close(g_x, w_x)
    assert_close(g_w, lay(w_t, kernel))
    assert bps.metrics_snapshot()["gauges"]["head.logit_blocks"] == blocks
    assert bps.metrics_snapshot()["gauges"]["head.logit_block_bytes"] == (
        128 // blocks * 300 * 4)
    if kernel:
        t_got, (t_x, t_t) = jax.jit(jax.value_and_grad(
            functools.partial(blocked, kernel=False), (0, 1)))(x, table)
        assert_close(got, t_got)
        assert_close(g_x, t_x)
        assert_close(g_w, t_t.T)
    # without differentiation: the same sums, no gradient formed
    nll, count = jax.jit(functools.partial(blocked_token_nll, kernel=kernel))(
        x, lay(table, kernel), labels)
    with jax.default_matmul_precision("highest"):
        want_nll, want_count = token_nll(x @ table.T, labels)
    assert_close(nll, want_nll)
    assert float(count) == float(want_count) == float((labels >= 0).sum())


@LAYOUTS
def test_blocked_head_with_the_table_tied(kernel):
    """One leaf receives the gather's gradient and the head's."""
    _, table, labels = head_inputs(v=64)
    ids = jnp.clip(labels, 0)

    def tied(head, kernel=False):
        def loss(w):
            rows = w.T if kernel else w
            x = jnp.tanh(rows[ids])                  # the gather's side
            return head(x, w)
        return jax.value_and_grad(loss)(lay(table, kernel))

    got, g = tied(lambda x, w: blocked_lm_loss(x, w, labels, kernel=kernel),
                  kernel)
    with jax.default_matmul_precision("highest"):
        want, w = tied(lambda x, t: lm_loss(x @ t.T, labels))
    assert_close(got, want)
    assert_close(g, lay(w, kernel))


def test_the_layout_is_said_not_read_from_the_shapes():
    """``V == h`` is legal: a square matrix is a table unless told."""
    x, table, labels = head_inputs(h=32, v=32)
    with jax.default_matmul_precision("highest"):
        as_table = lm_loss(x @ table.T, labels)
        as_kernel = lm_loss(x @ table, labels)
    assert abs(float(as_table) - float(as_kernel)) > 1e-2
    assert_close(blocked_lm_loss(x, table, labels), as_table)
    assert_close(blocked_lm_loss(x, table, labels, kernel=True), as_kernel)
    g = jax.grad(lambda w: blocked_lm_loss(x, w, labels, kernel=True))(table)
    with jax.default_matmul_precision("highest"):
        assert_close(g, jax.grad(lambda w: lm_loss(x @ w, labels))(table))


def _head_scan(kernel, grads, dtype=jnp.float32):
    """The blocked head's ``scan`` body at [128, 32] x 300 (one block)."""
    x, table, labels = head_inputs()

    def f(x, w):
        return blocked_lm_loss(x, w, labels, kernel=kernel)

    body, = jaxpr_count.scan_bodies(
        jax.value_and_grad(f, (0, 1)) if grads else f,
        x.astype(dtype), lay(table, kernel))
    return body


def _matmuls(body):
    return [(eqn.params["dimension_numbers"][0], eqn.outvars[0].aval.shape,
             str(eqn.outvars[0].aval.dtype))
            for eqn in body.eqns if eqn.primitive.name == "dot_general"]


def test_the_table_layout_traces_to_the_program_it_was():
    """ZAYA's and GLM's heads do not move: the [V, h] form's scan body is
    what it was before the head learnt the kernel's layout, equation for
    equation (42 / 44 with gradients in float32 / bfloat16, 31 without)
    and matmul for matmul; the [h, V] form is the same body under other
    dimension numbers."""
    for kernel in (False, True):
        assert jaxpr_count.equations(_head_scan(kernel, True)) == 42
        assert jaxpr_count.equations(
            _head_scan(kernel, True, jnp.bfloat16)) == 44
        assert jaxpr_count.equations(_head_scan(kernel, False)) == 31
    # logits, the rows' gradient, the matrix's gradient
    assert _matmuls(_head_scan(False, True)) == [
        (((1,), (1,)), (128, 300), "float32"),
        (((1,), (0,)), (128, 32), "float32"),
        (((0,), (0,)), (300, 32), "float32")]
    assert _matmuls(_head_scan(True, True)) == [
        (((1,), (0,)), (128, 300), "float32"),
        (((1,), (1,)), (128, 32), "float32"),
        (((0,), (0,)), (32, 300), "float32")]
    # nothing is transposed for either layout
    for kernel in (False, True):
        assert not [eqn for eqn in _head_scan(kernel, True).eqns
                    if eqn.primitive.name == "transpose"]


def test_block_rows_follow_the_shapes():
    assert logit_block_rows(16384, 131136) == 1024   # 0.5 GiB a block
    assert logit_block_rows(16384, 131072) == 2048   # exactly 1 GiB
    assert logit_block_rows(16384, 50304) == 4096
    assert logit_block_rows(48, 128) == 16           # 48 = 16 x 3
    assert logit_block_rows(7, 10 ** 9) == 1


def test_no_whole_logits_in_the_compiled_step(monkeypatch):
    """The ZAYA step's program holds no [tokens, vocabulary] array,
    forward or backward; the same model under ``lm_loss`` on whole logits
    does (so the search would find one)."""
    monkeypatch.setattr(gpt, "_LOGIT_BLOCK_BYTES", 16 * 128 * 4)
    cfg = zaya_tiny()
    model, params, batch = model_and_batch(cfg, seqs=2, seq_len=32)

    def whole(params, batch):
        logits = model.apply(params, batch["input_ids"], logits=True)
        return lm_loss(logits, batch["labels"])

    def text(loss_fn):
        return jax.jit(jax.value_and_grad(loss_fn)).lower(
            params, batch).compile().as_text()

    square = ("f32[64,128]", "f32[2,32,128]")
    blocked = text(functools.partial(zaya_loss, model))
    assert not any(s in blocked for s in square)
    assert "f32[16,128]" in blocked                  # a block of 16 rows
    assert any(s in text(whole) for s in square)
