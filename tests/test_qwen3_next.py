"""``models/qwen3_next.py`` against its plain reference
(``tests/qwen3_next_reference.py``): loss, logits and every gradient leaf
for one period (3 Gated DeltaNet layers, 1 gated attention layer, four
sparse MLPs) as one chip's share; the test that ties a share to the model
— the routed sums of ALL shares, with the shared expert counted once, add
up to the uncut layer; deliberate faults that have to FAIL the comparison;
the constructor's refusals."""

import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import qwen3_next
from byteps_tpu.models.qwen3_next import (Qwen3Next, Qwen3NextConfig,
                                          Qwen3NextSparseMoe,
                                          qwen3_next_loss, qwen3_next_tiny)

from . import qwen3_next_reference as reference

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tests"))
import gradcheck_qwen3_next as gradcheck  # noqa: E402

gdn = importlib.import_module("byteps_tpu.ops.gdn_scan")

# float32 program against float32 reference: the worst leaf reads 5e-5
# (the scan's solve is three bfloat16-operand passes a product, 2^-16); a
# dropped norm, gate or rotation reads 1e-2 and more
RTOL = 3e-4


def livelier(params, seed=7):
    """normal(0.02) weights at hidden size 32 leave every mixer's output a
    rounding error beside the residual: scale the matrices up and move the
    vectors off their initial 0 / 1 so that a wrong mixer, norm or router
    shows."""
    keys = iter(np.asarray(jax.random.split(jax.random.PRNGKey(seed), 400)))

    def one(path, leaf):
        if leaf.ndim >= 2 and "conv" not in jax.tree_util.keystr(path):
            return leaf * 8.0
        return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(one, p))(params)


@functools.lru_cache(maxsize=None)      # a model's init traces its forward
def setup(cfg, seed=0, seqs=2, t=32):
    model = Qwen3Next(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (seqs, t), 0,
                             cfg.vocab_size)
    params = livelier(jax.jit(model.init)(jax.random.PRNGKey(seed), ids))
    labels = jnp.concatenate([ids[:, 1:], jnp.full((seqs, 1), -1)], axis=1)
    return model, params, {"input_ids": ids, "labels": labels}


@functools.lru_cache(maxsize=None)
def wanted(cfg):
    """The reference's loss and gradients on ``setup(cfg)``."""
    _, params, batch = setup(cfg)
    return jax.tree.map(np.asarray, jax.jit(jax.value_and_grad(
        functools.partial(reference.reference_loss,
                          **reference.model_of(cfg))))(params, batch))


def program(cfg):
    """The program's loss and gradients on ``setup(cfg)``; built anew a
    call, so that no jit cache outlives a break."""
    model, params, batch = setup(cfg)
    with jax.default_matmul_precision("highest"):
        return jax.tree.map(np.asarray, jax.jit(jax.value_and_grad(
            lambda p: qwen3_next_loss(Qwen3Next(cfg), p, batch)))(params))


def worst_leaf(got, want):
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    rel = {jax.tree_util.keystr(path): float(
        np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
        for (path, a), b in zip(flat, jax.tree.leaves(want))}
    name = max(rel, key=rel.get)
    return name, rel[name]


@pytest.fixture
def chunked_scan(monkeypatch):
    """The mixer's scan as ``gdn_scan_chunked`` (``tests/test_gdn_scan.py``
    holds both forms against the recurrence): the Pallas interpreter
    compiles every call, and these tests are about the layers around the
    scan."""
    monkeypatch.setattr(
        gdn, "gdn_scan", lambda *a, chunk, interpret=None:
        gdn.gdn_scan_chunked(*a, chunk=chunk))


def test_loss_and_gradients_match_the_reference():
    """3 DeltaNet + 1 attention, four sparse MLPs holding experts 4 .. 7 of
    16, float32: the program (the scan's and the row stage's kernels
    interpreted, exact attention, grouped matmuls, the blocked head in the
    kernel's layout) against the reference (the delta rule position by
    position, dense experts), every leaf."""
    cfg = qwen3_next_tiny(experts_held=(4, 4))
    (loss, grads), (want, want_grads) = program(cfg), wanted(cfg)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    name, rel = worst_leaf(grads, want_grads)
    assert rel < RTOL, (name, rel)
    # every leaf is reached (nothing is a constant of the loss)
    assert all(np.abs(g).max() > 0 for g in jax.tree.leaves(grads))


@functools.lru_cache(maxsize=None)
def wanted_logits(cfg):
    _, params, batch = setup(cfg)
    with jax.default_matmul_precision("highest"):
        rows = jax.jit(functools.partial(
            reference.reference_hidden, **reference.model_of(cfg)))(
                params, batch["input_ids"])
        return np.asarray(rows @ params["params"]["lm_head"])


def logits_deviation(cfg):
    """The program's logits on ``setup(cfg)`` against the reference's:
    the largest deviation over the largest logit.  Built anew a call."""
    _, params, batch = setup(cfg)
    want = wanted_logits(cfg)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: Qwen3Next(cfg).apply(
            p, batch["input_ids"], logits=True))(params)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def test_logits_match_the_reference(chunked_scan):
    assert logits_deviation(qwen3_next_tiny(experts_held=(4, 4))) < RTOL


@pytest.mark.parametrize("what", gradcheck.MODEL_BREAKS + ("bf16_decays",))
def test_a_deliberate_fault_fails_the_comparison(what, chunked_scan):
    """Each of ``gradcheck_qwen3_next.py``'s faults, through the model on
    the tiny preset in float32: the gate's decay rounded to bfloat16, the
    attention's q / k norms, its rotation, its output gate, the DeltaNet
    output gate or the shared expert's gate left out — the logits have to
    read past ``RTOL``, ten times and more (the forward pass alone: a
    fifth of the gradients' compile; the state rounded as a chunk hands it
    on is ``tests/test_gdn_scan.py``'s, these sequences are one chunk)."""
    cfg = qwen3_next_tiny(experts_held=(4, 4))
    wanted_logits(cfg)
    with gradcheck.broken(what):
        # the break's own patch of ``gdn_scan`` replaces the fixture's
        deviation = logits_deviation(cfg)
    assert deviation > 10 * RTOL, (what, deviation)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 published experts in 4 shares of 4: the routed sums the four
    shares give, with the shared expert behind its gate and everything
    outside the routed sum counted ONCE, add up to what the uncut
    reference gives for the whole layer."""
    cfg = qwen3_next_tiny()
    n, h, e = 64, cfg.hidden_size, cfg.num_experts
    m = jax.random.normal(jax.random.PRNGKey(3), (1, n, h))
    whole = Qwen3NextSparseMoe(cfg)
    params = jax.jit(whole.init)(jax.random.PRNGKey(4), m)
    params = livelier(params, seed=5)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        want = reference.sparse_moe(
            m[0], p, held=(0, e), top_k=cfg.num_experts_per_tok,
            renormalize=True)
        shared_once = (
            jax.nn.sigmoid(m[0] @ p["shared_expert_gate"]["kernel"])
            * reference.swiglu(m[0], p["shared_expert"]))
        routed = jnp.zeros_like(want)
        for first in range(0, e, 4):
            layer = Qwen3NextSparseMoe(qwen3_next_tiny(
                experts_held=(first, 4)))
            share = {"params": {**p, **{k: p[k][first:first + 4]
                                        for k in ("gate", "up", "down")}}}
            got, sown = layer.apply(share, m, mutable=["moe_stats"])
            routed = routed + (got[0] - shared_once)
        np.testing.assert_allclose(np.asarray(routed + shared_once),
                                   np.asarray(want), rtol=0, atol=2e-5)
    # the counts are over all 16 experts, whatever is held
    assert int(sown["moe_stats"]["counts"][0].sum()) == (
        n * cfg.num_experts_per_tok)


def test_the_constructor_refuses_what_it_cannot_compute():
    for bad, match in (
            (dict(mlp_only_layers=(0,)), "mlp_only_layers"),
            (dict(decoder_sparse_step=2), "decoder_sparse_step"),
            (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
            (dict(use_sliding_window=True), "use_sliding_window"),
            (dict(hidden_act="gelu"), "hidden_act"),
            (dict(tie_word_embeddings=True), "tie_word_embeddings"),
            (dict(experts_held=(500, 32)), "no range"),
            (dict(linear_num_value_heads=24), "do not divide"),
            (dict(partial_rotary_factor=0.0), "lanes"),
            (dict(num_experts_per_tok=600), "num_experts_per_tok")):
        with pytest.raises(ValueError, match=match):
            Qwen3NextConfig(**bad)
    cfg = Qwen3NextConfig()                     # as published
    assert [cfg.is_attention(i) for i in range(8)] == [
        False, False, False, True] * 2
    assert cfg.rotary_dim == 64 and cfg.held == (0, 512)


def test_the_published_share_counts_625_7_million_parameters():
    """One chip's share of ISSUE 46's deployment, leaf by leaf from
    ``eval_shape``: a DeltaNet mixer 33 718 464, the attention mixer
    27 263 488, a sparse MLP at 32 held 104 859 648, two block norms,
    table + head, the final norm."""
    cfg = Qwen3NextConfig(num_hidden_layers=4, vocab_size=18992,
                          experts_held=(0, 32))
    shapes = jax.eval_shape(lambda: Qwen3Next(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))["params"]

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))

    assert count(shapes["h0"]["mixer_gdn"]) == 33_718_464
    assert count(shapes["h3"]["attn"]) == 27_263_488
    assert count(shapes["h0"]["moe"]) == 104_859_648
    assert count(shapes) == (3 * 33_718_464 + 27_263_488 + 4 * 104_859_648
                             + 4 * 4096 + 2 * 38_895_616 + 2048)
    assert count(shapes) == 625_667_136
