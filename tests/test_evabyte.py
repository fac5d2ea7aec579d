"""``models/evabyte.py`` against its plain reference
(``tests/evabyte_reference.py``): the eight heads' logits, the loss and
every gradient leaf on seeded weights; deliberate faults — a wrong EVA in
the attention's place — that have to FAIL the comparison; the float32
residual stream under bfloat16 compute; the heads' shifted labels; the
constructor's refusals."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models.evabyte import (EvaByte, EvaByteConfig, evabyte_loss,
                                       evabyte_tiny, head_labels)

from . import evabyte_reference as reference

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "tests"))
import gradcheck_evabyte as gradcheck  # noqa: E402

rel_l2 = gradcheck.rel_l2

# float32 program against float32 reference: the worst gradient leaf reads
# 3e-5 relative L2 (sums in another order); the mildest wrong EVA reads
# 1e-2 on the logits
RTOL = 2e-4
T = 256                    # four windows of 64


def livelier(params, seed=7):
    """normal(0.01275) weights at hidden size 32 leave the mixer's output a
    rounding error beside the residual: scale the matrices up and move the
    norms' weights off zero, so that a wrong attention shows in the
    logits."""
    keys = iter(np.asarray(jax.random.split(jax.random.PRNGKey(seed), 64)))

    def one(path, leaf):
        name = jax.tree_util.keystr(path)
        if name.endswith(("['mu']", "['phi']")):
            return leaf * 12.0
        if leaf.ndim >= 2:
            return leaf * 12.0
        return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(one, p))(params)


@functools.lru_cache(maxsize=None)      # a model's init traces its forward
def setup(cfg, seed=0, seqs=2):
    model = EvaByte(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (seqs, T), 0,
                             cfg.vocab_size)
    params = livelier(jax.jit(model.init)(jax.random.PRNGKey(seed), ids))
    labels = jnp.concatenate([ids[:, 1:], jnp.full((seqs, 1), -1)], axis=1)
    return model, params, {"input_ids": ids, "labels": labels}


@functools.lru_cache(maxsize=None)
def wanted(cfg):
    """The reference's loss, gradients and logits on ``setup(cfg)``."""
    _, params, batch = setup(cfg)
    model = reference.model_of(cfg)
    loss_and_grads = jax.jit(jax.value_and_grad(functools.partial(
        reference.reference_loss, **model)))(params, batch)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(functools.partial(
            reference.reference_logits, **model))(params, batch["input_ids"])
    return jax.tree.map(np.asarray, (loss_and_grads, logits))


def program_logits(cfg):
    """Built anew a call, so that no jit cache outlives a break."""
    _, params, batch = setup(cfg)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p: EvaByte(cfg).apply(
            p, batch["input_ids"], logits=True))(params))


def test_model_matches_the_reference():
    cfg = evabyte_tiny()
    _, params, batch = setup(cfg)
    (want_loss, want_grads), want_logits = wanted(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: evabyte_loss(EvaByte(cfg), p, batch)))(params)
    assert abs(float(loss) - want_loss) < 1e-5 * want_loss
    got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    want = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    assert got.keys() == want.keys() and len(got) == 2 * 11 + 3
    for path, g in got.items():
        assert np.linalg.norm(want[path]) > 0, path
        assert rel_l2(np.asarray(g), want[path]) < RTOL, path
    logits = program_logits(cfg)
    assert logits.shape == (2, T, 8, cfg.vocab_size) == want_logits.shape
    assert rel_l2(logits, want_logits) < RTOL
    # a head is its own run of the matrix's columns
    assert not np.allclose(logits[:, :, 0], logits[:, :, 1])


@pytest.mark.parametrize("what", gradcheck.BREAKS)
def test_a_wrong_eva_fails_through_the_model(what):
    cfg = evabyte_tiny()
    _, want_logits = wanted(cfg)
    with gradcheck.broken(what):
        wrong = program_logits(cfg)
    assert rel_l2(wrong, want_logits) > 20 * RTOL


def test_the_right_eva_in_plain_text_passes_through_the_model():
    """``wrong_eva(None)`` is the text of the breaks with the right sets:
    it has to PASS, or the breaks fail for the text's sake."""
    from unittest import mock
    import byteps_tpu.models.evabyte as model
    cfg = evabyte_tiny()
    _, want_logits = wanted(cfg)
    jax.clear_caches()
    with mock.patch.object(model, "eva_attention", gradcheck.wrong_eva(
            None, theta=cfg.rope_theta)):
        right = program_logits(cfg)
    jax.clear_caches()
    assert rel_l2(right, want_logits) < RTOL


def test_the_residual_stream_is_float32_under_bfloat16_compute():
    """``fp32_skip_add``: what a block takes and hands on — the rows a
    ``remat`` keeps — is float32; the mixer and the MLP compute in
    bfloat16; the rows the heads read are bfloat16."""
    cfg = evabyte_tiny(dtype=jnp.bfloat16, window_size=64)
    model = EvaByte(cfg)
    ids = jnp.zeros((1, 64), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    _, state = jax.eval_shape(functools.partial(
        model.apply, capture_intermediates=True), params, ids)
    seen = state["intermediates"]
    for block in ("h0", "h1"):
        assert seen[block]["__call__"][0].dtype == jnp.float32
        assert seen[block]["attn"]["__call__"][0].dtype == jnp.bfloat16
        assert seen[block]["mlp"]["__call__"][0].dtype == jnp.bfloat16
    assert seen["wte"]["__call__"][0].dtype == jnp.float32
    assert seen["norm_f"]["__call__"][0].dtype == jnp.bfloat16
    off = EvaByte(evabyte_tiny(dtype=jnp.bfloat16, fp32_skip_add=False))
    _, state = jax.eval_shape(functools.partial(
        off.apply, capture_intermediates=True), params, ids)
    assert state["intermediates"]["h0"]["__call__"][0].dtype == jnp.bfloat16


def test_head_labels():
    labels = jnp.asarray([[1, 2, 3, 4, -1]])
    got = np.asarray(head_labels(labels, 3))
    assert got.tolist() == [[[1, 2, 3, 4, -1]], [[2, 3, 4, -1, -1]],
                            [[3, 4, -1, -1, -1]]]
    # the reference's own, position-major
    assert np.array_equal(np.asarray(reference.head_labels(labels, 3)),
                          np.moveaxis(got, 0, -1))


@pytest.mark.parametrize("bad", [
    dict(rope_scaling={"factor": 2.0}), dict(attention_bias=True),
    dict(num_chunks=4), dict(tie_word_embeddings=True),
    dict(num_key_value_heads=1), dict(attention_class="flash"),
    dict(norm_add_unit_offset=False), dict(hidden_act="gelu"),
    dict(window_size=60), dict(fp32_logits=False)])
def test_constructor_refuses_what_it_cannot_compute(bad):
    with pytest.raises(ValueError):
        evabyte_tiny(**bad)


def test_defaults_are_the_published_model():
    cfg = EvaByteConfig()
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size, cfg.num_pred_heads,
            cfg.window_size, cfg.chunk_size) == (32, 4096, 128, 11008, 320,
                                                 8, 2048, 16)
    with pytest.raises(ValueError, match="whole windows"):
        EvaByte(evabyte_tiny()).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 96), jnp.int32))
