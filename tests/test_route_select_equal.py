"""``parallel.expert._select_experts`` (PR 41) against ``lax.top_k`` + the
gather + ``bincount`` it replaced, through the Pallas interpreter: indices,
the scores read and the counts EQUAL — ties, constant rows and infinities
included — and the gradient that reaches the scores equal to the gather's
scatter-add.  One test over all its cases: ``route_select_cases``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.parallel.expert import _select_experts

from .route_select_cases import selected

TOKENS = 200          # two lane tiles, the second padded
SHAPES = [(512, 22), (64, 8), (64, 4), (16, 1), (8, 8), (12, 5)]
INPUTS = ["random", "eight_levels", "constant_rows", "infinities",
          "bias_reorders", "bias_and_ties"]


def scores(e, what):
    """-> (scores [200, E] float32, bias [E] or None)."""
    raw = jax.random.normal(jax.random.PRNGKey(e), (TOKENS, e), jnp.float32)
    probs = jax.nn.sigmoid(raw)
    if what == "random":
        return probs, None
    if what == "eight_levels":           # every row has ties
        return jnp.round(probs * 7.0) / 7.0, None
    if what == "constant_rows":          # nothing but ties
        return probs.at[::2].set(0.25).at[1::4].set(0.0), None
    if what == "infinities":
        return (probs.at[::3, ::2].set(-jnp.inf).at[1::5, 1::3].set(jnp.inf)
                .at[7].set(-jnp.inf).at[8].set(jnp.inf)), None
    if what == "bias_reorders":
        return probs, 0.5 * jax.random.normal(jax.random.PRNGKey(3), (e,))
    if what == "bias_and_ties":
        return (jnp.round(probs * 7.0) / 7.0,
                jnp.round(jax.random.normal(jax.random.PRNGKey(3), (e,))))
    raise KeyError(what)


def test_selection_equals_top_k_gather_and_bincount():
    for (e, top_k), what in ((s, w) for s in SHAPES for w in INPUTS):
        case = f"E {e}, k {top_k}, {what}"
        probs, bias = scores(e, what)
        got = _select_experts(probs, bias, top_k, True)
        want = selected(probs, bias, top_k)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape, case
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=case)
        assert int(got[2].sum()) == TOKENS * top_k, case
        assert all(len(set(row)) == top_k
                   for row in np.asarray(got[0]).tolist()), case
        cot = jax.random.normal(jax.random.PRNGKey(4), (TOKENS, top_k))
        finite = jnp.where(jnp.isfinite(probs), probs, 0.0)  # inf * 0: NaN

        def grads(select):
            return jax.grad(
                lambda p, b: jnp.sum(select(p, b, top_k)[1] * cot),
                (0, 1) if bias is not None else 0)(finite, bias)
        for g, w in zip(jax.tree.leaves(grads(
                lambda p, b, k: _select_experts(p, b, k, True))),
                jax.tree.leaves(grads(selected))):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=case)
    with pytest.raises(ValueError, match="top_k"):
        _select_experts(jnp.zeros((16, 8)), None, 9, True)
