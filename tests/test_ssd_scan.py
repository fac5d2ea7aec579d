"""``ops/ssd_scan.py``: the chunked state-space scan — the Mosaic kernels
in ``interpret`` mode and the einsum form — against the recurrence written
position by position, value and the gradient of every input."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops.ssd_scan import ssd_scan, ssd_scan_chunked

from .jaxpr_count import _inner_jaxprs


def recurrence(xs, dt, A, B, C, D):
    """S_t = exp(dt_t A) S_(t-1) + dt_t B_t^T xs_t; y_t = C_t S_t + D xs_t,
    one position at a time from a zero state."""
    b, t, h, p = xs.shape
    g, n = B.shape[2], B.shape[3]
    b_h, c_h = (jnp.repeat(v, h // g, axis=2) for v in (B, C))

    def step(state, at):
        x, d, bb, cc = at
        state = (jnp.exp(d * A)[..., None, None] * state
                 + (d[..., None] * bb)[..., :, None] * x[..., None, :])
        return state, jnp.einsum("bhn,bhnp->bhp", cc, state) + D[:, None] * x

    _, ys = jax.lax.scan(step, jnp.zeros((b, h, n, p), jnp.float32),
                         tuple(jnp.moveaxis(v, 1, 0)
                               for v in (xs, dt, b_h, c_h)))
    return jnp.moveaxis(ys, 0, 1)


def inputs(seed, b=2, t=32, h=4, p=8, g=1, n=16, dt_range=(0.001, 0.1)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    lo, hi = np.log(dt_range[0]), np.log(dt_range[1])
    return (jax.random.normal(ks[0], (b, t, h, p)),
            jnp.exp(jax.random.uniform(ks[1], (b, t, h), minval=lo,
                                       maxval=hi)),
            -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0,
                                        maxval=np.log(16.0))),
            jax.random.normal(ks[3], (b, t, g, n)),
            jax.random.normal(ks[4], (b, t, g, n)),
            jax.random.normal(ks[5], (h,)))


FORMS = {"kernels": lambda *a: ssd_scan(*a, chunk=8, interpret=True),
         "chunked": lambda *a: ssd_scan_chunked(*a, chunk=8)}


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("case,kwargs", [
    ("one_group", dict(g=1)),
    ("two_groups", dict(g=2)),
    # exp(dt A) down to exp(-32): a state forgotten inside one chunk
    ("decay_near_0", dict(g=1, dt_range=(0.5, 2.0))),
    # exp(dt A) within 1e-5 of one: a state carried across every chunk
    ("decay_near_1", dict(g=2, dt_range=(1e-7, 1e-6))),
])
def test_value_and_every_gradient_match_the_recurrence(form, case, kwargs):
    args = inputs(len(case), **kwargs)
    weight = jax.random.normal(jax.random.PRNGKey(99), args[0].shape)

    def objective(fn):
        return lambda *a: jnp.sum(fn(*a) * weight)

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(
            objective(recurrence), argnums=tuple(range(6)))(*args)
        got, got_grads = jax.value_and_grad(
            objective(FORMS[form]), argnums=tuple(range(6)))(*args)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for name, a, b in zip(("xs", "dt", "A", "B", "C", "D"), got_grads,
                          want_grads):
        # float32 end to end; A's gradient sums T x H x P x N terms through
        # differences of ``cum`` as large as 128: 4e-5 at the fastest decay
        scale = float(jnp.max(jnp.abs(b))) + 1e-30
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4, (name, case)


def test_kernels_and_einsum_form_agree_on_bfloat16_operands():
    """Both forms round the same operands (``dt o xs``, the masked scores,
    ``B`` times its decay, the state as an operand) to bfloat16 and keep
    decays and state float32: they agree far inside bfloat16's own
    distance from the float32 recurrence."""
    xs, dt, A, B, C, D = inputs(5, t=64, g=2)
    low = (xs.astype(jnp.bfloat16), dt, A, B.astype(jnp.bfloat16),
           C.astype(jnp.bfloat16), D)
    kern = ssd_scan(*low, chunk=16, interpret=True).astype(jnp.float32)
    ein = ssd_scan_chunked(*low, chunk=16).astype(jnp.float32)
    want = recurrence(*(v.astype(jnp.float32) for v in low))
    scale = float(jnp.max(jnp.abs(want)))
    assert kern.dtype == jnp.float32 and ssd_scan(*low, chunk=16,
                                                  interpret=True).dtype \
        == jnp.bfloat16
    assert float(jnp.max(jnp.abs(kern - ein))) / scale < 1e-2
    assert float(jnp.max(jnp.abs(kern - want))) / scale < 3e-2


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_length_that_is_no_multiple_of_the_chunk_is_refused(form):
    with pytest.raises(ValueError, match="not a multiple of chunk"):
        FORMS[form](*inputs(1, t=36))


def test_padding_with_dt_zero_is_the_documented_way_to_a_whole_chunk():
    """A position with ``dt = 0`` hands the state on unchanged and adds
    nothing, so a sequence padded that way reads as the unpadded one."""
    xs, dt, A, B, C, D = inputs(2, t=36)
    pad = lambda v: jnp.pad(v, ((0, 0), (0, 4)) + ((0, 0),) * (v.ndim - 2))
    got = ssd_scan(pad(xs), pad(dt), A, pad(B), pad(C), D, chunk=8,
                   interpret=True)[:, :36]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(got, recurrence(xs, dt, A, B, C, D),
                                   rtol=2e-4, atol=2e-5)


def test_heads_that_do_not_divide_into_the_groups_are_refused():
    xs, dt, A, B, C, D = inputs(1, h=6, g=1)
    with pytest.raises(ValueError, match="do not divide"):
        ssd_scan(xs, dt, A, jnp.tile(B, (1, 1, 4, 1)),
                 jnp.tile(C, (1, 1, 4, 1)), D, chunk=8, interpret=True)


def test_tracing_a_call_sets_the_state_gauges():
    import byteps_tpu as bps
    ssd_scan(*inputs(3, b=2, t=32, h=4, p=8, n=16), chunk=8, interpret=True)
    gauges = bps.metrics_snapshot()["gauges"]
    assert gauges["ssm.heads_held"] == 4 and gauges["ssm.chunk"] == 8
    assert gauges["ssm.chunks_per_seq"] == 4
    assert gauges["ssm.state_bytes"] == 4 * 4 * 16 * 8
    assert gauges["ssm.saved_state_bytes"] == 2 * 4 * 4 * 4 * 16 * 8


def test_the_undifferentiated_forward_stores_no_states():
    """One kernel output without ``jax.grad``, two (``y`` and the
    chunk-start states) under it; the backward is one kernel more."""
    args = inputs(4)

    def kernels(fn):
        """Outputs of each ``pallas_call`` ``fn`` traces to, in order."""
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append(len(eqn.outvars))
                for inner in _inner_jaxprs(eqn):
                    walk(inner)
        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        return found

    scan = lambda *a: ssd_scan(*a, chunk=8, interpret=True)
    assert kernels(scan) == [1]
    assert kernels(jax.grad(lambda *a: scan(*a).sum(), argnums=(0, 1))) == [
        2, 6]
