"""What the tests of the route stage's selection (PR 41,
``tests/test_route_select_*.py``) share: the stage as it was, the scores
they select from, a small layer, a walk over a jaxpr's equations.

Those files hold ONE test function each, looping over its cases: the
tier-1 run hands files to its workers largest first (``--dist loadfile``
orders by test count), and files of one test come last — a larger file,
or a new one further up, moves the slot of every chaos test after it, and
a whole run then failed one of them on timing (twice of three runs while
these cases were sixty tests of ``test_moe_row_passes.py``)."""

import jax
import jax.numpy as jnp
import numpy as np

from byteps_tpu.parallel import expert

H, F, E, N, WINDOW = 32, 16, 8, 48, 16


def selected(probs, bias, top_k):
    """The stage as it was: ``lax.top_k`` of the chooser, the scores
    gathered at its indices, ``bincount``."""
    chooser = probs if bias is None else probs + bias
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(chooser), top_k)
    return (idx, jnp.take_along_axis(probs, idx, axis=-1),
            jnp.bincount(idx.reshape(-1), length=probs.shape[1]
                         ).astype(jnp.int32))


def with_the_stage_as_it_was(monkeypatch):
    monkeypatch.setattr(
        expert, "_select_experts",
        lambda probs, bias, top_k, interpret: selected(probs, bias, top_k))


def with_the_window(monkeypatch, window, seen=None):
    """A held layer takes windows of ``window`` rows (``None``: the whole
    arrays) whatever ``layer_plan`` says of its shapes; the plan is
    otherwise the rule's.  ``seen`` collects what the layer asked."""
    rule = expert.layer_plan

    def plan(*shape):
        if seen is not None:
            seen.append(shape)
        return rule(*shape)._replace(
            kind="held_rows" if window is None else "held_windows",
            window=window)
    monkeypatch.setattr(expert, "layer_plan", plan)


def layer_params(count, router=True):
    """SiLU-gated stacks of ``count`` experts (and a router over E)."""
    k = jax.random.split(jax.random.PRNGKey(11), 4)
    p = {"up": jax.random.normal(k[0], (count, H, F)) / np.sqrt(H),
         "down": jax.random.normal(k[1], (count, F, H)) / np.sqrt(F),
         "gate": jax.random.normal(k[2], (count, H, F)) / np.sqrt(H)}
    if router:
        p["router"] = jax.random.normal(k[3], (H, E))
    return p


def tokens():
    return jax.random.normal(jax.random.PRNGKey(1), (N, H))


def equation_stacks(jaxpr, prefix=""):
    """(primitive, name stack) of every equation of a jaxpr, nested ones
    too (a kernel's own aside), the stack rendered as the lowering renders
    an HLO ``op_name``."""
    for eqn in jaxpr.eqns:
        stack = "/".join(p for p in (prefix, str(eqn.source_info.name_stack))
                         if p)
        yield eqn.primitive.name, stack
        if eqn.primitive.name == "pallas_call":
            continue
        for key, value in eqn.params.items():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if not hasattr(sub, "eqns"):
                    continue
                step = {"jit": f"jit({eqn.params.get('name')})",
                        "while": "while/" + key.split("_")[0]}.get(
                            eqn.primitive.name, "")
                yield from equation_stacks(sub, "/".join(
                    p for p in (stack, step) if p))
