"""Equation counts of traced programs: what pins "this option leaves the
program as it was" as numbers (a Pallas kernel's size is set-up time)."""

import jax


def _inner_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def equations(jaxpr) -> int:
    """Equations of a jaxpr, nested jaxprs included."""
    return sum(1 + sum(equations(j) for j in _inner_jaxprs(e))
               for e in jaxpr.eqns)


def kernel_equations(fn, *args) -> list:
    """``equations`` of each ``pallas_call`` kernel ``fn(*args)`` traces
    to, in order."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(equations(eqn.params["jaxpr"]))
            else:
                for inner in _inner_jaxprs(eqn):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def scan_bodies(fn, *args) -> list:
    """The body jaxpr of each ``scan`` ``fn(*args)`` traces to, in order,
    nested ones included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                found.append(eqn.params["jaxpr"].jaxpr)
            for inner in _inner_jaxprs(eqn):
                walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found
