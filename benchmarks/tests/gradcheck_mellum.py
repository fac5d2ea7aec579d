"""What the timed program of ``mellum2_12b.fused_1c`` computes, against the
plain reference, beyond the loss (ISSUE 29, Tentpole 5 (ii)): at random
weights a loss is ~ln(vocabulary) whatever the mask does, so this compares
the step's GRADIENTS leaf by leaf (relative L2) on one batch of the
cell's own sizes, and the first sequence's logits.

    python3 benchmarks/tests/gradcheck_mellum.py [--seed N] [--rehearsal]
                                                 [--break WHAT]

On the chip at the published widths; ``--rehearsal`` is the CPU toy (same
control flow, the configuration's and the traffic's ``rehearsal`` sizes).
Prints one JSON line: ``ok``, the worst leaf, every leaf's deviation.
``--break`` (one of ``BREAKS``) puts one deliberate fault into the
PROGRAM first: the comparison has to fail then (exit code 1).

Tolerances, with their reason.  The program computes in bfloat16 (8
mantissa bits: one rounding is 2^-9 to 2^-8 relative; float32
accumulation) and the reference in float32.  Observed on the chip at the
cell's sizes (PERF.md section 6 PR 29, three seeds): the first sequence's
logits deviate by 2.5e-2 in relative L2 — the residual stream is rounded
to bfloat16 after each of 8 additions and around ~30 matmuls, and where
the rounding moves a token's 8th and 9th router probabilities past each
other the token changes an expert outright — and the gradient leaves by
1e-2 (the last norm) to 1.2e-1 (the first sliding layers' q/k
projections and the routers: small differences of large terms, which
keep the absolute noise of every later layer).  ``GRAD_RTOL`` and
``LOGIT_RTOL`` are about twice the largest seen.  A window off by one
sub-block, unrenormalised top-k weights or a missing ``attention_factor``
each move some leaf by 0.4 or more (``test_mellum_cell.py`` breaks each
once on the toy, in float32, where the clean comparison reads 1e-6).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))
sys.path.insert(0, os.path.dirname(_HERE))

CELL = "mellum2_12b.fused_1c"
GRAD_RTOL = 0.25      # relative L2 of a gradient leaf
LOGIT_RTOL = 0.06     # relative L2 of the first sequence's logits
BREAKS = ("window_off_by_a_sub_block", "weights_not_renormalised",
          "no_attention_factor")


@contextlib.contextmanager
def broken(what, window_by: int = 512):
    """One deliberate fault in what the program computes (a patch on the
    program's modules, undone on exit): the sliding window ``window_by``
    keys (one sub-block) too wide, the top-k weights not renormalised, or
    YaRN's ``attention_factor`` left at 1."""
    import byteps_tpu.models.mellum as model
    import byteps_tpu.ops as ops
    if what == "window_off_by_a_sub_block":
        where, name, real = ops, "flash_attention", ops.flash_attention

        def fault(q, k, v, **kw):
            if "window" in kw:
                kw["window"] += window_by
            return real(q, k, v, **kw)
    elif what == "weights_not_renormalised":
        where, name, real = model, "dropless_moe_mlp", model.dropless_moe_mlp

        def fault(*a, **kw):
            return real(*a, **{**kw, "renormalize": False})
    elif what == "no_attention_factor":
        where, name, real = model, "rope_frequencies", model.rope_frequencies

        def fault(d, positions, theta, yarn=None):
            return real(d, positions, theta,
                        yarn=yarn and {**yarn, "attention_factor": 1.0})
    else:
        raise ValueError(f"unknown break {what!r}; one of {BREAKS}")
    setattr(where, name, fault)
    try:
        yield
    finally:
        setattr(where, name, real)


def rel_l2(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def compare(family, params, batch) -> dict:
    """Gradients of the program's loss and of the reference's on ``batch``
    (one after the other: both trees do not fit the chip at once), and
    the first sequence's logits."""
    import jax
    import numpy as np

    def host(tree):
        return jax.tree.map(np.asarray, tree)

    loss, grads = jax.jit(jax.value_and_grad(family.loss_fn))(params, batch)
    loss, grads = float(loss), host(grads)
    want_loss, want = jax.jit(jax.value_and_grad(family.reference_loss))(
        params, batch)
    want_loss, want = float(want_loss), host(want)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    leaves = {jax.tree_util.keystr(path): rel_l2(g, flat_want[path])
              for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    del grads, want
    first = batch["input_ids"][:1]
    logits = np.asarray(jax.jit(family.logits)(params, first))
    want_logits = np.asarray(jax.jit(family.reference_logits)(params, first))
    worst = max(leaves, key=leaves.get)
    logit_dev = rel_l2(logits, want_logits)
    return {"ok": bool(leaves[worst] <= GRAD_RTOL and logit_dev <= LOGIT_RTOL
                       and abs(loss - want_loss) <= 1e-2 * abs(want_loss)),
            "loss": loss, "reference_loss": want_loss,
            "worst_leaf": worst, "worst_rel_l2": leaves[worst],
            "logits_rel_l2": logit_dev, "grad_rtol": GRAD_RTOL,
            "logit_rtol": LOGIT_RTOL, "leaves": leaves}


def build(rehearsal: bool, **config_overrides):
    from harness import spec
    found = spec.resolve(spec.load_benchmark(), CELL)
    config, traffic = found["config"], found["traffic"]
    if rehearsal:
        config, traffic = (spec.with_rehearsal(config),
                           spec.with_rehearsal(traffic))
    family = spec.load_module("families", config["family"]).build(
        dict(config, **config_overrides), traffic)
    return family, int(traffic["seqs_per_chip"])


def run(seed: int, rehearsal: bool, **config_overrides) -> dict:
    import jax
    family, seqs = build(rehearsal, **config_overrides)
    param_key, data_key = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.jit(family.init_params)(param_key)
    batch = jax.jit(family.make_batch, static_argnums=1)(
        jax.random.fold_in(data_key, 0), seqs)
    return compare(family, params, batch)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--break", dest="fault", choices=BREAKS, default=None)
    args = ap.parse_args(argv)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    platform = jax.devices()[0].platform
    if not args.rehearsal and platform != "tpu":
        print(f"gradcheck: no TPU ({platform}); --rehearsal is the CPU toy",
              file=sys.stderr)
        return 2
    with broken(args.fault) if args.fault else contextlib.nullcontext():
        out = run(args.seed, args.rehearsal)
    out["broken"] = args.fault
    out["device"] = {"platform": platform,
                     "kind": jax.devices()[0].device_kind}
    if args.rehearsal:
        out["device"]["rehearsal"] = True
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
