"""What the timed program of ``glm47_flash.fused_1c`` computes, against the
plain reference, beyond the loss (ISSUE 35, Tentpole 5): at random weights
a loss is ~ln(vocabulary) whatever the layers do, so this compares the
step's GRADIENTS leaf by leaf (relative L2) on one batch of the cell's own
sizes, the first sequence's logits of BOTH heads over all columns of the
slice (in blocks of rows), and the blocked head alone on the program's own
rows.

    python3 benchmarks/tests/gradcheck_glm_lite.py [--seed N] [--rehearsal]
                                   [--bias S] [--break WHAT | --all-breaks]

On the chip at the published widths; ``--rehearsal`` is the CPU toy (same
control flow, the configuration's and the traffic's ``rehearsal`` sizes).
Prints one JSON line a comparison: ``ok``, the worst leaf, every leaf's
deviation.  ``--break`` (one of ``BREAKS``) puts one deliberate fault into
the PROGRAM first: the comparison has to fail then (exit code 1).
``--all-breaks`` makes the reference once, compares the clean program and
then the program under each break, a line each: exit 0 iff the clean
comparison passes and every break fails.  ``--bias S`` moves every
selection bias off its zero by seeded noise of scale ``S`` (the same leaves
on both sides: the bias is a parameter of the published model, and one
that only chooses); ``bias_weighed`` shows only beside a bias that is not
zero, so that break and ``--all-breaks`` take 0.3 where none is given.

Limits, with their reason.  The program computes in bfloat16 (8 mantissa
bits: one rounding is 2^-9 to 2^-8 relative; float32 accumulation) and the
reference in float32.  Readings on the chip at the cell's sizes: PERF.md
section 6 PR 35.

- ``GRAD_RTOL`` (leaves of more than ``SMALL_LEAF`` numbers) and
  ``LOGIT_RTOL`` (each head's logits): the residual stream is rounded to
  bfloat16 after each of 12 additions and around ~60 matmuls, and where
  the rounding moves a token's fourth and fifth largest ``score + bias``
  past each other the token changes one of its four experts (the other
  three and the renormalisation soften it, which a top-1 router's flip
  nothing does).  Under the zero bias the random routers' sigmoids lie
  close together and flips are many: the largest seen are a router's
  gradient at 0.34 and a head's logits at 0.063 (0.24 and 0.034 beside a
  bias of noise 0.3, which spreads the choice).  ``LOGIT_RTOL`` is twice
  the largest seen.  ``GRAD_RTOL`` cannot be: the weakest breaks (the 1.8
  dropped: every routed leaf's gradient x 1/1.8; the bias weighed) read
  0.52 to 0.61 on the chip, so the limit lies between, a fifth above the
  largest clean reading and a quarter below the weakest break's.
- ``SMALL_GRAD_RTOL``: the leaves of at most ``SMALL_LEAF`` numbers (the
  norms' scales: every number ONE sum over all 16 384 tokens of terms that
  cancel) compared TOGETHER, as one vector whose large members set the
  scale (PERF.md section 7 (18)'s repair: no limit above 1, so a zero or a
  gradient of the wrong sign fails); each one's own deviation is printed
  beside it and held to nothing.  Twice the largest seen (0.079).
- ``HEAD_RTOL``: the blocked head alone, on the SAME rows and the same
  bfloat16-rounded matrix as a float32 head: the sum of the first
  ``HEAD_ROWS`` positions' negative log-likelihoods.  Products of bfloat16
  values are exact in float32, so the two differ by summation order only;
  logits rounded to bfloat16 before the log-sum-exp move each token's term
  by ~2e-3 of a logit, unbiased, so only a sum over FEW tokens shows it
  (which is why the loss, the gradients and the logits cannot see that
  break).
- the loss within 1e-2 (``harness/checks.py`` ``LOSS_RTOL``).

Every break of ``BREAKS`` moves one of them past its limit, on the chip
(PERF.md section 6 PR 35) and on the toy in float32
(``test_glm_lite_cell.py``).
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
sys.path.insert(0, _HERE)

# the three measures are ZAYA's comparison's, unchanged: relative L2 of two
# arrays, of logits in blocks of rows against any [V, h] matrix, and the
# blocked head against a float32 head on ``HEAD_ROWS`` rows
from gradcheck_zaya import (HEAD_ROWS, head_rel, logits_rel_l2,  # noqa: E402
                            rel_l2)

CELL = "glm47_flash.fused_1c"
GRAD_RTOL = 0.4       # relative L2 of a gradient leaf ...
SMALL_LEAF = 4096     # ... of more than this many numbers; the smaller ones
SMALL_GRAD_RTOL = 0.16  # together, as one vector, this
LOGIT_RTOL = 0.12     # relative L2 of the first sequence's logits, a head
HEAD_RTOL = 2e-6      # relative, the head's NLL summed over HEAD_ROWS rows
BIAS_FOR_BREAKS = 0.3
BREAKS = ("rotary_key_not_rotated", "kv_latent_norm_dropped",
          "scale_over_nope_only", "softmax_scores", "bias_weighed",
          "not_renormalised", "scaling_dropped", "shared_expert_dropped",
          "mtp_reads_this_token", "mtp_labels_not_shifted",
          "logits_rounded_to_bf16")


@contextlib.contextmanager
def broken(what):
    """One deliberate fault in what the program computes (a patch on the
    program's modules, undone on exit): the one rotary key left unturned,
    the key/value latent's norm left out, the scores divided by sqrt(192),
    a softmax over the 64 experts in place of sigmoids, the bias added to
    the weights as well as to the choice, the four weights not
    renormalised, the 1.8 left out, the shared expert left out, the module
    reading ``Emb(t_i)``, the module's labels those of the main head, or a
    block's logits rounded to bfloat16 before the log-sum-exp."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import byteps_tpu.models.glm_lite as model
    import byteps_tpu.models.gpt as gpt
    if what == "rotary_key_not_rotated":
        where, name, real = model, "apply_rope", model.apply_rope

        def fault(x, cos, sin):
            return x if x.shape[-2] == 1 else real(x, cos, sin)
    elif what == "kv_latent_norm_dropped":
        where, name, real = model, "RMSNorm", model.RMSNorm

        class Unnormed(nn.Module):
            dtype: jnp.dtype

            @nn.compact
            def __call__(self, x):       # the tree keeps its ``scale``
                self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
                return x.astype(self.dtype)

        def fault(eps, dtype, name=None):
            if name == "kv_a_layernorm":
                return Unnormed(dtype, name=name)
            return real(eps, dtype, name=name)
    elif what == "scale_over_nope_only":
        where, name, real = model, "score_scale", model.score_scale

        def fault(cfg):
            return cfg.qk_nope_head_dim ** -0.5
    elif what == "softmax_scores":
        where, name, real = model, "router_scores", model.router_scores

        def fault(rows, router):
            return jax.nn.softmax(jnp.dot(
                rows, router, precision=jax.lax.Precision.HIGHEST), -1)
    elif what in ("bias_weighed", "not_renormalised"):
        where, name, real = model, "dropless_moe_mlp", model.dropless_moe_mlp

        def fault(*a, **kw):
            if what == "not_renormalised":
                return real(*a, **{**kw, "renormalize": False})
            scores, bias = kw["routing"]
            return real(*a, **{**kw, "routing": (scores + bias, None)})
    elif what in ("scaling_dropped", "shared_expert_dropped"):
        where, name, real = model, "join_experts", model.join_experts

        def fault(routed, shared, scaling, dtype):
            if what == "scaling_dropped":
                return real(routed, shared, 1.0, dtype)
            return real(routed, jnp.zeros_like(shared), scaling, dtype)
    elif what == "mtp_reads_this_token":
        where, name, real = model, "next_tokens", model.next_tokens

        def fault(input_ids):
            return input_ids
    elif what == "mtp_labels_not_shifted":
        where, name, real = model, "mtp_labels", model.mtp_labels

        def fault(labels):
            return labels
    elif what == "logits_rounded_to_bf16":
        where, name, real = gpt, "_block_logits", gpt._block_logits

        def fault(xb, w):
            # not a convert pair: XLA:TPU keeps excess precision through
            # f32 -> bf16 -> f32 and the fault would be none (PR 31)
            return jax.lax.reduce_precision(real(xb, w), exponent_bits=8,
                                            mantissa_bits=7)
    else:
        raise ValueError(f"unknown break {what!r}; one of {BREAKS}")
    setattr(where, name, fault)
    try:
        yield
    finally:
        setattr(where, name, real)


def _without_last(rows):
    """The module's rows with the last position zeroed: it has no next
    token (the program reads a wrapped one there, the reference a zero row)
    and nothing scores it."""
    import numpy as np
    rows = np.array(rows)
    rows[-1] = 0
    return rows


def reference(family, params, batch) -> dict:
    """The reference's side of :func:`compare`: loss, gradients (on the
    host) and the rows the first sequence's two heads read."""
    import jax
    import numpy as np
    loss, grads = jax.jit(jax.value_and_grad(family.reference_loss))(
        params, batch)
    grads = jax.tree.map(np.asarray, grads)
    with jax.default_matmul_precision("highest"):
        x, g = jax.jit(family.reference_hidden)(params,
                                                batch["input_ids"][:1])
    return {"loss": float(loss), "grads": grads, "rows": np.asarray(x[0]),
            "mtp_rows": _without_last(g[0])}


def compare(family, params, batch, want=None) -> dict:
    """Gradients of the program's loss and of the reference's on ``batch``
    (one after the other: both trees do not fit the chip at once), the
    first sequence's logits of both heads, the head alone.  ``want``: a
    :func:`reference` of the same parameters and batch made earlier."""
    import jax
    import numpy as np
    loss, grads = jax.jit(jax.value_and_grad(family.loss_fn))(params, batch)
    loss, grads = float(loss), jax.tree.map(np.asarray, grads)
    if want is None:
        want = reference(family, params, batch)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want["grads"])[0])
    leaves, small, got_small, want_small = {}, {}, [], []
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        if g.size > SMALL_LEAF:
            leaves[jax.tree_util.keystr(path)] = rel_l2(g, flat_want[path])
        else:
            small[jax.tree_util.keystr(path)] = rel_l2(g, flat_want[path])
            got_small.append(g.ravel())
            want_small.append(flat_want[path].ravel())
    small_dev = rel_l2(np.concatenate(got_small), np.concatenate(want_small))
    del grads
    x, g = jax.jit(family.hidden)(params, batch["input_ids"][:1])
    head = params["params"]["lm_head"]
    logit_dev = logits_rel_l2(x[0], want["rows"], head)
    mtp_logit_dev = logits_rel_l2(
        jax.numpy.asarray(_without_last(g[0]), g.dtype), want["mtp_rows"],
        head)
    head_dev = head_rel(x[0], head, batch["labels"][0])
    worst = max(leaves, key=leaves.get)
    want_loss = want["loss"]
    return {"ok": bool(leaves[worst] <= GRAD_RTOL
                       and small_dev <= SMALL_GRAD_RTOL
                       and logit_dev <= LOGIT_RTOL
                       and mtp_logit_dev <= LOGIT_RTOL
                       and head_dev <= HEAD_RTOL
                       and abs(loss - want_loss) <= 1e-2 * abs(want_loss)),
            "loss": loss, "reference_loss": want_loss,
            "worst_leaf": worst, "worst_rel_l2": leaves[worst],
            "small_leaves_rel_l2": small_dev,
            "logits_rel_l2": logit_dev, "mtp_logits_rel_l2": mtp_logit_dev,
            "head_rel": head_dev,
            "grad_rtol": GRAD_RTOL, "small_grad_rtol": SMALL_GRAD_RTOL,
            "logit_rtol": LOGIT_RTOL, "head_rtol": HEAD_RTOL,
            "leaves": {**leaves, **small}}


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


def inputs(family, seqs: int, seed: int, bias: float = 0.0):
    """(parameters, one batch) from ``seed``, as ``run.py`` makes them;
    ``bias``: the scale of seeded noise on every selection bias."""
    import jax
    param_key, data_key = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.jit(family.init_params)(param_key)
    if bias:
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a + bias * jax.random.normal(
                jax.random.fold_in(param_key, 1), a.shape, a.dtype)
            if "e_score_correction_bias" in jax.tree_util.keystr(path) else a,
            params)
    batch = jax.jit(family.make_batch, static_argnums=1)(
        jax.random.fold_in(data_key, 0), seqs)
    return params, batch


def run(seed: int, rehearsal: bool, faults=(None,), bias: float = 0.0,
        **config_overrides):
    """One comparison a fault (``None``: the program as it is), the
    reference made once; yields ``(fault, result)``."""
    family, seqs = build(rehearsal, **config_overrides)
    params, batch = inputs(family, seqs, seed, bias)
    want = reference(family, params, batch)
    for fault in faults:
        with broken(fault) if fault else contextlib.nullcontext():
            # built inside: new closures, so no jit cache outlives a break
            family, _ = build(rehearsal, **config_overrides)
            yield fault, compare(family, params, batch, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--bias", type=float, default=None)
    ap.add_argument("--break", dest="fault", choices=BREAKS, default=None)
    ap.add_argument("--all-breaks", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    platform = jax.devices()[0].platform
    if not args.rehearsal and platform != "tpu":
        print(f"gradcheck: no TPU ({platform}); --rehearsal is the CPU toy",
              file=sys.stderr)
        return 2
    faults = (None, *BREAKS) if args.all_breaks else (args.fault,)
    bias = args.bias
    if bias is None:
        bias = (BIAS_FOR_BREAKS if args.all_breaks
                or args.fault == "bias_weighed" else 0.0)
    device = {"platform": platform, "kind": jax.devices()[0].device_kind}
    if args.rehearsal:
        device["rehearsal"] = True
    as_expected = True
    for fault, out in run(args.seed, args.rehearsal, faults, bias):
        out.update(broken=fault, bias=bias, device=device)
        if args.all_breaks and fault is not None:
            out.pop("leaves")            # the clean line carries them
        print(json.dumps(out), flush=True)
        as_expected &= out["ok"] == (fault is None)
    if args.all_breaks:
        return 0 if as_expected else 1
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
