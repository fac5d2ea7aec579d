"""What the timed program of ``zaya1_8b.fused_1c`` computes, against the
plain reference, beyond the loss (ISSUE 31, Tentpole 5): at random weights
a loss is ~ln(vocabulary) whatever the layers do, so this compares the
step's GRADIENTS leaf by leaf (relative L2) on one batch of the cell's own
sizes, the first sequence's logits over all columns of the slice (in
blocks of rows: whole they are 8.6 GB), and the blocked head alone on the
program's own rows.

    python3 benchmarks/tests/gradcheck_zaya.py [--seed N] [--rehearsal]
                                               [--break WHAT]

On the chip at the published widths; ``--rehearsal`` is the CPU toy (same
control flow, the configuration's and the traffic's ``rehearsal`` sizes).
Prints one JSON line: ``ok``, the worst leaf, every leaf's deviation.
``--break`` (one of ``BREAKS``) puts one deliberate fault into the
PROGRAM first: the comparison has to fail then (exit code 1).

Limits, with their reason.  The program computes in bfloat16 (8 mantissa
bits: one rounding is 2^-9 to 2^-8 relative; float32 accumulation) and the
reference in float32.  Readings on the chip at the cell's sizes: PERF.md
section 6 PR 31 (seeds 2931000013, ...73, ...74).

- ``GRAD_RTOL`` (leaves of more than ``SMALL_LEAF`` numbers) and
  ``LOGIT_RTOL``: the residual stream is rounded to bfloat16 after each of
  8 additions and around ~40 matmuls, and the router is TOP-1: where the
  rounding moves a token's two largest probabilities past each other the
  token changes its expert outright, which no second expert softens.
  Largest seen: 0.161 / 0.134 / 0.187 (the router's leaves), logits 0.0238
  / 0.0270 / 0.0247; the limits are about twice the largest.
- ``SMALL_GRAD_RTOL`` (``gamma``, a scalar a layer; the two temperatures):
  such a leaf is ONE sum over all 16 384 tokens of terms that cancel, so
  the flipped tokens' share of it is not averaged over a leaf's many
  numbers: a ``gamma`` read 0.394 / 0.841 / 0.493 where every other small
  leaf stayed under 0.18.  Twice the largest seen is ABOVE 1: this limit
  refuses a gradient of the wrong scale and nothing finer (a zero would
  pass), and says so; what holds these leaves to their value is the toy in
  float32 (2e-5).  PERF.md section 7 (18) has the repair.
- ``HEAD_RTOL``: the blocked head alone, on the SAME rows and the same
  bfloat16-rounded table as a float32 head: the sum of the first
  ``HEAD_ROWS`` positions' negative log-likelihoods.  Products of bfloat16
  values are exact in float32, so the two differ by summation order only
  (0.0 in all three clean runs; up to 2.3e-7 across
  the other programs of call A); logits rounded to bfloat16 before the
  log-sum-exp move each token's term by ~2e-3 of a logit, unbiased, so
  only a sum over FEW tokens shows it (2.1e-5 on the chip; over 16 384
  tokens it averages out below float32's own noise — which is why the
  loss, the gradients and the logits cannot see that break).

Every break of ``BREAKS`` moves one of them past its limit, on the chip
(PERF.md section 6 PR 31) and on the toy in float32, where the clean
comparison reads 2e-5 (``test_zaya_cell.py``).
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

CELL = "zaya1_8b.fused_1c"
GRAD_RTOL = 0.4       # relative L2 of a gradient leaf ...
SMALL_LEAF = 16       # ... of more than this many numbers; of a smaller one
SMALL_GRAD_RTOL = 1.7  # (``gamma``, the two temperatures) this
LOGIT_RTOL = 0.06     # relative L2 of the first sequence's logits
HEAD_RTOL = 2e-6      # relative, the head's NLL summed over HEAD_ROWS rows
HEAD_ROWS = 64
LOGIT_BLOCK = 512     # rows of logits compared at a time
BREAKS = ("value_shift_dropped", "qk_mean_dropped", "conv_reads_t_plus_1",
          "router_state_not_carried", "weight_renormalised",
          "logits_rounded_to_bf16")


@contextlib.contextmanager
def broken(what):
    """One deliberate fault in what the program computes (a patch on the
    program's modules, undone on exit): key/value head 1 read from this
    token, the q-k mean left out, the convolutions reading position t + 1,
    the router's state not handed on (``gamma`` term dropped), the chosen
    expert's weight renormalised to 1, or a block's logits rounded to
    bfloat16 before the log-sum-exp."""
    import jax
    import jax.numpy as jnp
    import byteps_tpu.models.gpt as gpt
    import byteps_tpu.models.zaya as model
    if what == "value_shift_dropped":
        where, name, real = model, "token_before", model.token_before

        def fault(x):
            return x
    elif what == "qk_mean_dropped":
        where, name, real = model, "qk_mean", model.qk_mean

        def fault(q, k, groups):
            return jnp.zeros_like(q), jnp.zeros_like(k)
    elif what == "conv_reads_t_plus_1":
        where, name, real = model, "causal_convs", model.causal_convs

        def fault(u, *a):
            ahead = jnp.concatenate([u[:, 1:], jnp.zeros_like(u[:, :1])], 1)
            return real(ahead, *a)
    elif what == "router_state_not_carried":
        where, name, real = (model.ZayaRouter, "__call__",
                             model.ZayaRouter.__call__)

        def fault(self, m, r_before):
            # the parameters are made as they are (``gamma`` included)
            return real(self, m, r_before if self.is_initializing() else None)
    elif what == "weight_renormalised":
        where, name, real = model, "dropless_moe_mlp", model.dropless_moe_mlp

        def fault(*a, **kw):
            return real(*a, **{**kw, "renormalize": True})
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


def rel_l2(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def logits_rel_l2(got_rows, want_rows, table) -> float:
    """Relative L2 between the program's logits (float32 from its own
    rows and table in their compute dtype) and the reference's (float32,
    full precision) over ALL columns, ``LOGIT_BLOCK`` rows at a time."""
    import math
    import jax
    import jax.numpy as jnp
    from byteps_tpu.models.gpt import _block_logits
    n = got_rows.shape[0]
    rows = math.gcd(n, LOGIT_BLOCK)

    def total(got_rows, want_rows, table):
        def one_block(carry, block):
            got, want = block
            with jax.default_matmul_precision("highest"):
                ref = jnp.einsum("nh,vh->nv", want, table)
            dev = _block_logits(got, table.astype(got.dtype)) - ref
            return (carry[0] + jnp.sum(dev * dev),
                    carry[1] + jnp.sum(ref * ref)), None

        (dev, ref), _ = jax.lax.scan(
            one_block, (jnp.zeros(()), jnp.zeros(())),
            (got_rows.reshape(n // rows, rows, -1),
             want_rows.reshape(n // rows, rows, -1)))
        return jnp.sqrt(dev / ref)

    return float(jax.jit(total)(got_rows, want_rows, table))


def head_rel(rows, table, labels) -> float:
    """The blocked head against a float32 head on the same rows and the
    same (rounded) table: the first ``HEAD_ROWS`` positions' summed NLL."""
    import jax
    import jax.numpy as jnp
    from byteps_tpu.models.gpt import blocked_token_nll
    few = jnp.where(jnp.arange(labels.shape[0]) < HEAD_ROWS, labels, -1)
    # a new function each call: a jit cache keyed on the head itself would
    # outlive a ``broken`` block
    got, _ = jax.jit(lambda *a: blocked_token_nll(*a))(rows, table, few)

    def plain(rows, table, labels):
        with jax.default_matmul_precision("highest"):
            logits = jnp.einsum(
                "nh,vh->nv", rows[:HEAD_ROWS].astype(jnp.float32),
                table.astype(rows.dtype).astype(jnp.float32))
        logp = jax.nn.log_softmax(logits, -1)
        lb = labels[:HEAD_ROWS]
        ll = jnp.take_along_axis(logp, jnp.maximum(lb, 0)[:, None], -1)[:, 0]
        return -(ll * (lb >= 0)).sum()

    want = float(jax.jit(plain)(rows, table, few))
    return abs(float(got) - want) / abs(want)


def reference(family, params, batch) -> dict:
    """The reference's side of :func:`compare`: loss, gradients (on the
    host) and the first sequence's last-norm rows."""
    import jax
    import numpy as np
    loss, grads = jax.jit(jax.value_and_grad(family.reference_loss))(
        params, batch)
    grads = jax.tree.map(np.asarray, grads)
    rows = jax.jit(family.reference_hidden)(params,
                                            batch["input_ids"][:1])[0]
    return {"loss": float(loss), "grads": grads, "rows": np.asarray(rows)}


def compare(family, params, batch, want=None) -> dict:
    """Gradients of the program's loss and of the reference's on ``batch``
    (one after the other: both trees do not fit the chip at once), the
    first sequence's logits, the head alone.  ``want``: a
    :func:`reference` of the same parameters and batch made earlier."""
    import jax
    import numpy as np
    loss, grads = jax.jit(jax.value_and_grad(family.loss_fn))(params, batch)
    loss, grads = float(loss), jax.tree.map(np.asarray, grads)
    if want is None:
        want = reference(family, params, batch)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want["grads"])[0])
    leaves, small = {}, {}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        (small if g.size <= SMALL_LEAF else leaves)[
            jax.tree_util.keystr(path)] = rel_l2(g, flat_want[path])
    del grads
    rows = jax.jit(family.hidden)(params, batch["input_ids"][:1])[0]
    table = params["params"]["wte"]["embedding"]
    logit_dev = logits_rel_l2(rows, want["rows"], table)
    head_dev = head_rel(rows, table, batch["labels"][0])
    worst, worst_small = max(leaves, key=leaves.get), max(small,
                                                          key=small.get)
    want_loss = want["loss"]
    return {"ok": bool(leaves[worst] <= GRAD_RTOL
                       and small[worst_small] <= SMALL_GRAD_RTOL
                       and logit_dev <= LOGIT_RTOL and head_dev <= HEAD_RTOL
                       and abs(loss - want_loss) <= 1e-2 * abs(want_loss)),
            "loss": loss, "reference_loss": want_loss,
            "worst_leaf": worst, "worst_rel_l2": leaves[worst],
            "worst_small_leaf": worst_small,
            "worst_small_rel_l2": small[worst_small],
            "logits_rel_l2": logit_dev, "head_rel": head_dev,
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


def inputs(family, seqs: int, seed: int):
    """(parameters, one batch) from ``seed``, as ``run.py`` makes them."""
    import jax
    param_key, data_key = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.jit(family.init_params)(param_key)
    batch = jax.jit(family.make_batch, static_argnums=1)(
        jax.random.fold_in(data_key, 0), seqs)
    return params, batch


def run(seed: int, rehearsal: bool, **config_overrides) -> dict:
    family, seqs = build(rehearsal, **config_overrides)
    return compare(family, *inputs(family, seqs, seed))


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
