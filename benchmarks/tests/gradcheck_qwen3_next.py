"""What the timed program of ``qwen3_next_80b.fused_1c`` computes, against
the plain reference, beyond the loss (ISSUE 46): at random weights a loss
is ~ln(vocabulary) whatever the layers do, so this compares the step's
GRADIENTS leaf by leaf (relative L2) on one batch of the cell's own sizes
and the first sequence's logits over all columns of the slice — in the
manner of ``gradcheck_ling.py``, whose measures these are — and the
head-decay delta-rule scan ALONE at the cell's shape (one sequence).

    python3 benchmarks/tests/gradcheck_qwen3_next.py [--seed N]
        [--rehearsal] [--break WHAT ... | --all-breaks | --scan-only
         | --model-only]

Prints one JSON line a comparison (``measure``: ``model`` or ``scan``).
``--break`` puts one deliberate fault into the PROGRAM first; the
comparison has to fail then.  ``--scan-only`` reads the scan as it is,
under its stand-in (``chunked_stand_in``: has to pass too) and under its
two precision faults, without building the model; ``--model-only`` the
program as it is and under each of ``MODEL_BREAKS``; ``--all-breaks``
both.  Exit 0 iff the clean comparisons pass and every fault fails (a
single ``--break``: iff that comparison passes).

Limits, with their reason.  The program computes in bfloat16 with float32
accumulation and the reference in float32; the scan's log-decays, their
sums, ``D``, the solve and the carried state are float32 in both.  Each
limit has a LOWER reading (the largest the program as it is gave) and,
where a fault was read against it, an UPPER one (the smallest a fault
gave); a limit with no upper reading guards against a gross fault only,
and says so.  Readings: my chip runs, PR 46 — the scan alone at 1 x 8192
positions, seeds 4646100011 (chunk 64) and 4646100021 (chunk 128, the
last of each pair); the model at 1 x 8192, seed 4646200011, chunk 128
(``PERF.md`` section 6):

- ``GRAD_RTOL`` (leaves of more than ``SMALL_LEAF`` numbers that are not
  the routed experts'): the residual stream is rounded to bfloat16 after
  each of 8 additions.  LOWER 0.120 (a DeltaNet layer's ``in_proj_ba``;
  the attention's leaves 0.09-0.11).  UPPER: the q / k norms left out
  0.307 (``k_proj``), the rotation 0.536, the attention's gate 0.92, the
  shared expert's gate 1.02, the DeltaNet output gate 1.66.  The limit is
  the geometric mean of 0.120 and 0.307: 1.6 times of room either side.
- ``ROUTED_RTOL`` (the routers and the held experts' stacks): where the
  rounding moves a token's 10th and 11th largest probability past each
  other — 512 near-equal probabilities at random weights — the token
  changes an expert, so these leaves read highest: LOWER 0.244 (a
  router; the stacks 0.15-0.23).  NO upper reading (no break is the
  routed experts'); under twice the clean reading: guards against a gross
  fault only.
- ``SMALL_GRAD_RTOL`` (all leaves of at most ``SMALL_LEAF`` numbers
  together, as one vector), ``GATE_RTOL`` (every DeltaNet layer's ``A_log``
  and ``dt_bias`` together — the leaves only the decays reach) and
  ``LOGIT_RTOL`` (the first sequence's logits): LOWER 0.107 / 0.098 /
  0.062; the weakest break (the q / k norms of one layer in four) reads
  0.128 / 0.112 / 0.071, inside twice the clean reading, which is where
  the limits stand: they guard against a gross fault only (the two gates'
  breaks read 1.0-1.4 on all three).
- ``SCAN_RTOL``: the scan ALONE at the cell's shape (16 key heads under 32
  value heads of 128 x 128, one sequence of 8 192 positions) on the cell's
  bfloat16 operands against the delta rule position by position on the
  same values: ``o`` and every input's gradient read 0.0029-0.0038
  (``d_k`` highest) at either chunk — the operands' rounding; a rounded
  state or rounded decays read the same there (0.0041 at most): NOT
  separable on bfloat16 operands.  Twice the clean reading: guards the
  algebra.
- ``SCAN_F32_RTOL``: the same values as FLOAT32 operands (the kernels then
  multiply at ``highest``; the solve stays three bfloat16 passes), so that
  only the scan's own float32 side is left: LOWER 4.2e-6 / 7.7e-6
  (``d_g``; every other 4e-7 / 8e-7; the chunked form in the kernels'
  place reads the same to three digits).  UPPER: ``bf16_state`` (the state
  rounded to bfloat16 as each chunk hands it on) 2.3e-4 / 1.55e-4,
  ``bf16_decays`` (``g`` rounded to bfloat16 before the chunk sums) 2.6e-3
  on both.  The limit is the geometric mean of 7.7e-6 and 1.55e-4: 4.5
  times of room either side.
- the loss within 1e-2 (``harness/checks.py`` ``LOSS_RTOL``).
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

from gradcheck_glm_lite import inputs  # noqa: E402
from gradcheck_ling import _scan_side, reference  # noqa: E402
from gradcheck_zaya import logits_rel_l2, rel_l2  # noqa: E402

CELL = "qwen3_next_80b.fused_1c"
GRAD_RTOL = 0.19      # relative L2 of a gradient leaf ...
SMALL_LEAF = 4096     # ... of more than this many numbers; the smaller ones
SMALL_GRAD_RTOL = 0.21  # together, as one vector, this
ROUTED_RTOL = 0.45    # ... and a router's or a held expert stack's this
GATE_RTOL = 0.2       # A_log and dt_bias of every DeltaNet layer, together
LOGIT_RTOL = 0.12     # relative L2 of the first sequence's logits
GATE_LEAVES = ("A_log']", "dt_bias']")
ROUTED_LEAVES = tuple(f"['moe']['{name}']"
                      for name in ("router", "gate", "up", "down"))
SCAN_RTOL = 0.008     # the scan alone: o and five gradients, relative L2
SCAN_F32_RTOL = 3.5e-5  # ... on the same values as float32 operands
SCAN_NAMES = ("o", "d_q", "d_k", "d_v", "d_g", "d_beta")
SCAN_PASSES = (None, "chunked_stand_in")
SCAN_FAULTS = ("bf16_state", "bf16_decays")
MODEL_BREAKS = ("qk_norms_dropped", "rotation_dropped",
                "attention_gate_dropped", "gdn_gate_dropped",
                "shared_gate_dropped")
BREAKS = SCAN_FAULTS + MODEL_BREAKS


@contextlib.contextmanager
def broken(what):
    """One deliberate fault in what the program computes (patches on the
    program's modules, undone on exit; the jit caches are emptied on both
    sides, because the scan's kernels are traced under inner ``jax.jit``s
    that a patched chunk text would not re-key).  ``chunked_stand_in`` is
    no fault: ``gdn_scan_chunked`` in the kernels' place, which has to
    PASS; ``bf16_state`` rounds the state every chunk hands on to
    bfloat16; ``bf16_decays`` rounds ``g`` to bfloat16 before the chunk
    sums; then the attention's q / k norms left out, its rotation, its
    output gate, the DeltaNet output gate (``silu(z)``) and the shared
    expert's gate."""
    import importlib
    from unittest import mock
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import byteps_tpu.models.qwen3_next as model
    scan = importlib.import_module("byteps_tpu.ops.gdn_scan")
    rows = importlib.import_module("byteps_tpu.ops.kda_rows")

    kernels, head_text, post = (scan.gdn_scan, scan._head_forward,
                                rows.kda_post)

    def stand_in(q, k, v, g, beta, *, chunk, interpret=None):
        return scan.gdn_scan_chunked(q, k, v, g, beta, chunk=chunk)

    def rounded_state(*args):
        o, state = head_text(*args)
        return o, state.astype(jnp.bfloat16).astype(jnp.float32)

    def low_decays(q, k, v, g, beta, **kw):
        # ``reduce_precision``, not a pair of casts: XLA:TPU folds those
        # away as excess precision
        return kernels(q, k, v, jax.lax.reduce_precision(g, 8, 7), beta,
                       **kw)

    class NoNorm(nn.Module):
        """The same weight, never applied."""
        eps: float
        dtype: object

        @nn.compact
        def __call__(self, x):
            self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                       jnp.float32)
            return x.astype(self.dtype)

    def ungated(o, gate, weight, *, eps, gate_act, **kw):
        # sigmoid(30) is 1 in bfloat16 and in float32
        return post(o, jnp.full_like(gate, 30.0), weight, eps=eps,
                    gate_act="sigmoid", **kw)

    patches = {
        "chunked_stand_in": [(scan, "gdn_scan", stand_in)],
        "bf16_state": [(scan, "_head_forward", rounded_state)],
        "bf16_decays": [(scan, "gdn_scan", low_decays)],
        "qk_norms_dropped": [(model, "QkNorm", NoNorm)],
        "rotation_dropped": [(model, "apply_rope",
                              lambda x, cos, sin, rotary_dim=None: x)],
        "attention_gate_dropped": [
            (model, "attention_gate",
             lambda ctx, gamma, dtype: ctx.astype(dtype))],
        "gdn_gate_dropped": [(rows, "kda_post", ungated)],
        "shared_gate_dropped": [
            (model, "join_shared",
             lambda routed, shared, gate, dtype: (
                 routed.astype(jnp.float32) + shared.astype(jnp.float32)
             ).astype(dtype))],
    }
    if what not in patches:
        raise ValueError(f"unknown break {what!r}")
    jax.clear_caches()
    try:
        with contextlib.ExitStack() as stack:
            for where, name, fault in patches[what]:
                stack.enter_context(mock.patch.object(where, name, fault))
            yield
    finally:
        jax.clear_caches()


def scan_inputs(family, seed: int):
    """One layer's scan of ONE sequence at the cell's shape: ``q``, ``k``
    unit vectors (``q`` / sqrt(d)) and ``v`` = silu(unit normal) in the
    cell's compute type, ``beta`` = sigmoid(unit normal), a cotangent for
    ``o`` from ``seed``; ``g = -A softplus(alpha + 1)`` with ``alpha`` a
    unit normal from ``seed`` and ``A`` laid on a grid over the range the
    model draws it from ([1, 16] over the value heads), the same on every
    seed: -1.3 to -21 a position at ``alpha = 0``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    _, t, hk, hv, d = family.gdn_shape
    b = 1                     # one sequence: the scan mixes none
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    lp = family.compute_dtype

    def unit(key):
        x = jax.random.normal(key, (b, t, hk, d))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    a = jnp.asarray(np.linspace(1.0, 16.0, hv), jnp.float32)
    g = -a * jax.nn.softplus(jax.random.normal(keys[3], (b, t, hv)) + 1.0)
    args = ((unit(keys[0]) / np.sqrt(d)).astype(lp), unit(keys[1]).astype(lp),
            jax.nn.silu(jax.random.normal(keys[2], (b, t, hv, d))).astype(lp),
            g, jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, hv))))
    return args, jax.random.normal(keys[5], (b, t, hv, d))


def scan_reference(family, seed: int):
    """The reference's side of :func:`scan_rel`: the delta rule position
    by position (``families/qwen3_next.py`` ``delta_rule``) in float32 on
    the same bfloat16-valued inputs."""
    import jax
    import jax.numpy as jnp
    from harness import spec
    delta_rule = spec.load_module("families", "qwen3_next").delta_rule
    args, weight = scan_inputs(family, seed)
    with jax.default_matmul_precision("highest"):
        return _scan_side(delta_rule,
                          tuple(a.astype(jnp.float32) for a in args), weight)


def scan_rel(family, seed: int, want, float32: bool = False) -> dict:
    """The scan ALONE, as the model calls it (``ops/gdn_scan.py``
    ``gdn_scan`` at the model's chunk), against :func:`scan_reference`.
    With ``float32`` the SAME values go in as float32 operands."""
    import importlib
    import math
    import jax.numpy as jnp
    from byteps_tpu.models.qwen3_next import GDN_CHUNK
    scan = importlib.import_module("byteps_tpu.ops.gdn_scan")
    args, weight = scan_inputs(family, seed)
    if float32:
        args = tuple(a.astype(jnp.float32) for a in args)
    chunk = math.gcd(args[0].shape[1], GDN_CHUNK)
    got = _scan_side(lambda *a: scan.gdn_scan(*a, chunk=chunk), args, weight)
    return {name: rel_l2(g, w) for name, g, w in zip(SCAN_NAMES, got, want)}


def scan_compare(family, seed: int, want=None) -> dict:
    """The scan alone on the cell's operands and on the same values as
    float32, under whatever :func:`broken` has put in place."""
    want = want or scan_reference(family, seed)
    low = scan_rel(family, seed, want)
    f32 = scan_rel(family, seed, want, float32=True)
    return {"ok": bool(max(low.values()) <= SCAN_RTOL
                       and max(f32.values()) <= SCAN_F32_RTOL),
            "seed": seed, "scan_rel_l2": low, "scan_f32_rel_l2": f32,
            "scan_rtol": SCAN_RTOL, "scan_f32_rtol": SCAN_F32_RTOL}


def compare(family, params, batch, want=None) -> dict:
    """Gradients of the program's loss and of the reference's on ``batch``
    (one after the other) and the first sequence's logits."""
    import jax
    import numpy as np
    loss, grads = jax.jit(jax.value_and_grad(family.loss_fn))(params, batch)
    loss, grads = float(loss), jax.tree.map(np.asarray, grads)
    if want is None:
        want = reference(family, params, batch)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want["grads"])[0])
    leaves, routed, small = {}, {}, {}
    vectors = {"small": ([], []), "gate": ([], [])}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        key = jax.tree_util.keystr(path)
        if g.size > SMALL_LEAF:
            kind = routed if key.endswith(ROUTED_LEAVES) else leaves
            kind[key] = rel_l2(g, flat_want[path])
            continue
        small[key] = rel_l2(g, flat_want[path])
        groups = ["small"] + (["gate"] if key.endswith(GATE_LEAVES) else [])
        for group in groups:
            vectors[group][0].append(g.ravel())
            vectors[group][1].append(flat_want[path].ravel())
    small_dev, gate_dev = (rel_l2(np.concatenate(got), np.concatenate(ref))
                           for got, ref in (vectors["small"],
                                            vectors["gate"]))
    del grads
    x = jax.jit(family.hidden)(params, batch["input_ids"][:1])
    # the helper reads a table [V, h]
    logit_dev = logits_rel_l2(x[0], want["rows"],
                              params["params"]["lm_head"].T)
    worst = max(leaves, key=leaves.get)
    worst_routed = max(routed.values())
    want_loss = want["loss"]
    return {"ok": bool(leaves[worst] <= GRAD_RTOL
                       and worst_routed <= ROUTED_RTOL
                       and small_dev <= SMALL_GRAD_RTOL
                       and gate_dev <= GATE_RTOL
                       and logit_dev <= LOGIT_RTOL
                       and abs(loss - want_loss) <= 1e-2 * abs(want_loss)),
            "loss": loss, "reference_loss": want_loss,
            "worst_leaf": worst, "worst_rel_l2": leaves[worst],
            "worst_routed_rel_l2": worst_routed,
            "small_leaves_rel_l2": small_dev, "gate_leaves_rel_l2": gate_dev,
            "logits_rel_l2": logit_dev,
            "grad_rtol": GRAD_RTOL, "routed_rtol": ROUTED_RTOL,
            "small_grad_rtol": SMALL_GRAD_RTOL,
            "gate_rtol": GATE_RTOL, "logit_rtol": LOGIT_RTOL,
            "leaves": {**leaves, **routed, **small}}


def build(rehearsal: bool, seqs=None, **config_overrides):
    from harness import spec
    found = spec.resolve(spec.load_benchmark(), CELL)
    config, traffic = found["config"], found["traffic"]
    if rehearsal:
        config, traffic = (spec.with_rehearsal(config),
                           spec.with_rehearsal(traffic))
    if seqs:
        traffic = dict(traffic, seqs_per_chip=seqs)
    family = spec.load_module("families", config["family"]).build(
        dict(config, **config_overrides), traffic)
    return family, int(traffic["seqs_per_chip"])


def run(seed: int, rehearsal: bool, faults=(None,), model: bool = True,
        scan: bool = True, seqs=None):
    """One comparison a fault (``None``: the program as it is), each
    reference made once; yields ``(fault, result)``.  The stand-in and the
    ``SCAN_FAULTS`` are read by the scan alone, the ``MODEL_BREAKS`` by
    the model's gradients, the program as it is by both."""
    family, seqs = build(rehearsal, seqs)
    scan_kinds = (*SCAN_PASSES, *SCAN_FAULTS) if scan else ()
    by_model = [f for f in faults if model and f not in scan_kinds[1:]]
    if any(f in scan_kinds for f in faults):
        want_scan = scan_reference(family, seed)
    if by_model:
        params, batch = inputs(family, seqs, seed)
        want = reference(family, params, batch)
    for fault in faults:
        with broken(fault) if fault else contextlib.nullcontext():
            if fault in scan_kinds:
                yield fault, dict(scan_compare(family, seed, want_scan),
                                  measure="scan")
            if fault in by_model:
                # built inside: new closures, so no jit cache outlives a
                # break
                family, _ = build(rehearsal, seqs)
                yield fault, dict(compare(family, params, batch, want),
                                  measure="model")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--seqs", type=int, default=None,
                    help="sequences of the model's batch (the cell's own "
                         "where not given)")
    ap.add_argument("--break", dest="fault", action="append",
                    choices=(*SCAN_PASSES[1:], *BREAKS))
    ap.add_argument("--all-breaks", action="store_true")
    ap.add_argument("--scan-only", action="store_true")
    ap.add_argument("--model-only", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    platform = jax.devices()[0].platform
    if not args.rehearsal and platform != "tpu":
        print(f"gradcheck: no TPU ({platform}); --rehearsal is the CPU toy",
              file=sys.stderr)
        return 2
    every = not args.fault and (args.all_breaks or args.scan_only
                                or args.model_only)
    if args.fault:
        faults = tuple(args.fault)
    elif args.scan_only:
        faults = (*SCAN_PASSES, *SCAN_FAULTS)
    elif args.model_only:
        faults = (None, *MODEL_BREAKS)
    elif args.all_breaks:
        faults = (*SCAN_PASSES, *BREAKS)
    else:
        faults = (None,)
    device = {"platform": platform, "kind": jax.devices()[0].device_kind}
    if args.rehearsal:
        device["rehearsal"] = True
    as_expected = all_ok = True
    for fault, out in run(args.seed, args.rehearsal, faults,
                          model=not args.scan_only,
                          scan=not args.model_only, seqs=args.seqs):
        out.update(broken=fault, device=device)
        if every and fault is not None:
            out.pop("leaves", None)      # the clean line carries them
        print(json.dumps(out), flush=True)
        all_ok &= out["ok"]
        as_expected &= out["ok"] == (fault in SCAN_PASSES)
    if every:
        return 0 if as_expected else 1
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
