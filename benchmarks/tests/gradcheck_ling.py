"""What the timed program of ``ling3_flash.fused_1c`` computes, against the
plain reference, beyond the loss (ISSUE 43, Tentpole 7): at random weights
a loss is ~ln(vocabulary) whatever the layers do, so this compares the
step's GRADIENTS leaf by leaf (relative L2) on one batch of the cell's own
sizes, the first sequence's logits over all columns of the slice, the
blocked head alone on the program's own rows — in the manner of
``gradcheck_nemotron_h.py``, whose measures these are — and the delta-rule
scan ALONE at the cell's shape.

    python3 benchmarks/tests/gradcheck_ling.py [--seed N] [--rehearsal]
                         [--break WHAT ... | --all-breaks | --scan-only
                          | --model-only]

Prints one JSON line a comparison (``measure``: ``model`` or ``scan``).
``--break`` puts one deliberate fault into the PROGRAM first; the
comparison has to fail then.  ``--all-breaks`` makes each reference once
and compares the program as it is, the scan under its stand-in
(``chunked_stand_in``: has to pass too) and the program under each break:
exit 0 iff the clean comparisons pass, every break fails, and the
precision faults fail on EVERY seed.  ``--scan-only`` is the scan's part
of that, without building the model; ``--model-only`` the model's part (the
program as it is and under each of ``MODEL_BREAKS``), without the scan
alone — which is one sequence's call whatever the cell's batch; with
``--break`` beside it, that fault (a scan's too) through the model alone.

Limits, with their reason.  The program computes in bfloat16 with float32
accumulation and the reference in float32; the scan's log-decays, their
sums, the solve and the carried state are float32 in both.  Readings on
the chip (my chip runs, PR 43: calls 1, 2 and 4 at 1 x 8192 positions,
seeds 4343100011 / 21 / 31; call 5 at the cell's 2 x 8192, seed
4343100041, ``--model-only``: the last reading of each list; chunk 128;
PERF.md section 6).
Each limit has a LOWER reading (the largest the program as it is gave) and,
where a fault was read against it, an UPPER one (the smallest a fault
gave); a limit with no upper reading guards against a gross fault only,
and says so:

- ``GRAD_RTOL`` (leaves of more than ``SMALL_LEAF`` numbers): the residual
  stream is rounded to bfloat16 after each of 12 additions; where the
  rounding moves a token's 8th and 9th largest score (or its 4th and 5th
  group) past each other the token changes an expert, which is why a
  router's leaf reads highest (the rule ``gradcheck_nemotron_h.py`` uses
  for choices that flip: ONE limit a leaf, between the clean reading and
  the weakest structural break's).  LOWER: the worst leaf is a router's at
  0.38 / 0.40 / 0.46 / 0.416.  UPPER: with the routed sum's 2.5 left out
  0.655 / 0.69 / 0.661, the group limit left out 0.77 / 0.735 / 0.749, the
  MLA gate left out 1.00 / 1.00 / 1.002 (its ``q_proj`` or ``g_proj``).  The limit is the geometric
  mean of 0.46 and 0.66: 1.2 times of room either side, which is all the
  flips leave — handing the reference the program's own choices (PERF.md
  section 7 (18)) would take the routers' leaves out of it.
- ``LOGIT_RTOL`` (the first sequence's logits) and ``SMALL_GRAD_RTOL`` (all
  leaves of at most ``SMALL_LEAF`` numbers together, as one vector): LOWER
  0.035 and 0.070-0.078; NO upper reading — the three structural breaks
  read 0.037-0.045 and 0.072-0.090, inside the limits (a break of one layer
  in six barely moves either).  Twice the clean reading: guards against a
  gross fault only.
- ``KDA_RTOL``: every KDA layer's ``A_log`` and ``dt_bias`` together, as
  one vector — the leaves only the decays reach (the decay's ``f`` is a
  bfloat16 projection multiplied by ``exp(A_log)`` up to 16).  LOWER:
  0.076-0.103 (0.077 / 0.083 / 0.091 / 0.087 as it is; 0.076-0.103 under the
  three structural breaks, none of which touches a KDA layer).  UNDER THE
  SCAN'S OWN PRECISION FAULTS, read through the model (call 6, ``--model-only
  --break bf16_decays --break bf16_state``, the parameters and batch of the
  clean 0.087): 0.098 and 0.092 — INSIDE the limit, 1.06-1.13 times the
  clean reading, and every other model-level measure within 3 % of its
  clean reading.  So this is no precision limit: on the cell's bfloat16 operands a
  rounded state or rounded decays are not separable from the operands' own
  rounding (``SCAN_RTOL`` below says the same of the scan alone) — only
  ``SCAN_F32_RTOL``, on a float32-operand call of the same kernels that the
  timed window never makes, sees them.  Twice the clean reading: guards
  against a gross fault in the gate (a wrong sign, a missing ``exp``).
- ``SCAN_RTOL``: the scan ALONE at the cell's shape (one layer's call: 32
  heads of 128 x 128, 64 chunks of 128) on the cell's bfloat16 operands
  against the delta rule position by position on the same values: ``o``
  and every input's gradient read 0.0040-0.0064 (``d_k`` highest) — the
  operands' rounding; a rounded state or rounded decays read 0.0065
  there: NOT separable on bfloat16 operands.  The limit, twice the clean
  reading, guards the algebra.
- ``SCAN_F32_RTOL``: the same values as FLOAT32 operands (the kernels then
  multiply at ``highest``; the solve stays three bfloat16 passes), so that
  only the scan's own float32 side is left — the log-decays' sums, the
  solve, the carried state: clean 7.8e-5 to 1.3e-4 (``d_g`` highest; the
  chunked form in the kernels' place reads the same to five digits),
  ``bf16_state`` (the state rounded to bfloat16 as each chunk hands it
  on) 2.6e-3, ``bf16_decays`` (``g`` rounded to bfloat16 before the
  kernels sum it) 1.67e-3, each the same on both seeds: ``g``'s channels
  are laid on a grid (:func:`scan_inputs`), the same on every seed,
  because how long a channel remembers sets what a rounded state reads.
  The limit is the geometric mean of the largest clean and the smallest
  fault's reading: 3.6 times of room either side.
- ``HEAD_RTOL``: the blocked head alone against a float32 head on the same
  rows (``gradcheck_zaya.py`` ``head_rel``; 0.0 clean).
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
from gradcheck_zaya import head_rel, logits_rel_l2, rel_l2  # noqa: E402

CELL = "ling3_flash.fused_1c"
GRAD_RTOL = 0.55      # relative L2 of a gradient leaf ...
SMALL_LEAF = 4096     # ... of more than this many numbers; the smaller ones
SMALL_GRAD_RTOL = 0.15  # together, as one vector, this
KDA_RTOL = 0.18       # A_log and dt_bias of every KDA layer, together
LOGIT_RTOL = 0.07     # relative L2 of the first sequence's logits
HEAD_RTOL = 2e-6      # relative, the head's NLL summed over HEAD_ROWS rows
KDA_LEAVES = ("A_log']", "dt_bias']")
SCAN_RTOL = 0.013     # the scan alone: o and five gradients, relative L2
SCAN_F32_RTOL = 4.7e-4  # ... on the same values as float32 operands
SCAN_SEEDS = 2        # the scan alone is read on this many seeds a run
SCAN_NAMES = ("o", "d_q", "d_k", "d_v", "d_g", "d_beta")
SCAN_PASSES = (None, "chunked_stand_in")
SCAN_FAULTS = ("bf16_state", "bf16_decays")
MODEL_BREAKS = ("scaling_dropped", "group_limit_dropped", "mla_gate_dropped")
BREAKS = SCAN_FAULTS + MODEL_BREAKS


@contextlib.contextmanager
def broken(what):
    """One deliberate fault in what the program computes (patches on the
    program's modules, undone on exit; the jit caches are emptied on both
    sides, because the scan's kernels are traced under inner ``jax.jit``s
    that a patched chunk text would not re-key).  ``chunked_stand_in`` is
    no fault: ``kda_scan_chunked`` in the kernels' place, the same chunk
    text under ``vmap`` and ``lax.scan``, which has to PASS (it shares the
    chunk's text with the kernels, so it guards their ``pallas_call``
    wrapping only; the algebra's witness is the position-by-position
    reference); ``bf16_state``
    rounds the state every chunk hands on to bfloat16 (kernels and chunked
    form alike: they run one text); ``bf16_decays`` rounds ``g`` to
    bfloat16 before the REAL kernels sum it; then the group limit left out
    (the 8 largest of all 512), the MLA gate left out, the routed sum's
    2.5 left out."""
    import importlib
    from unittest import mock
    import jax
    import jax.numpy as jnp
    import byteps_tpu.models.ling as model
    scan = importlib.import_module("byteps_tpu.ops.kda_scan")

    kernels, chunk_text, config = (scan.kda_scan, scan._chunk_forward,
                                   model.LingConfig)

    def stand_in(q, k, v, g, beta, *, chunk, interpret=None):
        return scan.kda_scan_chunked(q, k, v, g, beta, chunk=chunk)

    def rounded_state(*args):
        o, state = chunk_text(*args)
        return o, state.astype(jnp.bfloat16).astype(jnp.float32)

    def low_decays(q, k, v, g, beta, **kw):
        # ``reduce_precision``, not a pair of casts: XLA:TPU folds those
        # away as excess precision, and the fault read exactly as clean
        return kernels(q, k, v, jax.lax.reduce_precision(g, 8, 7), beta,
                       **kw)

    def no_limit(scores, bias, n_group, topk_group):
        return scores, jnp.ones((scores.shape[0], n_group), bool)

    def unscaled(**kw):
        return config(**dict(kw, routed_scaling_factor=1.0))

    patches = {
        "chunked_stand_in": [(scan, "kda_scan", stand_in)],
        "bf16_state": [(scan, "_chunk_forward", rounded_state)],
        "bf16_decays": [(scan, "kda_scan", low_decays)],
        "group_limit_dropped": [(model, "group_limited", no_limit)],
        "mla_gate_dropped": [(model, "mla_gate", lambda x: jnp.ones_like(x))],
        "scaling_dropped": [(model, "LingConfig", unscaled)],
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
    cell's compute type, ``beta`` = sigmoid(unit normal), a cotangent for ``o`` from
    ``seed``; ``g = floor x sigmoid(A (f + dt_bias))`` with ``f`` a unit
    normal from ``seed`` and ``A`` x ``dt_bias`` NOT drawn as the model
    draws them but laid on a grid over the ranges it draws them from (``A``
    in [1, 16] over the heads, ``softplus(dt_bias)`` log-spaced in [0.001,
    0.1] over a head's channels), the same on every seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    _, t, h, d = family.kda_shape
    b = 1                     # one sequence: the scan mixes none
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    lp = family.compute_dtype

    def unit(key):
        x = jax.random.normal(key, (b, t, h, d))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    a = jnp.asarray(np.linspace(1.0, 16.0, h), jnp.float32)[:, None]
    dt = jnp.asarray(np.geomspace(0.001, 0.1, d), jnp.float32)
    f = jax.random.normal(keys[3], (b, t, h, d))
    g = family.log_decay_floor * jax.nn.sigmoid(
        a * (f + dt + jnp.log(-jnp.expm1(-dt))))
    args = ((unit(keys[0]) / np.sqrt(d)).astype(lp), unit(keys[1]).astype(lp),
            jax.nn.silu(jax.random.normal(keys[2], (b, t, h, d))).astype(lp),
            g, jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h))))
    return args, jax.random.normal(keys[5], (b, t, h, d))


def _scan_side(scan_fn, args, weight):
    """(o and the five gradients) of ``sum(o * weight)`` on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def objective(*a):
        o = scan_fn(*a).astype(jnp.float32)
        return jnp.sum(o * weight), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        objective, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return [np.asarray(x, np.float32) for x in (o, *grads)]


def scan_reference(family, seed: int):
    """The reference's side of :func:`scan_rel`: the delta rule position by
    position (``families/ling.py`` ``delta_rule``) in float32 on the same
    bfloat16-valued inputs."""
    import jax
    import jax.numpy as jnp
    from harness import spec
    delta_rule = spec.load_module("families", "ling").delta_rule
    args, weight = scan_inputs(family, seed)
    with jax.default_matmul_precision("highest"):
        return _scan_side(delta_rule,
                          tuple(a.astype(jnp.float32) for a in args), weight)


def scan_rel(family, seed: int, want, float32: bool = False) -> dict:
    """The scan ALONE, as the model calls it (``ops/kda_scan.py``
    ``kda_scan`` at the model's chunk), against :func:`scan_reference`.
    With ``float32`` the SAME values go in as float32 operands (the kernels
    then multiply at ``highest`` themselves) and only the scan's own
    float32 side is left."""
    import importlib
    import jax.numpy as jnp
    from byteps_tpu.models.ling import KDA_CHUNK
    scan = importlib.import_module("byteps_tpu.ops.kda_scan")
    args, weight = scan_inputs(family, seed)
    if float32:
        args = tuple(a.astype(jnp.float32) for a in args)
    got = _scan_side(lambda *a: scan.kda_scan(*a, chunk=KDA_CHUNK), args,
                     weight)
    return {name: rel_l2(g, w) for name, g, w in zip(SCAN_NAMES, got, want)}


def scan_compare(family, seed: int, want=None) -> dict:
    """The scan alone on ``SCAN_SEEDS`` seeds from ``seed`` on, on the
    cell's operands and on the same values as float32, under whatever
    :func:`broken` has put in place.  ``ok``: every reading within its
    limit; ``fails_every_seed``: the float32 measure past
    ``SCAN_F32_RTOL`` on each seed."""
    seeds = [seed + i for i in range(SCAN_SEEDS)]
    want = want or [scan_reference(family, s) for s in seeds]
    low = [scan_rel(family, s, w) for s, w in zip(seeds, want)]
    f32 = [scan_rel(family, s, w, float32=True) for s, w in zip(seeds, want)]
    worst = [max(r.values()) for r in f32]
    return {"ok": bool(max(max(r.values()) for r in low) <= SCAN_RTOL
                       and max(worst) <= SCAN_F32_RTOL),
            "fails_every_seed": bool(min(worst) > SCAN_F32_RTOL),
            "seeds": seeds, "scan_rel_l2": low, "scan_f32_rel_l2": f32,
            "scan_rtol": SCAN_RTOL, "scan_f32_rtol": SCAN_F32_RTOL}


def reference(family, params, batch) -> dict:
    """The reference's side of :func:`compare`: loss, gradients (on the
    host) and the rows the first sequence's head reads."""
    import jax
    import numpy as np
    loss, grads = jax.jit(jax.value_and_grad(family.reference_loss))(
        params, batch)
    grads = jax.tree.map(np.asarray, grads)
    with jax.default_matmul_precision("highest"):
        x = jax.jit(family.reference_hidden)(params, batch["input_ids"][:1])
    return {"loss": float(loss), "grads": grads, "rows": np.asarray(x[0])}


def compare(family, params, batch, want=None) -> dict:
    """Gradients of the program's loss and of the reference's on ``batch``
    (one after the other), the first sequence's logits, the head alone."""
    import jax
    import numpy as np
    loss, grads = jax.jit(jax.value_and_grad(family.loss_fn))(params, batch)
    loss, grads = float(loss), jax.tree.map(np.asarray, grads)
    if want is None:
        want = reference(family, params, batch)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want["grads"])[0])
    leaves, small = {}, {}
    vectors = {"small": ([], []), "kda": ([], [])}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        key = jax.tree_util.keystr(path)
        if "expert_bias" in key:         # no gradient reaches it: 0 = 0
            continue
        if g.size > SMALL_LEAF:
            leaves[key] = rel_l2(g, flat_want[path])
            continue
        small[key] = rel_l2(g, flat_want[path])
        groups = ["small"] + (["kda"] if key.endswith(KDA_LEAVES) else [])
        for group in groups:
            vectors[group][0].append(g.ravel())
            vectors[group][1].append(flat_want[path].ravel())
    small_dev, kda_dev = (rel_l2(np.concatenate(got), np.concatenate(ref))
                          for got, ref in (vectors["small"], vectors["kda"]))
    del grads
    x = jax.jit(family.hidden)(params, batch["input_ids"][:1])
    head = params["params"]["lm_head"]
    logit_dev = logits_rel_l2(x[0], want["rows"], head)
    head_dev = head_rel(x[0], head, batch["labels"][0])
    worst = max(leaves, key=leaves.get)
    want_loss = want["loss"]
    return {"ok": bool(leaves[worst] <= GRAD_RTOL
                       and small_dev <= SMALL_GRAD_RTOL
                       and kda_dev <= KDA_RTOL
                       and logit_dev <= LOGIT_RTOL
                       and head_dev <= HEAD_RTOL
                       and abs(loss - want_loss) <= 1e-2 * abs(want_loss)),
            "loss": loss, "reference_loss": want_loss,
            "worst_leaf": worst, "worst_rel_l2": leaves[worst],
            "small_leaves_rel_l2": small_dev, "kda_leaves_rel_l2": kda_dev,
            "logits_rel_l2": logit_dev, "head_rel": head_dev,
            "grad_rtol": GRAD_RTOL, "small_grad_rtol": SMALL_GRAD_RTOL,
            "kda_rtol": KDA_RTOL, "logit_rtol": LOGIT_RTOL,
            "head_rtol": HEAD_RTOL, "leaves": {**leaves, **small}}


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


def run(seed: int, rehearsal: bool, faults=(None,), model: bool = True,
        scan: bool = True):
    """One comparison a fault (``None``: the program as it is), each
    reference made once; yields ``(fault, result)``.  The stand-in and the
    ``SCAN_FAULTS`` are read by the scan alone (:func:`scan_compare`), the
    ``MODEL_BREAKS`` by the model's gradients (:func:`compare`), the
    program as it is by both, as two results (``model`` false: by the scan
    alone; ``scan`` false: by the model alone)."""
    family, seqs = build(rehearsal)
    scan_kinds = (*SCAN_PASSES, *SCAN_FAULTS) if scan else ()
    by_model = [f for f in faults if model and f not in scan_kinds[1:]]
    if any(f in scan_kinds for f in faults):
        want_scan = [scan_reference(family, seed + i)
                     for i in range(SCAN_SEEDS)]
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
                family, _ = build(rehearsal)
                yield fault, dict(compare(family, params, batch, want),
                                  measure="model")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--break", dest="fault", action="append",
                    choices=(*SCAN_PASSES[1:], *BREAKS),
                    help="may be given more than once: one reference, one "
                         "comparison a fault; with --model-only a scan's "
                         "fault is read through the MODEL's gradients")
    ap.add_argument("--all-breaks", action="store_true")
    ap.add_argument("--scan-only", action="store_true",
                    help="the scan alone: as it is, under its stand-in "
                         "and under its two faults; no model is built")
    ap.add_argument("--model-only", action="store_true",
                    help="the model's gradients: as it is and under each "
                         "structural break; the scan alone is not read")
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
                          scan=not args.model_only):
        out.update(broken=fault, device=device)
        if every and fault is not None:
            out.pop("leaves", None)      # the clean line carries them
        print(json.dumps(out), flush=True)
        all_ok &= out["ok"]
        if fault in SCAN_FAULTS:
            as_expected &= not out["ok"] and out["fails_every_seed"]
        else:
            as_expected &= out["ok"] == (fault in SCAN_PASSES)
    if every:
        return 0 if as_expected else 1
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
