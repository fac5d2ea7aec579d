"""What the timed program of ``evabyte_6b5.fused_1c`` computes, against the
plain reference, beyond the loss (ISSUE 50): at random weights a loss is
~ln(320) whatever the layers do, so this compares the step's GRADIENTS leaf
by leaf (relative L2) on one batch of the cell's own sizes and the first
sequence's logits over all columns of the eight heads — in the manner of
``gradcheck_qwen3_next.py`` — and one layer's ``eva_attention`` ALONE at
the cell's shape against the reference's one masked softmax.

    python3 benchmarks/tests/gradcheck_evabyte.py [--seed N] [--rehearsal]
        [--break WHAT ... | --all-breaks | --control [--control-parts]]
        [--attention-only | --model-only]

Prints one JSON line a comparison (``measure``: ``model`` or
``attention``).  ``--break`` puts one deliberate fault into the PROGRAM
first (a wrong EVA in ``eva_attention``'s place); ``--control`` runs the
program as it is and then in the nearest precision BELOW the
configuration's (``low_precision``: the pooling and the merge in bfloat16
and ``fp32_skip_add`` off — a bfloat16 residual stream), which has to
fail one of its comparisons; ``--control-parts`` also reads each of the
three alone (``CONTROLS``), as readings.  Exit 0 iff the clean
comparisons pass, every fault fails and the control fails (a single
``--break``: iff that comparison passes).

Limits, with their reason.  The program computes in bfloat16 with float32
accumulation, statistics, pooling, merge and residual stream; the reference
in float32.  Each limit stands between a LOWER reading (the largest the
program as it is gave) and an UPPER one, near their geometric mean.
Readings: my chip runs, PR 50, at 1 x 16 384 positions — call 2 (seeds
5050000071 the attention alone with the six wrong EVAs, 5050000072 the
model with two of them), call 3 (seeds 5050000081-83, each with the
control: the limits were set from these) and call 4 (seeds 5050000121-122,
which the limits were NOT set with: the program passed, the control
failed; ``PERF.md`` section 6).  A reading moves by under 3 % between
seeds (a relative L2 over 67 M numbers).

- ``ATTN_RTOL`` (``eva_attention`` alone on the cell's bfloat16 operands:
  o and the gradients of q, k, v, mu, phi, each a relative L2 with a limit
  of its own).  UPPER is the CONTROL's smallest of five seeds — the
  pooling and the merge in bfloat16 inside the program:

      measure   program (6 seeds)   control (5 seeds)   limit
      o         0.00258-0.00259     0.00494-0.00498     0.0036
      d_q       0.00335-0.00337     0.00875-0.00879     0.0054
      d_k       0.00343-0.00345     0.00953-0.00965     0.0057
      d_v       0.00315-0.00317     0.00829-0.00834     0.0051
      d_mu      0.00342-0.00354     0.01421-0.01508     0.0071
      d_phi     0.00312-0.00328     0.01022-0.01057     0.0058

  1.4 (o) to 2 (d_mu) times of room on each side.  Each part ALONE fails
  them too (seed 5050000081): the pooling in bfloat16 by d_mu 0.0137,
  d_phi 0.0091 and d_k 0.0062, the merge by o 0.0046 and d_q / d_k / d_v
  0.0080-0.0085.  The weakest wrong EVA's weakest measure reads 0.079
  (``d_q`` under ``own_chunks_summarised``): 15 times a limit.
- The MODEL's limits cannot see the lower precision, and say so: through
  four layers of bfloat16 matmuls the control reads 1.0-1.3 times the
  program (worst leaf 0.0213-0.0231 against 0.0176-0.0198, the logits
  0.0113-0.0123 against 0.0099-0.0108, their ranges over the seeds
  touching for ``mu`` / ``phi``; a bfloat16 residual stream ALONE 0.0200 /
  0.0118), so no limit stands between the two.  Their UPPER
  reading is the smallest a wrong EVA gave.  ``GRAD_RTOL`` (a gradient
  leaf of more than ``SMALL_LEAF`` numbers): LOWER 0.0198 (``h3``'s
  ``q_proj``), UPPER 0.83 (``uniform_pooling``; ``no_summaries`` 1.23).
  ``SMALL_GRAD_RTOL`` (all smaller leaves together — the norms' weights —
  as one vector): 0.0121 / 0.27.  ``POOL_RTOL`` (every layer's ``mu`` and
  ``phi`` together: the leaves only the summaries reach): 0.0141 / 1.0.
  ``LOGIT_RTOL`` (the first sequence's logits, all eight heads): 0.0108 /
  0.195.  Only those two faults were put through the whole MODEL on the
  chip (the other four are read by the attention alone, and by the CPU
  tests through the model's logits).
- the loss within 1e-2 (``harness/checks.py`` ``LOSS_RTOL``): the program's
  and the reference's first losses differ by 5e-7 to 9e-5 over three steps
  on twelve seeds, and the loss moves by 1 % a step.  It sees NEITHER a
  wrong EVA (2.6e-5 under ``uniform_pooling``, 2.5e-4 under
  ``no_summaries``) nor the control (2e-5): at random weights on uniform
  random bytes a loss is ~ln 320 whatever the layers do.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import math
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(_HERE)))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, _HERE)

from gradcheck_glm_lite import inputs  # noqa: E402
from gradcheck_ling import _scan_side, reference  # noqa: E402
from gradcheck_zaya import logits_rel_l2, rel_l2  # noqa: E402

CELL = "evabyte_6b5.fused_1c"
# eva_attention alone: o and five gradients, a limit each
ATTN_RTOL = {"o": 0.0036, "d_q": 0.0054, "d_k": 0.0057, "d_v": 0.0051,
             "d_mu": 0.0071, "d_phi": 0.0058}
GRAD_RTOL = 0.1       # relative L2 of a gradient leaf ...
SMALL_LEAF = 4096     # ... of more than this many numbers; the smaller ones
SMALL_GRAD_RTOL = 0.056  # together, as one vector, this
POOL_RTOL = 0.1       # mu and phi of every layer, together
LOGIT_RTOL = 0.05     # relative L2 of the first sequence's logits
POOL_LEAVES = ("['mu']", "['phi']")
BREAKS = ("no_summaries", "uniform_pooling", "sliding_window",
          "own_chunks_summarised", "sets_averaged", "pooled_before_rotation")
# the nearest precision below the configuration's, and its three parts:
# what of the program computes in bfloat16 (``low_precision``'s arguments;
# ``residual``: ``fp32_skip_add`` off)
CONTROL = "low_precision"
CONTROLS = {"bf16_pool": dict(pool=True), "bf16_merge": dict(merge=True),
            "bf16_residual": dict(residual=True),
            CONTROL: dict(pool=True, merge=True, residual=True)}


def wrong_eva(what, theta: float = 1e5):
    """A function with ``eva_attention``'s signature that computes a WRONG
    EVA, in plain ``jax.numpy`` (one head at a time, float32 scores):

    - ``no_summaries``: R_i empty — every window alone;
    - ``uniform_pooling``: mu = phi = 0 forced — a chunk's plain mean;
    - ``sliding_window``: L_i the last ``window`` keys, ``i - window < j <=
      i``, in the place of the row's own block-aligned window;
    - ``own_chunks_summarised``: R_i holds every chunk that ENDS at or
      before i, the own window's too — those keys counted twice;
    - ``sets_averaged``: the two sets normalised separately, the two
      results averaged (where R_i is empty: L_i's alone);
    - ``pooled_before_rotation``: the summaries pooled from the keys as
      they were BEFORE the rotation (``theta``: the rotation to undo);
    - ``None``: the right one, the same text."""
    import jax
    import jax.numpy as jnp

    def unrotate(k):                         # [B, T, H, D]: rotate by -angle
        d = k.shape[-1]
        inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(k.shape[1], dtype=jnp.float32)[:, None] * inv
        ang = ang[None, :, None, :]
        k1, k2 = k[..., :d // 2], k[..., d // 2:]
        return jnp.concatenate([k1 * jnp.cos(ang) + k2 * jnp.sin(ang),
                                -k1 * jnp.sin(ang) + k2 * jnp.cos(ang)], -1)

    def one_head(q, k, v, k_pool, mu, phi, *, window, chunk):
        t, d = q.shape
        kc = k_pool.reshape(t // chunk, chunk, d)
        vc = v.reshape(t // chunk, chunk, -1)
        ks = jnp.einsum("nc,ncd->nd", jax.nn.softmax(kc @ mu, -1), kc)
        vs = jnp.einsum("nc,ncd->nd", jax.nn.softmax(kc @ phi, -1), vc)
        i = jnp.arange(t)[:, None]
        j, c = jnp.arange(t)[None, :], jnp.arange(t // chunk)[None, :]
        w = i // window
        own = (j // window == w) & (j <= i)
        earlier = c < (window // chunk) * w
        if what == "no_summaries":
            earlier = jnp.zeros_like(earlier)
        elif what == "sliding_window":
            own = (i - window < j) & (j <= i)
        elif what == "own_chunks_summarised":
            earlier = (c + 1) * chunk <= i + 1
        scale = 1.0 / math.sqrt(d)
        s_own = jnp.where(own, q @ k.T * scale, -jnp.inf)
        s_far = jnp.where(earlier, q @ ks.T * scale, -jnp.inf)
        if what == "sets_averaged":
            near = jax.nn.softmax(s_own, -1) @ v
            any_far = earlier.any(-1, keepdims=True)
            far = jax.nn.softmax(jnp.where(any_far, s_far, 0.0), -1) @ vs
            return jnp.where(any_far, 0.5 * (near + far), near)
        p = jax.nn.softmax(jnp.concatenate([s_own, s_far], 1), -1)
        return p @ jnp.concatenate([v, vs])

    def attention(q, k, v, mu, phi, *, window, chunk, **_):
        dtype = q.dtype
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        k_pool = unrotate(k) if what == "pooled_before_rotation" else k
        if what == "uniform_pooling":
            mu, phi = jnp.zeros_like(mu), jnp.zeros_like(phi)
        head = jax.checkpoint(functools.partial(one_head, window=window,
                                                chunk=chunk))
        ctx = jax.lax.map(
            lambda seq: jax.lax.map(lambda x: head(*x), seq + (mu, phi)),
            tuple(jnp.moveaxis(x, 2, 1) for x in (q, k, v, k_pool)))
        return jnp.moveaxis(ctx, 1, 2).astype(dtype)

    if what is not None and what not in BREAKS:
        raise ValueError(f"unknown break {what!r}")
    return attention


def low_precision(pool: bool = False, merge: bool = False,
                  residual: bool = False):
    """A precision below the configuration's, inside the PROGRAM:
    ``ops/eva_attention.py``'s ``pool_chunks`` and / or ``_merge`` computed
    in bfloat16 where the program computes in float32 (patches on the
    module, undone on exit).  ``residual`` is the caller's to apply: a
    model built with ``fp32_skip_add`` off (:func:`run`)."""
    from unittest import mock
    import jax
    import jax.numpy as jnp
    import byteps_tpu.ops.eva_attention as eva
    exact_merge, lp = eva._merge, jnp.bfloat16

    def low_pool(k, v, mu, phi, chunk):
        # ``pool_chunks``, bfloat16 for each of its float32
        bh, t, d = k.shape
        h = mu.shape[0]
        kc = k.astype(lp).reshape(bh // h, h, t // chunk, chunk, d)
        vc = v.astype(lp).reshape(kc.shape[:-1] + (v.shape[-1],))

        def weights(w):
            return jax.nn.softmax(
                jnp.sum(kc * w.astype(lp)[None, :, None, None, :], -1), -1)

        ks = jnp.sum(weights(mu)[..., None] * kc, -2)
        vs = jnp.sum(weights(phi)[..., None] * vc, -2)
        return (ks.reshape(bh, t // chunk, d).astype(jnp.float32),
                vs.reshape(bh, t // chunk, -1).astype(jnp.float32))

    def low_merge(*sets):
        return tuple(x.astype(jnp.float32)
                     for x in exact_merge(*(x.astype(lp) for x in sets)))

    stack = contextlib.ExitStack()
    if pool:
        stack.enter_context(mock.patch.object(eva, "pool_chunks", low_pool))
    if merge:
        stack.enter_context(mock.patch.object(eva, "_merge", low_merge))
    return stack


@contextlib.contextmanager
def broken(what):
    """One deliberate fault in what the program computes: ``wrong_eva(what)``
    in the place of ``models/evabyte.py``'s ``eva_attention`` — or, for
    one of ``CONTROLS``, :func:`low_precision` inside the right one
    (patches on the modules, undone on exit; the jit caches are emptied on
    both sides)."""
    from unittest import mock
    import jax
    import byteps_tpu.models.evabyte as model
    jax.clear_caches()
    try:
        with (low_precision(**CONTROLS[what]) if what in CONTROLS
              else mock.patch.object(model, "eva_attention",
                                     wrong_eva(what))):
            yield
    finally:
        jax.clear_caches()


# ------------------------------------------------------- the attention alone

def attention_inputs(family, seed: int):
    """One layer's call of ONE sequence at the cell's shape: q, k, v unit
    normals in the cell's compute type (so that a row's scores spread over
    +-3 at scale 1/sqrt(D)), mu and phi as the model draws them, times 4
    (a pooling that is far from a chunk's mean), a cotangent for o."""
    import jax
    import jax.numpy as jnp
    from byteps_tpu.models.evabyte import _pool_init
    _, t, h, d, _, _ = family.eva_shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    lp = family.compute_dtype
    q, k, v = (jax.random.normal(keys[i], (1, t, h, d)).astype(lp)
               for i in range(3))
    mu, phi = (4.0 * _pool_init(keys[3 + i], (h, d)) for i in range(2))
    return (q, k, v, mu, phi), jax.random.normal(keys[5], (1, t, h, d))


def attention_reference(family, seed: int):
    """The reference's side: ``families/evabyte.py`` ``eva_one_head`` (ONE
    masked softmax over the concatenated keys) a head, in float32 on the
    same bfloat16-valued inputs."""
    import jax
    import jax.numpy as jnp
    _, _, _, _, window, chunk = family.eva_shape
    one_head = jax.checkpoint(functools.partial(
        family.eva_one_head, window=window, chunk=chunk))

    def plain(q, k, v, mu, phi):
        ctx = jax.lax.map(lambda x: one_head(*x), tuple(
            jnp.moveaxis(x[0], 1, 0) for x in (q, k, v)) + (mu, phi))
        return jnp.moveaxis(ctx, 0, 1)[None]

    args, weight = attention_inputs(family, seed)
    with jax.default_matmul_precision("highest"):
        return _scan_side(
            plain, tuple(a.astype(jnp.float32) for a in args), weight)


def attention_compare(family, seed: int, want=None) -> dict:
    """``eva_attention`` alone, as the model calls it (whatever
    :func:`broken` has put in its place), against
    :func:`attention_reference`."""
    import byteps_tpu.models.evabyte as model
    _, _, _, _, window, chunk = family.eva_shape
    want = want or attention_reference(family, seed)
    args, weight = attention_inputs(family, seed)
    got = _scan_side(
        lambda *a: model.eva_attention(*a, window=window, chunk=chunk),
        args, weight)
    rel = {name: rel_l2(g, w) for name, g, w in zip(ATTN_RTOL, got, want)}
    return {"ok": all(rel[name] <= ATTN_RTOL[name] for name in rel),
            "seed": seed, "attention_rel_l2": rel, "attn_rtol": ATTN_RTOL}


# ------------------------------------------------------------ the whole model

def compare(family, params, batch, want=None) -> dict:
    """Gradients of the program's loss and of the reference's on ``batch``
    (one after the other) and the first sequence's logits, all heads."""
    import jax
    import numpy as np
    loss, grads = jax.jit(jax.value_and_grad(family.loss_fn))(params, batch)
    loss, grads = float(loss), jax.tree.map(np.asarray, grads)
    if want is None:
        want = reference(family, params, batch)
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want["grads"])[0])
    leaves, small = {}, {}
    vectors = {"small": ([], []), "pool": ([], [])}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        key = jax.tree_util.keystr(path)
        pooled = key.endswith(POOL_LEAVES)
        if g.size > SMALL_LEAF and not pooled:
            leaves[key] = rel_l2(g, flat_want[path])
            continue
        small[key] = rel_l2(g, flat_want[path])
        vectors["pool" if pooled else "small"][0].append(g.ravel())
        vectors["pool" if pooled else "small"][1].append(
            flat_want[path].ravel())
    small_dev, pool_dev = (rel_l2(np.concatenate(got), np.concatenate(ref))
                           for got, ref in (vectors["small"],
                                            vectors["pool"]))
    del grads
    x = jax.jit(family.hidden)(params, batch["input_ids"][:1])
    # the helper reads a table [V, h]: all eight heads' columns
    logit_dev = logits_rel_l2(x[0], want["rows"],
                              params["params"]["lm_head"].T)
    worst = max(leaves, key=leaves.get)
    want_loss = want["loss"]
    return {"ok": bool(leaves[worst] <= GRAD_RTOL
                       and small_dev <= SMALL_GRAD_RTOL
                       and pool_dev <= POOL_RTOL
                       and logit_dev <= LOGIT_RTOL
                       and abs(loss - want_loss) <= 1e-2 * abs(want_loss)),
            "loss": loss, "reference_loss": want_loss,
            "worst_leaf": worst, "worst_rel_l2": leaves[worst],
            "small_leaves_rel_l2": small_dev, "pool_leaves_rel_l2": pool_dev,
            "logits_rel_l2": logit_dev,
            "grad_rtol": GRAD_RTOL, "small_grad_rtol": SMALL_GRAD_RTOL,
            "pool_rtol": POOL_RTOL, "logit_rtol": LOGIT_RTOL,
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


def run(seed: int, rehearsal: bool, faults=(None,), model: bool = True,
        attention: bool = True):
    """One comparison a fault (``None``: the program as it is), each
    reference made once; yields ``(fault, result)``."""
    family, seqs = build(rehearsal)
    if attention:
        want_attention = attention_reference(family, seed)
    if model:
        params, batch = inputs(family, seqs, seed)
        want = reference(family, params, batch)
    for fault in faults:
        with broken(fault) if fault else contextlib.nullcontext():
            # built inside: new closures, so no jit cache outlives a break
            parts = CONTROLS.get(fault, {})
            family, _ = build(rehearsal, **(
                {"fp32_skip_add": False} if parts.get("residual") else {}))
            if attention and parts != CONTROLS["bf16_residual"]:
                yield fault, dict(attention_compare(family, seed,
                                                    want_attention),
                                  measure="attention")
            if model:
                yield fault, dict(compare(family, params, batch, want),
                                  measure="model")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--break", dest="fault", action="append", choices=BREAKS)
    ap.add_argument("--all-breaks", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--control-parts", action="store_true")
    ap.add_argument("--attention-only", action="store_true")
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
    if args.fault:
        faults = tuple(args.fault)
    else:
        faults = ((None,) + (BREAKS if args.all_breaks else ())
                  + (tuple(CONTROLS) if args.control_parts
                     else (CONTROL,) if args.control else ()))
    device = {"platform": platform, "kind": jax.devices()[0].device_kind}
    if args.rehearsal:
        device["rehearsal"] = True
    as_expected = all_ok = True
    control_ok = []
    for fault, out in run(args.seed, args.rehearsal, faults,
                          model=not args.attention_only,
                          attention=not args.model_only):
        out.update(broken=fault, device=device)
        if fault is not None:
            out.pop("leaves", None)      # the clean line carries them
        print(json.dumps(out), flush=True)
        all_ok &= out["ok"]
        if fault is None or fault in BREAKS:
            as_expected &= out["ok"] == (fault is None)
        elif fault == CONTROL:            # its parts are readings only
            control_ok.append(out["ok"])
    if control_ok and all(control_ok):
        as_expected = False     # not correct by ONE of the measures
    if args.all_breaks or args.control or args.control_parts:
        return 0 if as_expected else 1
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
