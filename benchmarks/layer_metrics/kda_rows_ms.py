"""Device milliseconds per step in the Kimi-Delta-Attention mixers' row
kernels: the Mosaic kernels whose ``op_name`` lies under the scopes
``bps.kda.pre`` or ``bps.kda.out`` (``byteps_tpu/ops/kda_rows.py``:
``bps_kda_pre_fwd`` / ``bps_kda_pre_bwd`` — the short convolution, SiLU,
the L2 norms of q and k, the log-decay ``g`` in one pass over the
projection — and ``bps_kda_post_fwd`` / ``bps_kda_post_bwd`` — the head
norm times the output gate) — every KDA layer's forward, the forward
recomputed under ``remat`` and the backward.  ``beta``'s columns, the
join of the cotangent's slices and ``W_o`` are plain XLA under the same
scopes and not in it; the scan's kernels lie under ``bps.kda.scan``
(``kda_scan_ms``).  The calls a traced step made go on the ``info`` line
(``kda_rows_calls_per_step``: thirty in ``ling3_flash.fused_1c``) —
whether the mechanism engaged.  A program whose row stages are XLA
fusions has no kernel under the scopes, and this returns nothing."""

import re

from harness import xplane

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"

# a transform wraps the first scope entered after it: ``jvp(bps.kda.pre)/``
RULE = re.compile(r"bps\.kda\.(pre|out)\)*/.*pallas_call$")


def read(run):
    if run.trace is None:
        return None
    names = {i for i, op in run.mosaic.items() if RULE.search(op)}
    if not names:
        return None
    steps = max(1, run.window.traced_steps)
    lo, hi = xplane.window(run.trace)
    chips = run.trace.device_ids
    calls = sum(1 for d in chips for n, s, e in run.trace.ops[d]
                if n in names and min(e, hi) > max(s, lo))
    run.info["kda_rows_calls_per_step"] = calls / max(1, len(chips)) / steps
    return xplane.op_seconds(run.trace, names, steps) * 1e3
