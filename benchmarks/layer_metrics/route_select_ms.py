"""Device milliseconds per step in the route stage's selection kernel:
the Mosaic kernels whose ``op_name`` lies under the scope
``bps.moe.route`` (``byteps_tpu/parallel/expert.py`` ``bps_moe_select``:
the k largest of a token's E scores, the scores read there and the
per-expert counts in one pass) — every MoE block's forward and the forward
recomputed under ``remat``; the transposes in front of and behind the
kernel and the dense backward are plain XLA under the same scope and not
in it.  The calls a traced step made go on the ``info`` line
(``route_select_calls_per_step``: twelve in ``nemotron3_super.fused_1c``)
— whether the mechanism engaged.  A program that selects by a sort has no
kernel under the scope, and this returns nothing."""

import re

from harness import xplane

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "parallel.expert (dropless MoE)"
MOVES = "tokens_per_s_per_chip"

# a transform wraps the first scope entered after it: ``jvp(bps.moe.route)/``
RULE = re.compile(r"bps\.moe\.route\)*/.*pallas_call$")


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
    run.info["route_select_calls_per_step"] = (
        calls / max(1, len(chips)) / steps)
    return xplane.op_seconds(run.trace, names, steps) * 1e3
