"""Device milliseconds per step in the Gated DeltaNet mixers' row kernels:
the Mosaic kernels whose ``op_name`` lies under the scopes ``bps.gdn.pre``
or ``bps.gdn.out`` — since PR 46 the output stage alone
(``byteps_tpu/ops/kda_rows.py`` ``bps_kda_post_fwd`` / ``bps_kda_post_bwd``
with the gate's activation SiLU: the head norm times ``silu(z)`` in one
pass); the input stage (the short convolution, SiLU, the L2 norms, ``g``,
``beta``) is XLA fusions under ``bps.gdn.pre`` and NOT in it — every
DeltaNet layer's forward, the forward recomputed under ``remat`` and the
backward.  The scan's kernels lie under ``bps.gdn.scan``
(``gdn_scan_ms``).  The calls a traced step made go on the ``info`` line
(``gdn_rows_calls_per_step``: nine in ``qwen3_next_80b.fused_1c``).  A
program with no kernel under the scopes gives nothing."""

import re

from harness import xplane

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"

# a transform wraps the first scope entered after it: ``jvp(bps.gdn.pre)/``
RULE = re.compile(r"bps\.gdn\.(pre|out)\)*/.*pallas_call$")


def kernels_ms(run, rule, calls_key):
    """Milliseconds a step in the Mosaic kernels whose ``op_name`` ``rule``
    matches, their calls a step under ``calls_key`` on the ``info`` line;
    None without a trace or without such kernels."""
    if run.trace is None:
        return None
    names = {i for i, op in run.mosaic.items() if rule.search(op)}
    if not names:
        return None
    steps = max(1, run.window.traced_steps)
    lo, hi = xplane.window(run.trace)
    chips = run.trace.device_ids
    calls = sum(1 for d in chips for n, s, e in run.trace.ops[d]
                if n in names and min(e, hi) > max(s, lo))
    run.info[calls_key] = calls / max(1, len(chips)) / steps
    return xplane.op_seconds(run.trace, names, steps) * 1e3


def read(run):
    return kernels_ms(run, RULE, "gdn_rows_calls_per_step")
