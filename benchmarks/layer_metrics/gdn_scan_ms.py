"""Device milliseconds per step in the head-decay delta-rule scan's Mosaic
kernels: ``bps_gdn_fwd`` / ``bps_gdn_bwd`` (``byteps_tpu/ops/gdn_scan.py``)
under the Gated DeltaNet mixers' ``bps.gdn.scan`` scope, every DeltaNet
layer's — the forward, the forward recomputed under ``remat`` (which also
stores the chunk-start states) and the backward.  The convolution, the L2
norms, the gate and the chunk sums of ``g`` in front of the kernels are
plain XLA and not in it.  The calls a traced step made go on the ``info``
line (``gdn_scan_calls_per_step``: nine in ``qwen3_next_80b.fused_1c`` —
six forward, three backward) — whether the mechanism engaged.  A program
without such kernels gives nothing."""

import re

from harness import spec

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    rule = run.kernel_work.get("gdn", {}).get("op_name_re")
    if rule is None:
        return None
    return spec.load_module("layer_metrics", "gdn_rows_ms").kernels_ms(
        run, re.compile(rule), "gdn_scan_calls_per_step")
