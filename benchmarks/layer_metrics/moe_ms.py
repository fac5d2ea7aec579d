"""Device milliseconds per step in the expert layer's grouped-matmul
Mosaic kernels (forward, row gradient, matrix gradient of gate, up and
down: nine calls a layer): trace events named after the program's
``tpu_custom_call`` instructions whose op_name the family's rule matches
(the kernels under the ``bps.moe.experts`` scope).  Nothing where the
cell's family has no expert layer."""

import re

from harness import xplane

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def moe_seconds(run):
    rule = run.kernel_work.get("moe", {}).get("op_name_re")
    if run.trace is None or rule is None:
        return None
    names = [i for i, op in run.mosaic.items() if re.search(rule, op)]
    return xplane.op_seconds(run.trace, names, run.window.traced_steps)


def read(run):
    s = moe_seconds(run)
    return None if s is None else s * 1e3
