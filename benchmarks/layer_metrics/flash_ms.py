"""Device milliseconds per step in the flash-attention Mosaic kernels
(forward + backward): trace events named after the program's
``tpu_custom_call`` instructions whose op_name the family's rule matches;
0 where the program holds none."""

import re

from harness import xplane

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def flash_seconds(run):
    if run.trace is None:
        return None
    rule = run.kernel_work.get("flash", {}).get("op_name_re")
    if rule is None:
        return 0.0
    names = [i for i, op in run.mosaic.items() if re.search(rule, op)]
    return xplane.op_seconds(run.trace, names, run.window.traced_steps)


def read(run):
    s = flash_seconds(run)
    return None if s is None else s * 1e3
