"""90th percentile of the host time of one step that ends in
``block_until_ready`` (untraced steps of the blocking run; the sample
count goes to the ``info`` line)."""

import numpy as np

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "job loop"
MOVES = "tokens_per_s_per_chip"


def read(run):
    s = run.window.step_s or run.window.traced_step_s
    if not s:
        return None
    return float(np.percentile(np.asarray(s) * 1e3, 90))
