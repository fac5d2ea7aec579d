"""Device milliseconds per step in the grouped-matmul Mosaic kernels of a
top-1 expert layer that holds a SHARE of its experts and is handed its
routing from outside (forward, row gradient, matrix gradient of gate, up
and down, and the forward recomputed under ``remat``): the kernels under
the ``bps.moe.experts`` scope, found as ``held_moe_ms`` finds them.  Their
grids cover the row tiles of the held experts' groups only, so the time
follows the tokens whose one expert lives here."""

from harness import kernel_time

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    s = kernel_time.seconds(run, "top1_moe")
    return None if s is None else s * 1e3
