"""Device milliseconds per step in the grouped-matmul Mosaic kernels of
sparse blocks that hold a SHARE of their routed experts behind sigmoid
scores and a choosing bias, top-4 (forward, row gradient, matrix gradient
of gate, up and down, and the forward recomputed under ``remat``; the
module's block with the model's): the kernels under the ``bps.moe.experts``
scope, found as ``held_moe_ms`` finds them.  Their grids cover the row
tiles of the held experts' groups only, so the time follows the pairs
routed here; the shared expert is plain XLA matmuls and is not in it."""

from harness import kernel_time

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    s = kernel_time.seconds(run, "routed_moe")
    return None if s is None else s * 1e3
