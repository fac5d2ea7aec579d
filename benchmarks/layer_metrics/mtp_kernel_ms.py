"""Device milliseconds per step in every Mosaic kernel whose ``op_name``
lies under the module scope ``mtp``: the multi-token-prediction module's
block in kernels (its flash calls, its grouped matmuls, its row passes;
forward, the forward recomputed under ``remat``, backward) — the part of
the second head's price a trace can read without the step's HLO text; its
plain XLA matmuls and its pass through the blocked head are not in it."""

from harness import kernel_time

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    s = kernel_time.seconds(run, "mtp")
    return None if s is None else s * 1e3
