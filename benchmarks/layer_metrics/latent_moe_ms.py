"""Device milliseconds per step in the grouped-matmul Mosaic kernels of the
LatentMoE blocks' routed experts — two matrices an expert, no gate, on the
1024-wide latent, a SHARE of 8 of 512 held behind sigmoid scores, top-22
(forward, row gradient, matrix gradient of up and down, and the forward
recomputed under ``remat``; the module's block with the model's): the
kernels under the ``bps.moe.experts`` scope, found as ``held_moe_ms`` finds
them.  Their grids cover the row tiles of the held experts' groups only;
the ``relu(.)^2`` row kernel (scope ``bps.moe.act``), the latent
projections and the shared expert are not in it."""

from harness import kernel_time

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    s = kernel_time.seconds(run, "latent_moe")
    return None if s is None else s * 1e3
