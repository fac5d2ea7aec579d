"""Device milliseconds per step in the state-space scan's Mosaic kernels:
``bps_ssd_fwd`` / ``bps_ssd_bwd`` (``byteps_tpu/ops/ssd_scan.py``) under
the Mamba-2 mixers' ``bps.ssm.scan`` scope, every ``M`` block's — the
forward, the forward recomputed under ``remat`` (which also stores the
chunk-start states) and the backward.  The cumulative sum, the head-major
transposes and ``D xs`` around the kernels are plain XLA and not in it."""

from harness import kernel_time

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    s = kernel_time.seconds(run, "ssd")
    return None if s is None else s * 1e3
