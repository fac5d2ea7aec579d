"""Device milliseconds per step in the delta-rule scan's Mosaic kernels:
``bps_kda_fwd`` / ``bps_kda_bwd`` (``byteps_tpu/ops/kda_scan.py``) under
the Kimi-Delta-Attention mixers' ``bps.kda.scan`` scope, every KDA layer's
— the forward, the forward recomputed under ``remat`` (which also stores
the chunk-start states) and the backward.  The L2 norms, the gate and the
convolution in front of the kernels are plain XLA under other scopes and
not in it.  A program without such kernels gives nothing."""

from harness import kernel_time

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    s = kernel_time.seconds(run, "kda")
    return s * 1e3 if s else None
