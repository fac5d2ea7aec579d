"""Device milliseconds per step in the flash kernels of the sliding-window
layers (forward + backward, and the forward recomputed under ``remat``):
trace events named after the program's ``tpu_custom_call`` instructions
whose op_name the family's rule matches (the ``pallas_call``s under the
models' ``attn_swa`` scope; the full layers' are under ``attn``, and
``flash_ms`` times both).  Nothing where the family has no such layers."""

from harness import kernel_time

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    s = kernel_time.seconds(run, "swa_flash")
    return None if s is None else s * 1e3
