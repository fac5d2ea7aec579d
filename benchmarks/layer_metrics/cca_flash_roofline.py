"""Share of their roofline the compressed-convolutional-attention layers'
flash kernels reach: the least time the chip could take for the
operations and HBM bytes the causal half of the latent's ``[8, seq, 128]``
needs (``families/zaya.py`` ``flash_work``: k/v counted at the 2 key/value
heads the algorithm reads, not the 8 the program repeats them to; the
forward recomputed under ``remat`` is not counted) over the device time of
the ``pallas_call``s under the scope ``attn_cca`` (``flash_ms`` reads the
same kernels' milliseconds).  Which roof binds goes to the ``info``
line."""

from harness import kernel_time

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    work = run.kernel_work.get("cca_flash")
    seconds = kernel_time.seconds(run, "cca_flash")
    if not work or not seconds:
        return None
    value, run.info["cca_flash_roofline_bound"] = (
        kernel_time.roofline_share(work, seconds, run.peaks))
    return value
