"""Share of their roofline the latent-attention blocks' flash kernels
reach: the least time the chip could take for the operations and HBM
bytes the causal half of ``[20, seq, 256]`` needs (``families/glm_lite.py``
``flash_work``: every block's, the module's too; k counted at 20 x 192 and
ONE 64-wide rotary key, which the algorithm reads once and the program
repeats over the 20 heads; the forward recomputed under ``remat`` is not
counted) over the device time of the ``pallas_call``s under the scope
``attn_mla`` (``flash_ms`` reads the same kernels' milliseconds).  Which
roof binds goes to the ``info`` line."""

from harness import kernel_time

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    work = run.kernel_work.get("mla_flash")
    seconds = kernel_time.seconds(run, "mla_flash")
    if not work or not seconds:
        return None
    value, run.info["mla_flash_roofline_bound"] = (
        kernel_time.roofline_share(work, seconds, run.peaks))
    return value
