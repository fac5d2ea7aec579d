"""Share of its roofline the flash kernels reach: the least time the chip
could take for the operations and HBM bytes the ALGORITHM needs at this
cell's shape (harness/flops.py, real head size, causal halved) over the
measured kernel time.  Which roof binds goes to the ``info`` line."""

from harness import flops, spec

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    work = run.kernel_work.get("flash")
    seconds = spec.load_module("layer_metrics", "flash_ms").flash_seconds(run)
    if not work or not seconds:
        return None
    roof = flops.roofline(work["flops"], work["bytes"], run.peaks)
    run.info["flash_roofline_bound"] = roof["bound"]
    return 100.0 * roof["seconds"] / seconds
