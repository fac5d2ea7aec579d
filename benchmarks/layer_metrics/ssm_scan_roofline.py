"""Share of their roofline the state-space scan's kernels reach: the least
time the chip could take for the operations and HBM bytes the ALGORITHM
needs (``families/nemotron_h.py`` ``ssd_work``: per chunk and group ``C
B^T``, per head the masked product with ``dt o xs``, the state's read-out
and update; a backward of twice the forward, the forward once more where
``remat`` recomputes it; ``xs``, ``dt``, ``B``, ``C``, ``y`` and their
gradients read or written once — the same whatever implements the scan)
over ``ssm_scan_ms``.  Which roof binds goes to the ``info`` line."""

from harness import kernel_time

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    work = run.kernel_work.get("ssd")
    seconds = kernel_time.seconds(run, "ssd")
    if not work or not seconds:
        return None
    value, run.info["ssm_scan_roofline_bound"] = (
        kernel_time.roofline_share(work, seconds, run.peaks))
    return value
