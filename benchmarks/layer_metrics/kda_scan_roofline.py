"""Share of their roofline the delta-rule scan's kernels reach: the least
time the chip could take for the operations and HBM bytes the ALGORITHM
needs (``families/ling.py`` ``kda_work``: the WY form at a STATED chunk of
64 — per chunk and head the two score products, the solve, ``W``, ``U``,
``W S``, the read-out, the intra-chunk product and the state's update; a
backward of twice the forward, the forward once more where ``remat``
recomputes it; ``q``, ``k``, ``v``, ``g``, ``beta``, ``o`` and their
gradients read or written once — the same whatever chunk or kernel
implements the scan) over ``kda_scan_ms``.  Which roof binds goes to the
``info`` line."""

from harness import kernel_time

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    work = run.kernel_work.get("kda")
    seconds = kernel_time.seconds(run, "kda")
    if not work or not seconds:
        return None
    value, run.info["kda_scan_roofline_bound"] = (
        kernel_time.roofline_share(work, seconds, run.peaks))
    return value
