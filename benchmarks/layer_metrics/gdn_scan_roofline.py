"""Share of their roofline the head-decay delta-rule scan's kernels reach:
the least time the chip could take for the operations and HBM bytes the
ALGORITHM needs (``families/qwen3_next.py`` ``gdn_work``: the head-decay
WY form at a STATED chunk of 64 — the two score products once a KEY head;
``D``, the solve, ``W``, ``U``, ``W S``, the read-out, ``P R`` and the
state's update once a VALUE head; a backward of twice the forward, the
forward once more where ``remat`` recomputes it; ``q``, ``k``, ``v``,
``g``, ``beta``, ``o`` and their gradients read or written once — the same
whatever chunk or kernel implements the scan) over ``gdn_scan_ms``.  Which
roof binds goes to the ``info`` line."""

from harness import kernel_time

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    work = run.kernel_work.get("gdn")
    seconds = kernel_time.seconds(run, "gdn")
    if not work or not seconds:
        return None
    value, run.info["gdn_scan_roofline_bound"] = (
        kernel_time.roofline_share(work, seconds, run.peaks))
    return value
