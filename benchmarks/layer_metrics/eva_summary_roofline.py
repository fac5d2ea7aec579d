"""Share of their roofline the flash kernels reach on EVA attention's
SUMMARY set: the least time the chip could take for the operations and HBM
bytes the ALGORITHM needs (``families/evabyte.py`` ``eva_summary_work``:
for every query row the scores and sums over the ``(window / chunk) (i //
window)`` summaries of the earlier windows, forward and backward; q, o, dO,
dQ at [T, heads, D] and the summaries and their gradients at [T / chunk,
heads, D], three float32 rows a head; the forward recomputed under
``remat`` not counted — the same whatever sub-block or kernel implements
it) over ``eva_summary_ms``.  Which roof binds goes to the ``info``
line."""

from harness import kernel_time

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    work = run.kernel_work.get("eva_summary")
    seconds = kernel_time.seconds(run, "eva_summary")
    if not work or not seconds:
        return None
    value, run.info["eva_summary_roofline_bound"] = (
        kernel_time.roofline_share(work, seconds, run.peaks))
    return value
